"""Serving benchmark: paged chunked-prefill scheduler vs the bucketed
slot-pool baseline vs sequential ``generate``.

Drives the same trace through three paths and reports aggregate generated
tokens/sec plus compile counts and the paged engine's ``stats()``:

 - **serving** (the headline): ``inference/serving.py`` with the block-paged
   KV pool, chunked prefill and prefix caching — exactly 2 compiled
   programs (1 prefill + 1 decode) for any trace, and shared prompt
   prefixes prefill for free after their first occurrence.
 - **serving_bucketed**: the PR 1-style fallback on the same engine —
   bucket-ladder prefill over the paged pool, no prefix reuse,
   O(#buckets)+1 compiled programs.  ``speedup_vs_bucketed`` is the paged/
   chunked win isolated from the continuous-batching win.
 - **sequential**: one-shot ``InferenceEngine.generate``, one request at a
   time, one compiled program per exact request shape.
 - **serving_speculative** (``--speculative K``): the chunked engine with
   speculative decoding — the n-gram prompt-lookup proposer drafts K
   tokens per slot per iteration and one K+1-token paged verify pass
   scores them (<= 3 compiled programs; 2 in n-gram mode).  Outputs stay
   token-exact with plain greedy decode; ``speedup_spec_vs_chunked`` is
   the draft–verify win over the single-token decode loop.
 - **serving_tp** (``--tp N``): the same chunked trace on a tensor-
   parallel engine — weights Megatron-sharded and the paged KV pool
   sharded over the KV-head dim (``inference/serving.py`` tp section), so
   each chip stores ``HKV/N`` heads.  Reports per-chip KV pool bytes
   (the headline: ~N× smaller than the replicated layout) and asserts
   token parity vs sequential.  Includes a speculative pass when
   ``--speculative`` is also given.  Needs >= N devices — on CPU set
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; CPU-sim tok/s
   under tp is emulation overhead, not a hardware prediction.
 - **serving_quant** (``--quantize kv8[,w8a8[,w8a8+kv8]]``): quantized
   serving lanes on the same trace — int8 KV pool with per-block scales
   (``kv8``), K-grouped int8 weights on the s8 decode kernels
   (``w8a8``), or both.  Each lane reports tok/s, the quant-adjusted
   per-chip pool bytes, ``servable_blocks_per_chip_vs_bf16`` (bf16 pool
   bytes / quant pool bytes — the memory headline; ~1.9x for ``kv8``),
   and the measured token match rate vs full-precision sequential
   (bounded-divergence contract, ``tests/unit/quant_divergence.py`` —
   quantized lanes are NOT exact-parity lanes).  With ``--tp N`` a
   ``kv8`` lane also runs on the tp engine (the tp × kv8 combo: per-chip
   pool bytes divide by BOTH factors).  CPU-sim tok/s measures XLA-CPU
   op mixes, not HBM bandwidth — the on-chip bandwidth argument
   (+32-34% w8a8 decode in a rounds 1–4 builder run; int8 KV halves
   decode's dominant traffic term) has no ledger number yet.

Methodology: the default
trace draws ARBITRARY prompt lengths in [32, 512] and completion budgets in
[16, 64] — real mixed traffic, where the sequential path jit-compiles one
program per exact request shape while the serving loop compiles O(1).
``--prefix-len N`` instead prepends a shared N-token system prompt to every
request (tails in [16, 64]) — the prefix-heavy trace where the prefix cache
collapses per-request prefill to the unique tail.  The headline is
aggregate generated tokens/sec over the whole trace, compiles included on
both sides; a second pass over the same trace reports the compile- and
prefix-warm steady state.  ``--grid`` snaps the default trace to a small
shape grid that fits the sequential LRU and reports a compile-warm
sequential pass too.  Greedy decoding; the bench asserts all serving
outputs are token-identical to sequential before reporting numbers.

``--decode-heavy`` draws short prompts and long completion budgets — the
decode-bound traffic speculative decoding targets (BENCH_r05 lane:
``--decode-heavy --speculative 4``).

``--pool-frac F`` adds the BENCH_r09 tiered-KV lane: the device pool is
deliberately sized at fraction F of the trace's working set (ROADMAP's
~25% scenario — block pressure guaranteed), and the same trace runs on
two engines differing ONLY in the host tier: the **evict/preempt
baseline** (cold blocks discarded, preemption recomputes whole
prefixes) vs the **tiered engine** (``host_blocks`` sized to the
working set: eviction/preemption demote to host DRAM, admission
promotes back with the double-buffered prefetch).  Reports
``speedup_tiered_vs_preemption`` (cold + warm), the swap counters,
prefetch-wait p50/p95 from the metrics registry, and both engines'
resume-recompute token counts; token parity vs sequential is asserted
for BOTH engines (zero parity loss is the tiering contract).  Best on
the prefix-heavy trace (``--prefix-len``) where the evicted prefix is
exactly what the next request needs.

``--telemetry-bench`` adds the BENCH_r08 overhead lane: the same chunked
trace on two fresh twin engines — telemetry-off (``trace_capacity=0``:
the event ring disabled; the metrics registry behind ``stats()`` is
always on) vs fully-enabled (default ring) — comparing interleaved
best-of-3 compile-warm passes.  The contract is ≤2% aggregate tok/s
overhead, recorded as ``within_2pct`` (a breach warns without failing
the run — wall-clock ratios on shared boxes carry ~±5% noise; the
committed 64-request BENCH_r08.json is the pinned artifact); the
lane also schema-validates the enabled engine's exported Chrome trace
(``telemetry/trace.py validate_chrome_trace``: monotonic ``ts``, paired/
complete events, pid/tid, per-request spans) and records the summary.
``--trace-out PATH`` writes that trace for Perfetto.  ``--emit-metrics
PATH`` dumps the headline serving engine's Prometheus text exposition to
``PATH`` and the JSON registry snapshot to ``PATH.json`` alongside the
bench JSON (tier-1 CI uploads these as a workflow artifact).

``--quant-suite`` runs the BENCH_r07 protocol: the mixed, prefix-heavy,
and decode-heavy traces each with the quantized lanes, plus the tp × kv8
combo, merged into one JSON.  Recommended at ``--dtype bf16`` (the
production serving dtype the memory/throughput headlines are quoted
against); bf16 runs gate the unquantized baseline on per-request
agreement instead of bit parity (see ``main`` — bf16 near-tie argmax
flips between equally valid compute shapes), fp32 runs keep the exact
gate.

``--replicas N`` runs the BENCH_r10 multi-replica router protocol
instead of the single-engine lanes: ``deepspeed_tpu/serving/``'s
``ReplicaRouter`` over 1 → 2 → 4 engine replicas (capped at N, weights
shared so every scale is token-identical) on the returning-session
trace.  Scaling is WEAK — n replicas serve n× the traffic (requests×n
over sessions×n), per-replica load constant: the DP capacity claim.
CPU-sim methodology: one process TIME-SLICES the replicas on the host
CPU — each replica stands in for an independent accelerator — so the
scaling headline is **aggregate busy-time throughput** (each replica's
generated tokens over its own ``step()`` wall time, summed over 3
interleaved warm rounds: the DP scaling signal), reported next to raw
wall clock (flat on a single core by construction; with >= N cores and
``threaded`` workers the wall numbers converge toward the busy
aggregate).  The protocol also runs affinity-vs-round-robin twin
fleets (prefix hit rate under pool pressure) and a drained-replica
migration: every migrated session's chain is KV-pulled from the
drained replica's host tier and resumed on the survivor with zero
prefix recompute, vs a ``kv_pull=False`` twin that re-prefills whole
prompts (TTFT-shaped continuations — migration changes the prefill
side).  Every lane is parity-gated; each replica's compile count is
checked against its unchanged sentry budget.

``--replicas N --slo`` runs the BENCH_r12 **fleet observability**
protocol instead: SLO-classed traffic (realtime/interactive/standard/
batch round-robin) on an N-replica router with the whole observability
layer enabled — the federated fleet registry scraped from the LIVE
``/metrics`` endpoint while the step loop runs (parse + snapshot
agreement asserted), a drain-forced cross-replica KV pull whose
``s``/``f`` flow events are validated in the ONE merged Chrome trace,
per-class SLO attainment (``router.slo_report()``), the FLOPs/MFU
profiler (cost_analysis vs analytic agreement ≤10% asserted on at least
one family; ``--peak-flops`` is a *nominal* CPU-sim MFU denominator),
and the PR 8 ≤2% overhead contract re-verified fleet-wide with twin
fleets (everything on vs trace rings off).  With ``--replicas`` (either
protocol), ``--emit-metrics`` writes the **federated fleet** Prometheus
text + JSON snapshot — router + every replica registry with ``replica=``
labels — not one engine's registry.

``--chaos`` runs the BENCH_r14 **fault-tolerance** protocol (PR 15,
docs/reliability.md): seeded ``FaultPlan``s (``serving/faults.py``)
against the returning-sessions trace — (1) a crash lane killing one of
two tiered replicas mid-decode, gated on token-EXACT parity vs the
fault-free twin fleet, zero hung handles, and unchanged compile
budgets, with recovery latency read off the ``replica_fail`` →
``rehome`` timeline gap (add ``--quantize kv8`` for the kv8 crash
twin: bit-exact vs unfaulted kv8, bounded match vs fp32 sequential);
(2) a flaky-transport lane where a drain-forced migration must land
its pulls through the transient-fault retry/backoff machinery; (3) a
corruption lane flipping bits in EVERY host-tier arena entry after a
full drain — 100% must be caught by checksum (promote exit gates +
the final patrol scrub) and recovered via recompute, corrupt KV never
served; (4) an ``--overload``x batch burst against bounded admission —
``realtime``/``interactive`` submit-to-first-token p95 must hold
within 1.5x of the unloaded baseline while batch absorbs every
``RequestRejected``.

``--host-loop`` runs the BENCH_r15 **fused multi-step decode** protocol
(PR 16, docs/inference.md): the K=1 per-token host loop vs the fused
``decode_steps=K`` engine (one on-device ``lax.while_loop`` program, one
host fence per K-token window) on the BENCH_r09 returning-sessions
trace.  Gated on EXACT token parity (fp32) between the twins, a kv8
twin pair that is bit-exact between K=1-kv8 and fused-kv8, and the
headline: host scheduler decode iterations per generated token down
``>= --host-loop-min-reduction`` (default 4x; the committed artifact
runs K=8).  Fused tok/s >= the K=1 baseline and the trace-ring-off
telemetry twin's <=2% overhead contract are recorded and warn on
breach (wall-clock on shared boxes is noise-prone; the committed
BENCH_r15.json pins passing measurements).

``--sampling`` runs the BENCH_r18 **on-device sampling** protocol
(PR 20, docs/inference.md "Sampled decoding"): per-slot temperature/
top-k/top-p/seed ride as fixed-shape ``[slots]`` device operands of the
SAME compiled programs (greedy is the temperature-0 row — zero extra
programs, zero recompiles across greedy/sampled/constrained mixes), and
every gate is DETERMINISTIC because the counter-based PRNG keys are
pure functions of (request seed, tokens emitted).  Lanes: fresh-twin
stream determinism, temp-0 bit parity vs a ``sampling=False`` engine
and sequential ``generate``, ``decode_steps=K`` fused decode token-
EXACT vs K=1 (``grid_keys`` ≡ per-step ``slot_keys``) with the host-
iteration-reduction floor, speculative **rejection sampling** (n-gram
+ 1-layer draft model) gated on twin determinism, the 2-/3-program
compile budget, the deterministic tokens-per-host-decode-iteration
ratio >= ``--sampling-min-spec-speedup`` x plain sampling, and a
statistical-parity TV gate (rejection sampling is distribution-exact
for ANY proposer, so spec-sampled token histograms must sit inside the
self-calibrated reseeded-plain null band), plus the mixed greedy +
sampled + constrained-JSON trace on a ``logit_masks=True`` engine —
still 2 programs, sentry strict, every constrained completion valid
JSON.  CPU-sim wall tok/s is recorded, never gated.

``--long-context`` runs the BENCH_r17 **long-context serving** protocol
(PR 19, docs/inference.md "Long-context serving"): the sp=1 chunked
engine vs the ``sp=N`` Ulysses sequence-parallel prefill twin on
``--long-prompt-len``-token prompts (EXACT token parity and the
unchanged 2-program compile budget exit-fatal; the prefill wall-clock
speedup recorded and warned only — CPU-sim shard_map emulates the sp
mesh on one host), the ``resident_window_blocks=W`` decode lane with
the device pool sized under 25% of the served context (window slides,
host-tier demotion, full token budgets, and the unamended compile
budget all exit-fatal; full-window bit-identity against the plain
engine pins the exactness floor), and a 131072-token-declared windowed
engine probing the compile budget at 128k scale.

Usage:
  python benchmarks/serving_bench.py [--requests 64] [--slots 8]
      [--prefix-len 256] [--grid] [--decode-heavy] [--speculative K]
      [--tp N] [--quantize kv8,w8a8+kv8 | --quant-suite]
      [--replicas N] [--slo] [--chaos] [--host-loop] [--long-context]
      [--sampling] [--hidden 128] [--seed 0] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PROMPT_RANGE = (32, 512)
NEW_TOKEN_RANGE = (16, 64)
#: --decode-heavy: short prompts, long completions — decode steps dominate
#: wall-clock (the BENCH_r04 147-decode-vs-55-prefill regime, amplified)
DECODE_HEAVY_PROMPT_RANGE = (16, 48)
DECODE_HEAVY_NEW_RANGE = (96, 160)
#: --prefix-len mode: unique tail length / completion budget ranges —
#: long shared context, short unique tail and output (the classification /
#: extraction-style traffic prefix caching exists for)
TAIL_RANGE = (16, 64)
PREFIX_NEW_RANGE = (8, 32)
# --grid shape grids: |prompts| * |budgets| stays under the engine's
# 32-entry LRU so a second sequential pass is compile-free (see module doc)
PROMPT_GRID = (32, 64, 96, 128, 192, 256, 384, 512)
NEW_TOKEN_GRID = (16, 32, 64)


def build_trace(n_requests: int, vocab: int, seed: int, grid: bool,
                prefix_len: int = 0, decode_heavy: bool = False,
                sessions: int = 0):
    """``sessions > 0`` (with ``prefix_len``) draws S distinct session
    prefixes and deals requests round-robin across them — the multi-turn
    chat shape: request i returns to session ``i % S`` with a fresh tail,
    AFTER the other sessions' traffic has pushed that session's blocks
    out of a pressure-sized pool.  This is the trace the tiered-KV lane
    runs: every return is a full re-prefill for the evict/preempt
    baseline and a host-tier promotion for the tiered engine."""
    from deepspeed_tpu.inference.serving import Request

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, prefix_len) \
        if prefix_len and not sessions else None
    if sessions and prefix_len:
        prefixes = [rng.integers(0, vocab, prefix_len)
                    for _ in range(sessions)]
    reqs = []
    for i in range(n_requests):
        if sessions and prefix_len:
            tail = rng.integers(0, vocab,
                                int(rng.integers(TAIL_RANGE[0],
                                                 TAIL_RANGE[1] + 1)))
            prompt = np.concatenate([prefixes[i % sessions], tail])
            mnew = int(rng.integers(PREFIX_NEW_RANGE[0],
                                    PREFIX_NEW_RANGE[1] + 1))
            reqs.append(Request(uid=i, max_new_tokens=mnew, prompt=prompt))
            continue
        if decode_heavy:
            prompt = rng.integers(
                0, vocab, int(rng.integers(DECODE_HEAVY_PROMPT_RANGE[0],
                                           DECODE_HEAVY_PROMPT_RANGE[1] + 1)))
            mnew = int(rng.integers(DECODE_HEAVY_NEW_RANGE[0],
                                    DECODE_HEAVY_NEW_RANGE[1] + 1))
        elif prefix_len:
            tail = rng.integers(0, vocab,
                                int(rng.integers(TAIL_RANGE[0],
                                                 TAIL_RANGE[1] + 1)))
            prompt = np.concatenate([prefix, tail])
            mnew = int(rng.integers(PREFIX_NEW_RANGE[0],
                                    PREFIX_NEW_RANGE[1] + 1))
        elif grid:
            prompt = rng.integers(0, vocab, int(rng.choice(PROMPT_GRID)))
            mnew = int(rng.choice(NEW_TOKEN_GRID))
        else:
            prompt = rng.integers(0, vocab,
                                  int(rng.integers(PROMPT_RANGE[0],
                                                   PROMPT_RANGE[1] + 1)))
            mnew = int(rng.integers(NEW_TOKEN_RANGE[0],
                                    NEW_TOKEN_RANGE[1] + 1))
        reqs.append(Request(uid=i, max_new_tokens=mnew, prompt=prompt))
    return reqs


def run_sequential(engine, reqs):
    outs = {}
    t0 = time.perf_counter()
    for r in reqs:
        outs[r.uid] = engine.generate(r.prompt[None, :],
                                      max_new_tokens=r.max_new_tokens)[0]
    return outs, time.perf_counter() - t0


def run_bench(requests: int = 64, slots: int = 8, prefill_batch: int = 4,
              layers: int = 2, hidden: int = 128, heads: int = 4,
              vocab: int = 2048, seed: int = 0, dtype: str = "fp32",
              grid: bool = False, prefix_len: int = 0,
              block_size: int = 32, prefill_chunk: int = 128,
              speculative: int = 0, decode_heavy: bool = False,
              tp: int = 1, quantize: tuple = (),
              pool_frac: float = 0.0, swap_batch: int = 8,
              sessions: int = 0,
              telemetry_bench: bool = False, trace_out: str = None,
              emit_metrics: str = None):
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models import gpt2

    if decode_heavy:
        max_total = max(DECODE_HEAVY_PROMPT_RANGE) + max(DECODE_HEAVY_NEW_RANGE)
    elif prefix_len:
        max_total = prefix_len + max(TAIL_RANGE) + max(PREFIX_NEW_RANGE)
    else:
        max_total = max(PROMPT_GRID) + max(NEW_TOKEN_GRID)
    cfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=1024,
                          num_layers=layers, num_heads=heads,
                          hidden_size=hidden)
    engine = deepspeed_tpu.init_inference(
        gpt2.build(cfg), config={"dtype": dtype,
                                 "tensor_parallel": {"tp_size": 1}})
    reqs = build_trace(requests, vocab, seed, grid, prefix_len, decode_heavy,
                       sessions)
    gen_tokens = sum(r.max_new_tokens for r in reqs)

    # --- sequential pass 1: per-shape compiles included — this IS the
    # sequential path's steady state on arbitrary request shapes
    seq_outs, seq_cold = run_sequential(engine, reqs)
    n_shapes = len({(len(r.prompt), r.max_new_tokens) for r in reqs})
    seq_warm = None
    if grid and not prefix_len:
        # grid mode: every shape program survived the LRU, pass 2 is
        # compile-free — the batching win isolated from the compile win
        assert n_shapes <= 32, "shape grid exceeds the LRU"
        _, seq_warm = run_sequential(engine, reqs)

    # --- bucketed fallback (PR 1-style slot-pool semantics on the paged
    # pool): bucket-ladder prefill, no prefix reuse
    buckets = tuple(b for b in PROMPT_GRID if b < max_total) + (max_total,)
    srv_b = ServingEngine(engine, slots=slots, max_seq_len=max_total,
                          prompt_buckets=buckets, prefill_batch=prefill_batch,
                          block_size=block_size)
    t0 = time.perf_counter()
    bkt_outs = srv_b.serve(reqs)
    bkt_cold = time.perf_counter() - t0
    bkt_stats_cold = srv_b.stats()
    # second pass on the same engine: compile-warm (no prefix cache in
    # bucketed mode, so there is nothing else to warm)
    t0 = time.perf_counter()
    bkt_outs2 = srv_b.serve(reqs)
    bkt_warm = time.perf_counter() - t0

    # --- paged chunked prefill + prefix cache: cold (compiles included),
    # then a second pass on the same engine — compile-warm AND prefix-warm
    # (the steady state under shared-prefix traffic)
    srv = ServingEngine(engine, slots=slots, max_seq_len=max_total,
                        prefill_batch=prefill_batch, block_size=block_size,
                        prefill_chunk=prefill_chunk)
    t0 = time.perf_counter()
    srv_outs = srv.serve(reqs)
    srv_cold = time.perf_counter() - t0
    stats_cold = srv.stats()               # pass-1 numbers (counters are
    t0 = time.perf_counter()               # cumulative across serve calls)
    srv_outs2 = srv.serve(reqs)
    srv_warm = time.perf_counter() - t0

    # --- speculative draft–verify on the same chunked engine config:
    # n-gram proposer drafts K per slot, one K+1 verify pass scores them
    spec_res = None
    if speculative:
        srv_s = ServingEngine(engine, slots=slots, max_seq_len=max_total,
                              prefill_batch=prefill_batch,
                              block_size=block_size,
                              prefill_chunk=prefill_chunk,
                              spec_tokens=speculative)
        t0 = time.perf_counter()
        spec_outs = srv_s.serve(reqs)
        spec_cold = time.perf_counter() - t0
        spec_stats_cold = srv_s.stats()
        t0 = time.perf_counter()
        spec_outs2 = srv_s.serve(reqs)
        spec_warm = time.perf_counter() - t0
        spec_res = {
            "tok_s": gen_tokens / spec_cold,
            "wall_s": spec_cold,
            "tok_s_warm": gen_tokens / spec_warm,
            "wall_warm_s": spec_warm,
            "compiled_programs": srv_s.compile_count,
            "spec_tokens": speculative,
            "acceptance_rate": spec_stats_cold["acceptance_rate"],
            "stats": spec_stats_cold,
            "stats_after_warm_pass": srv_s.stats(),
        }

    # --- tensor-parallel lane (--tp N): same chunked trace, weights
    # Megatron-sharded and the paged KV pool head-sharded over the tp mesh
    # axis.  The headline is per-chip KV pool bytes (~N× below the
    # replicated layout); CPU-sim tok/s under tp measures emulation
    # overhead, not hardware.  Token parity vs sequential is asserted.
    tp_res = None
    tp_outs = {}
    if tp > 1:
        import jax

        ndev = len(jax.devices())
        if ndev % tp:
            raise SystemExit(
                f"--tp {tp} does not divide the {ndev} visible devices — on "
                "CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8")
        deepspeed_tpu.comm.reset_topology()
        engine_tp = deepspeed_tpu.init_inference(
            gpt2.build(cfg), config={"dtype": dtype,
                                     "tensor_parallel": {"tp_size": tp}})
        srv_tp = ServingEngine(engine_tp, slots=slots, max_seq_len=max_total,
                               prefill_batch=prefill_batch,
                               block_size=block_size,
                               prefill_chunk=prefill_chunk)
        t0 = time.perf_counter()
        tp_outs = srv_tp.serve(reqs)
        tp_cold = time.perf_counter() - t0
        tp_stats = srv_tp.stats()
        t0 = time.perf_counter()
        tp_outs2 = srv_tp.serve(reqs)
        tp_warm = time.perf_counter() - t0
        tp_res = {
            "tp": tp,
            "tok_s": gen_tokens / tp_cold,
            "wall_s": tp_cold,
            "tok_s_warm": gen_tokens / tp_warm,
            "wall_warm_s": tp_warm,
            "compiled_programs": srv_tp.compile_count,
            "kv_sharded": tp_stats["kv_sharded"],
            "kv_pool_shape": tp_stats["kv_pool_shape"],
            "kv_pool_bytes": tp_stats["kv_pool_bytes"],
            "kv_pool_bytes_per_chip": tp_stats["kv_pool_bytes_per_chip"],
            "stats": tp_stats,
        }
        if speculative:
            srv_tp_s = ServingEngine(engine_tp, slots=slots,
                                     max_seq_len=max_total,
                                     prefill_batch=prefill_batch,
                                     block_size=block_size,
                                     prefill_chunk=prefill_chunk,
                                     spec_tokens=speculative)
            t0 = time.perf_counter()
            tp_spec_outs = srv_tp_s.serve(reqs)
            tp_spec_cold = time.perf_counter() - t0
            tp_res["speculative"] = {
                "tok_s": gen_tokens / tp_spec_cold,
                "wall_s": tp_spec_cold,
                "compiled_programs": srv_tp_s.compile_count,
                "acceptance_rate": srv_tp_s.stats()["acceptance_rate"],
                "kv_pool_bytes_per_chip":
                    srv_tp_s.stats()["kv_pool_bytes_per_chip"],
            }
            tp_outs = {u: (tp_outs[u], tp_spec_outs[u]) for u in tp_outs}
        else:
            tp_outs = {u: (tp_outs[u],) for u in tp_outs}
        tp_outs = {u: list(v) + [tp_outs2[u]] for u, v in tp_outs.items()}

    # --- quantized lanes (--quantize): int8 KV pool / w8a8 weights on the
    # same trace and engine config.  Bounded divergence replaces exact
    # parity here: the token match rate vs full-precision sequential is
    # measured and recorded (quantized greedy is a different — equally
    # valid — greedy model, so a near-tie argmax flip cascades).
    quant_res = {}
    if quantize:
        tu = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tests", "unit")
        if tu not in sys.path:     # idempotent: --quant-suite re-enters
            sys.path.insert(0, tu)
        from quant_divergence import token_match_rate

        for mode in quantize:
            eng_q = engine
            if "w8a8" in mode:
                deepspeed_tpu.comm.reset_topology()
                eng_q = deepspeed_tpu.init_inference(
                    gpt2.build(cfg),
                    config={"dtype": dtype,
                            "quant": {"enabled": True, "type": "w8a8"},
                            "tensor_parallel": {"tp_size": 1}})
            srv_q = ServingEngine(eng_q, slots=slots, max_seq_len=max_total,
                                  prefill_batch=prefill_batch,
                                  block_size=block_size,
                                  prefill_chunk=prefill_chunk,
                                  quantize=mode)
            t0 = time.perf_counter()
            q_outs = srv_q.serve(reqs)
            q_cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            srv_q.serve(reqs)
            q_warm = time.perf_counter() - t0
            qst = srv_q.stats()
            # bf16 yardstick for the memory headline: the pool's payload
            # element count at 2 bytes (identical to a bf16 pool's actual
            # bytes; the parity baseline above runs fp32, which would
            # flatter the ratio by 2x)
            bf16_bytes = 2 * 2 * int(np.prod(qst["kv_pool_shape"]))
            quant_res[mode] = {
                "tok_s": gen_tokens / q_cold,
                "wall_s": q_cold,
                "tok_s_warm": gen_tokens / q_warm,
                "wall_warm_s": q_warm,
                "compiled_programs": srv_q.compile_count,
                "kv_dtype": qst["kv_dtype"],
                "weight_quant": qst["weight_quant"],
                "kv_pool_bytes": qst["kv_pool_bytes"],
                "kv_scale_bytes": qst["kv_scale_bytes"],
                "kv_pool_bytes_per_chip": qst["kv_pool_bytes_per_chip"],
                "servable_blocks_per_chip_vs_bf16":
                    bf16_bytes / qst["kv_pool_bytes"]
                    if qst["kv_dtype"] == "int8" else 1.0,
                "token_match_rate_vs_sequential":
                    token_match_rate(seq_outs, q_outs),
                "tok_s_vs_serving": (gen_tokens / q_cold) /
                    (gen_tokens / srv_cold),
                "tok_s_warm_vs_serving": srv_warm / q_warm,
            }
        if tp > 1 and any("kv8" in m for m in quant_res):
            # tp x kv8 combo: the per-chip pool divides by BOTH factors
            srv_tpq = ServingEngine(engine_tp, slots=slots,
                                    max_seq_len=max_total,
                                    prefill_batch=prefill_batch,
                                    block_size=block_size,
                                    prefill_chunk=prefill_chunk,
                                    quantize="kv8")
            t0 = time.perf_counter()
            tpq_outs = srv_tpq.serve(reqs)
            tpq_cold = time.perf_counter() - t0
            tpq_st = srv_tpq.stats()
            bf16_rep_per_chip = 2 * 2 * int(np.prod(tpq_st["kv_pool_shape"]))
            quant_res["kv8+tp"] = {
                "tp": tp,
                "tok_s": gen_tokens / tpq_cold,
                "wall_s": tpq_cold,
                "kv_sharded": tpq_st["kv_sharded"],
                "kv_pool_bytes_per_chip":
                    tpq_st["kv_pool_bytes_per_chip"],
                "servable_blocks_per_chip_vs_bf16_replicated":
                    bf16_rep_per_chip / tpq_st["kv_pool_bytes_per_chip"],
                "token_match_rate_vs_sequential":
                    token_match_rate(seq_outs, tpq_outs),
                "compiled_programs": srv_tpq.compile_count,
            }

    # --- tiered-KV lane (--pool-frac F): a device pool sized at F of the
    # trace working set (guaranteed block pressure), evict/preempt
    # baseline vs the host-DRAM tier with prefetch.  Zero parity loss is
    # the contract — both engines must match sequential exactly.
    tiered_res = None
    tiered_outs = {}
    if pool_frac:
        from deepspeed_tpu.inference.paged import chain_keys
        from deepspeed_tpu.ops.paged_kv import blocks_for

        # working set = UNIQUE cacheable content blocks (shared session
        # prefixes count once — the same dedup the prefix trie does) plus
        # each request's private tail/generation blocks
        uniq = set()
        private = 0
        for r in reqs:
            nfull = len(r.prompt) // block_size
            uniq.update(chain_keys(r.prompt, nfull, block_size))
            private += blocks_for(len(r.prompt) + r.max_new_tokens,
                                  block_size) - nfull
        ws_blocks = len(uniq) + private
        nbper = blocks_for(max_total, block_size)
        small = max(1 + nbper + 1, int(round(ws_blocks * pool_frac)) + 1)
        small_kw = dict(slots=slots, max_seq_len=max_total,
                        prefill_batch=prefill_batch, block_size=block_size,
                        prefill_chunk=prefill_chunk, num_blocks=small)
        srv_small = ServingEngine(engine, **small_kw)
        t0 = time.perf_counter()
        small_outs = srv_small.serve(reqs)
        small_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        small_outs2 = srv_small.serve(reqs)
        small_warm = time.perf_counter() - t0
        small_stats = srv_small.stats()

        srv_t = ServingEngine(engine, host_blocks=ws_blocks + nbper,
                              swap_batch=swap_batch, **small_kw)
        t0 = time.perf_counter()
        t_outs = srv_t.serve(reqs)
        t_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        t_outs2 = srv_t.serve(reqs)
        t_warm = time.perf_counter() - t0
        t_stats = srv_t.stats()
        tiered_outs = {u: (t_outs[u], t_outs2[u], small_outs[u],
                           small_outs2[u]) for u in t_outs}
        tiered_res = {
            "pool_frac": pool_frac,
            "working_set_blocks": ws_blocks,
            "device_pool_blocks": small,
            "host_blocks": ws_blocks + nbper,
            "host_pool_bytes": t_stats["host_pool_bytes"],
            "swap_batch": swap_batch,
            "tiered": {
                "tok_s": gen_tokens / t_cold,
                "wall_s": t_cold,
                "tok_s_warm": gen_tokens / t_warm,
                "wall_warm_s": t_warm,
                "compiled_programs": srv_t.compile_count,
                "swap_out": t_stats["swap_out"],
                "swap_in": t_stats["swap_in"],
                "swap_bytes": t_stats["swap_bytes"],
                "prefetch_misses": t_stats["prefetch_misses"],
                "prefetch_wait_p50_s": t_stats["prefetch_wait_p50_s"],
                "prefetch_wait_p95_s": t_stats["prefetch_wait_p95_s"],
                "preempted": t_stats["evicted"],
                "resume_recompute_tokens":
                    t_stats["resume_recompute_tokens"],
                "prefix_cache_hit_rate": t_stats["prefix_cache_hit_rate"],
            },
            "preemption_baseline": {
                "tok_s": gen_tokens / small_cold,
                "wall_s": small_cold,
                "tok_s_warm": gen_tokens / small_warm,
                "wall_warm_s": small_warm,
                "compiled_programs": srv_small.compile_count,
                "preempted": small_stats["evicted"],
                "resume_recompute_tokens":
                    small_stats["resume_recompute_tokens"],
                "prefix_cache_hit_rate":
                    small_stats["prefix_cache_hit_rate"],
            },
            "speedup_tiered_vs_preemption": small_cold / t_cold,
            "speedup_tiered_vs_preemption_warm": small_warm / t_warm,
        }

    # --- telemetry overhead lane (--telemetry-bench): twin engines, same
    # config, differing ONLY in the trace-event ring (off vs default) —
    # interleaved best-of-3 compile-warm passes bound the wall-clock
    # noise on a shared box.  The registry behind stats() is always on in
    # both (it replaced the loose counter attributes 1:1), so this
    # isolates the cost of the event stream the ≤2% contract covers.
    telemetry_res = None
    if telemetry_bench:
        from deepspeed_tpu.telemetry import validate_chrome_trace

        def _mk(cap):
            return ServingEngine(engine, slots=slots, max_seq_len=max_total,
                                 prefill_batch=prefill_batch,
                                 block_size=block_size,
                                 prefill_chunk=prefill_chunk,
                                 trace_capacity=cap)

        srv_off, srv_on = _mk(0), _mk(16384)
        srv_off.serve(reqs)                 # compile + prefix-warm pass
        srv_on.serve(reqs)
        # interleaved best-of-3 pairs: machine drift (cache state, GC,
        # neighbors on a shared box) hits both engines alike instead of
        # biasing whichever ran last
        off_warm = on_warm = float("inf")
        on_outs = None
        for _ in range(3):
            t0 = time.perf_counter()
            srv_off.serve(reqs)
            off_warm = min(off_warm, time.perf_counter() - t0)
            t0 = time.perf_counter()
            on_outs = srv_on.serve(reqs)
            on_warm = min(on_warm, time.perf_counter() - t0)
        doc = srv_on.timeline.to_chrome()
        trace_summary = validate_chrome_trace(doc)   # raises if malformed
        if trace_out:
            srv_on.dump_trace(trace_out)
        on_stats = srv_on.stats()
        telemetry_res = {
            "tok_s_warm_off": gen_tokens / off_warm,
            "tok_s_warm_on": gen_tokens / on_warm,
            "wall_warm_off_s": off_warm,
            "wall_warm_on_s": on_warm,
            "overhead_pct": (on_warm / off_warm - 1.0) * 100.0,
            "within_2pct": on_warm <= off_warm * 1.02,
            "token_parity": all(np.array_equal(srv_outs[r.uid],
                                               on_outs[r.uid])
                                for r in reqs),
            "trace_valid": True,            # validate_chrome_trace passed
            "trace_summary": trace_summary,
            "trace_events_recorded": on_stats["trace_events"],
            "trace_events_dropped": on_stats["trace_events_dropped"],
            "trace_out": trace_out,
        }

    # --- metrics artifact (--emit-metrics): the headline serving engine's
    # Prometheus text + JSON registry snapshot, next to the bench JSON
    metrics_files = None
    if emit_metrics:
        with open(emit_metrics, "w") as f:
            f.write(srv.metrics.prometheus_text())
        snap_path = emit_metrics + ".json"
        with open(snap_path, "w") as f:
            f.write(srv.metrics.snapshot_json())
        metrics_files = {"prometheus": emit_metrics, "snapshot": snap_path}

    mismatches = [r.uid for r in reqs
                  if not (np.array_equal(seq_outs[r.uid], srv_outs[r.uid])
                          and np.array_equal(seq_outs[r.uid],
                                             srv_outs2[r.uid])
                          and np.array_equal(seq_outs[r.uid],
                                             bkt_outs[r.uid])
                          and np.array_equal(seq_outs[r.uid],
                                             bkt_outs2[r.uid])
                          and all(np.array_equal(seq_outs[r.uid], o)
                                  for o in tp_outs.get(r.uid, ()))
                          and all(np.array_equal(seq_outs[r.uid], o)
                                  for o in tiered_outs.get(r.uid, ()))
                          and (speculative == 0 or
                               (np.array_equal(seq_outs[r.uid],
                                               spec_outs[r.uid])
                                and np.array_equal(seq_outs[r.uid],
                                                   spec_outs2[r.uid]))))]
    result = {
        "trace": (f"decode-heavy prompts {DECODE_HEAVY_PROMPT_RANGE}, "
                  f"new {DECODE_HEAVY_NEW_RANGE}") if decode_heavy else
                 (f"{sessions} sessions x {prefix_len}-token prefixes "
                  f"(round-robin returns), tails {TAIL_RANGE}, new "
                  f"{PREFIX_NEW_RANGE}") if sessions and prefix_len else
                 (f"shared {prefix_len}-token prefix, tails {TAIL_RANGE}, "
                  f"new {PREFIX_NEW_RANGE}") if prefix_len else
                 ("shape-grid" if grid else
                  f"arbitrary prompts {PROMPT_RANGE}, new {NEW_TOKEN_RANGE}"),
        "requests": requests,
        "prefix_len": prefix_len,
        "request_shapes": n_shapes,
        "generated_tokens": gen_tokens,
        "sequential": {
            "tok_s": gen_tokens / seq_cold,
            "wall_s": seq_cold,
            "tok_s_warm": gen_tokens / seq_warm if seq_warm else None,
            "wall_warm_s": seq_warm,
            # resident programs only — the engine LRU caps at 32, so on the
            # arbitrary-shape trace true compile count is >= request_shapes
            "compiled_programs": len(engine._generate_fns),
        },
        "serving": {
            "tok_s": gen_tokens / srv_cold,
            "wall_s": srv_cold,
            "tok_s_warm": gen_tokens / srv_warm,
            "wall_warm_s": srv_warm,
            "compiled_programs": srv.compile_count,
            "slots": slots, "prefill_batch": prefill_batch,
            "stats": stats_cold,
            "stats_after_warm_pass": srv.stats(),
        },
        "serving_bucketed": {
            "tok_s": gen_tokens / bkt_cold,
            "wall_s": bkt_cold,
            "tok_s_warm": gen_tokens / bkt_warm,
            "wall_warm_s": bkt_warm,
            "compiled_programs": srv_b.compile_count,
            "stats": bkt_stats_cold,
        },
        "speedup": seq_cold / srv_cold,
        "speedup_warm": (seq_warm / srv_warm) if seq_warm else None,
        # the paged/chunked/prefix win over the PR 1-style bucketed slot
        # pool: compiles included, and the compile-warm steady state
        "speedup_vs_bucketed": bkt_cold / srv_cold,
        "speedup_vs_bucketed_warm": bkt_warm / srv_warm,
        "serving_speculative": spec_res,
        # the draft–verify win over single-token decode, same engine config
        "speedup_spec_vs_chunked": (srv_cold / spec_res["wall_s"])
        if spec_res else None,
        "speedup_spec_vs_chunked_warm": (srv_warm / spec_res["wall_warm_s"])
        if spec_res else None,
        "serving_tp": tp_res,
        "serving_quant": quant_res or None,
        # tiered-KV vs evict/preempt baseline on a pressure-sized pool
        # (the BENCH_r09 lane, module docstring)
        "serving_tiered": tiered_res,
        # telemetry-on vs telemetry-off twin engines + trace-schema check
        # (the BENCH_r08 ≤2% overhead contract, module docstring)
        "serving_telemetry": telemetry_res,
        "metrics_files": metrics_files,
        # the memory headline: per-chip KV pool bytes, replicated vs
        # head-sharded — sharding shrinks the per-chip share by ~tp
        "kv_bytes_per_chip_replicated":
            stats_cold["kv_pool_bytes_per_chip"],
        "kv_bytes_per_chip_tp": tp_res["kv_pool_bytes_per_chip"]
        if tp_res else None,
        "kv_per_chip_shrink": (stats_cold["kv_pool_bytes_per_chip"] /
                               tp_res["kv_pool_bytes_per_chip"])
        if tp_res else None,
        "token_parity": not mismatches and
        (telemetry_res is None or telemetry_res["token_parity"]),
        "mismatched_uids": mismatches,
        "model": f"gpt2-{layers}l-{hidden}d-{vocab}v ({dtype})",
        "backend": __import__("jax").default_backend(),
    }
    return result


_PROM_LINE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^}]*\})?)\s+'
    r'([+-]?(?:[0-9.eE+-]+|[Ii]nf|NaN))$')


def parse_prometheus_text(text: str):
    """Minimal Prometheus text-format parser: returns ``{sample_line_key:
    value}`` and raises ``ValueError`` on the first malformed line — the
    live-scrape acceptance check ("parses as Prometheus text")."""
    out = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        m = _PROM_LINE.match(ln)
        if m is None:
            raise ValueError(f"malformed Prometheus sample line: {ln!r}")
        out[m.group(1)] = float(m.group(2))
    return out


def run_fleet_observability_bench(replicas: int = 2, requests: int = 64,
                                  slots: int = 8, prefill_batch: int = 4,
                                  layers: int = 2, hidden: int = 128,
                                  heads: int = 4, vocab: int = 2048,
                                  seed: int = 0, dtype: str = "fp32",
                                  block_size: int = 32,
                                  prefill_chunk: int = 128,
                                  prefix_len: int = 192,
                                  sessions: int = 9, swap_batch: int = 8,
                                  peak_flops: float = 1e12,
                                  emit_metrics: str = None,
                                  trace_out: str = None):
    """The BENCH_r12 fleet observability protocol (``--replicas N
    --slo``): an SLO-classed returning-session trace on an N-replica
    router with the whole observability layer enabled — metrics
    federation scraped from the LIVE ``/metrics`` endpoint while the
    step loop runs, per-class SLO attainment, ONE merged Chrome trace
    with router→replica and kv-pull flow events validated, the
    cost_analysis/analytic FLOPs agreement + MFU/busy breakdown, and
    the PR 8 ≤2% overhead contract re-verified fleet-wide (twin fleets:
    everything on vs trace rings off).  ``peak_flops`` is a *nominal*
    MFU denominator on CPU-sim (the gauge mechanics, not a hardware
    claim).  Parity-gated vs sequential; per-replica compile budgets
    asserted unchanged."""
    import threading
    import urllib.request

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.ops.paged_kv import blocks_for
    from deepspeed_tpu.serving import ReplicaRouter
    from deepspeed_tpu.telemetry import validate_chrome_trace

    cfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=1024,
                          num_layers=layers, num_heads=heads,
                          hidden_size=hidden)
    spec = gpt2.build(cfg)
    max_total = prefix_len + max(TAIL_RANGE) + max(PREFIX_NEW_RANGE)
    nbper = blocks_for(max_total, block_size)
    state = {"params": None}

    def mk_engine():
        eng = deepspeed_tpu.init_inference(
            spec, config={"dtype": dtype,
                          "tensor_parallel": {"tp_size": 1}},
            params=state["params"])
        if state["params"] is None:
            state["params"] = eng.params
        return eng

    hb = sessions * (prefix_len // block_size + 2) + 2 * nbper

    def fleet(trace_capacity=16384, router_trace_capacity=8192):
        srvs = [ServingEngine(mk_engine(), slots=slots,
                              max_seq_len=max_total,
                              prefill_batch=prefill_batch,
                              block_size=block_size,
                              prefill_chunk=prefill_chunk,
                              host_blocks=hb, swap_batch=swap_batch,
                              trace_capacity=trace_capacity)
                for _ in range(replicas)]
        return ReplicaRouter(srvs, policy="affinity", kv_pull=True,
                             trace_capacity=router_trace_capacity)

    reqs = build_trace(requests, vocab, seed, False, prefix_len, False,
                       sessions)
    gen_tokens = sum(r.max_new_tokens for r in reqs)
    classes = ("realtime", "interactive", "standard", "batch")
    seq_engine = mk_engine()
    seq_outs, seq_wall = run_sequential(seq_engine, reqs)
    mismatched = []

    def gate(tag, outs, keys=None):
        for r in reqs if keys is None else keys:
            if not np.array_equal(seq_outs[r.uid], outs[r.uid]):
                mismatched.append((tag, r.uid))

    # --- phase 1: SLO-classed traffic with a LIVE scrape mid-loop -------
    router = fleet()
    server = router.start_metrics_server(port=0)
    url = f"http://127.0.0.1:{server.port}"
    handles = [router.submit(r, slo_class=classes[i % len(classes)])
               for i, r in enumerate(reqs)]

    live = {"scrapes": 0, "error": None}

    def drive():
        while router.step():
            pass

    t = threading.Thread(target=drive)
    t0 = time.perf_counter()
    t.start()
    # the acceptance check: the endpoint answers (and parses) WHILE the
    # scheduler steps — a scrape is a lock-bracketed registry walk, so
    # it interleaves with the loop rather than waiting it out
    while t.is_alive():
        try:
            text = urllib.request.urlopen(url + "/metrics",
                                          timeout=5).read().decode()
            parse_prometheus_text(text)
            live["scrapes"] += 1
        except Exception as e:       # noqa: BLE001 — recorded, gated below
            live["error"] = repr(e)
        t.join(timeout=0.05)
    t.join()
    wall_cold = time.perf_counter() - t0
    gate("slo-trace", {h.uid: h.result(timeout=0) for h in handles})

    # --- phase 2: drain -> cross-replica KV pulls (flow-event source) ---
    loads = [len(rep._prefix._entries) if rep._prefix else 0
             for rep in router.replicas]
    rid0 = int(np.argmax([router.replicas[r]._alloc.blocks_in_use
                          for r in range(replicas)]))
    router.drain(rid0)
    rng = np.random.default_rng(seed + 1)
    conts = [Request(uid=f"cont{i}",
                     prompt=np.concatenate(
                         [reqs[i % sessions].prompt[:prefix_len],
                          rng.integers(0, vocab, 6 + i % 3)]),
                     max_new_tokens=4) for i in range(sessions)]
    seq_conts = {c.uid: seq_engine.generate(
        c.prompt[None, :], max_new_tokens=c.max_new_tokens)[0]
        for c in conts}
    cont_outs = router.serve(conts)
    for c in conts:
        if not np.array_equal(seq_conts[c.uid], cont_outs[c.uid]):
            mismatched.append(("cont", c.uid))
    router.readmit(rid0)

    # --- phase 3: quiesced scrape agrees with the federated snapshot ----
    text = urllib.request.urlopen(url + "/metrics",
                                  timeout=5).read().decode()
    samples = parse_prometheus_text(text)
    fed_snap = router.fleet_registry().snapshot()
    spot = {}
    agree = True
    for name in ("serving_requests_finished_total",
                 "serving_generated_tokens_total",
                 "serving_kv_pulls_total",
                 "serving_routed_affinity_total"):
        fam = fed_snap.get(name, {"series": []})
        for s in fam["series"]:
            labels = ",".join(f'{k}="{v}"'
                              for k, v in sorted(s["labels"].items()))
            key = f"{name}{{{labels}}}" if labels else name
            scraped = samples.get(key)
            spot[key] = [scraped, s["value"]]
            agree &= scraped == s["value"]
    rstats = router.stats()

    # --- phase 4: merged multi-replica trace + flow-event validation ----
    merged = router.merged_trace()
    trace_summary = validate_chrome_trace(merged)   # raises if malformed
    flows = [e for e in merged["traceEvents"] if e["ph"] in ("s", "f")]
    route_flows = sum(1 for e in flows
                      if e["name"] == "route" and e["ph"] == "f")
    pull_flows = [e for e in flows if e["name"] == "kv_pull"]
    pull_cross_lane = any(
        s["pid"] != f["pid"]
        for s in pull_flows if s["ph"] == "s"
        for f in pull_flows if f["ph"] == "f" and f["id"] == s["id"])
    if trace_out:
        router.dump_merged_trace(trace_out)

    # --- phase 5: FLOPs/MFU (cost_analysis vs analytic agreement) -------
    rid_live = min(r for r in range(len(router.replicas)) if r != rid0)
    frep = router.replicas[rid_live].flops_report(peak_flops=peak_flops)
    # agreement is only meaningful where cost_analysis actually reported
    # — an analytic-fallback family has flops_per_call == flops_analytic
    # by construction (rel err 0 would gate vacuously)
    rel_errs = {
        f: abs(p["flops_per_call"] - p["flops_analytic"])
        / max(p["flops_analytic"], 1.0)
        for f, p in frep["programs"].items()
        if p["flops_cost_analysis"] is not None}
    flops_ok = bool(rel_errs) and min(rel_errs.values()) <= 0.10

    slo_report = router.slo_report()
    budgets_ok = all(p["compile_count"] <= p["compile_budget"]
                     for p in rstats["per_replica"])
    if emit_metrics:
        with open(emit_metrics, "w") as f:
            f.write(router.fleet_metrics_text())
        with open(emit_metrics + ".json", "w") as f:
            json.dump(router.fleet_snapshot(), f, indent=2)
    router.stop()

    # --- phase 6: the ≤2% overhead contract, fleet-wide -----------------
    # twin fleets differing ONLY in the observability layer: everything
    # on (trace rings + live server + SLO + FLOPs profiler built) vs
    # rings off / no server.  Interleaved best-of-3 warm passes (the
    # PR 8 methodology) bound box noise; the registry + SLO accounting
    # are always on in both — they replaced plain attributes 1:1.
    f_off = fleet(trace_capacity=0, router_trace_capacity=0)
    f_on = fleet()
    f_on.start_metrics_server(port=0)
    on_url = f"http://127.0.0.1:{f_on.metrics_server.port}"

    def serve_classed(rt, trace):
        hs = [rt.submit(r, slo_class=classes[i % len(classes)])
              for i, r in enumerate(trace)]
        while rt.step():
            pass
        return {h.uid: h.result(timeout=0) for h in hs}

    gate("twin-off-warmup", serve_classed(f_off, reqs))
    gate("twin-on-warmup", serve_classed(f_on, reqs))
    f_on.replicas[0].flops_report(peak_flops=peak_flops)
    off_warm = on_warm = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        serve_classed(f_off, reqs)
        off_warm = min(off_warm, time.perf_counter() - t0)
        t0 = time.perf_counter()
        on_outs = serve_classed(f_on, reqs)
        on_warm = min(on_warm, time.perf_counter() - t0)
    gate("twin-on", on_outs)
    urllib.request.urlopen(on_url + "/metrics", timeout=5).read()
    f_on.replicas[0].flops_report(peak_flops=peak_flops)
    f_on.stop()

    return {
        "protocol": "fleet observability (PR 12): SLO-classed traffic "
                    "on an N-replica router with federation + live "
                    "/metrics scrape + merged distributed trace + "
                    "FLOPs/MFU profiler, ≤2% twin-fleet overhead "
                    "contract, parity-gated vs sequential",
        "replicas": replicas,
        "requests": requests,
        "generated_tokens": gen_tokens,
        "trace": f"{sessions} sessions x {prefix_len}-token prefixes, "
                 f"slo classes {classes} round-robin",
        "sequential": {"tok_s": gen_tokens / seq_wall,
                       "wall_s": seq_wall},
        "fleet_tok_s_cold": gen_tokens / wall_cold,
        "slo": slo_report,
        "federation": {
            "live_scrapes_during_step_loop": live["scrapes"],
            "live_scrape_error": live["error"],
            "scrape_parses": True,          # parse_prometheus_text passed
            "scrape_agrees_with_snapshot": agree,
            "spot_checks": spot,
            "metrics_endpoint": url,
        },
        "merged_trace": {
            "summary": trace_summary,
            "route_flow_ends": route_flows,
            "kv_pull_flow_events": len(pull_flows),
            "kv_pull_crosses_replica_lanes": pull_cross_lane,
            "kv_pulls": rstats["kv_pulls"],
            "drains": rstats["drains"],
            "sources": merged["otherData"]["sources"],
            "trace_out": trace_out,
        },
        "flops": {
            "programs": frep["programs"],
            "per_family_rel_err": rel_errs,
            "agreement_within_10pct": flops_ok,
            "model_flops_total": frep["model_flops_total"],
            "flops_per_generated_token":
                frep["flops_per_generated_token"],
            "peak_flops_nominal": peak_flops,
            "mfu": frep["mfu"],
            "busy_fractions": frep["busy_fractions"],
        },
        "overhead": {
            "tok_s_warm_off": gen_tokens / off_warm,
            "tok_s_warm_on": gen_tokens / on_warm,
            "wall_warm_off_s": off_warm,
            "wall_warm_on_s": on_warm,
            "overhead_pct": (on_warm / off_warm - 1.0) * 100.0,
            "within_2pct": on_warm <= off_warm * 1.02,
        },
        "compile_budgets_ok": budgets_ok,
        "per_replica_compiles": [[p["compile_count"], p["compile_budget"]]
                                 for p in rstats["per_replica"]],
        "prefix_entry_loads_at_drain": loads,
        "token_parity": not mismatched,
        "mismatched": mismatched,
        "model": f"gpt2-{layers}l-{hidden}d-{vocab}v ({dtype})",
        "backend": __import__("jax").default_backend(),
    }


def run_replica_bench(replicas: int = 4, requests: int = 64,
                      slots: int = 8, prefill_batch: int = 4,
                      layers: int = 2, hidden: int = 128, heads: int = 4,
                      vocab: int = 2048, seed: int = 0,
                      dtype: str = "fp32", block_size: int = 32,
                      prefill_chunk: int = 128, prefix_len: int = 192,
                      sessions: int = 9, swap_batch: int = 8,
                      emit_metrics: str = None):
    # sessions defaults ODD on purpose: a session count divisible by the
    # replica count strides round-robin routing into perfect session
    # co-location (request i of session i%S lands on replica i%R — same
    # replica whenever R | S), which would flatter the baseline
    """The BENCH_r10 multi-replica router protocol (module docstring
    ``--replicas``): scaling over 1→2→4 replicas, affinity vs
    round-robin, and the drained-replica KV-pull migration."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.ops.paged_kv import blocks_for
    from deepspeed_tpu.serving import ReplicaRouter

    cfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=1024,
                          num_layers=layers, num_heads=heads,
                          hidden_size=hidden)
    spec = gpt2.build(cfg)
    max_total = prefix_len + max(TAIL_RANGE) + max(PREFIX_NEW_RANGE)
    nbper = blocks_for(max_total, block_size)
    state = {"params": None}

    def mk_engine():
        eng = deepspeed_tpu.init_inference(
            spec, config={"dtype": dtype,
                          "tensor_parallel": {"tp_size": 1}},
            params=state["params"])
        if state["params"] is None:
            state["params"] = eng.params     # every replica shares weights
        return eng

    def fleet(n, policy="affinity", host_blocks=0, kv_pull=True,
              num_blocks=None):
        extra = {"host_blocks": host_blocks, "swap_batch": swap_batch} \
            if host_blocks else {}
        if num_blocks is not None:
            extra["num_blocks"] = num_blocks
        srvs = [ServingEngine(mk_engine(), slots=slots,
                              max_seq_len=max_total,
                              prefill_batch=prefill_batch,
                              block_size=block_size,
                              prefill_chunk=prefill_chunk, **extra)
                for _ in range(n)]
        return ReplicaRouter(srvs, policy=policy, kv_pull=kv_pull)

    reqs = build_trace(requests, vocab, seed, False, prefix_len, False,
                       sessions)
    gen_tokens = sum(r.max_new_tokens for r in reqs)
    seq_engine = mk_engine()
    seq_outs, seq_wall = run_sequential(seq_engine, reqs)
    mismatched = []

    # working set in blocks (unique shared prefixes + private tails) —
    # sizes the scaling pools (no pressure: isolates pure DP scaling
    # from the aggregate-HBM capacity win) and the pressure lanes below
    from deepspeed_tpu.inference.paged import chain_keys
    uniq = set()
    private = 0
    for r in reqs:
        nfull = len(r.prompt) // block_size
        uniq.update(chain_keys(r.prompt, nfull, block_size))
        private += blocks_for(len(r.prompt) + r.max_new_tokens,
                              block_size) - nfull
    ws_blocks = len(uniq) + private
    big = 1 + ws_blocks + slots * nbper
    small = max(1 + nbper + 1, int(round(ws_blocks * 0.35)) + 1)

    def gate(tag, outs):
        for r in reqs:
            if not np.array_equal(seq_outs[r.uid], outs[r.uid]):
                mismatched.append((tag, r.uid))

    # --- scaling 1 -> 2 -> 4, WEAK: n replicas serve n x the traffic
    # (requests*n over sessions*n — the DP capacity claim: add a replica,
    # serve another replica's worth of users) with per-replica load held
    # constant.  Every scale gets a pool that holds its replica share of
    # the working set, so the ratio measures replica scaling, not
    # eviction luck.  Warm passes are INTERLEAVED 3-round across the
    # scales (the telemetry lane's trick) and busy/token deltas sum over
    # all rounds: wall-clock drift on a shared box hits every scale
    # alike instead of biasing whichever lane ran last.  Parity: the
    # base trace gates vs sequential; the bigger weak traces gate vs a
    # fresh single-replica fleet serving the identical trace (engine vs
    # sequential parity is the n=1 gate + every other serving test).
    scales = [n for n in (1, 2, 4) if n <= replicas]
    traces = {1: reqs}
    refs = {1: seq_outs}
    for n in scales:
        if n == 1:
            continue
        tr = build_trace(requests * n, vocab, seed, False, prefix_len,
                         False, sessions * n)
        traces[n] = tr
        refs[n] = fleet(1, num_blocks=n * big).serve(tr)
    fleets = {}
    scaling = {}
    for n in scales:
        router = fleet(n, num_blocks=big)
        t0 = time.perf_counter()
        outs = router.serve(traces[n])      # compile + prefix-warm pass
        cold = time.perf_counter() - t0
        for r in traces[n]:
            if not np.array_equal(refs[n][r.uid], outs[r.uid]):
                mismatched.append((f"scale{n}-cold", r.uid))
        fleets[n] = router
        gen_n = sum(r.max_new_tokens for r in traces[n])
        scaling[str(n)] = {"replicas": n,
                           "requests": len(traces[n]),
                           "generated_tokens": gen_n,
                           "wall_cold_s": cold,
                           "tok_s_wall_cold": gen_n / cold}
    acc = {n: [0.0, [0.0] * n, [0.0] * n] for n in scales}  # wall, busy, gen
    for _ in range(3):
        for n in scales:
            router = fleets[n]
            busy0 = router.busy_seconds
            gen0 = [p["generated_tokens"]
                    for p in router.stats()["per_replica"]]
            t0 = time.perf_counter()
            outs2 = router.serve(traces[n])
            warm = time.perf_counter() - t0
            for r in traces[n]:
                if not np.array_equal(refs[n][r.uid], outs2[r.uid]):
                    mismatched.append((f"scale{n}-warm", r.uid))
            busy1 = router.busy_seconds
            gen1 = [p["generated_tokens"]
                    for p in router.stats()["per_replica"]]
            acc[n][0] += warm
            acc[n][1] = [a + (b1 - b0) for a, b0, b1 in
                         zip(acc[n][1], busy0, busy1)]
            acc[n][2] = [a + (g1 - g0) for a, g0, g1 in
                         zip(acc[n][2], gen0, gen1)]
    for n in scales:
        wall3, busy, gens = acc[n]
        st = fleets[n].stats()
        gen_n = scaling[str(n)]["generated_tokens"]
        scaling[str(n)].update({
            "wall_warm_s": wall3 / 3,
            "tok_s_wall_warm": gen_n / (wall3 / 3),
            "busy_warm_s": busy,
            "aggregate_tok_s_busy": sum(
                g / max(b, 1e-9) for g, b in zip(gens, busy) if g > 0),
            "routed_affinity": st["routed_affinity"],
            "routed_balance": st["routed_balance"],
            "prefix_cache_hit_rate": st["prefix_cache_hit_rate"],
            "compile_budgets_ok": all(
                p["compile_count"] <= p["compile_budget"]
                for p in st["per_replica"]),
            "per_replica_compiles": [
                [p["compile_count"], p["compile_budget"]]
                for p in st["per_replica"]],
        })
    fleets.clear()                          # free the pools
    ratios = {}
    for a, b in ((1, 2), (2, 4)):
        if str(a) in scaling and str(b) in scaling:
            ratios[f"{a}to{b}"] = (scaling[str(b)]["aggregate_tok_s_busy"]
                                   / scaling[str(a)]["aggregate_tok_s_busy"])

    # --- affinity vs round-robin twin fleets at 2 replicas on a
    # PRESSURE-SIZED device pool (the tiered-lane working-set math):
    # affinity halves each replica's session working set, round-robin
    # makes every replica carry all of it — the hit-rate gap IS the
    # routing policy's value under real block pressure
    aff_vs_rr = None
    if replicas >= 2:
        r_aff = fleet(2, num_blocks=small)
        gate("aff-cold", r_aff.serve(reqs))
        aff_cold = r_aff.stats()["prefix_cache_hit_rate"]
        gate("aff-warm", r_aff.serve(reqs))
        r_rr = fleet(2, policy="round_robin", num_blocks=small)
        gate("rr-cold", r_rr.serve(reqs))
        rr_cold = r_rr.stats()["prefix_cache_hit_rate"]
        gate("rr-warm", r_rr.serve(reqs))
        sa, sr = r_aff.stats(), r_rr.stats()
        aff_vs_rr = {
            "device_pool_blocks": small,
            "working_set_blocks": ws_blocks,
            "affinity_hit_rate_cold": aff_cold,
            "round_robin_hit_rate_cold": rr_cold,
            "affinity_hit_rate": sa["prefix_cache_hit_rate"],
            "round_robin_hit_rate": sr["prefix_cache_hit_rate"],
            "affinity_routed": [sa["routed_affinity"],
                                sa["routed_balance"]],
            "hit_rate_advantage": (sa["prefix_cache_hit_rate"]
                                   - sr["prefix_cache_hit_rate"]),
        }

    # --- drained-replica migration: sessions co-locate under affinity,
    # the owning replica drains (chains demote to ITS host tier), and a
    # continuation of its session resumes on the cold replica via the
    # cross-replica KV pull — vs a kv_pull=False twin that re-prefills
    # the whole prompt.  Zero prefix recompute means the cold replica
    # prefills only the mandatory sub-block tail.
    migration = None
    if replicas >= 2:
        hb = sessions * (prefix_len // block_size + 2) + 2 * nbper
        # request i belongs to session i % sessions (build_trace), so the
        # first `sessions` requests carry each session's shared prefix
        prefixes = [reqs[j].prompt[:prefix_len] for j in range(sessions)]

        def prep_migration(kv_pull):
            # pressure-sized device pool: the trace itself exercises the
            # demote/promote swap programs on BOTH replicas, so the timed
            # migration below is compile-free on every side
            router = fleet(2, host_blocks=hb, kv_pull=kv_pull,
                           num_blocks=small)
            gate(f"mig-pull{kv_pull}-trace", router.serve(reqs))
            gate(f"mig-pull{kv_pull}-warm", router.serve(reqs))
            # each session's home replica, then drain the busier home and
            # continue EVERY migrated session on the survivor — the
            # pull-vs-recompute gap scales with the migrated population
            # instead of drowning in single-request timing noise
            homes = []
            for p in prefixes:
                probe = [router.replicas[r].affinity_probe(
                    np.concatenate([p, [0]])) for r in range(2)]
                homes.append(int(np.argmax(
                    [q["device_blocks"] + q["host_blocks"]
                     for q in probe])))
            rid0 = int(np.argmax([homes.count(r) for r in range(2)]))
            migrated = [j for j, h in enumerate(homes) if h == rid0]
            # short completion budgets on purpose: migration changes the
            # PREFILL side (pull vs recompute the prefix), so the timed
            # window is TTFT-shaped — a long decode tail would be the
            # same on both sides and bury the difference
            rng = np.random.default_rng(seed + 1)
            conts = [Request(uid=f"mig{j}-{k}",
                             prompt=np.concatenate(
                                 [prefixes[j],
                                  rng.integers(0, vocab, 9 + k)]),
                             max_new_tokens=4)
                     for j in migrated for k in range(2)]
            seq_cont = {c.uid: seq_engine.generate(
                c.prompt[None, :], max_new_tokens=c.max_new_tokens)[0]
                for c in conts}
            router.drain(rid0)
            return router, router.replicas[1 - rid0], conts, seq_cont

        def timed_migration(prep, tag):
            router, tgt, conts, seq_cont = prep
            # dispatch warmup outside the window: one session-free short
            # request (sub-block prompt: no trie/host interaction) so the
            # first timed iteration doesn't pay cold host caches for
            # whatever ran since this fleet's prep
            wrng = np.random.default_rng(seed + 2)
            router.serve([Request(uid=f"warm-{tag}",
                                  prompt=wrng.integers(0, vocab, 8),
                                  max_new_tokens=2)])
            pt0, ht0 = tgt.prompt_tokens, tgt.prefix_hit_tokens
            t0 = time.perf_counter()
            outs = router.serve(conts)
            wall = time.perf_counter() - t0
            for c in conts:
                if not np.array_equal(seq_cont[c.uid], outs[c.uid]):
                    mismatched.append((tag, c.uid))
            recompute = (tgt.prompt_tokens - pt0) - \
                (tgt.prefix_hit_tokens - ht0)
            min_tail = sum(
                len(c.prompt)
                - ((len(c.prompt) - 1) // block_size) * block_size
                for c in conts)
            return wall, recompute, min_tail, conts

        # prepare BOTH fleets first, then run the two timed windows
        # back-to-back — wall drift on a shared box cannot favor one
        prep_pull = prep_migration(True)
        prep_re = prep_migration(False)
        wall_pull, rec_pull, min_tail, conts = timed_migration(
            prep_pull, "mig-pull")
        wall_re, rec_re, _, _ = timed_migration(prep_re, "mig-recompute")
        r_pull = prep_pull[0]
        sp = r_pull.stats()
        migration = {
            "migrated_sessions": len(conts) // 2,
            "continuations": len(conts),
            "host_blocks": hb,
            "kv_pulls": sp["kv_pulls"],
            "kv_pull_blocks": sp["kv_pull_blocks"],
            "kv_pull_bytes": sp["kv_pull_bytes"],
            "drains": sp["drains"],
            "wall_pull_s": wall_pull,
            "wall_recompute_s": wall_re,
            "speedup_pull_vs_recompute": wall_re / wall_pull,
            "recompute_tokens_pull": int(rec_pull),
            "recompute_tokens_baseline": int(rec_re),
            "mandatory_tail_tokens": int(min_tail),
            "zero_prefix_recompute": bool(rec_pull <= min_tail),
        }

    # --- federated fleet metrics artifact (--emit-metrics): with
    # --replicas the snapshot is the FLEET view — router + every replica
    # registry federated with replica= labels (telemetry/aggregate.py) —
    # not one engine's registry.  Emitted from the migration fleet (its
    # counters carry the kv-pull/drain story), else the affinity fleet.
    metrics_files = None
    emit_router = None
    if replicas >= 2:
        emit_router = r_pull if migration is not None else r_aff
    if emit_metrics and emit_router is not None:
        with open(emit_metrics, "w") as f:
            f.write(emit_router.fleet_metrics_text())
        snap_path = emit_metrics + ".json"
        with open(snap_path, "w") as f:
            json.dump(emit_router.fleet_snapshot(), f, indent=2)
        metrics_files = {"prometheus": emit_metrics,
                         "snapshot": snap_path, "federated": True}

    return {
        "protocol": "multi-replica DP router (PR 11): busy-time scaling "
                    "over 1->2->4 replicas, affinity-vs-round-robin hit "
                    "rate, drained-replica KV-pull migration — all "
                    "parity-gated vs sequential generate",
        "methodology": "WEAK scaling: n replicas serve n x the traffic "
                       "(requests*n over sessions*n) with per-replica "
                       "load constant; a single process time-slices the "
                       "replicas on the host CPU (each replica = one "
                       "simulated accelerator), so aggregate_tok_s_busy "
                       "— each replica's tokens over its own step() "
                       "wall time, summed over 3 interleaved warm "
                       "rounds — is the DP scaling signal; wall-clock "
                       "tok/s is flat on a 1-core box by construction",
        "trace": f"{sessions} sessions x {prefix_len}-token prefixes "
                 f"(round-robin returns), tails {TAIL_RANGE}, new "
                 f"{PREFIX_NEW_RANGE}",
        "requests": requests,
        "generated_tokens": gen_tokens,
        "sequential": {"tok_s": gen_tokens / seq_wall, "wall_s": seq_wall},
        "scaling": scaling,
        "scaling_ratio_busy": ratios,
        "affinity_vs_round_robin": aff_vs_rr,
        "migration": migration,
        "metrics_files": metrics_files,
        "token_parity": not mismatched,
        "mismatched": mismatched,
        "model": f"gpt2-{layers}l-{hidden}d-{vocab}v ({dtype})",
        "backend": __import__("jax").default_backend(),
    }


def run_chaos_bench(requests: int = 64, slots: int = 8,
                    prefill_batch: int = 4, layers: int = 2,
                    hidden: int = 128, heads: int = 4, vocab: int = 2048,
                    seed: int = 0, dtype: str = "fp32",
                    block_size: int = 32, prefill_chunk: int = 128,
                    prefix_len: int = 192, sessions: int = 16,
                    swap_batch: int = 8, overload: int = 4,
                    quantize: tuple = ()):
    """The BENCH_r14 chaos protocol (PR 15, module docstring
    ``--chaos``): seeded fault plans against the 16-session returning
    trace, every recovery gate measured.

     - **crash lane**: a seeded FaultPlan kills one of two tiered
       replicas mid-decode; every in-flight + pending request must
       complete on the survivor with tokens EXACTLY matching the
       fault-free twin fleet (fp32), zero hung handles, budgets intact.
       Recovery latency = the timeline gap from ``replica_fail`` to the
       last ``rehome``.  A ``kv8`` lane repeats the kill vs an
       unfaulted kv8 twin (bit-exact) and records the bounded token
       match vs full-precision sequential.  A **sampled** twin (PR 20)
       repeats the kill with odd-uid requests sampling at temperature
       0.8 — the counter-based PRNG streams must replay token-EXACTLY
       on the survivor (keys are pure functions of (request seed,
       tokens emitted), never of replica/slot state).
     - **flaky-transport lane**: transient TransportErrors on the pull
       path; a drain-forced migration must still land its pulls through
       the retry/backoff machinery with exact parity.
     - **corruption lane**: bit flips in every host-tier arena entry
       after a full drain; 100% must be detected by checksum at the
       promote gate and recovered via recompute — corrupt KV is never
       served (exact parity).
     - **overload/shed lane**: an ``overload``x burst of batch traffic
       in front of the protected classes with bounded admission;
       ``realtime``/``interactive`` submit-to-first-token p95 must stay
       within 1.5x of the unloaded baseline while batch absorbs every
       rejection (bench-side stamps — engine TTFT excludes queue wait,
       and queue wait is exactly what shedding bounds).
     - **flight-recorder lane** (ISSUE 18): the crash lane re-run with
       an :class:`IncidentRecorder` armed — the dumped bundle must pass
       the structural audit and ``replay_bundle`` must reproduce the
       trigger at the recorded scheduler iteration with token-exact
       pre-crash streams; recorder-on tokens must be identical to the
       recorder-off twin (<=2% wall overhead recorded, warn-only).
     - **stall-watchdog lane**: traffic submitted, stepping withheld —
       the :class:`StallWatchdog` must detect no-progress within its
       deadline and dump a ``watchdog_stall`` bundle carrying every
       thread's stack; the parked traffic then serves out cleanly.
    """
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.ops.paged_kv import blocks_for
    from deepspeed_tpu.serving import (FaultInjector, FaultPlan,
                                       ReplicaRouter, RequestRejected)

    cfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=1024,
                          num_layers=layers, num_heads=heads,
                          hidden_size=hidden)
    spec = gpt2.build(cfg)
    max_total = prefix_len + max(TAIL_RANGE) + max(PREFIX_NEW_RANGE)
    nbper = blocks_for(max_total, block_size)
    state = {"params": None}

    def mk_engine():
        eng = deepspeed_tpu.init_inference(
            spec, config={"dtype": dtype,
                          "tensor_parallel": {"tp_size": 1}},
            params=state["params"])
        if state["params"] is None:
            state["params"] = eng.params
        return eng

    def mk_srv(**extra):
        kw = dict(slots=slots, max_seq_len=max_total,
                  prefill_batch=prefill_batch, block_size=block_size,
                  prefill_chunk=prefill_chunk, host_blocks=max(
                      32, sessions * (prefix_len // block_size + 2)),
                  swap_batch=swap_batch, debug_checks=True)
        kw.update(extra)
        return ServingEngine(mk_engine(), **kw)

    def fleet(n=2, **router_kw):
        return ReplicaRouter([mk_srv() for _ in range(n)],
                             debug_checks=True, **router_kw)

    reqs = build_trace(requests, vocab, seed, False, prefix_len, False,
                       sessions)
    gen_tokens = sum(r.max_new_tokens for r in reqs)
    seq_engine = mk_engine()
    seq_outs, seq_wall = run_sequential(seq_engine, reqs)
    mismatched = []

    def gate(tag, ref, outs, uids=None):
        for uid in (uids if uids is not None else [r.uid for r in reqs]):
            if not np.array_equal(ref[uid], outs[uid]):
                mismatched.append((tag, uid))

    def drive_handles(router, handles):
        while router.step():
            pass
        return {h.uid: (h.result(timeout=0) if h.status == "finished"
                        else None) for h in handles}

    def recovery_window_s(router):
        """Timeline gap replica_fail -> last rehome (microsecond stamps
        on the router ring) — the crash-to-recovered latency."""
        evs = router.timeline.events()
        t_fail = [e["ts"] for e in evs if e["name"] == "replica_fail"]
        t_home = [e["ts"] for e in evs if e["name"] == "rehome"]
        if not t_fail or not t_home:
            return None
        return (max(t_home) - min(t_fail)) / 1e6

    # ---------------------------------------------------------- crash lane
    crash_step = 6                 # mid-decode for this trace shape
    crash_plan = FaultPlan(seed=seed,
                           crashes=[{"replica": 1,
                                     "at_step": crash_step}])
    free = fleet()
    outs_free = free.serve(reqs)
    gate("crash-faultfree", seq_outs, outs_free)
    chaos = fleet()
    inj = chaos.arm_faults(crash_plan)
    handles = [chaos.submit(r) for r in reqs]
    t0 = time.perf_counter()
    outs_chaos = drive_handles(chaos, handles)
    chaos_wall = time.perf_counter() - t0
    gate("crash-chaos", outs_free, outs_chaos)
    st = chaos.stats()
    crash = {
        "plan": crash_plan.to_json(),
        "crashes_fired": inj.report()["crashes_fired"],
        "hung_handles": sum(1 for h in handles if not h.done),
        "unfinished": sum(1 for h in handles
                          if h.status != "finished"),
        "requests_rehomed": st["requests_rehomed"],
        "requests_failed": st["requests_failed"],
        "replica_failures": st["replica_failures"],
        "kv_pulls": st["kv_pulls"],
        "recovery_latency_s": recovery_window_s(chaos),
        "wall_s": chaos_wall,
        "tok_s_wall": gen_tokens / chaos_wall,
        "compile_budgets_ok": all(
            p["compile_count"] <= p["compile_budget"]
            for p in st["per_replica"]),
        "survivor_prefix_hit_rate":
            st["per_replica"][0]["prefix_cache_hit_rate"],
        "parity_exact_vs_faultfree": not any(
            t == "crash-chaos" for t, _ in mismatched),
    }

    # kv8 crash twin (bounded divergence vs fp32 sequential, bit-exact
    # vs the unfaulted kv8 fleet)
    crash_kv8 = None
    if quantize and "kv8" in quantize:
        tu = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tests", "unit")
        if tu not in sys.path:
            sys.path.insert(0, tu)
        from quant_divergence import token_match_rate

        def kv8_fleet():
            return ReplicaRouter([mk_srv(quantize="kv8")
                                  for _ in range(2)], debug_checks=True)

        ref_q = kv8_fleet().serve(reqs)
        chaos_q = kv8_fleet()
        chaos_q.arm_faults(FaultPlan(
            seed=seed, crashes=[{"replica": 1, "at_step": crash_step}]))
        hq = [chaos_q.submit(r) for r in reqs]
        outs_q = drive_handles(chaos_q, hq)
        gate("crash-kv8-vs-twin", ref_q, outs_q)
        crash_kv8 = {
            "bit_exact_vs_unfaulted_kv8": not any(
                t == "crash-kv8-vs-twin" for t, _ in mismatched),
            "token_match_rate_vs_sequential":
                token_match_rate(seq_outs, outs_q),
            "requests_rehomed":
                chaos_q.stats()["requests_rehomed"],
        }

    # ---------------------------------------------- sampled crash lane
    # PR 20: the crash lane repeated with odd-uid requests SAMPLING
    # (temperature 0.8, per-request seeds).  Re-homing must replay the
    # streams token-EXACTLY on the survivor: the counter-based PRNG key
    # is a pure function of (request seed, tokens emitted), never of
    # the replica/slot that drew it, so a rebuilt slot resumes the
    # stream mid-request with no drift.
    srng = np.random.default_rng([seed, 1009])
    sreqs = [Request(uid=r.uid, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens,
                     temperature=0.8, top_k=20, top_p=0.95,
                     seed=int(srng.integers(1, 2 ** 31 - 1)))
             if r.uid % 2 else
             Request(uid=r.uid, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens)
             for r in reqs]
    free_s = fleet()
    outs_free_s = free_s.serve(sreqs)
    chaos_s = fleet()
    inj_s = chaos_s.arm_faults(FaultPlan(
        seed=seed, crashes=[{"replica": 1, "at_step": crash_step}]))
    handles_s = [chaos_s.submit(r) for r in sreqs]
    outs_chaos_s = drive_handles(chaos_s, handles_s)
    gate("crash-sampled", outs_free_s, outs_chaos_s)
    st_s = chaos_s.stats()
    crash_sampled = {
        "sampled_requests": sum(1 for r in sreqs if r.sampled),
        "crashes_fired": inj_s.report()["crashes_fired"],
        "hung_handles": sum(1 for h in handles_s if not h.done),
        "requests_rehomed": st_s["requests_rehomed"],
        "replica_failures": st_s["replica_failures"],
        "compile_budgets_ok": all(
            p["compile_count"] <= p["compile_budget"]
            for p in st_s["per_replica"]),
        "parity_exact_vs_faultfree": not any(
            t == "crash-sampled" for t, _ in mismatched),
    }

    # ------------------------------------------------- flaky transport lane
    flaky_plan = FaultPlan(
        seed=seed + 1,
        transport={"ops": ["export", "import"], "transient_rate": 1.0,
                   "max_faults": 2},
        stalls=[{"replica": 0, "at_step": 3, "stall_s": 0.002}])
    flk = fleet(pull_retries=5)
    inj_f = flk.arm_faults(flaky_plan)
    gate("flaky-trace", seq_outs, flk.serve(reqs))
    # drain the busiest session home => forced cross-replica pulls
    # through the flaky transport
    prefixes = [reqs[j].prompt[:prefix_len] for j in range(sessions)]

    def _home(p):
        probes = [flk.replicas[r].affinity_probe(
            np.concatenate([p, [0]])) for r in range(2)]
        return int(np.argmax([q["device_blocks"] + q["host_blocks"]
                              for q in probes]))

    homes = [_home(p) for p in prefixes]
    rid0 = int(np.argmax([homes.count(r) for r in range(2)]))
    migrated = [j for j, h in enumerate(homes) if h == rid0]
    flk.drain(rid0)
    rng = np.random.default_rng(seed + 2)
    conts = [Request(uid=f"mig{j}", prompt=np.concatenate(
        [prefixes[j], rng.integers(0, vocab, 9)]), max_new_tokens=4)
        for j in migrated]
    seq_cont = {c.uid: seq_engine.generate(
        c.prompt[None, :], max_new_tokens=4)[0] for c in conts}
    outs_mig = flk.serve(conts)
    gate("flaky-migration", seq_cont, outs_mig,
         uids=[c.uid for c in conts])
    stf = flk.stats()
    flaky = {
        "plan": flaky_plan.to_json(),
        "transport_faults_injected": inj_f.report()["transport_faults"],
        "stalls_fired": inj_f.report()["stalls_fired"],
        "kv_pull_retries": stf["kv_pull_retries"],
        "kv_pulls": stf["kv_pulls"],
        "kv_pull_blocks": stf["kv_pull_blocks"],
        "migrated_sessions": len(migrated),
        "pulls_landed_through_retries": stf["kv_pulls"] >= 1
        and stf["kv_pull_retries"] >= 1,
    }

    # ------------------------------------------------------ corruption lane
    # arena sized with 3x headroom: during the post-corruption re-serve
    # nothing is LRU-evicted, so EVERY injected corruption is still
    # accountable at the end — caught at a promote exit gate during
    # traffic, or by the final patrol scrub (entries shadowed behind an
    # earlier corrupt block in their chain are never probed by traffic;
    # the scrub is the background-scrubber primitive that finds them)
    srv_c = mk_srv(host_blocks=3 * max(
        64, sessions * (prefix_len // block_size + 4)))
    outs_c = srv_c.serve(reqs)
    gate("corrupt-pre", seq_outs, outs_c)
    srv_c.drain()                  # host tier becomes the only copy
    n_host = len(srv_c._host)
    corrupt_plan = FaultPlan(
        seed=seed + 3,
        corruption=[{"replica": 0, "at_step": 1, "entries": n_host,
                     "bits": 3}])
    inj_c = FaultInjector(corrupt_plan)
    srv_c.arm_faults(inj_c.bind(0))
    re_reqs = [Request(uid=f"re{r.uid}", prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens) for r in reqs]
    outs_c2 = srv_c.serve(re_reqs)
    srv_c.arm_faults(None)
    gate("corrupt-post", {f"re{r.uid}": seq_outs[r.uid] for r in reqs},
         outs_c2, uids=[r.uid for r in re_reqs])
    detected_gate = int(srv_c._c_checksum_fail.value)
    scrubbed = srv_c.scrub_host_tier()
    detected = int(srv_c._c_checksum_fail.value)
    corruption = {
        "plan": corrupt_plan.to_json(),
        "host_entries_corrupted": inj_c.corrupted_entries,
        "detected_at_exit_gates": detected_gate,
        "detected_by_patrol_scrub": scrubbed,
        "checksum_failures_detected": detected,
        "detected_100pct": detected == inj_c.corrupted_entries
        and inj_c.corrupted_entries > 0,
        "recovered_via_recompute_parity": not any(
            t == "corrupt-post" for t, _ in mismatched),
        "swap_in_after_corruption": srv_c.stats()["swap_in"],
    }

    # -------------------------------------------------- overload/shed lane
    classes = ("realtime", "interactive")

    def measure_ttft(router, entries, warm_reqs=None):
        """Submit everything up front (batch first — the adversarial
        order), then step-poll: per-uid submit->first-token wall time,
        bench-side (INCLUDES queue wait, unlike the engine's
        slot-admission TTFT)."""
        if warm_reqs:                       # compile outside the window
            router.serve(warm_reqs)
        handles, t_submit, t_first, shed = {}, {}, {}, []
        for req, cls in entries:
            t_submit[req.uid] = time.perf_counter()
            try:
                handles[req.uid] = router.submit(req, slo_class=cls)
            except RequestRejected as e:
                shed.append((e.uid, e.slo_class))
        live = True
        while live:
            live = router.step()
            now = time.perf_counter()
            for uid, h in handles.items():
                if uid not in t_first and h.tokens():
                    t_first[uid] = now
        per_class = {}
        for (req, cls) in entries:
            if req.uid in t_first:
                per_class.setdefault(cls, []).append(
                    t_first[req.uid] - t_submit[req.uid])
        return handles, per_class, shed

    def p95(xs):
        return float(np.percentile(xs, 95)) if xs else None

    n_prot = max(4, requests // 4)
    rng = np.random.default_rng(seed + 4)
    prot_entries = [
        (Request(uid=f"p{i}", prompt=np.concatenate(
            [prefixes[i % sessions],
             rng.integers(0, vocab, 12)]), max_new_tokens=6),
         classes[i % 2]) for i in range(n_prot)]
    batch_entries = [
        (Request(uid=f"b{i}", prompt=np.concatenate(
            [prefixes[i % sessions],
             rng.integers(0, vocab, 12)]), max_new_tokens=6), "batch")
        for i in range(n_prot * (overload - 1))]
    warm = [Request(uid=f"w{i}", prompt=np.concatenate(
        [prefixes[i % sessions], rng.integers(0, vocab, 10)]),
        max_new_tokens=3) for i in range(4)]

    base_fleet = fleet()               # unloaded, shedding off
    _, base_cls, base_shed = measure_ttft(
        base_fleet, [(r, c) for r, c in prot_entries], warm_reqs=warm)
    shed_fleet = fleet(max_queue_depth=max(2, slots))
    over_entries = batch_entries + \
        [(Request(uid=r.uid + "o", prompt=r.prompt,
                  max_new_tokens=r.max_new_tokens), c)
         for r, c in prot_entries]
    over_handles, over_cls, over_shed = measure_ttft(
        shed_fleet, over_entries, warm_reqs=warm)
    base_p95 = p95(base_cls.get("realtime", [])
                   + base_cls.get("interactive", []))
    over_p95 = p95(over_cls.get("realtime", [])
                   + over_cls.get("interactive", []))
    shed_by_class = {}
    for _, cls in over_shed:
        key = cls if cls is not None else "standard"
        shed_by_class[key] = shed_by_class.get(key, 0) + 1
    overload_shed = {
        "overload_factor": overload,
        "protected_requests": n_prot,
        "batch_requests_offered": len(batch_entries),
        "max_queue_depth": max(2, slots),
        "unloaded_protected_ttft_p95_s": base_p95,
        "overloaded_protected_ttft_p95_s": over_p95,
        "protected_p95_ratio": (over_p95 / base_p95
                                if base_p95 and over_p95 else None),
        "protected_within_1p5x": bool(
            base_p95 and over_p95 and over_p95 <= 1.5 * base_p95),
        "shed_by_class": shed_by_class,
        "protected_shed": sum(v for k, v in shed_by_class.items()
                              if k != "batch"),
        "batch_absorbed_all_rejections": bool(shed_by_class) and all(
            k == "batch" for k in shed_by_class),
        "unloaded_sheds": len(base_shed),
        "protected_finished": sum(
            1 for uid, h in over_handles.items()
            if not uid.startswith("b") and h.status == "finished"),
    }

    # ------------------------------------------- flight-recorder lane
    # (ISSUE 18, docs/observability.md "Incident response"): the crash
    # lane re-run with the black-box recorder armed.  Gates: the
    # recorder must not perturb the schedule (token identity vs the
    # recorder-off chaos twin above), the dumped bundle must pass the
    # structural audit, and an in-process ``replay_bundle()`` must
    # re-execute it to the SAME trigger at the SAME scheduler iteration
    # with token-exact pre-crash streams.  The <=2% recorder-overhead
    # contract is recorded and warned on breach (wall-clock-noise-prone
    # on shared runners, like every wall-clock contract in this bench).
    import tempfile

    from deepspeed_tpu.analysis.invariants import audit_incident_bundle
    from deepspeed_tpu.telemetry.incident import (IncidentRecorder,
                                                  StallWatchdog,
                                                  gpt2_model_meta,
                                                  is_bundle,
                                                  replay_bundle)

    inc_dir = tempfile.mkdtemp(prefix="graft_incidents_")
    rec = IncidentRecorder(inc_dir, vocab=vocab,
                           model_meta=gpt2_model_meta(cfg, dtype=dtype))
    inc_fleet = fleet()
    rec.attach(inc_fleet)
    inc_fleet.arm_faults(FaultPlan(
        seed=seed, crashes=[{"replica": 1, "at_step": crash_step}]))
    h_inc = [inc_fleet.submit(r) for r in reqs]
    t0 = time.perf_counter()
    outs_inc = drive_handles(inc_fleet, h_inc)
    inc_wall = time.perf_counter() - t0
    rec.detach()
    gate("incident-recorder-on", outs_chaos, outs_inc)
    bundles = sorted(d for d in os.listdir(inc_dir)
                     if is_bundle(os.path.join(inc_dir, d)))
    bundle_audit_ok, replay_report = False, None
    if bundles:
        bpath = os.path.join(inc_dir, bundles[0])
        try:
            audit_incident_bundle(bpath)
            bundle_audit_ok = True
        except Exception as e:
            print(f"WARNING: incident bundle fails audit: {e}",
                  file=sys.stderr)
        replay_report = replay_bundle(bpath)

    # stall-watchdog lane: traffic submitted, stepping withheld — the
    # "fleet merely STOPPED" failure mode membership probes can't see.
    # The watchdog must detect no-progress within its deadline and dump
    # a watchdog_stall bundle carrying every thread's stack; afterwards
    # the parked traffic is served out so nothing leaks from the lane.
    stall_dir = tempfile.mkdtemp(prefix="graft_incidents_stall_")
    rec_s = IncidentRecorder(stall_dir, vocab=vocab,
                             model_meta=gpt2_model_meta(cfg, dtype=dtype))
    stall_fleet = fleet()
    rec_s.attach(stall_fleet)
    stall_handles = [stall_fleet.submit(r) for r in reqs[:4]]
    wd = StallWatchdog(stall_fleet, deadline_s=0.05, poll_s=0.01,
                       recorder=rec_s).start()
    t_w = time.perf_counter()
    while wd.stalls == 0 and time.perf_counter() - t_w < 10.0:
        time.sleep(0.01)
    wd.stop()
    while stall_fleet.step():
        pass
    rec_s.detach()
    stall_bundles = [d for d in os.listdir(stall_dir)
                     if is_bundle(os.path.join(stall_dir, d))]
    stall_has_stacks = False
    for d in stall_bundles:
        tpath = os.path.join(stall_dir, d, "threads.txt")
        if d.split("-")[-1] == "watchdog_stall" and \
                os.path.isfile(tpath) and os.path.getsize(tpath) > 0:
            stall_has_stacks = True
    wd_counter = int(stall_fleet.metrics.counter(
        "serving_watchdog_stalls_total", "").value)
    incident = {
        "bundle_dir": inc_dir,
        "bundles": bundles,
        "bundle_audit_ok": bundle_audit_ok,
        "replay_reproduced": bool(replay_report
                                  and replay_report["reproduced"]),
        "replay_trigger": replay_report["trigger"]
        if replay_report else None,
        "replay_mismatches": replay_report["mismatches"]
        if replay_report else ["no bundle dumped"],
        "recorder_token_identity": not any(
            t == "incident-recorder-on" for t, _ in mismatched),
        "recorder_wall_s": inc_wall,
        "recorder_off_wall_s": chaos_wall,
        "recorder_overhead_frac": inc_wall / chaos_wall - 1.0,
        "recorder_overhead_within_2pct":
            inc_wall <= 1.02 * chaos_wall,
        "watchdog_stalls_detected": wd.stalls,
        "watchdog_counter": wd_counter,
        "watchdog_bundles": stall_bundles,
        "watchdog_stall_has_thread_stacks": stall_has_stacks,
        "watchdog_parked_served_out": all(
            h.status == "finished" for h in stall_handles),
    }

    return {
        "protocol": "fault-tolerant serving fleet (PR 15, BENCH_r14): "
                    "seeded crash-at-iteration / flaky-transport / "
                    "host-corruption / overload-shedding lanes on the "
                    "returning-sessions trace, every lane parity- or "
                    "counter-gated (docs/reliability.md)",
        "trace": f"{sessions} sessions x {prefix_len}-token prefixes, "
                 f"tails {TAIL_RANGE}, new {PREFIX_NEW_RANGE}",
        "requests": requests,
        "generated_tokens": gen_tokens,
        "sequential": {"tok_s": gen_tokens / seq_wall,
                       "wall_s": seq_wall},
        "crash": crash,
        "crash_sampled": crash_sampled,
        "crash_kv8": crash_kv8,
        "flaky_transport": flaky,
        "corruption": corruption,
        "overload_shed": overload_shed,
        "incident": incident,
        "token_parity": not mismatched,
        "mismatched": mismatched,
        "model": f"gpt2-{layers}l-{hidden}d-{vocab}v ({dtype})",
        "backend": __import__("jax").default_backend(),
    }


def run_disaggregated_bench(requests: int = 48, slots: int = 8,
                            prefill_batch: int = 4, layers: int = 2,
                            hidden: int = 128, heads: int = 4,
                            vocab: int = 2048, seed: int = 0,
                            dtype: str = "fp32", block_size: int = 32,
                            prefill_chunk: int = 128,
                            prefix_len: int = 192, sessions: int = 12,
                            swap_batch: int = 8, victims: int = 6,
                            victim_new: int = 48,
                            burst_prompts: int = 6,
                            burst_prompt_len: int = 576):
    """The BENCH_r16 disaggregated-serving protocol (ISSUE 17,
    ``--disaggregated``): prefill/decode worker split + NVMe third KV
    tier, every lane parity- or counter-gated.

     - **structure lane** (deterministic stepping): a 1 prefill + 1
       decode fleet serves the returning-sessions trace with tokens
       EXACTLY matching the colocated 2x``role="both"`` twin and the
       sequential reference.  Every admission hands off
       (``handoffs == requests``), and the decode worker never re-runs
       prompt prefill: its recompute is bounded by the sub-block tail
       (``resume_recompute_tokens <= admitted * block_size``).
     - **interference lane** (threaded, wall-clock): decode-heavy
       victim streams measured quiet, then again with a long-prompt
       burst landing mid-decode.  Bench-side token-arrival stamps give
       victim TPOT p95 per fleet; the disaggregated fleet's
       burst/quiet ratio should stay ~flat (<= 1.15x) while the
       colocated twin absorbs the prefill stall in its decode gaps.
       Wall-clock ratios are recorded and warn-only in CI (CPU-sim
       noise); token parity in both runs is a hard gate.
     - **nvme lane** (deterministic stepping): a pressured host arena
       over a tmpdir spill file; serving the trace must spill
       (``nvme_spills > 0``), session resumes must promote back through
       the staged path (``nvme_loads > 0``) with zero prefix recompute
       (recompute delta bounded by the sub-block tails) and exact
       parity, zero checksum rejects, and the tier-labeled swap
       metrics + ``nvme_spill``/``nvme_load`` timeline events present.
     - **bit-identity lane**: ``role="both"`` + ``nvme_blocks=0`` vs
       the plain PR 16 engine — same tokens, same swap counters, same
       compile budget (the feature is free when off).
    """
    import tempfile

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.ops.paged_kv import blocks_for
    from deepspeed_tpu.serving import ReplicaRouter

    cfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=1024,
                          num_layers=layers, num_heads=heads,
                          hidden_size=hidden)
    spec = gpt2.build(cfg)
    max_total = max(prefix_len + max(TAIL_RANGE) + max(PREFIX_NEW_RANGE),
                    burst_prompt_len + 8)
    state = {"params": None}

    def mk_engine():
        eng = deepspeed_tpu.init_inference(
            spec, config={"dtype": dtype,
                          "tensor_parallel": {"tp_size": 1}},
            params=state["params"])
        if state["params"] is None:
            state["params"] = eng.params
        return eng

    host_blocks = max(32, sessions * (prefix_len // block_size + 2))

    def mk_srv(**extra):
        kw = dict(slots=slots, max_seq_len=max_total,
                  prefill_batch=prefill_batch, block_size=block_size,
                  prefill_chunk=prefill_chunk, host_blocks=host_blocks,
                  swap_batch=swap_batch, debug_checks=True)
        kw.update(extra)
        return ServingEngine(mk_engine(), **kw)

    def disagg_fleet(**router_kw):
        return ReplicaRouter([mk_srv(role="prefill"),
                              mk_srv(role="decode")],
                             kv_pull=True, debug_checks=True,
                             **router_kw)

    def colo_fleet(**router_kw):
        return ReplicaRouter([mk_srv(role="both"), mk_srv(role="both")],
                             debug_checks=True, **router_kw)

    reqs = build_trace(requests, vocab, seed, False, prefix_len, False,
                       sessions)
    gen_tokens = sum(r.max_new_tokens for r in reqs)
    seq_engine = mk_engine()
    seq_outs, seq_wall = run_sequential(seq_engine, reqs)
    mismatched = []

    def gate(tag, ref, outs, uids=None):
        for uid in (uids if uids is not None else [r.uid for r in reqs]):
            if not np.array_equal(ref[uid], outs[uid]):
                mismatched.append((tag, uid))

    def p95(xs):
        return float(np.percentile(xs, 95)) if xs else None

    # ------------------------------------------------------ structure lane
    colo = colo_fleet()
    t0 = time.perf_counter()
    outs_colo = colo.serve(reqs)
    colo_wall = time.perf_counter() - t0
    gate("structure-colocated", seq_outs, outs_colo)
    dis = disagg_fleet()
    t0 = time.perf_counter()
    outs_dis = dis.serve(reqs)
    dis_wall = time.perf_counter() - t0
    gate("structure-disaggregated", seq_outs, outs_dis)
    std = dis.stats()
    pre = next(p for p in std["per_replica"] if p["role"] == "prefill")
    dec = next(p for p in std["per_replica"] if p["role"] == "decode")
    pre_eng = dis.replicas[pre["replica"]].stats()
    dec_eng = dis.replicas[dec["replica"]].stats()
    ev_names = [e["name"] for e in dis.timeline.events()]
    structure = {
        "requests": requests,
        "handoffs": std["handoffs"],
        "every_admission_handed_off": std["handoffs"] == len(reqs),
        "prefill_worker": {
            "prompt_tokens": pre_eng["prompt_tokens"],
            "prefill_calls": pre_eng["prefill_calls"],
            "handoffs": pre_eng["handoffs"],
        },
        "decode_worker": {
            "admitted": dec_eng["admitted"],
            "prompt_tokens": dec_eng["prompt_tokens"],
            "prefix_hit_tokens": dec_eng["prefix_hit_tokens"],
            "resume_recompute_tokens": dec_eng["resume_recompute_tokens"],
        },
        # the decode worker never re-runs prompt prefill: after the
        # chain pull only the sub-block tail past the last committed
        # block boundary is recomputed at admission
        "decode_recompute_bounded": (
            dec_eng["resume_recompute_tokens"]
            <= dec_eng["admitted"] * block_size),
        "decode_rode_the_pulled_chain": dec_eng["prefix_hit_tokens"] > 0,
        "handoff_events_on_timeline": "handoff" in ev_names,
        "kv_pulls": std["kv_pulls"],
        "kv_pull_blocks": std["kv_pull_blocks"],
        "colocated_wall_s": colo_wall,
        "disaggregated_wall_s": dis_wall,
        "parity_exact": not any(t.startswith("structure")
                                for t, _ in mismatched),
    }

    # --------------------------------------------------- interference lane
    # victims fit the decode worker's slots so the measurement isolates
    # PREFILL interference (the thing disaggregation removes), not slot
    # contention; burst admissions are pure prefill (max_new_tokens=1:
    # the first token is emitted during prefill, so they finish on the
    # prefill worker and never take a decode slot)
    victims = min(victims, slots)
    rng = np.random.default_rng(seed + 1)
    victim_reqs = [Request(uid=f"v{i}",
                           prompt=rng.integers(0, vocab, 16),
                           max_new_tokens=victim_new)
                   for i in range(victims)]
    burst_reqs = [Request(uid=f"g{i}",
                          prompt=rng.integers(0, vocab,
                                              burst_prompt_len),
                          max_new_tokens=1)
                  for i in range(burst_prompts)]
    warm = [Request(uid=f"w{i}", prompt=rng.integers(0, vocab, 16),
                    max_new_tokens=3) for i in range(2)] + \
           [Request(uid="wg", prompt=rng.integers(0, vocab,
                                                  burst_prompt_len),
                    max_new_tokens=1)]
    seq_victim = {r.uid: seq_engine.generate(
        r.prompt[None, :], max_new_tokens=r.max_new_tokens)[0]
        for r in victim_reqs}
    seq_burst = {r.uid: seq_engine.generate(
        r.prompt[None, :], max_new_tokens=r.max_new_tokens)[0]
        for r in burst_reqs}

    def run_stepped(mk_fleet, tag, with_burst):
        """Step-driven interference run on the per-replica VIRTUAL
        clock: single-threaded stepping serializes the fleet, so each
        replica's accumulated busy time is exactly the time ITS engine
        spent executing — what wall TPOT is on real per-chip hardware,
        and the only uncontaminated basis on a shared-core CPU sim
        (thread overlap there just time-slices one core).  Every victim
        token is stamped with its owning replica's busy clock; TPOT =
        consecutive same-replica stamps' deltas.  The burst fires once
        every victim is >= 2 tokens into its stream, so the long-prompt
        prefills land mid-decode; in the colocated fleet they ride the
        victims' own engines (the busy clock between victim tokens
        swallows whole prefill chunks), in the disaggregated fleet the
        decode worker's clock never runs a prefill program."""
        router = mk_fleet()
        router.serve(warm)                  # compile outside the window
        handles = {r.uid: router.submit(r) for r in victim_reqs}
        arrivals = {r.uid: [] for r in victim_reqs}  # (rid, busy, fired)
        burst_handles = {}
        b_submit, b_first = {}, {}
        fired = False
        dec_rids = sorted(router._decode_capable)
        dec_prefill_at_fire = None

        def _dec_prefill_calls():
            return sum(router.replicas[r].stats()["prefill_calls"]
                       for r in dec_rids)

        while router.step():
            # the burst phase ends when the last burst admission
            # completes — the window where prefill interference is live
            in_burst = fired and not all(
                h.done for h in burst_handles.values())
            for uid, h in handles.items():
                n = len(h.tokens())
                while len(arrivals[uid]) < n:
                    rid = router._handles[uid][1]
                    arrivals[uid].append(
                        (rid, router._busy_s[rid], in_burst))
            for uid, h in burst_handles.items():
                if uid not in b_first and h.tokens():
                    rid = router._handles[uid][1]
                    b_first[uid] = (rid, router._busy_s[rid])
            if with_burst and not fired and all(
                    len(a) >= 2 for a in arrivals.values()):
                fired = True
                dec_prefill_at_fire = _dec_prefill_calls()
                for r in burst_reqs:
                    h = router.submit(r)
                    rid = router._handles[r.uid][1]
                    burst_handles[r.uid] = h
                    b_submit[r.uid] = (rid, router._busy_s[rid])
        outs = {uid: h.result(timeout=0)
                for uid, h in {**handles, **burst_handles}.items()}
        gate(tag, {**seq_victim, **seq_burst}, outs, uids=list(outs))
        # victim TPOT = same-replica busy deltas between consecutive
        # tokens, steady-state window only (post-fire for the burst
        # run; tokens 2+ for the quiet run)
        gaps = []
        for uid, ts in arrivals.items():
            for (r0, t0, f0), (r1, t1, f1) in zip(ts[2:], ts[3:]):
                if r0 == r1 and ((f0 and f1) if with_burst else True):
                    gaps.append(t1 - t0)
        ttft = [b_first[uid][1] - b_submit[uid][1]
                for uid in burst_handles
                if uid in b_first
                and b_first[uid][0] == b_submit[uid][0]]
        dec_prefill_during_burst = (
            _dec_prefill_calls() - dec_prefill_at_fire
            if dec_prefill_at_fire is not None else None)
        return {"tpot_p95_s": p95(gaps), "n_gaps": len(gaps),
                "burst_ttft_p95_s": p95(ttft),
                "decode_prefill_calls_during_burst":
                    dec_prefill_during_burst}

    interference = {}
    for name, mk in (("colocated", colo_fleet),
                     ("disaggregated", disagg_fleet)):
        quiet = run_stepped(mk, f"quiet-{name}", with_burst=False)
        burst = run_stepped(mk, f"burst-{name}", with_burst=True)
        ratio = (burst["tpot_p95_s"] / quiet["tpot_p95_s"]
                 if quiet["tpot_p95_s"] and burst["tpot_p95_s"]
                 else None)
        interference[name] = {
            "victim_tpot_quiet_p95_s": quiet["tpot_p95_s"],
            "victim_tpot_burst_p95_s": burst["tpot_p95_s"],
            "tpot_burst_over_quiet": ratio,
            "burst_ttft_p95_s": burst["burst_ttft_p95_s"],
            "decode_prefill_calls_during_burst":
                burst["decode_prefill_calls_during_burst"],
        }
    dis_ratio = interference["disaggregated"]["tpot_burst_over_quiet"]
    colo_ratio = interference["colocated"]["tpot_burst_over_quiet"]
    interference["basis"] = (
        "per-replica busy (virtual) seconds, single-threaded stepping "
        "— equals wall TPOT on per-chip hardware")
    interference["victims"] = victims
    interference["burst_prompts"] = burst_prompts
    interference["burst_prompt_len"] = burst_prompt_len
    # the deterministic half of the flatness claim: during the burst
    # window the disaggregated decode worker executes ZERO prefill
    # programs while the colocated twin's victim engines run every
    # burst prompt's chunks between victim tokens
    interference["decode_isolated_from_prefill"] = (
        interference["disaggregated"]
        ["decode_prefill_calls_during_burst"] == 0
        and interference["colocated"]
        ["decode_prefill_calls_during_burst"] > 0)
    interference["tpot_flat_within_1p15"] = bool(
        dis_ratio is not None and dis_ratio <= 1.15)
    interference["colocated_degrades_more"] = bool(
        dis_ratio is not None and colo_ratio is not None
        and colo_ratio > dis_ratio)
    interference["ttft_no_worse_1p1"] = bool(
        interference["disaggregated"]["burst_ttft_p95_s"] is not None
        and interference["colocated"]["burst_ttft_p95_s"] is not None
        and interference["disaggregated"]["burst_ttft_p95_s"]
        <= 1.1 * interference["colocated"]["burst_ttft_p95_s"])
    interference["parity_exact"] = not any(
        t.startswith(("quiet-", "burst-")) for t, _ in mismatched)

    # ----------------------------------------------------------- nvme lane
    # pressured three-tier ladder: a device pool barely over one
    # sequence forces constant demotion, a half-watermark host arena a
    # fraction of the session working set forces LRU spill past it —
    # so resumes MUST promote back out of the spill file
    bp = blocks_for(prefix_len, block_size)
    trace_max = prefix_len + max(TAIL_RANGE) + max(PREFIX_NEW_RANGE)
    nvme_host = max(2 * swap_batch, sessions * bp // 3)
    with tempfile.TemporaryDirectory() as tmp:
        srv_n = ServingEngine(
            mk_engine(), slots=slots, max_seq_len=trace_max,
            prefill_batch=prefill_batch, block_size=block_size,
            prefill_chunk=prefill_chunk,
            num_blocks=1 + blocks_for(trace_max, block_size) + bp,
            host_blocks=nvme_host, swap_batch=swap_batch,
            debug_checks=True,
            nvme_blocks=sessions * (bp + 2),
            nvme_high_watermark=0.5,
            nvme_path=os.path.join(tmp, "kv.spill"))
        outs_n = srv_n.serve(reqs)
        gate("nvme-trace", seq_outs, outs_n)
        st_mid = srv_n.stats()
        rng = np.random.default_rng(seed + 2)
        conts = [Request(uid=f"n{j}", prompt=np.concatenate(
            [reqs[j].prompt[:prefix_len], rng.integers(0, vocab, 9)]),
            max_new_tokens=4) for j in range(sessions)]
        seq_cont = {c.uid: seq_engine.generate(
            c.prompt[None, :], max_new_tokens=4)[0] for c in conts}
        outs_cont = srv_n.serve(conts)
        gate("nvme-resume", seq_cont, outs_cont,
             uids=[c.uid for c in conts])
        st_n = srv_n.stats()
        recompute_delta = (st_n["resume_recompute_tokens"]
                           - st_mid["resume_recompute_tokens"])
        hit_delta = (st_n["prefix_hit_tokens"]
                     - st_mid["prefix_hit_tokens"])
        # zero PREFIX recompute: per resume only the 9 appended tokens
        # + the sub-block tail of the prefix may re-prefill
        recompute_bound = sessions * (9 + block_size)
        prom = srv_n.metrics.prometheus_text()
        names_n = [e["name"] for e in srv_n.timeline.events()]
        nvme = {
            "host_blocks": nvme_host,
            "nvme_blocks": sessions * (bp + 2),
            "nvme_spills": st_n["nvme_spills"],
            "nvme_loads": st_n["nvme_loads"],
            "nvme_blocks_in_use": st_n["nvme_blocks_in_use"],
            "checksum_rejects": srv_n._host.nvme_checksum_rejects,
            "spilled_under_pressure": st_mid["nvme_spills"] > 0,
            "resumed_from_nvme": (st_n["nvme_loads"]
                                  - st_mid["nvme_loads"]) > 0,
            "resume_recompute_tokens_delta": recompute_delta,
            "resume_prefix_hit_tokens_delta": hit_delta,
            "zero_prefix_recompute": recompute_delta <= recompute_bound,
            "tier_labeled_metrics": (
                'serving_kv_swaps_total{direction="out",tier="nvme"}'
                in prom
                and 'tier="host"' in prom
                and "serving_nvme_blocks_in_use" in prom),
            "timeline_events": ("nvme_spill" in names_n
                                and "nvme_load" in names_n),
            "parity_exact": not any(t.startswith("nvme")
                                    for t, _ in mismatched),
        }

    # --------------------------------------------------- bit-identity lane
    plain = mk_srv()
    outs_plain = plain.serve(reqs)
    twin = mk_srv(role="both", nvme_blocks=0)
    outs_twin = twin.serve(reqs)
    gate("bitident-plain", seq_outs, outs_plain)
    gate("bitident-twin", outs_plain, outs_twin)
    sp, stw = plain.stats(), twin.stats()
    bit_identity = {
        "tokens_identical": not any(t == "bitident-twin"
                                    for t, _ in mismatched),
        "swap_counters_identical": all(
            sp[k] == stw[k] for k in ("swap_out", "swap_in",
                                      "swap_bytes")),
        "schedule_identical": all(
            sp[k] == stw[k] for k in ("iterations", "generated_tokens",
                                      "prefix_hit_tokens")),
        "compile_budget_identical":
            sp["compile_budget"] == stw["compile_budget"],
        "nvme_stats_zero": (stw["nvme_spills"] == 0
                            and stw["nvme_loads"] == 0
                            and stw["nvme_blocks"] == 0),
    }

    return {
        "protocol": "disaggregated prefill/decode + NVMe third tier "
                    "(ISSUE 17, BENCH_r16): structure / interference / "
                    "nvme / bit-identity lanes on the returning-"
                    "sessions trace (docs/inference.md)",
        "trace": f"{sessions} sessions x {prefix_len}-token prefixes, "
                 f"tails {TAIL_RANGE}, new {PREFIX_NEW_RANGE}",
        "requests": requests,
        "generated_tokens": gen_tokens,
        "sequential": {"tok_s": gen_tokens / seq_wall,
                       "wall_s": seq_wall},
        "structure": structure,
        "interference": interference,
        "nvme": nvme,
        "bit_identity": bit_identity,
        "token_parity": not mismatched,
        "mismatched": mismatched,
        "model": f"gpt2-{layers}l-{hidden}d-{vocab}v ({dtype})",
        "backend": __import__("jax").default_backend(),
    }


def run_autotune_bench(requests: int = 64, sessions: int = 16,
                       prefix_len: int = 256, pool_frac: float = 0.25,
                       slots: int = 8, layers: int = 2, hidden: int = 128,
                       heads: int = 4, vocab: int = 2048, seed: int = 0,
                       dtype: str = "fp32",
                       results_dir: str = "autotuning_results_serving",
                       max_trials: int = None, min_budget: int = None,
                       eta: int = 2, min_speedup: float = 1.0,
                       resume: bool = False):
    """BENCH_r13 protocol (ROADMAP item 5): closed-loop serving autotune
    on the BENCH_r09 returning-sessions trace.

    The workload is ``sessions`` distinct ``prefix_len``-token session
    prefixes dealt round-robin over ``requests`` requests, with the
    device pool pressure-sized at ``pool_frac`` of the unique working
    set — the hand-picked default config (pressured pool, no host tier,
    no speculation) is candidate 0 AND the parity reference for every
    trial.  ``autotuning/runner.py tune_serving`` searches the knob
    space under the byte-equal memory ceiling with successive halving;
    every trial is parity-gated and runs ``debug_checks=True`` so the
    recompile sentry enforces each candidate's compile budget at trace
    time.  The bench gates on the measured winner >= ``min_speedup`` x
    the measured default and on ``best_config.json`` round-tripping
    through ``init_serving(**config)``."""
    import deepspeed_tpu
    from deepspeed_tpu.autotuning import ModelGeom, sessions_trace, \
        tune_serving
    from deepspeed_tpu.autotuning.space import workload_space
    from deepspeed_tpu.models import gpt2

    trace = sessions_trace(requests, vocab=vocab, seed=seed,
                           sessions=sessions, prefix_len=prefix_len,
                           tail_range=TAIL_RANGE,
                           new_range=PREFIX_NEW_RANGE)
    cfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=1024,
                          num_layers=layers, num_heads=heads,
                          hidden_size=hidden)
    deepspeed_tpu.comm.reset_topology()
    engine = deepspeed_tpu.init_inference(
        gpt2.build(cfg), config={"dtype": dtype,
                                 "tensor_parallel": {"tp_size": 1}})
    # the searched knobs: block geometry vs pool depth under ONE byte
    # ceiling, chunk window, n-gram speculation, and the host tier (the
    # BENCH_r09 escape hatch from pool-pressure preemption).  The
    # spec_tokens=24 point is deliberately past the verify kernel's
    # window: the constraint layer must prune it BEFORE any trial runs
    # (pruned_by_constraint in the artifact), not crash a trial
    space = workload_space(
        ModelGeom.from_engine(engine), trace, pool_frac=pool_frac,
        base={"slots": slots},
        domains={"block_size": (32, 64),
                 "prefill_chunk": (128, 256),
                 "spec_tokens": (0, 4, 24),
                 "host_blocks": (0, "ws")})
    summary = tune_serving(engine, trace, space=space, eta=eta,
                           min_budget=min_budget, max_trials=max_trials,
                           results_dir=results_dir, resume=resume)

    # best_config.json must round-trip: build an engine straight from the
    # artifact and replay a short slice through it
    with open(os.path.join(results_dir, "best_config.json")) as f:
        best = json.load(f)
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        gpt2.build(cfg), config={"dtype": dtype}, **best)
    probe = trace.slice(min(4, len(trace)))
    handles = probe.submit_all(srv)
    while srv.step():
        pass
    outs = {h.uid: h.result(timeout=0) for h in handles}
    roundtrip_ok = all(outs[u] is not None for u in outs) and \
        srv.resolved_config()["block_size"] == best["block_size"] and \
        srv.resolved_config()["num_blocks"] == best["num_blocks"] and \
        srv.resolved_config()["host_blocks"] == best["host_blocks"]

    speedup = summary["speedup"] or 0.0
    res = {
        "protocol": "closed-loop serving autotune (BENCH_r13): "
                    "successive-halving search over the serving knob "
                    "space on the BENCH_r09 returning-sessions trace, "
                    "every trial parity-gated with sentry-enforced "
                    "compile budgets; winner re-run at full budget vs "
                    "the hand-picked default",
        "trace": {"requests": requests, "sessions": sessions,
                  "prefix_len": prefix_len, "pool_frac": pool_frac,
                  "working_set_tokens": trace.working_set_tokens(),
                  "max_total_len": trace.max_total_len()},
        "model": {"layers": layers, "hidden": hidden, "heads": heads,
                  "vocab": vocab, "dtype": dtype},
        "search": {
            "candidates": summary["candidates"],
            "admissible": summary["admissible"],
            "pruned_by_constraint": summary["pruned_by_constraint"],
            "trials_executed": summary["trials_executed"],
            "trials_total": summary["trials_total"],
            "budget_spent_requests": summary["budget_spent_requests"],
            "rungs": summary["rungs"],
            "exhausted": summary["exhausted"],
            "mem_ceiling_bytes": space.mem_ceiling_bytes,
        },
        "default": {
            "config": space.default_config(),
            "measured_tok_s": summary["default"]["measured_tok_s"],
        },
        "winner": {
            "config": summary["best_config"],
            "predicted_tok_s": summary["winner"]["predicted_tok_s"],
            "measured_tok_s": summary["winner"]["measured_tok_s"],
            "token_match": summary["winner"]["record"].get("token_match"),
            "compiled_programs":
                summary["winner"]["record"].get("compiled_programs"),
            "prefix_cache_hit_rate":
                summary["winner"]["record"].get("prefix_cache_hit_rate"),
        },
        "speedup": speedup,
        "gates": {
            "min_speedup": min_speedup,
            "winner_ge_min_speedup": speedup >= min_speedup,
            "best_config_roundtrip": bool(roundtrip_ok),
            "all_trials_parity_gated": True,
            "sentry_strict_in_trials": True,
        },
        "artifacts": {
            "results_dir": results_dir,
            "best_config": os.path.join(results_dir, "best_config.json"),
            "exps": os.path.join(results_dir, "exps.json"),
            "report": os.path.join(results_dir, "report.md"),
        },
    }
    return res


def run_host_loop_bench(requests: int = 64, slots: int = 8,
                        prefill_batch: int = 4, layers: int = 2,
                        hidden: int = 128, heads: int = 4,
                        vocab: int = 2048, seed: int = 0,
                        dtype: str = "fp32", block_size: int = 32,
                        prefill_chunk: int = 128, prefix_len: int = 256,
                        sessions: int = 16, decode_steps: int = 8,
                        min_iter_reduction: float = 4.0):
    """The BENCH_r15 fused multi-step decode protocol (PR 16, module
    docstring ``--host-loop``): K=1 per-token host loop vs the fused
    ``decode_steps=K`` twin on the BENCH_r09 returning-sessions trace.

    The headline counter pair: in K=1 mode every decode iteration is a
    Python scheduler iteration (``decode_steps`` counts them); the fused
    engine runs the same iterations inside ONE ``lax.while_loop``
    program and touches the host once per K-token window
    (``host_fence_waits``).  Both twins must be token-EXACT (fp32) and
    the kv8 twin pair bit-exact between themselves.  The twins' TOTAL
    batched-iteration counts are recorded but not compared: at K>1
    decode windows overlap prefill chunks differently, so the batching
    schedule — never any request's token stream — may differ."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models import gpt2

    reqs = build_trace(requests, vocab, seed, False,
                       prefix_len=prefix_len, sessions=sessions)
    gen_tokens = sum(r.max_new_tokens for r in reqs)
    max_total = prefix_len + max(TAIL_RANGE) + max(PREFIX_NEW_RANGE)
    cfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=1024,
                          num_layers=layers, num_heads=heads,
                          hidden_size=hidden)
    deepspeed_tpu.comm.reset_topology()
    engine = deepspeed_tpu.init_inference(
        gpt2.build(cfg),
        config={"dtype": dtype, "tensor_parallel": {"tp_size": 1}})

    def lane(K, quantize=None, trace_capacity=16384):
        srv = ServingEngine(engine, slots=slots, max_seq_len=max_total,
                            prefill_batch=prefill_batch,
                            block_size=block_size,
                            prefill_chunk=prefill_chunk,
                            decode_steps=K, quantize=quantize,
                            trace_capacity=trace_capacity)
        t0 = time.perf_counter()
        outs = srv.serve(reqs)
        cold = time.perf_counter() - t0
        st_cold = srv.stats()
        t0 = time.perf_counter()
        outs2 = srv.serve(reqs)
        warm = time.perf_counter() - t0
        st = srv.stats()
        # host-side scheduler decode iterations: one per decode program
        # dispatch at K=1, one per fence at K>1
        host_iters = st_cold["host_fence_waits"] if K > 1 \
            else st_cold["decode_steps"]
        return {
            "decode_steps_knob": K,
            "tok_s": gen_tokens / cold,
            "wall_s": cold,
            "tok_s_warm": gen_tokens / warm,
            "wall_warm_s": warm,
            "compiled_programs": srv.compile_count,
            "device_decode_iterations": st_cold["decode_steps"],
            "fused_iterations": st_cold["fused_iterations"],
            "host_decode_iterations": host_iters,
            "host_iters_per_token": host_iters / max(gen_tokens, 1),
            "generated_tokens": st_cold["generated_tokens"],
            "busy_fractions": srv.flops_report()["busy_fractions"],
            "stats": st_cold,
        }, outs, outs2

    base, base_outs, base_outs2 = lane(1)
    fused, fused_outs, fused_outs2 = lane(decode_steps)
    parity = all(np.array_equal(base_outs[r.uid], fused_outs[r.uid])
                 and np.array_equal(base_outs[r.uid], fused_outs2[r.uid])
                 and np.array_equal(base_outs[r.uid], base_outs2[r.uid])
                 for r in reqs)

    # kv8 twins: quantized greedy differs from fp32 (documented), but the
    # fused program must be BIT-exact against the K=1 kv8 twin — same
    # codes, same scales, same argmax
    kv8_base, kv8_base_outs, _ = lane(1, quantize="kv8")
    kv8_fused, kv8_fused_outs, _ = lane(decode_steps, quantize="kv8")
    kv8_exact = all(np.array_equal(kv8_base_outs[r.uid],
                                   kv8_fused_outs[r.uid]) for r in reqs)

    # telemetry twin: the fused engine with the trace ring off — the
    # BENCH_r08 <=2% contract must survive the new fence counters
    ring_off, off_outs, _ = lane(decode_steps, trace_capacity=0)
    overhead_pct = (fused["wall_warm_s"] / ring_off["wall_warm_s"]
                    - 1.0) * 100.0
    ring_parity = all(np.array_equal(base_outs[r.uid], off_outs[r.uid])
                      for r in reqs)

    iter_reduction = base["host_decode_iterations"] / \
        max(fused["host_decode_iterations"], 1)
    res = {
        "protocol": "fused multi-step on-device decode (PR 16, "
                    "BENCH_r15): K=1 per-token host loop vs one "
                    "lax.while_loop program fusing K decode iterations "
                    "with per-slot eos/budget exits on-device and one "
                    "host fence per window; exact-parity + kv8 "
                    "bit-exact twins on the returning-sessions trace",
        "trace": f"{sessions} sessions x {prefix_len}-token prefixes "
                 f"(round-robin returns), tails {TAIL_RANGE}, new "
                 f"{PREFIX_NEW_RANGE}",
        "requests": requests,
        "generated_tokens": gen_tokens,
        "decode_steps": decode_steps,
        "host_loop_baseline": base,
        "fused": fused,
        "kv8": {"baseline": kv8_base, "fused": kv8_fused,
                "bit_exact_between_twins": kv8_exact},
        "telemetry_twin": {
            "tok_s_warm_ring_off": ring_off["tok_s_warm"],
            "overhead_pct": overhead_pct,
            "within_2pct": overhead_pct <= 2.0,
            "token_parity": ring_parity,
        },
        "host_iteration_reduction": iter_reduction,
        "token_parity": parity,
        "gates": {
            "min_iter_reduction": min_iter_reduction,
            "iter_reduction_ok": iter_reduction >= min_iter_reduction,
            "exact_parity_fp32": parity,
            "kv8_bit_exact": kv8_exact,
            "fused_tok_s_ge_baseline":
                fused["tok_s_warm"] >= base["tok_s_warm"],
        },
    }
    return res


def run_sampling_bench(requests: int = 48, slots: int = 8,
                       prefill_batch: int = 4, layers: int = 2,
                       hidden: int = 128, heads: int = 4,
                       vocab: int = 2048, seed: int = 0,
                       dtype: str = "fp32", block_size: int = 32,
                       prefill_chunk: int = 128, spec_tokens: int = 4,
                       decode_steps: int = 8, temperature: float = 0.25,
                       top_k: int = 20, top_p: float = 0.95,
                       min_spec_speedup: float = 1.3,
                       min_iter_reduction: float = 4.0,
                       max_tv: float = 0.12):
    """The BENCH_r18 on-device sampling protocol (PR 20, module
    docstring ``--sampling``): per-slot temperature/top-k/top-p sampling
    as fixed-shape device operands on the decode-heavy trace, with the
    speculative rejection verifier, fused decode, and constrained-
    decoding compositions — every gate DETERMINISTIC (counter-based PRNG
    streams are pure functions of (request seed, tokens emitted), so
    the same trace replays bit-identically on any engine/fleet shape).

     - **plain_sampled**: the default (``sampling=True``) engine on a
       mixed greedy+sampled trace; a FRESH twin engine must reproduce
       every stream token-exactly, and at least one sampled stream must
       deviate from greedy (no silent argmax collapse).
     - **greedy_row**: the same prompts at temperature=0 through the
       sampling engine vs a ``sampling=False`` twin vs sequential
       ``generate`` — bit parity (greedy is the temp-0 ROW of the same
       program, not a separate program).
     - **fused**: ``decode_steps=K`` on the sampled trace — token-EXACT
       vs the K=1 engine (``grid_keys`` == per-step ``slot_keys``), host
       iterations per token down >= ``min_iter_reduction``.
     - **speculative**: ``spec_tokens=K`` n-gram with the rejection
       verifier — deterministic twin parity, 2 compiled programs, and
       the throughput headline gated on the DETERMINISTIC counter ratio
       tokens-per-host-decode-iteration >= ``min_spec_speedup`` x the
       plain sampled engine (CPU-sim wall tok/s is recorded, not gated).
     - **statistical parity**: aggregate sampled-token histogram TV
       between the spec and plain lanes must stay within the
       self-calibrated null band — 1.5x the TV between two plain lanes
       differing only in request seeds (+0.02), floored at ``max_tv``.
       Rejection sampling is distribution-exact for any proposer, so
       the spec lane must look statistically identical to plain
       sampling even though the streams differ draw-for-draw.
     - **draft**: a 1-layer draft model on the same trace — exactly 3
       programs (draft/prefill/verify) and twin determinism (draft
       params are seeded, rejection needs no draft probabilities).
     - **constrained / mixed**: a ``logit_masks=True`` engine serving
       greedy + sampled + JSON-constrained requests in ONE trace —
       still 2 programs, sentry strict, every constrained completion
       parses as valid JSON; repeated on a speculative engine.
    """
    import deepspeed_tpu
    from deepspeed_tpu.inference.constrain import (JsonMaskBuilder,
                                                   ascii_token_strings)
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models import gpt2

    def sampled_trace(trace_seed, greedy_every=4):
        """The decode-heavy trace with per-request sampling params:
        every ``greedy_every``-th request stays greedy (temp 0), the
        rest alternate temperature T / 2T with per-request seeds —
        prompts identical across ``trace_seed`` so reseeded twins
        differ ONLY in the sampling streams."""
        base_reqs = build_trace(requests, vocab, seed, False,
                                decode_heavy=True)
        rng = np.random.default_rng([trace_seed, 7919])
        out = []
        for r in base_reqs:
            if greedy_every and r.uid % greedy_every == greedy_every - 1:
                out.append(Request(uid=r.uid, prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens))
                continue
            t = temperature * (2.0 if r.uid % greedy_every == 1 else 1.0)
            out.append(Request(uid=r.uid, prompt=r.prompt,
                               max_new_tokens=r.max_new_tokens,
                               temperature=t, top_k=top_k, top_p=top_p,
                               seed=int(rng.integers(1, 2 ** 31 - 1))))
        return out

    reqs = sampled_trace(seed)
    reseeded = sampled_trace(seed + 1)
    greedy_reqs = [Request(uid=r.uid, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens) for r in reqs]
    budget_tokens = sum(r.max_new_tokens for r in reqs)
    max_total = DECODE_HEAVY_PROMPT_RANGE[1] + DECODE_HEAVY_NEW_RANGE[1]
    cfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=1024,
                          num_layers=layers, num_heads=heads,
                          hidden_size=hidden)
    deepspeed_tpu.comm.reset_topology()
    engine = deepspeed_tpu.init_inference(
        gpt2.build(cfg),
        config={"dtype": dtype, "tensor_parallel": {"tp_size": 1}})

    def mk(**extra):
        kw = dict(slots=slots, max_seq_len=max_total,
                  prefill_batch=prefill_batch, block_size=block_size,
                  prefill_chunk=prefill_chunk)
        kw.update(extra)
        return ServingEngine(engine, **kw)

    def run_lane(srv, trace, eos=None):
        t0 = time.perf_counter()
        outs = srv.serve(trace, eos_token_id=eos)
        wall = time.perf_counter() - t0
        st = srv.stats()
        gen = st["generated_tokens"]
        # host scheduler decode work: one dispatch per decode program
        # (plain), per verify round (spec), per K-token fence (fused)
        if st["config"]["decode_steps"] > 1:
            host_iters = st["host_fence_waits"]
        else:
            host_iters = st["decode_steps"] + st["spec_rounds"]
        return {
            "tok_s": gen / wall,
            "wall_s": wall,
            "generated_tokens": gen,
            "compiled_programs": srv.compile_count,
            "program_names": sorted(p[0] for p in srv.compiled_programs),
            "host_decode_iterations": host_iters,
            "tokens_per_host_iteration": gen / max(host_iters, 1),
            "sampled_requests": st["sampled_requests"],
            "retraces": st["retraces_observed"],
            "acceptance_rate": st["acceptance_rate"],
            "spec_draft_rejected": st["spec_draft_rejected"],
        }, outs

    def exact(a, b, trace):
        return all(np.array_equal(a[r.uid], b[r.uid]) for r in trace)

    # ------------------------------------------- plain sampled + twin
    plain, plain_outs = run_lane(mk(), reqs)
    _, twin_outs = run_lane(mk(), reqs)
    determinism = exact(plain_outs, twin_outs, reqs)

    # --------------------------------------------------- greedy row
    greedy_on, greedy_on_outs = run_lane(mk(), greedy_reqs)
    greedy_off, greedy_off_outs = run_lane(mk(sampling=False),
                                           greedy_reqs)
    greedy_parity = exact(greedy_on_outs, greedy_off_outs, greedy_reqs)
    seq_subset = all(
        np.array_equal(greedy_on_outs[r.uid],
                       engine.generate(r.prompt[None, :],
                                       max_new_tokens=r.max_new_tokens)[0])
        for r in greedy_reqs[:6])
    deviates = any(not np.array_equal(plain_outs[r.uid],
                                      greedy_on_outs[r.uid])
                   for r in reqs if r.sampled)

    # -------------------------------------------------------- fused
    fused, fused_outs = run_lane(mk(decode_steps=decode_steps), reqs)
    fused_exact = exact(plain_outs, fused_outs, reqs)
    iter_reduction = plain["host_decode_iterations"] / \
        max(fused["host_decode_iterations"], 1)

    # -------------------------------------------------- speculative
    spec, spec_outs = run_lane(mk(spec_tokens=spec_tokens), reqs)
    _, spec_twin_outs = run_lane(mk(spec_tokens=spec_tokens), reqs)
    spec_det = exact(spec_outs, spec_twin_outs, reqs)
    spec_speedup = spec["tokens_per_host_iteration"] / \
        plain["tokens_per_host_iteration"]

    # --------------------------------------------- statistical parity
    _, reseed_outs = run_lane(mk(), reseeded)

    def tail_hist(outs, trace):
        h = np.zeros(vocab, np.float64)
        for r in trace:
            if not r.sampled:
                continue
            h += np.bincount(np.asarray(outs[r.uid])[len(r.prompt):],
                             minlength=vocab)
        return h / max(h.sum(), 1.0)

    def tv(a, b):
        return 0.5 * float(np.abs(a - b).sum())

    h_plain = tail_hist(plain_outs, reqs)
    tv_null = tv(h_plain, tail_hist(reseed_outs, reseeded))
    tv_spec = tv(h_plain, tail_hist(spec_outs, reqs))
    tv_threshold = max(max_tv, 1.5 * tv_null + 0.02)
    stat_parity = tv_spec <= tv_threshold

    # -------------------------------------------------------- draft
    dcfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=1024,
                           num_layers=1, num_heads=heads,
                           hidden_size=max(hidden // 2, heads * 8))
    draft, draft_outs = run_lane(
        mk(spec_tokens=spec_tokens, draft=gpt2.build(dcfg)), reqs)
    _, draft_twin_outs = run_lane(
        mk(spec_tokens=spec_tokens, draft=gpt2.build(dcfg)), reqs)
    draft_det = exact(draft_outs, draft_twin_outs, reqs)

    # -------------------------------------------- constrained / mixed
    toks = ascii_token_strings(vocab)

    def constrained_reqs(cseed, n=4, max_new=24):
        rng = np.random.default_rng([cseed, 911])
        return [Request(uid=1000 + i,
                        prompt=rng.integers(0, vocab, 12),
                        max_new_tokens=max_new,
                        temperature=0.7, top_k=0, top_p=1.0,
                        seed=int(rng.integers(1, 2 ** 31 - 1)),
                        mask_builder=JsonMaskBuilder(toks,
                                                     eos_token_id=0))
                for i in range(n)]

    def json_valid(outs, trace):
        for r in trace:
            gen = [int(t) for t in np.asarray(outs[r.uid])[len(r.prompt):]]
            if 0 in gen:
                gen = gen[: gen.index(0)]
            try:
                json.loads("".join(toks[t] for t in gen))
            except (ValueError, IndexError):
                return False
        return True

    mixed_trace = reqs[: min(len(reqs), 12)]
    mixed_srv = mk(logit_masks=True)
    cons_a = constrained_reqs(seed)
    mixed_outs = mixed_srv.serve(mixed_trace + cons_a, eos_token_id=0)
    mixed_json_ok = json_valid(mixed_outs, cons_a)
    spec_mixed_srv = mk(spec_tokens=spec_tokens, logit_masks=True)
    cons_b = constrained_reqs(seed + 1)
    spec_mixed_outs = spec_mixed_srv.serve(mixed_trace + cons_b,
                                           eos_token_id=0)
    spec_mixed_json_ok = json_valid(spec_mixed_outs, cons_b)

    return {
        "protocol": "on-device sampling stack (PR 20, BENCH_r18): "
                    "per-slot temperature/top-k/top-p as fixed-shape "
                    "device operands + distribution-exact rejection "
                    "speculative sampling + fused-decode and "
                    "constrained-JSON composition on the decode-heavy "
                    "trace — every gate deterministic (counter-based "
                    "PRNG), zero recompiles across greedy/sampled/"
                    "constrained mixes",
        "trace": f"{requests} decode-heavy requests, prompts "
                 f"{DECODE_HEAVY_PROMPT_RANGE}, new "
                 f"{DECODE_HEAVY_NEW_RANGE}; temps "
                 f"({temperature}, {2 * temperature}, greedy every 4th), "
                 f"top_k={top_k}, top_p={top_p}, per-request seeds",
        "requests": requests,
        "generated_tokens_budget": budget_tokens,
        "plain_sampled": plain,
        "greedy_row": {"on": greedy_on, "off": greedy_off},
        "fused": fused,
        "host_iteration_reduction": iter_reduction,
        "speculative": spec,
        "speedup_spec_tokens_per_host_iter": spec_speedup,
        "draft": draft,
        "statistical_parity": {
            "tv_spec_vs_plain": tv_spec,
            "tv_null_reseeded_plain": tv_null,
            "tv_threshold": tv_threshold,
            "max_tv_floor": max_tv,
        },
        "constrained": {
            "requests": len(cons_a) + len(cons_b),
            "mixed_programs": mixed_srv.compile_count,
            "spec_mixed_programs": spec_mixed_srv.compile_count,
            "mixed_retraces": mixed_srv.sentry.retraces_observed,
            "spec_mixed_retraces":
                spec_mixed_srv.sentry.retraces_observed,
        },
        "gates": {
            "sampled_determinism_exact": determinism,
            "sampled_streams_deviate_from_greedy": deviates,
            "greedy_row_bit_parity": greedy_parity and seq_subset,
            "fused_token_exact_vs_plain": fused_exact,
            "min_iter_reduction": min_iter_reduction,
            "fused_iter_reduction_ok":
                iter_reduction >= min_iter_reduction,
            "spec_determinism_exact": spec_det,
            "draft_determinism_exact": draft_det,
            "min_spec_speedup": min_spec_speedup,
            "spec_host_iter_speedup_ok":
                spec_speedup >= min_spec_speedup,
            "statistical_parity_ok": stat_parity,
            "constrained_json_valid":
                mixed_json_ok and spec_mixed_json_ok,
            "mixed_compile_budget_ok":
                mixed_srv.compile_count == 2
                and spec_mixed_srv.compile_count == 2
                and mixed_srv.sentry.retraces_observed == 0
                and spec_mixed_srv.sentry.retraces_observed == 0,
            "compile_budgets_ok":
                plain["compiled_programs"] == 2
                and fused["compiled_programs"] == 2
                and spec["compiled_programs"] == 2
                and draft["compiled_programs"] == 3,
            "zero_retraces_ok": all(
                lane["retraces"] == 0
                for lane in (plain, greedy_on, greedy_off, fused,
                             spec, draft)),
        },
        "model": f"gpt2-{layers}l-{hidden}d-{vocab}v ({dtype})",
        "backend": __import__("jax").default_backend(),
    }


def run_long_context_bench(requests: int = 3, slots: int = 2,
                           prefill_batch: int = 2, layers: int = 2,
                           hidden: int = 128, heads: int = 4,
                           vocab: int = 2048, seed: int = 0,
                           dtype: str = "fp32", block_size: int = 32,
                           prefill_chunk: int = 128,
                           long_prompt_len: int = 4096,
                           max_new: int = 16, sp_degree: int = 4,
                           window_blocks: int = 16):
    """The BENCH_r17 long-context protocol (PR 19, module docstring
    ``--long-context``): sequence-parallel (Ulysses) prefill + the
    resident-window decode lane on giant single-session prompts.

    Lanes and gates:
     - **sp**: the sp=1 chunked engine vs the ``sp=N`` twin on the
       same long-prompt trace — exact token parity and the unchanged
       compile budget are exit-fatal; the prefill wall-clock speedup
       is recorded and warned only (CPU-sim shard_map emulates the
       all-to-all on one host, so linear scaling is a hardware claim,
       not a CI claim).
     - **window**: a ``resident_window_blocks=W`` engine whose device
       pool holds < 25% of the served context (landmark + window + one
       chunk span per slot) serves the same prompts through the host
       tier — window slides observed, device-residency fraction under
       a quarter, full token budgets produced, host tier actually
       holding cold context, and the unamended compile budget are all
       exit-fatal.  Windowed attention is approximate by design, so
       there is no parity gate on this lane — instead the
       **full-window identity** sub-lane pins bit-equality against the
       plain engine when the window covers the whole (short) context.
     - **probe_128k**: a windowed engine *declared* at a 131072-token
       ``max_seq_len`` (the 100k+ regime: 4096-entry block tables,
       device pool still ~20 blocks) serves a short prompt to prove
       the compiled-program budget is reachable and held at 128k
       scale."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models import gpt2
    import jax

    if sp_degree > 1 and len(jax.devices()) < sp_degree:
        sys.exit(f"--long-context needs >= {sp_degree} devices for the "
                 "sp lane; on CPU set XLA_FLAGS="
                 "--xla_force_host_platform_device_count=8")

    rng = np.random.default_rng(seed)
    long_reqs = [Request(uid=i,
                         prompt=rng.integers(0, vocab, long_prompt_len),
                         max_new_tokens=max_new)
                 for i in range(requests)]
    gen_tokens = requests * max_new
    max_total = long_prompt_len + max_new

    def fresh(reqs):
        return [Request(uid=r.uid, prompt=r.prompt,
                        max_new_tokens=r.max_new_tokens) for r in reqs]

    def mk_cfg(seq):
        return gpt2.GPT2Config(vocab_size=vocab, max_seq_len=seq,
                               num_layers=layers, num_heads=heads,
                               hidden_size=hidden)

    def lane_stats(srv, wall):
        st = srv.stats()
        return {
            "wall_s": wall,
            "tok_s": gen_tokens / wall,
            "compiled_programs": srv.compile_count,
            "compile_budget": srv.compile_budget,
            "sp": st["sp"],
            "sp_alltoall_bytes": st["sp_alltoall_bytes"],
            "context_window_slides": st["context_window_slides"],
            "host_blocks_in_use": st["host_blocks_in_use"],
            "swap_out": st["swap_out"],
            "config": srv.resolved_config(),
        }

    # ------------------------------------------------------- sp lane
    def sp_lane(sp):
        deepspeed_tpu.comm.reset_topology()
        srv = deepspeed_tpu.init_serving(
            gpt2.build(mk_cfg(max_total)), config={"dtype": dtype},
            sp=sp, slots=slots, max_seq_len=max_total,
            block_size=block_size, prefill_chunk=prefill_chunk,
            prefill_batch=prefill_batch)
        t0 = time.perf_counter()
        outs = srv.serve(fresh(long_reqs))
        return lane_stats(srv, time.perf_counter() - t0), outs

    sp1, sp1_outs = sp_lane(1)
    spN, spN_outs = sp_lane(sp_degree)
    sp_parity = all(np.array_equal(sp1_outs[r.uid], spN_outs[r.uid])
                    for r in long_reqs)
    sp_speedup = sp1["wall_s"] / max(spN["wall_s"], 1e-9)

    # --------------------------------------------------- window lane
    # device pool per slot: 1 landmark + W window + one chunk span —
    # sized to hold every slot's window at once, nothing more
    chunk_blocks = -(-prefill_chunk // block_size)
    per_slot = 1 + window_blocks + chunk_blocks
    num_blocks = slots * per_slot + 2
    host_blocks = slots * (-(-max_total // block_size)) + 16
    declared = 4 * max_total      # window pool is context-independent
    deepspeed_tpu.comm.reset_topology()
    win = deepspeed_tpu.init_serving(
        gpt2.build(mk_cfg(declared)), config={"dtype": dtype},
        slots=slots, max_seq_len=declared, block_size=block_size,
        prefill_chunk=prefill_chunk, prefill_batch=prefill_batch,
        num_blocks=num_blocks, host_blocks=host_blocks, swap_batch=8,
        resident_window_blocks=window_blocks, debug_checks=True)
    t0 = time.perf_counter()
    win_outs = win.serve(fresh(long_reqs))
    win_stats = lane_stats(win, time.perf_counter() - t0)
    residency_frac = per_slot * block_size / long_prompt_len
    tokens_complete = all(
        len(win_outs[r.uid]) == len(r.prompt) + max_new
        for r in long_reqs)

    # full-window identity: short context entirely inside the window
    short_len = 8 * block_size
    short_reqs = [Request(uid=i,
                          prompt=rng.integers(0, vocab, short_len),
                          max_new_tokens=max_new)
                  for i in range(requests)]
    short_total = short_len + max_new
    deepspeed_tpu.comm.reset_topology()
    plain = deepspeed_tpu.init_serving(
        gpt2.build(mk_cfg(short_total)), config={"dtype": dtype},
        slots=slots, max_seq_len=short_total, block_size=block_size,
        prefill_chunk=prefill_chunk, prefill_batch=prefill_batch)
    plain_outs = plain.serve(fresh(short_reqs))
    cover = -(-short_total // block_size) + chunk_blocks + 1
    deepspeed_tpu.comm.reset_topology()
    full_win = deepspeed_tpu.init_serving(
        gpt2.build(mk_cfg(short_total)), config={"dtype": dtype},
        slots=slots, max_seq_len=short_total, block_size=block_size,
        prefill_chunk=prefill_chunk, prefill_batch=prefill_batch,
        host_blocks=host_blocks, swap_batch=8,
        resident_window_blocks=cover, debug_checks=True)
    full_win_outs = full_win.serve(fresh(short_reqs))
    full_window_identical = all(
        np.array_equal(plain_outs[r.uid], full_win_outs[r.uid])
        for r in short_reqs)

    # ------------------------------------------------ 128k declared
    deepspeed_tpu.comm.reset_topology()
    probe = deepspeed_tpu.init_serving(
        gpt2.build(mk_cfg(131072)), config={"dtype": dtype}, slots=1,
        max_seq_len=131072, block_size=block_size,
        prefill_chunk=prefill_chunk, prefill_batch=1,
        num_blocks=per_slot + 2, host_blocks=64, swap_batch=8,
        resident_window_blocks=window_blocks, debug_checks=True)
    probe_reqs = [Request(uid=0,
                          prompt=rng.integers(0, vocab, 4 * block_size),
                          max_new_tokens=4)]
    probe.serve(probe_reqs)
    probe_stats = {
        "declared_max_seq_len": 131072,
        "block_table_entries": -(-131072 // block_size),
        "device_pool_blocks": per_slot + 2,
        "compiled_programs": probe.compile_count,
        "compile_budget": probe.compile_budget,
    }

    res = {
        "protocol": "long-context serving lane (PR 19, BENCH_r17): "
                    "Ulysses sp prefill parity + compile invariance "
                    "vs sp=1, resident-window decode with the device "
                    "pool under 25% of the served context (slides, "
                    "host-tier demotion, full-window bit-identity), "
                    "and the 128k-declared compile-budget probe",
        "trace": f"{requests} x {long_prompt_len}-token prompts, "
                 f"max_new={max_new}",
        "requests": requests,
        "generated_tokens": gen_tokens,
        "sp_degree": sp_degree,
        "sp1": sp1,
        "spN": spN,
        "sp_speedup": sp_speedup,
        "window": {**win_stats,
                   "window_blocks": window_blocks,
                   "device_residency_frac": residency_frac,
                   "declared_max_seq_len": declared},
        "probe_128k": probe_stats,
        "gates": {
            "sp_exact_parity": sp_parity,
            "sp_compile_budget_ok":
                spN["compiled_programs"] <= spN["compile_budget"]
                and spN["compile_budget"] == sp1["compile_budget"],
            "window_slides_ok":
                win_stats["context_window_slides"] > 0,
            "residency_under_quarter_ok": residency_frac < 0.25,
            "window_tokens_complete_ok": tokens_complete,
            "cold_context_on_host_ok":
                win_stats["host_blocks_in_use"] > 0
                or win_stats["swap_out"] > 0,
            "window_compile_budget_ok":
                win_stats["compiled_programs"]
                <= win_stats["compile_budget"],
            "full_window_identical": full_window_identical,
            "probe_128k_compile_budget_ok":
                probe_stats["compiled_programs"]
                <= probe_stats["compile_budget"],
        },
    }
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prefill-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=32)
    ap.add_argument("--prefill-chunk", type=int, default=128)
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="prepend a shared N-token system prompt to every "
                         "request (prefix-heavy trace); 0 disables, "
                         "default per lane")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="fp32")
    ap.add_argument("--grid", action="store_true",
                    help="snap the trace to a small shape grid and report a "
                         "compile-warm second pass for both paths")
    ap.add_argument("--decode-heavy", action="store_true",
                    help="short prompts, long completions — the decode-bound "
                         "trace speculative decoding targets")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="add a speculative lane: n-gram proposer drafting "
                         "K tokens per slot per iteration (0 = off)")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="add a tensor-parallel lane: weights + paged KV "
                         "pool sharded over an N-way tp mesh axis (needs "
                         ">= N devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--quantize", default=None, metavar="MODES",
                    help="comma list of quantized lanes to add: kv8, w8a8, "
                         "w8a8+kv8 (bounded divergence, not exact parity)")
    ap.add_argument("--sessions", type=int, default=None, metavar="S",
                    help="with --prefix-len: S distinct session prefixes "
                         "dealt round-robin (multi-turn returning-session "
                         "traffic — the tiered-KV scenario)")
    ap.add_argument("--pool-frac", type=float, default=None, metavar="F",
                    help="add the tiered-KV lane (BENCH_r09): size the "
                         "device pool at fraction F of the trace working "
                         "set and compare the host-DRAM tier against the "
                         "evict/preempt baseline (zero parity loss "
                         "asserted for both)")
    ap.add_argument("--swap-batch", type=int, default=8,
                    help="blocks per tiered-KV swap round trip")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="run the multi-replica router protocol "
                         "(BENCH_r10) instead of the single-engine "
                         "lanes: busy-time scaling over 1->2->4 "
                         "replicas (capped at N), affinity vs "
                         "round-robin, drained-replica KV-pull "
                         "migration")
    ap.add_argument("--slo", action="store_true",
                    help="with --replicas N: run the fleet observability "
                         "protocol (BENCH_r12) instead — SLO-classed "
                         "traffic, live /metrics scrape of the federated "
                         "fleet registry, merged distributed trace with "
                         "flow events, FLOPs/MFU profiler, and the "
                         "fleet-wide ≤2%% telemetry overhead twin")
    ap.add_argument("--peak-flops", type=float, default=1e12,
                    help="nominal MFU denominator for the --slo lane's "
                         "FLOPs report (CPU-sim: gauge mechanics, not a "
                         "hardware claim)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the BENCH_r14 fault-tolerance protocol "
                         "(PR 15): seeded crash-at-iteration, flaky "
                         "transport, host-tier corruption, and overload-"
                         "shedding lanes on the returning-sessions "
                         "trace — recovery latency, rehomed/shed "
                         "counts, 100%% checksum detection, and parity "
                         "vs the fault-free twin (add --quantize kv8 "
                         "for the kv8 crash lane)")
    ap.add_argument("--overload", type=int, default=4,
                    help="overload factor for the --chaos shed lane "
                         "(batch traffic = (N-1) x protected)")
    ap.add_argument("--disaggregated", action="store_true",
                    help="run the BENCH_r16 disaggregated-serving "
                         "protocol (ISSUE 17): prefill/decode worker "
                         "split vs the colocated twin (structure + "
                         "threaded interference lanes, victim TPOT "
                         "flatness under a long-prompt burst), the "
                         "NVMe third KV tier over a tmpdir spill file "
                         "(spill/resume/parity/checksum gates), and "
                         "the role='both' + nvme_blocks=0 bit-identity "
                         "lane")
    ap.add_argument("--burst-prompts", type=int, default=6,
                    help="long-prompt admissions fired mid-decode in "
                         "the --disaggregated interference lane")
    ap.add_argument("--burst-prompt-len", type=int, default=576,
                    help="prompt length of each burst admission")
    ap.add_argument("--long-context", action="store_true",
                    help="run the BENCH_r17 long-context protocol "
                         "(PR 19): Ulysses sequence-parallel prefill "
                         "parity + compile invariance vs sp=1, the "
                         "resident-window decode lane with the device "
                         "pool under 25%% of the served context "
                         "(slides + host-tier demotion exit-fatal, "
                         "full-window bit-identity), and the "
                         "128k-declared compile-budget probe (needs "
                         ">= --sp-degree devices; on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8)")
    ap.add_argument("--long-prompt-len", type=int, default=4096,
                    help="prompt length for the --long-context lanes")
    ap.add_argument("--sp-degree", type=int, default=4, metavar="N",
                    help="sequence-parallel degree for the "
                         "--long-context sp lane")
    ap.add_argument("--window-blocks", type=int, default=16,
                    metavar="W",
                    help="resident_window_blocks for the "
                         "--long-context window lane")
    ap.add_argument("--autotune", action="store_true",
                    help="run the closed-loop autotuner protocol "
                         "(BENCH_r13) instead of the single-engine "
                         "lanes: successive-halving search over the "
                         "serving knob space on the returning-sessions "
                         "trace, gated on winner >= "
                         "--autotune-min-speedup x the default")
    ap.add_argument("--autotune-trials", type=int, default=None,
                    metavar="N", help="bound on executed trials")
    ap.add_argument("--autotune-min-budget", type=int, default=None,
                    metavar="B", help="rung-0 replay length "
                                      "(default: requests/4)")
    ap.add_argument("--autotune-min-speedup", type=float, default=1.0,
                    metavar="F",
                    help="fail unless measured winner >= F x measured "
                         "default (the committed BENCH_r13 runs at 1.15)")
    ap.add_argument("--autotune-results-dir",
                    default="autotuning_results_serving")
    ap.add_argument("--autotune-resume", action="store_true",
                    help="replay completed trials from exps.json")
    ap.add_argument("--host-loop", action="store_true",
                    help="run the BENCH_r15 fused multi-step decode "
                         "protocol (PR 16): K=1 per-token host loop vs "
                         "the fused decode_steps=K on-device while_loop "
                         "twin on the returning-sessions trace — exact "
                         "fp32 parity, kv8 bit-exact twins, host "
                         "iterations per token down >= the floor")
    ap.add_argument("--decode-steps", type=int, default=8, metavar="K",
                    help="fused window width for the --host-loop lane")
    ap.add_argument("--host-loop-min-reduction", type=float, default=4.0,
                    metavar="F",
                    help="fail the --host-loop lane unless host "
                         "scheduler iterations per generated token drop "
                         "by >= F vs the K=1 baseline")
    ap.add_argument("--sampling", action="store_true",
                    help="run the BENCH_r18 on-device sampling "
                         "protocol (PR 20): per-slot temperature/"
                         "top-k/top-p as fixed-shape device operands "
                         "on the decode-heavy trace — fresh-twin "
                         "determinism, temp-0 bit parity vs greedy, "
                         "fused decode_steps=K token-exact "
                         "composition, spec rejection sampling gated "
                         "on the deterministic tokens-per-host-"
                         "iteration ratio + statistical parity (TV), "
                         "and the mixed greedy/sampled/constrained-"
                         "JSON 2-program zero-recompile gate "
                         "(uses --speculative K and --decode-steps)")
    ap.add_argument("--temperature", type=float, default=0.25,
                    metavar="T",
                    help="headline temperature for the --sampling "
                         "lanes (sampled rows alternate T and 2T)")
    ap.add_argument("--sampling-min-spec-speedup", type=float,
                    default=1.3, metavar="F",
                    help="fail the --sampling lane unless the spec "
                         "engine's tokens per host decode iteration "
                         ">= F x the plain sampled engine's")
    ap.add_argument("--sampling-max-tv", type=float, default=0.12,
                    metavar="TV",
                    help="statistical-parity floor for the --sampling "
                         "lane: spec-vs-plain token-histogram total "
                         "variation must stay within max(TV, 1.5 x "
                         "the reseeded-plain null TV + 0.02)")
    ap.add_argument("--quant-suite", action="store_true",
                    help="run the BENCH_r07 protocol: mixed + prefix-heavy "
                         "+ decode-heavy traces with quantized lanes and a "
                         "tp=4 x kv8 combo point, merged into one JSON")
    ap.add_argument("--telemetry-bench", action="store_true",
                    help="add the telemetry overhead lane (BENCH_r08): "
                         "trace-ring-off vs fully-enabled twin engines, "
                         "interleaved best-of-3 warm passes, ≤2%% contract "
                         "(recorded; breach warns) + Chrome trace schema "
                         "validation")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the telemetry lane's Chrome trace_event "
                         "JSON here (open at https://ui.perfetto.dev; "
                         "needs --telemetry-bench)")
    ap.add_argument("--emit-metrics", default=None, metavar="PATH",
                    help="dump the serving engine's Prometheus text "
                         "exposition to PATH and the JSON registry "
                         "snapshot to PATH.json alongside the bench JSON")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    if args.slo and args.replicas < 2:
        ap.error("--slo is the fleet observability lane: it needs "
                 "--replicas N with N >= 2")

    quantize = tuple(m for m in (args.quantize or "").split(",") if m)

    def _default(v, lane_default):
        # argparse default is None so an EXPLICIT 0 stays 0 (sessionless
        # / unpressured modes are reachable in every lane)
        return lane_default if v is None else v

    kw = dict(requests=args.requests, slots=args.slots,
              prefill_batch=args.prefill_batch, layers=args.layers,
              hidden=args.hidden, heads=args.heads, vocab=args.vocab,
              seed=args.seed, dtype=args.dtype, block_size=args.block_size,
              prefill_chunk=args.prefill_chunk)
    fail_msg = "serving outputs diverged from sequential generate"
    if args.replicas > 1 and args.slo:
        res = run_fleet_observability_bench(
            replicas=args.replicas, requests=args.requests,
            slots=args.slots, prefill_batch=args.prefill_batch,
            layers=args.layers, hidden=args.hidden, heads=args.heads,
            vocab=args.vocab, seed=args.seed, dtype=args.dtype,
            block_size=args.block_size, prefill_chunk=args.prefill_chunk,
            prefix_len=_default(args.prefix_len, 192),
            sessions=_default(args.sessions, 9),
            swap_batch=args.swap_batch,
            peak_flops=args.peak_flops, emit_metrics=args.emit_metrics,
            trace_out=args.trace_out)
        ok = res["token_parity"] and res["compile_budgets_ok"] and \
            res["federation"]["scrape_agrees_with_snapshot"] and \
            res["federation"]["live_scrapes_during_step_loop"] > 0 and \
            res["flops"]["agreement_within_10pct"] and \
            res["merged_trace"]["kv_pull_crosses_replica_lanes"] and \
            res["merged_trace"]["route_flow_ends"] > 0
        if not res["overhead"]["within_2pct"]:
            print("WARNING: fleet telemetry overhead "
                  f"{res['overhead']['overhead_pct']:.2f}% exceeds the "
                  "2% contract on this run (noise-prone on shared "
                  "boxes; see within_2pct in the JSON)", file=sys.stderr)
    elif args.replicas > 1:
        res = run_replica_bench(
            replicas=args.replicas, requests=args.requests,
            slots=args.slots, prefill_batch=args.prefill_batch,
            layers=args.layers, hidden=args.hidden, heads=args.heads,
            vocab=args.vocab, seed=args.seed, dtype=args.dtype,
            block_size=args.block_size, prefill_chunk=args.prefill_chunk,
            prefix_len=_default(args.prefix_len, 192),
            sessions=_default(args.sessions, 9),
            swap_batch=args.swap_batch,
            emit_metrics=args.emit_metrics)
        ok = res["token_parity"] and \
            all(s["compile_budgets_ok"] for s in res["scaling"].values())
    elif args.chaos:
        res = run_chaos_bench(
            requests=args.requests, slots=args.slots,
            prefill_batch=args.prefill_batch, layers=args.layers,
            hidden=args.hidden, heads=args.heads, vocab=args.vocab,
            seed=args.seed, dtype=args.dtype, block_size=args.block_size,
            prefill_chunk=args.prefill_chunk,
            prefix_len=_default(args.prefix_len, 192),
            sessions=_default(args.sessions, 16),
            swap_batch=args.swap_batch, overload=args.overload,
            quantize=quantize)
        ok = res["token_parity"] and \
            res["crash"]["hung_handles"] == 0 and \
            res["crash"]["unfinished"] == 0 and \
            res["crash"]["requests_rehomed"] >= 1 and \
            res["crash"]["compile_budgets_ok"] and \
            res["crash_sampled"]["parity_exact_vs_faultfree"] and \
            res["crash_sampled"]["requests_rehomed"] >= 1 and \
            res["crash_sampled"]["hung_handles"] == 0 and \
            res["crash_sampled"]["compile_budgets_ok"] and \
            res["flaky_transport"]["pulls_landed_through_retries"] and \
            res["corruption"]["detected_100pct"] and \
            res["corruption"]["recovered_via_recompute_parity"] and \
            res["overload_shed"]["batch_absorbed_all_rejections"] and \
            res["overload_shed"]["protected_shed"] == 0 and \
            res["incident"]["bundle_audit_ok"] and \
            res["incident"]["replay_reproduced"] and \
            res["incident"]["recorder_token_identity"] and \
            res["incident"]["watchdog_stalls_detected"] >= 1 and \
            res["incident"]["watchdog_stall_has_thread_stacks"] and \
            res["incident"]["watchdog_parked_served_out"]
        fail_msg = "chaos recovery gate failed (see JSON lanes)"
        if not res["overload_shed"]["protected_within_1p5x"]:
            # wall-clock contract: recorded and warned, not exit-fatal —
            # CPU-sim TTFT on a shared box is noise-prone (the committed
            # BENCH_r14.json pins a passing measurement)
            print("WARNING: protected TTFT p95 ratio "
                  f"{res['overload_shed']['protected_p95_ratio']} "
                  "exceeds the 1.5x shed contract on this run "
                  "(see overload_shed in the JSON)", file=sys.stderr)
        if not res["incident"]["recorder_overhead_within_2pct"]:
            # same convention: the <=2% flight-recorder overhead is a
            # wall-clock contract — recorded + warned, never exit-fatal
            print("WARNING: incident recorder overhead "
                  f"{res['incident']['recorder_overhead_frac']:+.2%} "
                  "exceeds the 2% contract on this run "
                  "(see incident in the JSON)", file=sys.stderr)
    elif args.disaggregated:
        res = run_disaggregated_bench(
            requests=args.requests, slots=args.slots,
            prefill_batch=args.prefill_batch, layers=args.layers,
            hidden=args.hidden, heads=args.heads, vocab=args.vocab,
            seed=args.seed, dtype=args.dtype,
            block_size=args.block_size,
            prefill_chunk=args.prefill_chunk,
            prefix_len=_default(args.prefix_len, 192),
            sessions=_default(args.sessions, 12),
            swap_batch=args.swap_batch,
            burst_prompts=args.burst_prompts,
            burst_prompt_len=args.burst_prompt_len)
        ok = res["token_parity"] and \
            res["structure"]["every_admission_handed_off"] and \
            res["structure"]["decode_recompute_bounded"] and \
            res["structure"]["decode_rode_the_pulled_chain"] and \
            res["structure"]["handoff_events_on_timeline"] and \
            res["interference"]["decode_isolated_from_prefill"] and \
            res["nvme"]["spilled_under_pressure"] and \
            res["nvme"]["resumed_from_nvme"] and \
            res["nvme"]["zero_prefix_recompute"] and \
            res["nvme"]["checksum_rejects"] == 0 and \
            res["nvme"]["tier_labeled_metrics"] and \
            res["nvme"]["timeline_events"] and \
            res["bit_identity"]["tokens_identical"] and \
            res["bit_identity"]["swap_counters_identical"] and \
            res["bit_identity"]["schedule_identical"] and \
            res["bit_identity"]["compile_budget_identical"] and \
            res["bit_identity"]["nvme_stats_zero"]
        fail_msg = "disaggregated gate failed (see structure/nvme/" \
                   "bit_identity in the JSON)"
        inter = res["interference"]
        if not inter["tpot_flat_within_1p15"]:
            # wall-clock contract: recorded + warned, not exit-fatal —
            # CPU-sim TPOT on a shared box is noise-prone (the
            # committed BENCH_r16.json pins a passing measurement)
            print("WARNING: disaggregated victim TPOT burst/quiet "
                  f"ratio {inter['disaggregated']['tpot_burst_over_quiet']} "
                  "exceeds the 1.15x flatness contract on this run "
                  "(see interference in the JSON)", file=sys.stderr)
        if not inter["ttft_no_worse_1p1"]:
            print("WARNING: disaggregated burst TTFT p95 "
                  f"{inter['disaggregated']['burst_ttft_p95_s']} vs "
                  f"colocated {inter['colocated']['burst_ttft_p95_s']} "
                  "exceeds the 1.1x contract on this run",
                  file=sys.stderr)
    elif args.long_context:
        # this lane's trace is a few GIANT prompts, not a wide mixed
        # batch — the shared --requests/--slots defaults (64/8) would
        # make it a multi-hour run, so the lane keeps its own
        lc_requests = 3 if args.requests == 64 else args.requests
        lc_slots = 2 if args.slots == 8 else args.slots
        res = run_long_context_bench(
            requests=lc_requests, slots=lc_slots,
            prefill_batch=args.prefill_batch, layers=args.layers,
            hidden=args.hidden, heads=args.heads, vocab=args.vocab,
            seed=args.seed, dtype=args.dtype,
            block_size=args.block_size,
            prefill_chunk=args.prefill_chunk,
            long_prompt_len=args.long_prompt_len,
            sp_degree=args.sp_degree,
            window_blocks=args.window_blocks)
        g = res["gates"]
        ok = g["sp_exact_parity"] and g["sp_compile_budget_ok"] and \
            g["window_slides_ok"] and \
            g["residency_under_quarter_ok"] and \
            g["window_tokens_complete_ok"] and \
            g["cold_context_on_host_ok"] and \
            g["window_compile_budget_ok"] and \
            g["full_window_identical"] and \
            g["probe_128k_compile_budget_ok"]
        fail_msg = "long-context gate failed (see gates in the JSON)"
        if res["sp_speedup"] < 1.0:
            # wall-clock contract: recorded + warned, never exit-fatal
            # — CPU-sim shard_map EMULATES the sp mesh on one host, so
            # prefill scaling there is mechanics, not a speedup claim
            print(f"WARNING: sp={res['sp_degree']} prefill wall-clock "
                  f"speedup {res['sp_speedup']:.2f}x < 1 on this "
                  "CPU-sim run (see sp_speedup in the JSON)",
                  file=sys.stderr)
    elif args.sampling:
        res = run_sampling_bench(
            requests=args.requests, slots=args.slots,
            prefill_batch=args.prefill_batch, layers=args.layers,
            hidden=args.hidden, heads=args.heads, vocab=args.vocab,
            seed=args.seed, dtype=args.dtype,
            block_size=args.block_size,
            prefill_chunk=args.prefill_chunk,
            spec_tokens=args.speculative or 4,
            decode_steps=args.decode_steps,
            temperature=args.temperature,
            min_spec_speedup=args.sampling_min_spec_speedup,
            max_tv=args.sampling_max_tv)
        g = res["gates"]
        ok = g["sampled_determinism_exact"] and \
            g["sampled_streams_deviate_from_greedy"] and \
            g["greedy_row_bit_parity"] and \
            g["fused_token_exact_vs_plain"] and \
            g["fused_iter_reduction_ok"] and \
            g["spec_determinism_exact"] and \
            g["draft_determinism_exact"] and \
            g["spec_host_iter_speedup_ok"] and \
            g["statistical_parity_ok"] and \
            g["constrained_json_valid"] and \
            g["mixed_compile_budget_ok"] and \
            g["compile_budgets_ok"] and \
            g["zero_retraces_ok"]
        fail_msg = "sampling gate failed (see gates in the JSON)"
    elif args.host_loop:
        res = run_host_loop_bench(
            requests=args.requests, slots=args.slots,
            prefill_batch=args.prefill_batch, layers=args.layers,
            hidden=args.hidden, heads=args.heads, vocab=args.vocab,
            seed=args.seed, dtype=args.dtype,
            block_size=args.block_size, prefill_chunk=args.prefill_chunk,
            prefix_len=_default(args.prefix_len, 256),
            sessions=_default(args.sessions, 16),
            decode_steps=args.decode_steps,
            min_iter_reduction=args.host_loop_min_reduction)
        ok = res["gates"]["exact_parity_fp32"] and \
            res["gates"]["kv8_bit_exact"] and \
            res["gates"]["iter_reduction_ok"] and \
            res["telemetry_twin"]["token_parity"]
        fail_msg = "fused decode gate failed (see gates in the JSON)"
        if not res["gates"]["fused_tok_s_ge_baseline"]:
            # wall-clock contract: recorded + warned, not exit-fatal
            # (CPU-sim throughput on shared boxes is noise-prone; the
            # committed BENCH_r15.json pins a passing measurement)
            print("WARNING: fused tok/s "
                  f"{res['fused']['tok_s_warm']:.1f} below the K=1 "
                  f"baseline {res['host_loop_baseline']['tok_s_warm']:.1f} "
                  "on this run (see gates in the JSON)", file=sys.stderr)
        if not res["telemetry_twin"]["within_2pct"]:
            print("WARNING: telemetry overhead "
                  f"{res['telemetry_twin']['overhead_pct']:.2f}% exceeds "
                  "the 2% contract on this run (noise-prone on shared "
                  "boxes)", file=sys.stderr)
    elif args.autotune:
        res = run_autotune_bench(
            requests=args.requests, sessions=_default(args.sessions, 16),
            prefix_len=_default(args.prefix_len, 256),
            pool_frac=_default(args.pool_frac, 0.25), slots=args.slots,
            layers=args.layers, hidden=args.hidden, heads=args.heads,
            vocab=args.vocab, seed=args.seed, dtype=args.dtype,
            results_dir=args.autotune_results_dir,
            max_trials=args.autotune_trials,
            min_budget=args.autotune_min_budget,
            min_speedup=args.autotune_min_speedup,
            resume=args.autotune_resume)
        ok = res["gates"]["winner_ge_min_speedup"] and \
            res["gates"]["best_config_roundtrip"]
        fail_msg = None          # the autotune gate prints its own reason
        if not ok:
            print("WARNING: autotune gate failed — winner "
                  f"{res['winner']['measured_tok_s']:.1f} tok/s vs "
                  f"default {res['default']['measured_tok_s']:.1f} "
                  f"(speedup {res['speedup']:.2f}x, floor "
                  f"{args.autotune_min_speedup}x; roundtrip="
                  f"{res['gates']['best_config_roundtrip']})",
                  file=sys.stderr)
    elif args.quant_suite:
        modes = quantize or ("kv8", "w8a8", "w8a8+kv8")
        # the protocol PROMISES a tp x kv8 combo point: default to tp=4
        # when --tp wasn't raised (needs >= 4 devices — run_bench exits
        # with the XLA_FLAGS hint otherwise) so the artifact can't
        # silently ship without it
        suite_tp = args.tp if args.tp > 1 else 4
        res = {
            "protocol": "quantized paged serving (PR 7): tok/s + servable "
                        "blocks-per-chip vs bf16 per trace; bounded "
                        "token divergence vs full-precision sequential "
                        "(tests/unit/quant_divergence.py)",
            "mixed": run_bench(quantize=modes, tp=suite_tp, **kw),
            "prefix_heavy": run_bench(prefix_len=256, quantize=modes,
                                      **kw),
            "decode_heavy": run_bench(decode_heavy=True, quantize=modes,
                                      **kw),
        }
        # the suite's recommended dtype is bf16 (the production serving
        # dtype the headlines are quoted against).  At bf16 even the
        # UNQUANTIZED serving-vs-sequential comparison can see rare
        # near-tie argmax flips — chunked prefill and one-shot generate
        # reduce in different shapes/orders, both equally valid bf16
        # greedy outputs — so bf16 runs gate on a >= 0.95 per-request
        # agreement floor and record the rate; fp32 runs keep the exact
        # bit-parity gate the non-quant benches pin.
        bf16 = str(args.dtype).replace("torch.", "") in (
            "bf16", "bfloat16")
        ok = True
        # the documented divergence bounds (tests/unit/quant_divergence.py
        # / README): a quant lane shipping below its bound must fail the
        # run, not silently land in the committed artifact
        bounds = {"kv8": 0.85, "kv8+tp": 0.85}
        for t in ("mixed", "prefix_heavy", "decode_heavy"):
            frac = 1.0 - len(res[t]["mismatched_uids"]) / res[t]["requests"]
            res[t]["baseline_request_agreement"] = frac
            ok &= res[t]["token_parity"] if not bf16 else frac >= 0.95
            for mode, lane in (res[t].get("serving_quant") or {}).items():
                rate = lane.get("token_match_rate_vs_sequential")
                if rate is None:
                    continue
                floor = bounds.get(mode, 0.70)   # w8a8 lanes: 0.70
                lane["token_match_bound"] = floor
                if rate < floor:
                    print(f"WARNING: {t}/{mode} token match {rate:.3f} "
                          f"below the documented bound {floor}",
                          file=sys.stderr)
                    ok = False
        res["baseline_parity_note"] = (
            "bf16 run: unquantized serving vs sequential is agreement-"
            "gated (>= 0.95 of requests token-exact) — bf16 near-tie "
            "argmax flips between equally valid compute shapes are not a "
            "serving bug; fp32 runs assert exact parity" if bf16 else
            "fp32 run: unquantized lanes assert exact token parity")
    else:
        res = run_bench(grid=args.grid,
                        prefix_len=_default(args.prefix_len, 0),
                        speculative=args.speculative,
                        decode_heavy=args.decode_heavy, tp=args.tp,
                        quantize=quantize,
                        pool_frac=_default(args.pool_frac, 0.0),
                        swap_batch=args.swap_batch,
                        sessions=_default(args.sessions, 0),
                        telemetry_bench=args.telemetry_bench,
                        trace_out=args.trace_out,
                        emit_metrics=args.emit_metrics, **kw)
        ok = res["token_parity"]
        tel = res.get("serving_telemetry")
        if tel is not None and not tel["within_2pct"]:
            # recorded in the JSON (within_2pct) but NOT an exit failure:
            # a wall-clock ratio on a shared box carries ~±5% noise, and
            # the pinned contract artifact is the committed BENCH_r08 run
            # — failing CI on a GC pause would be pure flake
            print(f"WARNING: telemetry overhead {tel['overhead_pct']:.2f}% "
                  "exceeds the 2% contract on this run (noise-prone on "
                  "shared boxes; see within_2pct in the JSON)",
                  file=sys.stderr)
    print(json.dumps(res, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
    if not ok:
        if fail_msg:
            print(f"WARNING: {fail_msg}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
