"""``kind: serve_latent`` — ``serve_closed``'s closed loop (N callers, each
waiting for its reply before it sends the next request, one
``ServingEngine`` driven by hand; the same stamps, counters and result) for
a model with LATENT ATTENTION, whose pool holds one latent a token and is
read absorbed (``ops/paged_kv.py`` "The latent kind"), and whose expert
layer holds a share of its experts.

``serve_closed.check_logits`` scores ``2 x prefill_chunk + 16`` positions,
272 at the default chunk: the query temperature ``t(p)`` leaves 1 only past
``original_max_position_embeddings`` (8,192), and YaRN's interpolated bands
have turned little before then.  Here the traffic file says how much is
compared, as ``serve_longctx`` and ``serve_mixedattn`` do:

    score_rows    sequences compared (seeded token ids)
    score_tokens  positions of each: chunked prefill of ``score_tokens -
                  16`` through the engine's own paged path, then 16 decode
                  steps, logits compared after every chunk and every step

With ``score_tokens`` = 1.25 x the original context a fifth of the compared
positions lie past it.  The relative RMSE is taken over the positions BELOW
the original context and over those PAST it, each against its own limit: a
shortcut that only shows past 8,192 (``t(p)`` dropped) cannot hide in the
four fifths before it, and the control that drops it must pass below and
fail past — the proof that the comparison reaches it.  The set-up, the stamps,
the window and the drain are ``serve_closed.run``'s, copied here because
that function has no seam at the window's opening (a later ``benchmark``
issue folds the serving drivers into one, PERF.md section 7 (30)).

**The start.**  ``serve_closed`` opens its window when every caller has had
its first token.  Here that is 64 prompts of ~7,450 tokens prefilled side
by side, 16 calls a step, and a request then lives for two thirds of the
window: the callers' first requests end in bunches, each bunch is a burst
of prefill calls, and where the bursts fall in a 51 s window follows the
point at which the seed enters the deck (six seeds spread ``serve_tok_s``
by 6.6-7.9 %, PERF.md section 6, PR 39).  The traffic is what users send
and stays as it is; the START is the driver's.  So the loop goes on
unmeasured for the traffic file's ``settle_s`` seconds after the last first
token — as long as the longest reply takes, 1,536 tokens at ~40 ms, so that
every request the callers started together has ended and each caller is in
its second or third — and only then does the window open.  It is set-up:
``setup_s`` carries it.

**Discrete choices.**  Every layer takes the top-4 of 128 softmax scores.
A bf16 engine and a float32 reference break near-ties differently, so where
the engine is not float32 its comparison path also returns the expert sets
it chose (``forward_cached(choices=True)``) and the reference computes its
logits on THOSE sets — while it still makes its own choices, and the
comparison holds the two to each other: of the reference's own experts at
least ``EXPERT_AGREEMENT`` must be in the engine's sets, and no disagreeing
expert may lie further from the reference's cut-off than rounding explains:
``EXPERT_GAP`` on the MEAN distance of the disagreeing experts and
``EXPERT_GAP_MAX`` on the FURTHEST, an extreme of ~250,000 draws that reads
0.044-0.073 over 39 runs of one program and is printed by layer, so that a
log shows whether it grows with the depth, as rounding carried along the
residual does, or sits in one layer.  A float32 engine (the rehearsal, the
CPU tests) is compared plainly: there the two sides must make the SAME
choices.

**Controls.**  ``python3 -m chipbench.drivers.serve_latent --workload
<cell> --seed N [--rehearse]`` builds the engine as the cell does and puts
the plain reference and each shortcut ``VARIANTS`` names through
:func:`check_logits` under the limits below: a JSON line each, exit 0 only
if the plain reference is ``ok`` and every variant is not.
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import costs, reference_mistral4, traffic
from chipbench.drivers import serve_closed

KIND = "serve_latent"

#: relative RMSE of the engine's logits against the float32 reference, by
#: the dtype served, over the positions below the original context and
#: over those past it alike.  fp32 (the rehearsal, the CPU tests; compared
#: plainly): the two sides make the same discrete choices and differ by
#: rounding order alone.  bf16 (the reference on the engine's own expert
#: sets): a bf16 engine reads 1.47-1.49 % below the original context and
#: 1.40-1.44 % past it over nine seeds on the chip (6 layers of bf16 matmul
#: and residual rounding and a bf16 latent read absorbed); the nearest
#: precision below — what is cached, ``c`` and ``k_r``, rounded to float8
#: e4m3 — reads 4.06-4.32 % below and 2.76-3.03 % past: 2 % lies between
#: with a third of room on either side, where ``serve_closed``'s 5 % would
#: pass a float8 latent (PERF.md section 6, PR 39, has every reading)
LOGIT_REL_RMSE = {"bf16": 2e-2, "fp32": 1e-4}
SCORE_DECODE_STEPS = serve_closed.SCORE_DECODE_STEPS
#: share of the reference's own chosen experts that the engine chose too,
#: and how far from the reference's cut-off the disagreeing experts lie, in
#: the MEAN and at the FURTHEST (a score's distance as a share of its
#: token's largest).  Each limit lies between what a bf16 engine reads on
#: the chip (0.98907-0.98967; 0.00255-0.00273; 0.044-0.073 over 39 runs)
#: and what the router's input rounded to float8 e4m3 — the nearest
#: precision below — reads there (0.9651-0.9658; 0.0078-0.0079; 0.132-0.185
#: over five seeds), whose LOGITS on the engine's sets stay inside their
#: tolerance: only these catch it (PERF.md section 6, PR 39, has the table
#: of controls).  The furthest is an extreme and has the least room: a
#: third above the largest sound reading, a quarter below the smallest
#: unsound one
EXPERT_AGREEMENT, EXPERT_GAP, EXPERT_GAP_MAX = 0.985, 0.0045, 0.10
#: the shortcuts the comparison must refuse, each by at least one limit
VARIANTS = reference_mistral4.VARIANTS[1:]


def paged_choices(srv, tokens: np.ndarray, n_decode: int
                  ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """``serve_closed.paged_logits`` (the same chunked prefill and decode
    steps on the engine's weights, cache layout, block size and decode
    hooks) that also brings back the engine's choices, ``{"experts": int32
    [L, B, S, k]}``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import paged_kv

    hooks = srv.engine.module.decode_hooks
    fwd, prepare = hooks["forward_cached"], srv.engine._prepare
    b, s = tokens.shape
    bs, chunk = srv.block_size, srv.prefill_chunk
    nbper = paged_kv.blocks_for(s, bs)
    cache = jax.eval_shape(lambda: hooks["init_cache"](
        1 + b * nbper, bs, srv.engine._config.jnp_dtype))
    cache = jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.zeros(a.shape, a.dtype),
                                 srv._pool_sharding), cache)
    bt = jnp.asarray(1 + np.arange(b * nbper).reshape(b, nbper), jnp.int32)

    @jax.jit
    def prefill(params, cache, ids, base, valid):
        return fwd(prepare(params), ids, cache, base, lengths=valid,
                   block_tables=bt, choices=True)

    @jax.jit
    def decode(params, cache, tok, lengths):
        return fwd(prepare(params), tok, cache, 0, lengths=lengths,
                   block_tables=bt, choices=True)

    params, rows, experts = srv.engine.params, [], []
    n_prefill = s - n_decode
    with srv._tp_ctx():
        for base in range(0, n_prefill, chunk):
            valid = min(chunk, n_prefill - base)
            ids = np.zeros((b, chunk), np.int32)
            ids[:, :valid] = tokens[:, base:base + valid]
            logits, cache, made = prefill(
                params, cache, jnp.asarray(ids),
                jnp.full((b,), base, jnp.int32),
                jnp.full((b,), valid, jnp.int32))
            rows.append(np.asarray(logits, np.float32))
            experts.append(np.asarray(made["experts"])[:, :, :valid])
        for p in range(n_prefill, s):
            logits, cache, made = decode(params, cache,
                                         jnp.asarray(tokens[:, p:p + 1]),
                                         jnp.full((b,), p, jnp.int32))
            rows.append(np.asarray(logits, np.float32))
            experts.append(np.asarray(made["experts"]))
    return np.stack(rows, axis=1), {
        "experts": np.concatenate(experts, axis=2)}


def _rel_rmse(got, want) -> Optional[float]:
    if not want.size:
        return None
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def check_logits(job, srv, variant: Optional[str] = None,
                 engine=None) -> Dict[str, Any]:
    """Engine vs the family's plain reference on ``score_rows`` seeded
    sequences of ``score_tokens`` positions (module docstring); with
    ``variant``, vs that shortcut of the reference (a control: ``ok`` has
    to come out false).  ``engine``: the engine's side, ``paged_choices``'s
    pair, where a caller has it already (the controls compare one reading
    of the engine with every variant)."""
    a = costs.arch(job.config)
    chunk = srv.prefill_chunk
    rows, s = int(job.traffic["score_rows"]), int(job.traffic["score_tokens"])
    if s > srv.max_seq_len:
        raise ValueError(f"score_tokens {s} over max_seq_len "
                         f"{srv.max_seq_len}")
    rng = np.random.default_rng(traffic.seed_sequence(job.seed).spawn(1)[0])
    tokens = rng.integers(0, a["vocab"], (rows, s)).astype(np.int32)
    n_prefill = s - SCORE_DECODE_STEPS
    at = [min(base + chunk, n_prefill) - 1
          for base in range(0, n_prefill, chunk)]
    at += list(range(n_prefill, s))
    got, chosen = engine or paged_choices(srv, tokens, SCORE_DECODE_STEPS)
    out: Dict[str, Any] = {"engine": (got, chosen)}
    agreed = True
    if job.config["dtype"] != "fp32":
        want, agreement = job.family.logits(
            job.config, srv.engine.params, tokens, at=at, forced=chosen,
            variant=variant)
        agreed = agreement["experts"] >= EXPERT_AGREEMENT \
            and agreement["expert_gap"] <= EXPERT_GAP \
            and agreement["expert_gap_max"] <= EXPERT_GAP_MAX
        out.update(agreement)
        job.note("reference on the engine's expert sets; of the "
                 f"reference's own experts {agreement['experts']:.5f} in "
                 f"the engine's (floor {EXPERT_AGREEMENT}); a disagreeing "
                 f"expert lies {agreement['expert_gap']:.5f} of its token's "
                 f"largest score from the cut-off in the mean (limit "
                 f"{EXPERT_GAP}), {agreement['expert_gap_max']:.5f} at the "
                 f"furthest (limit {EXPERT_GAP_MAX}; by layer "
                 f"{agreement['expert_gap_max_by_layer']})")
    else:
        want = job.family.logits(job.config, srv.engine.params, tokens,
                                 at=at, variant=variant)
    want = np.asarray(want, np.float32)
    past = np.asarray(at) >= a["original_positions"]
    below_rel = _rel_rmse(got[:, ~past], want[:, ~past])
    past_rel = _rel_rmse(got[:, past], want[:, past])
    tol = LOGIT_REL_RMSE[job.config["dtype"]]
    job.note(f"comparison: {rows} x {s} tokens at block {srv.block_size}, "
             f"{len(at)} positions a row of which {int(past.sum())} lie "
             f"past the original context ({a['original_positions']}): "
             f"relative RMSE {below_rel} below it, {past_rel} past it")
    parts = [r for r in (below_rel, past_rel) if r is not None]
    return {"ok": bool(np.isfinite(got).all() and agreed
                       and all(r <= tol for r in parts)),
            "logit_rel_rmse": _rel_rmse(got, want), "tolerance": tol,
            "logit_rel_rmse_below": below_rel,
            "logit_rel_rmse_past": past_rel, **out,
            "positions": int(got.shape[0] * got.shape[1]),
            "positions_past": int(got.shape[0] * past.sum())}


def run(job) -> Dict[str, Any]:
    """``serve_closed.run``'s closed loop — the same set-up, stamps,
    counters and result — with the comparison above and a SETTLED start:
    once every caller has had its first token the loop goes on, unmeasured,
    for the traffic file's ``settle_s`` seconds before the window opens
    (module docstring, "The start")."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request

    mix, sizing = job.traffic, job.sizing["serving"]
    clients_n, settle_s = int(mix["clients"]), float(mix["settle_s"])
    if clients_n > int(sizing["slots"]):
        raise ValueError(f"{clients_n} callers over {sizing['slots']} slots")
    model = job.family.build(job.config, job.sizing.get("model"))
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[job.config["dtype"]]

    with job.spans("cb.setup.weights"):
        params = jax.jit(lambda key: jax.tree_util.tree_map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            model.init_fn(key)))(jax.random.PRNGKey(job.seed))
        jax.block_until_ready(params)
    with job.spans("cb.setup.init_serving"):
        srv = deepspeed_tpu.init_serving(
            model, config={"dtype": job.config["dtype"]}, params=params,
            **sizing)
        del params
        jax.block_until_ready((srv.engine.params, srv._cache))
    with job.spans("cb.setup.check_logits"):
        check = check_logits(job, srv)
        del check["engine"]
    job.note(f"teacher-forced logits vs float32 reference: relative RMSE "
             f"{check['logit_rel_rmse']:.5f} (tolerance "
             f"{check['tolerance']}) over {check['positions']} positions")

    vocab = costs.arch(job.config)["vocab"]
    stream = traffic.RequestStream(mix, vocab, job.seed)
    clients = [serve_closed._Client() for _ in range(clients_n)]
    failed = 0
    ttfts: List[float] = []          # of requests submitted in the window
    gaps: List[float] = []           # between tokens stamped in the window
    state = {"open": None, "close": None, "tokens": 0, "attempted": 0,
             "first_tokens": 0, "kv_tokens": 0, "decode_samples": 0}
    pool_used: List[float] = []

    def submit(c, cut: float = 1.0) -> None:
        nonlocal failed
        r = next(stream)
        r["max_new_tokens"] = max(1, math.ceil(r["max_new_tokens"] * cut))
        c.seen, c.stamps = 0, []
        c.in_window = state["open"] is not None and state["close"] is None
        state["attempted"] += c.in_window
        c.submitted = time.perf_counter()
        try:
            c.handle = srv.submit(Request(**r))
        except Exception as e:  # refused: counted, the caller retries next
            job.note(f"submit refused: {type(e).__name__}: {e}")
            c.handle = None
            failed += c.in_window

    def harvest(now: float) -> None:
        """Stamp what the last step emitted; finish and resubmit."""
        nonlocal failed
        measuring = state["open"] is not None and state["close"] is None
        for c in clients:
            h = c.handle
            if h is None:
                if state["close"] is None:
                    submit(c)
                continue
            toks = h.tokens()
            new = len(toks) - c.seen
            if new:
                if c.seen == 0 and c.in_window:
                    ttfts.append(now - c.submitted)
                if measuring:
                    state["tokens"] += new
                    state["first_tokens"] += c.seen == 0
                    if c.stamps and c.stamps[-1] >= state["open"]:
                        gaps.append(now - c.stamps[-1])
                        gaps.extend([0.0] * (new - 1))
                c.stamps.extend([now] * new)
                c.seen = len(toks)
            if h.done:
                want = h.request.max_new_tokens
                good = (h.status == "finished" and len(toks) == want
                        and all(0 <= t < vocab for t in toks))
                if not good:
                    failed += 1
                    job.note(f"request {h.uid}: status {h.status}, "
                             f"{len(toks)} of {want} tokens")
                c.handle = None
                if state["close"] is None:
                    submit(c)

    def step() -> None:
        with job.spans("cb.step"):
            srv.step()
        now = time.perf_counter()
        with job.spans("cb.harvest"):
            harvest(now)
        if state["open"] is not None and state["close"] is None:
            pool_used.append(srv._alloc.blocks_in_use)
            live = [c for c in clients if c.handle is not None and c.seen]
            if live:
                state["kv_tokens"] += sum(
                    c.handle.request.prompt.size + c.seen for c in live)
                state["decode_samples"] += 1

    # warm-in: every caller's first request, cut to a seeded fraction; both
    # programs have compiled and run once every caller has its first token
    with job.spans("cb.setup.warm_in"):
        for c, frac in zip(clients, stream.warm_in_fractions(clients_n)):
            submit(c, cut=frac)
        firsts = [c.handle for c in clients]
        t_warm = time.perf_counter()
        while any(h is not None and not h.tokens() for h in firsts):
            step()
            if time.perf_counter() - t_warm > 900:
                raise RuntimeError("warm-in did not finish in 900 s")
    # the loop as it runs, unmeasured, until the shared start is forgotten
    with job.spans("cb.setup.settle"):
        t_settle, finished0 = time.perf_counter(), stream.issued
        while time.perf_counter() - t_settle < settle_s:
            step()
    job.note(f"settled {time.perf_counter() - t_settle:.1f} s before the "
             f"window: {stream.issued - finished0} requests ended and were "
             "followed by the caller's next")

    before = srv.stats()
    compiles0 = job.compiles()
    state["open"] = t_open = time.perf_counter()
    job.window_opened(t_open)
    while True:
        step()
        since = time.perf_counter() - t_open
        job.tracer.poll(since)
        if since >= job.seconds:
            break
    state["close"] = t_close = time.perf_counter()
    job.tracer.finish()
    after = srv.stats()
    compiles1 = job.compiles()

    # unmeasured: first tokens of what was submitted inside the window
    def waiting() -> int:
        return sum(c.handle is not None and c.in_window and c.seen == 0
                   for c in clients)

    t_drain = time.perf_counter()
    while waiting():
        if time.perf_counter() - t_drain > serve_closed.DRAIN_LIMIT_S:
            failed += waiting()
            job.note(f"{waiting()} requests had no first token "
                     f"{serve_closed.DRAIN_LIMIT_S} s after the window: "
                     "counted as failed")
            break
        step()

    window = t_close - t_open
    delta = {k: after[k] - before[k] for k in (
        "iterations", "decode_steps", "prefill_calls", "generated_tokens",
        "prompt_tokens", "prefix_hit_tokens", "evicted", "admitted",
        "compile_count")}
    no_compile = compiles1 == compiles0 and delta["compile_count"] == 0
    if not no_compile:
        job.note(f"compiled inside the window: backend compiles "
                 f"{compiles0} -> {compiles1}, engine compile_count "
                 f"+{delta['compile_count']}")
    ms, p95 = serve_closed._ms, serve_closed._p95
    medians = {"ttft_median_ms": ms(statistics.median(ttfts))
               if ttfts else None,
               "itl_median_ms": ms(statistics.median(gaps))
               if gaps else None}
    e2e = {"serve_tok_s": state["tokens"] / window}
    if ttfts:
        e2e["ttft_p95_ms"] = ms(p95(ttfts))
    if gaps:
        e2e["itl_p95_ms"] = ms(p95(gaps))
    job.note(f"window {window:.3f} s: {state['attempted']} requests "
             f"submitted, {len(ttfts)} first tokens, {len(gaps)} gaps, "
             f"{state['tokens']} tokens, {failed} failed; TTFT median "
             f"{medians['ttft_median_ms']} ms, p95 "
             f"{e2e.get('ttft_p95_ms')}; ITL median "
             f"{medians['itl_median_ms']} ms, p95 {e2e.get('itl_p95_ms')}; "
             f"evicted {delta['evicted']}")
    srv.close()
    return {
        "correct": bool(check["ok"] and no_compile),
        "attempted": state["attempted"], "failed": int(failed),
        "end_to_end": e2e, "window_s": window, "window": (t_open, t_close),
        "counters": {**delta, "slots": srv.slots,
                     "num_blocks": after["num_blocks"],
                     "block_size": after["block_size"],
                     "tokens_in_window": state["tokens"],
                     "first_tokens_in_window": state["first_tokens"],
                     "ttft_samples": len(ttfts), "itl_samples": len(gaps),
                     **medians,
                     "mean_valid_kv_tokens": state["kv_tokens"]
                     / max(1, state["decode_samples"]),
                     "logit_rel_rmse": check["logit_rel_rmse"]},
        "samples": {"blocks_in_use": pool_used},
        "devices": list(srv.engine.mesh.devices.flat),
    }


def controls(job) -> bool:
    """The plain reference and every shortcut of ``VARIANTS`` through
    :func:`check_logits` on the cell's engine (``serve_closed.run``'s
    set-up), a JSON line each; true if the plain comparison is ``ok`` and
    every shortcut is refused."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu

    model = job.family.build(job.config, job.sizing.get("model"))
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[job.config["dtype"]]
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a,
        model.init_fn(key)))(jax.random.PRNGKey(job.seed))
    srv = deepspeed_tpu.init_serving(
        model, config={"dtype": job.config["dtype"]}, params=params,
        **job.sizing["serving"])
    del params
    held, engine = True, None
    for variant in (None,) + VARIANTS:
        check = check_logits(job, srv, variant, engine)
        engine = check.pop("engine")
        held &= check["ok"] == (variant is None)
        print(json.dumps({"seed": job.seed, "variant": variant, **check}),
              flush=True)
    srv.close()
    return held


def main(argv=None) -> int:
    from deepspeed_tpu.utils.platform import enable_compile_cache

    from chipbench import run as cb

    ap = argparse.ArgumentParser(description=controls.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache(cb.ROOT)
    job = cb.Job(argparse.Namespace(
        seed=args.seed, seconds=0.0, rehearse=args.rehearse, trace=0,
        keep_trace=None), cb.load_cell(args.workload, args.rehearse))
    held = controls(job)
    print(json.dumps({"controls_held": held}), flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
