"""``kind: serve_mixedattn`` — ``serve_closed``'s closed loop (N callers,
each waiting for its reply before it sends the next request, one
``ServingEngine`` driven by hand; the same stamps, counters and result) for
a model whose layers are of TWO kinds, sliding-window and full attention,
each kind on pool leaves and a block table of its own (``ops/paged_kv.py``
"Layer kinds"), and whose expert layer holds a share of its experts.

``serve_closed.check_logits`` scores ``2 x prefill_chunk + 16`` positions,
272 at the default chunk, through ONE table: a 4,096-key window never
engages on so short a sequence, and the hook of such a model takes a table
per kind.  Here the traffic file says how much is compared, as
``serve_longctx`` does:

    score_rows    sequences compared (seeded token ids)
    score_tokens  positions of each: chunked prefill of ``score_tokens -
                  16`` through the engine's own two-table paged path, then
                  16 decode steps, logits compared after every chunk and
                  every step

With ``score_tokens`` = 1.5 x the window a third of the compared positions
lie past it.  The window kind's table is a ``WindowRing`` of the engine's
own width (``inference/paged.py``: the class the engine's scheduler keeps
its rings in), advanced before every chunk and every step by the call the
scheduler makes: blocks behind the window are RELEASED — their entries go
back to scratch, their ids to the free list, to be handed out again — and
the ring has wrapped by then, so the late chunks and the decode steps read
keys that were written over the ring's first blocks, through a table whose
released entries hold nothing: a release one block too early would hand a
query scratch where it needs keys.  Everything else — set-up, warm-in, the window, the drain — is
``serve_closed.run``, called as it is with this file's comparison in place
of its own; a later ``benchmark`` issue folds the three serving drivers
into one with the comparison as data.

**Discrete choices.**  Every layer takes the top-8 of 128 sigmoid scores.
A bf16 engine and a float32 reference break near-ties differently, so where
the engine is not float32 its comparison path also returns the expert sets
it chose (``forward_cached(choices=True)``) and the reference computes its
logits on THOSE sets — while it still makes its own choices, and the
comparison holds the two to each other: of the reference's own experts at
least ``EXPERT_AGREEMENT`` must be in the engine's sets, and no disagreeing
expert may lie further from the reference's cut-off than rounding explains
(``EXPERT_GAP``).  A router that is skipped, computed in a lower precision
or fed another token's input fails these, whatever the logits say.  A
float32 engine (the rehearsal, the CPU tests) is compared plainly: there
the two sides must make the SAME choices.

After the run ``counters["kv_kinds"]`` holds BOTH pools' peaks, from the
``step`` spans of the program's ring.  ``kv_pool_peak_used`` stays on the
full kind, as ``serve_closed`` takes it: the only pool that can run dry,
evict or preempt (every slot owns a whole ring of the window kind, which
reads ~97 % once every slot is past its window and says nothing more).

**Controls.**  ``python3 -m chipbench.drivers.serve_mixedattn --workload
<cell> --seed N [--rehearse]`` builds the engine as the cell does
and puts the plain reference and each shortcut ``VARIANTS`` names through
:func:`check_logits` under the limits below: a JSON line each, exit 0 only
if the plain reference is ``ok`` and every variant is not.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Optional, Tuple

import numpy as np

from chipbench import costs, reference_commanda, traffic
from chipbench.drivers import serve_closed
from chipbench.layer_metrics import _program_spans as ps

KIND = "serve_mixedattn"

#: relative RMSE of the engine's logits against the float32 reference, by
#: the dtype served.  fp32 (the rehearsal, the CPU tests; compared
#: plainly): the two sides make the same discrete choices and differ by
#: rounding order alone.  bf16 (the reference on the engine's own expert
#: sets): ``serve_closed``'s 5 % — what is left is 4 layers of bf16 matmul
#: and residual rounding (PERF.md section 6, PR 34, has every reading: the
#: cell's seeds, and each shortcut variant, which lands far above)
LOGIT_REL_RMSE = {"bf16": 5e-2, "fp32": 1e-4}
SCORE_DECODE_STEPS = serve_closed.SCORE_DECODE_STEPS
#: share of the reference's own chosen experts that the engine chose too,
#: and how far from the reference's cut-off a disagreeing expert may lie
#: (its score as a share of the token's largest).  Each limit lies between
#: what a bf16 engine reads on the chip over the cell's seeds and what the
#: router's input rounded to float8 e4m3 — the nearest precision below —
#: reads there, whose LOGITS on the engine's sets stay inside their
#: tolerance: only these catch it (PERF.md section 6, PR 34, has both
#: readings and the table of controls)
EXPERT_AGREEMENT, EXPERT_GAP = 0.985, 0.015
#: the shortcuts the comparison must refuse, each by at least one limit:
#: the router's input in float8, every key attended in the sliding layers,
#: rotary on the full layer too, the shared experts summed
VARIANTS = reference_commanda.VARIANTS[1:]


def paged_choices(srv, tokens: np.ndarray, n_decode: int
                  ) -> Tuple[np.ndarray, Dict[str, np.ndarray], int]:
    """``serve_closed.paged_logits`` (the same chunked prefill and decode
    steps on the engine's weights, cache layout and decode hooks) through a
    table PER LAYER KIND — the full kind's as wide as the sequences, the
    window kind's a ``WindowRing`` advanced as the scheduler advances its
    own (module docstring) — that also brings back the engine's choices,
    ``{"experts": int32 [L, B, S, k]}``, and the window blocks released."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.paged import WindowRing
    from deepspeed_tpu.ops import paged_kv

    hooks = srv.engine.module.decode_hooks
    fwd, prepare = hooks["forward_cached"], srv.engine._prepare
    b, s = tokens.shape
    bs, chunk = srv.block_size, srv.prefill_chunk
    nbper = paged_kv.blocks_for(s, bs)
    ring = WindowRing(b, srv._windows["window"], chunk, bs)
    cache = jax.eval_shape(lambda: hooks["init_cache"](
        1 + b * nbper, bs, srv.engine._config.jnp_dtype,
        window_blocks=ring.alloc.num_blocks))
    cache = jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.zeros(a.shape, a.dtype),
                                 srv._pool_sharding), cache)
    full = jnp.asarray(1 + np.arange(b * nbper).reshape(b, nbper), jnp.int32)

    def tables(first_query: int, upto: int):
        for row in range(b):
            ring.advance(row, first_query, upto)
        return {"full": full, "window": jnp.asarray(ring.tables)}

    @jax.jit
    def prefill(params, cache, ids, bt, base, valid):
        return fwd(prepare(params), ids, cache, base, lengths=valid,
                   block_tables=bt, choices=True)

    @jax.jit
    def decode(params, cache, tok, bt, lengths):
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
                params, cache, jnp.asarray(ids), tables(base, base + valid),
                jnp.full((b,), base, jnp.int32),
                jnp.full((b,), valid, jnp.int32))
            rows.append(np.asarray(logits, np.float32))
            experts.append(np.asarray(made["experts"])[:, :, :valid])
        for p in range(n_prefill, s):
            logits, cache, made = decode(params, cache,
                                         jnp.asarray(tokens[:, p:p + 1]),
                                         tables(p, p + 1),
                                         jnp.full((b,), p, jnp.int32))
            rows.append(np.asarray(logits, np.float32))
            experts.append(np.asarray(made["experts"]))
    return np.stack(rows, axis=1), {
        "experts": np.concatenate(experts, axis=2)}, ring.released


def check_logits(job, srv, variant: Optional[str] = None) -> Dict[str, Any]:
    """Engine vs the family's plain reference on ``score_rows`` seeded
    sequences of ``score_tokens`` positions (module docstring); with
    ``variant``, vs that shortcut of the reference (a control: ``ok`` has
    to come out false)."""
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
    got, chosen, released = paged_choices(srv, tokens, SCORE_DECODE_STEPS)
    out: Dict[str, Any] = {}
    agreed = True
    if job.config["dtype"] != "fp32":
        want, agreement = job.family.logits(
            job.config, srv.engine.params, tokens, at=at, forced=chosen,
            variant=variant)
        agreed = agreement["experts"] >= EXPERT_AGREEMENT \
            and agreement["expert_gap"] <= EXPERT_GAP
        out.update(agreement)
        job.note("reference on the engine's expert sets; of the "
                 f"reference's own experts {agreement['experts']:.5f} in "
                 f"the engine's (floor {EXPERT_AGREEMENT}); furthest "
                 f"disagreeing expert {agreement['expert_gap']:.5f} of its "
                 "token's largest score from the cut-off (limit "
                 f"{EXPERT_GAP})")
    else:
        want = job.family.logits(job.config, srv.engine.params, tokens,
                                 at=at, variant=variant)
    want = np.asarray(want, np.float32)
    rmse = float(np.sqrt(np.mean((got - want) ** 2)))
    rel = rmse / float(np.std(want))
    tol = LOGIT_REL_RMSE[job.config["dtype"]]
    job.note(f"comparison: {rows} x {s} tokens, {len(at)} positions a row "
             f"of which {sum(p >= a['window'] for p in at)} lie past the "
             f"window ({a['window']} keys); the window kind's ring "
             f"released {released} blocks behind the rows on the way")
    return {"ok": bool(np.isfinite(got).all() and rel <= tol and agreed),
            "logit_rel_rmse": rel, "tolerance": tol, **out,
            "window_blocks_released": released,
            "positions": int(got.shape[0] * got.shape[1])}


def both_pools(out: Dict[str, Any]) -> None:
    """Both pools' peaks over the window and the window blocks released,
    from the ``step`` spans of the program's ring, into
    ``out["counters"]``.  ``num_blocks`` and ``samples["blocks_in_use"]``
    (``kv_pool_peak_used``) stay the full kind's (module docstring)."""
    steps, _ = ps.steps_in_window({"window": out["window"]})
    used = [s["args"] for s, _ in steps or ()
            if "window_blocks_in_use" in s.get("args", {})]
    if not used:
        return
    out["counters"]["kv_kinds"] = {
        "full": {"num_blocks": out["counters"]["num_blocks"],
                 "peak_blocks_in_use": max(a["blocks_in_use"] for a in used)},
        "sliding": {"num_blocks": used[0]["window_num_blocks"],
                    "peak_blocks_in_use": max(a["window_blocks_in_use"]
                                              for a in used)}}
    out["counters"]["window_blocks_released"] = sum(
        a["window_blocks_released"] for a in used)


def run(job) -> Dict[str, Any]:
    """``serve_closed.run`` with the comparison above, then both pools."""
    short = serve_closed.check_logits
    serve_closed.check_logits = check_logits
    try:
        out = serve_closed.run(job)
    finally:
        serve_closed.check_logits = short
    both_pools(out)
    return out


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
    held = True
    for variant in (None,) + VARIANTS:
        check = check_logits(job, srv, variant)
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
