"""``kind: serve_tails`` — ``serve_latent``'s closed loop with its SETTLED
start (N callers, each waiting for its reply before it sends the next
request, one ``ServingEngine`` driven by hand; the loop goes on unmeasured
for the traffic file's ``settle_s`` after the last first token; the same
stamps, counters and result: that function is CALLED, with this module's
comparison in the place of its own) for a model whose keys are made by causal
convolutions — TAILS a slot beside the paged pool — and whose expert layer
routes TOP-1 behind an MLP router.

**The comparison** (``score_rows`` seeded sequences of ``score_tokens``
positions, ids from the whole vocabulary).  The engine's side is its own
cached forward on its own weights, block size and chunk, at the TIMED
shapes: the prompt in chunks of ``prefill_chunk`` as calls of
``prefill_batch`` rows (the rows past ``score_rows`` are pads: slot out of
range, no token), so that the tails cross ``score_tokens / prefill_chunk``
chunk boundaries, then ``SCORE_DECODE_STEPS`` decode steps at all ``slots``
rows (the rows past ``score_rows`` idle).  It also brings back the expert
each token ran in each layer and the router's scores.  Three parts, all of
which must hold:

 (a) LOGITS, after every chunk and every step, against the float32
     reference's full forward pass run under the ENGINE's routes:
     relative RMSE within ``logit``;
 (b) ROUTES: every (token, layer) at which the engine's expert is not the
     reference's own ``argmax`` must be a near-tie IN THE REFERENCE's
     scores — the reference's largest score less its score of the engine's
     expert within ``tie`` — and such pairs are at most ``flips`` of all.
     A route that differs where the reference sees no tie fails the cell;
 (c) SCORES: the router's softmax itself, engine against reference, RMS
     difference within ``score``.

One expert a token: a flipped route replaces the token's whole FFN output,
so the logits can only be compared under the same routes ((a)), and what
keeps (a) honest is that the routes themselves are held to the reference
((b)) by the reference's own margins, and the scores behind them ((c)).

**The limits** (``LIMITS``, by the dtype served; each between two readings
on the chip, PERF.md section 6, PR 66): the bf16 engine over its seeds, and
the reference with one part in the nearest precision below the
configuration's bfloat16 (``reference_zaya.VARIANTS``: ``tails_fp8``,
``router_fp8``), which must come out not ``ok`` by at least one limit.
``tie`` has a reason of its own: the engine's router is float32, but it
reads a bfloat16 ``y`` off a bfloat16 residual stream ten layers deep; a
relative error of ~1e-2 on logits whose spread is ~3 moves a softmax score
by a few hundredths, and no further (0.037-0.069 at the furthest of ~900
differing pairs of 81,920 over fifteen readings; float8 tails read
0.24-0.32).
float32 (the rehearsal, the CPU tests): the two sides differ by rounding
order alone and must make the same choices.

``SHOWN`` (``router_bf16``): the router's stream and activations rounded to
bfloat16, where the program keeps float32.  It is put through the
comparison and printed, and NOT required to fail: on the chip it reads
close to the engine's own numbers on every part over three seeds (flips
0.011-0.013 against 0.009-0.012, the furthest tie 0.049-0.094 against
0.037-0.069, scores 0.0023-0.0026 against 0.0019-0.0025) — the residual
stream's rounding, not the router's, is what the routes feel.  The
program's float32 router is the cheaper side of that (fewer flips for four
products of 128 rows); this comparison does not hold it to it.

**Controls.**  ``python3 -m chipbench.drivers.serve_tails --workload <cell>
--seed N [--rehearse]`` builds the engine as the cell does and puts the
plain reference and each shortcut of ``VARIANTS`` through
:func:`check_logits`: a JSON line each, exit 0 only if the plain reference
is ``ok`` and every variant is not.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Optional, Tuple
from unittest import mock

import numpy as np

from chipbench import costs, reference_zaya, traffic
from chipbench.drivers import serve_closed, serve_latent

KIND = "serve_tails"
SCORE_DECODE_STEPS = serve_closed.SCORE_DECODE_STEPS
#: by the dtype served: ``logit`` relative RMSE of the logits under the
#: engine's routes; ``tie`` how far below the reference's largest score its
#: score of the engine's expert may lie where the two differ; ``flips`` the
#: share of (token, layer) pairs that may differ at all; ``score`` the RMS
#: difference of the routers' softmax scores (module docstring; the
#: readings are in PERF.md section 6, PR 66)
#: bf16, engine over fifteen readings / float8 tails over three: logit
#: 0.0080-0.0105 / 0.0220-0.0300; tie 0.037-0.069 / 0.24-0.32; flips
#: 0.0093-0.0123 / 0.034-0.039; score 0.0019-0.0025 / 0.0074-0.0085
LIMITS = {
    "bf16": {"logit": 1.6e-2, "tie": 0.15, "flips": 0.02, "score": 4.5e-3},
    "fp32": {"logit": 1e-4, "tie": 1e-5, "flips": 1e-3, "score": 1e-5},
}
#: put through the comparison and printed, not required to fail (module
#: docstring)
SHOWN = ("router_bf16",)
#: the shortcuts the comparison must refuse, each by at least one limit
VARIANTS = tuple(v for v in reference_zaya.VARIANTS[1:] if v not in SHOWN)


def paged_routes(srv, tokens: np.ndarray, n_decode: int
                 ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """The engine's side (module docstring): ``(logits float32 [rows,
    chunks + n_decode, V], {"experts": int32 [L, rows, S], "scores": float32
    [L, rows, S, E]})`` on a cache of its own — blocks for ``rows`` rows,
    tails for every slot."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import paged_kv

    hooks = srv.engine.module.decode_hooks
    fwd, prepare = hooks["forward_cached"], srv.engine._prepare
    rows, s = tokens.shape
    bs, chunk = srv.block_size, srv.prefill_chunk
    wide, slots = max(rows, srv.prefill_batch), srv.slots
    nbper = paged_kv.blocks_for(s, bs)
    cache = jax.eval_shape(lambda: hooks["init_cache"](
        1 + rows * nbper, bs, srv.engine._config.jnp_dtype,
        state_rows=slots))
    cache = jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.zeros(a.shape, a.dtype),
                                 srv._pool_sharding), cache)

    def table(n):
        bt = np.zeros((n, nbper), np.int32)
        bt[:rows] = 1 + np.arange(rows * nbper).reshape(rows, nbper)
        return jnp.asarray(bt)

    slot = np.full(wide, slots, np.int32)          # a pad row: out of range
    slot[:rows] = np.arange(rows)
    tables = {"prefill": {"full": table(wide), "slot": jnp.asarray(slot)},
              "decode": {"full": table(slots)}}

    @jax.jit
    def prefill(params, cache, ids, base, valid):
        return fwd(prepare(params), ids, cache, base, lengths=valid,
                   block_tables=tables["prefill"], choices=True)

    @jax.jit
    def decode(params, cache, tok, lengths):
        return fwd(prepare(params), tok, cache, 0, lengths=lengths,
                   block_tables=tables["decode"], choices=True)

    params, out, experts, scores = srv.engine.params, [], [], []

    def keep(logits, made, valid):
        out.append(np.asarray(logits[:rows], np.float32))
        experts.append(np.asarray(made["experts"])[:, :rows, :valid, 0])
        scores.append(np.asarray(made["scores"], np.float32)
                      [:, :rows, :valid])

    n_prefill = s - n_decode
    with srv._tp_ctx():
        for base in range(0, n_prefill, chunk):
            valid = min(chunk, n_prefill - base)
            ids = np.zeros((wide, chunk), np.int32)
            ids[:rows, :valid] = tokens[:, base:base + valid]
            lengths = np.zeros(wide, np.int32)
            lengths[:rows] = valid
            logits, cache, made = prefill(
                params, cache, jnp.asarray(ids),
                jnp.full((wide,), base, jnp.int32), jnp.asarray(lengths))
            keep(logits, made, valid)
        for p in range(n_prefill, s):
            tok = np.zeros((slots, 1), np.int32)
            tok[:rows] = tokens[:, p:p + 1]
            logits, cache, made = decode(params, cache, jnp.asarray(tok),
                                         jnp.full((slots,), p, jnp.int32))
            keep(logits, made, 1)
    return np.stack(out, axis=1), {
        "experts": np.concatenate(experts, axis=2),
        "scores": np.concatenate(scores, axis=2)}


def _rel_rmse(got, want) -> float:
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def check_logits(job, srv, variant: Optional[str] = None,
                 engine=None) -> Dict[str, Any]:
    """Engine vs the family's plain reference (module docstring, (a)-(c));
    with ``variant``, vs that shortcut of the reference (a control: ``ok``
    has to come out false).  ``engine``: the engine's side,
    :func:`paged_routes`'s pair, where a caller has it already."""
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
    got, made = engine or paged_routes(srv, tokens, SCORE_DECODE_STEPS)
    want, own = job.family.logits(job.config, srv.engine.params, tokens,
                                  at=at, route=made["experts"],
                                  variant=variant)
    want, own = np.asarray(want, np.float32), np.asarray(own, np.float32)
    lim = LIMITS[job.config["dtype"]]
    rel = _rel_rmse(got, want)
    # (b) where the engine's expert is not the reference's own
    theirs = np.take_along_axis(own, made["experts"][..., None],
                                axis=-1)[..., 0]
    gap = own.max(axis=-1) - theirs                  # 0 where they agree
    flipped = made["experts"] != own.argmax(axis=-1)
    flips = float(flipped.mean())
    tie = float(gap[flipped].max()) if flipped.any() else 0.0
    by_layer = [round(float(g.max()), 5) for g in np.where(flipped, gap, 0.0)]
    # (c) the routers' scores themselves
    score = float(np.sqrt(np.mean((made["scores"] - own) ** 2)))
    job.note(f"comparison: {rows} x {s} tokens at block {srv.block_size}, "
             f"chunks of {chunk} in calls of {max(rows, srv.prefill_batch)} "
             f"rows then {SCORE_DECODE_STEPS} steps at {srv.slots} rows, "
             f"{len(at)} positions a row: (a) logits under the engine's "
             f"routes, relative RMSE {rel:.5f} (limit {lim['logit']}); (b) "
             f"{int(flipped.sum())} of {flipped.size} (token, layer) routes "
             f"are not the reference's own ({flips:.5f}, limit "
             f"{lim['flips']}), the furthest {tie:.5f} under the "
             f"reference's largest score (limit {lim['tie']}; by layer "
             f"{by_layer}); (c) router scores, RMS difference {score:.6f} "
             f"(limit {lim['score']})")
    return {"ok": bool(np.isfinite(got).all() and rel <= lim["logit"]
                       and tie <= lim["tie"] and flips <= lim["flips"]
                       and score <= lim["score"]),
            "logit_rel_rmse": rel, "tolerance": lim["logit"],
            "route_flips": flips, "route_tie": tie, "route_tie_by_layer":
            by_layer, "score_rms": score, "limits": lim,
            "engine": (got, made),
            "positions": int(got.shape[0] * got.shape[1])}


def run(job) -> Dict[str, Any]:
    """``serve_latent.run`` — its set-up, warm-in, settled start, window,
    drain, stamps, counters and result — with :func:`check_logits` above as
    the comparison (that function looks ``check_logits`` up in its module
    at the call, and has no other seam)."""
    with mock.patch.object(serve_latent, "check_logits", check_logits):
        return serve_latent.run(job)


def controls(job) -> bool:
    """The plain reference and every shortcut of ``VARIANTS`` through
    :func:`check_logits` on the cell's engine, a JSON line each; true if
    the plain comparison is ``ok`` and every shortcut is refused."""
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
    for variant in (None,) + VARIANTS + SHOWN:
        check = check_logits(job, srv, variant, engine)
        engine = check.pop("engine")
        if variant not in SHOWN:
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
