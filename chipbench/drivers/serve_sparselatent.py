"""``kind: serve_sparselatent`` — ``serve_latent``'s closed loop with its
settled start (N callers, each waiting for its reply before it sends the
next request, one ``ServingEngine`` driven by hand; the same stamps, counters
and result), called as it is, for a model whose layers are LATENT attention
of two kinds: under a learned selection on the full kind's table, and at
sizes of their own under a sliding window on the window kind's ring
(``models/dots3.py``).  What is this file's is the comparison.

``serve_latent.check_logits`` builds a cache of its own with ONE table;
this model's hook takes a table a kind, and what has to be held to the
reference is the engine's own pool at the cell's sizes.  So the comparison
runs ON THE ENGINE'S OWN CACHE, taken before any request is admitted and
handed back (every block free again): ``score_rows`` seeded sequences of
``score_tokens`` positions each, row ``i`` in table row ``i`` — the full
kind's blocks ``1 + i * nbper ..``, the window kind's a ``WindowRing`` of
the engine's own width and block, advanced before every call by the call the
scheduler makes — the first ``WIDE_FROM`` positions (past ``index_topk`` and
past the window) through the engine's first prefill rung beside pad rows,
the rest through its widest, then 16 decode steps at all ``slots`` rows;
logits compared after every call.

**Discrete choices** are ``serve_longctx``'s and ``serve_latent``'s
treatment together (``reference_keye``): every routed layer takes the top-8
of 256 experts and every full layer the top-2,048 keys; a bf16 engine and a
float32 reference break near-ties differently, so where the engine is not
float32 its comparison path also returns the sets it chose
(``forward_cached(choices=True)``: the keys packed to bits on the device) and
the reference computes its logits on THOSE sets while it still makes its
own, and the comparison holds the two to each other by the limits below.  A
float32 engine (the rehearsal, the CPU tests) is compared plainly: there the
two sides must make the SAME choices.

**Controls.**  ``python3 -m chipbench.drivers.serve_sparselatent --workload
<cell> --seed N [--rehearse]`` builds the engine as the cell does and puts
the plain reference and each shortcut ``VARIANTS`` names through
:func:`check_logits`, one reading of the engine for all: a JSON line each,
exit 0 only if the plain reference is ``ok`` and every variant is not.
"""

from __future__ import annotations

import contextlib
import sys
from typing import Any, Dict, Optional, Tuple

import numpy as np

from chipbench import costs, reference_dots3, traffic
from chipbench.drivers import serve_closed, serve_latent, serve_mixedattn

KIND = "serve_sparselatent"

#: relative RMSE of the engine's logits against the float32 reference, by the
#: dtype served (PERF.md section 6, PR 61, has every reading).  fp32 (the
#: rehearsal, the CPU tests; compared plainly): the two sides make the same
#: discrete choices and differ by rounding order alone.  bf16 (the reference
#: on the engine's own sets): a bf16 engine reads 2.77-2.78 % over its seeds
#: on the chip (five layers of bf16 matmul and residual rounding at d 5,120,
#: two bf16 latent pools read absorbed); the reference with its scores
#: rounded to bfloat16 and its output summed in bfloat16 reads 3.94 %, with
#: everything cached (``c``, ``k_r``, ``kI``) rounded to float8 e4m3 — the
#: nearest precision below — 7.39 %: 3.3 % lies between, a fifth above the
#: engine and a sixth below the nearer control
LOGIT_REL_RMSE = {"bf16": 3.3e-2, "fp32": 1e-4}
SCORE_DECODE_STEPS = serve_closed.SCORE_DECODE_STEPS
#: positions of a compared sequence that go through the FIRST prefill rung
#: (beside pad rows) before the widest takes over: whole calls of both
WIDE_FROM = 4096
#: share of the reference's own chosen keys / experts that the engine chose
#: too, and how far from the reference's cut-off a disagreeing entry may lie
#: (a key's score as a share of its query's largest; an expert's as a share
#: of its token's largest, in the mean and at the furthest).  Each between
#: what a bf16 engine reads on the chip over its seeds (keys 0.99143-0.99144,
#: furthest key 0.035-0.036; experts 0.9765-0.9768 — 256 sigmoid scores and a
#: bias, top 8: nearer ties than a softmax's top 4 —, 0.00096-0.00097 in the
#: mean, 0.0137-0.0172 at the furthest) and what the float8 control reads
#: (0.9716, 0.104; 0.9429, 0.00276, 0.0367); a key chosen from the hidden
#: state reads keys 0.286 with LOGITS unmoved (they are computed on the
#: engine's sets): only the key agreement catches it
KEY_AGREEMENT, KEY_GAP = 0.983, 0.065
EXPERT_AGREEMENT, EXPERT_GAP, EXPERT_GAP_MAX = 0.96, 0.0018, 0.03
#: THE WINDOW'S EDGE is held by a paired comparison: a key more or less in a
#: 513-key window moves the logits by a sixth of what bf16 rounding does
#: (0.0048 against 0.0277 of their deviation, my chip runs, PR 61), so no
#: limit on the logits can tell it.  Over the first ``4 x window`` positions
#: the engine's logits must lie CLOSER to the reference than to each of its
#: twins whose window is one key shorter / longer: ``(ms(engine, twin) -
#: ms(engine, reference)) / ms(twin, reference)`` (mean squares; the
#: engine's own rounding is in both terms and cancels) reads +1 for an engine
#: whose window is the reference's and -1 for one whose window is the twin's
#: (a bf16 engine reads 0.80-1.23 over eight runs on the chip, the one-key-short
#: control -0.90)
WINDOW_EDGE = 0.25
#: the shortcuts the comparison must refuse, each by at least one limit
VARIANTS = reference_dots3.VARIANTS[1:]


def paged_choices(srv, tokens: np.ndarray, n_decode: int
                  ) -> Tuple[np.ndarray, Dict[str, np.ndarray], list, int]:
    """The sequences ``tokens [rows, S]`` side by side through THE ENGINE'S
    OWN CACHE and both its tables (module docstring) -> ``(logits [rows,
    calls, V], {"experts": int32 [routed layers, rows, S, k], "keys": uint8
    [full layers, rows, S, ceil(S / 8)]}, the position each call's logits
    belong to, window blocks released on the way)``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.paged import WindowRing

    hooks = srv.engine.module.decode_hooks
    fwd, prepare = hooks["forward_cached"], srv.engine._prepare
    n, s = tokens.shape
    if n > srv.slots or s > srv.max_seq_len:
        raise ValueError(f"{n} x {s} compared positions over {srv.slots} "
                         f"slots of {srv.max_seq_len}")
    nbper = srv._nbper
    # (table rows: a decode step's slots, a prefill rung's rows)
    most = max(srv.slots, *(rows for rows, _ in srv._rungs))
    ring = WindowRing(most, srv._ring.window, srv._prefill_width,
                      srv._ring.block_size)
    full = np.zeros((most, nbper), np.int32)
    full[:n] = 1 + np.arange(n * nbper).reshape(n, nbper)
    cache, srv._cache = srv._cache, None
    nbytes = -(-s // 8)

    def outputs(logits, cache, made):
        keys = jnp.packbits(made["keys"][..., :nbytes * 8], axis=-1)
        return logits, srv._constrain_pool(cache), made["experts"], keys

    def prefill(params, cache, ids, bt, base, valid):
        return outputs(*fwd(prepare(params), ids, cache, base, lengths=valid,
                            block_tables=bt, choices=True))

    def decode(params, cache, tok, bt, lengths):
        return outputs(*fwd(prepare(params), tok, cache, 0, lengths=lengths,
                            block_tables=bt, choices=True))

    prefill = jax.jit(prefill, donate_argnums=srv._donate())
    decode = jax.jit(decode, donate_argnums=srv._donate())

    def tables(rows, first_query: int, upto: int):
        """Both kinds' tables for a call of ``rows`` table rows whose first
        ``n`` are the sequences (the others pads: all scratch)."""
        for row in range(n):
            ring.advance(row, first_query, upto)
        return {"full": jnp.asarray(full[:rows]),
                "window": jnp.asarray(ring.tables[:rows])}

    params = srv.engine.params
    n_prefill = s - n_decode
    got, experts, keys, at = [], [], [], []
    with srv._tp_ctx():
        base = 0
        while base < n_prefill:
            j, width = srv._rungs[0 if base < WIDE_FROM else -1]
            j = max(j, n)
            valid = min(width, n_prefill - base)
            ids = np.zeros((j, width), np.int32)
            ids[:n, :valid] = tokens[:, base:base + valid]
            live = np.arange(j) < n
            logits, cache, e, k = prefill(
                params, cache, jnp.asarray(ids), tables(j, base, base + valid),
                jnp.asarray(np.where(live, base, 0), jnp.int32),
                jnp.asarray(np.where(live, valid, 0), jnp.int32))
            got.append(np.asarray(logits, np.float32)[:n])
            experts.append(np.asarray(e)[:, :n, :valid])
            keys.append(np.asarray(k)[:, :n, :valid])
            base += valid
            at.append(base - 1)
        live = np.arange(srv.slots) < n
        for p in range(n_prefill, s):
            tok = np.zeros((srv.slots, 1), np.int32)
            tok[:n, 0] = tokens[:, p]
            logits, cache, e, k = decode(
                params, cache, jnp.asarray(tok), tables(srv.slots, p, p + 1),
                jnp.asarray(np.where(live, p, 0), jnp.int32))
            got.append(np.asarray(logits, np.float32)[:n])
            experts.append(np.asarray(e)[:, :n])
            keys.append(np.asarray(k)[:, :n])
            at.append(p)
    srv._cache = cache
    return np.stack(got, axis=1), {
        "experts": np.concatenate(experts, axis=2),
        "keys": np.concatenate(keys, axis=2)}, at, ring.released


def check_logits(job, srv, variant: Optional[str] = None,
                 engine=None) -> Dict[str, Any]:
    """Engine vs the family's plain reference on ``score_rows`` seeded
    sequences of ``score_tokens`` positions (module docstring); with
    ``variant``, vs that shortcut of the reference (a control: ``ok`` has to
    come out false).  ``engine``: the engine's side, ``paged_choices``'s
    result, where a caller has it already."""
    a = costs.arch(job.config)
    rows, s = int(job.traffic["score_rows"]), int(job.traffic["score_tokens"])
    rng = np.random.default_rng(traffic.seed_sequence(job.seed).spawn(1)[0])
    tokens = rng.integers(0, a["vocab"], (rows, s)).astype(np.int32)
    engine = engine or paged_choices(srv, tokens, SCORE_DECODE_STEPS)
    got, chosen, at, released = engine
    out: Dict[str, Any] = {"engine": engine}
    agreed = True
    if job.config["dtype"] != "fp32":
        want, agreement = job.family.logits(
            job.config, srv.engine.params, tokens, at=at, forced=chosen,
            variant=variant)
        agreed = agreement["keys"] >= KEY_AGREEMENT \
            and agreement["key_gap"] <= KEY_GAP \
            and agreement["experts"] >= EXPERT_AGREEMENT \
            and agreement["expert_gap"] <= EXPERT_GAP \
            and agreement["expert_gap_max"] <= EXPERT_GAP_MAX
        out.update(agreement)
        job.note("reference on the engine's sets; of the reference's own: "
                 f"keys {agreement['keys']:.5f} in the engine's (floor "
                 f"{KEY_AGREEMENT}), furthest disagreeing key "
                 f"{agreement['key_gap']:.5f} of its query's largest score "
                 f"from the cut-off (limit {KEY_GAP}); experts "
                 f"{agreement['experts']:.5f} (floor {EXPERT_AGREEMENT}), a "
                 f"disagreeing expert {agreement['expert_gap']:.5f} of its "
                 f"token's largest score from the cut-off in the mean (limit "
                 f"{EXPERT_GAP}), {agreement['expert_gap_max']:.5f} at the "
                 f"furthest (limit {EXPERT_GAP_MAX}; by layer "
                 f"{agreement['expert_gap_max_by_layer']})")
    else:
        want = job.family.logits(job.config, srv.engine.params, tokens,
                                 at=at, variant=variant)
    want = np.asarray(want, np.float32)
    rel = serve_latent._rel_rmse(got, want)
    tol = LOGIT_REL_RMSE[job.config["dtype"]]
    edges = window_edge(job, srv, tokens, chosen, at, got, want, variant)
    out["window_edge"] = edges
    job.note(f"comparison: {rows} x {s} tokens on the engine's own cache "
             f"(blocks {srv.block_size} / {srv._ring.block_size}), "
             f"{len(at)} calls a row of which "
             f"{sum(p >= a['index_topk'] for p in at)} select and "
             f"{sum(p >= a['window'] for p in at)} lie past the window; the "
             f"ring released {released} blocks on the way: relative RMSE "
             f"{rel}")
    return {"ok": bool(np.isfinite(got).all() and agreed and rel <= tol
                       and min(edges) >= WINDOW_EDGE),
            "logit_rel_rmse": rel, "tolerance": tol, **out,
            "window_blocks_released": released,
            "positions": int(got.shape[0] * got.shape[1])}


def window_edge(job, srv, tokens, chosen, at, got, want, variant):
    """``WINDOW_EDGE``'s two readings (one key shorter, one longer), over
    the calls whose logits lie in the first ``4 x window`` positions (rounded
    up to 64): the twins are computed on those positions alone — the model
    is causal — on the engine's own sets where the engine is not float32."""
    a = costs.arch(job.config)
    n = min(tokens.shape[1], -(-4 * a["window"] // 64) * 64)
    calls = [i for i, p in enumerate(at) if p < n]
    forced = None
    if job.config["dtype"] != "fp32":
        forced = {"experts": chosen["experts"][:, :, :n],
                  "keys": chosen["keys"][:, :, :n, :-(-n // 8)]}
    mine, ref = got[:, calls], want[:, calls]
    readings = []
    for shift in (-1, 1):
        twin = job.family.logits(
            job.config, srv.engine.params, tokens[:, :n],
            at=[at[i] for i in calls], forced=forced, variant=variant,
            window_shift=shift)
        twin = np.asarray(twin[0] if forced is not None else twin, np.float32)
        apart = float(np.mean((twin - ref) ** 2))
        readings.append(
            (float(np.mean((mine - twin) ** 2))
             - float(np.mean((mine - ref) ** 2))) / apart if apart else 0.0)
    job.note(f"the window's edge over the first {n} positions ({len(calls)} "
             f"calls): the engine lies {readings[0]:.3f} / {readings[1]:.3f} "
             "of the way from the reference's twin of one key less / more "
             f"to the reference (+1: on it; floor {WINDOW_EDGE})")
    return readings


@contextlib.contextmanager
def _in_serve_latent():
    """``serve_latent``'s loop, controls and command line with this file's
    comparison and shortcuts in place of its own."""
    short = serve_latent.check_logits, serve_latent.VARIANTS
    serve_latent.check_logits, serve_latent.VARIANTS = check_logits, VARIANTS
    try:
        yield
    finally:
        serve_latent.check_logits, serve_latent.VARIANTS = short


def run(job) -> Dict[str, Any]:
    """``serve_latent.run`` with the comparison above, then both pools."""
    with _in_serve_latent():
        out = serve_latent.run(job)
    serve_mixedattn.both_pools(out)
    return out


def main(argv=None) -> int:
    """``serve_latent.main`` (the controls, module docstring) likewise."""
    with _in_serve_latent():
        return serve_latent.main(argv)


if __name__ == "__main__":
    sys.exit(main())
