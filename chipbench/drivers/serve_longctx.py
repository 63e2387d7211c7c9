"""``kind: serve_longctx`` — ``serve_closed``'s closed loop (N callers, each
waiting for its reply before it sends the next request, one
``ServingEngine`` driven by hand; the same stamps, counters and result)
whose comparison with the plain reference covers LONG sequences.

``serve_closed.check_logits`` scores ``2 x prefill_chunk + 16`` positions,
272 at the default chunk.  A model with learned sparse attention (an
indexer choosing ``topk`` = 2,048 keys a query) never selects on so short
a sequence, and ``correct`` would pass with the indexer wired to nothing.
Here the traffic file says how much is compared:

    score_rows    sequences compared (seeded token ids)
    score_tokens  positions of each: chunked prefill of ``score_tokens -
                  16`` through the engine's own paged path, then 16 decode
                  steps, logits compared after every chunk and every step

With ``score_tokens`` = 3 x ``topk`` two thirds of the compared positions
attend a selected set, in prefill chunks (each query a set of its own,
chunks before, across and past ``topk``) and in decode steps.  Everything
else — set-up, warm-in, the window, the drain — is ``serve_closed.run``,
called as it is with this file's comparison in place of its own; a later
``benchmark`` issue folds the two drivers into one with the length as data.

**Discrete choices.**  This model makes two in every layer: the top-8 of
128 experts and the top-2,048 keys.  A bf16 engine and a float32 reference
break near-ties differently, and over thousands of positions many are near:
the plain comparison reads 22.5 % on the chip with every kernel agreeing
with its reference and a float32 engine at the same lengths reading 2e-6
(PERF.md section 6, PR 32).  So where the engine is not float32, the
engine's comparison path also returns the sets it chose
(``forward_cached(choices=True)``) and the reference computes its logits on
THOSE sets — while it still makes its own choices, and the comparison holds
the two to each other: of the reference's own keys (queries past ``topk``)
at least ``KEY_AGREEMENT`` must be in the engine's sets, of its experts
``EXPERT_AGREEMENT``, and no disagreeing entry may lie further from the
reference's cut-off than rounding explains (``KEY_GAP`` / ``EXPERT_GAP``).
An indexer that is skipped, computed in a lower precision, or fed another
block's keys disagrees wholesale and fails these, whatever the logits say.
A float32 engine (the rehearsal, the CPU tests) is compared plainly: there
the two sides must make the SAME choices.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from chipbench import costs, traffic
from chipbench.drivers import serve_closed

KIND = "serve_longctx"

#: relative RMSE of the engine's logits against the float32 reference, by
#: the dtype served.  fp32 (the rehearsal, the CPU tests; compared
#: plainly): the two sides make the same discrete choices and differ by
#: rounding order alone (2e-7 on the CPU, 2e-6 on the chip at 6,144
#: positions).  bf16 (the reference on the engine's own sets):
#: ``serve_closed``'s 5 % — what is left is 6 layers of bf16 matmul and
#: residual rounding, 0.92-0.94 % on the chip; a wrong cache block, a
#: scrambled head or int8 anywhere lands far above (PERF.md section 6,
#: PR 32, has every reading).
LOGIT_REL_RMSE = {"bf16": 5e-2, "fp32": 1e-4}
SCORE_DECODE_STEPS = serve_closed.SCORE_DECODE_STEPS
#: share of the reference's own chosen keys (queries past ``topk``) / experts
#: that the engine chose too: ISSUE 32's floors.  On the chip (three seeds;
#: my chip runs, PR 32) a bf16 engine reads keys 0.99433-0.99439 and experts
#: 0.99146-0.99158; with the indexer's queries, key and weights rounded to
#: float8 e4m3 — the nearest precision below — keys 0.98506, with the
#: router's input so rounded experts 0.96569, with the indexer skipped keys
#: 0.54734 (and the LOGITS still within 0.9 %: only these catch it)
KEY_AGREEMENT, EXPERT_AGREEMENT = 0.99, 0.97
#: how far from the reference's cut-off a disagreeing entry may lie: a key's
#: score as a share of the query's largest score (bf16: 0.033-0.040 over the
#: three seeds; float8 indexer 0.090, router 0.134, skipped 1.79), an
#: expert's probability as a share of the token's largest (bf16: 0.022-0.025;
#: float8 router 0.116)
KEY_GAP, EXPERT_GAP = 0.065, 0.06


def paged_choices(srv, tokens: np.ndarray, n_decode: int
                  ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """``serve_closed.paged_logits`` (the same chunked prefill and decode
    steps on the engine's weights, cache layout and decode hooks) that also
    brings back the engine's choices: ``{"experts": int32 [L, B, S, k],
    "keys": uint8 [L, B, S, ceil(max_seq / 8)]`` (one bit a key,
    ``numpy.packbits``)``}``."""
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

    params, rows = srv.engine.params, []
    chosen = {"experts": [], "keys": []}

    def keep(made, real):
        chosen["experts"].append(np.asarray(made["experts"])[:, :, :real])
        chosen["keys"].append(np.packbits(
            np.asarray(made["keys"])[:, :, :real], axis=-1))

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
            keep(made, valid)
        for p in range(n_prefill, s):
            logits, cache, made = decode(params, cache,
                                         jnp.asarray(tokens[:, p:p + 1]),
                                         jnp.full((b,), p, jnp.int32))
            rows.append(np.asarray(logits, np.float32))
            keep(made, 1)
    return np.stack(rows, axis=1), {
        k: np.concatenate(v, axis=2) for k, v in chosen.items()}


def check_logits(job, srv) -> Dict[str, Any]:
    """Engine vs the family's plain reference on ``score_rows`` seeded
    sequences of ``score_tokens`` positions (module docstring)."""
    vocab = costs.arch(job.config)["vocab"]
    chunk = srv.prefill_chunk
    rows, s = int(job.traffic["score_rows"]), int(job.traffic["score_tokens"])
    if s > srv.max_seq_len:
        raise ValueError(f"score_tokens {s} over max_seq_len "
                         f"{srv.max_seq_len}")
    rng = np.random.default_rng(traffic.seed_sequence(job.seed).spawn(1)[0])
    tokens = rng.integers(0, vocab, (rows, s)).astype(np.int32)
    n_prefill = s - SCORE_DECODE_STEPS
    at = [min(base + chunk, n_prefill) - 1
          for base in range(0, n_prefill, chunk)]
    at += list(range(n_prefill, s))
    got, chosen = paged_choices(srv, tokens, SCORE_DECODE_STEPS)
    agreed = True
    if job.config["dtype"] != "fp32":
        # the prefix of the key sets that this comparison's positions hold
        chosen["keys"] = chosen["keys"][..., :-(-s // 8)]
        want, agreement = job.family.logits(
            job.config, srv.engine.params, tokens, at=at, forced=chosen)
        agreed = agreement["keys"] >= KEY_AGREEMENT \
            and agreement["experts"] >= EXPERT_AGREEMENT \
            and agreement["key_gap"] <= KEY_GAP \
            and agreement["expert_gap"] <= EXPERT_GAP
        job.note("reference on the engine's sets; of the reference's own: "
                 f"keys {agreement['keys']:.5f} in the engine's (floor "
                 f"{KEY_AGREEMENT}), experts {agreement['experts']:.5f} "
                 f"(floor {EXPERT_AGREEMENT}); furthest disagreeing key "
                 f"{agreement['key_gap']:.5f} of its query's largest score "
                 f"from the cut-off (limit {KEY_GAP}), expert "
                 f"{agreement['expert_gap']:.5f} of its token's largest "
                 f"probability (limit {EXPERT_GAP})")
    else:
        want = job.family.logits(job.config, srv.engine.params, tokens,
                                 at=at)
    want = np.asarray(want, np.float32)
    rmse = float(np.sqrt(np.mean((got - want) ** 2)))
    rel = rmse / float(np.std(want))
    tol = LOGIT_REL_RMSE[job.config["dtype"]]
    topk = costs.arch(job.config).get("index_topk")
    if topk:
        job.note(f"comparison: {rows} x {s} tokens, {len(at)} positions a "
                 f"row of which {sum(p >= topk for p in at)} attend a "
                 f"selected set (past topk {topk})")
    return {"ok": bool(np.isfinite(got).all() and rel <= tol and agreed),
            "logit_rel_rmse": rel, "tolerance": tol,
            "positions": int(got.shape[0] * got.shape[1])}


def run(job) -> Dict[str, Any]:
    """``serve_closed.run`` with the comparison above."""
    short = serve_closed.check_logits
    serve_closed.check_logits = check_logits
    try:
        return serve_closed.run(job)
    finally:
        serve_closed.check_logits = short
