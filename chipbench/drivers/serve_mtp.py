"""``kind: serve_mtp`` — ``serve_latent``'s closed loop with its settled
start (N callers, each waiting for its reply before it sends the next request, one
``ServingEngine`` driven by hand; the same stamps, counters and result),
called as it is, for a model whose OWN multi-token-prediction module drafts
for the engine's verify round (``models/glm_dsa.py``; the cell's sizing asks
for it: ``spec_tokens`` 1, ``draft`` ``"self"``) over a latent pool under a
learned selection, behind a shared prompt prefix the trie serves.  What is
this file's is the comparison.

**The comparison** runs ON THE ENGINE'S OWN CACHE, taken before any request
is admitted and handed back (every block free again): ONE seeded sequence of
``score_tokens`` positions whose first ``score_prefix`` are the shared prefix.

1. ROW A, the first admission: the prefix alone through the engine's prefill
   rungs, the trunk's forward and the module's in one call as the engine's
   prefill program makes them — the module's entry at position ``t`` from the
   hidden state at ``t`` and token ``t + 1``; at the prefix's LAST position
   row A's own next token, which is NOT the sequence's.
2. ROW B, the sequence admitted again: its table holds row A's blocks as the
   trie hands them to a self-drafting engine — all the prefix's blocks but
   the last — and blocks of its own from there on; it prefills from the
   shared blocks' end through both rungs up to ``ROUNDS + 1`` positions before
   the sequence's end.
3. ``ROUNDS`` verify rounds at all ``slots`` rows (row B real, the others
   idle): window ``[x_p, x_{p+1}]`` at base ``p``, every position's logits and
   hidden state, then the module over both positions — teacher-forced: the
   "draft" is the sequence's own next token and each round commits ONE
   position, so the next round writes ``p + 1`` again (a rolled-back
   position) and the second position's logits are those of the sequence that
   ends in the draft.

Compared with the float32 reference (``chipbench/reference_glm5.py``): the
trunk's logits and the module's at every call's last position (but row A's
call that reaches into the block row B makes again), at BOTH window positions
of every round, and the module's CACHE ROW (latent and rotated
key) at the prefix's last position, read through row B's table.

**Discrete choices** are ``serve_sparselatent``'s treatment: where the engine
is not float32 its comparison path also returns the expert sets and the key
sets it chose (the second window positions' as the SECOND position made
them) and the reference computes its logits on THOSE sets while it still
makes its own; the comparison holds the two to each other by the limits
below — the keys of the second window positions also on their own
(``keys_second``).  A float32 engine (the rehearsal, the CPU tests) is
compared plainly.

**Controls.**  ``python3 -m chipbench.drivers.serve_mtp --workload <cell>
--seed N [--rehearse]`` builds the engine as the cell does and puts the
plain reference and each shortcut of ``VARIANTS`` through
:func:`check_logits`, one reading of the engine for all: a JSON line each,
exit 0 only if the plain reference is ``ok`` and every variant is not.
``trie_keeps_last`` is a shortcut of the ENGINE's side: the module's row at
the prefix's last position read through row A's last block, as a trie hit
that kept it would.
"""

from __future__ import annotations

import contextlib
import sys
from typing import Any, Dict, Optional

import numpy as np

from chipbench import costs, reference_glm5, traffic
from chipbench.drivers import serve_closed, serve_latent

KIND = "serve_mtp"

#: relative RMSE of the engine's logits against the float32 reference, by the
#: dtype served, the trunk's and the module's each, over all compared
#: positions and over the second window positions alone (PERF.md section 6,
#: PR 64, has every reading).  fp32 (the rehearsal, the CPU tests; compared
#: plainly): the two sides make the same discrete choices and differ by
#: rounding order alone.  bf16 (the reference on the engine's own sets): a
#: bf16 engine reads 1.58-1.69 % for the trunk and 1.33-1.34 % for the module
#: over its seeds on the chip (five layers of bf16 matmul and residual rounding at d 6,144
#: and bf16 latent pools read absorbed; the module one block on top of the
#: trunk's hidden state); the reference with everything cached (``c``,
#: ``k_r``, ``kI``; trunk and module) rounded to float8 e4m3 — the nearest
#: precision below — reads 3.12 % and 2.55 %: the limits lie between, a
#: third above the engine and a quarter below the control
LOGIT_REL_RMSE = {"bf16": {"trunk": 2.25e-2, "module": 1.85e-2},
                  "fp32": {"trunk": 1e-4, "module": 1e-4}}
#: relative RMSE of the module's cache row at the prefix's last position
#: (576 values — ONE row, so it wanders more than a mean over thousands of
#: logits): a bf16 row reads 0.93-1.20 % against the float32 one over its
#: seeds on the chip, the float8 control 2.11 %, a row made from another
#: request's next token (``trie_keeps_last``) 94 %
ROW_REL_RMSE = {"bf16": 1.65e-2, "fp32": 1e-4}
#: verify rounds of the comparison
ROUNDS = serve_closed.SCORE_DECODE_STEPS
#: calls of each row that go through the FIRST prefill rung (beside pad rows)
#: before the widest takes over
NARROW_CALLS = 2
#: share of the reference's own chosen keys / experts that the engine chose
#: too, and how far from the reference's cut-off a disagreeing entry may lie
#: (``serve_sparselatent`` has the definitions); ``keys_second`` is the key
#: agreement over the second window positions alone.  Each between what a
#: bf16 engine reads over its seeds on the chip (keys 0.99250-0.99252, of the
#: second positions 0.98921-0.98965, furthest key 0.030-0.036; experts
#: 0.98717-0.98752 — 256 sigmoid scores and a bias, top 8 —, 0.00044-0.00045
#: in the mean, 0.0058-0.0074 at the furthest, an extreme of ~400,000 draws) and what the float8 control reads (0.97997,
#: 0.9719, 0.083; 0.97432, 0.00096, 0.0155); a second window position that
#: takes the first one's set reads ``keys_second`` 0.200 with LOGITS unmoved
#: (they are computed on the engine's sets): only this agreement catches it
KEY_AGREEMENT, KEY_GAP, KEY_AGREEMENT_SECOND = 0.9865, 0.055, 0.981
EXPERT_AGREEMENT, EXPERT_GAP, EXPERT_GAP_MAX = 0.981, 0.0007, 0.0115
#: the shortcuts the comparison must refuse, each by at least one limit
VARIANTS = reference_glm5.VARIANTS[1:] + ("trie_keeps_last",)


def engine_side(srv, tokens: np.ndarray, prefix: int,
                vocab: int) -> Dict[str, Any]:
    """The sequence ``tokens [S + 1]`` (its last entry: the token behind the
    compared positions; ``vocab``: what row A's own next token is drawn
    from) through THE ENGINE'S OWN CACHE as the module docstring says ->
    ``{"trunk" / "module": float32 [calls, V], "at": the position of each,
    "compared": which of them are held to the reference,
    "seconds": the second window positions, "forced": {"experts", "keys"} a
    position, "row" / "row_kept": the module's cache row at the prefix's last
    position through row B's table / through row A's last block}``."""
    import jax
    import jax.numpy as jnp

    hooks = srv.engine.module.decode_hooks
    fwd, prepare = hooks["forward_cached"], srv.engine._prepare
    dfwd = hooks["self_draft"]["forward"]
    s = tokens.size - 1
    bs, nbper = srv.block_size, srv._nbper
    if s > srv.max_seq_len or prefix % bs or prefix // bs < 2 \
            or s - ROUNDS - 1 <= prefix:
        raise ValueError(f"{s} compared positions behind a prefix of "
                         f"{prefix} at blocks of {bs}, max_seq_len "
                         f"{srv.max_seq_len}")
    shared = prefix // bs - 1          # the blocks a trie hit hands over
    row_a = 1 + np.arange(nbper, dtype=np.int32)
    row_b = row_a.copy()
    row_b[shared:] = 1 + nbper + np.arange(nbper - shared)
    cache, srv._cache = srv._cache, None
    nbytes = -(-s // 8)
    trunk_layers = cache["latent"].shape[0] - 1

    def packed(made, more):
        keys = jnp.concatenate([made["keys"], more["keys"][None]])
        return jnp.concatenate([made["experts"], more["experts"][None]]), \
            jnp.packbits(keys[..., :nbytes * 8], axis=-1)

    def call(params, cache, ids, after, bt, base, valid, every):
        """The trunk over a window, then the module over the same positions
        with the token after each: both logits (``every``: at every
        position), the cache, both sides' choices."""
        p = prepare(params)
        logits, cache, made, hidden = fwd(
            p, ids, cache, base, lengths=valid, block_tables=bt,
            all_positions=every, choices=True, hidden=True)
        guess, cache, more = dfwd(
            p, hidden, after, cache, base, lengths=valid, block_tables=bt,
            all_positions=every, choices=True)
        return (logits, guess, srv._constrain_pool(cache),
                *packed(made, more))

    call = jax.jit(call, static_argnums=7, donate_argnums=srv._donate())
    params = srv.engine.params
    most = max(srv.slots, *(rows for rows, _ in srv._rungs))
    out: Dict[str, Any] = {"trunk": [], "module": [], "at": [],
                           "compared": [], "seconds": []}
    experts = np.zeros((trunk_layers, s, 0), np.int32)   # sized at first call
    keys = np.zeros((trunk_layers + 1, s, nbytes), np.uint8)

    def note(lo, hi, e, k):
        nonlocal experts
        if experts.shape[-1] == 0:
            experts = np.zeros((e.shape[0], s, e.shape[-1]), np.int32)
        experts[:, lo:hi], keys[:, lo:hi] = e, k

    def prefill(table, lo, hi, last_after, again=None):
        """Positions ``lo .. hi - 1`` of the sequence through the prefill
        rungs in one table row; ``last_after``: the token after ``hi - 1``;
        ``again``: the position from which a LATER row makes these positions
        once more — a call that reaches past it is not compared: the sets
        handed to the reference there are the later row's (two bf16 calls of
        two shapes break a near-tie differently), and row A's module at the
        prefix's end saw its own next token."""
        nonlocal cache
        after_all = np.append(tokens[lo + 1:hi], last_after)
        base, calls = lo, 0
        while base < hi:
            j, width = srv._rungs[0 if calls < NARROW_CALLS else -1]
            valid = min(width, hi - base)
            ids = np.zeros((j, width), np.int32)
            after = np.zeros((j, width), np.int32)
            ids[0, :valid] = tokens[base:base + valid]
            after[0, :valid] = after_all[base - lo:base - lo + valid]
            bt = np.zeros((j, nbper), np.int32)
            bt[0] = table
            live = np.arange(j) == 0
            logits, guess, cache, e, k = call(
                params, cache, jnp.asarray(ids), jnp.asarray(after),
                jnp.asarray(bt), jnp.asarray(np.where(live, base, 0),
                                             jnp.int32),
                jnp.asarray(np.where(live, valid, 0), jnp.int32), False)
            note(base, base + valid, np.asarray(e)[:, 0, :valid],
                 np.asarray(k)[:, 0, :valid])
            base += valid
            calls += 1
            out["trunk"].append(np.asarray(logits, np.float32)[0])
            out["module"].append(np.asarray(guess, np.float32)[0])
            out["at"].append(base - 1)
            out["compared"].append(again is None or base <= again)

    with srv._tp_ctx():
        prefill(row_a, 0, prefix, (int(tokens[prefix]) + 1) % vocab,
                again=shared * bs)
        n_prefill = s - ROUNDS - 1
        prefill(row_b, shared * bs, n_prefill, tokens[n_prefill])
        live = np.arange(srv.slots) == 0
        bt = np.zeros((srv.slots, nbper), np.int32)
        bt[0] = row_b
        for p in range(n_prefill, s - 1):
            ids = np.zeros((srv.slots, 2), np.int32)
            after = np.zeros((srv.slots, 2), np.int32)
            ids[0], after[0] = tokens[p:p + 2], tokens[p + 1:p + 3]
            logits, guess, cache, e, k = call(
                params, cache, jnp.asarray(ids), jnp.asarray(after),
                jnp.asarray(bt), jnp.asarray(np.where(live, p, 0), jnp.int32),
                jnp.asarray(np.where(live, 2, 0), jnp.int32), True)
            # (the second position's sets as the SECOND position made them:
            # the next round's first position does not overwrite them here)
            note(p, p + 1, np.asarray(e)[:, 0, :1], np.asarray(k)[:, 0, :1])
            note(p + 1, p + 2, np.asarray(e)[:, 0, 1:], np.asarray(k)[:, 0, 1:])
            for i in range(2):
                out["trunk"].append(np.asarray(logits, np.float32)[0, i])
                out["module"].append(np.asarray(guess, np.float32)[0, i])
                out["at"].append(p + i)
                out["compared"].append(True)
            out["seconds"].append(p + 1)
        # the module's row at the prefix's last position, through both tables
        module, at = trunk_layers, (prefix // bs - 1, bs - 1)
        width = hooks["latent_attention"]["width"]
        leaf = cache["latent"]
        assert leaf.shape[2:4] == (1, bs), leaf.shape
        out["row"] = np.asarray(
            leaf[module, row_b[at[0]], 0, at[1], :width], np.float32)
        out["row_kept"] = np.asarray(
            leaf[module, row_a[at[0]], 0, at[1], :width], np.float32)
    srv._cache = cache
    out["trunk"], out["module"] = np.stack(out["trunk"]), \
        np.stack(out["module"])
    out["forced"] = {"experts": experts[:, None], "keys": keys[:, None]}
    return out


def check_logits(job, srv, variant: Optional[str] = None,
                 engine=None) -> Dict[str, Any]:
    """Engine vs the family's plain reference on one seeded sequence of
    ``score_tokens`` positions behind ``score_prefix`` shared ones (module
    docstring); with ``variant``, vs that shortcut (a control: ``ok`` has to
    come out false).  ``engine``: the engine's side, :func:`engine_side`'s
    result, where a caller has it already."""
    a = costs.arch(job.config)
    s, prefix = int(job.traffic["score_tokens"]), \
        int(job.traffic["score_prefix"])
    rng = np.random.default_rng(traffic.seed_sequence(job.seed).spawn(1)[0])
    tokens = rng.integers(0, a["vocab"], s + 1).astype(np.int32)
    engine = engine or engine_side(srv, tokens, prefix, a["vocab"])
    dtype = job.config["dtype"]
    kept = variant == "trie_keeps_last"
    ref_variant = None if kept else variant
    args = dict(at=engine["at"], variant=ref_variant,
                seconds=engine["seconds"], rows_at=[prefix - 1],
                after=tokens[None, s])
    out: Dict[str, Any] = {"engine": engine}
    agreed = True
    if dtype != "fp32":
        want, agreement = job.family.logits(
            job.config, srv.engine.params, tokens[None, :s],
            forced=engine["forced"], **args)
        agreed = agreement["keys"] >= KEY_AGREEMENT \
            and agreement["keys_second"] >= KEY_AGREEMENT_SECOND \
            and agreement["key_gap"] <= KEY_GAP \
            and agreement["experts"] >= EXPERT_AGREEMENT \
            and agreement["expert_gap"] <= EXPERT_GAP \
            and agreement["expert_gap_max"] <= EXPERT_GAP_MAX
        out.update(agreement)
        job.note("reference on the engine's sets; of the reference's own: "
                 f"keys {agreement['keys']:.5f} in the engine's (floor "
                 f"{KEY_AGREEMENT}), of the second window positions "
                 f"{agreement['keys_second']:.5f} (floor "
                 f"{KEY_AGREEMENT_SECOND}), furthest disagreeing key "
                 f"{agreement['key_gap']:.5f} of its query's largest score "
                 f"from the cut-off (limit {KEY_GAP}); experts "
                 f"{agreement['experts']:.5f} (floor {EXPERT_AGREEMENT}), a "
                 f"disagreeing expert {agreement['expert_gap']:.5f} of its "
                 f"token's largest score from the cut-off in the mean (limit "
                 f"{EXPERT_GAP}), {agreement['expert_gap_max']:.5f} at the "
                 f"furthest (limit {EXPERT_GAP_MAX}; by layer "
                 f"{agreement['expert_gap_max_by_layer']})")
    else:
        want = job.family.logits(job.config, srv.engine.params,
                                 tokens[None, :s], **args)
    rel = serve_latent._rel_rmse
    pick = np.asarray(engine["compared"])
    # (a round's two entries lie side by side, the rounds last)
    seconds = np.zeros(len(engine["at"]), bool)
    seconds[len(seconds) - 2 * ROUNDS + 1::2] = True
    trunk = np.asarray(want["trunk"], np.float32)[0]
    module = np.asarray(want["module"], np.float32)[0]
    readings = {
        "trunk": rel(engine["trunk"][pick], trunk[pick]),
        "module": rel(engine["module"][pick], module[pick]),
        "trunk_second": rel(engine["trunk"][seconds], trunk[seconds]),
        "module_second": rel(engine["module"][seconds], module[seconds])}
    row = rel(engine["row_kept" if kept else "row"],
              np.asarray(want["rows"], np.float32)[0, 0])
    tol, row_tol = LOGIT_REL_RMSE[dtype], ROW_REL_RMSE[dtype]
    job.note(f"comparison: {s} positions on the engine's own cache (blocks "
             f"of {srv.block_size}), a prefix of {prefix} prefilled as a "
             f"first row and served to a second through its table all but "
             f"the last block, {len(engine['at']) - 2 * ROUNDS} prefill "
             f"calls and {ROUNDS} rounds: relative RMSE of the trunk's "
             f"logits {readings['trunk']}, of the module's "
             f"{readings['module']} (at the second window positions "
             f"{readings['trunk_second']} / {readings['module_second']}), "
             f"of the module's row at position {prefix - 1} {row}")
    ok = bool(np.isfinite(engine["trunk"]).all()
              and np.isfinite(engine["module"]).all() and agreed
              and readings["trunk"] <= tol["trunk"]
              and readings["trunk_second"] <= tol["trunk"]
              and readings["module"] <= tol["module"]
              and readings["module_second"] <= tol["module"]
              and row <= row_tol)
    return {"ok": ok, "logit_rel_rmse": readings["trunk"],
            "module_rel_rmse": readings["module"],
            "logit_rel_rmse_second": readings["trunk_second"],
            "module_rel_rmse_second": readings["module_second"],
            "module_row_rel_rmse": row, "tolerance": tol["trunk"],
            "module_tolerance": tol["module"], "row_tolerance": row_tol,
            **out, "positions": len(engine["at"])}


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
    """``serve_latent.run`` with the comparison above."""
    with _in_serve_latent():
        return serve_latent.run(job)


def main(argv=None) -> int:
    """``serve_latent.main`` (the controls, module docstring) likewise."""
    with _in_serve_latent():
        return serve_latent.main(argv)


if __name__ == "__main__":
    sys.exit(main())
