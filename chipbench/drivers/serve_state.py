"""``kind: serve_state`` — ``serve_latent``'s closed loop with a SETTLED start
(N callers, each waiting for its reply before it sends the next request, one
``ServingEngine`` driven by hand; the same stamps, counters and result) for
a model that keeps a RECURRENT STATE A SLOT beside a block-paged pool: gated
delta-rule layers (``ops/delta_rule.py``) whose whole past is a float32
matrix a head a slot, latent-attention layers on the latent kind's one leaf,
and an expert layer that holds a share of its experts
(``models/kimi_linear.py``).

``correct`` rests on TWO comparisons with the family's plain float32
reference, one of the tokens the TIMED engine served and one of logits.

**The served tokens** (:func:`check_served`).  Every request the loop
finishes is kept with the slot it ran in.  After the drain the traffic
file's ``served_pairs`` requests that ended inside the window, each with the
request that held ITS SLOT just before it, go through the reference teacher
forced (prompt + reply, every position), and every reply token — the
prefill's first token and each decode step's, as ``srv.submit`` /
``srv.step`` made them at the cell's slots, under lookahead, among the
other callers' rows — is held to the reference's logits at its position by
REPLAYING the draw: the sampler is counter-keyed (``fold_in(fold_in(
PRNGKey(seed), 1), tokens emitted)``, one Gumbel race over the nucleus), so
the reference draws with the request's own key from its own logits at the
request's temperature and ``top_p``, and a served token either is that draw
or lost the reference's race by a margin.  A request at a time: the share of
its tokens that ARE the reference's draw (``SERVED_REPLAY``, a floor), the
share outside the reference's nucleus (``SERVED_OUTSIDE``) and the mean
margin in nats (``SERVED_GAP``).  The reference makes its own expert
choices here (the timed programs return tokens, nothing else), so a bf16
engine's near-ties cost it some agreement; a wrong slot's state, a state an
idle row's lane advanced, or a row fed another's token costs it nearly all.

What it is blind to: a fault the size of a rounding (the reference rounded
to bfloat16 in its state replays as well as the sound one), and one a
prompt of 512 tokens forgets before the reply begins (a reset dropped).
Those are the second comparison's.

**The logits** (:func:`check_logits`), of the MODEL's programs at operands
of its own, not of the engine's calls.  ``serve_latent.paged_choices`` gives
every compared sequence a row and blocks of its own; a state that is not
reset when a sequence enters a slot would pass it.  Here the traffic file's
``score_rows`` sequences of ``score_tokens`` positions go ONE AFTER THE
OTHER through the SAME row (slot 0 of a ``[prefill_batch, chunk]`` call whose
other rows are pads, then row 0 of a decode call whose other rows are idle)
and the same latent blocks: chunked prefill of ``score_tokens - 16``
positions through the engine's own paged + state path
(``forward_cached(block_tables={"full", "slot"})``, the engine's weights,
block size, chunk and decode hooks), then 16 decode steps, logits compared
after every chunk and every step against the family's plain float32
reference.  The relative RMSE is taken over each sequence's prefill
positions and over its decode positions apart, each against the one limit:
a fault of the one-token kernel cannot hide among the chunks, nor one of the
second sequence (the reused slot) behind the first.

**Discrete choices** are ``serve_latent``'s: a bf16 engine and a float32
reference break near-ties of the top-8 of 256 differently, so where the
engine is not float32 its comparison path also returns the expert sets it
chose and the reference computes its logits on THOSE sets while it still
makes its own choices, which are held to the engine's by three limits
(``EXPERT_AGREEMENT``, ``EXPERT_GAP``, ``EXPERT_GAP_MAX``: below).  A
float32 engine (the rehearsal, the CPU tests) is compared plainly.

**Controls.**  ``python3 -m chipbench.drivers.serve_state --workload <cell>
--seed N [--seconds S] [--rehearse]`` runs the cell as :func:`run` does and
puts the plain reference and each shortcut ``VARIANTS`` names (the state
kept in bfloat16, the decay dropped, the reset dropped: under it the
reference hands each sampled slot's state from the earlier request to the
later) through BOTH comparisons under the limits below: a JSON line each
that names which comparison refused it, exit 0 only if the plain reference
is ``ok`` in both and every variant is refused by at least one.

The set-up, the stamps, the window and the drain are ``serve_latent.run``'s,
copied here because that function calls its own comparison (a later
``benchmark`` issue folds the serving drivers into one, PERF.md section 7
(30)); the counters gain what the state kind's readers take
(``state_bytes``, ``block_bytes_all_layers``, ``served_*``).
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

from chipbench import costs, reference_kimi_linear, traffic
from chipbench.drivers import serve_closed

KIND = "serve_state"

#: relative RMSE of the engine's logits against the float32 reference, by
#: the dtype served, over each compared sequence's prefill positions and
#: over its decode positions alike.  fp32 (the rehearsal, the CPU tests;
#: compared plainly): the two sides make the same discrete choices and
#: differ by rounding order and by the chunked form's regrouping of the
#: recurrence.  bf16 (the reference on the engine's own expert sets): a bf16
#: engine reads 2.28-2.53 % over the four parts of 21 seeds on the chip
#: (8 layers of bf16 matmul and residual rounding, bf16 projections and
#: convolution tails in front of a float32 rule, a bf16 latent read
#: absorbed); the nearest precision below — the recurrent state rounded to
#: bfloat16 after every token — reads 3.78-4.36 % over the four parts of
#: four seeds: 3.1 % lies between, 22 % above the largest sound reading and
#: 18 % below the smallest unsound one (PERF.md section 6, PR 51, has every
#: reading)
LOGIT_REL_RMSE = {"bf16": 3.1e-2, "fp32": 2e-4}
SCORE_DECODE_STEPS = serve_closed.SCORE_DECODE_STEPS
#: share of the reference's own chosen experts that the engine chose too,
#: and how far from the reference's cut-off the disagreeing experts lie, in
#: the MEAN and at the FURTHEST (a biased score's distance as a share of its
#: token's largest).  The first two lie between what a bf16 engine reads on
#: the chip (0.98051-0.98149; 0.00104-0.00111 over 21 seeds) and what the
#: bfloat16-state control reads there over four (0.96792-0.96984;
#: 0.00175-0.00190): the state's rounding reaches the router through the
#: residual (PERF.md section 6, PR 51).  The furthest is an extreme of
#: ~230,000 draws (sound 0.0114-0.0168) and does NOT refuse that control
#: (0.0198-0.0303): it is there for a fault that moves a few scores far —
#: the decay- and reset-dropped controls read 0.83-0.94
EXPERT_AGREEMENT, EXPERT_GAP, EXPERT_GAP_MAX = 0.976, 0.0014, 0.025
#: the served tokens (module docstring), a request at a time, by the dtype
#: served: the least share that is the reference's own draw, the largest
#: share outside the reference's nucleus and the largest mean margin (nats)
#: by which a served token lost the reference's race.  fp32: the draws ARE
#: the reference's.  bf16, twelve requests of three seeds on the chip
#: (PERF.md section 6, PR 51): replay 0.892-0.930 (a request of 512 tokens
#: wanders by 0.012 around ~0.915: the reference makes its own expert
#: choices here, and a bf16 engine's near-ties cost it a draw in twelve),
#: outside 0.004-0.017, margin 0.016-0.027 (40 more requests of ten later
#: runs: 0.897-0.935 / 0.003-0.018 / 0.013-0.058 — a margin's mean has a
#: tail: two requests of ~630 tokens read 0.049 and 0.058 where the rest
#: stay under 0.032); the decay-dropped control reads 0.145-0.202 /
#: 0.463-0.520 / 1.86-2.14: each limit lies between, several deviations of
#: a 512-token share from the sound side, the margin's four times over the
#: largest sound reading and seven under the least unsound.  What these limits
#: do NOT refuse: the bfloat16-state control (0.872-0.922 / 0.004-0.020 /
#: 0.022-0.044) and the reset-dropped one (the sound readings: a prompt of
#: 512 tokens or more has forgotten the stale state before the first reply
#: token) — rounding-sized faults are :func:`check_logits`'s to refuse; a
#: served token can show a fault of tens of per cent of the logits' spread
SERVED_REPLAY = {"bf16": 0.80, "fp32": 0.99}
SERVED_OUTSIDE = {"bf16": 0.05, "fp32": 0.002}
SERVED_GAP = {"bf16": 0.25, "fp32": 0.002}
#: the sampler's salt for a token draw (``ops/sampling.py``: the key of a
#: request's ``n``-th token is ``fold_in(fold_in(PRNGKey(seed), 1), n)``)
TOKEN_SALT = 1
#: sequences and reply positions are padded to whole multiples of this, so
#: that runs share compiled references
SERVED_PAD = 256
#: the shortcuts the comparison must refuse, each by at least one limit
VARIANTS = reference_kimi_linear.VARIANTS[1:]


def state_choices(srv, tokens: np.ndarray, n_decode: int
                  ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """The sequences of ``tokens [rows, S]`` one after the other through
    row 0 of a small cache of the engine's own kinds (module docstring):
    ``(logits float32 [rows, positions, V], {"experts": int32 [routed
    layers, rows, S, k]})``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import paged_kv

    hooks = srv.engine.module.decode_hooks
    fwd, prepare = hooks["forward_cached"], srv.engine._prepare
    n, s = tokens.shape
    bs, chunk, b = srv.block_size, srv.prefill_chunk, srv.prefill_batch
    nbper = paged_kv.blocks_for(s, bs)
    cache = jax.eval_shape(lambda: hooks["init_cache"](
        1 + nbper, bs, srv.engine._config.jnp_dtype, state_rows=b))
    cache = jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.zeros(a.shape, a.dtype),
                                 srv._pool_sharding), cache)
    table = np.zeros((b, nbper), np.int32)
    table[0] = 1 + np.arange(nbper)
    bt = jnp.asarray(table)
    # row 0 is slot 0; a pad row's slot is out of range
    slot = jnp.asarray([0] + [b] * (b - 1), jnp.int32)

    @jax.jit
    def prefill(params, cache, ids, base, valid):
        return fwd(prepare(params), ids, cache, base, lengths=valid,
                   block_tables={"full": bt, "slot": slot}, choices=True)

    @jax.jit
    def decode(params, cache, tok, lengths):
        return fwd(prepare(params), tok, cache, 0, lengths=lengths,
                   block_tables={"full": bt}, choices=True)

    params, n_prefill = srv.engine.params, s - n_decode
    first = np.arange(b) == 0
    out, chose = [], []
    with srv._tp_ctx():
        for seq in tokens:
            rows, experts = [], []
            for base in range(0, n_prefill, chunk):
                valid = min(chunk, n_prefill - base)
                ids = np.zeros((b, chunk), np.int32)
                ids[0, :valid] = seq[base:base + valid]
                logits, cache, made = prefill(
                    params, cache, jnp.asarray(ids),
                    jnp.asarray(np.where(first, base, 0), jnp.int32),
                    jnp.asarray(np.where(first, valid, 0), jnp.int32))
                rows.append(np.asarray(logits[0], np.float32))
                experts.append(np.asarray(made["experts"])[:, 0, :valid])
            for p in range(n_prefill, s):
                tok = np.zeros((b, 1), np.int32)
                tok[0, 0] = seq[p]
                logits, cache, made = decode(
                    params, cache, jnp.asarray(tok),
                    jnp.asarray(np.where(first, p, 0), jnp.int32))
                rows.append(np.asarray(logits[0], np.float32))
                experts.append(np.asarray(made["experts"])[:, 0])
            out.append(np.stack(rows))
            chose.append(np.concatenate(experts, axis=1))
    return np.stack(out), {"experts": np.stack(chose, axis=1)}


class _StepMarks:
    """The ``cb.step`` span of this driver: a step's seconds are recorded
    one a step (``job.spans.durations``), the profiler's annotation is ONE
    ``cb.step`` over ``EVERY`` consecutive steps (their harvests included).
    A 20 s trace of this cell holds ~2.2 M device operations and ~1.7 M
    idle gaps between them; ``trace_reduce`` gives every gap to the host
    span that covers it by walking all of them, and with a span a step (and
    one a harvest: ~1,100) that walk took 374 s of a 15 s trace's reduction
    (PERF.md section 6, PR 51)."""
    EVERY = 32

    def __init__(self, job):
        self.job, self._open, self._steps, self._t0 = job, None, 0, 0.0

    def __enter__(self):
        import jax

        if self._open is None:
            self._open = jax.profiler.TraceAnnotation("cb.step")
            self._open.__enter__()
            self._steps = 0
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        spans = self.job.spans
        spans.starts.setdefault("cb.step", []).append(self._t0)
        spans.durations.setdefault("cb.step", []).append(
            time.perf_counter() - self._t0)
        self._steps += 1
        if self._steps >= self.EVERY or exc[0] is not None:
            self.close()

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def poll(self, since_open: float) -> None:
        """``job.tracer.poll`` with no annotation open across the trace's
        start or stop."""
        tracer = self.job.tracer
        due = (tracer.state == "before" and since_open >= tracer.start) or (
            tracer.state == "tracing"
            and since_open >= tracer.start + tracer.length)
        if due:
            self.close()
        tracer.poll(since_open)


def _rel_rmse(got, want) -> Optional[float]:
    if not want.size:
        return None
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def check_logits(job, srv, variant: Optional[str] = None,
                 engine=None) -> Dict[str, Any]:
    """Engine vs the family's plain reference on ``score_rows`` seeded
    sequences of ``score_tokens`` positions (module docstring); with
    ``variant``, vs that shortcut of the reference (a control: ``ok`` has
    to come out false).  ``engine``: the engine's side, ``state_choices``'s
    pair, where a caller has it already."""
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
    got, chosen = engine or state_choices(srv, tokens, SCORE_DECODE_STEPS)
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
    chunks = len(at) - SCORE_DECODE_STEPS
    parts = {}
    for row in range(rows):
        parts[f"row{row}.prefill"] = _rel_rmse(got[row, :chunks],
                                               want[row, :chunks])
        parts[f"row{row}.decode"] = _rel_rmse(got[row, chunks:],
                                              want[row, chunks:])
    tol = LOGIT_REL_RMSE[job.config["dtype"]]
    job.note(f"comparison: {rows} x {s} tokens through ONE slot at block "
             f"{srv.block_size}, {len(at)} positions a row ({chunks} chunks "
             f"+ {SCORE_DECODE_STEPS} decode steps): relative RMSE "
             + json.dumps({k: None if v is None else round(v, 6)
                           for k, v in parts.items()}))
    seen = [r for r in parts.values() if r is not None]
    return {"ok": bool(np.isfinite(got).all() and agreed
                       and all(r <= tol for r in seen)),
            "logit_rel_rmse": _rel_rmse(got, want), "tolerance": tol,
            "logit_rel_rmse_parts": parts, **out,
            "positions": int(got.shape[0] * got.shape[1])}


def served_sample(served: List[Dict[str, Any]], window: Tuple[float, float],
                  pairs: int) -> List[Dict[str, Any]]:
    """Of the finished requests (in the order they ended), ``pairs`` that
    ended inside ``window`` — the earliest, in slots of their own — each
    PRECEDED by the request that held its slot before it: ``[a0, b0, a1,
    b1, ...]``.  Where the window ended fewer (a controls run's, a
    rehearsal's), the latest that ended before it.  A request of the
    warm-in, ``cut`` to a fraction of its reply (down to two tokens), is
    neither: a share of so few draws says nothing."""
    before: Dict[Any, Dict[str, Any]] = {}
    inside, earlier = [], []
    for r in served:
        a = before.get(r["slot"])
        before[r["slot"]] = r
        if a is None or r["slot"] is None or a["cut"] or r["cut"]:
            continue
        (inside if window[0] <= r["at"] < window[1] else earlier).append(
            (a, r))
    out, slots = [], set()
    for a, b in inside + earlier[::-1]:
        if b["slot"] not in slots and len(slots) < pairs:
            slots.add(b["slot"])
            out += [a, b]
    return out


def _replay(logits, served, seed, temperature, top_p):
    """One request's reply against the reference's ``logits [n, V]`` at its
    positions: the reference's own draw under the request's key a token
    (module docstring) -> ``(draw == served, served outside the nucleus,
    nats by which served lost the race)``, each ``[n]``."""
    import jax
    import jax.numpy as jnp

    lp = jax.nn.log_softmax(logits / temperature, axis=-1)
    p = jnp.exp(lp)
    # the nucleus: a token stays iff the mass of the strictly more probable
    # ones is below top_p
    order = jnp.sort(p, axis=-1)[:, ::-1]
    larger = jnp.cumsum(order, axis=-1) - order
    least = jnp.min(jnp.where(larger < top_p, order, jnp.inf), axis=-1)
    keep = p >= least[:, None]
    root = jax.random.fold_in(jax.random.PRNGKey(seed), TOKEN_SALT)
    keys = jax.vmap(lambda n: jax.random.fold_in(root, n))(
        jnp.arange(logits.shape[0], dtype=jnp.int32))
    race = lp + jax.vmap(
        lambda k: jax.random.gumbel(k, logits.shape[1:], jnp.float32))(keys)
    draw = jnp.argmax(jnp.where(keep, race, -jnp.inf), axis=-1)
    pick = lambda a, i: jnp.take_along_axis(a, i[:, None], axis=-1)[:, 0]
    return (draw == served, ~pick(keep, served),
            jnp.maximum(pick(race, draw) - pick(race, served), 0.0))


def check_served(job, srv, rows: List[Dict[str, Any]],
                 variant: Optional[str] = None) -> Dict[str, Any]:
    """The tokens the timed engine served for ``rows`` (:func:`served_sample`)
    against the family's plain reference, teacher forced (module docstring);
    with ``variant``, against that shortcut of it (a control)."""
    import jax
    import jax.numpy as jnp

    dtype = job.config["dtype"]
    limits = {"replay": SERVED_REPLAY[dtype], "outside": SERVED_OUTSIDE[dtype],
              "gap": SERVED_GAP[dtype]}
    if not rows:
        job.note("served tokens: no finished request with a known "
                 "predecessor in its slot: nothing to compare")
        return {"ok": False, "rows": [], "limits": limits, "tokens": 0}
    up = lambda n: -(-n // SERVED_PAD) * SERVED_PAD
    # a request's last token is never fed back: prompt + reply[:-1]
    fed = [np.concatenate([r["request"].prompt, r["tokens"][:-1]])
           .astype(np.int32) for r in rows]
    n = len(rows)
    tokens = np.zeros((n, up(max(f.size for f in fed))), np.int32)
    at = np.zeros((n, up(max(len(r["tokens"]) for r in rows))), np.int32)
    replies = np.zeros(at.shape, np.int32)
    for i, (r, f) in enumerate(zip(rows, fed)):
        tokens[i, :f.size] = f
        k = len(r["tokens"])
        at[i, :k] = r["request"].prompt.size - 1 + np.arange(k)
        replies[i, :k] = r["tokens"]
    want = job.family.logits(job.config, srv.engine.params, tokens, at=at,
                             variant=variant,
                             lengths=[f.size for f in fed])
    req = [r["request"] for r in rows]

    @jax.jit
    def replay(want, replies, seeds, temps, topps):
        return jax.lax.map(lambda a: _replay(*a),
                           (want, replies, seeds, temps, topps))

    same, outside, gap = (np.asarray(a) for a in replay(
        want, jnp.asarray(replies),
        jnp.asarray([q.seed for q in req], jnp.uint32),
        jnp.asarray([q.temperature for q in req], jnp.float32),
        jnp.asarray([q.top_p for q in req], jnp.float32)))
    del want
    out, ok = [], True
    for i, r in enumerate(rows):
        k = len(r["tokens"])
        got = {"uid": str(r["uid"]), "slot": r["slot"],
               "prompt": int(r["request"].prompt.size), "tokens": k,
               "replay": float(same[i, :k].mean()),
               "outside": float(outside[i, :k].mean()),
               "gap": float(gap[i, :k].mean())}
        ok &= got["replay"] >= limits["replay"] \
            and got["outside"] <= limits["outside"] \
            and got["gap"] <= limits["gap"]
        out.append(got)
    job.note(f"served tokens{' vs ' + variant if variant else ''}: "
             f"{sum(g['tokens'] for g in out)} tokens of {n} requests, a "
             f"slot's earlier then its later, replayed on the float32 "
             f"reference (floor replay {limits['replay']}, limits outside "
             f"{limits['outside']}, gap {limits['gap']}): " + json.dumps(
                 [{k: round(v, 5) if isinstance(v, float) else v
                   for k, v in g.items()} for g in out]))
    return {"ok": bool(ok), "rows": out, "limits": limits,
            "tokens": sum(g["tokens"] for g in out),
            "replay": min(g["replay"] for g in out),
            "outside": max(g["outside"] for g in out),
            "gap": max(g["gap"] for g in out)}


def run(job, variants: Tuple[str, ...] = ()) -> Dict[str, Any]:
    """``serve_latent.run``'s closed loop — the same set-up, stamps,
    counters, SETTLED start and result — with the two comparisons above.
    ``variants``: shortcuts of the reference to put through both as well
    (:func:`controls`): the result gains ``"controls"``, a line each."""
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
        engine_side = check.pop("engine") if variants else None
        check.pop("engine", None)
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
    served: List[Dict[str, Any]] = []    # every finished request, in order

    def submit(c, cut: float = 1.0) -> None:
        nonlocal failed
        r = next(stream)
        r["max_new_tokens"] = max(1, math.ceil(r["max_new_tokens"] * cut))
        c.seen, c.stamps, c.slot, c.cut = 0, [], None, cut < 1.0
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
                if c.seen == 0:
                    # the slot it runs in: the served sample's pairs
                    c.slot = next((slot for slot, st in srv._active.items()
                                   if st.req.uid == h.uid), None)
                    if c.in_window:
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
                else:
                    served.append({"uid": h.uid, "slot": c.slot, "at": now,
                                   "cut": c.cut, "request": h.request,
                                   "tokens": np.asarray(toks, np.int32)})
                c.handle = None
                if state["close"] is None:
                    submit(c)

    marks = _StepMarks(job)

    def step() -> None:
        with marks:
            srv.step()
            now = time.perf_counter()
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
        marks.poll(since)
        if since >= job.seconds:
            break
    state["close"] = t_close = time.perf_counter()
    marks.close()
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
    # what the window lost to stalled steps: a step (the benchmark's span,
    # its harvest included) over three times the window's median
    steps = job.spans.within("cb.step", t_open, t_close)
    typical = statistics.median(steps)
    slow = [d for d in steps if d > 3 * typical]
    job.note(f"{len(slow)} of {len(steps)} steps in the window took over 3 x "
             f"the median {typical * 1e3:.2f} ms and lost "
             f"{sum(slow) - len(slow) * typical:.3f} s to it; the longest "
             f"{max(steps) * 1e3:.1f} ms")
    job.note(f"window {window:.3f} s: {state['attempted']} requests "
             f"submitted, {len(ttfts)} first tokens, {len(gaps)} gaps, "
             f"{state['tokens']} tokens, {failed} failed; TTFT median "
             f"{medians['ttft_median_ms']} ms, p95 "
             f"{e2e.get('ttft_p95_ms')}; ITL median "
             f"{medians['itl_median_ms']} ms, p95 {e2e.get('itl_p95_ms')}; "
             f"evicted {delta['evicted']}")
    rows = served_sample(served, (t_open, t_close),
                         int(mix["served_pairs"]))
    tokens_served = check_served(job, srv, rows)
    lines = [{"variant": None, "logits_ok": check["ok"],
              "served_ok": tokens_served["ok"], "logits": check,
              "served": tokens_served}]
    for variant in variants:
        a = check_logits(job, srv, variant, engine_side)
        del a["engine"]
        b = check_served(job, srv, rows, variant)
        lines.append({"variant": variant, "logits_ok": a["ok"],
                      "served_ok": b["ok"], "logits": a, "served": b})
    srv.close()
    return {
        "correct": bool(check["ok"] and tokens_served["ok"] and no_compile),
        **({"controls": lines} if variants else {}),
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
                     "logit_rel_rmse": check["logit_rel_rmse"],
                     # the served-token comparison: its worst request
                     "served_tokens": tokens_served["tokens"],
                     "served_replay": tokens_served.get("replay"),
                     "served_outside": tokens_served.get("outside"),
                     "served_gap": tokens_served.get("gap"),
                     "stalled_steps": len(slow),
                     "stalled_s": sum(slow) - len(slow) * typical,
                     # the state kind (``stats()["kv_state"]``): its bytes,
                     # and a block's bytes over every layer that has blocks
                     "state_bytes": (after.get("kv_state") or {}).get(
                         "bytes"),
                     "block_bytes_all_layers": after["kv_pool_bytes"]
                     and (after["kv_pool_bytes"]
                          - ((after.get("kv_state") or {}).get("bytes") or 0))
                     // after["num_blocks"]},
        "samples": {"blocks_in_use": pool_used},
        "devices": list(srv.engine.mesh.devices.flat),
    }



def controls(job) -> bool:
    """The cell as :func:`run` runs it, then the plain reference and every
    shortcut of ``VARIANTS`` through both comparisons, a JSON line each; true
    if the plain reference is ``ok`` in both and every shortcut is refused by
    at least one."""
    held = True
    for line in run(job, VARIANTS)["controls"]:
        ok = line["logits_ok"] and line["served_ok"]
        held &= ok == (line["variant"] is None)
        print(json.dumps({"seed": job.seed, **line}), flush=True)
    return held


def main(argv=None) -> int:
    from deepspeed_tpu.utils.platform import enable_compile_cache

    from chipbench import run as cb

    ap = argparse.ArgumentParser(description=controls.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache(cb.ROOT)
    job = cb.Job(argparse.Namespace(
        seed=args.seed, seconds=args.seconds, rehearse=args.rehearse,
        trace=0, keep_trace=None), cb.load_cell(args.workload, args.rehearse))
    held = controls(job)
    print(json.dumps({"controls_held": held}), flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
