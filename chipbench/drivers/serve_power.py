"""``kind: serve_power`` — ``serve_ssm``'s closed loop with a SETTLED start (N
callers, each waiting for its reply before it sends the next request, one
``ServingEngine`` driven by hand; the same stamps, counters and result) for a
DENSE model whose EVERY layer keeps a recurrent state a slot and which
caches NO token: power-retention layers (``ops/power_retention.py``), a
float32 matrix of the key's degree-2 monomials by the value and its
normaliser a KV head a slot (``models/brumby.py``).  The engine has no paged
pool: there is no block table, no block id and no allocator to drive, and
``block_tables`` is ``{"slot": ...}`` in both programs.

``correct`` rests on ``serve_ssm``'s comparisons with the family's plain
float32 reference — which computes the layer in its ATTENTION form and never
builds the monomials, the state or the normaliser — under THIS cell's
limits.  **The logits** (:func:`check_logits`: ``score_rows`` sequences of
``score_tokens`` positions ONE AFTER THE OTHER through the SAME slot of THE
ENGINE'S OWN CACHE, at the shapes the window's programs have — the first
sequence's prompt through the ``[prefill_batch, prefill_chunk]`` rung beside
pad rows, the second's through the wide row, a sequence's first call a
chunk's tokens with pads behind them — then 16 decode steps at every slot's
row; relative RMSE over each sequence's prefill positions and over its
decode positions apart) in the engine's dtype against the one limit a dtype
AND, where that is not float32, a second time with float32 activations
(``exact``) against ``LOGIT_REL_RMSE["exact"]``: what is float32 by
construction — the state, ``z``, the log-gates, the sums over a chunk —
shows there alone.  The state kind's leaves are float32 whatever the served
dtype and this model has no other, so BOTH passes run on the engine's own
cache and on every rung (``serve_ssm``'s float32 pass needs a float32 pool of
its own and keeps to the narrow rung, the attention kernel's limit); the
float32 pass looks its tokens up in a float32 table of the scored tokens
alone (the whole table in float32 is 3.1 GB, which does not fit beside the
engine).  **The served tokens** (``serve_state.check_served``'s replay:
``served_pairs`` requests that ended inside the window, each with the request
that held ITS SLOT just before it, teacher forced through the reference,
every reply token held to the reference's own draw under the request's key),
the sample's tokens TOGETHER with a floor under each request's own replay
(:func:`check_served_sample`); the engine's state leaves are released first:
the reference's float32 logits of a 1,024-token reply over 151,936 rows do
not fit beside them.  **The timed programs** tied to the float32 pass by
structure (``serve_ssm.check_state_programs``: the same ``power_*`` bodies by
name on operands of the same element types, none narrower than float32, a
float32 state leaf).

**Controls.**  ``python3 -m chipbench.drivers.serve_power --workload <cell>
--seed N [--seconds S] [--rehearse]`` runs the cell as :func:`run` does and
puts the plain reference and each shortcut ``VARIANTS`` names (the state
kept in bfloat16, the gate dropped, the normaliser dropped, the off-diagonal
monomials weighted 1, the reset dropped) through BOTH comparisons: a JSON
line each that names which comparison refused it, exit 0 only if the plain
reference is ``ok`` in both and every variant is refused by at least one.

What is generic is imported (``serve_state``'s ``_StepMarks``,
``served_sample``, ``check_served``, ``_rel_rmse``; ``serve_ssm``'s
``state_kernels``, ``check_state_programs``); :func:`state_logits` and
:func:`run` are ``serve_ssm``'s copied, because the one drives a ``full``
table and block ids this engine does not have and the other calls its own
comparison (a later ``benchmark`` issue folds the serving drivers — six of
them now — into one, PERF.md section 7 (30) / (77)).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import costs, reference_brumby, traffic
from chipbench.drivers import serve_closed, serve_state
from chipbench.drivers.serve_ssm import (_program_name, check_state_programs,
                                         state_kernels)
from chipbench.drivers.serve_state import (SCORE_DECODE_STEPS, _StepMarks,
                                           _rel_rmse, check_served,
                                           served_sample)

KIND = "serve_power"

#: relative RMSE of the engine's logits against the float32 reference, by the
#: dtype served, over each compared sequence's prefill positions and over
#: its decode positions alike.  fp32 (the rehearsal, the CPU tests): the two
#: sides differ by rounding order and by the recurrent form's regrouping of
#: the attention form's sums.  bf16 and exact: set from chip readings at the
#: published widths (TPU v5 lite, PR 57; PERF.md section 6 has every one).
#: Sound, 4 parts a seed over the 18 runs of the kernels as they ship (every
#: product of the chunk kernel at full float32 precision): bf16
#: 0.0133-0.0180, exact (the same programs with float32 activations)
#: 5e-6-1.1e-5.  The shortcuts (seeds 5700000167 / 5700000173 /
#: 5700000227): ``state_bf16`` 0.357-0.583 in BOTH passes — 8,256 monomials
#: a head summed over thousands of tokens: a bfloat16 state loses every
#: increment under 2^-8 of what it holds, and unlike a 128 x 128 state's
#: rounding (PR 55) it does not hide under the bf16 matmuls' —,
#: ``unit_offdiag`` 0.400-0.446, ``no_gate`` 0.653-0.934, ``no_norm``
#: 1.26-1.36, ``no_reset`` over the second sequence 0.776-1.079 (its first,
#: which entered a fresh slot, reads sound in bf16, 0.0135-0.0162, and
#: 8e-4-1.3e-3 in float32: that is the reference's OWN per-token body
#: against its attention form — a gate applied 4,096 times compounds the
#: chip's ``exp`` error where the attention and chunked forms exponentiate a
#: summed log-gate once).  The bf16 limit stands between the largest sound
#: reading (0.0180) and the smallest of the nearest shortcut (``state_bf16``
#: 0.357), 2.2 x and 8.9 x from them; the exact limit between 1.1e-5 and
#: 0.357, 45 x above the sound readings (fresh seeds read higher) and under
#: the per-token body's own 8e-4.  (What the exact limit cannot see: the
#: chunk kernel's two large products from three bf16 passes in place of
#: six, tried in PR 57 and taken out, read 7e-6-1.3e-5 here.)
LOGIT_REL_RMSE = {"bf16": 4.0e-2, "fp32": 2e-4, "exact": 5e-4}
#: the served-token comparison's limits over the sample's tokens TOGETHER
#: and the floor under a request's own replay (:func:`check_served_sample`),
#: each between this cell's chip readings (TPU v5 lite, PR 57; 18 sound
#: samples of 1,252-1,965 tokens; the nearest shortcut is ``no_reset``, then
#: ``unit_offdiag``): replay — sound 0.9761-0.9887, ``no_reset`` 0.739-0.797:
#: the floor 0.88; outside the reference's nucleus — sound 0-0.0039,
#: ``no_reset`` 0.0297-0.046, ``unit_offdiag`` 0.0453-0.063: the limit 0.012,
#: 3.1 x and 2.5 x from them; the mean margin in nats — sound 0.0002-0.0046,
#: ``no_reset`` 0.129-0.188: the limit 0.03, 6.5 x and 4.3 x; a REQUEST's
#: own replay — sound 0.9612-0.9871, ``no_reset`` 0.578-0.644: the floor
#: 0.85.  fp32 (the rehearsal, the CPU tests): ``serve_state``'s, a sound
#: engine replays every token
SERVED_REPLAY = {"bf16": 0.88, "fp32": serve_state.SERVED_REPLAY["fp32"]}
SERVED_OUTSIDE = {"bf16": 0.012, "fp32": serve_state.SERVED_OUTSIDE["fp32"]}
SERVED_GAP = {"bf16": 0.03, "fp32": serve_state.SERVED_GAP["fp32"]}
SERVED_REPLAY_A_REQUEST = {"bf16": 0.85, "fp32": 0.85}
#: the shortcuts the comparison must refuse, each by at least one limit
VARIANTS = reference_brumby.VARIANTS[1:]


def state_logits(srv, tokens: np.ndarray, n_decode: int, exact: bool = False,
                 slot: int = 0):
    """The sequences of ``tokens [rows, S]`` one after the other through ONE
    row of THE ENGINE'S OWN CACHE (taken before any request is admitted and
    handed back: every state row at the cell's size, the live row ``slot``,
    every other row idle), at the shapes the window's programs have: an
    even sequence's prompt through the ``(prefill_batch, prefill_chunk)``
    rung with pad rows beside it, an odd one's through the wide rung a lone
    prompt takes, a sequence's first call carrying ``prefill_chunk`` tokens
    on either rung (in the wide row the rest is pads, as behind a short
    prompt), then ``n_decode`` one-token steps.  ``exact`` (module
    docstring): the same forward on the same weights and the same cache
    with float32 activations and full-precision matmuls.

    -> ``(logits, at, programs)``: ``serve_ssm.state_logits``'s."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import decode_attention

    hooks = srv.engine.module.decode_hooks
    fwd, prepare = hooks["forward_cached"], srv.engine._prepare
    bodies = hooks["state_layers"]["bodies"]
    n, s = tokens.shape
    n_prefill = s - n_decode
    cache, srv._cache = srv._cache, None
    state_rows = srv.slots
    programs: Dict[str, Dict[str, Any]] = {}
    built: Dict[str, Any] = {}

    def call(rung, body, *args):
        """``body(params, cache, *args)`` as a program of its own, compiled
        once a shape; what its state-kind layers lowered to is kept."""
        name = _program_name(rung)
        if name not in built:
            def traced(*a):
                with decode_attention.dispatch_log() as paths:
                    out = body(*a)
                programs[name] = {
                    "family": name.split("[")[0], "rung": rung,
                    "bodies": "+".join(sorted(
                        p for p in paths if p.startswith(bodies + "_")))}
                return out

            lowered = jax.jit(traced, donate_argnums=srv._donate()).lower(
                *args)
            programs[name]["kernels"] = state_kernels(lowered.as_text(),
                                                      bodies)
            built[name] = lowered.compile()
        return built[name](*args)

    def prefill(params, cache, ids, rows, base, valid):
        logits, cache = fwd(prepare(params), ids, cache, base, lengths=valid,
                            block_tables={"slot": rows})
        return logits[0], srv._constrain_pool(cache)

    def decode(params, cache, tok, rows, lengths, row):
        # the live row is an operand: one program whatever slot a seed draws
        logits, cache = fwd(prepare(params), tok, cache, 0, lengths=lengths,
                            block_tables={"slot": rows})
        return logits[row], srv._constrain_pool(cache)

    params = srv.engine.params
    precision = contextlib.nullcontext()
    if exact:
        # the activations take the token table's dtype and every weight is
        # cast to theirs where it is used: a float32 table makes the whole
        # forward float32 on the engine's own (bfloat16-valued) weights —
        # the rows of the scored tokens alone, looked up by their rank (the
        # head is untied and whole)
        known, tokens = np.unique(tokens, return_inverse=True)
        tokens = tokens.reshape(n, s).astype(np.int32)
        params = {**params, "embed": params["embed"][jnp.asarray(known)]
                  .astype(jnp.float32)}
        precision = jax.default_matmul_precision("highest")
    live = np.arange(state_rows) == slot
    dec_rows = jnp.asarray(np.where(live, slot, state_rows), jnp.int32)
    out, at = [], []
    with srv._tp_ctx(), precision:
        for i, seq in enumerate(tokens):
            rung = srv._rungs[-1 if i % 2 else 0]
            j, width = rung
            first = np.arange(j) == 0
            # row 0 is the live slot; a pad row's slot is out of range
            rows_ = jnp.asarray(np.where(first, slot, state_rows), jnp.int32)
            got, where, base = [], [], 0
            while base < n_prefill:
                # a sequence's FIRST call carries a chunk's tokens whatever
                # the rung: in the wide row pads follow them, as they follow
                # a lone short prompt in the window, and the state the row
                # entered with is scored while a stale one still shows
                valid = min(width if base else srv.prefill_chunk,
                            n_prefill - base)
                ids = np.zeros((j, width), np.int32)
                ids[0, :valid] = seq[base:base + valid]
                logits, cache = call(
                    rung, prefill, params, cache, jnp.asarray(ids), rows_,
                    jnp.asarray(np.where(first, base, 0), jnp.int32),
                    jnp.asarray(np.where(first, valid, 0), jnp.int32))
                got.append(np.asarray(logits, np.float32))
                base += valid
                where.append(base - 1)
            for p in range(n_prefill, s):
                tok = np.zeros((state_rows, 1), np.int32)
                tok[slot, 0] = seq[p]
                logits, cache = call(
                    None, decode, params, cache, jnp.asarray(tok), dec_rows,
                    jnp.asarray(np.where(live, p, 0), jnp.int32),
                    jnp.asarray(slot, jnp.int32))
                got.append(np.asarray(logits, np.float32))
                where.append(p)
            out.append(np.stack(got))
            at.append(where)
    srv._cache = cache
    return out, at, programs


def check_logits(job, srv, variant: Optional[str] = None,
                 engine=None) -> Dict[str, Any]:
    """Engine vs the family's plain reference on ``score_rows`` seeded
    sequences of ``score_tokens`` positions (module docstring); with
    ``variant``, vs that shortcut of the reference (a control: ``ok`` has
    to come out false).  ``engine``: the engine's side, ``state_logits``'s
    result a pass, where a caller has it already."""
    a = costs.arch(job.config)
    rows, s = int(job.traffic["score_rows"]), int(job.traffic["score_tokens"])
    if s > srv.max_seq_len:
        raise ValueError(f"score_tokens {s} over max_seq_len "
                         f"{srv.max_seq_len}")
    rng = np.random.default_rng(traffic.seed_sequence(job.seed).spawn(1)[0])
    tokens = rng.integers(0, a["vocab"], (rows, s)).astype(np.int32)
    slot = int(rng.integers(0, srv.slots))
    # the engine's dtype and, where that is not float32, a second pass in
    # float32 (module docstring)
    passes = ("",) if job.config["dtype"] == "fp32" else ("", ".exact")
    got = engine if engine is not None else tuple(
        state_logits(srv, tokens, SCORE_DECODE_STEPS, exact=bool(name),
                     slot=slot) for name in passes)
    # the reference at the union of the passes' positions (a sequence's own:
    # the two rungs end their calls at different positions)
    union = [sorted(set().union(*(at[row] for _, at, _ in got)))
             for row in range(rows)]
    width = max(map(len, union))
    all_ = np.asarray(job.family.logits(
        job.config, srv.engine.params, tokens, variant=variant,
        at=np.asarray([u + [u[-1]] * (width - len(u)) for u in union])),
        np.float32)
    parts, ok, wants = {}, True, []
    for name, (side, at, _) in zip(passes, got):
        tol = LOGIT_REL_RMSE["exact" if name else job.config["dtype"]]
        want = [all_[row, [union[row].index(p) for p in at[row]]]
                for row in range(rows)]
        wants.append(want)
        for row in range(rows):
            chunks = len(at[row]) - SCORE_DECODE_STEPS
            for part, at_ in (("prefill", slice(0, chunks)),
                              ("decode", slice(chunks, None))):
                r = _rel_rmse(side[row][at_], want[row][at_])
                parts[f"row{row}.{part}{name}"] = r
                ok &= r is None or r <= tol
            ok &= bool(np.isfinite(side[row]).all())
    at = got[0][1]
    shapes = " then ".join(
        f"{len(at[row]) - SCORE_DECODE_STEPS} calls of "
        f"{_program_name(srv._rungs[-1 if row % 2 else 0])}"
        for row in range(rows))
    job.note(f"comparison{' vs ' + variant if variant else ''}: {rows} x {s} "
             f"tokens through ONE slot ({slot} of {srv.slots}, the engine's "
             f"own cache): {shapes}, each + {SCORE_DECODE_STEPS} decode "
             f"steps at {srv.slots} rows"
             + (" (and again with float32 activations)"
                if len(passes) > 1 else "")
             + ": relative RMSE "
             + json.dumps({k: None if v is None else round(v, 6)
                           for k, v in parts.items()}))
    return {"ok": bool(ok),
            "logit_rel_rmse": _rel_rmse(np.concatenate(got[0][0]),
                                        np.concatenate(wants[0])),
            "tolerance": LOGIT_REL_RMSE[job.config["dtype"]],
            "tolerance_exact": LOGIT_REL_RMSE["exact"],
            "logit_rel_rmse_parts": parts, "engine": got,
            # the served dtype's pass and the one held to the float32 limit
            # (check_state_programs)
            "programs": {"served": got[0][2], "float32": got[-1][2]},
            "positions": int(sum(len(w) for w in at))}


def check_served_sample(job, srv, rows: List[Dict[str, Any]],
                        variant: Optional[str] = None) -> Dict[str, Any]:
    """``serve_state.check_served``'s replay (a line a request) under THIS
    cell's limits (``SERVED_*`` above): the three shares of the SAMPLE's
    tokens together and, for a fault in one slot that the others' tokens
    would dilute, a floor under each request's own replay share
    (``serve_ssm.check_served_sample``'s reasons)."""
    got = check_served(job, srv, rows, variant)
    if not got["rows"]:
        return got
    dtype = job.config["dtype"]
    limits = {"replay": SERVED_REPLAY[dtype], "outside": SERVED_OUTSIDE[dtype],
              "gap": SERVED_GAP[dtype], "replay_a_request":
              SERVED_REPLAY_A_REQUEST[dtype]}
    total = sum(r["tokens"] for r in got["rows"])
    pooled = {k: sum(r[k] * r["tokens"] for r in got["rows"]) / total
              for k in ("replay", "outside", "gap")}
    lowest = min(r["replay"] for r in got["rows"])
    ok = pooled["replay"] >= limits["replay"] \
        and pooled["outside"] <= limits["outside"] \
        and pooled["gap"] <= limits["gap"] \
        and lowest >= limits["replay_a_request"]
    job.note(f"served tokens{' vs ' + variant if variant else ''}, the "
             f"sample's {total} tokens together: "
             + json.dumps({k: round(v, 5) for k, v in pooled.items()})
             + f", the lowest replay of a request {lowest:.5f} (this cell's "
             f"limits {json.dumps(limits)}): {'ok' if ok else 'REFUSED'}")
    return {**got, **pooled, "replay_a_request": lowest, "limits": limits,
            "ok": bool(ok)}


def run(job, variants: Tuple[str, ...] = ()) -> Dict[str, Any]:
    """``serve_ssm.run``'s closed loop — the same set-up, stamps, counters,
    SETTLED start and result — with the comparisons above.  ``variants``:
    shortcuts of the reference to put through both as well
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
        engine_side, check_programs = check.pop("engine"), \
            check.pop("programs")
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
             "first_tokens": 0, "row_tokens": 0, "decode_samples": 0}
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
            live = [c for c in clients if c.handle is not None and c.seen]
            if live:
                state["row_tokens"] += sum(
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
    # TTFT and ITL are samples here, printed and unjudged
    e2e = {"serve_tok_s": state["tokens"] / window}
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
             f"{ms(p95(ttfts)) if ttfts else None}; ITL median "
             f"{medians['itl_median_ms']} ms, p95 "
             f"{ms(p95(gaps)) if gaps else None}; evicted "
             f"{delta['evicted']}")
    # the timed programs, now that the engine has built and run them all
    programs = check_state_programs(job, srv, check_programs)
    rows = served_sample(served, (t_open, t_close),
                         int(mix["served_pairs"]))
    # the reference's logits of the sample's replies take the room the
    # state leaves hold: the engine has served its last request
    devices = list(srv.engine.mesh.devices.flat)
    srv.close()
    srv._cache = None
    tokens_served = check_served_sample(job, srv, rows)
    lines = [{"variant": None, "logits_ok": check["ok"],
              "served_ok": tokens_served["ok"], "logits": check,
              "served": tokens_served}]
    for variant in variants:
        a = check_logits(job, srv, variant, engine_side)
        del a["engine"], a["programs"]
        b = check_served_sample(job, srv, rows, variant)
        lines.append({"variant": variant, "logits_ok": a["ok"],
                      "served_ok": b["ok"], "logits": a, "served": b})
    return {
        "correct": bool(check["ok"] and tokens_served["ok"]
                        and programs["ok"] and no_compile),
        **({"controls": lines, "state_programs": programs}
           if variants else {}),
        "attempted": state["attempted"], "failed": int(failed),
        "end_to_end": e2e, "window_s": window, "window": (t_open, t_close),
        "counters": {**delta, "slots": srv.slots,
                     "num_blocks": after["num_blocks"],
                     "block_size": after["block_size"],
                     "tokens_in_window": state["tokens"],
                     "first_tokens_in_window": state["first_tokens"],
                     "ttft_samples": len(ttfts), "itl_samples": len(gaps),
                     **medians,
                     "ttft_p95_ms": ms(p95(ttfts)) if ttfts else None,
                     "itl_p95_ms": ms(p95(gaps)) if gaps else None,
                     # tokens the live rows have behind them, a decode step
                     "mean_row_tokens": state["row_tokens"]
                     / max(1, state["decode_samples"]),
                     "logit_rel_rmse": check["logit_rel_rmse"],
                     # the served-token comparison: the sample's shares
                     "served_tokens": tokens_served["tokens"],
                     "served_replay": tokens_served.get("replay"),
                     "served_outside": tokens_served.get("outside"),
                     "served_gap": tokens_served.get("gap"),
                     "served_replay_a_request": tokens_served.get(
                         "replay_a_request"),
                     # the timed programs' state-kind bodies are the float32
                     # pass's, on float32 operands (check_state_programs)
                     "state_programs_held": programs["ok"],
                     "stalled_steps": len(slow),
                     "stalled_s": sum(slow) - len(slow) * typical,
                     # the state kind (``stats()["kv_state"]``): its bytes
                     "state_bytes": (after.get("kv_state") or {}).get(
                         "bytes")},
        "samples": {},
        "devices": devices,
    }


def controls(job) -> bool:
    """The cell as :func:`run` runs it, then the plain reference and every
    shortcut of ``VARIANTS`` through both comparisons, a JSON line each; true
    if the plain reference is ``ok`` in both and every shortcut is refused by
    at least one."""
    got = run(job, VARIANTS)
    held = got["state_programs"]["ok"]
    for line in got["controls"]:
        ok = line["logits_ok"] and line["served_ok"]
        held &= ok == (line["variant"] is None)
        print(json.dumps({"seed": job.seed, **line}), flush=True)
    print(json.dumps({"seed": job.seed,
                      "state_programs": got["state_programs"]}), flush=True)
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
