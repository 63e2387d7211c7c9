"""``kind: serve_ssm`` — ``serve_state``'s closed loop with a SETTLED start (N
callers, each waiting for its reply before it sends the next request, one
``ServingEngine`` driven by hand; the same stamps, counters and result) for a
DENSE model that keeps a RECURRENT STATE A SLOT beside the paged pool's K
and V: state-space layers (``ops/ssd.py``) whose whole past is a float32
matrix a head a slot, grouped-query layers on the ``full`` kind
(``models/granite_hybrid.py``).

``correct`` rests on ``serve_state``'s TWO comparisons with the family's
plain float32 reference, and on a third check that ties them to what the
window times.  **The served tokens** (``serve_state.check_served``'s replay:
``served_pairs`` requests that ended inside the window, each with the request
that held ITS SLOT just before it, teacher forced through the reference,
every reply token held to the reference's own draw under the request's key)
under THIS cell's limits, each set between this cell's own sound and unsound
chip readings and held to the sample's tokens TOGETHER, with a floor under
each request's own replay (``SERVED_*``, :func:`check_served_sample`).  **The
logits** (:func:`check_logits`: ``score_rows`` sequences of ``score_tokens``
positions ONE AFTER THE OTHER through the SAME slot and the same blocks of
THE ENGINE'S OWN CACHE, at the shapes the window's programs have — the first
sequence's prompt through the ``[prefill_batch, prefill_chunk]`` rung beside
pad rows, the second's through the wide row a lone chat prompt takes — its
first call a chunk's tokens with pads behind them, as a short prompt's is in
the window, its next crossing several chunk boundaries inside one call —
then 16 decode steps at every slot's row — relative RMSE over each
sequence's prefill positions and over its decode positions apart, each
against the one limit a dtype).  A dense model makes no discrete choice:
there are no forced sets, and a bf16 engine is compared plainly.

**What is float32 by construction, compared in float32.**  A bf16 engine's
logits carry the rounding of forty layers of bf16 matmuls and residuals
(1.3-1.6 % at a tenth of the width on the CPU), and a fault the size of ONE
more rounding a layer — the recurrent state kept in bfloat16: 0.2-1 % of a
layer's output at this model's decays — cannot be told from a seed's spread
under them: the logits of the engine's own dtype do not refuse that control.
So where the engine is not float32 the same sequences go through one slot a
SECOND time with float32 activations (:func:`state_logits` ``exact=True``:
the engine's own bfloat16-valued weights, cast where they are used, as the
reference casts them; full-precision matmuls; a small float32 pool and
float32 convolution tails — the engine's own forward, kernels and cache
kinds at another dtype, both sequences through the ``[prefill_batch,
prefill_chunk]`` rung: the attention kernel's plan holds no wider row in
float32), and the parts of that pass are held to
``LOGIT_REL_RMSE["exact"]``: what the state kind keeps in float32 — the
state, ``dt``, the decays, the sums over a chunk — shows there alone.

**The timed programs, tied to that pass.**  The float32 pass runs programs
the comparison builds for itself; the window times the engine's own, in the
served dtype, and every number read off THOSE passes the bfloat16-state
control.  :func:`check_state_programs` therefore holds the engine's own
decode and prefill bodies, lowered at the live shapes (the wide row too), to
the float32 pass's programs: the same state-kind bodies by name, the same
kernels on operands of the same element types, none of them narrower than
float32, and a float32 state leaf — else ``correct`` is false.  The configuration's float32 state
(``assumed.state_float32``) is thereby held ON THE TIMED PATH: by structure,
where no reading of the served dtype can hold it.

**Controls.**  ``python3 -m chipbench.drivers.serve_ssm --workload <cell>
--seed N [--seconds S] [--rehearse]`` runs the cell as :func:`run` does and
puts the plain reference and each shortcut ``VARIANTS`` names (the state
kept in bfloat16, the decay dropped, the reset dropped) through BOTH
comparisons: a JSON line each that names which comparison refused it, exit 0
only if the plain reference is ``ok`` in both and every variant is refused by
at least one.

What is ``serve_state``'s and generic is imported from it (``_StepMarks``,
``served_sample``, ``check_served``, ``_rel_rmse``); :func:`run`
is its ``run`` copied, because that function calls its own comparison, which
forces expert sets this model does not have (a later ``benchmark`` issue
folds the serving drivers — five of them now — into one, PERF.md section 7
(30) / (77)).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import re
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import costs, reference_granite_hybrid, traffic
from chipbench.drivers import serve_closed, serve_state
from chipbench.drivers.serve_state import (SCORE_DECODE_STEPS, _StepMarks,
                                           _rel_rmse, check_served,
                                           served_sample)

KIND = "serve_ssm"

#: relative RMSE of the engine's logits against the float32 reference, by
#: the dtype served, over each compared sequence's prefill positions and
#: over its decode positions alike.  fp32 (the rehearsal, the CPU tests): the
#: two sides differ by rounding order and by the chunked form's regrouping of
#: the recurrence.  bf16 and exact: set from chip readings at the published
#: widths (TPU v5 lite, PR 55; PERF.md section 6 has every one).  Sound, 4
#: parts a seed: bf16 0.0279-0.0314 over 14 seeds at the shapes compared now
#: (0.0282-0.0301 over the 19 seeds before them, at a 4-row cache), exact
#: 2.4e-5-6.2e-5 over all 35.  The shortcuts, 9 seeds: ``state_bf16`` reads
#: bf16 0.0287-0.0308 — INSIDE the sound readings, which is why the exact
#: pass exists — and exact 3.6e-3-9.3e-3; ``no_reset`` over the second
#: sequence's prefill 0.0625-0.0859 in bf16 at the shapes compared now (3
#: seeds; 0.0533-0.0671 over 5 before them; 0.0345 when the wide row's first
#: call was full and its first scored position 511, which is why a
#: sequence's first call carries one chunk) and 0.045-0.078 exact;
#: ``no_decay`` 1.28-1.32 in both.  The bf16 limit stands between the largest
#: sound reading (0.0314) and the smallest of the nearest shortcut bf16 CAN
#: tell (``no_reset`` 0.0533); the exact limit between 6.2e-5 and the
#: bfloat16 state's 3.6e-3, 8 x and 7 x from them.
LOGIT_REL_RMSE = {"bf16": 4.0e-2, "fp32": 2e-4, "exact": 5e-4}
#: the served-token comparison's limits, THIS cell's (``serve_state``'s were
#: set from Kimi Linear's replies of 512-2,048 tokens and stand ABOVE what the
#: dropped decay reads here: its gap limit of 0.25 refused nothing shown).
#: Each from this cell's chip readings at the published widths (TPU v5 lite,
#: PR 55; 35 sound samples and 9 of ``no_decay``, the one shortcut the served
#: tokens can tell: ``state_bf16`` and ``no_reset`` read as sound here and are
#: the logits' to refuse), over the sample's tokens TOGETHER
#: (:func:`check_served_sample`; 210-610 tokens a sample):
#: replay — sound 0.9853-1.0, ``no_decay`` 0.7148-0.7656: the floor 0.88;
#: outside the reference's nucleus — sound 0-0.0089, ``no_decay``
#: 0.0920-0.1333: the limit 0.03; the mean margin in nats — sound 0-0.0096
#: (heavy-tailed: a token the engine's nucleus drops at its edge loses the
#: reference's race by a nat or two, and a sample has one or two such),
#: ``no_decay`` 0.0984-0.1575: the limit 0.03, 3 x from each.  A REQUEST's
#: own replay (replies of 34 tokens and up) — sound 0.961-1.0 over 140
#: requests, ``no_decay`` 0.647-0.814 over 36: the floor 0.85, for a fault in
#: one slot of the sample's two.  fp32 (the rehearsal, the CPU tests):
#: ``serve_state``'s, a sound engine replays every token.
SERVED_REPLAY = {"bf16": 0.88, "fp32": serve_state.SERVED_REPLAY["fp32"]}
SERVED_OUTSIDE = {"bf16": 0.03, "fp32": serve_state.SERVED_OUTSIDE["fp32"]}
SERVED_GAP = {"bf16": 0.03, "fp32": serve_state.SERVED_GAP["fp32"]}
SERVED_REPLAY_A_REQUEST = {"bf16": 0.85, "fp32": 0.85}
#: the shortcuts the comparison must refuse, each by at least one limit
VARIANTS = reference_granite_hybrid.VARIANTS[1:]


_KERNEL = re.compile(r'kernel_name = "(\w+)"')
_ELEMENT = re.compile(r"tensor<(?:[0-9?]+x)*(\w+)>")


def state_kernels(text: str, bodies: str) -> List[List[Any]]:
    """The Mosaic calls of a program's lowered (StableHLO) ``text`` whose
    kernel's name starts with ``bodies + "_"``: ``[name, the element type of
    each operand, of each result]`` a distinct call, sorted."""
    out = set()
    for line in text.splitlines():
        m = _KERNEL.search(line)
        if m and m.group(1).startswith(bodies + "_"):
            operands, results = line.rsplit("} : ", 1)[1].split(" -> ")
            out.add((m.group(1), tuple(_ELEMENT.findall(operands)),
                     tuple(_ELEMENT.findall(results))))
    return [[name, list(a), list(b)] for name, a, b in sorted(out)]


def _program_name(rung=None) -> str:
    return "decode" if rung is None else f"prefill[{rung[0]}x{rung[1]}]"


def state_logits(srv, tokens: np.ndarray, n_decode: int, exact: bool = False,
                 slot: int = 0):
    """The sequences of ``tokens [rows, S]`` one after the other through ONE
    row and the same blocks of a cache of the engine's own kinds, at the
    shapes the window's programs have: an even sequence's prompt through the
    ``(prefill_batch, prefill_chunk)`` rung with pad rows beside it, an odd
    one's through the wide rung a lone prompt takes (``(1, prefill_batch *
    prefill_chunk)``: several chunk boundaries inside one call), a
    sequence's first call carrying ``prefill_chunk`` tokens on either rung
    (in the wide row the rest is pads, as behind a short prompt), then
    ``n_decode`` one-token steps.  The served dtype runs on THE ENGINE'S OWN
    CACHE (taken before any request is admitted and handed back: every
    leaf, pool block and state row at the cell's size, the live row
    ``slot``, every other row idle — a second copy of the state leaf would
    not fit beside it); ``exact`` (module docstring "What is float32 by
    construction") on a small float32 cache of its own: the same forward
    on the same weights with float32 activations and full-precision
    matmuls, EVERY sequence through the ``(prefill_batch, prefill_chunk)``
    rung — the attention kernel's plan holds no wider row in float32 (2,048
    or 1,024 float32 query rows a KV head do not fit its VMEM: Mosaic's
    compile for a v5e refuses ``[1, 512]`` and ``[2, 256]`` alike), which is
    the attention's limit, not the scan's; the wide row is scored in the
    served dtype and held to this pass by :func:`check_state_programs`.

    -> ``(logits, at, programs)``: a float32 ``[positions, V]`` array and
    the positions it holds, a sequence; and, a program this pass built
    (``"decode"``, ``"prefill[<rows>x<width>]"``), its ``family``, ``rung``,
    the ``bodies`` its state-kind layers lowered to (the dispatch log's
    names) and their ``kernels`` (:func:`state_kernels`)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import decode_attention, paged_kv

    hooks = srv.engine.module.decode_hooks
    fwd, prepare = hooks["forward_cached"], srv.engine._prepare
    bodies = hooks["state_layers"]["bodies"]
    n, s = tokens.shape
    n_prefill = s - n_decode
    bs, b = srv.block_size, srv.prefill_batch
    used = paged_kv.blocks_for(s, bs)
    if exact:
        # the engine's own views: K and V lane-packed, the state kind as is
        cache = jax.eval_shape(lambda: {
            k: v if k in paged_kv.STATE_LEAVES else paged_kv.pack_pool(v)
            for k, v in hooks["init_cache"](
                1 + used, bs, jnp.float32, state_rows=b).items()})
        cache = jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.zeros(a.shape, a.dtype),
                                     srv._pool_sharding), cache)
        state_rows, nbper, slot = b, used, 0
    else:
        cache, srv._cache = srv._cache, None
        state_rows, nbper = srv.slots, srv._nbper
    blocks = np.zeros(nbper, np.int32)
    blocks[:used] = 1 + np.arange(used)
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

    def prefill(params, cache, ids, bt, rows, base, valid):
        logits, cache = fwd(prepare(params), ids, cache, base, lengths=valid,
                            block_tables={"full": bt, "slot": rows})
        return logits[0], srv._constrain_pool(cache)

    def decode(params, cache, tok, bt, lengths, row):
        # the live row is an operand: one program whatever slot a seed draws
        logits, cache = fwd(prepare(params), tok, cache, 0, lengths=lengths,
                            block_tables={"full": bt})
        return logits[row], srv._constrain_pool(cache)

    params = srv.engine.params
    precision = contextlib.nullcontext()
    if exact:
        # the activations take the token table's dtype and every weight is
        # cast to theirs where it is used: one float32 leaf makes the whole
        # forward float32 on the engine's own (bfloat16-valued) weights
        params = {**params, "embed": params["embed"].astype(jnp.float32)}
        precision = jax.default_matmul_precision("highest")
    live = np.arange(state_rows) == slot
    dec_bt = jnp.asarray(np.where(live[:, None], blocks, 0).astype(np.int32))
    out, at = [], []
    with srv._tp_ctx(), precision:
        for i, seq in enumerate(tokens):
            rung = srv._rungs[-1 if i % 2 and not exact else 0]
            j, width = rung
            first = np.arange(j) == 0
            # row 0 is the live slot; a pad row's slot is out of range
            bt = jnp.asarray(np.where(first[:, None], blocks, 0)
                             .astype(np.int32))
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
                    rung, prefill, params, cache, jnp.asarray(ids), bt, rows_,
                    jnp.asarray(np.where(first, base, 0), jnp.int32),
                    jnp.asarray(np.where(first, valid, 0), jnp.int32))
                got.append(np.asarray(logits, np.float32))
                base += valid
                where.append(base - 1)
            for p in range(n_prefill, s):
                tok = np.zeros((state_rows, 1), np.int32)
                tok[slot, 0] = seq[p]
                logits, cache = call(
                    None, decode, params, cache, jnp.asarray(tok), dec_bt,
                    jnp.asarray(np.where(live, p, 0), jnp.int32),
                    jnp.asarray(slot, jnp.int32))
                got.append(np.asarray(logits, np.float32))
                where.append(p)
            out.append(np.stack(got))
            at.append(where)
    if not exact:
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
    # float32 (module docstring "What is float32 by construction")
    passes = ("",) if job.config["dtype"] == "fp32" else ("", ".exact")
    got = engine if engine is not None else tuple(
        state_logits(srv, tokens, SCORE_DECODE_STEPS, exact=bool(name),
                     slot=slot) for name in passes)
    # the reference at the union of the passes' positions, each pass's own
    # picked out of it a sequence
    union = sorted(set().union(*(p for _, at, _ in got for p in at)))
    all_ = np.asarray(job.family.logits(
        job.config, srv.engine.params, tokens, at=union, variant=variant),
        np.float32)
    parts, ok, wants = {}, True, []
    for name, (side, at, _) in zip(passes, got):
        tol = LOGIT_REL_RMSE["exact" if name else job.config["dtype"]]
        want = [all_[row, [union.index(p) for p in at[row]]]
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
             f"own cache) at block {srv.block_size}: {shapes}, each + "
             f"{SCORE_DECODE_STEPS} decode steps at {srv.slots} rows"
             + (f" (the float32 pass on a cache of its own: every sequence "
                f"through {_program_name(srv._rungs[0])}, decode at "
                f"{srv.prefill_batch} rows)" if len(passes) > 1 else "")
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


def check_state_programs(job, srv, programs: Dict[str, Dict[str, Any]]
                         ) -> Dict[str, Any]:
    """THE TIMED PROGRAMS against the pass that is held to the float32
    limit.  ``LOGIT_REL_RMSE["exact"]`` is read off programs the comparison
    builds for itself (:func:`state_logits` ``exact=True``); what the window
    times are the engine's own, at another dtype, and every number taken
    from THEM passes the bfloat16-state control (PERF.md section 6, PR 55:
    forty layers of bf16 matmuls bury one more rounding a layer).  So the
    two are tied by what they lowered to: the engine's own decode body and
    its prefill body at each rung the comparison drove
    (``ServingFlopsProfiler.lower``: the raw bodies at the live shapes, the
    sampling operands included) must name the SAME state-kind bodies
    (``stats()["kv_state"]``'s names) as the float32 pass's program of that
    family and as the served pass's own at that shape and, where those are
    kernels, call them on operands and results of the same element types —
    every one of them float32 or an integer — and the engine's state leaf
    must be float32.  A lower precision confined to the served dtype's
    programs (bfloat16 operands in ``ssd_chunk_state``, a bfloat16 leaf)
    turns ``correct`` false here, whatever the logits read; it takes a
    ``benchmark`` issue, with readings of its own, to admit one.
    ``programs``: :func:`check_logits`'s — :func:`state_logits`'s of the
    ``served`` pass and of the one held to the ``float32`` limit."""
    from deepspeed_tpu.telemetry.flops import ServingFlopsProfiler

    bodies = srv.engine.module.decode_hooks["state_layers"]["bodies"]
    profiler = ServingFlopsProfiler(srv)
    leaf = str(srv._cache["state"].dtype)
    ok, lines = leaf == "float32", {"state_leaf": leaf}
    for name, served in programs["served"].items():
        family = served["family"]
        want = next(({k: p[k] for k in ("bodies", "kernels")}
                     for p in programs["float32"].values()
                     if p["family"] == family), None)
        lowered = profiler.lower(family, served["rung"], sampling=True)
        got = {"bodies": srv.stats()["kv_state"][bodies].get(family),
               "kernels": None if lowered is None
               else state_kernels(lowered.as_text(), bodies)}
        held = want is not None and got == want \
            and got == {k: served[k] for k in got} \
            and [k[0] for k in got["kernels"]] == sorted(
                # the names the layers took that are kernels
                k for k in got["bodies"].split("+")
                if k and not k.endswith("_plain")) \
            and all(t == "f32" or t.startswith(("i", "ui"))
                    for k in got["kernels"] for t in k[1] + k[2])
        ok &= held
        lines[name] = {**got, "held": bool(held)}
        if not held:
            lines[name].update(float32_pass=want, served_pass={
                k: served[k] for k in ("bodies", "kernels")})
    job.note("timed programs vs the float32 pass's, state-kind bodies and "
             f"their operands' element types: {json.dumps(lines)}: "
             f"{'ok' if ok else 'REFUSED'}")
    return {"ok": bool(ok), **lines}


def check_served_sample(job, srv, rows: List[Dict[str, Any]],
                        variant: Optional[str] = None) -> Dict[str, Any]:
    """``serve_state.check_served``'s replay (a line a request) under THIS
    cell's limits (``SERVED_*`` above, each between this cell's own sound
    and unsound chip readings): the three shares of the SAMPLE's tokens
    together and, for a fault in one slot that the others' tokens would
    dilute, a floor under each request's own replay share.

    Why together: the shares are of a request's tokens, and this mix's
    replies are 32-256 tokens, where ONE token is up to 3 % of a request's
    share: a sound bf16 engine serves 0.2 % of its tokens outside the float32
    reference's nucleus (12 of 5,982 over 60 requests on the chip, PERF.md
    section 6, PR 55), the worst request 1 of 35 (2.9 %), so a limit a
    request tight enough to stand under the dropped decay's smallest reading
    (6.6 %) would refuse a sound run whose one short reply has two.  The
    sample together is 210-610 tokens."""
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
    """``serve_state.run``'s closed loop — the same set-up, stamps,
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
    # the timed programs, now that the engine has built and run them all
    programs = check_state_programs(job, srv, check_programs)
    rows = served_sample(served, (t_open, t_close),
                         int(mix["served_pairs"]))
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
    srv.close()
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
                     "mean_valid_kv_tokens": state["kv_tokens"]
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
