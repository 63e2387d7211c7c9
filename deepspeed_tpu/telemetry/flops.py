"""TPU-native FLOPs/MFU profiler for the serving engine: per-program
FLOPs from XLA cost analysis (with a deterministic analytic fallback),
cumulative model-FLOPs accounting, an MFU-style goodput gauge, and a
busy-fraction breakdown derived from the trace timeline.

The reference flops profiler (``profiling/flops_profiler.py``) costs the
*training* step; serving had no FLOPs story at all — tok/s says how fast
the loop runs, not how much of the hardware it uses.  This module is the
serving analogue, built on the same insight: under XLA nothing needs
patching, the compiler already knows the op costs.  For every
sentry-registered program family the engine has actually built
(``prefill`` / ``decode`` / ``verify`` / ``draft``; the ``kv_demote`` /
``kv_promote`` swap pair is pure data movement — zero FLOPs by
definition), the profiler lowers the **raw, unwrapped body** with
abstract ``ShapeDtypeStruct`` inputs and reads
``Lowered.cost_analysis()``:

 - the raw body (``ServingEngine._program_bodies``) bypasses the
   recompile sentry, and ``lower()`` **never compiles** — the
   observability layer traces zero new programs and the engine's compile
   budget is untouched (the contract the serving tests pin);
 - abstract inputs mean no device memory, no transfers — a 70B pool
   profiles for free.

When the backend reports nothing (some backends return empty cost
models), :func:`analytic_program_flops` supplies a deterministic
closed-form estimate from the model dimensions and the program's FIXED
shapes — rows × width tokens attending over the full padded table width,
exactly what the fixed-shape paged programs actually compute (padding
included: that is the FLOPs the hardware executes, which is what MFU is
about).  The two paths are pinned to agree within 10% on at least one
family in ``tests/unit/test_fleet_telemetry.py``.

Accounting: ``report()`` multiplies per-program FLOPs by the engine's
invocation counters (``decode_steps`` / ``prefill_calls`` /
``spec_rounds``) into ``serving_model_flops_total``, sets the
``serving_mfu`` gauge against a configurable ``peak_flops`` (per-chip
peak × chips — the MFU denominator), and decomposes wall time into
``serving_busy_fraction{phase=prefill|decode|swap|idle}`` from the
in-flight ``X``-span durations already on the trace timeline: the share
of the window a prefill / decode / swap program was in flight (dispatch
to results on the host), and ``idle`` the rest — the HOST's share, during
which the device has nothing to run.  Everything is
host-side; cost analysis runs only when explicitly invoked (a report is
an O(ring) walk plus, on first use, one lowering per program family).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from ..utils.logging import logger

__all__ = ["analytic_program_flops", "busy_fractions",
           "ServingFlopsProfiler"]

#: timeline ``X`` span names folded into each busy phase
_PHASE_SPANS = {
    "prefill": ("prefill",),
    "decode": ("decode", "spec_propose", "spec_verify", "spec_round"),
    "swap": ("swap",),
}


def _model_dims(model_config) -> Dict[str, int]:
    """Transformer dimensions with family-tolerant attribute fallbacks."""
    h = int(model_config.hidden_size)
    heads = int(model_config.num_heads)
    kvh = int(getattr(model_config, "num_kv_heads",
                      getattr(model_config, "num_key_value_heads", heads)))
    ffn = int(getattr(model_config, "ffn_hidden_size",
                      getattr(model_config, "intermediate_size", 4 * h)))
    dims = {"layers": int(model_config.num_layers), "hidden": h,
            "heads": heads, "kv_heads": kvh, "ffn": ffn,
            "vocab": int(model_config.vocab_size)}
    active = getattr(model_config, "active_layer_params", None)
    if callable(active):
        # a family that counts what ONE token multiplies with in a layer
        # (attention at a width of its own, a router that is an MLP, one
        # expert of many: ``models/zaya.py``) is priced by that count
        dims.update(layer_macs=int(active()),
                    head_dim=int(model_config.head_dim))
    return dims


def analytic_components(family: str, dims: Dict[str, int], *,
                        rows: int, width: int, ctx: int
                        ) -> Dict[str, float]:
    """Closed-form FLOPs components for one invocation of a fixed-shape
    serving program — ``{"head": lm-head flops, "layers": all-layer
    flops}`` — for ``rows × width`` tokens, each token's attention
    spanning the full padded table width ``ctx`` (fixed-shape kernels
    compute the pads too — that IS the executed work).  2 FLOPs per MAC
    throughout.

    Per token: QKV ``2h(h + 2·kvh·hd)`` + attention out ``2h²`` + MLP
    ``4h·ffn`` + scores/weighted-sum ``4h·ctx`` (per-query-head width ×
    context, GQA-invariant), per layer; plus the LM head ``2hV`` — at
    the **last position only** for prefill/decode (the programs gather
    final-position logits) and at every window position for the
    ``all_positions`` verify head and the draft rollout (one head per
    scan step).  LayerNorms/softmax/residuals are O(h)/O(ctx) per token
    — noise next to the matmuls — and excluded.  A family whose layer is
    not that block hands in its own count (``dims["layer_macs"]``: what one
    token multiplies with in a layer) and its heads' width.
    """
    L, h = dims["layers"], dims["hidden"]
    hd = h // dims["heads"]
    kv_width = dims["kv_heads"] * hd
    per_layer = (2 * h * (h + 2 * kv_width)   # qkv projections
                 + 2 * h * h                  # attention out projection
                 + 4 * h * dims["ffn"]        # mlp up + down
                 + 4 * h * ctx)               # scores + weighted sum
    if "layer_macs" in dims:
        # the family's own count of a token's multiplies in a layer, and
        # its attention at its own heads' width (``_model_dims``)
        per_layer = 2 * dims["layer_macs"] \
            + 4 * dims["heads"] * dims["head_dim"] * ctx
    tokens = rows * width
    head_positions = tokens if family in ("verify", "draft") else rows
    return {"head": float(head_positions * 2 * h * dims["vocab"]),
            "layers": float(tokens * L * per_layer)}


def analytic_program_flops(family: str, dims: Dict[str, int], *,
                           rows: int, width: int, ctx: int) -> float:
    """Total of :func:`analytic_components`."""
    c = analytic_components(family, dims, rows=rows, width=width, ctx=ctx)
    return c["head"] + c["layers"]


def busy_fractions(timeline, window_s: Optional[float] = None
                   ) -> Dict[str, float]:
    """Decompose the timeline window into prefill/decode/swap/idle
    fractions from the in-flight ``X``-span durations already on the ring.
    Those spans open just before a jitted call and close when its results
    are on the host, so ``prefill`` / ``decode`` / ``swap`` are the shares
    of the window a program of that kind was IN FLIGHT (an upper bound on
    the device's busy share: dispatch latency and the copy-back are
    inside), and ``idle`` is the host's share — scheduling, packing,
    commit loops, the caller's own code — not idleness of the device
    alone.  The device's own busy time needs a profile
    (``telemetry/idle_gaps.py``).  The window defaults to first-event →
    last-event-end over the live ring (a wrapped ring reports its retained
    window — check ``trace_events_dropped``)."""
    events = timeline.events()
    spans = {phase: 0.0 for phase in _PHASE_SPANS}
    lo = hi = None
    for e in events:
        ts = e["ts"]
        end = ts + e.get("dur", 0.0)
        lo = ts if lo is None else min(lo, ts)
        hi = end if hi is None else max(hi, end)
        if e.get("ph") != "X":
            continue
        for phase, names in _PHASE_SPANS.items():
            if e["name"] in names:
                spans[phase] += e.get("dur", 0.0) / 1e6
                break
    window = window_s if window_s is not None else \
        ((hi - lo) / 1e6 if lo is not None and hi > lo else 0.0)
    out = {"window_s": window}
    if window <= 0.0:
        out.update({p: 0.0 for p in _PHASE_SPANS})
        out["idle"] = 0.0
        return out
    busy = 0.0
    for phase in _PHASE_SPANS:
        frac = min(spans[phase] / window, 1.0)
        out[phase] = frac
        busy += frac
    out["idle"] = max(0.0, 1.0 - busy)
    return out


class ServingFlopsProfiler:
    """FLOPs/MFU accounting over one :class:`ServingEngine` (module
    docstring).  Construct once per engine (``srv.flops_report()`` does);
    metric cells land on the engine's registry so scrapes and federation
    see them."""

    def __init__(self, srv, peak_flops: Optional[float] = None):
        self.srv = srv
        self.peak_flops = peak_flops
        self._programs: Dict[str, Dict[str, Any]] = {}
        self._last_total = 0.0
        m = srv.metrics
        self._c_model_flops = m.counter(
            "serving_model_flops_total",
            "model FLOPs executed by the serving programs (per-program "
            "cost × invocation counters; padding included)")
        self._g_mfu = m.gauge(
            "serving_mfu", "model FLOPs utilization: flops_total / "
            "(elapsed wall time × peak_flops)")
        self._g_busy = {
            phase: m.gauge(
                "serving_busy_fraction",
                "fraction of the timeline window a program of that phase "
                "was in flight (dispatch to results on the host); idle = "
                "the host's share, no program in flight", phase=phase)
            for phase in ("prefill", "decode", "swap", "idle")}

    # -------------------------------------------------------- per-program cost
    def _abstract_args(self, family: str, rung=None, sampling=False):
        """ShapeDtypeStruct argument tree mirroring the live program's
        fixed shapes — no device memory, no transfers.  ``rung``: the
        ``(rows, width)`` of a prefill program (default ``(prefill_batch,
        prefill_chunk)``).  ``sampling``: the five sampling vectors of a
        sampling engine's decode / prefill program too (the mask matrix of
        a ``logit_masks`` engine is not among them); without them the body
        takes its greedy branch."""
        import jax
        import jax.numpy as jnp

        srv = self.srv

        def sds(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        params = sds(srv.engine.params)
        cache = sds(srv._cache)
        slots, nb = srv.slots, srv._nbper

        def tables(rows, prefill=False):
            """The block-table operand as the engine packs it
            (``ServingEngine._operand_spec``): one table, or one per layer
            kind — a model with a recurrent state a row: the full kind's
            table and, in a prefill call, the slot of each row."""
            return srv._operand_spec(
                rows, {"ids": 1} if prefill else {})["block_tables"]

        def samp(rows):
            """The sampling vectors, in the body's order."""
            if not (sampling and srv.sampling):
                return ()
            return tuple(list(srv._operand_spec(rows, {}).values())[1:])

        if family == "decode":
            return (params, cache, i32(slots), i32(slots),
                    tables(slots)) + samp(slots)
        if family == "prefill":
            j, width = rung or (srv.prefill_batch, srv.prefill_chunk)
            if srv._draft is not None:       # fused target+draft prefill
                head = (params, sds(srv._draft.params), cache,
                        sds(srv._dcache))
            else:
                head = (params, cache)
            # (a self-drafting engine: the token after each row's window
            # and where its draft is read)
            more = (i32(j), i32(j)) if srv._self_draft else ()
            return head + (i32(j, width), tables(j, prefill=True), i32(j),
                           i32(j)) + more + samp(j)
        if family == "verify" and srv._self_draft:
            # the round's first program: the window is taken on the device
            # (the token, draft and count vectors), then the host's tokens,
            # tables, bases, valid and behind
            return (params, cache, i32(slots), i32(slots, srv.spec_tokens),
                    i32(slots), i32(slots), tables(slots), i32(slots),
                    i32(slots), i32(slots)) + samp(slots)
        if family == "draft" and srv._self_draft:
            # its second: the model's own module over what the first handed
            # on (its abstract results)
            carry = jax.eval_shape(
                srv._program_bodies["verify"],
                *self._abstract_args("verify", sampling=sampling))[2]
            return (params, cache, i32(slots, srv.spec_tokens), carry)
        if family == "verify":
            w = srv.spec_tokens + 1
            return (params, cache, i32(slots, w), i32(slots, nb),
                    i32(slots), i32(slots))
        if family == "draft":
            return (sds(srv._draft.params), sds(srv._dcache), i32(slots),
                    i32(slots), i32(slots, nb))
        raise KeyError(f"unknown program family {family!r}")

    def _shape_meta(self, family: str) -> Dict[str, int]:
        srv = self.srv
        if family == "decode":
            return {"rows": srv.slots, "width": 1}
        if family == "prefill":
            return {"rows": srv.prefill_batch, "width": srv.prefill_chunk}
        if family == "verify":
            return {"rows": srv.slots, "width": srv.spec_tokens + 1}
        if family == "draft" and srv._self_draft:
            # the module over the window's K + 1 positions
            return {"rows": srv.slots, "width": srv.spec_tokens + 1}
        if family == "draft":
            # K single-token scan steps per invocation
            return {"rows": srv.slots, "width": srv.spec_tokens}
        return {"rows": 0, "width": 0}

    def lower(self, family: str, rung=None, sampling=False):
        """``jax.stages.Lowered`` of the raw program body at the live
        program's fixed shapes (``rung``, ``sampling``:
        :meth:`_abstract_args`) — lowering only: it never compiles and never
        ticks the sentry.  ``None`` when the engine has not built that
        program.  ``.as_text()`` shows which attention implementation the
        program took (a Mosaic ``tpu_custom_call`` vs gather + XLA) and, of
        a model with a recurrent state a row, which kernels its state-kind
        layers lowered to and the element type of every operand."""
        import jax

        body = self.srv._program_bodies.get(family)
        if body is None:
            return None
        args = self._abstract_args(family, rung, sampling)
        ctx = getattr(self.srv, "_decode_ctx", self.srv._tp_ctx) \
            if family == "decode" else self.srv._tp_ctx
        with ctx():
            return jax.jit(body).lower(*args)

    def built(self, family: str) -> Optional[str]:
        """The name under which the engine RECORDED the program of
        ``family`` at its first call (``telemetry/programs.py``: ``decode``,
        ``verify``, ``draft``; ``prefill[<rows>x<width>]`` of the default
        rung), or None for a program not yet called."""
        srv = self.srv
        name = family if family != "prefill" else \
            f"prefill[{srv._rung_name((srv.prefill_batch, srv.prefill_chunk))}]"
        records = getattr(getattr(srv, "programs", None), "records", {})
        return name if name in records else None

    def _cost_analysis_flops(self, family: str, built: bool = True
                             ) -> Optional[float]:
        """``Lowered.cost_analysis()`` of the program that was BUILT — the
        engine's own jitted function at the abstract signature of its first
        call, sampling operands and all (a trace-cache hit: the sentry does
        not tick) — or, for a program not yet called (or with ``built``
        false), of the raw body at :meth:`_abstract_args`' hand-derived
        greedy shapes.  Lowering only, never a compile; ``None`` when the
        backend reports nothing."""
        try:
            name = self.built(family) if built else None
            if name is not None:
                ctx = getattr(self.srv, "_decode_ctx", self.srv._tp_ctx) \
                    if family == "decode" else self.srv._tp_ctx
                with ctx():
                    lowered = self.srv.programs.lowered(name)
            else:
                lowered = self.lower(family)
            if lowered is None:
                return None
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float((ca or {}).get("flops", 0.0) or 0.0)
            return flops if flops > 0.0 else None
        except Exception as e:   # backend without a cost model, etc.
            logger.warning(
                f"flops profiler: cost_analysis({family}) unavailable "
                f"({e}); using the analytic estimate")
            return None

    def profile_programs(self, refresh: bool = False
                         ) -> Dict[str, Dict[str, Any]]:
        """Per-program FLOPs for every program the engine has built so
        far: ``{"flops_per_call", "flops_analytic", "tokens_per_call",
        "source"}`` — cached per program (shapes are fixed once built)."""
        srv = self.srv
        dims = _model_dims(srv.engine.module.model_config)
        ddims = _model_dims(srv._draft.module.model_config) \
            if srv._draft is not None else None
        for family in srv._program_bodies:
            if family in ("kv_demote", "kv_promote"):
                continue                      # data movement: zero FLOPs
            if family in self._programs and not refresh:
                continue
            meta = self._shape_meta(family)
            fam_dims = ddims if family == "draft" else dims
            if family == "draft" and srv._self_draft:
                # the model's own module: ONE block at the model's widths
                fam_dims = {**dims, "layers": int(srv._self_draft["depth"])}
            comp = analytic_components(
                family, fam_dims, rows=meta["rows"], width=meta["width"],
                ctx=srv._cache_len)
            analytic = comp["head"] + comp["layers"]
            # the layer loop's correction is read off the greedy body (the
            # analytic components know matmuls, not a sampler's passes);
            # what the BUILT program reports beyond it — the sampler over its
            # operands, outside the loop — is counted once on top
            body = self._cost_analysis_flops(family, built=False)
            reported = self._cost_analysis_flops(family) \
                if self.built(family) else body
            flops, source = self._reconcile(
                family, body, comp, fam_dims["layers"])
            if reported is not None and body is not None:
                flops += max(reported - body, 0.0)
            self._programs[family] = {
                "rows": meta["rows"],
                "width": meta["width"],
                "flops_analytic": analytic,
                "flops_cost_analysis": reported,
                "flops_per_call": flops,
                "tokens_per_call": meta["rows"] * max(meta["width"], 1),
                "source": source,
                # which signature was priced: the program's own, or the
                # greedy body's derived one (never called yet)
                "priced": "built" if self.built(family) else "derived",
            }
        return self._programs

    @staticmethod
    def _reconcile(family: str, reported: Optional[float],
                   comp: Dict[str, float], layers: int):
        """Pick the per-call FLOPs from the cost-analysis report and the
        analytic components.  XLA's HLO cost analysis counts a
        ``fori_loop``/``scan`` body ONCE — a layer-scanned model's
        reported cost is ~(head + ONE layer), not (head + L layers) (the
        training flops profiler documents the same bias).  The analytic
        components tell the two expectations apart: if the report sits
        near the *unrolled* expectation it stands as-is; near the
        *scanned* expectation, the loop-body share scales by L; near
        neither (e.g. the draft rollout — a scan of scans), the
        deterministic analytic estimate wins and the raw report is kept
        for reference."""
        analytic = comp["head"] + comp["layers"]
        if reported is None:
            return analytic, "analytic"
        if layers <= 1:
            return reported, "cost_analysis"
        scanned = comp["head"] + comp["layers"] / layers
        if abs(reported - analytic) <= 0.25 * analytic:
            return reported, "cost_analysis"
        if abs(reported - scanned) <= 0.25 * scanned:
            body = max(reported - comp["head"], 0.0)
            return reported + (layers - 1) * body, \
                "cost_analysis+layer_scan"
        return analytic, "analytic"

    # ---------------------------------------------------------------- report
    def report(self, peak_flops: Optional[float] = None,
               window_s: Optional[float] = None) -> Dict[str, Any]:
        """FLOPs/MFU snapshot: per-program costs, cumulative model FLOPs
        (also pushed into ``serving_model_flops_total``), the MFU gauge
        against ``peak_flops`` (falls back to the constructor value), and
        the busy-fraction breakdown.  ``window_s`` overrides the MFU
        wall-clock denominator (default: time since the engine was
        built)."""
        srv = self.srv
        programs = self.profile_programs()
        calls = {"prefill": srv.prefill_calls,
                 "decode": srv.decode_steps,
                 "verify": srv.spec_rounds,
                 "draft": srv.spec_rounds
                 if srv._draft is not None or srv._self_draft else 0}
        total = sum(p["flops_per_call"] * calls.get(f, 0)
                    for f, p in programs.items())
        if total > self._last_total:
            self._c_model_flops.inc(total - self._last_total)
            self._last_total = total
        window = window_s if window_s is not None else \
            srv.timeline.now_us() / 1e6
        peak = peak_flops if peak_flops is not None else self.peak_flops
        mfu = (total / (window * peak)) if peak and window > 0 else None
        if mfu is not None:
            self._g_mfu.set(mfu)
        busy = busy_fractions(srv.timeline)
        for phase, g in self._g_busy.items():
            g.set(busy[phase])
        gen = int(srv._c_gen_tokens.value)
        return {
            "programs": {f: dict(p) for f, p in programs.items()},
            "program_calls": {f: int(calls.get(f, 0)) for f in programs},
            "model_flops_total": total,
            "flops_per_generated_token": (total / gen) if gen else None,
            "generated_tokens": gen,
            "window_s": window,
            "peak_flops": peak,
            "mfu": mfu,
            "busy_fractions": busy,
        }
