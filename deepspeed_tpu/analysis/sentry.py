"""Recompile sentry: runtime trace-count enforcement for the compile
contracts the serving and training engines promise.

The paged serving stack's performance story rests on a *compile budget*:
a whole serving trace is exactly 1 decode program + a prefill program a
rung of the prefill ladder, a speculative trace one more.  Today
the tests assert ``compile_count`` after the fact — but ``compile_count``
only counts programs the engine *knowingly* built; a silent retrace
inside one of them (a weak-type flip, a new input shape leaking through,
a donated-buffer layout change) never shows up there, it just makes every
future step recompile.  The sentry closes that gap at the source: every
jitted entry point registers its *Python body* here, and since XLA runs
that body exactly once per (re)trace, counting body executions counts
compilations — with the traced abstract signature captured at the moment
it happens, so a violation can print the exact signature diff that caused
the retrace.

Usage::

    sentry = RecompileSentry(name="serving", total_budget=2)
    decode = jax.jit(sentry.wrap(step, "decode"), donate_argnums=(1,))

In ``strict`` mode (``ServingEngine(debug_checks=True)``) a trace beyond
a per-entry budget — or beyond the engine's declared total — raises
:class:`RetraceError` *at trace time*, naming the entry point and diffing
the offending abstract signature against the previous trace's.  Non-
strict mode just counts: ``retraces_observed`` feeds
``ServingEngine.stats()`` so production telemetry sees contract drift
without paying for enforcement.  Either way the wrapper's overhead is
zero on the hot path — the wrapped body only executes while tracing.

As corroborating global telemetry, :func:`install_compile_listener` hooks
``jax.monitoring`` — the program's one listener (:class:`BuildListener`),
installed by both engines as they are constructed.  It counts backend
compilations process-wide (``backend_compiles()``: this catches compiles
that never went through a registered entry point) and KEEPS what JAX says
of every function it builds: the ``trace`` / ``lower`` / ``compile`` time
spans, by function, as X-events on the process's start-up ring
(``telemetry/trace.py setup_timeline``, beside the engines' ``build``
spans, whose ``program`` they carry), whether the persistent cache hit, and
the counters ``program_build_seconds_total{phase}`` /
``compile_cache_hits_total`` / ``compile_cache_misses_total`` on the
process's registry.  The sentry's own ``jit_trace`` / ``retrace`` instants
stay where they were, on the engine's ring.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple


class RetraceError(RuntimeError):
    """A registered entry point traced past its compile budget."""

    def __init__(self, message: str, name: str = "",
                 signatures: Optional[Sequence[Tuple[str, ...]]] = None):
        super().__init__(message)
        self.name = name
        self.signatures = list(signatures or [])


def _describe_leaf(path: str, x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        weak = "~" if getattr(x, "weak_type", False) else ""
        return f"{path}: {dtype}{weak}[{','.join(map(str, shape))}]"
    r = repr(x)
    return f"{path}: {type(x).__name__}=" + (r[:40] + "…" if len(r) > 40
                                             else r)


def abstract_signature(args: tuple, kwargs: dict) -> Tuple[str, ...]:
    """One line per pytree leaf: ``path: dtype[shape]`` for array-likes
    (tracers included — their avals carry shape/dtype), ``path:
    type=value`` for static leaves.  Two traces of the same program differ
    exactly where their signatures differ."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path((args, kwargs))[0]
    return tuple(_describe_leaf(jax.tree_util.keystr(p), x)
                 for p, x in leaves)


def signature_diff(prev: Sequence[str], cur: Sequence[str]) -> List[str]:
    """Human-readable diff of two abstract signatures — only the leaves
    that moved (plus arity changes)."""
    out: List[str] = []
    for i in range(max(len(prev), len(cur))):
        a = prev[i] if i < len(prev) else "<absent>"
        b = cur[i] if i < len(cur) else "<absent>"
        if a != b:
            out.append(f"  - {a}\n  + {b}")
    return out or ["  (signatures identical — retrace caused by a "
                   "non-argument change: new wrapper identity, donated "
                   "layout, or jit cache eviction)"]


@dataclasses.dataclass
class _Entry:
    name: str
    budget: Optional[int]               # None = unbudgeted (count only)
    traces: int = 0
    signatures: List[Tuple[str, ...]] = dataclasses.field(
        default_factory=list)

    #: keep previous + current signature only — all any diff ever prints;
    #: signatures hold one string per pytree leaf, so a longer history on
    #: a large-params entry is retained memory with no reader
    _KEEP = 2

    def record(self, sig: Tuple[str, ...]) -> None:
        self.traces += 1
        self.signatures.append(sig)
        if len(self.signatures) > self._KEEP:
            del self.signatures[0]


class RecompileSentry:
    """Per-engine trace-count monitor over registered jitted entry points.

    Parameters
    ----------
    name:          label for error messages ("serving", "inference", ...).
    strict:        raise :class:`RetraceError` at trace time when an entry
                   exceeds its budget or the total exceeds
                   ``total_budget``.  Off: count only.
    total_budget:  engine-wide compiled-program ceiling (the ≤2/≤3
                   contracts); ``None`` = per-entry budgets only.
    """

    def __init__(self, name: str = "", strict: bool = False,
                 total_budget: Optional[int] = None):
        self.name = name
        self.strict = bool(strict)
        self.total_budget = total_budget
        self._entries: Dict[str, _Entry] = {}
        #: optional telemetry hook, called with the :class:`_Entry` on
        #: EVERY trace (before any strict-mode raise, so a fatal retrace
        #: still lands on the caller's timeline).  The serving engine
        #: points this at its trace timeline — each compile shows up as a
        #: ``jit_trace`` / ``retrace`` event next to the scheduler events
        #: that provoked it (telemetry/trace.py).
        self.on_trace: Optional[Callable[[_Entry], None]] = None

    # ------------------------------------------------------------- registry
    def register(self, name: str, budget: Optional[int] = 1) -> _Entry:
        """Declare an entry point (idempotent — re-registering updates the
        budget and keeps counts)."""
        e = self._entries.get(name)
        if e is None:
            e = self._entries[name] = _Entry(name=name, budget=budget)
        else:
            e.budget = budget
        return e

    def wrap(self, fn: Callable, name: str,
             budget: Optional[int] = 1) -> Callable:
        """Wrap a to-be-jitted Python body: each execution of the returned
        callable IS one trace (XLA replays compiled programs without ever
        re-entering Python), so pass the result straight to ``jax.jit`` /
        ``shard_map``.  Zero overhead once compiled."""
        entry = self.register(name, budget)

        @functools.wraps(fn)
        def traced(*args, **kwargs):
            self._record(entry, args, kwargs)
            return fn(*args, **kwargs)

        return traced

    # ------------------------------------------------------------- counting
    def _record(self, entry: _Entry, args: tuple, kwargs: dict) -> None:
        entry.record(abstract_signature(args, kwargs))
        if self.on_trace is not None:
            self.on_trace(entry)
        if not self.strict:
            return
        over_entry = entry.budget is not None and entry.traces > entry.budget
        over_total = self.total_budget is not None and \
            self.traces > self.total_budget
        if over_entry or over_total:
            raise RetraceError(self._violation(entry, over_entry),
                               name=entry.name,
                               signatures=entry.signatures)

    def _violation(self, entry: _Entry, over_entry: bool) -> str:
        label = f"{self.name}:{entry.name}" if self.name else entry.name
        if over_entry:
            head = (f"recompile sentry: '{label}' traced {entry.traces}x "
                    f"(budget {entry.budget}) — the compiled program is "
                    "not shape-stable")
        else:
            head = (f"recompile sentry: trace of '{label}' pushed the "
                    f"engine past its total compile budget "
                    f"({self.traces} > {self.total_budget})")
        if len(entry.signatures) >= 2:
            diff = signature_diff(entry.signatures[-2], entry.signatures[-1])
            head += ("\nabstract signature diff (previous trace -> this "
                     "trace):\n" + "\n".join(diff))
        head += "\nper-entry traces: " + ", ".join(
            f"{e.name}={e.traces}" for e in self._entries.values())
        return head

    # -------------------------------------------------------------- reading
    @property
    def traces(self) -> int:
        return sum(e.traces for e in self._entries.values())

    @property
    def retraces_observed(self) -> int:
        """Traces beyond the declared contract — 0 means every compiled
        program was built exactly as declared.  Counts both per-entry
        overruns AND total-budget drift (an unexpected NEW entry can blow
        the engine total while every entry stays within its own budget);
        ``max`` of the two views so one overrun is never double-counted."""
        per_entry = sum(max(0, e.traces - e.budget)
                        for e in self._entries.values()
                        if e.budget is not None)
        over_total = max(0, self.traces - self.total_budget) \
            if self.total_budget is not None else 0
        return max(per_entry, over_total)

    def report(self) -> Dict[str, Dict[str, Any]]:
        return {e.name: {"traces": e.traces, "budget": e.budget}
                for e in self._entries.values()}

    def reset_counts(self) -> None:
        for e in self._entries.values():
            e.traces = 0
            e.signatures.clear()


# ----------------------------------------------------- global compile probe
#: ``jax.monitoring`` event -> the phase of a function's build it times.  The
#: full names matter: ``backend_compile`` is also what :func:`backend_compiles`
#: counts, once a compile and not once a phase
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _BACKEND_COMPILE: "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class BuildListener:
    """What ``jax.monitoring`` says of every function JAX builds, put on the
    process's start-up ring (``telemetry/trace.py setup_timeline``) and
    counted on the process's registry (``telemetry/metrics.py
    process_registry``); :func:`install_compile_listener` registers ONE.

    JAX times a build's phases around its own code (``dispatch.
    log_elapsed_time``): a scalar as a phase begins, a duration and a time
    span — ``(event, start, end, fun_name=)`` on ``time.time()`` — as it
    ends.  :meth:`on_span` turns the spans into the ring's ``trace`` /
    ``lower`` / ``compile`` X-events with ``args.fn`` (``jit(...)``
    stripped) and, while an engine's ``build`` span is open, ``program``.
    ``jnp`` functions called WHILE a function is traced are traced
    themselves, thousands to a program: :meth:`on_begin` counts how deep
    the calling thread is in phases, and only a phase that ends with none
    open around it is kept — the others lie inside it.  The persistent
    cache says ``cache_hits`` or ``cache_misses`` (and a hit's
    ``cache_retrieval_time_sec``) just before the ``compile`` span they
    belong to ends: they ride on it as ``cache`` = ``hit`` | ``miss`` |
    ``off`` (no cache, or an entry under JAX's thresholds: neither was
    said) and ``retrieval_s``.

    The clock.  The ring runs on ``clock`` (``time.perf_counter``), JAX
    stamps with ``wall`` (``time.time``): ONE pair of reads, here, gives
    the offset every event is moved by.  The pair is good to the
    microsecond between its two reads; what it cannot see is the wall clock
    being steered afterwards — NTP slews it by up to 0.5 ms a second (at
    worst 60 ms over a two-minute start, as a rule a hundredth of that) and
    a step moves every later event by the step.  Durations are JAX's own
    differences and do not suffer; positions do, which is why whoever nests
    these events into the engines' spans does it by midpoint
    (``telemetry/trace.py _nest``) and by the ``program`` stamp.
    """

    def __init__(self, clock=time.perf_counter, wall=time.time,
                 registry=None):
        from ..telemetry.metrics import process_registry

        self.count = 0                      # backend compiles, process-wide
        self.offset_s = clock() - wall()
        self._depth = threading.local()
        self._cache: Optional[str] = None
        self._retrieval_s: Optional[float] = None
        m = registry if registry is not None else process_registry()
        self._seconds = {
            phase: m.counter(
                "program_build_seconds_total",
                "seconds JAX spent building functions, outermost phases "
                "only (trace: Python to jaxpr; lower: jaxpr to MLIR; "
                "compile: XLA / Mosaic, or the persistent cache's "
                "retrieval)", phase=phase)
            for phase in _BUILD_EVENTS.values()}
        self._hits = m.counter(
            "compile_cache_hits_total",
            "executables the persistent compilation cache returned")
        self._misses = m.counter(
            "compile_cache_misses_total",
            "executables compiled and written to the persistent cache")

    def on_begin(self, event, value, **kwargs):
        if event in _BUILD_EVENTS:
            self._depth.n = getattr(self._depth, "n", 0) + 1

    def on_event(self, event, **kwargs):
        if event == _CACHE_HIT:
            self._cache = "hit"
            self._hits.inc()
        elif event == _CACHE_MISS:
            self._cache = "miss"
            self._misses.inc()

    def on_duration(self, event, duration, **kwargs):
        if event == _CACHE_RETRIEVAL:
            self._retrieval_s = duration
        elif event == _BACKEND_COMPILE:
            self.count += 1

    def on_span(self, event, start, end, fun_name="", **kwargs):
        phase = _BUILD_EVENTS.get(event)
        if phase is None:
            return
        depth = self._depth.n = max(getattr(self._depth, "n", 1) - 1, 0)
        args: Dict[str, Any] = {}
        if phase == "compile":
            # said since the compile before this one ended: this one's
            args["cache"] = self._cache or "off"
            if self._retrieval_s is not None and self._cache == "hit":
                args["retrieval_s"] = self._retrieval_s
            self._cache = self._retrieval_s = None
        if depth:
            return                          # inside the phase still open
        from ..telemetry.trace import setup_timeline

        timeline = setup_timeline()
        fn = str(fun_name)
        if fn.startswith("jit(") and fn.endswith(")"):
            fn = fn[4:-1]
        if timeline.building is not None:
            args["program"] = timeline.building
        self._seconds[phase].inc(end - start)
        at = (start + self.offset_s - timeline.epoch_s) * 1e6
        timeline.complete(phase, at, end_us=at + (end - start) * 1e6,
                          fn=fn, **args)


_listener: Optional[BuildListener] = None


def install_compile_listener() -> BuildListener:
    """Register the process's :class:`BuildListener` with
    ``jax.monitoring`` — the program's one registration site (idempotent;
    both engines call it as they are constructed).  Its body runs when
    something is being built and never in a step: a compiled program's call
    reaches none of JAX's build phases."""
    global _listener
    if _listener is None:
        import jax.monitoring

        _listener = listener = BuildListener()
        jax.monitoring.register_scalar_listener(listener.on_begin)
        jax.monitoring.register_event_listener(listener.on_event)
        jax.monitoring.register_event_duration_secs_listener(
            listener.on_duration)
        jax.monitoring.register_event_time_span_listener(listener.on_span)
    return _listener


def backend_compiles() -> Optional[int]:
    """Compiles observed process-wide since the listener was installed
    (``None`` before :func:`install_compile_listener`)."""
    return _listener.count if _listener is not None else None
