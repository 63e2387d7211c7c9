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
``jax.monitoring``'s ``/jax/core/compile`` duration events (the lowering
hooks XLA itself reports through) and counts backend compilations
process-wide; this catches compiles that never went through a registered
entry point.
"""

from __future__ import annotations

import dataclasses
import functools
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
#: the full prefix matters: "/jax/core/compile" alone would also match the
#: jaxpr-trace and MLIR-lowering duration events (3 counts per compile)
_BACKEND_COMPILE_PREFIX = "/jax/core/compile/backend_compile"


class _CompileCounter:
    __slots__ = ("count",)

    def __init__(self):
        self.count = 0


_counter: Optional[_CompileCounter] = None


def install_compile_listener() -> _CompileCounter:
    """Process-wide backend-compile counter through ``jax.monitoring``'s
    duration events (idempotent; the listener is a string-prefix check per
    compile — nothing on the step path)."""
    global _counter
    if _counter is None:
        import jax.monitoring

        counter = _CompileCounter()

        def _on_duration(event, duration, **kwargs):
            if event.startswith(_BACKEND_COMPILE_PREFIX):
                counter.count += 1

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _counter = counter
    return _counter


def backend_compiles() -> Optional[int]:
    """Compiles observed process-wide since the listener was installed
    (``None`` before :func:`install_compile_listener`)."""
    return _counter.count if _counter is not None else None
