"""The benchmark's own spans and its traced window.

A span is recorded twice from one ``with``: its duration on the host's
clock (always, for the per-layer metrics that need no profiler), and as a
``jax.profiler.TraceAnnotation`` (free while no trace is being taken), so
that in a traced run the device's idle gaps can be laid beside what the
host was doing on the profiler's own clock.  Spans inside the program are
the program's matter; these sit around the calls into it.
"""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

#: how much of a --trace 1 run is traced: traces are large and tracing
#: slows the host, so some seconds of steady state — enough for a few whole
#: executions of the slowest step program (4.2 s, the four-chip cell)
TRACE_SECONDS = 20.0


class Spans:
    """``spans(name)`` is a context manager; ``durations[name]`` and
    ``starts[name]`` (``time.perf_counter()``) grow by one entry a use."""

    def __init__(self):
        self.durations: Dict[str, List[float]] = {}
        self.starts: Dict[str, List[float]] = {}

    def within(self, name: str, lo: float, hi: float) -> List[float]:
        """Durations of the spans of that name that began in [lo, hi)."""
        return [d for t, d in zip(self.starts.get(name, []),
                                  self.durations.get(name, []))
                if lo <= t < hi]

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.starts.setdefault(name, []).append(t0)
                self.durations.setdefault(name, []).append(
                    time.perf_counter() - t0)


class TraceWindow:
    """Traces ``length`` seconds that start ``start`` seconds into the
    measured window; ``poll`` is called between steps.  Off (``enabled``
    false) every call is a no-op."""

    def __init__(self, enabled: bool, seconds: float,
                 rehearse: bool = False, keep_dir: Optional[str] = None):
        #: a CPU rehearsal's trace has no device plane: nothing to reduce
        self.rehearse = rehearse
        #: keep the raw trace there (looking at one by hand); default:
        #: deleted once reduced
        self.keep_dir = keep_dir
        self.length = min(TRACE_SECONDS, seconds / 2)
        self.start = min(2.0, seconds / 4)
        self.dir: Optional[str] = None
        self._span: Any = None
        self.state = "before" if enabled else "done"
        self.reduced: Optional[Dict[str, Any]] = None

    def poll(self, since_open: float) -> None:
        if self.state == "before" and since_open >= self.start:
            self._begin()
        elif self.state == "tracing" \
                and since_open >= self.start + self.length:
            self.finish()

    def _begin(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # no Python frames: slow, huge
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation("cb.window")
        self._span.__enter__()
        self.state = "tracing"

    def finish(self) -> None:
        """Stop, reduce, delete the trace.  Safe to call when not tracing."""
        if self.state != "tracing":
            self.state = "done"
            return
        import jax

        from chipbench import trace_reduce

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"
        try:
            trace = trace_reduce.load_xplane(self.dir)
            if not self.rehearse:
                self.reduced = trace_reduce.reduce(trace)
        finally:
            if self.keep_dir is None:
                shutil.rmtree(self.dir, ignore_errors=True)
            else:
                shutil.rmtree(self.keep_dir, ignore_errors=True)
                shutil.move(self.dir, self.keep_dir)
