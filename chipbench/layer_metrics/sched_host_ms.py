"""Host time of one scheduler step: the ``step`` span's duration minus the
union of the in-flight spans inside it, median over the window's steps."""
import statistics

from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "sched_host_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "itl_p95_ms"}]


def read(ctx):
    steps, _ = ps.steps_in_window(ctx)
    if not steps:
        return None
    return statistics.median(own for _, own in steps) * 1e3
