"""Host wall time of one ``srv.step()``: the benchmark's own span around
the call, median over the steps of the measured window."""
import statistics

SPECS = [{"name": "step_wall_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "itl_p95_ms"}]


def read(ctx):
    steps = ctx["spans"].within("cb.step", *ctx["window"])
    return statistics.median(steps) * 1e3 if steps else None
