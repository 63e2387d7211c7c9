"""Share of the scheduler's host time during which its thread was NOT on a
CPU: per step, the self time (duration minus the in-flight spans) less the
thread-CPU seconds spent outside the in-flight spans (``cpu_s`` -
``flight_cpu_s``), floored at 0, summed over the window's steps, over the
sum of their self times.  The program has host work for every one of those
seconds and its thread is asleep: blocked in a runtime call that waits
(a host-to-device put that returns when the transfer has completed),
descheduled, or in a page fault — time no faster Python would win back."""
from chipbench.layer_metrics import _host_segments as hs
from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "host_offcpu_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    steps, _ = ps.steps_in_window(ctx)
    steps = [(s["args"], own) for s, own in steps or ()
             if "cpu_s" in s.get("args", {})]
    if not steps:
        return None
    off = sum(max(own - (a["cpu_s"] - a["flight_cpu_s"]), 0.0)
              for a, own in steps)
    return hs.share(ctx, off, sum(own for _, own in steps))
