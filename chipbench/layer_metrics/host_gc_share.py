"""Share of the scheduler's step time spent in Python's collector: ``gc_s``
(seconds of the collector runs that began inside the step, on the step's
own thread) summed over the window's steps, over the sum of their
durations.  Also prints the number of runs."""
from chipbench.layer_metrics import _host_segments as hs
from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "host_gc_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    steps, _ = ps.steps_in_window(ctx)
    steps = [s for s, _ in steps or () if "gc_s" in s.get("args", {})]
    if not steps:
        return None
    gc_s = sum(s["args"]["gc_s"] for s in steps)
    print(f"chipbench: collector runs inside the window's {len(steps)} "
          f"steps: {sum(s['args']['gc_n'] for s in steps)}, "
          f"{gc_s * 1e3:.3f} ms", flush=True)
    return hs.share(ctx, gc_s, sum(s["t1"] - s["t0"] for s in steps))
