"""Share of the scheduler's step time during which no device program was
in flight: sum over the window's ``step`` spans of (duration - union of the
in-flight spans inside it) over the sum of their durations.  The device has
nothing to run for that long, so it should sit within a point or so of
``device_idle.serve``.  Also prints the self time of each phase per step."""
import statistics

from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "sched_host_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    steps, events = ps.steps_in_window(ctx)
    if not steps:
        return None
    lo, hi = ctx["window"]
    total = sum(s["t1"] - s["t0"] for s, _ in steps)
    flights = [e for e in events if ps.in_flight(e)]
    phases = [e for e in events if e["ph"] == "X" and e["name"] in ps.PHASES
              and lo <= e["t0"] < hi]
    own = {p: [ps.self_s(e, flights) * 1e3 for e in phases if e["name"] == p]
           for p in ps.PHASES}
    covered = sum(e["t1"] - e["t0"] for e in phases)
    print("chipbench: host self time per step by phase, ms (median / "
          "mean): " + "; ".join(
              f"{p} {statistics.median(v):.3f} / {statistics.fmean(v):.3f}"
              for p, v in own.items() if v)
          + f"; phases cover {100 * covered / total:.2f} % of {len(steps)} "
          "steps", flush=True)
    return 100.0 * sum(own_s for _, own_s in steps) / total
