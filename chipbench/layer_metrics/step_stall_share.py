"""Share of the scheduler's step time lost to STALLED steps.  Steps come in
two shapes, with and without a ``prefill`` call, a prefill step being about
twice the other; per shape, every step longer than ``FACTOR`` x that
shape's median over the window is a stall and its excess over the median
is lost time: the excesses summed, over the sum of all step durations.
Also prints the program's own ``stall`` instants of the window by
``cause`` (it names a stall against a running median as it happens).  Read
only from a program that records ``cpu_s`` on its steps, the one that
names its stalls."""
import statistics

from chipbench.layer_metrics import _host_segments as hs
from chipbench.layer_metrics import _program_spans as ps

#: the program's ``inference/serving.py STALL_FACTOR``
FACTOR = 3.0

SPECS = [{"name": "step_stall_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    steps, events = ps.steps_in_window(ctx)
    steps = [s for s, _ in steps or () if "cpu_s" in s.get("args", {})]
    if not steps:
        return None
    lo, hi = ctx["window"]
    prefilled = {e["args"]["step"] for e in events
                 if e["ph"] == "X" and e["name"] == "prefill"}
    shapes = {False: [], True: []}
    for s in steps:
        shapes[s["args"]["step"] in prefilled].append(s["t1"] - s["t0"])
    lost, stalled = 0.0, 0
    for durs in shapes.values():
        if durs:
            median = statistics.median(durs)
            over = [d - median for d in durs if d > FACTOR * median]
            lost += sum(over)
            stalled += len(over)
    causes = {}
    for e in events:
        if e["ph"] == "i" and e["name"] == "stall" and lo <= e["t0"] < hi:
            cause = e["args"]["cause"]
            causes[cause] = causes.get(cause, 0) + 1
    print(f"chipbench: {stalled} of {len(steps)} steps over {FACTOR:g} x "
          f"their shape's median lost {lost:.4f} s; the program's stall "
          f"events by cause: {dict(sorted(causes.items())) or 'none'}",
          flush=True)
    return hs.share(ctx, lost, sum(sum(d) for d in shapes.values()))
