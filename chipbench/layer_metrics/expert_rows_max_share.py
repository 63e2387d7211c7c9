"""How uneven a decode step's routing is: the fullest expert's rows over
the step's live rows, a layer — ``expert_rows_max_sum`` (the layers'
largest groups, summed) over ``expert_rows`` (the routed rows, summed over
layers), both summed over the ``decode`` spans of the program's ring that
start inside the window.  Under top-1 a layer's grouped matmul waits for
its fullest group: ``1 / experts`` is even routing (6.25 % at 16), 100 %
every row in one expert.  A program whose spans carry no
``expert_rows_max_sum`` (a dense family, or the parent of the PR that added
the counter) gives ``None``."""
from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "expert_rows_max_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    events = ps.window_events(ctx)
    lo, hi = ctx["window"]
    routed = [e["args"] for e in events or ()
              if e["ph"] == "X" and e["name"] == "decode"
              and lo <= e["t0"] < hi
              and e.get("args", {}).get("expert_rows_max_sum")
              and e["args"].get("expert_rows")]
    if not routed:
        return None
    return 100.0 * sum(a["expert_rows_max_sum"] for a in routed) \
        / sum(a["expert_rows"] for a in routed)
