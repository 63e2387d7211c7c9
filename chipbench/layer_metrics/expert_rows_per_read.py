"""How many routed rows share each expert's weight read in a decode step:
``expert_rows`` over ``experts_touched``, both summed over the ``decode``
spans of the program's ring that start inside the window (the engine puts
the step's routing record on the span: per layer the experts that received
at least one live row and the rows routed, summed over layers).  The
batch is what pays for reading the experts; at 64 live rows x top-8 of 64
it reads 8.  A program whose ring carries no routing (a dense family, or
the parent of the PR that added it) gives ``None``."""
from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "expert_rows_per_read", "unit": "rows", "better": "higher",
          "source": "program_span", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    events = ps.window_events(ctx)
    lo, hi = ctx["window"]
    routed = [e["args"] for e in events or ()
              if e["ph"] == "X" and e["name"] == "decode"
              and lo <= e["t0"] < hi and e.get("args", {}).get(
                  "experts_touched")]
    if not routed:
        return None
    return sum(a["expert_rows"] for a in routed) \
        / sum(a["experts_touched"] for a in routed)
