"""What share of a decode step's needed bytes are convolution tails: the
tails the step's live rows read and write back, all layers (``tail_bytes``
on the ``decode`` spans of the program's ring that start inside the window,
mean a span), over those plus the weights and the valid K/V the step must
read (``costs.decode_bytes_per_step``).  A model without tails (every other
family: its spans carry no ``tail_bytes``) gives ``None``."""
from chipbench import costs
from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "kv_tail_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def read(ctx):
    events = ps.window_events(ctx)
    lo, hi = ctx["window"]
    tails = [e["args"]["tail_bytes"] for e in events or ()
             if e["ph"] == "X" and e["name"] == "decode"
             and lo <= e["t0"] < hi and e.get("args", {}).get("tail_bytes")]
    if not tails:
        return None
    tail = sum(tails) / len(tails)
    needed = costs.decode_bytes_per_step(
        ctx["config"], ctx["counters"]["mean_valid_kv_tokens"],
        ctx["counters"])
    return 100.0 * tail / (tail + needed)
