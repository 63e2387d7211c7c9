"""Share of a row's valid keys that lie inside its layers' reach in a
decode step: ``kv_visible`` over ``kv_valid``, both summed over the
``decode`` spans of the program's ring that start inside the window.  The
scheduler of a model that mixes sliding-window and full layers puts both on
the span from its own bookkeeping (``ServingEngine._kv_reach``: each live
row's length, summed over the rows and the LAYERS — all of a row's keys in
a full layer, at most the window in a sliding one).  It is ARITHMETIC on
lengths and the configuration's window, not a reading of what the kernels
fetched: it says how far the traffic engages the windows (three sliding
layers of 4,096 keys to one full layer read ~63 % at 8k keys a row; 100 %
says no row is past the window) and is what ``mixed_attn_roofline`` counts
as needed.  What holds the kernels to the window is the comparison with the
plain reference (whose every-key-attended control fails) and their device
time.  A program whose ring carries no such args (any model of one layer
kind, or the parent of the PR that added them) gives ``None``."""
from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "kv_visible_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def decode_counts(ctx, counter="kv_visible"):
    """The args of the window's ``decode`` spans that carry ``counter``, or
    None."""
    events = ps.window_events(ctx)
    lo, hi = ctx["window"]
    spans = [e["args"] for e in events or ()
             if e["ph"] == "X" and e["name"] == "decode"
             and lo <= e["t0"] < hi and counter in e.get("args", {})]
    return spans or None


def read(ctx):
    spans = decode_counts(ctx)
    valid = sum(a["kv_valid"] for a in spans or ())
    if not valid:
        return None
    return 100.0 * sum(a["kv_visible"] for a in spans) / valid
