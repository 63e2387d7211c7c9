"""Learned sparse attention against the memory roofline: the time the chip
needs to read, once and at peak HBM bandwidth, the indexer keys a decode
step scores and the K and V of the keys it chooses, over the device time
of ALL the mechanism's kernels (``sparse_attn_ms``: scoring, selection and
the read).  Memory-bound: a step scores ``ctx`` keys of 128 B and attends
``min(ctx, topk)`` tokens of 2 KB with one query a row.  The bytes are the
family's (``index_bytes`` / ``selected_kv_bytes``) of the mean
``index_keys`` / ``kv_selected`` the program counted on the device and put
on the window's ``decode`` spans: what the mechanism NEEDS.  The read
fetches whole blocks (``kv_read_share``), so bytes it moves beyond the
chosen tokens' lower this share, as time spent on anything else does.  A
family without those functions, or a ring without the counters, gives
``None``."""
from chipbench import families
from chipbench.layer_metrics import _program_spans as ps
from chipbench.layer_metrics import sparse_attn_ms

SPECS = [{"name": "sparse_attn_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def decode_counts(ctx):
    """The args of the window's ``decode`` spans that carry the sparse
    attention counters, or None."""
    events = ps.window_events(ctx)
    lo, hi = ctx["window"]
    spans = [e["args"] for e in events or ()
             if e["ph"] == "X" and e["name"] == "decode"
             and lo <= e["t0"] < hi and "kv_selected" in e.get("args", {})]
    return spans or None


def read(ctx):
    step_s = sparse_attn_ms.per_run_s(ctx["trace"])
    spans = decode_counts(ctx)
    if not step_s or not spans or not ctx["peaks"] \
            or "family" not in ctx["config"]:
        return None
    family = families.load(ctx["config"])
    if not hasattr(family, "index_bytes"):
        return None
    n = len(spans)
    needed = family.index_bytes(
        ctx["config"], sum(a["index_keys"] for a in spans) / n) \
        + family.selected_kv_bytes(
            ctx["config"], sum(a["kv_selected"] for a in spans) / n)
    return 100.0 * needed / ctx["peaks"]["hbm_bytes_per_s"] / step_s
