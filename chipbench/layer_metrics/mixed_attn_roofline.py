"""The paged walk of a model with sliding-window and full layers against
the memory roofline: the time the chip needs to read, once and at peak HBM
bandwidth, the K and V a decode step's attention NEEDS — the keys inside
each layer's reach, ``kv_visible`` as the scheduler reckons it from its
rows' lengths (summed over rows and layers; ``kv_visible_share``: a
property of the traffic and the window, not of the read), at the family's
``visible_kv_bytes`` (4,096 B a key a layer at 8 KV heads x 128 in bf16) —
over the device time of the walk kernel (``mixed_attn_ms``).
Memory-bound: one query a row against ``min(ctx, window)`` or ``ctx`` keys.
``costs.decode_bytes_per_step`` counts every VALID key in every layer as
needed, which a sliding layer does not read, so this cell is not in
``paged_attn_roofline`` / ``decode_roofline`` (PERF.md section 7).  The
walk fetches whole blocks, and the block of the first visible key is partly
masked: bytes it moves beyond the needed keys — a walk that read behind
the window included — lower this share, as time spent on anything else
does; they cannot raise it.  A family without ``visible_kv_bytes``, or a
ring without the counters, gives ``None``."""
from chipbench import families
from chipbench.layer_metrics import kv_visible_share, mixed_attn_ms

SPECS = [{"name": "mixed_attn_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    step_s = mixed_attn_ms.per_run_s(ctx)
    spans = kv_visible_share.decode_counts(ctx)
    if not step_s or not spans or not ctx["peaks"] \
            or "family" not in ctx["config"]:
        return None
    family = families.load(ctx["config"])
    if not hasattr(family, "visible_kv_bytes"):
        return None
    needed = family.visible_kv_bytes(
        ctx["config"], sum(a["kv_visible"] for a in spans) / len(spans))
    return 100.0 * needed / ctx["peaks"]["hbm_bytes_per_s"] / step_s
