"""The sliding layers' latent read of a decode step against the chip's
roofline: the LARGER of the time the chip needs to read, once and at peak HBM
bandwidth, the latents of the keys the rows' queries KEEP under the window —
the mean ``kv_window`` of the window's ``decode`` spans (at most 513 a row x
the sliding layers) x the family's ``latent_bytes_per_key`` of a sliding
layer (1,088 values = 2,176 B) — and the time the absorbed products over them
take at the bf16 peak — over the device time of ``paged_window_latent_attn``
(``window_latent_attn_ms``).  The walk lands whole ring blocks from the one
that holds the window's first key: bytes beyond the visible keys' lower this
share and cannot raise it."""
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "window_latent_attn_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    family = sl.family_of(ctx)
    means = sl.decode_means(ctx, "kv_window")
    if family is None or not means:
        return None
    cfg = ctx["config"]
    return sl.share(
        ctx, sl.per_run_s(ctx["trace"], sl.DECODE, sl.WINDOW_READ),
        means["kv_window"] * family.latent_bytes_per_key(cfg, "sliding"),
        means["kv_window"] * family.latent_flops_per_key(cfg, "sliding"))
