"""The selected latent read of a decode step against the chip's roofline: the
LARGER of the time the chip needs to read, once and at peak HBM bandwidth,
the latents of the keys the step CHOSE — the mean ``kv_selected`` of the
window's ``decode`` spans x the full layers x the family's
``latent_bytes_per_key`` (576 values = 1,152 B) — and the time the absorbed
products over them take at the bf16 peak (x ``latent_flops_per_key``: every
head's score over 576 values and its output over 512) — over the device time
of ``paged_sparse_latent_attn`` (``sparse_latent_attn_ms``).  The kernel
lands every BLOCK that holds a chosen key (``latent_read_share``) and
multiplies all of it: bytes and products beyond the chosen keys' lower this
share, as time spent on anything else does; they cannot raise it."""
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "sparse_latent_attn_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    family = sl.family_of(ctx)
    means = sl.decode_means(ctx, "kv_selected")
    if family is None or not means:
        return None
    cfg = ctx["config"]
    keys = means["kv_selected"] * family.arch(cfg)["full_layers"]
    return sl.share(
        ctx, sl.per_run_s(ctx["trace"], sl.DECODE, sl.SELECTED_READ),
        keys * family.latent_bytes_per_key(cfg),
        keys * family.latent_flops_per_key(cfg))
