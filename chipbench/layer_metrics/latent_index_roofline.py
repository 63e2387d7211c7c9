"""Scoring and selection of a decode step against the chip's roofline: the
LARGER of the time the chip needs to read every valid index key once at peak
HBM bandwidth — the mean ``index_keys`` of the window's ``decode`` spans x
the full layers x the family's ``index_bytes_per_key`` (128 values = 256 B)
— and the time the scores' products take at the bf16 peak (x
``index_flops_per_key``: 64 heads' dot products over 128 values, their ReLU
and weighted sum) — over the device time of ``paged_index_scores`` +
``paged_sparse_select`` (``latent_index_ms``).  The selection's own passes
over the scores are in the time and not in the work: they lower this share
and cannot raise it."""
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "latent_index_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    family = sl.family_of(ctx)
    means = sl.decode_means(ctx, "index_keys")
    if family is None or not means:
        return None
    cfg = ctx["config"]
    keys = means["index_keys"] * family.arch(cfg)["full_layers"]
    return sl.share(
        ctx, sl.per_run_s(ctx["trace"], sl.DECODE, sl.INDEX),
        keys * family.index_bytes_per_key(cfg),
        keys * family.index_flops_per_key(cfg))
