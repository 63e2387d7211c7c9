"""Scoring + selection + the selected read of a verify window against the
chip's roofline: the LARGER of the time the chip needs to read, once and at
peak HBM bandwidth, what the window's positions NEED — every scored (position,
key) pair's index key and every CHOSEN key's latent (the means of the
``spec_round`` spans' ``index_keys`` and ``kv_selected``, one layer's worth,
x the layers that select: the trunk's and the module's; the family's
``window_read_needs``) — and the time their products take at the bf16 peak,
over ``sparse_latent_verify_ms``.  The kernels land every BLOCK that holds a
chosen key and multiply all of it, and a key both positions score is counted
once a position: either lowers this share, neither can raise it."""
from chipbench.layer_metrics import _spec_round as sr
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "sparse_latent_verify_roofline", "unit": "%",
          "better": "higher", "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    family, seen = sr.family_of(ctx), sr.rounds(ctx)
    if family is None or not seen \
            or not all("index_keys" in a and "kv_selected" in a
                       for a in seen):
        return None
    cfg = ctx["config"]
    a = family.arch(cfg)
    layers = a["layers"] + a["mtp_layers"]
    nbytes, flops = family.window_read_needs(
        cfg, layers * sum(x["index_keys"] for x in seen) / len(seen),
        layers * sum(x["kv_selected"] for x in seen) / len(seen))
    return sl.share(ctx, sr.window_kernel_s(ctx), nbytes, flops)
