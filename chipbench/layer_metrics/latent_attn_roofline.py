"""The absorbed latent read of a decode step against the chip's roofline:
the LARGER of the time the chip needs to read, once and at peak HBM
bandwidth, what the step's attention NEEDS — the mean ``latent_bytes`` of
the window's ``decode`` spans: the rows' valid keys x layers x the 640 B of
a latent and its rope key, as the scheduler reckons them from its rows'
lengths — and the time its FLOPs take at the bf16 peak — the mean
``kv_pairs`` x the family's ``latent_flops_per_key`` (``2 x 32 x (320 +
256)`` a key a layer: every head's score and value over the one tile) —
over the device time of the ``paged_latent_*`` kernels (``latent_attn_ms``).
One query a row is memory-bound (57.6 FLOP/B against the chip's ridge of
240); a verify window of five queries is not, hence the larger of the two.
The pool holds a token in 384 lanes and the walk fetches whole blocks:
bytes it moves beyond the valid keys' 640 B lower this share, as time
spent on anything else does; they cannot raise it.  A family without
``latent_flops_per_key``, or a ring without the counters, gives ``None``."""
from chipbench import families
from chipbench.layer_metrics import latent_attn_ms

SPECS = [{"name": "latent_attn_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def share(ctx, program, span):
    """100 x max(bytes / bandwidth, FLOPs / peak) / the kernels' seconds a
    run of ``program``, from the window's ``span`` spans; or None."""
    step_s = latent_attn_ms.per_run_s(ctx["trace"], program)
    if not step_s or not ctx["peaks"] or "family" not in ctx["config"]:
        return None
    family = families.load(ctx["config"])
    means = latent_attn_ms.span_means(ctx, span, ("latent_bytes", "kv_pairs"))
    if not means or not hasattr(family, "latent_flops_per_key"):
        return None
    floor_s = max(
        means["latent_bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
        means["kv_pairs"] * family.latent_flops_per_key(ctx["config"])
        / ctx["peaks"]["bf16_flops"])
    return 100.0 * floor_s / step_s


def read(ctx):
    return share(ctx, latent_attn_ms.PROGRAM, "decode")
