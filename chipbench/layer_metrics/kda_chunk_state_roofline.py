"""The ``kda_chunk_state`` kernels of a prefill call against the chip's
roofline: the LARGER of the time their FLOPs take at the bf16 peak (the
family's ``kda_chunk_flops``) and the time their bytes take at peak HBM
bandwidth (``kda_chunk_bytes``), both for the VALID tokens a call advances —
the mean ``state_tokens`` of the window's ``prefill`` spans (its rows' real
tokens: the chunk less the pads) — over ``kda_chunk_state_ms``.  The
kernel multiplies in float32 at full precision, a sixth of the bf16 peak's
rate, and computes whole chunks under the pads: both lower the share; they
cannot raise it.  A family without the two functions gives ``None``."""
from chipbench import families
from chipbench.layer_metrics import kda_decode_ms, kda_chunk_state_ms, \
    latent_attn_ms

SPECS = [{"name": "kda_chunk_state_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    call_s = kda_decode_ms.per_run_s(ctx["trace"], kda_chunk_state_ms.PROGRAM,
                                     kda_chunk_state_ms.KERNELS)
    if not call_s or not ctx["peaks"] or "family" not in ctx["config"]:
        return None
    family = families.load(ctx["config"])
    means = latent_attn_ms.span_means(ctx, "prefill", ("state_tokens",))
    if not means or not hasattr(family, "kda_chunk_flops"):
        return None
    tokens = means["state_tokens"]
    floor_s = max(
        family.kda_chunk_flops(ctx["config"], tokens)
        / ctx["peaks"]["bf16_flops"],
        family.kda_chunk_bytes(ctx["config"], tokens)
        / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_s / call_s
