"""The ``power_chunk_state`` kernels of a prefill call against the chip's
roofline: the LARGER of the time their FLOPs take at the bf16 peak and the
time their bytes take at peak HBM bandwidth (the family's
``power_chunk_cost``), both for the VALID tokens a call advances — the mean
``state_tokens`` of the window's ``prefill`` spans (its rows' real tokens:
the call less the pads) — over ``power_chunk_ms``.  The kernel makes a
float32 product of six bfloat16 passes, a sixth of the bf16 peak's rate,
builds the monomials on the VPU, multiplies the layout's 64 duplicate rows a head and
whole chunks under the pads: all lower the share; none can raise it.  A
family without the function gives ``None``."""
from chipbench import families
from chipbench.layer_metrics import kda_decode_ms, latent_attn_ms, \
    power_chunk_ms

SPECS = [{"name": "power_chunk_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    call_s = kda_decode_ms.per_run_s(ctx["trace"], power_chunk_ms.PROGRAM,
                                     power_chunk_ms.KERNELS)
    if not call_s or not ctx["peaks"] or "family" not in ctx["config"]:
        return None
    family = families.load(ctx["config"])
    means = latent_attn_ms.span_means(ctx, "prefill", ("state_tokens",))
    if not means or not hasattr(family, "power_chunk_cost"):
        return None
    flops, nbytes = family.power_chunk_cost(ctx["config"],
                                            means["state_tokens"])
    floor_s = max(flops / ctx["peaks"]["bf16_flops"],
                  nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_s / call_s
