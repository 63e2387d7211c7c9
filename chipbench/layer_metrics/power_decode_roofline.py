"""The ``power_step`` kernels of a decode step against the chip's HBM
roofline: the time the chip needs to move, once and at peak bandwidth, what
the step's retention NEEDS — the family's ``power_step_bytes`` for the mean
``state_rows`` of the window's ``decode`` spans (the live rows whose state
the step advances: each (row, KV head) state and normaliser in and out at
the 8,256 distinct monomials a head, all layers) — over ``power_decode_ms``.
The kernel runs every slot's lane, idle ones too (their state is read and
written back unchanged), and moves the stored layout's 8,320 rows a head:
bytes moved for idle rows or for the layout's duplicates lower this share,
as time spent on arithmetic does; they cannot raise it.  A family without
``power_step_bytes``, or a ring without the counter, gives ``None``."""
from chipbench import families
from chipbench.layer_metrics import kda_decode_ms, latent_attn_ms, \
    power_decode_ms

SPECS = [{"name": "power_decode_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    step_s = kda_decode_ms.per_run_s(ctx["trace"], power_decode_ms.PROGRAM,
                                     power_decode_ms.KERNELS)
    if not step_s or not ctx["peaks"] or "family" not in ctx["config"]:
        return None
    family = families.load(ctx["config"])
    means = latent_attn_ms.span_means(ctx, "decode", ("state_rows",))
    if not means or not hasattr(family, "power_step_bytes"):
        return None
    need = family.power_step_bytes(ctx["config"], means["state_rows"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / step_s
