"""The flash kernels of a training step against the compute roofline: 14 x
``heads x head_dim`` x the VISIBLE (query, key) pairs of a step's rows,
summed over the layers (the forward's two matmuls = 4 FLOPs a pair and
width, the flash backward's five = 10: the kernel's own necessary work, its
score recomputation included — the family's ``flash_train_flops``) at the
peak bf16 rate, over ``window_flash_ms``.  A kernel that masks a sliding
layer's band instead of skipping the blocks outside it does the causal
triangle's work for the band's FLOPs and reads about a fifth lower; the
forward recomputed under remat is in the time and not in the FLOPs."""
from chipbench import families
from chipbench.layer_metrics import window_flash_ms

SPECS = [{"name": "window_flash_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "train_tok_s"}]


def read(ctx):
    step_s = window_flash_ms.per_step_s(ctx["trace"])
    c = ctx["counters"]
    family = families.load(ctx["config"]) if "family" in ctx["config"] \
        else None
    if not step_s or not ctx["peaks"] \
            or not hasattr(family, "flash_train_flops") \
            or "rows_per_step" not in c:
        return None
    flops = family.flash_train_flops(ctx["config"], c["seq_len"],
                                     c["rows_per_step"])
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / step_s
