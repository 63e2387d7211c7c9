"""The routed training step against the chip's peak: the FLOPs one
optimizer step NEEDS (the family's ``train_flops_per_token``: 6 x the
parameters a token multiplies with here — attention, router, the head's
slice, and its pairs on HELD experts from the step's own ``expert_rows`` —
plus 12 x ``heads x head_dim`` x the keys a query can see in each layer,
band and triangle, exact sums; recomputation not counted) at the peak bf16
rate, over the median device time of one ``train_step`` execution.  The
driver (``drivers/train_routed.py``) puts the step's FLOPs into
``counters["flops_per_step"]``; a run of another driver has no
``expert_rows`` beside it and nothing is read."""
from chipbench import trace_reduce

PROGRAM = r"^jit_train_step"

SPECS = [{"name": "routed_train_mfu", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "train_tok_s"}]


def read(ctx):
    step_s = trace_reduce.program_median(ctx["trace"], PROGRAM)
    c = ctx["counters"]
    if not step_s or not ctx["peaks"] or "expert_rows" not in c:
        return None
    floor_s = c["flops_per_step"] / (c["chips"] * ctx["peaks"]["bf16_flops"])
    return 100.0 * floor_s / step_s
