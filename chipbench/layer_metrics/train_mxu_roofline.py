"""The training step against the compute roofline: model FLOPs of one
optimizer step (``costs.train_flops_per_token``: 6N + 12LdS, recomputed
operations not counted) at the chips' peak bf16 rate, over the device time
of one step.  Compute-bound: ~6 FLOPs per parameter per token against 16
bytes of state per parameter per step."""
from chipbench import trace_reduce

PROGRAM = r"^jit_train_step"

SPECS = [{"name": "train_mxu_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "train_tok_s"}]


def read(ctx):
    step_s = trace_reduce.program_median(ctx["trace"], PROGRAM)
    if not step_s or not ctx["peaks"]:
        return None
    c = ctx["counters"]
    floor_s = c["flops_per_step"] / (c["chips"] * ctx["peaks"]["bf16_flops"])
    return 100.0 * floor_s / step_s
