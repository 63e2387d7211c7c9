"""The experts' grouped matmuls of a training step against the compute
roofline: 18 x ``expert_rows`` x ``d`` x ``ffn`` FLOPs a step (three
matmuls, each forward, ``d_lhs`` and ``d_rhs``, 2 FLOPs a multiply-add: the
family's ``expert_train_flops`` on the step's own ``expert_rows``) at the
peak bf16 rate, over ``expert_train_ms``.  Compute-bound: 768 rows an expert
a micro-batch is 768 FLOPs a weight byte against the chip's 240.  The
forwards recomputed under remat are in the time and not in the FLOPs."""
from chipbench import families
from chipbench.layer_metrics import expert_train_ms

SPECS = [{"name": "expert_train_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "train_tok_s"}]


def read(ctx):
    step_s = expert_train_ms.per_step_s(ctx["trace"])
    c = ctx["counters"]
    if not step_s or not ctx["peaks"] or "expert_rows" not in c:
        return None
    flops = families.load(ctx["config"]).expert_train_flops(
        ctx["config"], c["expert_rows"])
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / step_s
