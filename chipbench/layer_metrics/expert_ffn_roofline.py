"""The experts' grouped matmuls against the memory roofline: the time the
chip needs to read the touched experts' weights once at peak HBM bandwidth,
over the device time the grouped matmuls took per decode step
(``expert_ffn_ms``).  Memory-bound: a step's rows spread over the experts
give each one a handful (64 rows x top-8 of 64: 8 rows an expert), 8 FLOPs
a weight byte against the chip's 240.  The bytes are the family's
(``expert_bytes_touched(config, counters)``: each touched (layer, expert)
weight set once, from what the program's ring recorded); a family without
experts has no such function and the metric is left out."""
from chipbench import families
from chipbench.layer_metrics import expert_ffn_ms

SPECS = [{"name": "expert_ffn_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    step_s = expert_ffn_ms.per_step_s(ctx["trace"])
    if not step_s or not ctx["peaks"] or "family" not in ctx["config"]:
        return None
    touched = getattr(families.load(ctx["config"]), "expert_bytes_touched",
                      None)
    if touched is None:
        return None
    floor_s = touched(ctx["config"], ctx["counters"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / step_s
