"""The whole decode step against the memory roofline: (weight bytes +
valid KV bytes) at peak HBM bandwidth over the device time of one decode
program execution.  Memory-bound at these batch sizes: 32 rows x 2 FLOPs
per weight byte is far under the chip's 240 FLOPs per byte."""
from chipbench import costs, trace_reduce

PROGRAM = r"^jit_decode"

SPECS = [{"name": "decode_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    step_s = trace_reduce.program_median(ctx["trace"], PROGRAM)
    if not step_s or not ctx["peaks"]:
        return None
    floor_s = costs.decode_bytes_per_step(
        ctx["config"], ctx["counters"]["mean_valid_kv_tokens"],
        ctx["counters"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / step_s
