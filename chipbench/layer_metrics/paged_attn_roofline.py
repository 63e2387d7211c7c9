"""The paged decode-attention kernel against the memory roofline: the time
the chip needs to read the valid KV of a decode step once at peak HBM
bandwidth, over the device time the decode program's Mosaic custom calls
took per step.  Memory-bound: a [1, head_dim] query against the cache does
2 FLOPs per byte read."""
import re

from chipbench import costs

PROGRAM = r"^jit_decode"

SPECS = [{"name": "paged_attn_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["peaks"]:
        return None
    rx = re.compile(PROGRAM)
    runs = sum(len(v) for k, v in t["programs"].items() if rx.search(k))
    kernel_s = sum(v for k, v in t["custom_call_s"].items() if rx.search(k))
    if not runs or not kernel_s:
        return None
    kv_bytes = costs.kv_bytes_per_token(ctx["config"]) \
        * ctx["counters"]["mean_valid_kv_tokens"]
    floor_s = kv_bytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_s / runs)
