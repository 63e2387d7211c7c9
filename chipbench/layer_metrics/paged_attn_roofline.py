"""The paged decode-attention kernel against the memory roofline: the time
the chip needs to read the valid KV of a decode step once at peak HBM
bandwidth, over the device time that kernel took per step.  Memory-bound: a
[1, head_dim] query against the cache does 2 FLOPs per byte read.

The kernel is selected by the name the program gives it
(``pl.pallas_call(name="paged_decode_attn")``, pinned by
``tests/unit/test_program_spans.py``) in the decode program's entries of the
trace's ``custom_call_s``: another Mosaic kernel in the same program (an
expert matmul) is not attention's time."""
import re

from chipbench import costs

PROGRAM = r"^jit_decode"
KERNEL = ":mosaic:paged_decode_attn"

SPECS = [{"name": "paged_attn_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["peaks"]:
        return None
    rx = re.compile(PROGRAM)
    runs = sum(len(v) for k, v in t["programs"].items() if rx.search(k))
    kernel_s = sum(v for k, v in t["custom_call_s"].items()
                   if rx.search(k) and k.endswith(KERNEL))
    if not runs or not kernel_s:
        return None
    kv_bytes = costs.kv_bytes_per_token(ctx["config"]) \
        * ctx["counters"]["mean_valid_kv_tokens"]
    floor_s = kv_bytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (kernel_s / runs)
