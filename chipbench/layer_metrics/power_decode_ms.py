"""Device time one decode step of a model with power-retention layers spends
in the Mosaic kernel ``power_step`` (``ops/power_retention.py``: every (row,
KV head) state and normaliser read once, gated, fed, read out by the head's
group of queries and written once, in place; ``trace_reduce``'s
``custom_call_s`` key ``<module>:mosaic:power_step``), per WHOLE execution
of the decode program: every layer launches the one kernel.  The
projections, the q/k-norm, the rotation and the gate's projection are XLA
around it and are not counted.  A program with no such kernel (every other
family, and the parent of the PR that added it) gives ``None``."""
import re

from chipbench.layer_metrics import kda_decode_ms

PROGRAM = r"^jit_decode"
KERNELS = re.compile(r":mosaic:power_step")

SPECS = [{"name": "power_decode_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = kda_decode_ms.per_run_s(ctx["trace"], PROGRAM, KERNELS)
    return None if t is None else t * 1e3
