"""Device time one decode step of a model with state-space layers spends in
the Mosaic kernel ``ssd_step`` (``ops/ssd.py``: every (row, head) state
matrix read once, decayed, fed, read out and written once, in place;
``trace_reduce``'s ``custom_call_s`` key ``<module>:mosaic:ssd_step``), per
WHOLE execution of the decode program: every state-space layer launches the
one kernel.  The projections, the short convolution, the skip, the gate and
its norm are XLA around it and are not counted.  A program with no such
kernel (every other family, and the parent of the PR that added it) gives
``None``."""
import re

from chipbench.layer_metrics import kda_decode_ms

PROGRAM = r"^jit_decode"
KERNELS = re.compile(r":mosaic:ssd_step")

SPECS = [{"name": "ssd_decode_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = kda_decode_ms.per_run_s(ctx["trace"], PROGRAM, KERNELS)
    return None if t is None else t * 1e3
