"""Device time one prefill call of a model with power-retention layers
spends in the Mosaic kernel ``power_chunk_state`` (``ops/power_retention.py``:
a chunk's scores, decays and both sums AND the chunk-to-chunk carry of the
state and the normaliser, the whole chunked form), per WHOLE execution of
the prefill program (every rung of the prefill ladder is a ``jit_prefill``
program: the mean over the window's calls).  The projections, the q/k-norm,
the rotation and the gate's projection are XLA around it and are not
counted.  A program with no such kernel gives ``None``."""
import re

from chipbench.layer_metrics import kda_decode_ms

PROGRAM = r"^jit_prefill"
KERNELS = re.compile(r":mosaic:power_chunk_state$")

SPECS = [{"name": "power_chunk_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = kda_decode_ms.per_run_s(ctx["trace"], PROGRAM, KERNELS)
    return None if t is None else t * 1e3
