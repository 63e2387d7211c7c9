"""Device time one prefill call of a model with state-space layers spends in
the Mosaic kernel ``ssd_chunk_state`` (``ops/ssd.py``: a chunk's scores,
decays and outputs AND the chunk-to-chunk carry of the state, the whole
chunked scan), per WHOLE execution of the prefill program (every rung of the
prefill ladder is a ``jit_prefill`` program: the mean over the window's
calls).  The projections, the short convolution, the skip, the gate and its
norm are XLA around it and are not counted.  A program with no such kernel
gives ``None``."""
import re

from chipbench.layer_metrics import kda_decode_ms

PROGRAM = r"^jit_prefill"
KERNELS = re.compile(r":mosaic:ssd_chunk_state$")

SPECS = [{"name": "ssd_chunk_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = kda_decode_ms.per_run_s(ctx["trace"], PROGRAM, KERNELS)
    return None if t is None else t * 1e3
