"""Device time one prefill call of a model with gated delta-rule layers
spends in the Mosaic kernel ``kda_chunk_state`` (``ops/delta_rule.py``: the
chunk-to-chunk HALF of the chunked delta rule — four matmuls against the
state a chunk of 64 tokens a head), per WHOLE execution of the prefill
program.  NOT the chunked rule's whole cost: the other half — a chunk's
decays, scores and unit-lower-triangular solve — is unnamed float32 XLA
fusions under the scope ``kda_chunk_intra``, which ``trace_reduce`` (it
groups device operations by their base name) cannot tell from the
program's other fusions; a kernel for that half brings a metric of its own.
A program with no such kernel gives ``None``."""
import re

from chipbench.layer_metrics import kda_decode_ms

PROGRAM = r"^jit_prefill"
KERNELS = re.compile(r":mosaic:kda_chunk_state$")

SPECS = [{"name": "kda_chunk_state_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = kda_decode_ms.per_run_s(ctx["trace"], PROGRAM, KERNELS)
    return None if t is None else t * 1e3
