"""Device time one prefill call spends in learned sparse attention, per
WHOLE execution of the prefill program: the kernels ``sparse_attn_ms``
sums (scoring, selection and the read under the selection — a chunk's
queries choose apart, so its read takes every block some query chose),
there in the decode program, here in ``^jit_prefill``.  ``None`` for a
program without the kernels."""
from chipbench.layer_metrics import sparse_attn_ms

PROGRAM = r"^jit_prefill"

SPECS = [{"name": "prefill_select_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = sparse_attn_ms.per_run_s(ctx["trace"], PROGRAM)
    return None if t is None else t * 1e3
