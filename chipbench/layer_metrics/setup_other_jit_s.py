"""Seconds of a start JAX spent building functions that are NOT a
registered program, on the program's account: eager operations,
initialisers and casts compiled one by one inside ``init_serving`` /
``initialize`` and the warm-in calls (``trace`` + ``lower`` + ``compile``
events without a ``program``).  What the benchmark's own ``cb.setup.*``
spans built for themselves — the weights' initialiser, the comparison with
the reference — is not counted here; ``setup_engine_s`` prints it
(``_setup_spans.py``)."""
from chipbench.layer_metrics import _setup_spans as ss

SPECS = [{"name": "setup_other_jit_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "engine",
          "moves": "setup_s"}]


def read(ctx):
    return ss.row(ctx, "other_jit_s")
