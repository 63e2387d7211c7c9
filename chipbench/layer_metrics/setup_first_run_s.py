"""Seconds of a start inside the programs' ``build`` spans — the entry of
each registered program's first call to its results being ready — that no
``trace`` / ``lower`` / ``compile`` event covers: the executable's load
onto the chip and the first execution (``_setup_spans.py``)."""
from chipbench.layer_metrics import _setup_spans as ss

SPECS = [{"name": "setup_first_run_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "model step",
          "moves": "setup_s"}]


def read(ctx):
    return ss.row(ctx, "first_run_s")
