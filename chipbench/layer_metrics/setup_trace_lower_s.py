"""Seconds of a start that went into TRACING and LOWERING the engine's
registered programs (each prefill rung, ``decode``, ``verify`` / ``draft``
/ the swap pair where configured, ``train_step``): the ``trace`` and
``lower`` events the start-up ring holds under a ``build`` span's
``program`` — the Python that runs at every start, which the persistent
compile cache does not touch (``_setup_spans.py``)."""
from chipbench.layer_metrics import _setup_spans as ss

SPECS = [{"name": "setup_trace_lower_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "model step",
          "moves": "setup_s"}]


def read(ctx):
    return ss.row(ctx, "trace_lower_s")
