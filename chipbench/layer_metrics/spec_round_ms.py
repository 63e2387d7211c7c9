"""Device time of one self-drafting round: the medians of its two programs'
executions over the traced window — ``jit_verify`` (the window's forward,
the verdict and its walk) and ``jit_draft`` (the module over what the walk
committed).  ``None`` for an engine without the round."""
from chipbench.layer_metrics import _spec_round as sr

SPECS = [{"name": "spec_round_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    return sr.round_ms(ctx)
