"""Device time of the model's own multi-token-prediction module in a round:
the median execution of ``jit_draft`` (one routed block at the window's
positions, its latent and index-key writes, its selection and read, its 16
held experts, the head) over the traced window.  ``None`` without the
program."""
from chipbench.layer_metrics import _spec_round as sr

SPECS = [{"name": "mtp_draft_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    return sr.program_ms(ctx, sr.DRAFT) if sr.rounds(ctx) else None
