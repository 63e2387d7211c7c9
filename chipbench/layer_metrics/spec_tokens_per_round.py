"""Tokens a decoding row commits a round: ``emitted`` over ``slots`` summed
over the window's ``spec_round`` spans — 1.0 (every draft rejected) to
``spec_tokens + 1`` (every draft accepted).  What ``decode_occupancy`` would
say in tokens, without its bound of 100 %.  ``None`` without such spans."""
from chipbench.layer_metrics import _spec_round as sr

SPECS = [{"name": "spec_tokens_per_round", "unit": "tokens", "better": "higher",
          "source": "program_counter", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    return sr.ratio(ctx, "emitted", "slots")
