"""Share of the drafts of the model's own multi-token-prediction module that
the engine's rejection sampler ACCEPTED: ``accepted`` over ``drafted``, summed
over the window's ``spec_round`` spans (the device's own walk of the
verdicts; a row's eos or budget may cut what it commits).  With SEEDED
weights the module's guess and the trunk's distribution are unrelated and
this reads ~0: the floor of what a deployment sees (a trained one-deep module
is reported at 85-90 %).  ``None`` without such spans."""
from chipbench.layer_metrics import _spec_round as sr

SPECS = [{"name": "mtp_accept_rate", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    share = sr.ratio(ctx, "accepted", "drafted")
    return None if share is None else 100.0 * share
