"""Routed rows one held expert multiplies in one micro-batch: the step's
``expert_rows`` (the program's own count, ``metrics["model"]`` of
``train_batch``, summed over layers and micro-batches) over ``layers x held
experts x micro-batches`` — 768 under even routing at 8,192 tokens, top-6 of
64, the load the cell's ``why`` cites.  ``expert_rows_max`` (the largest
group, a layer's and a micro-batch's mean) is beside it in the run's
``detail`` line."""
from chipbench import costs

SPECS = [{"name": "expert_rows_per_expert", "unit": "rows",
          "better": "higher", "source": "program_counter",
          "layer": "model step", "moves": "train_tok_s"}]


def read(ctx):
    c = ctx["counters"]
    if "expert_rows" not in c:
        return None
    a = costs.arch(ctx["config"])
    return c["expert_rows"] / (a["layers"] * a["experts"] * c["gas"])
