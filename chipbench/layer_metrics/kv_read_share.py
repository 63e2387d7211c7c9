"""Share of a row's valid keys whose K and V a decode step FETCHED:
``kv_read`` (rows of the blocks the read copied: those that hold a chosen
key) over ``kv_valid``, summed over the window's ``decode`` spans like
``kv_selected_share``.  The distance between the two is what block
granularity costs: with seeded weights a row's ~30 % chosen keys lie in
nearly every block of 32, so this reads near 100 %; a trained indexer's
choices are more local.  ``None`` without the counter."""
from chipbench.layer_metrics import kv_selected_share

SPECS = [{"name": "kv_read_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def read(ctx):
    return kv_selected_share.share(ctx, "kv_read")
