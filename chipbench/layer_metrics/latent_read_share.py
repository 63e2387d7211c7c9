"""Share of the keys a decode step's indexer scored whose latent the selected
read LANDED: ``kv_read`` over ``index_keys``, both summed over the window's
``decode`` spans.  The read lands the blocks that hold a chosen key
(``ops/sparse_index_attention.py``): beside ``latent_selected_share`` it says
what block granularity costs — with seeded weights nearly every block of a
context holds one of its 2,048 chosen keys, a trained indexer's are more
local."""
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "latent_read_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def read(ctx):
    means = sl.decode_means(ctx, "index_keys", "kv_read")
    return None if not means else \
        100.0 * means["kv_read"] / means["index_keys"]
