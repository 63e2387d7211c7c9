"""Share of the keys a decode step's indexer scored that the step attended:
``kv_selected`` over ``index_keys``, both summed over the window's ``decode``
spans.  The program counts them on the DEVICE, from the selection it made
(``ops/sparse_index_attention.COUNTS``): ~``index_topk / ctx`` when the
selection bites (a tenth at ~21 k keys).  ``latent_read_share`` has what was
LANDED, which is whole blocks."""
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "latent_selected_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def read(ctx):
    means = sl.decode_means(ctx, "index_keys", "kv_selected")
    return None if not means else \
        100.0 * means["kv_selected"] / means["index_keys"]
