"""Share of a row's valid keys that a decode step attended:
``kv_selected`` over ``kv_valid``, both summed over the ``decode`` spans of
the program's ring that start inside the window.  The program counts them
on the DEVICE, from the selection it made (the sum of the mask the read
ran under; ``ops/sparse_index_attention.COUNTS``), and brings them back
behind the step's tokens: ~``topk / ctx`` when the selection bites, 100 %
when the program attended every valid key.  ``kv_read_share`` has what was
FETCHED, which is whole blocks.  A program whose ring carries no such
counters (any model without an indexer, or the parent of the PR that added
them) gives ``None``."""
from chipbench.layer_metrics import sparse_attn_roofline

SPECS = [{"name": "kv_selected_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def share(ctx, counter):
    """``counter`` over ``kv_valid``, in %, over the window's ``decode``
    spans that carry both; else None."""
    spans = [a for a in sparse_attn_roofline.decode_counts(ctx) or ()
             if counter in a]
    valid = sum(a["kv_valid"] for a in spans)
    if not valid:
        return None
    return 100.0 * sum(a[counter] for a in spans) / valid


def read(ctx):
    return share(ctx, "kv_selected")
