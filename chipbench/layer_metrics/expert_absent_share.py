"""Share of a decode step's routed (token, expert) pairs whose expert this
chip does not hold: ``expert_rows_absent`` over ``expert_rows +
expert_rows_absent``, summed over the ``decode`` spans of the program's ring
that start inside the window.  An expert layer that holds a share of its
experts (``moe/routed.py``, ``held=``) routes over ALL of them and computes
the held experts' partial sum; the pairs of the absent ones are sorted
behind the held groups and multiplied with nothing.  16 held of 128 under a
router that spreads its choices evenly read 87.5 %; 0 % says the layer
holds every expert.  A program whose ring carries no such counter (a model
that holds all its experts, or the parent of the PR that added it) gives
``None``."""
from chipbench.layer_metrics import kv_visible_share

SPECS = [{"name": "expert_absent_share", "unit": "%", "better": "lower",
          "source": "program_span", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    spans = kv_visible_share.decode_counts(ctx, "expert_rows_absent")
    pairs = sum(a["expert_rows"] + a["expert_rows_absent"]
                for a in spans or ())
    if not pairs:
        return None
    return 100.0 * sum(a["expert_rows_absent"] for a in spans) / pairs
