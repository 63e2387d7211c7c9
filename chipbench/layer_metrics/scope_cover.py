"""Of the bytes a call of the engine's programs moves through HBM, the share
under an entry of the program's scope vocabulary (not ``unscoped``; a
``mixed`` fusion counts under the scope that holds most of it, and its own
share is printed in the note): how much of ``breakdown`` the program can put
a layer's name to.  ``.serve``: the decode-side programs (``decode``, or a
speculative round's ``verify`` + ``draft``); ``.train``: the training step.
Counters of the compiled program (``_scope_tables.py``): no profiler."""
from chipbench.layer_metrics import _scope_tables as st

SPECS = [{"name": "scope_cover.serve", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "device",
          "moves": "serve_tok_s"},
         {"name": "scope_cover.train", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "device",
          "moves": "train_tok_s"}]


def read(ctx):
    serving = "slots" in ctx.get("counters", {})
    return st.cover(ctx, st.DECODE_SIDE if serving else st.TRAIN_SIDE,
                    "decode" if serving else "training")
