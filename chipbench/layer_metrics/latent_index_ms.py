"""Device time one decode step spends choosing its keys, per WHOLE execution
of the decode program: the kernels ``paged_index_scores`` (every valid index
key of a row against its query's 64 index heads) and ``paged_sparse_select``
(the exact top-2,048 of those scores as a threshold), once a full layer.
Between them XLA moves the scores into token order (inside the program's
``fusion`` time, not counted here).  ``None`` for a program without the
kernels."""
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "latent_index_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = sl.per_run_s(ctx["trace"], sl.DECODE, sl.INDEX)
    return None if t is None else t * 1e3
