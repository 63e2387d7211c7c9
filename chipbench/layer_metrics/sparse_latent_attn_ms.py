"""Device time one decode step spends in the absorbed latent read UNDER THE
SELECTION (the kernel ``paged_sparse_latent_attn``: a full layer's chosen
keys, landed by blocks and attended under the mask the kernel rebuilds from
the scores and the threshold), per WHOLE execution of the decode program:
every full layer launches it once.  The up-projections in and out of latent
space and the scoring and selection before it (``latent_index_ms``) are not
counted.  ``None`` for a program without the kernel."""
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "sparse_latent_attn_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = sl.per_run_s(ctx["trace"], sl.DECODE, sl.SELECTED_READ)
    return None if t is None else t * 1e3
