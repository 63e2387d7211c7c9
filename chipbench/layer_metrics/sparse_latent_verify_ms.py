"""Device time a ROUND spends choosing and reading its keys at the verify
window: the kernels ``paged_index_scores``, ``paged_sparse_select`` and
``paged_sparse_latent_attn`` in both of the round's programs, per round —
every selecting layer of the trunk and the module's launches each once.
``None`` for a program without them."""
from chipbench.layer_metrics import _spec_round as sr

SPECS = [{"name": "sparse_latent_verify_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = sr.window_kernel_s(ctx)
    return None if t is None else t * 1e3
