"""Device time one prefill call spends in learned sparse attention over the
latent pool, per WHOLE execution of a prefill program: scoring, selection and
the read under the selection (``paged_index_scores`` +
``paged_sparse_select`` + ``paged_sparse_latent_attn`` in ``^jit_prefill``) —
the part of a chunk that grows with the row's context: a chunk's 512 queries
choose apart, so its read lands every block some query of a grid step chose
and multiplies all of it.  ``None`` for a program without the kernels."""
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "sparse_latent_prefill_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = sl.per_run_s(ctx["trace"], sl.PREFILL, sl.INDEX, sl.SELECTED_READ)
    return None if t is None else t * 1e3
