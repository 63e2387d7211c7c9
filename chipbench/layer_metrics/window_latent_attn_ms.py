"""Device time one decode step spends in the sliding layers' absorbed latent
read (the kernel ``paged_window_latent_attn``: from the window's first key
on, over the ring's blocks), per WHOLE execution of the decode program: every
sliding layer launches it once.  ``None`` for a program without the
kernel."""
from chipbench.layer_metrics import _sparse_latent as sl

SPECS = [{"name": "window_latent_attn_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = sl.per_run_s(ctx["trace"], sl.DECODE, sl.WINDOW_READ)
    return None if t is None else t * 1e3
