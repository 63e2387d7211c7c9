"""Device time one prefill call of a model with latent attention spends in
the Mosaic kernel ``paged_latent_prefill`` (the SAME absorbed walk as a
decode step's, sixteen query positions x all heads a grid step: the
expanded path over ``paged_prefill_attn`` was measured and not kept,
PERF.md section 6, PR 39), per WHOLE execution of the prefill program.
Sums every ``paged_latent_*`` kernel of ``^jit_prefill``; a program with
none gives ``None``."""
from chipbench.layer_metrics import latent_attn_ms

PROGRAM = r"^jit_prefill"

SPECS = [{"name": "latent_prefill_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = latent_attn_ms.per_run_s(ctx["trace"], PROGRAM)
    return None if t is None else t * 1e3
