"""The latent kernel of a prefill call against the chip's roofline, as
``latent_attn_roofline`` reckons a decode step's: the larger of the needed
bytes at peak bandwidth (the mean ``latent_bytes`` of the window's
``prefill`` spans: each row's valid prefix once) and the chunk's score and
value FLOPs at the bf16 peak (the mean ``kv_pairs`` — every real query of
the chunk against the keys it sees, x layers — x the family's
``latent_flops_per_key``), over ``latent_prefill_ms``.  Compute-bound: 128
queries x 32 heads share each tile.  The kernel reads a row's prefix once
per tile of sixteen query positions and computes whole tiles under the
causal mask; both lower the share."""
from chipbench.layer_metrics import latent_attn_roofline, latent_prefill_ms

SPECS = [{"name": "latent_prefill_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "serve_tok_s"}]


def read(ctx):
    return latent_attn_roofline.share(ctx, latent_prefill_ms.PROGRAM,
                                      "prefill")
