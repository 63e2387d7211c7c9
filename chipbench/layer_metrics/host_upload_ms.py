"""Host time a scheduler step spends putting its calls' operands on the
device: ``upload_s`` of its ``step.prefill`` + ``step.decode`` spans (every
``jnp.asarray`` of tokens, lengths, tables and sampling knobs), mean over
the window's steps."""
from chipbench.layer_metrics import _host_segments as hs

SPECS = [{"name": "host_upload_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    return hs.segment_ms(ctx, "upload_s")
