"""Host time a scheduler step spends committing what its calls returned:
``commit_s`` of its ``step.prefill`` + ``step.decode`` spans (the per-slot
loop after the harvest: lengths, token emission, first-token stamps,
finished slots, trie registration), mean over the window's steps."""
from chipbench.layer_metrics import _host_segments as hs

SPECS = [{"name": "host_commit_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    return hs.segment_ms(ctx, "commit_s")
