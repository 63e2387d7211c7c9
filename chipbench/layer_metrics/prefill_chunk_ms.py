"""Device time of one execution of the engine's chunked-prefill program,
median over the traced window.  Where TTFT is judged it should move TTFT;
in the long-prompt cell, whose tails are not judged (PERF.md), prefill is
most of a request and it should move the tokens per second."""
from chipbench import trace_reduce

PROGRAM = r"^jit_prefill"

SPECS = [{"name": "prefill_chunk_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "ttft_p95_ms"},
         {"name": "prefill_chunk_ms.longprompt", "unit": "ms",
          "better": "lower", "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    t = trace_reduce.program_median(ctx["trace"], PROGRAM)
    return None if t is None else t * 1e3
