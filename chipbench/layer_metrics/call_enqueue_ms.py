"""Host time of one decode dispatch: ``enqueue_s`` of the ``decode`` span
(the jitted call, entry to return; dispatch is asynchronous, so this is
host work), median over the window's decode calls."""
import statistics

from chipbench.layer_metrics import _host_segments as hs

SPECS = [{"name": "call_enqueue_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    calls = hs.decode_calls(ctx)
    if calls is None:
        return None
    return statistics.median(c["args"]["enqueue_s"] for c in calls) * 1e3
