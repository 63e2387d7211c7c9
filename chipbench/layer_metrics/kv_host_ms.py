"""Host time the scheduler spends in the KV manager per step: the ``step``
span's ``kv_s`` (seconds inside the block allocator and the prefix trie:
prefix match at admission, block allocation with its evictions, chain
registration, a finished slot's release), mean over the window's steps."""
import statistics

from chipbench.layer_metrics import _program_spans as ps

SPECS = [{"name": "kv_host_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def read(ctx):
    steps, _ = ps.steps_in_window(ctx)
    kv = [s["args"]["kv_s"] for s, _ in steps or ()
          if "kv_s" in s.get("args", {})]
    return statistics.fmean(kv) * 1e3 if kv else None
