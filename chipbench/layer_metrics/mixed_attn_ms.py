"""Device time one decode step of a model with layers of two kinds
(sliding-window and full attention) spends in the paged walk kernel, per
WHOLE execution of the decode program: every layer of both kinds launches
the one kernel ``paged_decode_attn`` (``trace_reduce``'s ``custom_call_s``
key ``<module>:mosaic:paged_decode_attn``), a sliding layer with its window
bound and its kind's ring table, a full layer without.  Read only where the
program's ring says the model has two kinds (``kv_visible`` on its
``decode`` spans): a model of one kind reports the same kernel through
``paged_attn_roofline``, and the parent of the PR that added the counters
gives ``None``."""
import re

from chipbench.layer_metrics import kv_visible_share

PROGRAM = r"^jit_decode"
KERNEL = re.compile(r":mosaic:paged_decode_attn$")

SPECS = [{"name": "mixed_attn_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def per_run_s(ctx):
    """Seconds in the walk kernel per whole decode execution, or None."""
    trace = ctx["trace"]
    if not trace or kv_visible_share.decode_counts(ctx) is None:
        return None
    rx = re.compile(PROGRAM)
    mine = [v for k, v in trace["custom_call_s"].items()
            if rx.search(k) and KERNEL.search(k)]
    runs = sum(len(v) for k, v in trace["programs"].items() if rx.search(k))
    if not runs or not mine:
        return None
    return sum(mine) / runs


def read(ctx):
    t = per_run_s(ctx)
    return None if t is None else t * 1e3
