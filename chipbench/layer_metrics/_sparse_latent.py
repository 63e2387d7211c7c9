"""What the readers of a latent model under a learned selection and a window
share (``models/dots3.py``; the loader skips ``_*.py``).

The kernels, by the names the program launches them under (``trace_reduce``'s
``custom_call_s`` keys ``<module>:mosaic:<kernel>``):

``paged_index_scores``            scoring: every valid index key, in place
``paged_sparse_select``           selection: the exact top-k as a threshold
``paged_sparse_latent_attn``      the absorbed read under the selection
``paged_window_latent_attn``      a sliding layer's absorbed read of a decode
``paged_window_latent_prefill``   step / of a chunk, from the window's first
                                  key on, over ring blocks

and the counters of the window's ``decode`` spans: ``index_keys`` (keys the
indexer scored), ``kv_selected`` (keys chosen), ``kv_read`` (latent rows
landed) — ONE full layer's worth, counted on the device from the selection
(``ops/sparse_index_attention.COUNTS``) — and ``kv_window`` (keys the rows'
last queries keep under the window x the sliding layers: the scheduler's
arithmetic).  A program without the kernels or a ring without the counters
(every other model, and the parent of the PR that added them) gives
``None``."""
import re

from chipbench import families
from chipbench.layer_metrics import _program_spans as ps

DECODE, PREFILL = r"^jit_decode", r"^jit_prefill"
SELECTED_READ = re.compile(r":mosaic:paged_sparse_latent_attn$")
INDEX = re.compile(r":mosaic:paged_(index_scores|sparse_select)$")
WINDOW_READ = re.compile(r":mosaic:paged_window_latent_\w+$")


def per_run_s(trace, program, *kernels):
    """Seconds in the kernels matching any of ``kernels`` per whole
    execution of the programs matching ``program``, or None."""
    if not trace:
        return None
    rx = re.compile(program)
    runs = sum(len(v) for k, v in trace["programs"].items() if rx.search(k))
    kernel_s = sum(v for k, v in trace["custom_call_s"].items()
                   if rx.search(k) and any(r.search(k) for r in kernels))
    return kernel_s / runs if runs and kernel_s else None


def decode_means(ctx, *names):
    """Means of the counters ``names`` over the window's ``decode`` spans
    that carry them all and SELECTED (``index_keys`` > 0), or None."""
    events = ps.window_events(ctx)
    lo, hi = ctx["window"]
    seen = [e["args"] for e in events or ()
            if e["ph"] == "X" and e["name"] == "decode" and lo <= e["t0"] < hi
            and all(n in e.get("args", {}) for n in names)
            and e["args"].get("index_keys", 0) > 0]
    if not seen:
        return None
    return {n: sum(a[n] for a in seen) / len(seen) for n in names}


def family_of(ctx):
    """The family module if it has this model's byte and FLOP functions."""
    if "family" not in ctx["config"]:
        return None
    family = families.load(ctx["config"])
    return family if hasattr(family, "index_flops_per_key") else None


def share(ctx, seconds, nbytes, flops):
    """100 x max(bytes / bandwidth, FLOPs / peak) / ``seconds``."""
    if not seconds or not ctx["peaks"]:
        return None
    return 100.0 * max(nbytes / ctx["peaks"]["hbm_bytes_per_s"],
                       flops / ctx["peaks"]["bf16_flops"]) / seconds
