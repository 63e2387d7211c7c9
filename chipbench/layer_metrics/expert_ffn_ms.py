"""Device time one decode step spends in the experts' grouped matmuls
(three a layer: gate, up and down projections over the rows sorted by
expert), per WHOLE execution of the decode program.

The program calls ``jax.lax.ragged_dot``; XLA:TPU lowers each call to its
own Mosaic grouped-matmul kernel, which the trace names
``ragged-dot-none`` (``trace_reduce``'s ``custom_call_s`` key
``<module>:mosaic:ragged-dot-none``; its small scalar set-up kernel
``ragged-dot-metadata`` is counted with it: both exist only for the
experts).  A program that brings its own Pallas kernel names it
``moe_gmm``, and that key is read the same way.  A program with neither
(every dense family, and the parent of the PR that added this) gives
``None``."""
import re

PROGRAM = r"^jit_decode"
KERNELS = re.compile(r":mosaic:(moe_gmm|ragged-dot)")

SPECS = [{"name": "expert_ffn_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def per_step_s(trace):
    """Seconds in the grouped matmuls per whole decode execution, or
    None."""
    if not trace:
        return None
    rx = re.compile(PROGRAM)
    runs = sum(len(v) for k, v in trace["programs"].items() if rx.search(k))
    kernel_s = sum(v for k, v in trace["custom_call_s"].items()
                   if rx.search(k) and KERNELS.search(k))
    return kernel_s / runs if runs and kernel_s else None


def read(ctx):
    t = per_step_s(ctx["trace"])
    return None if t is None else t * 1e3
