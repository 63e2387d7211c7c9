"""Device time one decode step of a model with gated delta-rule layers
spends in the Mosaic kernel ``kda_step`` (``ops/delta_rule.py``: every (row,
head) state matrix read once, decayed, corrected, read out and written once,
in place; ``trace_reduce``'s ``custom_call_s`` key
``<module>:mosaic:kda_step``), per WHOLE execution of the decode program:
every KDA layer launches the one kernel.  The projections, the short
convolutions, the normalisations and the output gate are XLA around it and
are not counted.  A program with no such kernel (every other family, and
the parent of the PR that added it) gives ``None``."""
import re

PROGRAM = r"^jit_decode"
KERNELS = re.compile(r":mosaic:kda_step")

SPECS = [{"name": "kda_decode_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def per_run_s(trace, program=PROGRAM, kernels=KERNELS):
    """Seconds in ``kernels`` per whole execution of ``program``, or None."""
    if not trace:
        return None
    rx = re.compile(program)
    runs = sum(len(v) for k, v in trace["programs"].items() if rx.search(k))
    kernel_s = sum(v for k, v in trace["custom_call_s"].items()
                   if rx.search(k) and kernels.search(k))
    return kernel_s / runs if runs and kernel_s else None


def read(ctx):
    t = per_run_s(ctx["trace"])
    return None if t is None else t * 1e3
