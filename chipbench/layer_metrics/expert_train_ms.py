"""Device time one optimizer step spends in the experts' grouped matmuls:
the Pallas kernels ``moe_gmm`` (forward), ``moe_gmm_dlhs`` and
``moe_gmm_drhs`` (its two transposes) of the ``train_step`` program —
``trace_reduce``'s ``custom_call_s`` keys ``jit_train_step:mosaic:moe_gmm*``
— forwards recomputed under remat included as time, per whole execution of
the program.  A program without those kernels gives ``None``."""
import re

PROGRAM = r"^jit_train_step"
KERNELS = re.compile(r":mosaic:moe_gmm")

SPECS = [{"name": "expert_train_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "train_tok_s"}]


def per_step_s(trace, kernels=KERNELS):
    """Seconds in the kernels that match per whole ``train_step``
    execution, or None."""
    if not trace:
        return None
    rx = re.compile(PROGRAM)
    runs = sum(len(v) for k, v in trace["programs"].items() if rx.search(k))
    kernel_s = sum(v for k, v in trace["custom_call_s"].items()
                   if rx.search(k) and kernels.search(k))
    return kernel_s / runs if runs and kernel_s else None


def read(ctx):
    t = per_step_s(ctx["trace"])
    return None if t is None else t * 1e3
