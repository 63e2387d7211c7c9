"""Device time one optimizer step spends in the flash attention kernels
(``trace_reduce``'s ``custom_call_s`` keys ``jit_train_step:mosaic:flash_*``:
forward, ``dq`` and ``dkv`` of full and sliding layers alike, forwards
recomputed under remat included as time), per whole execution of the
``train_step`` program."""
import re

from chipbench.layer_metrics import expert_train_ms

KERNELS = re.compile(r":mosaic:flash_")

SPECS = [{"name": "window_flash_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "train_tok_s"}]


def per_step_s(trace):
    return expert_train_ms.per_step_s(trace, KERNELS)


def read(ctx):
    t = per_step_s(ctx["trace"])
    return None if t is None else t * 1e3
