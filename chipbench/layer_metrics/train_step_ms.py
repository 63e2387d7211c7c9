"""Device time of one optimizer step (one execution of the engine's
``train_step`` program, all its micro-batches and the update), median over
the traced window."""
from chipbench import trace_reduce

PROGRAM = r"^jit_train_step"

SPECS = [{"name": "train_step_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "train_tok_s"}]


def read(ctx):
    t = trace_reduce.program_median(ctx["trace"], PROGRAM)
    return None if t is None else t * 1e3
