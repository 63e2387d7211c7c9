"""Device time of one execution of the engine's decode program (the XLA
module jitted from ``decode_step``), median over the traced window."""
from chipbench import trace_reduce

PROGRAM = r"^jit_decode"

SPECS = [{"name": "decode_step_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "itl_p95_ms"}]


def read(ctx):
    t = trace_reduce.program_median(ctx["trace"], PROGRAM)
    return None if t is None else t * 1e3
