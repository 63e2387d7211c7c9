"""Idle time INSIDE a decode call: the ``decode`` span's duration on the
host (the jitted call to the tokens on the host, from the ring; median over
the window's calls) minus the device time of one execution of the decode
program (``trace_reduce.program_median``, from the trace): dispatch latency
before the program's first operation plus copy-back and wake-up after its
last.  Read only from a program whose decode spans carry ``enqueue_s``."""
import statistics

from chipbench import trace_reduce
from chipbench.layer_metrics import _host_segments as hs

PROGRAM = r"^jit_decode"

SPECS = [{"name": "call_overhead_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    device_s = trace_reduce.program_median(ctx["trace"], PROGRAM)
    calls = hs.decode_calls(ctx) if device_s is not None else None
    if calls is None:
        return None
    return (statistics.median(c["t1"] - c["t0"] for c in calls)
            - device_s) * 1e3
