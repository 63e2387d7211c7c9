"""Device time one decode step of a model with latent attention spends in
the Mosaic kernels named ``paged_latent_*`` (the absorbed walk over the
latent pool: ``paged_latent_attn`` at one query a row, ``paged_latent_verify``
under a verify window; ``trace_reduce``'s ``custom_call_s`` keys
``<module>:mosaic:paged_latent_*``), per WHOLE execution of the decode
program: every layer launches the one kernel.  The up-projections in and
out of latent space are XLA matmuls around it and are not counted.  A
program with no such kernel (every other family, and the parent of the PR
that added it) gives ``None``."""
import re

PROGRAM = r"^jit_decode"
KERNELS = re.compile(r":mosaic:paged_latent_")

SPECS = [{"name": "latent_attn_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def per_run_s(trace, program=PROGRAM):
    """Seconds in the latent kernels per whole execution of ``program``,
    or None."""
    if not trace:
        return None
    rx = re.compile(program)
    runs = sum(len(v) for k, v in trace["programs"].items() if rx.search(k))
    kernel_s = sum(v for k, v in trace["custom_call_s"].items()
                   if rx.search(k) and KERNELS.search(k))
    return kernel_s / runs if runs and kernel_s else None


def span_means(ctx, span, names):
    """Means of the counters ``names`` over the window's ``span`` spans of
    the program's ring that carry them all, or None."""
    from chipbench.layer_metrics import _program_spans as ps

    events = ps.window_events(ctx)
    lo, hi = ctx["window"]
    seen = [e["args"] for e in events or ()
            if e["ph"] == "X" and e["name"] == span and lo <= e["t0"] < hi
            and all(n in e.get("args", {}) for n in names)]
    if not seen:
        return None
    return {n: sum(a[n] for a in seen) / len(seen) for n in names}


def read(ctx):
    t = per_run_s(ctx["trace"])
    return None if t is None else t * 1e3
