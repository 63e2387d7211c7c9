"""What the readers of a self-drafting engine's round share (the loader
skips ``_*.py``).

A round (``ServingEngine._run_self_round``) is ONE ``spec_round`` span of the
program's ring carrying ``slots`` (decoding rows), ``window`` (K + 1),
``drafted``, ``accepted`` and ``emitted`` (as the device walked the verdicts)
and the selection's counters ``index_keys`` / ``kv_selected`` / ``kv_read`` —
ONE layer's worth, counted on the device over both window positions — and
two device programs, ``jit_verify`` (the window's forward, the verdict and
its walk) and ``jit_draft`` (the model's own module over what the walk
committed), whose Pallas kernels keep the names every program launches them
under (``trace_reduce``'s ``custom_call_s`` keys ``<module>:mosaic:<kernel>``).
A ring without such spans or a trace without the programs (every other
engine, and the parent of the PR that added them) gives ``None``."""
import re

from chipbench import families, trace_reduce
from chipbench.layer_metrics import _program_spans as ps

VERIFY, DRAFT = r"^jit_verify", r"^jit_draft"
WINDOW_KERNELS = re.compile(
    r":mosaic:paged_(index_scores|sparse_select|sparse_latent_attn)$")


def rounds(ctx):
    """The arguments of the window's ``spec_round`` spans, or None."""
    events = ps.window_events(ctx)
    lo, hi = ctx["window"]
    seen = [e.get("args", {}) for e in events or ()
            if e["ph"] == "X" and e["name"] == "spec_round"
            and lo <= e["t0"] < hi]
    return seen or None


def ratio(ctx, over, under):
    """``sum(over) / sum(under)`` of the window's rounds, or None."""
    seen = rounds(ctx)
    if not seen or not all(over in a and under in a for a in seen):
        return None
    total = sum(a[under] for a in seen)
    return sum(a[over] for a in seen) / total if total else None


def program_ms(ctx, program):
    t = trace_reduce.program_median(ctx["trace"], program)
    return None if t is None else t * 1e3


def round_ms(ctx):
    """Device time of a round: its two programs' medians, or None."""
    parts = [program_ms(ctx, p) for p in (VERIFY, DRAFT)]
    return None if None in parts or not rounds(ctx) else sum(parts)


def window_kernel_s(ctx):
    """Seconds a ROUND spends in scoring + selection + the selected read
    (both programs, every layer), or None."""
    trace = ctx["trace"]
    if not trace or not rounds(ctx):
        return None
    total = 0.0
    for program in (VERIFY, DRAFT):
        rx = re.compile(program)
        runs = sum(len(v) for k, v in trace["programs"].items()
                   if rx.search(k))
        kernel_s = sum(v for k, v in trace["custom_call_s"].items()
                       if rx.search(k) and WINDOW_KERNELS.search(k))
        if not runs or not kernel_s:
            return None
        total += kernel_s / runs
    return total


def family_of(ctx):
    """The family module if it has the window read's byte and FLOP
    function."""
    if "family" not in ctx["config"]:
        return None
    family = families.load(ctx["config"])
    return family if hasattr(family, "window_read_needs") else None
