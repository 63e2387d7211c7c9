"""What the host-segment readers share (the loader skips ``_*.py``).

From PR 36 the program times the pieces of a scheduler step WITHOUT a ring
event apiece (``TraceTimeline.segment``): the seconds go on arguments of
the spans the ring already has.  ``step.prefill`` and ``step.decode`` carry
``plan_s`` (choosing rows, reserving blocks, numpy packing), ``upload_s``
(the host-to-device puts of a call's operands) and ``commit_s`` (the
per-row loop after the harvest); the in-flight spans carry ``enqueue_s``
(the jitted call, entry to return) and ``wait_s`` (until the tokens are
numpy on the host); ``step`` carries ``cpu_s`` / ``flight_cpu_s``
(thread-CPU seconds of the step / of its in-flight spans) and ``gc_s`` /
``gc_n`` (collector runs that began in it).  A ring whose spans lack the
argument a reader needs — the parent of that PR — gives ``None``, as does
a ring that wrapped inside the window or no ring
(``_program_spans.steps_in_window``).
"""

from chipbench.layer_metrics import _program_spans as ps

HOST_PHASES = ("step.prefill", "step.decode")
SEGMENTS = ("plan_s", "upload_s", "commit_s")


def window_phases(ctx):
    """(steps, their ``step.prefill`` / ``step.decode`` spans, in-flight
    spans) for the steps that start inside the window; None without a ring
    that holds it or before the program timed its segments."""
    steps, events = ps.steps_in_window(ctx)
    if not steps:
        return None
    mine = {s["args"]["step"] for s, _ in steps if "step" in s.get("args", {})}
    phases = [e for e in events if e["ph"] == "X"
              and e["name"] in HOST_PHASES
              and e.get("args", {}).get("step") in mine]
    if not any(k in p["args"] for p in phases for k in SEGMENTS):
        return None
    return steps, phases, [e for e in events if ps.in_flight(e)]


def segment_ms(ctx, key):
    """Mean per window step of ``key`` summed over its host phases."""
    got = window_phases(ctx)
    if got is None:
        return None
    steps, phases, _ = got
    return 1e3 * sum(p["args"].get(key, 0.0) for p in phases) / len(steps)


def decode_calls(ctx):
    """The window's ``decode`` spans, if they carry ``enqueue_s``."""
    events = ps.window_events(ctx)
    if events is None:
        return None
    lo, hi = ctx["window"]
    calls = [e for e in events if e["ph"] == "X" and e["name"] == "decode"
             and lo <= e["t0"] < hi and "enqueue_s" in e.get("args", {})]
    return calls or None


def share(ctx, part, whole):
    """``part`` as a percentage of ``whole``.  A rehearsal's window is a
    second of a tiny model: a share that reads exactly 0 there is left
    out (a rehearsal checks paths and is never a measurement)."""
    if not whole:
        return None
    value = 100.0 * part / whole
    return None if ctx.get("rehearse") and not value else value
