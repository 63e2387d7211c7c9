"""What the program-span readers share (the loader skips ``_*.py``).

The program records its own spans into a bounded ring
(``deepspeed_tpu/telemetry/trace.py``): one ``step`` X-event per scheduler
iteration, tiled by ``step.admit`` / ``step.prefill`` / ``step.decode`` /
``step.post``, with the in-flight spans (``prefill``, ``decode``,
``spec_propose`` of a draft model, ``spec_verify``, ``swap``) inside them
marking when a device program is running; ``submit`` / ``admit`` instants
carry a request's ``uid``.  ``ts`` / ``dur`` are microseconds since the
ring's ``epoch_s`` on ``time.perf_counter()``, the clock of
``ctx["window"]``.  The newest serving engine's ring stays reachable
through ``telemetry.trace.kept("serve")`` after the engine is closed.

The arithmetic here is the yardstick's own: nothing of the program's
analysis code is imported.  A program without such a ring (the parent of
the PR that added it), a ring that is off, or one that wrapped inside the
window gives ``None``, and the metric is left out of the line.
"""

from chipbench import trace_reduce

PHASES = ("step.admit", "step.prefill", "step.decode", "step.post")
IN_FLIGHT = ("prefill", "decode", "spec_propose", "spec_verify", "swap")


def serve_ring():
    """(events oldest-pushed first, epoch_s, events dropped) of the newest
    serving engine's ring, or None."""
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return None
    kept = getattr(trace, "kept", None)
    timeline = kept("serve") if kept else None
    if timeline is None:
        return None
    return timeline.events(), timeline.epoch_s, timeline.dropped


def window_events(ctx):
    """The ring's events with ``t0`` / ``t1`` (``perf_counter`` seconds)
    added, if the ring holds the whole of ``ctx["window"]``; else None.
    An X-event is pushed when it ENDS, so the ring holds everything pushed
    since the window opened iff nothing was dropped or its oldest event
    ended before the window opened."""
    ring = serve_ring()
    if ring is None:
        return None
    events, epoch_s, dropped = ring
    if not events:
        return None
    out = []
    for e in events:
        t0 = epoch_s + e["ts"] * 1e-6
        out.append({**e, "t0": t0, "t1": t0 + e.get("dur", 0.0) * 1e-6})
    if dropped and out[0]["t1"] > ctx["window"][0]:
        return None
    return out


def in_flight(e):
    """Whether the X-event spans a device program in flight (the n-gram
    proposer of speculative decoding is host work under the same name)."""
    return e["ph"] == "X" and e["name"] in IN_FLIGHT \
        and e.get("args", {}).get("mode") != "ngram"


def self_s(span, flights):
    """Seconds of ``span`` during which no in-flight span ran."""
    inside = [(max(f["t0"], span["t0"]), min(f["t1"], span["t1"]))
              for f in flights
              if f["t0"] < span["t1"] and f["t1"] > span["t0"]]
    return (span["t1"] - span["t0"]) \
        - trace_reduce.total(trace_reduce.union(inside))


def steps_in_window(ctx):
    """[(step event, its self seconds)] for the ``step`` spans that start
    inside the window, plus every event of the ring; (None, None) without
    a ring that holds the window."""
    events = window_events(ctx)
    if events is None:
        return None, None
    lo, hi = ctx["window"]
    flights = [e for e in events if in_flight(e)]
    steps = [e for e in events if e["ph"] == "X" and e["name"] == "step"
             and lo <= e["t0"] < hi]
    return [(s, self_s(s, flights)) for s in steps], events
