"""What the set-up readers share (the loader skips ``_*.py``).

From PR 53 the program keeps a START-UP ring beside the serving engine's
(``deepspeed_tpu/telemetry/trace.py setup_timeline``, reachable as
``kept("setup")``, on ``time.perf_counter()`` like ``ctx["window"]`` and
the benchmark's ``Spans``).  It holds X-events of two kinds:

* the engines' own spans: ``import`` (the package's), ``init_serving`` /
  ``initialize`` (whole, with the constructors' phases inside them) and a
  ``build`` span for every program — ``args.program`` is the name the
  engine's sentry registered — from the entry of its first call to its
  results being ready;
* what ``jax.monitoring`` says of every function JAX builds, outermost
  phases only: ``trace`` / ``lower`` / ``compile`` with ``args.fn``,
  ``compile`` also with ``cache`` (``hit`` | ``miss`` | ``off``) and a
  hit's ``retrieval_s``.  One delivered while a ``build`` span was open
  carries that span's ``program``.

``account`` lays these, and the benchmark's own ``cb.setup.*`` spans, over
the seconds ``setup_s`` counts — from the process's start
(``chipbench.run.T_PROCESS``) to the window's opening — and gives every
instant to ONE row, the first of ``ROWS`` that covers it: the rows are
disjoint by construction and sum to ``setup_s``.  The arithmetic is the
yardstick's own: nothing of the program's analysis code is imported.  A
program without such a ring (the parent of the PR that added it) or a ring
that lost events gives ``None``, and the metrics are left out of the line.
"""

import sys

from chipbench import trace_reduce

BUILD = ("trace", "lower", "compile")
ENGINE = ("init_serving", "initialize")
#: ``cb.setup.*`` spans in which the PROGRAM is at work (its own calls are
#: being warmed); what JAX builds inside any other is the benchmark's own
#: jit — the weights' initialiser, the comparison with the reference
WARM = "cb.setup.warm"
ROWS = ("trace_lower_s", "compile_s", "other_jit_s", "bench_jit_s",
        "first_run_s", "engine_s", "ring_other_s", "cb_left_s",
        "uncovered_s")


def setup_ring():
    """(events oldest-pushed first, epoch_s, events dropped) of the
    process's start-up ring, or None."""
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return None
    kept = getattr(trace, "kept", None)
    timeline = kept("setup") if kept else None
    if timeline is None:
        return None
    return timeline.events(), timeline.epoch_s, timeline.dropped


def process_start():
    """``T_PROCESS`` of the run (``perf_counter`` at the top of
    ``chipbench/run.py``, as a script or as a module), or None."""
    for name in ("__main__", "chipbench.run"):
        t = getattr(sys.modules.get(name), "T_PROCESS", None)
        if t is not None:
            return t
    return None


def setup_events(ctx):
    """The ring's X-events that ENDED before the window opened, with ``t0``
    / ``t1`` (``perf_counter`` seconds) added; None without a ring, with an
    empty one, or with one that lost events."""
    ring = setup_ring()
    if ring is None:
        return None
    events, epoch_s, dropped = ring
    if dropped or not events:
        return None
    out = []
    for e in events:
        if e["ph"] != "X":
            continue
        t0 = epoch_s + e["ts"] * 1e-6
        t1 = t0 + e["dur"] * 1e-6
        if t1 <= ctx["window"][0]:
            out.append({**e, "args": e.get("args", {}), "t0": t0, "t1": t1})
    return out or None


def _inside(e, spans):
    mid = (e["t0"] + e["t1"]) / 2
    return [s for s in spans if s[0] <= mid <= s[1]]


def account(ctx):
    """``{row: seconds}`` over ``ROWS`` plus what the notes print (``by``:
    seconds of ``cb_left_s`` / ``ring_other_s`` / ``bench_jit_s`` by span
    name, of ``uncovered_s`` by where it lies; ``cache``: hits, misses,
    uncached, retrieval seconds over the registered programs' ``compile``
    events; ``setup_s``), or None."""
    events = setup_events(ctx)
    if events is None:
        return None
    hi = ctx["window"][0]
    start = process_start()
    lo = min(e["t0"] for e in events) if start is None else start
    spans = ctx.get("spans")
    cb = []                                     # (t0, t1, name)
    for name in (spans.starts if spans is not None else {}):
        if name.startswith("cb.setup."):
            cb += [(t, t + d, name) for t, d in zip(spans.starts[name],
                                                    spans.durations[name])]
    built = [e for e in events if e["name"] in BUILD]
    ring = [e for e in events if e["name"] not in BUILD]
    engine_spans = [(e["t0"], e["t1"]) for e in ring]
    rows = {k: [] for k in ROWS}
    names = {}                                  # interval -> its span's name
    programs = []
    for e in built:
        at = (e["t0"], e["t1"])
        if "program" in e["args"]:
            rows["compile_s" if e["name"] == "compile"
                 else "trace_lower_s"].append(at)
            if e["name"] == "compile":
                programs.append(e)
            continue
        own = [c for c in _inside(e, cb) if not c[2].startswith(WARM)]
        if own and not _inside(e, engine_spans):
            rows["bench_jit_s"].append(at)
            names[at] = own[0][2]
        else:
            rows["other_jit_s"].append(at)
    for e in ring:
        at = (e["t0"], e["t1"])
        row = "first_run_s" if e["name"] == "build" else \
            "engine_s" if e["name"] in ENGINE else "ring_other_s"
        rows[row].append(at)
        names[at] = e["name"]
    for t0, t1, name in cb:
        rows["cb_left_s"].append((t0, t1))
        names[t0, t1] = name
    rows["uncovered_s"].append((lo, hi))
    first_ring = min(e["t0"] for e in ring) if ring else hi

    out, by, claimed = {}, {}, []
    for row in ROWS:
        out[row] = 0.0
        for at in sorted(rows[row]):
            # an interval's own seconds: clipped to the set-up, less what
            # an earlier row — or an earlier interval of this one — took
            mine = trace_reduce.subtract(
                trace_reduce.clip([at], lo, hi), claimed)
            got = trace_reduce.total(mine)
            out[row] += got
            claimed = trace_reduce.union(claimed + mine)
            if row == "uncovered_s":
                for s, e in mine:
                    where = "before the package's import" \
                        if e <= first_ring else "after it"
                    key = (row, where)
                    by[key] = by.get(key, 0.0) + e - s
            elif at in names and got:
                key = (row, names[at])
                by[key] = by.get(key, 0.0) + got
    how = [e["args"].get("cache", "off") for e in programs]
    out["by"] = by
    out["cache"] = {"hits": how.count("hit"), "misses": how.count("miss"),
                    "off": how.count("off"),
                    "retrieval_s": sum(e["args"].get("retrieval_s", 0.0)
                                       for e in programs),
                    "missed": sorted({e["args"].get("program", "?")
                                      for e in programs
                                      if e["args"].get("cache") == "miss"})}
    out["setup_s"] = hi - lo
    return out


def row(ctx, name, got=None):
    """One row of the account (``got``, if the reader has it already); None
    without one, and where a rehearsal's row reads exactly 0
    (``test_chipbench.py`` holds every value a rehearsal prints above 0; a
    rehearsal checks paths and is never a measurement)."""
    got = account(ctx) if got is None else got
    if got is None:
        return None
    value = got[name]
    return None if ctx.get("rehearse") and not value else value
