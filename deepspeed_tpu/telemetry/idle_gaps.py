"""The device's idle time, by what the host was doing — from one profile.

    python -m deepspeed_tpu.telemetry.idle_gaps <profile_dir-or-xplane.pb>

A profile taken while the program runs (``serve(profile_dir=...)``, a
benchmark's traced window) holds the device's operations and the
program's host spans on ONE clock: :meth:`TraceTimeline.span` and
``train_batch`` enter ``ds.serve.*`` / ``ds.train.*`` profiler annotations
(``telemetry/trace.py``), and whoever drives the engine may add spans of
their own (a benchmark's ``cb.*``).  This module takes the union of the
first device's ``XLA Ops`` intervals, and PARTITIONS every gap between them
over the host spans by the innermost span at each instant.  One serving gap
runs from the commit loop of one step, through the caller's code, into the
admission and packing of the next — so a gap is split among the spans it
crosses, never awarded whole to one of them.  Time no span covers is
``outside_any_span``.

The window is the outermost caller span named ``*.window`` if the profile
has one (the benchmark's ``cb.window``), else first to last device
operation.  Reading the result: the segments of a phase
(``TraceTimeline.segment``: ``ds.serve.step.decode.plan`` / ``.upload`` /
``.commit``, the same under ``step.prefill``) are host time of that phase
outside any in-flight span; what is left on the bare
``ds.serve.step.decode`` is the phase's time in no segment.  Inside an
in-flight span, ``ds.serve.decode.enqueue`` is idle while the jitted call
has not returned (dispatch), ``ds.serve.decode.wait`` idle while the host
waits for the tokens (the tail of the dispatch latency before the first
operation, the copy-back after the last); a caller's span
(``cb.harvest``) is the caller's own code.

A second table, :func:`in_call`, puts the in-call idle time on ONE clock:
for every in-flight annotation (``ds.serve.decode``, ``ds.serve.prefill``,
...) the device module executions (``XLA Modules`` line) that belong to it,
with the **lead** (annotation start to the first module's start: dispatch
latency), the **lag** (the last module's end to the annotation's end:
copy-back and wake-up) and their sum.  A module that does NOT lie inside
its annotation means the profile's host and device clocks disagree: the
count is printed with the worst offset, lead and lag are signed, and a
constant offset between the clocks moves one into the other while their
sum stands.
"""

from __future__ import annotations

import bisect
import sys
from typing import Any, Dict, List, Optional

import numpy as np

from .profile import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,  # noqa: F401
                      SPAN_PREFIXES, Interval, Span, find_xplane, gaps, load,
                      union, window_of)

#: the program's in-flight spans (``ServingEngine.step``), as annotations
IN_FLIGHT = tuple("ds.serve." + n for n in (
    "prefill", "decode", "spec_propose", "spec_verify", "spec_round",
    "swap"))
OUTSIDE = "outside_any_span"


def innermost(spans: List[Span]) -> List[Span]:
    """The spans flattened into disjoint pieces ``(name, start, end)`` in
    time order, each piece named by the innermost span there: of the spans
    covering it, the one that started last (of equal starts the shorter)."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    todo = sorted(spans, key=lambda sp: sp[1])
    live: List[Span] = []
    out: List[Span] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(todo) and todo[i][1] <= a:
            live.append(todo[i])
            i += 1
        live = [sp for sp in live if sp[2] > a]
        if live:
            name = max(live, key=lambda sp: (sp[1], sp[1] - sp[2]))[0]
            out.append((name, a, b))
    return out


def partition(gap_list: List[Interval], spans: List[Span]
              ) -> Dict[str, float]:
    """Nanoseconds of ``gap_list`` (sorted, disjoint) by the innermost
    span at each instant, :data:`OUTSIDE` where no span is."""
    pieces = innermost(spans)
    out: Dict[str, float] = {}
    j = 0
    for lo, hi in gap_list:
        while j < len(pieces) and pieces[j][2] <= lo:
            j += 1
        covered, k = 0.0, j
        while k < len(pieces) and pieces[k][1] < hi:
            name, s, e = pieces[k]
            part = min(e, hi) - max(s, lo)
            out[name] = out.get(name, 0.0) + part
            covered += part
            k += 1
        if hi - lo > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (hi - lo - covered)
    return out


def idle_by_span(ops: List[Interval], spans: List[Span]) -> Dict[str, Any]:
    """``{"window_s", "idle_s", "by_span": [[name, seconds, share of the
    idle time], ...]}``, largest first.  A ``*.window`` span bounds the
    window and takes no idle time itself."""
    lo, hi = window_of(ops, spans)
    idle = gaps(union(ops), lo, hi)
    named = [sp for sp in spans if not sp[0].endswith(".window")]
    by = partition(idle, named)
    total = sum(by.values())
    ranked = sorted(by.items(), key=lambda kv: -kv[1])
    return {"window_s": (hi - lo) * 1e-9, "idle_s": total * 1e-9,
            "by_span": [[n, v * 1e-9, v / total if total else 0.0]
                        for n, v in ranked]}


def in_call(spans: List[Span], modules: List[Span],
            window: Optional[Interval] = None) -> Dict[str, Dict[str, Any]]:
    """Per in-flight annotation name (:data:`IN_FLIGHT`), over the
    ``calls`` a device module ran in (a module belongs to the annotation
    its midpoint lies in): ``lead_ms`` / ``lag_ms`` / ``overhead_ms``
    (lead + lag) as ``[median, 95th percentile]`` and ``device_ms`` (first
    module's start to the last's end, median).  Lead and lag are SIGNED: a
    module that starts before its annotation or ends after it — the
    profile's host and device clocks disagree, a constant offset moves one
    into the other and leaves their sum alone — reads negative and is
    counted in ``outside``, with ``worst_outside_ms`` the furthest any
    reached.  ``empty``: calls no module ran in.  Only the annotations
    that start inside ``window`` are read."""
    mods = sorted(modules, key=lambda m: m[1] + m[2])
    mids = [(m[1] + m[2]) / 2 for m in mods]
    out: Dict[str, Dict[str, Any]] = {}
    lo, hi = window or (float("-inf"), float("inf"))
    for name, s, e in spans:
        if name not in IN_FLIGHT or not lo <= s < hi:
            continue
        row = out.setdefault(name, {"lead": [], "lag": [], "device": [],
                                    "outside": 0, "worst": 0.0, "empty": 0})
        mine = mods[bisect.bisect_left(mids, s):bisect.bisect_right(mids, e)]
        if not mine:
            row["empty"] += 1
            continue
        first, last = min(m[1] for m in mine), max(m[2] for m in mine)
        row["lead"].append(first - s)
        row["lag"].append(e - last)
        row["device"].append(last - first)
        if first < s or last > e:
            row["outside"] += 1
            row["worst"] = max(row["worst"], s - first, last - e)

    def ms(values, *qs):
        return [float(np.quantile(values, q)) * 1e-6 for q in qs] \
            if values else None

    return {name: {
        "calls": len(r["lead"]), "lead_ms": ms(r["lead"], 0.5, 0.95),
        "lag_ms": ms(r["lag"], 0.5, 0.95),
        "overhead_ms": ms([a + b for a, b in zip(r["lead"], r["lag"])],
                          0.5, 0.95),
        "device_ms": (ms(r["device"], 0.5) or [None])[0],
        "outside": r["outside"], "worst_outside_ms": r["worst"] * 1e-6,
        "empty": r["empty"]} for name, r in out.items()}


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="the device's idle time by host span, from a profile")
    ap.add_argument("path", help="profile directory or .xplane.pb")
    args = ap.parse_args(argv)
    ops, spans, modules = load(args.path)[:3]
    res = idle_by_span(ops, spans)
    print(f"window {res['window_s']:.3f} s, device idle "
          f"{res['idle_s']:.4f} s "
          f"({100 * res['idle_s'] / res['window_s']:.2f} %)")
    for name, sec, share in res["by_span"]:
        print(f"  {name:<32} {sec * 1e3:10.2f} ms  {100 * share:6.2f} %")
    print("in-flight calls against the device's modules (ms: median / "
          "95th percentile)")
    for name, r in in_call(spans, modules, window_of(ops, spans)).items():
        if not r["calls"]:
            print(f"  {name:<22} no call a module ran in")
        else:
            print(f"  {name:<22} {r['calls']:6d} calls  lead "
                  f"{r['lead_ms'][0]:.3f} / {r['lead_ms'][1]:.3f}  lag "
                  f"{r['lag_ms'][0]:.3f} / {r['lag_ms'][1]:.3f}  lead + lag "
                  f"{r['overhead_ms'][0]:.3f} / {r['overhead_ms'][1]:.3f}  "
                  f"on the device {r['device_ms']:.3f}")
        print(f"  {'':<22} modules outside their call: {r['outside']}"
              + (f" (worst by {r['worst_outside_ms']:.3f} ms)"
                 if r["outside"] else "")
              + (f"; calls no module ran in: {r['empty']}"
                 if r["empty"] else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
