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
operation.  Reading the result: ``ds.serve.step.decode`` is host time in
the decode phase outside any in-flight span (packing, the per-slot commit
loop); ``ds.serve.decode`` itself is idle INSIDE the in-flight span —
dispatch latency before the first operation and the copy-back after the
last; a caller's span (``cb.harvest``) is the caller's own code.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]                 # (start_ns, end_ns)
Span = Tuple[str, float, float]                # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
#: host spans that take part: the program's, and a caller's own
SPAN_PREFIXES = ("ds.", "cb.")
OUTSIDE = "outside_any_span"


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str, prefixes: Iterable[str] = SPAN_PREFIXES
         ) -> Tuple[List[Interval], List[Span]]:
    """(operation intervals of the first device plane, host spans whose
    name starts with one of ``prefixes``), both in nanoseconds on the
    profile's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    prefixes = tuple(prefixes)
    ops: Optional[List[Interval]] = None
    spans: List[Span] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            if ops is not None:
                continue                        # the first device only
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns))
                           for ev in line.events]
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    spans.append((ev.name, float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns)))
    if not ops:
        raise ValueError("the profile has no device plane with an "
                         f"{OPS_LINE!r} line: nothing ran on the device, "
                         "or the profiler saw none")
    return ops, spans


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """``[lo, hi]`` minus ``busy`` (sorted, disjoint)."""
    out, cur = [], lo
    for s, e in busy:
        if e <= lo or s >= hi:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


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


def window_of(ops: List[Interval], spans: List[Span]) -> Interval:
    wins = [(s, e) for n, s, e in spans if n.endswith(".window")]
    if wins:
        return max(wins, key=lambda w: w[1] - w[0])
    return min(s for s, _ in ops), max(e for _, e in ops)


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


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="the device's idle time by host span, from a profile")
    ap.add_argument("path", help="profile directory or .xplane.pb")
    args = ap.parse_args(argv)
    res = idle_by_span(*load(args.path))
    print(f"window {res['window_s']:.3f} s, device idle "
          f"{res['idle_s']:.4f} s "
          f"({100 * res['idle_s'] / res['window_s']:.2f} %)")
    for name, sec, share in res["by_span"]:
        print(f"  {name:<32} {sec * 1e3:10.2f} ms  {100 * share:6.2f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main())
