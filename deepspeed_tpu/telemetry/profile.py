"""One loader of a profile, and the interval arithmetic its readers share.

A profile taken while the program runs (``serve(profile_dir=...)``, a
benchmark's traced window) is an ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``.  What the TPU runtime writes (looked at
by hand at PR 24 and again at PR 68: TPU v5 lite, jax 0.9.0): one plane a
chip, ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO instruction, named by the instruction's own text WITH its operands'
types (``%fusion.5 = bf16[..]{..} fusion(bf16[..] %p, ...), kind=...``) and
WITHOUT its metadata — no ``op_name``, no scope: an event's layer is found
by its serial name in the compiled program's table
(``telemetry/hlo_text.py scope_table``); a ``while`` is an event that
ENCLOSES its body's events.  ``XLA Modules`` holds one event per executed
program, ``jit_<function>(<fingerprint>)``.  Host threads are lines of
``/host:CPU``; the program's ``ds.*`` annotations and a caller's ``cb.*``
lie there under their own names.  All planes share one clock (nanoseconds).

:func:`load` reads the first device plane and the host spans ONCE; both
readers — ``telemetry/idle_gaps.py`` (where the device was NOT busy, by what
the host was doing) and ``telemetry/device_scopes.py`` (where it WAS, by
layer) — take their window from :func:`window_of`, so one profile gives both
tables on one clock and one window.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

Interval = Tuple[float, float]                 # (start_ns, end_ns)
Span = Tuple[str, float, float]                # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans that take part: the program's, and a caller's own
SPAN_PREFIXES = ("ds.", "cb.")
_HEAD = re.compile(r"^%?([\w.\-]+) = (\(.*?\)|\S+) [a-z][\w\-]*\(")


class Profile(NamedTuple):
    """What :func:`load` gives: ``ops`` the operation intervals of the first
    device plane, ``spans`` the host spans, ``modules`` the module
    executions of the same plane — and, of every operation, its serial name
    (``fusion.123``) and result type as the event's text has them
    (``names[i]`` / ``kinds[i]`` belong to ``ops[i]``; the strings are shared
    between the events of one instruction)."""
    ops: List[Interval]
    spans: List[Span]
    modules: List[Span]
    names: List[str]
    kinds: List[str]


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def instruction_head(text: str) -> Tuple[str, str]:
    """An ``XLA Ops`` event's name -> (serial name, result type)."""
    found = _HEAD.match(text)
    if found:
        return found.group(1), found.group(2)
    return text.split(" = ")[0].lstrip("%"), ""


def load(path: str, prefixes: Iterable[str] = SPAN_PREFIXES) -> Profile:
    """The first device plane's operations and module executions and the
    host spans whose name starts with one of ``prefixes``, all in
    nanoseconds on the profile's clock.  Linear in events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    prefixes = tuple(prefixes)
    ops: Optional[List[Interval]] = None
    names: List[str] = []
    kinds: List[str] = []
    spans: List[Span] = []
    modules: List[Span] = []
    heads: Dict[str, Tuple[str, str]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            if ops is not None:
                continue                        # the first device only
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = []
                    for ev in line.events:
                        text = ev.name
                        head = heads.get(text)
                        if head is None:
                            head = heads[text] = instruction_head(text)
                        start = float(ev.start_ns)
                        ops.append((start, start + float(ev.duration_ns)))
                        names.append(head[0])
                        kinds.append(head[1])
                elif line.name == MODULES_LINE:
                    modules = [(ev.name, float(ev.start_ns),
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
    return Profile(ops, spans, modules, names, kinds)


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


def window_of(ops: List[Interval], spans: List[Span]) -> Interval:
    """The outermost caller span named ``*.window`` if the profile has one
    (a benchmark's ``cb.window``), else first to last device operation."""
    wins = [(s, e) for n, s, e in spans if n.endswith(".window")]
    if wins:
        return max(wins, key=lambda w: w[1] - w[0])
    return min(s for s, _ in ops), max(e for _, e in ops)


def self_times(ops: List[Interval]) -> List[float]:
    """Duration of each operation less the operations directly nested in
    it: a ``while`` encloses its body's."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [e - s for s, e in ops]
    stack: List[Tuple[float, int]] = []
    for i in order:
        start, end = ops[i]
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= end - start
        stack.append((end, i))
    return own
