"""From a profiler trace to numbers.  Two stages, so that the arithmetic can
be checked on hand-countable intervals (``tests/chipbench``):

``load_xplane(path)``  reads the ``.xplane.pb`` the JAX profiler writes
                       (``jax.profiler.ProfileData``, nothing but JAX) into
                       a plain dict of planes -> lines -> events;
``reduce(trace)``      turns that dict into busy time, per-operation
                       sums, program executions, idle gaps attributed to
                       the benchmark's own host spans, and collective time.

What the TPU runtime writes (looked at by hand, PR 24: TPU v5 lite, jax
0.9.0, libtpu 0.0.34): one plane per chip named ``/device:TPU:<n>``.  Its
line ``XLA Ops`` holds one event per executed HLO operation, in time order
on the one TensorCore, named by the operation's whole HLO text
(``%fusion.5 = bf16[..] fusion(...)``); a ``while`` (the micro-batch loop)
is an event that encloses its body's events, so sums are taken over SELF
time.  A Pallas kernel is a ``custom-call`` whose text carries
``custom_call_target="tpu_custom_call"`` (other custom calls, such as
``ConcatBitcast``, are XLA's own and take no time).  ``XLA Modules`` holds
one event per executed program, named ``jit_<function>(<fingerprint>)``;
``Async XLA Ops`` holds the spans of asynchronous copies and collectives
(start to done), which overlap the TensorCore's line.  There is no
``hlo_category`` stat.  Host threads are lines of the plane ``/host:CPU``;
``jax.profiler.TraceAnnotation`` spans appear on the line of the thread
that made them (``python3``) under their own names.  All planes share one
clock (nanoseconds since the trace started).

    python -m chipbench.trace_reduce <dir-or-xplane.pb> [--out file.json]

prints (or writes) a summary of a trace: its planes, lines, the commonest
event names with their stats keys — for looking at one by hand.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
#: the benchmark's own host spans all start with this
SPAN_PREFIX = "cb."
WINDOW_SPAN = "cb.window"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)")
#: operations that enclose other operations' events on the ops line
CONTAINERS = ("while", "conditional", "call")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


# ------------------------------------------------------------------ loading
def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def parse_op(text: str) -> Tuple[str, Dict[str, Any]]:
    """An ``XLA Ops`` event's name (whole HLO text) -> (the operation's own
    name, {"opcode", and "mosaic": True for a Pallas kernel}).  The opcode
    is the first lower-case word followed by ``(`` after the result shape:
    layouts inside a shape (``T(8,128)``, ``S(1)``) follow a ``:`` or ``)``,
    never a space."""
    lhs, _, rhs = text.partition(" = ")
    m = _OPCODE.search(" " + rhs)
    stats: Dict[str, Any] = {"opcode": m.group(1) if m else base_name(lhs)}
    if MOSAIC_TARGET in rhs:
        stats["mosaic"] = True
    return lhs.lstrip("%"), stats


def load_xplane(path: str, keep_host_prefix: str = SPAN_PREFIX
                ) -> Dict[str, Any]:
    """The plain form ``reduce`` takes: planes -> lines -> events, an event
    being ``[name, start_ns, duration_ns, stats]``.  Of a device plane the
    lines above, each operation cut down to its name, opcode and whether it
    is a Pallas kernel (the HLO text is hundreds of characters, the line
    hundreds of thousands of events); of ``Async XLA Ops`` only the
    collectives; of the host planes only the benchmark's own spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE,
                                            MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                name, stats = ev.name, {}
                if not device:
                    if not name.startswith(keep_host_prefix):
                        continue
                elif line.name != MODULES_LINE:
                    name, stats = parse_op(name)
                    if line.name == ASYNC_LINE \
                            and not COLLECTIVE.match(stats["opcode"]):
                        continue
                events.append([name, float(ev.start_ns),
                               float(ev.duration_ns), stats])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# --------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both sorted and disjoint (as ``union`` gives)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(events) -> List[Interval]:
    return [(ev[1], ev[1] + ev[2]) for ev in events]


# ---------------------------------------------------------------- reduction
def base_name(name: str) -> str:
    """``%fusion.123`` -> ``fusion``: the operation without its serial
    number, so that the table groups what is the same work."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", name) or name


def self_times(events) -> List[float]:
    """Duration of each event minus the events directly nested in it
    (a ``while`` encloses its body's operations)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [ev[2] for ev in events]
    stack: List[Tuple[float, int]] = []
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= events[i][2]
        stack.append((end, i))
    return own


def module_name(name: str) -> str:
    """``jit_decode_step(1234567890)`` -> ``jit_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _host_spans(trace) -> List[Tuple[str, float, float]]:
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                if ev[0].startswith(SPAN_PREFIX):
                    out.append((ev[0], ev[1], ev[1] + ev[2]))
    return out


def _attribute(gap: Interval, spans) -> str:
    """The benchmark span that covers most of a gap; of equal cover the
    shortest (the innermost).  ``cb.window`` itself never wins."""
    best, best_key = "outside_any_span", (0.0, 0.0)
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        key = (cover, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce(trace: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Everything the per-layer metrics read, in seconds.

    ``window_s``       the traced window: the ``cb.window`` span if the
                       trace has one, else first to last device event
    ``busy_s``         union of the device-op intervals inside the window,
                       averaged over the device planes
    ``device_ops``     [[operation, seconds], ...] most SELF time first,
                       summed inside the window and averaged over devices;
                       the key is ``<module>:<operation without its serial
                       number>``, a Pallas kernel's marked ``mosaic:``
    ``idle_gaps``      [[span, seconds], ...] idle time of the first device
                       by the benchmark span that covered it
    ``longest_gaps``   [[span, seconds], ...] the single longest gaps
    ``programs``       module -> durations of its WHOLE executions inside the
                       window (first device; one cut by the trace's start
                       or stop is left out)
    ``custom_call_s``  ``<module>:mosaic:<kernel>`` (the key ``device_ops``
                       gives a Pallas kernel) -> seconds in that kernel
                       inside the module's whole executions (first device)
    ``collective_s`` / ``collective_exposed_s``  time in collective
                       operations, and the part of it during which no
                       other operation ran on that device (device average)
    """
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("the trace has no device plane: nothing ran on "
                         "the device, or the profiler saw no TPU")
    spans = _host_spans(trace)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    all_ops = [iv for p in devices for iv in _spans(_line(p, OPS_LINE))]
    if not all_ops:
        raise ValueError("the trace's device planes hold no operation")
    if win:
        lo, hi = win[0]
    else:
        lo, hi = min(s for s, _ in all_ops), max(e for _, e in all_ops)

    busy, coll, exposed = [], [], []
    op_sums: Dict[str, float] = {}
    for plane in devices:
        ops = _line(plane, OPS_LINE)
        mods = sorted((ev[1], ev[1] + ev[2], module_name(ev[0]))
                      for ev in _line(plane, MODULES_LINE))
        starts = [m[0] for m in mods]

        def module_at(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t < mods[i][1] else ""

        all_iv, leaf_iv, coll_iv = [], [], []
        for ev, own in zip(ops, self_times(ops)):
            iv = clip([(ev[1], ev[1] + ev[2])], lo, hi)
            if not iv:
                continue
            opcode = ev[3].get("opcode", "")
            all_iv.extend(iv)
            if COLLECTIVE.match(opcode):
                coll_iv.extend(iv)
            elif opcode not in CONTAINERS:
                leaf_iv.extend(iv)
            what = base_name(ev[0])
            if ev[3].get("mosaic"):
                what = "mosaic:" + what
            mod = module_at(ev[1])
            key = f"{mod}:{what}" if mod else what
            # an event cut by the window's edge counts for what is inside
            op_sums[key] = op_sums.get(key, 0.0) + min(own, total(iv))
        busy.append(total(union(all_iv)))
        # a collective lasts from its start to its done on the async line;
        # it holds the core up only inside its own events on the ops line
        cu = union(coll_iv)
        asyn = clip(_spans(_line(plane, ASYNC_LINE)), lo, hi)
        coll.append(total(union(coll_iv + asyn)))
        exposed.append(total(subtract(cu, union(leaf_iv))))

    first = devices[0]
    busy_iv = union(clip(_spans(_line(first, OPS_LINE)), lo, hi))
    gaps = subtract([(lo, hi)], busy_iv)
    by_span: Dict[str, float] = {}
    longest = []
    for gap in gaps:
        name = _attribute(gap, spans)
        by_span[name] = by_span.get(name, 0.0) + (gap[1] - gap[0])
        longest.append([name, (gap[1] - gap[0]) * 1e-9])
    longest.sort(key=lambda x: -x[1])

    programs: Dict[str, List[float]] = {}
    custom: Dict[str, float] = {}
    # whole executions only: the profiler cuts the program that is running
    # when the trace starts or stops, and such a stump is no step time
    first_ops = _spans(_line(first, OPS_LINE))
    t_first = min(s for s, _ in first_ops)
    t_last = max(e for _, e in first_ops)
    mods = [(ev[1], ev[1] + ev[2], module_name(ev[0]))
            for ev in _line(first, MODULES_LINE)
            if max(lo, t_first) < ev[1] and ev[1] + ev[2] < min(hi, t_last)]
    for s, e, name in mods:
        programs.setdefault(name, []).append((e - s) * 1e-9)
    cc = sorted((ev[1], ev[1] + ev[2], base_name(ev[0]))
                for ev in _line(first, OPS_LINE) if ev[3].get("mosaic"))
    for s, e, name in mods:
        inside: Dict[str, float] = {}
        for cs, ce, kernel in cc:
            if cs >= s and ce <= e:
                inside[kernel] = inside.get(kernel, 0.0) + (ce - cs)
        for kernel, ns in inside.items():
            key = f"{name}:mosaic:{kernel}"
            custom[key] = custom.get(key, 0.0) + ns * 1e-9

    n = len(devices)
    ranked = sorted(op_sums.items(), key=lambda kv: -kv[1])
    return {
        "devices": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "device_ops": [[k, v / n * 1e-9] for k, v in ranked[:top]],
        "idle_gaps": [[k, v * 1e-9] for k, v in
                      sorted(by_span.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gaps": longest[:top],
        "programs": programs,
        "custom_call_s": custom,
        "collective_s": sum(coll) / n * 1e-9,
        "collective_exposed_s": sum(exposed) / n * 1e-9,
    }


def program_times(reduced: Dict[str, Any], pattern: str) -> List[float]:
    """Durations of every execution of the programs whose module name
    matches ``pattern`` (a regular expression, searched)."""
    rx = re.compile(pattern)
    return [t for name, ts in reduced["programs"].items()
            if rx.search(name) for t in ts]


def program_median(reduced: Optional[Dict[str, Any]], pattern: str
                   ) -> Optional[float]:
    """Median device seconds of one whole execution of the programs that
    match ``pattern``; ``None`` without a trace or without such a program."""
    times = program_times(reduced, pattern) if reduced else []
    return statistics.median(times) if times else None


# ---------------------------------------------------------------- by hand
def summarize(path: str, names: int = 25) -> Dict[str, Any]:
    """What is in a trace: for looking at one by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            counts: Dict[str, List[float]] = {}
            sample: Dict[str, Any] = {}
            n, t0, t1 = 0, None, None
            for ev in line.events:
                n += 1
                c = counts.setdefault(ev.name[:120], [0, 0.0])
                c[0] += 1
                c[1] += ev.duration_ns
                t0 = ev.start_ns if t0 is None else min(t0, ev.start_ns)
                t1 = max(t1 or 0, ev.start_ns + ev.duration_ns)
                if ev.name[:120] not in sample and len(sample) < names:
                    sample[ev.name[:120]] = {
                        k: str(v)[:300] for k, v in list(ev.stats)[:12]}
            top_names = sorted(counts.items(), key=lambda kv: -kv[1][1])
            every = []
            if line.name in (MODULES_LINE, "Steps"):
                every = [[ev.name[:80], ev.start_ns, ev.duration_ns]
                         for ev in line.events][:400]
            lines.append({
                "line": line.name, "events": n, "first_ns": t0,
                "last_ns": t1, "every_event": every,
                "top": [[k, v[0], v[1] * 1e-9] for k, v in
                        top_names[:names]],
                "stats_of": {k: sample[k] for k, _ in top_names[:names]
                             if k in sample}})
        out.append({"plane": plane.name, "lines": lines})
    return {"file": find_xplane(path), "planes": out}


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="summarize a profiler trace")
    ap.add_argument("path")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    text = json.dumps(summarize(args.path), indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
