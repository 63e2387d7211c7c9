"""Per-request trace timeline: a bounded host-side ring buffer of engine
events, exportable as Chrome ``trace_event`` JSON (Perfetto-viewable).

``ServingEngine.stats()`` aggregates; it cannot answer "where did THIS
slow request spend its time".  The timeline records one small dict per
scheduler event — admit, prefill chunk, decode step, spec propose /
verify (with per-slot accept lengths), prefix hit/miss, block eviction,
preemption, finish, plus the ``analysis/`` sentry's (re)trace events and
the per-iteration invariant audits — into a ``deque(maxlen=capacity)``:
bounded memory forever, O(1) append, and a ``dropped`` counter that says
exactly how much history fell off the ring.  ``capacity=0`` disables
recording entirely (one predicate per would-be event — the "near-free
when idle" half of the telemetry overhead contract; the enabled half is
pinned ≤2% by the ``--telemetry-bench`` serving-bench lane).

Export (:meth:`TraceTimeline.to_chrome` / :meth:`dump`) follows the
Chrome ``trace_event`` JSON-object format: ``X`` (complete) events carry
``ts``+``dur``, ``i`` (instant) events just ``ts``, ``s``/``f`` flow
events carry a shared ``id`` and render as arrows between lanes (the
cross-replica request/KV-pull linkage — ``telemetry/aggregate.py``
merges rings onto distinct ``pid`` lanes), every event has
``pid``/``tid``, timestamps are microseconds since the timeline epoch and
sorted ascending, and ``M`` metadata events name the process and each
registered thread lane.  Load the file at https://ui.perfetto.dev (or
``chrome://tracing``) — requests appear as one span lane each, scheduler
phases as a shared lane (walkthrough: ``docs/observability.md``).

One span, two clocks: :meth:`TraceTimeline.span` records into the ring on
the host clock (always) AND enters a ``jax.profiler`` annotation named
``ds.<role>.<name>`` for the same interval (:func:`annotation`, the one
place the program constructs one).  An annotation is a flag test while no
profile is being taken; while one is — ``serve(profile_dir=...)``, a
benchmark's traced window — the span lands on the profiler's own clock
beside the device planes, where ``telemetry/idle_gaps.py`` lays the
device's idle gaps against it.  ``capacity=0`` turns the ring off, never
the annotations.  :func:`keep` / :func:`kept` hold the newest timeline of
each role reachable after its engine is gone (the ring and its epoch only
— a timeline references nothing of its engine).

A span costs a ring event; what happens INSIDE a span several times a step
must not.  :meth:`TraceTimeline.segment` is the second primitive: the same
``ds.<role>.<name>`` annotation, and — ring on — the interval's seconds
ADDED to an argument of the enclosing span (``step.decode``'s ``plan_s``,
``decode``'s ``wait_s``), no event of its own — the idiom the engine's
``kv_s`` keeps by hand on the ``step`` span.  :class:`GcWatch` puts the
collector's pauses on the same clock.

:class:`ProfilerWindow` is the deep-dive escalation: it brackets a region
with ``jax.profiler.start_trace`` / ``stop_trace`` so a slow window seen
in the host timeline can be re-run with full XLA/device traces
(``ServingEngine.serve(profile_dir=...)`` wires it around N scheduler
iterations).  Failures to start the profiler degrade to a logged warning
— telemetry must never take the serving loop down.
"""

from __future__ import annotations

import gc
import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

import jax

from ..utils.logging import logger

__all__ = ["TraceTimeline", "ProfilerWindow", "GcWatch", "FirstCall",
           "validate_chrome_trace", "annotation", "keep", "kept",
           "setup_timeline", "setup_summary", "setup_line"]

#: tid of the shared scheduler lane (request lanes are allocated upward)
SCHEDULER_TID = 0

#: events the serving ring holds by default.  The requirement: two minutes
#: of the densest serving cell at twice its step rate of PR 35 — the chat
#: cell, ~95 steps/s x ~7.5 events + ~20 requests/s x 3 = ~800 events/s,
#: 96,000 in 120 s — rounded up to a power of two.  An event is two small
#: dicts and a few floats: see ``docs/observability.md`` for the measured
#: footprint of a full ring
DEFAULT_CAPACITY = 131072

#: events the process's start-up ring holds (:func:`setup_timeline`).  The
#: requirement: everything one process builds before its first steps —
#: JAX hands over three events a function it builds (``trace`` / ``lower``
#: / ``compile``), an engine's start builds some hundreds of functions one
#: by one (eager operations, initialisers, casts: ~300 in the largest
#: serving cell) beside its few programs and two dozen spans, and a process
#: may hold a router's worth of engines (four) — 4 x ~1,000 events,
#: doubled and rounded up to a power of two
SETUP_CAPACITY = 8192

#: the JAX events the start-up ring holds, by phase of a function's build
BUILD_PHASES = ("trace", "lower", "compile")

#: role -> the newest timeline built for it (see :func:`keep`); the role
#: ``programs`` holds the newest engine's ``telemetry/programs.py Programs``
_KEPT: Dict[str, Any] = {}


def annotation(name: str):
    """Context manager that puts ``name`` on the profiler's host line for
    the interval it is entered — the program's ONE constructor of profiler
    annotations (``ds.serve.*`` through :meth:`TraceTimeline.span`,
    ``ds.train.*`` from ``train_batch``).  While no profile is being taken
    entering it is a flag test."""
    return jax.profiler.TraceAnnotation(name)


class _Segment:
    """``with`` block of :meth:`TraceTimeline.segment`: enters ``note`` (a
    profiler annotation) and adds the body's seconds on the timeline's
    clock to ``into[key]`` (``into`` None: the ring is off).  A timeline
    keeps ONE per segment name and hands it out again with the next
    ``into`` — a segment runs several times a step and is never inside
    itself.  The annotation is made anew at every entry: one decides AS IT
    IS MADE whether a profile is being taken, so a kept one would stay
    silent in every profile started after it.

    Segments TILE: one that is entered with nothing but bookkeeping since
    the last boundary — the exit of a segment, the end of a span — begins
    AT that boundary (the timeline's ``_lap``), not at its own clock
    read, so the microseconds of leaving one ``with`` and entering the
    next belong to the segment that follows.  A span's entry clears the
    boundary: what is entered first inside a span begins now."""

    __slots__ = ("into", "_tl", "_key", "_name", "_note", "_t0")

    def __init__(self, timeline, key, name):
        self._tl, self._key, self._name = timeline, key, name
        self.into = None

    def __enter__(self):
        self._note = annotation(self._name)
        self._note.__enter__()
        if self.into is not None:
            tl = self._tl
            lap, tl._lap = tl._lap, None
            self._t0 = tl._clock() if lap is None else lap
        return self.into

    def __exit__(self, *exc):
        into = self.into
        if into is not None:
            tl = self._tl
            tl._lap = now = tl._clock()
            into[self._key] = into.get(self._key, 0.0) + now - self._t0
        self._note.__exit__(*exc)
        return False


def keep(role: str, timeline: Any) -> None:
    """Hold ``timeline`` as the newest of its ``role`` (``serve``): whoever
    drove an engine reads its spans through :func:`kept` after the engine is
    closed or collected.  One slot per role, replaced by the next engine.
    The role ``programs`` holds an engine's ``Programs`` the same way: the
    scope tables of its compiled programs, built when first asked for."""
    _KEPT[role] = timeline


def kept(role: str) -> Optional[Any]:
    """The newest timeline (``programs``: the newest ``Programs``)
    :func:`keep` was given for ``role``, or None."""
    return _KEPT.get(role)


def setup_timeline(epoch_s: Optional[float] = None) -> "TraceTimeline":
    """The process's ONE start-up ring — ``kept("setup")``, role ``setup``,
    :data:`SETUP_CAPACITY` events — made by whoever asks first: the
    package's import (which hands it the clock read at its top as
    ``epoch_s``, so that ``import`` starts at 0), an engine's construction
    or ``analysis/sentry.install_compile_listener``.  It holds what happens
    BEFORE an engine's first steps: the engines' own boundaries as spans
    (``import``, ``init_serving``, ``initialize``, ``build`` ...; the table
    is in ``docs/observability.md`` "Start-up") and, from the listener,
    every function JAX builds as ``trace`` / ``lower`` / ``compile``
    X-events.  Nothing is pushed unless something is being built, so a
    step pays nothing for it."""
    timeline = _KEPT.get("setup")
    if timeline is None:
        timeline = TraceTimeline(capacity=SETUP_CAPACITY)
        timeline.role = "setup"
        if epoch_s is not None:
            timeline._t0 = epoch_s
        keep("setup", timeline)
    return timeline


class FirstCall:
    """A jitted function whose FIRST call is a ``build`` span of the
    start-up ring: from the call's entry to its results being ready, with
    ``program`` (the name the engine's sentry registered) and whatever
    sizes it (``args``).  While the span is open the ring says which
    program is being built (``TraceTimeline.building``), and the listener
    stamps it on the ``trace`` / ``lower`` / ``compile`` events JAX hands
    over: they join the span by name as well as by time.  What is left of
    the span beside them is the executable's load onto the chip and the
    first execution.  ``then(fn)``, if given, is called with the bare
    function once the first call is over — the engine puts it where this
    wrapper was, so no later call passes through here; one that does (a
    reference taken before the first call) is handed straight on.
    ``before(fn, *args)``, if given, runs inside the span ahead of the call
    with the call's own arguments: what it builds of the program (a
    ``fn.lower(*args).compile()`` to read) is built under the program's
    name, and the call then finds it in JAX's caches.
    ``programs``, if given (``telemetry/programs.py Programs``), is handed
    the function and the call's arguments BEFORE the call and keeps their
    abstract signature: what finds the executable again for a scope table —
    microseconds, nothing lowered or compiled.
    Attributes (``lower``, ``_cache_size``) are the function's own."""

    __slots__ = ("_fn", "_program", "_args", "_then", "_before", "_programs",
                 "_called")

    def __init__(self, fn, program: str, then=None, before=None,
                 programs=None, **args):
        self._fn, self._program, self._args = fn, program, args
        self._then, self._before, self._called = then, before, False
        self._programs = programs

    def __call__(self, *args, **kwargs):
        if self._called:
            return self._fn(*args, **kwargs)
        self._called = True
        if self._programs is not None:
            self._programs.record(self._program, self._fn, args, kwargs)
        timeline = setup_timeline()
        with timeline.span("build", program=self._program, **self._args):
            outer, timeline.building = timeline.building, self._program
            try:
                if self._before is not None:
                    self._before(self._fn, *args, **kwargs)
                out = jax.block_until_ready(self._fn(*args, **kwargs))
            finally:
                timeline.building = outer
        if self._then is not None:
            self._then(self._fn)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


class TraceTimeline:
    """Bounded ring buffer of trace events with Chrome export.

    Parameters
    ----------
    capacity:  max events retained (oldest evicted first; ``dropped``
               counts evictions).  ``0`` disables recording — every emit
               is one ``if`` and the buffer stays empty.
    pid:       the exported ``pid`` (multi-process launchers pass
               ``jax.process_index()`` so merged traces stay distinct).
    clock:     second-denominated monotonic clock (injectable for tests).

    ``role`` names the spans' profiler annotations (``ds.<role>.<name>``:
    ``serve`` for an engine's ring, ``setup`` for the process's start-up
    ring, :func:`setup_timeline`); ``step``, once its owner sets it, is
    stamped on every ``X`` event as ``args["step"]`` — the scheduler
    iteration that caused the span.
    """

    role = "serve"
    step: Optional[int] = None
    #: the program whose ``build`` span is open (:class:`FirstCall`)
    building: Optional[str] = None
    #: :func:`setup_summary` of the ring as it stood after that many events
    summarized: tuple = (None, None)

    def __init__(self, capacity: int = DEFAULT_CAPACITY, pid: int = 0,
                 clock=None):
        if capacity < 0:
            raise ValueError(f"capacity must be >= 0, got {capacity}")
        self.capacity = int(capacity)
        self.enabled = self.capacity > 0
        self.pid = int(pid)
        self._clock = clock or time.perf_counter
        self._t0 = self._clock()
        self._events: deque = deque(maxlen=max(self.capacity, 1))
        self.emitted = 0
        self.dropped = 0
        #: segment name -> its one ``_Segment``
        self._segments: Dict[str, _Segment] = {}
        #: the clock at the last boundary a segment may begin at (the exit
        #: of a segment, the end of a span); None: none since a span began
        self._lap: Optional[float] = None
        self._thread_names: Dict[int, str] = {SCHEDULER_TID: "scheduler"}
        self._next_tid = 1
        # lane allocation is check-then-act (look up name, else mint a
        # tid) and runs off the hot path, so it takes a lock; the emit
        # path stays lock-free on purpose — deque appends are GIL-atomic
        # and the ring tolerates interleaved emitters (the router
        # timeline is written by worker threads AND the caller thread)
        self._names_lock = threading.Lock()

    # ------------------------------------------------------------------ time
    def now_us(self) -> float:
        """Microseconds since the timeline epoch (event ``ts`` domain)."""
        return (self._clock() - self._t0) * 1e6

    @property
    def epoch_s(self) -> float:
        """The timeline's epoch on its own clock — rings recorded in one
        process share a clock, so ``telemetry/aggregate.py`` re-bases
        every ring's ``ts`` onto the earliest epoch when merging."""
        return self._t0

    # --------------------------------------------------------------- threads
    def thread(self, name: str) -> int:
        """Allocate (or look up) a named lane; returns its ``tid``.
        Lanes are for small, fixed sets (the serving engine allocates one
        per SLOT at construction — request spans land on the slot that
        finished them), never per-request values: every lane is a
        name-table entry and a Perfetto row forever."""
        with self._names_lock:
            for tid, n in self._thread_names.items():
                if n == name:
                    return tid
            tid = self._next_tid
            self._next_tid += 1
            self._thread_names[tid] = name
            return tid

    # ---------------------------------------------------------------- emits
    def _push(self, ev: Dict[str, Any]) -> None:
        if len(self._events) == self._events.maxlen:
            self.dropped += 1
        self._events.append(ev)
        self.emitted += 1

    def instant(self, name: str, tid: int = SCHEDULER_TID,
                ts: Optional[float] = None, **args) -> None:
        """One ``i`` (instant) event."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t",
              "ts": self.now_us() if ts is None else ts,
              "pid": self.pid, "tid": tid}
        if args:
            ev["args"] = args
        self._push(ev)

    def complete(self, name: str, start_us: float,
                 tid: int = SCHEDULER_TID, end_us: Optional[float] = None,
                 **args) -> None:
        """One ``X`` (complete) event spanning ``[start_us, end_us]``
        (``end_us`` defaults to now)."""
        if not self.enabled:
            return
        end = self.now_us() if end_us is None else end_us
        ev = {"name": name, "ph": "X", "ts": start_us,
              "dur": max(end - start_us, 0.0),
              "pid": self.pid, "tid": tid}
        if self.step is not None:
            args.setdefault("step", self.step)
        if args:
            ev["args"] = args
        self._push(ev)

    def flow_start(self, name: str, flow_id: int,
                   tid: int = SCHEDULER_TID, ts: Optional[float] = None,
                   **args) -> None:
        """One ``s`` (flow start) event.  Chrome flow events with the same
        ``id`` render as an arrow between lanes — even across ``pid``s in
        a merged multi-replica document — which is how a routed request's
        router span links to its replica admission, and a cross-replica
        KV pull links its source lane to its target lane.  Callers must
        allocate ``flow_id`` uniquely across every ring that will be
        merged (the ``ReplicaRouter`` owns one counter for the fleet)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "s", "cat": "flow", "id": int(flow_id),
              "ts": self.now_us() if ts is None else ts,
              "pid": self.pid, "tid": tid}
        if args:
            ev["args"] = args
        self._push(ev)

    def flow_end(self, name: str, flow_id: int,
                 tid: int = SCHEDULER_TID, ts: Optional[float] = None,
                 **args) -> None:
        """One ``f`` (flow finish) event — the arrowhead of the matching
        :meth:`flow_start`.  ``bp: "e"`` binds it to the enclosing slice
        (Chrome's "bind to enclosing" convention)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "f", "cat": "flow", "bp": "e",
              "id": int(flow_id),
              "ts": self.now_us() if ts is None else ts,
              "pid": self.pid, "tid": tid}
        if args:
            ev["args"] = args
        self._push(ev)

    @contextmanager
    def span(self, name: str, tid: int = SCHEDULER_TID, **args):
        """Context manager emitting an ``X`` event around the body AND a
        profiler annotation ``ds.<role>.<name>`` for the same interval
        (module docstring); the body can mutate ``args`` in place
        (accept-lengths are known only after the verify pass returns)."""
        with annotation(f"ds.{self.role}.{name}"):
            if not self.enabled:
                yield args
                return
            self.lap()
            start = self.now_us()
            try:
                yield args
            finally:
                # the end is read as the body ends, before the event is
                # built: a segment entered next begins here
                end = self.now_us()
                self.complete(name, start, tid=tid, end_us=end, **args)
                self.lap(end)

    def lap(self, ts_us: Optional[float] = None) -> None:
        """Set the boundary the next segment begins at (:class:`_Segment`)
        to ``ts_us`` on the event clock — the end of the span that was
        just emitted — or, with None, clear it: a span has begun, what is
        entered first inside it begins at its own clock read."""
        self._lap = None if ts_us is None else ts_us * 1e-6 + self._t0

    def segment(self, name: str, into: Dict[str, Any]):
        """Context manager for a piece of a span that is NOT an event: the
        profiler annotation ``ds.<role>.<name>`` for the interval and, ring
        on, its seconds added to ``into[<last part of name>_s]`` —
        ``into`` being the argument dict the ENCLOSING span yielded
        (``segment("step.decode.upload", phase)`` grows
        ``phase["upload_s"]``).  Pushes nothing; ring off it is the
        annotation's flag test and one predicate."""
        seg = self._segments.get(name)
        if seg is None:
            seg = self._segments[name] = _Segment(
                self, name.rsplit(".", 1)[-1] + "_s",
                f"ds.{self.role}.{name}")
        seg.into = into if self.enabled else None
        return seg

    # ---------------------------------------------------------------- export
    def __len__(self) -> int:
        return len(self._events) if self.enabled else 0

    def events(self) -> List[Dict[str, Any]]:
        """Live events, oldest first (the ring view — NOT yet sorted)."""
        return list(self._events) if self.enabled else []

    def to_chrome(self, process_name: str = "deepspeed_tpu") -> Dict[str, Any]:
        """Chrome ``trace_event`` JSON-object document: ``M`` metadata
        naming the process and lanes, then every ring event sorted by
        ``ts`` ascending."""
        meta: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "ts": 0.0,
            "pid": self.pid, "tid": SCHEDULER_TID,
            "args": {"name": process_name},
        }]
        with self._names_lock:
            lanes = sorted(self._thread_names.items())
        for tid, name in lanes:
            meta.append({"name": "thread_name", "ph": "M", "ts": 0.0,
                         "pid": self.pid, "tid": tid,
                         "args": {"name": name}})
        body = sorted(self.events(), key=lambda e: e["ts"])
        return {"traceEvents": meta + body,
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "emitted_events": self.emitted}}

    def dump(self, path: str, process_name: str = "deepspeed_tpu") -> str:
        """Write the Chrome trace JSON to ``path``; returns ``path``
        (open it at https://ui.perfetto.dev)."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(process_name), f)
        return path


def validate_chrome_trace(doc: Dict[str, Any],
                          strict_flows: Optional[bool] = None
                          ) -> Dict[str, Any]:
    """Schema-check an exported Chrome ``trace_event`` document; raises
    :class:`ValueError` naming the first violation, returns a summary.

    Checked (the contract the serving bench records and the telemetry
    tests pin): ``traceEvents`` is a list; every event carries ``name`` /
    ``ph`` / ``ts`` / ``pid`` / ``tid``; phases are ``M``/``i``/``X``/
    ``B``/``E``/``s``/``f`` with ``X`` events carrying a non-negative
    ``dur``, ``B``/``E`` balanced per ``(pid, tid)``, ``s``/``f`` flow
    events carrying an ``id``; non-metadata timestamps are monotone
    non-decreasing (sorted export).

    ``strict_flows`` additionally requires every flow to PAIR — each
    finish follows a start with the same id, no start dangles.  Default
    ``None`` auto-enables it for merged multi-source documents
    (``otherData.sources``, the ``merge_chrome_traces`` marker) and
    leaves single rings lenient: one replica's ring legitimately holds
    only its half of a cross-ring flow (the router holds the other), so
    strict pairing is a whole-fleet property.  Unpaired flows are
    counted in ``flow_unmatched`` either way (in a merged document a
    nonzero count means the other end was never emitted or fell off a
    ring — check ``dropped_events``).

    Disaggregated ``handoff`` instants pair the same way per ``uid``:
    the prefill engine emits its half first (args carry ``slot``), the
    router's pump emits the routing half second (args carry
    ``src``/``dst``), so a router-side handoff with no preceding
    engine-side one is a fabricated hop — an error under strict, else
    counted.  An engine-side handoff with no router half is a PARKED
    request the pump has not collected yet (legal at dump time) and
    only counts in ``handoff_unmatched``.  Summary counts let callers
    assert content (e.g. per-request span count, cross-replica flow
    count) without re-walking."""
    if strict_flows is None:
        strict_flows = bool(doc.get("otherData", {}).get("sources"))
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        raise ValueError("traceEvents must be a non-empty list")
    last_ts = None
    open_spans: Dict[tuple, int] = {}
    flow_started: Dict[Any, int] = {}      # flow id -> finish count
    handoff_parked: Dict[Any, int] = {}    # uid -> unconsumed engine half
    summary = {"events": len(events), "complete": 0, "instant": 0,
               "metadata": 0, "request_spans": 0, "flow_starts": 0,
               "flow_ends": 0, "flow_unmatched": 0, "handoffs": 0,
               "handoff_unmatched": 0}
    orphan_ends = 0
    orphan_handoffs = 0
    for i, e in enumerate(events):
        for field in ("name", "ph", "ts", "pid", "tid"):
            if field not in e:
                raise ValueError(f"event {i} ({e.get('name')!r}) is "
                                 f"missing {field!r}")
        ph = e["ph"]
        if ph not in ("M", "i", "X", "B", "E", "s", "f"):
            raise ValueError(f"event {i} has unknown phase {ph!r}")
        if ph == "M":
            summary["metadata"] += 1
            continue
        if last_ts is not None and e["ts"] < last_ts:
            raise ValueError(
                f"event {i} ts {e['ts']} < previous {last_ts} — export "
                "must be sorted")
        last_ts = e["ts"]
        if ph == "X":
            if e.get("dur", -1) < 0:
                raise ValueError(
                    f"complete event {i} ({e['name']!r}) lacks a "
                    "non-negative dur")
            summary["complete"] += 1
            if str(e["name"]).startswith("req "):
                summary["request_spans"] += 1
        elif ph == "i":
            summary["instant"] += 1
            if e["name"] == "handoff":
                args = e.get("args", {})
                uid = args.get("uid")
                if "src" in args or "dst" in args:     # router pump half
                    summary["handoffs"] += 1
                    if handoff_parked.get(uid, 0) > 0:
                        handoff_parked[uid] -= 1
                    elif strict_flows:
                        raise ValueError(
                            f"event {i}: router handoff for uid {uid!r} "
                            "without a preceding engine-side handoff — "
                            "a request was routed off a prefill replica "
                            "that never parked it")
                    else:
                        orphan_handoffs += 1
                else:                                  # engine park half
                    handoff_parked[uid] = handoff_parked.get(uid, 0) + 1
        elif ph == "B":
            key = (e["pid"], e["tid"])
            open_spans[key] = open_spans.get(key, 0) + 1
        elif ph == "E":
            key = (e["pid"], e["tid"])
            if not open_spans.get(key):
                raise ValueError(
                    f"event {i}: E without a matching B on lane {key}")
            open_spans[key] -= 1
        elif ph in ("s", "f"):
            if "id" not in e:
                raise ValueError(
                    f"flow event {i} ({e['name']!r}) is missing 'id'")
            if ph == "s":
                flow_started.setdefault(e["id"], 0)
                summary["flow_starts"] += 1
            else:
                if e["id"] not in flow_started:
                    if strict_flows:
                        raise ValueError(
                            f"event {i}: flow finish 'f' (id {e['id']!r}) "
                            "without a preceding flow start 's'")
                    orphan_ends += 1
                else:
                    flow_started[e["id"]] += 1
                summary["flow_ends"] += 1
    dangling = {k: v for k, v in open_spans.items() if v}
    if dangling:
        raise ValueError(f"unclosed B spans on lanes {dangling}")
    unfinished = [fid for fid, ends in flow_started.items() if not ends]
    if unfinished and strict_flows:
        raise ValueError(f"flow start(s) without a finish: {unfinished}")
    summary["flow_unmatched"] = orphan_ends + len(unfinished)
    # engine-side handoffs never pumped are legitimately parked (tolerated
    # even under strict — a dump can land mid-park), but they are visible:
    summary["handoff_unmatched"] = orphan_handoffs + \
        sum(handoff_parked.values())
    return summary


def _nest(spans: List[Dict[str, Any]]) -> None:
    """Give each span (``t0`` / ``t1``, seconds) its ``parent``: the
    innermost other span that holds its midpoint — spans of one thread nest
    by time, and a midpoint forgives the clock conversion of the events JAX
    timed (``analysis/sentry.py``) a millisecond at either end."""
    open_: List[Dict[str, Any]] = []
    for e in sorted(spans, key=lambda e: (e["t0"], -e["t1"])):
        mid = (e["t0"] + e["t1"]) / 2
        while open_ and not open_[-1]["t0"] <= mid <= open_[-1]["t1"]:
            open_.pop()
        e["parent"] = open_[-1] if open_ else None
        open_.append(e)


def setup_summary() -> Optional[Dict[str, Any]]:
    """The start-up ring in numbers an operator reads without Perfetto
    (``ServingEngine.stats()["setup"]``, ``DeepSpeedEngine.setup_report()``;
    None before anything made the ring):

    ``phases``      seconds by top-level span (``import``, ``init_serving``,
                    ``initialize``, ``build`` — the programs' first calls
                    after their engine was made)
    ``within``      each top-level span other than ``build`` split into the
                    spans directly inside it by name, ``jit`` (functions
                    JAX built directly inside it, below) and ``self``: the
                    parts sum to the phase
    ``programs``    by registered program: ``trace_s`` / ``lower_s`` /
                    ``compile_s`` of the events its ``build`` span holds,
                    ``cache`` (``hit`` | ``miss`` | ``off``),
                    ``retrieval_s`` of a hit, ``first_run_s`` (the span less
                    those events: the load and the first execution) and
                    ``build_s``, the span
    ``other_jit``   what JAX spent building functions that are NOT a
                    registered program (eager operations, initialisers,
                    casts, a driver's own jits), by the innermost span they
                    fell in (``(outside)``: in none): ``seconds``,
                    ``functions`` and the three ``slowest``
    ``cache``       ``hits`` / ``misses`` / ``off`` over every ``compile``
                    event, ``retrieval_s``, and ``missed``: the functions
                    that missed.  ``warm`` says something hit: a start that
                    is warm AND misses has a cache key that does not hold
                    from one start to the next, and ``missed`` names it
    ``events`` / ``dropped``   the ring's fill and what fell off it

    The ring holds only the OUTERMOST event of each build (``jnp``
    functions called while a program is traced are themselves traced; the
    listener drops them), so every JAX event's parent here is a span.
    Computed again only when the ring has grown."""
    timeline = _KEPT.get("setup")
    if timeline is None:
        return None
    if timeline.summarized[0] == timeline.emitted:
        return timeline.summarized[1]
    emitted = timeline.emitted
    events = [{**e, "t0": e["ts"] * 1e-6,
               "t1": (e["ts"] + e["dur"]) * 1e-6,
               "args": e.get("args", {})}
              for e in timeline.events() if e["ph"] == "X"]
    _nest(events)
    spans = [e for e in events if e["name"] not in BUILD_PHASES]
    built = [e for e in events if e["name"] in BUILD_PHASES]

    def seconds(e):
        return e["t1"] - e["t0"]

    phases: Dict[str, float] = {}
    within: Dict[str, Dict[str, float]] = {}
    for top in (e for e in spans if e["parent"] is None):
        phases[top["name"]] = phases.get(top["name"], 0.0) + seconds(top)
        if top["name"] == "build":
            continue
        parts = within.setdefault(top["name"], {"jit": 0.0, "self": 0.0})
        inside = 0.0
        for e in spans:
            if e["parent"] is top:
                parts[e["name"]] = parts.get(e["name"], 0.0) + seconds(e)
                inside += seconds(e)
        jit = sum(seconds(e) for e in built if e["parent"] is top)
        parts["jit"] += jit
        parts["self"] += seconds(top) - inside - jit
    programs: Dict[str, Dict[str, Any]] = {}
    for span in (e for e in spans if e["name"] == "build"):
        name = span["args"].get("program", "?")
        row = programs.setdefault(name, {
            "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache": "off", "first_run_s": 0.0, "build_s": 0.0})
        mine = [e for e in built if e["parent"] is span]
        for e in mine:
            row[e["name"] + "_s"] += seconds(e)
            if e["name"] == "compile":
                row["cache"] = e["args"].get("cache", "off")
                if "retrieval_s" in e["args"]:
                    row["retrieval_s"] = row.get("retrieval_s", 0.0) \
                        + e["args"]["retrieval_s"]
        row["build_s"] += seconds(span)
        row["first_run_s"] += seconds(span) - sum(seconds(e) for e in mine)
    other: Dict[str, Dict[str, Any]] = {}
    for e in built:
        at = e["parent"]
        if at is not None and at["name"] == "build":
            continue
        row = other.setdefault(at["name"] if at else "(outside)",
                               {"seconds": 0.0, "by_fn": {}})
        row["seconds"] += seconds(e)
        fn = e["args"].get("fn", "?")
        row["by_fn"][fn] = row["by_fn"].get(fn, 0.0) + seconds(e)
    for row in other.values():
        by_fn = row.pop("by_fn")
        row["functions"] = len(by_fn)
        row["slowest"] = sorted(by_fn.items(), key=lambda kv: -kv[1])[:3]
    compiles = [e for e in built if e["name"] == "compile"]
    how = [e["args"].get("cache", "off") for e in compiles]
    cache = {"hits": how.count("hit"), "misses": how.count("miss"),
             "off": how.count("off"),
             "retrieval_s": sum(e["args"].get("retrieval_s", 0.0)
                                for e in compiles),
             "missed": sorted({e["args"].get("fn", "?") for e in compiles
                               if e["args"].get("cache") == "miss"})}
    cache["warm"] = cache["hits"] > 0
    summary = {"phases": phases, "within": within, "programs": programs,
               "other_jit": other, "cache": cache,
               "events": len(timeline), "dropped": timeline.dropped}
    timeline.summarized = (emitted, summary)
    return summary


def setup_line(summary: Optional[Dict[str, Any]] = None) -> str:
    """:func:`setup_summary` as the ONE line an engine logs once its
    programs have run (seconds, one decimal past the millisecond)."""
    summary = summary or setup_summary() or {}

    def s(x):
        return f"{x:.3f}"

    parts = [f"{name} {s(sec)} s" + (" (" + ", ".join(
        f"{k} {s(v)}" for k, v in summary["within"][name].items()) + ")"
        if name in summary.get("within", {}) else "")
        for name, sec in summary.get("phases", {}).items()
        if name != "build"]
    for name, row in summary.get("programs", {}).items():
        parts.append(
            f"{name}: trace {s(row['trace_s'])} + lower "
            f"{s(row['lower_s'])} + compile {s(row['compile_s'])} "
            f"(cache {row['cache']}) + first run {s(row['first_run_s'])}")
    jit = summary.get("other_jit", {})
    if jit:
        parts.append("other functions built: " + ", ".join(
            f"{where} {s(row['seconds'])} s in {row['functions']}"
            for where, row in jit.items()))
    cache = summary.get("cache", {})
    if cache:
        parts.append(f"compile cache {cache['hits']} hits, "
                     f"{cache['misses']} misses, {cache['off']} uncached")
        if cache["warm"] and cache["missed"]:
            parts.append("MISSED on a warm start (a cache key that does "
                         "not hold): " + ", ".join(cache["missed"][:8]))
    return "start-up: " + "; ".join(parts)


class GcWatch:
    """The collector's pauses on a timeline's clock: seconds and count of
    the runs that began while :attr:`thread` named the calling thread.

    ``gc.callbacks`` is the PROCESS's list, and a collection runs on
    whichever thread's allocation tripped it, so the hook credits a run
    only to the owner whose thread it interrupted (an engine sets
    ``thread`` for the length of a ``step``): replicas stepping in worker
    threads do not take each other's pauses.  A run over :attr:`EVENT_S`
    also goes on the ring as a ``gc`` X-event (args ``generation``,
    ``collected``).  The hook references this object and the timeline only
    — never the engine, which must stay collectable to ``close()`` it."""

    #: a run at least this long is a ring event of its own
    EVENT_S = 1e-3

    def __init__(self, timeline: "TraceTimeline"):
        self.timeline = timeline
        self.thread: Optional[int] = None
        self.seconds = 0.0
        self.runs = 0
        self._start_us: Optional[float] = None

    def install(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def remove(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if self.thread != threading.get_ident():
            return
        if phase == "start":
            self._start_us = self.timeline.now_us()
        elif self._start_us is not None:
            start, self._start_us = self._start_us, None
            dur_s = (self.timeline.now_us() - start) * 1e-6
            self.seconds += dur_s
            self.runs += 1
            if dur_s >= self.EVENT_S:
                self.timeline.complete(
                    "gc", start, generation=info.get("generation"),
                    collected=info.get("collected"))


class ProfilerWindow:
    """Idempotent ``jax.profiler`` bracket around N engine iterations.

    ``start()`` begins a device/XLA trace into ``profile_dir`` (TensorBoard
    ``trace_viewer`` / Perfetto format), ``stop()`` ends it; both degrade
    to logged warnings when the profiler is unavailable or already active
    (e.g. nested windows) — profiling must never fail the serving loop.
    """

    def __init__(self, profile_dir: str):
        self.profile_dir = str(profile_dir)
        self.active = False

    def start(self) -> bool:
        if self.active:
            return True
        try:
            # no Python frames: with them a 50-iteration window is huge
            # and slows the very host path it is there to show
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.profile_dir,
                                     profiler_options=options)
            self.active = True
        except Exception as e:  # unavailable backend / nested trace
            logger.warning(f"jax.profiler window not started: {e}")
        return self.active

    def stop(self) -> None:
        if not self.active:
            return
        self.active = False
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            logger.warning(f"jax.profiler window not stopped cleanly: {e}")
