"""Black-box flight recorder: incident bundles, deterministic replay,
and a stall watchdog for the serving fleet.

PRs 8/12 made the fleet observable in steady state and PR 15 made
failure a replayable *input* (seeded FaultPlans); this module makes
failure a replayable *output*.  An :class:`IncidentRecorder` rides the
:class:`~deepspeed_tpu.serving.router.ReplicaRouter` (``recorder.attach
(router)`` installs it as ``router._incident`` — ``None`` costs one
attribute test per hook site, the ``faults.py`` zero-cost-disarmed
idiom) and, on a trigger, atomically dumps a self-contained **incident
bundle** directory an engineer can attach to a postmortem — or feed to
``bin/graft-replay`` to re-execute the failure bit-for-bit.

Triggers (the ``trigger.kind`` vocabulary, pinned by
``tests/unit/test_incident.py``):

 - ``replica_fail`` — ``router.fail(rid)`` ran its crash protocol
   (worker thread death, :class:`SimulatedCrash`, supervisor hard-death)
 - ``invariant_violation`` — a paged-state audit raised
   (``analysis/invariants.py PagedStateError``)
 - ``retrace`` — the compile sentry raised
   (``analysis/sentry.py RetraceError``)
 - ``checksum_burst`` — ≥ ``checksum_burst`` swap-checksum failures
   inside ``checksum_window_s`` across the fleet (polled per step)
 - ``burn_rate_breach`` — a class's **windowed** error-budget burn
   (``telemetry/slo.py merged_windowed_burn``) crossed
   ``burn_threshold`` with at least ``burn_min_requests`` in the window
 - ``watchdog_stall`` — the :class:`StallWatchdog` saw outstanding
   handles age past its deadline with zero fleet progress

Bundle layout (``manifest.json`` is written LAST inside a hidden temp
directory that is ``os.replace``d into place — a crash mid-dump can
never leave a directory that :func:`is_bundle` mistakes for a bundle):

 - ``manifest.json`` — trigger, wall/step clocks, seeds, git describe,
   schema version, file list, model meta, router config
 - ``trace_merged.json`` — merged Chrome trace over every ring
 - ``metrics.prom`` / ``metrics.json`` — federated fleet registry
 - ``router_stats.json`` / ``replica_stats.json`` /
   ``replica_configs.json`` / ``slo_report.json`` /
   ``slo_windowed.json`` / ``replica_slo.json``
 - ``paged_state.json`` — per-replica allocator/host-tier summaries
 - ``fault_plan.json`` + ``fault_report.json`` — if chaos is armed
 - ``request_trace.json`` — the chained TraceRecorder's verbatim
   request stream up to the trigger (the replay input)
 - ``progress.json`` — per-handle status + streamed tokens at the
   trigger (the replay *expected output*)
 - ``recovery.json`` — worker errors, failed/drained sets, and the
   salvage/re-home/request-failed timeline slice
 - ``threads.txt`` — every Python thread's stack (stall trigger)

Crash-path dumps gather under ``router._all_locks()`` (every lock is
reentrant, and the trigger hook sites hold none) for a point-in-time
snapshot; the stall path must assume a wedged worker is *holding* a
replica lock, so it gathers lockless and best-effort — every section
failure is recorded in ``manifest.json gather_errors`` instead of
raised (evidence collection must never finish the job a deadlock
started).

Replay (:func:`replay_bundle` / ``bin/graft-replay``) rebuilds the
fleet from ``replica_configs.json`` + ``router_config`` through the
ordinary ``init_serving``/``ReplicaRouter``/``submit``/``step`` path,
re-arms the recorded FaultPlan, replays ``request_trace.json``, and
asserts the trigger re-fires at the same per-replica scheduler
iteration with a token-exact pre-incident stream (deterministic
single-thread stepping; bundles recorded from ``threaded`` fleets
compare with ``prefix_match=True``).

Everything here is host-side stdlib (zero jax at import, like
``telemetry/server.py``); replay imports the engine stack lazily.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import traceback
from collections import OrderedDict, deque
from datetime import datetime, timezone
from typing import Any, Dict, List, Optional

from ..utils.logging import logger
from .aggregate import federate, merge_chrome_traces
from .slo import merged_slo_report, merged_windowed_burn

__all__ = ["IncidentRecorder", "StallWatchdog", "BUNDLE_SCHEMA_VERSION",
           "MANIFEST_KEYS", "TRIGGER_KINDS", "is_bundle", "load_bundle",
           "replay_bundle", "gpt2_model_meta", "format_thread_stacks"]

#: 2: ``replica_configs`` hold the 29 options ``ServingEngine`` takes —
#: a version-1 bundle's configs carry three it no longer does, so it is
#: refused by version rather than having keys stripped
BUNDLE_SCHEMA_VERSION = 2
BUNDLE_FORMAT = "graft-incident"

TRIGGER_KINDS = ("replica_fail", "invariant_violation", "retrace",
                 "checksum_burst", "burn_rate_breach", "watchdog_stall")

#: manifest.json key set — pinned by tests/unit/test_schema_stability.py
MANIFEST_KEYS = frozenset({
    "schema_version", "bundle_format", "trigger", "wall_time_s",
    "wall_time_iso", "step_clocks", "seeds", "git_describe", "files",
    "replicas", "model", "router_config", "replayable", "gather_errors",
})

#: trigger kinds whose failure is a deterministic function of (configs,
#: request trace, fault plan) — the ones ``graft-replay`` can re-fire
_REPLAYABLE_KINDS = frozenset({"replica_fail", "invariant_violation",
                               "retrace"})


# --------------------------------------------------------------- helpers
def _classify_exc(exc: Optional[BaseException]) -> str:
    """Trigger kind from the exception class NAME — string-matched so
    this module stays import-light (no serving/analysis imports at the
    hook sites)."""
    name = type(exc).__name__ if exc is not None else ""
    if name == "PagedStateError":
        return "invariant_violation"
    if name == "RetraceError":
        return "retrace"
    return "replica_fail"


def _git_describe() -> str:
    try:
        out = subprocess.run(
            ["git", "describe", "--always", "--dirty", "--tags"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or "unknown"
    except Exception as e:  # no git / not a checkout — evidence, not fatal
        logger.warning(f"git describe unavailable for manifest: {e}")
        return "unknown"


def _jsonable(obj: Any) -> Any:
    """Best-effort JSON coercion (numpy scalars, sets, exceptions)."""
    try:
        json.dumps(obj)
        return obj
    except TypeError:  # graft: noqa(GL013) predicate: "is it already JSON?" — fall through to coercion
        pass
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set, frozenset)):
        return [_jsonable(v) for v in obj]
    for caster in (int, float):
        try:
            return caster(obj)
        except (TypeError, ValueError):  # graft: noqa(GL013) predicate: try the next coercion
            continue
    return repr(obj)


def format_thread_stacks() -> str:
    """Every live Python thread's stack, one ``--- thread`` section each
    (``sys._current_frames`` — the watchdog's core evidence: *where* is
    the wedged worker sleeping?)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    lines: List[str] = []
    for tid, frame in sorted(sys._current_frames().items()):
        lines.append(f"--- thread {names.get(tid, '?')} (ident={tid}) ---")
        lines.extend(ln.rstrip("\n")
                     for ln in traceback.format_stack(frame))
        lines.append("")
    return "\n".join(lines)


def gpt2_model_meta(cfg, dtype: str = "fp32",
                    tp_size: int = 1) -> Dict[str, Any]:
    """Manifest ``model`` entry for a :mod:`deepspeed_tpu.models.gpt2`
    config — enough for :func:`replay_bundle` to rebuild the model with
    ``gpt2.build(GPT2Config(**config))`` (``gpt2.build`` is
    deterministic, so rebuilt params are bit-identical)."""
    import dataclasses

    return {"family": "gpt2", "config": dataclasses.asdict(cfg),
            "dtype": str(dtype), "tp_size": int(tp_size)}


# ------------------------------------------------------------- recorder
class IncidentRecorder:
    """The flight recorder (module docstring).

    Parameters
    ----------
    out_dir:    bundles land here as ``incident-<seq>-<kind>/``.
    vocab:      token-id range of the served traffic; enables the
                chained request-stream capture (``autotuning/trace.py
                TraceRecorder``) replay needs.  ``None`` = no capture
                (bundles still dump, marked ``replayable: false``).
    model_meta: manifest ``model`` entry (:func:`gpt2_model_meta`) so
                ``graft-replay`` can rebuild the fleet without the
                original process.
    checksum_burst / checksum_window_s:
                fleet-wide swap-checksum failures within the window
                that trip a ``checksum_burst`` dump.
    burn_threshold / burn_window_s / burn_min_requests:
                windowed burn-rate breach trigger (any class, either
                latency dimension); ``None`` threshold disables it.
    cooldown_s / max_bundles:
                dump rate limits — one incident storm must not fill
                the disk with near-identical bundles.
    poll_min_s: minimum spacing of the per-step trigger poll.
    """

    def __init__(self, out_dir: str, *, vocab: Optional[int] = None,
                 model_meta: Optional[Dict[str, Any]] = None,
                 checksum_burst: int = 8, checksum_window_s: float = 2.0,
                 burn_threshold: Optional[float] = None,
                 burn_window_s: float = 10.0, burn_min_requests: int = 4,
                 cooldown_s: float = 30.0, max_bundles: int = 4,
                 poll_min_s: float = 0.02, clock=None):
        self.out_dir = str(out_dir)
        self.vocab = None if vocab is None else int(vocab)
        self.model_meta = model_meta
        self.checksum_burst = int(checksum_burst)
        self.checksum_window_s = float(checksum_window_s)
        self.burn_threshold = None if burn_threshold is None \
            else float(burn_threshold)
        self.burn_window_s = float(burn_window_s)
        self.burn_min_requests = int(burn_min_requests)
        self.cooldown_s = float(cooldown_s)
        self.max_bundles = int(max_bundles)
        self.poll_min_s = float(poll_min_s)
        self._clock = clock or time.monotonic
        self._lock = threading.Lock()
        self._seq = 0
        self._cooldown_until = -float("inf")
        self._last_poll = -float("inf")
        #: (monotonic t, fleet checksum-failure total) ring for the
        #: burst window
        self._ck_hist: deque = deque()
        self.bundles: List[str] = []
        self._recorder = None               # chained TraceRecorder
        self._router = None
        self._c_bundles = None
        os.makedirs(self.out_dir, exist_ok=True)

    # ------------------------------------------------------------ wiring
    def attach(self, router) -> "IncidentRecorder":
        """Install on a router: hook sites see ``router._incident``,
        submits stream into a chained TraceRecorder (the incumbent
        observer, if any, keeps firing first), and the dump counter
        registers on the router registry."""
        if getattr(router, "_incident", "missing") == "missing":
            raise TypeError(
                f"{type(router).__name__} has no _incident hook — "
                "expected a ReplicaRouter")
        if router._incident is not None and router._incident is not self:
            raise RuntimeError("router already has an incident recorder "
                               "attached — detach it first")
        self._router = router
        if self.vocab is not None and self._recorder is None:
            from ..autotuning.trace import TraceRecorder

            self._recorder = TraceRecorder(self.vocab)
            self._recorder.attach(router, chain=True)
        self._c_bundles = router.metrics.counter(
            "serving_incident_bundles_total",
            "incident bundles dumped by the flight recorder")
        router._incident = self
        return self

    def detach(self) -> None:
        router, self._router = self._router, None
        if router is not None and \
                getattr(router, "_incident", None) is self:
            router._incident = None
        if self._recorder is not None:
            self._recorder.detach()
            self._recorder = None

    # ------------------------------------------------------- hook sites
    def on_replica_fail(self, router, rid: int,
                        exc: Optional[BaseException]) -> Optional[str]:
        """``router.fail(rid)`` completed its crash protocol (called
        outside every lock)."""
        return self.dump(router, _classify_exc(exc), replica=rid,
                         exc=exc)

    def on_engine_error(self, router, rid: Optional[int],
                        exc: BaseException) -> Optional[str]:
        """A deterministic ``router.step()`` is about to re-raise an
        engine/audit exception — dump first, evidence intact."""
        return self.dump(router, _classify_exc(exc), replica=rid,
                         exc=exc)

    def on_stall(self, router, detail: Dict[str, Any],
                 stacks: str) -> Optional[str]:
        """The :class:`StallWatchdog` detected no-progress: lockless
        gather — a wedged worker may hold a replica lock."""
        return self.dump(router, "watchdog_stall", detail=detail,
                         stacks=stacks, lockless=True)

    def on_step_poll(self, router) -> None:
        """Rate-limited per-step trigger poll: checksum bursts and
        windowed burn-rate breaches."""
        now = self._clock()
        if now - self._last_poll < self.poll_min_s:
            return
        self._last_poll = now
        total = 0.0
        for rep in router.replicas:
            cell = getattr(rep, "_c_checksum_fail", None)
            if cell is not None:
                total += cell.value
        hist = self._ck_hist
        hist.append((now, total))
        while hist and now - hist[0][0] > self.checksum_window_s:
            hist.popleft()
        burst = total - hist[0][1]
        if burst >= self.checksum_burst:
            self.dump(router, "checksum_burst",
                      detail={"failures_in_window": int(burst),
                              "window_s": self.checksum_window_s,
                              "threshold": self.checksum_burst})
            hist.clear()
            return
        if self.burn_threshold is None:
            return
        trackers = [rep._slo for rep in router.replicas
                    if getattr(rep, "_slo", None) is not None]
        if not trackers:
            return
        for cls, entry in merged_windowed_burn(
                trackers, window_s=self.burn_window_s).items():
            if entry["requests"] < self.burn_min_requests:
                continue
            for dim in ("ttft", "tpot"):
                burn = entry[f"{dim}_burn_rate"]
                if burn > self.burn_threshold:
                    self.dump(router, "burn_rate_breach",
                              detail={"slo_class": cls, "dim": dim,
                                      "burn_rate": burn,
                                      "requests": entry["requests"],
                                      "window_s": self.burn_window_s,
                                      "threshold": self.burn_threshold})
                    return

    # --------------------------------------------------------- dumping
    def dump(self, router, kind: str, *, replica: Optional[int] = None,
             exc: Optional[BaseException] = None,
             detail: Optional[Dict[str, Any]] = None,
             stacks: Optional[str] = None,
             lockless: bool = False) -> Optional[str]:
        """Dump one bundle (rate-limited); returns its path or ``None``
        when suppressed/failed.  Never raises — the recorder must not
        take down the serving loop it is documenting."""
        if kind not in TRIGGER_KINDS:
            raise ValueError(f"unknown trigger kind {kind!r} — expected "
                             f"one of {TRIGGER_KINDS}")
        with self._lock:
            now = self._clock()
            if now < self._cooldown_until:
                return None
            if len(self.bundles) >= self.max_bundles:
                return None
            self._cooldown_until = now + self.cooldown_s
            self._seq += 1
            seq = self._seq
        try:
            path = self._dump(router, kind, seq, replica, exc, detail,
                              stacks, lockless)
        except Exception as e:      # noqa: BLE001 — recorder must not kill
            logger.error(f"incident dump ({kind}) failed: {e!r}")
            return None
        self.bundles.append(path)
        if self._c_bundles is not None:
            self._c_bundles.inc()
        try:
            router.timeline.instant("incident_dump", kind=kind,
                                    bundle=os.path.basename(path))
        except Exception as e:      # noqa: BLE001 — recorder must not kill
            logger.warning(f"incident_dump timeline emit failed: {e!r}")
        logger.error(f"incident bundle dumped ({kind}): {path}")
        return path

    def _dump(self, router, kind, seq, replica, exc, detail, stacks,
              lockless) -> str:
        name = f"incident-{seq:03d}-{kind}"
        tmp = os.path.join(self.out_dir,
                           f".{name}.tmp-{os.getpid()}")
        final = os.path.join(self.out_dir, name)
        os.makedirs(tmp)
        if lockless:
            data, errors = self._gather(router)
        else:
            with router._all_locks():
                data, errors = self._gather(router)
        files: List[str] = []
        for fname, payload in data.items():
            fpath = os.path.join(tmp, fname)
            try:
                if fname.endswith(".json"):
                    with open(fpath, "w") as f:
                        json.dump(_jsonable(payload), f, indent=1)
                else:
                    with open(fpath, "w") as f:
                        f.write(payload)
                files.append(fname)
            except Exception as e:  # noqa: BLE001 — partial beats none
                errors[fname] = f"{type(e).__name__}: {e}"
        if stacks is not None:
            with open(os.path.join(tmp, "threads.txt"), "w") as f:
                f.write(stacks)
            files.append("threads.txt")
        step = getattr(exc, "step", None)
        if step is None and replica is not None:
            try:
                step = int(router.replicas[replica].iterations)
            except Exception:  # graft: noqa(GL013) duck-typed fakes lack the clock
                step = None
        plan = getattr(getattr(router, "_injector", None), "plan", None)
        manifest = {
            "schema_version": BUNDLE_SCHEMA_VERSION,
            "bundle_format": BUNDLE_FORMAT,
            "trigger": {
                "kind": kind,
                "replica": None if replica is None else int(replica),
                "step": step,
                "exception_type": type(exc).__name__
                if exc is not None else None,
                "exception": repr(exc) if exc is not None else None,
                "detail": _jsonable(detail) if detail else None,
            },
            "wall_time_s": time.time(),
            "wall_time_iso": datetime.now(timezone.utc).isoformat(),
            "step_clocks": self._step_clocks(router),
            "seeds": {"fault_plan":
                      None if plan is None else int(plan.seed)},
            "git_describe": _git_describe(),
            "files": sorted(files + ["manifest.json"]),
            "replicas": len(router.replicas),
            "model": self.model_meta,
            "router_config": self._router_config(router, errors),
            "replayable": kind in _REPLAYABLE_KINDS and
            "request_trace.json" in files and
            "replica_configs.json" in files,
            "gather_errors": errors,
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(_jsonable(manifest), f, indent=1)
        os.replace(tmp, final)
        return final

    @staticmethod
    def _step_clocks(router) -> Dict[str, Optional[int]]:
        clocks: Dict[str, Optional[int]] = {}
        for i, rep in enumerate(router.replicas):
            try:
                clocks[str(i)] = int(rep.iterations)
            except Exception:  # graft: noqa(GL013) duck-typed fakes lack the clock
                clocks[str(i)] = None
        return clocks

    @staticmethod
    def _router_config(router, errors) -> Dict[str, Any]:
        try:
            return router.resolved_config()
        except Exception as e:  # noqa: BLE001 — partial beats none
            errors["router_config"] = f"{type(e).__name__}: {e}"
            return {}

    def _gather(self, router):
        """Evidence collection, one guarded section per file — a failed
        section lands in ``gather_errors`` instead of killing the dump
        (the stall path runs this against a possibly-wedged fleet)."""
        data: "OrderedDict[str, Any]" = OrderedDict()
        errors: Dict[str, str] = {}

        def sec(fname, fn):
            try:
                data[fname] = fn()
            except Exception as e:  # noqa: BLE001 — partial beats none
                errors[fname] = f"{type(e).__name__}: {e}"

        # progress FIRST: the replay-exactness contract compares against
        # the handle map exactly as the trigger hook saw it
        sec("progress.json", lambda: self._progress(router))
        sec("request_trace.json", lambda: self._request_trace())
        sec("replica_configs.json",
            lambda: [rep.resolved_config() for rep in router.replicas])
        sec("trace_merged.json", lambda: merge_chrome_traces(
            [("router", router.timeline)] +
            [(f"replica {i}", rep.timeline)
             for i, rep in enumerate(router.replicas)]))
        reg = None

        def fed():
            nonlocal reg
            sources = OrderedDict([("router", router.metrics)])
            for i, rep in enumerate(router.replicas):
                sources[str(i)] = rep.metrics
            reg = federate(sources)
            return reg.prometheus_text()

        sec("metrics.prom", fed)
        sec("metrics.json",
            lambda: reg.snapshot() if reg is not None else {})
        sec("router_stats.json", router.stats)
        sec("slo_report.json", lambda: merged_slo_report(
            [rep._slo for rep in router.replicas
             if getattr(rep, "_slo", None) is not None]))
        sec("slo_windowed.json", lambda: merged_windowed_burn(
            [rep._slo for rep in router.replicas
             if getattr(rep, "_slo", None) is not None],
            window_s=self.burn_window_s))
        sec("replica_stats.json", lambda: [rep.stats()
                                           for rep in router.replicas])
        sec("replica_slo.json", lambda: [rep.slo_report()
                                         for rep in router.replicas])
        sec("paged_state.json", lambda: [self._paged_summary(rep)
                                         for rep in router.replicas])
        injector = getattr(router, "_injector", None)
        if injector is not None:
            sec("fault_plan.json", injector.plan.to_json)
            sec("fault_report.json", injector.report)
        sec("recovery.json", lambda: self._recovery(router))
        return data, errors

    @staticmethod
    def _progress(router) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        for uid, (handle, rid) in list(router._handles.items()):
            out[str(uid)] = {
                "status": handle.status,
                "replica": int(rid),
                "tokens": [int(t) for t in handle._tokens],
            }
        return out

    def _request_trace(self) -> Dict[str, Any]:
        if self._recorder is None:
            raise RuntimeError("no request capture (vocab=None)")
        return self._recorder.trace(
            meta={"source": "incident_recorder"}).to_dict()

    @staticmethod
    def _paged_summary(rep) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        try:
            ref, free = rep._alloc.snapshot()
            out["device"] = {"blocks": len(ref), "free": len(free),
                             "in_use": int(rep._alloc.blocks_in_use)}
        except Exception as e:  # noqa: BLE001 — partial beats none
            out["device_error"] = f"{type(e).__name__}: {e}"
        host = getattr(rep, "_host", None)
        if host is not None:
            try:
                hfree, table = host.snapshot()
                out["host"] = {"free": len(hfree),
                               "entries": len(table)}
            except Exception as e:  # noqa: BLE001 — partial beats none
                out["host_error"] = f"{type(e).__name__}: {e}"
        return out

    @staticmethod
    def _recovery(router) -> Dict[str, Any]:
        keep = {"replica_fail", "rehome", "request_failed", "drain",
                "readmit", "shed", "incident_dump", "watchdog_stall"}
        return {
            "worker_errors": {str(r): repr(e) for r, e in
                              router._worker_errors.items()},
            "failed": sorted(router._failed),
            "drained": sorted(router._drained),
            "events": [ev for ev in router.timeline.events()
                       if ev.get("name") in keep],
        }


# ------------------------------------------------------------- watchdog
class StallWatchdog:
    """No-progress detector for a serving fleet (stdlib thread, zero
    deps — the ``telemetry/server.py`` daemon-thread idiom).

    Progress signal = (streamed-token totals, resolved-handle count,
    per-replica ``iterations``) — an idle engine's no-op poll does NOT
    advance ``iterations`` (it early-returns before the counter), so
    iteration movement is real work, never a spinning heartbeat.  A
    stall fires when outstanding handles exist, the OLDEST has been
    outstanding past ``deadline_s``, and the progress signal has been
    frozen for ``deadline_s`` — then once per episode (re-arming on the
    next progress): ``serving_watchdog_stalls_total`` ticks, a
    ``watchdog_stall`` instant lands on the router timeline, every
    thread's stack is captured, and the recorder (if any) dumps a
    lockless bundle with ``threads.txt``.

    ``check()`` is the whole detector and runs fine without the thread
    (deterministic tests drive it directly with an injected clock).
    """

    def __init__(self, router, *, deadline_s: float = 30.0,
                 poll_s: float = 1.0,
                 recorder: Optional[IncidentRecorder] = None,
                 clock=None):
        self.router = router
        self.deadline_s = float(deadline_s)
        self.poll_s = float(poll_s)
        self.recorder = recorder
        self._clock = clock or time.monotonic
        self._c_stalls = router.metrics.counter(
            "serving_watchdog_stalls_total",
            "no-progress stalls detected by the watchdog (outstanding "
            "handles aged past the deadline with a frozen fleet)")
        self._lock = threading.Lock()
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_sig: Any = None
        self._last_progress_t = self._clock()
        self._first_seen: Dict[Any, float] = {}
        self._stalled = False
        self.stalls = 0

    # ----------------------------------------------------------- thread
    def start(self) -> "StallWatchdog":
        if self._thread is not None:
            return self
        self._stop_evt.clear()
        self._thread = threading.Thread(target=self._run,
                                        name="serving-stall-watchdog",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop_evt.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)

    def _run(self) -> None:
        while not self._stop_evt.wait(self.poll_s):
            try:
                self.check()
            except Exception as e:  # noqa: BLE001 — watchdog must not die
                logger.warning(f"stall watchdog check failed: {e!r}")

    # --------------------------------------------------------- detector
    def check(self) -> bool:
        """One detection pass; returns whether a stall fired NOW."""
        with self._lock:
            return self._check_locked()

    def _check_locked(self) -> bool:
        router = self.router
        now = self._clock()
        items = list(router._handles.items())
        outstanding = [(uid, h) for uid, (h, _rid) in items
                       if h.status in ("queued", "active")]
        resolved = len(items) - len(outstanding)
        iters = {}
        for i, rep in enumerate(router.replicas):
            try:
                iters[i] = int(rep.iterations)
            except Exception:  # graft: noqa(GL013) duck-typed fakes lack the clock
                iters[i] = -1
        streamed = sum(len(h._tokens) for _uid, h in outstanding)
        sig = (streamed, resolved, tuple(sorted(iters.items())))
        if sig != self._last_sig:
            self._last_sig = sig
            self._last_progress_t = now
            self._stalled = False
        live = {uid for uid, _h in outstanding}
        self._first_seen = {u: t for u, t in self._first_seen.items()
                            if u in live}
        for uid, _h in outstanding:
            self._first_seen.setdefault(uid, now)
        if not outstanding:
            self._stalled = False
            return False
        oldest_age = now - min(self._first_seen.values())
        frozen_for = now - self._last_progress_t
        if self._stalled or oldest_age <= self.deadline_s or \
                frozen_for <= self.deadline_s:
            return False
        self._stalled = True            # once per episode
        self.stalls += 1
        self._c_stalls.inc()
        detail = {"outstanding": len(outstanding),
                  "oldest_age_s": oldest_age,
                  "frozen_for_s": frozen_for,
                  "deadline_s": self.deadline_s,
                  "iterations": {str(k): v for k, v in iters.items()},
                  "uids": sorted(str(u) for u, _h in outstanding)[:32]}
        router.timeline.instant(
            "watchdog_stall", outstanding=len(outstanding),
            oldest_age_s=round(oldest_age, 3),
            frozen_for_s=round(frozen_for, 3))
        logger.error(
            f"stall watchdog fired: {len(outstanding)} outstanding "
            f"handle(s), oldest {oldest_age:.1f}s, fleet frozen "
            f"{frozen_for:.1f}s (deadline {self.deadline_s}s)")
        if self.recorder is not None:
            self.recorder.on_stall(router, detail,
                                   format_thread_stacks())
        return True


# --------------------------------------------------------------- bundles
def is_bundle(path: str) -> bool:
    """Whether ``path`` is a COMPLETE incident bundle — a manifest that
    parses with the right format/version.  In-progress temp dirs
    (``.incident-*.tmp-*``) have no manifest by construction (it is
    written last, the directory renamed after), so a crash mid-dump can
    never produce a false positive."""
    m = _read_manifest(path)
    return m is not None and \
        m.get("schema_version") == BUNDLE_SCHEMA_VERSION


def _read_manifest(path: str) -> Optional[Dict[str, Any]]:
    """The manifest of a bundle directory of THIS format (any schema
    version), else ``None``."""
    mpath = os.path.join(path, "manifest.json")
    if not os.path.isdir(path) or not os.path.isfile(mpath):
        return None
    try:
        with open(mpath) as f:
            m = json.load(f)
    except (OSError, ValueError):  # graft: noqa(GL013) predicate: unreadable = not a bundle
        return None
    return m if isinstance(m, dict) and \
        m.get("bundle_format") == BUNDLE_FORMAT else None


def load_bundle(path: str) -> Dict[str, Any]:
    """Parse a bundle directory into ``{stem: payload}`` (JSON files
    parsed, others raw text, plus ``"path"``); raises ``ValueError`` on
    a non-bundle and on a bundle of another schema version."""
    m = _read_manifest(path)
    if m is None:
        raise ValueError(f"{path!r} is not a complete incident bundle "
                         "(missing/invalid manifest.json)")
    if m.get("schema_version") != BUNDLE_SCHEMA_VERSION:
        raise ValueError(
            f"{path!r} is an incident bundle of schema_version "
            f"{m.get('schema_version')!r}; this build reads "
            f"{BUNDLE_SCHEMA_VERSION} only")
    out: Dict[str, Any] = {"path": os.path.abspath(path)}
    for fname in sorted(os.listdir(path)):
        fpath = os.path.join(path, fname)
        if not os.path.isfile(fpath):
            continue
        stem, ext = os.path.splitext(fname)
        with open(fpath) as f:
            out[stem] = json.load(f) if ext == ".json" else f.read()
    return out


# ---------------------------------------------------------------- replay
class _ReplayProbe:
    """Minimal ``router._incident`` for a replay fleet: captures the
    FIRST trigger (kind, replica, step clock) and the handle map at the
    exact hook point the original recorder dumped from — the equality
    basis of the token-exactness assertion."""

    def __init__(self):
        self.fired = False
        self.kind: Optional[str] = None
        self.replica: Optional[int] = None
        self.step: Optional[int] = None
        self.exception: Optional[BaseException] = None
        self.progress: Dict[str, Dict[str, Any]] = {}

    def on_replica_fail(self, router, rid, exc):
        self._capture(router, _classify_exc(exc), rid, exc)

    def on_engine_error(self, router, rid, exc):
        self._capture(router, _classify_exc(exc), rid, exc)

    def on_step_poll(self, router):
        pass

    def _capture(self, router, kind, rid, exc):
        if self.fired:
            return
        self.fired = True
        self.kind = kind
        self.replica = None if rid is None else int(rid)
        self.exception = exc
        step = getattr(exc, "step", None)
        if step is None and rid is not None:
            try:
                step = int(router.replicas[rid].iterations)
            except Exception:  # graft: noqa(GL013) duck-typed fakes lack the clock
                step = None
        self.step = step
        self.progress = IncidentRecorder._progress(router)


def replay_bundle(path: str, model=None, *, prefix_match: bool = False,
                  max_steps: int = 100000) -> Dict[str, Any]:
    """Re-execute a bundle's incident: rebuild the fleet from its
    resolved configs (``init_serving`` per replica, params shared like
    ``init_router``), re-arm the recorded FaultPlan, replay the captured
    request stream through the ordinary ``submit``/``step`` path, and
    compare the re-fired trigger + pre-incident token streams against
    the bundle.

    Returns a report: ``reproduced`` (bool), ``trigger`` (as re-fired),
    ``expected_trigger``, ``mismatches`` (list of human-readable
    diffs), ``steps`` driven, ``uids`` compared.

    ``model=None`` rebuilds from ``manifest.model`` (gpt2 only);
    ``prefix_match=True`` relaxes token equality to a prefix relation —
    bundles recorded from *threaded* fleets are schedule-racy, so the
    deterministic replay may be a few tokens ahead/behind per stream.
    """
    bundle = load_bundle(path)
    manifest = bundle["manifest"]
    if not manifest.get("replayable"):
        raise ValueError(
            f"bundle {path!r} is not replayable (trigger "
            f"{manifest['trigger']['kind']!r}, or no request capture) — "
            "only deterministic crash/invariant/retrace triggers with a "
            "recorded request stream re-execute")
    import deepspeed_tpu
    from ..autotuning.trace import ServingTrace
    from ..serving.faults import FaultPlan
    from ..serving.router import ReplicaRouter

    mm = manifest.get("model") or {}
    dtype = mm.get("dtype", "fp32")
    tp = int(mm.get("tp_size", 1))
    if model is None:
        if mm.get("family") != "gpt2":
            raise ValueError(
                "bundle carries no rebuildable model meta "
                f"(family={mm.get('family')!r}) — pass model=")
        from ..models import gpt2

        model = gpt2.build(gpt2.GPT2Config(**mm["config"]))
    deepspeed_tpu.comm.reset_topology()
    model_config = {"dtype": dtype,
                    "tensor_parallel": {"tp_size": tp}}
    srvs = []
    params = None
    for cfg in bundle["replica_configs"]:
        srv = deepspeed_tpu.init_serving(model, config=model_config,
                                         params=params, **cfg)
        params = srv.engine.params
        srvs.append(srv)
    router_cfg = dict(manifest.get("router_config") or {})
    router_cfg["threaded"] = False      # replay is deterministic
    router = ReplicaRouter(srvs, **router_cfg)
    probe = _ReplayProbe()
    router._incident = probe
    if bundle.get("fault_plan") is not None:
        router.arm_faults(FaultPlan.from_json(bundle["fault_plan"]))
    trace = ServingTrace.from_dict(bundle["request_trace"])
    trace.submit_all(router)
    steps = 0
    raised = None
    try:
        while router.step():
            steps += 1
            if probe.fired or steps >= max_steps:
                break
    except Exception as e:  # noqa: BLE001 — the re-fired trigger itself
        raised = e
        if not probe.fired:
            probe._capture(router, _classify_exc(e), None, e)
    expected = manifest["trigger"]
    mismatches: List[str] = []
    if not probe.fired:
        mismatches.append(
            f"trigger never re-fired ({steps} steps driven)")
    else:
        for field, got in (("kind", probe.kind),
                           ("replica", probe.replica),
                           ("step", probe.step)):
            if got != expected.get(field):
                mismatches.append(
                    f"trigger {field}: replay {got!r} != bundle "
                    f"{expected.get(field)!r}")
    recorded = bundle.get("progress") or {}
    for uid, exp in sorted(recorded.items()):
        got = probe.progress.get(uid)
        if got is None:
            mismatches.append(f"uid {uid}: absent from replay")
            continue
        gt, et = got["tokens"], exp["tokens"]
        if gt == et:
            continue
        n = min(len(gt), len(et))
        if prefix_match and gt[:n] == et[:n]:
            continue
        div = next((i for i in range(n) if gt[i] != et[i]), n)
        mismatches.append(
            f"uid {uid}: tokens diverge at position {div} "
            f"(replay {len(gt)} tokens, bundle {len(et)})")
    return {
        "reproduced": not mismatches,
        "trigger": {"kind": probe.kind, "replica": probe.replica,
                    "step": probe.step,
                    "exception_type": type(probe.exception).__name__
                    if probe.exception is not None else None,
                    "raised": repr(raised) if raised is not None
                    else None},
        "expected_trigger": expected,
        "mismatches": mismatches,
        "steps": steps,
        "uids": len(recorded),
    }
