"""Multi-replica serving front-end: a data-parallel router over N
``ServingEngine`` replicas with prefix-affinity scheduling, blocks-in-use
balancing, cross-replica KV migration, and drain/re-admit.

One ``ServingEngine`` is one mesh; production scale needs N engine
replicas behind a router (ROADMAP item 1 — the reference's
``launcher/runner.py`` + ``elasticity/`` layer, the SNIPPETS 2-D
``("batch", "model")`` dp×tp end state).  The router is HOST-SIDE ONLY:
it never traces a program, so every replica's compile contract (2
chunked / 3 speculative / +2 tiered, sentry-enforced) is byte-identical
to the single-engine case.

**Routing** (``submit``): probe every live replica's device prefix trie
and host tier by content-addressed chain key
(``ServingEngine.affinity_probe``) and route to the deepest hit —
prefix affinity first, because a hit turns the prompt's prefill into a
table claim.  Resident state lags arrivals (a burst of same-session
requests lands before the first one has prefilled), so a bounded
chain-key **hint table** backs the probes: every routed prompt records
``chain_key -> replica`` for its full blocks, and a prompt with no
resident hit anywhere follows its deepest hint — same-session requests
co-locate even when submitted back-to-back.  No hit, no hint: balance
by ``blocks_in_use`` (the actual KV footprint — a replica with few
long sessions can be heavier than one with many short ones, which
request counts get wrong), tie-broken by queue depth then rotation.
``policy="round_robin"`` ignores state (the bench baseline);
``policy="balance"`` skips the affinity preference.

**Cross-replica KV pull**: PR 9's ``HostBlockStore`` made KV chains
content-addressed — ``chain_key`` = a fixed-width rolling blake2b
digest over the int32 token bytes through the block (each key hashes
the previous block's key, so it commits to the whole prefix) — which
makes host-resident chains a replica-portable exchange format.  When the routed replica lacks a prefix another
replica holds, the router pulls it: the source snapshots its device-trie
chain into its host tier (``demote_chain`` — the same fixed-shape
``paged_block_gather`` + one ``device_get`` the tiered engine swaps
with), exports the per-leaf bytes (``host_chain_export``), and the
target imports them (``host_chain_import``); admission on the target
then promotes through the ordinary staged ``device_put`` +
``paged_block_scatter`` path.  Bytes move bit-identically — int8 codes
and per-block scale rows are leaves of the same block, tp-sharded pools
gather/scatter per shard — so a migrated session resumes with exact
token parity and zero prefix recompute (only the mandatory sub-block
tail re-prefills, same as a local prefix hit).  In-process the
host→host hop is a numpy copy; a multi-host deployment would put an
RPC/RDMA fabric behind exactly this export/import pair.

**Drain / re-admit** (``drain(rid)`` / ``readmit(rid)``): a drained
replica stops receiving routes and steps; its engine preempts every
active slot (committed blocks demote, generated tokens fold into the
resume prompt), demotes its prefix cache, and hands the whole pending
queue back — the router re-routes each request (with a KV pull for its
chain) onto live replicas, token streams continuing on the SAME
handles.  No request is dropped, and greedy resume keeps outputs
token-exact.  ``serving/supervisor.py`` ties this to an
``elastic_agent``-style membership probe.

**Driving**: ``step()`` runs one scheduler iteration on every live
replica (deterministic single-thread time-slicing — the CPU-sim mode:
each replica stands in for an independent accelerator, so the scaling
signal is per-replica busy-time throughput, which the router accounts
in ``busy_seconds``).  ``start()`` instead spawns one worker thread per
replica (``threaded=True``) for wall-clock overlap on multi-core hosts;
every engine touch — routing probes, pulls, submits, steps — runs under
a per-replica lock, so the engines themselves stay single-threaded.

**Telemetry**: the router carries its own ``MetricsRegistry`` —
``serving_routed_affinity_total`` / ``serving_routed_balance_total`` /
``serving_kv_pulls_total`` (+ blocks/bytes) / ``serving_drains_total``
/ ``serving_readmits_total`` counters and per-replica labeled gauges
(``serving_replica_blocks_in_use{replica=}``,
``serving_replica_queue_depth{replica=}``) — plus a trace timeline of
``route`` / ``kv_pull`` / ``drain`` / ``readmit`` events and the
cross-ring flow starts whose finishes land on the replica rings
(docs/observability.md).  The FLEET view joins it all:
``fleet_registry()`` federates the router + replica registries with
``replica=`` labels (``telemetry/aggregate.py``), ``merged_trace()``
exports one multi-``pid`` Chrome document with router→replica and
kv-pull flow arrows, ``slo_report()`` merges the per-replica SLO
trackers, and ``start_metrics_server(port=)`` serves ``/metrics`` /
``/stats`` / ``/trace`` live (``telemetry/server.py``).
``debug_checks=True`` adds the router-state audit
(``analysis/invariants.audit_router``) after every ``step`` AND swaps
every fleet/replica lock for an instrumented
:class:`~deepspeed_tpu.analysis.concurrency.OrderedLock`: lock-order
violations raise at acquire time, contended-wait time lands in
``serving_lock_wait_seconds{lock=}``, order checks tick
``serving_lock_order_checks_total``, and ``stats()`` reports
``lock_order_checks`` / ``lock_violations`` (docs/static_analysis.md
"graft-race").

**Failure model** (``fail(rid)`` — the hard twin of ``drain``;
docs/reliability.md): a replica that CRASHES mid-decode cannot run the
polite drain protocol (its device state is not to be trusted and no
program may run on it).  ``fail`` marks it dead without touching it,
then re-homes every live request from its host-side bookkeeping
(``ServingEngine.salvage``): pending items resubmit verbatim; in-flight
requests fold their already-streamed tokens into the resume prompt (the
preemption trick, cross-replica — greedy resume is token-exact) and
pull whatever prefix blocks survive in *other* replicas' host tiers
(the dead replica is excluded as a pull source), streaming onward on
the SAME handles.  A request whose re-home budget (``max_rehomes``) is
exhausted — or that has no live replica left to land on — resolves its
handle with a typed
:class:`~deepspeed_tpu.inference.serving.RequestFailedError` instead of
hanging its caller.  Crashes are detected three ways: a worker thread's
``step()`` raising (threaded mode), the deterministic ``step()`` loop
catching :class:`~deepspeed_tpu.serving.faults.SimulatedCrash` (the
chaos harness), and the supervisor's hard probe failure (capacity
``< 0`` — process gone — fails immediately, no grace window;
``serving/supervisor.py``).

**Replica state machine** (transitions outside this table are loud
no-ops, never crashes)::

    state    | drain(rid)        | fail(rid)          | readmit(rid)
    ---------+-------------------+--------------------+--------------
    live     | -> drained        | -> failed (rehome) | no-op
    drained  | no-op (log)       | -> failed (no work | -> live
             |                   |    left to rehome) |
    failed   | no-op (log: use   | no-op (log)        | -> live (clears
             |  readmit instead) |                    |  fault record)

**Load shedding** (``max_queue_depth`` / ``burn_threshold``;
docs/reliability.md "Shedding policy"): admission is bounded.  When the
fleet-wide pending depth reaches ``max_queue_depth`` — or a protected
class's SLO error-budget burn rate crosses ``burn_threshold`` —
``submit`` REJECTS ``shed_classes`` work (``batch`` by default) with a
loud, typed :class:`~deepspeed_tpu.serving.faults.RequestRejected`
instead of letting every class's latency collapse together; sheds tick
``serving_requests_shed_total{slo_class=}`` and drop a ``shed``
timeline event.  Higher classes keep admitting (the priority queue
already ordered them first), so ``realtime``/``interactive`` TTFT holds
while ``batch`` absorbs the rejections.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.concurrency import LockSanitizer, OrderedLock
from ..analysis.invariants import audit_router
from ..inference.paged import TransportError, chain_keys
from ..inference.serving import (Request, RequestFailedError,
                                 RequestHandle, ServingEngine,
                                 _PendingItem)
from ..telemetry import (MetricsRegistry, TraceTimeline, federate,
                         merge_chrome_traces, merged_slo_report)
from ..telemetry.server import MetricsServer
from ..utils.logging import logger
from .faults import FaultInjector, FaultPlan, RequestRejected, SimulatedCrash

__all__ = ["ReplicaRouter"]

#: SLO classes the burn-rate shed trigger protects: when one of THESE
#: classes is burning error budget past ``burn_threshold``, shed-class
#: work is rejected to shed load in its favor
_PROTECTED_CLASSES = ("realtime", "interactive")

_POLICIES = ("affinity", "balance", "round_robin")


class ReplicaRouter:
    """DP front-end over N :class:`ServingEngine` replicas (module
    docstring has the design).

    Parameters
    ----------
    replicas:   the engine replicas — same model family/config (the
                router checks ``block_size`` and, when pulling, the swap
                block byte layout; identical weights are the caller's
                contract, ``init_router`` shares one pytree).
    policy:     ``"affinity"`` (default: deepest prefix hit, else
                balance), ``"balance"`` (blocks-in-use only), or
                ``"round_robin"`` (stateless baseline).
    kv_pull:    pull missing prefixes from other replicas' host tiers at
                route time (needs ``host_blocks > 0`` on the replicas
                involved; silently skipped otherwise).
    threaded:   ``start()`` spawns one worker thread per replica; off,
                the caller drives ``step()`` (deterministic CPU-sim).
    debug_checks: audit router bookkeeping after every ``step`` (each
                engine's own paged-state audit rides its
                ``debug_checks`` flag as usual).
    max_queue_depth: fleet-wide pending-queue bound; reaching it sheds
                ``shed_classes`` submissions with a typed
                :class:`RequestRejected` (``None`` = unbounded, no
                shedding — the pre-PR-15 behavior).
    shed_classes: the SLO classes that absorb rejections under overload
                (module docstring "Load shedding").
    burn_threshold: shed ``shed_classes`` work while any protected
                class's SLO burn rate exceeds this (``None`` = depth
                trigger only).
    pull_retries: transient-transport retry budget per cross-replica KV
                pull (exhaustion falls back to local recompute).
    pull_backoff_s: base of the deterministic exponential backoff
                between pull retries (``base * 2^attempt``; 0 = retry
                immediately — the CPU-sim default).
    pull_timeout_s: per-attempt wall budget on a pull; an attempt
                running past it counts as a transient failure
                (``None`` = no timeout).
    max_rehomes: per-request crash re-home budget; past it the handle
                resolves with :class:`RequestFailedError` instead of
                bouncing between dying replicas forever.
    """

    def __init__(self, replicas: Sequence[ServingEngine], *,
                 policy: str = "affinity", kv_pull: bool = True,
                 threaded: bool = False, debug_checks: bool = False,
                 trace_capacity: int = 4096,
                 max_queue_depth: Optional[int] = None,
                 shed_classes: Sequence[str] = ("batch",),
                 burn_threshold: Optional[float] = None,
                 pull_retries: int = 2, pull_backoff_s: float = 0.0,
                 pull_timeout_s: Optional[float] = None,
                 max_rehomes: int = 3,
                 giant_context_tokens: int = 0):
        replicas = list(replicas)
        if not replicas:
            raise ValueError("ReplicaRouter needs at least one replica")
        if policy not in _POLICIES:
            raise ValueError(f"policy={policy!r} — expected one of "
                             f"{_POLICIES}")
        sizes = {r.block_size for r in replicas}
        if len(sizes) != 1:
            raise ValueError(
                f"replicas disagree on block_size ({sorted(sizes)}) — "
                "chain keys would not be portable between them")
        layouts = {r._host.block_nbytes for r in replicas
                   if r._host is not None}
        if kv_pull and len(layouts) > 1:
            raise ValueError(
                f"kv_pull=True but replica host tiers disagree on the "
                f"swap block layout ({sorted(layouts)} bytes/block) — "
                "pulled bytes would scatter into mismatched pools")
        dp_tp = [i for i, r in enumerate(replicas)
                 if getattr(r, "engine_mode", "replicas") == "dp_tp"]
        if dp_tp and len(replicas) > 1:
            raise ValueError(
                f"replica(s) {dp_tp} run engine_mode='dp_tp' — a dp×tp "
                "engine already batches across its dp groups inside one "
                "compiled program, so it must be the router's sole "
                "replica (the router demotes to front-end admission); "
                "mixing it with other replicas double-shards the fleet")
        # ----- disaggregated prefill/decode fleet (ISSUE 17): a replica's
        # role gates which admissions may route to it — new prompts to
        # prefill-capable replicas, in-flight resumes to decode-capable
        # ones.  An all-"both" fleet (the default) disables the filter
        # entirely: routing is bit-identical to the non-disaggregated
        # router.
        roles = [getattr(r, "role", "both") for r in replicas]
        self.roles = roles
        self._prefill_capable = frozenset(
            i for i, ro in enumerate(roles) if ro in ("prefill", "both"))
        self._decode_capable = frozenset(
            i for i, ro in enumerate(roles) if ro in ("decode", "both"))
        self.disaggregated = any(ro != "both" for ro in roles)
        if self.disaggregated:
            if not self._prefill_capable or not self._decode_capable:
                missing = "prefill" if not self._prefill_capable \
                    else "decode"
                raise ValueError(
                    f"disaggregated fleet has no {missing}-capable "
                    f"replica (roles={roles}) — the prefill_workers:"
                    "decode_workers ratio must keep at least one worker "
                    "on each side of the pipeline (or run every replica "
                    "role='both')")
            if not kv_pull:
                raise ValueError(
                    "disaggregated fleet needs kv_pull=True — the "
                    "prefill->decode handoff travels as a cross-replica "
                    "KV pull; without it every decode worker would "
                    "re-run the prefill it exists to avoid")
        self.replicas = replicas
        self.policy = policy
        self.kv_pull = bool(kv_pull)
        self.threaded = bool(threaded)
        self.debug_checks = bool(debug_checks)
        self._drained: set = set()
        #: crash-failed replicas (⊆ _drained: failed implies out of
        #: rotation) — excluded as KV-pull sources, cleared by readmit
        self._failed: set = set()
        self._worker_errors: Dict[int, BaseException] = {}
        self._handles: Dict[Any, Tuple[RequestHandle, int]] = {}
        #: per-request crash re-home count (pruned with the handle map)
        self._rehomes: Dict[Any, int] = {}
        self.max_rehomes = int(max_rehomes)
        self.max_queue_depth = None if max_queue_depth is None \
            else int(max_queue_depth)
        self.shed_classes = tuple(shed_classes)
        self.burn_threshold = None if burn_threshold is None \
            else float(burn_threshold)
        self.pull_retries = int(pull_retries)
        self.pull_backoff_s = float(pull_backoff_s)
        self.pull_timeout_s = pull_timeout_s
        #: prompts at/above this length route as the "giant_context"
        #: request class: session affinity is forced (even under
        #: round_robin — migrating a 100k-token KV chain dwarfs any
        #: balance gain), a migration cost model gates KV pulls (only a
        #: chain covering >= half the missing span is worth moving), and
        #: an unset slo_class defaults to "giant_context" so the
        #: dedicated SLO targets apply.  0 (default) disables the class.
        self.giant_context_tokens = int(giant_context_tokens)
        if self.giant_context_tokens < 0:
            raise ValueError(
                f"giant_context_tokens must be >= 0, got "
                f"{giant_context_tokens}")
        #: armed chaos harness (serving/faults.py); None = zero cost
        self._injector: Optional[FaultInjector] = None
        self._rr = 0
        self.block_size = replicas[0].block_size
        #: chain_key -> last replica routed there (bounded LRU) — the
        #: pending-prefix affinity signal (module docstring "Routing")
        self._hints: "OrderedDict[bytes, int]" = OrderedDict()
        self._hint_cap = 8192
        self._busy_s = [0.0] * len(replicas)
        self._stop_evt = threading.Event()
        self._threads: List[threading.Thread] = []
        #: trace-capture hook (autotuning/trace.py TraceRecorder): called
        #: per submit() with the caller's knobs, before routing — the
        #: recorded arrival order is the fleet-wide one
        self._submit_observer = None
        #: flight recorder (telemetry/incident.py IncidentRecorder):
        #: notified on replica failure / engine error / per-step poll;
        #: None = one attribute test per hook site (the faults.py
        #: zero-cost-disarmed idiom)
        self._incident = None

        # family names carry the serving_ namespace prefix (lint GL008:
        # the federated fleet registry stays greppable by subsystem)
        m = self.metrics = MetricsRegistry()
        self._c_aff = m.counter(
            "serving_routed_affinity_total",
            "requests routed to their deepest prefix-affinity replica")
        self._c_bal = m.counter(
            "serving_routed_balance_total",
            "requests routed by blocks-in-use balance (no affinity hit)")
        self._c_pulls = m.counter(
            "serving_kv_pulls_total", "cross-replica KV-pull operations")
        self._c_pull_blocks = m.counter(
            "serving_kv_pull_blocks_total", "KV blocks moved between "
            "replica host tiers by cross-replica pulls")
        self._c_pull_bytes = m.counter(
            "serving_kv_pull_bytes_total", "bytes moved between replica "
            "host tiers by cross-replica pulls")
        self._c_drains = m.counter(
            "serving_drains_total", "replica drains (sessions demoted + "
            "handed off)")
        self._c_readmits = m.counter(
            "serving_readmits_total",
            "drained replicas re-admitted to routing")
        self._c_failures = m.counter(
            "serving_replica_failures_total",
            "replica crash failures (fail(rid) — hard death, distinct "
            "from polite drains)")
        self._c_rehomed = m.counter(
            "serving_requests_rehomed_total",
            "requests re-homed onto survivors after a replica failure")
        self._c_req_failed = m.counter(
            "serving_requests_failed_total",
            "requests permanently failed (re-home budget exhausted or "
            "no live replica left) — handles resolve RequestFailedError")
        self._c_pull_retries = m.counter(
            "serving_kv_pull_retries_total",
            "cross-replica KV-pull attempts retried after a transient "
            "transport fault or per-attempt timeout")
        self._c_handoffs = m.counter(
            "serving_handoffs_total",
            "prefill->decode handoffs routed across the disaggregated "
            "fleet")
        self._c_giant = m.counter(
            "serving_giant_context_total",
            "requests routed as the giant_context class (prompt >= "
            "giant_context_tokens; affinity-pinned, pull-cost-gated)")
        #: per-class shed counters, created lazily on first shed so the
        #: family only exists once shedding is actually configured
        self._c_shed: Dict[str, Any] = {}
        self._g_blocks = [
            m.gauge("serving_replica_blocks_in_use",
                    "device KV blocks referenced on the replica",
                    replica=str(i)) for i in range(len(replicas))]
        self._g_queue = [
            m.gauge("serving_replica_queue_depth",
                    "requests waiting for a slot on the replica",
                    replica=str(i)) for i in range(len(replicas))]

        # ----- locking: one fleet lock serializing fleet-level decisions
        # (routing, hints, the handle->replica map, drain/readmit)
        # against each other — without it a submit could pick a replica
        # that drains between the routing decision and the enqueue,
        # stranding the request on an engine nothing steps — plus one
        # lock per replica so engines stay effectively single-threaded.
        # The declared partial order (checked statically by bin/graft-
        # race, dynamically by the sanitizer below) is fleet -> replica
        # [ascending index] -> handle condition; workers take only their
        # replica lock, so no cycle.  Under debug_checks every lock is
        # an instrumented OrderedLock: acquisition-order violations
        # raise LockOrderError at acquire time, contended-wait time
        # lands in serving_lock_wait_seconds{lock=}, and each cross-lock
        # order check ticks serving_lock_order_checks_total — the
        # concurrency analogue of the recompile sentry, zero overhead
        # off (analysis/concurrency.py; docs/static_analysis.md).
        if self.debug_checks:
            self._sanitizer = LockSanitizer()
            self._c_lock_checks = m.counter(
                "serving_lock_order_checks_total",
                "cross-lock acquisition-order checks run by the lock "
                "sanitizer")
            self._sanitizer.on_check = self._c_lock_checks.inc
            h_fleet = m.histogram(
                "serving_lock_wait_seconds",
                help="time spent waiting to acquire an instrumented "
                     "serving lock", lock="fleet")
            h_rep = m.histogram(
                "serving_lock_wait_seconds",
                help="time spent waiting to acquire an instrumented "
                     "serving lock", lock="replica")
            self._fleet_lock = OrderedLock(
                "serving.fleet", sanitizer=self._sanitizer,
                wait_observer=h_fleet.observe)
            self._locks = [
                OrderedLock("serving.replica", key=i,
                            sanitizer=self._sanitizer,
                            wait_observer=h_rep.observe)
                for i in range(len(replicas))]
            for rep in replicas:
                # handle Conditions the replicas mint from here on share
                # the fleet sanitizer, so replica-lock -> handle-cond
                # edges are checked too (jax-free fakes tolerate the
                # attribute fine)
                try:
                    rep._lock_sanitizer = self._sanitizer
                except AttributeError:  # graft: noqa(GL013) duck-typed fakes may forbid attribute set
                    pass
        else:
            self._sanitizer = None
            self._fleet_lock = threading.RLock()
            self._locks = [threading.RLock() for _ in replicas]

        self.timeline = TraceTimeline(capacity=trace_capacity)
        #: fleet-wide Chrome flow-id allocator: route->admit and kv-pull
        #: src->dst flow events must carry unique ids across EVERY ring
        #: that merge_chrome_traces will combine (allocated under the
        #: fleet lock only)
        self._next_flow = 0
        self.metrics_server: Optional[MetricsServer] = None

    # ------------------------------------------------------------- bookkeeping
    def _flow_id(self) -> int:
        self._next_flow += 1
        return self._next_flow

    def _start_route_flow(self, rid: int, uid, **args) -> None:
        """Distributed trace linkage for one routing decision: flow START
        on the router ring, flow id noted on the replica (its admission
        emits the finish).  Must run before the replica's enqueue — a
        threaded worker could admit the moment submit lands, and the
        merged document needs ``s`` strictly before ``f``.  ``note_flow``
        is an optional part of the replica protocol (jax-free test
        doubles skip it)."""
        note = getattr(self.replicas[rid], "note_flow", None)
        if note is None or not self.timeline.enabled \
                or not self.replicas[rid].timeline.enabled:
            return
        fid = self._flow_id()
        self.timeline.flow_start("route", fid, uid=str(uid),
                                 replica=int(rid), **args)
        note(uid, fid)
    def _live(self) -> List[int]:
        return [i for i in range(len(self.replicas))
                if i not in self._drained]

    def _refresh_gauges(self, rid: int) -> None:
        rep = self.replicas[rid]
        self._g_blocks[rid].set(rep._alloc.blocks_in_use)
        self._g_queue[rid].set(len(rep._pending))

    @property
    def busy_seconds(self) -> List[float]:
        """Per-replica cumulative ``step()`` wall time — the CPU-sim
        stand-in for each replica's accelerator occupancy (module
        docstring "Driving")."""
        return list(self._busy_s)

    # ----------------------------------------------------------------- routing
    def _full_block_keys(self, prompt) -> List[bytes]:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        usable = (int(prompt.size) - 1) // self.block_size
        return chain_keys(prompt, usable, self.block_size)

    def _hint_route(self, keys, live) -> Tuple[Optional[int], int]:
        """Deepest hint-table match among live replicas."""
        for i in range(len(keys) - 1, -1, -1):
            rid = self._hints.get(keys[i])
            if rid is not None and rid in live:
                return rid, i + 1
        return None, 0

    def _note_hints(self, keys, rid: int) -> None:
        for k in keys:
            self._hints[k] = rid
            self._hints.move_to_end(k)
        while len(self._hints) > self._hint_cap:
            self._hints.popitem(last=False)

    def _route(self, prompt, need: str = "any",
               force_affinity: bool = False) -> Tuple[int, str, int]:
        """Pick a replica for ``prompt``: ``(rid, policy_used, depth)``
        where ``policy_used`` is ``"affinity"`` (a prefix hit decided)
        or ``"balance"`` (load decided).  ``need`` restricts candidates
        by role capability in a disaggregated fleet — ``"prefill"`` for
        new admissions, ``"decode"`` for in-flight resumes/handoffs; on
        an all-"both" fleet every replica satisfies either, so the
        filter is a no-op and routing is bit-identical.
        ``force_affinity`` (the giant_context class) runs the affinity
        preference even under ``policy="round_robin"``/``"balance"`` —
        re-prefilling a 100k-token context costs more than any
        rotation fairness buys."""
        live = self._live()
        if not live:
            raise RuntimeError("every replica is drained — readmit one "
                               "before submitting")
        if need == "prefill":
            live = [r for r in live if r in self._prefill_capable]
        elif need == "decode":
            live = [r for r in live if r in self._decode_capable]
        if not live:
            raise RuntimeError(
                f"no live {need}-capable replica — the disaggregated "
                f"fleet lost its last {need} worker; readmit one before "
                "submitting")
        if self.policy == "round_robin" and not force_affinity:
            rid = live[self._rr % len(live)]
            self._rr += 1
            return rid, "balance", 0
        keys = self._full_block_keys(prompt)
        probes = {}
        for rid in live:
            with self._locks[rid]:
                probes[rid] = self.replicas[rid].affinity_probe(prompt)
        depth = {r: probes[r]["device_blocks"] + probes[r]["host_blocks"]
                 for r in live}
        load = {r: (probes[r]["blocks_in_use"],
                    probes[r]["queue_depth"] + probes[r]["active"])
                for r in live}
        if self.policy == "affinity" or force_affinity:
            best_depth = max(depth.values())
            if best_depth > 0:
                rid = min((r for r in live if depth[r] == best_depth),
                          key=lambda r: load[r])
                self._note_hints(keys, rid)
                return rid, "affinity", best_depth
            # resident state lags arrivals: follow the queued-prefix hint
            rid, hdepth = self._hint_route(keys, live)
            if rid is not None:
                self._note_hints(keys, rid)
                return rid, "affinity", hdepth
        n = len(live)
        rid = min(live, key=lambda r: (load[r],
                                       (r - self._rr) % max(n, 1)))
        self._rr += 1
        self._note_hints(keys, rid)
        return rid, "balance", depth[rid]

    def _pull_transfer_sync(self, src, tgt, prompt, start: int,
                            plen: int) -> int:
        """One hardened pull transfer under both replica locks (the
        sanctioned blocking-transfer helper — the backoff sleep between
        bounded retries is deliberate, exactly like the engine's
        demote/promote waits): demote the source's device chain, export
        bytes + checksums, import with verification on the target.
        Transient faults and over-budget attempts retry with
        deterministic exponential backoff (``pull_backoff_s *
        2^attempt``); permanent faults and budget exhaustion return 0 —
        the caller's admission path recomputes locally."""
        for attempt in range(self.pull_retries + 1):
            t0 = time.perf_counter()
            try:
                src.demote_chain(prompt, plen - 1, start_block=start)
                keys, blocks, sums = src.host_chain_export(
                    prompt, start, plen - 1)
                stored = tgt.host_chain_import(keys, blocks,
                                               checksums=sums)
            except TransportError as e:
                self.timeline.instant("kv_pull_fault", op=e.op,
                                      attempt=attempt,
                                      transient=e.transient)
                if not e.transient:
                    logger.warning(
                        f"kv pull: permanent transport fault ({e}) — "
                        "falling back to local recompute")
                    return 0
            else:
                over = self.pull_timeout_s is not None and \
                    time.perf_counter() - t0 > self.pull_timeout_s
                if not over or stored:
                    # landed (possibly late): a completed transfer is
                    # never discarded — the timeout exists to retry
                    # attempts that produced NOTHING, not to redo work
                    if over:
                        self.timeline.instant(
                            "kv_pull_fault", op="timeout",
                            attempt=attempt, transient=True, late=True)
                    return stored
                # over the per-attempt budget with nothing stored:
                # treat as transient (the import is idempotent by chain
                # key, a retry re-probes)
                self.timeline.instant("kv_pull_fault", op="timeout",
                                      attempt=attempt, transient=True)
            if attempt < self.pull_retries:
                self._c_pull_retries.inc()
                if self.pull_backoff_s:
                    time.sleep(self.pull_backoff_s * (2 ** attempt))
        logger.warning(
            f"kv pull: retry budget ({self.pull_retries}) exhausted — "
            "falling back to local recompute")
        return 0

    def _maybe_pull(self, rid: int, prompt,
                    min_gain_blocks: int = 0) -> int:
        """Cross-replica KV pull (module docstring): extend the routed
        replica's resident chain for ``prompt`` from the deepest other
        LIVE-TIERED replica's tiers — crash-failed replicas are never a
        source (their host arenas died with their process).  The
        transfer is hardened (docs/reliability.md): per-block checksums
        travel beside the bytes and are verified on import, transient
        :class:`TransportError`/per-attempt-timeout failures retry up
        to ``pull_retries`` times with deterministic exponential
        backoff, and a permanent fault (or an exhausted budget) falls
        back to local recompute — the pull is an optimization, never a
        correctness dependency.  Returns blocks pulled.

        ``min_gain_blocks`` is the migration cost model's floor (the
        giant_context class sets it to half the missing span): a foreign
        chain shallower than that is not worth moving — the request
        stays pinned and recomputes locally."""
        tgt = self.replicas[rid]
        if tgt._host is None or tgt._prefix is None:
            return 0
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = int(prompt.size)
        usable = (plen - 1) // tgt.block_size   # admission's lookup cap
        if usable <= 0:
            return 0
        with self._locks[rid]:
            p = tgt.affinity_probe(prompt)
        start = p["device_blocks"] + p["host_blocks"]
        if start >= usable:
            return 0
        best, best_depth = None, start
        for r in range(len(self.replicas)):
            if r == rid or r in self._failed or \
                    self.replicas[r]._host is None:
                continue
            with self._locks[r]:
                q = self.replicas[r].affinity_probe(prompt)
            d = q["device_blocks"] + q["host_blocks"]
            if d > best_depth:
                best, best_depth = r, d
        if best is None:
            return 0
        if min_gain_blocks and best_depth - start < min_gain_blocks:
            self.timeline.instant(
                "giant_pin", dst=int(rid), src=int(best),
                gain_blocks=int(best_depth - start),
                min_gain_blocks=int(min_gain_blocks))
            return 0
        lo, hi = sorted((rid, best))        # lock order: replica index
        src = self.replicas[best]
        with self._locks[lo], self._locks[hi]:
            stored = self._pull_transfer_sync(src, tgt, prompt, start,
                                              plen)
        if stored:
            self._c_pulls.inc()
            self._c_pull_blocks.inc(stored)
            self._c_pull_bytes.inc(stored * tgt._host.block_nbytes)
            self.timeline.instant("kv_pull", src=int(best), dst=int(rid),
                                  blocks=int(stored))
            # flow arrow source-replica lane -> target-replica lane in
            # the merged fleet trace (start strictly before finish: the
            # two now_us() stamps are taken sequentially here)
            if src.timeline.enabled and tgt.timeline.enabled:
                fid = self._flow_id()
                src.timeline.flow_start("kv_pull", fid, src=int(best),
                                        dst=int(rid), blocks=int(stored))
                tgt.timeline.flow_end("kv_pull", fid, src=int(best),
                                      dst=int(rid))
        return stored

    # ------------------------------------------------------------------ submit
    def _prune_handles(self) -> None:
        if len(self._handles) > 64 + 4 * len(self.replicas):
            self._handles = {u: hr for u, hr in self._handles.items()
                             if not hr[0].done}
            self._rehomes = {u: n for u, n in self._rehomes.items()
                             if u in self._handles}

    def _shed_counter(self, cls: str):
        c = self._c_shed.get(cls)
        if c is None:
            c = self.metrics.counter(
                "serving_requests_shed_total",
                "requests rejected by SLO-class-aware load shedding "
                "(bounded admission — docs/reliability.md)",
                slo_class=cls)
            self._c_shed[cls] = c
        return c

    #: burn-rate cache TTL: merging every replica's SLO report is
    #: O(replicas x classes) — exactly the work NOT to repeat per
    #: batch submit at the height of an overload burst.  Shedding is a
    #: heuristic; a quarter-second-stale burn rate sheds the same way.
    _BURN_TTL_S = 0.25
    #: minimum fresh requests between refreshes for the WINDOWED burn
    #: computation; thinner windows fall back to the lifetime rate
    _BURN_WINDOW_MIN = 8

    def _protected_burn(self):
        """``(class, burn)`` for the worst-burning protected class with
        traffic — computed from the merged fleet SLO report at most
        every ``_BURN_TTL_S`` seconds (cached between, so a flood of
        shed-class submits costs one dict read each, not a fleet-wide
        histogram merge).  The burn is **windowed**: attainment is
        computed over the requests finished since the previous refresh
        (the multi-window burn-rate practice ``telemetry/slo.py``
        cites), so shedding STOPS once the fleet recovers — a lifetime-
        cumulative rate would keep rejecting batch work for thousands
        of flawless requests after one past incident.  Windows thinner
        than ``_BURN_WINDOW_MIN`` fresh requests fall back to the
        lifetime rate (too few samples to call a recovery)."""
        cached = getattr(self, "_burn_cache", None)
        now = time.perf_counter()
        if cached is not None and now - cached[0] <= self._BURN_TTL_S:
            return cached[1], cached[2]
        worst, worst_burn = None, 0.0
        rep = self.slo_report()
        prev = getattr(self, "_burn_prev", {})
        cur = {}
        for pc in _PROTECTED_CLASSES:
            entry = rep.get(pc) or {}
            n = int(entry.get("requests") or 0)
            t_att = int(entry.get("ttft_attained") or 0)
            p_att = int(entry.get("tpot_attained") or 0)
            cur[pc] = (n, t_att, p_att)
            if not n:
                continue
            pn, pt, pp = prev.get(pc, (0, 0, 0))
            dn = n - pn
            if dn >= self._BURN_WINDOW_MIN:
                denom = max(1e-9, 1.0 - float(
                    entry.get("objective") or 0.99))
                burn = max((1.0 - (t_att - pt) / dn) / denom,
                           (1.0 - (p_att - pp) / dn) / denom)
            else:
                # window still thin: keep the PREVIOUS anchor (so slow
                # traffic accumulates a real window instead of
                # degenerating back to lifetime forever) and use the
                # lifetime rate meanwhile
                cur[pc] = (pn, pt, pp) if pc in prev else cur[pc]
                burn = max(entry.get("ttft_burn_rate") or 0.0,
                           entry.get("tpot_burn_rate") or 0.0)
            if worst is None or burn > worst_burn:
                worst, worst_burn = pc, burn
        self._burn_prev = cur
        self._burn_cache = (now, worst, worst_burn)
        return worst, worst_burn

    def _maybe_shed(self, uid, slo_class: Optional[str]) -> None:
        """Bounded admission (module docstring "Load shedding"), under
        the fleet lock: raises :class:`RequestRejected` when this
        submission's class is configured to absorb overload and a
        threshold is tripped; otherwise a no-op.  Zero cost with
        shedding unconfigured."""
        if self.max_queue_depth is None and self.burn_threshold is None:
            return
        cls = slo_class if slo_class is not None else "standard"
        if cls not in self.shed_classes:
            return
        reason = None
        if self.max_queue_depth is not None:
            depth = sum(len(self.replicas[r]._pending)
                        for r in self._live())
            if depth >= self.max_queue_depth:
                reason = (f"fleet queue depth {depth} >= "
                          f"max_queue_depth {self.max_queue_depth}")
        if reason is None and self.burn_threshold is not None:
            pc, burn = self._protected_burn()
            if pc is not None and burn > self.burn_threshold:
                reason = (f"{pc} SLO burn rate {burn:.2f} > "
                          f"burn_threshold {self.burn_threshold}")
        if reason is None:
            return
        self._shed_counter(cls).inc()
        self.timeline.instant("shed", uid=str(uid), slo_class=cls,
                              reason=reason)
        logger.warning(f"shedding request {uid!r} ({cls}): {reason}")
        raise RequestRejected(uid, slo_class, reason)

    def submit(self, request: Request, *, priority: int = 0,
               slo_class: Optional[str] = None,
               eos_token_id: Optional[int] = None) -> RequestHandle:
        """Route one request and enqueue it on the chosen replica;
        returns the engine's :class:`RequestHandle` (streaming /
        ``result()`` / ``cancel()`` — cancel routes back through the
        router so it lands on whichever replica owns the request after
        any drain handoffs).  With shedding configured
        (``max_queue_depth`` / ``burn_threshold``), an overloaded fleet
        rejects ``shed_classes`` submissions with a typed
        :class:`RequestRejected` instead of queueing them into latency
        collapse."""
        giant = bool(self.giant_context_tokens) and \
            len(request.prompt) >= self.giant_context_tokens
        if giant and slo_class is None:
            # unset class defaults to the dedicated giant_context SLO
            # targets (telemetry/slo.py); an explicit class always wins
            slo_class = "giant_context"
        if self._submit_observer is not None:
            self._submit_observer(request, priority=priority,
                                  slo_class=slo_class,
                                  eos_token_id=eos_token_id)
        with self._fleet_lock:
            self._maybe_shed(request.uid, slo_class)
            # new admissions carry an un-prefilled prompt: they need a
            # prefill-capable replica (no-op filter on a "both" fleet);
            # giant contexts additionally force session affinity
            rid, why, depth = self._route(request.prompt, need="prefill",
                                          force_affinity=giant)
            if why == "affinity":
                self._c_aff.inc()
            else:
                self._c_bal.inc()
            if giant:
                self._c_giant.inc()
                self.timeline.instant(
                    "giant_context", uid=str(request.uid),
                    replica=int(rid),
                    prompt_tokens=int(len(request.prompt)))
            if self.kv_pull:
                min_gain = 0
                if giant:
                    # migration cost model: a 100k-token chain only moves
                    # when the foreign tier covers at least half of what
                    # this replica is missing — anything less and local
                    # recompute beats the transfer
                    usable = (len(request.prompt) - 1) // self.block_size
                    min_gain = max(1, (usable - depth) // 2)
                self._maybe_pull(rid, request.prompt,
                                 min_gain_blocks=min_gain)
            # distributed trace linkage: the flow START must be on the
            # ring before the replica can possibly admit (a threaded
            # worker could admit the moment submit enqueues), so the
            # merged document always sees s before f
            with self._locks[rid]:
                self._start_route_flow(rid, request.uid)
                handle = self.replicas[rid].submit(
                    request, priority=priority, slo_class=slo_class,
                    eos_token_id=eos_token_id)
            # under the handle's own condition — a bare attribute store
            # would race a worker already streaming into the handle
            handle.set_canceller(self.cancel)
            self._prune_handles()
            self._handles[request.uid] = (handle, rid)
        self.timeline.instant("route", uid=str(request.uid),
                              replica=int(rid), policy=why,
                              depth_blocks=int(depth))
        self._refresh_gauges(rid)
        return handle

    def cancel(self, uid) -> bool:
        """Cancel wherever the request lives now (post-handoff aware).
        Taken under the fleet lock: a cancel racing a concurrent drain
        would otherwise read the stale handle->replica mapping and land
        on an engine that already handed the request off."""
        with self._fleet_lock:
            rec = self._handles.get(uid)
            if rec is None:
                return False
            _, rid = rec
            with self._locks[rid]:
                return self.replicas[rid].cancel(uid)

    # ----------------------------------------------------------------- driving
    def step(self) -> bool:
        """One scheduler iteration on every live replica (single-thread
        time-slicing); returns whether any replica has work left.  Busy
        time only accrues for steps that had work to do — an idle
        replica's no-op poll is not accelerator occupancy."""
        more = False
        for rid in self._live():
            rep = self.replicas[rid]
            try:
                with self._locks[rid]:
                    had_work = bool(rep._pending or rep._active or
                                    rep._cancel_flags)
                    t0 = time.perf_counter()
                    m = rep.step()
                    if had_work:
                        self._busy_s[rid] += time.perf_counter() - t0
            except SimulatedCrash as e:
                # the chaos harness killed this replica mid-iteration:
                # exactly a worker death — fail it and re-home.  Real
                # engine exceptions still propagate in deterministic
                # mode (they are bugs, not chaos).
                self._fail_replica(rid, e)
                more = True
                continue
            except Exception as e:
                # a REAL engine/audit exception (invariant violation,
                # retrace, ...) still propagates — but the flight
                # recorder dumps first, while the evidence is intact
                inc = self._incident
                if inc is not None:
                    inc.on_engine_error(self, rid, e)
                raise
            more = m or more
            self._refresh_gauges(rid)
            if self.disaggregated and \
                    getattr(rep, "role", "both") == "prefill" and \
                    self._pump_handoffs(rid):
                more = True     # handoffs enqueued work elsewhere
        # the handle map is fleet state: pruning it unlocked would race
        # a concurrent submit's insert (graft-race GL010)
        with self._fleet_lock:
            self._prune_handles()
        if self.debug_checks:
            try:
                audit_router(self)
            except Exception as e:
                inc = self._incident
                if inc is not None:
                    inc.on_engine_error(self, None, e)
                raise
        inc = self._incident
        if inc is not None:
            inc.on_step_poll(self)
        return more

    def start(self) -> "ReplicaRouter":
        """Spawn one worker thread per replica (``threaded`` mode); each
        worker steps its engine under the replica lock, so engines stay
        effectively single-threaded."""
        if self._threads:
            return self
        self._stop_evt.clear()
        for rid in range(len(self.replicas)):
            t = threading.Thread(target=self._worker, args=(rid,),
                                 name=f"serving-replica-{rid}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def _worker(self, rid: int) -> None:
        while not self._stop_evt.is_set():
            if rid in self._drained:
                time.sleep(0.005)
                continue
            rep = self.replicas[rid]
            try:
                with self._locks[rid]:
                    had_work = bool(rep._pending or rep._active or
                                    rep._cancel_flags)
                    t0 = time.perf_counter()
                    more = rep.step()
                    if had_work:
                        self._busy_s[rid] += time.perf_counter() - t0
            except Exception as e:          # noqa: BLE001 — must not die
                # a silently-dead worker would leave the replica "live"
                # for routing while nothing steps it, hanging every
                # handle it owns: surface the fault, pull the replica
                # out of routing, and unblock its callers
                self._fail_replica(rid, e)
                return
            self._refresh_gauges(rid)
            if self.disaggregated and \
                    getattr(rep, "role", "both") == "prefill":
                self._pump_handoffs(rid)
            inc = self._incident
            if inc is not None:
                inc.on_step_poll(self)
            if not more:
                time.sleep(0.001)           # idle: yield the core

    def _fail_replica(self, rid: int, exc: BaseException) -> None:
        """A replica's scheduler raised (worker thread death or a
        :class:`SimulatedCrash` in deterministic stepping): record the
        fault and run the crash protocol — :meth:`fail` pulls the
        replica out of routing and re-homes its live requests onto
        survivors, so streams continue on the same handles and only an
        exhausted re-home budget resolves a handle with
        :class:`RequestFailedError`."""
        logger.error(f"replica {rid} worker died: {exc!r} — failing it "
                     "out of routing and re-homing its requests")
        with self._fleet_lock:
            self._worker_errors[rid] = exc
        self.fail(rid)

    def fail(self, rid: int) -> int:
        """Mark replica ``rid`` crash-dead WITHOUT touching its engine
        (no drain, no demotion, no device program — its device state is
        not to be trusted), then re-home every live request it held
        (module docstring "Failure model"): host-side salvage
        (``ServingEngine.salvage`` — streamed tokens fold into resume
        prompts), re-route onto survivors with KV pulls from *their*
        host tiers, streams continuing on the SAME handles.  Requests
        whose re-home budget is exhausted (or with no live replica
        left) resolve their handles with a typed
        :class:`RequestFailedError`.  Idempotent per the state table in
        the module docstring: ``fail`` on a failed replica and ``fail``
        on a drained (quiesced) replica are loud no-ops for the re-home
        step.  Returns the number of requests re-homed."""
        with self._fleet_lock:
            if rid in self._failed:
                logger.warning(f"fail({rid}): replica already failed — "
                               "no-op")
                return 0
            was_drained = rid in self._drained
            self._failed.add(rid)
            self._drained.add(rid)          # out of routing and stepping
            self._c_failures.inc()
            self.timeline.instant("replica_fail", replica=int(rid),
                                  was_drained=bool(was_drained))
            if was_drained:
                # drain already quiesced it: nothing lives there to
                # re-home; recording the death still matters (excluded
                # as a pull source, readmit must clear the fault)
                logger.warning(
                    f"fail({rid}): replica was already drained "
                    "(quiesced) — marking failed, nothing to re-home")
                items = []
            else:
                salvage = getattr(self.replicas[rid], "salvage", None)
                try:
                    with self._locks[rid]:
                        items = salvage() if salvage is not None \
                            else self._fallback_salvage(rid)
                except Exception as e:      # noqa: BLE001 — must not hang
                    # the crash left even the HOST bookkeeping
                    # inconsistent (exactly the state the paged audits
                    # exist to catch) and salvage tripped over it: the
                    # resume contexts are unrecoverable, but the one
                    # inviolable rule stands — no caller may hang.
                    # Resolve every handle the corpse references LOUDLY
                    # and scrub the queue/active maps so the audit's
                    # zero-uids invariant holds.
                    logger.error(
                        f"fail({rid}): salvage itself failed ({e!r}) — "
                        "resolving the replica's handles as failed "
                        "instead of re-homing")
                    items = self._scrub_unsalvageable(rid, e)
                for r in self._live():
                    # migrated sessions promote on the survivors next —
                    # same warm-up as drain (no-op without a host tier)
                    with self._locks[r]:
                        self.replicas[r].warm_swap_programs()
            rehomed = self._rehome_items(items, rid)
        self._refresh_gauges(rid)
        # the flight recorder dumps AFTER the crash protocol, outside
        # every lock (its gather re-takes them): the bundle captures the
        # post-salvage fleet — re-home records included — at the exact
        # point replay's probe will compare against
        inc = self._incident
        if inc is not None:
            inc.on_replica_fail(self, rid, self._worker_errors.get(rid))
        return rehomed

    def _fallback_salvage(self, rid: int) -> list:
        """Salvage for duck-typed replicas without a ``salvage()``
        method (called under the replica lock): extract ACTIVE requests
        too, not just the queue — an active request left behind would
        hang its caller forever, the exact failure mode ``fail`` exists
        to prevent.  Streamed tokens fold into the resume prior exactly
        like the engine's own salvage; the replica's deeper state is its
        own problem (it is dead)."""
        rep = self.replicas[rid]
        items = []
        for slot in sorted(rep._active,
                           key=lambda s: getattr(rep._active[s],
                                                 "admit_seq", s)):
            st = rep._active[slot]
            items.append(_PendingItem(
                req=st.req,
                prior=list(getattr(st, "prior", [])) +
                list(getattr(st, "out", [])),
                priority=getattr(st, "priority", 0),
                slo_class=getattr(st, "slo_class", None),
                eos=getattr(st, "eos", None),
                handle=getattr(st, "handle", None)))
        rep._active.clear()
        items.extend(rep._pending.drain())
        return items

    def _scrub_unsalvageable(self, rid: int, exc: BaseException) -> list:
        """Last-resort crash path (salvage raised): fail every handle
        the dead replica still references with a typed
        :class:`RequestFailedError` and empty its queue/active maps —
        the engine's deeper state stays garbage (it is dead and needs a
        restart before readmit), but no caller hangs and the router
        audit's zero-uids invariant holds.  Returns an empty hand-off
        list."""
        rep = self.replicas[rid]
        with self._locks[rid]:
            victims = [it.handle for it in rep._pending] + \
                [st.handle for st in rep._active.values()]
            uids = [it.req.uid for it in rep._pending] + \
                [st.req.uid for st in rep._active.values()]
            rep._pending.drain()
            rep._active.clear()
            live = getattr(rep, "_live_uids", None)
            if live is not None:
                live.clear()
        for uid, handle in zip(uids, victims):
            self._c_req_failed.inc()
            self.timeline.instant("request_failed", uid=str(uid),
                                  reason="salvage failed")
            if handle is not None and not handle.done:
                handle._on_fail(RequestFailedError(
                    uid, f"replica {rid} crashed and salvage failed: "
                         f"{exc!r}"))
            self._handles.pop(uid, None)
        return []

    def _handoff_item(self, item, flow_arg: str) -> Tuple[int, str, int]:
        """Route one handed-off pending item onto a live replica — the
        shared half of BOTH hand-off protocols (drain re-route and
        crash re-home, so a change to hand-off routing can never apply
        to one and silently desynchronize the other): route + policy
        counters, optional KV pull, flow start, enqueue via
        ``_submit_item`` with the ROUTER's canceller (no window where a
        cancel routes around the fleet locks straight into a bare
        engine), handle-map update, gauges.  The caller emits its own
        protocol event (``route resumed=True`` / ``rehome``).  Returns
        ``(replica, policy_used, depth)``."""
        prompt_eff = np.concatenate(
            [item.req.prompt, np.asarray(item.prior, np.int32)]) \
            if item.prior else item.req.prompt
        # an item with prior tokens already prefilled somewhere (its KV
        # pulls or recomputes as a short resume) — it needs a decode-
        # capable target; a never-admitted queue item still needs prefill
        new_rid, why, depth = self._route(
            prompt_eff, need="decode" if item.prior else "prefill")
        if why == "affinity":
            self._c_aff.inc()
        else:
            self._c_bal.inc()
        if self.kv_pull:
            self._maybe_pull(new_rid, prompt_eff)
        with self._locks[new_rid]:
            self._start_route_flow(new_rid, item.req.uid,
                                   **{flow_arg: True})
            self.replicas[new_rid]._submit_item(item,
                                                canceller=self.cancel)
        if item.handle is not None:
            self._handles[item.req.uid] = (item.handle, new_rid)
        self._refresh_gauges(new_rid)
        return new_rid, why, depth

    def _rehome_items(self, items, from_rid: int) -> int:
        """Re-home salvaged requests onto live replicas (under the fleet
        lock): route each (affinity first — its session prefix may be
        resident or pullable on a survivor), pull KV, and hand the item
        over with its handle intact.  Per-request ``max_rehomes``
        budgets and a replica-less fleet resolve handles with
        :class:`RequestFailedError` — LOUD failure, never a hang."""
        rehomed = 0
        for item in items:
            uid = item.req.uid
            n = self._rehomes.get(uid, 0)
            live = self._live()
            if not live or n >= self.max_rehomes:
                reason = "no live replica left to take it" if not live \
                    else f"re-home budget exhausted ({n} prior re-homes)"
                self._c_req_failed.inc()
                self.timeline.instant("request_failed", uid=str(uid),
                                      reason=reason)
                logger.error(f"request {uid!r} permanently failed: "
                             f"{reason}")
                if item.handle is not None:
                    item.handle._on_fail(RequestFailedError(uid, reason))
                self._handles.pop(uid, None)
                continue
            self._rehomes[uid] = n + 1
            new_rid, why, depth = self._handoff_item(item, "rehomed")
            self._c_rehomed.inc()
            rehomed += 1
            self.timeline.instant("rehome", uid=str(uid),
                                  src=int(from_rid), dst=int(new_rid),
                                  policy=why, depth_blocks=int(depth),
                                  prior_tokens=len(item.prior))
        return rehomed

    def _pump_handoffs(self, rid: int) -> int:
        """Drain a prefill worker's parked handoffs and route each onto
        a decode-capable replica (the tentpole's handoff state machine):
        take under the replica lock, release, then run the shared
        hand-off protocol under the fleet lock — the same
        ``_handoff_item`` path as drain/re-home, so the resume travels
        as an ordinary integrity-checked KV pull from the prefill
        worker's host tier.  A fleet with no live decode-capable replica
        left resolves the handles LOUDLY (:class:`RequestFailedError`)
        instead of bouncing requests between prefill workers forever.
        Returns handoffs routed."""
        rep = self.replicas[rid]
        take = getattr(rep, "take_handoffs", None)
        if take is None:
            return 0
        with self._locks[rid]:
            items = take()
        if not items:
            return 0
        routed = 0
        with self._fleet_lock:
            for item in items:
                uid = item.req.uid
                try:
                    new_rid, why, depth = self._handoff_item(item,
                                                             "handoff")
                except RuntimeError as e:
                    self._c_req_failed.inc()
                    self.timeline.instant("request_failed", uid=str(uid),
                                          reason=str(e))
                    logger.error(f"handoff of {uid!r} failed: {e}")
                    if item.handle is not None:
                        item.handle._on_fail(
                            RequestFailedError(uid, str(e)))
                    self._handles.pop(uid, None)
                    continue
                routed += 1
                self._c_handoffs.inc()
                self.timeline.instant(
                    "handoff", uid=str(uid), src=int(rid),
                    dst=int(new_rid), policy=why,
                    depth_blocks=int(depth),
                    prior_tokens=len(item.prior))
        return routed

    def arm_faults(self, plan) -> FaultInjector:
        """Arm a chaos plan fleet-wide (``serving/faults.py``): builds
        the :class:`FaultInjector` (or takes one) and binds a per-replica
        view onto every engine.  Returns the injector — its ``report()``
        reconciles injected faults against recovery telemetry.  Zero
        cost until armed; :meth:`disarm_faults` restores it."""
        inj = plan if isinstance(plan, FaultInjector) else \
            FaultInjector(plan if isinstance(plan, FaultPlan)
                          else FaultPlan.from_json(plan))
        self._injector = inj
        for rid, rep in enumerate(self.replicas):
            arm = getattr(rep, "arm_faults", None)
            if arm is not None:
                arm(inj.bind(rid))
        return inj

    def disarm_faults(self) -> None:
        self._injector = None
        for rep in self.replicas:
            arm = getattr(rep, "arm_faults", None)
            if arm is not None:
                arm(None)

    def stop(self) -> None:
        self._stop_evt.set()
        for t in self._threads:
            t.join(timeout=10)
        self._threads = []
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None

    def serve(self, requests: Sequence[Request],
              eos_token_id: Optional[int] = None) -> Dict[Any, np.ndarray]:
        """Batch convenience over ``submit`` + ``step``: route the whole
        trace, drive to completion (worker threads when ``start()``-ed,
        else synchronous stepping), return ``uid -> [prompt +
        completion]`` like ``ServingEngine.serve``."""
        requests = list(requests)
        if not requests:
            return {}
        handles = [self.submit(r, eos_token_id=eos_token_id)
                   for r in requests]
        if self.threaded and not self._threads:
            self.start()
        if self._threads:
            return {h.uid: h.result() for h in handles}
        while self.step():
            pass
        return {h.uid: h.result(timeout=0) for h in handles}

    # ---------------------------------------------------------- drain/readmit
    def drain(self, rid: int) -> int:
        """Drain replica ``rid``: stop routing/stepping it, quiesce its
        engine (sessions preempt + demote to its host tier), and re-route
        every handed-off request onto live replicas — each with a KV pull
        for its chain, so the migrated sessions resume with zero prefix
        recompute.  Token streams continue on the original handles.
        Returns the number of requests handed off.  Idempotent per the
        module-docstring state table: draining an already-drained or
        crash-failed replica is a loud no-op, never a crash."""
        with self._fleet_lock:
            if rid in self._failed:
                logger.warning(
                    f"drain({rid}): replica is crash-failed (already "
                    "out of rotation; readmit after a restart instead) "
                    "— no-op")
                return 0
            if rid in self._drained:
                logger.warning(f"drain({rid}): replica already drained "
                               "— no-op")
                return 0
            if len(self._live()) <= 1:
                raise RuntimeError(
                    f"cannot drain replica {rid}: it is the last live "
                    "replica (readmit another first)")
            self._drained.add(rid)          # stop routing + worker first
            with self._locks[rid]:
                items = self.replicas[rid].drain()
            for r in self._live():
                # migrated sessions promote on the survivors next —
                # compile their swap pair NOW so no admission pays it
                # (no-op without a host tier / when already compiled)
                with self._locks[r]:
                    self.replicas[r].warm_swap_programs()
            self._c_drains.inc()
            self.timeline.instant("drain", replica=int(rid),
                                  handoff=len(items))
            for item in items:
                new_rid, why, depth = self._handoff_item(item, "resumed")
                self.timeline.instant("route", uid=str(item.req.uid),
                                      replica=int(new_rid), policy=why,
                                      depth_blocks=int(depth),
                                      resumed=True)
        self._refresh_gauges(rid)
        return len(items)

    def readmit(self, rid: int) -> None:
        """Re-admit a drained replica to routing and stepping.  Its host
        tier still holds whatever was demoted at drain time — affinity
        routing (and KV pulls from it) resume naturally.  A crash-failed
        replica (worker died) clears its fault record AND gets a fresh
        worker thread in threaded mode — the caller is asserting the
        replica is healthy again, and re-routing to a replica nothing
        steps would recreate the hang the crash guard exists to stop."""
        respawn = False
        with self._fleet_lock:
            if rid not in self._drained:
                logger.warning(f"readmit({rid}): replica is live — no-op")
                return
            self._drained.discard(rid)
            self._failed.discard(rid)       # fault record dies with this
            respawn = self._worker_errors.pop(rid, None) is not None \
                and bool(self._threads)
            self._c_readmits.inc()
        if respawn:
            t = threading.Thread(target=self._worker, args=(rid,),
                                 name=f"serving-replica-{rid}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        self.timeline.instant("readmit", replica=int(rid))

    @property
    def drained(self) -> List[int]:
        return sorted(self._drained)

    @property
    def failed(self) -> List[int]:
        """Crash-failed replicas (⊆ :attr:`drained`): out of rotation,
        excluded as KV-pull sources, cleared only by :meth:`readmit`."""
        return sorted(self._failed)

    # -------------------------------------------------------- fleet telemetry
    def _all_locks(self):
        """Fleet lock + every replica lock, ascending (the drain/cancel
        order — workers only ever hold one replica lock, so no cycle):
        a federation pass must not race a step() inserting new series."""
        from contextlib import ExitStack

        stack = ExitStack()
        stack.enter_context(self._fleet_lock)
        for lock in self._locks:
            stack.enter_context(lock)
        return stack

    def fleet_registry(self) -> MetricsRegistry:
        """ONE federated registry over the router registry plus every
        replica registry (``telemetry/aggregate.federate``): every series
        labeled ``replica=`` ("router", "0", "1", ...), histograms
        additionally bucket-wise-summed under ``replica="fleet"``.
        Rebuilt per call — a snapshot, not a live view."""
        sources = OrderedDict()
        sources["router"] = self.metrics
        for i, rep in enumerate(self.replicas):
            sources[str(i)] = rep.metrics
        with self._all_locks():
            return federate(sources)

    def fleet_metrics_text(self) -> str:
        """Prometheus text exposition of :meth:`fleet_registry` (the
        ``/metrics`` endpoint body)."""
        return self.fleet_registry().prometheus_text()

    def fleet_snapshot(self) -> Dict[str, Any]:
        """JSON fleet snapshot (the ``/stats`` endpoint body): router
        stats, the per-class SLO report, and the federated registry
        snapshot."""
        with self._all_locks():
            return {"stats": self.stats(),
                    "slo": self.slo_report(),
                    "metrics": self.fleet_registry().snapshot()}

    def merged_trace(self) -> Dict[str, Any]:
        """ONE Chrome trace document over the router ring plus every
        replica ring — router = pid 0, replica *i* = pid *i*+1, all
        timestamps re-based onto the earliest ring epoch — so a routed
        request's path (route flow -> admission -> per-slot span) and a
        kv_pull's source->target hop render as flow arrows across
        ``pid=replica`` lanes (the ``/trace`` endpoint body)."""
        sources = [("router", self.timeline)] + \
            [(f"replica {i}", rep.timeline)
             for i, rep in enumerate(self.replicas)]
        with self._all_locks():
            return merge_chrome_traces(sources)

    def dump_merged_trace(self, path: str) -> str:
        import json

        with open(path, "w") as f:
            json.dump(self.merged_trace(), f)
        return path

    def slo_report(self) -> Dict[str, Any]:
        """Fleet-wide per-``slo_class`` attainment (``telemetry/slo.py``):
        per-replica counts sum, TTFT/TPOT histograms merge bucket-wise,
        attainment and burn rate recompute from the merged totals."""
        return merged_slo_report([rep._slo for rep in self.replicas])

    def start_metrics_server(self, port: int = 0,
                             host: str = "127.0.0.1") -> MetricsServer:
        """Start the live exposition server (``telemetry/server.py``)
        over this fleet: ``/metrics`` = federated Prometheus text,
        ``/stats`` = fleet snapshot JSON, ``/trace`` = merged Chrome
        trace.  Scrapes run on the server thread and take the fleet +
        replica locks briefly — the scheduler never blocks on a slow
        scraper beyond one registry walk.  Idempotent; ``stop()`` shuts
        it down."""
        if self.metrics_server is None:
            self.metrics_server = MetricsServer(
                metrics_text=self.fleet_metrics_text,
                stats=self.fleet_snapshot,
                trace=self.merged_trace,
                host=host, port=port).start()
        return self.metrics_server

    # ------------------------------------------------------------------- stats
    def resolved_config(self) -> Dict[str, Any]:
        """The router's constructor kwargs, resolved and JSON-able — the
        fleet-level counterpart of ``ServingEngine.resolved_config()``:
        ``ReplicaRouter(replicas, **resolved_config())`` rebuilds an
        identically-configured router (incident bundles persist it so
        ``graft-replay`` reconstructs the fleet from artifacts alone)."""
        return {
            "policy": self.policy,
            "kv_pull": self.kv_pull,
            "threaded": self.threaded,
            "debug_checks": self.debug_checks,
            "trace_capacity": self.timeline.capacity,
            "max_queue_depth": self.max_queue_depth,
            "shed_classes": list(self.shed_classes),
            "burn_threshold": self.burn_threshold,
            "pull_retries": self.pull_retries,
            "pull_backoff_s": self.pull_backoff_s,
            "pull_timeout_s": self.pull_timeout_s,
            "max_rehomes": self.max_rehomes,
            "giant_context_tokens": self.giant_context_tokens,
        }

    def stats(self) -> Dict[str, Any]:
        """Router observability: routed/pull/drain counters, aggregate
        prefix hit rate over the fleet, per-replica load and busy time.
        Per-replica engine detail stays on ``replicas[i].stats()``."""
        per = []
        prompt_tokens = hit_tokens = gen_tokens = 0
        for rid, rep in enumerate(self.replicas):
            prompt_tokens += rep.prompt_tokens
            hit_tokens += rep.prefix_hit_tokens
            gen = int(rep._c_gen_tokens.value)
            gen_tokens += gen
            per.append({
                "replica": rid,
                "role": getattr(rep, "role", "both"),
                "drained": rid in self._drained,
                "blocks_in_use": rep._alloc.blocks_in_use,
                "queue_depth": len(rep._pending),
                "active": len(rep._active),
                "admitted": rep.admitted,
                "generated_tokens": gen,
                "prefix_cache_hit_rate": (
                    rep.prefix_hit_tokens / rep.prompt_tokens
                    if rep.prompt_tokens else 0.0),
                "compile_count": rep.compile_count,
                "compile_budget": rep.compile_budget,
                "busy_s": self._busy_s[rid],
                # optional protocol member (jax-free fakes skip it)
                "config": rep.resolved_config()
                if hasattr(rep, "resolved_config") else {},
            })
        return {
            "replicas": len(self.replicas),
            "policy": self.policy,
            "kv_pull": self.kv_pull,
            "drained": self.drained,
            "routed_affinity": int(self._c_aff.value),
            "routed_balance": int(self._c_bal.value),
            "kv_pulls": int(self._c_pulls.value),
            "kv_pull_blocks": int(self._c_pull_blocks.value),
            "kv_pull_bytes": int(self._c_pull_bytes.value),
            "kv_pull_retries": int(self._c_pull_retries.value),
            "drains": int(self._c_drains.value),
            "readmits": int(self._c_readmits.value),
            "handoffs": int(self._c_handoffs.value),
            "giant_context": int(self._c_giant.value),
            # failure/recovery surface (docs/reliability.md): crash
            # fails, re-homed/permanently-failed requests, sheds by class
            "failed": self.failed,
            "replica_failures": int(self._c_failures.value),
            "requests_rehomed": int(self._c_rehomed.value),
            "requests_failed": int(self._c_req_failed.value),
            "requests_shed": {cls: int(c.value)
                              for cls, c in sorted(self._c_shed.items())},
            "lock_order_checks": int(self._sanitizer.checks)
            if self._sanitizer is not None else 0,
            "lock_violations": int(self._sanitizer.violations)
            if self._sanitizer is not None else 0,
            "generated_tokens": gen_tokens,
            "prompt_tokens": prompt_tokens,
            "prefix_cache_hit_rate": (hit_tokens / prompt_tokens
                                      if prompt_tokens else 0.0),
            "busy_s": self.busy_seconds,
            "metrics_endpoint": self.metrics_server.url
            if self.metrics_server is not None else None,
            "per_replica": per,
        }
