"""Deterministic chaos harness for the serving fleet: a seeded,
JSON-replayable :class:`FaultPlan` injected through zero-cost hooks in
the router/engine stack.

The reference framework treats failure as a first-class input — its
``elasticity/`` layer exists so training survives host loss.  The
serving analogue needs the failures themselves to be *testable*: a
recovery path nobody can reproduce is a recovery path nobody can trust.
This module makes every failure mode the fleet defends against a
**deterministic, replayable event**, exactly like PR 13's
``ServingTrace`` made traffic replayable:

 - **replica crashes** at a chosen scheduler iteration
   (:class:`SimulatedCrash` raised from the victim's ``step()`` — the
   router or its worker thread converts it into
   ``ReplicaRouter.fail(rid)`` re-homing, ``serving/router.py``);
 - **transport faults** — transient or permanent failures injected into
   the swap/KV-pull transport ops (``demote`` / ``promote`` /
   ``export`` / ``import``) as
   :class:`~deepspeed_tpu.inference.paged.TransportError`; the engine's
   swap path and the router's cross-replica pull retry with bounded
   deterministic exponential backoff and fall back to local recompute
   on permanent failure;
 - **host-store corruption** — bit flips in
   :class:`~deepspeed_tpu.inference.paged.HostBlockStore` arena bytes,
   caught by the per-block checksums at every point bytes leave the
   arena (promotion staging / export / import) — corrupt KV is dropped
   and recomputed, never served;
 - **slow-replica stalls** — ``step()`` sleeps on schedule, so
   supervisor grace-tick handling ("slow", drains after grace) stays
   distinguishable from hard death ("dead", fails immediately).

**Zero-cost disarmed**: every injection point in the engine/router is a
single ``x is None`` predicate — arming a plan
(``ReplicaRouter.arm_faults`` / ``ServingEngine.arm_faults``) is the
only thing that changes behavior.  **Deterministic armed**: schedules
key off per-replica step counters (not wall clocks) and every random
draw comes from per-replica ``numpy`` Generator streams derived from
the plan seed, so the same plan against the same trace injects the
same faults at the same points — ``tests/unit/test_serving_faults.py``
replays a kill-one-of-two run and pins token-EXACT equality with the
fault-free twin.

:class:`RequestRejected` lives here too: the loud, typed result of
SLO-class-aware load shedding (``ReplicaRouter`` bounded admission —
docs/reliability.md "Shedding policy").
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..inference.paged import TransportError

__all__ = ["FaultPlan", "FaultInjector", "SimulatedCrash",
           "RequestRejected", "TransportError"]

#: transport ops a plan may target (the four swap/pull commit points)
TRANSPORT_OPS = ("demote", "promote", "export", "import")


class SimulatedCrash(RuntimeError):
    """A :class:`FaultPlan` killed this replica: raised out of
    ``ServingEngine.step()``; the router (or its worker thread) treats
    it exactly like a real worker death — ``fail(rid)`` re-homing."""

    def __init__(self, replica: int, step: int):
        super().__init__(
            f"replica {replica} crashed by the fault plan at its "
            f"scheduler iteration {step}")
        self.replica = int(replica)
        self.step = int(step)


class RequestRejected(RuntimeError):
    """The router shed this request at admission (bounded queue / SLO
    burn-rate protection): a loud, typed result instead of silent
    latency collapse.  ``slo_class`` is the class that absorbed the
    rejection (``batch`` first by policy), ``reason`` names the
    threshold that tripped."""

    def __init__(self, uid, slo_class: Optional[str], reason: str):
        super().__init__(
            f"request {uid!r} (slo_class={slo_class or 'standard'}) "
            f"rejected: {reason}")
        self.uid = uid
        self.slo_class = slo_class
        self.reason = reason


@dataclasses.dataclass
class FaultPlan:
    """A seeded, replayable fault schedule (JSON round-trippable like
    ``autotuning/trace.py ServingTrace``).

    crashes:    ``[{"replica": r, "at_step": k}]`` — raise
                :class:`SimulatedCrash` when replica ``r`` enters its
                ``k``-th scheduler iteration (1-based, counted per
                replica by the injector — independent of wall clock and
                of the other replicas' progress).
    stalls:     ``[{"replica": r, "at_step": k, "stall_s": s}]`` — sleep
                ``s`` seconds at iteration ``k`` (a slow replica, NOT a
                dead one: supervisors must keep draining these through
                the grace window, never hard-fail them).
    corruption: ``[{"replica": r, "at_step": k, "entries": n,
                "bits": b}]`` — flip ``b`` random bits in each of the
                ``n`` oldest resident (non-in-flight) host-tier entries
                at iteration ``k`` (positions drawn from the seeded
                per-replica stream).
    transport:  ``{"ops": [...], "transient_rate": p, "permanent_rate":
                q, "max_faults": n, "replicas": [..] | None}`` — each
                targeted transport call draws from the seeded stream:
                ``< q`` → permanent :class:`TransportError`, ``< q+p``
                → transient, at most ``n`` faults total per replica
                (``rate=1.0, max_faults=2`` = "exactly the first two
                calls fail", a fully deterministic schedule).
    """

    seed: int = 0
    crashes: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    stalls: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    corruption: List[Dict[str, Any]] = \
        dataclasses.field(default_factory=list)
    transport: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        for ev in self.crashes + self.stalls + self.corruption:
            if int(ev.get("at_step", 0)) < 1:
                raise ValueError(
                    f"fault event {ev} needs at_step >= 1 (steps are "
                    "1-based per-replica iteration counts)")
        bad = set(self.transport.get("ops", ())) - set(TRANSPORT_OPS)
        if bad:
            raise ValueError(
                f"unknown transport op(s) {sorted(bad)} — expected a "
                f"subset of {TRANSPORT_OPS}")

    # ------------------------------------------------------------ round trip
    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, doc: Dict[str, Any]) -> "FaultPlan":
        return cls(**doc)

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "FaultPlan":
        with open(path) as f:
            return cls.from_json(json.load(f))


class _ReplicaFaults:
    """A :class:`FaultInjector` bound to one replica id — the object the
    engine actually holds (``ServingEngine.arm_faults``), so every hook
    call carries its replica identity for free."""

    def __init__(self, injector: "FaultInjector", rid: int):
        self._inj = injector
        self.rid = int(rid)

    def on_step(self, engine) -> None:
        self._inj.on_step(self.rid, engine)

    def on_transport(self, op: str) -> None:
        self._inj.on_transport(self.rid, op)


class FaultInjector:
    """Executes a :class:`FaultPlan` deterministically across a fleet.

    ``bind(rid)`` returns the per-replica view an engine arms; the
    injector keeps per-replica step counters and seeded Generator
    streams (``default_rng([seed, rid, lane])``) so injection points
    depend only on (plan, per-replica call sequence) — never on wall
    clock or cross-replica interleaving.  ``report()`` returns what was
    actually injected, which the chaos bench and the corruption gate
    reconcile against the recovery/telemetry counters (e.g. corrupted
    entries == ``serving_checksum_failures_total`` when every corrupted
    chain is subsequently touched)."""

    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self._steps: Dict[int, int] = {}
        self._tfaults: Dict[int, int] = {}
        self._trng: Dict[int, np.random.Generator] = {}
        self._crng: Dict[int, np.random.Generator] = {}
        # injected-fault accounting (report())
        self.crashes_fired: List[Dict[str, int]] = []
        self.stalls_fired = 0
        self.transport_faults = {"transient": 0, "permanent": 0}
        self.corrupted_entries = 0
        self.corrupted_keys: List[bytes] = []

    def bind(self, rid: int) -> _ReplicaFaults:
        rid = int(rid)
        self._steps.setdefault(rid, 0)
        self._tfaults.setdefault(rid, 0)
        self._trng[rid] = np.random.default_rng(
            [int(self.plan.seed), rid, 1])
        self._crng[rid] = np.random.default_rng(
            [int(self.plan.seed), rid, 2])
        return _ReplicaFaults(self, rid)

    # ------------------------------------------------------------- schedules
    def on_step(self, rid: int, engine) -> None:
        step = self._steps.get(rid, 0) + 1
        self._steps[rid] = step
        for ev in self.plan.stalls:
            if int(ev["replica"]) == rid and int(ev["at_step"]) == step:
                self.stalls_fired += 1
                time.sleep(float(ev.get("stall_s", 0.05)))
        for ev in self.plan.corruption:
            if int(ev["replica"]) == rid and int(ev["at_step"]) == step:
                self.corrupted_entries += self._corrupt(
                    rid, engine, int(ev.get("entries", 1)),
                    int(ev.get("bits", 1)))
        for ev in self.plan.crashes:
            if int(ev["replica"]) == rid and int(ev["at_step"]) == step:
                self.crashes_fired.append({"replica": rid, "step": step})
                raise SimulatedCrash(rid, step)

    def on_transport(self, rid: int, op: str) -> None:
        t = self.plan.transport
        if not t or op not in t.get("ops", TRANSPORT_OPS):
            return
        only = t.get("replicas")
        if only is not None and rid not in [int(r) for r in only]:
            return
        if self._tfaults.get(rid, 0) >= int(t.get("max_faults", 1 << 30)):
            return
        u = float(self._trng[rid].random())
        q = float(t.get("permanent_rate", 0.0))
        p = float(t.get("transient_rate", 0.0))
        if u < q:
            self._tfaults[rid] = self._tfaults.get(rid, 0) + 1
            self.transport_faults["permanent"] += 1
            raise TransportError(op, transient=False,
                                 detail=f"injected on replica {rid}")
        if u < q + p:
            self._tfaults[rid] = self._tfaults.get(rid, 0) + 1
            self.transport_faults["transient"] += 1
            raise TransportError(op, transient=True,
                                 detail=f"injected on replica {rid}")

    def _corrupt(self, rid: int, engine, entries_n: int, bits: int) -> int:
        """Flip ``bits`` random bits in each of the ``entries_n`` oldest
        resident (non-in-flight) host-arena entries — the host-DRAM
        bit-flip model the checksum gate exists to catch."""
        store = getattr(engine, "_host", None)
        if store is None:
            return 0
        rng = self._crng[rid]
        _, entries = store.snapshot()
        victims = [(k, slot) for k, (slot, infl) in entries.items()
                   if not infl][:entries_n]
        for key, slot in victims:
            self.corrupted_keys.append(key)
            # distinct byte positions within one arena leaf: no two flips
            # can cancel, so every victim is GENUINELY corrupt and the
            # 100%-detection gate is well-posed
            arena = store.arenas[int(rng.integers(len(store.arenas)))]
            view = arena[slot].reshape(-1).view(np.uint8)
            n = min(max(1, bits), view.size)
            for idx in rng.choice(view.size, size=n, replace=False):
                view[int(idx)] ^= np.uint8(1 << int(rng.integers(8)))
        return len(victims)

    # --------------------------------------------------------------- report
    def report(self) -> Dict[str, Any]:
        return {
            # plan provenance: an incident bundle stores report() beside
            # fault_plan.json — the seed ties them together when bundles
            # from several chaos runs land in one out_dir
            "seed": int(self.plan.seed),
            "steps": dict(self._steps),
            "crashes_fired": list(self.crashes_fired),
            "stalls_fired": self.stalls_fired,
            "transport_faults": dict(self.transport_faults),
            "corrupted_entries": self.corrupted_entries,
        }
