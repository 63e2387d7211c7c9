"""Multi-replica serving: the incremental ServingEngine API
(submit/step/cancel/drain + streaming handles) and the ReplicaRouter
(prefix-affinity routing, blocks-in-use balancing, cross-replica KV
pull, drain/re-admit, supervisor integration).

Tier-1 (fast) coverage:
 - incremental API parity: submit+step-driven serving is token-identical
   to the batch ``serve()`` wrapper and to sequential ``generate``;
   handles stream exactly the committed tokens.
 - priorities / SLO classes order admission; preemption resumes still
   jump the queue.
 - ``cancel()``: queued requests drop immediately, active slots release
   their blocks at the iteration boundary with a ``cancelled`` timeline
   event — audited (``debug_checks=True`` throughout).
 - ``serve([])`` returns ``{}`` without tracing anything.
 - router routing units on jax-free fake replicas (affinity/hints/
   balance/drained), drain/re-admit handoff, supervisor grace ticks,
   and the router-state fault injections.
 - e2e: 2-replica affinity parity vs sequential, drained-replica
   KV-pull migration with zero prefix recompute (fp32 exact and kv8
   bit-exact vs an unmigrated kv8 twin), mid-flight drain with no
   dropped requests, per-replica compile budgets unchanged (strict
   sentry).
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.invariants import (PagedStateError,
                                               audit_router)
from deepspeed_tpu.inference.serving import (Request, RequestHandle,
                                             SLO_PRIORITY, ServingEngine,
                                             _PendingItem, _PendingQueue)
from deepspeed_tpu.serving import ReplicaRouter, RouterSupervisor
from tiny import sequential


# ---------------------------------------------------------------- fixtures
def _mk_engine(spec, params, **cfg_extra):
    config = {"dtype": "fp32", "tensor_parallel": {"tp_size": 1}}
    config.update(cfg_extra)
    return deepspeed_tpu.init_inference(spec, config=config, params=params)


_SRV_KW = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
               prefill_batch=2, debug_checks=True)


def _session_trace(cfg, n=9, sessions=3, seed=0, prefix_len=24,
                   max_new=10):
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, cfg.vocab_size, prefix_len)
                for _ in range(sessions)]
    return prefixes, [
        Request(uid=i,
                prompt=np.concatenate(
                    [prefixes[i % sessions],
                     rng.integers(0, cfg.vocab_size,
                                  int(rng.integers(3, 8)))]),
                max_new_tokens=max_new)
        for i in range(n)]


# ------------------------------------------------- incremental engine API
def test_pending_queue_priority_and_front():
    q = _PendingQueue()
    mk = lambda uid, pri: _PendingItem(req=Request(uid=uid, prompt=[1]),
                                       prior=[], priority=pri)
    q.push(mk("a", 0))
    q.push(mk("b", 2))
    q.push(mk("c", 0))
    q.push(mk("d", 2))
    assert [it.req.uid for it in q] == ["b", "d", "a", "c"]
    # preemption resume jumps every class
    q.push_front(mk("resume", 0))
    assert q[0].req.uid == "resume"
    # a later high-priority push still queues BEHIND the resume
    q.push(mk("e", 5))
    assert [it.req.uid for it in q][:2] == ["resume", "e"]
    assert q.remove("c").req.uid == "c" and q.remove("zz") is None
    assert len(q.drain()) == 5 and not q


def test_incremental_submit_step_matches_serve(tiny):
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg)
    seq = sequential(engine, reqs)

    srv = ServingEngine(engine, **_SRV_KW)
    handles = [srv.submit(r) for r in reqs]
    assert all(h.status == "queued" for h in handles)
    while srv.step():
        pass
    for r, h in zip(reqs, handles):
        assert h.status == "finished"
        np.testing.assert_array_equal(h.result(timeout=0), seq[r.uid])
        # the stream is exactly the committed completion prefix
        toks = h.tokens()
        np.testing.assert_array_equal(
            np.asarray(toks, np.int32),
            seq[r.uid][len(r.prompt):len(r.prompt) + len(toks)])
        assert 1 <= len(toks) <= r.max_new_tokens
    # the batch wrapper over a fresh engine is identical
    srv2 = ServingEngine(engine, **_SRV_KW)
    outs = srv2.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], seq[r.uid])


def test_streaming_cursor_and_generated_tokens_counter(tiny):
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=2)
    srv = ServingEngine(engine, **_SRV_KW)
    h = srv.submit(reqs[0])
    got = []
    while not h.done or h.next_token(timeout=0) is not None:
        t = h.next_token(timeout=0)
        if t is None:
            if not srv.step() and h.done:
                break
        else:
            got.append(t)
    # drain any tail the loop's interleaving left unread
    while (t := h.next_token(timeout=0)) is not None:
        got.append(t)
    assert got == h.tokens()
    assert srv.stats()["generated_tokens"] == len(got)


def test_priority_and_slo_order_admission(tiny):
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=3)
    srv = ServingEngine(engine, **{**_SRV_KW, "slots": 1})
    log = []
    srv._admission_log = log
    low = srv.submit(Request(uid="low", prompt=reqs[0].prompt,
                             max_new_tokens=4), priority=0)
    slo = srv.submit(Request(uid="slo", prompt=reqs[1].prompt,
                             max_new_tokens=4), slo_class="interactive")
    high = srv.submit(Request(uid="high", prompt=reqs[2].prompt,
                              max_new_tokens=4), priority=9)
    assert slo.priority == SLO_PRIORITY["interactive"] == 1
    while srv.step():
        pass
    srv._admission_log = None
    assert [uid for uid, _ in log] == ["high", "slo", "low"]
    assert all(h.status == "finished" for h in (low, slo, high))


def test_cancel_pending_and_active(tiny):
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=4, max_new=20)
    srv = ServingEngine(engine, **{**_SRV_KW, "slots": 2})
    handles = [srv.submit(r) for r in reqs]
    # queued cancel (slots=2: request 3 cannot be admitted yet): immediate
    assert handles[3].cancel()
    assert handles[3].status == "cancelled"
    assert handles[3].result() is None
    srv.step()
    srv.step()
    # active cancel: lands at the next iteration boundary, frees blocks
    assert handles[0].status == "active"
    held_before = len(srv._held[0]) + len(srv._held[1])
    assert held_before > 0
    assert handles[0].cancel()
    assert handles[0].status == "active"   # not yet — boundary-deferred
    srv.step()                             # audit runs after the release
    assert handles[0].status == "cancelled"
    while srv.step():
        pass
    st = srv.stats()
    assert st["cancelled"] == 2
    assert handles[1].status == handles[2].status == "finished"
    names = [e["name"] for e in srv.timeline.events()]
    assert names.count("cancelled") == 2
    # unknown / finished uids refuse
    assert not srv.cancel("nope") and not handles[1].cancel()


def test_empty_serve_traces_nothing(tiny):
    spec, cfg, engine = tiny
    srv = ServingEngine(engine, **_SRV_KW)
    assert srv.serve([]) == {}
    assert srv.compile_count == 0 and srv.iterations == 0


def test_serve_on_busy_engine_raises(tiny):
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=2)
    srv = ServingEngine(engine, **_SRV_KW)
    srv.submit(reqs[0])
    with pytest.raises(RuntimeError, match="busy"):
        srv.serve([reqs[1]])
    while srv.step():
        pass


# ------------------------------------------------------- fake-replica units
class _FakeReplica:
    """Duck-typed stand-in for ServingEngine: enough surface for the
    router's routing/drain/audit logic, zero jax."""

    def __init__(self, block_size=8, depth_for=None):
        self.block_size = block_size
        self._host = None
        self._prefix = None
        self._pending = _PendingQueue()
        self._active = {}
        # the router's workers read it once a replica has nothing queued
        # or active: without it an idle fake's worker "died" by timing
        self._cancel_flags = set()
        self._alloc = type("A", (), {"blocks_in_use": 0})()
        self.depth_for = depth_for or (lambda prompt: 0)
        self.prompt_tokens = 0
        self.prefix_hit_tokens = 0
        self.admitted = 0
        self.compile_count = 0
        self.compile_budget = 2
        self._c_gen_tokens = type("C", (), {"value": 0.0})()
        self.drained_calls = 0

    def affinity_probe(self, tokens):
        return {"device_blocks": self.depth_for(tokens), "host_blocks": 0,
                "blocks_in_use": self._alloc.blocks_in_use,
                "queue_depth": len(self._pending),
                "active": len(self._active)}

    def submit(self, request, priority=0, slo_class=None,
               eos_token_id=None):
        handle = RequestHandle(request, priority=priority,
                               slo_class=slo_class)
        self._pending.push(_PendingItem(req=request, prior=[],
                                        priority=priority,
                                        handle=handle))
        return handle

    def _submit_item(self, item, canceller=None):
        if item.handle is not None and canceller is not None:
            item.handle.set_canceller(canceller)
        self._pending.push(item)

    def step(self):
        if self._pending:
            item = self._pending.popleft()
            if item.handle is not None:
                item.handle._on_finish(np.asarray(item.req.prompt))
        return bool(self._pending)

    def cancel(self, uid):
        item = self._pending.remove(uid)
        if item is not None and item.handle is not None:
            item.handle._on_cancel()
        return item is not None

    def drain(self):
        self.drained_calls += 1
        return self._pending.drain()

    def warm_swap_programs(self):
        pass


def test_router_routing_units_affinity_balance_drained():
    # replica 1 "has" a 2-block prefix for prompts starting with 7
    deep = _FakeReplica(depth_for=lambda p: 2 if int(p[0]) == 7 else 0)
    flat = _FakeReplica()
    router = ReplicaRouter([flat, deep], kv_pull=False)
    h = router.submit(Request(uid="a", prompt=[7] * 20))
    assert router._handles["a"][1] == 1          # deepest hit wins
    assert router.stats()["routed_affinity"] == 1
    # no hit anywhere: balance by blocks_in_use
    flat._alloc.blocks_in_use = 50
    router.submit(Request(uid="b", prompt=[1] * 20))
    assert router._handles["b"][1] == 1
    assert router.stats()["routed_balance"] == 1
    # hint table co-locates a same-prefix request with NO resident state
    router2 = ReplicaRouter([_FakeReplica(), _FakeReplica()],
                            kv_pull=False)
    router2.submit(Request(uid="s0", prompt=[3] * 20))
    rid0 = router2._handles["s0"][1]
    router2.submit(Request(uid="s1", prompt=([3] * 17) + [9, 9, 9]))
    assert router2._handles["s1"][1] == rid0
    assert router2.stats()["routed_affinity"] == 1
    # drained replicas never route; draining the last live one raises
    router3 = ReplicaRouter([_FakeReplica(), _FakeReplica()],
                            policy="round_robin", kv_pull=False)
    router3.drain(0)
    for i in range(3):
        router3.submit(Request(uid=f"r{i}", prompt=[1] * 4))
        assert router3._handles[f"r{i}"][1] == 1
    with pytest.raises(RuntimeError, match="last live"):
        router3.drain(1)
    router3.readmit(0)
    router3.drain(1)                              # now legal


def test_router_drain_hands_off_and_supervisor_grace():
    a, b = _FakeReplica(), _FakeReplica()
    router = ReplicaRouter([a, b], policy="round_robin", kv_pull=False,
                           debug_checks=True)
    handles = [router.submit(Request(uid=i, prompt=[1] * 4))
               for i in range(4)]
    queued_on_a = len(a._pending)
    assert queued_on_a + len(b._pending) == 4
    handed = router.drain(0)
    assert handed == queued_on_a and a.drained_calls == 1
    assert len(b._pending) == 4                  # nothing dropped
    assert all(router._handles[h.uid][1] == 1 for h in handles)
    # cancel routes to the CURRENT owner after handoff
    assert router.cancel(handles[0].uid)
    assert handles[0].status == "cancelled"
    while router.step():
        pass
    assert all(h.done for h in handles)

    # supervisor: grace ticks hold a transient probe miss, expiry drains,
    # return re-admits (only replicas the supervisor itself drained)
    live = {0: 1, 1: 1}
    sup = RouterSupervisor(router, lambda: live, grace_ticks=1)
    router.readmit(0)
    assert sup.tick() == {"drained": [], "failed": [], "readmitted": []}
    live = {0: 1, 1: 0}                          # replica 1 goes dark
    assert sup.tick()["drained"] == []           # within grace
    assert sup.tick()["drained"] == [1]          # grace expired
    assert router.drained == [1]
    live = {0: 1, 1: 1}
    assert sup.tick()["readmitted"] == [1]
    assert router.drained == []
    # a manual drain is NOT the supervisor's to re-admit
    router.drain(0)
    assert sup.tick()["readmitted"] == []
    assert router.drained == [0]
    router.readmit(0)
    # stale-claim regression: supervisor drains a down replica, the
    # OPERATOR re-admits it while still down — the supervisor's claim
    # must die with that readmit, so a later operator drain (replica
    # live) is not auto-resurrected
    live = {0: 1, 1: 0}
    sup.tick()
    assert sup.tick()["drained"] == [1]
    router.readmit(1)                            # operator, while down
    live = {0: 1, 1: 1}                          # ...and it comes back
    sup.tick()                                   # claim must be dead now
    router.drain(1)                              # operator maintenance
    assert sup.tick()["readmitted"] == []
    assert router.drained == [1]
    router.readmit(1)


def test_supervisor_survives_fleet_wide_outage():
    """Every replica going dark must not crash the supervision loop: the
    last live replica stays in rotation (nowhere to hand its sessions),
    and recovery re-admits the ones that did drain."""
    router = ReplicaRouter([_FakeReplica(), _FakeReplica()],
                           kv_pull=False)
    live = {0: 0, 1: 0}
    sup = RouterSupervisor(router, lambda: live, grace_ticks=0)
    acts = sup.tick()                            # both dark, same tick
    assert len(acts["drained"]) == 1             # second refuses, no raise
    assert sup.tick()["drained"] == []           # keeps ticking calmly
    assert len(router.drained) == 1
    live = {0: 1, 1: 1}
    assert len(sup.tick()["readmitted"]) == 1
    assert router.drained == []


def test_threaded_worker_failure_rehomes_not_silence():
    """A replica whose step() raises must not die silently: the router
    pulls it out of routing, records the fault, and RE-HOMES its
    requests onto survivors (PR 15 crash protocol) so every caller gets
    a result — nobody blocks forever, nothing is dropped."""
    class _Exploding(_FakeReplica):
        def step(self):
            raise RuntimeError("boom")

    bad, good = _Exploding(), _FakeReplica()
    router = ReplicaRouter([bad, good], policy="round_robin",
                           kv_pull=False, threaded=True)
    handles = [router.submit(Request(uid=i, prompt=[1] * 4))
               for i in range(4)]
    router.start()
    try:
        for h in handles:
            h.result(timeout=10)                 # nobody blocks forever
    finally:
        router.stop()
    assert 0 in router.drained and 0 in router.failed
    assert 0 in router._worker_errors
    assert all(h.status == "finished" for h in handles)
    st = router.stats()
    assert st["replica_failures"] == 1
    assert st["requests_rehomed"] >= 1 and st["requests_failed"] == 0
    router.readmit(0)                            # operator says healthy
    assert 0 not in router._worker_errors and router.failed == []


def test_router_audit_fault_injection():
    a, b = _FakeReplica(), _FakeReplica()
    router = ReplicaRouter([a, b], kv_pull=False)
    h = router.submit(Request(uid="x", prompt=[1] * 4))
    audit_router(router)                         # green
    # same uid queued on two replicas
    b._pending.push(_PendingItem(req=Request(uid="x", prompt=[1] * 4),
                                 prior=[]))
    with pytest.raises(PagedStateError) as ei:
        audit_router(router)
    assert ei.value.invariant == "router-request-uniqueness"
    b._pending.drain()
    # a drained replica still holding work
    router._drained.add(0)
    if not a._pending:                           # x may live on b
        a._pending.push(_PendingItem(req=Request(uid="y", prompt=[1]),
                                     prior=[]))
    with pytest.raises(PagedStateError) as ei:
        audit_router(router)
    assert ei.value.invariant in ("router-drain-quiesced",
                                  "router-request-uniqueness")
    router._drained.discard(0)
    a._pending.drain()
    # a live handle no replica holds
    for rep in (a, b):
        rep._pending.drain()
    assert h.status == "queued"
    with pytest.raises(PagedStateError) as ei:
        audit_router(router)
    assert ei.value.invariant == "router-request-uniqueness"


def test_router_ctor_validation():
    with pytest.raises(ValueError, match="at least one"):
        ReplicaRouter([])
    with pytest.raises(ValueError, match="policy"):
        ReplicaRouter([_FakeReplica()], policy="nope")
    with pytest.raises(ValueError, match="block_size"):
        ReplicaRouter([_FakeReplica(block_size=8),
                       _FakeReplica(block_size=16)])


# --------------------------------------------------------------- router e2e
def test_router_two_replicas_parity_and_affinity(tiny):
    spec, cfg, engine = tiny
    prefixes, reqs = _session_trace(cfg)
    seq = sequential(engine, reqs)
    srvs = [ServingEngine(_mk_engine(spec, engine.params), **_SRV_KW)
            for _ in range(2)]
    router = ReplicaRouter(srvs, debug_checks=True)
    outs = router.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], seq[r.uid],
                                      err_msg=f"uid {r.uid}")
    st = router.stats()
    # 3 sessions: at most one balance route per session, the rest follow
    # affinity (resident or hinted)
    assert st["routed_affinity"] >= len(reqs) - 3
    assert st["routed_balance"] <= 3
    # both replicas actually served traffic, budgets intact
    assert all(p["admitted"] > 0 for p in st["per_replica"])
    assert all(p["compile_count"] <= p["compile_budget"]
               for p in st["per_replica"])
    names = {e["name"] for e in router.timeline.events()}
    assert "route" in names


def _tiered_pair(spec, params, quantize=None):
    kw = dict(_SRV_KW, host_blocks=32, swap_batch=4)
    if quantize:
        kw["quantize"] = quantize
    return [ServingEngine(_mk_engine(spec, params), **kw)
            for _ in range(2)]


def test_kv_pull_migration_zero_recompute(tiny):
    """Acceptance: a drained replica's session resumes on a cold replica
    through the cross-replica KV pull with exact token parity and zero
    prefix recompute (only the mandatory sub-block tail prefills)."""
    spec, cfg, engine = tiny
    prefixes, reqs = _session_trace(cfg)
    seq = sequential(engine, reqs)
    router = ReplicaRouter(_tiered_pair(spec, engine.params),
                           debug_checks=True)
    outs = router.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], seq[r.uid])
    # find session 0's home replica and drain it
    p0 = prefixes[0]
    probe = [rep.affinity_probe(np.concatenate([p0, [0]]))
             for rep in router.replicas]
    depth = [p["device_blocks"] + p["host_blocks"] for p in probe]
    rid0 = int(np.argmax(depth))
    assert depth[rid0] == len(p0) // 8           # whole prefix resident
    router.drain(rid0)
    tgt = router.replicas[1 - rid0]
    rng = np.random.default_rng(7)
    cont = Request(uid="cont",
                   prompt=np.concatenate(
                       [p0, rng.integers(0, cfg.vocab_size, 5)]),
                   max_new_tokens=8)
    seq_cont = engine.generate(cont.prompt[None, :], max_new_tokens=8)[0]
    pt0, ht0 = tgt.prompt_tokens, tgt.prefix_hit_tokens
    out = router.serve([cont])
    np.testing.assert_array_equal(out["cont"], seq_cont)
    st = router.stats()
    assert st["kv_pulls"] >= 1
    assert st["kv_pull_blocks"] >= len(p0) // 8
    # zero prefix recompute: the cold replica prefilled ONLY the tail
    # past the last pullable full block
    plen = len(cont.prompt)
    recompute = (tgt.prompt_tokens - pt0) - (tgt.prefix_hit_tokens - ht0)
    assert recompute == plen - ((plen - 1) // 8) * 8
    assert tgt.compile_count <= tgt.compile_budget
    names = {e["name"] for e in router.timeline.events()}
    assert {"drain", "kv_pull", "route"} <= names
    # re-admit: the drained replica serves again
    router.readmit(rid0)
    out2 = router.serve([Request(uid="back", prompt=reqs[0].prompt,
                                 max_new_tokens=6)])
    np.testing.assert_array_equal(
        out2["back"],
        engine.generate(reqs[0].prompt[None, :], max_new_tokens=6)[0])


def test_kv8_pull_bit_exact_vs_unmigrated(tiny):
    """kv8 composition: pulled int8 codes + scale rows are bit-identical,
    so a migrated kv8 session matches an UNMIGRATED kv8 engine exactly
    (same quantized model — deterministic codes)."""
    spec, cfg, engine = tiny
    prefixes, reqs = _session_trace(cfg, n=6)
    kw = dict(_SRV_KW, host_blocks=32, swap_batch=4, quantize="kv8")
    ref = ServingEngine(_mk_engine(spec, engine.params), **kw)
    ref_outs = ref.serve(reqs)

    router = ReplicaRouter(_tiered_pair(spec, engine.params,
                                        quantize="kv8"),
                           debug_checks=True)
    outs = router.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], ref_outs[r.uid])
    p0 = prefixes[0]
    depth = [rep.affinity_probe(np.concatenate([p0, [0]]))
             for rep in router.replicas]
    rid0 = int(np.argmax([d["device_blocks"] + d["host_blocks"]
                          for d in depth]))
    router.drain(rid0)
    rng = np.random.default_rng(11)
    cont = Request(uid="qcont",
                   prompt=np.concatenate(
                       [p0, rng.integers(0, cfg.vocab_size, 4)]),
                   max_new_tokens=6)
    ref_cont = ref.serve([cont])
    out = router.serve([Request(uid="qcont", prompt=cont.prompt,
                                max_new_tokens=6)])
    np.testing.assert_array_equal(out["qcont"], ref_cont["qcont"])
    assert router.stats()["kv_pulls"] >= 1


def test_drain_midflight_no_requests_dropped(tiny):
    """Drain while requests are queued AND decoding: everything finishes
    on the surviving replica, token-exact, on the original handles."""
    spec, cfg, engine = tiny
    prefixes, reqs = _session_trace(cfg, n=6, max_new=16)
    seq = sequential(engine, reqs)
    router = ReplicaRouter(_tiered_pair(spec, engine.params),
                           debug_checks=True)
    handles = [router.submit(r) for r in reqs]
    for _ in range(3):
        router.step()
    victim = next(rid for rid in range(2)
                  if router.replicas[rid]._active or
                  router.replicas[rid]._pending)
    router.drain(victim)
    assert not router.replicas[victim]._active
    assert not router.replicas[victim]._pending
    while router.step():
        pass
    for r, h in zip(reqs, handles):
        assert h.status == "finished", (r.uid, h.status)
        np.testing.assert_array_equal(h.result(timeout=0), seq[r.uid],
                                      err_msg=f"uid {r.uid}")
    assert router.stats()["drains"] == 1


def test_threaded_router_smoke(tiny):
    """Worker-thread mode: same outputs, engines stepped only under
    their replica locks."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=4)
    seq = sequential(engine, reqs)
    srvs = [ServingEngine(_mk_engine(spec, engine.params), **_SRV_KW)
            for _ in range(2)]
    router = ReplicaRouter(srvs, threaded=True)
    try:
        outs = router.serve(reqs)
    finally:
        router.stop()
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], seq[r.uid])


def test_init_router_places_replicas_and_shares_weights(tiny):
    """Each replica occupies its own tp x sp device group (a replica left
    on a dp=n mesh would replicate weights, pool and compute on every
    chip — and on a TPU its Pallas kernels are refused); replicas whose
    groups coincide share ONE weight pytree."""
    import jax

    spec, cfg, _ = tiny
    kw = dict(config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
              replicas=2, slots=2, max_seq_len=64, block_size=8,
              prefill_chunk=16, debug_checks=True)
    deepspeed_tpu.comm.reset_topology()
    shared = deepspeed_tpu.init_router(spec, device_group=0, **kw)
    for a, b in zip(
            jax.tree_util.tree_leaves(shared.replicas[0].engine.params),
            jax.tree_util.tree_leaves(shared.replicas[1].engine.params)):
        assert a is b                      # one pytree, zero duplication
    deepspeed_tpu.comm.reset_topology()
    router = deepspeed_tpu.init_router(spec, **kw)
    assert len(router.replicas) == 2
    for i, rep in enumerate(router.replicas):
        want = {jax.devices()[i]}
        assert set(rep.engine.mesh.devices.flat) == want
        for leaf in jax.tree_util.tree_leaves((rep.engine.params,
                                               rep._cache)):
            assert leaf.devices() == want
    for a, b in zip(
            jax.tree_util.tree_leaves(router.replicas[0].engine.params),
            jax.tree_util.tree_leaves(router.replicas[1].engine.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 12),
                    max_new_tokens=5) for i in range(3)]
    outs = router.serve(reqs)
    seq = sequential(router.replicas[0].engine, reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], seq[r.uid])
