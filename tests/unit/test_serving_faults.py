"""Fault-tolerant serving fleet (PR 15): deterministic chaos harness,
crash re-homing with KV salvage, integrity-checked + retried swap
transport, and SLO-aware load shedding.

Tier-1 (fast) coverage:
 - FaultPlan JSON round trip / validation; injector determinism.
 - block checksums: host-store integrity units, import rejection,
   corrupt-arena detection at promote with exact-parity recovery
   (corrupt KV is NEVER served — the corruption acceptance gate).
 - crash re-homing: a seeded SimulatedCrash kills one of two replicas
   mid-decode; every in-flight and pending request completes on the
   survivor with token output EXACTLY matching the fault-free run,
   zero hung handles, clean post-failure audits, per-replica compile
   budgets unchanged (the chaos parity acceptance gate), in fp32 and
   kv8 (bit-exact vs an unfaulted kv8 twin).
 - transport hardening: transient faults retry (counter ticks) with
   parity; permanent faults fall back to local recompute with parity.
 - typed failure: RequestFailedError on re-home exhaustion / empty
   fleet; RequestHandle timeout= raises TimeoutError instead of
   hanging forever.
 - shedding: bounded queue + burn-rate triggers reject batch-class
   work with typed RequestRejected; realtime is never shed.
 - replica state machine: drain/fail/readmit idempotent no-ops.
 - supervisor: hard probe failure (capacity < 0) fails immediately —
   no grace window — and recovery re-admits.
 - audit_router failure-state invariant fault injections.
"""

import json
import time

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.invariants import (PagedStateError,
                                               audit_router)
from deepspeed_tpu.inference.paged import (HostBlockStore, TransportError,
                                           block_checksum)
from deepspeed_tpu.inference.serving import (Request, RequestFailedError,
                                             RequestHandle, ServingEngine,
                                             _PendingItem, _PendingQueue)
from deepspeed_tpu.serving import (FaultInjector, FaultPlan, ReplicaRouter,
                                   RequestRejected, RouterSupervisor,
                                   SimulatedCrash)
from tiny import sequential


# ---------------------------------------------------------------- fixtures
_SRV_KW = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
               prefill_batch=2, debug_checks=True)


def _mk_engine(spec, params, **cfg_extra):
    config = {"dtype": "fp32", "tensor_parallel": {"tp_size": 1}}
    config.update(cfg_extra)
    return deepspeed_tpu.init_inference(spec, config=config, params=params)


def _mk_srv(spec, params, **kw):
    merged = dict(_SRV_KW, host_blocks=32, swap_batch=4)
    merged.update(kw)
    return ServingEngine(_mk_engine(spec, params,
                                    **merged.pop("cfg_extra", {})),
                         **merged)


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


# -------------------------------------------------------------- plan units
def test_fault_plan_roundtrip_and_validation(tmp_path):
    plan = FaultPlan(seed=7,
                     crashes=[{"replica": 1, "at_step": 12}],
                     stalls=[{"replica": 0, "at_step": 3, "stall_s": 0.01}],
                     corruption=[{"replica": 0, "at_step": 5,
                                  "entries": 2, "bits": 3}],
                     transport={"ops": ["export", "import"],
                                "transient_rate": 1.0, "max_faults": 2})
    path = plan.save(str(tmp_path / "plan.json"))
    loaded = FaultPlan.load(path)
    assert loaded == plan
    assert FaultPlan.from_json(json.loads(
        json.dumps(plan.to_json()))) == plan
    with pytest.raises(ValueError, match="at_step"):
        FaultPlan(crashes=[{"replica": 0, "at_step": 0}])
    with pytest.raises(ValueError, match="transport op"):
        FaultPlan(transport={"ops": ["teleport"]})


def test_injector_determinism():
    """Same plan, same per-replica call sequence => identical injected
    faults — the property the chaos parity gate rests on."""
    plan = FaultPlan(seed=11, transport={"ops": ["export"],
                                         "transient_rate": 0.5,
                                         "permanent_rate": 0.1,
                                         "max_faults": 100})

    def drive(inj):
        v = inj.bind(0)
        pattern = []
        for _ in range(40):
            try:
                v.on_transport("export")
                pattern.append("ok")
            except TransportError as e:
                pattern.append("t" if e.transient else "p")
        return pattern

    a, b = drive(FaultInjector(plan)), drive(FaultInjector(plan))
    assert a == b
    assert "t" in a and "ok" in a
    # replicas draw from independent streams: binding 1 differs from 0
    inj = FaultInjector(plan)
    inj.bind(0), inj.bind(1)


def test_stall_fires_and_counts():
    plan = FaultPlan(seed=0, stalls=[{"replica": 0, "at_step": 2,
                                      "stall_s": 0.03}])
    inj = FaultInjector(plan)
    v = inj.bind(0)

    class _E:                                 # no host tier needed
        _host = None

    t0 = time.perf_counter()
    v.on_step(_E())                           # step 1: nothing
    fast = time.perf_counter() - t0
    t0 = time.perf_counter()
    v.on_step(_E())                           # step 2: stall
    slow = time.perf_counter() - t0
    assert inj.stalls_fired == 1 and slow > max(fast, 0.02)


# ---------------------------------------------------------- checksum units
def test_block_checksum_and_host_store_integrity():
    store = HostBlockStore(4, [((2, 3), np.float32), ((2,), np.int8)])
    blk = [np.arange(6, dtype=np.float32).reshape(2, 3),
           np.array([1, -2], np.int8)]
    s = block_checksum(blk)
    assert s == block_checksum([b.copy() for b in blk])   # content only
    assert store.put(b"k0", blk) is not None
    assert store.checksum_of(b"k0") == s and store.verify(b"k0")
    # corrupt the arena in place: verify catches it, drop_corrupt frees
    store.arenas[0][store._entries[b"k0"].slot].reshape(-1)[0] += 1.0
    assert not store.verify(b"k0")
    free_before = len(store._free)
    store.drop_corrupt(b"k0")
    assert not store.has(b"k0") and len(store._free) == free_before + 1


def test_import_chain_rejects_corrupt_blocks():
    src = HostBlockStore(4, [((3,), np.float32)])
    for i in range(3):
        src.put(f"k{i}".encode(), [np.full(3, float(i), np.float32)])
    keys = [f"k{i}".encode() for i in range(3)]
    blocks = src.export_chain(keys)
    sums = src.export_checksums(keys)
    # flip a byte of block 1 "in transit"
    blocks[1][0].view(np.uint8)[0] ^= 0xFF
    dst = HostBlockStore(4, [((3,), np.float32)])
    stored = dst.import_chain(keys, blocks, checksums=sums)
    assert stored == 1                        # stops AT the corrupt block
    assert dst.has(keys[0]) and not dst.has(keys[1])
    assert dst.checksum_rejects == 1
    # without checksums the (corrupt) bytes would have been accepted —
    # the wire sums are what makes the transfer end-to-end verified
    dst2 = HostBlockStore(4, [((3,), np.float32)])
    assert dst2.import_chain(keys, blocks) == 3


def test_engine_import_counts_checksum_failures(tiny):
    spec, cfg, engine = tiny
    a = _mk_srv(spec, engine.params)
    b = _mk_srv(spec, engine.params)
    _, reqs = _session_trace(cfg, n=3)
    a.serve(reqs)
    a.drain()                                 # chains demote to a's tier
    keys, blocks, sums = a.host_chain_export(reqs[0].prompt, 0,
                                             len(reqs[0].prompt) - 1)
    assert keys and len(sums) == len(keys)
    blocks[0][0].reshape(-1).view(np.uint8)[3] ^= 0x10
    stored = b.host_chain_import(keys, blocks, checksums=sums)
    assert stored == 0
    assert b.stats()["num_blocks"] and \
        int(b._c_checksum_fail.value) == 1


# ------------------------------------------------ corruption (acceptance)
def test_corruption_detected_100pct_and_never_served(tiny):
    """Acceptance gate: injected bit-flips in host-tier arena bytes are
    detected by checksum on promote in 100% of injected cases and
    recovered via recompute — corrupt KV is never served (exact token
    parity throughout)."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=4, max_new=8)
    seq = sequential(engine, reqs)
    srv = _mk_srv(spec, engine.params)
    outs = srv.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], seq[r.uid])
    srv.drain()                               # host tier = the only copy
    n_host = len(srv._host)
    assert n_host >= 3
    inj = FaultInjector(FaultPlan(
        seed=3, corruption=[{"replica": 0, "at_step": 1,
                             "entries": n_host, "bits": 3}]))
    srv.arm_faults(inj.bind(0))
    # re-serve every session: every corrupted chain is probed, so every
    # injected corruption must be caught at the promote staging gate
    outs2 = srv.serve([Request(uid=f"r{r.uid}", prompt=r.prompt,
                               max_new_tokens=r.max_new_tokens)
                       for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(outs2[f"r{r.uid}"], seq[r.uid])
    srv.arm_faults(None)
    assert inj.corrupted_entries == n_host
    assert int(srv._c_checksum_fail.value) == inj.corrupted_entries
    names = [e["name"] for e in srv.timeline.events()]
    assert "checksum_fail" in names
    assert srv.compile_count <= srv.compile_budget


def test_patrol_scrub_finds_shadowed_corruption(tiny):
    """A corrupt block shadowed behind an EARLIER corrupt block in its
    chain is never probed by traffic (the run truncates before it);
    scrub_host_tier() is the patrol scrubber that still finds and drops
    it, counted into the same checksum-failure telemetry."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=3, max_new=6)
    srv = _mk_srv(spec, engine.params)
    srv.serve(reqs)
    srv.drain()
    n_host = len(srv._host)
    assert n_host >= 2
    inj = FaultInjector(FaultPlan(
        seed=9, corruption=[{"replica": 0, "at_step": 1,
                             "entries": n_host, "bits": 2}]))
    srv.arm_faults(inj.bind(0))
    srv.serve([Request(uid="probe", prompt=reqs[0].prompt,
                       max_new_tokens=4)])   # may only hit one chain
    srv.arm_faults(None)
    gate_hits = int(srv._c_checksum_fail.value)
    scrubbed = srv.scrub_host_tier()
    assert gate_hits + scrubbed == inj.corrupted_entries
    assert srv.scrub_host_tier() == 0         # idempotent: all clean now
    for key in inj.corrupted_keys:
        assert not srv._host.has(key) or srv._host.verify(key)


# ------------------------------------------------- crash re-homing (gate)
def _chaos_fleet(spec, params, n=2, **router_kw):
    return ReplicaRouter([_mk_srv(spec, params) for _ in range(n)],
                         debug_checks=True, **router_kw)


def test_crash_rehoming_token_exact_midflight(tiny):
    """Acceptance gate: a seeded FaultPlan kills one of two replicas
    mid-decode; every in-flight and pending request completes on the
    survivor with token output EXACTLY matching the fault-free run,
    zero hung handles, clean post-failure audits (debug_checks on every
    step), and per-replica compile budgets unchanged."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=9, max_new=12)
    seq = sequential(engine, reqs)

    # fault-free twin first (identical fleet construction)
    free = _chaos_fleet(spec, engine.params)
    outs_free = free.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs_free[r.uid], seq[r.uid])

    router = _chaos_fleet(spec, engine.params)
    plan = FaultPlan(seed=0, crashes=[{"replica": 1, "at_step": 4}])
    inj = router.arm_faults(plan)
    handles = [router.submit(r) for r in reqs]
    for _ in range(3):                       # let decode start fleet-wide
        router.step()
    assert any(rep._active for rep in router.replicas)
    while router.step():
        pass
    assert inj.report()["crashes_fired"] == [{"replica": 1, "step": 4}]
    for r, h in zip(reqs, handles):
        assert h.status == "finished", (r.uid, h.status)   # zero hung
        np.testing.assert_array_equal(h.result(timeout=0), seq[r.uid],
                                      err_msg=f"uid {r.uid}")
    st = router.stats()
    assert st["failed"] == [1] and st["replica_failures"] == 1
    assert st["requests_rehomed"] >= 1 and st["requests_failed"] == 0
    for p in st["per_replica"]:
        assert p["compile_count"] <= p["compile_budget"]
    names = {e["name"] for e in router.timeline.events()}
    assert {"replica_fail", "rehome"} <= names
    audit_router(router)                      # post-failure state green
    # the survivor owns every live uid; the corpse owns zero
    assert not router.replicas[1]._pending and \
        not router.replicas[1]._active


def test_crash_rehoming_kv8_bit_exact(tiny):
    """kv8 composition: the crash-recovered run matches an unfaulted
    kv8 twin bit-exactly (deterministic int8 codes + scales; the kv8
    lane of the chaos gate)."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=6, max_new=8)
    ref = ReplicaRouter([_mk_srv(spec, engine.params, quantize="kv8")
                         for _ in range(2)], debug_checks=True)
    ref_outs = ref.serve(reqs)

    router = ReplicaRouter([_mk_srv(spec, engine.params, quantize="kv8")
                            for _ in range(2)], debug_checks=True)
    router.arm_faults(FaultPlan(seed=0,
                                crashes=[{"replica": 0, "at_step": 3}]))
    handles = [router.submit(r) for r in reqs]
    while router.step():
        pass
    for r, h in zip(reqs, handles):
        assert h.status == "finished"
        np.testing.assert_array_equal(h.result(timeout=0),
                                      ref_outs[r.uid])
    assert router.stats()["replica_failures"] == 1


def test_crash_rehoming_resumes_streams_on_same_handles(tiny):
    """In-flight requests keep streaming on the SAME handle across the
    crash: tokens observed before the kill stand, the resume appends
    the identical continuation (greedy fold-in)."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=4, max_new=14)
    seq = sequential(engine, reqs)
    router = _chaos_fleet(spec, engine.params, n=2)
    router.arm_faults(FaultPlan(seed=0,
                                crashes=[{"replica": 0, "at_step": 6}]))
    handles = {r.uid: router.submit(r) for r in reqs}
    pre_crash: dict = {}
    for _ in range(6):
        router.step()
        for uid, h in handles.items():
            if h.tokens() and uid not in pre_crash:
                pre_crash[uid] = list(h.tokens())
    assert pre_crash                          # someone streamed pre-kill
    while router.step():
        pass
    for r in reqs:
        h = handles[r.uid]
        assert h.status == "finished"
        toks = h.tokens()
        np.testing.assert_array_equal(
            np.asarray(toks, np.int32),
            seq[r.uid][len(r.prompt):len(r.prompt) + len(toks)])
        if r.uid in pre_crash:                # prefix stood untouched
            assert toks[:len(pre_crash[r.uid])] == pre_crash[r.uid]


def test_crash_rehoming_salvages_survivor_kv(tiny):
    """Round-robin splits each session across both replicas, so when one
    dies the survivor already holds session prefixes — the re-homed
    resumes reuse them (prefix hits / pulls) instead of recomputing the
    world."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=8, max_new=10)
    seq = sequential(engine, reqs)
    router = ReplicaRouter([_mk_srv(spec, engine.params)
                            for _ in range(2)], policy="round_robin",
                           debug_checks=True)
    router.arm_faults(FaultPlan(seed=0,
                                crashes=[{"replica": 0, "at_step": 5}]))
    handles = [router.submit(r) for r in reqs]
    while router.step():
        pass
    for r, h in zip(reqs, handles):
        assert h.status == "finished"
        np.testing.assert_array_equal(h.result(timeout=0), seq[r.uid])
    survivor = router.replicas[1]
    assert survivor.prefix_hit_tokens > 0


def _sampled_reqs(cfg, n=8, max_new=12, seed=2, temperature=0.8):
    """Session trace with every odd request sampled (temperature/top-k/
    top-p + its own seed) and every even one greedy — the mixed stream
    the chaos gate must replay token-exactly."""
    rng = np.random.default_rng([seed, 1009])
    _, base = _session_trace(cfg, n=n, max_new=max_new, seed=seed)
    return [Request(uid=r.uid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens,
                    temperature=temperature if i % 2 else 0.0,
                    top_k=20 if i % 2 else 0,
                    top_p=0.95 if i % 2 else 1.0,
                    seed=int(rng.integers(1, 2 ** 31 - 1)) if i % 2 else 0)
            for i, r in enumerate(base)]


def test_crash_rehoming_token_exact_under_sampling(tiny):
    """PR 20 chaos gate: a replica dies mid-decode while serving SAMPLED
    requests; the re-homed resumes reproduce the exact sampled streams
    of a fault-free twin fleet.  Works because the sampler's PRNG is
    counter-based — the key at every emission position is a pure
    function of (request seed, tokens emitted), never of which replica
    or scheduling interleave drew it."""
    spec, cfg, engine = tiny
    reqs = _sampled_reqs(cfg)
    assert any(r.sampled for r in reqs) and any(not r.sampled for r in reqs)

    free = _chaos_fleet(spec, engine.params)
    outs_free = free.serve(reqs)

    router = _chaos_fleet(spec, engine.params)
    inj = router.arm_faults(
        FaultPlan(seed=0, crashes=[{"replica": 1, "at_step": 4}]))
    handles = [router.submit(r) for r in reqs]
    while router.step():
        pass
    assert inj.report()["crashes_fired"] == [{"replica": 1, "step": 4}]
    for r, h in zip(reqs, handles):
        assert h.status == "finished", (r.uid, h.status)
        np.testing.assert_array_equal(h.result(timeout=0),
                                      outs_free[r.uid],
                                      err_msg=f"uid {r.uid}")
    st = router.stats()
    assert st["requests_rehomed"] >= 1 and st["requests_failed"] == 0
    audit_router(router)


def test_crash_rehoming_token_exact_sampled_spec(tiny):
    """Sampled speculative lane under crash: the n-gram proposer plus
    rejection verifier re-homes token-exactly too (the resume backs up
    to re-emit through the verify program's RESIDUAL-salt draws)."""
    spec, cfg, engine = tiny
    reqs = _sampled_reqs(cfg, n=6, max_new=10, seed=5, temperature=0.6)
    mk = lambda: _mk_srv(spec, engine.params, spec_tokens=2)  # noqa: E731
    free = ReplicaRouter([mk() for _ in range(2)], debug_checks=True)
    outs_free = free.serve(reqs)

    router = ReplicaRouter([mk() for _ in range(2)], debug_checks=True)
    router.arm_faults(
        FaultPlan(seed=0, crashes=[{"replica": 0, "at_step": 4}]))
    handles = [router.submit(r) for r in reqs]
    while router.step():
        pass
    for r, h in zip(reqs, handles):
        assert h.status == "finished", (r.uid, h.status)
        np.testing.assert_array_equal(h.result(timeout=0),
                                      outs_free[r.uid],
                                      err_msg=f"uid {r.uid}")
    assert router.stats()["replica_failures"] == 1


# ------------------------------------------------------ transport faults
def test_transient_pull_faults_retry_with_parity(tiny):
    spec, cfg, engine = tiny
    prefixes, reqs = _session_trace(cfg)
    seq = sequential(engine, reqs)
    router = _chaos_fleet(spec, engine.params, pull_retries=4)
    inj = router.arm_faults(FaultPlan(
        seed=5, transport={"ops": ["export"], "transient_rate": 1.0,
                           "max_faults": 2}))
    outs = router.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], seq[r.uid])
    # force a migration pull (drain the session's home replica)
    p0 = prefixes[0]
    depth = [rep.affinity_probe(np.concatenate([p0, [0]]))
             for rep in router.replicas]
    rid0 = int(np.argmax([d["device_blocks"] + d["host_blocks"]
                          for d in depth]))
    router.drain(rid0)
    rng = np.random.default_rng(7)
    cont = Request(uid="cont", prompt=np.concatenate(
        [p0, rng.integers(0, cfg.vocab_size, 5)]), max_new_tokens=6)
    sc = engine.generate(cont.prompt[None, :], max_new_tokens=6)[0]
    out = router.serve([cont])
    np.testing.assert_array_equal(out["cont"], sc)
    st = router.stats()
    assert st["kv_pull_retries"] >= 1          # transient faults retried
    assert st["kv_pulls"] >= 1                 # ...and the pull landed
    assert inj.report()["transport_faults"]["transient"] >= 1


def test_permanent_pull_fault_falls_back_to_recompute(tiny):
    spec, cfg, engine = tiny
    prefixes, reqs = _session_trace(cfg, n=6)
    seq = sequential(engine, reqs)
    router = _chaos_fleet(spec, engine.params)
    router.arm_faults(FaultPlan(
        seed=6, transport={"ops": ["export"], "permanent_rate": 1.0,
                           "max_faults": 1000}))
    outs = router.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], seq[r.uid])
    router.drain(0)
    rng = np.random.default_rng(9)
    cont = Request(uid="cont", prompt=np.concatenate(
        [prefixes[0], rng.integers(0, cfg.vocab_size, 4)]),
        max_new_tokens=5)
    sc = engine.generate(cont.prompt[None, :], max_new_tokens=5)[0]
    out = router.serve([cont])                 # recompute, exact anyway
    np.testing.assert_array_equal(out["cont"], sc)
    assert router.stats()["kv_pulls"] == 0


def test_engine_swap_transport_fault_drops_demotion(tiny):
    """Engine-internal transport hardening: a permanent demote fault
    drops the demotion (contents recomputable), a permanent promote
    fault falls back to prefill recompute — parity holds either way."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=4, max_new=8)
    seq = sequential(engine, reqs)
    srv = _mk_srv(spec, engine.params)
    srv.serve(reqs)
    srv.drain()
    assert len(srv._host) > 0
    inj = FaultInjector(FaultPlan(
        seed=1, transport={"ops": ["promote"], "permanent_rate": 1.0,
                           "max_faults": 1000}))
    srv.arm_faults(inj.bind(0))
    outs = srv.serve([Request(uid="p0", prompt=reqs[0].prompt,
                              max_new_tokens=8)])
    np.testing.assert_array_equal(outs["p0"], seq[0])
    assert srv.stats()["swap_in"] == 0         # promotion never ran
    srv.arm_faults(None)


# ------------------------------------------------------- typed failures
def test_request_failed_error_when_no_survivor(tiny):
    """fail() on the only replica: nothing can re-home, so handles
    resolve LOUDLY with RequestFailedError — never a hang."""
    spec, cfg, engine = tiny
    # prompts past a prefill call's budget (2 x 16): none has a token yet
    _, reqs = _session_trace(cfg, n=3, prefix_len=40)
    router = ReplicaRouter([_mk_srv(spec, engine.params)],
                           debug_checks=True)
    handles = [router.submit(r) for r in reqs]
    router.step()
    rehomed = router.fail(0)
    assert rehomed == 0
    for h in handles:
        assert h.status == "failed" and h.done
        with pytest.raises(RequestFailedError, match="no live replica"):
            h.result(timeout=0)
        assert h.next_token(timeout=0) is None
    st = router.stats()
    assert st["requests_failed"] == len(reqs)
    assert st["requests_rehomed"] == 0
    audit_router(router)


def test_rehome_budget_exhaustion_fails_typed(tiny):
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=2)
    router = _chaos_fleet(spec, engine.params, max_rehomes=0)
    handles = [router.submit(r) for r in reqs]
    victims = {rid for rid in range(2)
               if router.replicas[rid]._pending}
    for rid in victims:
        router.fail(rid)
    reasons = []
    for h in handles:
        assert h.status == "failed"
        with pytest.raises(RequestFailedError) as ei:
            h.result(timeout=0)
        reasons.append(ei.value.reason)
    # a zero budget fails typed immediately (the second victim's request
    # may instead see "no live replica" once both replicas are dead)
    assert any("budget exhausted" in r for r in reasons)
    assert router.stats()["requests_failed"] == len(reqs)


def test_handle_timeout_params(tiny):
    """Satellite: result()/next_token() raise TimeoutError on a positive
    expired timeout instead of blocking forever; timeout=0 stays the
    non-blocking poll (None = nothing new)."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=1)
    srv = ServingEngine(engine, **_SRV_KW)
    h = srv.submit(reqs[0])
    with pytest.raises(TimeoutError, match="streamed nothing"):
        h.next_token(timeout=0.02)
    with pytest.raises(TimeoutError, match="still queued"):
        h.result(timeout=0.02)
    assert h.next_token(timeout=0) is None     # poll semantics unchanged
    while srv.step():
        pass
    assert h.status == "finished"
    assert h.result(timeout=0) is not None
    # after completion a positive timeout returns tokens then None
    assert h.next_token(timeout=0.05) is not None


# ------------------------------------------------------------- shedding
def test_shedding_bounded_queue_rejects_batch_not_realtime(tiny):
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=9, max_new=4)
    router = ReplicaRouter([ServingEngine(_mk_engine(spec, engine.params),
                                          **_SRV_KW) for _ in range(2)],
                           debug_checks=True, max_queue_depth=2)
    handles, shed = [], []
    for i, r in enumerate(reqs):
        cls = "batch" if i % 2 else "realtime"
        try:
            handles.append(router.submit(
                Request(uid=f"s{i}", prompt=r.prompt, max_new_tokens=4),
                slo_class=cls))
        except RequestRejected as e:
            assert e.slo_class == "batch"      # realtime never sheds
            assert "queue depth" in e.reason
            shed.append(e.uid)
    assert shed
    while router.step():
        pass
    assert all(h.status == "finished" for h in handles)
    st = router.stats()
    assert st["requests_shed"] == {"batch": len(shed)}
    assert "batch" not in {h.slo_class for h in handles
                           if h.slo_class == "realtime"}
    names = {e["name"] for e in router.timeline.events()}
    assert "shed" in names
    snap = router.metrics.snapshot()
    fam = snap["serving_requests_shed_total"]
    assert fam["type"] == "counter"
    assert [s["labels"]["slo_class"] for s in fam["series"]] == ["batch"]


def test_shedding_burn_rate_trigger(tiny):
    """An impossible realtime SLO target burns error budget on the first
    finished request; with burn_threshold set, batch-class work is then
    shed while realtime keeps admitting."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=6, max_new=4)
    srv = ServingEngine(
        _mk_engine(spec, engine.params), **_SRV_KW,
        slo_targets={"realtime": {"ttft_s": 1e-9, "tpot_s": 1e-9,
                                  "objective": 0.99}})
    router = ReplicaRouter([srv], debug_checks=True, burn_threshold=5.0)
    h = router.submit(Request(uid="rt", prompt=reqs[0].prompt,
                              max_new_tokens=4), slo_class="realtime")
    while router.step():
        pass
    assert h.status == "finished"              # burned its budget
    with pytest.raises(RequestRejected, match="burn rate"):
        router.submit(Request(uid="b0", prompt=reqs[1].prompt,
                              max_new_tokens=4), slo_class="batch")
    h2 = router.submit(Request(uid="rt2", prompt=reqs[2].prompt,
                               max_new_tokens=4), slo_class="realtime")
    while router.step():
        pass
    assert h2.status == "finished"
    assert router.stats()["requests_shed"] == {"batch": 1}


# ----------------------------------------------- state machine / salvage
def test_replica_state_machine_idempotence(tiny):
    spec, cfg, engine = tiny
    router = ReplicaRouter([ServingEngine(_mk_engine(spec, engine.params),
                                          **_SRV_KW) for _ in range(3)],
                           debug_checks=True)
    assert router.drain(0) == 0               # empty drain fine
    assert router.drain(0) == 0               # drained -> drain: no-op
    assert router.fail(0) == 0                # drained -> fail: marks
    assert router.failed == [0]
    assert router.fail(0) == 0                # failed -> fail: no-op
    assert router.drain(0) == 0               # failed -> drain: no-op
    router.readmit(0)
    assert router.failed == [] and router.drained == []
    router.readmit(0)                         # live -> readmit: no-op
    # fail a LIVE replica directly, then the state table again
    assert router.fail(1) == 0
    assert router.failed == [1]
    assert router.drain(1) == 0
    router.readmit(1)
    assert router.failed == []
    audit_router(router)


def test_salvage_folds_tokens_and_scrubs(tiny):
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=5, max_new=12)
    srv = _mk_srv(spec, engine.params)
    handles = [srv.submit(r) for r in reqs]
    for _ in range(4):
        srv.step()
    active_uids = [st.req.uid for st in srv._active.values()]
    assert active_uids
    streamed = {st.req.uid: len(st.prior) + len(st.out)
                for st in srv._active.values()}
    handles[-1].cancel()                      # a deferred cancel honored
    items = srv.salvage()
    uids = [it.req.uid for it in items]
    assert reqs[-1].uid not in uids           # cancelled, not salvaged
    assert handles[-1].status == "cancelled"
    # actives first, streamed tokens folded into prior
    for it in items:
        if it.req.uid in streamed:
            assert len(it.prior) == streamed[it.req.uid]
            assert it.handle is not None and not it.handle.done
    # the engine is scrubbed and consistent: no live uids, all blocks
    # released from slots, a fresh serve works
    assert not srv._pending and not srv._active and not srv._live_uids
    from deepspeed_tpu.analysis.invariants import audit_serving_engine
    audit_serving_engine(srv, srv._active)
    out = srv.serve([Request(uid="fresh", prompt=reqs[0].prompt,
                             max_new_tokens=4)])
    assert out["fresh"] is not None


# ----------------------------------------------------------- supervisor
class _FakeReplica:
    """Jax-free router stand-in (mirrors test_replica_router's fake)."""

    def __init__(self, block_size=8):
        self.block_size = block_size
        self._host = None
        self._prefix = None
        self._pending = _PendingQueue()
        self._active = {}
        self._alloc = type("A", (), {"blocks_in_use": 0})()
        self.prompt_tokens = 0
        self.prefix_hit_tokens = 0
        self.admitted = 0
        self.compile_count = 0
        self.compile_budget = 2
        self._c_gen_tokens = type("C", (), {"value": 0.0})()

    def affinity_probe(self, tokens):
        return {"device_blocks": 0, "host_blocks": 0,
                "blocks_in_use": 0,
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
        return self._pending.drain()

    def warm_swap_programs(self):
        pass


def test_supervisor_hard_probe_failure_fails_immediately():
    """Satellite: capacity < 0 (process GONE) skips the grace window
    entirely — fail(rid) re-homing runs on the same tick — while a soft
    miss (capacity 0) still waits out grace_ticks and drains."""
    a, b = _FakeReplica(), _FakeReplica()
    router = ReplicaRouter([a, b], kv_pull=False, debug_checks=True)
    handles = [router.submit(Request(uid=i, prompt=[1] * 4))
               for i in range(4)]
    live = {0: 1, 1: 1}
    sup = RouterSupervisor(router, lambda: live, grace_ticks=2)
    assert sup.tick() == {"drained": [], "failed": [], "readmitted": []}
    live = {0: 1, 1: -1}                      # hard death: process gone
    acts = sup.tick()
    assert acts["failed"] == [1] and acts["drained"] == []
    assert router.failed == [1]
    # everything re-homed onto the survivor, nothing dropped
    assert not b._pending
    while router.step():
        pass
    assert all(h.status == "finished" for h in handles)
    assert router.stats()["requests_rehomed"] >= 1
    # recovery (launcher restarted the worker): re-admitted, fault gone
    live = {0: 1, 1: 1}
    assert sup.tick()["readmitted"] == [1]
    assert router.failed == [] and router.drained == []
    # soft miss still drains via grace, never fails
    live = {0: 1, 1: 0}
    assert sup.tick() == {"drained": [], "failed": [], "readmitted": []}
    assert sup.tick() == {"drained": [], "failed": [], "readmitted": []}
    acts = sup.tick()
    assert acts["drained"] == [1] and router.failed == []
    live = {0: 1, 1: 1}
    assert sup.tick()["readmitted"] == [1]
    # an OPERATOR-drained replica that then hard-dies is failed (fault
    # recorded, excluded as pull source) but NOT claimed — recovery does
    # not auto-readmit over the operator's standing drain
    router.drain(1)
    live = {0: 1, 1: -1}
    assert sup.tick()["failed"] == [1]
    live = {0: 1, 1: 1}
    assert sup.tick()["readmitted"] == []
    assert router.failed == [1]               # operator's call to clear
    router.readmit(1)


def test_audit_router_failure_state_fault_injection():
    """Satellite: the failure-state invariant names its violation — a
    failed replica still owning uids, and a live handle mapped to a
    failed replica."""
    a, b = _FakeReplica(), _FakeReplica()
    router = ReplicaRouter([a, b], kv_pull=False)
    h = router.submit(Request(uid="x", prompt=[1] * 4))
    rid = router._handles["x"][1]
    audit_router(router)                      # green
    # a crash-failed replica still owning the request
    router._failed.add(rid)
    router._drained.add(rid)
    with pytest.raises(PagedStateError) as ei:
        audit_router(router)
    assert ei.value.invariant == "router-failure-state"
    assert "zero uids" in str(ei.value)
    # request moved off the corpse, but the handle map still points at
    # the failed replica: a live handle on a dead engine
    item = router.replicas[rid]._pending.drain()[0]
    router.replicas[1 - rid]._pending.push(item)
    with pytest.raises(PagedStateError) as ei:
        audit_router(router)
    assert ei.value.invariant == "router-failure-state"
    assert "crash-failed replica" in str(ei.value)
    # fix the map: green again
    router._handles["x"] = (h, 1 - rid)
    audit_router(router)


def test_fail_fallback_salvage_covers_active_requests():
    """Duck-typed replicas without salvage(): fail() must re-home their
    ACTIVE requests too, not just the queue — an active request left on
    the corpse hangs its caller and trips the failure-state audit."""
    bad, good = _FakeReplica(), _FakeReplica()
    router = ReplicaRouter([bad, good], policy="round_robin",
                           kv_pull=False, debug_checks=True)
    h_q = router.submit(Request(uid="queued", prompt=[1] * 4))
    h_a = router.submit(Request(uid="activ", prompt=[2] * 4))
    # move one request into the fake's ACTIVE map by hand (slot state
    # duck-type: req/prior/out/priority/handle)
    owner = router._handles["activ"][1]
    rep = router.replicas[owner]
    item = rep._pending.remove("activ")
    rep._active[0] = type("S", (), {
        "req": item.req, "prior": [], "out": [7, 8], "priority": 0,
        "slo_class": None, "eos": None, "handle": item.handle,
        "admit_seq": 0})()
    if owner != 0:                            # fail whichever owns it
        bad, good = good, bad
    router.fail(owner)
    audit_router(router)                      # corpse owns zero uids
    while router.step():
        pass
    assert h_a.status == "finished" and h_q.done
    # the streamed tokens folded into the resume prior
    assert router.stats()["requests_rehomed"] >= 1


def test_fail_survives_salvage_raising():
    """Last-resort crash path: if the crash left even the HOST
    bookkeeping inconsistent and salvage() itself raises, fail() must
    still resolve every handle LOUDLY (RequestFailedError) and leave
    the corpse with zero uids — the no-caller-ever-hangs rule holds
    even when the resume contexts are unrecoverable."""
    class _Unsalvageable(_FakeReplica):
        def salvage(self):
            raise AssertionError("decref on unowned block 7")

    bad, good = _Unsalvageable(), _FakeReplica()
    router = ReplicaRouter([bad, good], policy="round_robin",
                           kv_pull=False, debug_checks=True)
    handles = [router.submit(Request(uid=i, prompt=[1] * 4))
               for i in range(4)]
    on_bad = [h for h in handles if router._handles[h.uid][1] == 0]
    assert on_bad
    router.fail(0)
    for h in on_bad:
        assert h.status == "failed"
        with pytest.raises(RequestFailedError, match="salvage failed"):
            h.result(timeout=0)
    assert not bad._pending and not bad._active   # zero uids on corpse
    audit_router(router)                          # failure-state green
    while router.step():
        pass
    for h in handles:
        assert h.done                             # nobody hangs
    assert router.stats()["requests_failed"] == len(on_bad)


def test_simulated_crash_type():
    e = SimulatedCrash(2, 7)
    assert e.replica == 2 and e.step == 7 and "iteration 7" in str(e)


# ------------------------------------------- PR 17: disaggregated + nvme
def test_prefill_crash_mid_handoff_rehomes_token_exact(tiny):
    """Chaos composition (ISSUE 17): a disaggregated fleet (2 prefill +
    1 decode) loses a prefill worker mid-run — requests parked in its
    handoff buffer and requests still mid-prefill must re-home (salvage
    + host-chain pull on the decode side, re-prefill on the surviving
    prefill worker) with token output exactly matching the sequential
    reference, zero hung handles, and clean post-failure audits."""
    spec, cfg, engine = tiny
    _, reqs = _session_trace(cfg, n=9, max_new=12)
    seq = sequential(engine, reqs)

    roles = ("prefill", "prefill", "decode")
    router = ReplicaRouter(
        [_mk_srv(spec, engine.params, role=r) for r in roles],
        debug_checks=True)
    inj = router.arm_faults(
        FaultPlan(seed=0, crashes=[{"replica": 0, "at_step": 4}]))
    handles = [router.submit(r) for r in reqs]
    while router.step():
        pass
    assert inj.report()["crashes_fired"] == [{"replica": 0, "step": 4}]
    for r, h in zip(reqs, handles):
        assert h.status == "finished", (r.uid, h.status)
        np.testing.assert_array_equal(h.result(timeout=0), seq[r.uid],
                                      err_msg=f"uid {r.uid}")
    st = router.stats()
    assert st["failed"] == [0] and st["requests_failed"] == 0
    assert st["handoffs"] >= 1          # the disaggregated path ran
    audit_router(router)
    # the decode worker never prefills a PROMPT: every admission arrives
    # as a handoff/re-home whose committed blocks ride the host-chain
    # pull, so its recompute is bounded by the sub-block tail of each
    # prior (< block_size tokens per admission), never the prompt length
    dec = router.replicas[2]
    assert dec.role == "decode"
    ds = dec.stats()
    if ds["admitted"]:
        assert ds["resume_recompute_tokens"] <= \
            ds["admitted"] * dec.block_size


def test_last_decode_worker_lost_fails_handoffs_loudly(tiny):
    """If the fleet loses its LAST decode-capable replica, parked
    handoffs must resolve their handles with RequestFailedError — not
    bounce forever between prefill workers, not hang the caller."""
    spec, cfg, engine = tiny
    # prompts past a prefill call's budget (2 x 16): after one step every
    # request is still the prefill worker's
    _, reqs = _session_trace(cfg, n=4, max_new=8, prefix_len=40)
    router = ReplicaRouter(
        [_mk_srv(spec, engine.params, role=r)
         for r in ("prefill", "decode")], debug_checks=True)
    handles = [router.submit(r) for r in reqs]
    router.step()                        # prefill admits, maybe hands off
    router.fail(1)                       # the only decode worker dies
    while router.step():
        pass
    for h in handles:
        assert h.done                    # nobody hangs
        if h.status == "failed":
            with pytest.raises(RequestFailedError):
                h.result(timeout=0)
    assert router.stats()["requests_failed"] >= 1
    audit_router(router)


def test_nvme_bit_flip_caught_by_checksum_gate_unit(tmp_path):
    """NvmeBlockStore: a flipped byte in the spill file is caught at the
    NVMe exit — swap_in refuses the bytes, drops exactly that entry, and
    counts the reject."""
    from deepspeed_tpu.inference.paged import NvmeBlockStore

    specs = [((2, 8, 4), np.float32), ((2, 8, 4), np.float32)]
    store = NvmeBlockStore(4, specs, str(tmp_path / "spill.bin"))
    rng = np.random.default_rng(3)
    arrays = [rng.normal(size=s).astype(dt) for s, dt in specs]
    key = b"chain-key-0"
    assert store.swap_out(key, arrays, block_checksum(arrays))
    assert store.swap_in(key) is not None     # clean round trip
    with open(store.path, "r+b") as f:        # flip one payload byte
        f.seek(17)
        b = f.read(1)
        f.seek(17)
        f.write(bytes([b[0] ^ 0x40]))
    assert store.swap_in(key) is None
    assert store.checksum_rejects == 1
    assert not store.has(key)                 # entry dropped, slot freed
    assert store.blocks_in_use == 0
    store.close()


def test_nvme_corruption_recomputes_with_parity(tiny, tmp_path):
    """Engine-level checksum gate: corrupt the WHOLE spill file under a
    live engine, then resume a session whose prefix lives on NVMe — the
    promote path must reject the bytes, truncate the chain, recompute
    from tokens, and still serve token-exact output."""
    spec, cfg, engine = tiny
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 32) for _ in range(8)]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    seq = sequential(engine, reqs)
    srv = _mk_srv(spec, engine.params, slots=2, num_blocks=12,
                  host_blocks=8, swap_batch=2, nvme_blocks=32,
                  nvme_high_watermark=0.5,
                  nvme_path=str(tmp_path / "spill.bin"))
    outs = srv.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], seq[r.uid])
    assert srv.stats()["nvme_spills"] > 0
    with open(srv.nvme_path, "r+b") as f:     # scribble over every slot
        size = f.seek(0, 2)
        f.seek(0)
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    resumed = srv.serve([Request(uid="resume", prompt=prompts[0],
                                 max_new_tokens=6)])
    np.testing.assert_array_equal(resumed["resume"], seq[0])
    assert srv._host.nvme_checksum_rejects > 0
    srv.close()
