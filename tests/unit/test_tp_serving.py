"""Tensor-parallel paged serving: the KV pool and paged-attention ops
shard over the mesh ``tp`` axis (KV-head dim) with token-exact parity.

Tier-1 (fast) CPU-sim coverage on the 8-device mesh (conftest):
 - tp=1 vs tp=4 exact-token parity: plain chunked, prefix-heavy,
   speculative (n-gram), and under preemption pressure.
 - per-chip pool placement: ``addressable_shards`` carry ``HKV/tp`` heads
   and the sharding survives a full serve (the compiled programs hand the
   pool back with the same layout they received).
 - compile contract under tp: 2 programs plain, <= 3 speculative.
 - GQA head-divisibility: HKV < tp auto-falls-back to the replicated
   layout (parity intact); ``shard_kv=True`` then raises instead; a
   divisible GQA pool (tp=2, HKV=2) shards.
 - ``stats()`` KV footprint: ``kv_pool_bytes_per_chip`` scales 1/tp.

The scheduler (allocator, prefix trie, block tables) is host-side and
head-sharding-invariant, so admission order and compile counts are
bit-identical across tp degrees — the parity tests exercise exactly that.

Every trace here runs with ``debug_checks=True``: the recompile sentry
enforces the compile budget at trace time and the paged-state invariants
are audited every scheduler iteration (``analysis/``), so each parity
test doubles as a retrace + bookkeeping regression test.
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2, llama
from tiny import assert_sequential


def _mk_engine(tp, cfg):
    deepspeed_tpu.comm.reset_topology()
    return deepspeed_tpu.init_inference(
        gpt2.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": tp}})


@pytest.fixture(scope="module")
def tiny_cfg(tiny):
    return tiny[1]


@pytest.fixture(scope="module")
def tp1_engine(tiny):
    return tiny[2]


@pytest.fixture(scope="module")
def tp4_engine(tiny_cfg):
    return _mk_engine(4, tiny_cfg)


def _trace(cfg, n, prefix_len=24, seed=0, tail=(3, 10), max_new=(2, 10)):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, cfg.vocab_size,
                                              int(rng.integers(*tail)))]),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


def _serve_pair(e1, e4, cfg, seed, **srv_kw):
    """Serve the same trace at tp=1 and tp=4; return both result dicts and
    the two engines' ServingEngines."""
    kw = dict(slots=4, max_seq_len=128, block_size=8, prefill_chunk=16,
              prefill_batch=2, debug_checks=True)
    kw.update(srv_kw)
    s1 = ServingEngine(e1, **kw)
    s4 = ServingEngine(e4, **kw)
    reqs = _trace(cfg, 6, seed=seed)
    r1 = s1.serve(reqs)
    r4 = s4.serve(_trace(cfg, 6, seed=seed))   # fresh Request objects
    return r1, r4, s1, s4


def test_tp4_parity_prefix_heavy_and_pool_shards(tp1_engine, tp4_engine,
                                                 tiny_cfg):
    """Acceptance: tp=4 serving is token-exact vs tp=1 (and vs sequential
    generate) on a prefix-heavy trace; the pool's per-chip shard is HKV/4
    heads before AND after the serve; compile contract stays 2 programs."""
    r1, r4, s1, s4 = _serve_pair(tp1_engine, tp4_engine, tiny_cfg, seed=0)
    assert s4.kv_sharded and s4.tp_degree == 4
    hkv = tiny_cfg.num_heads
    for leaf in (s4._cache["k"], s4._cache["v"]):
        assert leaf.shape[2] == hkv
        for shard in leaf.addressable_shards:
            assert shard.data.shape[2] == hkv // 4
    assert_sequential(tp1_engine, _trace(tiny_cfg, 6, seed=0), r1, r4)
    assert s4.compile_count == 1 + len(s4._rungs), s4.compiled_programs
    # scheduler state is head-sharding-invariant: identical counters
    assert s4.prefix_hit_tokens == s1.prefix_hit_tokens
    assert s4.decode_steps == s1.decode_steps


def test_tp4_parity_speculative_and_compile_contract(tp1_engine, tp4_engine,
                                                     tiny_cfg):
    """Speculative (n-gram) serving under tp=4: token-exact vs tp=1 and
    the <= 3-program contract holds unchanged (2 in n-gram mode)."""
    r1, r4, s1, s4 = _serve_pair(tp1_engine, tp4_engine, tiny_cfg, seed=1,
                                 spec_tokens=3)
    for uid in r1:
        np.testing.assert_array_equal(r1[uid], r4[uid], err_msg=f"uid {uid}")
    assert s4.compile_count <= 2 + len(s4._rungs), s4.compiled_programs
    assert s4.compile_count == s1.compile_count
    assert s4.spec_rounds == s1.spec_rounds


def test_tp4_parity_under_preemption(tp1_engine, tp4_engine, tiny_cfg):
    """Block pressure (preemption + recompute) resolves identically at any
    tp degree — the allocator never sees head counts."""
    kw = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=32,
              prefill_batch=2, num_blocks=12, debug_checks=True)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, tiny_cfg.vocab_size, 17) for _ in range(5)]
    s1 = ServingEngine(tp1_engine, **kw)
    s4 = ServingEngine(tp4_engine, **kw)
    r1 = s1.serve([Request(uid=i, prompt=p, max_new_tokens=28)
                   for i, p in enumerate(prompts)])
    r4 = s4.serve([Request(uid=i, prompt=p, max_new_tokens=28)
                   for i, p in enumerate(prompts)])
    assert s4.preempted > 0 and s4.preempted == s1.preempted
    for uid in r1:
        np.testing.assert_array_equal(r1[uid], r4[uid], err_msg=f"uid {uid}")


def test_tp4_kv8_parity_and_sharded_scale_table(tp1_engine, tp4_engine,
                                                tiny_cfg):
    """int8 KV (quantize="kv8") composes with the tp head-shard with
    EXACT token parity across degrees: per-token-vector scales are
    head-local, so each chip quantizes its own shard to bit-identical
    codes/scales, and the scale table (``ps`` [L, NB, HKV, bs]) shards
    over the same head dim as the codes — the 8-device CI job's quant
    case."""
    r1, r4, s1, s4 = _serve_pair(tp1_engine, tp4_engine, tiny_cfg, seed=2,
                                 quantize="kv8")
    for uid in r1:
        np.testing.assert_array_equal(r1[uid], r4[uid], err_msg=f"uid {uid}")
    assert s4.kv_sharded
    hkv = tiny_cfg.num_heads
    for rec in (s4._cache["k"], s4._cache["v"]):
        for name, head_dim in (("qp", 2), ("ps", 2)):
            assert rec[name].shape[head_dim] == hkv
            for shard in rec[name].addressable_shards:
                assert shard.data.shape[head_dim] == hkv // 4, name
    st1, st4 = s1.stats(), s4.stats()
    assert st4["kv_dtype"] == "int8" and st4["kv_scale_bytes"] > 0
    assert st4["kv_pool_bytes"] == st1["kv_pool_bytes"]
    assert st4["kv_pool_bytes_per_chip"] == st1["kv_pool_bytes"] // 4
    assert s4.compile_count == 1 + len(s4._rungs), s4.compiled_programs


def test_tp4_tiered_kv_parity_per_shard_transfers(tp1_engine, tp4_engine,
                                                  tiny_cfg):
    """Tiered KV (host-DRAM offload) composes with the tp head-shard:
    demotion's ``device_get`` assembles per-addressable-shard and
    promotion's ``device_put`` re-shards the staged buffer, so the swap
    round trip is byte-exact at any degree — tp=4 tokens are BIT-identical
    to the tp=1 tiered run (and swap schedules match: the scheduler never
    sees head counts).  kv8 composes on top with the same exactness."""
    kw = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
              prefill_batch=2, num_blocks=10, host_blocks=64, swap_batch=4,
              debug_checks=True)
    reqs = _trace(tiny_cfg, 6, seed=3, max_new=(20, 28))
    s1 = ServingEngine(tp1_engine, **kw)
    s4 = ServingEngine(tp4_engine, **kw)
    r1 = s1.serve(reqs)
    r4 = s4.serve(_trace(tiny_cfg, 6, seed=3, max_new=(20, 28)))
    st1, st4 = s1.stats(), s4.stats()
    assert s4.kv_sharded
    assert st4["swap_out"] > 0 and st4["swap_in"] > 0
    assert (st4["swap_out"], st4["swap_in"]) == \
        (st1["swap_out"], st1["swap_in"])
    assert s4.compile_count == s4.compile_budget == 3 + len(s4._rungs)
    for uid in r1:
        np.testing.assert_array_equal(r1[uid], r4[uid], err_msg=f"uid {uid}")
    sq1 = ServingEngine(tp1_engine, quantize="kv8", **kw)
    sq4 = ServingEngine(tp4_engine, quantize="kv8", **kw)
    q1 = sq1.serve(_trace(tiny_cfg, 6, seed=3, max_new=(20, 28)))
    q4 = sq4.serve(_trace(tiny_cfg, 6, seed=3, max_new=(20, 28)))
    assert sq4.stats()["swap_out"] > 0
    for uid in q1:
        np.testing.assert_array_equal(q1[uid], q4[uid], err_msg=f"uid {uid}")


def test_shard_kv_false_forces_replicated(tp4_engine):
    srv = ServingEngine(tp4_engine, slots=2, max_seq_len=64, block_size=8,
                        shard_kv=False)
    assert not srv.kv_sharded
    leaf = srv._cache["k"]
    for shard in leaf.addressable_shards:
        assert shard.data.shape == leaf.shape      # fully replicated


def test_stats_kv_footprint_scales_with_tp(tp1_engine, tp4_engine):
    kw = dict(slots=2, max_seq_len=64, block_size=8)
    st1 = ServingEngine(tp1_engine, **kw).stats()
    st4 = ServingEngine(tp4_engine, **kw).stats()
    assert st1["tp_degree"] == 1 and not st1["kv_sharded"]
    assert st4["tp_degree"] == 4 and st4["kv_sharded"]
    assert st1["kv_pool_bytes"] == st4["kv_pool_bytes"]
    assert st1["kv_pool_bytes_per_chip"] == st1["kv_pool_bytes"]
    assert st4["kv_pool_bytes_per_chip"] * 4 == st4["kv_pool_bytes"]
    assert tuple(st4["kv_pool_shape"]) == tuple(st1["kv_pool_shape"])


def test_gqa_indivisible_heads_fall_back_or_raise():
    """llama-tiny has HKV=2: tp=4 cannot shard it — auto mode serves
    replicated with parity intact, shard_kv=True raises naming the counts."""
    deepspeed_tpu.comm.reset_topology()
    cfg = llama.LlamaConfig.tiny()
    engine = deepspeed_tpu.init_inference(
        llama.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 4}})
    srv = ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2,
                        debug_checks=True)
    assert not srv.kv_sharded and srv.tp_degree == 4
    prompt = np.arange(10) % cfg.vocab_size
    res = srv.serve([Request(uid=0, prompt=prompt, max_new_tokens=5)])
    want = engine.generate(prompt[None, :], max_new_tokens=5)[0]
    np.testing.assert_array_equal(res[0], want)
    with pytest.raises(ValueError, match="KV head count .2. does not divide"):
        ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                      shard_kv=True)


def test_gqa_divisible_heads_shard():
    """tp=2 divides llama-tiny's HKV=2: the GQA pool shards (1 head/chip)
    and decode stays token-exact."""
    deepspeed_tpu.comm.reset_topology()
    cfg = llama.LlamaConfig.tiny()
    engine = deepspeed_tpu.init_inference(
        llama.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 2}})
    srv = ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2,
                        debug_checks=True)
    assert srv.kv_sharded and srv.tp_degree == 2
    assert srv._cache["k"].addressable_shards[0].data.shape[2] == 1
    prompt = np.arange(12) % cfg.vocab_size
    res = srv.serve([Request(uid=0, prompt=prompt, max_new_tokens=6)])
    want = engine.generate(prompt[None, :], max_new_tokens=6)[0]
    np.testing.assert_array_equal(res[0], want)


def test_draft_pool_shards_with_target(tp4_engine, tiny_cfg):
    """A draft model whose HKV divides tp gets a sharded draft pool; the
    fused-prefill + rollout + verify trace stays token-exact vs the tp=1
    n-gram reference and within the 3-program contract."""
    dcfg = gpt2.GPT2Config(vocab_size=tiny_cfg.vocab_size, max_seq_len=128,
                           num_layers=1, num_heads=4, hidden_size=64)
    srv = ServingEngine(tp4_engine, slots=4, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2, spec_tokens=3,
                        draft=gpt2.build(dcfg), debug_checks=True)
    assert srv._dcache_sharded
    assert srv._dcache["k"].addressable_shards[0].data.shape[2] == 1
    reqs = _trace(tiny_cfg, 4, seed=2)
    res = srv.serve(reqs)
    assert srv.compile_count <= 2 + len(srv._rungs), srv.compiled_programs
    assert_sequential(tp4_engine, reqs, res)


def test_tiered_mixed_sharding_sharded_target_replicated_draft(tp4_engine,
                                                               tiny_cfg):
    """Tiered KV with a SHARDED target pool and a REPLICATED draft pool
    (GQA draft: 3 heads at tp=4): the staging device_put must apply each
    leaf's OWN sharding — one head-sharded spec over the whole swap tree
    crashed this supported combo.  Parity vs the tp=4 engine's own
    generate under pressure, with swaps in both directions."""
    dcfg = gpt2.GPT2Config(vocab_size=tiny_cfg.vocab_size, max_seq_len=128,
                           num_layers=1, num_heads=3, hidden_size=48)
    srv = ServingEngine(tp4_engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2, num_blocks=10,
                        spec_tokens=3, draft=gpt2.build(dcfg),
                        host_blocks=64, swap_batch=4, debug_checks=True)
    assert srv.kv_sharded and not srv._dcache_sharded
    # (a trace whose preempted rows come back to blocks in the host tier)
    reqs = _trace(tiny_cfg, 5, seed=5, max_new=(16, 24))
    res = srv.serve(reqs)
    st = srv.stats()
    assert st["swap_out"] > 0 and st["swap_in"] > 0
    assert srv.compile_count <= srv.compile_budget == 4 + len(srv._rungs)
    assert_sequential(tp4_engine, reqs, res)


def test_draft_indivisible_heads_raise_with_shard_kv(tp4_engine, tiny_cfg):
    """shard_kv=True + a draft whose HKV does not divide tp fails fast in
    the ctor, naming the draft's head count."""
    dcfg = gpt2.GPT2Config(vocab_size=tiny_cfg.vocab_size, max_seq_len=128,
                           num_layers=1, num_heads=3, hidden_size=48)
    with pytest.raises(ValueError, match="draft model's KV head count"):
        ServingEngine(tp4_engine, slots=2, max_seq_len=128, block_size=8,
                      prefill_chunk=16, spec_tokens=3,
                      draft=gpt2.build(dcfg), shard_kv=True)


def test_init_serving_topology_overrides_config(tiny_cfg):
    """``init_serving(topology=N)`` wins over a conflicting
    ``tensor_parallel`` in a dict config, and never mutates a caller-owned
    config object."""
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        gpt2.build(tiny_cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
        topology=4, slots=2, max_seq_len=128, block_size=8)
    assert srv.tp_degree == 4 and srv.kv_sharded

    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    obj = DeepSpeedInferenceConfig(dtype="fp32")
    deepspeed_tpu.comm.reset_topology()
    deepspeed_tpu.init_serving(gpt2.build(tiny_cfg), config=obj, topology=2,
                               slots=2, max_seq_len=128, block_size=8)
    assert obj.tensor_parallel.tp_size == 1


@pytest.mark.slow  # two engine builds per family
@pytest.mark.parametrize("family", ["opt", "bloom", "mixtral"])
def test_tp_parity_other_families(family):
    """The sharded-cache path holds across the remaining serving families
    (gpt2/llama are tier-1 above): opt's offset learned positions, bloom's
    ALiBi gather path, mixtral's GQA + MoE blocks — tp=2 serving is
    token-exact vs tp=1."""
    if family == "opt":
        from deepspeed_tpu.models import opt as m
        cfg = m.OPTConfig.tiny()
    elif family == "bloom":
        from deepspeed_tpu.models import bloom as m
        cfg = m.BloomConfig.tiny()
    else:
        from deepspeed_tpu.models import mixtral as m
        cfg = m.MixtralConfig.tiny()

    def build(tp):
        deepspeed_tpu.comm.reset_topology()
        return deepspeed_tpu.init_inference(
            m.build(cfg),
            config={"dtype": "fp32", "tensor_parallel": {"tp_size": tp}})

    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, int(rng.integers(5, 14)))
               for _ in range(4)]
    kw = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
              prefill_batch=2)
    r1 = ServingEngine(build(1), **kw).serve(
        [Request(uid=i, prompt=p, max_new_tokens=6)
         for i, p in enumerate(prompts)])
    s2 = ServingEngine(build(2), **kw)
    r2 = s2.serve([Request(uid=i, prompt=p, max_new_tokens=6)
                   for i, p in enumerate(prompts)])
    assert s2.kv_sharded
    for uid in r1:
        np.testing.assert_array_equal(r1[uid], r2[uid], err_msg=f"uid {uid}")


def test_router_kv_pull_tp4_kv8_composition(tp4_engine, tiny_cfg):
    """PR 11 acceptance: the cross-replica KV pull composes with tp
    sharding AND kv8 — two tp=4 replicas with int8 host tiers migrate a
    session (drain -> pull -> resume) bit-identically to an unmigrated
    tp=4 kv8 engine (per-shard gather/scatter moves codes + scale rows
    as ordinary swap leaves)."""
    from deepspeed_tpu.serving import ReplicaRouter

    kw = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
              prefill_batch=2, host_blocks=32, swap_batch=4,
              quantize="kv8", debug_checks=True)
    rng = np.random.default_rng(21)
    prefixes = [rng.integers(0, tiny_cfg.vocab_size, 24)
                for _ in range(2)]
    reqs = [Request(uid=i,
                    prompt=np.concatenate(
                        [prefixes[i % 2],
                         rng.integers(0, tiny_cfg.vocab_size,
                                      int(rng.integers(3, 8)))]),
                    max_new_tokens=8) for i in range(6)]
    ref = ServingEngine(tp4_engine, **kw)
    ref_outs = ref.serve(reqs)

    deepspeed_tpu.comm.reset_topology()
    peer = deepspeed_tpu.init_inference(
        gpt2.build(tiny_cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 4}},
        params=tp4_engine.params)
    reps = [ServingEngine(tp4_engine, **kw),
            ServingEngine(peer, **kw)]
    assert all(r.kv_sharded and r.tp_degree == 4 for r in reps)
    router = ReplicaRouter(reps, debug_checks=True)
    outs = router.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(outs[r.uid], ref_outs[r.uid],
                                      err_msg=f"uid {r.uid}")
    p0 = prefixes[0]
    depth = [rep.affinity_probe(np.concatenate([p0, [0]]))
             for rep in reps]
    rid0 = int(np.argmax([d["device_blocks"] + d["host_blocks"]
                          for d in depth]))
    router.drain(rid0)
    cont = Request(uid="tpq",
                   prompt=np.concatenate(
                       [p0, rng.integers(0, tiny_cfg.vocab_size, 4)]),
                   max_new_tokens=6)
    ref_cont = ref.serve([Request(uid="tpq", prompt=cont.prompt,
                                  max_new_tokens=6)])
    out = router.serve([cont])
    np.testing.assert_array_equal(out["tpq"], ref_cont["tpq"])
    st = router.stats()
    assert st["kv_pulls"] >= 1 and st["kv_pull_blocks"] >= 3
    assert all(p["compile_count"] <= p["compile_budget"]
               for p in st["per_replica"])


def test_chaos_crash_rehoming_tp4_parity(tp4_engine, tiny_cfg):
    """PR 15 chaos x tp composition: a seeded FaultPlan kills one of two
    tp=4 replicas mid-decode — every request completes on the survivor
    token-exactly vs the fault-free tp=4 fleet, with clean post-failure
    audits and budgets intact (the 8-device chaos lane of the chaos
    parity gate)."""
    from deepspeed_tpu.serving import FaultPlan, ReplicaRouter

    kw = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
              prefill_batch=2, host_blocks=32, swap_batch=4,
              debug_checks=True)
    rng = np.random.default_rng(31)
    prefixes = [rng.integers(0, tiny_cfg.vocab_size, 24)
                for _ in range(2)]
    reqs = [Request(uid=i,
                    prompt=np.concatenate(
                        [prefixes[i % 2],
                         rng.integers(0, tiny_cfg.vocab_size,
                                      int(rng.integers(3, 8)))]),
                    max_new_tokens=10) for i in range(6)]

    def _fleet():
        deepspeed_tpu.comm.reset_topology()
        peer = deepspeed_tpu.init_inference(
            gpt2.build(tiny_cfg),
            config={"dtype": "fp32", "tensor_parallel": {"tp_size": 4}},
            params=tp4_engine.params)
        reps = [ServingEngine(tp4_engine, **kw),
                ServingEngine(peer, **kw)]
        assert all(r.kv_sharded and r.tp_degree == 4 for r in reps)
        return ReplicaRouter(reps, debug_checks=True)

    free = _fleet()
    outs_free = free.serve(reqs)

    router = _fleet()
    inj = router.arm_faults(FaultPlan(
        seed=0, crashes=[{"replica": 1, "at_step": 4}]))
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
    assert st["failed"] == [1] and st["requests_failed"] == 0
    assert all(p["compile_count"] <= p["compile_budget"]
               for p in st["per_replica"])
    from deepspeed_tpu.analysis.invariants import audit_router
    audit_router(router)


def test_dp_tp_engine_token_identity_vs_router_fronted(tiny_cfg):
    """PR 16 acceptance: the 2-D ``engine_mode="dp_tp"`` engine — ONE
    compiled decode program over a dp-sharded slot batch with the KV
    pool's physical-block dim sharded over ``dp`` and KV heads over
    ``tp`` — is token-identical to the router-fronted replicas-mode
    twin on a mixed trace (8-device CI mesh: dp=4 x tp=2), keeps
    per-chip KV bytes equal to a
    tp-only replica serving its share of the slots, and demotes the
    router to front-end admission (mixing a dp_tp engine with another
    replica raises)."""
    from deepspeed_tpu.serving import ReplicaRouter

    deepspeed_tpu.comm.reset_topology()
    e2 = deepspeed_tpu.init_inference(
        gpt2.build(tiny_cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 2}})
    dp = dict(e2.mesh.shape)["dp"]
    assert dp == 4, e2.mesh.shape        # 8 devices / tp=2
    kw = dict(max_seq_len=128, block_size=8, prefill_chunk=16,
              prefill_batch=2, prefix_caching=False, debug_checks=True)
    rng = np.random.default_rng(7)

    def mixed_trace():
        r = np.random.default_rng(7)
        return [Request(uid=i,
                        prompt=r.integers(0, tiny_cfg.vocab_size,
                                          int(r.integers(4, 40))),
                        max_new_tokens=int(r.integers(2, 12)))
                for i in range(10)]

    # replicas-mode twin on the SAME mesh: the token-identity reference
    srv_ref = ServingEngine(e2, slots=8, **kw)
    outs_ref = srv_ref.serve(mixed_trace())

    srv_dp = ServingEngine(e2, slots=8, engine_mode="dp_tp", **kw)
    assert srv_dp.dp_degree == 4 and srv_dp.tp_degree == 2
    router = ReplicaRouter([srv_dp], debug_checks=True)
    handles = [router.submit(r) for r in mixed_trace()]
    while router.step():
        pass
    for r, h in zip(mixed_trace(), handles):
        np.testing.assert_array_equal(h.result(timeout=0), outs_ref[r.uid],
                                      err_msg=f"uid {r.uid}")
    st = srv_dp.stats()
    assert st["engine_mode"] == "dp_tp"
    # ONE decode program + ONE prefill program a rung
    assert st["compile_count"] == 1 + len(srv_dp._rungs)
    assert st["retraces_observed"] == 0

    # per-chip KV bytes: the dp_tp pool (4x blocks over 4x chips) costs
    # each chip exactly what a tp-only replica serving slots/dp costs
    tp_only = ServingEngine(e2, slots=8 // dp, **kw)
    assert srv_dp.stats()["kv_pool_bytes_per_chip"] == \
        tp_only.stats()["kv_pool_bytes_per_chip"]

    # router demotion: a dp_tp engine must be the SOLE replica
    with pytest.raises(ValueError, match="sole"):
        ReplicaRouter([srv_dp, srv_ref])
