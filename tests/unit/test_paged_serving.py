"""Block-paged KV cache serving: allocator/prefix-trie units, paged-op
correctness, and chunked-prefill scheduler parity.

Tier-1 (fast) CPU-sim coverage for the paged path:
 - BlockAllocator / PrefixCache host-side bookkeeping (alloc/free/refcount/
   OOM, trie lookup/register/evict ordering).
 - paged_cache_update / paged_gather / paged_decode_attention_reference
   against the contiguous reference layout.
 - ServingEngine in chunked-prefill mode: greedy token parity with
   sequential ``generate`` (incl. under preemption pressure), prefix-cache
   hits for shared system prompts, and the O(1) compile contract (1 prefill
   + 1 decode program per trace).

The Pallas paged-decode kernel's interpret-mode twin lives in
``test_decode_attention.py`` (slow lane).
"""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.paged import (SCRATCH_BLOCK, BlockAllocator,
                                           PrefixCache)
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.utils.lru import LRUCache
from tiny import assert_sequential


# ------------------------------------------------------------- BlockAllocator
def test_allocator_alloc_free_refcount():
    a = BlockAllocator(5)                      # 1 scratch + 4 usable
    assert a.free_blocks == 4 and a.blocks_in_use == 0
    blocks = [a.alloc() for _ in range(4)]
    assert sorted(blocks) == [1, 2, 3, 4]      # scratch block 0 never issued
    assert SCRATCH_BLOCK not in blocks
    assert a.alloc() is None                   # OOM -> None, not an exception
    a.incref(blocks[0])
    a.decref(blocks[0])
    assert a.free_blocks == 0                  # still held once
    a.decref(blocks[0])
    assert a.free_blocks == 1                  # now free
    b = a.alloc()
    assert b == blocks[0] and a.refcount(b) == 1
    with pytest.raises(ValueError):
        BlockAllocator(1)                      # no usable blocks


def test_allocator_decref_unowned_asserts():
    a = BlockAllocator(3)
    with pytest.raises(AssertionError):
        a.decref(1)
    with pytest.raises(AssertionError):
        a.incref(2)


# ---------------------------------------------------------------- PrefixCache
def test_prefix_cache_lookup_register_roundtrip():
    a = BlockAllocator(10)
    pc = PrefixCache(block_size=4)
    toks = np.arange(12)                       # 3 full blocks
    blocks = [a.alloc() for _ in range(3)]
    pc.register(toks, blocks, a)
    assert len(pc) == 3
    assert all(a.refcount(b) == 2 for b in blocks)  # holder + cache

    # full-prefix hit (capped below the full prompt => only 2 of 3 blocks
    # when max_tokens = len-1)
    assert pc.probe(toks, len(toks)) == 3
    assert pc.probe(toks, len(toks) - 1) == 2
    got = pc.lookup(toks, len(toks), a)
    assert got == blocks
    assert all(a.refcount(b) == 3 for b in blocks)
    for b in got:
        a.decref(b)

    # divergent tail: only the shared leading blocks hit
    other = np.concatenate([toks[:8], [99, 98, 97, 96]])
    assert pc.probe(other, len(other)) == 2
    got = pc.lookup(other, len(other), a)
    assert got == blocks[:2]
    for b in got:
        a.decref(b)

    # probe never touches refcounts
    before = [a.refcount(b) for b in blocks]
    pc.probe(toks, len(toks))
    assert [a.refcount(b) for b in blocks] == before


def test_prefix_cache_eviction_leaf_first_lru():
    a = BlockAllocator(10)
    pc = PrefixCache(block_size=2)
    toks = np.arange(6)                        # chain of 3 blocks
    blocks = [a.alloc() for _ in range(3)]
    pc.register(toks, blocks, a)
    for b in blocks:
        a.decref(b)                            # only the cache holds them
    assert pc.evictable(a) == 3
    assert pc.evict_one(a)
    # leaf-first: the chain tail goes first, parents stay walkable
    assert pc.probe(toks, len(toks)) == 2
    assert pc.evict_one(a) and pc.evict_one(a)
    assert len(pc) == 0 and a.free_blocks == 9
    assert not pc.evict_one(a)                 # empty -> False

    # entries still held by a sequence are not evictable
    blocks = [a.alloc() for _ in range(2)]
    pc.register(np.arange(4), blocks, a)
    assert pc.evictable(a) == 0                # refcount 2 (holder + cache)
    assert not pc.evict_one(a)


def test_prefix_cache_register_keeps_first_writer():
    a = BlockAllocator(10)
    pc = PrefixCache(block_size=2)
    toks = np.arange(4)
    b1 = [a.alloc(), a.alloc()]
    b2 = [a.alloc(), a.alloc()]
    pc.register(toks, b1, a)
    pc.register(toks, b2, a)                   # duplicate content
    assert len(pc) == 2                        # first writer wins
    got = pc.lookup(toks, len(toks), a)
    assert got == b1
    assert a.refcount(b2[0]) == 1              # duplicate not cached


# ------------------------------------------------------------------- LRUCache
def test_lru_cache_hit_refreshes_and_capacity_bounds():
    c = LRUCache(capacity=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1                     # refresh "a"
    c.put("c", 3)                              # evicts LRU = "b"
    assert "b" not in c and "a" in c and "c" in c
    built = []
    v = c.get_or_build("a", lambda: 99, on_build=built.append)
    assert v == 1 and built == []              # hit: no build
    v = c.get_or_build("d", lambda: 4, on_build=built.append)
    assert v == 4 and built == [4]


# ----------------------------------------------------------- paged device ops
def test_paged_gather_update_attention_match_contiguous():
    import jax.numpy as jnp

    from deepspeed_tpu.ops.decode_attention import (
        decode_attention_reference, paged_decode_attention_reference)
    from deepspeed_tpu.ops.paged_kv import paged_cache_update, paged_gather

    rng = np.random.default_rng(0)
    b, h, hkv, d, bs, nbper, nb = 3, 4, 2, 16, 8, 4, 13
    s = nbper * bs
    bt = rng.permutation(np.arange(1, nb))[:b * nbper] \
        .reshape(b, nbper).astype(np.int32)
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    kp = np.zeros((nb, hkv, bs, d), np.float32)
    vp = np.zeros((nb, hkv, bs, d), np.float32)
    for row in range(b):
        for i in range(nbper):
            kp[bt[row, i]] = kc[row, :, i * bs:(i + 1) * bs]
            vp[bt[row, i]] = vc[row, :, i * bs:(i + 1) * bs]

    # gather reconstructs the contiguous per-row view
    np.testing.assert_array_equal(
        np.asarray(paged_gather(jnp.asarray(kp), jnp.asarray(bt))), kc)

    # paged attention == contiguous attention (per-row decode positions)
    q = rng.standard_normal((b, h, 1, d)).astype(np.float32)
    pos = np.array([5, 17, 30], np.int32)
    ref = decode_attention_reference(jnp.asarray(q), jnp.asarray(kc),
                                     jnp.asarray(vc), jnp.asarray(pos))
    pag = paged_decode_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(pag), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)

    # chunk scatter: per-row bases + valid masking, pads -> scratch block
    t = 8
    kw = rng.standard_normal((b, hkv, t, d)).astype(np.float32)
    vw = rng.standard_normal((b, hkv, t, d)).astype(np.float32)
    base = np.array([0, 8, 16], np.int32)
    valid = np.array([8, 5, 1], np.int32)
    kp2, _ = paged_cache_update(
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(kw), jnp.asarray(vw),
        jnp.asarray(base), jnp.asarray(bt), valid=jnp.asarray(valid))
    got = np.asarray(paged_gather(kp2, jnp.asarray(bt)))
    want = kc.copy()
    for row in range(b):
        for i in range(valid[row]):
            want[row, :, base[row] + i] = kw[row, :, i]
    np.testing.assert_array_equal(got, want)


# ------------------------------------------- stacked pool, addressed in place
def _tp_mesh(n):
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:n]).reshape(1, 1, 1, 1, n)
    return Mesh(devs, ("pp", "dp", "ep", "sp", "tp"))


def _random_pool(rng, layers, nb, hkv, bs, d, kv8):
    """A stacked pool with every byte random (so an untouched byte that
    changed cannot hide behind a zero)."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops import paged_kv

    def leaf():
        if not kv8:
            return jnp.asarray(rng.standard_normal((layers, nb, hkv, bs, d)),
                               jnp.float32)
        return {"qp": jnp.asarray(rng.integers(
                    -127, 128, (layers, nb, hkv, bs, d)), jnp.int8),
                "ps": jnp.asarray(rng.uniform(
                    0.01, 0.1, (layers, nb, hkv, bs)), paged_kv.SCALE_DTYPE)}

    return leaf(), leaf()


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("kv8", [False, True], ids=["float", "kv8"])
@pytest.mark.parametrize("t", [1, 4, 128])
def test_stacked_pool_write_and_read_in_place(t, kv8, tp):
    """ISSUE 26: ``paged_cache_update`` writes a decode token (T=1), a
    verify window (T=4) or a prefill chunk (T=128) into the WHOLE stacked
    pool at ``[layer, phys, :, off]`` — every other layer, and every block
    outside the rows' tables (scratch included: all tokens are valid), stays
    bit-identical; inside the tables only the written offsets change; and
    the read at that layer returns what was written (``paged_gather``) and
    attends like the same read of that layer's pool alone."""
    import contextlib

    import jax.numpy as jnp

    from deepspeed_tpu.ops import paged_kv
    from deepspeed_tpu.ops.decode_attention import \
        paged_decode_attention_reference

    rng = np.random.default_rng(7 + t)
    layers, b, h, hkv, d, bs, nbper = 3, 3, 4, 2, 16, 16, 12
    nb = 1 + 2 * b * nbper
    bt = rng.permutation(np.arange(1, nb))[:b * nbper] \
        .reshape(b, nbper).astype(np.int32)
    kp, vp = _random_pool(rng, layers, nb, hkv, bs, d, kv8)
    kw = jnp.asarray(rng.standard_normal((b, hkv, t, d)), jnp.float32)
    vw = jnp.asarray(rng.standard_normal((b, hkv, t, d)), jnp.float32)
    pos = np.array([0, 21, nbper * bs - t], np.int32)   # 21: mid-block
    layer = 1

    ctx = paged_kv.tp_context(_tp_mesh(tp)) if tp > 1 \
        else contextlib.nullcontext()
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)

    def program(kp, vp, l):
        """The write and the three reads as ONE program (not an op at a
        time under the mesh: each would be a compile of its own)."""
        kp2, vp2 = paged_kv.paged_cache_update(
            kp, vp, kw, vw, jnp.asarray(pos), jnp.asarray(bt), layer=l)
        got_k = paged_kv.paged_gather(kp2, jnp.asarray(bt), layer=layer,
                                      out_dtype=jnp.float32)
        one = lambda p: jax.tree_util.tree_map(   # noqa: E731
            lambda a: a[layer], p)
        attn = paged_decode_attention_reference(
            q, kp2, vp2, jnp.asarray(bt), jnp.asarray(pos), layer=layer)
        attn_one = paged_decode_attention_reference(
            q, one(kp2), one(vp2), jnp.asarray(bt), jnp.asarray(pos))
        return kp2, vp2, got_k, attn, attn_one

    with ctx:
        kp2, vp2, got_k, attn, attn_one = jax.jit(program)(
            kp, vp, jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(attn), np.asarray(attn_one))

    # what must not have moved, leaf by leaf (codes and scale rows alike)
    written = np.zeros((nb, bs), bool)
    for row in range(b):
        for i in range(t):
            p = pos[row] + i
            written[bt[row, p // bs], p % bs] = True
    for before, after in zip(jax.tree_util.tree_leaves((kp, vp)),
                             jax.tree_util.tree_leaves((kp2, vp2))):
        before, after = np.asarray(before), np.asarray(after)
        for other in (0, 2):
            np.testing.assert_array_equal(after[other], before[other])
        keep = ~written                         # [NB, bs] -> [NB, HKV, bs]
        keep = np.broadcast_to(keep[:, None, :], before.shape[1:4])
        np.testing.assert_array_equal(after[layer][keep],
                                      before[layer][keep])
        assert (after[layer][~keep] != before[layer][~keep]).any()

    # and the written tokens read back (int8: to its rounding)
    got_k = np.asarray(got_k)
    for row in range(b):
        np.testing.assert_allclose(
            got_k[row, :, pos[row]:pos[row] + t], np.asarray(kw)[row],
            atol=0.05 if kv8 else 0, rtol=0)


# --------------------------------------------------- chunked-prefill scheduler
def _shared_prefix_trace(cfg, n, prefix_len=24, seed=0, tail=(3, 10),
                         max_new=(2, 10)):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, cfg.vocab_size,
                                              int(rng.integers(*tail)))]),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


def test_chunked_serving_matches_sequential_generate(tiny_engine):
    """Acceptance: paged chunked-prefill serving (prefix cache on) is
    token-identical to sequential ``generate`` on a shared-prefix trace —
    and the stats() / step_log observability probes fire."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2,
                        debug_checks=True)
    reqs = _shared_prefix_trace(cfg, 6)
    steps = []
    res = srv.serve(reqs, step_log=steps)
    assert_sequential(engine, reqs, res)
    st = srv.stats()
    assert st["prefix_cache_hit_rate"] > 0.2, st
    assert st["prefix_hit_tokens"] % srv.block_size == 0
    for key in ("prefix_cache_hit_rate", "blocks_in_use", "compile_count",
                "admitted", "evicted", "decode_steps", "prefill_calls",
                "num_blocks", "free_blocks", "compile_budget",
                "debug_checks", "invariant_checks_run",
                "retraces_observed"):
        assert key in st, key
    # debug_checks=True: every iteration audited, zero retrace drift
    assert st["debug_checks"] and st["invariant_checks_run"] > 0
    assert st["retraces_observed"] == 0
    assert st["admitted"] == len(reqs)
    assert steps and sum(s["admitted"] for s in steps) == len(reqs)
    assert all("blocks_in_use" in s and "evicted" in s for s in steps)


def test_chunked_serving_parity_with_eos(tiny_engine):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=3, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2,
                        debug_checks=True)
    reqs = _shared_prefix_trace(cfg, 4, seed=1, max_new=(4, 10))
    probe = engine.generate(reqs[0].prompt[None, :], max_new_tokens=1)
    eos = int(probe[0, len(reqs[0].prompt)])
    res = srv.serve(reqs, eos_token_id=eos)
    assert_sequential(engine, reqs, res, eos_token_id=eos)


@pytest.mark.slow  # two engine builds — tier-1 covers gpt2 here and these
@pytest.mark.parametrize("family", ["llama", "opt"])  # in test_serving.py
def test_chunked_serving_parity_other_families(family):
    """Chunked paged prefill holds beyond gpt2: per-row rope offsets
    (llama) and offset learned positions (opt) in T>1 windows."""
    deepspeed_tpu.comm.reset_topology()
    if family == "llama":
        from deepspeed_tpu.models import llama as m

        cfg = m.LlamaConfig.tiny()
    else:
        from deepspeed_tpu.models import opt as m

        cfg = m.OPTConfig.tiny()
    engine = deepspeed_tpu.init_inference(
        m.build(cfg), config={"dtype": "fp32",
                              "tensor_parallel": {"tp_size": 1}})
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2)
    reqs = _shared_prefix_trace(cfg, 5, prefix_len=10, seed=2, tail=(3, 8),
                                max_new=(2, 8))
    res = srv.serve(reqs)
    assert_sequential(engine, reqs, res)


@pytest.mark.parametrize("family,kv", [("opt", None), ("bloom", None),
                                       ("opt", "tp2")])
def test_paged_greedy_equals_contiguous_generate(family, kv):
    """ISSUE 26: a greedy sequence served through the paged engine (whole
    pool carried through a 3-layer loop, written and read at each layer
    index) equals the same model's CONTIGUOUS-cache ``generate`` token for
    token — opt through the shared cached attention, bloom through its
    ALiBi gather path, and opt again with the pool head-sharded over
    tp=2."""
    import dataclasses

    deepspeed_tpu.comm.reset_topology()
    if family == "opt":
        from deepspeed_tpu.models import opt as m

        cfg = dataclasses.replace(m.OPTConfig.tiny(), num_layers=3)
    else:
        from deepspeed_tpu.models import bloom as m

        cfg = dataclasses.replace(m.BloomConfig.tiny(), num_layers=3)
    tp = 2 if kv == "tp2" else 1
    engine = deepspeed_tpu.init_inference(
        m.build(cfg), config={"dtype": "fp32",
                              "tensor_parallel": {"tp_size": tp}})
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2)
    assert srv.kv_sharded == (tp > 1)
    assert srv._cache["k"].shape[0] == 3          # the stacked pool
    reqs = _shared_prefix_trace(cfg, 4, prefix_len=10, seed=3, tail=(3, 8),
                                max_new=(3, 9))
    res = srv.serve(reqs)
    assert_sequential(engine, reqs, res)
    deepspeed_tpu.comm.reset_topology()


@pytest.mark.parametrize("sampling", [True, False],
                         ids=["sampling", "greedy-only"])
def test_chunked_compile_count_is_two_programs(tiny_engine, sampling):
    """Acceptance: the serving loop compiles exactly 1 prefill + 1 decode
    program for a whole mixed-shape trace — and stays there for new shapes
    and for repeat traffic, with the sampling operands or without them.
    Enforced LIVE by the recompile sentry (debug_checks=True raises at
    trace time past the budget of 2): it counts actual Python-body traces,
    so silent retraces can't hide."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2,
                        sampling=sampling, debug_checks=True)
    assert srv.compile_budget == 1 + len(srv._rungs)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               int(rng.integers(3, 40))),
                    max_new_tokens=int(rng.integers(1, 12)))
            for i in range(12)]
    srv.serve(reqs)
    assert srv.compile_count == 1 + len(srv._rungs), srv.compiled_programs
    reqs2 = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                                int(rng.integers(40, 80))),
                     max_new_tokens=int(rng.integers(1, 8)))
             for i in range(6)]
    srv.serve(reqs2)                           # new shapes: no new programs
    assert srv.compile_count == 1 + len(srv._rungs), srv.compiled_programs
    srv.serve(reqs)                            # repeat traffic: none either
    assert srv.compile_count == 1 + len(srv._rungs), srv.compiled_programs
    assert sorted({p[0] for p in srv.compiled_programs}) == \
        ["decode", "prefill"]
    # each jitted fn holds exactly one executable
    for fn in (*srv._prefill_fns.values(), srv._decode_fn):
        assert fn._cache_size() == 1
    # sentry ledger: exactly one trace per program, zero beyond budget
    assert srv.sentry.traces == 1 + len(srv._rungs), srv.sentry.report()
    assert srv.sentry.retraces_observed == 0


def test_prefix_cache_reuse_across_serve_calls(tiny_engine):
    """A shared system prompt prefilled once is reused by later traffic:
    the second serve call's hit tokens cover the registered prefix."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=128, block_size=8,
                        prefill_chunk=32, prefill_batch=2,
                        debug_checks=True)
    rng = np.random.default_rng(4)
    prefix = rng.integers(0, cfg.vocab_size, 32)      # 4 full blocks

    def mk(uid, seed):
        r = np.random.default_rng(seed)
        return Request(uid=uid, prompt=np.concatenate(
            [prefix, r.integers(0, cfg.vocab_size, 5)]), max_new_tokens=4)

    srv.serve([mk(0, 0)])
    hit0 = srv.prefix_hit_tokens
    res = srv.serve([mk(1, 1), mk(2, 2)])
    # both later requests reuse the full 32-token (4-block) shared prefix
    assert srv.prefix_hit_tokens - hit0 == 2 * 32
    for uid, seed in ((1, 1), (2, 2)):
        want = engine.generate(mk(uid, seed).prompt[None, :],
                               max_new_tokens=4)[0]
        np.testing.assert_array_equal(res[uid], want)


def test_preemption_under_block_pressure_keeps_parity(tiny_engine):
    """Oversubscribed pool: decode growth forces preemption (sequence
    eviction + FIFO re-queue + recompute); greedy outputs stay identical
    and the eviction counters fire."""
    engine, cfg = tiny_engine
    # nbper = 64/8 = 8; 3 slots want up to 6 blocks each (17 prompt + 28
    # new -> 45 tokens) but only 11 usable blocks exist.  debug_checks
    # audits the allocator/trie/table invariants through every eviction +
    # preemption round — the hardest path for refcount conservation.
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=32, prefill_batch=2, num_blocks=12,
                        debug_checks=True)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 17),
                    max_new_tokens=28) for i in range(5)]
    log = []
    res = srv.serve(reqs, admission_log=log)
    assert srv.preempted > 0, srv.stats()      # pressure actually happened
    assert set(res) == set(range(5))           # everyone finished
    assert_sequential(engine, reqs, res)
    # FIRST admissions stay FIFO (re-admissions of evicted uids may repeat)
    first = []
    for uid, _ in log:
        if uid not in first:
            first.append(uid)
    assert first == list(range(5))


def test_paged_serving_rejects_legacy_models():
    deepspeed_tpu.comm.reset_topology()
    from deepspeed_tpu.models import gptj

    legacy = deepspeed_tpu.init_inference(
        gptj.build(gptj.GPTJConfig.tiny()),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}})
    with pytest.raises(ValueError, match="supports_lengths"):
        ServingEngine(legacy)


