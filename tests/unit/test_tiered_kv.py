"""Tiered KV cache: host-DRAM offload of cold paged blocks with
overlapped prefetch (``inference/paged.py`` HostBlockStore +
``ops/paged_kv.py`` block gather/scatter + the ServingEngine demote/
promote scheduler paths).

Tier-1 (fast) coverage:
 - host-store units: content-addressed chain keys, LRU eviction that
   never touches in-flight entries, slot accounting, probe runs.
 - device op units: ``paged_block_gather``/``paged_block_scatter``
   round-trip bit-identically on float pools AND quantized ``{qp, ps}``
   records (codes + scale rows travel together).
 - e2e parity under real block pressure: a deliberately small device
   pool (evictions + preemptions) with the host tier serves token-
   identically to sequential ``generate`` AND to the untiered engine,
   with swaps actually happening, preemption-resume recompute collapsing
   to the unfinished tail, and the compile contract at exactly base + 2
   programs (the two fixed-shape swap programs) — sentry-enforced, so
   H2D/D2H traffic can never introduce further programs.
 - kv8 roundtrip: the tiered small-pool int8 engine is BIT-identical to
   the untiered big-pool int8 engine (deterministic quantization + exact
   byte round trips), with the scale-lockstep ledger audited throughout.
 - residency fault injection: a leaked in-flight host block (flagged
   with no staged record) and a staged record over an unflagged entry
   both raise ``PagedStateError`` naming ``residency-conservation``.

Every serve here runs ``debug_checks=True``: the per-iteration audit
covers the new residency invariant alongside refcounts/trie/tables, and
the strict sentry enforces the +2 swap-program budget at trace time.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.analysis.invariants import (PagedStateError,
                                               audit_serving_engine)
from deepspeed_tpu.inference.paged import (HostBlockStore, chain_key,
                                           chain_keys)
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.ops import paged_kv
from tiny import sequential


# ------------------------------------------------------------- store units
def test_chain_key_is_cumulative_and_block_indexed():
    toks = np.arange(40, dtype=np.int32)
    k0 = chain_key(toks, 0, 8)
    k1 = chain_key(toks, 1, 8)
    # PR 19: keys are fixed-width rolling digests (the raw-chain byte
    # strings grew linearly with block index — quadratic total at 128k
    # contexts); depth never changes the width and distinct chains
    # never share a key
    from deepspeed_tpu.inference.paged import CHAIN_KEY_BYTES
    assert len(k0) == CHAIN_KEY_BYTES == len(k1) and k0 != k1
    # same leading chain => same key, regardless of what follows
    other = np.concatenate([toks[:16], np.full(8, 999, np.int32)])
    assert chain_key(other, 1, 8) == k1
    assert chain_key(other, 2, 8) != chain_key(toks, 2, 8)
    # the O(len) batch spelling is byte-identical to per-block calls —
    # every tier lookup depends on these two never diverging
    assert chain_keys(toks, 5, 8) == [chain_key(toks, i, 8)
                                      for i in range(5)]
    assert chain_keys(toks, 0, 8) == []


def test_host_store_put_read_pop_and_lru():
    store = HostBlockStore(2, [((3, 4), np.float32), ((3,), np.int8)])
    assert store.block_nbytes == 3 * 4 * 4 + 3
    a = [np.full((3, 4), 1.5, np.float32), np.full(3, 7, np.int8)]
    b = [np.full((3, 4), 2.5, np.float32), np.full(3, 8, np.int8)]
    c = [np.full((3, 4), 3.5, np.float32), np.full(3, 9, np.int8)]
    assert store.put(b"a", a) is not None
    assert store.put(b"b", b) is not None
    assert store.blocks_in_use == 2 and len(store) == 2
    np.testing.assert_array_equal(store.read(b"a")[0], a[0])
    # duplicate key keeps the first copy (and refreshes recency)
    assert store.put(b"a", c) is not None
    np.testing.assert_array_equal(store.read(b"a")[1], a[1])
    # arena full: LRU (now b"b") evicts to make room
    assert store.put(b"c", c) is not None
    assert not store.has(b"b") and store.has(b"a") and store.has(b"c")
    assert store.evictions == 1
    store.pop(b"c")
    assert store.blocks_in_use == 1 and not store.has(b"c")


def test_host_store_in_flight_entries_never_evict():
    store = HostBlockStore(2, [((2,), np.float32)])
    store.put(b"a", [np.zeros(2, np.float32)])
    store.put(b"b", [np.ones(2, np.float32)])
    store.mark_in_flight(b"a")
    store.mark_in_flight(b"b")
    # every slot pinned by a staged promotion: the demotion is refused
    assert store.put(b"c", [np.ones(2, np.float32)]) is None
    store.mark_in_flight(b"a", False)
    assert store.put(b"c", [np.ones(2, np.float32)]) is not None
    assert not store.has(b"a") and store.has(b"b")


def test_host_store_probe_run_contiguous():
    bs = 4
    toks = np.arange(20, dtype=np.int32)
    store = HostBlockStore(4, [((2,), np.float32)])
    arr = [np.zeros(2, np.float32)]
    store.put(chain_key(toks, 0, bs), arr)
    store.put(chain_key(toks, 2, bs), arr)      # hole at block 1
    assert store.probe_run(toks, 0, 20, bs) == [chain_key(toks, 0, bs)]
    assert store.probe_run(toks, 2, 20, bs) == [chain_key(toks, 2, bs)]
    assert store.probe_run(toks, 1, 20, bs) == []
    # cap below the full prompt mirrors the trie lookup cap (a 12-token
    # prompt probes with max_tokens=11: block 2 needs tokens 8..11)
    assert store.probe_run(toks, 2, 11, bs) == []


# ------------------------------------------------------------ device ops
def test_paged_block_gather_scatter_roundtrip_float_and_quantized():
    rng = np.random.default_rng(0)
    pool = {"k": jnp.asarray(rng.normal(size=(2, 6, 4, 8, 16)),
                             jnp.float32),
            "v": jnp.asarray(rng.normal(size=(2, 6, 4, 8, 16)),
                             jnp.float32)}
    ids = jnp.asarray([3, 1, 0, 0], jnp.int32)      # pad cols -> scratch
    staged = paged_kv.paged_block_gather(pool, ids)
    assert staged["k"].shape == (2, 4, 4, 8, 16)
    np.testing.assert_array_equal(np.asarray(staged["k"][:, 0]),
                                  np.asarray(pool["k"][:, 3]))
    # scatter into a zeroed pool: targeted blocks restore bit-identically
    zero = jax.tree_util.tree_map(jnp.zeros_like, pool)
    back = paged_kv.paged_block_scatter(zero, staged,
                                        jnp.asarray([3, 1, 0, 0]))
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(back[n][:, 3]),
                                      np.asarray(pool[n][:, 3]))
        np.testing.assert_array_equal(np.asarray(back[n][:, 1]),
                                      np.asarray(pool[n][:, 1]))
        assert not np.asarray(back[n][:, 2]).any()  # untouched stays zero

    # quantized records: codes + scale rows travel as one tree
    qpool = {"k": {"qp": jnp.asarray(
                       rng.integers(-127, 127, (2, 6, 4, 8, 16)), jnp.int8),
                   "ps": jnp.asarray(rng.normal(size=(2, 6, 4, 8)),
                                     paged_kv.SCALE_DTYPE)}}
    qstaged = paged_kv.paged_block_gather(qpool, jnp.asarray([5, 2]))
    qzero = jax.tree_util.tree_map(jnp.zeros_like, qpool)
    qback = paged_kv.paged_block_scatter(qzero, qstaged,
                                         jnp.asarray([5, 2]))
    for blk in (5, 2):
        np.testing.assert_array_equal(
            np.asarray(qback["k"]["qp"][:, blk]),
            np.asarray(qpool["k"]["qp"][:, blk]))
        np.testing.assert_array_equal(
            np.asarray(qback["k"]["ps"][:, blk]),
            np.asarray(qpool["k"]["ps"][:, blk]))


# ----------------------------------------------------------------- serving
def _pressure_trace(cfg, n=6, seed=5, prefix_len=24, max_new=28):
    """Shared prefix + completions long enough that a 10-block pool (on
    3 slots / block_size 8) must evict the trie and preempt."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, cfg.vocab_size,
                                              int(rng.integers(3, 10)))]),
                    max_new_tokens=max_new)
            for i in range(n)]


_PRESSURE_KW = dict(slots=3, max_seq_len=64, block_size=8,
                    prefill_chunk=16, prefill_batch=2, num_blocks=10,
                    debug_checks=True)


def test_tiered_parity_under_pressure_and_compile_contract(tiny_engine):
    """Acceptance: the tiered engine under real block pressure is token-
    identical to sequential generate and to the untiered engine, swaps
    actually happen in both directions, preemption-resume recompute
    collapses vs the evict/recompute baseline, and the compile contract
    is exactly base + 2 swap programs (strict sentry)."""
    engine, cfg = tiny_engine
    reqs = _pressure_trace(cfg)
    seq = sequential(engine, reqs)

    srv = ServingEngine(engine, host_blocks=64, swap_batch=4,
                        **_PRESSURE_KW)
    out = srv.serve(reqs)
    st = srv.stats()
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], seq[r.uid],
                                      err_msg=f"uid {r.uid}")
    assert st["swap_out"] > 0 and st["swap_in"] > 0
    assert st["swap_bytes"] == (st["swap_out"] + st["swap_in"]) * \
        srv._host.block_nbytes
    assert st["host_blocks_in_use"] > 0
    assert st["compile_count"] == st["compile_budget"] \
        == 3 + len(srv._rungs)
    names = sorted(srv.sentry.report())
    assert "kv_demote" in names and "kv_promote" in names

    base = ServingEngine(engine, **_PRESSURE_KW)
    outb = base.serve(reqs)
    stb = base.stats()
    for r in reqs:
        np.testing.assert_array_equal(outb[r.uid], seq[r.uid])
    # both preempt (the pool is the same size) but the tiered resume
    # re-prefills only unfinished tails, not whole prefixes
    assert st["evicted"] > 0 and stb["evicted"] > 0
    assert st["resume_recompute_tokens"] < stb["resume_recompute_tokens"]
    assert stb["swap_out"] == 0 and stb["swap_in"] == 0
    assert stb["compile_budget"] == 1 + len(base._rungs)


def test_tiered_warm_pass_promotes_evicted_prefix(tiny_engine):
    """A second pass over the same trace finds its (previously evicted)
    chains in the host tier: promotions run, parity holds, and at least
    part of the prefetch traffic is staged ahead (misses < promotions)."""
    engine, cfg = tiny_engine
    reqs = _pressure_trace(cfg, seed=7)
    seq = sequential(engine, reqs)
    srv = ServingEngine(engine, host_blocks=64, swap_batch=4,
                        **_PRESSURE_KW)
    srv.serve(reqs)
    in0 = srv.stats()["swap_in"]
    out2 = srv.serve(reqs)
    st = srv.stats()
    for r in reqs:
        np.testing.assert_array_equal(out2[r.uid], seq[r.uid])
    assert st["swap_in"] > in0
    assert st["prefetch_misses"] < st["swap_in"]
    assert st["prefetch_wait_p95_s"] is not None


def test_tiered_kv8_roundtrip_bit_identical(tiny_engine):
    """kv8 x tiered: int8 codes and their per-block scale rows demote and
    promote together, so the tiered small-pool engine reproduces the
    untiered big-pool int8 engine BIT-identically (deterministic
    quantization + byte-exact round trips).  debug_checks audits the
    scale-lockstep ledger and the residency invariant throughout."""
    engine, cfg = tiny_engine
    reqs = _pressure_trace(cfg, seed=9)
    big = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2,
                        quantize="kv8", debug_checks=True)
    ref = big.serve(reqs)
    srv = ServingEngine(engine, quantize="kv8", host_blocks=64,
                        swap_batch=4, **_PRESSURE_KW)
    out = srv.serve(reqs)
    st = srv.stats()
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], ref[r.uid],
                                      err_msg=f"uid {r.uid}")
    assert st["swap_out"] > 0 and st["swap_in"] > 0
    assert st["kv_dtype"] == "int8"
    # the swap tree carries the scale-table leaves: block bytes > codes
    codes = 2 * cfg.num_layers * cfg.num_heads * 8 * \
        (cfg.hidden_size // cfg.num_heads)
    assert srv._host.block_nbytes > codes


def test_tiered_speculative_parity(tiny_engine):
    """n-gram speculative decoding over the tiered pool: token-exact and
    within its 2 + 2 swap-program budget."""
    engine, cfg = tiny_engine
    reqs = _pressure_trace(cfg, seed=11)
    seq = sequential(engine, reqs)
    srv = ServingEngine(engine, spec_tokens=3, host_blocks=64,
                        swap_batch=4, **_PRESSURE_KW)
    out = srv.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], seq[r.uid])
    assert srv.compile_budget == 3 + len(srv._rungs) and srv.compile_count <= 3 + len(srv._rungs)
    assert srv.stats()["swap_out"] > 0


def test_residency_fault_injection_names_leaked_in_flight(tiny_engine):
    """Corrupting the in-flight lockstep raises PagedStateError naming
    residency-conservation: (a) a host entry flagged in-flight with no
    staged record — the leaked block whose arena slot can never free —
    and (b) a staged record over an unflagged (LRU-evictable) entry."""
    engine, cfg = tiny_engine
    reqs = _pressure_trace(cfg, seed=13)
    srv = ServingEngine(engine, host_blocks=64, swap_batch=4,
                        **_PRESSURE_KW)
    srv.serve(reqs)
    assert len(srv._host) > 0
    audit_serving_engine(srv, {})               # clean post-serve state
    key = next(iter(srv._host.snapshot()[1]))
    srv._host.mark_in_flight(key)               # no staged record exists
    with pytest.raises(PagedStateError, match="leaked in-flight") as ei:
        audit_serving_engine(srv, {})
    assert ei.value.invariant == "residency-conservation"
    srv._host.mark_in_flight(key, False)
    srv._staged["ghost"] = {"keys": [key], "chunks": []}
    with pytest.raises(PagedStateError, match="NOT flagged") as ei:
        audit_serving_engine(srv, {})
    assert ei.value.invariant == "residency-conservation"
    srv._staged.clear()
    audit_serving_engine(srv, {})


def test_staged_prefetch_records_never_outlive_their_request(tiny_engine):
    """Regression: a prefetch staged for a request whose chain a SHARING
    request promotes first used to leak its record past admission
    (probe_run comes back empty, the early return skipped the take) —
    two leaks then permanently filled the double buffer and the stale
    records pinned in-flight flags.  Every staged record must belong to
    a still-pending request at every scheduler iteration."""
    engine, cfg = tiny_engine
    rng = np.random.default_rng(21)
    prefix = rng.integers(0, cfg.vocab_size, 24)
    # many requests over ONE shared session prefix: consecutive pending
    # entries stage the same chain, the first admission promotes it
    reqs = [Request(uid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, cfg.vocab_size,
                                              int(rng.integers(3, 8)))]),
                    max_new_tokens=24)
            for i in range(8)]
    srv = ServingEngine(engine, host_blocks=64, swap_batch=4,
                        **_PRESSURE_KW)
    orig = srv._issue_prefetch
    leaks = []

    def hooked(pending):
        live = {item.req.uid for item in pending}
        stale = set(srv._staged) - live
        if stale:
            leaks.append(stale)
        return orig(pending)

    srv._issue_prefetch = hooked
    srv.serve(reqs)
    srv.serve(reqs)                     # warm pass: host tier populated
    assert not leaks, f"staged records leaked past admission: {leaks}"
    assert srv._staged == {}


def test_tiered_requires_prefix_caching(tiny_engine):
    engine, _ = tiny_engine
    with pytest.raises(ValueError, match="tiered KV"):
        ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                      prefix_caching=False, host_blocks=8)


def test_tiering_off_is_inert_and_stats_schema_stable(tiny_engine):
    """host_blocks=0 (default): no swap programs, no host arena, zeroed
    tier stats — and the pre-tiering stat keys are untouched."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2,
                        debug_checks=True)
    srv.serve(_pressure_trace(cfg, n=3, seed=15, max_new=4))
    st = srv.stats()
    assert srv._host is None \
        and st["compile_budget"] == 1 + len(srv._rungs)
    assert st["host_blocks"] == 0 and st["host_pool_bytes"] == 0
    assert st["swap_in"] == 0 and st["swap_out"] == 0
    for k in ("prefix_cache_hit_rate", "blocks_in_use", "free_blocks",
              "ttft_p50_s", "kv_pool_bytes"):
        assert k in st


def test_init_serving_plumbs_host_blocks(tiny_engine):
    _, cfg = tiny_engine
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        gpt2.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
        slots=2, max_seq_len=64, block_size=8, host_blocks=16,
        swap_batch=4, debug_checks=True)
    assert srv.host_blocks == 16 and srv.swap_batch == 4
    assert srv._host is not None and srv._host.num_blocks == 16
    assert srv.compile_budget == 3 + len(srv._rungs)
