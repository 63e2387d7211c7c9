"""Quantized paged serving: int8 KV pool (per-block scale table) and
w8a8 weights inside the ServingEngine.

Tier-1 (fast) CPU-sim coverage for the PR 7 quantization stack:
 - quantize/dequant round-trip units on the pool ops (``quantize_kv``,
   record scatter/gather vs the float pool, pad routing to scratch).
 - kv8 / w8a8 / w8a8+kv8 end-to-end bounded divergence for all five
   paged families — the shared "close enough" definition lives in
   ``quant_divergence.py`` (token match rate + teacher-forced logit
   RMSE), replacing exact greedy parity on quantized lanes.
 - gpt2 kv8 under speculative decoding and preemption pressure, with
   ``debug_checks=True`` so every iteration runs the paged-state audit
   (including the new ``scale-lockstep`` invariant) and the recompile
   sentry enforces the unchanged ≤2/≤3-program contracts.
 - ``quantize=None`` lanes bit-identical to pre-quantization behavior.
 - scale-ledger fault injection naming the violated invariant.

The Pallas quantized decode/verify kernels' interpret twins live in
``test_decode_attention.py`` (slow lane); the tp=4 × kv8 parity case in
``test_tp_serving.py`` (8-device CI job).
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.invariants import PagedStateError
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.ops import paged_kv
from quant_divergence import (assert_bounded_divergence, max_logit_rmse,
                              token_match_rate)
from tiny import sequential

#: documented divergence bounds for the tiny fp32 CPU-sim models (random
#: weights — near-uniform logits, the WORST case for argmax stability;
#: measured rates are ~1.0, the bounds leave cascade headroom)
KV8_MIN_MATCH = 0.85
W8A8_MIN_MATCH = 0.70
W8A8_MAX_LOGIT_RMSE = 0.15


# ------------------------------------------------------------ pool-op units
def test_quantize_kv_roundtrip_and_edge_cases():
    import jax.numpy as jnp

    from deepspeed_tpu.ops import quantization as quant

    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 2, 5, 16)).astype(np.float32) * \
        rng.uniform(0.01, 10.0, (3, 2, 5, 1)).astype(np.float32)
    codes, scale = quant.quantize_kv(jnp.asarray(x))
    assert codes.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    back = np.asarray(quant.dequantize_kv(codes, scale))
    # error bound: half a code of the STORED (bf16-rounded) scale
    bound = np.asarray(scale, np.float32)[..., None] * 0.51 + 1e-7
    assert (np.abs(back - x) <= bound).all()
    # all-zero vectors: scale 1, codes 0, exact zero round-trip
    z_codes, z_scale = quant.quantize_kv(jnp.zeros((2, 4)))
    assert np.asarray(z_scale).tolist() == [1.0, 1.0]
    assert np.asarray(z_codes).sum() == 0


def test_record_pool_scatter_gather_matches_float_pool():
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    b, hkv, d, bs, nbper, nb = 3, 2, 16, 8, 4, 13
    bt = rng.permutation(np.arange(1, nb))[:b * nbper] \
        .reshape(b, nbper).astype(np.int32)
    fp = jnp.zeros((nb, hkv, bs, d), jnp.float32)
    qp = paged_kv.quantize_pool(fp)
    assert paged_kv.is_quantized_pool(qp)
    assert qp["qp"].dtype == jnp.int8
    assert qp["ps"].shape == (nb, hkv, bs)
    assert paged_kv.pool_payload(qp).shape == fp.shape

    kw = rng.standard_normal((b, hkv, 8, d)).astype(np.float32)
    vw = rng.standard_normal((b, hkv, 8, d)).astype(np.float32)
    base = np.array([0, 8, 16], np.int32)
    valid = np.array([8, 5, 1], np.int32)
    fk, fv = paged_kv.paged_cache_update(
        fp, fp, jnp.asarray(kw), jnp.asarray(vw), jnp.asarray(base),
        jnp.asarray(bt), valid=jnp.asarray(valid))
    qk, qv = paged_kv.paged_cache_update(
        qp, qp, jnp.asarray(kw), jnp.asarray(vw), jnp.asarray(base),
        jnp.asarray(bt), valid=jnp.asarray(valid))
    gf = np.asarray(paged_kv.paged_gather(fk, jnp.asarray(bt)))
    gq = np.asarray(paged_kv.paged_gather(qk, jnp.asarray(bt)))
    amax = np.abs(gf).max()
    assert np.abs(gf - gq).max() <= amax / 127 * 0.55 + 1e-6
    # invalid tokens routed to scratch: block 0's scale row took writes,
    # but no allocated block picked up the masked tail
    gv = np.asarray(paged_kv.paged_gather(qv, jnp.asarray(bt)))
    assert np.abs(gv[1, :, base[1] + valid[1]:base[1] + 8]).max() == 0.0


def test_quantized_paged_attention_reference_tracks_float():
    import jax.numpy as jnp

    from deepspeed_tpu.ops.decode_attention import (
        paged_decode_attention_reference)

    rng = np.random.default_rng(2)
    b, h, hkv, d, bs, nbper, nb = 3, 4, 2, 16, 8, 4, 13
    bt = rng.permutation(np.arange(1, nb))[:b * nbper] \
        .reshape(b, nbper).astype(np.int32)
    fp = jnp.zeros((nb, hkv, bs, d), jnp.float32)
    kw = rng.standard_normal((b, hkv, 24, d)).astype(np.float32)
    vw = rng.standard_normal((b, hkv, 24, d)).astype(np.float32)
    zero = jnp.zeros(b, jnp.int32)
    fk, fv = paged_kv.paged_cache_update(fp, fp, jnp.asarray(kw),
                                         jnp.asarray(vw), zero,
                                         jnp.asarray(bt))
    qpool = paged_kv.quantize_pool(fp)
    qk, qv = paged_kv.paged_cache_update(qpool, qpool, jnp.asarray(kw),
                                         jnp.asarray(vw), zero,
                                         jnp.asarray(bt))
    q = rng.standard_normal((b, h, 1, d)).astype(np.float32)
    pos = np.array([5, 12, 23], np.int32)
    ref = np.asarray(paged_decode_attention_reference(
        jnp.asarray(q), fk, fv, jnp.asarray(bt), jnp.asarray(pos)))
    got = np.asarray(paged_decode_attention_reference(
        jnp.asarray(q), qk, qv, jnp.asarray(bt), jnp.asarray(pos)))
    np.testing.assert_allclose(got, ref, atol=5e-2)


# --------------------------------------------------------------- scheduling
def _trace(cfg, n=6, seed=1, prefix_len=24, tail=(3, 10), max_new=(2, 10)):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, cfg.vocab_size,
                                              int(rng.integers(*tail)))]),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


def test_kv8_serving_bounded_divergence_and_stats(tiny_engine):
    """kv8 end-to-end on gpt2: bounded token divergence vs sequential
    generate, ≤2-program compile contract live-enforced, quantized memory
    accounting in stats(), and the per-iteration audit (incl.
    scale-lockstep) green throughout."""
    engine, cfg = tiny_engine
    reqs = _trace(cfg)
    want = sequential(engine, reqs)
    srv = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2, quantize="kv8",
                        debug_checks=True)
    assert srv.compile_budget == 1 + len(srv._rungs)
    res = srv.serve(_trace(cfg))
    rate = assert_bounded_divergence(want, res, KV8_MIN_MATCH, "kv8")
    assert rate > 0  # helper returns the measured rate for logging
    st = srv.stats()
    assert st["quantize"] == "kv8" and st["kv_dtype"] == "int8"
    assert st["weight_quant"] is None
    assert st["kv_scale_bytes"] > 0
    assert st["compile_count"] == 1 + len(srv._rungs), \
        srv.compiled_programs
    assert st["retraces_observed"] == 0
    assert st["invariant_checks_run"] > 0
    # quant-adjusted pool accounting: int8 codes + scale table, and the
    # headline — ~2x (>= 1.8x vs a bf16 pool) servable blocks per byte
    plain = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                          prefill_chunk=16, prefill_batch=2)
    bf16_bytes = plain.stats()["kv_pool_bytes"] // 2   # fp32 pool -> bf16
    assert bf16_bytes / st["kv_pool_bytes"] >= 1.8 - 0.11  # hd=16 tiny cfg
    payload = 2 * int(np.prod(st["kv_pool_shape"]))   # k + v leaves, int8
    assert st["kv_pool_bytes"] == payload + st["kv_scale_bytes"]


def test_kv8_speculative_and_preemption_pressure(tiny_engine):
    """kv8 composes with the draft–verify round (n-gram, ≤2 programs) and
    survives eviction + preemption churn with the audit on: rollback
    rewrites the same positions with the same deterministic codes, and
    the scale ledger tracks every free/realloc."""
    engine, cfg = tiny_engine
    reqs = _trace(cfg, seed=3)
    want = sequential(engine, reqs)
    srv = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2, quantize="kv8",
                        spec_tokens=3, debug_checks=True)
    res = srv.serve(_trace(cfg, seed=3))
    assert_bounded_divergence(want, res, KV8_MIN_MATCH, "kv8+spec")
    assert srv.compile_count <= 1 + len(srv._rungs), srv.compiled_programs
    assert srv.stats()["acceptance_rate"] >= 0.0

    # oversubscribed pool: preemption + prefix eviction under kv8
    rng = np.random.default_rng(5)
    preqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 17),
                     max_new_tokens=28) for i in range(5)]
    pwant = sequential(engine, preqs)
    srv_p = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                          prefill_chunk=32, prefill_batch=2, num_blocks=12,
                          quantize="kv8", debug_checks=True)
    pres = srv_p.serve(preqs)
    assert srv_p.preempted > 0, srv_p.stats()
    assert_bounded_divergence(pwant, pres, KV8_MIN_MATCH, "kv8+preempt")
    # every free retired its ledger entry; survivors are exactly the
    # still-owned blocks (the audit checked this each iteration too)
    assert all(srv_p._alloc.refcount(b) > 0 for b in srv_p._kv_scale_live)


def test_quantize_none_is_bit_identical(tiny_engine):
    """The guardrail for everything above: an explicit ``quantize=None``
    engine (and the default) traces the exact pre-quantization programs —
    bit-equal tokens, float pool, no scale table."""
    engine, cfg = tiny_engine
    reqs = _trace(cfg, seed=7)
    want = sequential(engine, reqs)
    srv = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2, quantize=None,
                        debug_checks=True)
    res = srv.serve(_trace(cfg, seed=7))
    for r in reqs:
        np.testing.assert_array_equal(res[r.uid], want[r.uid],
                                      err_msg=f"uid {r.uid}")
    st = srv.stats()
    assert st["quantize"] is None and st["kv_dtype"] == "float32"
    assert st["kv_scale_bytes"] == 0
    assert token_match_rate(want, res) == 1.0


@pytest.mark.parametrize("family", ["gpt2", "llama", "opt", "mixtral",
                                    "bloom"])
def test_quant_serving_all_families(family):
    """kv8 AND w8a8+kv8 end-to-end per paged family: one plain engine
    serves the full-precision reference, the kv8 lane wraps the same
    engine, and the w8a8+kv8 lane rebuilds it with K-grouped int8 records
    through ``init_serving(quantize=...)`` (asserting records actually
    exist, so the lane can't silently serve dense weights)."""
    import jax

    from deepspeed_tpu.ops import quantization as quant

    if family == "gpt2":
        from deepspeed_tpu.models import gpt2 as m
        cfg = m.GPT2Config(vocab_size=512, max_seq_len=64, num_layers=2,
                           num_heads=4, hidden_size=128)
    elif family == "llama":
        from deepspeed_tpu.models import llama as m
        cfg = m.LlamaConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                            num_heads=4, num_kv_heads=2, hidden_size=128,
                            ffn_size=256, rope_theta=10000.0, remat=False)
    elif family == "opt":
        from deepspeed_tpu.models import opt as m
        cfg = m.OPTConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                          num_heads=4, hidden_size=128, ffn_size=256)
    elif family == "mixtral":
        from deepspeed_tpu.models import mixtral as m
        cfg = m.MixtralConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                              num_heads=4, num_kv_heads=2, hidden_size=128,
                              ffn_size=128, rope_theta=10000.0,
                              num_experts=4, top_k=2, remat=False)
    else:
        from deepspeed_tpu.models import bloom as m
        cfg = m.BloomConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                            num_heads=4, hidden_size=128)
    params = jax.device_get(m.build(cfg).init_fn(jax.random.PRNGKey(0)))
    deepspeed_tpu.comm.reset_topology()
    engine = deepspeed_tpu.init_inference(
        m.build(cfg), params=params,
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}})
    reqs = _trace(cfg, n=4, seed=2, prefix_len=10, tail=(3, 8),
                  max_new=(2, 8))
    want = sequential(engine, reqs)

    kw = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
              prefill_batch=2, debug_checks=True)
    srv = ServingEngine(engine, quantize="kv8", **kw)
    res = srv.serve(_trace(cfg, n=4, seed=2, prefix_len=10, tail=(3, 8),
                           max_new=(2, 8)))
    assert_bounded_divergence(want, res, KV8_MIN_MATCH, f"{family} kv8")
    assert srv.compile_count <= 1 + len(srv._rungs)

    deepspeed_tpu.comm.reset_topology()
    srv_w = deepspeed_tpu.init_serving(
        m.build(cfg), params=params, config={"dtype": "fp32"},
        quantize="w8a8+kv8", **kw)
    recs = [x for x in jax.tree_util.tree_leaves(
        srv_w.engine.params, is_leaf=quant.is_k_quantized)
        if quant.is_k_quantized(x)]
    assert recs, f"{family}: w8a8 produced no K-grouped records"
    res_w = srv_w.serve(_trace(cfg, n=4, seed=2, prefix_len=10,
                               tail=(3, 8), max_new=(2, 8)))
    assert_bounded_divergence(want, res_w, W8A8_MIN_MATCH,
                              f"{family} w8a8+kv8")
    st = srv_w.stats()
    assert st["weight_quant"] == "w8a8" and st["kv_dtype"] == "int8"
    assert srv_w.compile_count <= 1 + len(srv_w._rungs)
    # teacher-forced logit error stays bounded (no argmax-cascade luck)
    rmse = max_logit_rmse(engine, srv_w.engine,
                          [r.prompt for r in reqs[:2]])
    assert rmse <= W8A8_MAX_LOGIT_RMSE, rmse


def test_scale_lockstep_fault_injection(tiny_engine):
    """The scale ledger is a CHECKED contract: injecting a stale entry
    (freed block still marked live) or dropping a live one (owned block
    missing) raises PagedStateError naming ``scale-lockstep``."""
    from deepspeed_tpu.analysis.invariants import audit_serving_engine

    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2, quantize="kv8",
                        debug_checks=True)
    srv.serve(_trace(cfg, n=2, seed=9))
    # after the trace the prefix trie still owns blocks: ledger non-empty
    assert srv._kv_scale_live

    # stale scale: a freed block left in the ledger
    free_block = srv._alloc._free[0]
    srv._kv_scale_live.add(free_block)
    with pytest.raises(PagedStateError, match="scale-lockstep") as ei:
        audit_serving_engine(srv, {})
    assert ei.value.invariant == "scale-lockstep"
    srv._kv_scale_live.discard(free_block)
    audit_serving_engine(srv, {})              # green again

    # dropped entry: an owned (trie-held) block missing from the ledger
    owned = next(iter(srv._kv_scale_live))
    srv._kv_scale_live.discard(owned)
    with pytest.raises(PagedStateError, match="scale-lockstep"):
        audit_serving_engine(srv, {})
    srv._kv_scale_live.add(owned)
    audit_serving_engine(srv, {})


def test_quantize_validation_errors(tiny_engine):
    engine, cfg = tiny_engine
    with pytest.raises(ValueError, match="quantize"):
        ServingEngine(engine, quantize="int4")
    # w8a8 requested but the engine carries full-precision weights
    with pytest.raises(ValueError, match="w8a8"):
        ServingEngine(engine, quantize="w8a8")
    with pytest.raises(ValueError, match="w8a8"):
        ServingEngine(engine, quantize="w8a8+kv8")
    # kv8 against a family that never declared the record contract
    hooks = dict(engine.module.decode_hooks)
    hooks.pop("supports_kv_quant")
    spec = engine.module
    orig = spec.decode_hooks
    spec.decode_hooks = hooks
    try:
        with pytest.raises(ValueError, match="supports_kv_quant"):
            ServingEngine(engine, quantize="kv8")
    finally:
        spec.decode_hooks = orig
