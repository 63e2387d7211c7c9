"""KV-cache decode path: kernel correctness + end-to-end generation parity.

Mirrors the reference's inference-kernel tests (``tests/unit/ops/transformer/
inference``) and ``test_inference.py`` output-parity style: every cached path is
checked against the non-cached full-recompute forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.decode_attention import (
    decode_attention_pallas, decode_attention_reference,
    paged_decode_attention_pallas, paged_decode_attention_reference,
    paged_prefill_attention_pallas, paged_verify_attention_pallas)

#: Pallas interpret mode: minutes on CPU.  Marked per test, so that the
#: (small, fast) cases of the paged walk at the end of this file stay in the
#: quick lane.
slow = pytest.mark.slow


def _dense_reference(q, k, v, q_pos):
    """Naive masked attention, fp32."""
    b, h, t, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    if h != hkv:
        rep = h // hkv
        k = np.repeat(k, rep, axis=1)
        v = np.repeat(v, rep, axis=1)
    scores = np.einsum("bhtd,bhsd->bhts", q, k) / np.sqrt(d)
    mask = np.arange(s)[None, :] <= (q_pos + np.arange(t))[:, None]
    scores = np.where(mask[None, None], scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhts,bhsd->bhtd", p, v)


@slow
@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)])
def test_reference_path_matches_dense(h, hkv):
    rng = np.random.default_rng(0)
    b, s, d, t, pos = 2, 64, 32, 1, 17
    q = rng.standard_normal((b, h, t, d)).astype(np.float32)
    k = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    v = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    out = decode_attention_reference(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), pos)
    np.testing.assert_allclose(np.asarray(out), _dense_reference(q, k, v, pos),
                               rtol=2e-5, atol=2e-5)


@slow
def test_reference_path_prefill_matches_dense():
    rng = np.random.default_rng(1)
    b, h, s, d, t = 1, 4, 64, 16, 9
    q = rng.standard_normal((b, h, t, d)).astype(np.float32)
    k = rng.standard_normal((b, h, s, d)).astype(np.float32)
    v = rng.standard_normal((b, h, s, d)).astype(np.float32)
    out = decode_attention_reference(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), 0)
    np.testing.assert_allclose(np.asarray(out), _dense_reference(q, k, v, 0),
                               rtol=2e-5, atol=2e-5)


@slow
@pytest.mark.parametrize("h,hkv,pos", [(4, 4, 0), (4, 4, 63), (8, 2, 200)])
def test_pallas_kernel_matches_reference(h, hkv, pos):
    rng = np.random.default_rng(2)
    b, s, d = 2, 256, 64
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    got = decode_attention_pallas(q, k, v, pos, block_k=64, interpret=True)
    want = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@slow
@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)])
def test_pallas_kernel_per_sequence_lengths(h, hkv):
    """Ragged lengths[B] (continuous-batching slots): Pallas == reference ==
    per-row scalar, including GQA head sharing and a zero-length slot."""
    rng = np.random.default_rng(7)
    b, s, d = 4, 256, 64
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    lengths = jnp.asarray([0, 17, 200, 255], jnp.int32)
    got = decode_attention_pallas(q, k, v, lengths, block_k=64,
                                  interpret=True)
    want = decode_attention_reference(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # each row must equal the scalar-position path on that row alone
    for i, pos in enumerate(np.asarray(lengths)):
        row = decode_attention_reference(q[i:i + 1], k[i:i + 1],
                                         v[i:i + 1], int(pos))
        np.testing.assert_allclose(np.asarray(want[i:i + 1]),
                                   np.asarray(row), rtol=1e-6, atol=1e-6)


@slow
def test_pallas_kernel_ragged_under_jit_traced_lengths():
    """One compiled program serves every lengths vector (jit-traced)."""
    rng = np.random.default_rng(8)
    b, h, s, d = 2, 4, 128, 32

    @jax.jit
    def step(q, k, v, lengths):
        return decode_attention_pallas(q, k, v, lengths, block_k=64,
                                       interpret=True)

    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    for lens in ([0, 127], [5, 64], [127, 0]):
        lengths = jnp.asarray(lens, jnp.int32)
        got = step(q, k, v, lengths)
        want = decode_attention_reference(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@slow
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_forward_cached_ragged_matches_full_recompute(family):
    """Per-sequence lengths through forward_cached: ragged prefill window
    + per-row decode == full-recompute logits on each row's own sequence."""
    if family == "gpt2":
        from deepspeed_tpu.models import gpt2 as m

        cfg = m.GPT2Config.tiny()
    else:
        from deepspeed_tpu.models import llama as m

        cfg = m.LlamaConfig.tiny()
    params = m.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    lens = np.array([3, 5, 2], np.int32)
    t = 5
    ids = np.zeros((3, t), np.int32)
    for i, n in enumerate(lens):
        ids[i, :n] = rng.integers(1, cfg.vocab_size, n)
    ids = jnp.asarray(ids)
    cache = m.init_cache(cfg, 3, 64, jnp.float32)
    logits, cache = m.forward_cached(cfg, params, ids, cache, 0,
                                     lengths=jnp.asarray(lens))
    for i, n in enumerate(lens):
        full = m.forward(cfg, params, ids[i:i + 1, :n], train=False)
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   np.asarray(full[0, n - 1]),
                                   rtol=2e-4, atol=2e-4)
    seqs = [list(np.asarray(ids[i, :lens[i]])) for i in range(3)]
    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    cur = lens.copy()
    for _ in range(3):
        for i in range(3):
            seqs[i].append(int(toks[i]))
        logits, cache = m.forward_cached(cfg, params, toks[:, None], cache,
                                         0, lengths=jnp.asarray(cur))
        cur += 1
        for i in range(3):
            full = m.forward(cfg, params, jnp.asarray([seqs[i]], jnp.int32),
                             train=False)
            np.testing.assert_allclose(np.asarray(logits[i]),
                                       np.asarray(full[0, -1]),
                                       rtol=2e-4, atol=2e-4)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)


@slow
def test_pallas_kernel_under_jit_traced_pos():
    rng = np.random.default_rng(3)
    b, h, s, d = 1, 4, 128, 32

    @jax.jit
    def step(q, k, v, pos):
        return decode_attention_pallas(q, k, v, pos, block_k=64,
                                       interpret=True)

    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    for pos in [0, 5, 127]:
        got = step(q, k, v, jnp.int32(pos))
        want = decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@slow
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_forward_cached_matches_forward(family):
    """Cached incremental forward == full forward, token by token."""
    if family == "gpt2":
        from deepspeed_tpu.models import gpt2 as m

        cfg = m.GPT2Config.tiny()
    else:
        from deepspeed_tpu.models import llama as m

        cfg = m.LlamaConfig.tiny()
    params = m.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    b, s = 2, 12
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)

    full_logits = m.forward(cfg, params, ids, train=False)  # [B, S, V]

    cache = m.init_cache(cfg, b, 64, jnp.float32)
    prompt = 5
    logits, cache = m.forward_cached(cfg, params, ids[:, :prompt], cache, 0)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, prompt - 1]),
                               rtol=2e-4, atol=2e-4)
    for pos in range(prompt, s):
        logits, cache = m.forward_cached(cfg, params, ids[:, pos:pos + 1],
                                         cache, pos)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits[:, pos]),
                                   rtol=2e-4, atol=2e-4)


@slow
def test_generate_kv_cache_matches_recompute():
    """InferenceEngine KV-cache generation == full-recompute generation."""
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny(max_seq_len=256)
    model = gpt2.build(cfg)
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}})
    rng = np.random.default_rng(5)
    ids = rng.integers(0, cfg.vocab_size, (2, 7)).astype(np.int32)

    out_cached = engine.generate(ids, max_new_tokens=8)

    model_nocache = gpt2.build(cfg)
    model_nocache.decode_hooks = None
    engine2 = deepspeed_tpu.init_inference(
        model_nocache, config={"dtype": "fp32",
                               "tensor_parallel": {"tp_size": 1}},
        params=engine.params)
    out_full = engine2.generate(ids, max_new_tokens=8)
    np.testing.assert_array_equal(out_cached, out_full)


# ---------------------------------------------------------- paged decode kernel
def _paged_from_contiguous(kc, vc, nb, bs, rng):
    """Scatter a contiguous [B, HKV, S, D] cache into a pool of ``nb``
    blocks via random (non-overlapping) block tables."""
    b, hkv, s, d = kc.shape
    nbper = s // bs
    bt = rng.permutation(np.arange(1, nb))[:b * nbper] \
        .reshape(b, nbper).astype(np.int32)
    kp = np.zeros((nb, hkv, bs, d), kc.dtype)
    vp = np.zeros((nb, hkv, bs, d), vc.dtype)
    for row in range(b):
        for i in range(nbper):
            kp[bt[row, i]] = kc[row, :, i * bs:(i + 1) * bs]
            vp[bt[row, i]] = vc[row, :, i * bs:(i + 1) * bs]
    return kp, vp, bt


@slow
@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)])
def test_paged_pallas_kernel_matches_reference(h, hkv):
    """The block-table-walking kernel (scalar prefetch) == the gather-based
    reference == the contiguous kernel, with per-row ragged positions
    (including a zero-length slot)."""
    rng = np.random.default_rng(10)
    b, s, d, bs = 4, 256, 64, 64
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    kp, vp, bt = _paged_from_contiguous(kc, vc, 2 * b * (s // bs), bs, rng)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    lengths = jnp.asarray([0, 17, 200, 255], jnp.int32)
    want = decode_attention_reference(q, jnp.asarray(kc), jnp.asarray(vc),
                                      lengths)
    ref = paged_decode_attention_reference(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), lengths)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    got = paged_decode_attention_pallas(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), lengths,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@slow
@pytest.mark.parametrize("h,hkv,t", [(4, 4, 4), (8, 2, 5)])
def test_paged_verify_pallas_kernel_matches_reference(h, hkv, t):
    """The K+1 speculative verify window (T query rows per slot, each row's
    window starting at its own base) == the gather-based reference == the
    contiguous dense path, with ragged bases including 0 and a window that
    straddles a block boundary."""
    rng = np.random.default_rng(12)
    b, s, d, bs = 4, 256, 64, 64
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    kp, vp, bt = _paged_from_contiguous(kc, vc, 2 * b * (s // bs), bs, rng)
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    # bases: fresh slot, mid-block, window straddling the 64-boundary, and
    # a window ending at the last cached position
    bases = jnp.asarray([0, 17, 62, 256 - t], jnp.int32)
    want = decode_attention_reference(q, jnp.asarray(kc), jnp.asarray(vc),
                                      bases)
    ref = paged_decode_attention_reference(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), bases)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    got = paged_verify_attention_pallas(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), bases,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@slow
def test_paged_verify_pallas_kernel_under_jit_traced_bases():
    """One compiled verify program serves every (bases, block_table) pair —
    the speculative serving loop's contract."""
    rng = np.random.default_rng(13)
    b, h, s, d, bs, t = 2, 4, 128, 32, 32, 3
    kc = rng.standard_normal((b, h, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, h, s, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)

    @jax.jit
    def step(q, kp, vp, bt, bases):
        return paged_verify_attention_pallas(q, kp, vp, bt, bases,
                                             interpret=True)

    for seed, bases in ((0, [0, 100]), (1, [31, 125 - t])):
        r2 = np.random.default_rng(200 + seed)
        kp, vp, bt = _paged_from_contiguous(kc, vc, 2 * b * (s // bs), bs, r2)
        bases = jnp.asarray(bases, jnp.int32)
        got = step(q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
                   bases)
        want = decode_attention_reference(q, jnp.asarray(kc),
                                          jnp.asarray(vc), bases)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@slow
def test_paged_pallas_kernel_under_jit_traced_tables():
    """One compiled program serves every (lengths, block_table) pair — the
    serving loop's decode contract."""
    rng = np.random.default_rng(11)
    b, h, s, d, bs = 2, 4, 128, 32, 32
    kc = rng.standard_normal((b, h, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, h, s, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)

    @jax.jit
    def step(q, kp, vp, bt, lengths):
        return paged_decode_attention_pallas(q, kp, vp, bt, lengths,
                                             interpret=True)

    for seed, lens in ((0, [0, 127]), (1, [64, 5])):
        r2 = np.random.default_rng(100 + seed)
        kp, vp, bt = _paged_from_contiguous(kc, vc, 2 * b * (s // bs), bs, r2)
        lengths = jnp.asarray(lens, jnp.int32)
        got = step(q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
                   lengths)
        want = decode_attention_reference(q, jnp.asarray(kc),
                                          jnp.asarray(vc), lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# -------------------------------------------- tensor-parallel kernel shards
def _tp_mesh(n):
    """A (1,1,1,1,n) mesh over the first n CPU-sim devices — the tp slice
    of the engine topology the serving engine installs via tp_context."""
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:n]).reshape(1, 1, 1, 1, n)
    return Mesh(devs, ("pp", "dp", "ep", "sp", "tp"))


@slow
@pytest.mark.parametrize("h,hkv,tp", [(4, 4, 2), (8, 4, 4), (8, 2, 2)])
def test_paged_pallas_kernel_sharded_matches_reference(h, hkv, tp):
    """Under a configured tp context each chip launches the decode kernel
    on its own HKV/tp head shard of q and the pool; the assembled global
    output equals the unsharded reference bit-for-tolerance."""
    from deepspeed_tpu.ops import paged_kv

    rng = np.random.default_rng(20)
    b, s, d, bs = 4, 256, 64, 64
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    kp, vp, bt = _paged_from_contiguous(kc, vc, 2 * b * (s // bs), bs, rng)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    lengths = jnp.asarray([0, 17, 200, 255], jnp.int32)
    want = decode_attention_reference(q, jnp.asarray(kc), jnp.asarray(vc),
                                      lengths)
    with paged_kv.tp_context(_tp_mesh(tp)):
        got = jax.jit(
            lambda *a: paged_decode_attention_pallas(*a, interpret=True))(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), lengths)
        ref = jax.jit(paged_decode_attention_reference)(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@slow
@pytest.mark.parametrize("h,hkv,tp,t", [(4, 4, 2, 4), (8, 2, 2, 5)])
def test_paged_verify_pallas_kernel_sharded_matches_reference(h, hkv, tp, t):
    """The K+1 verify window shards over heads exactly like single-token
    decode (the T query rows ride inside each head-shard's tile)."""
    from deepspeed_tpu.ops import paged_kv

    rng = np.random.default_rng(21)
    b, s, d, bs = 4, 256, 64, 64
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    kp, vp, bt = _paged_from_contiguous(kc, vc, 2 * b * (s // bs), bs, rng)
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    bases = jnp.asarray([0, 17, 62, 256 - t], jnp.int32)
    want = decode_attention_reference(q, jnp.asarray(kc), jnp.asarray(vc),
                                      bases)
    with paged_kv.tp_context(_tp_mesh(tp)):
        got = jax.jit(
            lambda *a: paged_verify_attention_pallas(*a, interpret=True))(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), bases)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@slow
def test_paged_ops_gqa_below_tp_fall_back_replicated():
    """HKV smaller than the tp axis cannot shard: head_shards reports 1 and
    the ops run the replicated path — identical results, no error."""
    from deepspeed_tpu.ops import paged_kv

    rng = np.random.default_rng(22)
    b, h, hkv, s, d, bs = 2, 8, 2, 128, 32, 32
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    kp, vp, bt = _paged_from_contiguous(kc, vc, 2 * b * (s // bs), bs, rng)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    lengths = jnp.asarray([5, 100], jnp.int32)
    want = decode_attention_reference(q, jnp.asarray(kc), jnp.asarray(vc),
                                      lengths)
    with paged_kv.tp_context(_tp_mesh(4)):
        assert paged_kv.head_shards(hkv, h) == 1      # 2 % 4 != 0
        got = paged_decode_attention_reference(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# -------------------------------------------------- quantized (int8) pools
def _quantized_from_contiguous(kc, vc, nb, bs, rng):
    """Scatter contiguous [B, HKV, S, D] caches into an int8 record pool
    through random block tables (the write path quantizes per token)."""
    from deepspeed_tpu.ops import paged_kv

    b, hkv, s, d = kc.shape
    nbper = s // bs
    bt = rng.permutation(np.arange(1, nb))[:b * nbper] \
        .reshape(b, nbper).astype(np.int32)
    pool = paged_kv.quantize_pool(jnp.zeros((nb, hkv, bs, d), jnp.float32))
    kp, vp = paged_kv.paged_cache_update(
        pool, pool, jnp.asarray(kc), jnp.asarray(vc),
        jnp.zeros(b, jnp.int32), jnp.asarray(bt))
    return kp, vp, bt


@slow
@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)])
def test_quantized_paged_pallas_kernel_matches_reference(h, hkv):
    """int8 pool records through the decode kernel: the in-kernel
    scale-fold (scores * k-scale, probs * v-scale) equals the gather +
    dequant reference exactly, and both track the float cache within the
    int8 error envelope."""
    from deepspeed_tpu.ops import paged_kv  # noqa: F401 (fixture helper)

    rng = np.random.default_rng(30)
    b, s, d, bs = 4, 256, 64, 64
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    kp, vp, bt = _quantized_from_contiguous(kc, vc, 2 * b * (s // bs), bs,
                                            rng)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    lengths = jnp.asarray([0, 17, 200, 255], jnp.int32)
    ref = paged_decode_attention_reference(q, kp, vp, jnp.asarray(bt),
                                           lengths)
    got = paged_decode_attention_pallas(q, kp, vp, jnp.asarray(bt), lengths,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    dense = decode_attention_reference(q, jnp.asarray(kc), jnp.asarray(vc),
                                       lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               atol=5e-2)


@slow
@pytest.mark.parametrize("h,hkv,t", [(4, 4, 4), (8, 2, 5)])
def test_quantized_verify_pallas_kernel_matches_reference(h, hkv, t):
    """The K+1 verify window over an int8 pool: per-row bases, straddled
    block boundaries, in-kernel dequant — same contract as the float
    kernel within kernel tolerance of the dequant reference."""
    rng = np.random.default_rng(31)
    b, s, d, bs = 4, 256, 64, 64
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    kp, vp, bt = _quantized_from_contiguous(kc, vc, 2 * b * (s // bs), bs,
                                            rng)
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    bases = jnp.asarray([0, 17, 62, 256 - t], jnp.int32)
    ref = paged_decode_attention_reference(q, kp, vp, jnp.asarray(bt),
                                           bases)
    got = paged_verify_attention_pallas(q, kp, vp, jnp.asarray(bt), bases,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@slow
@pytest.mark.parametrize("h,hkv,tp", [(8, 4, 4), (8, 2, 2)])
def test_quantized_paged_kernel_sharded_matches_reference(h, hkv, tp):
    """int8 records shard whole under the tp context — codes AND the
    scale table split on the head dim — and the sharded kernel equals the
    unsharded dequant reference (scales are head-local, so sharding
    changes no value)."""
    from deepspeed_tpu.ops import paged_kv

    rng = np.random.default_rng(32)
    b, s, d, bs = 4, 256, 64, 64
    kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    kp, vp, bt = _quantized_from_contiguous(kc, vc, 2 * b * (s // bs), bs,
                                            rng)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    lengths = jnp.asarray([0, 17, 200, 255], jnp.int32)
    want = paged_decode_attention_reference(q, kp, vp, jnp.asarray(bt),
                                            lengths)
    with paged_kv.tp_context(_tp_mesh(tp)):
        assert paged_kv.head_shards(hkv, h) == tp
        got = jax.jit(
            lambda *a: paged_decode_attention_pallas(*a, interpret=True))(
            q, kp, vp, jnp.asarray(bt), lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------- stacked pool, addressed in place
def _stacked_pool(rng, layers, b, hkv, s, d, bs, kv8):
    """An L-layer pool whose every layer holds DIFFERENT contiguous caches
    behind one shared random block table, filled through the write path at
    each layer index (int8 records quantize on write):
    ``(k_pool, v_pool, bt)``."""
    from deepspeed_tpu.ops import paged_kv

    nbper = s // bs
    nb = 1 + 2 * b * nbper
    bt = jnp.asarray(rng.permutation(np.arange(1, nb))[:b * nbper]
                     .reshape(b, nbper).astype(np.int32))
    kp = jnp.zeros((layers, nb, hkv, bs, d), jnp.float32)
    if kv8:
        kp = paged_kv.quantize_pool(kp)
    vp = kp
    for layer in range(layers):
        kc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
        vc = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
        kp, vp = paged_kv.paged_cache_update(
            kp, vp, jnp.asarray(kc), jnp.asarray(vc),
            jnp.zeros(b, jnp.int32), bt, layer=layer)
    return kp, vp, bt


@slow
@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("kv8", [False, True], ids=["float", "kv8"])
@pytest.mark.parametrize("t", [1, 4, 128])
def test_stacked_pool_attention_reads_its_layer(t, kv8, tp):
    """ISSUE 26: the paged reads address the whole [L, NB, HKV, bs, D] pool
    at a (non-zero) layer index — reference and kernels agree with the same
    read of that layer's pool alone (the one-layer entry point, itself
    pinned against the dense path above), for a decode token (T=1), a
    verify window (T=4) and a prefill chunk (T=128: reference only), float
    and int8 pools, whole and head-sharded over tp=2."""
    rng = np.random.default_rng(40 + t)
    layers, b, h, hkv, s, d, bs = 3, 3, 4, 2, 256, 32, 32
    kp, vp, bt = _stacked_pool(rng, layers, b, hkv, s, d, bs, kv8)
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    pos = jnp.asarray([0, 37, s - t], jnp.int32)
    one = lambda p, l: jax.tree_util.tree_map(lambda a: a[l], p)  # noqa: E731
    import contextlib

    from deepspeed_tpu.ops import paged_kv

    ctx = paged_kv.tp_context(_tp_mesh(tp)) if tp > 1 \
        else contextlib.nullcontext()
    with ctx:
        for layer in (1, 2):
            want = paged_decode_attention_reference(
                q, one(kp, layer), one(vp, layer), bt, pos)
            ref = jax.jit(lambda q, kp, vp, l: paged_decode_attention_reference(
                q, kp, vp, bt, pos, layer=l))(q, kp, vp, jnp.int32(layer))
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(want))
            kernel = {1: paged_decode_attention_pallas,
                      4: paged_verify_attention_pallas}.get(t)
            if kernel is None:
                continue
            got = jax.jit(lambda q, kp, vp, l: kernel(
                q, kp, vp, bt, pos, interpret=True, layer=l))(
                    q, kp, vp, jnp.int32(layer))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
        # layers differ, so a read of the wrong layer cannot pass
        other = paged_decode_attention_reference(q, one(kp, 0), one(vp, 0),
                                                 bt, pos)
        assert not np.allclose(np.asarray(other), np.asarray(want),
                               atol=1e-3)


# ------------------------------------- the walk over a row's valid blocks
# ISSUE 29: the paged kernels loop over each row's VALID blocks and copy
# every KV head of a block themselves.  Small shapes, quick lane.
WALK_BS, WALK_CTX, WALK_LAYERS = 32, 160, 2          # 5 blocks a row


def _walk_case(rng, t, hd, rep, kv8, bases, held=None, ctx=None, hkv=2):
    """A 2-layer pool, lane-packed as the engine holds it (hd 64: g = 2,
    hd 128: g = 1), rows of the given ``bases`` (query positions ``base ..
    base + t - 1``; ``held``: the tokens each row holds, ``base + t`` unless
    given).  Returns ``(q, k_pool, v_pool, poisoned table, clean
    table)``: every block but the scratch id 0 and those of a row's valid
    prefix is full of NaN (an int8 record's scale rows are NaN there); past
    each row's valid prefix the poisoned table holds ids of such blocks, the
    clean one the scratch id — a kernel that copies one entry too many
    returns NaN, the gather reference reads the clean table."""
    from deepspeed_tpu.ops import paged_kv

    b, bs = len(bases), WALK_BS
    kp, vp, bt = _stacked_pool(rng, WALK_LAYERS, b, hkv, ctx or WALK_CTX, hd,
                               bs, kv8)
    bt = np.asarray(bt)
    nb = paged_kv.pool_payload(kp).shape[1]
    held = np.asarray(bases) + t if held is None else np.asarray(held)
    valid = (held + bs - 1) // bs                    # blocks a row may read
    past = np.arange(bt.shape[1])[None, :] >= valid[:, None]
    poison = np.setdiff1d(np.arange(1, nb), bt[~past])

    def poisoned(pool):
        if kv8:
            return {"qp": pool["qp"],
                    "ps": pool["ps"].at[:, poison].set(jnp.nan)}
        return pool.at[:, poison].set(jnp.nan)

    kp, vp = (paged_kv.pack_pool(poisoned(p)) for p in (kp, vp))
    garbage = poison[rng.integers(0, len(poison), bt.shape)]
    q = jnp.asarray(rng.standard_normal((b, hkv * rep, t, hd)), jnp.float32)
    return (q, kp, vp, jnp.asarray(np.where(past, garbage, bt), jnp.int32),
            jnp.asarray(np.where(past, 0, bt), jnp.int32))


@pytest.mark.parametrize("kv8", [False, True], ids=["float", "kv8"])
@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("hd", [128, 64], ids=["g1", "g2"])
def test_paged_walk_visits_each_rows_valid_blocks_only(hd, rep, kv8):
    """One batch of rows of length 1, a block less one, exactly a block,
    a block and one, ``max_seq_len`` (five trips of the double-buffered
    loop), and a slot with no live token (all its entries unset) beside
    them — at a non-zero layer, the table's entries past each row's valid
    prefix garbage."""
    rng = np.random.default_rng(50 + hd + rep)
    bases = [0, WALK_BS - 2, WALK_BS - 1, WALK_BS, WALK_CTX - 1, 0]
    q, kp, vp, bt, clean = _walk_case(rng, 1, hd, rep, kv8, bases)
    bt, clean = bt.at[-1].set(0), clean.at[-1].set(0)     # the empty slot
    pos = jnp.asarray(bases, jnp.int32)
    want = paged_decode_attention_reference(q, kp, vp, clean, pos, layer=1)
    got = paged_decode_attention_pallas(q, kp, vp, bt, pos, layer=1,
                                        interpret=True)
    assert np.isfinite(np.asarray(got)).all(), "read past a valid prefix"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv8", [False, True], ids=["float", "kv8"])
@pytest.mark.parametrize("hd,rep", [(128, 1), (64, 1), (64, 4)],
                         ids=["g1", "g2", "g2-rep4"])
def test_paged_walk_verify_window_across_a_block_boundary(hd, rep, kv8):
    """T = 4 verify windows that start a block, straddle a boundary (the
    trip count comes from ``base + T - 1``, one block more than the base
    holds), end a block and end the context."""
    t = 4
    rng = np.random.default_rng(60 + hd + rep)
    bases = [0, WALK_BS - 2, 2 * WALK_BS - t, WALK_CTX - t]
    q, kp, vp, bt, clean = _walk_case(rng, t, hd, rep, kv8, bases)
    pos = jnp.asarray(bases, jnp.int32)
    want = paged_decode_attention_reference(q, kp, vp, clean, pos, layer=1)
    got = paged_verify_attention_pallas(q, kp, vp, bt, pos, layer=1,
                                        interpret=True)
    assert np.isfinite(np.asarray(got)).all(), "read past a valid prefix"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ISSUE 45: a loop iteration of the walk is a TILE of ``nt`` blocks and
# ONE online-softmax update over all their keys.
def _walk_tile(hd):
    """The walk's tile (blocks) at ``WALK_BS``-token blocks of ``hd``."""
    from deepspeed_tpu.ops import decode_attention as da, paged_kv

    return da.walk_tile_blocks(
        WALK_BS // paged_kv.lane_pack(WALK_BS, hd), 1 << 10)


def _tile_case(rng, t, hd, rep, kv8):
    """:func:`_walk_case` with rows that hold 0, 1, ``nt - 1``, ``nt``, ``nt
    + 1`` and ``2 nt + 1`` blocks (``nt``: the tile at these shapes), the
    last block full, partly full or holding ONE key.  -> (its five, the
    rows' positions, nt)."""
    bs, nt = WALK_BS, _walk_tile(hd)
    # (blocks, keys in the last of them)
    rows = [(0, 0), (1, max(t, 1)), (1, bs), (nt - 1, 5), (nt, bs),
            (nt + 1, 1), (2 * nt + 1, 7), (2 * nt + 1, bs)]
    held = np.asarray([max(0, (n - 1) * bs + last) for n, last in rows])
    return (*_walk_case(rng, t, hd, rep, kv8, held - t, held=held,
                        ctx=(2 * nt + 1) * bs),
            jnp.asarray(held - t, jnp.int32), nt)


def _window_tile_case(t):
    """Rows of a 40-key window layer over blocks of 8 and a ring of 7, the
    walk's tile cut to 4 blocks (by the caller): the window starts before
    the row's first key (0, 3), its first visible key lies mid-block and
    the walk takes two tiles (45, 100), and the ring wraps inside the
    second tile (64) and the first (131).  Every block that is not live for
    its row — the scratch id too — is NaN."""
    q, kp, vp, bt, pos, want = _ring_case(t, 40, 8, 7,
                                          [0, 3, 45, 64, 100, 131], seed=3)
    dead = np.setdiff1d(np.arange(kp.shape[1]), np.asarray(bt)[bt > 0])
    return (q, kp.at[:, dead].set(jnp.nan), vp.at[:, dead].set(jnp.nan), bt,
            pos, want)


@pytest.mark.parametrize("hd,rep,t,kv8,window", [
    (128, 1, 1, False, 0), (64, 1, 1, False, 0),
    (128, 1, 4, False, 0), (64, 1, 4, False, 0),
    (128, 16, 1, False, 0), (128, 16, 4, False, 0), (64, 4, 4, False, 0),
    (64, 1, 1, True, 0), (128, 4, 4, True, 0), (64, 4, 4, True, 0),
    (128, 2, 1, False, 40), (128, 2, 4, False, 40),
], ids=["g1", "g2", "g1-verify", "g2-verify", "gqa16", "gqa16-verify",
        "g2-rep4-verify", "kv8-g2", "kv8-g1-verify", "kv8-g2-verify",
        "window", "window-verify"])
def test_paged_walk_attends_a_tile_of_blocks_an_update(hd, rep, t, kv8,
                                                       window, monkeypatch):
    """The walk's tile against the gather reference (a window layer: against
    plain windowed attention), every block and table entry outside a row's
    valid blocks NaN: rows that end a block short of a tile, on it, a block
    and ONE key past it, and two tiles and a block long; decode and a T = 4
    verify window; packed (g = 2) and plain blocks; a GQA group of 16; int8
    records; a ring that wraps inside a tile."""
    from deepspeed_tpu.ops import decode_attention as da

    kernel = paged_decode_attention_pallas if t == 1 \
        else paged_verify_attention_pallas
    if window:
        monkeypatch.setattr(da, "_WALK_COLS", 32)        # 4 blocks of 8
        q, kp, vp, bt, pos, want = _window_tile_case(t)
        assert da.walk_tile_blocks(8, bt.shape[1]) == 4
        got = np.asarray(kernel(q, kp, vp, bt, pos, layer=0, window=window,
                                interpret=True))
    else:
        rng = np.random.default_rng(90 + hd + rep + t)
        q, kp, vp, bt, clean, pos, nt = _tile_case(rng, t, hd, rep, kv8)
        assert nt == {128: 4, 64: 8}[hd]
        want = np.array(paged_decode_attention_reference(
            q, kp, vp, clean, pos, layer=1))
        got = np.asarray(kernel(q, kp, vp, bt, pos, layer=1,
                                interpret=True))
        # the row that holds nothing walks nothing and returns zeros
        assert not got[0].any()
        want[0] = 0
    assert np.isfinite(got).all(), "read outside a row's valid blocks"
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ISSUE 56: on a row's last tile the walk starts tile 0 of the NEXT grid
# step, into the slot that tile does not hold; the next step only waits.
def _simulated_chip(mode="on_wait"):
    """Pallas' TPU interpret mode: DMAs and their semaphores simulated, a
    copy made only when it is waited for (``on_wait``: one that nobody
    waits for never lands) or at its start (``eager``: one that nobody
    waits for leaves its semaphore raised at the kernel's exit)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams(dma_execution_mode=mode, detect_races=True)


def _races_found() -> bool:
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    return bool(interpret_pallas_call.races.races_found)


def _carried_case(rng, t, hd, rep, kv8, blocks, hkv=2):
    """:func:`_walk_case` with rows of the given ``blocks`` each (0: an
    idle row, position ``-t``: no block holds a key it may see), the last
    block of the live ones full, partly full or holding one key in turn.
    -> (its five, the rows' positions, the idle rows)."""
    bs = WALK_BS
    last = [bs, 5, max(t, 1), 7]
    held = np.asarray([max(0, (n - 1) * bs + max(last[i % 4], t))
                       for i, n in enumerate(blocks)])
    held = np.where(np.asarray(blocks) > 0, held, 0)
    return (*_walk_case(rng, t, hd, rep, kv8, held - t, held=held,
                        ctx=max(blocks) * bs, hkv=hkv),
            jnp.asarray(held - t, jnp.int32), np.asarray(blocks) == 0)


def _carried_window_case(t):
    """:func:`_window_tile_case`'s rows (one and two tiles of 4 blocks, the
    ring wrapping inside a row's FIRST tile at 131 — the tile the step
    before starts — and inside its second at 64) with idle rows before,
    between and behind them, their table entries dead blocks."""
    q, kp, vp, bt, pos, want = _ring_case(t, 40, 8, 7,
                                          [45, 131, 64, 3, 100, 131], seed=5)
    dead = np.setdiff1d(np.arange(kp.shape[1]), np.asarray(bt)[bt > 0])
    at = [0, 2, 2, 6]                                 # idle rows go here
    idle = np.zeros(len(pos) + len(at), bool)
    idle[[0, 3, 4, 9]] = True
    grow = lambda a, fill: jnp.asarray(np.insert(     # noqa: E731
        np.asarray(a), at, fill, axis=0))
    return (grow(q, 1.0), kp.at[:, dead].set(jnp.nan),
            vp.at[:, dead].set(jnp.nan), grow(bt, dead[0]), grow(pos, -t),
            np.insert(want, at, 0.0, axis=0), idle)


@pytest.mark.parametrize("hd,rep,t,kv8,shape", [
    (128, 1, 1, False, "rows"), (64, 1, 1, False, "rows"),
    (128, 1, 4, False, "rows"), (64, 1, 4, False, "rows"),
    (128, 4, 1, False, "rows"), (64, 4, 4, False, "rows"),
    (64, 1, 1, True, "rows"), (128, 4, 4, True, "rows"),
    (128, 2, 1, False, "window"), (128, 2, 4, False, "window"),
    (128, 1, 1, False, "one-row"), (64, 2, 4, False, "one-idle-row"),
    (128, 1, 1, False, "head-split"), (128, 1, 4, False, "head-split"),
], ids=["g1", "g2", "g1-verify", "g2-verify", "gqa4", "g2-gqa4-verify",
        "kv8-g2", "kv8-g1-gqa4-verify", "window", "window-verify", "one-row",
        "one-idle-row", "head-split", "head-split-verify"])
def test_paged_walk_carries_a_tile_across_grid_steps(hd, rep, t, kv8, shape,
                                                     monkeypatch):
    """A row's last tile starts tile 0 of the next grid step: on a
    simulated chip whose copies are made only when waited for, with its
    race detector on and every block outside a row's valid blocks NaN.
    Idle rows first, last and between live ones (they hand the duty on and
    keep the slot's parity); rows of ``nt - 1``, ``nt``, ``nt + 1`` and ``2
    nt + 1`` blocks side by side (1, 1, 2, 3 tiles: both parities are
    carried); decode and a T = 4 verify window; packed and plain blocks; GQA;
    int8 records; a window layer whose ring wraps in the carried tile; a
    launch of one row; a grid that splits a row's heads over two steps."""
    from deepspeed_tpu.ops import decode_attention as da

    kernel = paged_decode_attention_pallas if t == 1 \
        else paged_verify_attention_pallas
    nt = _walk_tile(hd)
    if shape == "window":
        monkeypatch.setattr(da, "_WALK_COLS", 32)        # 4 blocks of 8
        q, kp, vp, bt, pos, want, idle = _carried_window_case(t)
        got = np.asarray(kernel(q, kp, vp, bt, pos, layer=0, window=40,
                                interpret=_simulated_chip()))
    else:
        hkv = 2
        blocks = {"rows": [0, nt - 1, nt, nt + 1, 0, 2 * nt + 1, nt + 1, 1,
                           2 * nt + 1, 0],
                  "one-row": [nt + 1], "one-idle-row": [0],
                  "head-split": [1, 0, nt + 1, nt]}[shape]
        if shape == "head-split":
            hkv = 32
            monkeypatch.setattr(
                da, "_WALK_VMEM_BUDGET",
                16 * nt * WALK_BS * hd * (4 * 4 + 2 * 2))
            assert da._walk_head_tile(hkv, WALK_BS, hd, 4, nt) == 16
        rng = np.random.default_rng(560 + hd + rep + t)
        q, kp, vp, bt, clean, pos, idle = _carried_case(
            rng, t, hd, rep, kv8, blocks, hkv=hkv)
        want = np.array(paged_decode_attention_reference(
            q, kp, vp, clean, pos, layer=1))
        want[idle] = 0
        got = np.asarray(kernel(q, kp, vp, bt, pos, layer=1,
                                interpret=_simulated_chip()))
    assert not _races_found()
    assert np.isfinite(got).all(), "a tile that nobody waited for, or read " \
        "outside a row's valid blocks"
    assert not got[idle].any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _dma_sites(fn, *args):
    """``{"dma_start": n, "dma_wait": n}``: the sites of the ONE Pallas
    kernel ``fn(*args)`` launches that start and that wait for a copy."""
    def sites(jaxpr, counts):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in counts:
                counts[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                sites(sub, counts)
        return counts

    kernel, = (eqn.params["jaxpr"] for eqn in jax.make_jaxpr(fn)(
        *args).jaxpr.eqns if eqn.primitive.name == "pallas_call")
    return sites(kernel, {"dma_start": 0, "dma_wait": 0})


@pytest.mark.parametrize("kv8", [False, True], ids=["float", "kv8"])
def test_paged_walk_waits_for_every_copy_it_starts(kv8, capfd):
    """The kernel's text starts a tile at two sites (a launch's first
    step's own, or the next step's from a row of no tile; the loop's: the
    row's next tile or the next step's first) and waits at one, an operand
    each; what it starts it waits for — on a simulated chip whose copies
    signal at their start, no semaphore is left raised at the kernel's exit,
    behind rows of 0, 1, 2 and 3 tiles and behind a last row of each."""
    nt = _walk_tile(128)
    rng = np.random.default_rng(561)
    for blocks in ([0, nt + 1, 1, 0, 2 * nt + 1], [nt, 0], [2 * nt]):
        q, kp, vp, bt, clean, pos, idle = _carried_case(
            rng, 1, 128, 1, kv8, blocks)
        got = np.asarray(paged_decode_attention_pallas(
            q, kp, vp, bt, pos, layer=1,
            interpret=_simulated_chip("eager")))
        assert np.isfinite(got).all() and not _races_found()
    assert "non-zero count" not in capfd.readouterr().out

    operands = 4 if kv8 else 2
    assert _dma_sites(lambda *a: paged_decode_attention_pallas(
        *a, layer=1, interpret=False), q, kp, vp, bt, pos) == {
        "dma_start": 2 * operands, "dma_wait": operands}


@pytest.mark.parametrize(
    "hkv,r,width,itemsize,want",
    [(32, 16, 128, 2, 32),        # OPT-1.3B: 128 KB a block and side
     (16, 32, 128, 2, 16),        # OLMoE
     (32, 16, 128, 1, 32),        # int8 codes
     (8, 16, 128, 2, 8),          # a tp=4 shard of OPT
     (32, 32, 128, 4, 32),        # float32: 512 KB a block
     (64, 128, 256, 4, 16),       # 8 MB a block: the heads are split
     (24, 128, 256, 4, 24)],      # ... only in halves a multiple of 16
    ids=["opt", "olmoe", "int8", "tp-shard", "f32", "split-heads",
         "odd-heads"])
def test_paged_walk_head_tile_is_read_off_the_shapes(hkv, r, width, itemsize,
                                                     want):
    from deepspeed_tpu.ops import decode_attention as da

    assert da._walk_head_tile(hkv, r, width, itemsize) == want


def test_paged_walk_splits_heads_over_the_grid(monkeypatch):
    """A block of all heads over the VMEM budget: the heads go to a second
    grid dim in halves, each grid step copying its own head slice."""
    from deepspeed_tpu.ops import decode_attention as da

    rng = np.random.default_rng(70)
    b, h, s, d, bs = 2, 32, 64, 32, 16
    monkeypatch.setattr(da, "_WALK_VMEM_BUDGET", 6 * 16 * bs * d * 4)
    assert da._walk_head_tile(h, bs, d, 4) == 16
    kc = rng.standard_normal((b, h, s, d)).astype(np.float32)
    vc = rng.standard_normal((b, h, s, d)).astype(np.float32)
    kp, vp, bt = _paged_from_contiguous(kc, vc, 2 * b * (s // bs), bs, rng)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    pos = jnp.asarray([s - 1, 20], jnp.int32)
    want = decode_attention_reference(q, jnp.asarray(kc), jnp.asarray(vc),
                                      pos)
    got = paged_decode_attention_pallas(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt), pos,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------ a prefill chunk walks them too
# ISSUE 31: T = prefill_chunk query rows against the row's valid blocks,
# ``cdiv(base + valid, block_size)`` of them, ``nt`` blocks a landing tile.
PREFILL_T = 48
#: (base, valid): a chunk from 0 across a block boundary; from mid-block;
#: from a block's first token with valid < T; a pad row; the table's last
#: block; a short chunk across a boundary
PREFILL_ROWS = [(0, PREFILL_T), (37, PREFILL_T), (2 * WALK_BS, 20), (0, 0),
                (WALK_CTX - PREFILL_T, PREFILL_T), (WALK_BS - 2, 5)]


def _prefill_case(rng, hd, rep, rows=PREFILL_ROWS, t=PREFILL_T, ctx=None):
    bases, valid = (np.asarray(c, np.int32) for c in zip(*rows))
    q, kp, vp, bt, clean = _walk_case(rng, t, hd, rep, False, bases,
                                      held=np.where(valid > 0,
                                                    bases + valid, 0),
                                      ctx=ctx)
    return q, kp, vp, bt, clean, jnp.asarray(bases), jnp.asarray(valid)


def _assert_prefill_matches(got, want, valid):
    """Real queries equal the gather reference's; a pad row is zeros."""
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all(), "read past a valid prefix"
    for b, n in enumerate(np.asarray(valid)):
        np.testing.assert_allclose(got[b, :, :n], want[b, :, :n],
                                   rtol=2e-5, atol=2e-5)
        if n == 0:
            assert not got[b].any()


@pytest.mark.parametrize("cols", [128, 32], ids=["one-tile", "tiles"])
@pytest.mark.parametrize("rep", [1, 4])
@pytest.mark.parametrize("hd", [128, 64], ids=["g1", "g2"])
def test_paged_prefill_walks_each_rows_valid_blocks_only(hd, rep, cols,
                                                         monkeypatch):
    """The prefill kernel against the gather reference at a non-zero
    layer, every block and every table entry past ``cdiv(base + valid,
    bs)`` poisoned (NaN blocks no row owns): the output is finite and
    unchanged, so the walk read valid blocks only.  ``cols = 32`` makes a
    landing tile 1-2 blocks, so a row walks several tiles, unmasked below
    its base and masked from there."""
    from deepspeed_tpu.ops import decode_attention as da

    monkeypatch.setattr(da, "_PREFILL_COLS", cols)
    rng = np.random.default_rng(80 + hd + rep)
    q, kp, vp, bt, clean, bases, valid = _prefill_case(rng, hd, rep)
    want = paged_decode_attention_reference(q, kp, vp, clean, bases, layer=1)
    got = paged_prefill_attention_pallas(q, kp, vp, bt, bases, valid=valid,
                                         layer=1, interpret=True)
    _assert_prefill_matches(got, want, valid)


def test_paged_prefill_tile_of_two_score_registers_a_row():
    """The shape the cells run: a landing tile of 256 score columns (8
    blocks at hd 128), m and l lane-replicated over both 128-lane halves;
    a 9-block context, so a row walks two tiles."""
    rng = np.random.default_rng(93)
    ctx = 9 * WALK_BS
    q, kp, vp, bt, clean, bases, valid = _prefill_case(
        rng, 128, 1, rows=[(8 * WALK_BS - 26, PREFILL_T), (0, 30),
                           (ctx - PREFILL_T, PREFILL_T)], ctx=ctx)
    want = paged_decode_attention_reference(q, kp, vp, clean, bases, layer=1)
    got = paged_prefill_attention_pallas(q, kp, vp, bt, bases, valid=valid,
                                         layer=1, interpret=True)
    _assert_prefill_matches(got, want, valid)


def test_paged_prefill_takes_an_unpacked_pool_and_a_whole_chunk():
    """The benchmark's comparison passes a pool as ``init_cache`` built it
    (hd 64, not lane-packed: ``_lane_rows`` packs this layer's rows) and no
    ``valid`` (every query real), under jit with traced bases."""
    from deepspeed_tpu.ops import paged_kv

    rng = np.random.default_rng(90)
    t, b, hkv, d = 32, 3, 2, 64
    kp, vp, bt = _stacked_pool(rng, WALK_LAYERS, b, hkv, WALK_CTX, d,
                               WALK_BS, False)
    q = jnp.asarray(rng.standard_normal((b, hkv, t, d)), jnp.float32)
    bases = jnp.asarray([0, 41, WALK_CTX - t], jnp.int32)
    want = paged_decode_attention_reference(q, kp, vp, bt, bases, layer=1)
    got = jax.jit(lambda q, kp, vp, bases: paged_prefill_attention_pallas(
        q, kp, vp, bt, bases, layer=1, interpret=True))(q, kp, vp, bases)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    packed = paged_prefill_attention_pallas(
        q, paged_kv.pack_pool(kp), paged_kv.pack_pool(vp), bt, bases,
        layer=1, interpret=True)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(got),
                               rtol=1e-6, atol=1e-6)


def test_paged_prefill_shards_over_heads():
    """tp = 2 through ``_tp_shard_heads``: each chip walks its own head
    shard of the pool, ``valid`` replicated beside the bases."""
    from deepspeed_tpu.ops import paged_kv

    rng = np.random.default_rng(91)
    q, kp, vp, bt, clean, bases, valid = _prefill_case(
        rng, 64, 2, rows=[(37, 24), (0, 0), (WALK_CTX - 24, 24)], t=24)
    want = paged_decode_attention_reference(q, kp, vp, clean, bases, layer=1)
    with paged_kv.tp_context(_tp_mesh(2)):
        got = jax.jit(lambda q, kp, vp: paged_prefill_attention_pallas(
            q, kp, vp, bt, bases, valid=valid, layer=1, interpret=True))(
                q, kp, vp)
    _assert_prefill_matches(got, want, valid)


@pytest.mark.parametrize(
    "hkv,rows,spans,nt,r,width,itemsize,want",
    [(32, 128, 2, 16, 16, 128, 2, 16),    # OPT-1.3B: 640 KB a head
     (16, 128, 1, 8, 32, 128, 2, 16),     # OLMoE: 608 KB a head
     (8, 128, 2, 16, 16, 128, 2, 8),      # a tp=4 shard of OPT
     (8, 512, 1, 8, 32, 128, 2, 4),       # GQA rep 4 at hd 128
     (32, 128, 1, 8, 32, 128, 4, 8)],     # float32
    ids=["opt", "olmoe", "tp-shard", "gqa", "f32"])
def test_paged_prefill_head_tile_is_read_off_the_shapes(
        hkv, rows, spans, nt, r, width, itemsize, want):
    from deepspeed_tpu.ops import decode_attention as da

    assert da._prefill_head_tile(hkv, rows, spans, nt, r, width,
                                 itemsize) == want


def test_dispatcher_sends_a_prefill_chunk_to_the_kernel_on_a_tpu(monkeypatch):
    """``T > VERIFY_T_MAX`` takes the prefill kernel on a TPU — a float
    pool; an int8 record and a resident-window context keep the gather —
    and ``dispatch_log`` names the path taken, at trace time."""
    from deepspeed_tpu.ops import decode_attention as da
    from deepspeed_tpu.ops import paged_kv

    rng = np.random.default_rng(92)
    q, kp, vp, bt, clean, bases, valid = _prefill_case(rng, 64, 1)
    want = paged_decode_attention_reference(q, kp, vp, clean, bases, layer=1)
    with da.dispatch_log() as off_tpu:
        da.paged_decode_attention(q, kp, vp, clean, bases, layer=1,
                                  valid=valid)
    assert off_tpu == {"gather"}
    monkeypatch.setattr(da, "on_tpu", lambda: True)
    monkeypatch.setattr(da, "interpret_kernels", lambda: True)
    with da.dispatch_log() as paths:
        got = da.paged_decode_attention(q, kp, vp, bt, bases, layer=1,
                                        valid=valid)
    assert paths == {"paged_prefill_attn"}
    _assert_prefill_matches(got, want, valid)
    kv8 = paged_kv.pack_pool(paged_kv.quantize_pool(
        jnp.zeros(kp.shape[:3] + (WALK_BS, 64), jnp.float32)))
    with da.dispatch_log() as paths:
        da.paged_decode_attention(q, kv8, kv8, clean, bases, layer=1)
        with da.window_context(jnp.zeros(len(bases), jnp.int32), 0):
            da.paged_decode_attention(q, kp, vp, clean, bases, layer=1)
    assert paths == {"gather"}


# ------------------------------------------ a sliding-window layer's reads
import math  # noqa: E402

from deepspeed_tpu.ops import decode_attention as da  # noqa: E402


def _ring_case(t, window, bs, ring, bases, valid=None, hd=128, h=4, hkv=2,
               seed=0):
    """Rows of a window layer over a RING table: row ``r`` holds ``bases[r]
    + t`` positions, its live logical blocks (from the first query's first
    visible key to the last real query) at ring entries ``i % ring`` with
    block ids of their own, every other entry unset and the whole pool
    garbage.  -> (q, k_pool, v_pool, ring table, positions, the plain dense
    windowed attention of every row)."""
    rng = np.random.default_rng(seed)
    b = len(bases)
    nb = 1 + b * ring
    kp = rng.standard_normal((1, nb, hkv, bs, hd)).astype(np.float32)
    vp = rng.standard_normal((1, nb, hkv, bs, hd)).astype(np.float32)
    bt = np.zeros((b, ring), np.int32)
    q = rng.standard_normal((b, h, t, hd)).astype(np.float32)
    want = []
    for r, base in enumerate(bases):
        nv = t if valid is None else valid[r]
        s = base + t
        k = rng.standard_normal((hkv, s, hd)).astype(np.float32)
        v = rng.standard_normal((hkv, s, hd)).astype(np.float32)
        first, last = max(0, base - window + 1) // bs, (base + nv - 1) // bs
        assert last - first + 1 <= ring
        for li in range(first, last + 1):
            phys = 1 + r * ring + li % ring
            bt[r, li % ring] = phys
            n = min((li + 1) * bs, s) - li * bs
            kp[0, phys, :, :n] = k[:, li * bs:li * bs + n]
            vp[0, phys, :, :n] = v[:, li * bs:li * bs + n]
        kk, vv = np.repeat(k, h // hkv, 0), np.repeat(v, h // hkv, 0)
        sc = np.einsum("htd,hsd->hts", q[r], kk) / math.sqrt(hd)
        key, pos = np.arange(s)[None, :], (base + np.arange(t))[:, None]
        sc = np.where(((key <= pos) & (key > pos - window))[None], sc, -1e30)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want.append(np.einsum("hts,hsd->htd", p / p.sum(-1, keepdims=True),
                              vv))
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(bases, jnp.int32), np.stack(want))


#: row positions against a 20-key window over blocks of 8: the window
#: starts before the row's first key (0, 3, 19), at a block edge (27: first
#: visible key 8), mid-block (21, 100), and the ring of 5 entries has
#: wrapped (64, 100)
_WINDOW_BASES = [0, 3, 19, 21, 27, 64, 100]


@pytest.mark.parametrize("t", [1, 4], ids=["decode", "verify"])
def test_window_bound_in_the_paged_walk(t):
    """ISSUE 34: ``_paged_walk_kernel`` with a sliding layer's bound — the
    walk starts at the block of the first visible key through the ring
    table and masks the keys before each query's own bound inside it — and
    the XLA reference with the same bound both equal plain windowed
    attention."""
    q, kp, vp, bt, pos, want = _ring_case(t, 20, 8, 5, _WINDOW_BASES)
    ref = da.paged_decode_attention_reference(q, kp, vp, bt, pos, layer=0,
                                              window=20)
    kernel = da.paged_decode_attention_pallas if t == 1 \
        else da.paged_verify_attention_pallas
    got = kernel(q, kp, vp, bt, pos, layer=0, window=20, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


@pytest.mark.parametrize("hd,h,hkv,t,window,ring,bases,valid", [
    (64, 4, 2, 16, 24, 7, [0, 8, 30, 64, 100], None),
    (128, 4, 2, 16, 24, 7, [0, 8, 32, 64, 96], [16, 3, 16, 9, 1]),
    (128, 8, 2, 32, 40, 12, [0, 32, 64, 320], None),
], ids=["g2-mid-block", "ragged-chunks", "tiles"])
def test_window_bound_in_the_paged_prefill_walk(hd, h, hkv, t, window, ring,
                                                bases, valid):
    """ISSUE 34: ``_paged_prefill_kernel`` with a sliding layer's bound, per
    query row of the chunk: rows whose window starts before their first
    key, mid-block and at a block edge, ragged chunks (the ring holds the
    blocks up to the last REAL query) and walks of several tiles."""
    q, kp, vp, bt, pos, want = _ring_case(t, window, 8, ring, bases, valid,
                                          hd=hd, h=h, hkv=hkv)
    nv = None if valid is None else jnp.asarray(valid, jnp.int32)
    ref = np.array(da.paged_decode_attention_reference(
        q, kp, vp, bt, pos, layer=0, window=window, valid=nv))
    got = np.array(da.paged_prefill_attention_pallas(
        q, kp, vp, bt, pos, valid=nv, layer=0, window=window,
        interpret=True))
    for r, n in enumerate(valid or ()):
        # a pad query's output is nobody's
        ref[r, :, n:] = got[r, :, n:] = want[r, :, n:] = 0
    np.testing.assert_allclose(ref, want, atol=2e-5)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_ring_write_lands_where_the_windowed_read_looks():
    """``paged_cache_update(ring=True)`` writes logical block ``i`` at ring
    entry ``i % width``: a row written chunk by chunk through a static ring
    (each entry rewritten as the ring wraps) reads back, through the
    windowed reference, as plain windowed attention over all it wrote."""
    from deepspeed_tpu.ops import paged_kv

    rng = np.random.default_rng(1)
    hkv, hd, bs, ring, window, chunk, s = 2, 16, 8, 6, 24, 16, 80
    k = rng.standard_normal((1, hkv, s, hd)).astype(np.float32)
    v = rng.standard_normal((1, hkv, s, hd)).astype(np.float32)
    q = rng.standard_normal((1, 4, s, hd)).astype(np.float32)
    ck = jnp.zeros((1, 1 + ring, hkv, bs, hd))
    cv = jnp.zeros_like(ck)
    bt = jnp.asarray(1 + np.arange(ring)[None], jnp.int32)
    outs = []
    for base in range(0, s, chunk):
        pos = jnp.asarray([base], jnp.int32)
        ck, cv = paged_kv.paged_cache_update(
            ck, cv, jnp.asarray(k[:, :, base:base + chunk]),
            jnp.asarray(v[:, :, base:base + chunk]), pos, bt, layer=0,
            ring=True)
        outs.append(np.asarray(da.paged_decode_attention_reference(
            jnp.asarray(q[:, :, base:base + chunk]), ck, cv, bt, pos,
            layer=0, window=window)))
    got = np.concatenate(outs, axis=2)[0]
    kk, vv = np.repeat(k[0], 2, 0), np.repeat(v[0], 2, 0)
    sc = np.einsum("htd,hsd->hts", q[0], kk) / math.sqrt(hd)
    key, pos = np.arange(s)[None, :], np.arange(s)[:, None]
    sc = np.where(((key <= pos) & (key > pos - window))[None], sc, -1e30)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    want = np.einsum("hts,hsd->htd", p / p.sum(-1, keepdims=True), vv)
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---------------------------------------------------------------------------
# The latent kind (PR 39): the absorbed walk over a pool of ONE leaf
# ---------------------------------------------------------------------------
def _latent_case(block, t, rank=256, width=384, heads=4, seed=0):
    """Three rows over a latent pool ``[2, NB, 1, block, width]`` (lanes
    past 320 zero, as the write pads them): one ending mid-block, one whose
    last query sits on a block's last slot, one idle (all-scratch table,
    position 0)."""
    from deepspeed_tpu.ops import decode_attention as da

    rng = np.random.default_rng(seed)
    nbper = 4
    pool = rng.standard_normal((2, 1 + 3 * nbper, 1, block, width))
    pool[..., 320:] = 0
    q = rng.standard_normal((3, heads, t, width)) * 0.1
    q[..., 320:] = 0
    bt = 1 + np.arange(3 * nbper).reshape(3, nbper)
    bt[2] = 0
    pos = np.asarray([block + block // 2 - t // 2, 3 * block - t, 0])
    return (da, jnp.asarray(q, jnp.float32), jnp.asarray(pool, jnp.float32),
            jnp.asarray(bt, jnp.int32), jnp.asarray(pos, jnp.int32), rank)


def _latent_naive(q, pool, bt, pos, layer, rank):
    """float64, key by key: every head scores the row's ``[c | k_r]``
    vectors and takes the softmax-weighted sum of their first ``rank``."""
    q, pool = np.asarray(q, np.float64), np.asarray(pool, np.float64)
    b, h, t, w = q.shape
    out = np.zeros((b, h, t, rank))
    for r in range(b):
        keys = pool[layer, np.asarray(bt[r])].reshape(-1, w)
        for i in range(t):
            n = int(pos[r]) + i + 1
            s = q[r, :, i] @ keys[:n].T
            p = np.exp(s - s.max(-1, keepdims=True))
            out[r, :, i] = (p / p.sum(-1, keepdims=True)) @ keys[:n, :rank]
    return out


@pytest.mark.parametrize("block", [32, 256])
@pytest.mark.parametrize("t", [1, 4, 40], ids=["decode", "verify",
                                               "prefill-chunk"])
def test_latent_walk_equals_its_reference(block, t):
    """``paged_latent_*`` (interpreted) and the XLA reference against a
    key-by-key softmax, at block sizes 32 and 256: rows ending mid-block
    and at a block's edge; an idle row stays finite."""
    da, q, pool, bt, pos, rank = _latent_case(block, t)
    want = _latent_naive(q, pool, bt, pos, 1, rank)
    ref = da.paged_latent_attention_reference(q, pool, bt, pos, rank=rank,
                                              layer=1)
    got = da.paged_latent_attention_pallas(q, pool, bt, pos, rank=rank,
                                           layer=1, interpret=True)
    np.testing.assert_allclose(ref[:2], want[:2], atol=2e-5)
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


# ISSUE 58: a loop iteration of the latent walk is a TILE of ``nt`` blocks
# and ONE online-softmax update over their keys, and a step's last tile
# starts tile 0 of the NEXT grid step.
LATENT_BS, LATENT_W, LATENT_RANK, LATENT_HEADS = 32, 256, 128, 2


def _latent_tile_case(nt, t, blocks=None, seed=58):
    """Rows of 1, ``3 nt + 2``, 0, 1, ``nt - 1``, ``nt``, 0, ``nt + 1`` and
    ``3 nt + 2`` blocks in ONE call (the launch's first tile is partly
    landed, in buffers nothing has written yet; a carried first tile
    crosses from a long row to an empty one and back; 4, 1, 1, 2 and 4
    tiles: both parities of the slot are carried), the last block full,
    half full or holding ONE key in turn; a row's ``valid`` queries are its newest ``min(t, keys)``
    positions.  Every block that is not live for its row — the scratch id
    and what its table names past its last block too — is NaN.  ->
    ``(q, pool, bt, pos, valid)``."""
    bs = LATENT_BS
    blocks = blocks if blocks is not None else \
        [1, 3 * nt + 2, 0, 1, nt - 1, nt, 0, nt + 1, 3 * nt + 2]
    rng = np.random.default_rng(seed + nt + t)
    nbper = max(max(blocks), 1) + 1
    last = [bs, bs // 2, 1]
    keys = np.asarray([max(0, (n - 1) * bs + last[i % 3]) if n else 0
                       for i, n in enumerate(blocks)])
    pool = rng.standard_normal((2, 1 + len(blocks) * nbper, 1, bs, LATENT_W))
    bt = 1 + np.arange(len(blocks) * nbper).reshape(len(blocks), nbper)
    for row, n in enumerate(blocks):
        pool[:, bt[row, n:]] = np.nan
        bt[row, n:] = bt[row, nbper - 1]
    pool[:, 0] = np.nan
    q = rng.standard_normal((len(blocks), LATENT_HEADS, t, LATENT_W)) * 0.1
    valid = np.minimum(t, keys)
    return (jnp.asarray(q, jnp.float32), jnp.asarray(pool, jnp.float32),
            jnp.asarray(bt, jnp.int32), jnp.asarray(keys - valid, jnp.int32),
            jnp.asarray(valid, jnp.int32))


def _latent_tile_want(q, pool, bt, pos, valid, layer):
    """:func:`_latent_naive` over each row's real queries; zeros for the
    pad ones."""
    q, pool = np.asarray(q, np.float64), np.asarray(pool, np.float64)
    out = np.zeros(q.shape[:3] + (LATENT_RANK,))
    for r in range(q.shape[0]):
        keys = pool[layer, np.asarray(bt[r])].reshape(-1, q.shape[3])
        for i in range(int(valid[r])):
            n = int(pos[r]) + i + 1
            s = q[r, :, i] @ keys[:n].T
            p = np.exp(s - s.max(-1, keepdims=True))
            out[r, :, i] = (p / p.sum(-1, keepdims=True)) \
                @ keys[:n, :LATENT_RANK]
    return out


def _real(out, valid):
    """``out [B, H, T, rank]`` with every pad query's rows zeroed."""
    out = np.array(out)
    for r, v in enumerate(np.asarray(valid)):
        out[r, :, int(v):] = 0
    return out


@pytest.mark.parametrize("t", [1, 5, 128], ids=["decode", "verify",
                                                "prefill-chunk"])
@pytest.mark.parametrize("nt", [1, 2, 4])
def test_latent_walk_attends_a_tile_of_blocks_an_update(nt, t, monkeypatch):
    """``paged_latent_*`` at a tile of ``nt`` blocks against a key-by-key
    softmax and the XLA reference, on a simulated chip: its copies are made
    only when waited for, its race detector is on, its landing buffers
    start as NaN and so does every block outside a row's valid ones — a
    tile's uncopied slots, a tile nobody waited for or a read past a row's
    blocks would reach the output.  Rows of 0 / 1 / ``nt - 1`` / ``nt`` /
    ``nt + 1`` / ``3 nt + 2`` blocks side by side; an empty row hands the
    carried tile on and returns zeros."""
    from deepspeed_tpu.ops import decode_attention as da

    monkeypatch.setattr(da, "latent_tile_blocks",
                        lambda *shapes: min(nt, shapes[-1]))
    q, pool, bt, pos, valid = _latent_tile_case(nt, t)
    want = _latent_tile_want(q, pool, bt, pos, valid, 1)
    ref = da.paged_latent_attention_reference(
        q, jnp.nan_to_num(pool), bt, pos, rank=LATENT_RANK, layer=1)
    got = np.asarray(da.paged_latent_attention_pallas(
        q, pool, bt, pos, rank=LATENT_RANK, layer=1, valid=valid,
        interpret=_simulated_chip()))
    assert not _races_found()
    np.testing.assert_allclose(_real(ref, valid), want, atol=2e-5)
    assert np.isfinite(got).all(), "an uncopied slot of a tile, a tile " \
        "that nobody waited for, or a read outside a row's valid blocks"
    np.testing.assert_allclose(_real(got, valid), want, atol=2e-5)
    assert not got[np.asarray(valid) == 0].any()


@pytest.mark.parametrize("t", [1, 40], ids=["decode", "prefill-chunk"])
def test_latent_walk_waits_for_every_copy_it_starts(t, monkeypatch, capfd):
    """The latent kernel's text starts a tile at two sites (a launch's
    first step's own, or the next step's from a step of no tile; the
    loop's: the step's next tile or the next step's first) and waits at
    one; what it starts it waits for — on a simulated chip whose copies
    signal at their start, no semaphore is left raised at the kernel's
    exit, behind steps of 0, 1, 2 and 3 tiles and behind a last step of
    each."""
    from deepspeed_tpu.ops import decode_attention as da

    monkeypatch.setattr(da, "latent_tile_blocks",
                        lambda *shapes: min(2, shapes[-1]))
    for blocks in ([0, 3, 1, 0, 5], [2, 0], [4], [0]):
        q, pool, bt, pos, valid = _latent_tile_case(2, t, blocks)
        got = np.asarray(da.paged_latent_attention_pallas(
            q, pool, bt, pos, rank=LATENT_RANK, layer=0, valid=valid,
            interpret=_simulated_chip("eager")))
        assert np.isfinite(got).all() and not _races_found()
        np.testing.assert_allclose(
            _real(got, valid), _latent_tile_want(q, pool, bt, pos, valid, 0),
            atol=2e-5)
    assert "non-zero count" not in capfd.readouterr().out

    assert _dma_sites(lambda *a: da.paged_latent_attention_pallas(
        *a, rank=LATENT_RANK, layer=0, interpret=False), q, pool, bt,
        pos) == {"dma_start": 2, "dma_wait": 1}


@pytest.mark.parametrize(
    "rows,bs,w,itemsize,nbper,want",
    [(32, 512, 384, 2, 32, 4),           # Mistral Small 4, a decode step
     (512, 512, 384, 2, 32, 2),          # ... a prefill step: 16 positions
     (32, 256, 640, 2, 32, 4),           # Kimi Linear, a decode step
     (512, 256, 640, 2, 32, 2),          # ... a prefill step
     (160, 512, 384, 2, 32, 4),          # a verify window of 5
     (32, 512, 384, 2, 3, 3),            # never more than the table holds
     (32, 32, 384, 2, 512, 4),           # ... nor than the update's widths,
     (32, 1024, 384, 4, 32, 1),          # float32, 1,024 tokens: the budget
     (8192, 512, 384, 4, 32, 1)],        # never less than one block
    ids=["mistral4-decode", "mistral4-prefill", "kimi-decode",
         "kimi-prefill", "verify", "short-table", "small-blocks",
         "large-blocks", "one-block"])
def test_latent_tile_is_read_off_the_shapes(rows, bs, w, itemsize, nbper,
                                            want):
    """``latent_tile_blocks``: the most blocks whose two landing slots and
    one update's scores (float32, and ``p`` in the pool's dtype) stay in
    ``_LATENT_VMEM_BUDGET``, inside ``[1, min(NBPER, _LATENT_TILE_MAX,
    _LATENT_TILE_ROWS // rows)]`` — the next one would break one of them;
    more query rows, a smaller tile."""
    from deepspeed_tpu.ops import decode_attention as da

    nt = da.latent_tile_blocks(rows, bs, w, itemsize, nbper)

    def need(n):
        return n * bs * (2 * w * itemsize + rows * (4 + itemsize))

    assert nt == want
    assert nt == 1 or need(nt) <= da._LATENT_VMEM_BUDGET
    assert need(nt + 1) > da._LATENT_VMEM_BUDGET \
        or nt + 1 > min(da._LATENT_TILE_MAX, nbper,
                        max(da._LATENT_TILE_ROWS // rows, 1))
    assert nt <= da.latent_tile_blocks(max(rows // 2, 1), bs, w, itemsize,
                                       nbper)


def test_latent_walk_stops_at_a_rows_real_queries():
    """``valid``: a chunk's pad queries cost no block and their tiles are
    not walked — a tile wholly past ``valid`` comes back zero — while the
    real queries read what they read without it."""
    da, q, pool, bt, pos, rank = _latent_case(32, 40)
    valid = jnp.asarray([40, 17, 0], jnp.int32)
    want = _latent_naive(q, pool, bt, pos, 0, rank)
    got = np.asarray(da.paged_latent_attention_pallas(
        q, pool, bt, pos, rank=rank, layer=0, valid=valid, interpret=True))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1, :, :17], want[1, :, :17], atol=2e-5)
    assert np.all(got[1, :, 32:] == 0) and np.all(got[2] == 0)


def test_latent_write_lands_where_the_walk_reads():
    """``paged_window_update`` puts a chunk's ``[c | k_r]`` at ``(layer,
    block, offset)`` of the one leaf, across a block edge and for the real
    tokens only; the walk then reads exactly those keys."""
    from deepspeed_tpu.ops import paged_kv

    da, q, pool, bt, _, rank = _latent_case(32, 1)
    pool = jnp.zeros_like(pool)
    rng = np.random.default_rng(3)
    new = np.zeros((3, 1, 40, 384), np.float32)
    new[..., :320] = rng.standard_normal((3, 1, 40, 320))
    base = jnp.asarray([20, 0, 0], jnp.int32)
    valid = jnp.asarray([40, 9, 0], jnp.int32)
    pool = paged_kv.paged_window_update(pool, jnp.asarray(new), base, bt,
                                        valid=valid, layer=1)
    flat = np.asarray(pool[1, np.asarray(bt[0])]).reshape(-1, 384)
    np.testing.assert_array_equal(flat[20:60], new[0, 0])
    assert not flat[:20].any() and not flat[60:].any()
    row1 = np.asarray(pool[1, np.asarray(bt[1])]).reshape(-1, 384)
    np.testing.assert_array_equal(row1[:9], new[1, 0, :9])
    assert not row1[9:].any() and not np.asarray(pool[0]).any()
    got = da.paged_latent_attention_pallas(
        q, pool, bt, jnp.asarray([59, 8, 0], jnp.int32), rank=rank, layer=1,
        interpret=True)
    want = _latent_naive(q, pool, bt, [59, 8, 0], 1, rank)
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5)


def test_dispatcher_sends_the_latent_read_to_its_kernel_on_a_tpu(monkeypatch):
    """On a TPU every window width takes ``paged_latent_*`` (named by the
    width); on the CPU the gather; a tp context is refused by name."""
    from deepspeed_tpu.ops import paged_kv

    da, q, pool, bt, pos, rank = _latent_case(32, 1)
    with da.dispatch_log() as paths:
        da.paged_latent_attention(q, pool, bt, pos, rank=rank, layer=0)
    assert paths == {"latent_gather"}
    monkeypatch.setattr(da, "on_tpu", lambda: True)
    for t, name in ((1, "paged_latent_attn"), (4, "paged_latent_verify"),
                    (40, "paged_latent_prefill")):
        assert da.latent_kernel_name(t) == name
    with da.dispatch_log() as paths:
        da.paged_latent_attention(q, pool, bt, pos, rank=rank, layer=0)
    assert paths == {"paged_latent_attn"}
    with paged_kv.tp_context(object()):
        with pytest.raises(NotImplementedError, match="one shard"):
            da.paged_latent_attention(q, pool, bt, pos, rank=rank, layer=0)
    assert paged_kv.latent_pool_width(320) == 384
    assert paged_kv.latent_pool_width(256) == 256


# ISSUE 61: a sliding-window LATENT layer reads its kind's ring from the
# window's first key on (``paged_window_latent_*``: the same body, ``window``
# static).
WINDOW = 40


def _window_latent_case(t, keys, seed=61):
    """Rows holding ``keys`` tokens each (0: a pad row) over a ring the
    scheduler's own ``WindowRing`` laid out for a call of ``t`` positions —
    a row's ``valid`` queries are its newest ``min(t, keys)`` — the live
    blocks random, every other block (scratch, released, never written) NaN.
    -> ``(q, pool, ring tables, pos, valid)``."""
    from deepspeed_tpu.inference.paged import WindowRing

    bs = LATENT_BS
    rng = np.random.default_rng(seed + t)
    keys = np.asarray(keys)
    valid = np.minimum(t, keys)
    ring = WindowRing(len(keys), WINDOW, max(t, 2), bs)
    pool = np.full((2, ring.alloc.num_blocks, 1, bs, LATENT_W), np.nan)
    for row, n in enumerate(keys):
        if n:
            ring.advance(row, int(n - valid[row]), int(n))
        live = ring.tables[row][ring.tables[row] != 0]
        pool[:, live] = rng.standard_normal((2, len(live), 1, bs, LATENT_W))
    q = rng.standard_normal((len(keys), LATENT_HEADS, t, LATENT_W)) * 0.1
    return (jnp.asarray(q, jnp.float32), jnp.asarray(pool, jnp.float32),
            jnp.asarray(ring.tables, jnp.int32),
            jnp.asarray(keys - valid, jnp.int32),
            jnp.asarray(valid, jnp.int32))


def _window_latent_want(q, pool, bt, pos, valid, layer):
    """Key by key: query ``p`` keeps ``p - WINDOW < j <= p``, key ``j`` at
    ring entry ``j // bs % R``; zeros for the pad queries."""
    q, pool = np.asarray(q, np.float64), np.asarray(pool, np.float64)
    bs, width = LATENT_BS, bt.shape[1]
    out = np.zeros(q.shape[:3] + (LATENT_RANK,))
    for r in range(q.shape[0]):
        for i in range(int(valid[r])):
            p = int(pos[r]) + i
            js = np.arange(max(0, p - WINDOW + 1), p + 1)
            tile = pool[layer, np.asarray(bt)[r, js // bs % width], 0,
                        js % bs]
            s = q[r, :, i] @ tile.T
            w = np.exp(s - s.max(-1, keepdims=True))
            out[r, :, i] = (w / w.sum(-1, keepdims=True)) \
                @ tile[:, :LATENT_RANK]
    return out


@pytest.mark.parametrize("t", [1, 24, 128], ids=["decode", "chunk", "wide"])
def test_window_latent_walk_reads_the_ring_from_the_windows_first_key(t):
    """``paged_window_latent_*`` against a key-by-key softmax and the XLA
    reference on a simulated chip (NaN landing buffers, NaN outside every
    row's live ring blocks, the race detector): rows of 0 / 1 / ``WINDOW``
    (exactly the window) / ``WINDOW + 1`` (one key out) / many keys, whose
    rings have wrapped and whose oldest blocks were released."""
    from deepspeed_tpu.ops import decode_attention as da

    keys = [1, WINDOW, 0, WINDOW + 1, 31, 33, 5 * LATENT_BS + 7,
            9 * LATENT_BS, 300]
    q, pool, bt, pos, valid = _window_latent_case(t, keys)
    want = _window_latent_want(q, pool, bt, pos, valid, 1)
    ref = da.paged_latent_attention_reference(
        q, jnp.nan_to_num(pool), bt, pos, rank=LATENT_RANK, layer=1,
        window=WINDOW, valid=valid)
    got = np.asarray(da.paged_latent_attention_pallas(
        q, pool, bt, pos, rank=LATENT_RANK, layer=1, valid=valid,
        window=WINDOW, interpret=_simulated_chip()))
    assert not _races_found()
    np.testing.assert_allclose(_real(ref, valid), want, atol=2e-5)
    assert np.isfinite(got).all(), "a read outside a row's live ring blocks"
    np.testing.assert_allclose(_real(got, valid), want, atol=2e-5)
    assert not got[np.asarray(valid) == 0].any()


def test_window_latent_launches_have_names_of_their_own(monkeypatch):
    """A sliding latent layer's launches are told from the full walk's in a
    trace, and 32 heads keep the query tile they had while 128 take 4
    positions a step (``latent_walk_shape``)."""
    from deepspeed_tpu.ops import decode_attention as da

    assert da.latent_kernel_name(1, 513) == "paged_window_latent_attn"
    assert da.latent_kernel_name(512, 513) == "paged_window_latent_prefill"
    assert da.latent_kernel_name(512) == "paged_latent_prefill"
    assert da.latent_walk_shape(32, 512, 512, 384, 2, 32) == (16, 2)
    assert da.latent_walk_shape(32, 1, 256, 640, 2, 17) == (1, 4)
    assert da.latent_walk_shape(128, 512, 256, 640, 2, 128)[0] == 4
    assert da.latent_walk_shape(64, 512, 128, 1152, 2, 10)[0] == 8
    q, pool, bt, pos, valid = _window_latent_case(1, [70, 3])
    monkeypatch.setattr(da, "on_tpu", lambda: True)
    monkeypatch.setattr(da, "interpret_kernels", lambda: True)
    with da.dispatch_log() as paths:
        da.paged_latent_attention(q, jnp.nan_to_num(pool), bt, pos,
                                  rank=LATENT_RANK, layer=0, window=WINDOW)
    assert paths == {"paged_window_latent_attn"}
