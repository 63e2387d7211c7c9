"""Learned sparse attention over the paged pool
(``ops/sparse_index_attention.py`` and its three kernels in
``ops/decode_attention.py``): the kernels against their XLA references in
interpret mode, and the whole read against a per-query loop written here."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import decode_attention as da
from deepspeed_tpu.ops import paged_kv
from deepspeed_tpu.ops import sparse_index_attention as sia


# ------------------------------------------------------------------ select
@pytest.mark.parametrize("n,s,k", [(16, 512, 64), (3, 200, 50),
                                   (8, 256, 256), (1, 130, 7)])
def test_select_kernel_is_top_k_as_a_threshold(n, s, k):
    rng = np.random.default_rng(n + s)
    x = rng.normal(size=(n, s)).astype(np.float32)
    x[0, :s // 2] = 0.5                 # a long tie across the cut
    x[1 % n, 10:] = -np.inf             # fewer finite keys than k
    x[2 % n, ::3] = -0.0                # -0.0 ties with 0.0
    x[2 % n, 1::3] = 0.0
    theta, last = da.paged_sparse_select_pallas(jnp.asarray(x), k,
                                                interpret=True)
    want_theta, want_last = sia.select_threshold_reference(jnp.asarray(x), k)
    np.testing.assert_array_equal(np.asarray(theta), np.asarray(want_theta))
    np.testing.assert_array_equal(np.asarray(last), np.asarray(want_last))
    # and the set it describes is the k first of a stable descending sort
    keep = np.asarray(sia.chosen(jnp.asarray(x), theta, last,
                                 jnp.full((n,), s - 1, jnp.int32)))
    for row in range(n):
        order = np.argsort(-x[row], kind="stable")[:k]
        assert set(np.flatnonzero(keep[row])) == set(order), row


# ------------------------------------------------------------------ scores
def _index_case(rng, b, hi, t, di, bs, nbper, layers, dtype=jnp.float32):
    nb = 1 + b * nbper
    pool = jnp.asarray(rng.normal(size=(layers, nb, 1, bs, di)), dtype)
    bt = jnp.asarray(1 + rng.permutation(b * nbper).reshape(b, nbper),
                     jnp.int32)
    qi = jnp.asarray(rng.normal(size=(b, hi, t, di)), dtype)
    wi = jnp.asarray(rng.normal(size=(b, t, hi)), jnp.float32)
    return pool, bt, qi, wi


@pytest.mark.parametrize("b,hi,t,di,bs,nbper,packed", [
    (3, 2, 1, 16, 8, 12, True),        # decode rows, g = 8 spans a lane row
    (2, 4, 64, 64, 32, 20, True),      # two query tiles, two landing tiles
    (2, 4, 5, 64, 32, 6, False),       # a pool exactly as init_cache built it
    (2, 3, 8, 128, 16, 4, True),       # g = 1
])
def test_scores_kernel_walks_the_valid_blocks(b, hi, t, di, bs, nbper, packed):
    rng = np.random.default_rng(b * 100 + t)
    pool, bt, qi, wi = _index_case(rng, b, hi, t, di, bs, nbper, layers=2)
    pos = jnp.asarray(rng.integers(0, nbper * bs - t, b), jnp.int32)
    valid = None if t == 1 else jnp.asarray(rng.integers(0, t + 1, b),
                                            jnp.int32)
    last = sia.last_visible(pos, t, b, valid)
    # blocks past a row's valid prefix are never read: poison them
    held = (np.asarray(last).max(axis=1) + bs) // bs
    poisoned = np.array(bt)
    for row in range(b):
        poisoned[row, max(held[row], 0):] = 0
    pool = pool.at[:, 0].set(jnp.nan)
    view = paged_kv.pack_pool(pool) if packed else pool
    got = da.paged_index_scores_pallas(qi, wi, view, jnp.asarray(poisoned),
                                       last, layer=1, interpret=True)
    want = sia.index_scores_reference(qi, wi, view, bt, last, 1)
    finite = np.isfinite(np.asarray(want))
    np.testing.assert_array_equal(np.isfinite(np.asarray(got)), finite)
    np.testing.assert_allclose(np.asarray(got)[finite],
                               np.asarray(want)[finite], rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------- the whole read
def _loop_attention(q, k_all, v_all, qi, wi, ki_all, last, topk):
    """Per query, in numpy float64: score the visible keys, take the topk
    of largest score (ties: the lower position), softmax over them."""
    b, h, t, d = q.shape
    rep = h // k_all.shape[1]
    out = np.zeros((b, h, t, d))
    for row in range(b):
        for i in range(t):
            n = int(last[row, i]) + 1
            if n <= 0:
                continue
            dots = np.maximum(np.einsum("hd,sd->hs", qi[row, :, i],
                                        ki_all[row, :n]), 0.0)
            score = wi[row, i] @ dots
            keys = np.arange(n) if n <= topk else \
                np.sort(np.argsort(-score, kind="stable")[:topk])
            for head in range(h):
                kk, vv = k_all[row, head // rep, keys], \
                    v_all[row, head // rep, keys]
                att = kk @ q[row, head, i] / math.sqrt(d)
                p = np.exp(att - att.max())
                out[row, head, i] = (p / p.sum()) @ vv
    return out


def _read_case(seed, b, t, topk, pos, valid=None, h=4, hkv=2, d=16, hi=2,
               di=16, bs=8, nbper=16, layers=2, layer=1):
    rng = np.random.default_rng(seed)
    nb = 1 + b * nbper
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    pools = {"k": f32(layers, nb, hkv, bs, d), "v": f32(layers, nb, hkv, bs, d),
             "idx": f32(layers, nb, 1, bs, di)}
    bt = jnp.asarray(1 + rng.permutation(b * nbper).reshape(b, nbper),
                     jnp.int32)
    q, qi, wi = f32(b, h, t, d), f32(b, hi, t, di), f32(b, t, hi)
    pos = jnp.asarray(pos, jnp.int32)
    valid = None if valid is None else jnp.asarray(valid, jnp.int32)
    packed = paged_kv.pack_pool(pools)
    got, counts = sia.paged_sparse_attention(
        q, packed["k"], packed["v"], packed["idx"], qi, wi, bt, pos,
        topk=topk, layer=layer, valid=valid)
    flat = {name: np.asarray(paged_kv.paged_gather(leaf, bt, layer=layer),
                             np.float64) for name, leaf in pools.items()}
    last = np.asarray(sia.last_visible(pos, t, b, valid))
    want = _loop_attention(np.asarray(q, np.float64), flat["k"], flat["v"],
                           np.asarray(qi, np.float64),
                           np.asarray(wi, np.float64), flat["idx"][:, 0],
                           last, topk)
    return np.asarray(got), want, (q, packed, bt, pos, valid, counts)


@pytest.mark.parametrize("name,t,pos,valid", [
    ("decode", 1, [100, 5, 40], None),            # rows past and under topk
    ("verify", 4, [90, 29, 31], None),            # a window across topk
    ("chunk-straddles-topk", 16, [24, 96, 0], [16, 16, 9]),
    ("chunk-with-a-pad-row", 16, [64, 0, 40], [16, 0, 3]),
])
def test_read_agrees_with_a_per_query_loop(name, t, pos, valid):
    got, want, (*_, counts) = _read_case(7, 3, t, 32, pos, valid)
    real = np.ones(got.shape[:1] + got.shape[2:3], bool) if valid is None \
        else np.arange(t)[None, :] < np.asarray(valid)[:, None]
    mask = real[:, None, :, None]
    np.testing.assert_allclose(got * mask, want * mask, atol=2e-5)
    # what the call says it did, against the lengths: a real query with n
    # visible keys scores n and attends min(n, topk); whole blocks are read
    ctx = np.asarray(pos)[:, None] + np.arange(t)[None, :] + 1
    n = ctx[real]
    rows = int(((ctx > 32) & real).any(axis=1).sum())
    counts = dict(zip(sia.COUNTS, np.asarray(counts).tolist()))
    assert counts["index_keys"] == counts["kv_valid"] == n.sum()
    assert counts["kv_selected"] == np.minimum(n, 32).sum()
    assert counts["sparse_rows"] == rows
    # rows fetched once a row, whichever of its queries chose them
    assert 0 < counts["kv_read"] <= sum(
        -(-int(np.asarray(pos)[i] + real[i].sum()) // 8) * 8
        for i in range(3) if real[i].any())
    assert t > 1 or counts["kv_selected"] <= counts["kv_read"]


def test_rows_under_topk_take_todays_read():
    # no row past topk: the dense branch, bit for bit today's dispatch
    got, want, (q, pools, bt, pos, valid, counts) = _read_case(
        3, 3, 1, 32, [30, 5, 12])
    dense = da.paged_decode_attention(q, pools["k"], pools["v"], bt, pos,
                                      layer=1)
    np.testing.assert_array_equal(got, np.asarray(dense))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # nothing scored, every visible key attended, whole blocks read
    assert np.asarray(counts).tolist() == [0, 50, 50, 0, 32 + 8 + 16]
    # a short row beside a long one selects every key it has: the dense
    # answer, through the sparse branch
    got, _, (q, pools, bt, pos, valid, _) = _read_case(3, 3, 1, 32,
                                                     [100, 5, 12])
    dense = da.paged_decode_attention(q, pools["k"], pools["v"], bt, pos,
                                      layer=1)
    np.testing.assert_allclose(got[1:], np.asarray(dense)[1:], atol=2e-5)
    assert np.abs(got[0] - np.asarray(dense)[0]).max() > 1e-3


def test_a_pool_no_longer_than_topk_never_selects():
    got, want, (q, pools, bt, pos, valid, _) = _read_case(5, 2, 1, 128,
                                                        [100, 7])
    dense = da.paged_decode_attention(q, pools["k"], pools["v"], bt, pos,
                                      layer=1)
    np.testing.assert_array_equal(got, np.asarray(dense))


@pytest.mark.parametrize("name,t,pos,valid,local", [
    ("decode", 1, [300, 5, 140, 0], None, False),
    ("decode-local-choices", 1, [500, 260, 140, 33], None, True),
    ("chunk-straddles-topk", 16, [24, 296, 0, 100], [16, 16, 9, 0], False),
    ("chunk-local-choices", 8, [400, 96, 0, 100], [8, 8, 3, 8], True),
])
def test_read_kernel_is_the_masked_walk(name, t, pos, valid, local):
    # ``paged_sparse_attn`` (interpret mode) against the XLA walk on the
    # same scores and thresholds, at a head a lane row wide; ``local``:
    # scores that fall with distance, so that whole blocks and whole tiles
    # hold no chosen key and are neither copied nor attended
    rng = np.random.default_rng(len(name))
    b, h, hkv, d, bs, nbper, layers, layer, topk = 4, 4, 2, 128, 8, 64, 2, 1, 32
    nb = 1 + b * nbper
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    k_pool, v_pool = f32(layers, nb, hkv, bs, d), f32(layers, nb, hkv, bs, d)
    bt = jnp.asarray(1 + rng.permutation(b * nbper).reshape(b, nbper),
                     jnp.int32)
    q = f32(b, h, t, d)
    pos = jnp.asarray(pos, jnp.int32)
    valid = None if valid is None else jnp.asarray(valid, jnp.int32)
    last = sia.last_visible(pos, t, b, valid)
    s = jnp.arange(nbper * bs)
    scores = f32(b, t, nbper * bs)
    if local:
        scores = scores - 0.5 * jnp.abs(
            s[None, None, :] - last[:, :, None] // 2).astype(jnp.float32)
    scores = jnp.where(s[None, None, :] <= last[:, :, None], scores,
                       -jnp.inf)
    theta, s_last = sia.select_threshold_reference(scores, topk)
    keep = sia.chosen(scores, theta, s_last, last)
    hit = jnp.any(keep.reshape(b, t, nbper, bs), axis=(1, 3))
    if local:
        assert float(hit.mean()) < 0.5
    want = sia._masked_walk(q, k_pool, v_pool, bt, keep, last, layer,
                            1.0 / math.sqrt(d))
    got = da.paged_sparse_attention_pallas(
        q, k_pool, v_pool, bt, scores, theta, s_last, last, hit, layer=layer,
        interpret=True)
    real = np.asarray(last >= 0) if valid is None else np.asarray(
        jnp.arange(t)[None, :] < valid[:, None])
    mask = real[:, None, :, None]
    np.testing.assert_allclose(np.asarray(got) * mask,
                               np.asarray(want) * mask, atol=2e-5)
    # a pad row (no query of it real) walks nothing and comes back zeros
    if valid is not None:
        assert not np.asarray(got)[np.asarray(valid) == 0].any()


def test_third_leaf_is_written_where_k_and_v_are():
    rng = np.random.default_rng(0)
    layers, nb, bs, di, b, nbper, t = 2, 7, 8, 16, 2, 3, 5
    pool = paged_kv.pack_pool(jnp.zeros((layers, nb, 1, bs, di)))
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    win = jnp.asarray(rng.normal(size=(b, 1, t, di)), jnp.float32)
    pos, valid = jnp.asarray([6, 0]), jnp.asarray([5, 3])
    pool = paged_kv.paged_window_update(pool, win, pos, bt, valid, layer=1)
    flat = np.asarray(paged_kv.paged_gather(pool, bt, layer=1, head_dim=di))
    np.testing.assert_array_equal(flat[0, 0, 6:11], np.asarray(win)[0, 0])
    np.testing.assert_array_equal(flat[1, 0, 0:3], np.asarray(win)[1, 0, :3])
    assert not flat[1, 0, 3:].any() and not flat[0, 0, :6].any()
    other = np.asarray(paged_kv.paged_gather(pool, bt, layer=0, head_dim=di))
    assert not other.any()


def test_sharded_or_quantized_pools_are_refused_by_name():
    got, want, (q, pools, bt, pos, valid, _) = _read_case(1, 2, 1, 32,
                                                        [40, 3])
    record = paged_kv.quantize_pool(pools["k"])
    with pytest.raises(NotImplementedError, match="learned sparse attention"):
        sia.paged_sparse_attention(
            q, record, record, pools["idx"], q[:, :2], jnp.ones((2, 1, 2)),
            bt, pos, topk=32, layer=1)


# ISSUE 61: the selection over a LATENT pool (``paged_sparse_latent_attention``):
# the same scores and threshold, the absorbed read under them.
L_RANK, L_W, L_DI = 128, 256, 128


def _latent_read_case(seed, b, t, topk, pos, valid=None, h=8, hi=2, bs=8,
                      nbper=16, layers=2, layer=1):
    """``paged_sparse_latent_attention`` against a per-query loop over the
    gathered latents: -> ``(got, want, counts, the dense latent read)``."""
    rng = np.random.default_rng(seed)
    nb = 1 + b * nbper
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    pool, idx = f32(layers, nb, 1, bs, L_W), f32(layers, nb, 1, bs, L_DI)
    bt = jnp.asarray(1 + rng.permutation(b * nbper).reshape(b, nbper),
                     jnp.int32)
    q, qi, wi = f32(b, h, t, L_W) * 0.1, f32(b, hi, t, L_DI), f32(b, t, hi)
    pos = jnp.asarray(pos, jnp.int32)
    valid = None if valid is None else jnp.asarray(valid, jnp.int32)
    got, counts = sia.paged_sparse_latent_attention(
        q, pool, idx, qi, wi, bt, pos, rank=L_RANK, topk=topk, layer=layer,
        valid=valid)
    lat = np.asarray(paged_kv.paged_gather(pool, bt, layer=layer),
                     np.float64)[:, 0]
    ki = np.asarray(paged_kv.paged_gather(idx, bt, layer=layer),
                    np.float64)[:, 0]
    last = np.asarray(sia.last_visible(pos, t, b, valid))
    q64, qi64, wi64 = (np.asarray(a, np.float64) for a in (q, qi, wi))
    want = np.zeros((b, h, t, L_RANK))
    for row in range(b):
        for i in range(t):
            n = last[row, i] + 1
            if n <= 0:
                continue
            score = (wi64[row, i][:, None] * np.maximum(
                qi64[row, :, i] @ ki[row, :n].T, 0)).sum(0)
            keep = np.argsort(-score, kind="stable")[:topk]
            tile = lat[row, :n][keep]
            s = q64[row, :, i] @ tile.T
            p = np.exp(s - s.max(-1, keepdims=True))
            want[row, :, i] = (p / p.sum(-1, keepdims=True)) \
                @ tile[:, :L_RANK]
    dense = da.paged_latent_attention(q, pool, bt, pos, rank=L_RANK,
                                      layer=layer, valid=valid)
    return np.asarray(got), want, dict(zip(
        sia.COUNTS, np.asarray(counts).tolist())), np.asarray(dense)


@pytest.mark.parametrize("name,t,pos,valid", [
    ("decode", 1, [100, 5, 40], None),            # rows past and under topk
    ("exactly-topk-and-one-more", 1, [31, 32, 33], None),
    ("chunk-straddles-topk", 16, [24, 96, 0], [16, 16, 9]),
    ("chunk-with-a-pad-row", 16, [64, 0, 40], [16, 0, 3]),
])
def test_latent_read_agrees_with_a_per_query_loop(name, t, pos, valid):
    got, want, counts, _ = _latent_read_case(7, 3, t, 32, pos, valid)
    real = np.ones(got.shape[:1] + got.shape[2:3], bool) if valid is None \
        else np.arange(t)[None, :] < np.asarray(valid)[:, None]
    mask = real[:, None, :, None]
    np.testing.assert_allclose(got * mask, want * mask, atol=2e-5)
    ctx = np.asarray(pos)[:, None] + np.arange(t)[None, :] + 1
    n = ctx[real]
    assert counts["index_keys"] == counts["kv_valid"] == n.sum()
    assert counts["kv_selected"] == np.minimum(n, 32).sum()
    assert counts["sparse_rows"] == int(((ctx > 32) & real).any(axis=1).sum())


def test_latent_rows_under_topk_take_the_dense_walk():
    # no row past topk (a context of exactly topk keys too): the dense
    # latent walk, bit for bit; one key more and the row selects
    got, want, counts, dense = _latent_read_case(3, 3, 1, 32, [31, 5, 12])
    np.testing.assert_array_equal(got, dense)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert [counts[k] for k in sia.COUNTS] == [0, 51, 51, 0, 32 + 8 + 16]
    got, want, counts, dense = _latent_read_case(3, 3, 1, 32, [32, 5, 12])
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert counts["sparse_rows"] == 1 and counts["kv_selected"] == 32 + 6 + 13
    assert np.abs(got[0] - dense[0]).max() > 1e-4
    np.testing.assert_allclose(got[1:], dense[1:], atol=2e-5)


@pytest.mark.parametrize("name,t,pos,valid,local", [
    ("decode", 1, [300, 5, 140, 0], None, False),
    ("decode-local-choices", 1, [500, 260, 140, 33], None, True),
    ("chunk-straddles-topk", 16, [24, 296, 0, 100], [16, 16, 9, 0], False),
    ("chunk-local-choices", 8, [400, 96, 0, 100], [8, 8, 3, 8], True),
])
def test_latent_read_kernel_is_the_masked_walk(name, t, pos, valid, local):
    # ``paged_sparse_latent_attn`` on a simulated chip (NaN landing buffers:
    # a slot nobody copied must stay masked; the race detector) against the
    # XLA walk on the same scores and thresholds; ``local``: scores that
    # fall with distance, so that whole blocks and whole tiles hold no
    # chosen key and are neither copied nor attended; every block no query
    # chose is NaN in the pool
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.default_rng(len(name))
    b, h, bs, nbper, layers, layer, topk = 4, 8, 8, 64, 2, 1, 32
    nb = 1 + b * nbper
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    pool = f32(layers, nb, 1, bs, L_W)
    bt = jnp.asarray(1 + rng.permutation(b * nbper).reshape(b, nbper),
                     jnp.int32)
    q = f32(b, h, t, L_W) * 0.1
    pos = jnp.asarray(pos, jnp.int32)
    valid = None if valid is None else jnp.asarray(valid, jnp.int32)
    last = sia.last_visible(pos, t, b, valid)
    s = jnp.arange(nbper * bs)
    scores = f32(b, t, nbper * bs)
    if local:
        scores = scores - 0.5 * jnp.abs(
            s[None, None, :] - last[:, :, None] // 2).astype(jnp.float32)
    scores = jnp.where(s[None, None, :] <= last[:, :, None], scores,
                       -jnp.inf)
    theta, s_last = sia.select_threshold_reference(scores, topk)
    keep = sia.chosen(scores, theta, s_last, last)
    real = last >= 0 if valid is None else (
        jnp.arange(t)[None, :] < valid[:, None]) & (last >= 0)
    hit = np.asarray(jnp.any((keep & real[:, :, None])
                             .reshape(b, t, nbper, bs), axis=(1, 3)))
    if local:
        assert hit.mean() < 0.5
    want = sia._masked_latent_walk(q, pool, bt, keep, last, layer, L_RANK)
    holed = np.array(pool)
    for row in range(b):
        holed[:, np.asarray(bt)[row][~hit[row]]] = np.nan
    holed[:, 0] = np.nan
    got, landed = da.paged_sparse_latent_attention_pallas(
        q, jnp.asarray(holed), bt, scores, theta, s_last, last, rank=L_RANK,
        layer=layer, real=real, interpret=pltpu.InterpretParams(
            dma_execution_mode="on_wait", detect_races=True))
    mask = np.asarray(real)[:, None, :, None]
    assert np.isfinite(np.asarray(got)).all(), "a block no query chose"
    np.testing.assert_allclose(np.asarray(got) * mask,
                               np.asarray(want) * mask, atol=2e-5)
    # a decode step lands each hit block once; a chunk's steps each their own
    assert int(landed) == hit.sum() if t <= 8 else int(landed) >= hit.sum()
    if valid is not None:
        assert not np.asarray(got)[np.asarray(valid) == 0].any()
