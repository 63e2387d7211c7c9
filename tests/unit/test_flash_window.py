"""ISSUE 47: a static sliding ``window`` inside flash attention — forward,
``dq`` and ``dkv`` of all three kernel generations — equals the dense
masked attention (``models/llama.py _dense_attention``) forward and
backward at S in {W - 1, W, W + 1, 2W, 2W + 37}, with 7 query heads a KV
head and with 1.  ISSUE 62: the same with the tiles an edge crosses computed
in row strips (``fa._STRIP`` set to 8 here: the program reads the strip from
its shapes, and 256 rows would need S in the thousands), and the calls a
strip cannot serve falling back to whole tiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import llama
from deepspeed_tpu.ops import flash_attention as fa

W = 24
LENGTHS = (W - 1, W, W + 1, 2 * W, 2 * W + 37)
#: environment that steers ``_generation`` to each of the three
GENERATIONS = {"v2": {}, "v3": {"DS_FLASH_V2": "0", "DS_FLASH_V3_MIN_KV": "8"},
               "v1": {"DS_FLASH_V2": "0", "DS_FLASH_V3": "0"}}


def _dense(q, k, v, window):
    cfg = llama.LlamaConfig(num_heads=q.shape[1], num_kv_heads=k.shape[1],
                            hidden_size=q.shape[1] * q.shape[3],
                            head_width=q.shape[3])
    return llama._dense_attention(cfg, q, k, v, window)


def _steer(monkeypatch, generation, strip):
    for key in ("DS_FLASH_V2", "DS_FLASH_V3", "DS_FLASH_V3_MIN_KV"):
        monkeypatch.delenv(key, raising=False)
    for key, value in GENERATIONS[generation].items():
        monkeypatch.setenv(key, value)
    if strip:
        monkeypatch.setattr(fa, "_STRIP", strip)


@pytest.mark.parametrize("strip", [0, 8], ids=["whole", "strips"])
@pytest.mark.parametrize("generation", sorted(GENERATIONS))
@pytest.mark.parametrize("rep", [7, 1])
@pytest.mark.parametrize("s_len", LENGTHS)
def test_windowed_flash_is_the_dense_mask(generation, rep, s_len, strip,
                                          monkeypatch):
    _steer(monkeypatch, generation, strip)
    ks = jax.random.split(jax.random.PRNGKey(s_len), 4)
    hkv = 2
    q = jax.random.normal(ks[0], (1, hkv * rep, s_len, 16))
    k = jax.random.normal(ks[1], (1, hkv, s_len, 16))
    v = jax.random.normal(ks[2], (1, hkv, s_len, 16))
    ct = jax.random.normal(ks[3], q.shape)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, window=W, block_q=16, block_k=8)

    before = fa.choices()
    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, W),
                               atol=2e-6, rtol=1e-5)
    (choice,) = fa.choices(since=before)
    assert (choice.generation, choice.window) == (generation, W)
    assert choice.strip == (strip if generation != "v1" else 0)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, W) * ct), (0, 1, 2))(
        q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-4)


#: case -> (q_len, kv_len, block_q, block_k, window, causal, query heads a KV
#: head, the strip the call must resolve to at ``_STRIP`` = 16)
STRIP_CASES = {
    "causal-equal-blocks": (128, 128, 64, 64, 0, True, 1, 16),
    "gqa": (128, 128, 64, 64, 0, True, 3, 16),
    "window-a-multiple": (128, 128, 64, 64, 32, True, 2, 16),
    "window-across-two-tiles": (256, 256, 64, 64, 144, True, 1, 16),
    "window-under-a-block": (128, 128, 128, 128, 64, True, 1, 16),
    "window-not-a-multiple-falls-back": (128, 128, 64, 64, 40, True, 1, 0),
    "block-q-half-of-block-k": (128, 128, 32, 64, 0, True, 1, 16),
    "block-q-twice-block-k-window": (128, 128, 64, 32, 16, True, 2, 16),
    "blocks-not-a-multiple-fall-back": (96, 96, 24, 48, 0, True, 1, 0),
    "pad-inside-an-edge-tile": (120, 120, 64, 64, 0, True, 1, 16),
    "pad-and-window": (100, 100, 32, 32, 32, True, 2, 16),
    "fewer-queries-than-keys": (64, 128, 32, 32, 0, True, 1, 16),
    "more-queries-than-keys": (128, 64, 32, 32, 0, True, 1, 16),
    "not-causal": (128, 128, 64, 64, 0, False, 1, 16),
    "not-causal-padded": (120, 120, 64, 64, 0, False, 2, 16),
}


@pytest.mark.parametrize("generation", ["v2", "v3"])
@pytest.mark.parametrize("case", sorted(STRIP_CASES))
def test_strips_inside_a_tile_are_the_dense_mask(case, generation,
                                                 monkeypatch):
    """Forward, ``dq``, ``dk`` and ``dv`` of the resident and the chunked
    kernels with the edge tiles in strips and the interior ones unmasked,
    against dense masked attention; and what ``computed_pairs`` says the
    kernels multiply, against a count of the sub-tiles the mask touches."""
    q_len, kv_len, bq, bk, window, causal, rep, strip = STRIP_CASES[case]
    _steer(monkeypatch, generation, 16)
    ks = jax.random.split(jax.random.PRNGKey(q_len + kv_len), 4)
    q = jax.random.normal(ks[0], (1, 2 * rep, q_len, 16))
    k = jax.random.normal(ks[1], (1, 2, kv_len, 16))
    v = jax.random.normal(ks[2], (1, 2, kv_len, 16))
    ct = jax.random.normal(ks[3], q.shape)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  block_q=bq, block_k=bk)

    def dense(q, k, v):
        if causal and q_len == kv_len:
            return _dense(q, k, v, window)
        return fa.mha_reference(q, k, v, causal=causal)

    before = fa.choices()
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-6,
                               rtol=1e-5)
    (choice,) = fa.choices(since=before)
    assert (choice.generation, choice.strip, choice.causal) == (
        generation, strip, causal), choice
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * ct), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-4)

    # in strips, the kernels multiply exactly the sub-tiles that hold a pair
    # the mask lets through (padded query rows see by the same rule)
    q_pad, kv_pad = q_len + (-q_len) % bq, kv_len + (-kv_len) % bk
    rows, cols = np.arange(q_pad)[:, None], np.arange(kv_pad)[None, :]
    seen = np.broadcast_to(cols < kv_len, (q_pad, kv_pad)).copy()
    if causal:
        seen &= cols <= rows
    if window:
        seen &= rows - cols < window
    visible, computed = fa.computed_pairs(choice)
    assert visible == seen[:q_len].sum()
    if strip:
        tiles = seen.reshape(q_pad // strip, strip, kv_pad // strip, strip)
        assert computed == tiles.any(axis=(1, 3)).sum() * strip * strip
    else:
        step = kv_pad if generation == "v2" else bk
        tiles = seen.reshape(q_pad // bq, bq, kv_pad // step, step)
        assert computed == tiles.any(axis=(1, 3)).sum() * bq * step


def test_no_window_is_recorded_as_none_and_a_bad_one_is_refused():
    q = jnp.ones((1, 2, 32, 16))
    before = fa.choices()
    fa.flash_attention(q, q, q)
    (choice,) = fa.choices(since=before)
    assert choice.window == 0
    assert choice == fa.Choice(32, 32, 16, "v2", 32, 32, "chosen")
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q[:, :, :16], q, q, window=8)


def test_blocks_outside_the_band_are_skipped_not_masked():
    """The band's block arithmetic, against a brute-force count: block
    (qi, ki) runs iff it holds a visible (query, key) pair; the clamped
    index maps name only such blocks."""
    bq, bk, w, s = 16, 8, 24, 96
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (cols <= rows) & (rows - cols < w)
    nq, nk = s // bq, s // bk
    for qi in range(nq):
        visible = [ki for ki in range(nk)
                   if seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].any()]
        for ki in range(nk):
            run = ki * bk <= qi * bq + bq - 1
            run = bool(fa._in_band(run, qi, ki, bq, bk, w))
            assert run == (ki in visible), (qi, ki)
            assert int(fa._band_k(qi, ki, bq, bk, w)) \
                == min(max(ki, visible[0]), visible[-1])
    for ki in range(nk):
        visible = [qi for qi in range(nq)
                   if seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].any()]
        for qi in range(nq):
            assert int(fa._band_q(ki, qi, bq, bk, w, nq)) \
                == min(max(qi, visible[0]), visible[-1])
