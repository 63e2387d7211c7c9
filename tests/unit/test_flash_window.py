"""ISSUE 47: a static sliding ``window`` inside flash attention — forward,
``dq`` and ``dkv`` of all three kernel generations — equals the dense
masked attention (``models/llama.py _dense_attention``) forward and
backward at S in {W - 1, W, W + 1, 2W, 2W + 37}, with 7 query heads a KV
head and with 1."""

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


@pytest.mark.parametrize("generation", sorted(GENERATIONS))
@pytest.mark.parametrize("rep", [7, 1])
@pytest.mark.parametrize("s_len", LENGTHS)
def test_windowed_flash_is_the_dense_mask(generation, rep, s_len,
                                          monkeypatch):
    for key in ("DS_FLASH_V2", "DS_FLASH_V3", "DS_FLASH_V3_MIN_KV"):
        monkeypatch.delenv(key, raising=False)
    for key, value in GENERATIONS[generation].items():
        monkeypatch.setenv(key, value)
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
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, W) * ct), (0, 1, 2))(
        q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-4)


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
