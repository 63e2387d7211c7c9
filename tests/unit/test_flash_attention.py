"""Flash-attention kernel vs einsum reference (interpret mode on CPU).

Model: reference tests/unit/ops/* comparing CUDA kernels to eager torch.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.ops.flash_attention import flash_attention, mha_reference

slow = pytest.mark.slow  # Pallas interpret mode: minutes on CPU


def rand_qkv(key, b=2, h=4, s=256, d=64, hkv=None, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    hkv = hkv or h
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    return q, k, v


@slow
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = rand_qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@slow
def test_forward_unaligned_seq():
    # seq 200 not a multiple of the 128 block: padding + key masking path
    q, k, v = rand_qkv(jax.random.PRNGKey(1), s=200)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@slow
def test_forward_small_seq():
    q, k, v = rand_qkv(jax.random.PRNGKey(2), s=32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@slow
def test_gqa_heads():
    q, k, v = rand_qkv(jax.random.PRNGKey(3), h=8, hkv=2, s=128)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@slow
@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_reference(causal):
    q, k, v = rand_qkv(jax.random.PRNGKey(4), b=1, h=2, s=256, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True)**2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal)**2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name} mismatch")


@slow
def test_backward_unaligned():
    q, k, v = rand_qkv(jax.random.PRNGKey(5), b=1, h=2, s=200, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True)**2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True)**2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name} mismatch")


@slow
def test_backward_gqa():
    # exercises the fused-v2 backward's rep-grid dk/dv accumulation
    q, k, v = rand_qkv(jax.random.PRNGKey(7), b=1, h=8, hkv=2, s=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True)**2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True)**2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name} mismatch")


@slow
def test_long_seq_v1_fallback():
    # kv > _V2_MAX_KV falls back to the v1 two-kernel backward
    q, k, v = rand_qkv(jax.random.PRNGKey(8), b=1, h=1, s=4096, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True,
                                       block_q=512, block_k=512)**2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True)**2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name} mismatch")


@slow
def test_bf16_runs():
    q, k, v = rand_qkv(jax.random.PRNGKey(6), s=128, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2)


# ----------------------------------------------------------------------------
# ISSUE 35: a caller that names no blocks gets them from the operands.
# ----------------------------------------------------------------------------
#: (q_len, kv_len, d, itemsize) -> what must hold of the chosen blocks
RULE_CASES = {
    "opt13b-zero3-x4": ((2048, 2048, 64, 2), dict(gen="v3", at_least=512)),
    "llama-4k-hd128": ((4096, 4096, 128, 2), dict(gen="v3", at_least=512)),
    "llama-8k-hd128": ((8192, 8192, 128, 2), dict(gen="v3", at_least=512)),
    "gpt2m-train-1k-unnamed": ((1024, 1024, 64, 2),
                               dict(gen="v2", at_least=512)),
    "float32-operands": ((2048, 2048, 128, 4), dict(gen="v3", at_least=256)),
    "hd256": ((2048, 2048, 256, 2), dict(gen="v3", at_least=256)),
    "short": ((96, 96, 64, 2), dict(blocks=(96, 96))),
    "one-block": ((128, 128, 64, 2), dict(blocks=(128, 128))),
    "s1100": ((1100, 1100, 64, 2), dict(gen="v3")),
    "s1000-pads-to-1024": ((1000, 1000, 64, 2), dict(gen="v2",
                                                    at_least=512)),
    "decode-like-one-query": ((1, 4096, 128, 2), dict(gen="v3")),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_blocks_are_chosen_from_the_operands(case):
    (q_len, kv_len, d, itemsize), want = RULE_CASES[case]
    c, pad_q, pad_k = fa._resolve_blocks(q_len, kv_len, d, itemsize, None,
                                         None)
    assert c.how == "chosen" and (c.q_len, c.kv_len, c.d) == (q_len, kv_len,
                                                              d)
    # whole blocks, padded by under an eighth (or by what 128 pads)
    assert (q_len + pad_q) % c.block_q == 0 and pad_q < max(c.block_q, 1)
    assert (kv_len + pad_k) % c.block_k == 0
    for length, pad in ((q_len, pad_q), (kv_len, pad_k)):
        assert pad * 8 <= length or pad == (-length) % 128, (length, pad)
    if "blocks" in want:
        assert (c.block_q, c.block_k) == want["blocks"] and not pad_q + pad_k
        return
    assert c.generation == want["gen"]
    assert max(c.block_q, c.block_k) <= 1024
    resident = kv_len + pad_k if c.generation == "v2" else c.block_k
    assert fa._bwd_vmem_bytes(c.block_q, resident, d,
                              itemsize) <= fa._VMEM_BUDGET < fa._VMEM_LIMIT
    if "at_least" in want:
        assert min(c.block_q, resident) >= want["at_least"], c
    if c.generation == "v2":
        assert c.block_q * resident <= fa._V2_MAX_SCORE_ELEMS


@pytest.mark.parametrize("given", [(128, 128), (1024, 1024), (512, 1024),
                                   (256, 64)])
def test_given_blocks_come_back_untouched_and_choices_records_both(given):
    before = fa.choices()
    shape = jax.ShapeDtypeStruct((1, 2, 2048, 64), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention(
        q, q, q, block_q=given[0], block_k=given[1], interpret=True), shape)
    jax.eval_shape(lambda q: flash_attention(q, q, q, interpret=True), shape)
    new = fa.choices(since=before)
    by_how = {c.how: c for c in new}
    assert set(by_how) == {"given", "chosen"} and set(new.values()) == {1}
    # ISSUE 62: blocks that are multiples of the strip name it; the others
    # keep whole tiles
    strip = 0 if given[0] % fa._STRIP or given[1] % fa._STRIP else fa._STRIP
    assert by_how["given"] == fa.Choice(2048, 2048, 64, "v3", *given, "given",
                                        window=0, strip=strip)
    assert by_how["chosen"][:4] == (2048, 2048, 64, "v3")
    assert min(by_how["chosen"].block_q, by_how["chosen"].block_k) >= 512
    assert by_how["chosen"].strip == fa._STRIP
    # the GPT-2 cell's call: v2 at the blocks it names, its cap not biting,
    # its one tile a head in strips
    c, pad_q, pad_k = fa._resolve_blocks(1024, 1024, 64, 2, 1024, 1024)
    assert c == fa.Choice(1024, 1024, 64, "v2", 1024, 1024, "given")
    assert (pad_q, pad_k) == (0, 0)
    before = fa.choices()
    shape = jax.ShapeDtypeStruct((1, 2, 1024, 64), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention(
        q, q, q, block_q=1024, block_k=1024, interpret=True), shape)
    assert list(fa.choices(since=before)) == [
        fa.Choice(1024, 1024, 64, "v2", 1024, 1024, "given", window=0,
                  strip=fa._STRIP)]


#: ISSUE 62, the three training cells' calls: (Choice less its strip) ->
#: visible / computed pairs of a head, %, in whole tiles and in strips of 256
CELL_PAIRS = {
    "gpt2m-train-1k": ((1024, 1024, 64, "v2", 1024, 1024, "given", 0),
                       50.0, 80.1),
    "opt13b-zero3-x4": ((2048, 2048, 64, "v3", 1024, 1024, "chosen", 0),
                        66.7, 88.9),
    "smallthinker-train-8k-window": (
        (8192, 8192, 128, "v3", 1024, 1024, "chosen", 4096), 80.0, 94.1),
    "smallthinker-train-8k-full": (
        (8192, 8192, 128, "v3", 1024, 1024, "chosen", 0), 88.9, 97.0),
}


@pytest.mark.parametrize("strip", [0, 256])
@pytest.mark.parametrize("cell", sorted(CELL_PAIRS))
def test_computed_pairs_is_a_count_of_the_mask(cell, strip):
    """What the engine logs of a call — visible pairs over the pairs the
    kernels multiply — against a brute-force count over the mask, tile by
    tile (whole tiles) and sub-tile by sub-tile (strips)."""
    fields, whole, strips = CELL_PAIRS[cell]
    c = fa.Choice(*fields, strip=strip)
    rows, cols = np.arange(c.q_len)[:, None], np.arange(c.kv_len)[None, :]
    seen = cols <= rows
    if c.window:
        seen &= rows - cols < c.window
    step_q = strip or c.block_q
    step_k = strip or (c.kv_len if c.generation == "v2" else c.block_k)
    tiles = seen.reshape(c.q_len // step_q, step_q, c.kv_len // step_k,
                         step_k).any(axis=(1, 3))
    visible, computed = fa.computed_pairs(c)
    assert (visible, computed) == (seen.sum(), tiles.sum() * step_q * step_k)
    assert round(100.0 * visible / computed, 1) == pytest.approx(
        strips if strip else whole, abs=0.06)
    # the shapes alone give the strip: nothing the caller sets
    assert fa._resolve_strip(c.generation, True, c.q_len, c.kv_len, c.kv_len,
                             c.block_q, c.block_k, c.window) == fa._STRIP


@pytest.mark.parametrize("causal,hkv,s_len", [
    (True, 2, 2048), (False, 2, 2048), (True, 1, 2048), (True, 2, 1024)],
    ids=["causal", "full", "causal-gqa", "causal-resident"])
def test_default_blocks_at_the_cells_length_match_reference(causal, hkv,
                                                            s_len):
    """Forward and backward at S = 2048, hd 64, bf16 — the four-chip
    training cell's call, at the blocks the rule gives it and (ISSUE 62) in
    the strips its shapes give it; S = 1024: the resident kernels'."""
    q, k, v = rand_qkv(jax.random.PRNGKey(11), b=1, h=2, hkv=hkv, s=s_len,
                       dtype=jnp.bfloat16)
    c, _, _ = fa._resolve_blocks(s_len, s_len, 64, 2, None, None)
    assert c.generation == ("v3" if s_len > 1024 else "v2")
    assert min(c.block_q, c.block_k) >= 512
    assert fa._resolve_strip(c.generation, causal, s_len, s_len, s_len,
                             c.block_q, c.block_k, 0) == fa._STRIP == 256

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=causal).astype(jnp.float32) ** 2)

    flash = functools.partial(flash_attention, interpret=True)
    out = flash(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2,
                               rtol=3e-2)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert err < 2e-2, f"d{name}: relative error {err}"
