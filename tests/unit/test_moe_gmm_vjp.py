"""ISSUE 47: ``moe_gmm`` can be differentiated.  ``jax.grad`` through
``routed_ffn`` with the Pallas kernel (``moe_gmm`` / ``moe_gmm_dlhs`` /
``moe_gmm_drhs``, interpreted here) equals ``jax.grad`` through the
``ragged_dot`` branch and through a dense per-expert loop, in float32 to
rounding order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import grouped_matmul as gm
from deepspeed_tpu.moe import routed

D, F, E, K = 32, 48, 8, 3


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (D, E)),
            jax.random.normal(ks[1], (E, D, F)) * 0.2,
            jax.random.normal(ks[2], (E, D, F)) * 0.2,
            jax.random.normal(ks[3], (E, F, D)) * 0.2)


def _dense(y, gate, w1, w3, w2, held, act):
    p = jax.nn.softmax(y @ gate, -1)
    tp, te = jax.lax.top_k(p, K)
    tp = tp / tp.sum(-1, keepdims=True)
    first, count = held or (0, E)
    out = jnp.zeros_like(y)
    for e in range(first, first + count):
        w = jnp.where(te == e, tp, 0).sum(-1)
        h = routed.ACTS[act](y @ w1[e - first]) * (y @ w3[e - first])
        out = out + w[:, None] * (h @ w2[e - first])
    return out


#: (tokens, held, activation): all experts and a held share, a token count
#: whose groups are no multiple of the 128-row tile (50 x 3 pairs over 8
#: experts) and one that fills several tiles
CASES = [(50, None, "relu"), (50, (2, 3), "relu"), (50, (5, 3), "silu"),
         (300, None, "silu"), (300, (0, 4), "relu")]


@pytest.mark.parametrize("tokens,held,act", CASES)
def test_grad_through_the_kernel_is_ragged_dots_and_a_dense_loops(
        tokens, held, act):
    gate, w1, w3, w2 = _weights()
    sl = slice(None) if held is None else slice(held[0], sum(held))
    y = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, D))
    ct = jax.random.normal(jax.random.PRNGKey(7), (tokens, D))
    args = (y, gate, w1[sl], w3[sl], w2[sl])

    def through(kernel):
        return jax.grad(lambda *a: jnp.sum(routed.routed_ffn(
            *a, K, True, kernel=kernel, held=held, act=act)[0] * ct),
            argnums=range(5))(*args)

    want = jax.grad(lambda *a: jnp.sum(_dense(*a, held, act) * ct),
                    argnums=range(5))(*args)
    for got in (through(True), through(False)):
        for g, w in zip(got, want):
            assert bool(jnp.isfinite(g).all())
            np.testing.assert_allclose(g, w, atol=3e-5, rtol=1e-4)


def test_an_expert_without_a_row_gets_a_zero_gradient_and_nobodys_rows_none():
    """Groups of 0 rows (first, middle, last), a tail of rows that belong to
    nobody holding NaN: ``d_rhs`` of an empty group is zero, ``d_lhs`` of
    nobody's rows is zero, and nothing of the NaN reaches a gradient."""
    sizes = jnp.asarray([0, 130, 0, 5, 61, 0], jnp.int32)    # 196 of 256
    m, k, n = 256, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    lhs = jax.random.normal(ks[0], (m, k)).at[196:].set(jnp.nan)
    rhs = jax.random.normal(ks[1], (6, k, n))
    ct = jax.random.normal(ks[2], (m, n)).at[196:].set(jnp.nan)

    def kernel(lhs, rhs):
        out = gm.moe_gmm(lhs, rhs, sizes)
        return jnp.sum(jnp.where(jnp.arange(m)[:, None] < 196, out * ct, 0))

    def ragged(lhs, rhs):
        out = jax.lax.ragged_dot(jnp.nan_to_num(lhs), rhs, sizes)
        return jnp.sum(jnp.where(jnp.arange(m)[:, None] < 196,
                                 out * jnp.nan_to_num(ct), 0))

    d_lhs, d_rhs = jax.grad(kernel, argnums=(0, 1))(lhs, rhs)
    w_lhs, w_rhs = jax.grad(ragged, argnums=(0, 1))(lhs, rhs)
    assert bool(jnp.isfinite(d_lhs).all() and jnp.isfinite(d_rhs).all())
    np.testing.assert_allclose(d_lhs, w_lhs, atol=3e-5, rtol=1e-4)
    np.testing.assert_allclose(d_rhs, w_rhs, atol=3e-5, rtol=1e-4)
    for empty in (0, 2, 5):
        assert float(jnp.abs(d_rhs[empty]).max()) == 0.0
    assert float(jnp.abs(d_lhs[196:]).max()) == 0.0


@pytest.mark.parametrize("row_tile", [16, 128, 512])
def test_the_transposes_row_tile_changes_no_number(row_tile, monkeypatch):
    monkeypatch.setattr(gm, "DRHS_TILE_M", row_tile)
    sizes = jnp.asarray([70, 0, 333, 1, 160, 36], jnp.int32)  # 600 of 640
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    lhs = jax.random.normal(ks[0], (640, 64))
    rhs = jax.random.normal(ks[1], (6, 64, 128))
    ct = jax.random.normal(ks[2], (640, 128))
    keep = (jnp.arange(640) < 600)[:, None]

    def loss(fn):
        return lambda a, b: jnp.sum(jnp.where(keep, fn(a, b) * ct, 0))

    got = jax.grad(loss(lambda a, b: gm.moe_gmm(a, b, sizes)),
                   argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(a, b, sizes)),
                    argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=1e-4)


def test_the_stacks_gradient_lands_in_its_layer_alone():
    sizes = jnp.asarray([40, 24], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    lhs = jax.random.normal(ks[0], (64, 32))
    stack = jax.random.normal(ks[1], (3, 2, 32, 128))
    layer = jnp.int32(1)
    d_stack = jax.grad(lambda s: jnp.sum(gm.moe_gmm(lhs, s, sizes, layer)))(
        stack)
    d_layer = jax.grad(lambda w: jnp.sum(gm.moe_gmm(lhs, w, sizes)))(
        stack[1])
    np.testing.assert_allclose(d_stack[1], d_layer, atol=1e-6)
    assert float(jnp.abs(d_stack[0]).max()) == 0.0
    assert float(jnp.abs(d_stack[2]).max()) == 0.0


def test_the_three_kernels_keep_their_names():
    """``breakdown`` and the readers tell forward, ``d_lhs`` and ``d_rhs``
    apart by these names."""
    sizes = jnp.asarray([40, 24], jnp.int32)
    lhs = jnp.ones((64, 32))
    rhs = jnp.ones((2, 32, 128))
    text = str(jax.make_jaxpr(jax.grad(
        lambda a, b: jnp.sum(gm.moe_gmm(a, b, sizes, interpret=False)),
        argnums=(0, 1)))(lhs, rhs))
    for name in ("moe_gmm", "moe_gmm_dlhs", "moe_gmm_drhs"):
        assert f"name={name}\n" in text or f"name={name} " in text, name


def test_balance_term_is_one_under_even_routing_and_learns_through_scores():
    gate, w1, w3, w2 = _weights()
    y = jax.random.normal(jax.random.PRNGKey(3), (64, D))
    *_, aux = routed.routed_ffn(y, gate * 0.0, w1, w3, w2, K, True,
                                balance=True)
    # a zero router: every score 1 / E, the top-k the first k ids
    assert abs(float(aux) - 1.0) < 1e-6
    d_gate = jax.grad(lambda g: routed.routed_ffn(
        y, g, w1, w3, w2, K, True, balance=True)[-1])(gate)
    assert float(jnp.abs(d_gate).max()) > 0


@pytest.mark.parametrize("held", [None, (2, 3)])
def test_the_combines_two_layouts_are_one_sum(held):
    """``choice_major`` picks how the pairs are gathered for the float32
    combine, not what is summed: outputs and gradients agree."""
    gate, w1, w3, w2 = _weights()
    sl = slice(None) if held is None else slice(held[0], sum(held))
    y = jax.random.normal(jax.random.PRNGKey(11), (50, D))
    ct = jax.random.normal(jax.random.PRNGKey(12), (50, D))
    args = (y, gate, w1[sl], w3[sl], w2[sl])

    def both(choice_major):
        def out(*a):
            return routed.routed_ffn(*a, K, True, kernel=False, held=held,
                                     choice_major=choice_major)[0]
        return out(*args), jax.grad(lambda *a: jnp.sum(out(*a) * ct),
                                    argnums=range(5))(*args)

    (o1, g1), (o2, g2) = both(False), both(True)
    np.testing.assert_allclose(o1, o2, atol=1e-5, rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)
