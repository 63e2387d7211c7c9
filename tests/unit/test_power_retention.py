"""Power retention of degree 2 (``ops/power_retention.py``): the chunked form
and the one-token step, plain and Pallas (interpreted), against the per-token
float32 recurrence on the triangle's monomials AND against the attention
form ``exp(c_i - c_j) (q_i . k_j)^2`` that never builds them."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import decode_attention as da
from deepspeed_tpu.ops import power_retention as pr

pytestmark = pytest.mark.limit(60)
B, T, H, G, N = 2, 32, 2, 5, 16


def _inputs(seed, b=B, t=T, h=H, g=G, n=N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(ks[1], (b, t, h, n))
    # (a query near its own key: no token's weights sum to almost nothing,
    # which would make its quotient, not the code, ill-conditioned)
    return (jnp.repeat(k, g, axis=2)
            + 0.5 * jax.random.normal(ks[0], (b, t, h * g, n)), k,
            jax.random.normal(ks[2], (b, t, h, n)),
            jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, t, h)) + 3.0))


def _zero(b=B, h=H, n=N):
    return (jnp.zeros((b,) + pr.stored_shape(h, n)),
            jnp.zeros((b, h, pr.distances(n), n)))


def _attention_form(q, k, v, lg):
    """``y_i = sum_j w_ij v_j / sum_j w_ij``, ``w_ij = exp(c_i - c_j) (q_i .
    k_j)^2`` for ``j <= i``: no ``phi``, no state."""
    b, t, hq, n = q.shape
    h = k.shape[2]
    c = jnp.cumsum(lg, axis=1)                                   # [B, T, H]
    dots = jnp.einsum("bihgn,bjhn->bhgij", q.reshape(b, t, h, hq // h, n), k)
    decay = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((t, t), bool)),
        c.transpose(0, 2, 1)[..., :, None] - c.transpose(0, 2, 1)[..., None, :],
        -jnp.inf))
    w = dots ** 2 * decay[:, :, None]
    y = jnp.einsum("bhgij,bjhn->bihgn", w, v) \
        / jnp.moveaxis(w.sum(-1), -1, 1)[..., None]
    return y.reshape(b, t, hq, n)


def _close(got, want, rtol=2e-5):
    np.testing.assert_allclose(got, want, atol=rtol * float(
        jnp.abs(want).max()))


def _by_steps(q, k, v, lg, kernel):
    leaf = jnp.zeros((3, B) + pr.stored_shape(H, N))
    zleaf = jnp.zeros(leaf.shape[:-2] + (N,))
    ys = []
    for t in range(q.shape[1]):
        y, leaf, zleaf = pr.step(q[:, t], k[:, t], v[:, t], lg[:, t], leaf,
                                 zleaf, 1, kernel=kernel, interpret=True)
        ys.append(y)
    return jnp.stack(ys, 1), leaf, zleaf


def test_phi_of_q_dot_phi_of_k_is_q_dot_k_squared():
    """In the triangle's order and in the stored one (8,256 distinct
    monomials in 65 x 128 stored rows at the published head)."""
    q, k = _inputs(0)[:2]
    want = jnp.einsum("btn,btn->bt", q[:, :, 0], k[:, :, 0]) ** 2
    _close((pr.phi(q[:, :, 0]) * pr.phi(k[:, :, 0])).sum(-1), want)
    _close((pr.phi_stored(q[:, :, 0]) * pr.phi_stored(k[:, :, 0]))
           .sum((-1, -2)), want)
    assert pr.monomials(128) == 8256
    assert pr.stored_shape(8, 128) == (8, 65, 128, 128)
    s = jax.random.normal(jax.random.PRNGKey(1), (2, 3, pr.monomials(N), N))
    np.testing.assert_allclose(pr.unpack_state(pr.pack_state(s)), s,
                               rtol=1e-6)
    np.testing.assert_allclose(pr.unpack_z(pr.pack_z(s[..., 0])), s[..., 0],
                               rtol=1e-6)
    with pytest.raises(ValueError, match="even width"):
        pr.distances(15)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "pallas"])
def test_chunked_and_step_are_the_recurrence_and_the_attention_form(
        kernel, monkeypatch):
    q, k, v, lg = _inputs(2)
    d = pr.monomials(N)
    want, s_want, z_want = pr.recurrent(
        q, k, v, lg, jnp.zeros((B, H, d, N)), jnp.zeros((B, H, d)))
    _close(want, _attention_form(q, k, v, lg))
    monkeypatch.setattr(pr, "CHUNK", 8)
    got, s, z = pr.chunked(q, k, v, lg, *_zero(), kernel=kernel,
                           interpret=True)
    _close(got, want)
    _close(pr.unpack_state(s), s_want)
    _close(pr.unpack_z(z), z_want)
    got, leaf, zleaf = _by_steps(q, k, v, lg, kernel)
    _close(got, want)
    _close(pr.unpack_state(leaf[1]), s_want)
    _close(pr.unpack_z(zleaf[1]), z_want)
    assert not leaf[0].any() and not leaf[2].any()      # the layer's alone


def test_a_chunk_boundary_anywhere_gives_the_same_state(monkeypatch):
    """One call of 32, chunks of 8 or of 16, and two calls of 16: the same
    outputs and the same state; a carried state is used."""
    q, k, v, lg = _inputs(3)
    whole = pr.chunked(q, k, v, lg, *_zero(), kernel=False)
    for chunk in (8, 16):
        monkeypatch.setattr(pr, "CHUNK", chunk)
        for a, b in zip(pr.chunked(q, k, v, lg, *_zero(), kernel=False),
                        whole):
            _close(a, b)
    first = pr.chunked(q[:, :16], k[:, :16], v[:, :16], lg[:, :16], *_zero(),
                       kernel=True, interpret=True)
    second = pr.chunked(q[:, 16:], k[:, 16:], v[:, 16:], lg[:, 16:],
                        *first[1:], kernel=True, interpret=True)
    _close(jnp.concatenate([first[0], second[0]], 1), whole[0])
    _close(second[1], whole[1])
    _close(second[2], whole[2])
    with pytest.raises(ValueError, match="whole chunks"):
        pr.chunked(q[:, :20], k[:, :20], v[:, :20], lg[:, :20], *_zero(),
                   kernel=False)


def test_a_scale_changes_nothing_and_a_pad_moves_nothing():
    q, k, v, lg = _inputs(4)
    base = pr.chunked(q, k, v, lg, *_zero(), kernel=False)[0]
    _close(pr.chunked(q * 0.25, k * 3.0, v, lg, *_zero(), kernel=False)[0],
           base, rtol=1e-4)
    # the last 8 tokens pads (k = 0, lg = 0): the state is the one after 24
    pad = jnp.arange(T) >= 24
    kp = jnp.where(pad[None, :, None, None], 0.0, k)
    lp = jnp.where(pad[None, :, None], 0.0, lg)
    for kernel in (False, True):
        got = pr.chunked(q, kp, v, lp, *_zero(), kernel=kernel,
                         interpret=True)
        want = pr.chunked(q[:, :24], k[:, :24], v[:, :24], lg[:, :24],
                          *_zero(), kernel=False)
        for a, b in zip((got[0][:, :24],) + got[1:], want):
            _close(a, b)
        # an idle decode row: nothing moves, and it reads 0, not 0 / 0
        y, leaf, zleaf = pr.step(
            q[:, 0] * 0, k[:, 0] * 0, v[:, 0], lg[:, 0] * 0,
            want[1][None], want[2][None], 0, kernel=kernel, interpret=True)
        np.testing.assert_array_equal(leaf[0], want[1])
        np.testing.assert_array_equal(zleaf[0], want[2])
        assert not np.asarray(y).any()


def test_five_query_heads_read_one_state():
    """A KV head's state is a function of k, v and the gate alone: each
    query head of its group reads what it would read alone."""
    q, k, v, lg = _inputs(5)
    got, s, z = pr.chunked(q, k, v, lg, *_zero(), kernel=True, interpret=True)
    qg = q.reshape(B, T, H, G, N)
    for g in range(G):
        alone, s1, z1 = pr.chunked(qg[:, :, :, g], k, v, lg, *_zero(),
                                   kernel=False)
        _close(got.reshape(B, T, H, G, N)[:, :, :, g], alone)
        _close(s1, s)
        _close(z1, z)


def test_both_kernels_carry_their_names_and_are_logged():
    """The trace shows the kernels by name (``power_step`` /
    ``power_chunk_state``: ``chipbench/layer_metrics/power_*`` find them by
    name), and an open dispatch log collects which body was built."""
    assert 'name="power_step"' in inspect.getsource(pr._step_pallas)
    assert 'name="power_chunk_state"' in inspect.getsource(pr._chunked_pallas)
    q, k, v, lg = _inputs(6, t=8)
    for kernel, names in ((True, {"power_chunk_state", "power_step"}),
                          (False, {"power_chunk_plain", "power_step_plain"})):
        with da.dispatch_log() as paths:
            jax.make_jaxpr(lambda *a: pr.chunked(
                *a, kernel=kernel, interpret=True))(q, k, v, lg, *_zero())
            text = str(jax.make_jaxpr(lambda *a: pr.step(
                *a, 0, kernel=kernel, interpret=True))(
                    q[:, 0], k[:, 0], v[:, 0], lg[:, 0],
                    *(a[None] for a in _zero())))
        assert paths == names
        assert ("power_step" in text) == kernel
