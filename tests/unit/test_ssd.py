"""The Mamba-2 state-space scan (``ops/ssd.py``): the chunked form and the
one-token step, plain and Pallas (interpreted), against the per-token
float32 recurrence, on the head-packed state."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import ssd

pytestmark = pytest.mark.limit(60)


def _inputs(seed, b=2, t=32, h=4, p=16, n=16, amax=2.7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 1.0),
            -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=amax)),
            jax.random.normal(ks[3], (b, t, n)),
            jax.random.normal(ks[4], (b, t, n)),
            jax.random.normal(ks[5], (b, h, p, n)))


def _close(got, want, rtol=1e-5):
    np.testing.assert_allclose(got, want, atol=rtol * float(
        jnp.abs(want).max()))


def test_the_stored_view_is_the_same_numbers():
    """``g`` heads side by side on the lanes, the state's axis on the
    sublanes; at the published sizes a layer's state is ``[32, 128, 128]``
    a row."""
    s = _inputs(0, h=8, p=16, n=24)[-1]
    packed = ssd.pack_state(s)
    assert packed.shape == (2,) + ssd.packed_shape(8, 16, 24) == (2, 1, 24, 128)
    np.testing.assert_array_equal(ssd.unpack_state(packed, 16), s)
    # head 3's element [p, n] is lane 3 P + p of sublane n
    assert packed[1, 0, 5, 3 * 16 + 7] == s[1, 3, 7, 5]
    assert ssd.packed_shape(64, 64, 128) == (32, 128, 128)
    assert ssd.head_pack(6, 32) == 3


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "pallas"])
@pytest.mark.parametrize("shape", [(2, 32, 4, 16, 16), (1, 256, 2, 64, 128),
                                   (2, 16, 8, 8, 16)],
                         ids=["one-group", "published-heads", "eight-a-row"])
def test_chunked_form_is_the_recurrence(kernel, shape):
    """From a non-zero state; ``published-heads``: two chunks of 128 of two
    64 x 128 heads packed on one lane row.  A head here forgets up to e^-15
    a token, e^-1900 a chunk: every exponent of the chunked form is <= 0."""
    b, t, h, p, n = shape
    *args, s0 = _inputs(0, b, t, h, p, n)
    want_y, want_s = ssd.recurrent(*args, s0)
    y, s = ssd.chunked(*args, ssd.pack_state(s0), kernel=kernel,
                       interpret=True)
    _close(y, want_y)
    _close(ssd.unpack_state(s, p), want_s)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "pallas"])
@pytest.mark.parametrize("cut", [8, 16, 40])
def test_a_chunk_boundary_anywhere_gives_the_same_state(kernel, cut):
    """48 tokens in two calls cut at ``cut``, the state handed from call to
    call, are one call of 48."""
    x, dt, a, b, c, s0 = _inputs(1, t=48)
    want_y, want_s = ssd.recurrent(x, dt, a, b, c, s0)
    ys, s = [], ssd.pack_state(s0)
    for lo, hi in ((0, cut), (cut, 48)):
        y, s = ssd.chunked(x[:, lo:hi], dt[:, lo:hi], a, b[:, lo:hi],
                           c[:, lo:hi], s, kernel=kernel, interpret=True)
        ys.append(y)
    _close(jnp.concatenate(ys, axis=1), want_y)
    _close(ssd.unpack_state(s, 16), want_s)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "pallas"])
def test_a_pad_moves_nothing(kernel):
    """Rows of one call with 0, 5 and 32 real tokens, right-padded (``dt =
    0`` on the pads): each row's state is the recurrence over its real
    tokens alone, the all-pad row's is the one it came with."""
    x, dt, a, b, c, s0 = _inputs(2, b=3)
    valid = jnp.asarray([0, 5, 32])
    dt = jnp.where((jnp.arange(32)[None, :] < valid[:, None])[..., None],
                   dt, 0.0)
    _, s = ssd.chunked(x, dt, a, b, c, ssd.pack_state(s0), kernel=kernel,
                       interpret=True)
    s = ssd.unpack_state(s, 16)
    np.testing.assert_array_equal(s[0], s0[0])
    for row, n in ((1, 5), (2, 32)):
        _, want = ssd.recurrent(x[row:row + 1, :n], dt[row:row + 1, :n], a,
                                b[row:row + 1, :n], c[row:row + 1, :n],
                                s0[row:row + 1])
        _close(s[row], want[0])


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "pallas"])
def test_step_updates_one_layer_of_the_leaf_in_place(kernel):
    """One token a row on the whole leaf at a traced layer index: that
    layer's live rows advance as the recurrence does, an idle row (``dt =
    0``) and every other layer stay bit for bit what they were."""
    x, dt, a, b, c, s0 = _inputs(3, b=4, t=1, h=4, p=64, n=128)
    dt = dt.at[2].set(0.0)
    leaf = jnp.stack([ssd.pack_state(s0) + 1.0, ssd.pack_state(s0)])
    step = jax.jit(lambda *v: ssd.step(*v, kernel=kernel, interpret=True))
    y, out = step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], leaf, jnp.int32(1))
    want_y, want_s = ssd.recurrent(x, dt, a, b, c, s0)
    _close(y, want_y[:, 0])
    _close(ssd.unpack_state(out[1], 64), want_s)
    np.testing.assert_array_equal(out[0], leaf[0])
    np.testing.assert_array_equal(out[1, 2], leaf[1, 2])


def test_both_kernels_carry_their_names_and_are_logged():
    """The trace shows ``ssd_step`` and ``ssd_chunk_state`` (the readers of
    ``chipbench/layer_metrics/ssd_*`` find them by name), and an open
    dispatch log says which body a trace took."""
    from deepspeed_tpu.ops import decode_attention as da

    assert 'name="ssd_step"' in inspect.getsource(ssd._step_pallas)
    assert 'name="ssd_chunk_state"' in inspect.getsource(ssd._chunked_pallas)
    x, dt, a, b, c, s0 = _inputs(4, t=16)
    leaf = ssd.pack_state(s0)[None]
    for kernel, names in ((True, {"ssd_chunk_state", "ssd_step"}),
                          (False, {"ssd_chunk_plain", "ssd_step_plain"})):
        with da.dispatch_log() as paths:
            jax.eval_shape(lambda: ssd.chunked(
                x, dt, a, b, c, leaf[0], kernel=kernel, interpret=True))
            jax.eval_shape(lambda: ssd.step(
                x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], leaf, 0,
                kernel=kernel, interpret=True))
        assert paths == names
    lowered = jax.jit(lambda: ssd.chunked(
        x, dt, a, b, c, leaf[0], kernel=True, interpret=False)).trace()
    assert "ssd_chunk_state" in str(lowered.jaxpr)


def test_tokens_that_are_not_whole_chunks_are_refused():
    x, dt, a, b, c, s0 = _inputs(5, t=ssd.CHUNK + 8)
    with pytest.raises(ValueError, match="whole chunks"):
        ssd.chunked(x, dt, a, b, c, ssd.pack_state(s0), kernel=False)
