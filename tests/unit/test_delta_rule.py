"""The gated delta rule (``ops/delta_rule.py``): the chunked form and the
one-token step, plain and Pallas (interpreted), against the per-token
float32 recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import delta_rule as dr

pytestmark = pytest.mark.limit(60)


def _inputs(seed, b=2, h=3, t=128, dk=32, dv=32, gmax=1.6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, h, t, dk))
    k = jax.random.normal(ks[1], (b, h, t, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, h, t, dv))
    g = -gmax * jax.random.uniform(ks[3], (b, h, t, dk))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, t)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dk, dv))


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "pallas"])
@pytest.mark.parametrize("gmax", [0.1, 1.6, 6.0])
def test_chunked_form_is_the_recurrence(kernel, gmax):
    """Two chunks of 64 in four sub-blocks each, from a non-zero state; at
    ``gmax`` 6 a channel forgets e^-6 a token, e^-384 a chunk: a decay split
    over the whole chunk would overflow float32."""
    args = _inputs(0, gmax=gmax)
    want_o, want_s = dr.recurrent(*args)
    o, s = dr.chunked(*args, kernel=kernel, interpret=True)
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(s, want_s, atol=1e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "pallas"])
def test_state_carries_across_calls_and_short_chunks(kernel):
    """Three calls of 16 tokens (one chunk, one sub-block each), the state
    handed from call to call, are one call of 48."""
    q, k, v, g, beta, s0 = _inputs(1, t=48)
    want_o, want_s = dr.recurrent(q, k, v, g, beta, s0)
    outs, s = [], s0
    for at in range(0, 48, 16):
        o, s = dr.chunked(*(a[:, :, at:at + 16] for a in (q, k, v, g, beta)),
                          s, kernel=kernel, interpret=True)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=2), want_o,
                               atol=2e-6)
    np.testing.assert_allclose(s, want_s, atol=1e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "pallas"])
def test_ragged_valid_lengths_in_one_call(kernel):
    """Rows of one call with 0, 5, 64 and 128 real tokens, right-padded
    (``beta = 0``, ``g = 0`` on the pads): each row's state is the
    recurrence over its real tokens alone, and the all-pad row's state is
    bit-identical to what it was."""
    q, k, v, g, beta, s0 = _inputs(2, b=4)
    valid = np.array([0, 5, 64, 128])
    live = jnp.arange(128)[None, :] < valid[:, None]             # [B, T]
    gm = jnp.where(live[:, None, :, None], g, 0.0)
    bm = jnp.where(live[:, None, :], beta, 0.0)
    o, s = dr.chunked(q, k, v, gm, bm, s0, kernel=kernel, interpret=True)
    if not kernel:
        np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(s0[0]))
    else:
        # Diag(1) @ S on the MXU path: equal to rounding
        np.testing.assert_allclose(s[0], s0[0], rtol=1e-6, atol=1e-6)
    for row, n in enumerate(valid):
        if not n:
            continue
        want_o, want_s = dr.recurrent(*(a[row:row + 1, :, :n]
                                        for a in (q, k, v, g, beta)),
                                      s0[row:row + 1])
        np.testing.assert_allclose(o[row:row + 1, :, :n], want_o, atol=2e-6)
        np.testing.assert_allclose(s[row:row + 1], want_s, atol=1e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "pallas"])
def test_step_updates_one_layer_of_the_leaf_in_place(kernel):
    """The one-token step on a ``[L, rows, H, dk, dv]`` leaf at a traced
    layer: that layer's live rows advance as the recurrence says, an idle
    row (``g = 0``, ``beta = 0``) and every other layer stay bit-identical."""
    q, k, v, g, beta, s = _inputs(3, b=4, h=8, t=1)
    leaf = jnp.stack([s, 2 * s, 3 * s])
    idle = jnp.arange(4) == 2
    g = jnp.where(idle[:, None, None, None], 0.0, g)
    beta = jnp.where(idle[:, None, None], 0.0, beta)
    want_o, want_s = dr.recurrent(q, k, v, g, beta, leaf[1])
    o, out = jax.jit(lambda *a: dr.step(*a, kernel=kernel, interpret=True))(
        q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, :, 0], beta[:, :, 0], leaf,
        jnp.int32(1))
    np.testing.assert_allclose(o, want_o[:, :, 0], atol=2e-6)
    np.testing.assert_allclose(out[1], want_s, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out[1, 2]),
                                  np.asarray(leaf[1, 2]))
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(out[layer]),
                                      np.asarray(leaf[layer]))


def test_a_chunks_state_goes_on_into_decode_steps():
    """Prefill 64 tokens chunked, then 8 one-token steps on the leaf: the
    outputs and the final state are the recurrence over all 72."""
    q, k, v, g, beta, s0 = _inputs(4, t=72)
    want_o, want_s = dr.recurrent(q, k, v, g, beta, s0)
    o, s = dr.chunked(*(a[:, :, :64] for a in (q, k, v, g, beta)), s0,
                      kernel=True, interpret=True)
    leaf, outs = s[None], [o]
    for t in range(64, 72):
        o, leaf = dr.step(q[:, :, t], k[:, :, t], v[:, :, t], g[:, :, t],
                          beta[:, :, t], leaf, jnp.int32(0), kernel=True,
                          interpret=True)
        outs.append(o[:, :, None])
    np.testing.assert_allclose(jnp.concatenate(outs, axis=2), want_o,
                               atol=2e-6)
    np.testing.assert_allclose(leaf[0], want_s, atol=1e-5)


def test_whole_chunks_only():
    q, k, v, g, beta, s0 = _inputs(5, t=72)
    with pytest.raises(ValueError, match="whole chunks"):
        dr.chunked(q, k, v, g, beta, s0, kernel=False)


def test_the_kernels_names_survive_a_traces_reduction():
    """``tests/chipbench/test_program_span_metrics.py``'s rule for a Pallas
    kernel's name (XLA appends a serial number, ``trace_reduce.base_name``
    strips trailing digits and dots), held here for this file's two kernels:
    that test finds them (``pl.pallas_call``, as every ops file calls it)
    and raises ``KeyError`` at its table of three files until a ``benchmark``
    issue gives it a ``"delta_rule.py": ("kda_",)`` row (PERF.md section 7
    (82), a known pin)."""
    import ast
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench import trace_reduce

    tree = ast.parse(open(dr.__file__).read())
    names = [kw.value.value for node in ast.walk(tree)
             if isinstance(node, ast.Call)
             and getattr(node.func, "attr", None) == "pallas_call"
             for kw in node.keywords if kw.arg == "name"]
    assert sorted(names) == ["kda_chunk_state", "kda_step"]
    for name in names:
        assert name.startswith("kda_")
        assert trace_reduce.base_name(f"%{name}.12") == name
