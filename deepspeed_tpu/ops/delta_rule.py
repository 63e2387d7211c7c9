"""The gated delta rule with per-channel decays (Kimi Delta Attention, KDA;
Kimi Linear technical report, arXiv:2510.26692): a LINEAR-attention layer
whose whole past is one float32 matrix a head, ``S [dk, dv]``, and no cache
that grows with the sequence.

    S <- Diag(exp(g_t)) S                       decay, a factor a KEY channel
    S <- S + beta_t k_t (v_t - S^T k_t)^T       the delta rule's correction
    o_t = S^T q_t

``g_t [dk] <= 0`` is the token's log-decay, ``beta_t`` in (0, 1) its step;
``q``, ``k`` arrive L2-normalised (``q`` scaled), so ``|k_t| = 1`` and the
correction is a contraction.  Three forms of the one recurrence:

* :func:`recurrent` — the per-token ``lax.scan``: the oracle of the other
  two and of the tests.
* :func:`chunked` — prefill.  Over chunks of ``CHUNK`` = 64 tokens the
  corrections of a chunk solve ONE unit-lower-triangular system (the WY /
  UT form): with ``G`` the running sum of ``g`` inside the chunk,

      A_ij = sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])      (j < i)
      (I + Diag(beta) A) [W | U~] = Diag(beta) [k * exp(G) | v]
      U = U~ - W S0          O = (q * exp(G)) S0 + tril(B) U
      S1 = Diag(exp(G_C)) S0 + (k * exp(G_C - G))^T U

  (``B`` is ``A`` with ``q_i`` for ``k_i`` and the diagonal kept).  What does
  not need the state — ``G``, ``A``, ``B``, the solve, ``W``, ``U~`` — is
  plain XLA over all chunks at once (:func:`_intra`, under the scope
  ``layer/state/chunk_intra``); what carries it from
  chunk to chunk — four matmuls against ``S`` a chunk, most of the FLOPs —
  is the Pallas kernel ``kda_chunk_state`` on a TPU and :func:`_state_plain`
  elsewhere.  ``exp(G_i - G_j)`` is never split into ``exp(G_i) exp(-G_j)``
  over a whole chunk (a channel that decays by ``e^-2`` a token would
  overflow float32 in 44 tokens): rows take their decays relative to the
  first token of their 16-token SUB-block, so every factor left of the
  contraction is <= 1 and every factor right of it at most the decay of 16
  tokens (clamped at ``e^80``: exact while a channel keeps more than
  ``e^-5`` a token).
* :func:`step` — decode, one token a row, on the WHOLE state leaf ``[L,
  rows, H, dk, dv]`` at a layer index: the Pallas kernel ``kda_step`` reads
  each (row, head) matrix once and writes it once, in place
  (``input_output_aliases``), all on the VPU — the decay and the key enter
  as COLUMNS (one lane each of a ``[dk, 4 H]`` tile prepared outside: no
  transpose in the kernel), the value and the output are rows.

A PAD (``beta = 0``, ``g = 0``) leaves the state as it was: its correction
is zero and its decay one.  The state, the decays and the solve are float32
whatever the model's dtype.  An open ``ops/decode_attention.dispatch_log``
collects which body a trace was built with (``kda_step`` /
``kda_chunk_state``, or ``kda_*_plain``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.platform import interpret_kernels, on_tpu
from . import decode_attention as da

#: tokens of one chunk of the chunked form, and of one of its sub-blocks
CHUNK, SUB = 64, 16
#: a right-hand decay factor is ``exp(min(., EXP_CLAMP))`` (module docstring)
EXP_CLAMP = 80.0
#: heads one ``kda_step`` grid step holds: 8 x 64 KiB in and out, doubled
STEP_HEADS = 8
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _f32(*arrays):
    return tuple(jnp.asarray(a, _F32) for a in arrays)


# ------------------------------------------------------------------ the oracle
def recurrent(q, k, v, g, beta, state):
    """The recurrence token by token, float32.  ``q``, ``k``, ``g`` ``[B, H,
    T, dk]``, ``v`` ``[B, H, T, dv]``, ``beta`` ``[B, H, T]``, ``state``
    ``[B, H, dk, dv]`` -> ``(o [B, H, T, dv] float32, state)``."""
    q, k, v, g, beta, state = _f32(q, k, v, g, beta, state)

    def one(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=_HI))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=_HI)

    state, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2), state


# ------------------------------------------------------------ the chunked form
def _unit_lower_solve(n, rhs, sub: int):
    """``X`` of ``(I + n) X = rhs`` for strictly lower triangular ``n [...,
    C, C]`` and ``rhs [..., C, N]``, by forward substitution: each ``sub`` x
    ``sub`` diagonal block is inverted row by row (``sub`` static steps, all
    blocks at once), then the block rows are substituted in order."""
    c = n.shape[-1]
    r = c // sub
    lead = n.shape[:-2]
    blocks = n.reshape(lead + (r, sub, r, sub))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(r)], axis=-3)
    rows = []                     # row i of (I + diag)^-1, [..., r, sub]
    eye = jnp.eye(sub, dtype=n.dtype)
    for i in range(sub):
        row = jnp.broadcast_to(eye[i], lead + (r, sub))
        if i:
            row = row - jnp.einsum("...j,...jc->...c", diag[..., i, :i],
                                   jnp.stack(rows, axis=-2), precision=_HI)
        rows.append(row)
    inv = jnp.stack(rows, axis=-2)                       # [..., r, sub, sub]
    rhs = rhs.reshape(lead + (r, sub, rhs.shape[-1]))
    out = []
    for i in range(r):
        acc = rhs[..., i, :, :]
        for j in range(i):
            acc = acc - jnp.einsum("...ab,...bn->...an",
                                   blocks[..., i, :, j, :], out[j],
                                   precision=_HI)
        out.append(jnp.einsum("...ab,...bn->...an", inv[..., i, :, :], acc,
                              precision=_HI))
    return jnp.stack(out, axis=-3).reshape(lead + (c, rhs.shape[-1]))


def _intra(q, k, v, g, beta, chunk: int):
    """What a chunk's corrections and outputs are made of before the state
    enters (module docstring), all chunks at once, float32: ``(w, ut, qd, kd
    [.., n, C, dk | dv], bm [.., n, C, C], decay [.., n, dk])``."""
    b, h, t, dk = k.shape
    n, sub = t // chunk, min(SUB, chunk)
    r = chunk // sub

    def chunks(a):
        return a.reshape(a.shape[:2] + (n, chunk) + a.shape[3:])

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    big = jnp.cumsum(g, axis=3)                           # G [b,h,n,C,dk]
    # a sub-block's reference: G just before its first token
    ref = jnp.concatenate(
        [jnp.zeros_like(big[..., :1, :]), big[..., sub - 1:-1:sub, :]],
        axis=3)                                           # [b,h,n,r,dk]
    left = jnp.exp(big.reshape(b, h, n, r, sub, dk) - ref[..., None, :])
    right = jnp.exp(jnp.minimum(ref[..., None, :] - big[..., None, :, :],
                                EXP_CLAMP))               # [b,h,n,r,C,dk]
    kr = k[..., None, :, :] * right

    def scores(rows):
        rows = rows.reshape(b, h, n, r, sub, dk) * left
        return jnp.einsum("...rid,...rjd->...rij", rows, kr,
                          precision=_HI).reshape(b, h, n, chunk, chunk)

    i = jnp.arange(chunk)
    a = jnp.where(i[:, None] > i[None, :], scores(k), 0.0)
    bm = jnp.where(i[:, None] >= i[None, :], scores(q), 0.0)
    down = jnp.exp(big)
    solved = _unit_lower_solve(
        beta[..., None] * a,
        beta[..., None] * jnp.concatenate([k * down, v], axis=-1), sub)
    last = big[..., -1:, :]
    return (solved[..., :dk], solved[..., dk:], q * down,
            k * jnp.exp(last - big), bm, jnp.exp(last[..., 0, :]))


def _state_plain(w, ut, qd, kd, bm, decay, state):
    """The chunk-to-chunk half in plain XLA: a scan over the chunks."""
    def one(s, xs):
        w, ut, qd, kd, bm, decay = xs
        u = ut - jnp.einsum("bhck,bhkv->bhcv", w, s, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", qd, s, precision=_HI) \
            + jnp.einsum("bhcj,bhjv->bhcv", bm, u, precision=_HI)
        s = decay[..., None] * s \
            + jnp.einsum("bhck,bhcv->bhkv", kd, u, precision=_HI)
        return s, o

    state, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(a, 2, 0) for a in (w, ut, qd, kd, bm, decay)))
    return jnp.moveaxis(o, 0, 2), state


def _chunk_state_kernel(w_ref, ut_ref, qd_ref, kd_ref, bm_ref, decay_ref,
                        s_ref, o_ref, s_out_ref, *, chunks: int):
    """One (row, head): the state through its ``chunks`` chunks.  The decay
    of a key channel scales a ROW of ``S [dk, dv]``; it arrives as a lane
    row and goes in as ``Diag(decay) @ S`` (a matmul: no transpose)."""
    def mm(a, b, dims=((1,), (0,))):
        return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                                   preferred_element_type=_F32)

    s = s_ref[...]
    dk = s.shape[0]
    eye = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    for c in range(chunks):
        u = ut_ref[c] - mm(w_ref[c], s)
        o_ref[c] = mm(qd_ref[c], s) + mm(bm_ref[c], u)
        diag = jnp.where(eye, jnp.broadcast_to(decay_ref[c], (dk, dk)), 0.0)
        s = mm(diag, s) + mm(kd_ref[c], u, ((0,), (0,)))
    s_out_ref[...] = s


def _state_pallas(w, ut, qd, kd, bm, decay, state, interpret=None):
    b, h, n, c, dk = w.shape
    dv = ut.shape[-1]

    def spec(*tail):
        return pl.BlockSpec((None, None) + tail,
                            lambda i, j: (i, j) + (0,) * len(tail))

    o, state = pl.pallas_call(
        functools.partial(_chunk_state_kernel, chunks=n),
        grid=(b, h),
        in_specs=[spec(n, c, dk), spec(n, c, dv), spec(n, c, dk),
                  spec(n, c, dk), spec(n, c, c), spec(n, 1, dk),
                  spec(dk, dv)],
        out_specs=[spec(n, c, dv), spec(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((b, h, n, c, dv), _F32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_kernels() if interpret is None else interpret,
        name="kda_chunk_state",
    )(w, ut, qd, kd, bm, decay[..., None, :], state)
    return o, state


@jax.named_scope("layer/state/chunk")
def chunked(q, k, v, g, beta, state, *, kernel: Optional[bool] = None,
            interpret: Optional[bool] = None):
    """:func:`recurrent`'s contract through the chunked form; ``T`` a whole
    number of chunks (``min(CHUNK, T)`` tokens each; a multiple of ``SUB``
    or fewer than ``SUB``).  ``kernel``: the Pallas state kernel (default:
    on a TPU)."""
    q, k, v, g, beta, state = _f32(q, k, v, g, beta, state)
    b, h, t, _ = k.shape
    chunk = min(CHUNK, t)
    if t % chunk or (chunk > SUB and chunk % SUB):
        raise ValueError(f"{t} tokens are not whole chunks of {chunk} in "
                         f"sub-blocks of {SUB}")
    with jax.named_scope("layer/state/chunk_intra"):
        parts = _intra(q, k, v, g, beta, chunk)
    if on_tpu() if kernel is None else kernel:
        da._took("kda_chunk_state")
        o, state = _state_pallas(*parts, state, interpret=interpret)
    else:
        da._took("kda_chunk_plain")
        o, state = _state_plain(*parts, state)
    return o.reshape(b, h, t, o.shape[-1]), state


# ----------------------------------------------------------------- decode step
def _step_kernel(layer_ref, cols_ref, bv_ref, s_ref, o_ref, s_out_ref, *,
                 heads: int):
    """``heads`` (row, head) matrices, each read once and written once.
    ``cols_ref [dk, 4 heads]``: lane ``j * heads + h`` is head ``h``'s
    ``exp(g)`` (j = 0), ``k`` (1), ``beta k`` (2), ``q`` (3) as a COLUMN over
    the key channels; ``bv_ref [heads, dv]``: ``beta v`` rows."""
    del layer_ref                       # consumed by the state's index map
    cols = cols_ref[...]

    def col(j, h):
        return cols[:, j * heads + h:j * heads + h + 1]          # [dk, 1]

    for h in range(heads):
        s = s_ref[h] * col(0, h)
        u = bv_ref[h:h + 1, :] - jnp.sum(s * col(2, h), axis=0,
                                         keepdims=True)          # [1, dv]
        s = s + col(1, h) * u
        o_ref[h:h + 1, :] = jnp.sum(s * col(3, h), axis=0, keepdims=True)
        s_out_ref[h] = s


def _step_pallas(cols, bv, leaf, layer, interpret=None):
    rows, groups, dk, _ = cols.shape
    heads = bv.shape[1] // groups
    dv = bv.shape[-1]
    state_spec = pl.BlockSpec((None, None, heads, dk, dv),
                              lambda i, j, layer: (layer[0], i, j, 0, 0))
    o, leaf = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, groups),
            in_specs=[
                pl.BlockSpec((None, None, dk, 4 * heads),
                             lambda i, j, layer: (i, j, 0, 0)),
                pl.BlockSpec((None, heads, dv), lambda i, j, layer: (i, j, 0)),
                state_spec],
            out_specs=[
                pl.BlockSpec((None, heads, dv), lambda i, j, layer: (i, j, 0)),
                state_spec]),
        out_shape=[jax.ShapeDtypeStruct(bv.shape, _F32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        # the state leaf is updated in place (operand 3, after the scalar)
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_kernels() if interpret is None else interpret,
        name="kda_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), cols, bv, leaf)
    return o, leaf


@jax.named_scope("layer/state/step")
def step(q, k, v, g, beta, leaf, layer, *, kernel: Optional[bool] = None,
         interpret: Optional[bool] = None):
    """One token a row against the WHOLE state leaf ``[L, rows, H, dk, dv]``
    (float32) at ``layer`` (traced): ``q``, ``k``, ``g`` ``[rows, H, dk]``,
    ``v`` ``[rows, H, dv]``, ``beta`` ``[rows, H]`` -> ``(o [rows, H, dv]
    float32, leaf)``.  A row to be left as it is carries ``g = 0`` and
    ``beta = 0``."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    rows, h, dk = k.shape
    if on_tpu() if kernel is None else kernel:
        da._took("kda_step")
        heads = STEP_HEADS if h % STEP_HEADS == 0 else h
        # [rows, H / heads, dk, 4 heads]: the four columns of a group's heads
        cols = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=1) \
            .reshape(rows, 4, h // heads, heads, dk) \
            .transpose(0, 2, 4, 1, 3).reshape(rows, h // heads, dk, 4 * heads)
        return _step_pallas(cols, beta[..., None] * v, leaf, layer,
                            interpret=interpret)
    da._took("kda_step_plain")
    s = jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)
    o, s = recurrent(q[:, :, None], k[:, :, None], v[:, :, None],
                     g[:, :, None], beta[:, :, None], s)
    return o[:, :, 0], jax.lax.dynamic_update_index_in_dim(leaf, s, layer, 0)
