"""The Mamba-2 state-space scan (state-space duality, SSD; Dao & Gu,
arXiv:2405.21060): a layer whose whole past is one float32 matrix a head,
``S [P, N]`` (``P`` the head's width, ``N`` the state's), and no cache that
grows with the sequence.

    S <- exp(dt_t a) S + dt_t x_t B_t^T         ONE scalar decay a head
    y_t = S C_t

``a < 0`` is a head's rate, ``dt_t > 0`` the token's step a head (after its
softplus), ``B_t``, ``C_t`` ``[N]`` are shared by all heads (one group).  The
skip ``D x_t``, the gate and the norm around it are the model's.  Three
forms of the one recurrence:

* :func:`recurrent` — the per-token ``lax.scan`` on ``S [B, H, P, N]``: the
  oracle of the other two and of the tests.
* :func:`chunked` — prefill.  Over chunks of ``CHUNK`` tokens, with ``c_i``
  the running sum of ``dt a`` inside the chunk,

      G = C B^T                    once a chunk (B, C are every head's)
      L_h[i, j] = exp(c_i - c_j)   j <= i, else 0   (every exponent <= 0)
      Y_h = (G * L_h)(dt * X_h) + exp(c) * (C S0_h^T)
      S1_h = exp(c_T) S0_h + sum_j exp(c_T - c_j) dt_j x_j B_j^T

  no clamp, no sub-blocks, no solve.  On a TPU the WHOLE chunk — scores,
  decays, outputs and the chunk-to-chunk carry — is the Pallas kernel
  ``ssd_chunk_state`` (grid: row x head group x chunk, the state resident
  over the chunks); elsewhere :func:`_chunked_plain`.
* :func:`step` — decode, one token a row, on the WHOLE state leaf at a layer
  index: the Pallas kernel ``ssd_step`` reads each matrix once and writes it
  once, in place (``input_output_aliases``), all on the VPU.

**The stored state** is head-PACKED and transposed, as the paged pool is
lane-packed (``ops/paged_kv.py`` "Layout"): ``g = 128 // P`` heads side by
side on the lanes, the state's ``N`` on the sublanes — ``[.., H / g, N, g
P]`` (:func:`pack_state`; ``[.., 32, 128, 128]`` at 64 heads of 64 x 128:
the same bytes as ``[.., 64, 64, 128]``).  In that view ``B_t`` and ``C_t``
enter as COLUMNS every head of a row shares, a head's decay, its ``dt x``
and its output are lane ROWS, and the readout ``S C`` is a sum over
sublanes: no transpose and no cross-lane reduction in either kernel.

A PAD (``dt = 0``) leaves the state as it was: its decay is one and its
input zero.  The state, ``dt``, the decays and the sums over a chunk are
float32 whatever the model's dtype.  An open
``ops/decode_attention.dispatch_log`` collects which body a trace was built
with (``ssd_step`` / ``ssd_chunk_state``, or ``ssd_*_plain``).
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
from .paged_kv import LANES

#: tokens of one chunk of the chunked form
CHUNK = 128
#: packed head groups one ``ssd_step`` grid step holds (16 x 64 KiB in and
#: out, doubled) and one ``ssd_chunk_state`` grid step
STEP_GROUPS, CHUNK_GROUPS = 16, 8
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _f32(*arrays):
    return tuple(jnp.asarray(a, _F32) for a in arrays)


# ------------------------------------------------------------ the stored view
def head_pack(heads: int, head_dim: int) -> int:
    """Heads side by side on the lanes of the stored state: as many as fill
    a 128-lane row, a divisor of ``heads``."""
    g = max(1, min(heads, LANES // head_dim))
    while heads % g:
        g -= 1
    return g


def packed_shape(heads: int, head_dim: int, state: int):
    """``(H / g, N, g P)``: the stored view of a ``[H, P, N]`` state."""
    g = head_pack(heads, head_dim)
    return heads // g, state, g * head_dim


def pack_state(s):
    """``[.., H, P, N] -> [.., H / g, N, g P]`` (module docstring)."""
    *lead, h, p, n = s.shape
    g = head_pack(h, p)
    s = s.reshape(*lead, h // g, g, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // g, n, g * p)


def unpack_state(s, head_dim: int):
    """:func:`pack_state`'s inverse."""
    *lead, groups, n, lanes = s.shape
    g = lanes // head_dim
    s = s.reshape(*lead, groups, n, g, head_dim)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, groups * g, head_dim, n)


# ------------------------------------------------------------------ the oracle
def recurrent(x, dt, a, b, c, state):
    """The recurrence token by token, float32.  ``x [B, T, H, P]``, ``dt
    [B, T, H]``, ``a [H]``, ``b``, ``c`` ``[B, T, N]``, ``state [B, H, P,
    N]`` (unpacked) -> ``(y [B, T, H, P] float32, state)``."""
    x, dt, a, b, c, state = _f32(x, dt, a, b, c, state)

    def one(s, xs):
        xt, dtt, bt, ct = xs
        s = s * jnp.exp(dtt * a)[..., None, None] \
            + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return s, jnp.einsum("bhpn,bn->bhp", s, ct, precision=_HI)

    state, y = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


# ------------------------------------------------------------ the chunked form
def _chunked_plain(x, dt, a, b, c, state, chunk: int):
    """The chunked form in plain XLA, on the packed state: a scan over the
    chunks."""
    bsz, t, h, p = x.shape
    groups, n, lanes = state.shape[1:]
    i = jnp.arange(chunk)
    tri = (i[:, None] >= i[None, :])[None, :, :, None]

    def one(s, xs):
        xc, dc, bc, cc = xs
        cs = jnp.cumsum(dc * a, axis=1)                          # [B, C, H]
        low = jnp.where(tri, jnp.exp(jnp.minimum(
            cs[:, :, None] - cs[:, None, :], 0.0)), 0.0)         # [B, C, C, H]
        gram = jnp.einsum("bin,bjn->bij", cc, bc, precision=_HI)
        dx = dc[..., None] * xc
        y = jnp.einsum("bijh,bjhp->bihp", gram[..., None] * low, dx,
                       precision=_HI)
        carried = jnp.einsum("bin,bgnl->bigl", cc, s, precision=_HI)
        y = y + jnp.exp(cs)[..., None] * carried.reshape(bsz, chunk, h, p)
        last = cs[:, -1:]
        fed = (jnp.exp(last - cs) * dc)[..., None] * xc
        s = jnp.repeat(jnp.exp(last[:, 0]), p, axis=-1) \
            .reshape(bsz, groups, 1, lanes) * s \
            + jnp.einsum("bjn,bjgl->bgnl", bc,
                         fed.reshape(bsz, chunk, groups, lanes),
                         precision=_HI)
        return s, y

    def chunks(v):
        return jnp.moveaxis(
            v.reshape((bsz, t // chunk, chunk) + v.shape[2:]), 1, 0)

    state, y = jax.lax.scan(one, state, tuple(
        chunks(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, t, h, p), state


def _mm(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=_F32)


def _chunk_kernel(x_ref, d_ref, ct_ref, ch_ref, b_ref, c_ref, s_ref, y_ref,
                  s_out_ref, *, groups: int, pack: int, head_dim: int):
    """One (row, head groups, chunk): the chunk's scores, decays and outputs
    and the state through it.  ``d_ref`` / ``ct_ref [C, heads]``: ``dt`` and
    the running sum of ``dt a`` with the step's heads on the lanes (a head's
    COLUMN over the tokens); ``ch_ref [heads, C]``: the same sum with the
    tokens on the lanes (its ROW); ``s_out_ref`` stays resident over the
    chunk axis and carries the state."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_out_ref[...] = s_ref[...]

    t, lanes = x_ref.shape[0], s_ref.shape[-1]
    bm, cm = b_ref[...], c_ref[...]
    gram = _mm(cm, bm, ((1,), (1,)))                              # C B^T
    tri = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    d, ct, ch = d_ref[...], ct_ref[...], ch_ref[...]
    last = ct[t - 1:t, :]
    grown = jnp.exp(ct)                       # exp(c_i): what S0 has kept
    fed = jnp.exp(last - ct) * d              # exp(c_T - c_j) dt_j
    kept = jnp.exp(last)

    def lane_head(rows):
        return jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1) \
            // head_dim

    def spread(cols, q):
        """The ``pack`` heads' columns of group ``q``, each over its head's
        lanes: ``[rows, lanes]``."""
        rows = cols.shape[0]
        out = jnp.broadcast_to(cols[:, q * pack:q * pack + 1], (rows, lanes))
        for h in range(1, pack):
            out = jnp.where(lane_head(rows) >= h,
                            cols[:, q * pack + h:q * pack + h + 1], out)
        return out

    for q in range(groups):
        xq = x_ref[:, q * lanes:(q + 1) * lanes]
        s = s_out_ref[q]
        dx = xq * spread(d, q)
        y = spread(grown, q) * _mm(cm, s)
        for h in range(pack):
            hh = q * pack + h
            low = jnp.where(tri, jnp.exp(jnp.minimum(
                ct[:, hh:hh + 1] - ch[hh:hh + 1, :], 0.0)), 0.0)
            own = dx if pack == 1 else jnp.where(lane_head(t) == h, dx, 0.0)
            y = y + _mm(gram * low, own)
        y_ref[:, q * lanes:(q + 1) * lanes] = y
        s_out_ref[q] = spread(kept, q) * s \
            + _mm(bm, xq * spread(fed, q), ((0,), (0,)))


def _step_groups(groups: int, lanes: int, most: int) -> int:
    """Head groups a grid step holds: the largest divisor of ``groups`` up
    to ``most`` whose lanes are whole 128-lane rows (or all of them)."""
    for k in range(min(most, groups), 0, -1):
        if groups % k == 0 and (k * lanes % LANES == 0 or k == groups):
            return k
    return groups


def _chunked_pallas(x, dt, a, b, c, state, chunk: int, interpret=None):
    bsz, t, h, p = x.shape
    groups, n, lanes = state.shape[1:]
    pack = lanes // p
    k = _step_groups(groups, lanes, CHUNK_GROUPS)
    steps, nc, heads = groups // k, t // chunk, k * pack
    # a head's running sum of dt a inside each chunk, and dt itself, by grid
    # step: [B, steps, chunks, C, heads] (columns) / [.., heads, C] (rows)
    by_step = lambda v: v.reshape(bsz, nc, chunk, steps, heads) \
        .transpose(0, 3, 1, 2, 4)
    cs = by_step(jnp.cumsum((dt * a).reshape(bsz, nc, chunk, h), axis=2))
    tok = lambda w: pl.BlockSpec((None, chunk, w), lambda i, j, ci: (i, ci, 0))
    wide = pl.BlockSpec((None, chunk, k * lanes), lambda i, j, ci: (i, ci, j))
    col = pl.BlockSpec((None, None, None, chunk, heads),
                       lambda i, j, ci: (i, j, ci, 0, 0))
    row = pl.BlockSpec((None, None, None, heads, chunk),
                       lambda i, j, ci: (i, j, ci, 0, 0))
    st = pl.BlockSpec((None, k, n, lanes), lambda i, j, ci: (i, j, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, groups=k, pack=pack, head_dim=p),
        grid=(bsz, steps, nc),
        in_specs=[wide, col, col, row, tok(n), tok(n), st],
        out_specs=[wide, st],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, h * p), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_kernels() if interpret is None else interpret,
        name="ssd_chunk_state",
    )(x.reshape(bsz, t, h * p), by_step(dt), cs, jnp.swapaxes(cs, -1, -2),
      b, c, state)
    return y.reshape(bsz, t, h, p), state


@jax.named_scope("layer/state/chunk")
def chunked(x, dt, a, b, c, state, *, kernel: Optional[bool] = None,
            interpret: Optional[bool] = None):
    """:func:`recurrent`'s contract through the chunked form, on the PACKED
    state ``[B, H / g, N, g P]``; ``T`` a whole number of chunks
    (``min(CHUNK, T)`` tokens each).  ``kernel``: the Pallas kernel
    (default: on a TPU)."""
    x, dt, a, b, c, state = _f32(x, dt, a, b, c, state)
    t = x.shape[1]
    chunk = min(CHUNK, t)
    if t % chunk:
        raise ValueError(f"{t} tokens are not whole chunks of {chunk}")
    if on_tpu() if kernel is None else kernel:
        da._took("ssd_chunk_state")
        return _chunked_pallas(x, dt, a, b, c, state, chunk, interpret)
    da._took("ssd_chunk_plain")
    return _chunked_plain(x, dt, a, b, c, state, chunk)


# ----------------------------------------------------------------- decode step
def _step_kernel(layer_ref, dr_ref, bc_ref, s_ref, y_ref, s_out_ref, *,
                 groups: int):
    """``groups`` packed matrices of one row, each read once and written
    once.  ``dr_ref [2, groups x lanes]``: a head's decay (row 0) and its
    ``dt x`` (row 1) over its lanes; ``bc_ref [N, 2]``: ``B`` and ``C`` as
    COLUMNS over the state's axis, every head's."""
    del layer_ref                       # consumed by the state's index map
    n, lanes = s_ref.shape[-2:]
    b = jnp.broadcast_to(bc_ref[:, 0:1], (n, lanes))
    c = jnp.broadcast_to(bc_ref[:, 1:2], (n, lanes))
    for q in range(groups):
        at = slice(q * lanes, (q + 1) * lanes)
        s = s_ref[q] * dr_ref[0:1, at] + b * dr_ref[1:2, at]
        y_ref[0:1, at] = jnp.sum(s * c, axis=0, keepdims=True)
        s_out_ref[q] = s


def _step_pallas(dr, bc, leaf, layer, interpret=None):
    rows = dr.shape[0]
    groups, n, lanes = leaf.shape[2:]
    k = _step_groups(groups, lanes, STEP_GROUPS)
    state_spec = pl.BlockSpec((None, None, k, n, lanes),
                              lambda i, j, layer: (layer[0], i, j, 0, 0))
    y, leaf = pl.pallas_call(
        functools.partial(_step_kernel, groups=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, groups // k),
            in_specs=[
                pl.BlockSpec((None, 2, k * lanes),
                             lambda i, j, layer: (i, 0, j)),
                pl.BlockSpec((None, n, 2), lambda i, j, layer: (i, 0, 0)),
                state_spec],
            out_specs=[
                pl.BlockSpec((None, 1, k * lanes),
                             lambda i, j, layer: (i, 0, j)),
                state_spec]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, groups * lanes), _F32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        # the state leaf is updated in place (operand 3, after the scalar)
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_kernels() if interpret is None else interpret,
        name="ssd_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), dr, bc, leaf)
    return y[:, 0], leaf


@jax.named_scope("layer/state/step")
def step(x, dt, a, b, c, leaf, layer, *, kernel: Optional[bool] = None,
         interpret: Optional[bool] = None):
    """One token a row against the WHOLE packed state leaf ``[L, rows, H /
    g, N, g P]`` (float32) at ``layer`` (traced): ``x [rows, H, P]``, ``dt
    [rows, H]``, ``a [H]``, ``b``, ``c`` ``[rows, N]`` -> ``(y [rows, H, P]
    float32, leaf)``.  A row to be left as it is carries ``dt = 0``."""
    x, dt, a, b, c = _f32(x, dt, a, b, c)
    rows, h, p = x.shape
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)
    dr = jnp.stack([decay, dt[..., None] * x], axis=1).reshape(rows, 2, h * p)
    if on_tpu() if kernel is None else kernel:
        da._took("ssd_step")
        y, leaf = _step_pallas(dr, jnp.stack([b, c], axis=-1), leaf, layer,
                               interpret=interpret)
        return y.reshape(rows, h, p), leaf
    da._took("ssd_step_plain")
    groups, _, lanes = leaf.shape[2:]
    dr = dr.reshape(rows, 2, groups, 1, lanes)
    s = jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False) * dr[:, 0] \
        + b[:, None, :, None] * dr[:, 1]
    y = jnp.sum(s * c[:, None, :, None], axis=2)
    return y.reshape(rows, h, p), \
        jax.lax.dynamic_update_index_in_dim(leaf, s, layer, 0)
