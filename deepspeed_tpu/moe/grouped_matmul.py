"""Grouped matmul for routed experts: ``out[rows of group g] = lhs[rows of
group g] @ rhs[layer, g]`` over ragged, contiguous row groups.

``moe/routed.py`` sorts a step's (token, expert) pairs by expert, so expert
``g`` owns ``group_sizes[g]`` consecutive rows of ``lhs``.  The kernel walks
*work items*: one per (row tile, group) pair that overlaps, in row order —
``tiles_m + groups - 1`` at most — and each item multiplies its whole
``[tm, K]`` row tile with the group's ``[K, tn]`` weight tile and stores
only the rows the group owns (the scheme of
``jax.experimental.pallas.ops.tpu.megablox``).  Consecutive items of one
row tile revisit the same output block, so it stays in VMEM until the walk
leaves the tile; a group's weights are read once per row tile it touches
(once, for the handful of rows a decode step gives an expert) and an empty
group's weights are never read.

Training differentiates it (``moe_gmm``'s ``custom_vjp``): the same walk
serves the two transposes, ``moe_gmm_dlhs`` (each group's weights read
transposed, in place) and ``moe_gmm_drhs`` (a group's rows contracted into
that expert's ``[K, N]`` tile).

The weights ride WHOLE, ``[L, G, K, N]``, with the layer index a
scalar-prefetch operand of the index map — as the paged KV pool does
(``ops/decode_attention.py``).  A layer scan that sliced its layer's
``[G, K, N]`` out of the stack would hand a custom call a 134 MB copy per
matmul (OLMoE: XLA materialises a dynamic-slice operand of a custom call;
compiled for a described v5e, PR 28), tripling the bytes of a memory-bound
step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.platform import interpret_kernels

#: rows of one work item: 8 sublanes x 16 (bf16 packs two rows a sublane)
TILE_M = 128
#: rows of one ``moe_gmm_drhs`` item: every item adds its product into a
#: float32 ``[K, N]`` tile in VMEM, as long a job as a 128-row item's matmul,
#: so the transposed kernel takes longer items (on the chip, PR 47: 45 -> 50 %
#: of the compute roofline).  The other two kernels LOSE by them (64 -> 58 %:
#: the half-empty tile at each group's edge outweighs their select and cast)
#: and keep ``TILE_M``
DRHS_TILE_M = 512
#: weight tile budget: [K, tn] in bf16, double-buffered by the pipeline
_RHS_TILE_BYTES = 4 << 20


def work_items(group_sizes, m: int, tm: int):
    """The kernel's walk, from ``group_sizes`` int32 [G] (rows of ``m``,
    a multiple of ``tm``; rows past ``sum(group_sizes)`` belong to nobody):
    ``(offsets [G+1], group_ids [I], tile_ids [I], count [1])`` with
    ``I = m // tm + G - 1`` slots of which the first ``count`` are real —
    item ``i`` multiplies row tile ``tile_ids[i]`` with group
    ``group_ids[i]``; the slots past ``count`` repeat the last real item
    (same blocks: nothing is fetched or written for them)."""
    g = group_sizes.shape[0]
    slots = m // tm + g - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    count = tiles.sum()
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                           total_repeat_length=slots)
    item0 = jnp.cumsum(tiles) - tiles            # a group's first item
    i = jnp.arange(slots, dtype=jnp.int32)
    tile_ids = first[group_ids] + i - item0[group_ids]
    last = jnp.maximum(count - 1, 0)
    real = i < count
    group_ids = jnp.where(real, group_ids, group_ids[last])
    tile_ids = jnp.where(real, tile_ids, tile_ids[last])
    offsets = jnp.concatenate([jnp.zeros(1, ends.dtype), ends])
    return (offsets.astype(jnp.int32), group_ids.astype(jnp.int32),
            tile_ids.astype(jnp.int32), count.astype(jnp.int32).reshape(1))


def _gmm_kernel(layer_ref, offsets_ref, group_ref, tile_ref, count_ref,
                lhs_ref, rhs_ref, out_ref, *, tm: int,
                transposed: bool = False):
    del layer_ref                      # consumed by the weight index map
    i = pl.program_id(1)

    @pl.when(i < count_ref[0])
    def _item():
        g = group_ref[i]
        rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
        if transposed:                 # the weight tile is [tn, K]: lhs . rhs^T
            acc = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                          preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])


def _row_tile(m: int, row_tile: int = TILE_M) -> int:
    return min(row_tile, -(-m // 16) * 16)


def _gmm_call(lhs, rhs, group_sizes, layer, interpret, transposed=False):
    """The forward kernel, or (``transposed``) the same walk against each
    group's weights transposed — ``lhs [M, N] x rhs[layer, g]^T -> [M, K]``,
    the ``[tk, N]`` weight tile read in place from the ``[L, G, K, N]``
    stack: what ``d_lhs`` of the forward is."""
    m, c = lhs.shape
    _, g, k, n = rhs.shape
    width = k if transposed else n       # the result's columns
    tm = _row_tile(m)
    mp = -(-m // tm) * tm
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    tn = width
    while c * tn * rhs.dtype.itemsize > _RHS_TILE_BYTES and tn % 256 == 0:
        tn //= 2
    offsets, group_ids, tile_ids, count = work_items(
        group_sizes.astype(jnp.int32), mp, tm)
    if transposed:
        rhs_spec = pl.BlockSpec((None, None, tn, n),
                                lambda j, i, layer, offs, gid, tid, cnt:
                                (layer[0], gid[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, None, k, tn),
                                lambda j, i, layer, offs, gid, tid, cnt:
                                (layer[0], gid[i], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,     # layer, offsets, group ids, tile ids, count
        grid=(width // tn, group_ids.shape[0]),
        in_specs=[
            pl.BlockSpec((tm, c), lambda j, i, layer, offs, gid, tid, cnt:
                         (tid[i], 0)),
            rhs_spec,
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, layer, offs, gid, tid, cnt:
                               (tid[i], j)),
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, width), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret_kernels() if interpret is None else interpret,
        name="moe_gmm_dlhs" if transposed else "moe_gmm",
    )(layer.reshape(1), offsets, group_ids, tile_ids, count, lhs, rhs)
    return out[:m]


def _drhs_kernel(offsets_ref, group_ref, tile_ref, count_ref, lhs_ref,
                 dout_ref, out_ref, acc_ref, *, tm: int, slots: int):
    i = pl.program_id(2)
    count = count_ref[0]

    @pl.when(i < count)
    def _item():
        g = group_ref[i]
        first = (i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g)
        last = (i == count - 1) \
            | (group_ref[jnp.minimum(i + 1, slots - 1)] != g)
        rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
        # a row tile holds other groups' rows, and behind the last group
        # rows that are nobody's (possibly not even finite): both sides are
        # cleared, since 0 x NaN is NaN
        lhs = jnp.where(mine, lhs_ref[...], 0)
        dout = jnp.where(mine, dout_ref[...], 0)
        part = jax.lax.dot_general(lhs, dout, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

        @pl.when(first)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += part

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


#: float32 accumulator of one [tk, tn] tile of an expert's weight gradient
_DRHS_ACC_BYTES = 8 << 20


def _gmm_drhs(lhs, dout, group_sizes, interpret):
    """The TRANSPOSED grouped matmul: ``out[g] = lhs[rows of g]^T @
    dout[rows of g]`` -> ``[G, K, N]`` in ``lhs``'s dtype, float32
    accumulation — ``d_rhs`` of the forward.  The same walk; an expert's
    ``[tk, tn]`` tile stays in VMEM over the consecutive items of its group
    and is written once.  A group without a row is visited by no item: its
    tile is cleared afterwards."""
    m, k = lhs.shape
    n = dout.shape[1]
    g = group_sizes.shape[0]
    tm = _row_tile(m, DRHS_TILE_M)
    mp = -(-m // tm) * tm
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
        dout = jnp.pad(dout, ((0, mp - m), (0, 0)))
    tk, tn = k, n
    while tk * tn * 4 > _DRHS_ACC_BYTES and tn % 256 == 0:
        tn //= 2
    while tk * tn * 4 > _DRHS_ACC_BYTES and tk % 256 == 0:
        tk //= 2
    offsets, group_ids, tile_ids, count = work_items(
        group_sizes.astype(jnp.int32), mp, tm)
    slots = group_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,             # offsets, group ids, tile ids, count
        grid=(k // tk, n // tn, slots),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda a, b, i, offs, gid, tid, cnt:
                         (tid[i], a)),
            pl.BlockSpec((tm, tn), lambda a, b, i, offs, gid, tid, cnt:
                         (tid[i], b)),
        ],
        out_specs=pl.BlockSpec((None, tk, tn),
                               lambda a, b, i, offs, gid, tid, cnt:
                               (gid[i], a, b)),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_drhs_kernel, tm=tm, slots=slots),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret_kernels() if interpret is None else interpret,
        name="moe_gmm_drhs",
    )(offsets, group_ids, tile_ids, count, lhs, dout)
    return jnp.where((group_sizes > 0)[:, None, None], out, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm(lhs, rhs, group_sizes, layer, interpret):
    return _gmm_call(lhs, rhs, group_sizes, layer, interpret)


def _gmm_fwd(lhs, rhs, group_sizes, layer, interpret):
    return _gmm_call(lhs, rhs, group_sizes, layer, interpret), \
        (lhs, rhs, group_sizes, layer)


def _gmm_bwd(interpret, res, d_out):
    lhs, rhs, group_sizes, layer = res
    d_out = d_out.astype(lhs.dtype)
    # rows behind the last group are no item's: what the kernel leaves there
    # is undefined, and the cotangent of a row nobody multiplied is zero
    owned = (jnp.arange(lhs.shape[0], dtype=jnp.int32)
             < group_sizes.sum())[:, None]
    d_lhs = jnp.where(owned, _gmm_call(d_out, rhs, group_sizes, layer,
                                       interpret, transposed=True), 0)
    d_here = _gmm_drhs(lhs, d_out, group_sizes, interpret).astype(rhs.dtype)
    if rhs.shape[0] == 1:
        d_rhs = d_here[None]
    else:
        d_rhs = jnp.zeros_like(rhs).at[layer].set(d_here)
    return d_lhs, d_rhs, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def moe_gmm(lhs, rhs, group_sizes, layer=None, *, interpret=None):
    """``lhs [M, K]`` (rows sorted by group) x ``rhs [L, G, K, N]`` at
    ``layer`` (or ``[G, K, N]`` with ``layer=None``) -> ``[M, N]`` in
    ``lhs``'s dtype, float32 accumulation.  ``group_sizes`` int32 [G] sums
    to at most ``M``; rows beyond the sum come back undefined.

    Differentiable in ``lhs`` and ``rhs`` (``jax.custom_vjp``): ``d_lhs`` is
    the same walk against the weights transposed (kernel ``moe_gmm_dlhs``;
    zero in the rows beyond the sum), ``d_rhs[g] = lhs_g^T d_out_g`` the
    transposed grouped matmul (``moe_gmm_drhs``; zero for a group without a
    row, and for every layer of the stack but ``layer``).  A caller that
    takes no gradient runs the one forward kernel."""
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    return _gmm(lhs, rhs, group_sizes, jnp.asarray(layer, jnp.int32),
                interpret)
