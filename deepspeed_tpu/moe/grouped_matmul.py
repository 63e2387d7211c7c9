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
                lhs_ref, rhs_ref, out_ref, *, tm: int):
    del layer_ref                      # consumed by the weight index map
    i = pl.program_id(1)

    @pl.when(i < count_ref[0])
    def _item():
        g = group_ref[i]
        rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
        acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                      preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])


def moe_gmm(lhs, rhs, group_sizes, layer=None, *, interpret=None):
    """``lhs [M, K]`` (rows sorted by group) x ``rhs [L, G, K, N]`` at
    ``layer`` (or ``[G, K, N]`` with ``layer=None``) -> ``[M, N]`` in
    ``lhs``'s dtype, float32 accumulation.  ``group_sizes`` int32 [G] sums
    to at most ``M``; rows beyond the sum come back undefined."""
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    m, k = lhs.shape
    _, g, _, n = rhs.shape
    tm = min(TILE_M, -(-m // 16) * 16)
    mp = -(-m // tm) * tm
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    tn = n
    while k * tn * rhs.dtype.itemsize > _RHS_TILE_BYTES and tn % 256 == 0:
        tn //= 2
    offsets, group_ids, tile_ids, count = work_items(
        group_sizes.astype(jnp.int32), mp, tm)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,     # layer, offsets, group ids, tile ids, count
        grid=(n // tn, group_ids.shape[0]),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, layer, offs, gid, tid, cnt:
                         (tid[i], 0)),
            pl.BlockSpec((None, None, k, tn),
                         lambda j, i, layer, offs, gid, tid, cnt:
                         (layer[0], gid[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, layer, offs, gid, tid, cnt:
                               (tid[i], j)),
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret_kernels() if interpret is None else interpret,
        name="moe_gmm",
    )(jnp.asarray(layer, jnp.int32).reshape(1), offsets, group_ids, tile_ids,
      count, lhs, rhs)
    return out[:m]
