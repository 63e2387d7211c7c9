"""Sequence-parallel (DeepSpeed-Ulysses-style) prefill attention over the
mesh ``sp`` axis.

Long prompts make prefill compute the bottleneck: one chip owns the whole
``[T, S]`` score matrix of every head.  Ulysses sequence parallelism
shards the PROMPT over ``sp`` ranks instead — each rank projects QKV for
its own ``T/sp`` token slice (the model families' ``shard_seq`` hint
makes GSPMD keep hidden states token-sharded through the projections) —
and converts between the two layouts around attention with a pair of
``lax.all_to_all`` collectives:

 1. heads -> sequence: ``[B, H/tp, T/sp, D] -> [B, H/(tp*sp), T, D]`` —
    every rank now holds ALL chunk positions for its own 1/sp slice of
    the query heads, so attention itself stays embarrassingly parallel
    over heads (exactly the property the tp path exploits);
 2. attention against the row's full paged-KV view (gathered through the
    block table, same pool, same scatter ops — nothing downstream of the
    pool changes);
 3. sequence -> heads: the inverse all-to-all restores the token-sharded
    layout the output projection expects.

Like ``paged_kv``'s tp/dp contexts, the sp context is module state
installed by the serving engine around *prefill* program invocations
only — tracing happens inside the call, so prefill programs bake in the
sp shard_map while decode/verify programs (traced outside the context)
are untouched, and ``sp=1`` engines never enter this module at all.
"""

from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import paged_kv
from .paged_kv import _paged_gather, pool_payload, tp_axis

# ------------------------------------------------------------- sp context
_SP_MESH = None
_SP_AXIS = "sp"


def configure_sp(mesh=None, axis: str = "sp") -> None:
    """Install (mesh + axis name) or clear (``None``) the sequence-parallel
    context.  With a mesh installed, paged prefill attention (T > 1) whose
    shapes divide the axis runs the Ulysses all-to-all path."""
    global _SP_MESH, _SP_AXIS
    _SP_MESH = mesh
    _SP_AXIS = axis


@contextlib.contextmanager
def sp_context(mesh, axis: str = "sp"):
    """Scoped :func:`configure_sp` — the serving engine wraps prefill
    invocations (and only those) in this, so each engine's prefill
    programs bake in ITS sp mesh even when engines of different sp
    degrees coexist in one process."""
    prev = (_SP_MESH, _SP_AXIS)
    configure_sp(mesh, axis)
    try:
        yield
    finally:
        configure_sp(*prev)


def sp_mesh():
    return _SP_MESH


def sp_axis() -> str:
    return _SP_AXIS


def sp_shards(h: int, hkv: int, t: int) -> int:
    """Shard count the configured sp context puts on a ``[B, H, T, D]``
    prefill: the mesh's sp-axis size when the chunk width and the
    per-tp-shard query heads both divide it (and GQA groups divide
    evenly), else 1 — the replicated fallback, mirroring
    ``paged_kv.head_shards``."""
    if _SP_MESH is None:
        return 1
    sp = int(dict(_SP_MESH.shape).get(_SP_AXIS, 1))
    if sp <= 1 or t <= 1:
        return 1
    tp = paged_kv.head_shards(h, hkv)
    if t % sp or h % hkv or (h // tp) % sp:
        return 1
    return sp


def shard_seq(x):
    """Prefill hook for the model families: constrain hidden states
    ``[B, T, D]`` token-sharded over the sp axis so the QKV/MLP
    projections around attention run on 1/sp of the chunk per rank
    (GSPMD propagates the layout through the elementwise/matmul chain).
    No-op without an sp context, on decode (T == 1), or when T doesn't
    divide the axis — so the hook is safe to leave in every family's
    cached-forward path unconditionally."""
    if _SP_MESH is None:
        return x
    sp = int(dict(_SP_MESH.shape).get(_SP_AXIS, 1))
    if sp <= 1 or x.ndim != 3 or x.shape[1] <= 1 or x.shape[1] % sp:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_SP_MESH, P(None, _SP_AXIS, None)))


def sp_prefill_attention(q, k_pool, v_pool, block_tables, q_pos, *,
                         sm_scale: Optional[float] = None, layer=None):
    """Ulysses sequence-parallel paged prefill attention.

    q:            [B, H, T, D] — a T-token prefill chunk (T % sp == 0)
    k/v_pool:     [L, NB, HKV, block_size, D] stacked paged pool read at
                  ``layer`` (one layer's pool with ``layer=None``;
                  optionally tp-head-sharded; sp composes with tp in ONE
                  shard_map)
    block_tables: int32 [B, NBPER]
    q_pos:        scalar or int32 [B] — per-row chunk base positions

    The KV side is each row's full logical cache view gathered through
    its block table (replicated across sp ranks — the pool has no
    sequence dim to shard); the [T, S] score/softmax work, which is what
    actually scales quadratically with context, splits sp-ways over query
    heads after the first all-to-all.
    """
    from .decode_attention import decode_attention_reference

    mesh, ax = _SP_MESH, _SP_AXIS
    (k_pool, layer), (v_pool, _) = (paged_kv.whole_pool(k_pool, layer),
                                    paged_kv.whole_pool(v_pool, layer))
    b, h, t, d = q.shape
    hkv = pool_payload(k_pool).shape[2]
    sp = sp_shards(h, hkv, t)
    if sp <= 1:
        raise ValueError("sp_prefill_attention called without a dividing "
                         "sp context; dispatch should have fallen back")
    tp = paged_kv.head_shards(h, hkv)
    rep = h // hkv
    hq_loc = h // (tp * sp)        # query heads per program after the a2a
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    tp_ax = tp_axis() if tp > 1 else None
    qs = P(None, tp_ax, ax)        # [B, H, T, D]: heads over tp, T over sp
    ps = P(None, None, tp_ax)      # pool leaves: heads over tp only

    def body(q, kp, vp, bt, pos, layer):
        # q arrives [B, H/tp, T/sp, D]; heads -> sequence
        q = jax.lax.all_to_all(q, ax, split_axis=1, concat_axis=2,
                               tiled=True)                # [B, hq_loc, T, D]
        k = _paged_gather(kp, bt, layer, d, out_dtype=q.dtype)
        v = _paged_gather(vp, bt, layer, d, out_dtype=q.dtype)  # [B,HKV/tp,S,D]
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)                # [B, H/tp, S, D]
            v = jnp.repeat(v, rep, axis=1)
        # this rank's query heads are the idx-th hq_loc-slice of the tp
        # shard (tiled all_to_all concatenates source parts in rank order)
        idx = jax.lax.axis_index(ax)
        k = jax.lax.dynamic_slice_in_dim(k, idx * hq_loc, hq_loc, axis=1)
        v = jax.lax.dynamic_slice_in_dim(v, idx * hq_loc, hq_loc, axis=1)
        out = decode_attention_reference(q, k, v, pos, sm_scale=scale)
        # sequence -> heads: restore the token-sharded layout
        return jax.lax.all_to_all(out, ax, split_axis=2, concat_axis=1,
                                  tiled=True)

    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(qs, ps, ps, P(), P(), P()),
                         out_specs=qs, check_vma=False)(
        q, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32), pos, layer)


def alltoall_bytes(n_layers: int, rows: int, width: int, heads: int,
                   head_dim: int, itemsize: int, sp: int) -> int:
    """Host-side accounting of cross-rank bytes moved by the two Ulysses
    all-to-alls of one prefill call (per layer: q in, attention out —
    each a [rows, heads, width, head_dim] tensor of which the (sp-1)/sp
    off-diagonal fraction crosses ranks).  Feeds the
    ``serving_sp_alltoall_bytes_total`` counter; an estimate from shapes,
    not a device measurement."""
    per = 2 * int(n_layers) * int(rows) * int(width) * int(heads) * \
        int(head_dim) * int(itemsize)
    return (per * (int(sp) - 1)) // max(int(sp), 1)
