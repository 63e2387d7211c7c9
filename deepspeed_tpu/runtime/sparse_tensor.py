"""Sparse (row-compressed) gradients + sparse all-reduce.

Reference: ``runtime/sparse_tensor.py SparseTensor`` and the engine's
``sparse_allreduce_*`` (``runtime/engine.py:2461-2476``) — embedding
gradients touch few vocabulary rows per step, so instead of all-reducing the
dense [V, D] tensor, each rank ships (row indices, row values) and the
reduction is an all-gather + scatter-add (the reference concatenates
per-rank indices/values exactly the same way, leaving duplicate rows to the
dense conversion).

TPU realisation: row compression with a **static** row budget (jit needs
fixed shapes — the budget plays the role the reference's bucket size plays),
``lax.all_gather`` over the dp axis inside ``shard_map``, and a segment-sum
scatter back to dense.  Wire volume: 2 * world * k * (D + 1) words vs
2 * V * D for a ring all-reduce — a win whenever rows-touched << V.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class SparseTensor(NamedTuple):
    """Row-sparse view of a dense [V, D] tensor (reference ``SparseTensor``)."""

    indices: jnp.ndarray   # [k] int32 row ids (may repeat; padded rows = V)
    values: jnp.ndarray    # [k, D]
    dense_shape: Tuple[int, int]

    @staticmethod
    def from_dense(dense, k: Optional[int] = None) -> "SparseTensor":
        """Compress the (at most) ``k`` largest-norm rows.

        ``k`` is the static row budget (jit needs fixed shapes — pick it
        from the worst-case unique tokens per batch, like the reference
        sizes its buckets).  **A budget smaller than the touched-row count
        silently drops the smallest-norm rows** — size it generously.
        Under jit ``k`` is REQUIRED; on concrete arrays ``k=None`` derives
        it from the nonzero-row count (power-of-two rounded).
        """
        v, d = dense.shape
        norms = jnp.sum(jnp.abs(dense), axis=-1)
        if k is None:
            try:
                nnz = int(jnp.sum(norms > 0))
            except jax.errors.ConcretizationTypeError as e:
                raise ValueError(
                    "SparseTensor.from_dense(k=None) needs a concrete array;"
                    " inside jit/shard_map pass an explicit static row "
                    "budget k") from e
            k = max(1, 1 << (nnz - 1).bit_length())
        k = min(k, v)
        _, idx = jax.lax.top_k(norms, k)
        vals = dense[idx]
        # rows beyond the true support carry zero values; mark padded ids
        padded = jnp.where(norms[idx] > 0, idx, v)
        return SparseTensor(padded.astype(jnp.int32), vals, (v, d))

    def to_dense(self) -> jnp.ndarray:
        """Scatter-add back to dense (duplicate indices accumulate, matching
        the reference's sparse-to-dense)."""
        v, d = self.dense_shape
        out = jnp.zeros((v + 1, d), self.values.dtype)  # +1: padded-row sink
        out = out.at[self.indices].add(self.values)
        return out[:v]

    @property
    def nnz(self) -> int:
        return self.indices.shape[0]


def sparse_allreduce(st: SparseTensor, axis_name: str,
                     average: bool = True) -> SparseTensor:
    """All-reduce a row-sparse gradient over ``axis_name`` (inside
    shard_map/pmap): all-gather per-rank indices+values and concatenate —
    the reference's ``sparse_allreduce_bucket`` wire pattern.  Duplicate
    rows across ranks remain and accumulate at ``to_dense``."""
    n = jax.lax.psum(1, axis_name)
    local = st.values / n if average else st.values  # divide pre-gather
    idx = jax.lax.all_gather(st.indices, axis_name).reshape(-1)
    vals = jax.lax.all_gather(local, axis_name)
    vals = vals.reshape(-1, vals.shape[-1])
    return SparseTensor(idx, vals, st.dense_shape)


def sparse_allreduce_dense_result(st: SparseTensor, axis_name: str,
                                  average: bool = True) -> jnp.ndarray:
    """Convenience: sparse all-reduce then densify (what the engine does
    with the result before the optimizer step)."""
    return sparse_allreduce(st, axis_name, average=average).to_dense()


# ---------------------------------------------------------------------------
# engine-path sparse embedding-grad exchange (config key sparse_gradients,
# reference runtime/engine.py:2461-2476 sparse_allreduce_no_retain)
# ---------------------------------------------------------------------------
def _data_axes_in(mesh):
    from ..parallel.topology import DATA_AXES

    return tuple(a for a in DATA_AXES
                 if mesh is not None and mesh.shape.get(a, 1) > 1)


@jax.custom_vjp
def sparse_embedding_lookup(table, ids):
    """``table[ids]`` whose BACKWARD ships the gradient row-sparse.

    The dense embedding vjp scatter-adds into a [V, D] zero tensor *per
    device*, and XLA then all-reduces the dense [V, D] across the data
    axes.  Here the backward enters ``shard_map`` over (dp, ep), all-gathers
    only the touched (token-id, row-grad) pairs — ``world * T_local * (D+1)``
    words on the wire instead of the dense ``V * D`` ring — and each device
    scatter-adds the gathered rows locally (the reference concatenates
    per-rank indices/values the same way).  Exact: duplicates accumulate in
    the scatter, so the result equals the dense exchange bit-for-bit in f32.

    Wins when tokens-per-device << vocab; the engine enables it on models
    that opt in via ``sparse_gradients: true`` (runtime/config.py).  Note
    that a TIED lm-head still produces a dense [V, D] grad contribution
    through the head matmul — as in the reference, the sparse exchange
    covers the lookup side only.
    """
    return table[ids]


def _sel_fwd(table, ids):
    # dtype rides as a zero-size proto (a dtype object is not a jax type)
    return table[ids], (ids, table.shape, jnp.zeros((0,), table.dtype))


def _sel_bwd(res, ct):
    ids, tshape, tproto = res
    tdtype = tproto.dtype
    v, d = tshape
    flat_ids = ids.reshape(-1)
    flat_ct = ct.reshape(-1, d).astype(tdtype)

    def scatter(gi, gv):
        return jnp.zeros((v, d), tdtype).at[gi].add(gv)

    from .. import comm

    mesh = comm.get_mesh()
    axes = _data_axes_in(mesh)
    world = 1
    for a in axes:
        world *= mesh.shape[a]
    if not axes or flat_ids.shape[0] % world != 0:
        # no data axes, or a token count shard_map cannot split evenly
        # (e.g. an unsharded eval path): plain local scatter — XLA still
        # inserts whatever exchange the sharding requires
        grad = scatter(flat_ids, flat_ct)
    else:
        from jax.sharding import PartitionSpec as P

        def exchange(idl, ctl):
            gi = jax.lax.all_gather(idl, axes, tiled=True)
            gv = jax.lax.all_gather(ctl, axes, tiled=True)
            return scatter(gi, gv)

        grad = jax.shard_map(
            exchange, mesh=mesh,
            in_specs=(P(axes), P(axes, None)),
            out_specs=P(), check_vma=False)(flat_ids, flat_ct)
    return grad, np.zeros(ids.shape, jax.dtypes.float0)


sparse_embedding_lookup.defvjp(_sel_fwd, _sel_bwd)
