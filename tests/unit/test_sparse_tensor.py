"""Sparse-gradient tests (reference ``tests/unit/runtime/test_sparse_grads``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.runtime.sparse_tensor import (SparseTensor,
                                                 sparse_allreduce,
                                                 sparse_allreduce_dense_result)


def _rowsparse(v=64, d=8, rows=(3, 10, 41), seed=0):
    rng = np.random.default_rng(seed)
    dense = np.zeros((v, d), np.float32)
    for r in rows:
        dense[r] = rng.normal(size=d)
    return jnp.asarray(dense)


def test_from_dense_roundtrip():
    dense = _rowsparse()
    st = SparseTensor.from_dense(dense)
    assert st.nnz == 4  # 3 rows -> power-of-two budget 4
    np.testing.assert_allclose(np.asarray(st.to_dense()), np.asarray(dense),
                               atol=1e-7)


def test_duplicate_indices_accumulate():
    st = SparseTensor(jnp.asarray([2, 2, 5], jnp.int32),
                      jnp.ones((3, 4), jnp.float32), (8, 4))
    dense = np.asarray(st.to_dense())
    assert (dense[2] == 2.0).all() and (dense[5] == 1.0).all()
    assert dense.sum() == 3 * 4


def test_static_budget_truncates_smallest():
    dense = _rowsparse(rows=(1, 2, 3, 4))
    st = SparseTensor.from_dense(dense, k=2)
    assert st.nnz == 2
    kept = np.asarray(st.to_dense())
    # the two largest-norm rows survive
    norms = np.abs(np.asarray(dense)).sum(-1)
    top2 = set(np.argsort(norms)[-2:])
    nz = {i for i in range(dense.shape[0]) if np.abs(kept[i]).sum() > 0}
    assert nz == top2


def test_sparse_allreduce_matches_dense_psum(eight_devices):
    """Sparse all-gather+densify == dense psum mean over the dp axis."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.topology import MeshTopology

    mesh = MeshTopology(dp=8).mesh
    per_rank = [np.asarray(_rowsparse(rows=(r, (r * 3) % 64), seed=r))
                for r in range(8)]
    stacked = jnp.asarray(np.stack(per_rank))          # [8, V, D]
    expected = np.mean(np.stack(per_rank), axis=0)

    @jax.jit
    def run(x):
        def body(xw):
            st = SparseTensor.from_dense(xw[0], k=4)
            return sparse_allreduce_dense_result(st, "dp")[None]

        return jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"))(x)

    with mesh:
        out = np.asarray(run(stacked))
    for r in range(8):  # every rank holds the same reduced dense tensor
        np.testing.assert_allclose(out[r], expected, atol=1e-6)


def test_sparse_allreduce_sum_mode(eight_devices):
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.topology import MeshTopology

    mesh = MeshTopology(dp=8).mesh
    x = jnp.asarray(np.stack([np.asarray(_rowsparse(rows=(5,), seed=0))
                              for _ in range(8)]))

    @jax.jit
    def run(x):
        def body(xw):
            st = SparseTensor.from_dense(xw[0], k=1)
            return sparse_allreduce(st, "dp", average=False).to_dense()[None]

        return jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"))(x)

    with mesh:
        out = np.asarray(run(x))
    np.testing.assert_allclose(out[0][5], 8 * np.asarray(x)[0][5], atol=1e-5)
