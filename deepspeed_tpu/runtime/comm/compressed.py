"""Error-compensated 1-bit compressed collectives.

Reference: ``runtime/comm/nccl.py:52 NcclBackend.compressed_allreduce`` — the
1-bit Adam communication layer.  The algorithm is two-stage, chunked:

 1. every worker splits its tensor into ``n`` chunks, 1-bit-compresses each
    (sign int8 + one f32 scale per chunk, residual kept as **worker error**
    feedback), and all-to-alls the chunks so worker ``j`` holds everyone's
    chunk ``j``;
 2. worker ``j`` decompresses and averages its chunk, compresses the average
    (residual kept as **server error** feedback), and all-gathers the result.

Signs travel PACKED, 8 per byte (``uint8`` bitwise ops around the
collectives), exactly like the reference's ``compress_by_chunk``
(``cupy.packbits``, ``runtime/comm/nccl.py:78-85``): wire traffic is
~2x size x 1/8 byte + per-chunk f32 scales vs ~2x size x 4 bytes for an
fp32 ring all-reduce — a ~32x wire reduction, expressed with
``lax.all_to_all``/``all_gather`` on packed uint8 inside ``shard_map`` so
XLA moves the small payload over ICI.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# bit i of a packed byte holds sign of element 8*j + i (1 <-> +1, 0 <-> -1)
_BIT_WEIGHTS = tuple(1 << i for i in range(8))


def _pack_signs(comp):
    """comp [..., c] (c % 8 == 0) -> packed sign bits, uint8 [..., c // 8]."""
    bits = (comp >= 0).astype(jnp.uint8).reshape(comp.shape[:-1] + (-1, 8))
    w = jnp.asarray(_BIT_WEIGHTS, jnp.uint8)
    return jnp.sum(bits * w, axis=-1, dtype=jnp.uint8)


def _unpack_signs(packed):
    """packed uint8 [..., c8] -> signs f32 [..., c8 * 8] in {-1, +1}."""
    shifts = jnp.asarray(range(8), jnp.uint8)
    bits = (packed[..., None] >> shifts) & jnp.uint8(1)
    return (bits.astype(jnp.float32) * 2.0 -
            1.0).reshape(packed.shape[:-1] + (-1,))


def _compress(comp):
    """sign/scale 1-bit quantization per leading chunk: comp [n, c] ->
    (packed sign bits uint8 [n, c/8], scales f32 [n], residual)."""
    scales = jnp.mean(jnp.abs(comp), axis=-1)
    sign_f = jnp.where(comp >= 0, 1.0, -1.0)
    packed = _pack_signs(comp)
    return packed, scales, comp - sign_f * scales[..., None]


def compressed_allreduce(x, worker_error, server_error, axis_name: str):
    """All-reduce-mean of ``x`` over ``axis_name`` with 1-bit compression.

    Must run inside ``shard_map``/``pmap``.  ``worker_error`` has ``x``'s
    (padded, chunked) shape [n, c]; ``server_error`` has one chunk's shape
    [c].  Returns ``(mean, new_worker_error, new_server_error)``; threading
    the errors into the next call keeps the *accumulated* reduction unbiased
    even though each step is lossy (the 1-bit Adam convergence argument).

    Use :func:`error_shapes` to initialize the error buffers.
    """
    n = jax.lax.psum(1, axis_name)
    orig_shape = x.shape
    flat = x.reshape(-1)
    c = error_shapes(orig_shape, n)[0][1]     # 8-aligned chunk length
    pad = n * c - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, c)                               # [n, c]

    # stage 1: worker-side compression + all-to-all of PACKED sign bits
    comp = chunks + worker_error
    packed, scales, new_worker_error = _compress(comp)
    # trace-time wire accounting: the comms logger records the packed uint8
    # payloads (the dense equivalent would be 4 bytes/elem both rounds)
    from ...comm.comm import _record

    _record("all_to_all", packed, axis_name, log_name="compressed_allreduce")
    # worker j receives row j of every peer: [n, c/8] rows ordered by source
    recv_packed = jax.lax.all_to_all(packed, axis_name, split_axis=0,
                                     concat_axis=0, tiled=True)
    recv_scales = jax.lax.all_to_all(scales, axis_name, split_axis=0,
                                     concat_axis=0, tiled=True)
    chunk_mean = jnp.mean(
        _unpack_signs(recv_packed) * recv_scales[:, None], axis=0)  # [c]

    # stage 2: server-side compression + all-gather of packed bits
    comp2 = (chunk_mean + server_error)[None, :]
    packed2, scales2, server_residual = _compress(comp2)
    new_server_error = server_residual[0]
    _record("all_gather", packed2[0], axis_name,
            log_name="compressed_allreduce")
    out_packed = jax.lax.all_gather(packed2[0], axis_name)   # [n, c/8] uint8
    out_scales = jax.lax.all_gather(scales2[0], axis_name)   # [n]
    out = (_unpack_signs(out_packed) * out_scales[:, None]).reshape(-1)
    size = int(np.prod(orig_shape))
    return out[:size].reshape(orig_shape), new_worker_error, new_server_error


def error_shapes(x_shape, n: int) -> Tuple[tuple, tuple]:
    """(worker_error_shape, server_error_shape) for a tensor of x_shape
    reduced over n workers; chunk length is 8-aligned for bit packing.

    Format note: the 8-alignment (introduced with the packed wire format)
    changed these shapes wherever ``ceil(size/n) % 8 != 0`` — 1-bit
    checkpoints written by the earlier int8-sign build store unpadded
    error buffers and cannot resume against the new shapes (no released
    version ever shipped the old layout, so no pad-on-load migration is
    provided)."""
    size = int(np.prod(x_shape))
    c = -(-size // n)
    c = (c + 7) // 8 * 8
    return (n, c), (c,)


class CompressedBackend:
    """Stateful convenience wrapper holding per-worker/server error buffers
    (reference ``NcclBackend`` keeps ``worker_error``/``server_error``)."""

    def __init__(self, mesh, axis_name: str = "dp"):
        self.mesh = mesh
        self.axis_name = axis_name
        self.n = mesh.shape[axis_name]
        self._errors = {}
        self._run = self._build_run()  # jitted ONCE; retraces only per shape

    def _build_run(self):
        from jax.sharding import PartitionSpec as P

        @jax.jit
        def run(x, we, se):
            def body(xw, wew, sew):
                m, nwe, nse = compressed_allreduce(
                    xw[0], wew[0], sew[0], self.axis_name)
                return m[None], nwe[None], nse[None]

            return jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(self.axis_name),) * 3,
                out_specs=(P(self.axis_name),) * 3)(x, we, se)

        return run

    def allreduce(self, key: str, x_sharded):
        """All-reduce a [n, ...]-stacked per-worker array (leading dim =
        worker) with persistent error feedback keyed by ``key``."""
        n = self.n
        per_shape = x_sharded.shape[1:]
        if key not in self._errors:
            we_s, se_s = error_shapes(per_shape, n)
            self._errors[key] = (jnp.zeros((n,) + we_s, jnp.float32),
                                 jnp.zeros((n,) + se_s, jnp.float32))
        we, se = self._errors[key]
        mean_sh, nwe, nse = self._run(x_sharded, we, se)
        self._errors[key] = (nwe, nse)
        return mean_sh
