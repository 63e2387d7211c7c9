"""Sequence/context parallelism: Ulysses all-to-all + ring attention.

The reference at v0.8.2 predates DeepSpeed-Ulysses — its long-sequence story is
block-sparse attention + curriculum seqlen + a reserved "slice parallel" axis on
the topology (`pipe/topology.py:443`, SURVEY §5.7).  The TPU build makes SP a
first-class mesh axis (``sp``) with two interchangeable attention strategies:

 - **Ulysses** (`ulysses_attention`): all-to-all over the ``sp`` axis scatters
   heads / gathers sequence around the attention op, so each device runs plain
   flash attention on the *full* sequence for ``H/sp`` of the heads.  Two
   all-to-alls per attention, rides ICI.  Requires local head count divisible
   by sp.

 - **Ring attention** (`ring_attention`): Q stays put; KV chunks rotate around
   the ``sp`` ring via ``ppermute``.  Each step runs the flash-attention
   forward kernel on a (local Q, visiting KV) pair and merges the partial
   output into a running online-softmax state.  The backward pass is a second
   ring: per-step dq/dk/dv from the flash backward kernels evaluated with the
   *globally merged* log-sum-exp, with dk/dv accumulators rotating alongside
   the KV chunks back to their owners.  Memory per device stays O(S/sp).

Both run inside ``shard_map`` over the engine's global mesh, composing with
``dp`` (batch) and ``tp`` (heads) sharding.  ``sequence_parallel_attention``
picks Ulysses when head counts divide (cheaper: 2 all-to-alls vs sp ppermute
rounds), else ring.

Causal load balance: with contiguous chunking, device 0's chunk attends only
itself while the last device attends everything — every ring step issues
kernels on all devices but discards the future-chunk results, wasting ~2x
FLOPs at large sp.  ``zigzag=True`` (default for causal) assigns each device
the HALF-chunK PAIR (i, 2*sp-1-i) of 2*sp sequence blocks.  Then at every
step each device runs exactly two half-sized, fully-valid non-causal kernels
(plus causal diagonals at step 0): which halves participate depends only on
the predicate ``idx >= step``, so inputs are routed with selects and the
compiled program is SPMD-uniform with NO discarded kernel work.  The test
asserts the kernel-invocation count and shapes (work balance) and numeric
parity of o/dq/dk/dv against dense flash attention.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import flash_attention as fa
from ..utils.platform import interpret_kernels
from .topology import DATA_AXES, SP_AXIS, TP_AXIS

NEG_INF = -jnp.inf


def _repeat_kv(q, k, v):
    h, hkv = q.shape[1], k.shape[1]
    if hkv != h:
        assert h % hkv == 0, f"GQA needs num_heads {h} % kv_heads {hkv} == 0"
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


# ---------------------------------------------------------------------------
# ring attention core (runs per-shard inside shard_map)
# ---------------------------------------------------------------------------
def _flat(x):
    b, h, c, d = x.shape
    return x.reshape(b * h, c, d)


def _rep_flat(kv, rep):
    """[B, Hkv, C, D] -> repeated+flattened [B*Hkv*rep, C, D] matching q's
    head order — GQA KV chunks rotate un-repeated so ring traffic stays
    O(Hkv), and only the per-step kernel input is expanded."""
    if rep == 1:
        return _flat(kv)
    b, hkv, c, d = kv.shape
    out = jnp.broadcast_to(kv[:, :, None], (b, hkv, rep, c, d))
    return out.reshape(b * hkv * rep, c, d)


def _ring_fwd_impl(q, k, v, axis_name, sp, sm_scale, causal, block_q, block_k,
                   interpret):
    """q: [B, H, C, D]; k, v: [B, Hkv, C, D] local chunks (device i holds
    sequence chunk i).  Returns (o [B, H, C, D], lse [B*H, C]).
    """
    b, h, c, d = q.shape
    rep = h // k.shape[1]
    bh = b * h
    qf = _flat(q)
    idx = jax.lax.axis_index(axis_name)

    m = jnp.full((bh, c, 1), NEG_INF, jnp.float32)   # running max
    s = jnp.zeros((bh, c, 1), jnp.float32)           # running sum-exp
    acc = jnp.zeros((bh, c, d), jnp.float32)         # running weighted output
    k_cur, v_cur = k, v
    perm = [(r, (r + 1) % sp) for r in range(sp)]

    for step in range(sp):
        # after `step` rotations device idx holds KV chunk (idx - step) mod sp
        o_j, lse_j = fa._fwd(qf, _rep_flat(k_cur, rep), _rep_flat(v_cur, rep),
                             sm_scale, causal and step == 0, block_q, block_k,
                             interpret, c)
        lse_j = lse_j[..., None]                     # [bh, C, 1]
        m_new = jnp.maximum(m, lse_j)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(lse_j - m_new)
        s_new = s * alpha + beta
        acc_new = acc * alpha + beta * o_j.astype(jnp.float32)
        if causal and step > 0:
            # visiting chunk j = idx - step (mod sp) is in the past iff
            # idx >= step; future chunks contribute nothing
            attend = idx >= step
            m = jnp.where(attend, m_new, m)
            s = jnp.where(attend, s_new, s)
            acc = jnp.where(attend, acc_new, acc)
        else:
            m, s, acc = m_new, s_new, acc_new
        if step < sp - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    s_safe = jnp.where(s == 0.0, 1.0, s)
    o = (acc / s_safe).astype(q.dtype).reshape(b, h, c, d)
    lse = (m + jnp.log(s_safe))[..., 0]
    return o, lse


def _ring_bwd_impl(q, k, v, o, lse, do, axis_name, sp, sm_scale, causal,
                   block_q, block_k, interpret):
    b, h, c, d = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    idx = jax.lax.axis_index(axis_name)
    perm = [(r, (r + 1) % sp) for r in range(sp)]

    qf, of, dof = _flat(q), _flat(o), _flat(do)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[..., None], lse.shape + (fa.LANES,))
    delta_b = jnp.broadcast_to(delta[..., None], delta.shape + (fa.LANES,))

    def fold_kv(g):
        """Sum repeated-head grads back onto the Hkv KV heads."""
        if rep == 1:
            return g.reshape(b, hkv, c, d)
        return g.reshape(b, hkv, rep, c, d).sum(axis=2)

    dq = jnp.zeros((b * h, c, d), jnp.float32)
    dk_cur = jnp.zeros((b, hkv, c, d), jnp.float32)
    dv_cur = jnp.zeros((b, hkv, c, d), jnp.float32)
    k_cur, v_cur = k, v

    for step in range(sp):
        kw = dict(sm_scale=sm_scale, causal=causal and step == 0,
                  block_q=block_q, block_k=block_k, kv_len=c,
                  interpret=interpret)
        kf, vf = _rep_flat(k_cur, rep), _rep_flat(v_cur, rep)
        dq_j = fa._bwd_dq_call(qf, kf, vf, dof, lse_b, delta_b, **kw)
        dk_j, dv_j = fa._bwd_dkv_call(qf, kf, vf, dof, lse_b, delta_b, **kw)
        dk_j = fold_kv(dk_j.astype(jnp.float32))
        dv_j = fold_kv(dv_j.astype(jnp.float32))
        if causal and step > 0:
            # select, don't multiply: future-chunk kernels evaluate
            # exp(s - lse) with an lse that doesn't bound s, so dq_j can be
            # inf — 0*inf would poison the accumulator with NaN
            attend = idx >= step
            dq = jnp.where(attend, dq + dq_j.astype(jnp.float32), dq)
            dk_cur = jnp.where(attend, dk_cur + dk_j, dk_cur)
            dv_cur = jnp.where(attend, dv_cur + dv_j, dv_cur)
        else:
            dq = dq + dq_j.astype(jnp.float32)
            dk_cur = dk_cur + dk_j
            dv_cur = dv_cur + dv_j
        # rotate the visiting KV chunk and its grad accumulators together;
        # after sp rotations the accumulators are home at the chunk's owner
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
        if step < sp - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    return (dq.astype(q.dtype).reshape(b, h, c, d), dk_cur.astype(k.dtype),
            dv_cur.astype(v.dtype))


# ---------------------------------------------------------------------------
# zigzag ring attention: balanced causal work (see module docstring)
# ---------------------------------------------------------------------------
def _merge_state(state, o_j, lse_j):
    """Online-softmax merge of a partial attention output into (m, s, acc)."""
    m, s, acc = state
    lse_j = lse_j[..., None]
    m_new = jnp.maximum(m, lse_j)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(lse_j - m_new)
    return (m_new, s * alpha + beta,
            acc * alpha + beta * o_j.astype(jnp.float32))


def _merge_if(pred, state, o_j, lse_j):
    new = _merge_state(state, o_j, lse_j)
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(pred, n, o), new, state)


def _zz_fwd_impl(q, k, v, axis_name, sp, sm_scale, block_q, block_k,
                 interpret):
    """Zigzag-causal ring forward.  Device ``i`` holds sequence HALF-BLOCKS
    (i, 2*sp-1-i) concatenated: q/k/v are [B, H(kv), c, D] with c = 2 half
    blocks.  Every kernel issued is fully valid:

      step 0 (self):      q1 x k1 (diag), q2 x k1 (full), q2 x k2 (diag)
      step j, src r < i:  q1 x k1 (full), q2 x k1 (full)
      step j, src r > i:  q2 x k1 (full), q2 x k2 (full)

    The r<i / r>i cases differ only in which halves feed two equal-shape
    non-causal kernels, so inputs route through selects on ``idx >= step``
    and the program is SPMD-uniform.
    """
    b, h, c, d = q.shape
    rep = h // k.shape[1]
    bh = b * h
    ch = c // 2
    qf = _flat(q)
    q1, q2 = qf[:, :ch], qf[:, ch:]
    idx = jax.lax.axis_index(axis_name)
    perm = [(r, (r + 1) % sp) for r in range(sp)]

    def halves(kv_cur):
        kvf = _rep_flat(kv_cur, rep)
        return kvf[:, :ch], kvf[:, ch:]

    zero = lambda: (jnp.full((bh, ch, 1), NEG_INF, jnp.float32),
                    jnp.zeros((bh, ch, 1), jnp.float32),
                    jnp.zeros((bh, ch, d), jnp.float32))
    st1, st2 = zero(), zero()
    k_cur, v_cur = k, v

    kw = dict(block_q=block_q, block_k=block_k, interpret=interpret)
    # ---- step 0: self-attention of the local half pair
    k1, k2 = halves(k_cur)
    v1, v2 = halves(v_cur)
    o11, l11 = fa._fwd(q1, k1, v1, sm_scale, True, true_kv_len=ch, **kw)
    o21, l21 = fa._fwd(q2, k1, v1, sm_scale, False, true_kv_len=ch, **kw)
    o22, l22 = fa._fwd(q2, k2, v2, sm_scale, True, true_kv_len=ch, **kw)
    st1 = _merge_state(st1, o11, l11)
    st2 = _merge_state(st2, o21, l21)
    st2 = _merge_state(st2, o22, l22)

    for step in range(1, sp):
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        k1, k2 = halves(k_cur)
        v1, v2 = halves(v_cur)
        past = idx >= step            # visiting source r = idx - step < idx
        qA = jnp.where(past, q1, q2)
        kB = jnp.where(past, k1, k2)
        vB = jnp.where(past, v1, v2)
        oA, lA = fa._fwd(qA, k1, v1, sm_scale, False, true_kv_len=ch, **kw)
        oB, lB = fa._fwd(q2, kB, vB, sm_scale, False, true_kv_len=ch, **kw)
        st1 = _merge_if(past, st1, oA, lA)
        st2 = _merge_if(jnp.logical_not(past), st2, oA, lA)
        st2 = _merge_state(st2, oB, lB)

    outs = []
    lses = []
    for m, s, acc in (st1, st2):
        s_safe = jnp.where(s == 0.0, 1.0, s)
        outs.append((acc / s_safe).astype(q.dtype))
        lses.append((m + jnp.log(s_safe))[..., 0])
    o = jnp.concatenate(outs, axis=1).reshape(b, h, c, d)
    lse = jnp.concatenate(lses, axis=1)                  # [bh, c]
    return o, lse


def _zz_bwd_impl(q, k, v, o, lse, do, axis_name, sp, sm_scale, block_q,
                 block_k, interpret):
    b, h, c, d = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    ch = c // 2
    idx = jax.lax.axis_index(axis_name)
    perm = [(r, (r + 1) % sp) for r in range(sp)]

    qf, of, dof = _flat(q), _flat(o), _flat(do)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[..., None], lse.shape + (fa.LANES,))
    delta_b = jnp.broadcast_to(delta[..., None], delta.shape + (fa.LANES,))
    q1, q2 = qf[:, :ch], qf[:, ch:]
    do1, do2 = dof[:, :ch], dof[:, ch:]
    l1, l2 = lse_b[:, :ch], lse_b[:, ch:]
    d1, d2 = delta_b[:, :ch], delta_b[:, ch:]

    def halves(kv_cur):
        kvf = _rep_flat(kv_cur, rep)
        return kvf[:, :ch], kvf[:, ch:]

    def fold(g):
        """[b*hkv*rep, ch, d] half grads -> [b, hkv, ch, d]."""
        if rep == 1:
            return g.reshape(b, hkv, ch, d).astype(jnp.float32)
        return g.reshape(b, hkv, rep, ch, d).sum(axis=2)

    def kernels(qx, dox, lx, dx, kx, vx, causal):
        kw = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=ch, interpret=interpret)
        dq_ = fa._bwd_dq_call(qx, kx, vx, dox, lx, dx, **kw)
        dk_, dv_ = fa._bwd_dkv_call(qx, kx, vx, dox, lx, dx, **kw)
        return dq_.astype(jnp.float32), fold(dk_), fold(dv_)

    dq1 = jnp.zeros((b * h, ch, d), jnp.float32)
    dq2 = jnp.zeros((b * h, ch, d), jnp.float32)
    dkv_z = lambda: jnp.zeros((b, hkv, ch, d), jnp.float32)
    k_cur, v_cur = k, v
    dk_cur = jnp.zeros((b, hkv, c, d), jnp.float32)
    dv_cur = jnp.zeros((b, hkv, c, d), jnp.float32)

    def add_halves(full, h1, h2):
        return full + jnp.concatenate([h1, h2], axis=2)

    # ---- step 0
    k1, k2 = halves(k_cur)
    v1, v2 = halves(v_cur)
    a_dq, a_dk, a_dv = kernels(q1, do1, l1, d1, k1, v1, True)
    b_dq, b_dk, b_dv = kernels(q2, do2, l2, d2, k1, v1, False)
    c_dq, c_dk, c_dv = kernels(q2, do2, l2, d2, k2, v2, True)
    dq1 += a_dq
    dq2 += b_dq + c_dq
    dk_cur = add_halves(dk_cur, a_dk + b_dk, c_dk)
    dv_cur = add_halves(dv_cur, a_dv + b_dv, c_dv)
    dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
    dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)

    for step in range(1, sp):
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        k1, k2 = halves(k_cur)
        v1, v2 = halves(v_cur)
        past = idx >= step
        qA = jnp.where(past, q1, q2)
        doA = jnp.where(past, do1, do2)
        lA = jnp.where(past, l1, l2)
        dA = jnp.where(past, d1, d2)
        kB = jnp.where(past, k1, k2)
        vB = jnp.where(past, v1, v2)
        a_dq, a_dk, a_dv = kernels(qA, doA, lA, dA, k1, v1, False)
        b_dq, b_dk, b_dv = kernels(q2, do2, l2, d2, kB, vB, False)
        # route (all kernel outputs are finite — every issued kernel is a
        # valid past-attending pair, so additive where-routing is safe)
        z = jnp.zeros_like(a_dq)
        dq1 += jnp.where(past, a_dq, z)
        dq2 += b_dq + jnp.where(past, z, a_dq)
        zk = dkv_z()
        dk_cur = add_halves(dk_cur, a_dk + jnp.where(past, b_dk, zk),
                            jnp.where(past, zk, b_dk))
        dv_cur = add_halves(dv_cur, a_dv + jnp.where(past, b_dv, zk),
                            jnp.where(past, zk, b_dv))
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)

    # after sp rotations (one per step) the accumulators are home
    dq = jnp.concatenate([dq1, dq2], axis=1)
    return (dq.astype(q.dtype).reshape(b, h, c, d), dk_cur.astype(k.dtype),
            dv_cur.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _zz_ring_attn(q, k, v, axis_name, sp, sm_scale, block_q, block_k,
                  interpret):
    o, _ = _zz_fwd_impl(q, k, v, axis_name, sp, sm_scale, block_q, block_k,
                        interpret)
    return o


def _zz_ring_attn_fwd(q, k, v, axis_name, sp, sm_scale, block_q, block_k,
                      interpret):
    o, lse = _zz_fwd_impl(q, k, v, axis_name, sp, sm_scale, block_q, block_k,
                          interpret)
    return o, (q, k, v, o, lse)


def _zz_ring_attn_bwd(axis_name, sp, sm_scale, block_q, block_k, interpret,
                      res, do):
    q, k, v, o, lse = res
    return _zz_bwd_impl(q, k, v, o, lse, do, axis_name, sp, sm_scale, block_q,
                        block_k, interpret)


_zz_ring_attn.defvjp(_zz_ring_attn_fwd, _zz_ring_attn_bwd)


def zigzag_order(s_len: int, sp: int):
    """Permutation placing half-block pair (i, 2*sp-1-i) on device i, and its
    inverse.  ``2*sp`` must divide ``s_len``."""
    import numpy as np

    c2 = s_len // (2 * sp)
    blocks = []
    for i in range(sp):
        blocks += [i, 2 * sp - 1 - i]
    zig = np.concatenate([np.arange(bl * c2, (bl + 1) * c2) for bl in blocks])
    inv = np.argsort(zig)
    return zig, inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _ring_attn(q, k, v, axis_name, sp, sm_scale, causal, block_q, block_k,
               interpret):
    o, _ = _ring_fwd_impl(q, k, v, axis_name, sp, sm_scale, causal, block_q,
                          block_k, interpret)
    return o


def _ring_attn_fwd(q, k, v, axis_name, sp, sm_scale, causal, block_q, block_k,
                   interpret):
    o, lse = _ring_fwd_impl(q, k, v, axis_name, sp, sm_scale, causal, block_q,
                            block_k, interpret)
    return o, (q, k, v, o, lse)


def _ring_attn_bwd(axis_name, sp, sm_scale, causal, block_q, block_k,
                   interpret, res, do):
    q, k, v, o, lse = res
    return _ring_bwd_impl(q, k, v, o, lse, do, axis_name, sp, sm_scale, causal,
                          block_q, block_k, interpret)


_ring_attn.defvjp(_ring_attn_fwd, _ring_attn_bwd)


# ---------------------------------------------------------------------------
# public ops: global [B, H, S, D] -> [B, H, S, D] over the mesh
# ---------------------------------------------------------------------------
def _resolve_mesh(mesh):
    if mesh is not None:
        return mesh
    from .. import comm

    return comm.get_mesh()


def sp_size() -> int:
    """Size of the active sequence-parallel axis (trace-time python int)."""
    from .. import comm

    return comm.get_topology().sequence_parallel_size


from ..utils.sharding import axis_size as _axis_size  # noqa: E402


def _qkvo_spec(mesh, q_shape, batch_axes, head_axis, sp_axis):
    """Shard batch over dp/ep and heads over tp only when sizes divide —
    otherwise keep those dims replicated (the seq dim must always divide sp)."""
    b_axes = batch_axes if q_shape[0] % _axis_size(mesh, batch_axes) == 0 \
        else None
    h_axes = head_axis if q_shape[1] % _axis_size(mesh, head_axis) == 0 \
        else None
    return P(b_axes, h_axes, sp_axis, None)


def _qkv_specs(mesh, q_shape, kv_shape, batch_axes, head_axis, sp_axis):
    """``(q_spec, kv_spec)`` for one attention call.  GQA with kv heads not
    divisible by tp: per-shard q heads would fall below the kv head count —
    keep both head dims replicated instead."""
    q_spec = _qkvo_spec(mesh, q_shape, batch_axes, head_axis, sp_axis)
    kv_spec = _qkvo_spec(mesh, kv_shape, batch_axes, head_axis, sp_axis)
    if q_spec[1] != kv_spec[1]:
        q_spec = P(q_spec[0], None, sp_axis, None)
        kv_spec = P(kv_spec[0], None, sp_axis, None)
    return q_spec, kv_spec


#: whole-chunk fallback cap: a [bq, bk] f32 score tile + scratch must fit VMEM
_MAX_RING_BLOCK = 512


def _ring_block(c: int, want: int) -> int:
    """TPU-friendly block size for a per-device chunk of length ``c``.

    The ring kernels require the block to tile the chunk exactly (they don't
    pad), and the TPU needs >=8 sublanes per block.  Pick the largest divisor
    of ``c`` that is a multiple of 8 and <= max(want, _MAX_RING_BLOCK cap);
    raise a clear trace-time error instead of letting an undersized or
    VMEM-busting block surface as an opaque Pallas compile failure on
    hardware (tests run in interpret mode and would never see it)."""
    want = max(want, 8)  # TPU needs >=8 sublanes per block
    if c % 8 == 0:
        for b in range(min(want, c), 7, -1):
            if c % b == 0 and b % 8 == 0:
                return b  # always found: 8 itself divides c
    if c <= _MAX_RING_BLOCK:
        return c  # odd chunk: one whole-chunk block (Pallas pads the tile)
    raise ValueError(
        f"ring attention: per-device chunk length {c} has no block size that "
        f"is a multiple of 8, and a whole-chunk block would exceed VMEM "
        f"(cap {_MAX_RING_BLOCK}); use a sequence length divisible by 8*sp")


def ring_attention(q, k, v, causal: bool = True,
                   sm_scale: Optional[float] = None, mesh=None,
                   sp_axis: str = SP_AXIS, batch_axes=DATA_AXES,
                   head_axis: str = TP_AXIS, block_q: int = 128,
                   block_k: int = 128, interpret: Optional[bool] = None,
                   zigzag="auto"):
    """Ring attention over the ``sp`` mesh axis.  q: [B, H, S, D] global.

    S is chunked over sp; KV chunks rotate via ppermute.  k, v may have fewer
    (GQA) heads — they are repeated to H first.  ``zigzag`` ("auto" | True |
    False): balanced-causal half-block pairing (module docstring) — auto uses
    it for causal attention whenever the per-device chunk splits into two
    TPU-tileable halves; non-causal attention has no imbalance to fix.
    """
    mesh = _resolve_mesh(mesh)
    sp = mesh.shape[sp_axis]
    h, hkv = q.shape[1], k.shape[1]
    assert h % hkv == 0, f"GQA needs num_heads {h} % kv_heads {hkv} == 0"
    if interpret is None:
        interpret = interpret_kernels()
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if sp == 1:
        return fa.flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)
    s_len = q.shape[2]
    assert s_len % sp == 0, f"seq len {s_len} must divide sp={sp}"
    c = s_len // sp
    use_zz = (causal and c % 2 == 0 and (c // 2) % 8 == 0) \
        if zigzag == "auto" else bool(zigzag and causal)
    if use_zz and c % 2:
        raise ValueError(f"zigzag ring attention needs an even per-device "
                         f"chunk, got {c}")

    q_spec, kv_spec = _qkv_specs(mesh, q.shape, k.shape, batch_axes,
                                 head_axis, sp_axis)

    if use_zz:
        bq = _ring_block(c // 2, block_q)
        bk = _ring_block(c // 2, block_k)
        # NOTE: the zig/inv gathers below re-permute the sp-sharded
        # sequence ACROSS devices on every call (~4 rotation-equivalents of
        # ICI traffic per attention + the backward's scatters).  The FLOP
        # balance win is ~2x of the attention compute, which dominates at
        # long S, but a model that keeps its token stream in zigzag layout
        # end-to-end (permute once at the embedding, fold positions/labels)
        # would pay this once per step instead of per layer — future work.
        zig, inv = zigzag_order(s_len, sp)

        def local(q, k, v):
            return _zz_ring_attn(q, k, v, sp_axis, sp, sm_scale, bq, bk,
                                 interpret)

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(q_spec, kv_spec, kv_spec),
                           out_specs=q_spec, check_vma=False)
        o = fn(q[:, :, zig], k[:, :, zig], v[:, :, zig])
        return o[:, :, inv]

    bq = _ring_block(c, block_q)
    bk = _ring_block(c, block_k)

    def local(q, k, v):
        return _ring_attn(q, k, v, sp_axis, sp, sm_scale, causal, bq, bk,
                          interpret)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                       out_specs=q_spec, check_vma=False)
    return fn(q, k, v)


def ulysses_attention(q, k, v, causal: bool = True,
                      sm_scale: Optional[float] = None, mesh=None,
                      sp_axis: str = SP_AXIS, batch_axes=DATA_AXES,
                      head_axis: str = TP_AXIS, block_q: int = 128,
                      block_k: int = 128, interpret: Optional[bool] = None):
    """DeepSpeed-Ulysses-style attention: all-to-all scatters heads / gathers
    sequence so each device runs full-sequence flash attention on H/sp heads.
    """
    mesh = _resolve_mesh(mesh)
    sp = mesh.shape[sp_axis]
    tp = mesh.shape[head_axis] if head_axis in mesh.shape else 1
    if interpret is None:
        interpret = interpret_kernels()
    if sp == 1:
        k, v = _repeat_kv(q, k, v)
        return fa.flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)
    h, hkv = q.shape[1], k.shape[1]
    assert h % tp == 0 and (h // tp) % sp == 0, (
        f"ulysses needs heads/tp divisible by sp: H={h}, tp={tp}, sp={sp}")
    # GQA: keep KV un-repeated through the all-to-alls when its per-shard head
    # count divides sp — chunk j of the q heads maps exactly onto chunk j of
    # the kv heads, and flash repeats internally after the exchange.  Only
    # fall back to an up-front repeat when the counts don't divide.
    q_heads_sharded = hkv % tp == 0  # shard q heads only if kv can match
    hkv_loc = hkv // tp if q_heads_sharded else hkv
    if hkv_loc % sp != 0:
        k, v = _repeat_kv(q, k, v)
        hkv = h
    head = head_axis if q_heads_sharded else None
    q_spec = P(batch_axes if q.shape[0] % _axis_size(mesh, batch_axes) == 0
               else None, head, sp_axis, None)
    kv_spec = P(q_spec[0], head, sp_axis, None)

    def local(q, k, v):
        # [b, h_loc, C, D] -> all-to-all -> [b, h_loc/sp, S, D]
        a2a = functools.partial(jax.lax.all_to_all, axis_name=sp_axis,
                                split_axis=1, concat_axis=2, tiled=True)
        o = fa.flash_attention(a2a(q), a2a(k), a2a(v), causal=causal,
                               sm_scale=sm_scale, block_q=block_q,
                               block_k=block_k, interpret=interpret)
        return jax.lax.all_to_all(o, sp_axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                       out_specs=q_spec, check_vma=False)
    return fn(q, k, v)


def mesh_flash_attention(q, k, v, causal: bool = True,
                         sm_scale: Optional[float] = None, mesh=None,
                         batch_axes=DATA_AXES, head_axis: str = TP_AXIS,
                         **kw):
    """``flash_attention`` placed on the mesh by hand: batch over the data
    axes and heads over ``tp`` inside ``shard_map``, each chip running the
    kernel on its own slice.

    A Mosaic custom call has no partitioning rule.  Left bare inside a
    GSPMD-partitioned step (the interpreted kernel is plain XLA and
    partitions, which is why the CPU-sim mesh never showed it), XLA gathers
    q/k/v and every chip computes attention for the whole global batch.
    Attention is independent per (sequence, head), so no collective appears
    here.  Runs the kernel directly on one device, when no mesh axis
    divides the batch/head dims, or when the caller is already inside a
    ``shard_map`` (its per-shard arrays are local already)."""
    mesh = _resolve_mesh(mesh)
    if mesh.size > 1 and not jax.sharding.get_abstract_mesh().manual_axes:
        q_spec, kv_spec = _qkv_specs(mesh, q.shape, k.shape, batch_axes,
                                     head_axis, None)
        if _axis_size(mesh, q_spec[0]) * _axis_size(mesh, q_spec[1]) > 1:
            def local(q, k, v):
                return fa.flash_attention(q, k, v, causal=causal,
                                          sm_scale=sm_scale, **kw)

            return jax.shard_map(local, mesh=mesh,
                                 in_specs=(q_spec, kv_spec, kv_spec),
                                 out_specs=q_spec, check_vma=False)(q, k, v)
    return fa.flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                              **kw)


def sequence_parallel_attention(q, k, v, causal: bool = True,
                                sm_scale: Optional[float] = None,
                                impl: str = "auto", mesh=None,
                                sp_axis: str = SP_AXIS, batch_axes=DATA_AXES,
                                head_axis: str = TP_AXIS,
                                interpret: Optional[bool] = None, **kw):
    """Dispatch to ulysses/ring based on config and divisibility.

    ``impl``: "auto" | "ulysses" | "ring".  Auto prefers Ulysses (2 all-to-alls
    beat sp ppermute rounds) when heads/tp divide by sp, else ring (which has
    no head-count constraint and O(S/sp) memory for arbitrarily long S).
    """
    mesh = _resolve_mesh(mesh)
    sp = mesh.shape[sp_axis]
    if sp == 1 or q.shape[2] % sp != 0:
        # no sp axis, or sequence doesn't chunk evenly: replicated-seq
        # flash attention, batch/heads still placed by hand
        return mesh_flash_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, mesh=mesh,
            batch_axes=batch_axes, head_axis=head_axis, interpret=interpret,
            **kw)
    tp = mesh.shape[head_axis] if head_axis in mesh.shape else 1
    h = q.shape[1]
    ulysses_ok = h % tp == 0 and (h // tp) % sp == 0
    if impl == "ulysses" or (impl == "auto" and ulysses_ok):
        return ulysses_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                 mesh=mesh, sp_axis=sp_axis,
                                 batch_axes=batch_axes, head_axis=head_axis,
                                 interpret=interpret, **kw)
    return ring_attention(q, k, v, causal=causal, sm_scale=sm_scale, mesh=mesh,
                          sp_axis=sp_axis, batch_axes=batch_axes,
                          head_axis=head_axis, interpret=interpret, **kw)
