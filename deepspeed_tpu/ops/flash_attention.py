"""Fused (flash) attention in Pallas — TPU replacement for the reference's CUDA
attention kernels (training: ``csrc/transformer/softmax_kernels.cu`` +
``strided_batch_gemm`` composition, ``ds_transformer_cuda.cpp:78-121``).

Flash-2 style: online-softmax forward that never materialises the [S, S] score
matrix (the thing that OOMed GPT-2 125M on a 16GB v5e), and a recomputing
backward driven by saved row log-sum-exps.  Causal blocks strictly above the
diagonal are skipped with ``pl.when`` — ~2x fewer MXU flops for causal LM.

Layout: q, k, v are [B, H, S, D]; the grid walks (B*H, Sq/bq, Sk/bk) with the KV
dimension innermost ("arbitrary") so the accumulator scratch carries across KV
blocks.  f32 accumulation regardless of input dtype (bf16 in, bf16 out).

Interpret mode is the default only where the CPU platform was requested
(``utils/platform.py``); on a TPU every kernel here is compiled by Mosaic.
"""

from __future__ import annotations

import collections
import functools
import math
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.platform import interpret_kernels


DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128


# ---------------------------------------------------------------------------
# a sliding window (``window`` > 0, causal): the query at ``p`` sees the keys
# ``max(0, p - window + 1) .. p``.  Every ``window`` branch below is taken at
# trace time, so ``window = 0`` is the program it always was.
# ---------------------------------------------------------------------------
def _in_band(run, qi, ki, block_q: int, block_k: int, window: int):
    """``run`` and: block ``(qi, ki)`` holds a key some query of it still
    sees — its last column lies inside the first row's window."""
    if not window:
        return run
    return jnp.logical_and(
        run, ki * block_k + block_k - 1 > qi * block_q - window)


def _band_mask(mask, row, col, window: int):
    if not window:
        return mask
    return jnp.logical_and(mask, row - col < window)


def _band_k(i, j, block_q: int, block_k: int, window: int):
    """Key block ``j`` of query block ``i``, held to the blocks the band
    touches: a step outside it names a block it already has, and fetches
    nothing."""
    if not window:
        return j
    lo = jnp.maximum(i * block_q - window + 1, 0) // block_k
    return jnp.clip(j, lo, (i * block_q + block_q - 1) // block_k)


def _band_q(j, i, block_q: int, block_k: int, window: int, nq: int):
    """Query block ``i`` of key block ``j``, likewise: the band of a key
    block ends ``window`` queries after its last key."""
    if not window:
        return i
    hi = jnp.minimum((j * block_k + block_k + window - 2) // block_q, nq - 1)
    return jnp.clip(i, (j * block_k) // block_q, hi)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, sm_scale: float, causal: bool, block_q: int,
                block_k: int, kv_len: int, num_k_blocks: int,
                has_layout: bool = False, window: int = 0):
    if has_layout:
        (q_ref, k_ref, v_ref, layout_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: block (qi, ki) contributes iff some col <= some row;
    # a sparsity layout gates blocks on top (ops/sparse_attention)
    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True
    run = _in_band(run, qi, ki, block_q, block_k, window)
    if has_layout:
        # per-head layout slice in SMEM (a (1,1,1) VMEM block would violate
        # Mosaic's (8,128) tiling floor — surfaced on hardware only; a
        # whole-array SMEM operand would hit scalar-memory limits at
        # H x (S/block)^2 scale)
        run = jnp.logical_and(run, layout_ref[0, qi, ki] != 0)

    @pl.when(run)
    def _compute():
        # keep native (bf16) dtype into the MXU; f32 comes out via
        # preferred_element_type — f32 MXU inputs run at 1/8 rate on v5e
        q = q_ref[0, ...]  # [bq, d]
        k = k_ref[0, ...]  # [bk, d]
        v = v_ref[0, ...]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

        col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        mask = col < kv_len  # padded keys never attend
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = _band_mask(jnp.logical_and(mask, row >= col), row, col,
                              window)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)

        m_prev = m_scr[...][:, :1]                      # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                 # [bq, 1]
        p = jnp.exp(s - m_new)                          # [bq, bk]
        l_prev = l_scr[...][:, :1]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = m_scr[...][:, :1] + jnp.log(l_safe)
        lse_ref[0, ...] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd(q, k, v, sm_scale: float, causal: bool, block_q: int, block_k: int,
         interpret: bool, true_kv_len: int, head_rep: int = 1, layout=None,
         window: int = 0):
    """``head_rep``: GQA ratio — q has ``bh`` leading entries, k/v have
    ``bh // head_rep``; the KV index map divides so repeated heads read the
    same KV block in place (no ``jnp.repeat`` materialization).
    ``layout``: optional f32 [H, nq, nk] block-sparsity gate."""
    bh, q_len, d = q.shape
    kv_len = true_kv_len  # mask out padded keys beyond the real length
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_len, block_k)
    rep = head_rep

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, kv_len=kv_len,
                               num_k_blocks=nk, has_layout=layout is not None,
                               window=window)
    out_shape = [
        jax.ShapeDtypeStruct((bh, q_len, d), q.dtype),          # o
        jax.ShapeDtypeStruct((bh, q_len, LANES), jnp.float32),  # lse (lane-bcast)
    ]
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // rep, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // rep, j, 0)),
    ]
    inputs = [q, k, v]
    if layout is not None:
        h, lq, lk = layout.shape
        in_specs.append(pl.BlockSpec((1, lq, lk), lambda b, i, j: (b % h, 0, 0),
                                     memory_space=pltpu.SMEM))
        inputs.append(layout.astype(jnp.int32))
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
        ],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*inputs)
    return o, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward: dq kernel (grid kv-innermost) and dkv kernel (grid q-innermost)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, sm_scale: float, causal: bool, block_q: int,
                   block_k: int, kv_len: int, num_k_blocks: int,
                   has_layout: bool = False, window: int = 0):
    if has_layout:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, layout_ref,
         dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
         dq_scr) = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True
    run = _in_band(run, qi, ki, block_q, block_k, window)
    if has_layout:
        # per-head layout slice in SMEM (a (1,1,1) VMEM block would violate
        # Mosaic's (8,128) tiling floor — surfaced on hardware only; a
        # whole-array SMEM operand would hit scalar-memory limits at
        # H x (S/block)^2 scale)
        run = jnp.logical_and(run, layout_ref[0, qi, ki] != 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, ...]
        k = k_ref[0, ...]
        v = v_ref[0, ...]
        do = do_ref[0, ...]
        lse = lse_ref[0, ...][:, :1]      # [bq, 1]
        delta = delta_ref[0, ...][:, :1]  # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = _band_mask(jnp.logical_and(mask, row >= col), row, col,
                              window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, ...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, sm_scale: float, causal: bool,
                    block_q: int, block_k: int, kv_len: int, num_q_blocks: int,
                    rep: int = 1, has_layout: bool = False, window: int = 0):
    """Inner grid dim 2 runs over (head_rep, q_blocks) flattened: for GQA the
    dk/dv of one KV head accumulates contributions from all ``rep`` query
    heads without materializing repeated K/V."""
    if has_layout:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, layout_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_scr, dv_scr) = refs
    ki = pl.program_id(1)
    inner = pl.program_id(2)
    qi = inner % num_q_blocks

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True
    run = _in_band(run, qi, ki, block_q, block_k, window)
    if has_layout:
        # per-head layout slice in SMEM (a (1,1,1) VMEM block would violate
        # Mosaic's (8,128) tiling floor — surfaced on hardware only; a
        # whole-array SMEM operand would hit scalar-memory limits at
        # H x (S/block)^2 scale)
        run = jnp.logical_and(run, layout_ref[0, qi, ki] != 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0, ...]
        k = k_ref[0, ...]
        v = v_ref[0, ...]
        do = do_ref[0, ...]
        lse = lse_ref[0, ...][:, :1]
        delta = delta_ref[0, ...][:, :1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = _band_mask(jnp.logical_and(mask, row >= col), row, col,
                              window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)                 # [bq, bk]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale                           # [bq, bk]
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(inner == rep * num_q_blocks - 1)
    def _finalize():
        dk_ref[0, ...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_call(q, k, v, do, lse_b, delta_b, *, sm_scale, causal, block_q,
                 block_k, kv_len, interpret, head_rep: int = 1, layout=None,
                 window: int = 0):
    """dq for one (q-chunk, kv-chunk) pair given *global* lse/delta.

    Exposed separately so ring attention (parallel/sequence.py) can reuse the
    kernel per ring step with the globally-merged log-sum-exp.
    """
    bh, q_len, d = q.shape
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_len, block_k)
    rep = head_rep
    dq_kernel = functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                                  causal=causal, block_q=block_q,
                                  block_k=block_k, kv_len=kv_len,
                                  num_k_blocks=nk,
                                  has_layout=layout is not None,
                                  window=window)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // rep, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // rep, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
    ]
    inputs = [q, k, v, do, lse_b, delta_b]
    if layout is not None:
        h, lq, lk = layout.shape
        in_specs.append(pl.BlockSpec((1, lq, lk), lambda b, i, j: (b % h, 0, 0),
                                     memory_space=pltpu.SMEM))
        inputs.append(layout.astype(jnp.int32))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*inputs)
    return dq


def _bwd_dkv_call(q, k, v, do, lse_b, delta_b, *, sm_scale, causal, block_q,
                  block_k, kv_len, interpret, head_rep: int = 1, layout=None,
                  window: int = 0):
    """dk, dv for one (q-chunk, kv-chunk) pair given *global* lse/delta.

    For GQA (``head_rep > 1``) q/do/lse/delta have ``rep`` times more heads
    than k/v; the inner grid walks (rep, q_blocks) and accumulates into the
    single KV head's dk/dv."""
    bh_kv = k.shape[0]
    q_len, d = q.shape[1], q.shape[2]
    rep = head_rep
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_len, block_k)
    dkv_kernel = functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                                   causal=causal, block_q=block_q,
                                   block_k=block_k, kv_len=kv_len,
                                   num_q_blocks=nq, rep=rep,
                                   has_layout=layout is not None,
                                   window=window)
    q_map = lambda b, j, i: (b * rep + i // nq, i % nq, 0)
    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_q, LANES), q_map),
        pl.BlockSpec((1, block_q, LANES), q_map),
    ]
    if layout is not None:
        # the layout is per Q-head: follow the q index map through the
        # (rep, q_blocks) inner grid so GQA composes with sparsity
        h, lq, lk = layout.shape
        in_specs.append(pl.BlockSpec(
            (1, lq, lk),
            lambda b, j, i: ((b * rep + i // nq) % h, 0, 0),
            memory_space=pltpu.SMEM))
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh_kv, nk, rep * nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*([q, k, v, do, lse_b, delta_b] +
        ([layout.astype(jnp.int32)] if layout is not None else [])))
    return dk, dv


def _bwd(sm_scale, causal, block_q, block_k, interpret, true_kv_len, head_rep,
         residuals, g, window: int = 0):
    q, k, v, o, lse = residuals
    do = g
    kv_len = true_kv_len

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[..., None], lse.shape + (LANES,))
    delta_b = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))

    kw = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
              block_k=block_k, kv_len=kv_len, interpret=interpret,
              head_rep=head_rep, window=window)
    dq = _bwd_dq_call(q, k, v, do, lse_b, delta_b, **kw)
    dk, dv = _bwd_dkv_call(q, k, v, do, lse_b, delta_b, **kw)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# v2 kernels: full-row forward + ONE fused backward (dq+dk+dv in one pass)
#
# Profiling showed the v1 kernels are VPU/overhead-bound, not MXU-bound: the
# online-softmax rescale machinery, the separate dq/dkv backward kernels that
# EACH recompute the score matrix (9 S^2-matmuls where 6 suffice), and the
# [bh, S, LANES]-broadcast lse/delta operands (200MB of f32 HBM traffic per
# layer at bench shapes) dominate.  When the whole K/V sequence fits VMEM
# (S <= _V2_MAX_KV), a single-row-block design removes all of it:
#  - forward: one [bq, S] score pass, plain softmax (no cross-block rescale),
#    exp2 with the softmax scale folded into q, OUTPUT IS O ONLY — the
#    backward recomputes row max/sum in-kernel, so no lse is ever written.
#  - backward: one kernel computes dq (written once per q block) and
#    accumulates dk/dv in VMEM scratch over the sequential q-block grid
#    dimension; delta = rowsum(do*o) is computed in-kernel and 1/l is folded
#    into do, so no [bq, S] divide and no broadcast operands exist.
# Long sequences (kv_pad > _V2_MAX_KV) take the v3 kernels below; the v1
# kernels remain for the sparse-layout path, the ring-attention building
# blocks (parallel/sequence.py), and the DS_FLASH_V2/V3=0 kill switches.
# ---------------------------------------------------------------------------
_LOG2E = math.log2(math.e)
_LN2 = math.log(2.0)
# v2's fused backward at kv_pad=2048 measured 348KB OVER the 16MB
# scoped-vmem limit on v5e at EVERY block_q (round-4 compile failures; the
# round-3 on-chip fuzz only reached S=512 so the former 2048 limit was
# never hardware-validated).  kv_pad <= 1024 compiles and is the measured
# winner at bench shapes; v3 takes over beyond.
_V2_MAX_KV = 1024
# defensive cap on the [block_q, kv_pad] f32 score intermediates
_V2_MAX_SCORE_ELEMS = 2 ** 20


def _v2_eligible(kv_pad: int, d: int) -> bool:
    import os

    if os.environ.get("DS_FLASH_V2", "1") == "0":  # A/B kill switch
        return False
    # experiment override: a larger scoped-vmem budget (set via
    # --xla_tpu_scoped_vmem_limit_kib) can admit the fused v2 backward past
    # 1024 — DS_V2_MAX_KV raises the gate for A/B runs
    max_kv = int(os.environ.get("DS_V2_MAX_KV", _V2_MAX_KV))
    return kv_pad <= max_kv and kv_pad % 8 == 0 and d <= 256


def _v3_eligible(kv_pad: int, d: int) -> bool:
    """v3 kernels (chunked-grid, compact row stats): the long-sequence path.

    Measured on v5e (round-4 builder runs): ~8-10% faster than the v1
    two-kernel path at S in [2048, 8192] — fwd folds the softmax scale and
    log2(e) into q and uses exp2; bwd reads lse/delta as compact 8-sublane
    operands instead of v1's [bh, S, 128]-broadcast f32 arrays (~200MB of
    HBM traffic per layer at bench shapes).  A fused one-kernel backward and
    a resident-KV chunk-loop variant were both probed and lost (fused: VMEM
    cliff at S=8192 + slower at 4096).
    """
    import os

    if os.environ.get("DS_FLASH_V3", "1") == "0":  # A/B kill switch
        return False
    min_kv = int(os.environ.get("DS_FLASH_V3_MIN_KV", _V2_MAX_KV + 1))
    return kv_pad >= min_kv and kv_pad % 8 == 0 and d <= 256


# ---------------------------------------------------------------------------
# block sizes: ``_resolve_blocks`` is the only place one is decided.
#
# A grid step of these kernels costs ~0.4 us on a v5e whatever it computes.
# At 128 x 128 and hd 64 that was twenty times the step's MXU work and 62 % of
# OPT's S=2048 training step (PERF.md section 6, PR 35), so a caller that
# names no blocks gets the largest the backward's scoped VMEM and the
# lengths' padding allow.
# ---------------------------------------------------------------------------
#: Mosaic's scoped-VMEM limit for one kernel on a v5e, and the part of it the
#: chosen blocks may plan for: ``_bwd_vmem_bytes`` reads within a tenth of
#: what the compiler's own refusals name, over and under
_VMEM_LIMIT = 16 * 2 ** 20
_VMEM_BUDGET = _VMEM_LIMIT * 3 // 4
#: candidate blocks, largest first.  2048 rows on a side are refused by
#: Mosaic beside 1024 on the other at S = 2048, and were slower on the chip
#: where they compile (2048 x 512, 512 x 2048, 2048 x 1024 at S = 4096)
_BLOCKS = (1024, 512, 256, 128)

#: the Mosaic kernels of each generation, under the names a trace shows
KERNELS = {"v1": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
           "v2": ("flash_fwd_resident", "flash_bwd_fused"),
           "v3": ("flash_fwd_chunked", "flash_bwd_dq_chunked",
                  "flash_bwd_dkv_chunked")}
#: one resolution: the operands' lengths and head width, the generation they
#: dispatch to, the blocks, whether the rule chose them or the caller gave
#: them, and the sliding window the call was made with (0: none)
Choice = collections.namedtuple(
    "Choice", "q_len kv_len d generation block_q block_k how window",
    defaults=(0,))
_CHOICES: Dict[Choice, int] = {}
_CHOICES_LOCK = threading.Lock()


def choices(since: Optional[Dict[Choice, int]] = None) -> Dict[Choice, int]:
    """Every distinct block resolution traced in this process -> the number
    of ``flash_attention`` calls traced with it; given ``since``, an earlier
    return value, only what was traced after it.  Resolution happens at trace
    time, once a program: a record of which kernels at which blocks a
    program runs (``DeepSpeedEngine`` logs its step's), not a rate."""
    with _CHOICES_LOCK:
        now = dict(_CHOICES)
    if since is None:
        return now
    return {c: n - since.get(c, 0) for c, n in now.items()
            if n > since.get(c, 0)}


def _generation(kv_pad: int, d: int) -> str:
    if _v2_eligible(kv_pad, d):
        return "v2"
    return "v3" if _v3_eligible(kv_pad, d) else "v1"


def _bwd_vmem_bytes(block_q: int, block_k: int, d: int, itemsize: int) -> int:
    """Scoped VMEM of the hungriest kernel, the backward that accumulates dk
    and dv (``flash_bwd_dkv_chunked``; ``flash_bwd_fused`` with ``block_k`` the
    whole resident length): the live ``[block_q, block_k]`` float32 scores and
    their copy in the operands' dtype for the MXU, the double-buffered
    operand and result blocks (up to four a side), two float32 accumulators.
    ``d`` occupies whole 128-lane rows."""
    row = -(-d // LANES) * LANES
    tile = block_q * block_k * (4 + itemsize)
    q_side = block_q * 4 * 2 * row * itemsize
    k_side = block_k * (4 * 2 * row * itemsize + 2 * row * 4)
    return tile + q_side + k_side


def _len_block(length: int) -> int:
    """The largest candidate that pads ``length`` by under an eighth (1024
    would turn 1,100 rows into 2,048); a length within the smallest is one
    block of its own."""
    if length <= _BLOCKS[-1]:
        return max(length, 1)
    for block in _BLOCKS:
        if (-length) % block * 8 <= length:
            return block
    return _BLOCKS[-1]


def _resolve_blocks(q_len: int, kv_len: int, d: int, itemsize: int,
                    block_q: Optional[int], block_k: Optional[int]):
    """-> (Choice, pad_q, pad_k).  A block the caller gave is honoured as it
    always was (clipped to its length; v2 caps ``block_q`` so the resident
    scores stay under ``_V2_MAX_SCORE_ELEMS``).  One left ``None`` is chosen
    from what the operands show — lengths, head width, element size:
    ``_len_block`` of its length, then halved (never under 128) until
    ``_bwd_vmem_bytes`` fits ``_VMEM_BUDGET``.  v1 has no cell and no chip
    reading: it keeps the 128 it always ran at."""
    bq = _len_block(q_len) if block_q is None else min(block_q, max(q_len, 1))
    bk = _len_block(kv_len) if block_k is None else min(block_k,
                                                        max(kv_len, 1))
    if _generation(kv_len + (-kv_len) % bk, d) == "v1":
        bq = min(bq, _BLOCKS[-1]) if block_q is None else bq
        bk = min(bk, _BLOCKS[-1]) if block_k is None else bk
    pad_q, pad_k = (-q_len) % bq, (-kv_len) % bk
    kv_pad = kv_len + pad_k
    generation = _generation(kv_pad, d)

    def fits(bq, bk):
        return _bwd_vmem_bytes(bq, bk, d, itemsize) <= _VMEM_BUDGET

    def halvable(block, given):
        return given is None and block > _BLOCKS[-1]

    if generation == "v2":  # K and V resident: block_k only set the padding
        while halvable(bq, block_q) and not fits(bq, kv_pad):
            bq //= 2
        bq = max(8, min(bq, _V2_MAX_SCORE_ELEMS // kv_pad))
    elif generation == "v3":
        # the larger side gives way, the query side on a tie: on the chip
        # 512 x 1024 beat 1024 x 512 at every shape (PERF.md section 6)
        while not fits(bq, bk):
            if halvable(bq, block_q) and (bq >= bk
                                          or not halvable(bk, block_k)):
                bq //= 2
            elif halvable(bk, block_k):
                bk //= 2
            else:
                break
    how = "chosen" if block_q is None or block_k is None else "given"
    return (Choice(q_len, kv_len, d, generation, bq, bk, how), pad_q, pad_k)


def _v2_compiler_params(dimension_semantics):
    """CompilerParams for the v2 kernels; ``DS_V2_VMEM_MB`` raises the
    per-kernel scoped-vmem budget (the fused v2 backward at kv_pad=2048
    needs ~16.4MB against the 16MB default — see _V2_MAX_KV note)."""
    import os

    vmem_mb = os.environ.get("DS_V2_VMEM_MB")
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=(int(float(vmem_mb) * 2**20) if vmem_mb else None))


def _fwd_v2_kernel(q_ref, k_ref, v_ref, o_ref, *, scale2: float, causal: bool,
                   block_q: int, kv_pad: int, kv_len: int, window: int = 0):
    qi = pl.program_id(1)
    # fold softmax scale AND log2(e) into q (one [bq, d] pass instead of a
    # [bq, S] one); exp2 is the native transcendental
    q = q_ref[0, ...]
    qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
    k = k_ref[0, ...]
    v = v_ref[0, ...]
    s2 = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [bq, S]
    col = jax.lax.broadcasted_iota(jnp.int32, (block_q, kv_pad), 1)
    if causal:
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, kv_pad), 0)
        mask = _band_mask(col <= row, row, col, window)
        if kv_len != kv_pad:
            mask = jnp.logical_and(mask, col < kv_len)
        s2 = jnp.where(mask, s2, DEFAULT_MASK_VALUE)
    elif kv_len != kv_pad:
        s2 = jnp.where(col < kv_len, s2, DEFAULT_MASK_VALUE)
    m = jnp.max(s2, axis=1, keepdims=True)          # [bq, 1]
    p = jnp.exp2(s2 - m)                            # masked lanes -> 0
    l = jnp.sum(p, axis=1, keepdims=True)           # >= 1 for any valid row
    acc = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0, ...] = (acc / l).astype(o_ref.dtype)


def _fwd_v2(q, k, v, sm_scale, causal, block_q, interpret, true_kv_len,
            head_rep, window: int = 0):
    bh, q_len, d = q.shape
    kv_pad = k.shape[1]
    nq = pl.cdiv(q_len, block_q)
    kernel = functools.partial(
        _fwd_v2_kernel, scale2=sm_scale * _LOG2E, causal=causal,
        block_q=block_q, kv_pad=kv_pad, kv_len=true_kv_len,
        window=window)
    rep = head_rep
    return pl.pallas_call(
        kernel,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, kv_pad, d), lambda b, i: (b // rep, 0, 0)),
            pl.BlockSpec((1, kv_pad, d), lambda b, i: (b // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, q_len, d), q.dtype),
        compiler_params=_v2_compiler_params(("parallel", "parallel")),
        interpret=interpret,
        name="flash_fwd_resident",
    )(q, k, v)


def _bwd_v2_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, dq_ref, dk_ref, dv_ref,
                   dk_scr, dv_scr, *, scale2: float, sm_scale: float,
                   causal: bool, block_q: int, kv_pad: int, kv_len: int,
                   num_q_blocks: int, rep: int, window: int = 0):
    inner = pl.program_id(1)
    qi = inner % num_q_blocks

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, ...]
    qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
    k = k_ref[0, ...]
    v = v_ref[0, ...]
    o = o_ref[0, ...]
    do = do_ref[0, ...]

    s2 = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [bq, S]
    col = jax.lax.broadcasted_iota(jnp.int32, (block_q, kv_pad), 1)
    if causal:
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, kv_pad), 0)
        mask = _band_mask(col <= row, row, col, window)
        if kv_len != kv_pad:
            mask = jnp.logical_and(mask, col < kv_len)
        s2 = jnp.where(mask, s2, DEFAULT_MASK_VALUE)
    elif kv_len != kv_pad:
        s2 = jnp.where(col < kv_len, s2, DEFAULT_MASK_VALUE)
    m = jnp.max(s2, axis=1, keepdims=True)
    p0 = jnp.exp2(s2 - m)                           # l * softmax(s)
    l = jnp.sum(p0, axis=1, keepdims=True)
    linv = 1.0 / l                                  # [bq, 1]

    do32 = do.astype(jnp.float32)
    delta_s = jnp.sum(do32 * o.astype(jnp.float32), axis=1,
                      keepdims=True) * linv         # delta / l, [bq, 1]
    do_s = (do32 * linv).astype(do.dtype)           # do / l (folded softmax div)
    # dp/l = (do/l) @ v^T ; ds = softmax*(dp-delta) = p0*(dp - delta)/l
    dp_s = jax.lax.dot_general(do_s, v, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    ds = p0 * (dp_s - delta_s)
    ds_b = ds.astype(q.dtype)
    # dv += softmax^T @ do = p0^T @ (do/l)
    dv_scr[...] += jax.lax.dot_general(
        p0.astype(do.dtype), do_s, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # dk_true = ds^T @ (sm_scale*q) = (ds^T @ qs) * ln2   (qs = q*scale*log2e)
    dk_scr[...] += jax.lax.dot_general(
        ds_b, qs, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # dq = sm_scale * (ds @ k)
    dq = jax.lax.dot_general(ds_b, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dq_ref[0, ...] = (dq * sm_scale).astype(dq_ref.dtype)

    @pl.when(inner == rep * num_q_blocks - 1)
    def _finalize():
        dk_ref[0, ...] = (dk_scr[...] * _LN2).astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_v2(q, k, v, o, do, sm_scale, causal, block_q, interpret, true_kv_len,
            head_rep, window: int = 0):
    bh, q_len, d = q.shape
    bh_kv, kv_pad, _ = k.shape
    nq = pl.cdiv(q_len, block_q)
    rep = head_rep
    kernel = functools.partial(
        _bwd_v2_kernel, scale2=sm_scale * _LOG2E, sm_scale=sm_scale,
        causal=causal, block_q=block_q, kv_pad=kv_pad, kv_len=true_kv_len,
        num_q_blocks=nq, rep=rep, window=window)
    q_map = lambda b, i: (b * rep + i // nq, i % nq, 0)
    kv_map = lambda b, i: (b, 0, 0)
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(bh_kv, rep * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),       # q
            pl.BlockSpec((1, kv_pad, d), kv_map),       # k
            pl.BlockSpec((1, kv_pad, d), kv_map),       # v
            pl.BlockSpec((1, block_q, d), q_map),       # o
            pl.BlockSpec((1, block_q, d), q_map),       # do
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, kv_pad, d), kv_map),
            pl.BlockSpec((1, kv_pad, d), kv_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((kv_pad, d), jnp.float32),
            pltpu.VMEM((kv_pad, d), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=_v2_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_fused",
    )(q, k, v, o, do)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# v3 kernels: the long-sequence path (kv_pad > _V2_MAX_KV).
#
# Same chunked-grid structure as v1 (scratch-carried online softmax in the
# forward; separate dq / dkv backward kernels) but with the v2 tricks that
# carry over to chunking, each A/B-measured on-chip (round-4 builder runs):
#  - softmax scale AND log2(e) folded into q once per block ([bq, d] pass
#    instead of a [bq, bk] f32 multiply per chunk); exp2 everywhere.
#  - row stats live in COMPACT [bh, 1, S] f32 arrays.  The forward writes
#    lse2 = m2 + log2(l) via a sublane->lane relayout at finalize; the
#    backward reads it back with the reverse relayout (measured free) and
#    reconstructs true probabilities p = exp2(s2 - lse2) directly — no
#    division, no [bh, S, LANES] broadcast operands (v1 ships ~200MB/layer
#    of those at bench shapes).
#  - delta = rowsum(do * o) is one fused XLA pass, also [bh, 1, S].
# Rejected by measurement (do NOT revisit without new evidence):
#  - fused one-kernel backward (dk/dv full-row scratch + resident KV):
#    VMEM cliff at S=8192 (compile failure) and slower at 2048/4096.
#  - dq-partials-summed-by-XLA fused variant: partial-write traffic costs
#    more than the two matmuls it saves.
#  - masked/unmasked chunk-body forking: no measurable win.
# Reference parity: csrc/transformer/ds_transformer_cuda.cpp:78-121 claims
# fused-kernel supremacy at its benchmark shapes; this path is what makes
# the S=4096-8192 driver configs run on the measured-best kernels.
# ---------------------------------------------------------------------------


def _fwd_v3_kernel(*refs, scale2: float, causal: bool, block_q: int,
                   block_k: int, kv_pad: int, kv_len: int, num_k_blocks: int,
                   window: int = 0):
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True
    run = _in_band(run, qi, ki, block_q, block_k, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, ...]
        qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
        k = k_ref[0, ...]
        v = v_ref[0, ...]
        s2 = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = _band_mask(jnp.logical_and(mask, row >= col), row, col,
                              window)
        s2 = jnp.where(mask, s2, DEFAULT_MASK_VALUE)
        m_prev = m_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s2 - m_new)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_scr[...][:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse2 = m_scr[...][:, :1] + jnp.log2(l_safe)   # exp2-domain lse
        lse_ref[0, ...] = lse2.reshape(1, block_q)    # sublane -> lane


def _fwd_v3(q, k, v, sm_scale, causal, block_q, block_k, interpret,
            true_kv_len, head_rep, window: int = 0):
    bh, q_len, d = q.shape
    kv_pad = k.shape[1]
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_pad, block_k)
    rep = head_rep
    kernel = functools.partial(
        _fwd_v3_kernel, scale2=sm_scale * _LOG2E, causal=causal,
        block_q=block_q, block_k=block_k, kv_pad=kv_pad, kv_len=true_kv_len,
        num_k_blocks=nk, window=window)
    kv_map = lambda b, i, j: (
        b // rep, _band_k(i, j, block_q, block_k, window), 0)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, q_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, q_len), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd_chunked",
    )(q, k, v)
    return o, lse


def _bwd_v3_dq_kernel(*refs, scale2: float, sm_scale: float, causal: bool,
                      block_q: int, block_k: int, kv_len: int,
                      num_k_blocks: int, window: int = 0):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_scr) = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True
    run = _in_band(run, qi, ki, block_q, block_k, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, ...]
        k = k_ref[0, ...]
        v = v_ref[0, ...]
        do = do_ref[0, ...]
        qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
        lse2 = lse_ref[0, ...].reshape(block_q, 1)   # lane -> sublane
        delta = dl_ref[0, ...].reshape(block_q, 1)
        s2 = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = _band_mask(jnp.logical_and(mask, row >= col), row, col,
                              window)
        s2 = jnp.where(mask, s2, DEFAULT_MASK_VALUE)
        p = jnp.exp2(s2 - lse2)                      # true softmax probs
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, ...] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_v3_dkv_kernel(*refs, scale2: float, causal: bool, block_q: int,
                       block_k: int, kv_len: int, num_q_blocks: int,
                       rep: int, window: int = 0):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
     dk_scr, dv_scr) = refs
    ki = pl.program_id(1)
    inner = pl.program_id(2)
    qi = inner % num_q_blocks

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True
    run = _in_band(run, qi, ki, block_q, block_k, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, ...]
        k = k_ref[0, ...]
        v = v_ref[0, ...]
        do = do_ref[0, ...]
        qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
        lse2 = lse_ref[0, ...].reshape(block_q, 1)
        delta = dl_ref[0, ...].reshape(block_q, 1)
        s2 = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = _band_mask(jnp.logical_and(mask, row >= col), row, col,
                              window)
        s2 = jnp.where(mask, s2, DEFAULT_MASK_VALUE)
        p = jnp.exp2(s2 - lse2)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        # dk_true = sm_scale * ds^T @ q = (ds^T @ qs) * ln2
        dk_scr[...] += jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(inner == rep * num_q_blocks - 1)
    def _finalize():
        dk_ref[0, ...] = (dk_scr[...] * _LN2).astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_v3(q, k, v, o, lse, do, sm_scale, causal, block_q, block_k,
            interpret, true_kv_len, head_rep, window: int = 0):
    bh, q_len, d = q.shape
    bh_kv, kv_pad, _ = k.shape
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_pad, block_k)
    rep = head_rep
    scale2 = sm_scale * _LOG2E
    # delta = rowsum(do * o): one fused XLA pass, compact [bh, 1, S] layout
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]

    dq_kernel = functools.partial(
        _bwd_v3_dq_kernel, scale2=scale2, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=true_kv_len,
        num_k_blocks=nk, window=window)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (
        b // rep, _band_k(i, j, block_q, block_k, window), 0))
    lspec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, lspec, lspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq_chunked",
    )(q, k, v, do, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_v3_dkv_kernel, scale2=scale2, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=true_kv_len, num_q_blocks=nq, rep=rep,
        window=window)
    band_q = lambda j, i: _band_q(j, i % nq, block_q, block_k, window, nq)
    q_map = lambda b, j, i: (b * rep + i // nq, band_q(j, i), 0)
    l_map = lambda b, j, i: (b * rep + i // nq, 0, band_q(j, i))
    qspec2 = pl.BlockSpec((1, block_q, d), q_map)
    kspec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    lspec2 = pl.BlockSpec((1, 1, block_q), l_map)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh_kv, nk, rep * nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, lspec2, lspec2],
        out_specs=[kspec2, kspec2],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv_chunked",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_attention_bh(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                        true_kv_len, head_rep, window=0):
    if _v2_eligible(k.shape[1], q.shape[2]):
        return _fwd_v2(q, k, v, sm_scale, causal, block_q, interpret,
                       true_kv_len, head_rep, window)
    if _v3_eligible(k.shape[1], q.shape[2]):
        o, _ = _fwd_v3(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                       true_kv_len, head_rep, window)
        return o
    o, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                true_kv_len, head_rep, window=window)
    return o


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                    true_kv_len, head_rep, window=0):
    from jax.ad_checkpoint import checkpoint_name

    if _v2_eligible(k.shape[1], q.shape[2]):
        o = _fwd_v2(q, k, v, sm_scale, causal, block_q, interpret,
                    true_kv_len, head_rep, window)
        # no lse residual: the fused backward recomputes row stats in-kernel
        o = checkpoint_name(o, "flash_out")
        return o, (q, k, v, o)
    if _v3_eligible(k.shape[1], q.shape[2]):
        o, lse = _fwd_v3(q, k, v, sm_scale, causal, block_q, block_k,
                         interpret, true_kv_len, head_rep, window)
        o = checkpoint_name(o, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
        return o, (q, k, v, o, lse)
    o, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                  true_kv_len, head_rep, window=window)
    # named so remat policies can pin the kernel's residuals: saving o+lse
    # means the backward under jax.checkpoint reuses them instead of
    # re-running the forward kernel (see gpt2._remat_policy)
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, interpret, true_kv_len,
                    head_rep, window, res, g):
    if len(res) == 4:  # v2 path (see _flash_fwd_rule)
        q, k, v, o = res
        return _bwd_v2(q, k, v, o, g, sm_scale, causal, block_q, interpret,
                       true_kv_len, head_rep, window)
    if res[4].ndim == 3:  # v3 path: compact [bh, 1, S] exp2-domain lse
        q, k, v, o, lse = res
        return _bwd_v3(q, k, v, o, lse, g, sm_scale, causal, block_q,
                       block_k, interpret, true_kv_len, head_rep, window)
    return _bwd(sm_scale, causal, block_q, block_k, interpret, true_kv_len,
                head_rep, res, g, window)


_flash_attention_bh.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _sparse_attention_bh(q, k, v, layout, sm_scale, causal, block_q, block_k,
                         interpret, head_rep=1):
    o, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                k.shape[1], head_rep, layout)
    return o


def _sparse_fwd_rule(q, k, v, layout, sm_scale, causal, block_q, block_k,
                     interpret, head_rep=1):
    o, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                  k.shape[1], head_rep, layout)
    return o, (q, k, v, layout, o, lse)


def _sparse_bwd_rule(sm_scale, causal, block_q, block_k, interpret, head_rep,
                     res, g):
    q, k, v, layout, o, lse = res
    kv_len = k.shape[1]
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[..., None], lse.shape + (LANES,))
    delta_b = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))
    kw = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
              block_k=block_k, kv_len=kv_len, interpret=interpret,
              head_rep=head_rep, layout=layout)
    dq = _bwd_dq_call(q, k, v, g, lse_b, delta_b, **kw)
    dk, dv = _bwd_dkv_call(q, k, v, g, lse_b, delta_b, **kw)
    return dq, dk, dv, jnp.zeros_like(layout)


_sparse_attention_bh.defvjp(_sparse_fwd_rule, _sparse_bwd_rule)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None, window: int = 0):
    """Fused attention. q: [B, H, Sq, D]; k, v: [B, Hkv, Sk, D] (GQA: Hkv | H).
    ``window`` > 0 (static; causal self-attention): a query sees its
    ``window`` newest keys, itself included — forward, ``dq`` and ``dkv``
    skip the blocks wholly outside that band and mask inside the blocks on
    its edges; ``window = 0`` is the program without one.

    Returns [B, H, Sq, D] in q's dtype.  Sequence lengths are padded internally
    to the block size; padded keys are masked, padded query rows sliced off.
    ``block_q`` / ``block_k`` left ``None`` are chosen from the operands
    (``_resolve_blocks``); ``choices()`` records every resolution.
    """
    if interpret is None:
        interpret = interpret_kernels()
    b, h, q_len, d = q.shape
    hkv = k.shape[1]
    if hkv != h:
        assert h % hkv == 0, f"GQA needs num_heads {h} % kv_heads {hkv} == 0"
    rep = h // hkv  # repeated heads read KV blocks in place via the index map
    kv_len = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    window = int(window)
    if window < 0 or (window and not (causal and q_len == kv_len)):
        raise ValueError(f"window={window}: a sliding window is causal "
                         f"self-attention's (q {q_len}, kv {kv_len})")
    choice, pad_q, pad_k = _resolve_blocks(q_len, kv_len, d, q.dtype.itemsize,
                                           block_q, block_k)
    if window:
        choice = choice._replace(window=window)
    with _CHOICES_LOCK:
        _CHOICES[choice] = _CHOICES.get(choice, 0) + 1
    block_q, block_k = choice.block_q, choice.block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v

    qf = qp.reshape(b * h, q_len + pad_q, d)
    kf = kp.reshape(b * hkv, kv_len + pad_k, d)
    vf = vp.reshape(b * hkv, kv_len + pad_k, d)
    # kv_len for masking must be the real length: padded keys get masked out
    o = _flash_attention_bh(qf, kf, vf, sm_scale, causal, block_q, block_k,
                            interpret, kv_len, rep, window)
    o = o.reshape(b, h, q_len + pad_q, d)
    if pad_q:
        o = o[:, :, :q_len, :]
    return o


def mha_reference(q, k, v, causal: bool = True,
                  sm_scale: Optional[float] = None):
    """Plain einsum attention (the thing the kernel replaces); used by tests."""
    b, h, sq, d = q.shape
    if k.shape[1] != h:
        rep = h // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, k.shape[2]), bool))
        s = jnp.where(mask[None, None], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
