"""Decode attention over a static KV cache — TPU replacement for the
reference's fused ``softmax_context`` inference kernels
(``csrc/transformer/inference/csrc/pt_binding.cpp`` attention variants, KV
workspace ``csrc/transformer/inference/includes/inference_context.h``).

The cache is a statically-shaped HBM buffer ``[B, HKV, S_max, D]`` sized by
``max_out_tokens`` exactly like the reference's ``InferenceContext`` workspace;
the valid prefix length is a traced scalar — or, for continuous-batching
serving, a traced ``int32[B]`` vector so every cache slot attends over its own
valid prefix — so one compiled program serves every decode step (the reference
gets the same effect from CUDA-graph replay; here it falls out of ``jit`` +
static shapes).

Two paths, one API:
 - ``decode_attention_reference``: q of one or more new positions against the
   cache, with position-aware causal masking (query at global position p sees
   keys ``<= p``) and grouped-query (GQA) head sharing.  Pure XLA — used for
   prefill and as the CPU/correctness path.
 - ``decode_attention_pallas``: single-token kernel that streams the cache in
   ``block_k`` chunks with an online softmax (f32 accumulation, no [S] score
   materialisation).  Chunks past the valid prefix are skipped with ``pl.when``
   so FLOPs scale with the *valid* length, not the workspace size.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..utils.platform import interpret_kernels, on_tpu
from . import paged_kv
from .paged_kv import (_paged_gather, head_shard_map, head_shards,
                       is_quantized_pool, pool_payload, tp_axis)

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128



# --------------------------------------------------------- window context
#: Resident-window attention state for 100k+-token serving
#: (``ServingEngine(resident_window_blocks=N)``): ``(window_start,
#: landmark_tokens)`` where ``window_start`` is a TRACED int32 [B] operand
#: of the serving program (per-row first token of the device-resident
#: window) and ``landmark_tokens`` a static int — the pinned leading span
#: that never leaves the device.  A query keeps a key iff it is causal AND
#: (``key < landmark_tokens`` OR ``key >= window_start[b]``); the masked
#: middle region's blocks have been demoted to the host/NVMe tiers and
#: their table entries re-point at scratch, so the mask is what makes the
#: scratch garbage unreachable.  Like the tp/dp contexts this is module
#: state read at TRACE time — the engine enters it INSIDE the jitted
#: program body, so only windowed programs bake in the extra mask and
#: ``window_start = landmark_tokens`` rows reduce to exact full attention.
_WINDOW = None


@contextlib.contextmanager
def window_context(window_start, landmark_tokens: int):
    """Scoped install of the resident-window mask state (see ``_WINDOW``).
    Entered inside a traced serving-program body; nesting restores the
    previous state on exit."""
    global _WINDOW
    prev = _WINDOW
    _WINDOW = (window_start, int(landmark_tokens))
    try:
        yield
    finally:
        _WINDOW = prev


def window_state():
    """``(window_start int32 [B], landmark_tokens int)`` or ``None``."""
    return _WINDOW


def decode_attention_reference(q, k_cache, v_cache, q_pos, *,
                               sm_scale: Optional[float] = None):
    """Masked attention of new queries against the KV cache (pure XLA).

    q:        [B, H, T, D]  — T new query positions (T=1 for decode,
                              T=prompt_len for prefill)
    k_cache:  [B, HKV, S, D], v_cache: [B, HKV, S, D] — the *already updated*
              cache (new keys written at q_pos .. q_pos+T-1)
    q_pos:    scalar int32 — global position of q[:, :, 0]; or int32 [B] for
              per-sequence positions (continuous-batching slots, each row
              attends over its own valid prefix)
    """
    b, h, t, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if h != hkv:
        rep = h // hkv
        k_cache = jnp.repeat(k_cache, rep, axis=1)
        v_cache = jnp.repeat(v_cache, rep, axis=1)
    scores = jnp.einsum("bhtd,bhsd->bhts", q, k_cache).astype(jnp.float32)
    scores = scores * scale
    q_pos = jnp.asarray(q_pos, jnp.int32)
    key_idx = jnp.arange(s)
    if q_pos.ndim == 0:
        query_idx = q_pos + jnp.arange(t)[:, None]
        mask = key_idx[None, :] <= query_idx          # [T, S]
        mask = mask[None, None]                       # [1, 1, T, S]
    else:
        query_idx = q_pos[:, None] + jnp.arange(t)[None, :]
        mask = key_idx[None, None, :] <= query_idx[:, :, None]  # [B, T, S]
        mask = mask[:, None]                          # [B, 1, T, S]
    win = window_state()
    if win is not None:
        wstart, landmark = win
        wstart = jnp.asarray(wstart, jnp.int32).reshape(-1)
        keep = (key_idx[None, :] < landmark) | \
            (key_idx[None, :] >= wstart[:, None])     # [B, S]
        mask = mask & keep[:, None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", probs, v_cache)


# ---------------------------------------------------------------------------
# Pallas single-token decode kernel
# ---------------------------------------------------------------------------
def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, sm_scale: float, block_k: int,
                   ks_ref=None, vs_ref=None):
    """Grid: (B, HKV, S // block_k), KV innermost so scratch carries across.

    q_ref: [1, 1, rep, D] — the ``rep`` query heads sharing this KV head.
    k_ref/v_ref: [1, 1, block_k, D] chunk of the cache.
    pos_ref: int32 [B] in SMEM — per-row query position (a scalar q_pos is
    broadcast before the call), read for the row this grid step covers, so
    chunk skipping scales FLOPs with each slot's own valid length.

    ``ks_ref``/``vs_ref`` (int8-KV pools only): [1, 1, 1, block_k] per-token
    dequant scales riding next to the code chunks.  They fold into the
    math on its 2-D lane-dim tiles — ``q·(code*s_k) = (q·code)*s_k`` on
    the score columns, ``Σ p·(code*s_v) = (p*s_v)·code`` on the prob
    columns — so no dequantized [bk, D] copy is ever materialized and the
    online softmax (which normalizes over UNscaled probabilities) is
    untouched.
    """
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = kb * block_k

    @pl.when(start <= pos)  # skip chunks entirely past the valid prefix
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [rep, D]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                              # [rep, bk]
        if ks_ref is not None:
            s = s * ks_ref[0, 0].astype(jnp.float32)    # [1, bk] row
        idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx <= pos, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                    # [rep, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # [rep, bk]
        l_new = l_scr[...][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)           # [bk, D]
        pv = p * vs_ref[0, 0].astype(jnp.float32) \
            if vs_ref is not None else p
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kb == nk - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, q_pos, *,
                            sm_scale: Optional[float] = None,
                            block_k: int = 256,
                            interpret: Optional[bool] = None):
    """Single-token decode: q [B, H, 1, D] vs cache [B, HKV, S, D].
    ``q_pos``: scalar or per-row int32 [B] query positions."""
    b, h, t, d = q.shape
    assert t == 1, "pallas decode kernel is single-token; use the XLA path"
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    rep = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    block_k = min(block_k, s)
    while s % block_k:  # largest divisor of s not above the requested block
        block_k -= 1
    if interpret is None:
        interpret = interpret_kernels()

    qg = q[:, :, 0, :].reshape(b, hkv, rep, d)        # [B, HKV, rep, D]
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=scale, block_k=block_k),
        grid=(b, hkv, s // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, rep, d), lambda i, j, k: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda i, j, k: (i, j, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, d), lambda i, j, k: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, LANES), jnp.float32),    # m
            pltpu.VMEM((rep, LANES), jnp.float32),    # l
            pltpu.VMEM((rep, d), jnp.float32),        # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn",
    )(pos, qg, k_cache, v_cache)
    return out.reshape(b, h, 1, d)


def decode_attention(q, k_cache, v_cache, q_pos, *,
                     sm_scale: Optional[float] = None):
    """Dispatch: Pallas kernel for single-token decode on TPU, XLA otherwise."""
    if q.shape[2] == 1 and on_tpu():
        return decode_attention_pallas(q, k_cache, v_cache, q_pos,
                                       sm_scale=sm_scale)
    return decode_attention_reference(q, k_cache, v_cache, q_pos,
                                      sm_scale=sm_scale)


# ---------------------------------------------------------------------------
# Block-paged attention (vLLM PagedAttention layout; ops/paged_kv.py holds
# the layout contract).  KV lives in a shared pool [NB, HKV, bs, D]; each
# row reaches its tokens through an int32 [B, NBPER] block table.
#
# Tensor parallelism: when ops/paged_kv carries a configured tp context and
# the head counts divide its axis, each paged-attention entry point runs
# its body inside shard_map — every chip attends its own HKV/tp (and H/tp
# query) head shard against its own pool shard, block tables and positions
# replicated.  Attention is embarrassingly parallel over heads, so no
# collective appears here; the tensor-parallel all-reduce happens after the
# model's output projection, exactly like the Megatron matmul path.
# ---------------------------------------------------------------------------
def _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos):
    """Run ``body(q, k_pool, v_pool, bt, pos)`` sharded over the head dims
    when the configured tp context divides them, else directly.  Int8 pool
    records shard whole: codes and their scale table both carry the head
    dim at index 1, so the one head spec broadcasts over the record.

    Under a configured dp context (``paged_kv.dp_context`` —
    ``engine_mode='dp_tp'`` serving) the batch rows and the pool's
    physical-block dim additionally shard over the mesh ``dp`` axis: each
    dp shard attends its own contiguous row span against its own pool
    chunk, localizing the global block-table ids into that chunk first
    (``paged_kv.localize_block_tables``) — group-scoped allocation makes
    the localization exact, so no cross-shard gather ever happens."""
    n = head_shards(pool_payload(k_pool).shape[1], q.shape[1])
    if paged_kv.dp_groups() > 1:
        mesh, _, gsize = paged_kv.dp_state()
        dp = paged_kv.dp_axis()
        pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1),
                               (q.shape[0],))
        qs = P(dp, tp_axis()) if n > 1 else P(dp)     # [B, H, T, D]
        ps = P(dp, tp_axis()) if n > 1 else P(dp)     # [NB, HKV, bs, D]
        rs = P(dp)                                    # [B, ...] row args

        def dp_body(q, kp, vp, bt, pos):
            bt = paged_kv.localize_block_tables(bt, gsize)
            return body(q, kp, vp, bt, pos)

        return jax.shard_map(dp_body, mesh=mesh,
                             in_specs=(qs, ps, ps, rs, rs),
                             out_specs=qs, check_vma=False)(
            q, k_pool, v_pool,
            jnp.asarray(block_tables, jnp.int32), pos)
    if n <= 1:
        return body(q, k_pool, v_pool, block_tables, q_pos)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1),
                           (q.shape[0],))
    hs = P(None, tp_axis())
    return head_shard_map(body, (hs, hs, hs, P(), P()), hs)(
        q, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32), pos)


def paged_decode_attention_reference(q, k_pool, v_pool, block_tables, q_pos,
                                     *, sm_scale: Optional[float] = None):
    """Gather-based paged attention (pure XLA): materialize each row's
    logical cache view through its block table, then run the contiguous
    reference path.  Serves prefill (T > 1) and the CPU decode path.

    q:            [B, H, T, D]
    k/v_pool:     [NB, HKV, block_size, D] shared pool
    block_tables: int32 [B, NBPER]
    q_pos:        scalar or int32 [B] — global position of q[:, :, 0]
    """
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])

    def body(q, kp, vp, bt, pos):
        # int8 records dequantize to the query dtype so downstream
        # residual math keeps the model's compute dtype (float pools
        # ignore the hint — reads stay bit-identical)
        k = _paged_gather(kp, bt, out_dtype=q.dtype)
        v = _paged_gather(vp, bt, out_dtype=q.dtype)
        return decode_attention_reference(q, k, v, pos, sm_scale=scale)

    return _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos)


def _paged_decode_kernel(pos_ref, bt_ref, q_ref, *refs, sm_scale: float,
                         block_size: int, quant: bool = False):
    """Grid: (B, HKV, NBPER), logical blocks innermost so scratch carries.

    ``pos_ref`` int32 [B] and ``bt_ref`` int32 [B, NBPER] arrive via scalar
    prefetch — the k/v BlockSpec index maps read ``bt_ref[b, i]`` so each
    grid step DMAs the row's *physical* block straight from the pool.  The
    paging indirection lives entirely in those index maps: the body is the
    contiguous kernel's online softmax unchanged (a logical block at grid
    step ``kb`` holds positions ``kb*block_size ..``, exactly like a
    contiguous chunk), including the ``pl.when`` skip of blocks past the
    row's valid prefix.

    ``quant``: the pool is int8 — two extra scale operands ([1, 1, 1, bs]
    rows of the per-block scale table, same index maps) ride next to the
    code blocks and dequantize in-kernel, so HBM traffic is codes +
    scales only.
    """
    del bt_ref                       # consumed by the BlockSpec index maps
    if quant:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, sm_scale=sm_scale, block_k=block_size,
                   ks_ref=ks_ref, vs_ref=vs_ref)


def _paged_pool_operands(k_pool, v_pool):
    """(operand list, BlockSpec list, quant flag) for a k/v pool pair —
    float pools contribute two operands, int8 records four (codes +
    per-block scale rows), all walking the same ``bt_ref[i, k]`` physical-
    block index map."""
    quant = is_quantized_pool(k_pool)
    nb, hkv, bs, d = pool_payload(k_pool).shape
    blk = pl.BlockSpec((1, 1, bs, d),
                       lambda i, j, k, pos_ref, bt_ref: (bt_ref[i, k], j, 0, 0))
    if not quant:
        return [k_pool, v_pool], [blk, blk], False
    # scale rows ride as [NB, HKV, 1, bs] views: Mosaic wants a block's
    # last two dims tile-aligned or equal to the array's, and a (1, bs)
    # tail of the 3-D table is neither (refused at lowering)
    sblk = pl.BlockSpec(
        (1, 1, 1, bs),
        lambda i, j, k, pos_ref, bt_ref: (bt_ref[i, k], j, 0, 0))
    ks = k_pool["ps"].reshape(nb, hkv, 1, bs)
    vs = v_pool["ps"].reshape(nb, hkv, 1, bs)
    return ([k_pool["qp"], ks, v_pool["qp"], vs],
            [blk, sblk, blk, sblk], True)


def _paged_decode_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                         sm_scale: float, interpret: bool):
    """Single-shard kernel launch of :func:`paged_decode_attention_pallas`
    (shapes may be the full head count or one tp shard's slice — the grid
    and GQA grouping are computed from the local arrays either way)."""
    b, h, t, d = q.shape
    nb, hkv, bs, _ = pool_payload(k_pool).shape
    rep = h // hkv
    nbper = block_tables.shape[1]
    scale = sm_scale

    qg = q[:, :, 0, :].reshape(b, hkv, rep, d)        # [B, HKV, rep, D]
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    pools, pool_specs, quant = _paged_pool_operands(k_pool, v_pool)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                        # pos, block table
        grid=(b, hkv, nbper),
        in_specs=[
            pl.BlockSpec((1, 1, rep, d),
                         lambda i, j, k, pos_ref, bt_ref: (i, j, 0, 0)),
        ] + pool_specs,
        out_specs=pl.BlockSpec((1, 1, rep, d),
                               lambda i, j, k, pos_ref, bt_ref: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, LANES), jnp.float32),    # m
            pltpu.VMEM((rep, LANES), jnp.float32),    # l
            pltpu.VMEM((rep, d), jnp.float32),        # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, sm_scale=scale,
                          block_size=bs, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attn",
    )(pos, bt, qg, *pools)
    return out.reshape(b, h, 1, d)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                                  sm_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None):
    """Single-token paged decode: q [B, H, 1, D] against the block pool,
    walking each row's block table in-kernel via scalar prefetch.  Under a
    configured tp context each chip launches the kernel on its own head
    shard of q and the pool."""
    assert q.shape[2] == 1, \
        "pallas paged decode is single-token; use the XLA path"
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = interpret_kernels()
    body = functools.partial(_paged_decode_pallas, sm_scale=scale,
                             interpret=interpret)
    return _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos)


def _paged_verify_kernel(pos_ref, bt_ref, q_ref, *refs, sm_scale: float,
                         block_size: int, t: int, quant: bool = False):
    """Multi-token (T = K+1 speculative verify window) variant of the paged
    decode kernel.  Grid: (B, HKV, NBPER), logical blocks innermost.

    q_ref: [1, 1, rep*T, D] — query row ``r*T + i`` is head ``r`` of this KV
    group at window offset ``i``, so its global position is ``base + i``
    with ``base = pos_ref[b]`` (the row's committed length — the verify
    window was just scattered at ``base .. base+T-1``).  The causal mask is
    per query ROW (``key <= base + row % T``): every verify query sees the
    row's history plus the window prefix up to itself, never the
    yet-unverified draft tail.  Blocks wholly past ``base + T - 1`` are
    skipped, so FLOPs track each row's own valid length.

    ``quant``: int8 pool — [1, 1, 1, bs] scale rows ride next to the code
    blocks and fold into the score/prob columns exactly like the decode
    kernel (``_decode_kernel`` docstring).
    """
    del bt_ref                       # consumed by the BlockSpec index maps
    if quant:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    base = pos_ref[pl.program_id(0)]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = kb * block_size

    @pl.when(start <= base + t - 1)  # skip blocks past the window's last row
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [rep*T, D]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                              # [rep*T, bk]
        if ks_ref is not None:
            s = s * ks_ref[0, 0].astype(jnp.float32)    # [1, bk] row
        key_idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_off = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % t
        s = jnp.where(key_idx <= base + q_off, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                    # [rep*T, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # [rep*T, bk]
        l_new = l_scr[...][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)           # [bk, D]
        pv = p * vs_ref[0, 0].astype(jnp.float32) \
            if vs_ref is not None else p
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kb == nk - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


#: widest window the verify kernel takes; larger T (chunked prefill) uses
#: the gather-based reference path
VERIFY_T_MAX = 16


def _paged_verify_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                         sm_scale: float, interpret: bool):
    """Single-shard kernel launch of :func:`paged_verify_attention_pallas`
    (shapes may be the full head count or one tp shard's slice)."""
    b, h, t, d = q.shape
    nb, hkv, bs, _ = pool_payload(k_pool).shape
    rep = h // hkv
    nbper = block_tables.shape[1]
    scale = sm_scale

    # [B, H, T, D] -> [B, HKV, rep*T, D]: row r*T + i = (head r of the KV
    # group, window offset i) — matches the repeat-based GQA grouping
    qg = q.reshape(b, hkv, rep, t, d).reshape(b, hkv, rep * t, d)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    pools, pool_specs, quant = _paged_pool_operands(k_pool, v_pool)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                        # pos, block table
        grid=(b, hkv, nbper),
        in_specs=[
            pl.BlockSpec((1, 1, rep * t, d),
                         lambda i, j, k, pos_ref, bt_ref: (i, j, 0, 0)),
        ] + pool_specs,
        out_specs=pl.BlockSpec((1, 1, rep * t, d),
                               lambda i, j, k, pos_ref, bt_ref: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep * t, LANES), jnp.float32),    # m
            pltpu.VMEM((rep * t, LANES), jnp.float32),    # l
            pltpu.VMEM((rep * t, d), jnp.float32),        # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_verify_kernel, sm_scale=scale,
                          block_size=bs, t=t, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep * t, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_verify_attn",
    )(pos, bt, qg, *pools)
    return out.reshape(b, hkv, rep, t, d).reshape(b, h, t, d)


def paged_verify_attention_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                                  sm_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None):
    """Speculative-verify paged attention: q [B, H, T, D] with T = K+1
    window positions per row, each row's window starting at its own
    ``q_pos[b]`` base (scalar q_pos broadcasts).  Same scalar-prefetch
    block-table walk as the single-token kernel; the T query rows ride in
    the row dim of one [rep*T, D] tile per (row, KV-head) grid step.
    Under a configured tp context each chip launches the kernel on its own
    head shard of q and the pool."""
    t = q.shape[2]
    assert 1 <= t <= VERIFY_T_MAX, \
        f"verify kernel takes windows up to {VERIFY_T_MAX}, got T={t}"
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = interpret_kernels()
    body = functools.partial(_paged_verify_pallas, sm_scale=scale,
                             interpret=interpret)
    return _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos)


def paged_decode_attention(q, k_pool, v_pool, block_tables, q_pos, *,
                           sm_scale: Optional[float] = None):
    """Dispatch: block-table-walking Pallas kernels on TPU — single-token
    decode (T == 1) or the speculative K+1 verify window (T <=
    ``VERIFY_T_MAX``); gather + XLA reference otherwise (prefill chunks,
    CPU-sim).  A configured sp context (``ops/sp_attention``) routes
    prefill chunks through the Ulysses all-to-all path; a resident-window
    context forces the reference path, which carries the window mask."""
    if q.shape[2] > 1:
        from . import sp_attention

        if sp_attention.sp_shards(q.shape[1], pool_payload(k_pool).shape[1],
                                  q.shape[2]) > 1:
            return sp_attention.sp_prefill_attention(
                q, k_pool, v_pool, block_tables, q_pos, sm_scale=sm_scale)
    if window_state() is None and on_tpu():
        if q.shape[2] == 1:
            return paged_decode_attention_pallas(
                q, k_pool, v_pool, block_tables, q_pos, sm_scale=sm_scale)
        if q.shape[2] <= VERIFY_T_MAX:
            return paged_verify_attention_pallas(
                q, k_pool, v_pool, block_tables, q_pos, sm_scale=sm_scale)
    return paged_decode_attention_reference(q, k_pool, v_pool, block_tables,
                                            q_pos, sm_scale=sm_scale)
