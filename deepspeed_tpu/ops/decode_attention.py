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
def _span_rows(scale_row, span, chunk):
    """[rows, chunk] scales for span-major query rows: row i takes lanes
    ``span[i]*chunk ..`` of the [1, g*chunk] per-token scale row."""
    g = scale_row.shape[1] // chunk
    out = scale_row[:, :chunk]
    for h in range(1, g):
        out = jnp.where(span == h, scale_row[:, h * chunk:(h + 1) * chunk],
                        out)
    return out.astype(jnp.float32)


def _span_queries(qg, spans: int):
    """``[B, HKV, rows, D] -> [B, HKV, spans*rows, spans*D]``: every query
    once per span, span-major, its D values in the span's lane group and
    zeros elsewhere (the operand :func:`_attend_chunk` expects; the identity
    for ``spans == 1``)."""
    if spans == 1:
        return qg
    d = qg.shape[-1]
    return jnp.concatenate(
        [jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (h * d, (spans - 1 - h) * d)))
         for h in range(spans)], axis=2)


def _attend_chunk(q_ref, k_ref, v_ref, ks_ref, vs_ref, keep, start, sm_scale,
                  m_scr, l_scr, acc_scr, *, spans: int):
    """One online-softmax update of ``m/l/acc`` with a KV chunk — the body
    the decode and verify kernels share.

    ``k_ref``/``v_ref`` blocks [1, 1, R, g*D]: a chunk of ``g*R`` keys held
    as ``g = spans`` consecutive R-key SPANS side by side in the lanes
    (``paged_kv.pack_pool``; g == 1 is the plain [bk, D] chunk of a
    contiguous cache or an unpacked pool).  ``q_ref`` [1, 1, g*rows, g*D]
    carries each query g times, span-major (:func:`_span_queries`), so ONE
    ``q·kᵀ`` over the whole tile gives row ``h*rows + i`` the scores of
    query i against span h (keys ``start + h*R ..``) — no lane slicing of
    the tile: the arithmetic of the g == 1 body on g times the rows.  Each
    span row keeps its own (m, l, acc) like an independent query;
    :func:`_finish_chunks` merges the g partial softmaxes of a query once,
    at the end.  ``keep(idx, query) -> bool`` is the caller's causal mask
    on key positions ``idx`` for query rows ``query`` (both [g*rows, R]).

    ``ks_ref``/``vs_ref`` (int8-KV pools only): [1, 1, 1, g*R] per-token
    dequant scales in token order, so span h reads lanes ``h*R ..``.  They
    fold into the math on its 2-D lane-dim tiles — ``q·(code*s_k) =
    (q·code)*s_k`` on the score columns, ``Σ p·(code*s_v) = (p*s_v)·code``
    on the prob columns — so no dequantized copy is ever materialized and
    the online softmax (which normalizes over UNscaled probabilities) is
    untouched."""
    q = q_ref[0, 0].astype(jnp.float32)               # [g*rows, g*D]
    k = k_ref[0, 0].astype(jnp.float32)               # [R, g*D]
    v = v_ref[0, 0].astype(jnp.float32)
    r, per = k.shape[0], q.shape[0] // spans
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                  # [g*rows, R]
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    span = row // per
    if ks_ref is not None:
        s = s * _span_rows(ks_ref[0, 0], span, r)
    idx = start + span * r + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(keep(idx, row % per), s, NEG_INF)

    m_prev = m_scr[...][:, :1]                        # [g*rows, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                            # [g*rows, R]
    l_new = l_scr[...][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = p * _span_rows(vs_ref[0, 0], span, r) if vs_ref is not None else p
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _finish_chunks(o_ref, m_scr, l_scr, acc_scr, *, spans: int):
    """Write ``acc / l`` — after merging, for ``spans > 1``, each query's
    per-span partial softmaxes (flash-decoding's split-K merge: weights
    ``exp(m_h - max_h m_h)``; a span that saw no unmasked key has ``m_h =
    NEG_INF`` and weighs 0).  Span h's numerator sits in lane group h of its
    rows of ``acc``."""
    per, d = o_ref.shape[2], o_ref.shape[3]
    m, l, acc = m_scr[...][:, :1], l_scr[...][:, :1], acc_scr[...]
    parts = [(m[h * per:(h + 1) * per], l[h * per:(h + 1) * per],
              acc[h * per:(h + 1) * per, h * d:(h + 1) * d])
             for h in range(spans)]
    m_all = parts[0][0]
    for m_h, _, _ in parts[1:]:
        m_all = jnp.maximum(m_all, m_h)
    num = den = 0.0
    for m_h, l_h, acc_h in parts:
        w = jnp.exp(m_h - m_all)
        num, den = num + w * acc_h, den + w * l_h
    o_ref[0, 0] = (num / jnp.where(den == 0.0, 1.0, den)).astype(o_ref.dtype)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, sm_scale: float, block_k: int, spans: int = 1,
                   ks_ref=None, vs_ref=None):
    """Grid: (B, HKV, S // block_k), KV innermost so scratch carries across.

    q_ref: the ``rep`` query heads sharing this KV head, [1, 1, rep, D] (or
    span-expanded, :func:`_attend_chunk`); o_ref: [1, 1, rep, D].
    k_ref/v_ref: one ``block_k``-key chunk of the cache, [1, 1, block_k, D]
    or lane-packed in ``spans`` spans.
    pos_ref: int32 [B] in SMEM — per-row query position (a scalar q_pos is
    broadcast before the call), read for the row this grid step covers, so
    chunk skipping scales FLOPs with each slot's own valid length.
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
        _attend_chunk(q_ref, k_ref, v_ref, ks_ref, vs_ref,
                      lambda idx, query: idx <= pos, start, sm_scale,
                      m_scr, l_scr, acc_scr, spans=spans)

    @pl.when(kb == nk - 1)
    def _finish():
        _finish_chunks(o_ref, m_scr, l_scr, acc_scr, spans=spans)


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
# the layout contract).  KV lives in the stacked pool [L, NB, HKV, bs, D],
# addressed in place as (layer, physical block, head, offset); each row
# reaches its tokens through an int32 [B, NBPER] block table.  Every entry
# point takes the whole pool plus ``layer`` — or one layer's [NB, HKV, bs,
# D] with ``layer=None``, viewed as a one-layer stack
# (``paged_kv.whole_pool``).
#
# Tensor parallelism: when ops/paged_kv carries a configured tp context and
# the head counts divide its axis, each paged-attention entry point runs
# its body inside shard_map — every chip attends its own HKV/tp (and H/tp
# query) head shard against its own pool shard, block tables and positions
# replicated.  Attention is embarrassingly parallel over heads, so no
# collective appears here; the tensor-parallel all-reduce happens after the
# model's output projection, exactly like the Megatron matmul path.
# ---------------------------------------------------------------------------
def _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos, layer):
    """Run ``body(q, k_pool, v_pool, bt, pos, layer)`` on the stacked pool
    (``layer=None``: a one-layer pool, lifted here), sharded over the head
    dims when the configured tp context divides them, else directly.  Int8
    pool records shard whole: codes and their scale table both carry the
    head dim at index 2, so the one head spec broadcasts over the record.

    Under a configured dp context (``paged_kv.dp_context`` —
    ``engine_mode='dp_tp'`` serving) the batch rows and the pool's
    physical-block dim additionally shard over the mesh ``dp`` axis: each
    dp shard attends its own contiguous row span against its own pool
    chunk, localizing the global block-table ids into that chunk first
    (``paged_kv.localize_block_tables``) — group-scoped allocation makes
    the localization exact, so no cross-shard gather ever happens."""
    (k_pool, layer), (v_pool, _) = (paged_kv.whole_pool(k_pool, layer),
                                    paged_kv.whole_pool(v_pool, layer))
    bt = jnp.asarray(block_tables, jnp.int32)
    n = head_shards(pool_payload(k_pool).shape[2], q.shape[1])
    tp = tp_axis() if n > 1 else None
    if paged_kv.dp_groups() <= 1 and n <= 1:
        return body(q, k_pool, v_pool, bt, q_pos, layer)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1),
                           (q.shape[0],))
    if paged_kv.dp_groups() <= 1:
        return head_shard_map(
            body, (P(None, tp), P(None, None, tp), P(None, None, tp),
                   P(), P(), P()), P(None, tp))(
            q, k_pool, v_pool, bt, pos, layer)
    mesh, _, gsize = paged_kv.dp_state()
    dp = paged_kv.dp_axis()
    qs, ps, rs = P(dp, tp), P(None, dp, tp), P(dp)    # q, pool, row args

    def dp_body(q, kp, vp, bt, pos, layer):
        bt = paged_kv.localize_block_tables(bt, gsize)
        return body(q, kp, vp, bt, pos, layer)

    return jax.shard_map(dp_body, mesh=mesh,
                         in_specs=(qs, ps, ps, rs, rs, P()),
                         out_specs=qs, check_vma=False)(
        q, k_pool, v_pool, bt, pos, layer)


def paged_decode_attention_reference(q, k_pool, v_pool, block_tables, q_pos,
                                     *, sm_scale: Optional[float] = None,
                                     layer=None):
    """Gather-based paged attention (pure XLA): materialize each row's
    logical cache view through its block table, then run the contiguous
    reference path.  Serves prefill (T > 1) and the CPU decode path.

    q:            [B, H, T, D]
    k/v_pool:     [L, NB, HKV, block_size, D] stacked pool + ``layer``
                  (or [NB, HKV, block_size, D] with ``layer=None``)
    block_tables: int32 [B, NBPER]
    q_pos:        scalar or int32 [B] — global position of q[:, :, 0]
    """
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])

    def body(q, kp, vp, bt, pos, layer):
        # int8 records dequantize to the query dtype so downstream
        # residual math keeps the model's compute dtype (float pools
        # ignore the hint — reads stay bit-identical)
        k = _paged_gather(kp, bt, layer, q.shape[-1], out_dtype=q.dtype)
        v = _paged_gather(vp, bt, layer, q.shape[-1], out_dtype=q.dtype)
        return decode_attention_reference(q, k, v, pos, sm_scale=scale)

    return _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos,
                           layer)


def _pool_refs(refs, quant: bool):
    """``(k, ks, v, vs, o, m, l, acc)`` from a paged kernel's refs after
    ``q_ref`` — float pools carry no scale operands (``ks = vs = None``)."""
    if quant:
        return refs
    k_ref, v_ref, *rest = refs
    return (k_ref, None, v_ref, None, *rest)


def _paged_decode_kernel(layer_ref, pos_ref, bt_ref, q_ref, *refs,
                         sm_scale: float, block_size: int, spans: int,
                         quant: bool = False):
    """Grid: (B, HKV, NBPER), logical blocks innermost so scratch carries.

    ``layer_ref`` int32 [1], ``pos_ref`` int32 [B] and ``bt_ref`` int32
    [B, NBPER] arrive via scalar prefetch — the k/v BlockSpec index maps
    read ``(layer_ref[0], bt_ref[b, i])`` so each grid step DMAs the row's
    *physical* block of this layer straight from the stacked pool.  The
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
    del layer_ref, bt_ref            # consumed by the BlockSpec index maps
    k_ref, ks_ref, v_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = \
        _pool_refs(refs, quant)
    _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, sm_scale=sm_scale, block_k=block_size,
                   spans=spans, ks_ref=ks_ref, vs_ref=vs_ref)


def _paged_pool_operands(k_pool, v_pool, layer):
    """(operand list, BlockSpec list, quant flag) for a stacked k/v pool
    pair at ``layer`` — float pools contribute two operands, int8 records
    four (codes + per-block scale rows), all walking the same ``(layer,
    bt_ref[i, k])`` index map.  The pool payload rides whole: the layer dim
    is squeezed out of its block (``None``) and indexed by the prefetched
    ``layer_ref``, so the kernels see the [1, 1, bs, D] tiles they always
    saw and no layer slice of the pool ever exists outside them."""
    quant = is_quantized_pool(k_pool)
    _, nb, hkv, rows, width = pool_payload(k_pool).shape   # either view
    blk = pl.BlockSpec(
        (None, 1, 1, rows, width),
        lambda i, j, k, layer_ref, pos_ref, bt_ref:
        (layer_ref[0], bt_ref[i, k], j, 0, 0))
    if not quant:
        return [k_pool, v_pool], [blk, blk], False
    # scale rows ride as [NB, HKV, 1, bs] views of the layer's rows of the
    # (small) table: Mosaic wants a block's last two dims tile-aligned or
    # equal to the array's, and a (1, bs) tail of the table is neither
    # (refused at lowering).  The view re-tiles what it views, so it is
    # taken of this layer's 1/L of the table, not of the carried whole.
    bs = k_pool["ps"].shape[3]
    sblk = pl.BlockSpec(
        (1, 1, 1, bs),
        lambda i, j, k, layer_ref, pos_ref, bt_ref: (bt_ref[i, k], j, 0, 0))
    ks, vs = (jax.lax.dynamic_index_in_dim(p["ps"], layer, keepdims=False)
              .reshape(nb, hkv, 1, bs) for p in (k_pool, v_pool))
    return ([k_pool["qp"], ks, v_pool["qp"], vs],
            [blk, sblk, blk, sblk], True)


def _paged_launch(qg, k_pool, v_pool, block_tables, q_pos, layer):
    """What the decode and verify launches share, as ``(pallas_call
    keywords, operands, static kernel keywords)``: grid (B, HKV, NBPER)
    over ``qg`` [B, HKV, rows, D], scalar prefetch of (layer, pos, block
    table), pool operands by :func:`_paged_pool_operands`, the queries
    span-expanded to the pool's packing (read off its minor dim).  Each
    launch site keeps its own ``pl.pallas_call`` with its kernel's name as
    a constant (the trace readers select by it)."""
    b, hkv, rows, d = qg.shape
    nbper = block_tables.shape[1]
    r, width = pool_payload(k_pool).shape[3:]
    spans = width // d
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    bt = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    pools, pool_specs, quant = _paged_pool_operands(k_pool, v_pool, layer)

    def row_block(n_rows, n_lanes):
        return pl.BlockSpec((1, 1, n_rows, n_lanes),
                            lambda i, j, k, layer_ref, pos_ref, bt_ref:
                            (i, j, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                    # layer, pos, block table
        grid=(b, hkv, nbper),
        in_specs=[row_block(spans * rows, width)] + pool_specs,
        out_specs=row_block(rows, d),
        scratch_shapes=[
            pltpu.VMEM((spans * rows, LANES), jnp.float32),   # m
            pltpu.VMEM((spans * rows, LANES), jnp.float32),   # l
            pltpu.VMEM((spans * rows, width), jnp.float32),   # acc
        ],
    )
    call = dict(
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))
    operands = (jnp.asarray(layer, jnp.int32).reshape(1), pos, bt,
                _span_queries(qg, spans), *pools)
    return call, operands, dict(block_size=r * spans, spans=spans,
                                quant=quant)


def _paged_decode_pallas(q, k_pool, v_pool, block_tables, q_pos, layer, *,
                         sm_scale: float, interpret: bool):
    """Single-shard kernel launch of :func:`paged_decode_attention_pallas`
    (shapes may be the full head count or one tp shard's slice — the grid
    and GQA grouping are computed from the local arrays either way)."""
    b, h, t, d = q.shape
    hkv = pool_payload(k_pool).shape[2]
    rep = h // hkv
    qg = q[:, :, 0, :].reshape(b, hkv, rep, d)        # [B, HKV, rep, D]
    call, operands, static = _paged_launch(qg, k_pool, v_pool, block_tables,
                                           q_pos, layer)
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, sm_scale=sm_scale, **static),
        interpret=interpret, name="paged_decode_attn", **call)(*operands)
    return out.reshape(b, h, 1, d)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                                  sm_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None,
                                  layer=None):
    """Single-token paged decode: q [B, H, 1, D] against the stacked block
    pool at ``layer``, walking each row's block table in-kernel via scalar
    prefetch.  Under a configured tp context each chip launches the kernel
    on its own head shard of q and the pool."""
    assert q.shape[2] == 1, \
        "pallas paged decode is single-token; use the XLA path"
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = interpret_kernels()
    body = functools.partial(_paged_decode_pallas, sm_scale=scale,
                             interpret=interpret)
    return _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos,
                           layer)


def _paged_verify_kernel(layer_ref, pos_ref, bt_ref, q_ref, *refs,
                         sm_scale: float, block_size: int, t: int,
                         spans: int, quant: bool = False):
    """Multi-token (T = K+1 speculative verify window) variant of the paged
    decode kernel.  Grid: (B, HKV, NBPER), logical blocks innermost.

    q_ref: [1, 1, rep*T, D] (span-expanded for a lane-packed pool,
    :func:`_attend_chunk`) — query row ``r*T + i`` is head ``r`` of this KV
    group at window offset ``i``, so its global position is ``base + i``
    with ``base = pos_ref[b]`` (the row's committed length — the verify
    window was just scattered at ``base .. base+T-1``).  The causal mask is
    per query ROW (``key <= base + row % T``): every verify query sees the
    row's history plus the window prefix up to itself, never the
    yet-unverified draft tail.  Blocks wholly past ``base + T - 1`` are
    skipped, so FLOPs track each row's own valid length.

    ``quant``: int8 pool — [1, 1, 1, bs] scale rows ride next to the code
    blocks and fold into the score/prob columns exactly like the decode
    kernel (:func:`_attend_chunk`).
    """
    del layer_ref, bt_ref            # consumed by the BlockSpec index maps
    k_ref, ks_ref, v_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = \
        _pool_refs(refs, quant)
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
        _attend_chunk(q_ref, k_ref, v_ref, ks_ref, vs_ref,
                      lambda idx, query: idx <= base + query % t,
                      start, sm_scale, m_scr, l_scr, acc_scr, spans=spans)

    @pl.when(kb == nk - 1)
    def _finish():
        _finish_chunks(o_ref, m_scr, l_scr, acc_scr, spans=spans)


#: widest window the verify kernel takes; larger T (chunked prefill) uses
#: the gather-based reference path
VERIFY_T_MAX = 16


def _paged_verify_pallas(q, k_pool, v_pool, block_tables, q_pos, layer, *,
                         sm_scale: float, interpret: bool):
    """Single-shard kernel launch of :func:`paged_verify_attention_pallas`
    (shapes may be the full head count or one tp shard's slice)."""
    b, h, t, d = q.shape
    hkv = pool_payload(k_pool).shape[2]
    rep = h // hkv
    # [B, H, T, D] -> [B, HKV, rep*T, D]: row r*T + i = (head r of the KV
    # group, window offset i) — matches the repeat-based GQA grouping
    qg = q.reshape(b, hkv, rep, t, d).reshape(b, hkv, rep * t, d)
    call, operands, static = _paged_launch(qg, k_pool, v_pool, block_tables,
                                           q_pos, layer)
    out = pl.pallas_call(
        functools.partial(_paged_verify_kernel, sm_scale=sm_scale, t=t,
                          **static),
        interpret=interpret, name="paged_verify_attn", **call)(*operands)
    return out.reshape(b, hkv, rep, t, d).reshape(b, h, t, d)


def paged_verify_attention_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                                  sm_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None,
                                  layer=None):
    """Speculative-verify paged attention: q [B, H, T, D] with T = K+1
    window positions per row, each row's window starting at its own
    ``q_pos[b]`` base (scalar q_pos broadcasts).  Same scalar-prefetch
    (layer, block-table) walk as the single-token kernel; the T query rows
    ride in the row dim of one [rep*T, D] tile per (row, KV-head) grid
    step.  Under a configured tp context each chip launches the kernel on
    its own head shard of q and the pool."""
    t = q.shape[2]
    assert 1 <= t <= VERIFY_T_MAX, \
        f"verify kernel takes windows up to {VERIFY_T_MAX}, got T={t}"
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = interpret_kernels()
    body = functools.partial(_paged_verify_pallas, sm_scale=scale,
                             interpret=interpret)
    return _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos,
                           layer)


def paged_decode_attention(q, k_pool, v_pool, block_tables, q_pos, *,
                           sm_scale: Optional[float] = None, layer=None):
    """Dispatch: block-table-walking Pallas kernels on TPU — single-token
    decode (T == 1) or the speculative K+1 verify window (T <=
    ``VERIFY_T_MAX``); gather + XLA reference otherwise (prefill chunks,
    CPU-sim).  A configured sp context (``ops/sp_attention``) routes
    prefill chunks through the Ulysses all-to-all path; a resident-window
    context forces the reference path, which carries the window mask.
    ``k_pool``/``v_pool`` are the stacked pool with ``layer`` given, one
    layer's pool otherwise (``paged_kv.whole_pool``)."""
    if q.shape[2] > 1:
        from . import sp_attention

        hkv = pool_payload(k_pool).shape[1 if layer is None else 2]
        if sp_attention.sp_shards(q.shape[1], hkv, q.shape[2]) > 1:
            return sp_attention.sp_prefill_attention(
                q, k_pool, v_pool, block_tables, q_pos, sm_scale=sm_scale,
                layer=layer)
    if window_state() is None and on_tpu():
        if q.shape[2] == 1:
            return paged_decode_attention_pallas(
                q, k_pool, v_pool, block_tables, q_pos, sm_scale=sm_scale,
                layer=layer)
        if q.shape[2] <= VERIFY_T_MAX:
            return paged_verify_attention_pallas(
                q, k_pool, v_pool, block_tables, q_pos, sm_scale=sm_scale,
                layer=layer)
    return paged_decode_attention_reference(q, k_pool, v_pool, block_tables,
                                            q_pos, sm_scale=sm_scale,
                                            layer=layer)
