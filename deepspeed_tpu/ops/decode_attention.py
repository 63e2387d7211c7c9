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
   keys ``<= p``) and grouped-query (GQA) head sharing.  Pure XLA — the
   contiguous cache's prefill and the CPU/correctness path.
 - ``decode_attention_pallas``: single-token kernel that streams the cache in
   ``block_k`` chunks with an online softmax (f32 accumulation, no [S] score
   materialisation).  Chunks past the valid prefix are skipped with ``pl.when``
   so FLOPs scale with the *valid* length, not the workspace size.

The serving path is the block-paged pool further down (``paged_decode_
attention``).  Its kernels share the contiguous kernel's online softmax
(:func:`_attend_chunk`) but not its iteration space: a grid step there is
one ROW, which loops over its own valid blocks and copies each block — every
KV head of it, contiguous in the lane-packed pool — out of HBM itself,
double-buffered, a TILE of blocks a loop iteration: one softmax update over
all the tile's keys, 128 score columns wide (:func:`_paged_walk_kernel`).
Time follows the tokens a row holds, not ``max_seq_len`` (a grid over (row,
head, logical block) spent ~0.17 us a step whether or not the step held a
key: 24,576 steps a layer for 24 rows of ~160 tokens, 1 % of the HBM
roofline; the walk of PR 29 read the same bytes in ~140 copies of 256 KB at
30-45 %, one update a block, 0.89 us a visit against 0.31 us of bytes; a
tile's visits cost 0.35 us a block, 75-87 % of the roofline at rows of 24-56
blocks, and what is left is ~2 us a row before its first tile has landed —
my chip runs, PR 45, PERF.md).  A prefill chunk
(T = ``prefill_chunk`` query rows a sequence) takes the same walk, several
blocks a landing tile, under a flash body sized for its query rows
(:func:`_paged_prefill_kernel`; the gather it replaced read, un-packed and
transposed all ``max_seq_len`` keys of every row, every layer, every chunk,
and wrote ``[T, S]`` scores to HBM: 30 of a long-prompt chunk's 41.6 ms,
PERF.md PR 31).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional, Tuple

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
def _lanes(x, n: int):
    """A lane-replicated ``[..., 128]`` value at ``n`` lanes."""
    if n % LANES:
        return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))
    return x if n == LANES else jnp.concatenate([x] * (n // LANES), axis=-1)


def _span_cols(scales, span, r: int, spans: int):
    """[H, rows, cols] scales for span-major query rows over a TILE of
    blocks: ``scales`` holds, per block of the tile, its [H, >= spans*r]
    per-token scale rows in token order (lanes past the block are padding);
    row i takes, of every block, lanes ``span[i]*r ..`` — the tokens its
    span of that block's packed rows holds."""
    out = None
    for h in range(spans):
        part = jnp.concatenate(
            [s.astype(jnp.float32)[:, h * r:(h + 1) * r] for s in scales],
            axis=-1)[:, None, :]
        out = part if out is None else jnp.where(span == h, part, out)
    return out


def _span_queries(qg, spans: int, width: int):
    """``[B, HKV, rows, D] -> [B, HKV, spans*rows, width]``: every query
    once per span, span-major, its D values in the span's lane group and
    zeros elsewhere (the operand :func:`_attend_chunk` expects; the identity
    for ``spans == 1`` and ``width == D``).  ``width`` is ``spans * D``, or
    more where the pool's rows were padded to whole lane tiles."""
    d = qg.shape[-1]
    return jnp.concatenate(
        [jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (h * d, width - (h + 1) * d)))
         for h in range(spans)], axis=2)


def _attend_chunk(q, k, v, ks, vs, keep, start, sm_scale,
                  m_scr, l_scr, acc_scr, *, spans: int, r: int):
    """ONE online-softmax update of ``m/l/acc`` with a TILE of KV blocks of
    H heads — the body the contiguous, paged-decode and paged-verify kernels
    share (the contiguous kernel passes H = 1 and its one ``block_k`` chunk,
    the paged ones every KV head of ``nt`` blocks at once).

    ``k``/``v`` [H, cols, g*D]: per head the ``cols = nt * r`` packed key
    rows of ``nt`` consecutive blocks, each block ``r`` rows that hold its
    ``g*r`` keys as ``g = spans`` consecutive r-key SPANS side by side in
    the lanes (``paged_kv.pack_pool``; g == 1 is the plain [bk, D] chunk of
    a contiguous cache or an unpacked pool).  ``q`` [H, g*rows, g*D] carries
    each query g times, span-major (:func:`_span_queries`), so ONE batched
    ``q·kᵀ`` over the whole tile gives row ``h*rows + i`` the scores of
    query i against span h of every block (column ``j*r + x``: key ``start
    + j*g*r + h*r + x``) — no lane slicing of the tile: the arithmetic of
    the g == 1 body on g times the rows, full 128-lane score registers at
    128 columns.  Each span row keeps its own (m, l, acc) like an
    independent query; :func:`_finish_chunks` merges the g partial
    softmaxes of a query once, at the end.  ``keep(idx, query) -> bool`` is
    the caller's causal mask on key positions ``idx`` for query rows
    ``query`` (both [1, g*rows, cols]: every head's mask is the same).

    The MXU takes ``q``, ``k``, ``v`` in the pool's dtype (an int8 pool's
    codes arrive here in the query's: exact) and accumulates in float32; the
    probabilities go to it in that dtype too, as
    :func:`decode_attention_reference`'s do.  m and l are float32 and stay
    lane-replicated [H, g*rows, 128], as the scratch holds them, and are
    read, rescaled and written back ONCE a tile.

    ``ks``/``vs`` (int8-KV pools only): per block of the tile its [H, g*r]
    per-token dequant scales in token order, so span h reads lanes ``h*r
    ..`` of each (:func:`_span_cols`).  They fold into the math on its
    lane-dim tiles — ``q·(code*s_k) = (q·code)*s_k`` on the score columns,
    ``Σ p·(code*s_v) = (p*s_v)·code`` on the prob columns — so no
    dequantized copy is ever materialized and the online softmax (which
    normalizes over UNscaled probabilities) is untouched."""
    q = q.astype(k.dtype)
    cols, per = k.shape[1], q.shape[1] // spans
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                  # [H, g*rows, cols]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, q.shape[1], 1), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, cols), 2)
    span = row // per
    if ks is not None:
        s = s * _span_cols(ks, span, r, spans)
    # span 0's key of each column; span h's is h*r further
    idx = start + col + col // r * ((spans - 1) * r) + span * r
    s = jnp.where(keep(idx, row % per), s, NEG_INF)

    m_prev = m_scr[...]                               # [H, g*rows, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - _lanes(m_new, cols))              # [H, g*rows, cols]
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new
    if vs is not None:
        p = p * _span_cols(vs, span, r, spans)
    acc_scr[...] = acc_scr[...] * _lanes(alpha, acc_scr.shape[-1]) \
        + jax.lax.dot_general(p.astype(v.dtype), v,
                              (((2,), (1,)), ((0,), (0,))),
                              preferred_element_type=jnp.float32)


def _start_chunks(m_scr, l_scr, acc_scr):
    """Empty online-softmax state: no key seen yet."""
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finish_chunks(o_ref, m_scr, l_scr, acc_scr, *, spans: int):
    """Write ``acc / l`` to ``o_ref`` [1, H, rows, D] — after merging, for
    ``spans > 1``, each query's per-span partial softmaxes (flash-decoding's
    split-K merge: weights ``exp(m_h - max_h m_h)``; a span that saw no
    unmasked key has ``m_h = NEG_INF`` and weighs 0).  Span h's numerator
    sits in lane group h of its rows of ``acc``."""
    per, d = o_ref.shape[2], o_ref.shape[3]
    m, l, acc = m_scr[...][:, :, :1], l_scr[...][:, :, :1], acc_scr[...]
    parts = [(m[:, h * per:(h + 1) * per], l[:, h * per:(h + 1) * per],
              acc[:, h * per:(h + 1) * per, h * d:(h + 1) * d])
             for h in range(spans)]
    m_all = parts[0][0]
    for m_h, _, _ in parts[1:]:
        m_all = jnp.maximum(m_all, m_h)
    num = den = 0.0
    for m_h, l_h, acc_h in parts:
        w = jnp.exp(m_h - m_all)
        num, den = num + w * acc_h, den + w * l_h
    o_ref[0] = (num / jnp.where(den == 0.0, 1.0, den)).astype(o_ref.dtype)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, sm_scale: float, block_k: int):
    """Grid: (B, HKV, S // block_k), KV innermost so scratch carries across.

    q_ref: the ``rep`` query heads sharing this KV head, [1, 1, rep, D];
    o_ref: [1, 1, rep, D].  k_ref/v_ref: one ``block_k``-key chunk of the
    cache, [1, 1, block_k, D].
    pos_ref: int32 [B] in SMEM — per-row query position (a scalar q_pos is
    broadcast before the call), read for the row this grid step covers, so
    chunk skipping scales FLOPs with each slot's own valid length.
    """
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(kb == 0)
    def _init():
        _start_chunks(m_scr, l_scr, acc_scr)

    start = kb * block_k

    @pl.when(start <= pos)  # skip chunks entirely past the valid prefix
    def _compute():
        _attend_chunk(q_ref[0], k_ref[0], v_ref[0], None, None,
                      lambda idx, query: idx <= pos, start, sm_scale,
                      m_scr, l_scr, acc_scr, spans=1, r=block_k)

    @pl.when(kb == nk - 1)
    def _finish():
        _finish_chunks(o_ref, m_scr, l_scr, acc_scr, spans=1)


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
            pltpu.VMEM((1, rep, LANES), jnp.float32),     # m
            pltpu.VMEM((1, rep, LANES), jnp.float32),     # l
            pltpu.VMEM((1, rep, d), jnp.float32),         # acc
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
# the layout contract).  KV lives in the stacked pool [L, NB, HKV, bs, D]
# (lane-packed [L, NB, HKV, bs/g, g*D] in a serving engine), addressed in
# place as (layer, physical block): each row reaches its tokens through an
# int32 [B, NBPER] block table.  Every entry point takes the whole pool plus
# ``layer`` — or one layer's [NB, HKV, bs, D] with ``layer=None``, viewed as
# a one-layer stack (``paged_kv.whole_pool``).  The kernels take the pool as
# an HBM operand and copy out of it, per row, the blocks that row holds —
# all KV heads of a block in one DMA (``_paged_walk_kernel``).
#
# Tensor parallelism: when ops/paged_kv carries a configured tp context and
# the head counts divide its axis, each paged-attention entry point runs
# its body inside shard_map — every chip attends its own HKV/tp (and H/tp
# query) head shard against its own pool shard, block tables and positions
# replicated.  Attention is embarrassingly parallel over heads, so no
# collective appears here; the tensor-parallel all-reduce happens after the
# model's output projection, exactly like the Megatron matmul path.
# ---------------------------------------------------------------------------
def _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos, layer,
                    *per_row):
    """Run ``body(q, k_pool, v_pool, bt, pos, layer, *per_row)`` on the
    stacked pool (``layer=None``: a one-layer pool, lifted here), sharded
    over the head dims when the configured tp context divides them, else
    directly.  ``per_row``: further int32 [B] operands that travel as the
    positions do (the prefill kernel's ``valid``).  Int8 pool records shard
    whole: codes and their scale table both carry the head dim at index 2,
    so the one head spec broadcasts over the record.

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
        return body(q, k_pool, v_pool, bt, q_pos, layer, *per_row)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1),
                           (q.shape[0],))
    if paged_kv.dp_groups() <= 1:
        return head_shard_map(
            body, (P(None, tp), P(None, None, tp), P(None, None, tp),
                   P(), P(), P()) + (P(),) * len(per_row), P(None, tp))(
            q, k_pool, v_pool, bt, pos, layer, *per_row)
    mesh, _, gsize = paged_kv.dp_state()
    dp = paged_kv.dp_axis()
    qs, ps, rs = P(dp, tp), P(None, dp, tp), P(dp)    # q, pool, row args

    def dp_body(q, kp, vp, bt, pos, layer, *per_row):
        bt = paged_kv.localize_block_tables(bt, gsize)
        return body(q, kp, vp, bt, pos, layer, *per_row)

    return jax.shard_map(dp_body, mesh=mesh,
                         in_specs=(qs, ps, ps, rs, rs, P())
                         + (rs,) * len(per_row),
                         out_specs=qs, check_vma=False)(
        q, k_pool, v_pool, bt, pos, layer, *per_row)


def _ring_attention_reference(q, k, v, q_pos, valid, window: int, bs: int,
                              sm_scale: float):
    """A window layer's attention over keys gathered IN RING ORDER: ``k`` /
    ``v`` [B, HKV, R*bs, D] are the row's ring entries ``0 .. R-1``, entry
    ``e`` holding the newest logical block ``i <= last`` with ``i % R == e``
    (``last``: the block of the row's last query).  Query ``p`` keeps key
    ``j`` iff ``0 <= p - j < window``; an entry whose block is older than
    that (released, or never written) holds keys no query keeps."""
    b, h, t, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    ring = s // bs
    if h != hkv:
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    last = (pos + jnp.maximum(valid, 1) - 1) // bs
    entry = jnp.arange(ring, dtype=jnp.int32)
    li = last[:, None] - (last[:, None] - entry[None, :]) % ring   # [B, R]
    key = (li[:, :, None] * bs
           + jnp.arange(bs, dtype=jnp.int32)).reshape(b, 1, s)
    query = (pos[:, None] + jnp.arange(t, dtype=jnp.int32))[:, :, None]
    mask = (key >= 0) & (key <= query) & (key > query - window)  # [B, T, S]
    scores = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32)
    scores = jnp.where(mask[:, None], scores * sm_scale, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", probs, v)


def paged_decode_attention_reference(q, k_pool, v_pool, block_tables, q_pos,
                                     *, sm_scale: Optional[float] = None,
                                     layer=None, window: int = 0,
                                     valid=None):
    """Gather-based paged attention (pure XLA): materialize each row's
    logical cache view through its block table, then run the contiguous
    reference path.  The CPU path for every T and the tests' oracle; on a
    TPU only what the kernels do not take: a prefill chunk (T > 1) over
    int8 records, and the resident-window mask.

    q:            [B, H, T, D]
    k/v_pool:     [L, NB, HKV, block_size, D] stacked pool + ``layer``
                  (or [NB, HKV, block_size, D] with ``layer=None``)
    block_tables: int32 [B, NBPER]
    q_pos:        scalar or int32 [B] — global position of q[:, :, 0]
    window:       a sliding-window layer's reach (0: none): the table is
                  that layer kind's RING (``ops/paged_kv.py`` "Layer
                  kinds") and a query keeps the ``window`` newest keys,
                  itself included; ``valid`` int32 [B] then says how many
                  of the T queries are real (default all): the ring holds
                  the blocks up to the last real one
    """
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, t = q.shape[0], q.shape[2]
    per_row = () if not window else (
        jnp.full((b,), t, jnp.int32) if valid is None
        else jnp.clip(jnp.asarray(valid, jnp.int32), 0, t),)

    def body(q, kp, vp, bt, pos, layer, *valid):
        # int8 records dequantize to the query dtype so downstream
        # residual math keeps the model's compute dtype (float pools
        # ignore the hint — reads stay bit-identical)
        k = _paged_gather(kp, bt, layer, q.shape[-1], out_dtype=q.dtype)
        v = _paged_gather(vp, bt, layer, q.shape[-1], out_dtype=q.dtype)
        if window:
            return _ring_attention_reference(
                q, k, v, pos, valid[0], window, k.shape[2] // bt.shape[1],
                scale)
        return decode_attention_reference(q, k, v, pos, sm_scale=scale)

    return _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos,
                           layer, *per_row)


#: score columns (packed key rows) ONE softmax update of the decode / verify
#: walk takes: a whole 128-lane register of float32 scores a query row — 8
#: blocks a tile at OPT-1.3B's ``[16, 128]`` blocks (256 keys), 4 at the
#: ``[32, 128]`` of OLMoE and Command A+ (128 keys).  The kernel alone, us a
#: row at 64 / 128 / 256 columns (one update a block before: my chip runs,
#: PR 45): OPT, 6 blocks a row 3.91 / 3.94 / 5.15 (5.48), 56 blocks 21.2 /
#: 21.2 / 22.6 (40.2) — at 256 its 8 MB of landing buffers split the heads;
#: OLMoE, 6 blocks 3.64 / 3.32 / 3.50 (4.74), 56 blocks 24.4 / 20.6 / 20.8
#: (37.1); Command A+ (8 KV heads x 16 query rows), 56 blocks 18.9 / 12.0 /
#: 11.1 (32.2).  A row of ONE block costs what it did (2.1 / 1.5 / 1.4 us)
_WALK_COLS = 128

#: grid steps the decode / verify walk's copies run ahead of its updates: a
#: row's last tile starts tile 0 of the NEXT grid step into the landing
#: buffers' other slot (two slots: one step ahead and no further)
WALK_ROWS_AHEAD = 1

#: VMEM the decode / verify walk gives its K/V landing buffers (two slots of
#: ``nt`` blocks each) and the query-dtype copies :func:`_attend_chunk` makes
#: of an int8 pool's tiles (a float pool's go to the MXU as they landed)
_WALK_VMEM_BUDGET = 8 << 20


def walk_tile_blocks(r: int, nbper: int) -> int:
    """Blocks one loop iteration of the decode / verify walk lands and
    attends — its TILE — from the shapes alone: as many ``r``-row blocks as
    give one softmax update :data:`_WALK_COLS` score columns, never more
    than a row's table holds."""
    return max(1, min(_WALK_COLS // r, nbper))


def _walk_head_tile(hkv: int, r: int, width: int, itemsize: int,
                    nt: int = 1) -> int:
    """KV heads one grid step of the paged kernels takes, for a shard's
    ``[hkv, r, width]`` blocks ``nt`` to a tile, from the shapes alone: all
    of them (OPT-1.3B: 8 blocks of 128 KB of K and of V in bf16, 4 MB of
    landing buffers; OLMoE: 4, 2 MB) unless a tile of all heads,
    double-buffered, would overrun :data:`_WALK_VMEM_BUDGET`.  Then the
    heads are split over a second grid dim, in halves that stay a multiple
    of 16 (the sublane tile of the int8 records' bf16 scale rows, which the
    copies then slice by head)."""
    def need(ht):
        return ht * nt * r * width * (4 * itemsize + 2 * 2)

    ht = hkv
    while need(ht) > _WALK_VMEM_BUDGET and ht % 32 == 0:
        ht //= 2
    return ht


def _paged_walk_kernel(layer_ref, pos_ref, bt_ref, q_ref, *refs,
                       sm_scale: float, t: int, spans: int, quant: bool,
                       window: int = 0):
    """The paged decode (``t == 1``) and verify (``t`` window positions)
    kernel.  Grid ``(B, HKV // ht)``: one step is one row's whole attention
    for ``ht`` KV heads (all of the shard's, :func:`_walk_head_tile`).

    ``layer_ref`` int32 [1], ``pos_ref`` int32 [B] and ``bt_ref`` int32
    [B, NBPER] arrive via scalar prefetch.  The pool operands stay in HBM
    (``pl.ANY``): the kernel walks the row's VALID logical blocks only —
    ``n = cdiv(last query position + 1, block_size)`` of them, read off
    ``pos_ref``, never an entry of the table past them — ``nt`` blocks a
    TILE (:func:`walk_tile_blocks`; read here off the landing buffers'
    shape).  It copies block ``i`` itself, ``pool.at[layer, bt[b, i]]`` ->
    its place ``buf[slot, j]`` in its tile, a ``[ht, bs/g, g*D]`` VMEM
    buffer: every head of the block in ONE contiguous DMA (K and V; an int8
    pool's ``[ht, bs]`` scale rows ride beside them).  The buffers have two
    slots, so tile ``i + 1`` lands while tile ``i`` is attended; of the last
    tile only the blocks the row holds are copied.  A row costs its own
    length: no grid step and no copy is spent on the part of
    ``max_seq_len`` it does not hold.

    The two slots carry a tile ACROSS grid steps (the grid runs in order:
    ``arbitrary``): where a row's last tile has no next one to start, it
    starts tile 0 of the NEXT grid step — the next row's, or the next head
    tile's — into the slot it does not hold itself, and that step starts
    nothing for its tile 0 and only waits.  So the flight of a row's first
    tile, which nothing of its own row can hide (a chat row is ONE tile),
    lies under the update, the output's write and the prologue of the step
    before.  One rule: step ``s`` starts whatever step ``s + 1``'s tile 0
    is — no block, for a row that holds none — and step ``s + 1`` waits for
    exactly that, both from :func:`reach` of the row's index; only a
    launch's first step starts its own, the last starts nothing.  A row's
    first slot is therefore no constant: it follows the tiles walked before
    it (``slot_ref``, SMEM scratch; a row of no tile leaves it as it was).

    ``q_ref`` [1, ht, g*rows, g*D] (span-expanded, :func:`_span_queries`),
    ``rows = rep * t``: query row ``r*t + i`` is head ``r`` of its KV group
    at window offset ``i``, global position ``base + i`` with ``base =
    pos_ref[b]`` (decode: the one query at ``base``; verify: the window was
    just scattered at ``base .. base + t - 1``).  The causal mask is per
    query ROW (``key <= base + row % t``): a verify query sees the row's
    history plus the window up to itself, never the unverified draft tail.
    A loop iteration is ONE online-softmax update over all ``nt *
    block_size`` keys of its tile (:func:`_attend_chunk`: a head's ``nt``
    landed blocks are ``cols = nt * bs/g`` consecutive packed key rows):
    the body exists once a tile, whatever ``nt``.  The mask also hides the
    slots of a last, partly landed tile; their V (and an int8 pool's V
    scales) are zeroed first, so that what the mask zeroes in ``p`` meets
    no NaN there.  ``o_ref`` [1, ht, rows, D] is written once, after the
    walk.

    ``window`` (static; 0: a full-attention layer, the program above): a
    sliding-window layer.  Query ``p`` keeps keys ``p - window < key <= p``,
    so the walk starts at the block of the row's FIRST VISIBLE KEY,
    ``max(0, base - window + 1) // block_size`` — its tiles count from
    there, aligned to nothing — masks the keys before each query's own
    bound, and reads the table as the RING it is (``ops/paged_kv.py``
    "Layer kinds"): logical block ``i`` at entry ``i % NBPER``.  A row
    costs ``min(length, window)`` keys.  A verify query whose window starts
    after a tile's last key sees nothing of that tile: its state stays
    empty (``m`` at ``NEG_INF``) and what such a tile adds to ``l`` /
    ``acc`` is scaled by ``alpha = 0`` when the query's first real key
    arrives — every query sees at least itself.

    Refs after ``q_ref``: the pool operands (K, [K scales], V, [V scales]),
    ``o_ref``, one two-slot landing buffer per pool operand, the DMA
    semaphores ``[2, operands]``, the slot of this step's tile 0 (int32 [1]
    in SMEM), then m / l / acc."""
    n_ops = 4 if quant else 2
    pools, o_ref = refs[:n_ops], refs[n_ops]
    bufs = refs[n_ops + 1:2 * n_ops + 1]
    sem, slot_ref, m_scr, l_scr, acc_scr = refs[2 * n_ops + 1:]
    _, nt, ht, r, _ = bufs[0].shape
    bs = r * spans
    width = bt_ref.shape[1]
    splits = pools[0].shape[2] // ht              # grid steps a row
    b, layer = pl.program_id(0), layer_ref[0]
    base = pos_ref[b]
    if window:
        keep = lambda idx, query: (idx <= base + query % t) \
            & (idx > base + query % t - window)       # noqa: E731
    else:
        keep = lambda idx, query: idx <= base + query % t     # noqa: E731

    def reach(row):
        """``(n, first)`` of ``row``: it walks its blocks ``first .. n - 1``,
        those that hold a key some query of it may see (keys <= its last
        query's).  THE arithmetic of a row's copies, for the step that
        starts them and the step that waits for them."""
        at = pos_ref[row]
        if not window:
            return jnp.clip((at + t - 1 + bs) // bs, 0, width), 0
        n = jnp.maximum((at + t - 1 + bs) // bs, 0)
        return n, jnp.maximum(jnp.maximum(at - window + 1, 0) // bs,
                              n - width)

    def tile(step, i):
        """Tile ``i`` of grid step ``step`` (row-major over the grid; one
        past the last: no tile), as what its copies need: ``(row, first
        head, first block, blocks the row holds of it)`` — 0 past its last
        tile."""
        row = jnp.minimum(step // splits, pl.num_programs(0) - 1)
        n, first = reach(row)
        return (row, step % splits * ht, first + i * nt,
                jnp.where(step < pl.num_programs(0) * splits,
                          jnp.clip(n - first - i * nt, 0, nt), 0))

    def each_block(of, slot, act):
        """``act`` on the copies of the valid blocks of the tile ``of``."""
        row, head, start, held = of

        def one(j, carry):
            at = start + j
            for op, (pool, buf) in enumerate(zip(pools, bufs)):
                # an operand that is this layer's rows alone (_lane_rows)
                # is a one-layer stack
                src = (layer if pool.shape[0] > 1 else 0,
                       bt_ref[row, at % width if window else at])
                act(pltpu.make_async_copy(
                    pool.at[src if splits == 1 else src + (pl.ds(head, ht),)],
                    buf.at[slot, j], sem.at[slot, op]))
            return carry

        jax.lax.fori_loop(0, held, one, None)

    def choose(pred, a, b):
        return tuple(jnp.where(pred, x, y) for x, y in zip(a, b))

    step = b * splits + pl.program_id(1)
    n, first = reach(b)
    tiles = (n - first + nt - 1) // nt
    ahead = tile(step + 1, 0)

    # a launch's first step starts its own tile 0; every other step finds
    # it started (the step before's ``ahead``) and only waits.  A row of no
    # tile passes the duty on here, where no loop iteration does
    @pl.when(step == 0)
    def _():
        slot_ref[0] = 0

    slot0 = slot_ref[0]
    *which, held = choose(tiles > 0, tile(step, 0), ahead)
    each_block((*which, jnp.where((step == 0) | (tiles == 0), held, 0)),
               slot0, lambda copy: copy.start())

    def attend(i, carry):
        slot = (slot0 + i) % 2
        # the tile after this one lands meanwhile: the row's next, or, on
        # its last, tile 0 of the NEXT grid step
        each_block(choose(i + 1 < tiles, tile(step, i + 1), ahead),
                   1 - slot, lambda copy: copy.start())
        mine = tile(step, i)

        def blank(j, carry):
            for buf in bufs[n_ops // 2:]:             # V, [V scales]
                buf[slot, j] = jnp.zeros(buf.shape[2:], buf.dtype)
            return carry

        jax.lax.fori_loop(mine[3], nt, blank, None)
        each_block(mine, slot, lambda copy: copy.wait())
        landed = [[buf[slot, j] for j in range(nt)] for buf in bufs]
        k, ks, v, vs = landed if quant else (landed[0], None, landed[1], None)
        # a head's nt landed blocks, one under the other: [ht, nt * r, g*D]
        # (an int8 pool's codes in the query's dtype first: its 32-row
        # tiles do not stack at r = 16)
        k, v = (jnp.concatenate([x.astype(q_ref.dtype) if quant else x
                                 for x in blocks], axis=1)
                for blocks in (k, v))
        _attend_chunk(q_ref[0], k, v, ks, vs, keep, (first + i * nt) * bs,
                      sm_scale, m_scr, l_scr, acc_scr, spans=spans, r=r)
        return carry

    _start_chunks(m_scr, l_scr, acc_scr)
    jax.lax.fori_loop(0, tiles, attend, None)
    slot_ref[0] = (slot0 + tiles) % 2
    _finish_chunks(o_ref, m_scr, l_scr, acc_scr, spans=spans)


def _lane_rows(leaf, layer, head_dim=None):
    """A pool operand Mosaic can copy blocks out of: its minor dim whole
    128-lane rows.  Mosaic takes a copy out of an HBM operand in whole lane
    tiles only (a ``[HKV, 16, 64]`` or ``[HKV, 32]`` slice is refused at
    lowering), so the engine's lane-packed payload — minor dim ``g*hd``, a
    multiple of 128 — rides as it is, the WHOLE stack, and costs nothing
    here.  Anything else rides as a copy of this layer's rows alone, a
    one-layer stack ``[1, NB, HKV, ...]``: a payload exactly as
    ``init_cache`` built it (``head_dim`` given: the benchmark's comparison
    and the kernel tests pass one) is lane-packed first
    (``paged_kv.pack_pool``: 128 // hd spans a row), then — like an int8
    record's scale table ``[L, NB, HKV, bs]`` — zero-padded to whole rows.
    1/L of the pool a call: fine for a comparison, not for serving."""
    if leaf.shape[-1] % LANES == 0:
        return leaf
    rows = jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=True)
    if head_dim == rows.shape[-1]:
        rows = paged_kv.pack_pool(rows)
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1)
                   + ((0, -rows.shape[-1] % LANES),))


def _paged_launch(q, k_pool, v_pool, block_tables, q_pos, layer, *,
                  sm_scale: float, window: int = 0):
    """The one launch of the paged kernels, ``q`` [B, H, T, D] against one
    shard's stacked pool at ``layer``, as ``(kernel, pallas_call keywords,
    operands)``: decode and verify each keep a ``pl.pallas_call`` site of
    their own only to give it their kernel's name as a constant (the trace
    readers select by it).  Shapes may be the full head count or one
    tp shard's slice — grid, GQA grouping, the walk's tile of blocks
    (:func:`walk_tile_blocks`) and its head tile (:func:`_walk_head_tile`)
    are computed from the local arrays either way, and the pool's packing
    is read off its minor dim.

    Grid ``(B, HKV // ht)`` over queries regrouped ``[B, HKV, rep*T, D]``
    (row ``r*T + i`` = head ``r`` of the KV group at window offset ``i`` —
    the repeat-based GQA grouping); scalar prefetch of (layer, pos, block
    table); the pool operands in HBM (``memory_space=pl.ANY``) — the
    lane-packed payload the whole stack as it lies, so no slice or view of
    it exists outside the kernel (:func:`_lane_rows` has the exceptions)."""
    b, h, t, d = q.shape
    quant = is_quantized_pool(k_pool)
    _, nb, _, r_in, w_in = pool_payload(k_pool).shape
    pools = [_lane_rows(pool_payload(p), layer, d) for p in (k_pool, v_pool)]
    if quant:
        pools = [pools[0], _lane_rows(k_pool["ps"], layer),
                 pools[1], _lane_rows(v_pool["ps"], layer)]
    hkv, r, width = pools[0].shape[2:]
    rows, spans = h // hkv * t, r_in * w_in // d // r     # block_size over R
    nt = walk_tile_blocks(r, block_tables.shape[1])
    ht = _walk_head_tile(hkv, r, width, pools[0].dtype.itemsize, nt)
    qg = _span_queries(q.reshape(b, hkv, rows, d), spans, width)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    # a copy is issued for entries of a row's valid prefix only; clipped all
    # the same, so that no table can send a DMA outside the pool
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, nb - 1)

    def row_block(n_rows, n_lanes):
        return pl.BlockSpec((1, ht, n_rows, n_lanes),
                            lambda i, j, layer_ref, pos_ref, bt_ref:
                            (i, j, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                    # layer, pos, block table
        grid=(b, hkv // ht),
        in_specs=[row_block(spans * rows, width)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=row_block(rows, d),
        scratch_shapes=[pltpu.VMEM((2, nt, ht) + p.shape[3:], p.dtype)
                        for p in pools] + [
            pltpu.SemaphoreType.DMA((2, len(pools))),
            pltpu.SMEM((1,), jnp.int32),          # the slot of tile 0
            pltpu.VMEM((ht, spans * rows, LANES), jnp.float32),   # m
            pltpu.VMEM((ht, spans * rows, LANES), jnp.float32),   # l
            pltpu.VMEM((ht, spans * rows, width), jnp.float32),   # acc
        ],
    )
    kernel = functools.partial(_paged_walk_kernel, sm_scale=sm_scale, t=t,
                               spans=spans, quant=quant, window=window)
    call = dict(
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        # a tile is carried from a grid step to the next: the steps run in
        # order on one core (a tp mesh shards the heads outside the kernel)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")))
    return kernel, call, (jnp.asarray(layer, jnp.int32).reshape(1), pos, bt,
                          qg, *pools)


def _paged_decode_call(q, *args, interpret: bool, **kwargs):
    kernel, call, operands = _paged_launch(q, *args, **kwargs)
    return pl.pallas_call(kernel, interpret=interpret,
                          name="paged_decode_attn", **call)(
        *operands).reshape(q.shape)


def _paged_verify_call(q, *args, interpret: bool, **kwargs):
    kernel, call, operands = _paged_launch(q, *args, **kwargs)
    return pl.pallas_call(kernel, interpret=interpret,
                          name="paged_verify_attn", **call)(
        *operands).reshape(q.shape)


def _paged_attention_pallas(launch, q, k_pool, v_pool, block_tables, q_pos,
                            sm_scale, interpret, layer, *per_row,
                            window: int = 0):
    """``launch`` (one of the kernels' call sites) per head shard under a
    configured tp / dp context (:func:`_tp_shard_heads`).  ``window``: a
    sliding-window layer's reach (static; 0: a full-attention layer)."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = interpret_kernels()
    body = functools.partial(launch, sm_scale=scale, interpret=interpret,
                             window=window)
    return _tp_shard_heads(body, q, k_pool, v_pool, block_tables, q_pos,
                           layer, *per_row)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                                  sm_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None,
                                  layer=None, window: int = 0):
    """Single-token paged decode: q [B, H, 1, D] against the stacked block
    pool at ``layer``, each row walking its own valid blocks in-kernel
    (:func:`_paged_walk_kernel`, as ``paged_decode_attn``).  Under a
    configured tp context each chip launches the kernel on its own head
    shard of q and the pool."""
    assert q.shape[2] == 1, \
        "pallas paged decode is single-token; use the XLA path"
    return _paged_attention_pallas(_paged_decode_call, q, k_pool, v_pool,
                                   block_tables, q_pos, sm_scale, interpret,
                                   layer, window=window)


#: widest window the verify kernel takes; a wider T is a prefill chunk
#: (:func:`paged_prefill_attention_pallas`)
VERIFY_T_MAX = 16


def paged_verify_attention_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                                  sm_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None,
                                  layer=None, window: int = 0):
    """Speculative-verify paged attention: q [B, H, T, D] with T = K+1
    window positions per row, each row's window starting at its own
    ``q_pos[b]`` base (scalar q_pos broadcasts).  The decode kernel's walk
    with ``rep*T`` query rows a KV head, the per-row causal mask and a trip
    count from ``base + T - 1`` (:func:`_paged_walk_kernel`, as
    ``paged_verify_attn``).  Under a configured tp context each chip
    launches the kernel on its own head shard of q and the pool."""
    t = q.shape[2]
    assert 1 <= t <= VERIFY_T_MAX, \
        f"verify kernel takes windows up to {VERIFY_T_MAX}, got T={t}"
    return _paged_attention_pallas(_paged_verify_call, q, k_pool, v_pool,
                                   block_tables, q_pos, sm_scale, interpret,
                                   layer, window=window)


# ---------------------------------------------------------------------------
# The prefill chunk: T = ``prefill_chunk`` query positions a row against the
# row's valid blocks, walked in place like the decode kernel's, attended by
# a flash body sized for that many query rows.
# ---------------------------------------------------------------------------
#: score columns (packed key rows) one softmax update of the prefill kernel
#: takes — whole 128-lane registers of float32 scores a query row, two of
#: them: 512 keys a tile at hd 64 (16 blocks), 256 at hd 128 (8).  What a
#: head pays per update whatever the tile holds (its m / l / acc read and
#: written back, the chain's latency) is spread over twice the keys of a
#: 128-column tile: 8.1 -> 6.1 ms a 24-layer call at 1,920 keys, and 2.2 ->
#: 2.5 ms at an empty prefix, where the one tile is mostly masked (PERF.md
#: PR 31)
_PREFILL_COLS = 256

#: heads the prefill kernel attends in one iteration of its loop over a
#: tile's heads: one head's chain (q·kᵀ, max, exp, p·v, the update of its
#: acc) is a few hundred cycles of latency with little to overlap, and a
#: loop keeps iterations apart; four heads' chains interleave.  The body
#: exists 4 times for it, in each of the two loops (PERF.md PR 31: 17.5 ->
#: 10.8 ms a 24-layer call at 1,920 keys; 8 heads an iteration spilled)
_PREFILL_HEAD_UNROLL = 4

#: VMEM one grid step of the prefill kernel may plan for (landing buffers,
#: m / l / acc, the pipelined query and output blocks); the per-head
#: temporaries of the body (scores, probabilities: ~0.5 MB) come on top,
#: under Mosaic's 16 MB default limit
_PREFILL_VMEM_BUDGET = 10 << 20


def _prefill_head_tile(hkv: int, rows: int, spans: int, nt: int, r: int,
                       width: int, itemsize: int) -> int:
    """KV heads one grid step of the prefill kernel takes, from the shapes
    alone: ``rows = rep * T`` query rows a KV head, ``nt`` blocks of
    ``[r, width]`` a landing tile.  A head costs its share of the two-slot
    K and V landing buffers, float32 m / l / acc for its query rows, its
    span-expanded queries and its double-buffered query and output blocks
    (lane-padded) — 640 KB at OPT-1.3B's ``[4, 128]`` chunk, so 16 of its
    32 heads a step (10 MB), all 16 of OLMoE's (9 MB); the heads are halved
    while they overrun :data:`_PREFILL_VMEM_BUDGET`."""
    head = _prefill_head_bytes(rows, spans, nt, r, width, itemsize)
    ht = hkv
    while ht * head > _PREFILL_VMEM_BUDGET and ht % 2 == 0:
        ht //= 2
    return ht


def _prefill_head_bytes(rows: int, spans: int, nt: int, r: int, width: int,
                        itemsize: int) -> int:
    """VMEM one KV head of a grid step of the prefill kernel costs
    (:func:`_prefill_head_tile` says what of)."""
    return (2 * 2 * nt * r * width * itemsize
            + rows * (width + 2 * LANES) * 4
            + (spans + 4) * rows * width * itemsize)


def prefill_row_fits(heads: int, hkv: int, block_size: int, head_dim: int,
                     itemsize: int, t: int, nbper: int) -> bool:
    """Whether the prefill kernel can plan ``t``-wide rows of ``heads``
    query heads over a lane-packed float pool of ``hkv`` KV heads,
    ``block_size`` x ``head_dim`` a block: ONE KV head a grid step — the
    fewest :func:`_prefill_head_tile` can choose — inside
    :data:`_PREFILL_VMEM_BUDGET`, from the shapes alone
    (:func:`_paged_prefill_call`'s own arithmetic).  What the serving
    engine asks before it builds a prefill program with wider rows."""
    g = paged_kv.lane_pack(block_size, head_dim)
    r, width = block_size // g, g * head_dim
    width += -width % LANES
    nt = max(1, min(_PREFILL_COLS // r, nbper))
    return _prefill_head_bytes(heads // hkv * t, g, nt, r, width,
                               itemsize) <= _PREFILL_VMEM_BUDGET


def _paged_prefill_kernel(layer_ref, base_ref, valid_ref, bt_ref, q_ref,
                          k_pool, v_pool, o_ref, kbuf, vbuf, sem, qs_scr,
                          m_scr, l_scr, acc_scr, *,
                          sm_scale: float, t: int, spans: int,
                          window: int = 0):
    """The paged prefill kernel.  Grid ``(B, HKV // ht)``: one step is one
    row's chunk of ``t`` query positions, ``base .. base + t - 1`` of which
    the first ``valid`` are real, for ``ht`` KV heads
    (:func:`_prefill_head_tile`).

    ``layer_ref`` int32 [1], ``base_ref`` / ``valid_ref`` int32 [B] and
    ``bt_ref`` int32 [B, NBPER] arrive via scalar prefetch; the pools stay
    in HBM (``pl.ANY``).  The row walks its ``n = cdiv(base + valid,
    block_size)`` valid blocks — the chunk was just written into the last
    of them — ``nt`` blocks a TILE: each block is one DMA of all ``ht``
    heads, ``pool.at[layer, bt[b, i]]`` -> ``buf[slot, j]``, and tile ``i +
    1`` lands while tile ``i`` is attended.  Neither a table entry nor a
    block past ``n`` is read, and a pad row (``valid == 0``) walks nothing
    and returns zeros.

    A tile is attended head by head (a ``fori_loop``,
    :data:`_PREFILL_HEAD_UNROLL` heads an iteration): the head's ``nt``
    landed ``[r, width]`` blocks are ``cols = nt * r`` packed key rows, and
    ``qs_scr`` [ht, g*rows, width] carries each query of ``q_ref`` [1, ht,
    rows, D] once per span, its D values in the span's lane group and zeros
    elsewhere (:func:`_span_queries`'s operand, built here once a grid step
    by a 0/1 placement matmul, which is exact), so one ``q·kᵀ`` — pool
    dtype operands, float32 accumulation — gives rows ``h*rows ..`` the
    scores against span ``h`` of every block, ``[rows, cols]`` a span.  One
    online-softmax update takes all ``g * cols`` keys of the tile: m and l
    are per QUERY (shared by its spans; float32, lane-replicated), the
    probabilities go to the MXU in the pool's dtype as the reference's do,
    and each span's ``p·v`` lands in its own lane group of ``acc`` [rows,
    width] (float32), summed over the groups once, at the end.  Tiles
    wholly below ``base`` need no mask; the rest keep key ``<= base +
    min(i, valid - 1)`` for query offset ``i`` (pad queries see what the
    last real one sees), which also hides the slots of a last, partly
    landed tile — ``vbuf`` is zeroed at the start, so that what the mask
    zeroes in ``p`` meets no NaN in ``v``.

    ``window`` (static; 0: a full-attention layer, the program above): a
    sliding-window layer, as in :func:`_paged_walk_kernel`.  The walk
    starts at the TILE that holds the first visible key of the chunk's
    first query, every tile is masked on both sides (``last - window < key
    <= last`` per query row), and the table is read as a ring.  A query
    whose window starts after a tile's last key sees nothing of that tile:
    its state stays empty (``m`` at ``NEG_INF``) and what such a tile adds
    to ``l`` / ``acc`` is scaled by ``alpha = 0`` when the query's first
    real key arrives — every query sees at least itself."""
    _, nt, ht, r, width = kbuf.shape
    rows, d = o_ref.shape[2:]
    bs, cols = r * spans, nt * r
    b, layer = pl.program_id(0), layer_ref[0]
    base, valid = base_ref[b], valid_ref[b]
    if window:
        n = jnp.maximum((base + valid + bs - 1) // bs, 0)
    else:
        n = jnp.clip((base + valid + bs - 1) // bs, 0, bt_ref.shape[1])
    n = jnp.where(valid > 0, n, 0)
    ntiles = (n + nt - 1) // nt
    clear = jnp.minimum(base // (nt * bs), ntiles)    # tiles below the chunk
    if window:
        # the tile of the first query's first visible key
        tile0 = jnp.minimum(
            jnp.maximum(base - window + 1, 0) // (nt * bs), ntiles)
    whole = k_pool.shape[2] == ht
    heads = pl.ds(pl.program_id(1) * ht, ht)
    unroll = math.gcd(ht, _PREFILL_HEAD_UNROLL)
    # the span whose p·v each lane of acc keeps
    group = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // d

    def each_block(i, slot, act):
        """``act`` on the copies of tile ``i``'s valid blocks."""
        def one(j, carry):
            for op, (pool, buf) in enumerate(((k_pool, kbuf), (v_pool, vbuf))):
                # an operand that is this layer's rows alone (_lane_rows)
                # is a one-layer stack
                at = i * nt + j
                src = (layer if pool.shape[0] > 1 else 0,
                       bt_ref[b, at % bt_ref.shape[1] if window else at])
                act(pltpu.make_async_copy(
                    pool.at[src if whole else src + (heads,)],
                    buf.at[slot, j], sem.at[slot, op]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(n - i * nt, 0, nt), one, None)

    def attend(masked: bool):
        def tile(i, carry):
            slot = i % 2
            each_block(i + 1, 1 - slot, lambda copy: copy.start())
            each_block(i, slot, lambda copy: copy.wait())
            if masked:
                col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
                # span 0's key of each column; span h's is h*r further
                key = i * (nt * bs) + col + col // r * (bs - r)
                row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                last = base + jnp.minimum(row % t, valid - 1)

            def head(h):
                k = kbuf[slot, :, h].reshape(cols, width)
                v = vbuf[slot, :, h].reshape(cols, width)
                s = jax.lax.dot_general(
                    qs_scr[h], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                parts = [s[g * rows:(g + 1) * rows] for g in range(spans)]
                if masked and window:
                    parts = [jnp.where((key + g * r <= last)
                                       & (key + g * r > last - window),
                                       part, NEG_INF)
                             for g, part in enumerate(parts)]
                elif masked:
                    parts = [jnp.where(key + g * r <= last, part, NEG_INF)
                             for g, part in enumerate(parts)]
                # m and l stay lane-replicated [rows, 128], as the scratch
                # holds them: a [rows, 1] column would be broadcast over
                # the lanes again at each of its uses
                m_prev = m_scr[h]
                top = parts[0]
                for part in parts[1:]:
                    top = jnp.maximum(top, part)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(top, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                m_cols, pv, psum = _lanes(m_new, cols), None, None
                for g, part in enumerate(parts):
                    p = jnp.exp(part - m_cols)
                    psum = p if psum is None else psum + p
                    out = jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    pv = out if pv is None else jnp.where(group == g, out, pv)
                acc_scr[h] = acc_scr[h] * _lanes(alpha, width) + pv
                m_scr[h] = m_new
                l_scr[h] = l_scr[h] * alpha + jnp.sum(psum, axis=-1,
                                                      keepdims=True)

            def heads_at(i, carry):
                for u in range(unroll):
                    head(i * unroll + u)
                return carry

            jax.lax.fori_loop(0, ht // unroll, heads_at, None)
            return carry
        return tile

    def span_queries(h, carry):
        q = q_ref[0, h].astype(qs_scr.dtype)                    # [rows, D]
        lane = jax.lax.broadcasted_iota(jnp.int32, (d, width), 1)
        at = jax.lax.broadcasted_iota(jnp.int32, (d, width), 0)
        for g in range(spans):
            qs_scr[h, g * rows:(g + 1) * rows] = jnp.dot(
                q, (lane == at + g * d).astype(q.dtype),
                preferred_element_type=jnp.float32).astype(q.dtype)
        return carry

    def finish(h, carry):
        l, acc = l_scr[h][:, :1], acc_scr[h]
        num = acc[:, :d]
        for g in range(1, spans):
            num = num + acc[:, g * d:(g + 1) * d]
        o_ref[0, h] = (num / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        return carry

    _start_chunks(m_scr, l_scr, acc_scr)
    vbuf[...] = jnp.zeros_like(vbuf)
    if window:
        each_block(tile0, tile0 % 2, lambda copy: copy.start())
        jax.lax.fori_loop(0, ht, span_queries, None)
        jax.lax.fori_loop(tile0, ntiles, attend(True), None)
    else:
        each_block(0, 0, lambda copy: copy.start())
        jax.lax.fori_loop(0, ht, span_queries, None)
        jax.lax.fori_loop(0, clear, attend(False), None)
        jax.lax.fori_loop(clear, ntiles, attend(True), None)
    jax.lax.fori_loop(0, ht, finish, None)


def _paged_prefill_call(q, k_pool, v_pool, block_tables, q_pos, layer, valid,
                        *, sm_scale: float, interpret: bool,
                        window: int = 0):
    """The launch of :func:`_paged_prefill_kernel`, ``q`` [B, H, T, D]
    against one shard's stacked float pool at ``layer``: the decode
    kernel's operands (:func:`_paged_launch`) plus ``valid``, the landing
    tile and the head tile read off the local shapes."""
    b, h, t, d = q.shape
    _, nb, _, r_in, w_in = k_pool.shape
    pools = [_lane_rows(p, layer, d) for p in (k_pool, v_pool)]
    hkv, r, width = pools[0].shape[2:]
    rows, spans = h // hkv * t, r_in * w_in // d // r     # block_size over R
    nt = max(1, min(_PREFILL_COLS // r, block_tables.shape[1]))
    ht = _prefill_head_tile(hkv, rows, spans, nt, r, width,
                            pools[0].dtype.itemsize)
    base = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    valid = jnp.clip(valid, 0, t)
    # clipped as the decode kernel's: no table can send a DMA outside the pool
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, nb - 1)

    def row_block(n_rows, n_lanes):
        return pl.BlockSpec((1, ht, n_rows, n_lanes),
                            lambda i, j, *prefetched: (i, j, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,             # layer, base, valid, block table
        grid=(b, hkv // ht),
        in_specs=[row_block(rows, d),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_block(rows, d),
        scratch_shapes=[pltpu.VMEM((2, nt, ht, r, width), p.dtype)
                        for p in pools] + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((ht, spans * rows, width), pools[0].dtype),  # q
            pltpu.VMEM((ht, rows, LANES), jnp.float32),           # m
            pltpu.VMEM((ht, rows, LANES), jnp.float32),           # l
            pltpu.VMEM((ht, rows, width), jnp.float32),           # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_prefill_kernel, sm_scale=sm_scale, t=t,
                          spans=spans, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="paged_prefill_attn",
    )(jnp.asarray(layer, jnp.int32).reshape(1), base, valid, bt,
      q.reshape(b, hkv, rows, d), *pools).reshape(q.shape)


def paged_prefill_attention_pallas(q, k_pool, v_pool, block_tables, q_pos, *,
                                   valid=None,
                                   sm_scale: Optional[float] = None,
                                   interpret: Optional[bool] = None,
                                   layer=None, window: int = 0):
    """A prefill chunk's paged attention: q [B, H, T, D], row b's T
    positions starting at its own ``q_pos[b]`` (scalar q_pos broadcasts)
    and just written to the pool, the first ``valid[b]`` of them real
    (default all T; a row with none is a pad row and comes back zeros).
    Each row walks its own ``cdiv(base + valid, block_size)`` blocks of the
    stacked float pool at ``layer`` in-kernel (:func:`_paged_prefill_kernel`,
    as ``paged_prefill_attn``); pad queries of a real row return what its
    last real query sees.  Under a configured tp context each chip launches
    the kernel on its own head shard of q and the pool."""
    assert not is_quantized_pool(k_pool), \
        "the prefill kernel takes float pools; int8 records use the gather"
    b, t = q.shape[0], q.shape[2]
    valid = jnp.full((b,), t, jnp.int32) if valid is None \
        else jnp.asarray(valid, jnp.int32)
    return _paged_attention_pallas(_paged_prefill_call, q, k_pool, v_pool,
                                   block_tables, q_pos, sm_scale, interpret,
                                   layer, valid, window=window)


# ---------------------------------------------------------------------------
# Learned sparse attention (``ops/sparse_index_attention.py``): the indexer's
# scores over a row's valid keys, read in place out of the third pool leaf,
# the exact top-k of them without a sort, and the attention over the chosen
# keys (``paged_index_scores``, ``paged_sparse_select``, ``paged_sparse_attn``:
# the trace readers sum every kernel named ``paged_index_*`` / ``paged_sparse_*``
# as the mechanism's time).
# ---------------------------------------------------------------------------
#: blocks of indexer keys one landing tile of the scoring kernel takes
_INDEX_TILE_BLOCKS = 32
#: queries one grid step of the scoring kernel scores (its output block is
#: ``[queries, max_seq_len]`` float32)
_INDEX_QUERY_TILE = 32
#: most score columns (key rows) of a landing tile, and most query rows
#: (heads x queries) of a grid step
_INDEX_TILE_COLS = 512


def _index_scores_kernel(layer_ref, n_ref, bt_ref, q_ref, w_ref, last_ref,
                         pool, o_ref, buf, sem, *, heads: int):
    """The indexer's scores of ``tq`` queries of one row.  Grid ``(B, T //
    tq)``.

    ``layer_ref`` int32 [1], ``n_ref`` int32 [B] (the row's valid blocks)
    and ``bt_ref`` int32 [B, NBPER] arrive via scalar prefetch; the indexer's
    key pool ``[L, NB, 1, r, width]`` (one key head, ``g = width // DI``
    token spans a row) stays in HBM.  The row walks its ``n`` valid blocks
    ``nt`` a tile, one DMA a block, tile ``i + 1`` landing while tile ``i``
    is scored: ``q_ref`` [1, 1, g, heads * tq, width] holds each query head
    once per span (row ``h * tq + t``, its DI values in the span's lane
    group), so one ``q . k^T`` gives a span's ``[heads * tq, nt * r]`` dot
    products; ReLU, times ``w_ref`` [1, 1, heads * tq, 128] (the head's
    weight for that query, lane-replicated, float32), summed over the heads
    — ``heads`` slabs of ``tq`` rows.  ``o_ref`` [1, ntiles, g, tq, nt * r]
    float32: column ``j * r + x`` of tile ``i``, span ``s`` is token
    ``(i * nt + j) * bs + s * r + x``; a key past ``last_ref`` [1, 1, tq,
    128] (per query, lane-replicated) scores ``-inf`` — which also hides
    the slots of a last, partly landed tile: a column's score depends on
    its own key alone — and so does every tile the walk never reached."""
    _, nt, r, width = buf.shape
    g = q_ref.shape[2]
    tq = o_ref.shape[3]
    bs, cols = r * g, nt * r
    b, layer = pl.program_id(0), layer_ref[0]
    n = jnp.clip(n_ref[b], 0, bt_ref.shape[1])
    ntiles = (n + nt - 1) // nt
    w = _lanes(w_ref[0, 0], cols)
    last = _lanes(last_ref[0, 0], cols)

    def each_block(i, slot, act):
        def one(j, carry):
            act(pltpu.make_async_copy(
                pool.at[layer if pool.shape[0] > 1 else 0,
                        bt_ref[b, i * nt + j], 0],
                buf.at[slot, j], sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(n - i * nt, 0, nt), one, None)

    def tile(i, carry):
        slot = i % 2
        each_block(i + 1, 1 - slot, lambda copy: copy.start())
        each_block(i, slot, lambda copy: copy.wait())
        k = buf[slot].reshape(cols, width)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        # span 0's token of each column; span s's is s * r further
        token = i * (nt * bs) + col + col // r * (bs - r)
        for span in range(g):
            dots = jax.lax.dot_general(
                q_ref[0, 0, span], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dots = jnp.maximum(dots, 0.0) * w
            score = dots[:tq]
            for h in range(1, heads):
                score = score + dots[h * tq:(h + 1) * tq]
            o_ref[0, i, span] = jnp.where(token + span * r <= last, score,
                                          -jnp.inf)
        return carry

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)
    each_block(0, 0, lambda copy: copy.start())
    jax.lax.fori_loop(0, ntiles, tile, None)


def paged_index_scores_pallas(qi, wi, idx_pool, block_tables, last, *,
                              layer, interpret: Optional[bool] = None):
    """``I[b, t, s] = sum_h wi[b, t, h] * relu(qi[b, h, t] . kI[b, s])`` in
    float32 for every key of each row's table, ``-inf`` past ``last[b, t]``
    (int32 ``[B, T]``: the last key the query may see): ``[B, T,
    max_seq_len]``.  ``qi [B, HI, T, DI]``, ``wi [B, T, HI]``; ``idx_pool``
    the stacked float pool leaf of the indexer's keys ``[L, NB, 1, bs, DI]``
    (either view) at ``layer``, read in place: each row walks its own
    ``cdiv(max last + 1, bs)`` blocks (:func:`_index_scores_kernel`, as
    ``paged_index_scores``)."""
    b, hi, t, di = qi.shape
    if interpret is None:
        interpret = interpret_kernels()
    _, nb, _, r_in, w_in = idx_pool.shape
    pool = _lane_rows(idx_pool, layer, di)
    r, width = pool.shape[3:]
    bs = r_in * w_in // di
    g = bs // r
    nbper = block_tables.shape[1]
    # (a block of 32 keys two a lane row, 16 heads: 32 blocks and 32 queries
    # — 512 x 512 dot products a span; wider blocks and more heads take
    # fewer of each, so that a step's products stay that size)
    nt = max(1, min(_INDEX_TILE_BLOCKS, _INDEX_TILE_COLS // r, nbper))
    while nbper % nt:
        nt -= 1
    ntiles, cols = nbper // nt, nt * r
    tq = next((c for c in (_INDEX_QUERY_TILE, 16, 8)
               if t % c == 0 and (c * hi <= _INDEX_TILE_COLS or c == 8)), t)
    tt = t // tq
    # queries once per span, head-major rows within a query tile
    qx = qi.reshape(b, hi, tt, tq, di).transpose(0, 2, 1, 3, 4) \
        .reshape(b, tt, 1, hi * tq, di).astype(pool.dtype)
    qx = jnp.concatenate(
        [jnp.pad(qx, ((0, 0),) * 4 + ((s * di, width - (s + 1) * di),))
         for s in range(g)], axis=2)                   # [B, tt, g, hi*tq, W]
    wx = jnp.broadcast_to(
        wi.astype(jnp.float32).reshape(b, tt, tq, hi).transpose(0, 1, 3, 2)
        .reshape(b, tt, hi * tq, 1), (b, tt, hi * tq, LANES))
    lx = jnp.broadcast_to(last.astype(jnp.int32).reshape(b, tt, tq, 1),
                          (b, tt, tq, LANES))
    n = jnp.clip((jnp.max(last, axis=1) + bs) // bs, 0, nbper)
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, nb - 1)

    def block(shape):
        return pl.BlockSpec((1, 1) + shape,
                            lambda i, j, *prefetched: (i, j) + (0,) * len(
                                shape))

    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, heads=hi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                 # layer, blocks, table
            grid=(b, tt),
            in_specs=[block((g, hi * tq, width)), block((hi * tq, LANES)),
                      block((tq, LANES)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (1, ntiles, g, tq, cols),
                lambda i, j, *prefetched: (i, 0, 0, j, 0)),
            scratch_shapes=[pltpu.VMEM((2, nt, r, width), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, ntiles, g, t, cols),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="paged_index_scores",
    )(jnp.asarray(layer, jnp.int32).reshape(1), n.astype(jnp.int32), bt,
      qx, wx, lx, pool)
    # [B, tile, span, T, (block, row)] -> token order
    return out.reshape(b, ntiles, g, t, nt, r).transpose(0, 3, 1, 4, 2, 5) \
        .reshape(b, t, nbper * bs)


_INT_MIN = -(2 ** 31)


def _sparse_select_kernel(s_ref, theta_ref, last_ref, *, topk: int,
                          bits: int):
    """The exact top-``topk`` of each row of ``s_ref`` [tn, S] float32, as
    a threshold: ``theta_ref`` the ``topk``-th largest value and
    ``last_ref`` the position of the last entry a stable largest-first
    order takes (both [tn, 128], lane-replicated).  No sort: float32 bits
    map to int32 keys of the same order, the threshold key is built bit by
    bit from the top (a bit stays set while at least ``topk`` keys reach the
    candidate: 32 counts), and among the keys equal to it the
    ``topk - count(greater)``-th position, likewise (``bits`` counts)."""
    x = s_ref[...]
    x = jnp.where(x == 0.0, 0.0, x)                   # -0.0 ties with 0.0
    u = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(u < 0, u ^ jnp.int32(0x7FFFFFFF), u)
    pos = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    want = jnp.float32(topk)

    def count(mask):
        return jnp.sum(mask.astype(jnp.float32), axis=-1, keepdims=True)

    t = jnp.where(count(key >= 0) >= want, jnp.int32(0), jnp.int32(_INT_MIN))

    def value_bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(key >= cand) >= want, cand, t)

    t = jax.lax.fori_loop(0, 31, value_bit, t)
    need = want - count(key > t)                      # of the ties, >= 1
    tie = key == t

    def position_bit(i, p):
        cand = p | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(count(tie & (pos < cand)) < need, cand, p)

    p = jax.lax.fori_loop(0, bits, position_bit, jnp.zeros_like(t))
    theta = jax.lax.bitcast_convert_type(
        jnp.where(t < 0, t ^ jnp.int32(0x7FFFFFFF), t), jnp.float32)
    theta_ref[...] = jnp.broadcast_to(theta, theta_ref.shape)
    last_ref[...] = jnp.broadcast_to(p, last_ref.shape)


def paged_sparse_select_pallas(scores, topk: int, *,
                               interpret: Optional[bool] = None):
    """``(theta, s_last)`` of float32 ``scores [..., S]`` (``S >= topk``):
    the ``topk``-th largest value of each row and the position of the last
    entry taken when equal values go by position, so that entry ``s`` is
    among the row's top ``topk`` iff ``score > theta or (score == theta and
    s <= s_last)`` — what ``lax.top_k`` gives as its last value and index
    (:func:`_sparse_select_kernel`, as ``paged_sparse_select``)."""
    *lead, s = scores.shape
    assert s >= topk, (s, topk)
    if interpret is None:
        interpret = interpret_kernels()
    flat = scores.reshape(-1, s).astype(jnp.float32)
    n = flat.shape[0]
    tn = next((t for t in (16, 8) if n % t == 0), n)
    theta, last = pl.pallas_call(
        functools.partial(_sparse_select_kernel, topk=topk,
                          bits=max(1, (s - 1).bit_length())),
        grid=(n // tn,),
        in_specs=[pl.BlockSpec((tn, s), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tn, LANES), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((n, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((n, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="paged_sparse_select",
    )(flat)
    return theta[:, 0].reshape(lead), last[:, 0].reshape(lead)


#: keys one softmax update of the sparse read takes (whole blocks).  One
#: layer's read alone on the chip, dispatch (~0.9 ms) included, at 256 / 512
#: / 1,024 keys: 1.43 / 1.25 / 1.14 ms for 16 decode rows of ~7,700 keys,
#: 1.76 / 1.53 / 1.42 ms for a [4, 128] chunk (PERF.md PR 32)
_SPARSE_COLS = 512
#: (KV head, query slab) pairs the sparse read attends in one iteration of
#: its loop (:data:`_PREFILL_HEAD_UNROLL` has why)
_SPARSE_UNROLL = 4


def _paged_sparse_kernel(layer_ref, n_ref, bt_ref, hit_ref, q_ref, theta_ref,
                         slast_ref, last_ref, scores, k_pool, v_pool, o_ref,
                         kbuf, vbuf, sbuf, sem, m_scr, l_scr, acc_scr, *,
                         sm_scale: float, t: int):
    """Attention of one row's ``t`` queries over the keys each has CHOSEN
    (learned sparse attention), for ``ht`` KV heads.  Grid ``(B, HKV //
    ht)``.

    ``layer_ref`` int32 [1], ``n_ref`` int32 [B] (the row's valid blocks),
    ``bt_ref`` int32 [B, NBPER] and ``hit_ref`` int32 [B, NBPER] (whether
    any query of the row chose a key of the block) arrive via scalar
    prefetch; the K and V pools ``[L, NB, HKV, bs, D]`` and the indexer's
    ``scores`` [B, t, NBPER * bs] float32 stay in HBM.  The row walks its
    ``n`` valid blocks ``nt`` a tile and copies, of each tile, the blocks
    with a hit (one DMA of all ``ht`` heads a block, K and V) and the
    tile's ``[t, nt * bs]`` slab of scores; tile ``i + 1`` lands while tile
    ``i`` is attended.  A block no query chose is neither read nor — where
    the whole tile has none — attended: the K/V bytes read are those of
    the blocks that hold a chosen key.

    The set is rebuilt from the scores, a tile at a time: query ``i``
    keeps key ``s`` iff ``s <= last[i]`` and ``score > theta[i]`` or
    ``score == theta[i]`` and ``s <= s_last[i]`` (``theta_ref`` /
    ``slast_ref`` / ``last_ref`` [1, t, 128], lane-replicated:
    ``paged_sparse_select``'s threshold and the last key the query may
    see), which is ``sparse_index_attention.chosen``.  A slot of the landing
    buffers that was not copied holds an earlier block or the zeros of the
    start: every key of it is masked.

    ``q_ref`` / ``o_ref`` [1, ht, rows, D], ``rows = rep * t``, row ``r * t
    + i`` head ``r`` of the KV group at query ``i``: with ``t`` a multiple
    of 8 a head's rows are attended ``t`` at a time (a slab: one
    ``[t, nt * bs]`` mask serves each), a single query (``t == 1``) all
    ``rep`` at once under the one mask row.  Online softmax in float32, m
    and l lane-replicated, the probabilities to the MXU in the pool's
    dtype."""
    _, nt, ht, bs, d = kbuf.shape
    rows = o_ref.shape[2]
    cols = nt * bs
    slabs = rows // t if t > 1 else 1
    slab = rows // slabs
    b, layer = pl.program_id(0), layer_ref[0]
    n = jnp.clip(n_ref[b], 0, bt_ref.shape[1])
    ntiles = (n + nt - 1) // nt
    whole = k_pool.shape[2] == ht
    heads = pl.ds(pl.program_id(1) * ht, ht)
    unroll = math.gcd(ht * slabs, _SPARSE_UNROLL)
    theta = _lanes(theta_ref[0], cols)
    slast = _lanes(slast_ref[0], cols)
    last = _lanes(last_ref[0], cols)

    def each_copy(i, slot, act):
        """``act`` on tile ``i``'s copies: its blocks with a hit, its
        scores."""
        def one(j, hits):
            hit = hit_ref[b, i * nt + j]

            @pl.when(hit > 0)
            def _block():
                for op, (pool, buf) in enumerate(((k_pool, kbuf),
                                                  (v_pool, vbuf))):
                    src = (layer, bt_ref[b, i * nt + j])
                    act(pltpu.make_async_copy(
                        pool.at[src if whole else src + (heads,)],
                        buf.at[slot, j], sem.at[slot, op]))
            return hits + hit

        hits = jax.lax.fori_loop(0, jnp.clip(n - i * nt, 0, nt), one,
                                 jnp.int32(0))

        @pl.when(i < ntiles)
        def _scores():
            act(pltpu.make_async_copy(
                scores.at[b, :, pl.ds(pl.multiple_of(i * cols, cols), cols)],
                sbuf.at[slot], sem.at[slot, 2]))
        return hits

    def tile(i, carry):
        slot = i % 2
        each_copy(i + 1, 1 - slot, lambda copy: copy.start())
        hits = each_copy(i, slot, lambda copy: copy.wait())

        @pl.when(hits > 0)
        def _attend():
            sc = sbuf[slot]
            key = i * cols + jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
            keep = (key <= last) & ((sc > theta)
                                    | ((sc == theta) & (key <= slast)))

            def one(idx):
                h = idx // slabs
                at = pl.ds(pl.multiple_of(idx % slabs * slab, slab), slab) \
                    if slabs > 1 else slice(None)
                k = kbuf[slot, :, h].reshape(cols, d)
                v = vbuf[slot, :, h].reshape(cols, d)
                s = jax.lax.dot_general(
                    q_ref[0, h, at].astype(k.dtype), k,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                s = jnp.where(keep, s, NEG_INF)
                m_prev = m_scr[h, at]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # a query with no key kept so far has m = NEG_INF, where
                # exp(s - m) is 1: the mask, not the exponent, zeroes it
                p = jnp.where(keep, jnp.exp(s - _lanes(m_new, cols)), 0.0)
                acc_scr[h, at] = acc_scr[h, at] * _lanes(alpha, d) \
                    + jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_scr[h, at] = m_new
                l_scr[h, at] = l_scr[h, at] * alpha \
                    + jnp.sum(p, axis=-1, keepdims=True)

            def some(u, carry):
                for x in range(unroll):
                    one(u * unroll + x)
                return carry

            jax.lax.fori_loop(0, ht * slabs // unroll, some, None)
        return carry

    def finish(h, carry):
        l = l_scr[h][:, :1]
        o_ref[0, h] = (acc_scr[h] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)
        return carry

    _start_chunks(m_scr, l_scr, acc_scr)
    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)
    each_copy(0, 0, lambda copy: copy.start())
    jax.lax.fori_loop(0, ntiles, tile, None)
    jax.lax.fori_loop(0, ht, finish, None)


def paged_sparse_attention_pallas(q, k_pool, v_pool, block_tables, scores,
                                  theta, s_last, last, hit, *, layer,
                                  sm_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None):
    """The read of learned sparse attention: ``q [B, H, T, D]`` (``T`` 1 or
    a multiple of 8) over the keys each query chose, out of the stacked
    float pool ``[L, NB, HKV, bs, D]`` at ``layer`` (a head a lane row: ``D``
    a multiple of 128), read in place.  ``scores [B, T, NBPER * bs]``
    float32 (``paged_index_scores``), ``theta`` / ``s_last [B, T]``
    (``paged_sparse_select``) and ``last [B, T]`` (the last key a query may
    see; -1: a pad row, which comes back zeros) say which keys; ``hit``
    int32 ``[B, NBPER]`` which blocks hold one — the others are not read
    (:func:`_paged_sparse_kernel`, as ``paged_sparse_attn``)."""
    b, h, t, d = q.shape
    _, nb, hkv, bs, width = k_pool.shape
    assert width == d and (t == 1 or t % 8 == 0), (k_pool.shape, q.shape)
    if interpret is None:
        interpret = interpret_kernels()
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    nbper = block_tables.shape[1]
    nt = max(1, min(_SPARSE_COLS // bs, nbper))
    while nbper % nt:
        nt -= 1
    rows = h // hkv * t
    ht = _prefill_head_tile(hkv, rows, 1, nt, bs, d, k_pool.dtype.itemsize)
    n = jnp.clip((jnp.max(last, axis=1) + bs) // bs, 0, nbper)
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, nb - 1)

    def lanes(x, dtype):
        return jnp.broadcast_to(x.astype(dtype)[..., None], (b, t, LANES))

    def row_block(n_rows, n_lanes):
        return pl.BlockSpec((1, ht, n_rows, n_lanes),
                            lambda i, j, *prefetched: (i, j, 0, 0))

    per_query = pl.BlockSpec((1, t, LANES), lambda i, j, *prefetched:
                             (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,               # layer, blocks, table, hits
        grid=(b, hkv // ht),
        in_specs=[row_block(rows, d), per_query, per_query, per_query]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=row_block(rows, d),
        scratch_shapes=[
            pltpu.VMEM((2, nt, ht, bs, d), k_pool.dtype),
            pltpu.VMEM((2, nt, ht, bs, d), v_pool.dtype),
            pltpu.VMEM((2, t, nt * bs), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.VMEM((ht, rows, LANES), jnp.float32),           # m
            pltpu.VMEM((ht, rows, LANES), jnp.float32),           # l
            pltpu.VMEM((ht, rows, d), jnp.float32),               # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_sparse_kernel, sm_scale=scale, t=t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="paged_sparse_attn",
    )(jnp.asarray(layer, jnp.int32).reshape(1), n.astype(jnp.int32), bt,
      jnp.asarray(hit, jnp.int32), q.reshape(b, hkv, rows, d),
      lanes(theta, jnp.float32), lanes(s_last, jnp.int32),
      lanes(last, jnp.int32), scores.astype(jnp.float32), k_pool,
      v_pool).reshape(q.shape)


# ---------------------------------------------------------------------------
# Latent attention (MLA), absorbed: every head's query, taken into latent
# space, scores the ONE ``[c | k_r]`` tile a block holds, and the value is a
# slice of the same tile (``ops/paged_kv.py`` "The latent kind").
# ---------------------------------------------------------------------------
#: query POSITIONS one grid step of the latent kernel takes (times the
#: heads: 512 query rows at 32 heads — a ``[512, 384] x [384, bs]`` score
#: matmul a block, 0.5 MB of float32 scores at 256 keys a block)
_LATENT_QUERY_TILE = 16
#: and most query ROWS a step (both latent families of 32 heads: 16
#: positions; 128 heads take 4 — a step's query tile, its output and its
#: float32 accumulator are ``rows x W`` each and share 16 MiB of VMEM)
_LATENT_QUERY_ROWS = 512


def paged_latent_attention_reference(q, pool, block_tables, q_pos, *,
                                     rank: int, layer=None, window: int = 0,
                                     valid=None, keep=None):
    """Gather-based absorbed latent attention (pure XLA): the CPU path and
    the tests' oracle.

    q:            [B, H, T, W] — each head's query in latent space beside
                  its rotated part, ``[q_n W_uk | q_r]``, zero-padded to the
                  pool's width, the softmax scale already on it
    pool:         the latent leaf [L, NB, 1, block_size, W] + ``layer`` (or
                  one layer's [NB, 1, block_size, W] with ``layer=None``)
    block_tables: int32 [B, NBPER]
    q_pos:        scalar or int32 [B] — global position of q[:, :, 0]
    window:       a sliding-window layer's reach (0: none): the table is
                  the window kind's RING (``ops/paged_kv.py`` "Layer
                  kinds": entry ``e`` holds the newest logical block ``i <=
                  last`` with ``i % R == e``) and a query keeps its
                  ``window`` newest keys, itself included; ``valid`` int32
                  [B] then says how many of the T queries are real
                  (default all): the ring holds the blocks up to the last
                  real one
    keep:         bool [B, T, S] — the keys each query attends (a learned
                  selection), under the causal mask; default all
    -> [B, H, T, rank]: ``softmax(q . tile^T) . tile[:, :rank]``, a query
    at position ``p`` keeping keys ``<= p``."""
    pool, layer = paged_kv.whole_pool(pool, layer)
    b, _, t, w = q.shape
    bt = jnp.asarray(block_tables, jnp.int32)
    lat = _paged_gather(pool, bt, layer, w)[:, 0]                # [B, S, W]
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    query = (pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :])[:, :,
                                                                     None]
    key = jnp.arange(lat.shape[1], dtype=jnp.int32)[None, None, :]
    if window:
        ring, bs = bt.shape[1], lat.shape[1] // bt.shape[1]
        nvalid = jnp.full((b,), t, jnp.int32) if valid is None \
            else jnp.clip(jnp.asarray(valid, jnp.int32), 0, t)
        last = (pos + jnp.maximum(nvalid, 1) - 1) // bs
        entry = jnp.arange(ring, dtype=jnp.int32)
        li = last[:, None] - (last[:, None] - entry[None, :]) % ring
        key = (li[:, :, None] * bs
               + jnp.arange(bs, dtype=jnp.int32)).reshape(b, 1, ring * bs)
        mask = (key >= 0) & (key <= query) & (key > query - window)
    else:
        mask = key <= query
    if keep is not None:
        mask = mask & keep
    scores = jnp.einsum("bhtw,bsw->bhts", q, lat).astype(jnp.float32)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bsc->bhtc", probs, lat[..., :rank])


#: VMEM the latent walk gives a TILE: its two slots of landed blocks and the
#: scores of one softmax update over them (:func:`latent_tile_blocks`)
_LATENT_VMEM_BUDGET = 5 << 20

#: most blocks a tile of the latent walk holds, and most query rows x blocks:
#: the update's text exists once a WIDTH (``1 .. nt`` blocks), each width's
#: two products unrolled over its query rows x keys, and a program that is
#: compiled at a start pays for that text — a decode tile of 8 and a prefill
#: tile of 4 added 11 s to the 127 s of a start that compiles ten of these
#: kernels (my chip runs, PR 58, ``kimilinear-statedecode-closed``).  What a
#: wider tile buys ends here anyway.  The kernel alone (my chip runs, PR 58;
#: us a BLOCK, rows of 16 blocks in calls of 64; one block a visit before):
#: Mistral Small 4's ``[512, 384]`` blocks at a tile of 1 / 2 / 4 / 8 blocks
#: 0.79 / 0.59 / 0.52 / 0.53 (0.83; the copy alone is 0.48), Kimi Linear's
#: ``[256, 640]`` 0.72 / 0.52 / 0.44 / 0.43 (0.75; 0.40); a ``[1, 512]``
#: chunk over 16 blocks (512 query rows a step) 1,460 / 1,196 / 1,196 us at
#: 1 / 2 / 4 (1,437), Kimi's 1,339 / 1,080 / 1,014 (1,330)
_LATENT_TILE_MAX = 4
_LATENT_TILE_ROWS = 1024


def latent_tile_blocks(rows: int, bs: int, w: int, itemsize: int,
                       nbper: int) -> int:
    """Blocks one loop iteration of the latent walk lands and attends — its
    TILE — from the shapes alone: the most whose two landing slots (``bs x
    w`` values a block) and whose scores for ``rows`` query rows (float32,
    and ``p`` again in the pool's dtype for the second product) stay inside
    :data:`_LATENT_VMEM_BUDGET`, never more than a row's table holds, than
    :data:`_LATENT_TILE_MAX`, or than gives ``rows`` x blocks
    :data:`_LATENT_TILE_ROWS`.  So the tile follows the query rows: a decode
    step (``rows`` = the heads) takes 4 blocks, a prefill step (16 positions
    of every head) 2."""
    a_block = bs * (2 * w * itemsize + rows * (4 + itemsize))
    return max(1, min(_LATENT_VMEM_BUDGET // a_block, _LATENT_TILE_MAX,
                      _LATENT_TILE_ROWS // rows, nbper))


def latent_walk_shape(h: int, t: int, bs: int, w: int, itemsize: int,
                      nbper: int) -> Tuple[int, int]:
    """``(tq, nt)`` of the latent walk for ``h`` heads over a window of
    ``t`` positions: the query positions a grid step takes and the blocks
    its loop iterations land (:func:`latent_tile_blocks` at ``tq * h``
    query rows).  The launcher's arithmetic, and what the serving engine
    counts a call's walks with."""
    tq = min(t, _LATENT_QUERY_TILE, max(1, _LATENT_QUERY_ROWS // h))
    return tq, latent_tile_blocks(tq * h, bs, w, itemsize, nbper)


def _paged_latent_kernel(layer_ref, pos_ref, valid_ref, bt_ref, q_ref,
                         pool_ref, o_ref, buf, sem, slot_ref, m_scr, l_scr,
                         acc_scr, *, heads: int, tq: int, rank: int,
                         window: int = 0):
    """The latent kind's decode (``T == 1``), verify and prefill kernel.
    Grid ``(B, T / tq)``: one step is ``tq`` query positions of one row,
    all ``heads`` of them — ``rows = tq * heads`` query rows, row ``r`` the
    head ``r % heads`` at window offset ``j * tq + r // heads`` — against
    the row's VALID blocks, walked as :func:`_paged_walk_kernel` walks them:
    ``layer_ref`` int32 [1], ``pos_ref`` / ``valid_ref`` int32 [B] (the
    window's first position, its real queries) and ``bt_ref`` int32 [B,
    NBPER] by scalar prefetch; the pool stays in HBM and block ``i`` is ONE
    copy, ``pool.at[layer, bt[b, i]]`` -> its place in its TILE of up to
    ``nt`` blocks (:func:`latent_tile_blocks`; read here off the landing
    buffer's shape ``[2, nt, bs, W]``).  A tile's copies fly together, on
    one semaphore a slot, while the tile before is attended.  A step walks
    the blocks up to its own last real query, ``n = cdiv(base + min((j + 1)
    * tq, valid), bs)`` — none if its queries are all pad — in ``cdiv(n,
    nt)`` tiles of near-equal size (:func:`tile`).

    A loop iteration is ONE online-softmax update over its tile's landed
    keys, the blocks one under the other: ``q [rows, W] . tile^T`` are the
    scores of every head (the pad lanes meet zeros), ``p . tile[:, :rank]``
    the output in latent space — the tile is read ONCE for both sides — and
    ``m`` / ``l`` / ``acc`` are read and written once a tile, whatever
    ``nt``.  The update's text exists once a WIDTH (``1 .. nt`` blocks,
    chosen by the blocks the tile holds): a tile of fewer than ``nt`` blocks
    is attended at the blocks it holds, so the slots that were not copied —
    whatever an earlier step or nothing at all left there — are never read,
    and a short tile costs its own keys' products.  The mask is per query
    row (``key <= base + offset``).  Matmuls take the pool's dtype in and
    float32 out.

    The two slots carry a tile ACROSS grid steps, as the plain walk's do
    (the grid runs in order: ``arbitrary``): a step's last tile starts tile
    0 of the NEXT step — the row's next query tile, or the next row — into
    the slot it does not hold, and that step starts nothing for its tile 0
    and only waits, both from :func:`tile` of the step's :func:`reach`.  A step of
    no block passes the duty on; a launch's first step starts its own, the
    last starts nothing.  The slot of a step's tile 0 follows the tiles
    walked before it (``slot_ref``, SMEM scratch).

    ``window`` (static; 0: none, and the text above is the whole kernel): a
    sliding-window layer over its kind's RING table (``ops/paged_kv.py``
    "Layer kinds": logical block ``i`` at entry ``i % R``).  A step then
    walks from the block of its FIRST query's oldest key on, ``lo = max(base
    + j * tq - window + 1, 0) // bs``, and a query row keeps ``key > its
    position - window`` too: a [1, 512] chunk under a 513-key window reads
    ~5 blocks a step whatever the row's length."""
    b, j = pl.program_id(0), pl.program_id(1)
    per_row = pl.num_programs(1)
    steps = pl.num_programs(0) * per_row
    layer, base = layer_ref[0], pos_ref[b]
    _, nt, bs, _ = buf.shape
    first = j * tq

    def reach(step):
        """``(row, n)`` of grid step ``step`` (row-major over the grid; one
        past the last: no block): it walks blocks ``0 .. n - 1`` of
        ``row``.  THE arithmetic of a step's copies, for the step that
        starts them and the step that waits for them."""
        row = jnp.minimum(step // per_row, pl.num_programs(0) - 1)
        at = step % per_row * tq
        last = jnp.minimum(at + tq, valid_ref[row]) - 1
        if window:
            # ``(row, blocks, first block)``: the ring holds every block
            # from the oldest key its first query keeps to its last query's
            lo = jnp.maximum(pos_ref[row] + at - window + 1, 0) // bs
            return row, jnp.where(
                (last >= at) & (step < steps),
                (pos_ref[row] + last + bs) // bs - lo, 0), lo
        return row, jnp.where(
            (last >= at) & (step < steps),
            jnp.clip((pos_ref[row] + last + bs) // bs, 0, bt_ref.shape[1]),
            0)

    def tile(of, i):
        """Tile ``i`` of the step whose :func:`reach` is ``of``, as what
        its copies need: ``(row, first block, blocks the step holds of
        it)`` — 0 past its last tile.  A step's ``n`` blocks go in
        ``cdiv(n, nt)`` tiles of near-EQUAL size (the first ``n % tiles``
        hold one more): with two slots a tile's copies fly under the update
        of the tile before, so a short tile behind a long one leaves the
        copy queue idle, and a long one behind a short one is waited
        for."""
        row, n, *lo = of
        tiles = (n + nt - 1) // nt
        per = n // jnp.maximum(tiles, 1)
        more = n - per * tiles
        start = i * per + jnp.minimum(i, more)
        return (row, start + lo[0] if window else start,
                jnp.where(i < tiles, per + (i < more), 0))

    def each_block(of, slot, act):
        """``act`` on the copies of the valid blocks of the tile ``of``."""
        row, start, held = of

        def one(k, carry):
            at = (start + k) % bt_ref.shape[1] if window else start + k
            act(pltpu.make_async_copy(
                pool_ref.at[layer, bt_ref[row, at], 0],
                buf.at[slot, k], sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, held, one, None)

    def choose(pred, a, b):
        return tuple(jnp.where(pred, x, y) for x, y in zip(a, b))

    step = b * per_row + j
    here = reach(step)
    tiles = (here[1] + nt - 1) // nt
    ahead = tile(reach(step + 1), 0)

    # a launch's first step starts its own tile 0; every other step finds
    # it started (the step before's ``ahead``) and only waits.  A step of
    # no tile passes the duty on here, where no loop iteration does
    @pl.when(step == 0)
    def _():
        slot_ref[0] = 0

    slot0 = slot_ref[0]
    *which, held = choose(tiles > 0, tile(here, 0), ahead)
    each_block((*which, jnp.where((step == 0) | (tiles == 0), held, 0)),
               slot0, lambda copy: copy.start())

    def attend(i, carry):
        slot = (slot0 + i) % 2
        # the tile after this one lands meanwhile: the step's next, or, on
        # its last, tile 0 of the NEXT grid step
        each_block(choose(i + 1 < tiles, tile(here, i + 1), ahead),
                   1 - slot, lambda copy: copy.start())
        mine = tile(here, i)
        each_block(mine, slot, lambda copy: copy.wait())

        def update(held):
            """ONE online-softmax update over the tile's first ``held``
            (static) blocks, one under the other."""
            keys = buf[slot, :held].reshape(held * bs, buf.shape[3])
            s = jax.lax.dot_general(q_ref[0], keys, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            key = mine[1] * bs + jax.lax.broadcasted_iota(jnp.int32,
                                                          s.shape, 1)
            query = base + first + row // heads
            s = jnp.where((key <= query) & (key > query - window)
                          if window else key <= query, s, NEG_INF)
            m_prev = m_scr[...][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                     # [rows, held * bs]
            l_new = l_scr[...][:, :1] * alpha + jnp.sum(p, axis=-1,
                                                        keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
                p.astype(keys.dtype), keys[:, :rank],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        # the update exists once a width: a tile of fewer than ``nt``
        # blocks is attended at the blocks it holds, so no product and no
        # mask ever meets a slot that was not copied
        for held in range(1, nt + 1):
            pl.when(mine[2] == held)(functools.partial(update, held))
        return carry

    _start_chunks(m_scr, l_scr, acc_scr)
    jax.lax.fori_loop(0, tiles, attend, None)
    slot_ref[0] = (slot0 + tiles) % 2
    den = l_scr[...][:, :1]
    o_ref[0] = (acc_scr[...] / jnp.where(den == 0.0, 1.0, den)) \
        .astype(o_ref.dtype)


def latent_kernel_name(t: int, window: int = 0) -> str:
    """The name the latent kernel is launched under for a window of ``t``
    query positions (one reader sums ``paged_latent_*``); a sliding-window
    layer's launches (``window``) have names of their own, a decode step's
    and a chunk's (such a model is served no verify window)."""
    if window:
        return "paged_window_latent_attn" if t == 1 \
            else "paged_window_latent_prefill"
    return "paged_latent_attn" if t == 1 else \
        "paged_latent_verify" if t <= VERIFY_T_MAX else "paged_latent_prefill"


# one ``pl.pallas_call`` site a name: ``name=latent_kernel_name(t)`` at one
# site would do for the trace, but
# ``tests/chipbench/test_program_span_metrics.py`` requires every site's
# ``name=`` to be a string constant it can read from the source (as
# ``_paged_decode_call`` / ``_paged_verify_call`` above)
def _latent_attn_call(kernel, call, operands):
    return pl.pallas_call(kernel, name="paged_latent_attn", **call)(*operands)


def _latent_verify_call(kernel, call, operands):
    return pl.pallas_call(kernel, name="paged_latent_verify",
                          **call)(*operands)


def _latent_prefill_call(kernel, call, operands):
    return pl.pallas_call(kernel, name="paged_latent_prefill",
                          **call)(*operands)


def _window_latent_attn_call(kernel, call, operands):
    return pl.pallas_call(kernel, name="paged_window_latent_attn",
                          **call)(*operands)


def _window_latent_prefill_call(kernel, call, operands):
    return pl.pallas_call(kernel, name="paged_window_latent_prefill",
                          **call)(*operands)


_LATENT_CALLS = {"paged_latent_attn": _latent_attn_call,
                 "paged_latent_verify": _latent_verify_call,
                 "paged_latent_prefill": _latent_prefill_call,
                 "paged_window_latent_attn": _window_latent_attn_call,
                 "paged_window_latent_prefill": _window_latent_prefill_call}


def paged_latent_attention_pallas(q, pool, block_tables, q_pos, *, rank: int,
                                  layer=None, valid=None, window: int = 0,
                                  interpret: Optional[bool] = None):
    """:func:`paged_latent_attention_reference`'s contract through
    :func:`_paged_latent_kernel`, on one shard, for any ``T`` (padded to
    whole query tiles); ``valid`` int32 [B]: how many of the ``T`` queries
    are real (default all — a pad query's output is unspecified);
    ``window``: a sliding-window layer over its ring table."""
    pool, layer = paged_kv.whole_pool(pool, layer)
    b, h, t, w = q.shape
    nb, bs = pool.shape[1], pool.shape[3]
    assert pool.shape[2] == 1 and pool.shape[4] == w and w % LANES == 0, \
        f"latent pool {pool.shape} against queries {q.shape}"
    if interpret is None:
        interpret = interpret_kernels()
    tq, nt = latent_walk_shape(h, t, bs, w, pool.dtype.itemsize,
                               block_tables.shape[1])
    tp = -(-t // tq) * tq
    rows = tq * h
    qq = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, tp - t), (0, 0),
                                           (0, 0)))
    qq = qq.reshape(b, tp * h, w).astype(pool.dtype)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    nvalid = jnp.full((b,), t, jnp.int32) if valid is None \
        else jnp.clip(jnp.asarray(valid, jnp.int32), 0, t)
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, nb - 1)

    def tile(lanes):
        return pl.BlockSpec((1, rows, lanes),
                            lambda i, j, *prefetched: (i, j, 0))

    call = dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,          # layer, pos, valid, block table
            grid=(b, tp // tq),
            in_specs=[tile(w), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile(rank),
            scratch_shapes=[
                pltpu.VMEM((2, nt, bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),          # the slot of tile 0
                pltpu.VMEM((rows, LANES), jnp.float32),           # m
                pltpu.VMEM((rows, LANES), jnp.float32),           # l
                pltpu.VMEM((rows, rank), jnp.float32)]),          # acc
        out_shape=jax.ShapeDtypeStruct((b, tp * h, rank), q.dtype),
        # a tile is carried from a grid step to the next: the steps run in
        # order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret)
    out = _LATENT_CALLS[latent_kernel_name(t, window)](
        functools.partial(_paged_latent_kernel, heads=h, tq=tq, rank=rank,
                          **({"window": window} if window else {})),
        call, (jnp.asarray(layer, jnp.int32).reshape(1), pos, nvalid, bt, qq,
               pool))
    return out.reshape(b, tp, h, rank)[:, :t].transpose(0, 2, 1, 3)


def paged_latent_attention(q, pool, block_tables, q_pos, *, rank: int,
                           layer=None, valid=None, window: int = 0):
    """Dispatch: the walking kernel on a TPU (decode, verify window and
    prefill chunk alike, :func:`latent_kernel_name`), gather + XLA
    otherwise.  ``window`` (static): a sliding-window layer's reach over its
    kind's ring table; 0 is the program it always was.  One shard over a
    float pool, outside a resident-window context: the serving engine
    refuses the rest by name."""
    if is_quantized_pool(pool) or window_state() is not None \
            or paged_kv.tp_mesh() is not None or paged_kv.dp_groups() > 1:
        raise NotImplementedError(
            "the latent kind is read from a float pool on one shard, "
            "outside a resident-window context")
    if on_tpu():
        _took(latent_kernel_name(q.shape[2], window))
        return paged_latent_attention_pallas(q, pool, block_tables, q_pos,
                                             rank=rank, layer=layer,
                                             valid=valid, window=window)
    _took("window_latent_gather" if window else "latent_gather")
    return paged_latent_attention_reference(q, pool, block_tables, q_pos,
                                            rank=rank, layer=layer,
                                            window=window, valid=valid)


# ---------------------------------------------------------------------------
# The latent read UNDER A SELECTION (learned sparse attention over a latent
# pool): ``paged_index_scores`` and ``paged_sparse_select`` above say which
# keys, this kernel attends them absorbed.
# ---------------------------------------------------------------------------
#: keys one softmax update of the selected latent read takes (whole blocks;
#: twice as many for the one position of a decode step)
_SPARSE_LATENT_COLS = 512
#: query POSITIONS one grid step of it takes (a window of one: 1)
_SPARSE_LATENT_QUERY_TILE = 8
_SPARSE_LATENT_VMEM = 32 << 20


def sparse_latent_walk_shape(t: int, bs: int, nbper: int) -> Tuple[int, int]:
    """``(tq, nt)`` of the selected latent read for a window of ``t``
    positions (1, 2 or 4 — a decode step's one, a verify window's K + 1 —
    or a multiple of 8: ``sparse_index_attention`` pads any other): the query positions a grid step takes and the
    blocks a landing tile holds.  A short window is ONE grid step a row:
    its positions' query rows side by side, a block landed once for every
    position of the row that chose in it."""
    tq = min(t, _SPARSE_LATENT_QUERY_TILE)
    short = tq < _SPARSE_LATENT_QUERY_TILE
    nt = max(1, min(_SPARSE_LATENT_COLS * (2 if short else 1) // bs, nbper))
    while nbper % nt:
        nt -= 1
    return tq, nt


def _paged_sparse_latent_kernel(layer_ref, n_ref, bt_ref, hit_ref, q_ref,
                                theta_ref, slast_ref, last_ref, scores, pool,
                                o_ref, buf, sbuf, sem, m_scr, l_scr, acc_scr,
                                *, heads: int, tq: int, rank: int):
    """Absorbed latent attention of ``tq`` query positions of one row, all
    ``heads`` of them, over the keys each position has CHOSEN.  Grid ``(B,
    T / tq)``; query row ``o * heads + h`` is head ``h`` at window offset
    ``j * tq + o`` (:func:`_paged_latent_kernel`'s order).

    ``layer_ref`` int32 [1], ``n_ref`` int32 [B, T / tq] (the blocks up to
    the step's last real query), ``bt_ref`` int32 [B, NBPER] and ``hit_ref``
    int32 [B * T / tq, NBPER] (whether any query of the STEP chose a key of
    the block) arrive via scalar prefetch; the latent pool ``[L, NB, 1, bs,
    W]`` and the indexer's ``scores`` [B, T, NBPER * bs] float32 stay in
    HBM.  The step walks its ``n`` blocks ``nt`` a tile and copies, of each
    tile, the blocks with a hit (one DMA a block: a latent block serves
    every head, keys and values) and the tile's ``[tq, nt * bs]`` slab of
    scores; tile ``i + 1`` lands while tile ``i`` is attended.  A block no
    query of the step chose is neither read nor — where the whole tile has
    none — attended: the latent bytes read are those of the blocks that hold
    a chosen key.

    The set is rebuilt from the scores as ``_paged_sparse_kernel`` rebuilds
    it (``sparse_index_attention.chosen``): position ``o`` keeps key ``s``
    iff ``s <= last[o]`` and ``score > theta[o]`` or ``score == theta[o]``
    and ``s <= s_last[o]`` — ONE mask row a position, spread over its
    ``heads`` query rows: ``q [tq * heads, W] . tile^T`` are the scores of
    every head of every position (the pad lanes meet zeros), ``p . tile[:,
    :rank]`` the output in latent space, one product each a tile.  A slot
    of the landing buffer that was not copied holds an earlier block or the
    zeros of the start: every key of it is masked.  Online softmax in
    float32, the probabilities to the MXU in the pool's dtype."""
    _, nt, bs, w = buf.shape
    cols = nt * bs
    b, j = pl.program_id(0), pl.program_id(1)
    step = b * pl.num_programs(1) + j
    layer = layer_ref[0]
    n = jnp.clip(n_ref[b, j], 0, bt_ref.shape[1])
    ntiles = (n + nt - 1) // nt
    theta = _lanes(theta_ref[0], cols)
    slast = _lanes(slast_ref[0], cols)
    last = _lanes(last_ref[0], cols)

    def each_copy(i, slot, act):
        """``act`` on tile ``i``'s copies: its blocks with a hit, its
        scores."""
        def one(k, hits):
            hit = hit_ref[step, i * nt + k]

            @pl.when(hit > 0)
            def _block():
                act(pltpu.make_async_copy(
                    pool.at[layer, bt_ref[b, i * nt + k], 0],
                    buf.at[slot, k], sem.at[slot, 0]))
            return hits + hit

        hits = jax.lax.fori_loop(0, jnp.clip(n - i * nt, 0, nt), one,
                                 jnp.int32(0))

        @pl.when(i < ntiles)
        def _scores():
            act(pltpu.make_async_copy(
                scores.at[b, pl.ds(pl.multiple_of(j * tq, tq), tq),
                          pl.ds(pl.multiple_of(i * cols, cols), cols)],
                sbuf.at[slot], sem.at[slot, 1]))
        return hits

    def tile(i, carry):
        slot = i % 2
        each_copy(i + 1, 1 - slot, lambda copy: copy.start())
        hits = each_copy(i, slot, lambda copy: copy.wait())

        @pl.when(hits > 0)
        def _attend():
            sc = sbuf[slot]
            key = i * cols + jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
            keep = (key <= last) & ((sc > theta)
                                    | ((sc == theta) & (key <= slast)))
            keys = buf[slot].reshape(cols, w)
            # ONE product for the step's rows (a product a position, 128
            # rows each, ran 37.7 ms a [1, 512] chunk at 20k keys where the
            # dense walk's 512-row products run 19.1: my chip runs, PR 61);
            # the mask is a row a POSITION, spread over its heads
            s = jax.lax.dot_general(
                q_ref[0], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            mine = keep if tq == 1 else jnp.broadcast_to(
                keep[:, None, :], (tq, heads, cols)).reshape(tq * heads, cols)
            s = jnp.where(mine, s, NEG_INF)
            m_prev = m_scr[...][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a position with no key kept so far has m = NEG_INF, where
            # exp(s - m) is 1: the mask, not the exponent, zeroes it
            p = jnp.where(mine, jnp.exp(s - m_new), 0.0)
            l_new = l_scr[...][:, :1] * alpha \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
                p.astype(keys.dtype), keys[:, :rank],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    _start_chunks(m_scr, l_scr, acc_scr)
    buf[...] = jnp.zeros_like(buf)
    each_copy(0, 0, lambda copy: copy.start())
    jax.lax.fori_loop(0, ntiles, tile, None)
    den = l_scr[...][:, :1]
    o_ref[0] = (acc_scr[...] / jnp.where(den == 0.0, 1.0, den)) \
        .astype(o_ref.dtype)


def paged_sparse_latent_attention_pallas(q, pool, block_tables, scores, theta,
                                         s_last, last, *, rank: int, layer,
                                         real=None,
                                         interpret: Optional[bool] = None):
    """The read of learned sparse attention over a LATENT pool: ``q [B, H,
    T, W]`` (absorbed, as :func:`paged_latent_attention_reference` takes
    it; ``T`` 1, 2, 4 or a multiple of 8) over the keys each position chose, out
    of the stacked float leaf ``[L, NB, 1, bs, W]`` at ``layer``, read in
    place.  ``scores [B, T, NBPER * bs]`` float32 (``paged_index_scores``),
    ``theta`` / ``s_last [B, T]`` (``paged_sparse_select``) and ``last [B,
    T]`` (the last key a position may see; -1: a pad row, which comes back
    zeros) say which keys; ``real`` bool ``[B, T]`` which positions are
    somebody's (default all): a block is read for a grid step iff a real
    position of the step chose a key of it.  -> ``(out [B, H, T, rank],
    latent blocks landed)`` (:func:`_paged_sparse_latent_kernel`, as
    ``paged_sparse_latent_attn``)."""
    b, h, t, w = q.shape
    _, nb, one, bs, width = pool.shape
    assert one == 1 and width == w and w % LANES == 0 \
        and (t in (1, 2, 4) or t % 8 == 0), (pool.shape, q.shape)
    if interpret is None:
        interpret = interpret_kernels()
    nbper = block_tables.shape[1]
    tq, nt = sparse_latent_walk_shape(t, bs, nbper)
    tt, rows = t // tq, tq * h
    s = jnp.arange(nbper * bs, dtype=jnp.int32)
    keep = (s <= last[..., None]) & (
        (scores > theta[..., None])
        | ((scores == theta[..., None]) & (s <= s_last[..., None])))
    if real is not None:
        keep = keep & real[:, :, None]
    hit = jnp.any(keep.reshape(b, tt, tq, nbper, bs), axis=(2, 4))
    n = jnp.clip((jnp.max(last.reshape(b, tt, tq), axis=2) + bs) // bs, 0,
                 nbper)
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, nb - 1)
    qq = q.transpose(0, 2, 1, 3).reshape(b, t * h, w).astype(pool.dtype)

    def lanes(x, dtype):
        return jnp.broadcast_to(x.astype(dtype)[..., None], (b, t, LANES))

    def tile(width):
        return pl.BlockSpec((1, rows, width),
                            lambda i, j, *prefetched: (i, j, 0))

    per_query = pl.BlockSpec((1, tq, LANES),
                             lambda i, j, *prefetched: (i, j, 0))
    out = pl.pallas_call(
        functools.partial(_paged_sparse_latent_kernel, heads=h, tq=tq,
                          rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,               # layer, blocks, table, hits
            grid=(b, tt),
            in_specs=[tile(w), per_query, per_query, per_query]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=tile(rank),
            scratch_shapes=[
                pltpu.VMEM((2, nt, bs, w), pool.dtype),
                pltpu.VMEM((2, tq, nt * bs), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, LANES), jnp.float32),           # m
                pltpu.VMEM((rows, LANES), jnp.float32),           # l
                pltpu.VMEM((rows, rank), jnp.float32)]),          # acc
        out_shape=jax.ShapeDtypeStruct((b, t * h, rank), q.dtype),
        # (8 positions of 128 heads: the query tile, the output and the
        # float32 accumulator are 1,024 rows each, 19.5 MB with a tile's
        # scores — over the 16 MiB a kernel gets unasked, a sixth of VMEM)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_SPARSE_LATENT_VMEM),
        interpret=interpret, name="paged_sparse_latent_attn",
    )(jnp.asarray(layer, jnp.int32).reshape(1), n.astype(jnp.int32), bt,
      hit.reshape(b * tt, nbper).astype(jnp.int32), qq,
      lanes(theta, jnp.float32), lanes(s_last, jnp.int32),
      lanes(last, jnp.int32), scores.astype(jnp.float32), pool)
    return out.reshape(b, t, h, rank).transpose(0, 2, 1, 3), jnp.sum(hit)


#: paths :func:`paged_decode_attention` took while a :func:`dispatch_log`
#: was open — written at TRACE time, like the contexts above
_DISPATCHED = None


@contextlib.contextmanager
def dispatch_log():
    """Collect the name of the path each :func:`paged_decode_attention`
    call inside the block takes (a kernel's name, ``"gather"`` or
    ``"sp"``).  The serving engine opens it around the traced body of a
    program to record which read that program was built with."""
    global _DISPATCHED
    prev, _DISPATCHED = _DISPATCHED, set()
    try:
        yield _DISPATCHED
    finally:
        _DISPATCHED = prev


def _took(path: str) -> None:
    if _DISPATCHED is not None:
        _DISPATCHED.add(path)


def paged_decode_attention(q, k_pool, v_pool, block_tables, q_pos, *,
                           sm_scale: Optional[float] = None, layer=None,
                           valid=None, window: int = 0):
    """Dispatch: block-table-walking Pallas kernels on TPU — single-token
    decode (T == 1), the speculative K+1 verify window (T <=
    ``VERIFY_T_MAX``) or a prefill chunk (any wider T, float pools;
    ``valid`` int32 [B]: the chunk's real tokens per row, as the write
    took them); gather + XLA reference otherwise (CPU-sim, int8 records
    under a prefill chunk).  A configured sp context
    (``ops/sp_attention``) routes prefill chunks through the Ulysses
    all-to-all path; a resident-window context forces the reference path,
    which carries the window mask.  ``k_pool``/``v_pool`` are the stacked
    pool with ``layer`` given, one layer's pool otherwise
    (``paged_kv.whole_pool``).  ``window`` (static): a sliding-window
    layer's reach over its kind's ring table (``ops/paged_kv.py`` "Layer
    kinds"), taken by the same kernels and the same reference; 0 is a
    full-attention layer and the program it always was."""
    t = q.shape[2]
    if window and (window_state() is not None
                   or is_quantized_pool(k_pool)):
        raise NotImplementedError(
            "a sliding-window layer reads a float pool, outside a "
            "resident-window context")
    if t > 1 and not window:
        from . import sp_attention

        hkv = pool_payload(k_pool).shape[1 if layer is None else 2]
        if sp_attention.sp_shards(q.shape[1], hkv, t) > 1:
            _took("sp")
            return sp_attention.sp_prefill_attention(
                q, k_pool, v_pool, block_tables, q_pos, sm_scale=sm_scale,
                layer=layer)
    if window_state() is None and on_tpu():
        if t == 1:
            _took("paged_decode_attn")
            return paged_decode_attention_pallas(
                q, k_pool, v_pool, block_tables, q_pos, sm_scale=sm_scale,
                layer=layer, window=window)
        if t <= VERIFY_T_MAX:
            _took("paged_verify_attn")
            return paged_verify_attention_pallas(
                q, k_pool, v_pool, block_tables, q_pos, sm_scale=sm_scale,
                layer=layer, window=window)
        if not is_quantized_pool(k_pool):
            _took("paged_prefill_attn")
            return paged_prefill_attention_pallas(
                q, k_pool, v_pool, block_tables, q_pos, valid=valid,
                sm_scale=sm_scale, layer=layer, window=window)
    _took("gather")
    return paged_decode_attention_reference(q, k_pool, v_pool, block_tables,
                                            q_pos, sm_scale=sm_scale,
                                            layer=layer, window=window,
                                            valid=valid)
