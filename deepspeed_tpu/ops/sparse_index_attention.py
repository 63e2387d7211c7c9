"""Learned sparse attention over the block-paged pool (DeepSeek-V3.2's
"lightning indexer", as Keye-VL-2.0's ``sa_config`` has it).

A layer keeps, beside K and V, ONE more vector a token — the indexer's key
``kI_s`` (``index_head_dim`` wide, one head) — in a third pool leaf
``[L, NB, 1, block_size, DI]`` under the same block table
(``ops/paged_kv.py`` "Layout": written by :func:`paged_kv.paged_window_update`
at the ``(layer, block, offset)`` K and V take).  A query at position ``t``
scores every key it may see,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)

and attends, in all its heads, the ``topk`` keys of largest ``I`` (ties: the
lower ``s``); a query with at most ``topk`` visible keys attends them all.

What a call does, from what it can observe (no option):

* no row's context passes ``topk``: today's read, untouched
  (:func:`decode_attention.paged_decode_attention`) — chosen at RUN time by
  a ``lax.cond`` on the rows' lengths, so a batch of short rows takes the
  kernels every other model takes;
* else **score** the rows' indexer keys in place (:func:`index_scores`: on a
  TPU the kernel ``paged_index_scores`` walks each row's valid blocks; the
  gather elsewhere), **select** exactly (:func:`select_threshold`: the
  ``topk``-th largest score and the position of the last tie taken, which
  together say of ANY key whether it is chosen — on a TPU the kernel
  ``paged_sparse_select``, a bit-by-bit search with no sort; ``lax.top_k``
  elsewhere), and **read** under the selection as a mask: on a TPU the
  kernel ``paged_sparse_attn`` (``decode_attention.py``) walks the row's
  valid blocks, copies those that hold a key some query of the row chose
  and rebuilds each query's set from its scores, a tile of keys at a time;
  elsewhere (and for a window that is neither one query nor a multiple of
  8, or a pool whose head is not a whole lane row) :func:`_masked_walk`
  does the same in XLA, ``_CHUNK_KEYS`` keys a step.  Online softmax
  either way: no ``[T, max_seq_len]`` attention score exists, and the one
  score array held is the indexer's ``[B, T, max_seq_len]`` float32 of
  this layer.

**What is read.**  The indexer's key of every valid token; K and V of the
BLOCKS that hold a chosen key — at most ``min(cdiv(ctx, bs), topk)`` of
them.  Not of the chosen tokens alone: Mosaic copies out of a bf16 pool
in slices of at least 8 rows of a block (a 1- or 2-row slice is refused
at compile time: "must be aligned to tiling (8)"), and the XLA gather of
single rows that met the bound on paper took 28x its bytes' time on the
chip (PERF.md section 6, PR 32).  With seeded weights nearly every block
of a 6k-12k context holds a chosen key; a trained indexer's are more
local.

Every call also returns what it did, int32 ``[5]`` (:data:`COUNTS`),
counted on the device from the selection itself.

One shard only: under a ``tp`` / ``dp`` mesh, an int8 pool or a resident
window the call raises (``ServingEngine`` refuses those engines by name).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.platform import on_tpu
from . import decode_attention, paged_kv

NEG_INF = decode_attention.NEG_INF
#: keys one step of the masked walk attends
_CHUNK_KEYS = 512

#: what the last :func:`paged_sparse_attention` traced was built with —
#: written at TRACE time, like ``decode_attention.dispatch_log``
_TOOK = None


def took() -> Optional[str]:
    return _TOOK


def layer_norm(x, scale_bias, eps: float = 1e-6):
    """LayerNorm over the last dim; ``scale_bias`` ``[2, D]``."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale_bias[0]
            + scale_bias[1]).astype(x.dtype)


def scores_of(qi, wi, ki):
    """``I`` of the module docstring for ``qi [B, HI, T, DI]``, ``wi [B, T,
    HI]`` and keys ``ki [B, S, DI]`` -> float32 ``[B, T, S]``."""
    dots = jnp.einsum("bhtd,bsd->bhts", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bth,bhts->bts", wi.astype(jnp.float32),
                      jax.nn.relu(dots))


def last_visible(q_pos, t: int, b: int, valid=None):
    """int32 ``[B, T]``: the last key position each query of a window at
    ``q_pos`` may see — itself; a pad query (past ``valid``) what the
    row's last real query sees; none (-1) in a pad row."""
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    off = jnp.arange(t, dtype=jnp.int32)[None, :]
    if valid is None:
        return pos[:, None] + off
    valid = jnp.asarray(valid, jnp.int32)[:, None]
    return jnp.where(valid > 0, pos[:, None] + jnp.minimum(off, valid - 1),
                     -1)


def index_scores_reference(qi, wi, idx_pool, block_tables, last, layer):
    """:func:`scores_of` against every key of each row's table, by gather
    (pure XLA); a key past ``last[b, t]`` scores ``-inf``."""
    ki = paged_kv._paged_gather(idx_pool, block_tables, layer,
                                qi.shape[-1])[:, 0]            # [B, S, DI]
    s = jnp.arange(ki.shape[1], dtype=jnp.int32)
    return jnp.where(s[None, None, :] <= last[:, :, None],
                     scores_of(qi, wi, ki), -jnp.inf)


def index_scores(qi, wi, idx_pool, block_tables, last, layer):
    if on_tpu():
        return decode_attention.paged_index_scores_pallas(
            qi, wi, idx_pool, block_tables, last, layer=layer)
    return index_scores_reference(qi, wi, idx_pool, block_tables, last,
                                  layer)


def select_threshold_reference(scores, topk: int):
    """``(theta, s_last)`` of float32 ``scores [..., S]``: the ``topk``-th
    largest value and the position of the last entry a stable
    largest-first order takes (``lax.top_k``: of equal values the lower
    index first; ``-0.0`` counts as ``0.0``, as it compares)."""
    vals, idx = jax.lax.top_k(jnp.where(scores == 0.0, 0.0, scores), topk)
    return vals[..., -1], idx[..., -1].astype(jnp.int32)


def select_threshold(scores, topk: int):
    if on_tpu():
        return decode_attention.paged_sparse_select_pallas(scores, topk)
    return select_threshold_reference(scores, topk)


def chosen(scores, theta, s_last, last):
    """bool like ``scores``: whether key ``s`` is in the query's set."""
    s = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    theta, s_last = theta[..., None], s_last[..., None]
    return (s <= last[..., None]) & (
        (scores > theta) | ((scores == theta) & (s <= s_last)))


@jax.named_scope("layer/attn/core")
def sparse_attention_uncached(q, k, v, qi, wi, ki, topk: int):
    """The whole sequence at once, no cache: ``q [B, H, S, D]``, ``k`` /
    ``v [B, HKV, S, D]``, the indexer's ``qi [B, HI, S, DI]``, ``wi [B, S,
    HI]``, ``ki [B, S, DI]``.  ``[S, S]`` scores: the models' uncached
    forwards (tests, short evaluations), not serving."""
    b, h, s, d = q.shape
    rep = h // k.shape[1]
    last = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    keep = jnp.arange(s)[None, None, :] <= last[:, :, None]
    if s > topk:
        scores = jnp.where(keep, scores_of(qi, wi, ki), -jnp.inf)
        keep = chosen(scores, *select_threshold_reference(scores, topk),
                      last)
    att = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, rep, axis=1),
                     preferred_element_type=jnp.float32) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(keep[:, None], att, NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype),
                      jnp.repeat(v, rep, axis=1))


def _masked_walk(q, k_pool, v_pool, block_tables, keep, last, layer,
                 sm_scale):
    """``q [B, H, T, D]`` against each row's valid blocks, ``_CHUNK_KEYS``
    keys a step, under ``keep [B, T, S]``; online softmax in float32."""
    b, h, t, d = q.shape
    hkv = k_pool.shape[2]
    rep = h // hkv
    nbper = block_tables.shape[1]
    bs = keep.shape[-1] // nbper
    nbc = max(1, min(nbper, _CHUNK_KEYS // bs))
    while nbper % nbc:
        nbc -= 1
    kc = nbc * bs
    qg = q.reshape(b, hkv, rep * t, d)                  # row r*T + i
    steps = (jnp.max(last) + kc) // kc                  # chunks with a key

    def step(c, carry):
        m, l, acc = carry
        bt = jax.lax.dynamic_slice_in_dim(block_tables, c * nbc, nbc, axis=1)
        k = paged_kv._paged_gather(k_pool, bt, layer, d)     # [B,HKV,kc,D]
        v = paged_kv._paged_gather(v_pool, bt, layer, d)
        kp = jax.lax.dynamic_slice_in_dim(keep, c * kc, kc, axis=2)
        kp = jnp.tile(kp, (1, rep, 1))[:, None]              # [B,1,rep*T,kc]
        s = jnp.einsum("bgrd,bgkd->bgrk", qg, k,
                       preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(kp, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(kp, jnp.exp(s - m_new), 0.0)
        acc = acc * alpha + jnp.einsum(
            "bgrk,bgkd->bgrd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True), acc

    shape = (b, hkv, rep * t)
    m, l, acc = jax.lax.fori_loop(
        0, steps, step,
        (jnp.full(shape + (1,), NEG_INF, jnp.float32),
         jnp.zeros(shape + (1,), jnp.float32),
         jnp.zeros(shape + (d,), jnp.float32)))
    return (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype) \
        .reshape(b, h, t, d)


#: what :func:`paged_sparse_attention` returns beside its output, int32 in
#: this order, summed over the call's real queries (a pad query, a pad row
#: and an idle slot — an all-scratch table — count nothing): indexer keys
#: scored; keys attended; keys a dense read attends; rows with a query past
#: ``topk``; K/V rows fetched (whole blocks)
COUNTS = ("index_keys", "kv_selected", "kv_valid", "sparse_rows", "kv_read")


def _queries(block_tables, q_pos, t: int, b: int, valid, bs: int):
    """What both selected reads reckon of a call's queries: ``(the table
    int32, last_visible, which queries are somebody's [B, T], the keys they
    may see summed, the blocks a row's real queries reach [B])``."""
    bt = jnp.asarray(block_tables, jnp.int32)
    last = last_visible(q_pos, t, b, valid)
    real = bt[:, :1] != 0
    if valid is not None:
        real = real & (jnp.arange(t, dtype=jnp.int32)[None, :]
                       < jnp.asarray(valid, jnp.int32)[:, None])
    real = jnp.broadcast_to(real, (b, t)) & (last >= 0)
    visible = jnp.sum(jnp.where(real, last + 1, 0))
    # blocks that hold a key some real query of the row may see
    blocks = (jnp.max(jnp.where(real, last, -1), axis=1) + bs) // bs
    return bt, last, real, visible, blocks


@jax.named_scope("layer/attn/core")
def paged_sparse_attention(q, k_pool, v_pool, idx_pool, qi, wi, block_tables,
                           q_pos, *, topk: int, layer, valid=None,
                           sm_scale: Optional[float] = None,
                           return_keep: bool = False):
    """Attention of ``q [B, H, T, D]`` over each row's ``topk`` chosen keys
    (module docstring), as ``(out, counts)``: ``counts`` int32 ``[5]``
    (:data:`COUNTS`).  ``qi [B, HI, T, DI]`` / ``wi [B, T, HI]``: the
    indexer's queries and head weights; the three pools stacked, the
    window already written to them; ``q_pos`` / ``valid`` as
    ``paged_decode_attention`` takes them.  ``return_keep`` adds the keys
    each query attended, bool ``[B, T, max_seq_len]``."""
    global _TOOK
    if paged_kv.tp_mesh() is not None or paged_kv.dp_groups() > 1 \
            or decode_attention.window_state() is not None \
            or paged_kv.is_quantized_pool(k_pool):
        raise NotImplementedError(
            "learned sparse attention (an indexer's selection) runs on one "
            "shard over a float pool: not under a tp/dp mesh, an int8 KV "
            "pool or a resident window")
    b, _, t, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    bs = math.prod(k_pool.shape[3:]) // d
    bt, last, real, visible, blocks = _queries(block_tables, q_pos, t, b,
                                               valid, bs)
    nbper = bt.shape[1]
    s_max = nbper * bs

    def finish(out, counts, keep):
        counts = jnp.stack(counts).astype(jnp.int32)
        return (out, counts, keep()) if return_keep else (out, counts)

    def dense():
        out = decode_attention.paged_decode_attention(
            q, k_pool, v_pool, bt, q_pos, sm_scale=scale, layer=layer,
            valid=valid)
        zero = jnp.zeros((), visible.dtype)
        return finish(
            out, [zero, visible, visible, zero, jnp.sum(blocks) * bs],
            lambda: jnp.arange(s_max)[None, None, :] <= last[:, :, None])

    if s_max <= topk:
        return dense()
    kernel = on_tpu() and k_pool.shape[-1] == d and (t == 1 or t % 8 == 0)
    _TOOK = "paged_index_scores+paged_sparse_select+" + (
        "paged_sparse_attn" if kernel else "walk") if on_tpu() \
        else "gather+top_k+walk"

    def sparse():
        with jax.named_scope("layer/attn/select/score"):
            scores = index_scores(qi, wi, idx_pool, bt, last, layer)
        with jax.named_scope("layer/attn/select/select"):
            theta, s_last = select_threshold(scores, topk)
            keep = chosen(scores, theta, s_last, last)
            mine = keep & real[:, :, None]
            hit = jnp.any(mine.reshape(b, t, nbper, bs), axis=(1, 3))
        with jax.named_scope("layer/attn/select/read"):
            if kernel:
                out = decode_attention.paged_sparse_attention_pallas(
                    q, k_pool, v_pool, bt, scores, theta, s_last, last, hit,
                    layer=layer, sm_scale=scale)
                read = jnp.sum(hit) * bs
            else:
                out = _masked_walk(q, k_pool, v_pool, bt, keep, last, layer,
                                   scale)
                read = jnp.sum(blocks) * bs
        return finish(
            out, [visible, jnp.sum(mine), visible,
                  jnp.sum(jnp.any(real & (last >= topk), axis=1)), read],
            lambda: keep)

    return jax.lax.cond(jnp.max(last) >= topk, sparse, dense)


def _masked_latent_walk(q, pool, block_tables, keep, last, layer, rank: int):
    """:func:`_masked_walk` over a LATENT leaf: ``q [B, H, T, W]`` (absorbed,
    scaled) against each row's valid blocks, ``_CHUNK_KEYS`` keys a step,
    under ``keep [B, T, S]``; the value is the tile's first ``rank`` lanes;
    online softmax in float32.  -> ``[B, H, T, rank]``."""
    b, h, t, w = q.shape
    nbper = block_tables.shape[1]
    bs = keep.shape[-1] // nbper
    nbc = max(1, min(nbper, _CHUNK_KEYS // bs))
    while nbper % nbc:
        nbc -= 1
    kc = nbc * bs
    steps = (jnp.max(last) + kc) // kc                  # chunks with a key

    def step(c, carry):
        m, l, acc = carry
        bt = jax.lax.dynamic_slice_in_dim(block_tables, c * nbc, nbc, axis=1)
        lat = paged_kv._paged_gather(pool, bt, layer, w)[:, 0]   # [B, kc, W]
        kp = jax.lax.dynamic_slice_in_dim(keep, c * kc, kc, axis=2)[:, None]
        s = jnp.einsum("bhtw,bkw->bhtk", q, lat,
                       preferred_element_type=jnp.float32)
        s = jnp.where(kp, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(kp, jnp.exp(s - m_new), 0.0)
        acc = acc * alpha + jnp.einsum(
            "bhtk,bkc->bhtc", p.astype(lat.dtype), lat[..., :rank],
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True), acc

    shape = (b, h, t)
    m, l, acc = jax.lax.fori_loop(
        0, steps, step,
        (jnp.full(shape + (1,), NEG_INF, jnp.float32),
         jnp.zeros(shape + (1,), jnp.float32),
         jnp.zeros(shape + (rank,), jnp.float32)))
    return (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)


@jax.named_scope("layer/attn/core")
def paged_sparse_latent_attention(q, pool, idx_pool, qi, wi, block_tables,
                                  q_pos, *, rank: int, topk: int, layer,
                                  valid=None, return_keep: bool = False):
    """:func:`paged_sparse_attention` over a LATENT pool (a latent layer
    under a learned selection): the absorbed queries ``q [B, H, T, W]``
    (``decode_attention.paged_latent_attention``'s operand) attend, in all
    their heads, the ``topk`` keys the indexer chose, out of the latent leaf
    ``pool [L, NB, 1, bs, W]``; ``idx_pool`` is the indexer's key leaf under
    the same table.  The scores and the threshold are that function's — the
    same two kernels — and so are the counts (:data:`COUNTS`; ``kv_read``:
    latent rows landed).  No row's context past ``topk``: the dense latent
    walk, chosen at run time.  The read under the selection lands the
    BLOCKS that hold a key some position of a grid step chose and masks (on
    a TPU the kernel ``paged_sparse_latent_attn``; :func:`_masked_latent_walk`
    elsewhere and for a window of 9 or more positions that is no multiple
    of 8): a latent token is one row of a block, and Mosaic copies rows
    eight at a time.  A WINDOW of ``K + 1`` positions (a verify round: ``T``
    under 8, each row at a base of its own) is one grid step a row — both
    positions' latents and index keys are written before the call, position
    ``i`` scores and selects over the keys ``<= q_pos + i``
    (:func:`last_visible`), and a block lands once for all the positions of
    the row that chose in it."""
    global _TOOK
    if paged_kv.tp_mesh() is not None or paged_kv.dp_groups() > 1 \
            or decode_attention.window_state() is not None \
            or paged_kv.is_quantized_pool(pool):
        raise NotImplementedError(
            "learned sparse attention (an indexer's selection) runs on one "
            "shard over a float pool: not under a tp/dp mesh, an int8 KV "
            "pool or a resident window")
    b, h, t, w = q.shape
    bs = pool.shape[3]
    bt, last, real, visible, blocks = _queries(block_tables, q_pos, t, b,
                                               valid, bs)
    nbper = bt.shape[1]
    s_max = nbper * bs

    def finish(out, counts, keep):
        counts = jnp.stack(counts).astype(jnp.int32)
        return (out, counts, keep()) if return_keep else (out, counts)

    def dense():
        out = decode_attention.paged_latent_attention(
            q, pool, bt, q_pos, rank=rank, layer=layer, valid=valid)
        zero = jnp.zeros((), visible.dtype)
        return finish(
            out, [zero, visible, visible, zero, jnp.sum(blocks) * bs],
            lambda: jnp.arange(s_max)[None, None, :] <= last[:, :, None])

    if s_max <= topk:
        return dense()
    kernel = on_tpu() and h % 8 == 0
    _TOOK = "paged_index_scores+paged_sparse_select+" + (
        "paged_sparse_latent_attn" if kernel else "latent_walk") \
        if on_tpu() else "gather+top_k+latent_walk"
    # the window the kernels take: 1, 2 or 4 positions as they are (one grid
    # step a row), any other padded to whole steps of 8 with positions that
    # see no key and are nobody's (Mosaic slices a score slab by 4 rows)
    tk = t if not kernel or t in (1, 2, 4) else -(-t // 8) * 8

    def padded(x, axis, value=0):
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, tk - t)
        return jnp.pad(x, widths, constant_values=value) if tk != t else x

    def sparse():
        seen, mine = padded(last, 1, -1), padded(real, 1, False)
        with jax.named_scope("layer/attn/select/score"):
            scores = index_scores(padded(qi, 2), padded(wi, 1), idx_pool, bt,
                                  seen, layer)
        with jax.named_scope("layer/attn/select/select"):
            theta, s_last = select_threshold(scores, topk)
            keep = chosen(scores, theta, s_last, seen)
        with jax.named_scope("layer/attn/select/read"):
            if kernel:
                out, landed = \
                    decode_attention.paged_sparse_latent_attention_pallas(
                        padded(q, 2), pool, bt, scores, theta, s_last, seen,
                        rank=rank, layer=layer, real=mine)
                out, read = out[:, :, :t], landed * bs
            else:
                out = _masked_latent_walk(q, pool, bt, keep, last, layer,
                                          rank)
                read = jnp.sum(blocks) * bs
        keep = keep[:, :t]
        return finish(
            out, [visible, jnp.sum(keep & real[:, :, None]), visible,
                  jnp.sum(jnp.any(real & (last >= topk), axis=1)), read],
            lambda: keep)

    return jax.lax.cond(jnp.max(last) >= topk, sparse, dense)
