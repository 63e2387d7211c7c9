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
    return run & (ki * block_k + block_k - 1 > qi * block_q - window)


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
# strips: inside a grid step the work follows the visible pairs.
#
# A grid step costs ~0.4 us whatever it computes, so the tiles are large
# (1024 x 1024 in every training cell) — and a tile the causal diagonal or a
# window's lower edge crosses was computed whole, half of it for the mask to
# throw away.  With ``strip`` = t > 0 a tile is a grid of t x t sub-tiles,
# each of them wholly visible, wholly hidden, or cut by ONE edge at its own
# diagonal (``_tile_strips``); a step then
#  - is *skipped* where no sub-tile holds a visible pair (as always),
#  - is *interior* where all of them are visible: one body, no iota, no
#    compare, no select,
#  - is an *edge* otherwise: strips of t keys or t queries, each multiplied
#    against the other side only as far as some pair of it is visible, and
#    masked only in the sub-tile an edge crosses.
# Which of these a step is depends on where its first row stands against its
# first column (``qi * block_q - ki * block_k``): the few values that give
# an edge are enumerated at trace time, a body each (``_kinds``).  That
# needs the edges at multiples of t inside the tile; any other call
# (``_resolve_strip``: W = 1,000, 96-row blocks) has ``strip`` = 0, ONE kind
# and the whole-tile masked body.  Masked lanes contributed exact zeros, so
# leaving them out changes no value, only the order of some float32 sums.
#
# Which side a strip runs along is each kernel's own.  The compiler's own
# schedule of these kernels (``benchmarks/flash_bundles.py``: bundles a grid
# step and the share of them each unit is busy, read without a chip) shows a
# whole tile bound by the MXU's streamed rows — 13.6 cycles a packed 16-row
# push on each of four MXUs, 90 % busy — so a strip saves what it does not
# stream, PROVIDED nothing else takes the MXU's place: cross-lane reductions
# and lane permutes (one a row a PIECE, 2.6 x a whole tile's), and spills.
# So the forward and ``dq`` take strips of KEYS (K / V rows latched, the
# queries that see them streamed); the forward in two passes (``_fwd_tile``:
# every piece's scores, then one softmax update a row segment, its
# statistics kept in the scratch's lane-replicated form and folded lane-wise
# before the one cross-lane reduction); ``dkv`` and the fused backward
# strips of QUERIES over scores held TRANSPOSED, ``[keys, queries]`` (q / do
# rows latched, the keys streamed; p^T and ds^T are what dv and dk multiply,
# lse / delta are read in the lane layout they are stored in, and the fused
# backward's dp needs nothing of the softmax and runs beside the scores).
# ---------------------------------------------------------------------------
#: keys or queries of a strip, chosen from the kernels' schedules and their
#: times alone on a v5e at the training cells' calls (PERF.md section 6,
#: PR 62): 512 computes 3/4 of an edge tile where 256 computes 5/8; at 128
#: ``dq`` and the fused backward schedule worse than at 256
_STRIP = 256
#: unrolled strip bodies a kernel may hold before the call keeps whole tiles
#: (block_q != block_k multiplies the offsets an edge can stand at; a
#: kernel's text is start-up seconds)
_MAX_STRIPS = 24

#: what of the mask a piece of scores still needs: the causal diagonal, the
#: window's lower edge (its width; 0: not), the padded keys
_Mask = collections.namedtuple("_Mask", "causal window pad")
#: one body of a kernel: ``spans`` — ``(lo, hi, last)``: the steps whose
#: first row stands ``lo..hi`` past their first column, on the last key
#: block only / not (``None``: either) — or ``None``, every step that runs;
#: ``strips`` — ``(start, size, pieces)`` with ``pieces`` of ``(lo, hi,
#: mask)`` along the other side of the tile, ``mask`` a ``_Mask`` or None
_Kind = collections.namedtuple("_Kind", "spans strips")


def _tile_strips(d: int, last: bool, by_cols: bool, causal: bool,
                 block_q: int, block_k: int, window: int, strip: int,
                 kv_left: int):
    """The strips of a tile whose first row stands ``d`` past its first
    column (``last``: its keys end at ``kv_left``), () if it holds no
    visible pair.  Sub-tile (i, j) lies ``u = i - j + d / strip`` strips
    under the diagonal: hidden above it (u < 0) and past the window
    (u > W / strip), cut at u == 0 and at u == W / strip, whole between."""
    t, w = strip, window // strip

    def kind(i, j):
        u = i - j + d // t
        if (causal and u < 0) or (window and u > w) \
                or (last and j * t >= kv_left):
            return False
        mask = _Mask(causal and u == 0, window if window and u == w else 0,
                     last and (j + 1) * t > kv_left)
        return mask if any(mask) else None

    n_strips, n_pieces = ((block_k, block_q) if by_cols
                          else (block_q, block_k))
    strips = []
    for a in range(n_strips // t):
        pieces = []
        for b in range(n_pieces // t):
            k = kind(b, a) if by_cols else kind(a, b)
            if k is False:
                continue
            if k is None and pieces and pieces[-1][1:] == (b * t, None):
                pieces[-1] = (pieces[-1][0], (b + 1) * t, None)
            else:
                pieces.append((b * t, (b + 1) * t, k))
        strips.append((a * t, t, tuple(pieces)))
    if not any(pieces for _, _, pieces in strips):
        return ()
    if all(pieces == ((0, n_pieces, None),) for _, _, pieces in strips):
        return ((0, n_strips, ((0, n_pieces, None),)),)    # interior
    return tuple(strips)


@functools.lru_cache(maxsize=None)
def _kinds(causal: bool, block_q: int, block_k: int, nq: int, nk: int,
           kv_len: int, window: int, strip: int, by_cols: bool = False):
    """The bodies a kernel over an ``nq x nk`` grid of tiles holds (v2:
    ``nk`` = 1, ``block_k`` the resident length)."""
    padded = kv_len != nk * block_k
    if not strip:
        mask = _Mask(causal, window, padded)
        sides = (block_k, block_q) if by_cols else (block_q, block_k)
        return (_Kind(None, ((0, sides[0],
                              ((0, sides[1], mask if any(mask) else None),)),
                             )),)
    kv_left = kv_len - (nk - 1) * block_k
    keys = sorted({(qi * block_q - ki * block_k, padded and ki == nk - 1)
                   for qi in range(nq) for ki in range(nk)})
    groups = {}
    for d, last in keys:
        strips = _tile_strips(d, last, by_cols, causal, block_q, block_k,
                              window, strip, kv_left)
        if strips:
            groups.setdefault(strips, []).append((d, last))
    kinds = []
    for strips, members in groups.items():
        spans = []
        for last in (False, True):
            ds = [d for d, l in members if l == last]
            if not ds:
                continue
            # the sub-tiles' kinds are monotone in d: a body's offsets are
            # an interval of those the grid has
            assert not any(min(ds) <= d <= max(ds) and l == last
                           for d, l in keys if (d, l) not in members)
            spans.append((min(ds), max(ds), last if padded else None))
        kinds.append(_Kind(tuple(spans), strips))
    return tuple(kinds)


def _all(*conds):
    """``and`` of conditions of which some are plain ``True``."""
    conds = [c for c in conds if c is not True]
    return functools.reduce(jnp.logical_and, conds) if conds else True


def _tiles(qi, ki, *, causal: bool, block_q: int, block_k: int, nq: int,
           nk: int, kv_len: int, window: int, strip: int,
           by_cols: bool = False):
    """The bodies of grid step ``(qi, ki)``: ``(when, (row, col, kv), strips)``
    — run ``strips`` where ``when`` holds; ``row`` / ``col``: where the tile's
    first pair stands and ``kv`` where the keys end, on one scale (static
    inside a strip body, so its masks are constants)."""
    kinds = _kinds(causal, block_q, block_k, nq, nk, kv_len, window, strip,
                   by_cols)
    if not strip:
        run = True
        if nk > 1:      # one resident block: every query sees key 0 of it
            run = (ki * block_k <= qi * block_q + block_q - 1) if causal \
                else True
            run = _in_band(run, qi, ki, block_q, block_k, window)
        return [(run, (qi * block_q, ki * block_k, kv_len), kinds[0].strips)]
    delta = qi * block_q - ki * block_k
    bodies = []
    for spans, strips in kinds:
        when = []
        for lo, hi, last in spans:
            when.append(_all(
                lo <= -(nk - 1) * block_k or delta >= lo,
                hi >= (nq - 1) * block_q or delta <= hi,
                last is None or nk == 1
                or ((ki == nk - 1) if last else (ki < nk - 1))))
        when = True if any(c is True for c in when) \
            else functools.reduce(jnp.logical_or, when)
        bodies.append((when, (spans[0][0], 0, kv_len - (nk - 1) * block_k),
                       strips))
    return bodies


def _when(cond):
    """``pl.when``, and no branch at all where ``cond`` is plain True."""
    return (lambda body: body()) if cond is True else pl.when(cond)


def _masked(s2, mask, row, col, kv_len, keys_first: bool = False):
    """``s2`` with the pairs ``mask`` hides at the mask value; ``s2[0, 0]``
    is the pair (``row``, ``col``); ``keys_first``: ``s2`` is ``[keys,
    queries]``."""
    if mask is None:
        return s2
    if isinstance(row, int) and isinstance(col, int):
        row, col, kv_len = row - col, 0, kv_len - col

    def iota(side, first):
        at = jax.lax.broadcasted_iota(jnp.int32, s2.shape, side)
        return at if isinstance(first, int) and first == 0 else first + at

    cols = iota(0 if keys_first else 1, col)
    keep = [cols < kv_len] if mask.pad else []
    if mask.causal or mask.window:
        rows = iota(1 if keys_first else 0, row)
        if mask.causal:
            keep.append(rows >= cols)
        if mask.window:
            keep.append(rows - cols < mask.window)
    return jnp.where(functools.reduce(jnp.logical_and, keep), s2,
                     DEFAULT_MASK_VALUE)


def _dot(a, b, contract):
    """``a`` x ``b`` over dimensions ``contract``, float32 out of the MXU."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


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
#: them, the sliding window the call was made with (0: none), the rows of a
#: strip inside a tile (0: whole tiles; ``_resolve_strip``), causal or not
Choice = collections.namedtuple(
    "Choice", "q_len kv_len d generation block_q block_k how window strip "
    "causal", defaults=(0, 0, True))
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


def _resolve_strip(generation: str, causal: bool, q_pad: int, kv_pad: int,
                   kv_len: int, block_q: int, block_k: int,
                   window: int) -> int:
    """Rows of a strip inside a tile, 0 for whole tiles: ``_STRIP`` where
    the blocks (v2: the resident keys) and the window are multiples of it —
    every edge then stands at a multiple of it inside its tile — and the
    kernels stay under ``_MAX_STRIPS`` unrolled strip bodies.  Read from the
    shapes at trace time; v1 has no cell and no chip reading and keeps whole
    tiles."""
    t = _STRIP
    if generation == "v2":
        block_k = kv_pad
    if generation == "v1" or block_q % t or block_k % t or window % t:
        return 0
    kinds = _kinds(causal, block_q, block_k, q_pad // block_q,
                   kv_pad // block_k, kv_len, window, t)
    bodies = sum(1 for kind in kinds for _, _, pieces in kind.strips
                 if pieces)
    return t if bodies <= _MAX_STRIPS else 0


def computed_pairs(choice: Choice):
    """``(visible, computed)`` (query, key) pairs of one head's call: what
    the mask lets through, and what the kernels multiply for it — whole
    tiles, or strips of ``choice.strip`` rows — by the arithmetic the
    kernels branch on (``_kinds``).  The forward, ``dq`` and ``dkv`` compute
    the same sub-tiles."""
    c = choice
    visible = sum(
        max(0, (min(p + 1, c.kv_len) if c.causal else c.kv_len)
            - (max(0, p + 1 - c.window) if c.window else 0))
        for p in range(c.q_len))
    q_pad = c.q_len + (-c.q_len) % c.block_q
    kv_pad = c.kv_len + (-c.kv_len) % c.block_k
    block_k = kv_pad if c.generation == "v2" else c.block_k
    nq, nk = q_pad // c.block_q, kv_pad // block_k
    computed = 0
    for qi in range(nq):
        for ki in range(nk):
            for when, _, strips in _tiles(
                    qi, ki, causal=c.causal, block_q=c.block_q,
                    block_k=block_k, nq=nq, nk=nk, kv_len=c.kv_len,
                    window=c.window, strip=c.strip):
                if when:
                    computed += sum(size * (hi - lo) for _, size, pieces
                                    in strips for lo, hi, _ in pieces)
    return visible, computed


def _v2_compiler_params(dimension_semantics):
    """CompilerParams for the v2 kernels; ``DS_V2_VMEM_MB`` raises the
    per-kernel scoped-vmem budget (the fused v2 backward at kv_pad=2048
    needs ~16.4MB against the 16MB default — see _V2_MAX_KV note)."""
    import os

    vmem_mb = os.environ.get("DS_V2_VMEM_MB")
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=(int(float(vmem_mb) * 2**20) if vmem_mb else None))


def _rows(x, start: int, size: int):
    """Rows ``start .. start + size`` of a value (static; the value itself
    where that is all of it)."""
    return x if (start, size) == (0, x.shape[0]) else x[start:start + size]


def _lanes(x, op):
    """``x`` folded to its first 128 columns by ``op`` (elementwise, on the
    vector unit), so that the cross-lane reduction — the scarce unit's — is
    made once a row and not once a piece."""
    if x.shape[1] <= LANES or x.shape[1] % LANES:
        return x
    return functools.reduce(op, [x[:, c:c + LANES]
                                 for c in range(0, x.shape[1], LANES)])


def _fit(x, n: int):
    """A row statistic — ``[rows, 1]``, or ``[rows, 128]`` with every lane
    the same, as the scratch holds it — against ``n`` columns.  (Slicing a
    column out of the scratch's form and broadcasting it again goes through
    the lane-permute unit, the busiest in the forward.)"""
    if x.shape[1] in (1, n):
        return x
    if n < x.shape[1]:
        return x[:, :n]
    if n % x.shape[1]:
        return x[:, :1]
    return pltpu.repeat(x, n // x.shape[1], axis=1)


def _fwd_tile(strips, at, qs, k_ref, v_ref, carried=None):
    """The forward of one tile over ``strips`` of KEYS, in two passes:
    every piece's scores first — a strip's K rows latched in the MXU once,
    only the queries that see them streaming past — then ONE softmax update
    a row segment over all the pieces that cover it, and the pieces'
    ``p @ v`` likewise by strips of keys.  ``carried(a, b)``: the running
    ``(m, l, acc)`` of rows ``a..b``, ``m`` and ``l`` in the scratch's
    ``[rows, 128]`` (None: none yet).  Yields ``(a, b, m, l, acc)`` a
    segment some piece covers, ``m`` and ``l`` in that form if carried."""
    row, col, kv = at
    pieces = [(start, cols, lo, hi, mask) for start, cols, on in strips
              for lo, hi, mask in on]
    s2 = [_masked(_dot(_rows(qs, lo, hi - lo),
                       k_ref[0, pl.ds(start, cols), :], (1, 1)), mask,
                  row + lo, col + start, kv)
          for start, cols, lo, hi, mask in pieces]
    tops = [_lanes(s, jnp.maximum) for s in s2]
    bounds = sorted({x for _, _, lo, hi, _ in pieces for x in (lo, hi)})
    segments = []
    for a, b in zip(bounds, bounds[1:]):
        over = [i for i, (_, _, lo, hi, _) in enumerate(pieces)
                if lo <= a and b <= hi]
        if not over:
            continue
        prev = carried(a, b) if carried else None
        m = jnp.max(functools.reduce(jnp.maximum, [
            _rows(tops[i], a - pieces[i][2], b - a) for i in over]),
            axis=1, keepdims=True)
        segments.append((a, b, over, prev,
                         jnp.maximum(prev[0], m) if prev else m))
    sums, outs = [], []
    for (start, cols, lo, hi, _), s in zip(pieces, s2):
        m = jnp.concatenate([m for a, b, _, _, m in segments
                             if lo <= a and b <= hi], axis=0)
        p = jnp.exp2(s - _fit(m, cols))                 # masked lanes -> 0
        sums.append(_lanes(p, jnp.add))
        outs.append(_dot(p.astype(v_ref.dtype),
                         v_ref[0, pl.ds(start, cols), :], (1, 0)))
    for a, b, over, prev, m in segments:
        l = jnp.sum(sum(_rows(sums[i], a - pieces[i][2], b - a)
                        for i in over), axis=1, keepdims=True)
        acc = sum(_rows(outs[i], a - pieces[i][2], b - a) for i in over)
        if prev:
            alpha = jnp.exp2(prev[0] - m)
            l = alpha * prev[1] + l
            acc = prev[2] * _fit(alpha, acc.shape[1]) + acc
        yield a, b, m, l, acc


def _fwd_v2_kernel(q_ref, k_ref, v_ref, o_ref, *, scale2: float, causal: bool,
                   block_q: int, kv_pad: int, kv_len: int, num_q_blocks: int,
                   window: int = 0, strip: int = 0):
    qi = pl.program_id(1)
    # fold softmax scale AND log2(e) into q (one [bq, d] pass instead of a
    # [bq, S] one); exp2 is the native transcendental
    q = q_ref[0, ...]
    qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
    for when, at, strips in _tiles(
            qi, 0, causal=causal, block_q=block_q, block_k=kv_pad,
            nq=num_q_blocks, nk=1, kv_len=kv_len, window=window, strip=strip,
            by_cols=True):
        @_when(when)
        def _compute():
            segments = list(_fwd_tile(strips, at, qs, k_ref, v_ref))
            if sum(b - a for a, b, *_ in segments) < block_q:
                # padded queries past a window's reach: unseen by any key
                o_ref[0, ...] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)
            for a, b, _, l, acc in segments:
                o_ref[0, pl.ds(a, b - a), :] = (acc / l).astype(o_ref.dtype)


def _fwd_v2(q, k, v, sm_scale, causal, block_q, interpret, true_kv_len,
            head_rep, window: int = 0, strip: int = 0):
    bh, q_len, d = q.shape
    kv_pad = k.shape[1]
    nq = pl.cdiv(q_len, block_q)
    kernel = functools.partial(
        _fwd_v2_kernel, scale2=sm_scale * _LOG2E, causal=causal,
        block_q=block_q, kv_pad=kv_pad, kv_len=true_kv_len, num_q_blocks=nq,
        window=window, strip=strip)
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
                   num_q_blocks: int, rep: int, window: int = 0,
                   strip: int = 0):
    inner = pl.program_id(1)
    qi = inner % num_q_blocks

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # scores TRANSPOSED, [keys, queries], in strips of QUERIES: dv, dk and the
    # scores themselves then latch the strip's q / do rows and stream the
    # keys it sees, and p^T, ds^T come out as dv and dk multiply them
    q = q_ref[0, ...]
    qs_all = (q.astype(jnp.float32) * scale2).astype(q.dtype)
    for when, at, strips in _tiles(
            qi, 0, causal=causal, block_q=block_q, block_k=kv_pad,
            nq=num_q_blocks, nk=1, kv_len=kv_len, window=window, strip=strip):
        @_when(when)
        def _compute():
            row, col, kv = at
            for start, rows, pieces in strips:
                at_rows = pl.ds(start, rows)
                if not pieces:      # padded queries past a window: unseen
                    dq_ref[0, at_rows, :] = jnp.zeros(
                        (rows,) + dq_ref.shape[2:], dq_ref.dtype)
                    continue
                qs = _rows(qs_all, start, rows)
                do = do_ref[0, at_rows, :]
                s2 = [_masked(_dot(k_ref[0, pl.ds(lo, hi - lo), :], qs,
                                   (1, 1)), mask, row + start, col + lo, kv,
                              keys_first=True) for lo, hi, mask in pieces]
                # dp needs nothing of the softmax: the MXU takes it beside
                # the scores (1 / l goes into p, not into do)
                dp = [_dot(v_ref[0, pl.ds(lo, hi - lo), :], do, (1, 1))
                      for lo, hi, _ in pieces]
                m = functools.reduce(jnp.maximum, [
                    jnp.max(s, axis=0, keepdims=True) for s in s2])
                p0 = [jnp.exp2(s - m) for s in s2]      # l * softmax(s)^T
                linv = 1.0 / sum(jnp.sum(x, axis=0, keepdims=True)
                                 for x in p0)           # [1, rows]
                delta = jnp.sum(
                    do.astype(jnp.float32)
                    * o_ref[0, at_rows, :].astype(jnp.float32), axis=1,
                    keepdims=True).reshape(1, rows)     # sublane -> lane
                dq = 0.0
                for x, y, (lo, hi, _) in zip(p0, dp, pieces):
                    keys = pl.ds(lo, hi - lo)
                    p = x * linv                        # softmax(s)^T
                    ds = (p * (y - delta)).astype(q.dtype)
                    dv_scr[keys, :] += _dot(p.astype(do.dtype), do, (1, 0))
                    # dk_true = ds^T @ (sm_scale*q) = (ds^T @ qs) * ln2
                    dk_scr[keys, :] += _dot(ds, qs, (1, 0))
                    # dq = sm_scale * (ds @ k)
                    dq = dq + _dot(ds, k_ref[0, keys, :], (0, 0))
                dq_ref[0, at_rows, :] = (dq * sm_scale).astype(dq_ref.dtype)

    @pl.when(inner == rep * num_q_blocks - 1)
    def _finalize():
        dk_ref[0, ...] = (dk_scr[...] * _LN2).astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_v2(q, k, v, o, do, sm_scale, causal, block_q, interpret, true_kv_len,
            head_rep, window: int = 0, strip: int = 0):
    bh, q_len, d = q.shape
    bh_kv, kv_pad, _ = k.shape
    nq = pl.cdiv(q_len, block_q)
    rep = head_rep
    kernel = functools.partial(
        _bwd_v2_kernel, scale2=sm_scale * _LOG2E, sm_scale=sm_scale,
        causal=causal, block_q=block_q, kv_pad=kv_pad, kv_len=true_kv_len,
        num_q_blocks=nq, rep=rep, window=window, strip=strip)
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
#  - masked/unmasked chunk-body forking ALONE: no measurable win in ``dq``
#    and ``dkv`` — round 4 at 128 x 128 blocks, where a step was ~0.4 us of
#    overhead around 0.02 us of work, and again in PR 62 at 1024 x 1024
#    (fork on / off within 2 % on the chip; 6,062 against 6,009 bundles a
#    ``dq`` tile: the mask's vector work hides under the matrix products).
#    The fork stays because the strips need an unmasked body for the
#    sub-tiles anyway, and the forward, whose vector units are the busier,
#    does gain from it (6,823 -> 6,313 bundles a tile).  What PR 62 gained
#    came from not MULTIPLYING the hidden half of the edge tiles.
#  - PR 62, on the chip at [8,32,2048,64]: row strips of queries in the
#    forward (a cross-lane reduction and a scratch update a strip and piece:
#    +5 % at t = 256), key strips with an online-softmax update a strip
#    (the running max / sum / output of 1,024 rows re-read, lane-sliced and
#    re-written a strip: +11 %), one piece a strip under the union of its
#    masks (no better than masking the one cut sub-tile), interior tiles in
#    strips (no better than whole), the backward's sums carried as values
#    to one scratch update a block (-1..2 % of the bundles: not worth its
#    lines).
# Reference parity: csrc/transformer/ds_transformer_cuda.cpp:78-121 claims
# fused-kernel supremacy at its benchmark shapes; this path is what makes
# the S=4096-8192 driver configs run on the measured-best kernels.
# ---------------------------------------------------------------------------


def _fwd_v3_kernel(*refs, scale2: float, causal: bool, block_q: int,
                   block_k: int, kv_len: int, num_q_blocks: int,
                   num_k_blocks: int, window: int = 0, strip: int = 0):
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    for when, at, strips in _tiles(
            qi, ki, causal=causal, block_q=block_q, block_k=block_k,
            nq=num_q_blocks, nk=num_k_blocks, kv_len=kv_len, window=window,
            strip=strip, by_cols=True):
        @_when(when)
        def _compute():
            q = q_ref[0, ...]
            qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)

            def carried(a, b):
                rows = pl.ds(a, b - a)
                return m_scr[rows, :], l_scr[rows, :], acc_scr[rows, :]

            for a, b, m, l, acc in _fwd_tile(strips, at, qs, k_ref, v_ref,
                                             carried):
                rows = pl.ds(a, b - a)
                m_scr[rows, :] = m
                l_scr[rows, :] = l
                acc_scr[rows, :] = acc

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_scr[...] / _fit(l_safe, acc_scr.shape[1])
                         ).astype(o_ref.dtype)
        lse2 = m_scr[...] + jnp.log2(l_safe)          # exp2-domain lse
        lse_ref[0, ...] = lse2[:, :1].reshape(1, block_q)   # sublane -> lane


def _fwd_v3(q, k, v, sm_scale, causal, block_q, block_k, interpret,
            true_kv_len, head_rep, window: int = 0, strip: int = 0):
    bh, q_len, d = q.shape
    kv_pad = k.shape[1]
    nq = pl.cdiv(q_len, block_q)
    nk = pl.cdiv(kv_pad, block_k)
    rep = head_rep
    kernel = functools.partial(
        _fwd_v3_kernel, scale2=sm_scale * _LOG2E, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=true_kv_len,
        num_q_blocks=nq, num_k_blocks=nk, window=window, strip=strip)
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
                      num_q_blocks: int, num_k_blocks: int, window: int = 0,
                      strip: int = 0):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_scr) = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    # strips of KEYS, as the forward: k and v latched, the queries stream
    for when, at, strips in _tiles(
            qi, ki, causal=causal, block_q=block_q, block_k=block_k,
            nq=num_q_blocks, nk=num_k_blocks, kv_len=kv_len, window=window,
            strip=strip, by_cols=True):
        @_when(when)
        def _compute():
            row, col, kv = at
            q = q_ref[0, ...]
            do_all = do_ref[0, ...]
            qs = (q.astype(jnp.float32) * scale2).astype(q.dtype)
            lse2 = lse_ref[0, ...].reshape(block_q, 1)   # lane -> sublane
            delta = dl_ref[0, ...].reshape(block_q, 1)
            for start, cols, pieces in strips:
                k = k_ref[0, pl.ds(start, cols), :]
                v = v_ref[0, pl.ds(start, cols), :]
                for lo, hi, mask in pieces:
                    s2 = _masked(_dot(_rows(qs, lo, hi - lo), k, (1, 1)),
                                 mask, row + lo, col + start, kv)
                    p = jnp.exp2(s2 - _rows(lse2, lo, hi - lo))  # true probs
                    dp = _dot(_rows(do_all, lo, hi - lo), v, (1, 1))
                    ds = (p * (dp - _rows(delta, lo, hi - lo))).astype(
                        q.dtype)
                    dq_scr[pl.ds(lo, hi - lo), :] += _dot(ds, k, (1, 0))

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, ...] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_v3_dkv_kernel(*refs, scale2: float, causal: bool, block_q: int,
                       block_k: int, kv_len: int, num_q_blocks: int,
                       num_k_blocks: int, rep: int, window: int = 0,
                       strip: int = 0):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
     dk_scr, dv_scr) = refs
    ki = pl.program_id(1)
    inner = pl.program_id(2)
    qi = inner % num_q_blocks

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # scores TRANSPOSED, [keys, queries], in strips of QUERIES: all four
    # products latch the strip's q / do rows and stream the keys it sees;
    # p^T and ds^T come out as dv and dk multiply them, and lse / delta are
    # read in the lane layout they are stored in
    for when, at, strips in _tiles(
            qi, ki, causal=causal, block_q=block_q, block_k=block_k,
            nq=num_q_blocks, nk=num_k_blocks, kv_len=kv_len, window=window,
            strip=strip):
        @_when(when)
        def _compute():
            row, col, kv = at
            q = q_ref[0, ...]
            do_all = do_ref[0, ...]
            qs_all = (q.astype(jnp.float32) * scale2).astype(q.dtype)
            for start, rows, pieces in strips:
                qs = _rows(qs_all, start, rows)
                do = _rows(do_all, start, rows)
                lse2 = lse_ref[0, :, pl.ds(start, rows)]        # [1, rows]
                delta = dl_ref[0, :, pl.ds(start, rows)]
                for lo, hi, mask in pieces:
                    keys = pl.ds(lo, hi - lo)
                    s2 = _masked(_dot(k_ref[0, keys, :], qs, (1, 1)), mask,
                                 row + start, col + lo, kv, keys_first=True)
                    p = jnp.exp2(s2 - lse2)                 # [keys, rows]
                    dv_scr[keys, :] += _dot(p.astype(do.dtype), do, (1, 0))
                    dp = _dot(v_ref[0, keys, :], do, (1, 1))
                    ds = (p * (dp - delta)).astype(q.dtype)
                    # dk_true = sm_scale * ds^T @ q = (ds^T @ qs) * ln2
                    dk_scr[keys, :] += _dot(ds, qs, (1, 0))

    @pl.when(inner == rep * num_q_blocks - 1)
    def _finalize():
        dk_ref[0, ...] = (dk_scr[...] * _LN2).astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_v3(q, k, v, o, lse, do, sm_scale, causal, block_q, block_k,
            interpret, true_kv_len, head_rep, window: int = 0,
            strip: int = 0):
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
        num_q_blocks=nq, num_k_blocks=nk, window=window, strip=strip)
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
        block_k=block_k, kv_len=true_kv_len, num_q_blocks=nq,
        num_k_blocks=nk, rep=rep, window=window, strip=strip)
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
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_attention_bh(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                        true_kv_len, head_rep, window=0, strip=0):
    if _v2_eligible(k.shape[1], q.shape[2]):
        return _fwd_v2(q, k, v, sm_scale, causal, block_q, interpret,
                       true_kv_len, head_rep, window, strip)
    if _v3_eligible(k.shape[1], q.shape[2]):
        o, _ = _fwd_v3(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                       true_kv_len, head_rep, window, strip)
        return o
    o, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                true_kv_len, head_rep, window=window)
    return o


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                    true_kv_len, head_rep, window=0, strip=0):
    from jax.ad_checkpoint import checkpoint_name

    if _v2_eligible(k.shape[1], q.shape[2]):
        o = _fwd_v2(q, k, v, sm_scale, causal, block_q, interpret,
                    true_kv_len, head_rep, window, strip)
        # no lse residual: the fused backward recomputes row stats in-kernel
        o = checkpoint_name(o, "flash_out")
        return o, (q, k, v, o)
    if _v3_eligible(k.shape[1], q.shape[2]):
        o, lse = _fwd_v3(q, k, v, sm_scale, causal, block_q, block_k,
                         interpret, true_kv_len, head_rep, window, strip)
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
                    head_rep, window, strip, res, g):
    if len(res) == 4:  # v2 path (see _flash_fwd_rule)
        q, k, v, o = res
        return _bwd_v2(q, k, v, o, g, sm_scale, causal, block_q, interpret,
                       true_kv_len, head_rep, window, strip)
    if res[4].ndim == 3:  # v3 path: compact [bh, 1, S] exp2-domain lse
        q, k, v, o, lse = res
        return _bwd_v3(q, k, v, o, lse, g, sm_scale, causal, block_q,
                       block_k, interpret, true_kv_len, head_rep, window,
                       strip)
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
    its edges (in strips, where the shapes allow: ``_resolve_strip``);
    ``window = 0`` is the program without one.

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
    block_q, block_k = choice.block_q, choice.block_k
    strip = _resolve_strip(_generation(kv_len + pad_k, d), causal,
                           q_len + pad_q, kv_len + pad_k, kv_len, block_q,
                           block_k, window)
    choice = choice._replace(window=window, strip=strip, causal=bool(causal))
    with _CHOICES_LOCK:
        _CHOICES[choice] = _CHOICES.get(choice, 0) + 1
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v

    qf = qp.reshape(b * h, q_len + pad_q, d)
    kf = kp.reshape(b * hkv, kv_len + pad_k, d)
    vf = vp.reshape(b * hkv, kv_len + pad_k, d)
    # kv_len for masking must be the real length: padded keys get masked out
    o = _flash_attention_bh(qf, kf, vf, sm_scale, causal, block_q, block_k,
                            interpret, kv_len, rep, window, strip)
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
