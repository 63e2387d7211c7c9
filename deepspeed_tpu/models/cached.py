"""The cached forward: what every family's ``forward_cached`` is built from.

A family (``gpt2``, ``opt``, ``bloom``, ``llama`` + ``mixtral``, ``gptj``,
``gptneo``, ``gptneox``) owns its config, its parameters, its embedding, its
head and ONE block body ``body(x, get, mm, ck, cv, layer) -> (x, ck, cv)``.
Everything else about serving a window of tokens against a KV cache lives
here, and nothing here knows a family:

 - **the window** (:func:`window`): which rows decode and which are a
   chunk, where a row's logits are gathered, what a pad writes — THE
   statement of the ``lengths`` / ``block_tables`` / ``all_positions``
   contract of every ``forward_cached``;
 - **weight access** (:func:`layer_accessors`, :func:`qmm`,
   :func:`qmm_indexed`, :func:`dequant_resident`): a body reads its layer
   through ``get(name)`` / ``mm(y, name, dtype)`` so that dense leaves,
   INT8 weight-only records and K-grouped W8A8 records run the same math;
 - **the caches**: the contiguous ``[L, B, H, S, hd]`` workspace of
   ``InferenceEngine.generate`` (:func:`init_kv_cache`,
   :func:`cache_update`) and the block-paged pool of ``ServingEngine``
   (``ops/paged_kv.py``), written and attended by :func:`cached_attention`;
 - **the three layer loops**, side by side because a new architecture picks
   one of them and should not write a fourth:

   ================================ ==========================================
   :func:`decode_over_layers`       a dense family: the scan below, or — W8A8
                                    records at decode shapes — a layer-INDEXED
                                    ``fori_loop`` whose matmuls pick the layer
                                    in-kernel
   :func:`scan_layers_cached`       a body that needs the whole layer dict or
                                    hands back a per-layer record (mixtral's
                                    routed FFN, its indexer's third leaf)
   :func:`scan_periods_cached`      layers of several KINDS (full / sliding
                                    window): a scan over periods with the
                                    period written out, a pool and a table a
                                    kind
   ================================ ==========================================

   On a paged cache all three CARRY the whole pool and hand the body the
   pool plus a layer index; nothing slices a layer out or re-stacks it, so
   a program that donates the pool gets it back in the same buffer.
"""

from __future__ import annotations

import os
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops import decode_attention as da
from ..ops import paged_kv
from ..ops import quantization as quant
from ..ops import quantized_matmul as qmm_ops


# --------------------------------------------------------------- weight access
def _maybe_dequant(layer, dtype):
    """Expand INT8 weight records (ops/quantization) for ONE layer slice —
    the point-of-use dequant that keeps peak memory at one layer of
    full-precision weights when the engine stores blocks as int8."""
    return jax.tree_util.tree_map(
        lambda v: quant.dequantize(v, dtype) if quant.is_quantized(v) else v,
        layer, is_leaf=quant.is_quantized)


def qmm(x, leaf, dtype=None):
    """``x @ leaf`` where ``leaf`` may be an int8 record: K-grouped (W8A8)
    records run the s8-MXU kernel, N-grouped weight-only records run the
    dequant path (or the opt-in fused kernel — ops/quantized_matmul);
    dense leaves take the plain matmul (the identical ``x @ w.astype``
    HLO)."""
    dtype = dtype or x.dtype
    if quant.is_k_quantized(leaf):
        return qmm_ops.w8a8_matmul(x, leaf, out_dtype=dtype)
    if quant.is_quantized(leaf):
        return qmm_ops.quantized_matmul(x, leaf, out_dtype=dtype)
    return x @ leaf.astype(dtype)


def qmm_indexed(x, leaf, l, dtype=None):
    """``x @ leaf[l]`` for STACKED per-layer leaves selected by a (possibly
    traced) layer index: K-grouped records run the stacked s8 kernel with
    the layer chosen in-kernel (scalar prefetch — no per-layer weight copy
    in HBM); other leaf kinds dynamic-slice the layer and take the same
    path as :func:`qmm`."""
    dtype = dtype or x.dtype
    if quant.is_k_quantized(leaf):
        return qmm_ops.w8a8_matmul_stacked(x, leaf, l, out_dtype=dtype)
    if quant.is_quantized(leaf):
        sliced = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
                  for k, v in leaf.items()}
        return qmm_ops.quantized_matmul(x, sliced, out_dtype=dtype)
    w = jax.lax.dynamic_index_in_dim(leaf, l, keepdims=False)
    return x @ w.astype(dtype)


def layer_accessors(layer):
    """Default weight accessors for an accessor-parameterized block body:
    ``get(name)`` reads a small leaf from the pre-sliced layer dict, ``mm(y,
    name, dtype)`` runs the matmul through :func:`qmm` (identical HLO for
    dense leaves; point-of-use dequant / w8a8 kernel for INT8 records).
    The quantized indexed decode path substitutes stacked-kernel accessors
    instead (:func:`decode_over_layers`)."""
    def mm(y, name, dtype):
        return qmm(y, layer[name], dtype)

    return layer.__getitem__, mm


def use_indexed_decode(blocks, probe: str = "qkv_w",
                       rows: int = 1) -> bool:
    """Trace-time dispatch for quantized serving: run the layer-INDEXED
    decode loop (stacked s8 kernel selects the layer in-kernel — no
    per-layer int8 weight copy in HBM) instead of the scan.  False when the
    stacked kernel wouldn't engage (TP, kernel off, or ``rows`` beyond the
    kernel's decode-shaped cap — prefill traces and big batches) — there
    the indexed loop would only add KV-stack slice/update traffic.
    ``DS_INDEXED_DECODE=0`` is the kill switch (on-chip A/B)."""
    return (quant.is_k_quantized(blocks[probe])
            and qmm_ops.stacked_kernel_enabled()
            and rows <= qmm_ops.W8A8_MAX_ROWS
            and os.environ.get("DS_INDEXED_DECODE", "1") != "0")


def dequant_resident(params, dtype=None):
    """Dequantize the small resident params (embeddings, final LN) up front;
    the stacked ``blocks`` stay int8 and expand per layer in the body."""
    leaves = jax.tree_util.tree_leaves(params, is_leaf=quant.is_quantized)
    if not any(quant.is_quantized(v) for v in leaves):
        return params
    if dtype is None:
        # compute dtype = dtype of the small unquantized float leaves
        # (norm scales stay below quantize_pytree's min_size filter)
        dtype = next((v.dtype for v in leaves
                      if not quant.is_quantized(v)
                      and jnp.issubdtype(v.dtype, jnp.floating)),
                     jnp.bfloat16)
    return {k: (_maybe_dequant(v, dtype) if k != "blocks" else v)
            for k, v in params.items()}


# ------------------------------------------------------------------ the caches
def init_kv_cache(layers: int, batch: int, heads: int, max_len: int, hd: int,
                  dtype=jnp.bfloat16):
    """Static KV workspace (reference ``inference_context.h``): ``k`` and
    ``v`` ``[L, B, H, S, hd]`` (``H`` the KV heads).  The serving engine
    calls the same hook with (blocks, block size) for (B, S): the paged
    pool ``[L, NB, H, bs, hd]``."""
    shape = (layers, batch, heads, max_len, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_update(ck, cv, k, v, pos):
    """Write new keys/values into the contiguous cache at ``pos``: a scalar
    writes one contiguous [T]-span shared by every row (the classic
    static-batch decode); an int32 [B] vector writes each row's single new
    entry at its own position (continuous-batching slots, T must be 1)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, 0, pos, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, 0, pos, 0))
        return ck, cv
    assert k.shape[2] == 1, "per-sequence positions require T == 1"
    rows = jnp.arange(k.shape[0])
    ck = ck.at[rows, :, pos].set(k[:, :, 0].astype(ck.dtype))
    cv = cv.at[rows, :, pos].set(v[:, :, 0].astype(cv.dtype))
    return ck, cv


def cached_attention(q, k, v, ck, cv, pos, block_tables=None,
                     chunk_valid=None, layer=None, window: int = 0,
                     sm_scale: Optional[float] = None):
    """Write new KV + attend, on either cache layout.  Contiguous
    (``block_tables is None``): ck/cv are one layer's [B, H, S, hd]
    per-sequence regions.  Paged: ck/cv are the WHOLE stacked
    [L, NB, H, bs, hd] pool and ``layer`` the (traced) index of the layer
    being run — the write and the read address the pool in place as
    (layer, physical block, head, offset) through ``block_tables`` int32
    [B, NBPER] (``ops/paged_kv.py``), so the caller carries the pool
    through its layer loop untouched.  ``chunk_valid`` (int32 [B]) marks
    how many of a T>1 chunk's tokens are real — pads write to the scratch
    block, and the read of a prefill chunk walks the blocks ``pos +
    chunk_valid`` reaches and no further.  ``window`` (static, paged
    only): a sliding-window layer — ck/cv and ``block_tables`` are the
    window kind's leaves and ring (``ops/paged_kv.py`` "Layer kinds").
    ``sm_scale``: the scores' scale where it is not ``head_dim ** -0.5``."""
    if block_tables is None:
        with jax.named_scope("layer/attn/kv_write"):
            ck, cv = cache_update(ck, cv, k, v, pos)
        with jax.named_scope("layer/attn/core"):
            out = da.decode_attention(q, ck, cv, pos, sm_scale=sm_scale)
        return out, ck, cv
    # (the paged write names its own scope: ops/paged_kv.py)
    ck, cv = paged_kv.paged_cache_update(ck, cv, k, v, pos, block_tables,
                                         valid=chunk_valid, layer=layer,
                                         ring=bool(window))
    with jax.named_scope("layer/attn/core"):
        out = da.paged_decode_attention(q, ck, cv, block_tables, pos,
                                        layer=layer, valid=chunk_valid,
                                        window=window, sm_scale=sm_scale)
    return out, ck, cv


# ------------------------------------------------------------------ the window
class Window(NamedTuple):
    """What :func:`window` made of a ``forward_cached`` call's operands."""
    #: where the window's first token sits: a scalar (every row) or int32
    #: ``[B]`` (a row each) — position embeddings, rotary offsets, the cache
    #: write and the attention prefix all follow it
    step_pos: Any
    #: int32 ``[B]``, the real tokens of a paged chunk's rows; else None
    chunk_valid: Optional[Any]
    #: what :func:`gather_last` takes: ``lengths`` where rows are
    #: right-padded (T > 1), None where the last column is every row's
    gather: Optional[Any]
    #: the cache is the block-paged pool
    paged: bool


def window(input_ids, pos, lengths=None, block_tables=None) -> Window:
    """The contract of ``forward_cached(params, input_ids [B, T], cache,
    pos, lengths=None, block_tables=None, all_positions=False) -> (logits,
    cache)`` in every family, decided in this one place.

    With neither option the call is the static batch of
    ``InferenceEngine.generate``: all rows hold T tokens at the shared
    scalar ``pos``, the cache is the contiguous ``[L, B, H, S, hd]``
    workspace and the logits are column T-1's, ``[B, V]``.

    ``lengths`` (int32 ``[B]``) makes the rows continuous-batching slots:

     - T == 1, a decode step: row ``b``'s token sits at position
       ``lengths[b]`` — its position embedding or rotary offset, its cache
       write and its attention prefix are per row.  ``pos`` is ignored.
     - T > 1, a ragged prefill window: rows are right-padded to T with
       ``pos`` their base (0 for fresh slots); causal attention makes the
       pads unreachable from real queries and each row's logits are
       gathered at its own last token, ``lengths[b] - 1``.

    ``block_tables`` (int32 ``[B, NBPER]``; a dict ``{"full", "window"}``
    of them for a model with layer kinds) makes the cache the block-paged
    pool (``ops/paged_kv.py``): its leaves are shared ``[L, NB, H,
    block_size, hd]`` stacks and a row reaches its tokens through its
    table.  T == 1 keeps the decode contract above.  T > 1 is a
    chunked-prefill (or speculative verify) window: ``pos`` may then be
    int32 ``[B]``, each row's base (tokens already cached, e.g. a reused
    prefix), and ``lengths`` counts the REAL tokens of each row's window —
    a pad writes to the scratch block 0 and the read walks no block past
    ``pos + lengths``.

    ``all_positions`` (the caller's, after the layers): logits for every
    position, ``[B, T, V]``, not the gathered row — the verify head scores
    a K+1-token window in one pass."""
    pos = jnp.asarray(pos, jnp.int32)
    t = input_ids.shape[1]
    per_row = lengths is not None and t == 1
    paged = block_tables is not None
    return Window(
        step_pos=jnp.asarray(lengths, jnp.int32) if per_row else pos,
        chunk_valid=jnp.asarray(lengths, jnp.int32)
        if (paged and lengths is not None and t > 1) else None,
        gather=None if per_row else lengths, paged=paged)


def live_tokens(input_ids, lengths=None, block_tables=None):
    """bool ``[B, T]``: which input positions are somebody's tokens.  A
    paged decode step (T == 1) runs every slot, idle ones with an all-scratch
    (zero) block table; a paged prefill chunk (T > 1) is right-padded to
    ``lengths``.  Without a paged table every position counts."""
    b, t = input_ids.shape
    if block_tables is None:
        return jnp.ones((b, t), bool)
    if isinstance(block_tables, dict):     # a table per layer kind
        block_tables = block_tables["full"]
    if t == 1 or lengths is None:
        return jnp.broadcast_to(block_tables[:, :1] != 0, (b, t))
    return jnp.arange(t)[None, :] < jnp.asarray(lengths)[:, None]


def gather_last(x, lengths):
    """Last valid hidden state per row: column T-1 when ``lengths`` is None
    (uniform batch / per-row decode where T == 1), else each row's
    ``lengths[b] - 1`` (ragged prefill) — :attr:`Window.gather`."""
    if lengths is None:
        return x[:, -1]
    t = x.shape[1]
    idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, t - 1)
    return x[jnp.arange(x.shape[0]), idx]


# ------------------------------------------------------------- the layer loops
def scan_layers_cached(step, x, blocks, cache_k, cache_v, paged: bool):
    """``lax.scan`` of ``step(x, layer_params, ck, cv, l) -> (x, ck, cv)``
    over the stacked ``blocks``, on either cache layout.  A ``step`` that
    returns a fourth value (a small per-layer record: mixtral's routing
    counts) gets it back stacked ``[L, ...]`` as a fourth result.

    Contiguous (``paged=False``): the stacked [L, B, H, S, hd] cache rides
    as ``xs`` beside the weights, each step gets its own layer's slice
    (``l`` is None) and the updated slices re-stack as ``ys``.

    Paged: the stacked pool [L, NB, H, bs, hd] is the loop CARRY and each
    step gets the whole pool plus its layer index ``l`` — nothing slices a
    layer out of the pool or re-stacks it, so the compiled ``while`` updates
    the (donated) pool buffer in place (``ops/paged_kv.py`` has the
    contract).  ``cache_k`` may be any pytree the step understands (a pair
    ``(K, extra)`` for an indexer) and ``cache_v`` None (a latent pool has
    one leaf)."""
    if not paged:
        def sbody(x, xs):
            layer, ck, cv = xs
            x, ck, cv, *aux = step(x, layer, ck, cv, None)
            return x, (ck, cv, *aux)

        with jax.named_scope("layer"):
            x, out = jax.lax.scan(sbody, x, (blocks, cache_k, cache_v))
        return (x, *out)

    def pbody(carry, xs):
        x, pk, pv = carry
        layer, l = xs
        x, pk, pv, *aux = step(x, layer, pk, pv, l)
        return (x, pk, pv), tuple(aux)

    n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    with jax.named_scope("layer"):
        carry, aux = jax.lax.scan(
            pbody, (x, cache_k, cache_v),
            (blocks, jnp.arange(n, dtype=jnp.int32)))
    return (*carry, *aux)


def decode_over_layers(body, x, blocks, cache_k, cache_v, num_layers,
                       probe: str = "qkv_w", paged: bool = False):
    """Run ``body(x, get, mm, ck, cv, layer) -> (x, ck, cv)`` over all
    layers: a ``lax.scan`` over pre-sliced layers normally, or — quantized
    serving with the stacked s8 kernel available — a layer-indexed
    ``fori_loop`` whose matmuls select the layer in-kernel (scalar
    prefetch), so no per-layer int8 weight copy is ever materialized in
    HBM.  ``probe`` names a leaf every layer of the family has.

    ``paged`` (:attr:`Window.paged`): both loop forms carry the whole
    stacked pool and hand the body the pool plus the layer index
    (:func:`scan_layers_cached`).  Contiguous caches keep the per-layer
    slice (``layer`` is None there)."""
    stack_l = jax.tree_util.tree_leaves(
        blocks, is_leaf=quant.is_record)[0]
    if quant.is_record(stack_l):
        stack_l = stack_l.get("qk", stack_l.get("q"))
    stack_l = stack_l.shape[0]
    if stack_l != num_layers:
        # fail-fast like lax.scan would: the fori_loop path's clamped
        # dynamic indexing would otherwise silently re-run the last layer
        raise ValueError(
            f"stacked blocks carry {stack_l} layers but num_layers="
            f"{num_layers}")
    if use_indexed_decode(blocks, probe, rows=x.shape[0] * x.shape[1]):
        def ibody(l, carry):
            x, ck_all, cv_all = carry

            def get(name):
                return jax.lax.dynamic_index_in_dim(blocks[name], l,
                                                    keepdims=False)

            def mm(y, name, dtype):
                return qmm_indexed(y, blocks[name], l, dtype)

            if paged:
                return body(x, get, mm, ck_all, cv_all, l)
            ck = jax.lax.dynamic_index_in_dim(ck_all, l, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(cv_all, l, keepdims=False)
            x, ck, cv = body(x, get, mm, ck, cv, None)
            return (x,
                    jax.lax.dynamic_update_index_in_dim(ck_all, ck, l, 0),
                    jax.lax.dynamic_update_index_in_dim(cv_all, cv, l, 0))

        with jax.named_scope("layer"):
            return jax.lax.fori_loop(0, num_layers, ibody,
                                     (x, cache_k, cache_v))

    return scan_layers_cached(
        lambda x, layer, ck, cv, l: body(x, *layer_accessors(layer),
                                         ck, cv, l),
        x, blocks, cache_k, cache_v, paged)


#: the cache leaves and the table of each layer kind of a patterned model.
#: ``full`` / ``sliding`` hold a key and a value a KV head a token
#: (``ops/paged_kv.py`` "Layer kinds"), ``latent`` ONE leaf (a latent a token,
#: under the full kind's table); the STATE kind's layers — ``kda`` (a gated
#: delta rule, ``models/kimi_linear.py``), ``ssm`` (a state-space scan,
#: ``models/granite_hybrid.py``) and ``power`` (power retention,
#: ``models/brumby.py``): the leaves' shapes are the family's —
#: hold no token at all: ``state`` and the leaf the family keeps beside it
#: (``paged_kv.STATE_COMPANIONS``) are indexed by ROW
#: (``ops/paged_kv.py`` "The state kind") and their "table" is ``slot``,
#: int32 ``[B]`` — the row of the leaves each row of the call owns (absent
#: in a decode step beside a paged table, where row ``b`` IS row ``b``).
#: ``latent_indexed`` is a latent layer under a learned selection: the latent
#: leaf and the indexer's key beside it, both under the full kind's table;
#: ``latent_sliding`` a latent layer under a window: ONE leaf of its own width
#: (``latw``) under the window kind's ring (``models/dots3.py``)
KIND_LEAVES = {"full": ("k", "v", "full"), "sliding": ("kw", "vw", "window"),
               "latent": ("latent", None, "full"),
               "latent_indexed": ("latent", "idx", "full"),
               "latent_sliding": ("latw", None, "window"),
               **{kind: ("state", beside, "slot")
                  for kind, beside in paged_kv.STATE_COMPANIONS.items()}}


def scan_periods_cached(kinds, num_layers: int, step, x, blocks, cache,
                        block_tables, head: int = 0, before=None):
    """The layer loop of a patterned model (``kinds``: the period, e.g.
    ``("sliding",) * 3 + ("full",)``) over the block-paged pool: a
    ``lax.scan`` over PERIODS whose body is the period's layers written
    out, so that each layer's kind — rotated or not, how far it reaches,
    which leaves and which table it addresses — is static in the program,
    where a ``lax.cond`` on a traced kind would hold both branches and both
    pools in every layer.  Where all layers have the same weight shapes the
    ``[L, ...]`` stacks stay, and layer ``period * P + j`` is read out of
    them at a traced index.  BY KIND: where the kinds' weights differ in
    shape (a gated delta-rule layer beside a latent one) ``blocks`` is
    ``{kind: that kind's [L_kind, ...] stacks}`` — told from the flat stacks
    by its values being trees — and a layer is read out of its kind's
    stacks at its place among its kind's layers.  ``head``: that many
    leading PERIODS are written out before the scan, with a static period
    number (a model whose first layers differ from the rest: a leading
    dense FFN).  ``before`` (stacks by kind): ``{kind: layers of it that lie
    BEFORE this loop's first}`` — a model whose layers are not one whole
    number of periods (a leading layer outside the period, a partial closing
    period) runs a loop a stretch, each counting on from the one before.
    ``step(x, layer, ck, cv, index, table, kind) -> (x, ck,
    cv, aux)`` with ``index`` the layer's place among its KIND's layers
    (``ops/paged_kv.py`` "Layer kinds"; stacks by kind add an eighth
    argument, the layer's number in the model — an ``int`` in a head
    period); ``cache`` and ``block_tables`` hold what ``KIND_LEAVES`` names
    (a leaf or table a kind lacks is ``None``).  -> ``(x, cache, aux
    stacked [L, ...])``."""
    p, n = len(kinds), num_layers
    by_kind = all(isinstance(blocks.get(kind), dict) for kind in kinds)
    before = dict(before or {})
    number0 = sum(before.values())

    def body(carry, period):
        x, pools = carry
        pools, auxes = dict(pools), []
        for j, kind in enumerate(kinds):
            same = [i for i in range(p) if kinds[i] == kind]
            ck, cv, table = KIND_LEAVES[kind]
            # the layer's weights, read where they lie in the stacks: the
            # slices fuse into their matmuls, with a constant index (a
            # one-period scan unrolls) as with a traced one.  (What PR 34
            # saw copied here was ``q_w``, transposed for a head split XLA
            # had folded into its dot: ``llama._attend_cached``)
            if by_kind:
                index = before.get(kind, 0) + period * len(same) \
                    + same.index(j)
                layer = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, index, keepdims=False), blocks[kind])
                more = (number0 + period * p + j,)
            else:
                layer = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, period * p + j, keepdims=False), blocks)
                index, more = period * len(same) + same.index(j), ()
            x, *leaves, aux = step(
                x, layer, pools.get(ck), pools.get(cv), index,
                block_tables.get(table), kind, *more)
            pools.update((name, leaf) for name, leaf in zip((ck, cv), leaves)
                         if name is not None)
            auxes.append(aux)
        return (x, pools), jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *auxes)

    heads = []
    for period in range(head):
        with jax.named_scope("layer"):
            (x, cache), aux = body((x, cache), period)
        heads.append(aux)
    if head < n // p:
        with jax.named_scope("layer"):
            (x, cache), aux = jax.lax.scan(
                body, (x, cache), jnp.arange(head, n // p, dtype=jnp.int32))
        aux = jax.tree_util.tree_map(
            lambda a: a.reshape((n - head * p,) + a.shape[2:]), aux)
        heads.append(aux)
    return x, cache, jax.tree_util.tree_map(
        lambda *a: jnp.concatenate(a) if len(a) > 1 else a[0], *heads)
