"""GPT-2 family, TPU-native.

Decoder-only transformer written as pure functions over a param pytree, designed
for the sharding engine rather than ported from torch modules:

 - **Layers are stacked** ``[L, ...]`` and executed with ``lax.scan`` — one
   compiled block body regardless of depth, and under ZeRO-3 the per-layer weight
   slice is all-gathered exactly one scan step before use (XLA pipelines the
   gather with the previous layer's compute), reproducing the reference's
   ``PartitionedParameterCoordinator`` prefetch semantics without hooks.
 - ``remat=True`` wraps the block in ``jax.checkpoint`` — the analog of the
   reference's activation checkpointing (``activation_checkpointing/checkpointing.py``).
 - ``tp_rules`` emits Megatron-style column/row parallel PartitionSpecs for the
   attention and MLP weights over the ``tp`` mesh axis.

This is driver config #1's model (GPT-2 125M).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.topology import TP_AXIS
from ..runtime.model import ModelSpec
from ..utils.platform import on_tpu

PyTree = Any


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    hidden_size: int = 768
    mlp_ratio: int = 4
    dropout: float = 0.0
    remat: bool = False
    #: "full" recomputes the whole block in bwd (reference activation
    #: checkpointing); "dots" saves projection outputs and recomputes only
    #: the attention map + elementwise ops (selective checkpointing —
    #: ~13% extra flops instead of ~33%, still O(S) memory)
    remat_policy: str = "dots"
    #: offload saved remat residuals to pinned host memory (the reference's
    #: activation_checkpointing.cpu_checkpointing; see runtime/remat.py)
    remat_offload: bool = False
    tie_embeddings: bool = True
    #: None = auto (Pallas flash attention on TPU, einsum elsewhere);
    #: flash path requires attention-dropout == 0
    use_flash: Optional[bool] = None
    #: flash kernel block sizes; larger blocks amortize grid overhead when
    #: head_dim is small (d=64 -> half-width MXU ops)
    #: 1024x1024 is the measured best for both the v2 (S<=1024) and v3
    #: (S>=2048) kernel paths on v5e (rounds 3-4 builder runs)
    flash_block_q: int = 1024
    flash_block_k: int = 1024
    #: sequence-parallel attention impl when mesh sp>1: auto|ulysses|ring
    sp_impl: str = "auto"
    #: Fused CE head: compute the head's input/weight cotangents during
    #: forward (3 big head matmuls per step instead of the checkpointed
    #: head's 4), chunked so at most [T/ce_chunks, V] logits are live.
    #: Default OFF — measured slower than the checkpointed lse head on v5e
    #: at GPT-2 size (the extra f32 softmax traffic beats the saved
    #: matmul); the option remains for large-vocab/small-d models.
    fused_ce: Optional[bool] = None
    ce_chunks: int = 4
    #: activation fake-quantization bits (compression_training
    #: ``activation_quantization``; None = off).  Matmul inputs in the
    #: block quantize-dequantize with straight-through gradients.
    act_quant_bits: Optional[int] = None
    act_quant_type: str = "symmetric"
    #: random-LTD kept-token count (None/>=S = dense).  Set by the engine's
    #: RandomLTDScheduler (runtime/engine.py _advance_random_ltd); middle
    #: layers process a random ordered subset of this many tokens
    #: (data_pipeline/random_ltd.py).
    random_ltd_keep: Optional[int] = None
    #: which layers drop tokens (reference random_ltd_layer_id_start /
    #: random_ltd_layer_num); default = all middle layers [1, L-1)
    random_ltd_layer_start: int = 1
    random_ltd_layer_num: Optional[int] = None
    #: Route the wte lookup through sparse_embedding_lookup so the DP
    #: gradient exchange ships only touched rows (engine sets this from the
    #: ``sparse_gradients`` config key; see runtime/sparse_tensor.py)
    sparse_embedding_grad: bool = False
    #: True (default): execute the layer stack with lax.scan (O(1) compiled
    #: code size; the remat residuals of every iteration are stacked into
    #: [L, ...] buffers via dynamic-update-slice — measurable HBM write
    #: traffic in backward).  False: unroll a python loop over layers —
    #: residuals stay as L separate buffers (no stacking copies), at the
    #: cost of L× compile time.  Worth it for small L on the perf path.
    scan_layers: bool = True
    #: ZeRO-3 liveness: gather this many layers per scan step (engine sets
    #: it from stage3_prefetch_bucket_size / stage3_max_live_parameters)
    scan_group_size: int = 1

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @property
    def ffn_size(self) -> int:
        return self.hidden_size * self.mlp_ratio

    @staticmethod
    def gpt2_125m() -> "GPT2Config":
        return GPT2Config(num_layers=12, num_heads=12, hidden_size=768)

    @staticmethod
    def tiny(vocab_size: int = 512, max_seq_len: int = 64) -> "GPT2Config":
        return GPT2Config(vocab_size=vocab_size, max_seq_len=max_seq_len,
                          num_layers=2, num_heads=4, hidden_size=64)

    def num_params(self) -> int:
        d, l, v, s = self.hidden_size, self.num_layers, self.vocab_size, \
            self.max_seq_len
        per_layer = (3 * d * d + 3 * d) + (d * d + d) + \
            2 * self.mlp_ratio * d * d + (self.mlp_ratio + 1) * d + 4 * d
        return v * d + s * d + l * per_layer + 2 * d


def init_params(cfg: GPT2Config, rng) -> PyTree:
    d, l = cfg.hidden_size, cfg.num_layers
    f = cfg.ffn_size
    keys = jax.random.split(rng, 8)
    std = 0.02
    # residual-path projections get the GPT-2 1/sqrt(2L) scaled init
    res_std = std / math.sqrt(2 * l)

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(jnp.float32)

    return {
        "wte": normal(keys[0], (cfg.vocab_size, d)),
        "wpe": normal(keys[1], (cfg.max_seq_len, d), 0.01),
        "blocks": {
            "ln1_scale": jnp.ones((l, d)),
            "ln1_bias": jnp.zeros((l, d)),
            "qkv_w": normal(keys[2], (l, d, 3 * d)),
            "qkv_b": jnp.zeros((l, 3 * d)),
            "o_w": normal(keys[3], (l, d, d), res_std),
            "o_b": jnp.zeros((l, d)),
            "ln2_scale": jnp.ones((l, d)),
            "ln2_bias": jnp.zeros((l, d)),
            "fc_w": normal(keys[4], (l, d, f)),
            "fc_b": jnp.zeros((l, f)),
            "proj_w": normal(keys[5], (l, f, d), res_std),
            "proj_b": jnp.zeros((l, d)),
        },
        "lnf_scale": jnp.ones((d,)),
        "lnf_bias": jnp.zeros((d,)),
    }


def _layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _remat_policy(cfg):
    from ..runtime.remat import remat_policy

    return remat_policy(getattr(cfg, "remat_policy", "full"),
                        getattr(cfg, "remat_offload", False))


_warned_sp_dropout = False


def _maybe_dequant(layer, dtype):
    """Expand INT8 weight records (ops/quantization) for ONE layer slice —
    the point-of-use dequant that keeps peak memory at one layer of
    full-precision weights when the engine stores blocks as int8."""
    from ..ops import quantization as quant

    return jax.tree_util.tree_map(
        lambda v: quant.dequantize(v, dtype) if quant.is_quantized(v) else v,
        layer, is_leaf=quant.is_quantized)


def _qmm(x, leaf, dtype=None):
    """``x @ leaf`` where ``leaf`` may be an int8 record: K-grouped (W8A8)
    records run the s8-MXU kernel, N-grouped weight-only records run the
    dequant path (or the opt-in fused kernel — ops/quantized_matmul);
    dense leaves take the plain matmul."""
    from ..ops import quantization as quant

    dtype = dtype or x.dtype
    if quant.is_k_quantized(leaf):
        from ..ops.quantized_matmul import w8a8_matmul

        return w8a8_matmul(x, leaf, out_dtype=dtype)
    if quant.is_quantized(leaf):
        from ..ops.quantized_matmul import quantized_matmul

        return quantized_matmul(x, leaf, out_dtype=dtype)
    return x @ leaf.astype(dtype)


def _qmm_indexed(x, leaf, l, dtype=None):
    """``x @ leaf[l]`` for STACKED per-layer leaves selected by a (possibly
    traced) layer index: K-grouped records run the stacked s8 kernel with
    the layer chosen in-kernel (scalar prefetch — no per-layer weight copy
    in HBM); other leaf kinds dynamic-slice the layer and take the same
    path as :func:`_qmm`."""
    from ..ops import quantization as quant

    dtype = dtype or x.dtype
    if quant.is_k_quantized(leaf):
        from ..ops.quantized_matmul import w8a8_matmul_stacked

        return w8a8_matmul_stacked(x, leaf, l, out_dtype=dtype)
    if quant.is_quantized(leaf):
        from ..ops.quantized_matmul import quantized_matmul

        sliced = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
                  for k, v in leaf.items()}
        return quantized_matmul(x, sliced, out_dtype=dtype)
    w = jax.lax.dynamic_index_in_dim(leaf, l, keepdims=False)
    return x @ w.astype(dtype)


def layer_accessors(layer):
    """Default weight accessors for an accessor-parameterized block body:
    ``get(name)`` reads a small leaf from the pre-sliced layer dict, ``mm(y,
    name, dtype)`` runs the matmul through :func:`_qmm` (identical HLO for
    dense leaves; point-of-use dequant / w8a8 kernel for INT8 records).
    The quantized indexed decode path substitutes stacked-kernel accessors
    instead (:func:`decode_over_layers`)."""
    def mm(y, name, dtype):
        return _qmm(y, layer[name], dtype)

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
    from ..ops import quantization as quant
    from ..ops.quantized_matmul import W8A8_MAX_ROWS, stacked_kernel_enabled

    return (quant.is_k_quantized(blocks[probe])
            and stacked_kernel_enabled()
            and rows <= W8A8_MAX_ROWS
            and os.environ.get("DS_INDEXED_DECODE", "1") != "0")


def _dequant_resident(params, dtype=None):
    """Dequantize the small resident params (embeddings, final LN) up front;
    the stacked ``blocks`` stay int8 and expand per layer in ``_block``."""
    from ..ops import quantization as quant

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
    out = {k: (_maybe_dequant(v, dtype) if k != "blocks" else v)
           for k, v in params.items()}
    return out


def _block(cfg: GPT2Config, x, layer, mask, rng, dropout: float):
    """One transformer block. x: [B, S, D]; layer: per-layer param slice.
    ``mask=None`` means pure causal; the flash/SP fast paths require it (they
    implement causality internally and would silently drop a custom mask)."""
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    aq_bits = getattr(cfg, "act_quant_bits", None)

    def _aq(t):
        if aq_bits is None:
            return t
        from ..compression.ops import quantize_activation

        return quantize_activation(t, aq_bits,
                                   getattr(cfg, "act_quant_type",
                                           "symmetric"))

    with jax.named_scope("layer/attn"):
        y = _aq(_layer_norm(x, layer["ln1_scale"], layer["ln1_bias"]))
        qkv = _qmm(y, layer["qkv_w"]) + layer["qkv_b"].astype(y.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
        from ..parallel import sequence as seq_parallel

        use_flash = cfg.use_flash
        if use_flash is None:
            use_flash = on_tpu()
        if seq_parallel.sp_size() > 1 and dropout > 0.0:
            global _warned_sp_dropout
            if not _warned_sp_dropout:
                _warned_sp_dropout = True
                from ..utils.logging import logger

                logger.warning(
                    "mesh sp>1 with attention dropout>0: sequence-parallel "
                    "attention requires dropout=0; falling back to the "
                    "dense path (quadratic in S)")
        if seq_parallel.sp_size() > 1 and dropout == 0.0 and mask is None:
            attn = seq_parallel.sequence_parallel_attention(
                q, k, v, causal=True, impl=getattr(cfg, "sp_impl", "auto"))
        elif use_flash and dropout == 0.0 and mask is None:
            attn = seq_parallel.mesh_flash_attention(
                q, k, v, causal=True,
                block_q=getattr(cfg, "flash_block_q", 512),
                block_k=getattr(cfg, "flash_block_k", 1024))
        else:
            if mask is None:
                mask = jnp.tril(jnp.ones((s, s), bool))[None, None, :, :]
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
            scores = jnp.where(mask, scores.astype(jnp.float32), -1e9)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            if dropout > 0.0 and rng is not None:
                keep = jax.random.bernoulli(rng, 1.0 - dropout, probs.shape)
                probs = probs * keep / (1.0 - dropout)
            attn = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        attn = _aq(attn.transpose(0, 2, 1, 3).reshape(b, s, d))
        x = x + _qmm(attn, layer["o_w"], x.dtype) + \
            layer["o_b"].astype(x.dtype)
    with jax.named_scope("layer/mlp"):
        y = _aq(_layer_norm(x, layer["ln2_scale"], layer["ln2_bias"]))
        hid = _aq(jax.nn.gelu(_qmm(y, layer["fc_w"]) +
                              layer["fc_b"].astype(y.dtype)))
        x = x + _qmm(hid, layer["proj_w"], x.dtype) + \
            layer["proj_b"].astype(x.dtype)
    return x


def forward(cfg: GPT2Config, params: PyTree, input_ids, rng=None,
            train: bool = True):
    """Token logits. input_ids: [B, S] int32."""
    params = _dequant_resident(params)
    x = _trunk(cfg, params, input_ids, rng=rng, train=train)
    with jax.named_scope("head"):
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
        logits = x @ params["wte"].T.astype(x.dtype)
    return logits


def init_cache(cfg: GPT2Config, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    """Static KV workspace (reference ``inference_context.h``): [L,B,H,S,hd]."""
    shape = (cfg.num_layers, batch_size, cfg.num_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_update(ck, cv, k, v, pos):
    """Write new keys/values into the cache at ``pos``: a scalar writes one
    contiguous [T]-span shared by every row (the classic static-batch decode);
    an int32 [B] vector writes each row's single new entry at its own
    position (continuous-batching slots, T must be 1).  Shared by every
    decode-hook model family."""
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


def _cached_attention(q, k, v, ck, cv, pos, block_tables=None,
                      chunk_valid=None, layer=None, window: int = 0):
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
    Shared by every decode-hook model family."""
    from ..ops.decode_attention import decode_attention, \
        paged_decode_attention

    if block_tables is None:
        ck, cv = cache_update(ck, cv, k, v, pos)
        return decode_attention(q, ck, cv, pos), ck, cv
    from ..ops.paged_kv import paged_cache_update

    ck, cv = paged_cache_update(ck, cv, k, v, pos, block_tables,
                                valid=chunk_valid, layer=layer,
                                ring=bool(window))
    return paged_decode_attention(q, ck, cv, block_tables, pos, layer=layer,
                                  valid=chunk_valid, window=window), ck, cv


def _block_cached_body(cfg: GPT2Config, x, get, mm, ck, cv, pos,
                       block_tables=None, chunk_valid=None, layer=None):
    """One block with KV-cache read/write, parameterized by weight access
    (``get(name)`` small leaf, ``mm(y, name, dtype)`` matmul) so the scan
    and layer-indexed decode paths share the math.  x: [B, T, D]; ck/cv:
    [B, H, S, hd] — or, when ``block_tables`` is given, the whole paged
    pool [L, NB, H, bs, hd] plus this block's ``layer`` index (contract in
    :func:`_cached_attention`); pos: traced global position of x[:, 0] —
    scalar, or int32 [B] per-row positions (continuous-batching decode
    T=1, or paged chunked-prefill bases T>1)."""
    b, t, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    with jax.named_scope("layer/attn"):
        y = _layer_norm(x, get("ln1_scale"), get("ln1_bias"))
        qkv = mm(y, "qkv_w", None) + get("qkv_b").astype(y.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        attn, ck, cv = _cached_attention(q, k, v, ck, cv, pos, block_tables,
                                         chunk_valid, layer)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, t, d)
        x = x + mm(attn, "o_w", x.dtype) + get("o_b").astype(x.dtype)
    with jax.named_scope("layer/mlp"):
        y = _layer_norm(x, get("ln2_scale"), get("ln2_bias"))
        hid = jax.nn.gelu(mm(y, "fc_w", None) + get("fc_b").astype(y.dtype))
        x = x + mm(hid, "proj_w", x.dtype) + get("proj_b").astype(x.dtype)
    return x, ck, cv


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
    contract)."""
    if not paged:
        def sbody(x, xs):
            layer, ck, cv = xs
            x, ck, cv, *aux = step(x, layer, ck, cv, None)
            return x, (ck, cv, *aux)

        x, out = jax.lax.scan(sbody, x, (blocks, cache_k, cache_v))
        return (x, *out)

    def pbody(carry, xs):
        x, pk, pv = carry
        layer, l = xs
        x, pk, pv, *aux = step(x, layer, pk, pv, l)
        return (x, pk, pv), tuple(aux)

    n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
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
    HBM.

    ``paged`` (the caller passes ``block_tables is not None``): both loop
    forms carry the whole stacked pool and hand the body the pool plus the
    layer index (:func:`scan_layers_cached`).  Contiguous caches keep the
    per-layer slice (``layer`` is None there)."""
    from ..ops import quantization as quant

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
                return _qmm_indexed(y, blocks[name], l, dtype)

            if paged:
                return body(x, get, mm, ck_all, cv_all, l)
            ck = jax.lax.dynamic_index_in_dim(ck_all, l, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(cv_all, l, keepdims=False)
            x, ck, cv = body(x, get, mm, ck, cv, None)
            return (x,
                    jax.lax.dynamic_update_index_in_dim(ck_all, ck, l, 0),
                    jax.lax.dynamic_update_index_in_dim(cv_all, cv, l, 0))

        return jax.lax.fori_loop(0, num_layers, ibody,
                                 (x, cache_k, cache_v))

    return scan_layers_cached(
        lambda x, layer, ck, cv, l: body(x, *layer_accessors(layer),
                                         ck, cv, l),
        x, blocks, cache_k, cache_v, paged)


def forward_cached(cfg: GPT2Config, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False):
    """Incremental forward: logits for the LAST input position + updated
    cache — or for EVERY input position when ``all_positions`` is set (the
    speculative-decoding verify head: a K+1-token window is scored in one
    pass, returning [B, T, V] so the scheduler can compare the target's
    greedy choice at each draft position).

    ``lengths`` (optional int32 [B]) is the per-sequence valid length for
    continuous-batching slots:
     - T == 1 (decode): row ``b``'s token sits at global position
       ``lengths[b]`` — per-row cache write, per-row attention prefix.
       ``pos`` is ignored.
     - T > 1 (ragged prefill window): rows are right-padded to T with
       ``pos`` as the shared base (0 for fresh slots); causal attention makes
       the pad positions unreachable from valid queries, and the returned
       logits are gathered at each row's own last prompt token
       (``lengths[b] - 1``) instead of column T-1.

    ``block_tables`` (optional int32 [B, NBPER]) switches the cache to the
    block-paged layout (``ops/paged_kv.py``): cache leaves are the shared
    ``[L, NB, H, block_size, hd]`` pool and each row reaches its tokens
    through its table.  T == 1 keeps the decode contract above; T > 1 is a
    *chunked-prefill* window — ``pos`` may then be int32 [B] per-row chunk
    bases (tokens already cached, e.g. a reused prefix) and ``lengths`` the
    per-row count of real tokens in the window (pad tokens write to the
    scratch block).
    """
    params = _dequant_resident(params)
    b, t = input_ids.shape
    d = cfg.hidden_size
    pos = jnp.asarray(pos, jnp.int32)
    per_row = lengths is not None and t == 1
    with jax.named_scope("embed"):
        if per_row:
            lengths = jnp.asarray(lengths, jnp.int32)
            step_pos = lengths
            wpe = params["wpe"][jnp.clip(lengths, 0,
                                         cfg.max_seq_len - 1)][:, None]
        elif block_tables is not None and pos.ndim == 1:
            # chunked prefill: per-row base positions for a T-token window
            step_pos = pos
            idx = jnp.clip(
                pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :],
                0, cfg.max_seq_len - 1)
            wpe = params["wpe"][idx]                              # [B, T, D]
        else:
            step_pos = pos
            wpe = jax.lax.dynamic_slice(params["wpe"], (pos, 0), (t, d))
        x = (params["wte"][input_ids] + wpe).astype(params["wte"].dtype)
    from ..ops.sp_attention import shard_seq

    # sequence-parallel prefill hook: token-shard hidden states over the
    # mesh sp axis (no-op outside an sp context or when T == 1)
    x = shard_seq(x)

    chunk_valid = jnp.asarray(lengths, jnp.int32) \
        if (block_tables is not None and lengths is not None and t > 1) \
        else None
    x, ks, vs = decode_over_layers(
        lambda x, get, mm, ck, cv, layer: _block_cached_body(
            cfg, x, get, mm, ck, cv, step_pos, block_tables=block_tables,
            chunk_valid=chunk_valid, layer=layer),
        x, params["blocks"], cache["k"], cache["v"], cfg.num_layers,
        paged=block_tables is not None)
    if not all_positions:
        x = _gather_last(x, lengths if not per_row else None)
    with jax.named_scope("head"):
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
        logits = x @ params["wte"].T.astype(x.dtype)
    return logits, {"k": ks, "v": vs}


def _gather_last(x, lengths):
    """Last valid hidden state per row: column T-1 when ``lengths`` is None
    (uniform batch / per-row decode where T == 1), else each row's
    ``lengths[b] - 1`` (ragged prefill).  Shared by the model families'
    ``forward_cached`` heads."""
    if lengths is None:
        return x[:, -1]
    t = x.shape[1]
    idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, t - 1)
    return x[jnp.arange(x.shape[0]), idx]


def _wte_lookup(cfg: GPT2Config, params, input_ids):
    if getattr(cfg, "sparse_embedding_grad", False):
        from ..runtime.sparse_tensor import sparse_embedding_lookup

        return sparse_embedding_lookup(params["wte"], input_ids)
    return params["wte"][input_ids]


def _trunk(cfg: GPT2Config, params, input_ids, rng=None, train: bool = True):
    """Embeddings + all blocks; returns pre-final-LN activations [B, S, D]."""
    b, s = input_ids.shape
    x = _embed(cfg, params, input_ids)
    dropout = cfg.dropout if train else 0.0

    block_fn = _block
    if cfg.remat:
        block_fn = jax.checkpoint(_block, static_argnums=(0, 5),
                                  policy=_remat_policy(cfg))

    # random-LTD: middle layers process a random ordered token subset
    # (reference data_routing/basic_layer.py:13); the kept count is a
    # static shape, and it differs between boundary and middle layers, so
    # the layer loop must unroll (scan needs one uniform body)
    ltd_keep = getattr(cfg, "random_ltd_keep", None)
    use_ltd = (train and rng is not None and ltd_keep is not None
               and ltd_keep < s and cfg.num_layers > 2)

    ltd_lo = getattr(cfg, "random_ltd_layer_start", 1)
    ltd_n = getattr(cfg, "random_ltd_layer_num", None)
    ltd_hi = ltd_lo + ltd_n if ltd_n is not None else cfg.num_layers - 1

    if use_ltd or not getattr(cfg, "scan_layers", True):
        from ..runtime.data_pipeline.random_ltd import (token_drop,
                                                        token_restore)

        for i in range(cfg.num_layers):
            layer = jax.tree_util.tree_map(lambda p: p[i], params["blocks"])
            r = (jax.random.fold_in(rng, i)
                 if (rng is not None and dropout > 0.0) else None)
            if use_ltd and ltd_lo <= i < ltd_hi:
                kept, idx = token_drop(
                    x, jax.random.fold_in(rng, 0x17D + i), ltd_keep)
                kept = block_fn(cfg, kept, layer, None, r, dropout)
                x = token_restore(x, kept, idx)
            else:
                x = block_fn(cfg, x, layer, None, r, dropout)
        return x

    def step(carry, layer):
        x, idx = carry
        r = (jax.random.fold_in(rng, idx) if (rng is not None and dropout > 0.0)
             else None)
        x = block_fn(cfg, x, layer, None, r, dropout)
        return (x, idx + 1)

    # ZeRO-3 liveness: scan_group_size > 1 gathers G layers per scan step
    # (engine sets it from stage3_prefetch_bucket_size / max_live_parameters)
    from ..runtime.zero.liveness import scan_layers_grouped

    (x, _) = scan_layers_grouped(step, (x, jnp.zeros((), jnp.int32)),
                                 params["blocks"],
                                 getattr(cfg, "scan_group_size", 1))
    return x


def loss_from_batch(cfg: GPT2Config, params, batch, rng=None, train: bool = True):
    """Next-token cross entropy. batch: {"input_ids": [B, S]} (targets = shift)
    or {"input_ids", "labels"}; label -100 entries are masked (HF convention).

    The LN + lm-head matmul + CE is checkpointed: backward recomputes the
    [T, V] logits from the saved [T, D] activations instead of storing a
    float32 logit tensor (6.6 GB at B=32, S=1024, V=50k) — the dominant
    activation-memory/HBM-traffic term for small models."""
    if isinstance(batch, (tuple, list)):
        input_ids, labels = batch
    else:
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
    if labels is None:
        labels = input_ids[:, 1:]
        input_ids = input_ids[:, :-1]
    x = _trunk(cfg, params, input_ids, rng=rng, train=train)
    if getattr(cfg, "fused_ce", None):
        return _head_loss_fused(cfg, params, x, labels)
    head = jax.checkpoint(lambda p, x, t: _head_loss(cfg, p, x, t),
                          policy=None)
    return head(params, x, labels)


def tp_rules(cfg: GPT2Config, abstract_params: PyTree) -> PyTree:
    """Megatron-style TP: qkv/fc column-parallel, o/proj row-parallel
    (reference module_inject sharding directions, ``replace_module.py:25``)."""
    specs = {
        "wte": P(TP_AXIS, None),
        "wpe": P(),
        "blocks": {
            "ln1_scale": P(), "ln1_bias": P(),
            "qkv_w": P(None, None, TP_AXIS), "qkv_b": P(None, TP_AXIS),
            "o_w": P(None, TP_AXIS, None), "o_b": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "fc_w": P(None, None, TP_AXIS), "fc_b": P(None, TP_AXIS),
            "proj_w": P(None, TP_AXIS, None), "proj_b": P(),
        },
        "lnf_scale": P(), "lnf_bias": P(),
    }
    return specs


@jax.named_scope("embed")
def _embed(cfg: GPT2Config, params, input_ids):
    s = input_ids.shape[1]
    x = _wte_lookup(cfg, params, input_ids) + params["wpe"][:s]
    return x.astype(params["wte"].dtype)


@jax.named_scope("head")
def _head_loss(cfg: GPT2Config, params, x, targets):
    """Final LN + tied head + CE, as ``lse - label_logit`` so no [T, V]
    log-softmax tensor is ever materialized (XLA fuses the f32 upcast into
    the reductions)."""
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    logits = x @ params["wte"].T.astype(x.dtype)
    valid = targets >= 0  # -100 = ignore (HF convention, same as loss_from_batch)
    safe = jnp.where(valid, targets, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None],
                                 axis=-1)[..., 0].astype(jnp.float32)
    nll = lse - picked
    return jnp.where(valid, nll, 0.0).sum() / jnp.maximum(valid.sum(), 1)


# ------------------------------------------------------------- fused CE head
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ce(w, x2d, targets, n_chunks):
    loss, _ = _fused_ce_fwd(w, x2d, targets, n_chunks)
    return loss


def _fused_ce_fwd(w, x2d, targets, n_chunks):
    """Chunked CE over a tied head: computes loss AND the (unscaled) input /
    weight cotangents during the forward pass.

    The checkpointed head (``loss_from_batch``) runs 4 full [T,D]x[D,V]
    matmuls per train step (fwd logits, bwd recompute, dx, dW); computing
    ``dlogits = softmax - onehot`` while the chunk's logits are live needs
    only 3 and never materializes more than [T/n_chunks, V] of logits.  The
    softmax/one-hot trick is textbook CE backward (cf. the reference's fused
    logits kernels, ``csrc/transformer/softmax_kernels.cu``); loss scaling
    happens in the vjp by the (linear) upstream cotangent.
    """
    n, d = x2d.shape
    v = w.shape[1]
    assert n % n_chunks == 0, (n, n_chunks)
    c = n // n_chunks
    xs = x2d.reshape(n_chunks, c, d)
    ts = targets.reshape(n_chunks, c)
    valid_all = targets >= 0
    denom = jnp.maximum(valid_all.sum(), 1).astype(jnp.float32)

    def chunk(xc, tc):
        logits = (xc @ w).astype(jnp.float32)            # [c, V]
        valid = tc >= 0
        safe = jnp.where(valid, tc, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)           # [c]
        picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        loss = jnp.where(valid, lse - picked, 0.0).sum() / denom
        # dlogits of mean-NLL (unscaled by upstream cotangent).  gc is cast
        # to the param dtype for the MXU matmuls: fine for bf16 (f32
        # exponent range), lossy for fp16 where tiny unscaled entries land
        # in the subnormal range — prefer bf16 training with fused_ce.
        p = jnp.exp(logits - lse[:, None])
        g = p.at[jnp.arange(c), safe].add(-1.0)
        g = jnp.where(valid[:, None], g, 0.0) / denom     # [c, V] f32
        gc = g.astype(w.dtype)
        # MXU inputs stay in param dtype; outputs come out f32 so unscaled
        # fp16 grads don't flush to subnormals before the bwd ct multiply
        dxi = jax.lax.dot_general(gc, w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dwi = jax.lax.dot_general(xc, gc, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return loss, dxi, dwi                             # loss, [c,D], [D,V]

    # unrolled chunk loop (a scan's dw carry would copy [D, V] f32 per
    # iteration and serialize; unrolled, XLA overlaps chunk i+1's logits
    # with chunk i's grad matmuls)
    loss = jnp.zeros((), jnp.float32)
    dw = jnp.zeros((d, v), jnp.float32)
    dxs = []
    for i in range(n_chunks):
        li, dxi, dwi = chunk(xs[i], ts[i])
        loss += li
        dw += dwi
        dxs.append(dxi)
    dx = jnp.concatenate(dxs, axis=0) if n_chunks > 1 else dxs[0]
    # Residuals stay f32: under fp16 loss scaling the upstream cotangent
    # (the scale) is applied in _fused_ce_bwd, and casting the UNSCALED
    # grads to fp16 here would underflow exactly the small values the
    # scaler exists to preserve.  One f32 [D,V] + [N,D] residual is the
    # price; the cast to param dtype happens after the ct multiply.  The
    # target dtypes ride as zero-size arrays (a dtype object is not a
    # valid jax residual leaf).
    return loss, (jnp.zeros((0,), w.dtype), jnp.zeros((0,), x2d.dtype),
                  dw, dx)


def _fused_ce_bwd(n_chunks, res, ct):
    w_proto, x_proto, dw, dx = res
    ct = ct.astype(jnp.float32)
    return ((ct * dw).astype(w_proto.dtype), (ct * dx).astype(x_proto.dtype),
            None)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


@jax.named_scope("head")
def _head_loss_fused(cfg: GPT2Config, params, x, targets):
    """LN + tied-head CE via the chunked fused-backward formulation."""
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    b, s, d = x.shape
    n = b * s
    n_chunks = getattr(cfg, "ce_chunks", 4)
    while n % n_chunks:
        n_chunks -= 1
    return _fused_ce(params["wte"].T.astype(x.dtype), x.reshape(n, d),
                     targets.reshape(n), n_chunks)


def build(cfg: Optional[GPT2Config] = None, **overrides) -> ModelSpec:
    cfg = cfg or GPT2Config(**overrides)

    def init_fn(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch, rng=None, train=True):
        return loss_from_batch(cfg, params, batch, rng=rng, train=train)

    def apply_fn(params, batch, rng=None):
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return forward(cfg, params, input_ids, rng=rng, train=False)

    def block_fn(layer, x, rng=None):
        return _block(cfg, x, layer, None, rng,
                      cfg.dropout if rng is not None else 0.0)

    pipeline_hooks = {
        "blocks_key": ("blocks",),
        "embed_fn": lambda params, ids: _embed(cfg, params, ids),
        "block_fn": block_fn,
        "head_loss_fn": lambda params, x, tgt: _head_loss(cfg, params, x, tgt),
        "dropout": cfg.dropout,
    }

    decode_hooks = {
        "init_cache": lambda b, s, dtype=jnp.bfloat16: init_cache(cfg, b, s,
                                                                  dtype),
        "forward_cached": lambda params, ids, cache, pos, lengths=None,
            block_tables=None, all_positions=False:
            forward_cached(cfg, params, ids, cache, pos, lengths,
                           block_tables, all_positions),
        # learned absolute positions: decoding past this silently clamps the
        # wpe dynamic_slice, so the engine must reject it up front
        "max_seq_len": cfg.max_seq_len,
        # per-sequence decode positions (continuous-batching serving)
        "supports_lengths": True,
        # block-paged KV layout + chunked prefill (paged serving)
        "supports_paged": True,
        # all-position logits over a K+1 window (speculative verify head)
        "supports_verify": True,
        # int8 pool records flow through this family's cached attention
        # untouched (all KV reads/writes go through ops/paged_kv), so the
        # serving engine may quantize the pool (quantize="kv8")
        "supports_kv_quant": True,
        # logits feed the on-device sampler unchanged (no fused head-side
        # argmax / renorm), so per-slot temperature/top-k/top-p holds
        "supports_sampling": True,
    }

    return ModelSpec(
        init_fn=init_fn, model_config=cfg, loss_fn=loss_fn, apply_fn=apply_fn,
                     tp_rules=lambda ap: tp_rules(cfg, ap),
                     flops_per_token=6.0 * cfg.num_params(),
                     pipeline_hooks=pipeline_hooks,
                     decode_hooks=decode_hooks,
                     quant_aware=True,
                     name=f"gpt2-{cfg.num_layers}l-{cfg.hidden_size}d")
