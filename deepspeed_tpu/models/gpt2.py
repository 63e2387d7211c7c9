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
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.chunked_ce import chunked_ce, whole_chunks
from ..ops.sp_attention import shard_seq
from ..parallel.topology import TP_AXIS
from ..runtime.model import ModelSpec
from ..utils.platform import on_tpu
from .cached import (cached_attention, decode_over_layers, dequant_resident,
                     gather_last, init_kv_cache, qmm, window)

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
    #: the blocks' shardings when the engine pipelines the layer loop
    #: (``overlap_comm`` at ZeRO-3, ``liveness.scan_layers_prefetched``)
    scan_prefetch: Any = None

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

    from ..parallel import sequence as seq_parallel

    with jax.named_scope("layer/attn"):
        with jax.named_scope("layer/norm"):
            y = _aq(_layer_norm(x, layer["ln1_scale"], layer["ln1_bias"]))
        with jax.named_scope("layer/attn/qkv"):
            qkv = qmm(y, layer["qkv_w"]) + layer["qkv_b"].astype(y.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
            k = k.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
            v = v.reshape(b, s, h, hd).transpose(0, 2, 1, 3)

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
        with jax.named_scope("layer/attn/core"):
            if seq_parallel.sp_size() > 1 and dropout == 0.0 \
                    and mask is None:
                attn = seq_parallel.sequence_parallel_attention(
                    q, k, v, causal=True,
                    impl=getattr(cfg, "sp_impl", "auto"))
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
                    keep = jax.random.bernoulli(rng, 1.0 - dropout,
                                                probs.shape)
                    probs = probs * keep / (1.0 - dropout)
                attn = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        with jax.named_scope("layer/attn/out"):
            attn = _aq(attn.transpose(0, 2, 1, 3).reshape(b, s, d))
            x = x + qmm(attn, layer["o_w"], x.dtype) + \
                layer["o_b"].astype(x.dtype)
    with jax.named_scope("layer/mlp"):
        with jax.named_scope("layer/norm"):
            y = _aq(_layer_norm(x, layer["ln2_scale"], layer["ln2_bias"]))
        hid = _aq(jax.nn.gelu(qmm(y, layer["fc_w"]) +
                              layer["fc_b"].astype(y.dtype)))
        x = x + qmm(hid, layer["proj_w"], x.dtype) + \
            layer["proj_b"].astype(x.dtype)
    return x


def forward(cfg: GPT2Config, params: PyTree, input_ids, rng=None,
            train: bool = True):
    """Token logits. input_ids: [B, S] int32."""
    params = dequant_resident(params)
    x = _trunk(cfg, params, input_ids, rng=rng, train=train)
    with jax.named_scope("layer/norm"):
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    with jax.named_scope("head"):
        logits = x @ params["wte"].T.astype(x.dtype)
    return logits


def init_cache(cfg: GPT2Config, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    return init_kv_cache(cfg.num_layers, batch_size, cfg.num_heads, max_len,
                         cfg.head_dim, dtype)


def _block_cached_body(cfg: GPT2Config, x, get, mm, ck, cv, pos,
                       block_tables=None, chunk_valid=None, layer=None):
    """One block with KV-cache read/write, parameterized by weight access
    (``get(name)`` small leaf, ``mm(y, name, dtype)`` matmul) so the scan
    and layer-indexed decode paths share the math.  x: [B, T, D]; ck/cv:
    [B, H, S, hd] — or, when ``block_tables`` is given, the whole paged
    pool [L, NB, H, bs, hd] plus this block's ``layer`` index (contract in
    ``cached.cached_attention``); pos: ``cached.Window.step_pos``."""
    b, t, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    with jax.named_scope("layer/attn"):
        with jax.named_scope("layer/norm"):
            y = _layer_norm(x, get("ln1_scale"), get("ln1_bias"))
        with jax.named_scope("layer/attn/qkv"):
            qkv = mm(y, "qkv_w", None) + get("qkv_b").astype(y.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
            k = k.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
            v = v.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        attn, ck, cv = cached_attention(q, k, v, ck, cv, pos, block_tables,
                                        chunk_valid, layer)
        with jax.named_scope("layer/attn/out"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, t, d)
            x = x + mm(attn, "o_w", x.dtype) + get("o_b").astype(x.dtype)
    with jax.named_scope("layer/mlp"):
        with jax.named_scope("layer/norm"):
            y = _layer_norm(x, get("ln2_scale"), get("ln2_bias"))
        hid = jax.nn.gelu(mm(y, "fc_w", None) + get("fc_b").astype(y.dtype))
        x = x + mm(hid, "proj_w", x.dtype) + get("proj_b").astype(x.dtype)
    return x, ck, cv


def _embed_cached(cfg: GPT2Config, params, input_ids, pos):
    """Token + learned position embeddings of a cached window whose first
    token sits at ``pos`` (``cached.Window.step_pos``): one slice of ``wpe``
    for a shared scalar, a clipped lookup a row for int32 [B]."""
    t = input_ids.shape[1]
    with jax.named_scope("embed"):
        if pos.ndim == 0:
            wpe = jax.lax.dynamic_slice(params["wpe"], (pos, 0),
                                        (t, cfg.hidden_size))
        elif t == 1:
            wpe = params["wpe"][jnp.clip(pos, 0,
                                         cfg.max_seq_len - 1)][:, None]
        else:
            idx = jnp.clip(
                pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :],
                0, cfg.max_seq_len - 1)
            wpe = params["wpe"][idx]                              # [B, T, D]
        return (params["wte"][input_ids] + wpe).astype(params["wte"].dtype)


def forward_cached(cfg: GPT2Config, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False):
    """Incremental forward: logits for the LAST input position + updated
    cache — or for EVERY input position when ``all_positions`` is set.  The
    contract of ``lengths`` / ``block_tables`` is ``cached.window``'s."""
    params = dequant_resident(params)
    w = window(input_ids, pos, lengths, block_tables)
    # sequence-parallel prefill hook: token-shard hidden states over the
    # mesh sp axis (no-op outside an sp context or when T == 1)
    x = shard_seq(_embed_cached(cfg, params, input_ids, w.step_pos))
    x, ks, vs = decode_over_layers(
        lambda x, get, mm, ck, cv, layer: _block_cached_body(
            cfg, x, get, mm, ck, cv, w.step_pos, block_tables=block_tables,
            chunk_valid=w.chunk_valid, layer=layer),
        x, params["blocks"], cache["k"], cache["v"], cfg.num_layers,
        paged=w.paged)
    if not all_positions:
        x = gather_last(x, w.gather)
    with jax.named_scope("layer/norm"):
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    with jax.named_scope("head"):
        logits = x @ params["wte"].T.astype(x.dtype)
    return logits, {"k": ks, "v": vs}


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
    # and scan_prefetch pipelines the loop (overlap_comm)
    from ..runtime.zero.liveness import scan_layers_prefetched

    (x, _) = scan_layers_prefetched(step, (x, jnp.zeros((), jnp.int32)),
                                    params["blocks"],
                                    getattr(cfg, "scan_group_size", 1),
                                    getattr(cfg, "scan_prefetch", None))
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


@jax.named_scope("loss")
def _head_loss(cfg: GPT2Config, params, x, targets):
    """Final LN + tied head + CE, as ``lse - label_logit`` so no [T, V]
    log-softmax tensor is ever materialized (XLA fuses the f32 upcast into
    the reductions)."""
    with jax.named_scope("layer/norm"):
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    with jax.named_scope("head"):
        logits = x @ params["wte"].T.astype(x.dtype)
    valid = targets >= 0  # -100 = ignore (HF convention, same as loss_from_batch)
    safe = jnp.where(valid, targets, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None],
                                 axis=-1)[..., 0].astype(jnp.float32)
    nll = lse - picked
    return jnp.where(valid, nll, 0.0).sum() / jnp.maximum(valid.sum(), 1)


@jax.named_scope("loss")
def _head_loss_fused(cfg: GPT2Config, params, x, targets):
    """LN + tied-head CE via the chunked fused-backward formulation (the
    products inside it are ``head``'s: ``ops/chunked_ce.py``)."""
    with jax.named_scope("layer/norm"):
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    b, s, d = x.shape
    n = b * s
    return chunked_ce(params["wte"].T.astype(x.dtype), x.reshape(n, d),
                      targets.reshape(n),
                      whole_chunks(n, getattr(cfg, "ce_chunks", 4)))


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
