"""OPT family (125m .. 66B), TPU-native.

Reference parity targets: the OPT injection policy + container
(``module_inject/replace_policy.py``, ``module_inject/containers/opt.py``) and
the fused inference module ``model_implementations/transformers/ds_opt.py`` —
here the architecture is a pure function over a scan-stacked param pytree like
``models/gpt2.py``, and "injection" is the TP PartitionSpec annotation.

OPT specifics vs GPT-2:
 - learned positions with a hard-coded **offset of 2** (HF
   ``OPTLearnedPositionalEmbedding``), weight shape ``[max_pos + 2, D]``;
 - ReLU MLP;
 - ``do_layer_norm_before``: True (125m, 1.3B+ — pre-LN, plus a decoder-level
   final LN before the head) or False (350m — post-LN, no final LN);
 - ``word_embed_proj_dim`` may differ from ``hidden_size`` (350m), adding
   ``project_in``/``project_out`` matrices around the decoder stack.

``from_hf_state_dict`` ingests HuggingFace OPT checkpoints (q/k/v fused into
one ``qkv_w``); see ``runtime/state_dict_factory.py`` for the shard loader.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.sp_attention import shard_seq
from ..parallel.topology import TP_AXIS
from ..runtime.model import ModelSpec
from ..runtime.remat import checkpoint_block
from ..utils.platform import on_tpu
from .cached import (cached_attention, decode_over_layers, dequant_resident,
                     gather_last, init_kv_cache, layer_accessors, qmm, window)

PyTree = Any
_POS_OFFSET = 2  # HF OPTLearnedPositionalEmbedding.offset


@dataclasses.dataclass
class OPTConfig:
    vocab_size: int = 50272
    max_seq_len: int = 2048
    num_layers: int = 12
    num_heads: int = 12
    hidden_size: int = 768
    ffn_size: int = 3072
    word_embed_proj_dim: Optional[int] = None  # None -> hidden_size
    do_layer_norm_before: bool = True
    dropout: float = 0.0
    remat: bool = False
    use_flash: Optional[bool] = None
    #: ZeRO-3 liveness, set by the engine at trace time
    #: (``runtime/zero/liveness.py``): layers gathered a scan step, and the
    #: blocks' shardings when the layer loop is pipelined (``overlap_comm``)
    scan_group_size: int = 1
    scan_prefetch: Any = None

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @property
    def embed_dim(self) -> int:
        return self.word_embed_proj_dim or self.hidden_size

    @property
    def has_proj(self) -> bool:
        return self.embed_dim != self.hidden_size

    @staticmethod
    def opt_125m() -> "OPTConfig":
        return OPTConfig(num_layers=12, num_heads=12, hidden_size=768,
                         ffn_size=3072)

    @staticmethod
    def opt_350m() -> "OPTConfig":
        return OPTConfig(num_layers=24, num_heads=16, hidden_size=1024,
                         ffn_size=4096, word_embed_proj_dim=512,
                         do_layer_norm_before=False)

    @staticmethod
    def opt_1_3b() -> "OPTConfig":
        return OPTConfig(num_layers=24, num_heads=32, hidden_size=2048,
                         ffn_size=8192)

    @staticmethod
    def opt_2_7b() -> "OPTConfig":
        return OPTConfig(num_layers=32, num_heads=32, hidden_size=2560,
                         ffn_size=10240)

    @staticmethod
    def opt_6_7b() -> "OPTConfig":
        return OPTConfig(num_layers=32, num_heads=32, hidden_size=4096,
                         ffn_size=16384)

    @staticmethod
    def opt_13b() -> "OPTConfig":
        return OPTConfig(num_layers=40, num_heads=40, hidden_size=5120,
                         ffn_size=20480)

    @staticmethod
    def opt_30b() -> "OPTConfig":
        return OPTConfig(num_layers=48, num_heads=56, hidden_size=7168,
                         ffn_size=28672)

    @staticmethod
    def opt_66b() -> "OPTConfig":
        return OPTConfig(num_layers=64, num_heads=72, hidden_size=9216,
                         ffn_size=36864)

    @staticmethod
    def tiny(vocab_size: int = 512, max_seq_len: int = 64) -> "OPTConfig":
        return OPTConfig(vocab_size=vocab_size, max_seq_len=max_seq_len,
                         num_layers=2, num_heads=4, hidden_size=64,
                         ffn_size=256)

    @staticmethod
    def from_hf(hf_config) -> "OPTConfig":
        """Translate a ``transformers.OPTConfig``."""
        return OPTConfig(
            vocab_size=hf_config.vocab_size,
            max_seq_len=hf_config.max_position_embeddings,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            hidden_size=hf_config.hidden_size,
            ffn_size=hf_config.ffn_dim,
            word_embed_proj_dim=(
                None if hf_config.word_embed_proj_dim == hf_config.hidden_size
                else hf_config.word_embed_proj_dim),
            do_layer_norm_before=hf_config.do_layer_norm_before,
            dropout=getattr(hf_config, "dropout", 0.0),
        )

    def num_params(self) -> int:
        d, l, f = self.hidden_size, self.num_layers, self.ffn_size
        e = self.embed_dim
        per_layer = (3 * d * d + 3 * d) + (d * d + d) + \
            (d * f + f) + (f * d + d) + 4 * d
        n = self.vocab_size * e + (self.max_seq_len + _POS_OFFSET) * d + \
            l * per_layer
        if self.do_layer_norm_before:
            n += 2 * d
        if self.has_proj:
            n += 2 * e * d
        return n


def init_params(cfg: OPTConfig, rng) -> PyTree:
    d, l, f, e = cfg.hidden_size, cfg.num_layers, cfg.ffn_size, cfg.embed_dim
    keys = jax.random.split(rng, 8)
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(jnp.float32)

    params = {
        "embed_tokens": normal(keys[0], (cfg.vocab_size, e)),
        "embed_positions": normal(keys[1], (cfg.max_seq_len + _POS_OFFSET, d)),
        "blocks": {
            "ln1_scale": jnp.ones((l, d)), "ln1_bias": jnp.zeros((l, d)),
            "qkv_w": normal(keys[2], (l, d, 3 * d)),
            "qkv_b": jnp.zeros((l, 3 * d)),
            "o_w": normal(keys[3], (l, d, d)), "o_b": jnp.zeros((l, d)),
            "ln2_scale": jnp.ones((l, d)), "ln2_bias": jnp.zeros((l, d)),
            "fc_w": normal(keys[4], (l, d, f)), "fc_b": jnp.zeros((l, f)),
            "proj_w": normal(keys[5], (l, f, d)), "proj_b": jnp.zeros((l, d)),
        },
    }
    if cfg.do_layer_norm_before:
        params["lnf_scale"] = jnp.ones((d,))
        params["lnf_bias"] = jnp.zeros((d,))
    if cfg.has_proj:
        params["project_in"] = normal(keys[6], (e, d))
        params["project_out"] = normal(keys[7], (d, e))
    return params


def _layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _attention(cfg: OPTConfig, q, k, v):
    """Causal attention on [B, H, S, hd]; flash on TPU, einsum elsewhere."""
    use_flash = cfg.use_flash
    if use_flash is None:
        use_flash = on_tpu()
    if use_flash:
        from ..parallel.sequence import mesh_flash_attention

        return mesh_flash_attention(q, k, v, causal=True)
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(cfg.head_dim)
    mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _block(cfg: OPTConfig, x, layer):
    """One OPT decoder layer. Pre-LN (do_layer_norm_before) or post-LN."""
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    # INT8 weight-only serving: quantized records run the fused Pallas
    # dequant-matmul (ops/quantized_matmul) — no bf16 weight copy in HBM

    with jax.named_scope("layer/attn"):
        res = x
        with jax.named_scope("layer/norm"):
            y = _layer_norm(x, layer["ln1_scale"], layer["ln1_bias"]) \
                if cfg.do_layer_norm_before else x
        with jax.named_scope("layer/attn/qkv"):
            qkv = qmm(y, layer["qkv_w"]) + layer["qkv_b"].astype(y.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
            k = k.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
            v = v.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
        with jax.named_scope("layer/attn/core"):
            attn = _attention(cfg, q, k, v)
        with jax.named_scope("layer/attn/out"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
            x = res + qmm(attn, layer["o_w"], x.dtype) + \
                layer["o_b"].astype(x.dtype)
        if not cfg.do_layer_norm_before:
            with jax.named_scope("layer/norm"):
                x = _layer_norm(x, layer["ln1_scale"], layer["ln1_bias"])
    with jax.named_scope("layer/mlp"):
        res = x
        with jax.named_scope("layer/norm"):
            y = _layer_norm(x, layer["ln2_scale"], layer["ln2_bias"]) \
                if cfg.do_layer_norm_before else x
        hid = jax.nn.relu(qmm(y, layer["fc_w"]) +
                          layer["fc_b"].astype(y.dtype))
        x = res + qmm(hid, layer["proj_w"], x.dtype) + \
            layer["proj_b"].astype(x.dtype)
        if not cfg.do_layer_norm_before:
            x = _layer_norm(x, layer["ln2_scale"], layer["ln2_bias"])
    return x


@jax.named_scope("embed")
def _embed(cfg: OPTConfig, params, input_ids, pos0: int = 0):
    """Token + learned position embeddings.  ``pos0``: shared base position
    (scalar), or int32 [B] per-sequence offsets — T == 1 for
    continuous-batching decode, T > 1 for paged chunked prefill (each
    row's window starts at its own base)."""
    s = input_ids.shape[1]
    x = params["embed_tokens"][input_ids]
    if cfg.has_proj:
        x = x @ params["project_in"].astype(x.dtype)
    pos0 = jnp.asarray(pos0, jnp.int32)
    if pos0.ndim == 0:
        pos = jax.lax.dynamic_slice(
            params["embed_positions"], (pos0 + _POS_OFFSET, 0),
            (s, cfg.hidden_size))
    elif s == 1:
        idx = jnp.clip(pos0 + _POS_OFFSET, 0,
                       params["embed_positions"].shape[0] - 1)
        pos = params["embed_positions"][idx][:, None]      # [B, 1, D]
    else:
        idx = jnp.clip(pos0[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
                       + _POS_OFFSET, 0,
                       params["embed_positions"].shape[0] - 1)
        pos = params["embed_positions"][idx]               # [B, S, D]
    return (x + pos).astype(params["embed_tokens"].dtype)


@jax.named_scope("head")
def _head(cfg: OPTConfig, params, x):
    """Final LN (pre-LN models) + tied lm head; x: [..., D] -> logits."""
    if cfg.do_layer_norm_before:
        with jax.named_scope("layer/norm"):
            x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    if cfg.has_proj:
        x = x @ params["project_out"].astype(x.dtype)
    return x @ params["embed_tokens"].T.astype(x.dtype)


def _run_blocks(cfg: OPTConfig, x, blocks: PyTree):
    """The decoder stack over ``[L, ...]``-stacked blocks (training and
    uncached forward)."""
    def step(x, layer):
        block_fn = checkpoint_block(_block, static_argnums=(0,)) \
            if cfg.remat else _block
        return block_fn(cfg, x, layer)

    # ZeRO-3 liveness: the engine sets both (a group of layers a scan step;
    # the pipelined loop of overlap_comm); unset, this is a plain lax.scan
    from ..runtime.zero.liveness import scan_layers_prefetched

    return scan_layers_prefetched(step, x, blocks, cfg.scan_group_size,
                                  cfg.scan_prefetch)


def forward(cfg: OPTConfig, params: PyTree, input_ids, rng=None,
            train: bool = True):
    """Token logits. input_ids: [B, S] int32."""
    params = dequant_resident(params)
    x = _run_blocks(cfg, _embed(cfg, params, input_ids), params["blocks"])
    return _head(cfg, params, x)


def init_cache(cfg: OPTConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    return init_kv_cache(cfg.num_layers, batch_size, cfg.num_heads, max_len,
                         cfg.head_dim, dtype)


def _block_cached_body(cfg: OPTConfig, x, get, mm, ck, cv, pos,
                       block_tables=None, chunk_valid=None, layer=None):
    """One decoder layer over a KV cache, parameterized by how per-layer
    weights are fetched: ``get(name)`` returns a small leaf, ``mm(y, name,
    dtype)`` runs ``y @ weight`` — the scan path indexes a pre-sliced layer
    dict, the quantized indexed path selects the layer in-kernel.
    ``block_tables``/``chunk_valid`` switch ck/cv to the whole paged pool,
    addressed in place at ``layer`` (contract in cached.cached_attention)."""
    b, t, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    with jax.named_scope("layer/attn"):
        res = x
        with jax.named_scope("layer/norm"):
            y = _layer_norm(x, get("ln1_scale"), get("ln1_bias")) \
                if cfg.do_layer_norm_before else x
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
            x = res + mm(attn, "o_w", x.dtype) + get("o_b").astype(x.dtype)
        if not cfg.do_layer_norm_before:
            with jax.named_scope("layer/norm"):
                x = _layer_norm(x, get("ln1_scale"), get("ln1_bias"))
    with jax.named_scope("layer/mlp"):
        res = x
        with jax.named_scope("layer/norm"):
            y = _layer_norm(x, get("ln2_scale"), get("ln2_bias")) \
                if cfg.do_layer_norm_before else x
        hid = jax.nn.relu(mm(y, "fc_w", None) + get("fc_b").astype(y.dtype))
        x = res + mm(hid, "proj_w", x.dtype) + get("proj_b").astype(x.dtype)
        if not cfg.do_layer_norm_before:
            x = _layer_norm(x, get("ln2_scale"), get("ln2_bias"))
    return x, ck, cv


def _block_cached(cfg: OPTConfig, x, layer, ck, cv, pos):
    return _block_cached_body(cfg, x, *layer_accessors(layer), ck, cv, pos)


def forward_cached(cfg: OPTConfig, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False):
    """Incremental forward: logits for the LAST position + updated cache —
    or for EVERY position when ``all_positions`` is set.  The contract of
    ``lengths`` / ``block_tables`` is ``cached.window``'s; the learned
    position embeddings follow each row's base."""
    params = dequant_resident(params)
    w = window(input_ids, pos, lengths, block_tables)
    # sequence-parallel prefill hook (no-op outside an sp context)
    x = shard_seq(_embed(cfg, params, input_ids, pos0=w.step_pos))
    x, ks, vs = decode_over_layers(
        lambda x, get, mm, ck, cv, layer: _block_cached_body(
            cfg, x, get, mm, ck, cv, w.step_pos, block_tables=block_tables,
            chunk_valid=w.chunk_valid, layer=layer),
        x, params["blocks"], cache["k"], cache["v"], cfg.num_layers,
        paged=w.paged)
    if not all_positions:
        x = gather_last(x, w.gather)
    return _head(cfg, params, x), {"k": ks, "v": vs}


@jax.named_scope("loss")
def _ce_from_logits(logits, targets):
    """``lse - picked_logit`` cross entropy: never materializes a [T, V] f32
    log-softmax tensor (same memory reasoning as gpt2._head_loss)."""
    valid = targets >= 0  # -100 = ignore (HF convention)
    safe = jnp.where(valid, targets, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None],
                                 axis=-1)[..., 0].astype(jnp.float32)
    nll = lse - picked
    return jnp.where(valid, nll, 0.0).sum() / jnp.maximum(valid.sum(), 1)


def loss_from_batch(cfg: OPTConfig, params, batch, rng=None,
                    train: bool = True):
    if isinstance(batch, (tuple, list)):
        input_ids, labels = batch
    else:
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
    if labels is None:
        labels = input_ids[:, 1:]
        input_ids = input_ids[:, :-1]
    x = _run_blocks(cfg, _embed(cfg, params, input_ids), params["blocks"])
    # checkpointed head: backward recomputes logits from [T, D] activations
    head = jax.checkpoint(lambda p, x, t: _head_loss(cfg, p, x, t))
    return head(params, x, labels)


def tp_rules(cfg: OPTConfig, abstract_params: PyTree) -> PyTree:
    """Megatron column/row specs; also derivable generically by
    ``module_inject.auto_tp.infer_tp_specs`` (tested for agreement)."""
    specs = {
        "embed_tokens": P(TP_AXIS, None),
        "embed_positions": P(),
        "blocks": {
            "ln1_scale": P(), "ln1_bias": P(),
            "qkv_w": P(None, None, TP_AXIS), "qkv_b": P(None, TP_AXIS),
            "o_w": P(None, TP_AXIS, None), "o_b": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "fc_w": P(None, None, TP_AXIS), "fc_b": P(None, TP_AXIS),
            "proj_w": P(None, TP_AXIS, None), "proj_b": P(),
        },
    }
    if cfg.do_layer_norm_before:
        specs["lnf_scale"] = P()
        specs["lnf_bias"] = P()
    if cfg.has_proj:
        specs["project_in"] = P()
        specs["project_out"] = P()
    return specs


# --------------------------------------------------------------------- HF I/O
def from_hf_state_dict(cfg: OPTConfig, sd: Dict[str, Any]) -> PyTree:
    """Build the param pytree from a HuggingFace OPT state dict.

    Accepts torch tensors or numpy arrays; q/k/v projections are fused into
    ``qkv_w``/``qkv_b``.  The analog of the reference's OPT container weight
    mapping (``module_inject/containers/opt.py``).
    """
    def get(name):
        for prefix in ("model.decoder.", "decoder.", ""):
            key = prefix + name
            if key in sd:
                t = sd[key]
                return np.asarray(t.detach().cpu().numpy()
                                  if hasattr(t, "detach") else t,
                                  dtype=np.float32)
        raise KeyError(f"missing OPT weight {name!r}; have "
                       f"{sorted(sd)[:8]}...")

    l = cfg.num_layers

    def stack(fmt, transpose=False, fuse_qkv=False):
        rows = []
        for i in range(l):
            if fuse_qkv:
                parts = [get(fmt.format(i=i, p=p)) for p in
                         ("q_proj", "k_proj", "v_proj")]
                w = np.concatenate(parts, axis=0)
            else:
                w = get(fmt.format(i=i))
            rows.append(w.T if transpose else w)
        return jnp.asarray(np.stack(rows))

    params = {
        "embed_tokens": jnp.asarray(get("embed_tokens.weight")),
        "embed_positions": jnp.asarray(get("embed_positions.weight")),
        "blocks": {
            "ln1_scale": stack("layers.{i}.self_attn_layer_norm.weight"),
            "ln1_bias": stack("layers.{i}.self_attn_layer_norm.bias"),
            # HF Linear weight is [out, in]; ours is [in, out]
            "qkv_w": stack("layers.{i}.self_attn.{p}.weight", transpose=True,
                           fuse_qkv=True),
            "qkv_b": stack("layers.{i}.self_attn.{p}.bias", fuse_qkv=True),
            "o_w": stack("layers.{i}.self_attn.out_proj.weight",
                         transpose=True),
            "o_b": stack("layers.{i}.self_attn.out_proj.bias"),
            "ln2_scale": stack("layers.{i}.final_layer_norm.weight"),
            "ln2_bias": stack("layers.{i}.final_layer_norm.bias"),
            "fc_w": stack("layers.{i}.fc1.weight", transpose=True),
            "fc_b": stack("layers.{i}.fc1.bias"),
            "proj_w": stack("layers.{i}.fc2.weight", transpose=True),
            "proj_b": stack("layers.{i}.fc2.bias"),
        },
    }
    if cfg.do_layer_norm_before:
        params["lnf_scale"] = jnp.asarray(get("final_layer_norm.weight"))
        params["lnf_bias"] = jnp.asarray(get("final_layer_norm.bias"))
    if cfg.has_proj:
        params["project_in"] = jnp.asarray(get("project_in.weight").T)
        params["project_out"] = jnp.asarray(get("project_out.weight").T)
    return params


def build(cfg: Optional[OPTConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or OPTConfig(**overrides)

    def init_fn(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch, rng=None, train=True):
        return loss_from_batch(cfg, params, batch, rng=rng, train=train)

    def apply_fn(params, batch, rng=None):
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return forward(cfg, params, input_ids, rng=rng, train=False)

    pipeline_hooks = {
        "blocks_key": ("blocks",),
        "embed_fn": lambda params, ids: _embed(cfg, params, ids),
        "block_fn": lambda layer, x, rng=None: _block(cfg, x, layer),
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
        "max_seq_len": cfg.max_seq_len,
        "supports_lengths": True,
        "supports_paged": True,
        "supports_verify": True,
        # int8 KV pool records flow through ops/paged_kv untouched
        # (quantize="kv8" in the serving engine)
        "supports_kv_quant": True,
        # raw next-token logits reach the serving engine's on-device
        # sampler unchanged (per-slot temperature/top-k/top-p)
        "supports_sampling": True,
    }

    def _stream_embed(params, ids, pos):
        return _embed(cfg, dequant_resident(params), ids, pos0=pos)

    def _stream_head(params, x_last):
        return _head(cfg, dequant_resident(params), x_last)

    stream_hooks = {
        "embed": _stream_embed,
        "block": lambda layer, x, ck, cv, pos: _block_cached(
            cfg, x, layer, ck, cv, pos),
        "head": _stream_head,
    }

    return ModelSpec(
        init_fn=init_fn, model_config=cfg, loss_fn=loss_fn, apply_fn=apply_fn,
                     tp_rules=lambda ap: tp_rules(cfg, ap),
                     flops_per_token=6.0 * cfg.num_params(),
                     pipeline_hooks=pipeline_hooks,
                     decode_hooks=decode_hooks,
                     stream_hooks=stream_hooks,
                     quant_aware=True,  # per-layer point-of-use dequant
                     name=f"opt-{cfg.num_layers}l-{cfg.hidden_size}d")


def _head_loss(cfg: OPTConfig, params, x, targets):
    return _ce_from_logits(_head(cfg, params, x), targets)
