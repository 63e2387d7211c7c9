"""GPT-NeoX family, TPU-native.

Reference parity: the GPT-NeoX injection policy
(``module_inject/replace_policy.py`` GPTNEOXLayerPolicy,
``containers/gptneox.py``).  Architecture vs GPT-2: **partial rotary**
embeddings (``rotary_pct`` of each head's dims), **parallel residual**
(x + attn(ln1(x)) + mlp(ln2(x))), untied lm head, and HF's head-interleaved
fused qkv (reordered in the converter).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.topology import TP_AXIS
from ..runtime.model import ModelSpec
from ..runtime.remat import checkpoint_block
from .cached import (cache_update, decode_over_layers, dequant_resident,
                     init_kv_cache, layer_accessors)

PyTree = Any


@dataclasses.dataclass
class GPTNeoXConfig:
    vocab_size: int = 50432
    max_seq_len: int = 2048
    num_layers: int = 44
    num_heads: int = 64
    hidden_size: int = 6144
    rotary_pct: float = 0.25
    rope_theta: float = 10000.0
    use_parallel_residual: bool = True
    dropout: float = 0.0
    remat: bool = False

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @property
    def rotary_ndims(self) -> int:
        return int(self.head_dim * self.rotary_pct)

    @staticmethod
    def neox_20b() -> "GPTNeoXConfig":
        return GPTNeoXConfig()

    @staticmethod
    def pythia_160m() -> "GPTNeoXConfig":
        return GPTNeoXConfig(num_layers=12, num_heads=12, hidden_size=768,
                             rotary_pct=0.25, vocab_size=50304)

    @staticmethod
    def tiny(vocab_size: int = 512, max_seq_len: int = 64) -> "GPTNeoXConfig":
        return GPTNeoXConfig(vocab_size=vocab_size, max_seq_len=max_seq_len,
                             num_layers=2, num_heads=4, hidden_size=64,
                             rotary_pct=0.5)

    @staticmethod
    def from_hf(hf) -> "GPTNeoXConfig":
        return GPTNeoXConfig(
            vocab_size=hf.vocab_size,
            max_seq_len=hf.max_position_embeddings,
            num_layers=hf.num_hidden_layers,
            num_heads=hf.num_attention_heads,
            hidden_size=hf.hidden_size,
            rotary_pct=hf.rotary_pct,
            rope_theta=getattr(hf, "rotary_emb_base", 10000.0),
            use_parallel_residual=hf.use_parallel_residual)

    def num_params(self) -> int:
        d, l, v = self.hidden_size, self.num_layers, self.vocab_size
        per_layer = (3 * d * d + 3 * d) + (d * d + d) + \
            (8 * d * d + 5 * d) + 4 * d
        return 2 * v * d + l * per_layer + 2 * d


def init_params(cfg: GPTNeoXConfig, rng) -> PyTree:
    d, l = cfg.hidden_size, cfg.num_layers
    keys = jax.random.split(rng, 7)
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(jnp.float32)

    return {
        "embed_in": normal(keys[0], (cfg.vocab_size, d)),
        "blocks": {
            "ln1_scale": jnp.ones((l, d)), "ln1_bias": jnp.zeros((l, d)),
            "qkv_w": normal(keys[1], (l, d, 3 * d)),
            "qkv_b": jnp.zeros((l, 3 * d)),
            "o_w": normal(keys[2], (l, d, d)), "o_b": jnp.zeros((l, d)),
            "ln2_scale": jnp.ones((l, d)), "ln2_bias": jnp.zeros((l, d)),
            "fc_w": normal(keys[3], (l, d, 4 * d)),
            "fc_b": jnp.zeros((l, 4 * d)),
            "proj_w": normal(keys[4], (l, 4 * d, d)),
            "proj_b": jnp.zeros((l, d)),
        },
        "lnf_scale": jnp.ones((d,)), "lnf_bias": jnp.zeros((d,)),
        "embed_out": normal(keys[5], (d, cfg.vocab_size)),
    }


def _layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * scale +
            bias).astype(x.dtype)


def _rope(cfg: GPTNeoXConfig, x, offset=0):
    """Partial rotary: rotate the first ``rotary_ndims`` of each head
    (NeoX-style rotate_half on the rotary slice)."""
    b, h, s, hd = x.shape
    rot = cfg.rotary_ndims
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2,
                                               dtype=jnp.float32) / rot))
    pos = jnp.arange(s, dtype=jnp.float32) + offset
    ang = pos[:, None] * inv[None, :]                       # [s, rot/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    half = rot // 2
    rotated = jnp.concatenate([-x_rot[..., half:], x_rot[..., :half]],
                              axis=-1)
    x_rot = (x_rot.astype(jnp.float32) * cos + rotated.astype(jnp.float32) *
             sin).astype(x.dtype)
    return jnp.concatenate([x_rot, x_pass], axis=-1)


def _attention(cfg: GPTNeoXConfig, q, k, v, q_offset=0):
    sq, sk = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(cfg.head_dim)
    mask = (jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None] + q_offset)
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _block(cfg: GPTNeoXConfig, x, layer, pos=0, cache=None, get=None,
           mm=None):
    if get is None or mm is None:
        get, mm = layer_accessors(layer)

    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    y1 = _layer_norm(x, get("ln1_scale"), get("ln1_bias"))
    qkv = mm(y1, "qkv_w", None) + get("qkv_b").astype(y1.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    q = _rope(cfg, q, offset=pos)
    k = _rope(cfg, k, offset=pos)
    if cache is not None:
        ck, cv = cache_update(*cache, k, v, pos)
        attn = _attention(cfg, q, ck, cv, q_offset=pos)
        cache = (ck, cv)
    else:
        attn = _attention(cfg, q, k, v)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
    attn_out = mm(attn, "o_w", x.dtype) + get("o_b").astype(x.dtype)

    if cfg.use_parallel_residual:
        y2 = _layer_norm(x, get("ln2_scale"), get("ln2_bias"))
    else:
        x = x + attn_out
        y2 = _layer_norm(x, get("ln2_scale"), get("ln2_bias"))
    hid = jax.nn.gelu(mm(y2, "fc_w", None) + get("fc_b").astype(y2.dtype),
                      approximate=False)
    mlp_out = mm(hid, "proj_w", x.dtype) + get("proj_b").astype(x.dtype)
    if cfg.use_parallel_residual:
        x = x + attn_out + mlp_out
    else:
        x = x + mlp_out
    return x, cache


def forward(cfg: GPTNeoXConfig, params: PyTree, input_ids, rng=None,
            train: bool = True):
    params = dequant_resident(params)
    x = params["embed_in"][input_ids].astype(params["embed_in"].dtype)

    def body(x, xs):
        layer, = xs
        fn = checkpoint_block(lambda xx, ll: _block(cfg, xx, ll)[0]) \
            if cfg.remat else (lambda xx, ll: _block(cfg, xx, ll)[0])
        return fn(x, layer), None

    x, _ = jax.lax.scan(body, x, (params["blocks"],))
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    return x @ params["embed_out"].astype(x.dtype)


def init_cache(cfg: GPTNeoXConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    return init_kv_cache(cfg.num_layers, batch_size, cfg.num_heads, max_len,
                         cfg.head_dim, dtype)


def forward_cached(cfg: GPTNeoXConfig, params, input_ids, cache, pos):
    params = dequant_resident(params)
    pos = jnp.asarray(pos, jnp.int32)
    x = params["embed_in"][input_ids].astype(params["embed_in"].dtype)

    def body(x, get, mm, ck, cv, layer):
        del layer                 # contiguous cache: ck/cv are the slice
        x, (ck, cv) = _block(cfg, x, None, pos=pos, cache=(ck, cv),
                             get=get, mm=mm)
        return x, ck, cv

    x, ks, vs = decode_over_layers(body, x, params["blocks"], cache["k"],
                                   cache["v"], cfg.num_layers)
    x = _layer_norm(x[:, -1], params["lnf_scale"], params["lnf_bias"])
    return x @ params["embed_out"].astype(x.dtype), {"k": ks, "v": vs}


def loss_from_batch(cfg: GPTNeoXConfig, params, batch, rng=None,
                    train: bool = True):
    if isinstance(batch, (tuple, list)):
        input_ids, labels = batch
    else:
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
    if labels is None:
        labels = input_ids[:, 1:]
        input_ids = input_ids[:, :-1]
    logits = forward(cfg, params, input_ids, rng=rng, train=train)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None],
                                 axis=-1)[..., 0].astype(jnp.float32)
    return jnp.where(valid, lse - picked,
                     0.0).sum() / jnp.maximum(valid.sum(), 1)


def tp_rules(cfg: GPTNeoXConfig, abstract_params: PyTree) -> PyTree:
    return {
        "embed_in": P(TP_AXIS, None),
        "blocks": {
            "ln1_scale": P(), "ln1_bias": P(),
            "qkv_w": P(None, None, TP_AXIS), "qkv_b": P(None, TP_AXIS),
            "o_w": P(None, TP_AXIS, None), "o_b": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "fc_w": P(None, None, TP_AXIS), "fc_b": P(None, TP_AXIS),
            "proj_w": P(None, TP_AXIS, None), "proj_b": P(),
        },
        "lnf_scale": P(), "lnf_bias": P(),
        "embed_out": P(None, TP_AXIS),
    }


# --------------------------------------------------------------------- HF I/O
def from_hf_state_dict(cfg: GPTNeoXConfig, sd: Dict[str, Any]) -> PyTree:
    """HF GPT-NeoX state dict -> pytree (qkv de-interleaved per head, like
    bloom; ``embed_out`` is the untied lm head)."""
    def get(name):
        for prefix in ("gpt_neox.", ""):
            if prefix + name in sd:
                t = sd[prefix + name]
                return np.asarray(t.detach().cpu().numpy()
                                  if hasattr(t, "detach") else t, np.float32)
        raise KeyError(name)

    l, d, h, hd = cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.head_dim

    def dequkv_w(w):
        w = w.reshape(h, 3, hd, d)
        return np.concatenate([w[:, i].reshape(d, d) for i in range(3)],
                              axis=0).T

    def dequkv_b(b_):
        b_ = b_.reshape(h, 3, hd)
        return np.concatenate([b_[:, i].reshape(d) for i in range(3)])

    def stack(fmt, fn=lambda x: x):
        return jnp.asarray(np.stack([fn(get(fmt.format(i=i)))
                                     for i in range(l)]))

    return {
        "embed_in": jnp.asarray(get("embed_in.weight")),
        "blocks": {
            "ln1_scale": stack("layers.{i}.input_layernorm.weight"),
            "ln1_bias": stack("layers.{i}.input_layernorm.bias"),
            "qkv_w": stack("layers.{i}.attention.query_key_value.weight",
                           dequkv_w),
            "qkv_b": stack("layers.{i}.attention.query_key_value.bias",
                           dequkv_b),
            "o_w": stack("layers.{i}.attention.dense.weight", lambda w: w.T),
            "o_b": stack("layers.{i}.attention.dense.bias"),
            "ln2_scale": stack("layers.{i}.post_attention_layernorm.weight"),
            "ln2_bias": stack("layers.{i}.post_attention_layernorm.bias"),
            "fc_w": stack("layers.{i}.mlp.dense_h_to_4h.weight",
                          lambda w: w.T),
            "fc_b": stack("layers.{i}.mlp.dense_h_to_4h.bias"),
            "proj_w": stack("layers.{i}.mlp.dense_4h_to_h.weight",
                            lambda w: w.T),
            "proj_b": stack("layers.{i}.mlp.dense_4h_to_h.bias"),
        },
        "lnf_scale": jnp.asarray(get("final_layer_norm.weight")),
        "lnf_bias": jnp.asarray(get("final_layer_norm.bias")),
        "embed_out": jnp.asarray(get("embed_out.weight").T),
    }


def build(cfg: Optional[GPTNeoXConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or GPTNeoXConfig(**overrides)
    if cfg.dropout:
        raise NotImplementedError(
            "gptneox: dropout is not implemented yet (the forward ignores "
            "it); set dropout=0")

    def init_fn(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch, rng=None, train=True):
        return loss_from_batch(cfg, params, batch, rng=rng, train=train)

    def apply_fn(params, batch, rng=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return forward(cfg, params, ids, rng=rng, train=False)

    decode_hooks = {
        "init_cache": lambda b, s, dtype=jnp.bfloat16: init_cache(
            cfg, b, s, dtype),
        "forward_cached": lambda params, ids, cache, pos: forward_cached(
            cfg, params, ids, cache, pos),
        "max_seq_len": cfg.max_seq_len,
    }

    return ModelSpec(
        init_fn=init_fn, model_config=cfg, loss_fn=loss_fn, apply_fn=apply_fn,
                     tp_rules=lambda ap: tp_rules(cfg, ap),
                     flops_per_token=6.0 * cfg.num_params(),
                     decode_hooks=decode_hooks,
                     quant_aware=True,  # point-of-use dequant in _block
                     blocks_key=("blocks",),
                     name=f"gptneox-{cfg.num_layers}l-{cfg.hidden_size}d")
