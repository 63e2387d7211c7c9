"""GPT-J family, TPU-native.

Reference parity: the GPT-J injection policy
(``module_inject/replace_policy.py`` HFGPTJLayerPolicy,
``containers/gptj.py``).  Architecture vs GPT-NeoX: **interleaved** rotary
on the first ``rotary_dim`` dims (GPT-J rotates (even, odd) pairs, NeoX
rotates halves), a **single** shared layer norm per block feeding both the
attention and the MLP branch (parallel residual), bias-free q/k/v/out
projections, and an untied lm head **with** bias.
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
class GPTJConfig:
    vocab_size: int = 50400
    max_seq_len: int = 2048
    num_layers: int = 28
    num_heads: int = 16
    hidden_size: int = 4096
    rotary_dim: int = 64
    rope_theta: float = 10000.0
    mlp_ratio: int = 4
    #: explicit FFN width (HF ``n_inner``); None = mlp_ratio * hidden_size
    ffn_dim: Optional[int] = None
    dropout: float = 0.0
    remat: bool = False

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @property
    def ffn_size(self) -> int:
        return self.ffn_dim or self.hidden_size * self.mlp_ratio

    @staticmethod
    def gptj_6b() -> "GPTJConfig":
        return GPTJConfig()

    @staticmethod
    def tiny(vocab_size: int = 512, max_seq_len: int = 64) -> "GPTJConfig":
        return GPTJConfig(vocab_size=vocab_size, max_seq_len=max_seq_len,
                          num_layers=2, num_heads=4, hidden_size=64,
                          rotary_dim=8)

    @staticmethod
    def from_hf(hf) -> "GPTJConfig":
        return GPTJConfig(
            vocab_size=hf.vocab_size,
            max_seq_len=hf.n_positions,
            num_layers=hf.n_layer,
            num_heads=hf.n_head,
            hidden_size=hf.n_embd,
            rotary_dim=hf.rotary_dim or (hf.n_embd // hf.n_head),
            ffn_dim=hf.n_inner or 4 * hf.n_embd)

    def num_params(self) -> int:
        d, l, v, f = self.hidden_size, self.num_layers, self.vocab_size, \
            self.ffn_size
        per_layer = 4 * d * d + (2 * f * d + f + d) + 2 * d
        return v * d + l * per_layer + 2 * d + (v * d + v)


def init_params(cfg: GPTJConfig, rng) -> PyTree:
    d, l = cfg.hidden_size, cfg.num_layers
    keys = jax.random.split(rng, 8)
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(jnp.float32)

    return {
        "wte": normal(keys[0], (cfg.vocab_size, d)),
        "blocks": {
            "ln1_scale": jnp.ones((l, d)), "ln1_bias": jnp.zeros((l, d)),
            "q_w": normal(keys[1], (l, d, d)),
            "k_w": normal(keys[2], (l, d, d)),
            "v_w": normal(keys[3], (l, d, d)),
            "o_w": normal(keys[4], (l, d, d)),
            "fc_w": normal(keys[5], (l, d, cfg.ffn_size)),
            "fc_b": jnp.zeros((l, cfg.ffn_size)),
            "proj_w": normal(keys[6], (l, cfg.ffn_size, d)),
            "proj_b": jnp.zeros((l, d)),
        },
        "lnf_scale": jnp.ones((d,)), "lnf_bias": jnp.zeros((d,)),
        "lm_head_w": normal(keys[7], (d, cfg.vocab_size)),
        "lm_head_b": jnp.zeros((cfg.vocab_size,)),
    }


def _layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * scale +
            bias).astype(x.dtype)


def _rope_interleaved(cfg: GPTJConfig, x, offset=0):
    """GPT-J rotary: rotate (even, odd) dim pairs of the first
    ``rotary_dim`` dims.  x: [B, H, S, hd]."""
    b, h, s, hd = x.shape
    rot = cfg.rotary_dim
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2,
                                               dtype=jnp.float32) / rot))
    pos = jnp.arange(s, dtype=jnp.float32) + offset
    ang = pos[:, None] * inv[None, :]                       # [s, rot/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even = x_rot[..., 0::2].astype(jnp.float32)
    odd = x_rot[..., 1::2].astype(jnp.float32)
    r_even = even * cos - odd * sin
    r_odd = odd * cos + even * sin
    x_rot = jnp.stack([r_even, r_odd], axis=-1).reshape(b, h, s, rot)
    return jnp.concatenate([x_rot.astype(x.dtype), x_pass], axis=-1)


def _attention(cfg: GPTJConfig, q, k, v, q_offset=0):
    sq, sk = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(cfg.head_dim)
    mask = (jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None] + q_offset)
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _block(cfg: GPTJConfig, x, layer, pos=0, cache=None, get=None, mm=None):
    if get is None or mm is None:
        get, mm = layer_accessors(layer)

    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    y = _layer_norm(x, get("ln1_scale"), get("ln1_bias"))
    q = mm(y, "q_w", None).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = mm(y, "k_w", None).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    v = mm(y, "v_w", None).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    q = _rope_interleaved(cfg, q, offset=pos)
    k = _rope_interleaved(cfg, k, offset=pos)
    if cache is not None:
        ck, cv = cache_update(*cache, k, v, pos)
        attn = _attention(cfg, q, ck, cv, q_offset=pos)
        cache = (ck, cv)
    else:
        attn = _attention(cfg, q, k, v)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
    attn_out = mm(attn, "o_w", x.dtype)

    # parallel residual off the SAME norm output (GPT-J has one ln per block)
    hid = jax.nn.gelu(mm(y, "fc_w", None) + get("fc_b").astype(y.dtype),
                      approximate=True)
    mlp_out = mm(hid, "proj_w", x.dtype) + get("proj_b").astype(x.dtype)
    return x + attn_out + mlp_out, cache


def forward(cfg: GPTJConfig, params: PyTree, input_ids, rng=None,
            train: bool = True):
    params = dequant_resident(params)
    x = params["wte"][input_ids].astype(params["wte"].dtype)

    def body(x, xs):
        layer, = xs
        fn = checkpoint_block(lambda xx, ll: _block(cfg, xx, ll)[0]) \
            if cfg.remat else (lambda xx, ll: _block(cfg, xx, ll)[0])
        return fn(x, layer), None

    x, _ = jax.lax.scan(body, x, (params["blocks"],))
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    return x @ params["lm_head_w"].astype(x.dtype) + \
        params["lm_head_b"].astype(x.dtype)


def init_cache(cfg: GPTJConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    return init_kv_cache(cfg.num_layers, batch_size, cfg.num_heads, max_len,
                         cfg.head_dim, dtype)


def forward_cached(cfg: GPTJConfig, params, input_ids, cache, pos):
    params = dequant_resident(params)
    pos = jnp.asarray(pos, jnp.int32)
    x = params["wte"][input_ids].astype(params["wte"].dtype)

    def body(x, get, mm, ck, cv, layer):
        del layer                 # contiguous cache: ck/cv are the slice
        x, (ck, cv) = _block(cfg, x, None, pos=pos, cache=(ck, cv),
                             get=get, mm=mm)
        return x, ck, cv

    x, ks, vs = decode_over_layers(body, x, params["blocks"], cache["k"],
                                   cache["v"], cfg.num_layers, probe="q_w")
    x = _layer_norm(x[:, -1], params["lnf_scale"], params["lnf_bias"])
    logits = x @ params["lm_head_w"].astype(x.dtype) + \
        params["lm_head_b"].astype(x.dtype)
    return logits, {"k": ks, "v": vs}


def loss_from_batch(cfg: GPTJConfig, params, batch, rng=None,
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


def tp_rules(cfg: GPTJConfig, abstract_params: PyTree) -> PyTree:
    """q/k/v/fc column-parallel, o/proj row-parallel (reference
    ``module_inject/replace_module.py:25`` sharding directions)."""
    return {
        "wte": P(TP_AXIS, None),
        "blocks": {
            "ln1_scale": P(), "ln1_bias": P(),
            "q_w": P(None, None, TP_AXIS),
            "k_w": P(None, None, TP_AXIS),
            "v_w": P(None, None, TP_AXIS),
            "o_w": P(None, TP_AXIS, None),
            "fc_w": P(None, None, TP_AXIS), "fc_b": P(None, TP_AXIS),
            "proj_w": P(None, TP_AXIS, None), "proj_b": P(),
        },
        "lnf_scale": P(), "lnf_bias": P(),
        "lm_head_w": P(None, TP_AXIS),
        "lm_head_b": P(TP_AXIS),
    }


# --------------------------------------------------------------------- HF I/O
def from_hf_state_dict(cfg: GPTJConfig, sd: Dict[str, Any]) -> PyTree:
    """HF GPT-J state dict -> pytree (torch Linear stores [out, in] -> .T)."""
    def get(name):
        for prefix in ("transformer.", ""):
            if prefix + name in sd:
                t = sd[prefix + name]
                return np.asarray(t.detach().cpu().numpy()
                                  if hasattr(t, "detach") else t, np.float32)
        raise KeyError(name)

    l = cfg.num_layers

    def stack(fmt, fn=lambda x: x):
        return jnp.asarray(np.stack([fn(get(fmt.format(i=i)))
                                     for i in range(l)]))

    t = lambda w: w.T
    return {
        "wte": jnp.asarray(get("wte.weight")),
        "blocks": {
            "ln1_scale": stack("h.{i}.ln_1.weight"),
            "ln1_bias": stack("h.{i}.ln_1.bias"),
            "q_w": stack("h.{i}.attn.q_proj.weight", t),
            "k_w": stack("h.{i}.attn.k_proj.weight", t),
            "v_w": stack("h.{i}.attn.v_proj.weight", t),
            "o_w": stack("h.{i}.attn.out_proj.weight", t),
            "fc_w": stack("h.{i}.mlp.fc_in.weight", t),
            "fc_b": stack("h.{i}.mlp.fc_in.bias"),
            "proj_w": stack("h.{i}.mlp.fc_out.weight", t),
            "proj_b": stack("h.{i}.mlp.fc_out.bias"),
        },
        "lnf_scale": jnp.asarray(get("ln_f.weight")),
        "lnf_bias": jnp.asarray(get("ln_f.bias")),
        "lm_head_w": jnp.asarray(get("lm_head.weight").T),
        "lm_head_b": jnp.asarray(get("lm_head.bias")),
    }


def build(cfg: Optional[GPTJConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or GPTJConfig(**overrides)
    if cfg.dropout:
        raise NotImplementedError(
            "gptj: dropout is not implemented (the forward ignores it); "
            "set dropout=0")

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
                     name=f"gptj-{cfg.num_layers}l-{cfg.hidden_size}d")
