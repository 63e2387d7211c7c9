"""GPT-Neo family, TPU-native.

Reference parity: the GPT-Neo injection policy
(``module_inject/replace_policy.py`` HFGPTNEOLayerPolicy,
``containers/gptneo.py``).  Architecture vs GPT-2: learned positions like
GPT-2 but **separate bias-free q/k/v** projections (out proj has a bias),
**unscaled** attention scores (no 1/sqrt(hd)), and alternating
global/**local** (sliding-window) attention layers per
``attention_types``.

The local layers are banded attention — on TPU the band is expressed as a
mask over the same einsum (XLA folds the band predicate into the softmax
fusion); a block-sparse Pallas path for long sequences lives in
``ops/sparse_attention`` (SlidingWindowSparsityConfig).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.topology import TP_AXIS
from ..runtime.model import ModelSpec
from ..runtime.remat import checkpoint_block
from .cached import cache_update, dequant_resident, init_kv_cache, qmm

PyTree = Any


@dataclasses.dataclass
class GPTNeoConfig:
    vocab_size: int = 50257
    max_seq_len: int = 2048
    num_layers: int = 24
    num_heads: int = 16
    hidden_size: int = 2048
    window_size: int = 256
    #: per-layer attention kind, "global" | "local"; defaults to alternating
    attention_layers: Optional[List[str]] = None
    mlp_ratio: int = 4
    #: explicit FFN width (HF ``intermediate_size``); None = 4 * hidden
    ffn_dim: Optional[int] = None
    dropout: float = 0.0
    remat: bool = False

    def __post_init__(self):
        if self.attention_layers is None:
            self.attention_layers = [
                "global" if i % 2 == 0 else "local"
                for i in range(self.num_layers)]
        assert len(self.attention_layers) == self.num_layers

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @property
    def ffn_size(self) -> int:
        return self.ffn_dim or self.hidden_size * self.mlp_ratio

    @staticmethod
    def neo_1p3b() -> "GPTNeoConfig":
        return GPTNeoConfig()

    @staticmethod
    def neo_2p7b() -> "GPTNeoConfig":
        return GPTNeoConfig(num_layers=32, num_heads=20, hidden_size=2560)

    @staticmethod
    def tiny(vocab_size: int = 512, max_seq_len: int = 64) -> "GPTNeoConfig":
        return GPTNeoConfig(vocab_size=vocab_size, max_seq_len=max_seq_len,
                            num_layers=2, num_heads=4, hidden_size=64,
                            window_size=8)

    @staticmethod
    def from_hf(hf) -> "GPTNeoConfig":
        # hf.attention_layers expands the [[types], repeat] spec per layer
        return GPTNeoConfig(
            vocab_size=hf.vocab_size,
            max_seq_len=hf.max_position_embeddings,
            num_layers=hf.num_layers,
            num_heads=hf.num_heads,
            hidden_size=hf.hidden_size,
            window_size=hf.window_size,
            attention_layers=list(hf.attention_layers),
            ffn_dim=hf.intermediate_size or 4 * hf.hidden_size)

    def num_params(self) -> int:
        d, l, v, f = self.hidden_size, self.num_layers, self.vocab_size, \
            self.ffn_size
        per_layer = 3 * d * d + (d * d + d) + \
            (2 * f * d + f + d) + 4 * d
        return v * d + self.max_seq_len * d + l * per_layer + 2 * d


def init_params(cfg: GPTNeoConfig, rng) -> PyTree:
    d, l = cfg.hidden_size, cfg.num_layers
    keys = jax.random.split(rng, 8)
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(jnp.float32)

    return {
        "wte": normal(keys[0], (cfg.vocab_size, d)),
        "wpe": normal(keys[1], (cfg.max_seq_len, d), 0.01),
        "blocks": {
            "ln1_scale": jnp.ones((l, d)), "ln1_bias": jnp.zeros((l, d)),
            "q_w": normal(keys[2], (l, d, d)),
            "k_w": normal(keys[3], (l, d, d)),
            "v_w": normal(keys[4], (l, d, d)),
            "o_w": normal(keys[5], (l, d, d)), "o_b": jnp.zeros((l, d)),
            "ln2_scale": jnp.ones((l, d)), "ln2_bias": jnp.zeros((l, d)),
            "fc_w": normal(keys[6], (l, d, cfg.ffn_size)),
            "fc_b": jnp.zeros((l, cfg.ffn_size)),
            "proj_w": normal(keys[7], (l, cfg.ffn_size, d)),
            "proj_b": jnp.zeros((l, d)),
        },
        "lnf_scale": jnp.ones((d,)), "lnf_bias": jnp.zeros((d,)),
    }


def _layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * scale +
            bias).astype(x.dtype)


def _attention(cfg: GPTNeoConfig, q, k, v, local: bool, q_offset=0):
    """GPT-Neo attention: NO 1/sqrt(hd) scaling; causal band for local."""
    sq, sk = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = kpos <= qpos
    if local:
        mask = mask & (kpos > qpos - cfg.window_size)
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _block(cfg: GPTNeoConfig, x, layer, local: bool, pos=0, cache=None):
    # matmuls route through cached.qmm (identical HLO for dense leaves;
    # point-of-use dequant / per-layer w8a8 kernel for INT8 records — the
    # unrolled loop slices layers statically, so records arrive per-layer
    # and the stacked indexed path is unnecessary here)
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    y = _layer_norm(x, layer["ln1_scale"], layer["ln1_bias"])
    q = qmm(y, layer["q_w"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = qmm(y, layer["k_w"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    v = qmm(y, layer["v_w"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    if cache is not None:
        ck, cv = cache_update(*cache, k, v, pos)
        attn = _attention(cfg, q, ck, cv, local, q_offset=pos)
        cache = (ck, cv)
    else:
        attn = _attention(cfg, q, k, v, local)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + qmm(attn, layer["o_w"], x.dtype) + layer["o_b"].astype(x.dtype)

    y = _layer_norm(x, layer["ln2_scale"], layer["ln2_bias"])
    hid = jax.nn.gelu(qmm(y, layer["fc_w"]) +
                      layer["fc_b"].astype(y.dtype), approximate=True)
    x = x + qmm(hid, layer["proj_w"], x.dtype) + \
        layer["proj_b"].astype(x.dtype)
    return x, cache


def _run_blocks(cfg: GPTNeoConfig, params, x, pos=0, cache=None):
    """Python loop over layers: the global/local pattern is static per layer
    (a scan would need the band predicate as a traced switch; the unrolled
    loop lets XLA specialize each layer's mask)."""
    new_k, new_v = [], []
    for i, kind in enumerate(cfg.attention_layers):
        layer = jax.tree_util.tree_map(lambda p: p[i], params["blocks"])
        c = None if cache is None else (cache["k"][i], cache["v"][i])
        fn = _block
        if cfg.remat and cache is None:
            fn = checkpoint_block(_block, static_argnums=(0, 3))
        x, c = fn(cfg, x, layer, kind == "local", pos, c)
        if cache is not None:
            new_k.append(c[0])
            new_v.append(c[1])
    if cache is not None:
        cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
    return x, cache


def forward(cfg: GPTNeoConfig, params: PyTree, input_ids, rng=None,
            train: bool = True):
    params = dequant_resident(params)
    b, s = input_ids.shape
    x = (params["wte"][input_ids] + params["wpe"][:s]).astype(
        params["wte"].dtype)
    x, _ = _run_blocks(cfg, params, x)
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    return x @ params["wte"].T.astype(x.dtype)


def init_cache(cfg: GPTNeoConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    return init_kv_cache(cfg.num_layers, batch_size, cfg.num_heads, max_len,
                         cfg.head_dim, dtype)


def forward_cached(cfg: GPTNeoConfig, params, input_ids, cache, pos):
    params = dequant_resident(params)
    b, t = input_ids.shape
    d = cfg.hidden_size
    pos = jnp.asarray(pos, jnp.int32)
    wpe = jax.lax.dynamic_slice(params["wpe"], (pos, 0), (t, d))
    x = (params["wte"][input_ids] + wpe).astype(params["wte"].dtype)
    x, cache = _run_blocks(cfg, params, x, pos=pos, cache=cache)
    x = _layer_norm(x[:, -1], params["lnf_scale"], params["lnf_bias"])
    return x @ params["wte"].T.astype(x.dtype), cache


def loss_from_batch(cfg: GPTNeoConfig, params, batch, rng=None,
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


def tp_rules(cfg: GPTNeoConfig, abstract_params: PyTree) -> PyTree:
    return {
        "wte": P(TP_AXIS, None),
        "wpe": P(),
        "blocks": {
            "ln1_scale": P(), "ln1_bias": P(),
            "q_w": P(None, None, TP_AXIS),
            "k_w": P(None, None, TP_AXIS),
            "v_w": P(None, None, TP_AXIS),
            "o_w": P(None, TP_AXIS, None), "o_b": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "fc_w": P(None, None, TP_AXIS), "fc_b": P(None, TP_AXIS),
            "proj_w": P(None, TP_AXIS, None), "proj_b": P(),
        },
        "lnf_scale": P(), "lnf_bias": P(),
    }


# --------------------------------------------------------------------- HF I/O
def from_hf_state_dict(cfg: GPTNeoConfig, sd: Dict[str, Any]) -> PyTree:
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
        "wpe": jnp.asarray(get("wpe.weight")),
        "blocks": {
            "ln1_scale": stack("h.{i}.ln_1.weight"),
            "ln1_bias": stack("h.{i}.ln_1.bias"),
            "q_w": stack("h.{i}.attn.attention.q_proj.weight", t),
            "k_w": stack("h.{i}.attn.attention.k_proj.weight", t),
            "v_w": stack("h.{i}.attn.attention.v_proj.weight", t),
            "o_w": stack("h.{i}.attn.attention.out_proj.weight", t),
            "o_b": stack("h.{i}.attn.attention.out_proj.bias"),
            "ln2_scale": stack("h.{i}.ln_2.weight"),
            "ln2_bias": stack("h.{i}.ln_2.bias"),
            "fc_w": stack("h.{i}.mlp.c_fc.weight", t),
            "fc_b": stack("h.{i}.mlp.c_fc.bias"),
            "proj_w": stack("h.{i}.mlp.c_proj.weight", t),
            "proj_b": stack("h.{i}.mlp.c_proj.bias"),
        },
        "lnf_scale": jnp.asarray(get("ln_f.weight")),
        "lnf_bias": jnp.asarray(get("ln_f.bias")),
    }


def build(cfg: Optional[GPTNeoConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or GPTNeoConfig(**overrides)
    if cfg.dropout:
        raise NotImplementedError(
            "gptneo: dropout is not implemented (the forward ignores it); "
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
                     quant_aware=True,  # per-layer point-of-use dequant
                     blocks_key=("blocks",),
                     name=f"gptneo-{cfg.num_layers}l-{cfg.hidden_size}d")
