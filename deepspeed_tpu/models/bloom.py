"""BLOOM family, TPU-native.

Reference parity: the BLOOM injection policy/container
(``module_inject/replace_policy.py``, ``module_inject/containers/bloom.py``)
and the fused module ``model_implementations/transformers/ds_bloom.py``.
Architecture vs GPT-2: **ALiBi** attention bias instead of position
embeddings, a LayerNorm on the word embeddings, and HF's head-interleaved
fused qkv layout (handled in the weight converter, not the compute path).

ALiBi slopes follow the published formula (powers of 2^(-8/H) for the
power-of-two head prefix, interpolated for the rest).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.decode_attention import window_state
from ..ops.paged_kv import paged_cache_update, paged_gather
from ..ops.sp_attention import shard_seq
from ..parallel.topology import TP_AXIS
from ..runtime.model import ModelSpec
from ..runtime.remat import checkpoint_block
from .cached import (cache_update, decode_over_layers, dequant_resident,
                     gather_last, init_kv_cache, layer_accessors, window)

PyTree = Any


@dataclasses.dataclass
class BloomConfig:
    vocab_size: int = 250880
    num_layers: int = 24
    num_heads: int = 16
    hidden_size: int = 1024
    max_seq_len: int = 2048
    dropout: float = 0.0
    remat: bool = False

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @staticmethod
    def bloom_560m() -> "BloomConfig":
        return BloomConfig(num_layers=24, num_heads=16, hidden_size=1024)

    @staticmethod
    def bloom_7b1() -> "BloomConfig":
        return BloomConfig(num_layers=30, num_heads=32, hidden_size=4096)

    @staticmethod
    def tiny(vocab_size: int = 512, max_seq_len: int = 64) -> "BloomConfig":
        return BloomConfig(vocab_size=vocab_size, max_seq_len=max_seq_len,
                           num_layers=2, num_heads=4, hidden_size=64)

    @staticmethod
    def from_hf(hf) -> "BloomConfig":
        return BloomConfig(vocab_size=hf.vocab_size,
                           num_layers=hf.n_layer, num_heads=hf.n_head,
                           hidden_size=hf.hidden_size,
                           max_seq_len=getattr(hf, "seq_length", 2048))

    def num_params(self) -> int:
        d, l, v = self.hidden_size, self.num_layers, self.vocab_size
        per_layer = (3 * d * d + 3 * d) + (d * d + d) + \
            (4 * d * d + 4 * d) + (4 * d * d + d) + 4 * d
        return v * d + 2 * d + l * per_layer + 2 * d


def alibi_slopes(num_heads: int) -> np.ndarray:
    """Published ALiBi slope schedule (framework-neutral math)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        return np.asarray(pow2_slopes(num_heads), np.float32)
    closest = 2 ** math.floor(math.log2(num_heads))
    base = pow2_slopes(closest)
    extra = pow2_slopes(2 * closest)[0::2][: num_heads - closest]
    return np.asarray(base + extra, np.float32)


def init_params(cfg: BloomConfig, rng) -> PyTree:
    d, l = cfg.hidden_size, cfg.num_layers
    keys = jax.random.split(rng, 6)
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(jnp.float32)

    return {
        "word_embeddings": normal(keys[0], (cfg.vocab_size, d)),
        "word_ln_scale": jnp.ones((d,)), "word_ln_bias": jnp.zeros((d,)),
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
    }


def _layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * scale +
            bias).astype(x.dtype)


def _alibi_bias(cfg: BloomConfig, q_len: int, kv_len: int,
                q_offset=0) -> jnp.ndarray:
    """[H, q_len, kv_len] additive bias: slope_h * -(q_pos - k_pos) for
    k <= q (the causal mask handles the rest)."""
    slopes = jnp.asarray(alibi_slopes(cfg.num_heads))
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    rel = (k_pos - q_pos).astype(jnp.float32)       # <= 0 in the causal part
    return slopes[:, None, None] * rel[None]


def _attention(cfg: BloomConfig, q, k, v, q_offset=0):
    """Causal + ALiBi attention (einsum path: the bias rules out the plain
    flash kernel; a biased Pallas variant is future work)."""
    sq, sk = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(cfg.head_dim)
    scores = scores.astype(jnp.float32) + _alibi_bias(cfg, sq, sk, q_offset)
    mask = (jnp.arange(sk)[None, :] <=
            jnp.arange(sq)[:, None] + q_offset)     # causal w/ offset
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _block(cfg: BloomConfig, x, layer):
    """One uncached block (training / the plain forward);
    :func:`_block_cached_body` is the same math over a KV cache."""
    get, mm = layer_accessors(layer)
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    y = _layer_norm(x, get("ln1_scale"), get("ln1_bias"))
    qkv = mm(y, "qkv_w", None) + get("qkv_b").astype(y.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    attn = _attention(cfg, q, k, v).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + mm(attn, "o_w", x.dtype) + get("o_b").astype(x.dtype)

    y = _layer_norm(x, get("ln2_scale"), get("ln2_bias"))
    hid = jax.nn.gelu(mm(y, "fc_w", None) + get("fc_b").astype(y.dtype),
                      approximate=False)
    return x + mm(hid, "proj_w", x.dtype) + get("proj_b").astype(x.dtype)


def _embed(cfg: BloomConfig, params, input_ids):
    x = params["word_embeddings"][input_ids]
    return _layer_norm(x, params["word_ln_scale"], params["word_ln_bias"])


def forward(cfg: BloomConfig, params: PyTree, input_ids, rng=None,
            train: bool = True):
    params = dequant_resident(params)
    x = _embed(cfg, params, input_ids)

    def body(x, xs):
        layer, = xs
        fn = functools.partial(_block, cfg)
        return (checkpoint_block(fn) if cfg.remat else fn)(x, layer), None

    x, _ = jax.lax.scan(body, x, (params["blocks"],))
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    return x @ params["word_embeddings"].T.astype(x.dtype)


def init_cache(cfg: BloomConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    return init_kv_cache(cfg.num_layers, batch_size, cfg.num_heads, max_len,
                         cfg.head_dim, dtype)


def _alibi_cached_attention(cfg: BloomConfig, q, k, v, ck, cv, pos,
                            block_tables=None, chunk_valid=None,
                            layer=None):
    """Write new KV + ALiBi attention, on either cache layout (contract in
    cached.cached_attention).  Pure XLA on both layouts: the additive ALiBi
    bias rules out the shared position-masked decode kernels, so the paged
    path gathers each row's logical view through its block table and biases
    by absolute positions (``pos`` scalar, or int32 [B] per-row — decode
    offsets, chunked-prefill bases, or speculative verify-window bases)."""
    if block_tables is None:
        ck, cv = cache_update(ck, cv, k, v, pos)
        kk, vv = ck, cv
    else:
        ck, cv = paged_cache_update(ck, cv, k, v, pos, block_tables,
                                    valid=chunk_valid, layer=layer)
        # int8 records dequantize to the query dtype (kv8 serving) so the
        # residual stream keeps the model's compute dtype
        kk = paged_gather(ck, block_tables, out_dtype=q.dtype, layer=layer,
                          head_dim=q.shape[-1])
        vv = paged_gather(cv, block_tables, out_dtype=q.dtype, layer=layer,
                          head_dim=q.shape[-1])

    t, s = q.shape[2], kk.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    qpos = pos.reshape(-1, 1) + jnp.arange(t, dtype=jnp.int32)[None, :]
    kpos = jnp.arange(s, dtype=jnp.int32)                 # qpos: [B | 1, T]
    scores = jnp.einsum("bhtd,bhsd->bhts", q, kk) / math.sqrt(cfg.head_dim)
    rel = (kpos[None, None, :] - qpos[:, :, None]).astype(jnp.float32)
    slopes = jnp.asarray(alibi_slopes(cfg.num_heads))
    scores = scores.astype(jnp.float32) + \
        slopes[None, :, None, None] * rel[:, None]
    mask = kpos[None, None, :] <= qpos[:, :, None]        # [B | 1, T, S]
    mask = mask[:, None]                                  # [B | 1, 1, T, S]
    win = window_state()
    if win is not None:
        # resident-window serving: the demoted middle region
        # [landmark, window_start) is masked out (its table entries point
        # at scratch), exactly like the shared decode-attention path
        wstart, landmark = win
        wstart = jnp.asarray(wstart, jnp.int32).reshape(-1)
        keep = (kpos[None, :] < landmark) | \
            (kpos[None, :] >= wstart[:, None])            # [B, S]
        mask = mask & keep[:, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", probs, vv), ck, cv


def _block_cached_body(cfg: BloomConfig, x, get, mm, ck, cv, pos,
                       block_tables=None, chunk_valid=None, layer=None):
    """One BLOOM block over a KV cache, parameterized by weight access
    (``cached.decode_over_layers``'s body)."""
    b, t, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim

    y = _layer_norm(x, get("ln1_scale"), get("ln1_bias"))
    qkv = mm(y, "qkv_w", None) + get("qkv_b").astype(y.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
    attn, ck, cv = _alibi_cached_attention(cfg, q, k, v, ck, cv, pos,
                                           block_tables, chunk_valid, layer)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + mm(attn, "o_w", x.dtype) + get("o_b").astype(x.dtype)

    y = _layer_norm(x, get("ln2_scale"), get("ln2_bias"))
    hid = jax.nn.gelu(mm(y, "fc_w", None) + get("fc_b").astype(y.dtype),
                      approximate=False)
    x = x + mm(hid, "proj_w", x.dtype) + get("proj_b").astype(x.dtype)
    return x, ck, cv


def forward_cached(cfg: BloomConfig, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False):
    """Incremental forward: logits for the LAST input position + updated
    cache — or every position when ``all_positions`` is set.  The contract
    of ``lengths`` / ``block_tables`` is ``cached.window``'s.  ALiBi has no
    position table, so only the attention bias (absolute positions) moves
    with the per-row offsets — the embedding is position-free."""
    params = dequant_resident(params)
    w = window(input_ids, pos, lengths, block_tables)
    # sequence-parallel prefill hook: BLOOM's ALiBi attention has no
    # Ulysses all-to-all path (the additive bias rules out the shared
    # kernels), so sp here token-shards the projection/MLP chain and lets
    # GSPMD partition the bias-attention einsums
    x = shard_seq(_embed(cfg, params, input_ids))
    x, ks, vs = decode_over_layers(
        lambda x, get, mm, ck, cv, layer: _block_cached_body(
            cfg, x, get, mm, ck, cv, w.step_pos, block_tables=block_tables,
            chunk_valid=w.chunk_valid, layer=layer),
        x, params["blocks"], cache["k"], cache["v"], cfg.num_layers,
        paged=w.paged)
    if not all_positions:
        x = gather_last(x, w.gather)
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    return x @ params["word_embeddings"].T.astype(x.dtype), \
        {"k": ks, "v": vs}


def loss_from_batch(cfg: BloomConfig, params, batch, rng=None,
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
    nll = jnp.where(valid, lse - picked, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def tp_rules(cfg: BloomConfig, abstract_params: PyTree) -> PyTree:
    return {
        "word_embeddings": P(TP_AXIS, None),
        "word_ln_scale": P(), "word_ln_bias": P(),
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


# --------------------------------------------------------------------- HF I/O
def from_hf_state_dict(cfg: BloomConfig, sd: Dict[str, Any]) -> PyTree:
    """HF BLOOM state dict -> pytree.  HF fuses qkv **interleaved by head**
    ([h, 3, hd] rows); ours is [q; k; v] blocks — the converter reorders
    (the same transform the reference's bloom container applies,
    ``containers/bloom.py``)."""
    def get(name):
        for prefix in ("transformer.", ""):
            if prefix + name in sd:
                t = sd[prefix + name]
                return np.asarray(t.detach().cpu().numpy()
                                  if hasattr(t, "detach") else t, np.float32)
        raise KeyError(name)

    l, d, h, hd = cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.head_dim

    def dequkv_w(w):
        # HF: [3*d, d] rows ordered (head, {q,k,v}, hd); ours: [d, 3*d] cols
        w = w.reshape(h, 3, hd, d)
        q, k, v = w[:, 0], w[:, 1], w[:, 2]       # each [h, hd, d]
        return np.concatenate([q.reshape(d, d), k.reshape(d, d),
                               v.reshape(d, d)], axis=0).T

    def dequkv_b(b_):
        b_ = b_.reshape(h, 3, hd)
        return np.concatenate([b_[:, 0].reshape(d), b_[:, 1].reshape(d),
                               b_[:, 2].reshape(d)])

    def stack(fmt, fn=lambda x: x):
        return jnp.asarray(np.stack([fn(get(fmt.format(i=i)))
                                     for i in range(l)]))

    return {
        "word_embeddings": jnp.asarray(get("word_embeddings.weight")),
        "word_ln_scale": jnp.asarray(get("word_embeddings_layernorm.weight")),
        "word_ln_bias": jnp.asarray(get("word_embeddings_layernorm.bias")),
        "blocks": {
            "ln1_scale": stack("h.{i}.input_layernorm.weight"),
            "ln1_bias": stack("h.{i}.input_layernorm.bias"),
            "qkv_w": stack("h.{i}.self_attention.query_key_value.weight",
                           dequkv_w),
            "qkv_b": stack("h.{i}.self_attention.query_key_value.bias",
                           dequkv_b),
            "o_w": stack("h.{i}.self_attention.dense.weight",
                         lambda w: w.T),
            "o_b": stack("h.{i}.self_attention.dense.bias"),
            "ln2_scale": stack("h.{i}.post_attention_layernorm.weight"),
            "ln2_bias": stack("h.{i}.post_attention_layernorm.bias"),
            "fc_w": stack("h.{i}.mlp.dense_h_to_4h.weight", lambda w: w.T),
            "fc_b": stack("h.{i}.mlp.dense_h_to_4h.bias"),
            "proj_w": stack("h.{i}.mlp.dense_4h_to_h.weight", lambda w: w.T),
            "proj_b": stack("h.{i}.mlp.dense_4h_to_h.bias"),
        },
        "lnf_scale": jnp.asarray(get("ln_f.weight")),
        "lnf_bias": jnp.asarray(get("ln_f.bias")),
    }


def build(cfg: Optional[BloomConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or BloomConfig(**overrides)
    if cfg.dropout:
        raise NotImplementedError(
            "bloom: dropout is not implemented yet (the forward ignores it);"
            " set dropout=0")

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
        "forward_cached": lambda params, ids, cache, pos, lengths=None,
            block_tables=None, all_positions=False:
            forward_cached(cfg, params, ids, cache, pos, lengths,
                           block_tables, all_positions),
        # ALiBi has no learned position table: the context is bounded only
        # by the KV workspace
        "max_seq_len": None,
        "supports_lengths": True,
        "supports_paged": True,
        "supports_verify": True,
        # _alibi_cached_attention reads the pool only through paged_gather
        # (which dequantizes int8 records), so kv8 serving is supported
        "supports_kv_quant": True,
        # raw next-token logits reach the serving engine's on-device
        # sampler unchanged (per-slot temperature/top-k/top-p)
        "supports_sampling": True,
    }

    pipeline_hooks = {
        "blocks_key": ("blocks",),
        "embed_fn": lambda params, ids: _embed(cfg, params, ids),
        "block_fn": lambda layer, x, rng=None: _block(cfg, x, layer),
        "head_loss_fn": lambda params, x, tgt: _head_loss(cfg, params, x,
                                                          tgt),
        "dropout": 0.0,  # dropout unimplemented (build() rejects > 0)
    }

    return ModelSpec(
        init_fn=init_fn, model_config=cfg, loss_fn=loss_fn, apply_fn=apply_fn,
                     tp_rules=lambda ap: tp_rules(cfg, ap),
                     flops_per_token=6.0 * cfg.num_params(),
                     pipeline_hooks=pipeline_hooks,
                     decode_hooks=decode_hooks,
                     quant_aware=True,  # point-of-use dequant in _block
                     name=f"bloom-{cfg.num_layers}l-{cfg.hidden_size}d")


def _head_loss(cfg: BloomConfig, params, x, targets):
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    logits = x @ params["word_embeddings"].T.astype(x.dtype)
    valid = targets >= 0
    safe = jnp.where(valid, targets, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None],
                                 axis=-1)[..., 0].astype(jnp.float32)
    return jnp.where(valid, lse - picked,
                     0.0).sum() / jnp.maximum(valid.sum(), 1)
