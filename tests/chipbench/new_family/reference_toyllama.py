"""The plain reference a new family brings as a NEW file beside
``chipbench/reference.py`` (test fixture): a Llama-style decoder in float32
``jax.numpy`` — RMSNorm, rotary embeddings in the half-split convention
(HF ``LlamaRotaryEmbedding``), grouped-query attention, SwiGLU, an untied
head; no kernels, no cache, ``highest`` matmul precision.  It reads the
program's parameter pytree and shares no code with it."""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rope(x, theta):
    """x ``[B, H, S, hd]``: pair ``i`` with ``i + hd/2``, position ``p``
    turns the pair by ``p * theta ** (-2i / hd)``."""
    hd, s = x.shape[-1], x.shape[2]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def hidden_states(config: Dict[str, Any], params: Any, tokens):
    """Final-RMSNorm'd hidden states ``[B, S, D]`` in float32."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    b, s = tokens.shape
    d = params["embed"].shape[1]
    hd = d // heads
    x = _f32(params["embed"][tokens])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def split(y, n):
        return y.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    def block(x, layer):
        y = _rms_norm(x, layer["attn_norm"], eps)
        q = _rope(split(y @ _f32(layer["q_w"]), heads), theta)
        k = _rope(split(y @ _f32(layer["k_w"]), kv), theta)
        v = split(y @ _f32(layer["v_w"]), kv)
        # query head h reads KV head h // (heads // kv)
        k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        attn = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        x = x + attn.transpose(0, 2, 1, 3).reshape(b, s, d) \
            @ _f32(layer["o_w"])
        y = _rms_norm(x, layer["mlp_norm"], eps)
        gated = jax.nn.silu(y @ _f32(layer["w1"])) * (y @ _f32(layer["w3"]))
        return x + gated @ _f32(layer["w2"]), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    return _rms_norm(x, params["final_norm"], eps)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None):
    tokens = jnp.asarray(tokens)

    def run(params, tokens):
        x = hidden_states(config, params, tokens)
        if at is not None:
            x = x[:, jnp.asarray(at)]
        return x @ _f32(params["lm_head"])

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    tokens = jnp.asarray(tokens)

    def run(params, tokens):
        lg = hidden_states(config, params, tokens[:, :-1]) \
            @ _f32(params["lm_head"])
        picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)
