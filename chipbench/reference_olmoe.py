"""The plain reference of ``family: olmoe``: OLMoE's decoder
(``transformers`` 4.57 ``models/olmoe/modeling_olmoe.py``; Muennighoff et
al. 2024) in float32 ``jax.numpy`` — no kernels, no cache, no sort, no
grouped matmul, full-precision matmuls
(``jax.default_matmul_precision("highest")``).

    y = rmsnorm(x, w_in);  q = rmsnorm(y Wq, w_qn);  k = rmsnorm(y Wk, w_kn)
    v = y Wv;  q, k split into heads, then RoPE (rotate-half)
    x = x + causal_softmax(q k^T / sqrt(hd)) v Wo
    y = rmsnorm(x, w_post);  p = softmax(y Wr) over ALL experts
    S = top-k of p;  no renormalisation unless ``norm_topk_prob``
    x = x + sum_{e in S} p_e (silu(y W1_e) * (y W3_e)) W2_e
    logits = rmsnorm(x, w_f) W_head

It reads the PROGRAM's parameter pytree (``models/mixtral.py``: ``embed``,
``blocks`` stacked ``[L, ...]``, ``final_norm``, ``lm_head``) so the same
seeded weights feed both sides, and shares no code with it.  Weights stay
in the dtype they are served in: a layer's attention matrices are upcast
inside the layer scan and its experts ONE at a time inside an inner loop
(a whole layer's experts in float32 are 1.6 GB at the published widths,
beside 11 GB of engine).

Departures from ``modeling_olmoe.py``, none of which changes the function:
  * HF keeps activations in the checkpoint's dtype and upcasts inside
    RMSNorm and the router softmax only; here everything is float32 (HF
    also casts the routing weights back to the activations' dtype).
  * HF's expert loop gathers each expert's tokens (``torch.where`` +
    ``index_add_``); here every expert runs over every token and the
    result is weighted by ``p_e`` inside the top-k set and 0 outside it —
    the same sum, no data-dependent shape.
  * The program stores a projection as ``[in, out]`` (``x @ W``), HF's
    ``nn.Linear`` as ``[out, in]``.
  * ``clip_qkv`` is null in the published configuration and not applied;
    there are no biases (``attention_bias: false``).
  * The number of layers is whatever the pytree holds (the benchmark builds
    ``depth`` of the published 16).
"""

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
    """x ``[B, H, S, hd]``: HF ``rotate_half`` — pair ``i`` with
    ``i + hd/2``; position ``p`` turns the pair by ``p * theta^(-2i/hd)``."""
    hd, s = x.shape[-1], x.shape[2]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _experts(y, layer, k: int, renormalize: bool):
    """The sparse block over ``y [N, D]``: a dense loop over experts, each
    upcast alone, weighted by the router inside the top-k set."""
    n_experts = layer["gate_w"].shape[-1]
    p = jax.nn.softmax(y @ _f32(layer["gate_w"]), axis=-1)       # [N, E]
    top_p, top_e = jax.lax.top_k(p, k)
    chosen = jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32).sum(-2)
    weight = p * chosen
    if renormalize:
        weight = weight / top_p.sum(-1, keepdims=True)

    def one(e, acc):
        w1, w3, w2 = (_f32(jax.lax.dynamic_index_in_dim(
            layer[name], e, keepdims=False))
            for name in ("experts_w1", "experts_w3", "experts_w2"))
        out = (jax.nn.silu(y @ w1) * (y @ w3)) @ w2
        return acc + out * jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)

    return jax.lax.fori_loop(0, n_experts, one, jnp.zeros_like(y))


def hidden_states(config: Dict[str, Any], params: Any, tokens):
    """Final-RMSNorm'd hidden states ``[B, S, D]`` in float32."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    k, renorm = config["num_experts_per_tok"], config["norm_topk_prob"]
    b, s = tokens.shape
    d = params["embed"].shape[1]
    hd = d // heads
    x = _f32(params["embed"][tokens])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def split(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    def block(x, layer):
        y = _rms_norm(x, layer["attn_norm"], eps)
        # q/k-norm: ONE RMSNorm over all heads' features, before the split
        q = _rms_norm(y @ _f32(layer["q_w"]), layer["q_norm"], eps)
        kk = _rms_norm(y @ _f32(layer["k_w"]), layer["k_norm"], eps)
        q, kk = _rope(split(q, heads), theta), _rope(split(kk, kv), theta)
        v = split(y @ _f32(layer["v_w"]), kv)
        kk, v = (jnp.repeat(t, heads // kv, axis=1) for t in (kk, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        attn = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        x = x + attn.transpose(0, 2, 1, 3).reshape(b, s, d) \
            @ _f32(layer["o_w"])
        y = _rms_norm(x, layer["mlp_norm"], eps)
        moe = _experts(y.reshape(b * s, d), layer, k, renorm)
        return x + moe.reshape(b, s, d), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    return _rms_norm(x, params["final_norm"], eps)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only.  The head is untied from the embedding."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens):
        x = hidden_states(config, params, tokens)
        if at is not None:
            x = x[:, jnp.asarray(at)]
        return x @ _f32(params["lm_head"])

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    """Mean next-token cross entropy of ``tokens[:, :-1] -> tokens[:, 1:]``
    in float32 (no router auxiliary loss: that is a training regulariser,
    not part of the model's function)."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens):
        lg = hidden_states(config, params, tokens[:, :-1]) \
            @ _f32(params["lm_head"])
        picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)
