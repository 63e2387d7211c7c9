"""The plain reference: a pre-LN decoder-only transformer in float32
``jax.numpy`` — no kernels, no cache, no batching tricks, full-precision
matmuls (``jax.default_matmul_precision("highest")``; on a TPU a float32
matmul otherwise runs in bf16 passes).  One function, parametrised for

* ``opt``   learned positions offset by 2, ReLU MLP
             (Zhang et al. 2022, HF ``OPTDecoder`` with
             ``do_layer_norm_before=True``);
* ``gpt2``  learned positions from 0, tanh-approximated GELU
             ("gelu_new"; Radford et al. 2019, HF ``GPT2Model``).

Both: LayerNorm eps 1e-5, fused ``qkv`` projection split q|k|v, causal
softmax attention scaled by 1/sqrt(head_dim), a final LayerNorm and a head
tied to the token embedding.

It is reached through ``families/opt.py`` and ``families/gpt2.py`` (the
table below IS those two families' reference; another family brings its own
``reference_<x>.py``).  It reads the PROGRAM's parameter pytree (so the same seeded weights feed
both sides) but shares no code with it.  Weights stay in the dtype they are
served in and are upcast one layer at a time inside the scan, so a 1.3 B
model's float32 copy (5.3 GB) never exists beside the engine's pool.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

_LN_EPS = 1e-5

#: family -> (token table, position table, position offset, activation)
_FAMILIES = {
    "opt": ("embed_tokens", "embed_positions", 2, jax.nn.relu),
    "gpt2": ("wte", "wpe", 0,
             lambda x: jax.nn.gelu(x, approximate=True)),
}


def _f32(x):
    return x.astype(jnp.float32)


def _layer_norm(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + _LN_EPS) * _f32(scale) + _f32(bias)


def hidden_states(family: str, params: Any, tokens, num_heads: int):
    """Final-LayerNorm'd hidden states ``[B, S, D]`` in float32."""
    tok_key, pos_key, offset, act = _FAMILIES[family]
    b, s = tokens.shape
    d = params[tok_key].shape[1]
    hd = d // num_heads
    x = _f32(params[tok_key][tokens]) \
        + _f32(params[pos_key][offset:offset + s])[None]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, layer):
        y = _layer_norm(x, layer["ln1_scale"], layer["ln1_bias"])
        qkv = y @ _f32(layer["qkv_w"]) + _f32(layer["qkv_b"])
        q, k, v = (t.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        attn = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + attn @ _f32(layer["o_w"]) + _f32(layer["o_b"])
        y = _layer_norm(x, layer["ln2_scale"], layer["ln2_bias"])
        hid = act(y @ _f32(layer["fc_w"]) + _f32(layer["fc_b"]))
        return x + hid @ _f32(layer["proj_w"]) + _f32(layer["proj_b"]), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    return _layer_norm(x, params["lnf_scale"], params["lnf_bias"])


def logits(family: str, params: Any, tokens, num_heads: int,
           at: Optional[Sequence[int]] = None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only (the head over 50k rows is the large part)."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens):
        x = hidden_states(family, params, tokens, num_heads)
        if at is not None:
            x = x[:, jnp.asarray(at)]
        return x @ _f32(params[_FAMILIES[family][0]]).T

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)


def next_token_loss(family: str, params: Any, tokens, num_heads: int):
    """Mean next-token cross entropy of ``tokens[:, :-1] -> tokens[:, 1:]``
    in float32, one sequence at a time."""
    tokens = jnp.asarray(tokens)

    def one(params, row):
        x = hidden_states(family, params, row[None, :-1], num_heads)[0]
        lg = x @ _f32(params[_FAMILIES[family][0]]).T
        picked = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()

    def run(params, tokens):
        return jax.lax.map(lambda row: one(params, row), tokens).mean()

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)
