"""The plain reference of ``family: brumby``: the language model of
manifestai/Brumby-14B-Base (``model_type: brumby``: the Qwen3-14B block with
its attention replaced by power retention of degree 2; Buckman, Gelada,
Zhang, "Scaling Context Requires Rethinking Attention", arXiv:2507.04239) in
float32 ``jax.numpy`` — no kernels, no cache, no chunked form, no batching,
full-precision matmuls (``jax.default_matmul_precision("highest")``) — with
the mixer in its ATTENTION form: the expansion ``phi``, the state ``S`` and
the normaliser ``z`` that the program keeps never appear.  ``x0 = E[ids]``;
with ``h = RMS(x; w1)`` (eps ``rms_norm_eps``) a layer is

    q = h W_q (heads x hd), k = h W_k, v = h W_v (KV heads x hd), no bias
    q <- RMS_hd(q; w_qn), k <- RMS_hd(k; w_kn)    a head, before the rotation
    q, k <- rotate-half rotary at the token's position, theta ``rope_theta``
    lg_t = log sigmoid(h_t W_gate + b_gate)       one log-gate a KV head, <= 0
    c_t  = sum_{l <= t} lg_l
    w_ij = exp(c_i - c_j) (q_i . k_j)^2,  j <= i   query head n, KV head n // G
    m    = concat_n(sum_j w_ij v_j / sum_j w_ij) W_o

followed by ``x1 = x + m`` and the SwiGLU ``x' = x1 + (SiLU(g) * u) W_d``
with ``g = RMS(x1; w2) W_g``, ``u = RMS(x1; w2) W_u``; ``logits = RMS(x_L;
w_f) W_head`` (the head is untied).  Every exponent is <= 0 and nothing is
clamped; the sums are divided as they are (``w_ii > 0`` whenever ``q_i . k_i
!= 0``).

It reads the PROGRAM's parameter pytree (``models/brumby.py``: ``llama``'s
stacked leaves plus ``gate_w [L, d, HKV]`` / ``gate_b [L, HKV]``) so that the
same seeded weights feed both sides, and shares no code with it.  A sequence
goes through the layers alone, its queries ``QUERY_BLOCK`` at a time, the
SwiGLU ``TOKEN_BLOCK`` tokens at a time and the head a block of the
vocabulary at a time: at the published widths a layer's weights are 1.3 GB
in float32 and the head 3.1 GB, and the reference runs beside a serving
engine that fills the chip.

Departures from the published modeling code that the builder knows of, each
also under ``assumed`` in the configuration file: the degree (2), the gate's
form (a linear map of the layer's normed input through ``log sigmoid``, one
a KV head) and its bias are not keys of the published config; the published
inference code may keep keys and values for a row's first few thousand
tokens and fold them into the state later (the same result); weights are
seeded, not trained.

``VARIANTS`` are shortcuts the benchmark's comparison must refuse:
``state_bf16`` (``S`` and ``z`` rounded to bfloat16 after every token),
``no_gate`` (``g = 1``), ``no_norm`` (the numerator undivided),
``unit_offdiag`` (the off-diagonal monomials weighted 1, not ``sqrt 2``: ``w
= ((q . k)^2 + q^2 . k^2) / 2``), ``no_reset`` (sequence ``i + 1`` starts
from the state sequence ``i`` left: a slot handed on without a reset).
``state_bf16`` and ``no_reset`` exist only where there is a state: they go
through :func:`_recurrent`, the per-token body kept beside the attention
form (``phi`` the upper triangle, row-major)."""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
TOKEN_BLOCK = 1024
#: blocks the head's vocabulary is multiplied in (where it divides)
HEAD_BLOCKS = 8
VARIANTS = (None, "state_bf16", "no_gate", "no_norm", "unit_offdiag",
            "no_reset")
#: the variants that need the state itself
RECURRENT = ("state_bf16", "no_reset")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def _rotate(x, theta: float):
    """Rotate-half rotary of ``x [S, heads, hd]`` at positions ``0 .. S``."""
    s, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(fn, xs, size: int):
    """``fn`` over blocks of ``size`` rows of the arrays ``xs`` (one, or a
    tuple, each ``[S, ...]``; zero rows pad the last block and their
    results are dropped)."""
    s = jax.tree_util.tree_leaves(xs)[0].shape[0]
    size = min(size, s)
    pad = -s % size
    xs = jax.tree_util.tree_map(
        lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        .reshape((-1, size) + a.shape[1:]), xs)
    out = jax.lax.map(fn, xs)
    return out.reshape((s + pad,) + out.shape[2:])[:s]


def _attention_form(q, k, v, lg, variant):
    """``q [S, HKV, G, hd]``, ``k``, ``v`` ``[S, HKV, hd]``, ``lg [S, HKV]``
    -> ``[S, HKV, G, hd]``: the weights ``w_ij`` and the two sums, the
    queries ``QUERY_BLOCK`` at a time."""
    s = q.shape[0]
    c = jnp.cumsum(lg, axis=0)                                   # [S, HKV]
    key_pos = jnp.arange(s)

    def block(xs):
        qq, cq, pos = xs                       # [QB, HKV, G, hd], [QB, HKV]
        dots = jnp.einsum("qngd,snd->ngqs", qq, k)
        w = dots * dots
        if variant == "unit_offdiag":
            w = 0.5 * (w + jnp.einsum("qngd,snd->ngqs", qq * qq, k * k))
        keep = key_pos[None, :] <= pos[:, None]                  # [QB, S]
        decay = jnp.where(keep[None], cq.T[:, :, None] - c.T[:, None, :],
                          -jnp.inf)                              # [HKV,QB,S]
        w = w * jnp.exp(decay)[:, None]
        num = jnp.einsum("ngqs,snd->qngd", w, v)
        if variant == "no_norm":
            return num
        return num / jnp.moveaxis(w.sum(-1), -1, 0)[..., None]

    return _blocks(block, (q, c, key_pos), QUERY_BLOCK)


def _phi(x):
    """``[.., hd] -> [.., hd (hd + 1) / 2]``: the degree-2 monomials ``a <=
    b``, row-major, the off-diagonal ones weighted ``sqrt 2``."""
    a, b = np.triu_indices(x.shape[-1])
    return x[..., a] * x[..., b] * jnp.where(a == b, 1.0, math.sqrt(2.0))


def _recurrent(q, k, v, lg, carry, variant):
    """The same layer token by token from ``carry = (S [HKV, D, hd], z [HKV,
    D])``: ``-> ([S, HKV, G, hd], carry)``.  (The scan reads its tokens as
    rows of whole 128-lane features: XLA:TPU pads a ``[S, 8, 5, 128]``
    operand's 5 to 128 otherwise, 7.4 GB at 15,000 tokens.)"""
    s, kv, g, hd = q.shape

    def token(carry, xs):
        st, zz = carry
        qt, kt, vt, lt = xs
        qt, kt, vt = qt.reshape(kv, g, hd), kt.reshape(kv, hd), \
            vt.reshape(kv, hd)
        gate, pk = jnp.exp(lt), _phi(kt)
        st = gate[:, None, None] * st + pk[:, :, None] * vt[:, None, :]
        zz = gate[:, None] * zz + pk
        if variant == "state_bf16":
            # (reduce_precision, not a cast there and back: XLA:TPU folds
            # the pair away under its excess-precision default)
            st, zz = (jax.lax.reduce_precision(a, exponent_bits=8,
                                               mantissa_bits=7)
                      for a in (st, zz))
        pq = _phi(qt)                                            # [HKV, G, D]
        num = jnp.einsum("ngm,nmd->ngd", pq, st)
        den = jnp.einsum("ngm,nm->ng", pq, zz)
        return (st, zz), (num / den[..., None]).reshape(-1)

    carry, y = jax.lax.scan(token, carry, (
        q.reshape(s, -1), k.reshape(s, -1), v.reshape(s, -1), lg))
    return y.reshape(q.shape), carry


def zero_carry(config: Dict[str, Any]):
    """The states a sequence starts from, all layers: ``(S [L, HKV, D, hd],
    z [L, HKV, D])``."""
    kv, hd = config["num_key_value_heads"], config["head_dim"]
    d = hd * (hd + 1) // 2
    return (jnp.zeros((config["depth"], kv, d, hd), jnp.float32),
            jnp.zeros((config["depth"], kv, d), jnp.float32))


def sequence(config: Dict[str, Any], params: Any, tokens, length=None,
             carry=None, variant: Optional[str] = None):
    """One sequence ``tokens [S]`` through the model: ``-> (final-RMSNorm'd
    hidden states [S, d] float32, carry)``.  With ``length`` (traced) the
    positions from it on are padding: the carry that comes back is the one
    after ``length`` tokens.  ``carry``: :func:`zero_carry`'s, used by the
    ``RECURRENT`` variants alone."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    if config["attention_bias"] or config["hidden_act"] != "silu" \
            or config["rope_scaling"] is not None \
            or config["use_sliding_window"] or config["tie_word_embeddings"]:
        raise ValueError("the reference follows the published block: no "
                         "attention bias, SiLU, unscaled rotary, no window, "
                         "an untied head")
    eps = config["rms_norm_eps"]
    heads, kv, hd = config["num_attention_heads"], \
        config["num_key_value_heads"], config["head_dim"]
    s = tokens.shape[0]
    live = jnp.ones((s,), bool) if length is None \
        else jnp.arange(s) < length
    recurrent = variant in RECURRENT
    if carry is None:
        carry = zero_carry(config) if recurrent else ()

    def one_layer(x, xs):
        index, state = xs
        layer = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False),
            params["blocks"])
        h = _rms(x, layer["attn_norm"], eps)
        q = _rms((h @ _f32(layer["q_w"])).reshape(s, heads, hd),
                 layer["q_norm"], eps)
        k = _rms((h @ _f32(layer["k_w"])).reshape(s, kv, hd),
                 layer["k_norm"], eps)
        v = (h @ _f32(layer["v_w"])).reshape(s, kv, hd)
        q = _rotate(q, config["rope_theta"]).reshape(s, kv, heads // kv, hd)
        k = jnp.where(live[:, None, None], _rotate(k, config["rope_theta"]),
                      0.0)
        lg = jax.nn.log_sigmoid(h @ _f32(layer["gate_w"])
                                + _f32(layer["gate_b"]))
        lg = jnp.where(live[:, None], lg, 0.0)
        if variant == "no_gate":
            lg = jnp.zeros_like(lg)
        if recurrent:
            m, state = _recurrent(q, k, v, lg, state, variant)
        else:
            m = _attention_form(q, k, v, lg, variant)
        x = x + m.reshape(s, heads * hd) @ _f32(layer["o_w"])
        w1, w3, w2 = (_f32(layer[n]) for n in ("w1", "w3", "w2"))
        x = x + _blocks(
            lambda y: (jax.nn.silu(y @ w1) * (y @ w3)) @ w2,
            _rms(x, layer["mlp_norm"], eps), TOKEN_BLOCK)
        return x, state

    x = _f32(params["embed"][tokens])
    x, carry = jax.lax.scan(
        one_layer, x, (jnp.arange(config["depth"], dtype=jnp.int32), carry))
    return _rms(x, params["final_norm"], eps), carry


def _head(params, x):
    """``x [n, d] @ W_head`` a block of the vocabulary at a time."""
    w = params["lm_head"]
    d, vocab = w.shape
    blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
    out = jax.lax.map(lambda wb: x @ _f32(wb),
                      jnp.moveaxis(w.reshape(d, blocks, vocab // blocks),
                                   1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], vocab)


@functools.lru_cache(maxsize=None)
def _jitted(config_items, variant):
    config = dict(config_items)

    def run(params, tokens, at, length, carry):
        x, carry = sequence(config, params, tokens, length, carry, variant)
        return _head(params, x if at is None else x[at]), carry

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _put():
    """``out[i] = row`` in place (the result donated where the backend
    takes donations)."""
    return jax.jit(
        lambda out, row, i: jax.lax.dynamic_update_index_in_dim(
            out, row, i, 0),
        donate_argnums=() if jax.default_backend() == "cpu" else (0,))


def _hashable(config: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, variant: Optional[str] = None,
           lengths=None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only (``at [B, n]``: sequence ``i`` at ``at[i]``), the
    sequences one after the other.  ``lengths`` (int32 ``[B]``): sequence
    ``i`` is its first ``lengths[i]`` tokens and padding after them (what a
    sequence hands to the next under ``no_reset`` is its state there)."""
    tokens = jnp.asarray(tokens)
    at = None if at is None else np.asarray(at, np.int32)
    run = _jitted(_hashable(config), variant)
    carry = zero_carry(config) if variant in RECURRENT else None
    out = None
    with jax.default_matmul_precision("highest"):
        for i in range(tokens.shape[0]):
            row, left = run(
                params, tokens[i],
                None if at is None else jnp.asarray(
                    at if at.ndim == 1 else at[i]),
                None if lengths is None else jnp.asarray(lengths[i],
                                                         jnp.int32),
                carry)
            # (a sequence's logits land in the one result where it lies: a
            # stack would hold every row twice, 2 x 2.5 GB for four replies
            # of 1,024 tokens over 151,936 rows, beside the engine's weights)
            if out is None:
                out = jnp.zeros((tokens.shape[0],) + row.shape, row.dtype)
            out = _put()(out, row, i)
            if variant == "no_reset":
                carry = left
    return out


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    """Mean next-token cross entropy of ``tokens[:, :-1] -> tokens[:, 1:]``
    in float32."""
    tokens = jnp.asarray(tokens)
    lg = logits(config, params, tokens[:, :-1])
    picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()
