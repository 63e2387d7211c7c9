"""The plain reference of ``family: zaya``: Zyphra/ZAYA1-8B (``model_type:
zaya``) in float32 ``jax.numpy`` — no kernels, no cache, no paging, no
tails, no batching (a sequence at a time), full-precision matmuls
(``jax.default_matmul_precision("highest")``); the two causal convolutions
as two shifted adds over the whole sequence, the attention as a plain causal
softmax over every earlier key, the routing as a dense ``argmax`` and every
expert computed for every token, the chosen one kept.

``x0 = E[ids]``; with ``d`` the hidden size, ``H`` / ``G`` query / KV heads
of ``hd``, ``R`` the router's width, ``E`` experts of width ``F``, each layer
is (``RMS`` with eps ``rms_norm_eps``; ``a[-1] = 0`` for every sequence
``a``):

  attention (compressed convolutional attention, grouped), ``h = RMS(x)``:
    q~ = h W_q [H x hd]      k~ = h W_k [G x hd]      c = [q~ | k~]
    m_q = (q~ + rep(k~)) / 2     m_k = (mean(q~) + k~) / 2
                   (rep / mean over a KV head's H / G query heads)
    c1[t] = w0[0] * c[t] + w0[1] * c[t-1]           depthwise, cca_time0 = 2
    c2[t] = W1[0] c1[t] + W1[1] c1[t-1]     by head: hd x hd a head a tap,
                                                            cca_time1 = 2
    [q | k] = c2 + [m_q | m_k]
    q = sqrt(hd) q / |q|     k = tau_g sqrt(hd) k / |k|          a head
    q, k rotated over their first partial_rotary_factor * hd channels
                   (rotate-half, theta rope_parameters.hybrid.rope_theta)
    v[t] = [h[t] W_va | h[t-1] W_vb]      a KV head: hd / 2 from each
    o = softmax(q k^T / sqrt(hd), causal) v;     f = o W_o
  experts (top-1 of E, none shared), ``y = RMS(x)``, ``r_{-1} = 0``:
    r_l = y W_down + gamma_l r_{l-1}
    s = softmax(W_c GELU(W_b GELU(W_a r_l)))           GELU exact (erf)
    e = argmax s;     f = s_e (SiLU(y W1_e) * (y W3_e)) W2_e
  either merged as   x <- (x + b_r) * s_r + (f + b_f) * s_f

and ``logits = RMS(x_L) E^T`` (the head is tied).

It reads the PROGRAM's parameter pytree (``models/zaya.py``: ``blocks``
stacked ``[L, ...]``) so that the same seeded weights feed both sides, and
shares no code with it.  The layers are a ``lax.scan`` over the stacks: at
the published widths a layer's weights are 0.83 GB in float32 and the
reference runs beside a serving engine that fills the chip.

Departures from the published model (no modeling code is in the repository;
the equations are written from the public ``config.json``, arXiv:2510.04476
and arXiv:2511.17127), each also under ``assumed`` in the configuration
file: the two convolutions' forms and order (depthwise, then grouped by
head, both causal with two taps, no bias, no activation); the q-k mean taken
before the convolutions and grouped by KV head; the L2 norm to ``sqrt(hd)``
with a temperature on ``k`` only; the value's shifted half split a KV head;
the router's depth (three matrices, no bias), exact GELU, the depth average
``r_l = y W_down + gamma_l r_{l-1}``; the top-1 weight ``s_e`` left
unnormalised; no skip ("zero-compute") output; the residual merge; rotate-half
rotary; ``rope_parameters.hybrid_sliding`` unused (``sliding_window`` null);
weights seeded, not trained.

``VARIANTS`` are shortcuts the benchmark's comparison must refuse:
``tails_fp8`` (what the convolutions read, ``c`` and ``c1``, rounded to
float8 e4m3: the nearest precision below the bfloat16 the program keeps them
in), ``router_fp8`` / ``router_bf16`` (the router's stream and the MLP's
activations rounded to float8 e4m3 / to bfloat16: the program keeps them in
float32; the first is the nearest precision below the configuration's
bfloat16 and must be refused, the second is SHOWN — behind a bfloat16
residual stream ten layers deep a comparison cannot tell a bfloat16 router
from a float32 one, ``chipbench/drivers/serve_tails.py``), ``no_shift`` (the
value's second half from the token itself), ``no_eda`` (``gamma = 0``: a
router that does not read the layer before),
``no_mean`` (the q-k mean left out)."""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128
VARIANTS = (None, "tails_fp8", "router_fp8", "router_bf16", "no_shift",
            "no_eda", "no_mean")
_MERGE = ("res_b", "res_s", "out_b", "out_s")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def _rounded(x, exponent_bits: int, mantissa_bits: int):
    # (reduce_precision, not a cast there and back: XLA:TPU folds the pair
    # away under its excess-precision default)
    return jax.lax.reduce_precision(x, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits)


def _behind(a):
    """``a [S, ...]`` a token late, zeros first."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)


def _merge(layer, sub, x, f):
    b_r, s_r, b_f, s_f = (_f32(layer[f"{sub}_{n}"]) for n in _MERGE)
    return (x + b_r) * s_r + (f + b_f) * s_f


def _rotary(config, a):
    """``a [S, heads, hd]`` rotated over its first ``partial_rotary_factor
    * hd`` channels, rotate-half, at positions ``0 .. S - 1``."""
    hd = a.shape[-1]
    rope = config["rope_parameters"]["hybrid"]
    rd = int(hd * rope["partial_rotary_factor"])
    inv = 1.0 / (float(rope["rope_theta"])
                 ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = jnp.arange(a.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a1, a2 = a[..., :rd // 2], a[..., rd // 2:rd]
    return jnp.concatenate(
        [a1 * cos - a2 * sin, a2 * cos + a1 * sin, a[..., rd:]], axis=-1)


def _attention(config, layer, h, variant):
    """One sequence ``h [S, d]`` (normed) through the attention sublayer's
    ``f``: ``[S, d]``."""
    hq, g, hd = config["num_attention_heads"], \
        config["num_key_value_heads"], config["head_dim"]
    s, rep = h.shape[0], hq // g
    qt, kt = h @ _f32(layer["q_w"]), h @ _f32(layer["k_w"])
    c = jnp.concatenate([qt, kt], axis=-1)
    if variant == "tails_fp8":
        c = _rounded(c, 4, 3)
    w0, w1 = _f32(layer["conv0_w"]), _f32(layer["conv1_w"])
    c1 = w0[0] * c + w0[1] * _behind(c)
    if variant == "tails_fp8":
        c1 = _rounded(c1, 4, 3)
    c1 = c1.reshape(s, hq + g, hd)
    c2 = jnp.einsum("shi,hio->sho", c1, w1[0]) \
        + jnp.einsum("shi,hio->sho", _behind(c1), w1[1])
    q4, k3 = qt.reshape(s, g, rep, hd), kt.reshape(s, g, hd)
    if variant != "no_mean":
        c2 = c2 + jnp.concatenate(
            [(0.5 * (q4 + k3[:, :, None])).reshape(s, hq, hd),
             0.5 * (q4.mean(axis=2) + k3)], axis=1)
    unit = c2 / jnp.sqrt(jnp.sum(c2 * c2, axis=-1, keepdims=True) + 1e-12) \
        * math.sqrt(hd)
    q = _rotary(config, unit[:, :hq])
    k = _rotary(config, unit[:, hq:] * _f32(layer["tau"])[:, None])
    va, vb = h @ _f32(layer["va_w"]), h @ _f32(layer["vb_w"])
    v = jnp.concatenate(
        [va.reshape(s, g, hd // 2),
         (vb if variant == "no_shift" else _behind(vb))
         .reshape(s, g, hd // 2)], axis=-1)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK, axis=0)
        score = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(hd)
        seen = jnp.arange(s)[None, :] \
            <= (q0 + jnp.arange(QUERY_BLOCK))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], score, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", prob, v)

    pad = -s % QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    o = jax.lax.map(block, jnp.arange(0, s + pad, QUERY_BLOCK))
    return o.reshape(s + pad, hq * hd)[:s] @ _f32(layer["o_w"])


def _experts(config, layer, y, r, route, variant):
    """One sequence ``y [S, d]`` (normed) and the stream ``r [S, R]`` the
    layer before left through the expert sublayer: ``(f [S, d], r, scores
    [S, E])``; ``route`` (int32 ``[S]`` or None): the expert each token runs
    in place of its own ``argmax``."""
    gamma = 0.0 if variant == "no_eda" else _f32(layer["gamma"])
    r = y @ _f32(layer["down_w"]) + gamma * r
    bits = {"router_bf16": (8, 7), "router_fp8": (4, 3)}.get(variant)
    low = (lambda a: _rounded(a, *bits)) if bits else (lambda a: a)
    z = low(jax.nn.gelu(low(r) @ _f32(layer["ra_w"]), approximate=False))
    z = low(jax.nn.gelu(z @ _f32(layer["rb_w"]), approximate=False))
    scores = jax.nn.softmax(low(z @ _f32(layer["rc_w"])), axis=-1)
    chosen = jnp.argmax(scores, axis=-1) if route is None else route
    weight = jnp.take_along_axis(scores, chosen[:, None], axis=-1)

    def expert(acc, xs):
        number, w1, w3, w2 = xs
        f = (jax.nn.silu(y @ _f32(w1)) * (y @ _f32(w3))) @ _f32(w2)
        return acc + jnp.where((chosen == number)[:, None], weight * f,
                               0.0), None

    f, _ = jax.lax.scan(
        expert, jnp.zeros_like(y),
        (jnp.arange(config["num_experts"]), layer["experts_w1"],
         layer["experts_w3"], layer["experts_w2"]))
    return f, r, scores


def hidden_states(config: Dict[str, Any], params: Any, tokens,
                  variant: Optional[str] = None, route=None):
    """``(RMS(x_L) [B, S, d], the router's scores [L, B, S, E])``;
    ``route``: int32 ``[L, B, S]`` or None."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    eps = config["rms_norm_eps"]
    b, s = tokens.shape
    given = route is not None

    def one_layer(carry, xs):
        x, r = carry
        layer, forced = xs

        def sequence(args):
            x, r, forced = args
            x = _merge(layer, "attn", x, _attention(
                config, layer, _rms(x, layer["attn_norm"], eps), variant))
            f, r, scores = _experts(
                config, layer, _rms(x, layer["moe_norm"], eps), r,
                forced if given else None, variant)
            return _merge(layer, "moe", x, f), r, scores

        x, r, scores = jax.lax.map(sequence, (x, r, forced))
        return (x, r), scores

    layers = config["depth"]
    if route is None:
        route = jnp.zeros((layers, b, s), jnp.int32)
    x = _f32(params["embed"][tokens])
    r = jnp.zeros((b, s, config["router_hidden_size"]), jnp.float32)
    (x, _), scores = jax.lax.scan(one_layer, (x, r),
                                  (params["blocks"], route))
    return _rms(x, params["final_norm"], eps), scores


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, route=None,
           variant: Optional[str] = None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only.  With ``route`` (int32 ``[L, B, S]``; ``[L, S]`` for
    one sequence) every token runs the GIVEN expert in every layer instead
    of its own ``argmax``, and the result is the pair ``(logits, the
    reference's OWN scores float32 [L, B, S, E])`` — what a comparison holds
    the program's routes to."""
    tokens = jnp.asarray(tokens)
    if route is not None:
        route = jnp.asarray(route, jnp.int32).reshape(
            (-1,) + tuple(tokens.shape))

    def run(params, tokens, at, route):
        x, scores = hidden_states(config, params, tokens, variant, route)
        if at is not None:
            x = x[:, at]
        out = jnp.einsum("bsd,vd->bsv", x, _f32(params["embed"]))
        return out if route is None else (out, scores)

    at = None if at is None else jnp.asarray(at, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens, at, route)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    """Mean next-token cross entropy of ``tokens[:, :-1] -> tokens[:, 1:]``
    in float32."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens):
        x, _ = hidden_states(config, params, tokens[:, :-1])
        lg = jnp.einsum("bsd,vd->bsv", x, _f32(params["embed"]))
        picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)


def num_params(config: Dict[str, Any], layers: Optional[int] = None) -> int:
    """The reference's own count, from the equations above: ``layers``
    layers (the configuration's ``depth`` by default) + the tied table + the
    final norm."""
    d, hd = config["hidden_size"], config["head_dim"]
    hq, g = config["num_attention_heads"], config["num_key_value_heads"]
    r, e, f = config["router_hidden_size"], config["num_experts"], \
        config["moe_intermediate_size"]
    attention = d * hq * hd + d * g * hd + 2 * d * (g * hd // 2) \
        + hq * hd * d + 2 * (hq + g) * hd + 2 * (hq + g) * hd * hd + g
    router = d * r + 2 * r * r + r * e + 1
    experts = e * 3 * d * f
    norms_and_merges = 2 * d + 8 * d
    per_layer = attention + router + experts + norms_and_merges
    layers = config["depth"] if layers is None else layers
    return layers * per_layer + config["vocab_size"] * d + d
