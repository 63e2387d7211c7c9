"""The plain reference of ``family: mistral4``: the language model of
mistralai/Mistral-Small-4-119B-2603 (``mistral4``) in float32
``jax.numpy`` — no kernels, no cache, no paging, no grouped matmul,
full-precision matmuls (``jax.default_matmul_precision("highest")``) — in
the EXPANDED form of its latent attention only: every head's keys and
values are written out from the latent, so that the comparison holds the
program's ABSORBED read (queries taken into latent space, the output
leaving through ``W_uv``) to the definition.  With ``h = RMS(x)`` (eps
``rms_norm_eps``), ``p`` a token's position and ``g`` one of the ``H``
heads:

    c_q           = RMS_q(h W_dq)                     [q_lora_rank]
    [qn_g | qr_g] = c_q W_uq,g                        [qk_nope | qk_rope]
    [c' | k']     = h W_dkv                           [kv_lora_rank | qk_rope]
    c = RMS_kv(c');  k_r = rope(k', p);  qr_g = rope(qr_g, p)
    [kn_g | v_g]  = c W_ukv,g                         [qk_nope | v_head_dim]
    rope: pairs (2i, 2i + 1) turned by p * f_i, f the YaRN inverse
          frequencies over qk_rope dims (theta, factor, original, beta_fast,
          beta_slow: HF ``_compute_yarn_parameters`` — a linear ramp between
          the two correction dimensions, truncated to whole dimensions,
          blends theta^(-2i/d) with theta^(-2i/d) / factor); cos and sin
          times mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    t(p)  = 1 + beta ln(1 + floor(p / original))      (llama_4_scaling_beta)
    s     = m^2 / sqrt(qk_nope + qk_rope),  m = 0.1 mscale_all_dim ln(factor) + 1
    score_g(i, j) = t(p_i) s (qn_g,i . kn_g,j + qr_g,i . k_r,j),   j <= i
    a     = concat_g(softmax(score_g) v_g) W_o
    x1 = x + a;   y = RMS(x1)
    r = softmax(y W_r) over ALL ``n_routed_experts_published`` (float32)
    S = top-k of r (ties: the lower id);  w_e = r_e / sum_{e' in S} r_e'
    E_e(y) = (silu(y W1_e) * (y W3_e)) W2_e
    m = sum_{e in S, e HELD} w_e E_e(y) + Sh(y)       (one shared expert)
    x' = x1 + m;    logits = RMS_f(x_L) W_head        (untied)

**The share.**  The pytree holds the experts ``experts_first ..
experts_first + count - 1`` of each layer (``count`` = the leaves' expert
axis; all of them in the uncut model).  The router is whole — scores and
top-k over all published experts, the weights normalised over all ``k``
chosen — and ``m`` sums the HELD chosen experts only: the partial result
this chip would send into its group's exchange, which is also what goes on
to the next layer, as in the program (no stand-in for the absent chips).
Eight shares' partial sums, plus the shared expert counted once, are the
uncut layer (``tests/unit/test_mistral4_serving.py`` shows it).  The
vocabulary is whatever slice of the token table and the head the pytree
holds.

It reads the PROGRAM's parameter pytree (``models/mixtral.py`` over
``models/llama.py``: ``attn_norm``, ``q_a_w [d, q_lora_rank]``,
``q_a_norm``, ``q_b_w [q_lora_rank, H * (nope + rope)]``, ``kv_a_w [d,
kv_lora_rank + rope]``, ``kv_a_norm``, ``kv_b_w [kv_lora_rank, H * (nope +
v)]``, ``o_w [H * v, d]``, ``mlp_norm``, ``gate_w [d, E]``, ``experts_w1 /
w3 [count, d, f]``, ``experts_w2 [count, f, d]``, ``shared_w1 / w3 [d,
f]``, ``shared_w2 [f, d]``, ``final_norm``, ``embed``, ``lm_head``) so the
same seeded weights feed both sides, and shares no code with it.  Queries
are attended ``QUERY_BLOCK`` at a time, one sequence at a time, so that 2 x
10,240 positions fit beside an engine.

What the published configuration leaves open, and what is taken here (the
configuration file lists the same under ``assumed``): the softmax scale
carries ``m^2`` (DeepSeek-V3's convention for ``mscale_all_dim`` under
YaRN); the router is a float32 softmax over all experts without a
correction bias; ``t(p)`` takes ``p``, not ``p + 1``, and multiplies the
whole query; ``rope_interleave`` is the pairwise rotation; the norms on
``c_q`` and ``c`` are RMSNorm at ``rms_norm_eps``; the shared expert is
added ungated; YaRN's correction range is truncated (HF's default).

Departures from the source's torch code, none of which changes the
function: everything is float32 (weights stay in the dtype they are served
in and are upcast a layer, and an expert, at a time); every held expert
runs over every token, weighted 0 outside the token's set; a projection is
stored ``[in, out]``; the number of layers is whatever the pytree holds.

``variant`` (the comparison's own check that it can tell a shortcut from
the model, PERF.md section 6): ``"latent_fp8"`` rounds what would be cached
— ``c`` and ``k_r`` — to float8 e4m3, ``"key_unrotated"`` leaves the shared
rope key unrotated, ``"no_temperature"`` drops ``t(p)``, ``"no_mscale"``
drops ``m^2`` from the scale, ``"no_latent_norm"`` skips ``RMS_kv``,
``"no_shared"`` leaves the shared expert out, ``"router_fp8"`` rounds the
router's input to float8 e4m3.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: queries attended at a time
QUERY_BLOCK = 64
VARIANTS = (None, "latent_fp8", "key_unrotated", "no_temperature",
            "no_mscale", "no_latent_norm", "no_shared", "router_fp8")


def _f32(x):
    return x.astype(jnp.float32)


def _fp8(x):
    return _f32(x.astype(jnp.float8_e4m3fn))


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(rope: Dict[str, Any], dim: int) -> np.ndarray:
    """float64 ``[dim / 2]``: YaRN's inverse frequencies over ``dim``
    rotated values (module docstring)."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = rope["original_max_position_embeddings"]
    extrapolation = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    interpolation = extrapolation / factor

    def correction(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return interpolation * ramp + extrapolation * (1.0 - ramp)


def _rotate(x, rope: Dict[str, Any]):
    """x ``[..., S, dim]`` turned at positions ``0 .. S-1``: pair ``(2i,
    2i + 1)`` by ``position * f_i``."""
    s, dim = x.shape[-2], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(rope, dim), jnp.float32)
    factor = _mscale(rope["factor"], rope["mscale"]) \
        / _mscale(rope["factor"], rope["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, scale):
    """One sequence: ``q`` / ``k [H, S, dk]``, ``v [H, S, dv]``, ``scale
    [S]`` (a query's own factor) -> ``[H, S, dv]``, ``QUERY_BLOCK`` queries
    at a time; query ``i`` keeps keys ``j <= i``."""
    h, s, _ = q.shape
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q = jnp.pad(q * scale[None, :, None], ((0, 0), (0, pad), (0, 0)))
    key_pos = jnp.arange(s)

    def block(i):
        at = i * qb
        qq = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=1)
        keep = key_pos[None, :] <= (at + jnp.arange(qb))[:, None]
        att = jnp.einsum("hqd,hsd->hqs", qq, k)
        probs = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,hsd->hqd", probs, v)

    out = jax.lax.map(block, jnp.arange((s + pad) // qb))    # [N, H, Q, dv]
    return jnp.moveaxis(out, 0, 1).reshape(h, s + pad, -1)[:, :s]


def _experts(y, layer, k: int, first: int, forced=None, fp8: bool = False):
    """The routed experts over ``y [N, D]``: the softmax router over ALL
    experts, a dense loop over the HELD ones (ids ``first ..``), each upcast
    alone, weighted by the router inside the top-k set — or inside
    ``forced`` (int32 ``[N, k]``: another side's sets), which also returns
    ``(experts of the own set that are in the forced one, the distances of
    the disagreeing experts' scores from the own k-th, each as a share of
    its token's largest: their largest, their sum, their number)``."""
    n_experts, held = layer["gate_w"].shape[-1], layer["experts_w1"].shape[0]
    r = _fp8(y) if fp8 else y
    score = jax.nn.softmax(r @ _f32(layer["gate_w"]), axis=-1)   # [N, E]
    top_s, top_e = jax.lax.top_k(score, k)
    chosen = jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32).sum(-2)
    report = None
    if forced is not None:
        own = chosen
        chosen = jax.nn.one_hot(forced, n_experts, dtype=jnp.float32).sum(-2)
        apart = own != chosen
        gap = jnp.where(apart, jnp.abs(score - top_s[:, -1:]), 0.0) \
            / top_s[:, :1]
        report = ((own * chosen).sum(), gap.max(), gap.sum(), apart.sum())
    weight = score * chosen
    weight = weight / weight.sum(-1, keepdims=True)     # over all k chosen

    def one(e, acc):
        w1, w3, w2 = (_f32(jax.lax.dynamic_index_in_dim(
            layer[name], e, keepdims=False))
            for name in ("experts_w1", "experts_w3", "experts_w2"))
        out = (jax.nn.silu(y @ w1) * (y @ w3)) @ w2
        return acc + out * jax.lax.dynamic_slice_in_dim(
            weight, first + e, 1, axis=1)

    out = jax.lax.fori_loop(0, held, one, jnp.zeros_like(y))
    return out if forced is None else (out, report)


def _shared(y, layer):
    """The one shared expert over ``y [N, D]``, added ungated."""
    return (jax.nn.silu(y @ _f32(layer["shared_w1"]))
            * (y @ _f32(layer["shared_w3"]))) @ _f32(layer["shared_w2"])


def hidden_states(config: Dict[str, Any], params: Any, tokens, forced=None,
                  variant: Optional[str] = None):
    """Final-RMSNorm'd hidden states ``[B, S, D]`` in float32.  ``forced``
    (``{"experts": int32 [L, B, S, k]}``): another side's expert sets, taken
    in place of the own ones; then the result is ``(hidden states,
    report)``, ``report`` the pair of :func:`_experts`, stacked over the
    layers."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    heads, eps = config["num_attention_heads"], config["rms_norm_eps"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rope_dim, vd = config["qk_rope_head_dim"], config["v_head_dim"]
    rope = config["rope_parameters"]
    k_exp, first = config["num_experts_per_tok"], config.get("experts_first", 0)
    if config["n_shared_experts"] != 1 or not config["norm_topk_prob"] \
            or config["routed_scaling_factor"] != 1 \
            or config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("the reference follows the published block: one "
                         "shared expert, top-k weights renormalised, "
                         "routed_scaling_factor 1, no expert groups")
    blocks = params["blocks"]
    b, s = tokens.shape
    d = params["embed"].shape[1]
    x = _f32(params["embed"][tokens])
    if forced is not None:
        forced = forced["experts"].reshape(blocks["gate_w"].shape[0], b * s,
                                           -1)
    m = _mscale(rope["factor"], rope["mscale_all_dim"])
    scale = (1.0 if variant == "no_mscale" else m * m) \
        / math.sqrt(nope + rope_dim)
    positions = jnp.arange(s)
    temperature = jnp.ones((s,), jnp.float32) if variant == "no_temperature" \
        else 1.0 + rope["llama_4_scaling_beta"] * jnp.log1p(_f32(
            positions // rope["original_max_position_embeddings"]))

    def block(x, per_layer):
        layer, force = per_layer
        h = _rms(x, layer["attn_norm"], eps)

        def attend(hr):                      # one sequence [S, D] at a time
            cq = _rms(hr @ _f32(layer["q_a_w"]), layer["q_a_norm"], eps)
            q = (cq @ _f32(layer["q_b_w"])).reshape(s, heads, nope + rope_dim)
            kv = hr @ _f32(layer["kv_a_w"])
            c, kr = kv[:, :rank], kv[:, rank:]
            if variant != "no_latent_norm":
                c = _rms(c, layer["kv_a_norm"], eps)
            if variant != "key_unrotated":
                kr = _rotate(kr, rope)
            if variant == "latent_fp8":
                c, kr = _fp8(c), _fp8(kr)
            qn, qr = q[..., :nope], _rotate(q[..., nope:].transpose(1, 0, 2),
                                            rope)
            kvb = (c @ _f32(layer["kv_b_w"])).reshape(s, heads, nope + vd)
            kn, v = kvb[..., :nope], kvb[..., nope:]
            q = jnp.concatenate([qn.transpose(1, 0, 2), qr], axis=-1)
            k = jnp.concatenate(
                [kn.transpose(1, 0, 2),
                 jnp.broadcast_to(kr[None], (heads, s, rope_dim))], axis=-1)
            a = _attention(q, k, v.transpose(1, 0, 2), temperature * scale)
            return a.transpose(1, 0, 2).reshape(s, heads * vd) \
                @ _f32(layer["o_w"])

        x = x + jax.lax.map(attend, h)
        y = _rms(x, layer["mlp_norm"], eps).reshape(b * s, d)
        moe = _experts(y, layer, k_exp, first, force,
                       fp8=variant == "router_fp8")
        report = None
        if force is not None:
            moe, report = moe
        if variant != "no_shared":
            moe = moe + _shared(y, layer)
        return x + moe.reshape(b, s, d), report

    x, reports = jax.lax.scan(block, x, (blocks, forced))
    x = _rms(x, params["final_norm"], eps)
    return x if forced is None else (x, reports)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None,
           variant: Optional[str] = None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only, through the untied head.  With ``forced``
    (:func:`hidden_states`): ``(logits, agreement)``, ``agreement`` =
    ``{"experts": share of the own chosen experts that the forced sets
    hold, "expert_gap": the MEAN distance of a disagreeing expert from the
    own cut-off, "expert_gap_max": the largest, "expert_gap_max_by_layer":
    the largest of each layer}`` (:func:`_experts`; the largest is an
    extreme of ~250,000 draws and wanders from seed to seed, the mean of
    ~10,000 does not)."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens, forced):
        x = hidden_states(config, params, tokens, forced, variant)
        if forced is not None:
            x, report = x
        if at is not None:
            x = x[:, jnp.asarray(at)]
        out = x @ _f32(params["lm_head"])
        return out if forced is None else (out, report)

    with jax.default_matmul_precision("highest"):
        out = jax.jit(run)(params, tokens, forced)
    if forced is None:
        return out
    out, (agree, gap, total, apart) = out
    k = config["num_experts_per_tok"]
    return out, {
        "experts": float(agree.sum()) / (tokens.size * k * agree.shape[0]),
        "expert_gap": float(total.sum()) / max(1.0, float(apart.sum())),
        "expert_gap_max": float(gap.max()),
        "expert_gap_max_by_layer": [round(float(g), 5) for g in gap]}


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    """Mean next-token cross entropy of ``tokens[:, :-1] -> tokens[:, 1:]``
    in float32."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens):
        lg = hidden_states(config, params, tokens[:, :-1]) \
            @ _f32(params["lm_head"])
        picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)
