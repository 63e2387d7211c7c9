"""The plain reference of ``family: kimi_linear``: the language model of
moonshotai/Kimi-Linear-48B-A3B-Instruct (``kimi_linear``; technical report
arXiv:2510.26692) in float32 ``jax.numpy`` — no kernels, no cache, no
paging, no chunked form, no grouped matmul, full-precision matmuls
(``jax.default_matmul_precision("highest")``): the gated delta rule TOKEN BY
TOKEN in a ``lax.scan``, the latent attention in its EXPANDED form (every
head's keys and values written out from the latent), a loop over the held
experts.  With ``h = RMS(x)`` (eps ``rms_norm_eps``) and ``g`` one of the
heads, a layer of the pattern is one of

  KDA (layers 1-3, 5-7, ...; ``H`` heads of width ``dk = dv``):
    [q' | k' | v'] = h [W_q | W_k | W_v]
    (q, k, v)_t  = SiLU(sum_{j < K} tap_j * (q', k', v')_{t - K + 1 + j})
                   (causal depthwise convolution, zeros before position 0)
    q_g = unit(q_g) dk^-0.5;   k_g = unit(k_g)      unit(x) = x rsqrt(|x|^2 + 1e-6)
    a_t = -exp(A_log_g) softplus(h W_f1 W_f2 + dt_bias)_g          [dk]
    b_t = sigmoid(h W_b)_g
    S <- Diag(exp(a_t)) S;  S <- S + b_t k_t (v_t - S^T k_t)^T;  o_t = S^T q_t
    a   = concat_g(RMS_head(o_g) * sigmoid(h W_g1 W_g2)_g) W_o
  MLA (layers 4, 8, ...; NO rotation, ``mla_use_nope``):
    [qn_g | qr_g] = h W_q,g        [c' | k_r] = h W_dkv     c = RMS_kv(c')
    [kn_g | v_g]  = c W_ukv,g
    score_g(i, j) = (qn_g,i . kn_g,j + qr_g,i . k_r,j) / sqrt(nope + rope),  j <= i
    a   = concat_g(softmax(score_g) v_g) W_o

followed by ``x1 = x + a; y = RMS(x1)`` and the FFN: in the first
``first_k_dense_replace`` layers the dense ``(silu(y W1) * (y W3)) W2``;
in every other one

    r = sigmoid(y W_r) over ALL published experts (float32)
    S = top-k of r + bias (ties: the lower id);  w_e = f r_e / sum_{e' in S} r_e'
    m = sum_{e in S, e HELD} w_e E_e(y) + Sh(y)        (f = routed_scaling_factor)

``x' = x1 + m``; ``logits = RMS_f(x_L) W_head`` (untied).

**The share.**  The pytree holds ``count`` experts of each routed layer
(ids ``experts_first ..``; all of them in the uncut model): the router is
whole, ``m`` sums the HELD chosen experts only — this chip's partial result,
which is also what goes on to the next layer, as in the program.  Eight
shares' partial sums, the shared expert counted once, are the uncut layer
(``tests/unit/test_kimi_linear_serving.py``).

It reads the PROGRAM's parameter pytree (``models/kimi_linear.py``: stacks
BY KIND, ``blocks["kda" | "latent" | "dense" | "moe"]``, each attention
kind's stack carrying its layers' ``attn_norm`` / ``mlp_norm``) so that the
same seeded weights feed both sides, and shares no code with it.

Departures from the published description, each also under ``assumed`` in
the configuration file: the gates' bottleneck rank is the KDA head width;
the convolutions have no bias; the router's correction bias is a seeded
parameter; 8 of 27 layers, 32 of 256 experts and 20,480 of 163,840
vocabulary rows are built; weights are seeded, not trained.

``VARIANTS`` are shortcuts the benchmark's comparison must refuse:
``state_bf16`` (the state rounded to bfloat16 after every token),
``no_decay`` (``a_t = 0``), ``no_reset`` (sequence ``i + 1`` starts from the
state and the convolution tail sequence ``i`` left: a slot handed on
without a reset)."""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

QUERY_BLOCK = 64
VARIANTS = (None, "state_bf16", "no_decay", "no_reset")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def layer_kinds(config: Dict[str, Any]):
    """The kind of each built layer, from the published 1-based lists."""
    lin = config["linear_attn_config"]
    kinds = []
    for n in range(1, config["depth"] + 1):
        if n in lin["kda_layers"]:
            kinds.append("kda")
        elif n in lin["full_attn_layers"]:
            kinds.append("latent")
        else:
            raise ValueError(f"layer {n} is in neither published list")
    return kinds


def _kda(config, layer, h, carry, variant, length=None):
    """One sequence ``h [S, d]`` through a KDA layer from ``carry = (state
    [H, dk, dv], tail [K - 1, 3 C])``: ``-> (a [S, d], carry)``.  With
    ``length`` (traced) the positions from it on are padding: the carry that
    comes back is the one after ``length`` tokens."""
    lin = config["linear_attn_config"]
    heads, hd, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    s, c = h.shape[0], heads * hd
    state, tail = carry
    x3 = jnp.concatenate([h @ _f32(layer[n]) for n in ("q_w", "k_w", "v_w")],
                         axis=-1)
    ext = jnp.concatenate([tail, x3], axis=0)
    w = _f32(layer["conv_w"])
    conv = jax.nn.silu(sum(ext[j:j + s] * w[j] for j in range(taps)))
    q, k, v = (conv[:, i * c:(i + 1) * c].reshape(s, heads, hd)
               for i in range(3))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q, k = unit(q) * hd ** -0.5, unit(k)
    decay = -jnp.exp(_f32(layer["a_log"]))[None, :, None] * jax.nn.softplus(
        h @ _f32(layer["f_a_w"]) @ _f32(layer["f_b_w"])
        + _f32(layer["dt_bias"])).reshape(s, heads, hd)
    if variant == "no_decay":
        decay = jnp.zeros_like(decay)
    beta = jax.nn.sigmoid(h @ _f32(layer["b_w"]))                 # [S, H]
    if length is not None:
        real = jnp.arange(s) < length
        decay = jnp.where(real[:, None, None], decay, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)

    def token(st, xs):
        qt, kt, vt, at, bt = xs
        st = st * jnp.exp(at)[:, :, None]
        st = st + bt[:, None, None] * kt[:, :, None] * (
            vt - jnp.einsum("hkv,hk->hv", st, kt))[:, None, :]
        if variant == "state_bf16":
            # (reduce_precision, not a cast there and back: XLA:TPU folds
            # the pair away under its excess-precision default)
            st = jax.lax.reduce_precision(st, exponent_bits=8,
                                          mantissa_bits=7)
        return st, jnp.einsum("hkv,hk->hv", st, qt)

    state, o = jax.lax.scan(token, state, (q, k, v, decay, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + config["rms_norm_eps"]) * _f32(layer["o_norm"])
    gate = jax.nn.sigmoid(h @ _f32(layer["g_a_w"]) @ _f32(layer["g_b_w"]))
    out = (o.reshape(s, c) * gate) @ _f32(layer["o_w"])
    if length is None:
        return out, (state, ext[s:])
    return out, (state, jax.lax.dynamic_slice_in_dim(ext, length, taps - 1))


def _attention(q, k, v, scale):
    """Causal softmax attention of one sequence, ``q [H, S, d]``, queries
    ``QUERY_BLOCK`` at a time."""
    h, s, _ = q.shape
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q = jnp.pad(q * scale, ((0, 0), (0, pad), (0, 0)))
    key_pos = jnp.arange(s)

    def block(i):
        at = i * qb
        qq = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=1)
        keep = key_pos[None, :] <= (at + jnp.arange(qb))[:, None]
        att = jnp.einsum("hqd,hsd->hqs", qq, k)
        probs = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,hsd->hqd", probs, v)

    out = jax.lax.map(block, jnp.arange((s + pad) // qb))
    return jnp.moveaxis(out, 0, 1).reshape(h, s + pad, -1)[:, :s]


def _mla(config, layer, h):
    """One sequence ``h [S, d]`` through a latent layer, expanded."""
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope, vd = config["qk_nope_head_dim"], \
        config["qk_rope_head_dim"], config["v_head_dim"]
    s = h.shape[0]
    q = (h @ _f32(layer["q_w"])).reshape(s, heads, nope + rope)
    kv = h @ _f32(layer["kv_a_w"])
    c = _rms(kv[:, :rank], layer["kv_a_norm"], config["rms_norm_eps"])
    kvb = (c @ _f32(layer["kv_b_w"])).reshape(s, heads, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(kv[:, None, rank:], (s, heads, rope))], axis=-1)
    a = _attention(q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                   kvb[..., nope:].transpose(1, 0, 2),
                   1.0 / math.sqrt(nope + rope))
    return a.transpose(1, 0, 2).reshape(s, heads * vd) @ _f32(layer["o_w"])


def _experts(config, y, layer, forced=None):
    """The routed experts over ``y [N, D]`` plus the shared one: sigmoid
    scores over ALL experts, the top-k of ``score + bias``, a dense loop
    over the HELD experts, weighted by the scaled renormalised scores inside
    the top-k set — or inside ``forced`` (int32 ``[N, k]``: another side's
    sets), which also returns ``(experts of the own set that are in the
    forced one, the distances of the disagreeing experts' biased scores from
    the own k-th, each as a share of its token's largest: their largest,
    their sum, their number)``."""
    k, first = config["num_experts_per_token"], config.get("experts_first", 0)
    n_experts, held = layer["gate_w"].shape[-1], layer["experts_w1"].shape[0]
    score = jax.nn.sigmoid(y @ _f32(layer["gate_w"]))            # [N, E]
    biased = score + _f32(layer["gate_bias"])
    top_b, top_e = jax.lax.top_k(biased, k)
    chosen = jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32).sum(-2)
    report = None
    if forced is not None:
        own = chosen
        chosen = jax.nn.one_hot(forced, n_experts, dtype=jnp.float32).sum(-2)
        apart = own != chosen
        gap = jnp.where(apart, jnp.abs(biased - top_b[:, -1:]), 0.0) \
            / top_b[:, :1]
        report = ((own * chosen).sum(), gap.max(), gap.sum(), apart.sum())
    weight = score * chosen
    if config["moe_renormalize"]:
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight * config["routed_scaling_factor"]

    def one(e, acc):
        w1, w3, w2 = (_f32(jax.lax.dynamic_index_in_dim(
            layer[name], e, keepdims=False))
            for name in ("experts_w1", "experts_w3", "experts_w2"))
        out = (jax.nn.silu(y @ w1) * (y @ w3)) @ w2
        return acc + out * jax.lax.dynamic_slice_in_dim(
            weight, first + e, 1, axis=1)

    out = jax.lax.fori_loop(0, held, one, jnp.zeros_like(y))
    out = out + (jax.nn.silu(y @ _f32(layer["shared_w1"]))
                 * (y @ _f32(layer["shared_w3"]))) @ _f32(layer["shared_w2"])
    return out if forced is None else (out, report)


def _at(stack, i):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


def hidden_states(config: Dict[str, Any], params: Any, tokens, forced=None,
                  variant: Optional[str] = None, lengths=None):
    """Final-RMSNorm'd hidden states ``[B, S, D]`` in float32, the
    sequences one after the other.  ``lengths`` (int32 ``[B]``): sequence
    ``i`` is its first ``lengths[i]`` tokens and padding after them (what a
    sequence hands to the next under ``no_reset`` is its state there).  ``forced`` (``{"experts": int32 [routed
    layers, B, S, k]}``): another side's expert sets, taken in place of the
    own ones; then the result is ``(hidden states, report)``, ``report`` the
    tuple of :func:`_experts`, stacked over the routed layers."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    if config["num_shared_experts"] != 1 or not config["mla_use_nope"] \
            or config["q_lora_rank"] is not None \
            or config["moe_router_activation_func"] != "sigmoid" \
            or config["num_expert_group"] != 1 or config["topk_group"] != 1 \
            or config["rope_scaling"] is not None \
            or config["tie_word_embeddings"] or config["moe_layer_freq"] != 1:
        raise ValueError("the reference follows the published block: one "
                         "shared expert, NoPE latent attention with a "
                         "full-rank query, a sigmoid router without groups, "
                         "every layer past the dense ones routed, an untied "
                         "head")
    eps, dense = config["rms_norm_eps"], config["first_k_dense_replace"]
    lin = config["linear_attn_config"]
    blocks = params["blocks"]
    b, s = tokens.shape
    x = _f32(params["embed"][tokens])
    seen = {"kda": 0, "latent": 0}
    reports = []
    for number, kind in enumerate(layer_kinds(config)):
        layer = _at(blocks[kind], seen[kind])
        seen[kind] += 1
        h = _rms(x, layer["attn_norm"], eps)
        if kind == "kda":
            zero = (jnp.zeros((lin["num_heads"], lin["head_dim"],
                               lin["head_dim"]), jnp.float32),
                    jnp.zeros((lin["short_conv_kernel_size"] - 1,
                               3 * lin["num_heads"] * lin["head_dim"]),
                              jnp.float32))
            carry, rows = zero, []
            for i in range(b):
                a, carry = _kda(config, layer, h[i], carry, variant,
                                None if lengths is None else lengths[i])
                rows.append(a)
                if variant != "no_reset":
                    carry = zero
            x = x + jnp.stack(rows)
        else:
            x = x + jax.lax.map(lambda hr: _mla(config, layer, hr), h)
        y = _rms(x, layer["mlp_norm"], eps).reshape(b * s, -1)
        if number < dense:
            lyr = _at(blocks["dense"], number)
            out = (jax.nn.silu(y @ _f32(lyr["w1"])) * (y @ _f32(lyr["w3"]))) \
                @ _f32(lyr["w2"])
        else:
            lyr = _at(blocks["moe"], number - dense)
            out = _experts(config, y, lyr, None if forced is None else
                           forced["experts"][number - dense].reshape(
                               b * s, -1))
            if forced is not None:
                out, report = out
                reports.append(report)
        x = x + out.reshape(x.shape)
    x = _rms(x, params["final_norm"], eps)
    if forced is None:
        return x
    return x, tuple(jnp.stack(r) for r in zip(*reports))


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None,
           variant: Optional[str] = None, lengths=None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only (``at [B, n]``: sequence ``i`` at ``at[i]``).  ``lengths``:
    :func:`hidden_states`.  With ``forced`` (:func:`hidden_states`): ``(logits,
    agreement)``, ``agreement`` = ``{"experts": share of the own chosen
    experts that the forced sets hold, "expert_gap": the MEAN distance of a
    disagreeing expert from the own cut-off, "expert_gap_max": the largest,
    "expert_gap_max_by_layer": the largest of each routed layer}``."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens, forced, at, lengths):
        x = hidden_states(config, params, tokens, forced, variant, lengths)
        if forced is not None:
            x, report = x
        if at is not None:
            x = x[:, at] if at.ndim == 1 else \
                jnp.take_along_axis(x, at[:, :, None], axis=1)
        out = x @ _f32(params["lm_head"])
        return out if forced is None else (out, report)

    at = None if at is None else jnp.asarray(at, jnp.int32)
    lengths = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(run)(params, tokens, forced, at, lengths)
    if forced is None:
        return out
    out, (agree, gap, total, apart) = out
    k = config["num_experts_per_token"]
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
