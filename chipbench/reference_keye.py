"""The plain reference of ``family: keye``: the language model of
Kwai-Keye/Keye-VL-2.0-30B-A3B in float32 ``jax.numpy`` — no kernels, no
cache, no paging, no grouped matmul, full-precision matmuls
(``jax.default_matmul_precision("highest")``).  The block is the Qwen3-MoE
decoder of Keye-VL's earlier releases plus the lightning indexer
DeepSeek-V3.2 published (``sa_config``); for a token at position ``t``:

    y = rmsnorm(x, w_in)
    q = split_H(y Wq);  k = split_HKV(y Wk);  v = split_HKV(y Wv)
    q_h = rmsnorm(q_h, w_qn);  k_g = rmsnorm(k_g, w_kn)     one [hd] scale,
                                                    over each head's features
    q, k: M-RoPE (rotate-half; frequency pair i takes the position
          component of its ``mrope_section``: temporal, height, width)
    qI = split_HI(y WIq);  kI = layernorm(y WIk);  w = y WIw
    qI, kI: RoPE at the text position over the whole indexer width
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])                  s <= t
    S_t = all s <= t if t + 1 <= topk, else the topk s of largest I[t, s]
          (ties: the lower s); one set a token and layer, for all heads
    x = x + concat_h softmax_{s in S_t}(q_h . k_g(h) / sqrt(hd)) v_g(h) Wo
    y = rmsnorm(x, w_post);  p = softmax(y Wr) over ALL experts
    E = top-k of p, renormalised to sum 1 (``norm_topk_prob``)
    x = x + sum_{e in E} p_e (silu(y W1_e) * (y W3_e)) W2_e
    logits = rmsnorm(x, w_f) W_head                                (untied)

It reads the PROGRAM's parameter pytree (``models/mixtral.py`` with an
indexer: ``idx_q_w``, ``idx_k_w``, ``idx_w_w``, ``idx_k_norm [2, DI]`` =
scale row, bias row) so the same seeded weights feed both sides, and shares
no code with it.  Queries are attended ``QUERY_BLOCK`` at a time so that
6,144 positions fit beside an engine (a block's scores are ``[B, H, 128,
S]``); the selection is materialised as a MASK over all ``S`` keys, from
the rank of each key in a stable largest-first order (two sorts, no top-k
primitive, no threshold).

What the published configuration leaves open, and what is taken here (the
configuration file lists the same under ``assumed``):
  * per-head q/k-norm: the Qwen3 convention; no key turns it off;
  * the indexer's key is LayerNorm'd (scale and bias, eps 1e-6) and both
    its queries and key are rotated over their whole 64 features
    (DeepSeek-V3.2's indexer norms its key and rotates the part its rotary
    width covers; here no width is given); it reads the block's normed
    input (there is no q-LoRA to read from);
  * DeepSeek's positive scale on ``w`` is left out: it cannot change S_t;
  * ``q_chunk_size`` / ``kv_chunk_size`` are an implementation's tiling and
    change no result: selection is per token;
  * the vision tower is absent.  ``positions`` (``[3, B, S]``: temporal,
    height, width) default to three equal text components, on which the
    sectioned M-RoPE IS the 1-D rotary (a test shows it).

Departures from the source's torch code, none of which changes the
function: everything is float32 (weights stay in the dtype they are served
in and are upcast a layer, and an expert, at a time); every expert runs
over every token, weighted 0 outside the token's set; a projection is
stored ``[in, out]``; the number of layers is whatever the pytree holds.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

#: queries attended at a time
QUERY_BLOCK = 128


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _layer_norm(x, scale_bias, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(scale_bias[0]) \
        + _f32(scale_bias[1])


def _rotate(x, ang):
    """x ``[B, H, S, hd]`` turned by ``ang [B, S, hd/2]``: HF
    ``rotate_half`` pairs feature ``i`` with ``i + hd/2``."""
    hd = x.shape[-1]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mrope_angles(positions, hd: int, theta: float,
                 sections: Optional[Sequence[int]] = None):
    """Angles ``[B, S, hd/2]`` of ``positions [3, B, S]``: frequency pair
    ``i`` turns by ``theta^(-2i/hd)`` times the position component of its
    section (``sections`` pairs each: temporal, height, width); without
    sections, by the temporal component alone (1-D RoPE)."""
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    pos = _f32(positions)
    if sections is None:
        return pos[0][..., None] * freq
    assert sum(sections) == hd // 2, (sections, hd)
    part = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                      total_repeat_length=hd // 2)            # [hd/2]
    # [B, S, hd/2]: component part[i] of the position, for each pair i
    return jnp.moveaxis(pos, 0, -1)[..., part] * freq


def selection_mask(scores, visible, topk: int):
    """bool like ``scores [..., Q, S]``: the keys each query attends.
    ``visible`` (same shape): the causal keys.  A query with at most
    ``topk`` visible keys attends them all; otherwise the ``topk`` of
    largest score, of equal scores (``-0.0 == 0.0``) the lower position
    first."""
    scores = jnp.where(scores == 0.0, 0.0, scores)
    order = jnp.argsort(jnp.where(visible, -scores, jnp.inf), axis=-1,
                        stable=True)                # largest score first
    rank = jnp.argsort(order, axis=-1, stable=True)
    return visible & (rank < topk)


def _experts(y, layer, k: int, renormalize: bool, forced=None):
    """The sparse block over ``y [N, D]``: a dense loop over experts, each
    upcast alone, weighted by the router inside the top-k set — or inside
    ``forced`` (int32 ``[N, k]``: another side's sets), which also returns
    ``(experts of the own set that are in the forced one, the largest
    distance of a disagreeing expert's probability from the own k-th, as a
    share of the token's largest)``."""
    n_experts = layer["gate_w"].shape[-1]
    p = jax.nn.softmax(y @ _f32(layer["gate_w"]), axis=-1)       # [N, E]
    top_p, top_e = jax.lax.top_k(p, k)
    chosen = jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32).sum(-2)
    report = None
    if forced is not None:
        own = chosen
        chosen = jax.nn.one_hot(forced, n_experts, dtype=jnp.float32).sum(-2)
        gap = jnp.where(own != chosen, jnp.abs(p - top_p[:, -1:]), 0.0) \
            / top_p[:, :1]
        report = ((own * chosen).sum(), gap.max())
    weight = p * chosen
    if renormalize:
        weight = weight / weight.sum(-1, keepdims=True)

    def one(e, acc):
        w1, w3, w2 = (_f32(jax.lax.dynamic_index_in_dim(
            layer[name], e, keepdims=False))
            for name in ("experts_w1", "experts_w3", "experts_w2"))
        out = (jax.nn.silu(y @ w1) * (y @ w3)) @ w2
        return acc + out * jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)

    out = jax.lax.fori_loop(0, n_experts, one, jnp.zeros_like(y))
    return out if forced is None else (out, report)


def _attention(q, k, v, qi, ki, wi, topk: int, forced=None):
    """``q [B, HKV, rep, S, hd]``, ``k`` / ``v [B, HKV, S, hd]``, the
    indexer's ``qi [B, HI, S, DI]``, ``ki [B, S, DI]``, ``wi [B, S, HI]``
    -> ``[B, HKV, rep, S, hd]``, ``QUERY_BLOCK`` queries at a time.
    ``forced`` (uint8 ``[B, S, ceil(S / 8)]``: another side's sets, one bit
    a key, ``numpy.packbits`` order) is attended instead of the own
    selection and adds a second result, over the queries past ``topk``:
    ``(keys chosen, keys of the own sets that are in the forced ones, the
    largest distance of a disagreeing key's score from the own cut-off, as
    a share of the query's largest score)``."""
    b, hkv, rep, s, hd = q.shape
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    key_pos = jnp.arange(s)

    def padded(x, axis):
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)

    q, qi, wi = padded(q, 3), padded(qi, 2), padded(wi, 1)
    if forced is not None:
        forced = padded(forced, 1)

    def block(i):
        at = i * qb
        qq = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=3)
        qqi = jax.lax.dynamic_slice_in_dim(qi, at, qb, axis=2)
        ww = jax.lax.dynamic_slice_in_dim(wi, at, qb, axis=1)
        visible = key_pos[None, :] <= (at + jnp.arange(qb))[:, None]  # [Q,S]
        dots = jax.nn.relu(jnp.einsum("bjqd,bsd->bjqs", qqi, ki))
        index = jnp.einsum("bqj,bjqs->bqs", ww, dots)            # I[t, s]
        keep = selection_mask(index, visible[None], topk)       # [B, Q, S]
        report = None
        if forced is not None:
            own = keep
            bits = jax.lax.dynamic_slice_in_dim(forced, at, qb, axis=1)
            keep = visible[None] & jnp.unpackbits(bits, axis=-1)[
                ..., :s].astype(bool)
            # a pad query of the last block sees every key: selects, counts
            past = ((at + jnp.arange(qb) >= topk)
                    & (at + jnp.arange(qb) < s))[None, :, None]
            cut = jnp.min(jnp.where(own, index, jnp.inf), -1, keepdims=True)
            scale = jnp.max(jnp.where(visible[None], jnp.abs(index), 0.0),
                            -1, keepdims=True)
            gap = jnp.where(past & (own != keep), jnp.abs(index - cut), 0.0) \
                / jnp.maximum(scale, 1e-30)
            report = ((past & own).sum(), (past & own & keep).sum(),
                      gap.max())
        att = jnp.einsum("bgrqd,bgsd->bgrqs", qq, k) / math.sqrt(hd)
        probs = jax.nn.softmax(
            jnp.where(keep[:, None, None], att, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqs,bgsd->bgrqd", probs, v)
        return out if forced is None else (out, report)

    out = jax.lax.map(block, jnp.arange((s + pad) // qb))  # [N,B,G,R,Q,hd]
    if forced is not None:
        out, (total, agree, gap) = out
        report = (total.sum(), agree.sum(), gap.max())
    out = jnp.moveaxis(out, 0, 3).reshape(b, hkv, rep, s + pad, hd)[
        :, :, :, :s]
    return out if forced is None else (out, report)


def hidden_states(config: Dict[str, Any], params: Any, tokens,
                  positions=None, forced=None):
    """Final-RMSNorm'd hidden states ``[B, S, D]`` in float32.
    ``positions [3, B, S]``: the M-RoPE components (default: text, three
    equal ``arange``).  ``forced`` (``{"experts": int32 [L, B, S, k],
    "keys": uint8 [L, B, S, ceil(S / 8)]}``): another side's discrete
    choices, taken in place of the own ones; then the result is ``(hidden
    states, report)``, ``report`` the per-layer tuples of :func:`_attention`
    and :func:`_experts`."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    theta = float(config["rope_theta"])
    sections = (config.get("rope_scaling") or {}).get("mrope_section")
    k_exp, renorm = config["num_experts_per_tok"], config["norm_topk_prob"]
    sa = config["sa_config"]
    hi, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    b, s = tokens.shape
    d = params["embed"].shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (3, b, s))
    ang = mrope_angles(positions, hd, theta, sections)
    ang_i = mrope_angles(positions, di, theta)      # the text position
    x = _f32(params["embed"][tokens])

    def split(t, n, width):
        return t.reshape(b, s, n, width).transpose(0, 2, 1, 3)

    def block(x, layer_and_forced):
        layer, force = layer_and_forced
        y = _rms_norm(x, layer["attn_norm"], eps)
        q = _rms_norm(split(y @ _f32(layer["q_w"]), heads, hd),
                      layer["q_norm"], eps)
        kk = _rms_norm(split(y @ _f32(layer["k_w"]), kv, hd),
                       layer["k_norm"], eps)
        q, kk = _rotate(q, ang), _rotate(kk, ang)
        v = split(y @ _f32(layer["v_w"]), kv, hd)
        qi = _rotate(split(y @ _f32(layer["idx_q_w"]), hi, di), ang_i)
        ki = _rotate(_layer_norm(y @ _f32(layer["idx_k_w"]),
                                 layer["idx_k_norm"])[:, None], ang_i)[:, 0]
        wi = y @ _f32(layer["idx_w_w"])
        attn = _attention(q.reshape(b, kv, heads // kv, s, hd), kk, v,
                          qi, ki, wi, topk,
                          None if force is None else force["keys"])
        moe = None if force is None else force["experts"].reshape(b * s, -1)
        report = None
        if force is not None:
            attn, key_report = attn
        attn = attn.reshape(b, heads, s, hd).transpose(0, 2, 1, 3)
        x = x + attn.reshape(b, s, heads * hd) @ _f32(layer["o_w"])
        y = _rms_norm(x, layer["mlp_norm"], eps)
        moe = _experts(y.reshape(b * s, d), layer, k_exp, renorm, moe)
        if force is not None:
            moe, expert_report = moe
            report = (key_report, expert_report)
        return x + moe.reshape(b, s, d), report

    x, report = jax.lax.scan(block, x, (params["blocks"], forced))
    x = _rms_norm(x, params["final_norm"], eps)
    return x if forced is None else (x, report)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, positions=None, forced=None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only.  The head is untied from the embedding.  With
    ``forced`` (:func:`hidden_states`): ``(logits, agreement)``,
    ``agreement`` = ``{"keys": share of the own chosen keys that the forced
    sets hold (queries past topk), "key_gap", "experts", "expert_gap"}``
    over all layers (the gaps: :func:`_attention`, :func:`_experts`)."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens, forced):
        x = hidden_states(config, params, tokens, positions, forced)
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
    out, ((key_total, key_agree, key_gap), (expert_agree, expert_gap)) = out
    k = config["num_experts_per_tok"]
    return out, {
        "keys": float(key_agree.sum()) / max(1.0, float(key_total.sum())),
        "key_gap": float(key_gap.max()),
        "experts": float(expert_agree.sum())
        / (tokens.size * k * expert_agree.shape[0]),
        "expert_gap": float(expert_gap.max())}


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
