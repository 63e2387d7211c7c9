"""The plain reference of ``family: commanda``: the language model of
CohereLabs/command-a-plus-05-2026 (``cohere2_moe``) in float32
``jax.numpy`` — no kernels, no cache, no paging, no grouped matmul,
full-precision matmuls (``jax.default_matmul_precision("highest")``).  With
``h = LN(x)`` the ONE LayerNorm of a block (mean subtracted, a scale, no
bias, eps ``layer_norm_eps``) and ``l`` the layer's index:

    kind(l) = layer_types[l]        (sliding, sliding, sliding, full, ...)
    q, k, v = h Wq [H x hd], h Wk [HKV x hd], h Wv [HKV x hd]  (no bias,
                                                              no q/k-norm)
    sliding: q, k rotated pairwise-INTERLEAVED (pairs 2i, 2i + 1), theta
             ``rope_theta``, over the whole head; query i sees keys j with
             0 <= i - j < sliding_window
    full:    no rotation; query i sees keys j <= i
    a = softmax(q k^T / sqrt(hd)) v Wo          (GQA: H / HKV queries a key)
    s = sigmoid(h Wr) over ALL ``num_experts_published`` experts
    S = top-k of s (ties: the lower id);  w_e = s_e / sum_{e' in S} s_e'
    E_e(h) = (silu(h W1_e) * (h W3_e)) W2_e
    m = sum_{e in S, e HELD} w_e E_e(h) + (1 / Sh) sum_{j < Sh} Sh_j(h)
    x' = x + a + m                                       (parallel block)
    logits = logit_scale * LN_f(x_L) Emb^T                    (tied head)

**The share.**  The pytree holds the experts ``experts_first ..
experts_first + count - 1`` of each layer (``count`` = the leaves' expert
axis; all of them in the uncut model).  The router is whole — scores and
top-k over all published experts, the weights normalised over all ``k``
chosen — and ``m`` sums the HELD chosen experts only: the partial result
this chip would send into its group's exchange, which is also what goes on
to the next layer, as in the program (no stand-in for the absent chips).
Eight shares' partial sums, plus the shared experts' average counted once,
are the uncut layer (``tests/unit/test_commanda_serving.py`` shows it).
The vocabulary is whatever slice of the token table the pytree holds.

It reads the PROGRAM's parameter pytree (``models/mixtral.py``:
``attn_norm``, ``q_w`` .. ``o_w``, ``gate_w [d, E]``, ``experts_w1 / w3 [count,
d, f]``, ``experts_w2 [count, f, d]``, ``shared_w1 / w3 [d, Sh * f]``,
``shared_w2 [Sh * f, d]`` — shared expert ``j`` is columns / rows ``j * f ..
(j + 1) * f`` — ``final_norm``, ``embed``) so the same seeded weights feed
both sides, and shares no code with it.  Queries are attended
``QUERY_BLOCK`` at a time, one sequence at a time, so that 2 x 6,144
positions at 128 heads fit beside an engine.

What the published configuration leaves open, and what is taken here (the
configuration file lists the same under ``assumed``): ``intermediate_size``
is one expert's and one shared expert's width; "average" is the mean of the
shared experts' outputs, added to the routed sum; the LayerNorm is Cohere's
bias-free one; a sliding query sees exactly ``sliding_window`` keys, itself
included; full layers take no rotation; sigmoid scores in float32.

Departures from the source's torch code, none of which changes the
function: everything is float32 (weights stay in the dtype they are served
in and are upcast a layer, and an expert, at a time); every held expert
runs over every token, weighted 0 outside the token's set; a projection is
stored ``[in, out]``; the number of layers is whatever the pytree holds.

``variant`` (the comparison's own check that it can tell a shortcut from
the model, PERF.md section 6): ``"router_fp8"`` rounds the router's input
to float8 e4m3, ``"no_window"`` lets the sliding layers see every key,
``"rope_full"`` rotates the full layers too, ``"shared_sum"`` adds the
shared experts' sum instead of their mean.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

#: queries attended at a time
QUERY_BLOCK = 64
VARIANTS = (None, "router_fp8", "no_window", "rope_full", "shared_sum")


def _f32(x):
    return x.astype(jnp.float32)


def _layer_norm(x, scale, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(scale)


def _rotate_interleaved(x, theta: float):
    """x ``[H, S, hd]`` turned at positions ``0 .. S-1``: pair ``(2i, 2i +
    1)`` by ``position * theta^(-2i / hd)``."""
    s, hd = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq       # [S, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def layer_kinds(config: Dict[str, Any], layers: int) -> Sequence[str]:
    """``"sliding"`` | ``"full"`` of the first ``layers`` layers, from the
    published ``layer_types`` (``sliding_attention`` / ``full_attention``)."""
    return ["sliding" if "sliding" in t else "full"
            for t in config["layer_types"][:layers]]


def _attention(q, k, v, window):
    """One sequence: ``q [HKV, rep, S, hd]``, ``k`` / ``v [HKV, S, hd]`` ->
    ``[HKV, rep, S, hd]``, ``QUERY_BLOCK`` queries at a time; query ``i``
    keeps keys ``j <= i`` with ``i - j < window`` (a full layer's
    ``window`` is longer than the sequence)."""
    hkv, rep, s, hd = q.shape
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    key_pos = jnp.arange(s)

    def block(i):
        at = i * qb
        qq = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=2)
        pos = (at + jnp.arange(qb))[:, None]
        keep = (key_pos[None, :] <= pos) & (key_pos[None, :] > pos - window)
        att = jnp.einsum("grqd,gsd->grqs", qq, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        # a pad query past S + window keeps no key: its row is NaN and is
        # cut off below
        return jnp.einsum("grqs,gsd->grqd", probs, v)

    out = jax.lax.map(block, jnp.arange((s + pad) // qb))   # [N, G, R, Q, hd]
    return jnp.moveaxis(out, 0, 2).reshape(hkv, rep, s + pad, hd)[:, :, :s]


def _experts(y, layer, k: int, first: int, forced=None, fp8: bool = False):
    """The routed experts over ``y [N, D]``: the router over ALL experts, a
    dense loop over the HELD ones (ids ``first ..``), each upcast alone,
    weighted by the router inside the top-k set — or inside ``forced``
    (int32 ``[N, k]``: another side's sets), which also returns ``(experts
    of the own set that are in the forced one, the largest distance of a
    disagreeing expert's score from the own k-th, as a share of the token's
    largest)``."""
    n_experts, held = layer["gate_w"].shape[-1], layer["experts_w1"].shape[0]
    r = _f32(y.astype(jnp.float8_e4m3fn)) if fp8 else y
    score = jax.nn.sigmoid(r @ _f32(layer["gate_w"]))            # [N, E]
    top_s, top_e = jax.lax.top_k(score, k)
    chosen = jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32).sum(-2)
    report = None
    if forced is not None:
        own = chosen
        chosen = jax.nn.one_hot(forced, n_experts, dtype=jnp.float32).sum(-2)
        gap = jnp.where(own != chosen, jnp.abs(score - top_s[:, -1:]), 0.0) \
            / top_s[:, :1]
        report = ((own * chosen).sum(), gap.max())
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


def _shared(y, layer, n: int, average: bool):
    """The ``n`` shared experts over ``y [N, D]``, one at a time; their
    mean (or sum)."""
    f = layer["shared_w1"].shape[-1] // n
    out = jnp.zeros_like(y)
    for j in range(n):
        at = slice(j * f, (j + 1) * f)
        out = out + (jax.nn.silu(y @ _f32(layer["shared_w1"][:, at]))
                     * (y @ _f32(layer["shared_w3"][:, at]))) \
            @ _f32(layer["shared_w2"][at])
    return out / n if average else out


def hidden_states(config: Dict[str, Any], params: Any, tokens, forced=None,
                  variant: Optional[str] = None):
    """Final-LayerNorm'd hidden states ``[B, S, D]`` in float32.
    ``forced`` (``{"experts": int32 [L, B, S, k]}``): another side's expert
    sets, taken in place of the own ones; then the result is ``(hidden
    states, report)``, ``report`` the pair of :func:`_experts`, stacked over
    the layers."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["layer_norm_eps"]
    theta, window = float(config["rope_theta"]), config["sliding_window"]
    k_exp, first = config["num_experts_per_tok"], config.get("experts_first", 0)
    shared = config["num_shared_experts"]
    blocks = params["blocks"]
    n_layers = blocks["q_w"].shape[0]
    # a layer's kind rides through the layer loop as a flag: the rotation
    # is computed and taken or not, the window is ``sliding_window`` or
    # longer than the sequence (a static slice of the stacked weights would
    # be a copy of each layer's, 9 GB at the cell's size)
    sliding = jnp.asarray([k == "sliding"
                           for k in layer_kinds(config, n_layers)])
    b, s = tokens.shape
    d = params["embed"].shape[1]
    x = _f32(params["embed"][tokens])
    if forced is not None:
        forced = forced["experts"].reshape(n_layers, b * s, -1)

    def split(t, n):
        return t.reshape(s, n, hd).transpose(1, 0, 2)            # [n, S, hd]

    def block(x, per_layer):
        layer, is_sliding, force = per_layer
        h = _layer_norm(x, layer["attn_norm"], eps)
        rotated = is_sliding | (variant == "rope_full")
        reach = jnp.where(is_sliding & (variant != "no_window"), window,
                          s + 1)

        def attend(hr):                      # one sequence at a time
            q, kk, v = (split(hr @ _f32(layer[name]), n)
                        for name, n in (("q_w", heads), ("k_w", kv),
                                        ("v_w", kv)))
            q = jnp.where(rotated, _rotate_interleaved(q, theta), q)
            kk = jnp.where(rotated, _rotate_interleaved(kk, theta), kk)
            a = _attention(q.reshape(kv, heads // kv, s, hd), kk, v, reach)
            return a.reshape(heads, s, hd).transpose(1, 0, 2) \
                .reshape(s, heads * hd) @ _f32(layer["o_w"])

        a = jax.lax.map(attend, h)
        moe = _experts(h.reshape(b * s, d), layer, k_exp, first, force,
                       fp8=variant == "router_fp8")
        report = None
        if force is not None:
            moe, report = moe
        moe = moe + _shared(h.reshape(b * s, d), layer, shared,
                            variant != "shared_sum")
        return x + a + moe.reshape(b, s, d), report

    x, reports = jax.lax.scan(block, x, (blocks, sliding, forced))
    x = _layer_norm(x, params["final_norm"], eps)
    return x if forced is None else (x, reports)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None,
           variant: Optional[str] = None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only; the head is the token table.  With ``forced``
    (:func:`hidden_states`): ``(logits, agreement)``, ``agreement`` =
    ``{"experts": share of the own chosen experts that the forced sets
    hold, "expert_gap"}`` over all layers (:func:`_experts`)."""
    tokens = jnp.asarray(tokens)
    scale = float(config.get("logit_scale", 1.0))

    def run(params, tokens, forced):
        x = hidden_states(config, params, tokens, forced, variant)
        if forced is not None:
            x, report = x
        if at is not None:
            x = x[:, jnp.asarray(at)]
        out = scale * (x @ _f32(params["embed"]).T)
        return out if forced is None else (out, report)

    with jax.default_matmul_precision("highest"):
        out = jax.jit(run)(params, tokens, forced)
    if forced is None:
        return out
    out, (agree, gap) = out
    k = config["num_experts_per_tok"]
    return out, {
        "experts": float(agree.sum()) / (tokens.size * k * agree.shape[0]),
        "expert_gap": float(gap.max())}


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    """Mean next-token cross entropy of ``tokens[:, :-1] -> tokens[:, 1:]``
    in float32."""
    tokens = jnp.asarray(tokens)
    scale = float(config.get("logit_scale", 1.0))

    def run(params, tokens):
        lg = scale * (hidden_states(config, params, tokens[:, :-1])
                      @ _f32(params["embed"]).T)
        picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)
