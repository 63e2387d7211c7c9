"""The plain reference of ``family: smallthinker``: PowerInfer's
SmallThinker-21BA3B-Instruct in float32 ``jax.numpy`` — no kernels, no
remat, no grouped matmul, no flash attention, full-precision matmuls
(``jax.default_matmul_precision("highest")``) — with its TRAINING loss.
``RMS`` is an RMSNorm (a scale, no bias, eps ``rms_norm_eps``), ``l`` the
layer's index, ``p`` a token's position:

    h       = RMS_1(x)
    q, k, v = h Wq [H x hd], h Wk [HKV x hd], h Wv [HKV x hd]    (no bias,
                                                                no q/k-norm)
    rope_layout[l] = 1 (sliding): q, k rotated, pairs (i, i + hd / 2), theta
             ``rope_theta``, over the whole head; query i sees keys j with
             0 <= i - j < sliding_window_size
    rope_layout[l] = 0 (full): no rotation; query i sees keys j <= i
    a  = softmax(q k^T / sqrt(hd)) v Wo             (GQA: H / HKV queries a key)
    x1 = x + a ;  y = RMS_2(x1)
    s  = softmax(h Wr) over ALL published experts       <- the router reads h,
                                             the ATTENTION's normed input
    S  = top-k of s (ties: the lower id);  w_e = s_e / sum_{e' in S} s_e'
    m  = sum_{e in S, e HELD} w_e (relu(y W1_e) * (y W3_e)) W2_e
    x' = x1 + m ;      logits = RMS_f(x_L) W_head                  (untied)
    loss = mean_t nll_t + c_aux * mean_l (E * sum_e f_l,e P_l,e)
           f: the share of the k T pairs routed to e;  P: the mean over the
           tokens of s_e  — over ALL E experts, one sequence at a time (as
           a micro-batch of one row gives the program)

**The share.**  The pytree holds the experts ``experts_first ..
experts_first + count - 1`` of each layer (``count`` = the leaves' expert
axis).  The router is whole — scores, top-k and the balance term over all
published experts, the weights normalised over all ``k`` chosen — and ``m``
sums the HELD chosen experts only: the partial result this chip would send
into its group's exchange, which is also what goes on to the next layer, as
in the program (no stand-in for the absent chips).  The vocabulary is
whatever slice the token table and the head hold.

It reads the PROGRAM's parameter pytree (``models/mixtral.py``:
``attn_norm``, ``q_w`` .. ``o_w``, ``mlp_norm``, ``gate_w [d, E]``,
``experts_w1 / w3 [count, d, f]``, ``experts_w2 [count, f, d]``,
``final_norm``, ``embed``, ``lm_head [d, V]``) so the same seeded weights
feed both sides, and shares no code with it.  Queries are attended
``QUERY_BLOCK`` at a time, one sequence at a time, and the head is taken
``HEAD_BLOCK`` positions at a time, so that 8,192 positions fit beside a
training engine's state (the held experts' loop ``TOKEN_BLOCK`` tokens at
a time for the same reason).  The layers run under one ``lax.scan`` with the
layer's kind as a flag (a static slice of the stacked weights is a copy of
each layer's on a TPU: PERF.md section 6, PR 34).

What the published configuration leaves open, and what is taken here (the
configuration file lists the same under ``assumed``): the router reads the
NORMED attention input (the model card says "before attention"); the
experts multiply ``RMS_2(x1)``; a sliding query sees exactly
``sliding_window_size`` keys, itself included; the balance loss is the
Switch form over the full softmax at ``c_aux`` 0.001 (no coefficient is
published); float32 router, ties to the lower id.

``variant`` (the comparison's own check that it can tell a shortcut from
the model, PERF.md section 6): ``"all_keys"`` lets the sliding layers see
every key, ``"rope_full"`` rotates the full layers too, ``"router_after"``
feeds the router ``y`` (after attention), ``"silu"`` gates with silu for
relu, ``"no_renorm"`` keeps the six softmax weights as they are,
``"router_fp8"`` rounds the router's input to float8 e4m3, ``"fp8"`` rounds
every weight to float8 e5m2 (the whole model's weights in the nearest
precision below the configuration's bf16).

:func:`loss_and_grad` is the same loss differentiated with respect to every
leaf (``jax.checkpoint`` around a sequence's block, a query block and a
head block: what a backward pass keeps, not what it computes).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

#: queries attended at a time (a 28 x 512 x 8,192 float32 score block is
#: 470 MB), positions the head is multiplied with at a time, and tokens the
#: experts' loop runs over at a time (what the gradient's backward pass keeps
#: of each is a block's: at 8,192 tokens a described-v5e compile assigns the
#: whole gradient 3.7 GiB of temporaries, PR 47)
QUERY_BLOCK = 512
HEAD_BLOCK = 1024
TOKEN_BLOCK = 1024
VARIANTS = (None, "all_keys", "rope_full", "router_after", "silu",
            "no_renorm", "router_fp8", "fp8")
#: the balance loss's coefficient: assumed (the configuration's ``assumed``)
C_AUX = 0.001


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rotate_half(x, theta: float):
    """x ``[H, S, hd]`` turned at positions ``0 .. S-1``: pair ``(i, i + hd /
    2)`` by ``position * theta^(-2i / hd)``."""
    s, hd = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq       # [S, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer_kinds(config: Dict[str, Any], layers: int) -> Sequence[str]:
    """``"sliding"`` | ``"full"`` of the first ``layers`` layers, from the
    published ``sliding_window_layout`` (1: a window)."""
    return ["sliding" if flag else "full"
            for flag in config["sliding_window_layout"][:layers]]


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

    out = jax.lax.map(jax.checkpoint(block),
                      jnp.arange((s + pad) // qb))          # [N, G, R, Q, hd]
    return jnp.moveaxis(out, 0, 2).reshape(hkv, rep, s + pad, hd)[:, :, :s]


def _experts(y, r, layer, k: int, first: int, forced, variant):
    """The routed experts over one sequence ``y [S, D]``, the router fed
    ``r [S, D]``: the router over ALL experts, a dense loop over the HELD
    ones (ids ``first ..``), each upcast alone, weighted by the router
    inside the top-k set — or inside ``forced`` (int32 ``[S, k]``: another
    side's sets).  -> ``(out, stats)``, ``stats`` = (the balance term, the
    pairs the OWN sets route to held experts, own chosen experts that the
    forced sets hold, the largest distance of a disagreeing expert's score from the own
    k-th as a share of the token's largest) — the last two 0 unforced."""
    n_experts, held = layer["gate_w"].shape[-1], layer["experts_w1"].shape[0]
    if variant == "router_fp8":
        r = _f32(r.astype(jnp.float8_e4m3fn))
    score = jax.nn.softmax(r @ _f32(layer["gate_w"]), axis=-1)   # [S, E]
    top_s, top_e = jax.lax.top_k(score, k)
    own = chosen = jax.nn.one_hot(top_e, n_experts,
                                  dtype=jnp.float32).sum(-2)
    agree = gap = jnp.zeros((), jnp.float32)
    if forced is not None:
        chosen = jax.nn.one_hot(forced, n_experts, dtype=jnp.float32).sum(-2)
        gap = (jnp.where(own != chosen, jnp.abs(score - top_s[:, -1:]), 0.0)
               / top_s[:, :1]).max()
        agree = (own * chosen).sum()
    weight = score * chosen
    if variant != "no_renorm":
        weight = weight / weight.sum(-1, keepdims=True)  # over all k chosen
    act = jax.nn.silu if variant == "silu" else jax.nn.relu

    def tokens(args):                   # TOKEN_BLOCK tokens at a time
        yb, wb = args

        def one(e, acc):
            w1, w3, w2 = (_f32(jax.lax.dynamic_index_in_dim(
                layer[name], e, keepdims=False))
                for name in ("experts_w1", "experts_w3", "experts_w2"))
            out = (act(yb @ w1) * (yb @ w3)) @ w2
            return acc + out * jax.lax.dynamic_slice_in_dim(
                wb, first + e, 1, axis=1)

        return jax.lax.fori_loop(0, held, one, jnp.zeros_like(yb))

    tb = min(TOKEN_BLOCK, y.shape[0])
    pad = -y.shape[0] % tb
    out = jax.lax.map(jax.checkpoint(tokens), tuple(
        jnp.pad(a, ((0, pad), (0, 0))).reshape(-1, tb, a.shape[-1])
        for a in (y, weight)))
    out = out.reshape(-1, y.shape[-1])[:y.shape[0]]
    share = chosen.sum(0) / (y.shape[0] * k)          # f_e, over all E
    balance = n_experts * (share * score.mean(0)).sum()
    here = jax.lax.dynamic_slice_in_dim(own, first, held, axis=1).sum()
    return out, jnp.stack([balance, here, agree, gap])


def hidden_states(config: Dict[str, Any], params: Any, tokens, forced=None,
                  variant: Optional[str] = None):
    """-> ``(x, stats)``: the final RMSNorm'd hidden states ``[B, S, D]`` in
    float32 and float32 ``[L, B, 4]`` (:func:`_experts`' stats a layer, a
    sequence).  ``forced`` (int32 ``[L, B, S, k]``): another side's expert
    sets, taken in place of the own ones."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    theta, window = float(config["rope_theta"]), config["sliding_window_size"]
    k_exp = config["moe_num_active_primary_experts"]
    first = config.get("experts_first", 0)
    blocks = params["blocks"]
    n_layers = blocks["q_w"].shape[0]
    sliding = jnp.asarray([k == "sliding"
                           for k in layer_kinds(config, n_layers)])
    b, s = tokens.shape
    x = _f32(params["embed"][tokens])

    def split(t, n):
        return t.reshape(s, n, hd).transpose(1, 0, 2)            # [n, S, hd]

    def block(x, per_layer):
        layer, is_sliding, force = per_layer
        rotated = is_sliding | (variant == "rope_full")
        reach = jnp.where(is_sliding & (variant != "all_keys"), window, s + 1)

        def row(args):                       # one sequence at a time
            xr, fr = args
            h = _rms(xr, layer["attn_norm"], eps)
            q, kk, v = (split(h @ _f32(layer[name]), n)
                        for name, n in (("q_w", heads), ("k_w", kv),
                                        ("v_w", kv)))
            q = jnp.where(rotated, _rotate_half(q, theta), q)
            kk = jnp.where(rotated, _rotate_half(kk, theta), kk)
            a = _attention(q.reshape(kv, heads // kv, s, hd), kk, v, reach)
            x1 = xr + a.reshape(heads, s, hd).transpose(1, 0, 2) \
                .reshape(s, heads * hd) @ _f32(layer["o_w"])
            y = _rms(x1, layer["mlp_norm"], eps)
            moe, stats = _experts(y, y if variant == "router_after" else h,
                                  layer, k_exp, first, fr, variant)
            return x1 + moe, stats

        return jax.lax.map(jax.checkpoint(row), (x, force))

    x, stats = jax.lax.scan(block, x, (blocks, sliding, forced))
    return _rms(x, params["final_norm"], eps), stats


def _weights(params, variant):
    """The weights a variant computes with.  ``"fp8"``: every weight in
    float8 e5m2 (2 mantissa bits; ``reduce_precision``, which no compiler
    pass may drop — a convert there and back was dropped on the TPU — and
    which flushes what float8 holds as a subnormal), the gradient handed
    straight through to the weight it was rounded from."""
    if variant != "fp8":
        return params
    return jax.tree_util.tree_map(
        lambda a: a + jax.lax.stop_gradient(
            jax.lax.reduce_precision(a, 5, 2) - a), params)


def _report(config, stats, tokens) -> Dict[str, float]:
    """What a comparison reads of ``stats [L, B, 4]``."""
    k = config["moe_num_active_primary_experts"]
    return {"experts": float(stats[..., 2].sum())
            / (tokens.size * k * stats.shape[0]),
            "expert_gap": float(stats[..., 3].max()),
            "expert_rows": float(stats[..., 1].sum()),
            "router_aux": float(stats[..., 0].mean())}


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None,
           variant: Optional[str] = None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only.  With ``forced`` (``{"experts": int32 [L, B, S, k]}``):
    ``(logits, report)``, ``report`` = ``{"experts": share of the own chosen
    experts that the forced sets hold, "expert_gap", "expert_rows": pairs its
    OWN sets put on held experts, "router_aux"}`` over all layers."""
    tokens = jnp.asarray(tokens)
    sets = None if forced is None else jnp.asarray(forced["experts"])

    def run(params, tokens, sets):
        params = _weights(params, variant)
        x, stats = hidden_states(config, params, tokens, sets, variant)
        if at is not None:
            x = x[:, jnp.asarray(at)]
        return x @ _f32(params["lm_head"]), stats

    with jax.default_matmul_precision("highest"):
        out, stats = jax.jit(run)(params, tokens, sets)
    return out if forced is None else (out, _report(config, stats, tokens))


def _loss(config, params, tokens, sets, variant):
    """-> ``(loss, (lm, stats))``."""
    params = _weights(params, variant)
    x, stats = hidden_states(config, params, tokens[:, :-1], sets, variant)
    b, s, d = x.shape
    hb = min(HEAD_BLOCK, b * s)
    pad = -(b * s) % hb
    xs = jnp.pad(x.reshape(b * s, d), ((0, pad), (0, 0)))
    want = jnp.pad(tokens[:, 1:].reshape(b * s), (0, pad))
    head = _f32(params["lm_head"])

    def nll(args):
        xc, tc = args
        lg = xc @ head
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, tc[:, None], axis=-1)[:, 0]

    each = jax.lax.map(jax.checkpoint(nll), (xs.reshape(-1, hb, d),
                                             want.reshape(-1, hb)))
    lm = each.reshape(-1)[:b * s].mean()
    return lm + C_AUX * stats[..., 0].mean(), (lm, stats)


def next_token_loss(config: Dict[str, Any], params: Any, tokens,
                    variant: Optional[str] = None, report: bool = False,
                    forced=None, grad: bool = False):
    """The training loss of ``tokens[:, :-1] -> tokens[:, 1:]`` in float32:
    the mean next-token cross entropy plus ``C_AUX`` x the balance term
    (the layers' mean, a sequence at a time, the sequences' mean).  With
    ``report``: ``(loss, {"lm_loss", "router_aux", "expert_rows", "experts",
    "expert_gap"})``, the last three as :func:`logits` reports them.
    ``forced``: as :func:`logits`.  ``grad`` appends the loss's gradient
    with respect to every leaf of ``params`` (the pytree's structure)."""
    tokens = jnp.asarray(tokens)
    sets = None if forced is None else jnp.asarray(forced["experts"])

    def run(params, tokens, sets):
        if grad:
            return jax.value_and_grad(
                lambda p: _loss(config, p, tokens, sets, variant),
                has_aux=True)(params)
        return _loss(config, params, tokens, sets, variant), None

    with jax.default_matmul_precision("highest"):
        (loss, (lm, stats)), grads = jax.jit(run)(params, tokens, sets)
    out = (loss,)
    if report:
        out += ({"lm_loss": float(lm),
                 **_report(config, stats, tokens[:, :-1])},)
    if grad:
        out += (grads,)
    return out if len(out) > 1 else loss
