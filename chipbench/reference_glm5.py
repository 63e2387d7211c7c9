"""The plain reference of ``family: glm_dsa``: GLM-5's language model
(``zai-org/GLM-5``, ``glm_moe_dsa``) AND its multi-token-prediction module in
float32 ``jax.numpy`` — no kernels, no cache, no paging, no grouped matmul,
no window of two, full-precision matmuls
(``jax.default_matmul_precision("highest")``) — with its latent attention in
the EXPANDED form only (every head's keys and values written out from the
latent), so that the comparison holds the program's ABSORBED reads, its
selection kernels, its verify window and its module's cache rows to the
definition.  With ``h = RMS(x)`` (eps ``rms_norm_eps``), ``p`` a token's
position, ``g`` one of the 64 heads and ``d = hidden_size``:

    a trunk layer (all 78 the same kind; ``depth`` of them built)
      c_q           = RMS_q(h W_qa)                     [q_lora_rank]
      [qn_g | qr_g] = c_q W_qb,g                        [qk_nope | qk_rope]
      [c' | k']     = h W_kva                           [kv_lora_rank | qk_rope]
      c = RMS_kv(c');  k_r = rope(k', p);  qr_g = rope(qr_g, p)
                                  pairs (2i, 2i + 1), theta (rope_interleave)
      [kn_g | v_g]  = c W_kvb,g                         [qk_nope | v_head_dim]
      score_g(i, j) = (qn_g,i . kn_g,j + qr_g,i . k_r,j) (qk_nope + qk_rope)^-0.5
      a = concat_g(softmax_{j in K(i)}(score_g) v_g) W_o      (no gate)
      qI_i = c_q,i W_iq     [index_n_heads, index_head_dim]
      kI_j = LayerNorm(h_j W_ik)                        one head
      the first qk_rope values of qI and kI rotated, pairs (2i, 2i + 1)
                                              (indexer_rope_interleave)
      w_i  = h_i W_iw index_n_heads^-0.5 index_head_dim^-0.5
      I(i, j) = sum_n w_i,n relu(qI_i,n . kI_j)
      K(i) = the index_topk keys j <= i of largest I (ties: the lower j;
             ``lax.top_k``), every j <= i where there are no more
    x1 = x + a;   y = RMS(x1)
    a dense layer:  m = (silu(y W1) * (y W3)) W2
    else  r = sigmoid(y W_r) over ALL published experts (float32)
          S = top-k of r + bias (ties: the lower id);  w_e = r_e / sum_S r
          m = routed_scaling_factor sum_{e in S, e HELD} w_e E_e(y) + Sh(y)
    x' = x1 + m;    hid = RMS_f(x_L);    logits = hid W_head      (untied)

    the module (num_nextn_predict_layers = 1), position t, next token x_{t+1}
      u_t = [RMS_e(Emb(x_{t+1})) ; RMS_h(hid_t)] W_eh            (2d -> d)
      one block of the routed kind above on u (its own weights; position t)
      logits^mtp_t = RMS_s(block(u)_t) W_head       a distribution for x_{t+2}

**Departures from the published description**, each at its line: ``hid_t``
is taken AFTER the trunk's final norm (the family's serving code hands the
module the model's output; DeepSeek-V3's paper draws it before the head's
norm); the module's input order is ``[embedding ; hidden]``; the correction
bias is a seeded parameter that chooses and does not weigh.

**The share** is ``reference_mistral4``'s: the pytree holds the experts
``experts_first ..`` of each routed layer, the router is whole, ``m`` sums
the HELD chosen experts plus the shared expert; the vocabulary is whatever
slice of the token table and of the head the pytree holds.

It reads the PROGRAM's parameter pytree (``models/glm_dsa.py``:
``blocks["latent_indexed" | "dense" | "moe"]`` stacks and ``mtp``) and shares
no code with it.  One sequence at a time; a layer's heads ``HEAD_GROUP`` at a
time and its queries ``QUERY_BLOCK`` at a time, a layer's selection held as
ONE ``[S, S]`` mask — so that 10,240 positions fit beside an engine.

``forced`` (``{"experts": int32 [routed layers + 1, B, S, k], "keys": uint8
[layers + 1, B, S, ceil(S / 8)]}``, the module's layer last): another side's
discrete choices, taken in place of the own ones while the own are still
made (``reference_keye``'s treatment of near-ties): the result then carries
how far the two sides agree.

``variant`` (the comparison's own check that it can tell a shortcut from the
model): ``"latent_fp8"`` what would be cached (``c``, ``k_r``, ``kI``; trunk
and module) rounded to float8 e4m3 — the nearest precision below the served
one; ``"mtp_no_rows"`` the module attends its own position alone (a module
drafting without cache rows of its own); ``"second_takes_first"`` a query at
one of the positions ``seconds`` takes the key set of the query BEFORE it
(a verify window whose second position selects over the first one's set).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

#: queries attended at a time, heads expanded at a time
QUERY_BLOCK = 64
HEAD_GROUP = 16
VARIANTS = (None, "latent_fp8", "mtp_no_rows", "second_takes_first")
KIND = "latent_indexed"


def _f32(x):
    return x.astype(jnp.float32)


def _fp8(x):
    return _f32(x.astype(jnp.float8_e4m3fn))


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def dense_layers(config: Dict[str, Any]) -> int:
    """The leading dense layers BUILT (``dense_depth``; the published
    ``first_k_dense_replace`` where the cut names none)."""
    return int(config.get("dense_depth", config["first_k_dense_replace"]))


def _rotate_pairs(x, positions, theta: float):
    """x ``[..., S, dim]``: pair ``(2i, 2i + 1)`` turned by ``p theta^(-2i /
    dim)``."""
    dim = x.shape[-1]
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = _f32(positions)[:, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _selection(config, layer, h, cq, cached, forced, seconds):
    """A layer's keys over one sequence ``h [S, d]``: bool ``[S + pad, S]``
    (query, key), and with ``forced`` (uint8 ``[S, ceil(S / 8)]``) that
    side's sets in place of the own, beside the report ``(own keys of each
    query past index_topk [S + pad], those of them in the forced sets, the
    query's largest distance of a disagreeing key's score from the own
    cut-off as a share of its largest score)``.  ``seconds`` (bool ``[S +
    pad]`` or None): queries whose OWN set is the one of the query before
    (``"second_takes_first"``)."""
    s = h.shape[0]
    hi, di = config["index_n_heads"], config["index_head_dim"]
    topk, r = config["index_topk"], config["qk_rope_head_dim"]
    theta = float(config["rope_parameters"]["rope_theta"])
    positions = jnp.arange(s)
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb

    def turned(x, pos):
        # (the first ``qk_rope_head_dim`` values, interleaved pairs:
        # ``indexer_rope_interleave`` true)
        return jnp.concatenate([_rotate_pairs(x[..., :r], pos, theta),
                                x[..., r:]], axis=-1)

    k = h @ _f32(layer["idx_k_w"])
    mu = k.mean(-1, keepdims=True)
    k = (k - mu) / jnp.sqrt(((k - mu) ** 2).mean(-1, keepdims=True) + 1e-6) \
        * _f32(layer["idx_k_norm"][0]) + _f32(layer["idx_k_norm"][1])
    k = cached(turned(k, positions))                            # [S, DI]
    cq = jnp.pad(cq, ((0, pad), (0, 0)))
    wi = jnp.pad(h @ _f32(layer["idx_w_w"]), ((0, pad), (0, 0))) \
        * (hi ** -0.5 * di ** -0.5)
    if forced is not None:
        forced = jnp.pad(forced, ((0, pad), (0, 0)))

    def index_of(at):
        """The block's queries' index scores ``[Q, S]`` and what they see."""
        pos = at + jnp.arange(qb)
        visible = positions[None, :] <= pos[:, None]              # [Q, S]
        q = jax.lax.dynamic_slice_in_dim(cq, at, qb) @ _f32(layer["idx_q_w"])
        q = turned(q.reshape(qb, hi, di).transpose(1, 0, 2), pos)  # [HI,Q,DI]
        dots = jax.nn.relu(jnp.einsum("nqd,sd->nqs", q, k))
        index = jnp.einsum("qn,nqs->qs",
                           jax.lax.dynamic_slice_in_dim(wi, at, qb), dots)
        return jnp.where(index == 0.0, 0.0, index), visible, pos  # -0.0 = 0.0

    def own(i):
        index, visible, _ = index_of(i * qb)
        _, chosen = jax.lax.top_k(jnp.where(visible, index, -jnp.inf),
                                  min(topk, s))
        return visible & jnp.zeros((qb, s), bool).at[
            jnp.arange(qb)[:, None], chosen].set(True)

    blocks = jnp.arange((s + pad) // qb)
    keep = jax.lax.map(own, blocks).reshape(s + pad, s)
    if seconds is not None:
        # the shortcut: the set of the query before, as it is
        keep = jnp.where(seconds[:, None], jnp.roll(keep, 1, axis=0), keep)
    if forced is None:
        return keep, None

    def against(i):
        """The block's own sets beside the forced ones (the scores made
        again: no ``[S, S]`` float array is held)."""
        at = i * qb
        index, visible, pos = index_of(at)
        mine = jax.lax.dynamic_slice_in_dim(keep, at, qb)
        bits = jax.lax.dynamic_slice_in_dim(forced, at, qb)
        theirs = visible & jnp.unpackbits(bits, axis=-1)[:, :s].astype(bool)
        # a pad query of the last block sees every key: selects, counts
        past = ((pos >= topk) & (pos < s))[:, None]
        cut = jnp.min(jnp.where(mine, index, jnp.inf), -1, keepdims=True)
        scale = jnp.max(jnp.where(visible, jnp.abs(index), 0.0), -1,
                        keepdims=True)
        gap = jnp.where(past & (mine != theirs), jnp.abs(index - cut), 0.0) \
            / jnp.maximum(scale, 1e-30)
        return theirs, (past & mine).sum(-1), (past & mine & theirs).sum(-1), \
            gap.max(-1)

    theirs, total, agree, gap = jax.lax.map(against, blocks)
    return theirs.reshape(s + pad, s), (
        total.reshape(-1), agree.reshape(-1), gap.reshape(-1))


def _attend(q, k, v, scale, rows):
    """One head group over one sequence: ``q`` / ``k [G, S, dk]``, ``v [G,
    S, dv]`` -> ``[G, S, dv]``, ``QUERY_BLOCK`` queries at a time; ``rows``
    (bool ``[S + pad, S]``): the keys each query keeps, or None: every ``j
    <= p``."""
    g, s, _ = q.shape
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q = jnp.pad(q * scale, ((0, 0), (0, pad), (0, 0)))
    key = jnp.arange(s)[None, :]

    def block(i):
        at = i * qb
        qq = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=1)
        keep = jax.lax.dynamic_slice_in_dim(rows, at, qb) \
            if rows is not None else key <= (at + jnp.arange(qb))[:, None]
        att = jnp.einsum("hqd,hsd->hqs", qq, k)
        probs = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,hsd->hqd", probs, v)

    out = jax.lax.map(block, jnp.arange((s + pad) // qb))     # [N, G, Q, dv]
    return jnp.moveaxis(out, 0, 1).reshape(g, s + pad, -1)[:, :s]


def _attention(config, layer, h, variant, forced, seconds, alone=False):
    """One layer's attention over one sequence ``h [S, d]`` -> ``([S, d],
    the selection's report or None, what is cached a position [S, 576])``
    (module docstring).  ``alone``: every query keeps its own position only
    (``"mtp_no_rows"``)."""
    s, d = h.shape
    eps, heads = config["rms_norm_eps"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, rank = config["v_head_dim"], config["kv_lora_rank"]
    q_rank = config["q_lora_rank"]
    theta = float(config["rope_parameters"]["rope_theta"])
    cached = _fp8 if variant == "latent_fp8" else (lambda a: a)
    positions = jnp.arange(s)

    cq = _rms(h @ _f32(layer["q_a_w"]), layer["q_a_norm"], eps)
    kv = h @ _f32(layer["kv_a_w"])
    c = cached(_rms(kv[:, :rank], layer["kv_a_norm"], eps))
    kr = cached(_rotate_pairs(kv[:, rank:], positions, theta))

    rows = report = None
    if alone:
        qb = min(QUERY_BLOCK, s)
        rows = jnp.pad(jnp.eye(s, dtype=bool), ((0, -s % qb), (0, 0)))
    elif s > config["index_topk"]:
        rows, report = _selection(config, layer, h, cq, cached, forced,
                                  seconds)

    group = min(HEAD_GROUP, heads)
    assert heads % group == 0, (heads, group)
    w_qb = layer["q_b_w"].reshape(q_rank, heads, nope + rope)
    w_kvb = layer["kv_b_w"].reshape(rank, heads, nope + vd)
    w_o = layer["o_w"].reshape(heads, vd, d)
    scale = 1.0 / math.sqrt(nope + rope)

    def one(i, acc):
        at = i * group
        q = jnp.einsum("sr,rhd->hsd", cq, _f32(
            jax.lax.dynamic_slice_in_dim(w_qb, at, group, axis=1)))
        kvb = jnp.einsum("sc,chd->hsd", c, _f32(
            jax.lax.dynamic_slice_in_dim(w_kvb, at, group, axis=1)))
        q = jnp.concatenate(
            [q[..., :nope], _rotate_pairs(q[..., nope:], positions, theta)],
            axis=-1)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(kr[None], (group, s, rope))],
            axis=-1)
        out = _attend(q, k, kvb[..., nope:], scale, rows)
        return acc + jnp.einsum("hsv,hvd->sd", out, _f32(
            jax.lax.dynamic_slice_in_dim(w_o, at, group, axis=0)))

    out = jax.lax.fori_loop(0, heads // group, one,
                            jnp.zeros((s, d), jnp.float32))
    return out, report, jnp.concatenate([c, kr], axis=-1)


def _experts(config, y, moe, number: int, forced=None):
    """The routed experts of routed layer ``number`` of the stacks ``moe``
    over ``y [N, D]``: the sigmoid router over ALL experts, the top-k of
    ``score + bias`` (the bias chooses and does not weigh), a dense loop over
    the HELD ones weighted inside the set — or inside ``forced`` (int32 ``[N,
    k]``), which also returns ``reference_mistral4._experts``'s report — plus
    the shared expert."""
    k, first = config["num_experts_per_tok"], config.get("experts_first", 0)
    gate_w = _f32(moe["gate_w"][number])
    n_experts, held = gate_w.shape[-1], moe["experts_w1"].shape[1]
    score = jax.nn.sigmoid(y @ gate_w)                             # [N, E]
    biased = score + _f32(moe["gate_bias"][number])
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
    if config["norm_topk_prob"]:
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight * config["routed_scaling_factor"]

    def one(e, acc):
        w1, w3, w2 = (_f32(jax.lax.dynamic_slice(
            moe[name], (number, e, 0, 0),
            (1, 1) + moe[name].shape[2:])[0, 0])
            for name in ("experts_w1", "experts_w3", "experts_w2"))
        out = (jax.nn.silu(y @ w1) * (y @ w3)) @ w2
        return acc + out * jax.lax.dynamic_slice_in_dim(
            weight, first + e, 1, axis=1)

    out = jax.lax.fori_loop(0, held, one, jnp.zeros_like(y))
    out = out + (jax.nn.silu(y @ _f32(moe["shared_w1"][number]))
                 * (y @ _f32(moe["shared_w3"][number]))) \
        @ _f32(moe["shared_w2"][number])
    return out if forced is None else (out, report)


def _at(stack, i):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


def _check(config, variant):
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    if config["n_shared_experts"] != 1 or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" or config["n_group"] != 1 \
            or config["rope_parameters"]["rope_type"] != "default" \
            or config["tie_word_embeddings"] or config["moe_layer_freq"] != 1 \
            or config["attention_bias"] or config["hidden_act"] != "silu" \
            or not config["rope_interleave"] \
            or not config["indexer_rope_interleave"] \
            or config["num_nextn_predict_layers"] != 1:
        raise ValueError("the reference follows the published block: one "
                         "shared expert, a sigmoid router with a correction "
                         "bias and one group, plain interleaved rotary in "
                         "attention and indexer, every layer past the dense "
                         "ones routed, no biases, an untied head, one module")


def _block(config, x, layer, ffn, variant, forced_keys, forced_experts,
           seconds, alone=False):
    """One block over ``x [B, S, d]`` (``ffn``: ``("dense", stack, i)`` or
    ``("moe", stacks, i)``) -> ``(x', the routed report or None, the
    selection's reports a row, what is cached [B, S, 576])``."""
    eps = config["rms_norm_eps"]
    b, s, _ = x.shape
    h = _rms(x, layer["attn_norm"], eps)
    rows, selected, cached = [], [], []
    for i in range(b):
        a, report, kept = _attention(
            config, layer, h[i], variant,
            None if forced_keys is None else forced_keys[i], seconds, alone)
        rows.append(a)
        cached.append(kept)
        if report is not None:
            selected.append(report)
    x = x + jnp.stack(rows)
    y = _rms(x, layer["mlp_norm"], eps).reshape(b * s, -1)
    kind, stack, i = ffn
    routed = None
    if kind == "dense":
        lyr = _at(stack, i)
        out = (jax.nn.silu(y @ _f32(lyr["w1"])) * (y @ _f32(lyr["w3"]))) \
            @ _f32(lyr["w2"])
    else:
        out = _experts(config, y, stack, i,
                       None if forced_experts is None
                       else forced_experts.reshape(b * s, -1))
        if forced_experts is not None:
            out, routed = out
    return x + out.reshape(x.shape), routed, selected, jnp.stack(cached)


def forward(config: Dict[str, Any], params: Any, tokens, forced=None,
            variant: Optional[str] = None,
            seconds: Sequence[int] = (), after=None):
    """The trunk over ``tokens [B, S]`` and the module over its first ``S -
    1`` positions (position ``t`` with the next token ``tokens[t + 1]``) —
    over all ``S`` with ``after`` (int ``[B]``: the token that follows each
    sequence): ``{"hidden": the trunk's final-normed states [B, S, d],
    "module": the module's closing-normed states [B, S - 1 or S, d], "rows":
    what the module caches [B, S - 1 or S, 576]}`` in float32, the sequences
    one after the other;
    with ``forced`` also ``"reports": (the routed layers' reports stacked,
    the selecting layers' reports stacked [layers + 1, B, 3, S + pad])``,
    the module's layer last."""
    _check(config, variant)
    eps, dense = config["rms_norm_eps"], dense_layers(config)
    blocks = params["blocks"]
    b, s = tokens.shape
    mark = None
    if variant == "second_takes_first":
        qb = min(QUERY_BLOCK, s)
        mark = jnp.zeros((s + -s % qb,), bool).at[
            jnp.asarray(list(seconds), jnp.int32)].set(True)
    x = _f32(params["embed"][tokens])
    routed, selected = [], []

    def take(number, report, picked):
        if report is not None:
            routed.append(report)
        if picked:
            selected.append(tuple(jnp.stack(r) for r in zip(*picked)))

    for number in range(config["depth"]):
        layer = _at(blocks[KIND], number)
        ffn = ("dense", blocks["dense"], number) if number < dense \
            else ("moe", blocks["moe"], number - dense)
        x, report, picked, _ = _block(
            config, x, layer, ffn, variant,
            None if forced is None else forced["keys"][number],
            None if forced is None or number < dense
            else forced["experts"][number - dense], mark)
        take(number, report, picked)
    hidden = _rms(x, params["final_norm"], eps)
    # the module: position t from hid_t and the NEXT token (departure: hid_t
    # after the trunk's final norm; input order [embedding ; hidden])
    m = params["mtp"]
    nxt = tokens[:, 1:] if after is None else jnp.concatenate(
        [tokens[:, 1:], jnp.asarray(after, tokens.dtype)[:, None]], axis=1)
    sm = nxt.shape[1]
    u = jnp.concatenate(
        [_rms(_f32(params["embed"][nxt]), m["enorm"], eps),
         _rms(hidden[:, :sm], m["hnorm"], eps)], axis=-1) @ _f32(m["eh_w"])
    mark1 = None if mark is None else mark[:sm + -sm % min(QUERY_BLOCK, sm)]
    x, report, picked, rows = _block(
        config, u, _at(m["blocks"][KIND], 0), ("moe", m["blocks"]["moe"], 0),
        variant,
        None if forced is None else forced["keys"][-1][:, :sm,
                                                       :-(-sm // 8)],
        None if forced is None else forced["experts"][-1][:, :sm],
        mark1, alone=variant == "mtp_no_rows")
    out = {"hidden": hidden, "module": _rms(x, m["final_norm"], eps),
           "rows": rows}
    if forced is not None:
        routed.append(report)
        # (the module's reports cover S - 1 positions: padded to the
        # trunk's length with queries that chose nothing)
        width = selected[0][0].shape[-1] if selected else 0
        if picked:
            mine = tuple(jnp.stack(r) for r in zip(*picked))
            selected.append(tuple(
                jnp.pad(r, ((0, 0), (0, width - r.shape[-1])))
                for r in mine))
        out["reports"] = (
            tuple(jnp.stack(r) for r in zip(*routed)),
            tuple(jnp.stack(r) for r in zip(*selected)) if selected
            else None)
    return out


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None,
           variant: Optional[str] = None, seconds: Sequence[int] = (),
           rows_at: Sequence[int] = (), after=None):
    """Float32 logits through the untied head: ``{"trunk": [B, len(at), V]
    at the listed positions (every one of the ``S`` without ``at``),
    "module": the module's at the same positions (every one of the first
    ``S - 1`` without ``at``; a listed position is below ``S - 1``, or
    ``after`` names the token behind each sequence), "rows":
    what the module caches at the positions ``rows_at`` [B, len, 576]}``.
    With ``forced`` (:func:`forward`): ``(that, agreement)``, ``agreement``
    = ``{"keys": share of the own chosen keys (queries past ``index_topk``)
    that the forced sets hold, "keys_second": the same over the queries
    ``seconds`` alone, "key_gap": the largest distance of a disagreeing key
    from the own cut-off, "experts" / "expert_gap" / "expert_gap_max" /
    "expert_gap_max_by_layer"``: ``reference_mistral4.logits``'s}``."""
    tokens = jnp.asarray(tokens)
    seconds = tuple(int(p) for p in seconds)

    def run(params, tokens, forced):
        out = forward(config, params, tokens, forced, variant, seconds,
                      after)
        head = _f32(params["lm_head"])
        trunk, module = out["hidden"], out["module"]
        if at is not None:
            pick = jnp.asarray(at)
            trunk, module = trunk[:, pick], module[:, pick]
        got = {"trunk": trunk @ head, "module": module @ head,
               "rows": out["rows"][:, jnp.asarray(list(rows_at), jnp.int32)]}
        return got if forced is None else (got, out["reports"])

    with jax.default_matmul_precision("highest"):
        out = jax.jit(run)(params, tokens, forced)
    if forced is None:
        return out
    out, ((agree, gap, total, apart), selected) = out
    k = config["num_experts_per_tok"]
    # (the module's layer routes S - 1 positions of each sequence, all S
    # with ``after``)
    routed_pairs = tokens.size * k * (agree.shape[0] - 1) \
        + (tokens.size - (after is None) * tokens.shape[0]) * k
    agreement = {
        "experts": float(agree.sum()) / routed_pairs,
        "expert_gap": float(total.sum()) / max(1.0, float(apart.sum())),
        "expert_gap_max": float(gap.max()),
        "expert_gap_max_by_layer": [round(float(g), 5) for g in gap],
        "keys": 1.0, "keys_second": 1.0, "key_gap": 0.0}
    if selected is not None:
        key_total, key_agree, key_gap = selected       # [layers, B, S + pad]
        agreement.update(
            keys=float(key_agree.sum()) / max(1.0, float(key_total.sum())),
            key_gap=float(key_gap.max()))
        if seconds:
            pick = jnp.asarray(seconds)
            agreement["keys_second"] = float(key_agree[..., pick].sum()) \
                / max(1.0, float(key_total[..., pick].sum()))
    return out, agreement


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    """Mean next-token cross entropy of ``tokens[:, :-1] -> tokens[:, 1:]``
    through the TRUNK, in float32."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens):
        lg = forward(config, params, tokens[:, :-1])["hidden"] \
            @ _f32(params["lm_head"])
        picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)
