"""The plain reference of ``family: dots3``: the language model of
dots-studio/dots3-note-prev (``dots3_note``) in float32 ``jax.numpy`` — no
kernels, no cache, no paging, no grouped matmul, full-precision matmuls
(``jax.default_matmul_precision("highest")``) — with its latent attention in
the EXPANDED form only (every head's keys and values written out from the
latent), so that the comparison holds the program's ABSORBED reads, its
selection kernels and its ring to the definition.  With ``h = RMS(x)`` (eps
``rms_norm_eps``), ``p`` a token's position, ``g`` one of a layer's heads and
``d = hidden_size``:

    every layer (its own sizes: the ``swa_*`` keys in a sliding layer)
      c_q           = s_q RMS_q(h W_qa)        s_q  = (d / q_lora_rank)^0.5
      [qn_g | qr_g] = c_q W_qb,g               [qk_nope | qk_rope]
      [c' | k']     = h W_kva                  [kv_lora_rank | qk_rope]
      c = s_kv RMS_kv(c')                      s_kv = (d / kv_lora_rank)^0.5
      k_r = rope(k', p);  qr_g = rope(qr_g, p)     pairs (2i, 2i + 1), theta
      [kn_g | v_g]  = c W_kvb,g                [qk_nope | v_head_dim]
      score_g(i, j) = (qn_g,i . kn_g,j + qr_g,i . k_r,j) (qk_nope + qk_rope)^-0.5
      a = concat_g(sigmoid(h W_g)_g softmax_j(score_g) v_g) W_o
    a full layer keeps the keys j of K(i), a sliding layer i - window < j <= i
      qI_i = c_q,i W_iq     [index_n_heads, index_head_dim]
      kI_j = LayerNorm(h_j W_ik)               one head
      the first qk_rope values of qI and kI rotated, pairs (i, i + qk_rope/2)
      w_i  = h_i W_iw index_n_heads^-0.5 index_head_dim^-0.5
      I(i, j) = sum_n w_i,n relu(qI_i,n . kI_j)
      K(i) = the index_topk keys j <= i of largest I (ties: the lower j;
             ``lax.top_k``), every j <= i where there are no more
    x1 = x + a;   y = RMS(x1)
    layer < first_k_dense_replace:  m = (silu(y W1) * (y W3)) W2
    else  r = sigmoid(y W_r) over ALL published experts (float32)
          S = top-k of r + bias (ties: the lower id);  w_e = r_e / sum_S r
          m = routed_scaling_factor sum_{e in S, e HELD} w_e E_e(y) + Sh(y)
    x' = x1 + m;    logits = RMS_f(x_L) W_head      (untied)

**The share** is ``reference_mistral4``'s: the pytree holds the experts
``experts_first ..`` of each routed layer, the router is whole, ``m`` sums the
HELD chosen experts (the partial sum this chip would send into its group's
exchange) plus the shared expert; the vocabulary is whatever slice of the
token table and of the head the pytree holds.  Eight shares' partial sums,
the shared expert counted once, and eight slices' logits side by side are the
uncut layer and head (``tests/unit/test_dots3_serving.py`` shows it).

It reads the PROGRAM's parameter pytree (``models/dots3.py``: stacks BY KIND,
``blocks["latent_indexed" | "latent_sliding" | "dense" | "moe"]``) so the
same seeded weights feed both sides, and shares no code with it.  One
sequence at a time; a layer's heads ``HEAD_GROUP`` at a time and its queries
``QUERY_BLOCK`` at a time, a full layer's selection held as ONE ``[S, S]``
mask, a sliding layer's keys sliced to the band a query block can see — so
that 17,408 positions of 128 heads fit beside an engine.

What the published configuration leaves open, and what is taken here (the
configuration file lists the same under ``assumed``): the two rescales after
the latents' norms; the indexer's queries from the query latent; interleaved
rotary in attention and rotate-half in the indexer; the gate's input the
block's normed input; the window inclusive of the query; a correction bias
that chooses and does not weigh.

``forced`` (``{"experts": int32 [routed layers, B, S, k], "keys": uint8
[full layers, B, S, ceil(S / 8)]}``): another side's discrete choices, taken
in place of the own ones while the own are still made — ``reference_keye``'s
treatment of near-ties: the result then carries how far the two sides agree.

``variant`` (the comparison's own check that it can tell a shortcut from the
model): ``"no_index"`` full layers attend every key, ``"no_window"`` sliding
layers attend every key, ``"window_512"`` the window one key short,
``"no_gate"`` no head gate, ``"no_rescale"`` ``s_q = s_kv = 1``,
``"index_from_hidden"`` the indexer's queries from the block's normed input
``h`` itself, as Keye's indexer reads it (its first ``q_lora_rank`` values:
the one reading ``W_iq``'s shape admits),
``"latent_bf16_accum"`` scores rounded to bfloat16 and the output summed in
bfloat16 a key block at a time, ``"latent_fp8"`` what would be cached (``c``,
``k_r``, ``kI``) rounded to float8 e4m3 — the nearest precision below the
served one.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

#: queries attended at a time, heads expanded at a time, keys summed at a time
#: under ``latent_bf16_accum``
QUERY_BLOCK = 64
HEAD_GROUP = 16
ACCUM_BLOCK = 256
VARIANTS = (None, "no_index", "no_window", "window_512", "no_gate",
            "no_rescale", "index_from_hidden", "latent_bf16_accum",
            "latent_fp8")
KINDS = {"full_attention": "latent_indexed",
         "sliding_attention": "latent_sliding"}


def _f32(x):
    return x.astype(jnp.float32)


def _fp8(x):
    return _f32(x.astype(jnp.float8_e4m3fn))


def _bf16(x):
    return _f32(x.astype(jnp.bfloat16))


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def layer_kinds(config: Dict[str, Any]) -> List[str]:
    """The kinds of the ``depth`` layers built, off the published list."""
    return [KINDS[t] for t in config["layer_types"][:config["depth"]]]


def sizes(config: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """A layer kind's attention sizes by one set of names."""
    pre = "swa_" if kind == "latent_sliding" else ""
    return {"heads": config[pre + "num_attention_heads"],
            "q_rank": config[pre + "q_lora_rank"],
            "rank": config[pre + "kv_lora_rank"],
            "nope": config[pre + "qk_nope_head_dim"],
            "rope": config[pre + "qk_rope_head_dim"],
            "v": config[pre + "v_head_dim"],
            "theta": float(config[pre + "rope_theta"])}


def _angles(positions, dim: int, theta: float):
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return _f32(positions)[:, None] * freq


def _rotate_pairs(x, positions, theta: float):
    """x ``[..., S, dim]``: pair ``(2i, 2i + 1)`` turned by ``p theta^(-2i /
    dim)``."""
    ang = _angles(positions, x.shape[-1], theta)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _rotate_half(x, positions, theta: float):
    """x ``[..., S, dim]``: pair ``(i, i + dim / 2)`` turned likewise."""
    half = x.shape[-1] // 2
    ang = _angles(positions, x.shape[-1], theta)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _selection(config, layer, h, cq, kI_round, forced):
    """A full layer's keys over one sequence ``h [S, d]``: bool ``[S, S]``
    (query, key), and with ``forced`` (uint8 ``[S, ceil(S / 8)]``) that
    side's sets in place of the own, beside ``(own keys of the queries past
    index_topk, those of them in the forced sets, the largest distance of a
    disagreeing key's score from the own cut-off as a share of its query's
    largest score)``."""
    s = h.shape[0]
    hi, di = config["index_n_heads"], config["index_head_dim"]
    topk, r = config["index_topk"], config["qk_rope_head_dim"]
    theta = float(config["rope_theta"])
    positions = jnp.arange(s)
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb

    def turned(x, pos):
        return jnp.concatenate([_rotate_half(x[..., :r], pos, theta),
                                x[..., r:]], axis=-1)

    k = h @ _f32(layer["idx_k_w"])
    mu = k.mean(-1, keepdims=True)
    k = (k - mu) / jnp.sqrt(((k - mu) ** 2).mean(-1, keepdims=True) + 1e-6) \
        * _f32(layer["idx_k_norm"][0]) + _f32(layer["idx_k_norm"][1])
    k = kI_round(turned(k, positions))                          # [S, DI]
    cq = jnp.pad(cq, ((0, pad), (0, 0)))
    wi = jnp.pad(h @ _f32(layer["idx_w_w"]), ((0, pad), (0, 0))) \
        * (hi ** -0.5 * di ** -0.5)
    if forced is not None:
        forced = jnp.pad(forced, ((0, pad), (0, 0)))

    def block(i):
        at = i * qb
        pos = at + jnp.arange(qb)
        visible = positions[None, :] <= pos[:, None]              # [Q, S]
        q = jax.lax.dynamic_slice_in_dim(cq, at, qb) @ _f32(layer["idx_q_w"])
        q = turned(q.reshape(qb, hi, di).transpose(1, 0, 2), pos)  # [HI,Q,DI]
        dots = jax.nn.relu(jnp.einsum("nqd,sd->nqs", q, k))
        index = jnp.einsum("qn,nqs->qs",
                           jax.lax.dynamic_slice_in_dim(wi, at, qb), dots)
        index = jnp.where(index == 0.0, 0.0, index)       # -0.0 ties with 0.0
        _, chosen = jax.lax.top_k(jnp.where(visible, index, -jnp.inf),
                                  min(topk, s))
        keep = visible & jnp.zeros((qb, s), bool).at[
            jnp.arange(qb)[:, None], chosen].set(True)
        if forced is None:
            return keep
        own = keep
        bits = jax.lax.dynamic_slice_in_dim(forced, at, qb)
        keep = visible & jnp.unpackbits(bits, axis=-1)[:, :s].astype(bool)
        # a pad query of the last block sees every key: selects, counts
        past = ((pos >= topk) & (pos < s))[:, None]
        cut = jnp.min(jnp.where(own, index, jnp.inf), -1, keepdims=True)
        scale = jnp.max(jnp.where(visible, jnp.abs(index), 0.0), -1,
                        keepdims=True)
        gap = jnp.where(past & (own != keep), jnp.abs(index - cut), 0.0) \
            / jnp.maximum(scale, 1e-30)
        return keep, ((past & own).sum(), (past & own & keep).sum(),
                      gap.max())

    out = jax.lax.map(block, jnp.arange((s + pad) // qb))
    if forced is None:
        return out.reshape(s + pad, s), None
    keep, (total, agree, gap) = out
    return keep.reshape(s + pad, s), (total.sum(), agree.sum(), gap.max())


def _attend(q, k, v, scale, rows, band: int, window: int, low: bool):
    """One head group over one sequence: ``q`` / ``k [G, S, dk]``, ``v [G,
    S, dv]`` -> ``[G, S, dv]``, ``QUERY_BLOCK`` queries at a time.  ``rows``
    (bool ``[S + pad, S]``): the keys each query keeps — or None with
    ``band`` (keys before a block's first query that the block may see; 0:
    all) and ``window`` (a query keeps ``p - window < j <= p``; 0: every ``j
    <= p``).  ``low``: scores rounded to bfloat16, the output summed in
    bfloat16 ``ACCUM_BLOCK`` keys at a time."""
    g, s, _ = q.shape
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q = jnp.pad(q * scale, ((0, 0), (0, pad), (0, 0)))
    span = band + qb if band else s
    if band:
        k = jnp.pad(k, ((0, 0), (band, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (band, pad), (0, 0)))

    def block(i):
        at = i * qb
        qq = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=1)
        pos = (at + jnp.arange(qb))[:, None]
        if band:
            kk = jax.lax.dynamic_slice_in_dim(k, at, span, axis=1)
            vv = jax.lax.dynamic_slice_in_dim(v, at, span, axis=1)
            key = (at - band + jnp.arange(span))[None, :]
        else:
            kk, vv, key = k, v, jnp.arange(s)[None, :]
        if rows is not None:
            keep = jax.lax.dynamic_slice_in_dim(rows, at, qb)
        else:
            keep = (key >= 0) & (key <= pos)
            if window:
                keep = keep & (key > pos - window)
        att = jnp.einsum("hqd,hsd->hqs", qq, kk)
        if low:
            att = _bf16(att)
        probs = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        if not low:
            return jnp.einsum("hqs,hsd->hqd", probs, vv)
        acc = jnp.zeros((g, qb, vv.shape[-1]), jnp.float32)
        for lo in range(0, span, ACCUM_BLOCK):
            acc = _bf16(acc + jnp.einsum(
                "hqs,hsd->hqd", _bf16(probs[..., lo:lo + ACCUM_BLOCK]),
                vv[:, lo:lo + ACCUM_BLOCK]))
        return acc

    out = jax.lax.map(block, jnp.arange((s + pad) // qb))     # [N, G, Q, dv]
    return jnp.moveaxis(out, 0, 1).reshape(g, s + pad, -1)[:, :s]


def _attention(config, kind, layer, h, variant, forced, window_shift=0):
    """One layer's attention over one sequence ``h [S, d]`` -> ``([S, d],
    the selection's report or None)`` (module docstring)."""
    z = sizes(config, kind)
    s, d = h.shape
    eps, heads = config["rms_norm_eps"], z["heads"]
    nope, rope, vd = z["nope"], z["rope"], z["v"]
    rescale = config["apply_mla_qkv_lora_rescale"] \
        and variant != "no_rescale"
    s_q = math.sqrt(d / z["q_rank"]) if rescale else 1.0
    s_kv = math.sqrt(d / z["rank"]) if rescale else 1.0
    cached = _fp8 if variant == "latent_fp8" else (lambda a: a)
    positions = jnp.arange(s)

    cq = _rms(h @ _f32(layer["q_a_w"]), layer["q_a_norm"], eps) * s_q
    kv = h @ _f32(layer["kv_a_w"])
    c = cached(_rms(kv[:, :z["rank"]], layer["kv_a_norm"], eps) * s_kv)
    kr = cached(_rotate_pairs(kv[:, z["rank"]:], positions, z["theta"]))
    gate = jax.nn.sigmoid(h @ _f32(layer["head_gate_w"])) \
        if config["attention_gate_type"] == "headwise" \
        and variant != "no_gate" else jnp.ones((s, heads), jnp.float32)

    rows = report = None
    band = window = 0
    if kind == "latent_sliding":
        if variant != "no_window":
            window = config["sliding_window_size"] + window_shift \
                - (variant == "window_512")
            band = window - 1 if s > window else 0
    elif variant != "no_index" and s > config["index_topk"]:
        rows, report = _selection(
            config, layer, h, h[:, :z["q_rank"]]
            if variant == "index_from_hidden" else cq, cached, forced)

    group = min(HEAD_GROUP, heads)
    assert heads % group == 0, (heads, group)
    w_qb = layer["q_b_w"].reshape(z["q_rank"], heads, nope + rope)
    w_kvb = layer["kv_b_w"].reshape(z["rank"], heads, nope + vd)
    w_o = layer["o_w"].reshape(heads, vd, d)
    scale = 1.0 / math.sqrt(nope + rope)

    def one(i, acc):
        at = i * group
        q = jnp.einsum("sr,rhd->hsd", cq, _f32(
            jax.lax.dynamic_slice_in_dim(w_qb, at, group, axis=1)))
        kvb = jnp.einsum("sc,chd->hsd", c, _f32(
            jax.lax.dynamic_slice_in_dim(w_kvb, at, group, axis=1)))
        q = jnp.concatenate(
            [q[..., :nope],
             _rotate_pairs(q[..., nope:], positions, z["theta"])], axis=-1)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(kr[None], (group, s, rope))],
            axis=-1)
        out = _attend(q, k, kvb[..., nope:], scale, rows, band, window,
                      variant == "latent_bf16_accum")
        out = out * jax.lax.dynamic_slice_in_dim(gate, at, group,
                                                 axis=1).T[:, :, None]
        return acc + jnp.einsum("hsv,hvd->sd", out, _f32(
            jax.lax.dynamic_slice_in_dim(w_o, at, group, axis=0)))

    out = jax.lax.fori_loop(0, heads // group, one,
                            jnp.zeros((s, d), jnp.float32))
    return out, report


def _experts(config, y, moe, number: int, forced=None):
    """The routed experts of routed layer ``number`` over ``y [N, D]``
    (``moe``: the stacks of all routed layers, read an expert at a time):
    the sigmoid router over ALL experts, the top-k of ``score + bias``, a
    dense loop over the HELD ones weighted inside the set — or inside
    ``forced`` (int32 ``[N, k]``), which also returns
    ``reference_mistral4._experts``'s report — plus the shared expert."""
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


def hidden_states(config: Dict[str, Any], params: Any, tokens, forced=None,
                  variant: Optional[str] = None, window_shift: int = 0):
    """Final-RMSNorm'd hidden states ``[B, S, D]`` in float32, the sequences
    one after the other.  With ``forced`` (module docstring): ``(hidden
    states, (the routed layers' reports stacked, the full layers' reports
    stacked))``.  ``window_shift``: keys added to the sliding layers' window
    (the TWINS a comparison holds the window's edge with: one key shorter,
    one longer)."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    if config["n_shared_experts"] != 1 or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or config["rope_scaling"] is not None \
            or config["tie_word_embeddings"] or config["moe_layer_freq"] != 1 \
            or config["attention_bias"] or config["hidden_act"] != "silu":
        raise ValueError("the reference follows the published block: one "
                         "shared expert, a sigmoid router with a correction "
                         "bias and no groups, plain rotary, every layer "
                         "past the dense ones routed, no biases, an untied "
                         "head")
    eps, dense = config["rms_norm_eps"], config["first_k_dense_replace"]
    blocks = params["blocks"]
    b, s = tokens.shape
    x = _f32(params["embed"][tokens])
    seen = dict.fromkeys(KINDS.values(), 0)
    routed, selected = [], []
    for number, kind in enumerate(layer_kinds(config)):
        layer = _at(blocks[kind], seen[kind])
        h = _rms(x, layer["attn_norm"], eps)
        rows = []
        for i in range(b):
            keys = None if forced is None or kind != "latent_indexed" \
                else forced["keys"][seen[kind], i]
            a, report = _attention(config, kind, layer, h[i], variant, keys,
                                   window_shift)
            rows.append(a)
            if report is not None:
                selected.append(report)
        seen[kind] += 1
        x = x + jnp.stack(rows)
        y = _rms(x, layer["mlp_norm"], eps).reshape(b * s, -1)
        if number < dense:
            lyr = _at(blocks["dense"], number)
            out = (jax.nn.silu(y @ _f32(lyr["w1"])) * (y @ _f32(lyr["w3"]))) \
                @ _f32(lyr["w2"])
        else:
            out = _experts(config, y, blocks["moe"], number - dense,
                           None if forced is None else
                           forced["experts"][number - dense].reshape(
                               b * s, -1))
            if forced is not None:
                out, report = out
                routed.append(report)
        x = x + out.reshape(x.shape)
    x = _rms(x, params["final_norm"], eps)
    if forced is None:
        return x
    stack = lambda reports: tuple(jnp.stack(r) for r in zip(*reports))  # noqa: E731
    return x, (stack(routed), stack(selected) if selected else None)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None,
           variant: Optional[str] = None, window_shift: int = 0):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only, through the untied head.  With ``forced``
    (:func:`hidden_states`): ``(logits, agreement)``, ``agreement`` =
    ``{"keys": share of the own chosen keys (queries past ``index_topk``)
    that the forced sets hold, "key_gap": the largest distance of a
    disagreeing key from the own cut-off, "experts" / "expert_gap" /
    "expert_gap_max" / "expert_gap_max_by_layer"``:
    ``reference_mistral4.logits``'s}``."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens, forced):
        x = hidden_states(config, params, tokens, forced, variant,
                          window_shift)
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
    out, ((agree, gap, total, apart), selected) = out
    k = config["num_experts_per_tok"]
    agreement = {
        "experts": float(agree.sum()) / (tokens.size * k * agree.shape[0]),
        "expert_gap": float(total.sum()) / max(1.0, float(apart.sum())),
        "expert_gap_max": float(gap.max()),
        "expert_gap_max_by_layer": [round(float(g), 5) for g in gap],
        "keys": 1.0, "key_gap": 0.0}
    if selected is not None:
        key_total, key_agree, key_gap = selected
        agreement.update(
            keys=float(key_agree.sum()) / max(1.0, float(key_total.sum())),
            key_gap=float(key_gap.max()))
    return out, agreement


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
