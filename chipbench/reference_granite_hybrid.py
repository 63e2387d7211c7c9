"""The plain reference of ``family: granite_hybrid``: the language model of
ibm-granite/granite-4.0-h-micro (``model_type: granitemoehybrid`` with no
routed experts) in float32 ``jax.numpy`` — no kernels, no cache, no paging,
no chunked form, no batching, full-precision matmuls
(``jax.default_matmul_precision("highest")``): the Mamba-2 recurrence TOKEN
BY TOKEN in a ``lax.scan``, the attention as a plain causal softmax over
every earlier key.  ``x0 = embedding_multiplier E[ids]``; with ``h = RMS(x;
w1)`` (eps ``rms_norm_eps``) a layer of ``layer_types`` is one of

  mamba (``H`` heads of width ``P``, state ``N``, one group):
    [z | xBC | dt] = h W_in                     widths H P | H P + 2 N | H
    xBC_t = SiLU(sum_{i < K} tap_i * xBC_{t - K + 1 + i} + bias)
                   (causal depthwise convolution, zeros before position 0)
    [x | B | C] = xBC_t;   D_t = softplus(dt_t + dt_bias);   A = -exp(A_log)
    S_t[g] = exp(D_t[g] A[g]) S_{t-1}[g] + D_t[g] x_t[g] B_t^T     S[g] [P, N]
    y_t[g] = S_t[g] C_t + Dskip[g] x_t[g]
    m   = RMS(y * SiLU(z); w_n) W_out           one norm over all H P channels
  attention (NO positional encoding):
    q = h W_q (heads x hd), k = h W_k, v = h W_v (KV heads x hd)
    score(i, j) = (q_i . k_j) * attention_multiplier,  j <= i
    m   = concat_g(softmax(score_g) v_kv(g)) W_o

followed by ``x1 = x + residual_multiplier m`` and the dense SwiGLU ``x' = x1
+ residual_multiplier (SiLU(g) * u) W_o2`` with ``[g | u] = RMS(x1; w2)
W_i2``; ``logits = RMS(x_L; w_f) E^T / logits_scaling`` (the head is tied).

It reads the PROGRAM's parameter pytree (``models/granite_hybrid.py``: stacks
BY KIND, ``blocks["ssm" | "full"]``, each carrying its layers' ``attn_norm`` /
``mlp_norm`` / ``ffn_in_w`` / ``ffn_out_w``) so that the same seeded weights
feed both sides, and shares no code with it.  The layers are a scan over the
periods of ``layer_types`` with the period written out: at the published
widths a layer's weights are 0.3 GB in float32, and the reference runs
beside a serving engine that fills the chip.

Departures from the published modeling code
(``transformers``' ``modeling_granitemoehybrid.py``), each also under
``assumed`` in the configuration file: the state is float32 (the config has
no key for its precision); ``dt`` is
not clamped (``time_step_limit`` is absent from the config: the code's
default ``(0, inf)``); ``mamba_chunk_size`` tiles the published kernel and
changes no result; the (absent) routed experts' ``block_sparse_moe`` adds
nothing at ``num_local_experts`` 0; weights are seeded, not trained.

``VARIANTS`` are shortcuts the benchmark's comparison must refuse:
``state_bf16`` (the state rounded to bfloat16 after every token),
``no_decay`` (``exp(D A) = 1``), ``no_reset`` (sequence ``i + 1`` starts from
the state and the convolution tail sequence ``i`` left: a slot handed on
without a reset)."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

QUERY_BLOCK = 64
VARIANTS = (None, "state_bf16", "no_decay", "no_reset")
#: the published ``layer_types`` to the program's stacks
KIND = {"mamba": "ssm", "attention": "full"}


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(scale)


def period(config: Dict[str, Any]):
    """The shortest period of the published ``layer_types``, as the
    program's kinds."""
    kinds = [KIND[t] for t in config["layer_types"]]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    p = next(p for p in range(1, len(kinds) + 1) if len(kinds) % p == 0
             and all(k == kinds[i % p] for i, k in enumerate(kinds)))
    return tuple(kinds[:p])


def _mamba(config, layer, h, carry, variant, length=None):
    """One sequence ``h [S, d]`` through a mamba layer from ``carry = (state
    [H, P, N], tail [K - 1, channels])``: ``-> (m [S, d], carry)``.  With
    ``length`` (traced) the positions from it on are padding: the carry that
    comes back is the one after ``length`` tokens."""
    heads, p, n = config["mamba_n_heads"], config["mamba_d_head"], \
        config["mamba_d_state"]
    taps, inner, s = config["mamba_d_conv"], heads * p, h.shape[0]
    state, tail = carry
    proj = h @ _f32(layer["in_w"])
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + inner + 2 * n], \
        proj[:, inner + inner + 2 * n:]
    ext = jnp.concatenate([tail, xbc], axis=0)
    w = _f32(layer["conv_w"])
    conv = jax.nn.silu(sum(ext[j:j + s] * w[j] for j in range(taps))
                       + _f32(layer["conv_b"]))
    x = conv[:, :inner].reshape(s, heads, p)
    b, c = conv[:, inner:inner + n], conv[:, inner + n:]
    step = jax.nn.softplus(dt + _f32(layer["dt_bias"]))          # [S, H]
    if length is not None:
        step = jnp.where((jnp.arange(s) < length)[:, None], step, 0.0)
    decay = jnp.exp(step * -jnp.exp(_f32(layer["a_log"])))
    if variant == "no_decay":
        decay = jnp.ones_like(decay)

    def token(st, xs):
        xt, bt, ct, dt_, at = xs
        st = at[:, None, None] * st \
            + (dt_[:, None] * xt)[:, :, None] * bt[None, None, :]
        if variant == "state_bf16":
            # (reduce_precision, not a cast there and back: XLA:TPU folds
            # the pair away under its excess-precision default)
            st = jax.lax.reduce_precision(st, exponent_bits=8,
                                          mantissa_bits=7)
        return st, jnp.einsum("hpn,n->hp", st, ct)

    state, y = jax.lax.scan(token, state, (x, b, c, step, decay))
    y = y + _f32(layer["d_skip"])[None, :, None] * x
    g = _rms(y.reshape(s, inner) * jax.nn.silu(z), layer["gate_norm"],
             config["rms_norm_eps"])
    out = g @ _f32(layer["out_w"])
    if length is None:
        return out, (state, ext[s:])
    return out, (state, jax.lax.dynamic_slice_in_dim(ext, length, taps - 1))


def _attention(config, layer, h):
    """One sequence ``h [S, d]`` through an attention layer: no rotation,
    scores times ``attention_multiplier``, queries ``QUERY_BLOCK`` at a
    time."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, s = config["hidden_size"] // heads, h.shape[0]
    q = (h @ _f32(layer["q_w"])).reshape(s, heads, hd).transpose(1, 0, 2)
    k = (h @ _f32(layer["k_w"])).reshape(s, kv, hd).transpose(1, 0, 2)
    v = (h @ _f32(layer["v_w"])).reshape(s, kv, hd).transpose(1, 0, 2)
    k, v = (jnp.repeat(a, heads // kv, axis=0) for a in (k, v))
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q = jnp.pad(q * config["attention_multiplier"], ((0, 0), (0, pad), (0, 0)))
    key_pos = jnp.arange(s)

    def block(i):
        at = i * qb
        qq = jax.lax.dynamic_slice_in_dim(q, at, qb, axis=1)
        keep = key_pos[None, :] <= (at + jnp.arange(qb))[:, None]
        att = jnp.einsum("hqd,hsd->hqs", qq, k)
        probs = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,hsd->hqd", probs, v)

    out = jax.lax.map(block, jnp.arange((s + pad) // qb))
    out = jnp.moveaxis(out, 0, 1).reshape(heads, s + pad, hd)[:, :s]
    return out.transpose(1, 0, 2).reshape(s, heads * hd) @ _f32(layer["o_w"])


def hidden_states(config: Dict[str, Any], params: Any, tokens,
                  variant: Optional[str] = None, lengths=None):
    """Final-RMSNorm'd hidden states ``[B, S, D]`` in float32, the sequences
    one after the other.  ``lengths`` (int32 ``[B]``): sequence ``i`` is its
    first ``lengths[i]`` tokens and padding after them (what a sequence hands
    to the next under ``no_reset`` is its state there)."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    if config["position_embedding_type"] != "nope" \
            or config["mamba_n_groups"] != 1 or config["num_local_experts"] \
            or not config["tie_word_embeddings"] or config["attention_bias"] \
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"] \
            or config["hidden_act"] != "silu" \
            or config["normalization_function"] != "rmsnorm":
        raise ValueError("the reference follows the published block: no "
                         "positional encoding, one group of B and C, no "
                         "routed experts, a tied head, a convolution bias "
                         "and no other, SiLU, RMSNorm")
    eps, res = config["rms_norm_eps"], config["residual_multiplier"]
    f = config["shared_intermediate_size"]
    heads, p, n = config["mamba_n_heads"], config["mamba_d_head"], \
        config["mamba_d_state"]
    kinds = period(config)
    b, s = tokens.shape
    blocks = params["blocks"]
    zero = (jnp.zeros((heads, p, n), jnp.float32),
            jnp.zeros((config["mamba_d_conv"] - 1, heads * p + 2 * n),
                      jnp.float32))

    def one_period(x, number):
        for j, kind in enumerate(kinds):
            same = [i for i in range(len(kinds)) if kinds[i] == kind]
            index = number * len(same) + same.index(j)
            layer = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, index,
                                                       keepdims=False),
                blocks[kind])
            h = _rms(x, layer["attn_norm"], eps)
            if kind == "ssm":
                carry, rows = zero, []
                for i in range(b):
                    m, carry = _mamba(config, layer, h[i], carry, variant,
                                      None if lengths is None else lengths[i])
                    rows.append(m)
                    if variant != "no_reset":
                        carry = zero
                x = x + res * jnp.stack(rows)
            else:
                x = x + res * jax.lax.map(
                    lambda hr: _attention(config, layer, hr), h)
            gu = _rms(x, layer["mlp_norm"], eps) @ _f32(layer["ffn_in_w"])
            x = x + res * (jax.nn.silu(gu[..., :f]) * gu[..., f:]) \
                @ _f32(layer["ffn_out_w"])
        return x, None

    x = config["embedding_multiplier"] * _f32(params["embed"][tokens])
    x, _ = jax.lax.scan(
        one_period, x,
        jnp.arange(config["num_hidden_layers"] // len(kinds), dtype=jnp.int32))
    return _rms(x, params["final_norm"], eps)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, variant: Optional[str] = None,
           lengths=None):
    """Float32 logits ``[B, S, V]``, or ``[B, len(at), V]`` at the listed
    positions only (``at [B, n]``: sequence ``i`` at ``at[i]``).
    ``lengths``: :func:`hidden_states`."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens, at, lengths):
        x = hidden_states(config, params, tokens, variant, lengths)
        if at is not None:
            x = x[:, at] if at.ndim == 1 else \
                jnp.take_along_axis(x, at[:, :, None], axis=1)
        return jnp.einsum("bsd,vd->bsv", x, _f32(params["embed"])) \
            / config["logits_scaling"]

    at = None if at is None else jnp.asarray(at, jnp.int32)
    lengths = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens, at, lengths)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    """Mean next-token cross entropy of ``tokens[:, :-1] -> tokens[:, 1:]``
    in float32."""
    tokens = jnp.asarray(tokens)

    def run(params, tokens):
        lg = jnp.einsum(
            "bsd,vd->bsv", hidden_states(config, params, tokens[:, :-1]),
            _f32(params["embed"])) / config["logits_scaling"]
        picked = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(lg, axis=-1) - picked).mean()

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(params, tokens)
