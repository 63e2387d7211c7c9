"""Kimi Linear (``moonshotai/Kimi-Linear-48B-A3B-Instruct``; technical report
arXiv:2510.26692): a hybrid of LINEAR attention and latent attention over
routed experts, served through ``init_serving`` / ``ServingEngine``.

The block is the sequential pre-norm RMSNorm residual block (``x += attn(
norm(x)); x += ffn(norm(x))``) over the pattern ``[kda, kda, kda, latent]``:

* a **KDA layer** (``ops/delta_rule.py``: the gated delta rule with a decay a
  key channel) keeps no token: its whole past is one float32 matrix a head a
  ROW, ``state [L_kda, rows, H, dk, dv]``, and the last ``kda_conv - 1``
  inputs of its three short convolutions, ``conv [L_kda, rows, 1, K - 1, 3 H
  dk]`` (``ops/paged_kv.py`` "The state kind").  ``q, k, v = SiLU(conv(W x))``
  (causal, depthwise, no bias); ``q``, ``k`` L2-normalised a head, ``q`` times
  ``dk^-0.5``; the log-decay ``g = -exp(a_log) softplus(W_f2 W_f1 x +
  dt_bias)`` a key channel, ``beta = sigmoid(W_b x)`` a head; the output is
  ``W_o [RMSNorm_head(o) * sigmoid(W_g2 W_g1 x)]``.
* a **latent layer** is ``models/llama.py``'s latent attention with a
  full-rank query projection (``q_lora_rank = 0``) and NO rotation
  (``latent_rope = False``): the ``qk_rope_dim`` values of a query and of the
  one key all heads share are used as they are.  Its pool is the latent
  kind's one leaf, ``[L_latent, NB, 1, block, 640]``, read absorbed.
* the **FFN** of the first ``first_dense`` layers is a dense SwiGLU of width
  ``dense_ffn_size``; every other layer routes: sigmoid scores over all
  ``num_experts``, the top-k of ``score + gate_bias`` (a selection bias that
  chooses and does not weigh), the chosen scores renormalised and scaled by
  ``routed_scale``, beside ``shared_experts`` ungated shared experts; the
  layer may hold a share of its experts (``experts_held``).

The layers' weights differ in SHAPE, so ``params["blocks"]`` holds stacks BY
KIND — ``{"kda": [L_kda, ...], "latent": [L_latent, ...], "dense":
[first_dense, ...], "moe": [L - first_dense, ...]}`` (each attention kind's
stack carries its layers' two block norms) — and the layer loop is
``cached.scan_periods_cached(head=...)`` over stacks by kind: the leading dense
layer's period is written out, the rest scanned.

A cached forward takes a window of a ROW's tokens like any other
(``cached.window``), with two things of its own: ``block_tables`` is ``{"full":
the latent kind's table, "slot": int32 [B]}`` — the row of the state leaves
each row of a prefill call owns (a decode step's row ``b`` IS row ``b``) — and
a prefill window whose base is 0 starts from a ZERO state and a zero
convolution tail: a row that a new sequence enters needs no reset from
outside.  A pad (a token past ``lengths``, an idle decode row) moves neither
the state nor the tail.  Served on one shard; what else such a model is
refused is ``inference/serving.py``'s to say, by name.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import delta_rule
from ..ops.paged_kv import latent_pool_width
from ..runtime.model import ModelSpec
from . import cached
from . import llama as L
from . import mixtral as M
from .cached import layer_accessors, live_tokens, qmm, scan_periods_cached

PyTree = Any
KINDS = ("kda", "latent")


@dataclasses.dataclass
class KimiLinearConfig(M.MixtralConfig):
    kda_heads: int = 32
    #: key width and value width of a KDA head (the published model's agree)
    kda_head_dim: int = 128
    #: taps of the causal depthwise convolutions on q, k and v
    kda_conv: int = 4
    #: leading layers whose FFN is dense, of width ``dense_ffn_size``
    first_dense: int = 1
    dense_ffn_size: int = 9216
    #: ``params["blocks"]`` is stacks by kind (``LlamaConfig.by_kind``)
    by_kind: ClassVar[bool] = True

    def __post_init__(self):
        super().__post_init__()
        if not self.layer_kinds or set(self.layer_kinds) - set(KINDS):
            raise ValueError(f"layer_kinds={self.layer_kinds!r}: a pattern "
                             f"of {KINDS}")
        if ("latent" in self.layer_kinds) != self.latent:
            raise ValueError("a 'latent' layer needs kv_lora_rank > 0 (and "
                             "a pattern without one takes none)")
        if not 0 <= self.first_dense <= len(self.layer_kinds):
            raise ValueError(
                f"first_dense={self.first_dense}: the leading dense layers "
                f"lie in the first period ({len(self.layer_kinds)} layers)")
        if self.parallel_block or self.router_input != "ffn" \
                or self.index_heads or self.tie_embeddings \
                or self.norm != "rms":
            raise ValueError("Kimi Linear's block is the sequential RMSNorm "
                             "block with an untied head")
        if self.kda_conv < 2:
            raise ValueError(f"kda_conv={self.kda_conv}: at least 2 taps")

    @property
    def gate_rank(self) -> int:
        """Bottleneck of the decay's and of the output gate's projections:
        the head width (the published modeling code's)."""
        return self.kda_head_dim

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def layers_of(self, kind: str) -> int:
        return self.num_layers // len(self.layer_kinds) \
            * self.layer_kinds.count(kind)

    @staticmethod
    def kimi_linear_48b_a3b() -> "KimiLinearConfig":
        """moonshotai/Kimi-Linear-48B-A3B-Instruct's widths over its regular
        part: d 2,304, 32 KDA heads x 128 with 4-tap convolutions, latent
        attention of 32 heads (128 + 64 score, 128 value, latent 512, no
        rotation), a leading dense FFN of 9,216, 256 sigmoid-scored SwiGLU
        experts of width 1,024 top-8 with a selection bias, renormalised,
        times 2.446, one shared expert, an untied head.  The published model
        has 27 layers (its last three are kda, kda, latent): 24 is its whole
        periods.  One chip's share of it (``experts_held``, fewer layers, a
        vocabulary slice) is a deployment's to state."""
        return KimiLinearConfig(
            vocab_size=163840, max_seq_len=1048576, num_layers=24,
            num_heads=32, num_kv_heads=32, head_width=192, hidden_size=2304,
            ffn_size=1024, rms_eps=1e-5, kv_lora_rank=512, qk_nope_dim=128,
            qk_rope_dim=64, v_head_dim=128, latent_rope=False,
            layer_kinds=("kda",) * 3 + ("latent",), num_experts=256, top_k=8,
            norm_topk_prob=True, router_score="sigmoid", router_bias=True,
            routed_scale=2.446, shared_experts=1, capacity_factor=None)

    def num_params(self) -> int:
        d, f = self.hidden_size, self.ffn_size
        c, r, h = self.kda_width, self.gate_rank, self.kda_heads
        kda = 4 * d * c + 2 * (d * r + r * c) + d * h \
            + 3 * self.kda_conv * c + h + c + self.kda_head_dim
        latent = sum(math.prod(s) for s in L.latent_shapes(self).values())
        moe = d * self.num_experts + self.num_experts * self.router_bias \
            + (self.experts_here + self.shared_experts) * 3 * d * f
        return 2 * self.vocab_size * d + d + 2 * d * self.num_layers \
            + self.layers_of("kda") * kda + self.layers_of("latent") * latent \
            + self.first_dense * 3 * d * self.dense_ffn_size \
            + (self.num_layers - self.first_dense) * moe

    def active_params(self) -> int:
        idle = (self.num_experts - self.top_k) * self.experts_here \
            // self.num_experts * 3 * self.hidden_size * self.ffn_size
        return self.num_params() - (self.num_layers - self.first_dense) * idle


# ------------------------------------------------------------------ parameters
def kda_shapes(cfg: KimiLinearConfig):
    """One KDA layer's leaves by name (a projection is stored ``[in, out]``;
    ``conv_w [K, 3 C]`` holds the taps of q | k | v, tap ``K - 1`` on the
    current token)."""
    d, c, r = cfg.hidden_size, cfg.kda_width, cfg.gate_rank
    return {"q_w": (d, c), "k_w": (d, c), "v_w": (d, c),
            "conv_w": (cfg.kda_conv, 3 * c),
            "f_a_w": (d, r), "f_b_w": (r, c), "dt_bias": (c,),
            "a_log": (cfg.kda_heads,), "b_w": (d, cfg.kda_heads),
            "g_a_w": (d, r), "g_b_w": (r, c),
            "o_norm": (cfg.kda_head_dim,), "o_w": (c, d)}


def init_params(cfg: KimiLinearConfig, rng) -> PyTree:
    """Seeded parameters: matrices N(0, 0.02) (output projections scaled
    down with the depth, as the other families), the convolution taps
    U(-1/sqrt(K), 1/sqrt(K)) (a depthwise ``Conv1d``'s default), ``a_log =
    ln U(1, 16)`` and ``dt_bias`` the inverse softplus of a step log-uniform
    in [0.001, 0.1] (the published code's initialisation of the decay)."""
    d, n, std = cfg.hidden_size, cfg.num_layers, 0.02
    keys = iter(jax.random.split(rng, 64))

    def normal(shape, s=std):
        return (jax.random.normal(next(keys), shape) * s).astype(jnp.float32)

    out_std = std / math.sqrt(2 * n)
    stacks = {}
    lk = cfg.layers_of("kda")
    if lk:
        kda = {}
        for name, shape in kda_shapes(cfg).items():
            shape = (lk,) + shape
            if name == "conv_w":
                bound = 1.0 / math.sqrt(cfg.kda_conv)
                kda[name] = jax.random.uniform(next(keys), shape, jnp.float32,
                                               -bound, bound)
            elif name == "a_log":
                kda[name] = jnp.log(jax.random.uniform(
                    next(keys), shape, jnp.float32, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    next(keys), shape, jnp.float32, math.log(1e-3),
                    math.log(1e-1)))
                kda[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name == "o_norm":
                kda[name] = jnp.ones(shape)
            else:
                kda[name] = normal(shape, out_std if name == "o_w" else std)
        stacks["kda"] = kda
    lm = cfg.layers_of("latent")
    if lm:
        stacks["latent"] = {
            name: jnp.ones((lm,) + shape) if name.endswith("_norm")
            else normal((lm,) + shape, out_std if name == "o_w" else std)
            for name, shape in L.latent_shapes(cfg).items()}
    for kind, stack in stacks.items():
        count = cfg.layers_of(kind)
        stack["attn_norm"] = jnp.ones((count, d))
        stack["mlp_norm"] = jnp.ones((count, d))
    if cfg.first_dense:
        fd, f = cfg.first_dense, cfg.dense_ffn_size
        stacks["dense"] = {"w1": normal((fd, d, f)), "w3": normal((fd, d, f)),
                           "w2": normal((fd, f, d), out_std)}
    lr, f, e = n - cfg.first_dense, cfg.ffn_size, cfg.experts_here
    moe = {"gate_w": normal((lr, d, cfg.num_experts)),
           "experts_w1": normal((lr, e, d, f)),
           "experts_w3": normal((lr, e, d, f)),
           "experts_w2": normal((lr, e, f, d))}
    if cfg.router_bias:
        moe["gate_bias"] = normal((lr, cfg.num_experts))
    if cfg.shared_experts:
        sf = cfg.shared_experts * f
        moe.update(shared_w1=normal((lr, d, sf)), shared_w3=normal((lr, d, sf)),
                   shared_w2=normal((lr, sf, d)))
    stacks["moe"] = moe
    return {"embed": normal((cfg.vocab_size, d)), "blocks": stacks,
            "final_norm": jnp.ones((d,)),
            "lm_head": normal((d, cfg.vocab_size))}


# ------------------------------------------------------------------- KDA layer
def _kda_inputs(cfg: KimiLinearConfig, layer, y, ext, live):
    """What the delta rule takes, from the normed input ``y [B, T, d]`` and
    ``ext [B, K - 1 + T, 3 C]`` (the q | k | v projections behind the row's
    convolution tail): float32 ``q, k, g [B, H, T, dk]``, ``v [B, H, T,
    dv]``, ``beta [B, H, T]``; where ``live [B, T]`` is false, ``g`` and
    ``beta`` are 0 (the token moves no state)."""
    b, t, _ = y.shape
    h, hd, c = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_width
    with jax.named_scope("layer/state/conv"):
        taps = layer["conv_w"].astype(jnp.float32)
        conv = sum(ext[:, j:j + t].astype(jnp.float32) * taps[j]
                   for j in range(cfg.kda_conv))
        q, k, v = (jax.nn.silu(conv[..., i * c:(i + 1) * c])
                   .reshape(b, t, h, hd).transpose(0, 2, 1, 3)
                   for i in range(3))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    with jax.named_scope("layer/state/gate"):
        decay = qmm(qmm(y, layer["f_a_w"]), layer["f_b_w"]) \
            .astype(jnp.float32) + layer["dt_bias"].astype(jnp.float32)
        g = -jnp.exp(layer["a_log"].astype(jnp.float32))[
            None, :, None, None] * jax.nn.softplus(decay).reshape(
                b, t, h, hd).transpose(0, 2, 1, 3)
        beta = jax.nn.sigmoid(qmm(y, layer["b_w"]).astype(jnp.float32)) \
            .transpose(0, 2, 1)
        keep = live[:, None, :]
        return (unit(q) * hd ** -0.5, unit(k), v,
                jnp.where(keep[..., None], g, 0.0),
                jnp.where(keep, beta, 0.0))


def _kda_output(cfg: KimiLinearConfig, layer, y, o):
    """``W_o [RMSNorm_head(o) * o_norm * sigmoid(W_g2 W_g1 y)]`` from the
    rule's float32 ``o [B, H, T, dv]``."""
    b, t, _ = y.shape
    with jax.named_scope("layer/state/gate"):
        o = o.transpose(0, 2, 1, 3)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_eps) \
            * layer["o_norm"].astype(jnp.float32)
        gate = jax.nn.sigmoid(qmm(qmm(y, layer["g_a_w"]), layer["g_b_w"])
                              .astype(jnp.float32)).reshape(o.shape)
    with jax.named_scope("layer/attn/out"):
        return qmm((o * gate).reshape(b, t, cfg.kda_width).astype(y.dtype),
                   layer["o_w"], y.dtype)


@jax.named_scope("layer/attn/qkv")
def _kda_projections(layer, y):
    """q | k | v of ``y``, ``[B, T, 3 C]`` (the barrier: a later head split
    moves these products, not the weights — ``llama._attend_cached``)."""
    return jnp.concatenate(jax.lax.optimization_barrier(
        (qmm(y, layer["q_w"]), qmm(y, layer["k_w"]), qmm(y, layer["v_w"]))),
        axis=-1)


def _kda_cached(cfg: KimiLinearConfig, layer, y, state, conv, index, slot,
                base, live):
    """A KDA layer's window against the row-indexed leaves (module
    docstring): ``-> (attention output [B, T, d], state, conv)``."""
    b, t, _ = y.shape
    taps = cfg.kda_conv - 1
    x3 = _kda_projections(layer, y)
    if slot is None:
        # a decode step (one token a row): row b is row b of the leaves
        with jax.named_scope("layer/attn/kv_write"):
            tail = jax.lax.dynamic_index_in_dim(conv, index,
                                                keepdims=False)[:, 0]
            ext = jnp.concatenate([tail, x3.astype(tail.dtype)], axis=1)
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, jnp.where(live[:, :, None], ext[:, 1:], tail)[:, None],
                index, 0)
        q, k, v, g, beta = _kda_inputs(cfg, layer, y, ext, live)
        o, state = delta_rule.step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                   g[:, :, 0], beta[:, :, 0], state, index)
        return _kda_output(cfg, layer, y, o[:, :, None]), state, conv
    # a prefill window: the rows' leaves by ``slot`` (a pad row's is out of
    # range: read clamped, written nowhere); a window at base 0 starts from
    # nothing
    with jax.named_scope("layer/attn/kv_write"):
        rows = jnp.clip(slot, 0, state.shape[1] - 1)
        fresh = (jnp.asarray(base, jnp.int32) == 0).reshape(-1)
        tail = jnp.where(fresh[:, None, None], 0, conv[index, rows, 0])
        s0 = jnp.where(fresh[:, None, None, None], 0.0, state[index, rows])
        ext = jnp.concatenate([tail, x3.astype(tail.dtype)], axis=1)
        valid = live.sum(axis=1, dtype=jnp.int32)
        tail = jnp.take_along_axis(
            ext, (valid[:, None] + jnp.arange(taps))[:, :, None], axis=1)
    q, k, v, g, beta = _kda_inputs(cfg, layer, y, ext, live)
    o, s1 = delta_rule.chunked(q, k, v, g, beta, s0)
    with jax.named_scope("layer/attn/kv_write"):
        state = state.at[index, slot].set(s1, mode="drop")
        conv = conv.at[index, slot, 0].set(tail, mode="drop")
    return _kda_output(cfg, layer, y, o), state, conv


# ------------------------------------------------------------------------- FFN
def _dense_ffn(layer, y):
    with jax.named_scope("layer/mlp"):
        gate = jax.nn.silu(qmm(y, layer["w1"]))
        return qmm(gate * qmm(y, layer["w3"]), layer["w2"], y.dtype)


def _at(stack, index):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False),
        stack)


def _ffn(cfg: KimiLinearConfig, blocks, stacks, number, y, live, choices,
         routed=None):
    """The FFN of layer ``number`` (an ``int`` in a written-out period):
    ``-> (output, routing record, chosen experts or None)``; a dense layer's
    record is zeros and its choices -1.  ``routed``: ``mixtral._routed`` as
    :func:`forward_cached` holds it (one trace a program; default: called as
    it is)."""
    if isinstance(number, int) and number < cfg.first_dense:
        out = _dense_ffn(_at(blocks["dense"], number), y)
        record = jnp.zeros(len(M.record_columns(cfg)), jnp.int32)
        chosen = jnp.full(y.shape[:-1] + (cfg.top_k,), -1, jnp.int32)
        return out, record, chosen if choices else None
    index = number - cfg.first_dense
    moe = blocks["moe"]
    if stacks is not None:
        moe = {k: v for k, v in moe.items() if k not in stacks}
    layer = _at(moe, index)
    if stacks is not None:
        layer["layer_index"] = jnp.asarray(index, jnp.int32)
    out, record = routed(layer, y, live, stacks) if routed is not None \
        else M._routed(cfg, layer, y, live, stacks, choices)
    if choices:
        return out, record[0], record[1]
    return out, record, None


# --------------------------------------------------------------------- forward
def forward_cached(cfg: KimiLinearConfig, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False,
                   routing: bool = False, choices: bool = False):
    """The cached forward (module docstring; ``cached.window`` has the
    contract of ``lengths`` / ``block_tables`` / ``all_positions``,
    ``mixtral.forward_cached`` that of ``routing`` and ``choices``: the
    records are ``[L, ..]`` with zeros for a dense layer, ``choices`` gives
    ``{"experts": int32 [L - first_dense, B, T, top_k]}``)."""
    if not isinstance(block_tables, dict):
        raise NotImplementedError(
            "a model with gated delta-rule layers is served through "
            "init_serving / ServingEngine, whose cache holds a recurrent "
            "state a row beside the block-paged latent pool (block_tables "
            "{'full', 'slot'}); the contiguous cache of "
            "InferenceEngine.generate has one kind of state")
    w = cached.window(input_ids, pos, lengths, block_tables["full"])
    live = live_tokens(input_ids, lengths, block_tables)
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(params["embed"].dtype)
    blocks = params["blocks"]
    stacks = None
    if M._expert_kernel(blocks["moe"]):
        # the expert stacks stay whole: the kernel reads a layer of them in
        # place (``mixtral.forward_cached``)
        stacks = {k: blocks["moe"][k] for k in M._EXPERT_LEAVES}

    # ONE trace and one lowered function a program for the layers that
    # repeat: a period writes its layers out, the head period writes them
    # out again, and all six KDA layers (and all seven routed FFNs) of this
    # chip's share are the same computation at the same shapes — traced a
    # layer at a time, a program's build is ~14 s of Python at every start
    # of the cell's process (PERF.md section 6, PR 52).  The compiler
    # inlines the calls: the program it optimises is the one it was.
    # (no donation of their own: they are calls inside the engine's
    # program, whose jit donates the cache)
    kda = jax.jit(functools.partial(_kda_cached, cfg), donate_argnums=())
    routed = jax.jit(lambda layer, y, live, stacks: M._routed(
        cfg, layer, y, live, stacks, choices))

    def step(x, layer, ck, cv, index, table, kind, number):
        get, mm = layer_accessors(layer)
        with jax.named_scope("layer/attn"):
            with jax.named_scope("layer/norm"):
                y = L.rms_norm(x, layer["attn_norm"], cfg.rms_eps)
            if kind == "kda":
                out, ck, cv = kda(layer, y, ck, cv,
                                  jnp.asarray(index, jnp.int32), table,
                                  w.step_pos, live)
            else:
                attn, ck = L._latent_cached(cfg, y, get, mm, ck, w.step_pos,
                                            table, w.chunk_valid, index)
                with jax.named_scope("layer/attn/out"):
                    out = mm(attn, "o_w", x.dtype)
            x = x + out
        with jax.named_scope("layer/norm"):
            y = L.rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        out, record, chosen = _ffn(cfg, blocks, stacks, number, y, live,
                                   choices, routed)
        leaves = (ck, cv) if kind == "kda" else (ck,)
        return (x + out, *leaves, (record, chosen) if choices else record)

    x, cache, aux = scan_periods_cached(
        cfg.layer_kinds, cfg.num_layers, step, x,
        {kind: blocks[kind] for kind in set(cfg.layer_kinds)}, cache,
        block_tables, head=1 if cfg.first_dense else 0)
    if not all_positions:
        x = cached.gather_last(x, w.gather)
    with jax.named_scope("layer/norm"):
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    with jax.named_scope("head"):
        logits = x @ params["lm_head"].astype(x.dtype)
    records, chosen = aux if choices else (aux, None)
    out = (logits, cache, records) if routing else (logits, cache)
    if choices:
        out += ({"experts": chosen[cfg.first_dense:]},)
    return out


def forward(cfg: KimiLinearConfig, params, input_ids):
    """The uncached forward over whole sequences from position 0 (zero
    states, zero convolution tails), layer by layer: the delta rule token
    by token (``delta_rule.recurrent``), the latent layers expanded
    (``llama._latent_attention``).  ``[B, S, V]``."""
    b, s = input_ids.shape
    x = params["embed"][input_ids].astype(params["embed"].dtype)
    blocks = params["blocks"]
    live = jnp.ones((b, s), bool)
    seen = dict.fromkeys(KINDS, 0)
    p = len(cfg.layer_kinds)
    for number in range(cfg.num_layers):
        kind = cfg.layer_kinds[number % p]
        layer = _at(blocks[kind], seen[kind])
        seen[kind] += 1
        y = L.rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        if kind == "kda":
            x3 = _kda_projections(layer, y)
            ext = jnp.pad(x3, ((0, 0), (cfg.kda_conv - 1, 0), (0, 0)))
            q, k, v, g, beta = _kda_inputs(cfg, layer, y, ext, live)
            o, _ = delta_rule.recurrent(q, k, v, g, beta, jnp.zeros(
                (b, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim)))
            x = x + _kda_output(cfg, layer, y, o)
        else:
            x = x + qmm(L._latent_attention(cfg, layer, y, None, None),
                        layer["o_w"], x.dtype)
        y = L.rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        x = x + _ffn(cfg, blocks, None, number, y, None, False)[0]
    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x @ params["lm_head"].astype(x.dtype)


def init_cache(cfg: KimiLinearConfig, num_blocks: int, block_size: int,
               dtype=jnp.bfloat16, state_rows: Optional[int] = None):
    """The cache of a serving engine (block-paged only): ``latent [L_latent,
    num_blocks, 1, block_size, W]`` (``ops/paged_kv.py`` "The latent kind")
    beside, for ``state_rows`` rows, ``state [L_kda, rows, H, dk, dv]``
    float32 and ``conv [L_kda, rows, 1, K - 1, 3 H dk]`` (``ops/paged_kv.py``
    "The state kind")."""
    if state_rows is None:
        raise NotImplementedError(
            "a model with gated delta-rule layers is served through "
            "init_serving / ServingEngine (init_cache(..., state_rows=)): "
            "the contiguous cache of InferenceEngine.generate has one kind "
            "of state")
    cache = {}
    if cfg.layers_of("latent"):
        cache["latent"] = jnp.zeros(
            (cfg.layers_of("latent"), num_blocks, 1, block_size,
             latent_pool_width(cfg.latent_width)), dtype)
    lk, hd = cfg.layers_of("kda"), cfg.kda_head_dim
    if lk:
        cache["state"] = jnp.zeros((lk, state_rows, cfg.kda_heads, hd, hd),
                                   jnp.float32)
        cache["conv"] = jnp.zeros((lk, state_rows, 1, cfg.kda_conv - 1,
                                   3 * cfg.kda_width), dtype)
    return cache


def build(cfg: Optional[KimiLinearConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or KimiLinearConfig(**overrides)

    def loss_fn(params, batch, rng=None, train=True):
        if train:
            raise NotImplementedError(
                "Kimi Linear is an inference path: the chunked delta rule's "
                "backward is not built")
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logp = jax.nn.log_softmax(
            forward(cfg, params, ids[:, :-1]).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1).mean()

    def apply_fn(params, batch, rng=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return forward(cfg, params, ids)

    hd = cfg.kda_head_dim
    decode_hooks = {
        "init_cache": lambda b, s, dtype=jnp.bfloat16, **kinds: init_cache(
            cfg, b, s, dtype, **kinds),
        "forward_cached": lambda params, ids, cache, pos, lengths=None,
            block_tables=None, all_positions=False, routing=False,
            choices=False:
            forward_cached(cfg, params, ids, cache, pos, lengths,
                           block_tables, all_positions, routing, choices),
        "routing_record": True,
        "max_seq_len": cfg.max_seq_len,
        "supports_lengths": True,
        "supports_paged": True,
        # a rejected draft token has already moved the state: no verify
        # window (the engine refuses speculation by name)
        "supports_verify": False,
        "supports_kv_quant": False,
        "supports_sampling": True,
        **L.latent_hook(cfg),
        # the state kind: leaves indexed by ROW, no block ids, no table
        "state_layers": {
            "layers": cfg.layers_of("kda"), "heads": cfg.kda_heads,
            "key_dim": hd, "value_dim": hd, "conv_taps": cfg.kda_conv - 1,
            "channels": 3 * cfg.kda_width,
            # the prefix of the names the rule's bodies go by
            # (``ops/delta_rule.py``; ``stats()["kv_state"]["kda"]``)
            "bodies": "kda"},
    }
    if cfg.experts_held is not None:
        decode_hooks["experts_held"] = cfg.experts_held
    return ModelSpec(
        init_fn=lambda rng: init_params(cfg, rng), model_config=cfg,
        loss_fn=loss_fn, apply_fn=apply_fn,
        # served on one shard: every leaf whole on every chip
        tp_rules=lambda ap: jax.tree_util.tree_map(lambda _: P(), ap),
        flops_per_token=6.0 * cfg.active_params(),
        decode_hooks=decode_hooks, quant_aware=False,
        name=f"kimi-linear-{cfg.num_layers}l-{cfg.num_experts}e")
