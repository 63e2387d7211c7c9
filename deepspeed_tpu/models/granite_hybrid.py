"""Granite 4.0-H (``ibm-granite/granite-4.0-h-micro``, ``model_type:
granitemoehybrid`` with no routed experts): a hybrid of Mamba-2 state-space
layers and grouped-query attention WITHOUT any positional encoding, over a
dense SwiGLU, served through ``init_serving`` / ``ServingEngine``.

``x0 = embedding_multiplier * E[ids]``; every layer is the sequential
pre-norm RMSNorm block with a scaled residual — ``x += residual_multiplier *
mixer(norm(x))``, then ``x += residual_multiplier * W_o (SiLU(g) * u)``, ``[g |
u] = W_i norm(x)`` — over the pattern ``layer_kinds`` (a period of ``ssm`` and
``full``); ``logits = norm(x) E^T / logits_scaling`` (a tied head).

* an **ssm layer** (``ops/ssd.py``: the Mamba-2 scan, one scalar decay a
  head, ``B`` and ``C`` shared by the heads) keeps no token: its whole past is
  one float32 matrix a head a ROW, ``state [L_ssm, rows, H / g, N, g P]``
  (head-packed, ``ops/ssd.py``), and the last ``ssm_conv - 1`` inputs of its
  short convolution, ``conv [L_ssm, rows, 1, K - 1, channels]``
  (``ops/paged_kv.py`` "The state kind").  ``[z | xBC | dt] = W_in h``;
  ``xBC = SiLU(conv(xBC) + bias)`` (causal, depthwise); ``[x | B | C] = xBC``;
  ``dt = softplus(dt + dt_bias)``, ``a = -exp(a_log)`` a head; the scan gives
  ``y``; the output is ``W_out RMSNorm((y + d_skip x) * SiLU(z))`` — ONE norm
  over all the inner channels, the gate before the norm.
* a **full layer** caches a key and a value a KV head a token in the paged
  pool's ``full`` kind and rotates nothing; its scores are scaled by
  ``attention_multiplier`` (not ``head_dim ** -0.5``).

The layers' weights differ in SHAPE, so ``params["blocks"]`` holds stacks BY
KIND — ``{"ssm": [L_ssm, ...], "full": [L_full, ...]}``, each carrying its
layers' two norms and FFN — and the layer loop is
``cached.scan_periods_cached`` over the period, stacks by kind.

A cached forward takes a window of a ROW's tokens like any other
(``cached.window``); ``block_tables`` is ``{"full": the paged table, "slot":
int32 [B]}`` — the row of the state leaves each row of a prefill call owns (a
decode step's row ``b`` IS row ``b``) — and a prefill window whose base is 0
starts from a ZERO state and a zero convolution tail.  A pad (a token past
``lengths``, an idle decode row) moves neither.  Served on one shard; what
else such a model is refused is ``inference/serving.py``'s to say, by name.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import ssd
from ..runtime.model import ModelSpec
from . import cached
from .cached import live_tokens, qmm, scan_periods_cached
from .llama import rms_norm

PyTree = Any
KINDS = ("ssm", "full")


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    max_seq_len: int = 131072
    num_layers: int = 40
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    #: every layer's dense SwiGLU (the published ``shared_intermediate_size``)
    ffn_size: int = 8192
    rms_eps: float = 1e-5
    #: one period of the layer pattern, ``"ssm"`` | ``"full"`` each
    layer_kinds: tuple = ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    #: taps of the causal depthwise convolution on x | B | C (with a bias)
    ssm_conv: int = 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0

    def __post_init__(self):
        self.layer_kinds = tuple(self.layer_kinds)
        if not self.layer_kinds or set(self.layer_kinds) - set(KINDS):
            raise ValueError(f"layer_kinds={self.layer_kinds!r}: a pattern "
                             f"of {KINDS}")
        if self.num_layers % len(self.layer_kinds):
            raise ValueError(
                f"num_layers={self.num_layers} is not a whole number of "
                f"periods of {len(self.layer_kinds)} layers")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads over "
                             f"{self.num_kv_heads} KV heads")
        if self.ssm_conv < 2:
            raise ValueError(f"ssm_conv={self.ssm_conv}: at least 2 taps")

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """x | B | C (one group of B and C)."""
        return self.ssm_inner + 2 * self.ssm_state

    def layers_of(self, kind: str) -> int:
        return self.num_layers // len(self.layer_kinds) \
            * self.layer_kinds.count(kind)

    @staticmethod
    def granite_4_0_h_micro() -> "GraniteHybridConfig":
        """ibm-granite/granite-4.0-h-micro as published: every default."""
        return GraniteHybridConfig()

    def num_params(self) -> int:
        shapes = {kind: layer_shapes(self, kind) for kind in KINDS}
        return self.vocab_size * self.hidden_size + self.hidden_size + sum(
            self.layers_of(kind) * sum(math.prod(s) for s in shape.values())
            for kind, shape in shapes.items())


# ------------------------------------------------------------------ parameters
def layer_shapes(cfg: GraniteHybridConfig, kind: str):
    """One layer's leaves by name (a projection is stored ``[in, out]``;
    ``conv_w [K, channels]`` holds the taps, tap ``K - 1`` on the current
    token; ``ffn_in_w`` is gate | up)."""
    d, f = cfg.hidden_size, cfg.ffn_size
    ffn = {"attn_norm": (d,), "mlp_norm": (d,), "ffn_in_w": (d, 2 * f),
           "ffn_out_w": (f, d)}
    if kind == "full":
        hd = cfg.head_dim
        return {"q_w": (d, cfg.num_heads * hd),
                "k_w": (d, cfg.num_kv_heads * hd),
                "v_w": (d, cfg.num_kv_heads * hd),
                "o_w": (cfg.num_heads * hd, d), **ffn}
    inner, h = cfg.ssm_inner, cfg.ssm_heads
    return {"in_w": (d, inner + cfg.conv_channels + h),
            "conv_w": (cfg.ssm_conv, cfg.conv_channels),
            "conv_b": (cfg.conv_channels,), "dt_bias": (h,), "a_log": (h,),
            "d_skip": (h,), "gate_norm": (inner,), "out_w": (inner, d), **ffn}


def init_params(cfg: GraniteHybridConfig, rng) -> PyTree:
    """Seeded parameters: the token table N(0, 0.02); a matrix N(0, 0.9 /
    sqrt(fan_in)) — the published code's 0.02 at the published hidden size,
    kept in proportion at any other so that a narrow model's activations,
    steps and states are the size the published one's are (the scaled
    residuals stand in for a scaling with the depth) —, the convolution's
    taps and bias U(-1/sqrt(K), 1/sqrt(K)) (a depthwise ``Conv1d``'s
    default), ``a_log = ln U(1, 16)``, ``dt_bias`` the inverse softplus of a
    step log-uniform in [0.001, 0.1] (the published code's initialisation),
    ``d_skip = 1``."""
    keys = iter(jax.random.split(rng, 64))

    def normal(shape, s):
        return (jax.random.normal(next(keys), shape) * s).astype(jnp.float32)

    def leaf(name, shape):
        if name in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(cfg.ssm_conv)
            return jax.random.uniform(next(keys), shape, jnp.float32,
                                      -bound, bound)
        if name == "a_log":
            return jnp.log(jax.random.uniform(next(keys), shape, jnp.float32,
                                              1.0, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                next(keys), shape, jnp.float32, math.log(1e-3),
                math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name.endswith("_norm") or name == "d_skip":
            return jnp.ones(shape)
        return normal(shape, 0.9 / math.sqrt(shape[-2]))

    stacks = {kind: {name: leaf(name, (cfg.layers_of(kind),) + shape)
                     for name, shape in layer_shapes(cfg, kind).items()}
              for kind in KINDS if cfg.layers_of(kind)}
    return {"embed": normal((cfg.vocab_size, cfg.hidden_size), 0.02),
            "blocks": stacks, "final_norm": jnp.ones((cfg.hidden_size,))}


# ------------------------------------------------------------------- ssm layer
def _ssm_inputs(cfg: GraniteHybridConfig, layer, proj, tail, live):
    """What the scan takes, from ``proj [B, T, inner + channels + H]`` (``z |
    xBC | dt`` of the normed input) and the row's convolution ``tail [B, K -
    1, channels]``: ``(z [B, T, inner], ext [B, K - 1 + T, channels], x [B,
    T, H, P], dt [B, T, H], b, c [B, T, N])``, float32 but ``z`` and ``ext``;
    where ``live [B, T]`` is false ``dt`` is 0 (the token moves no state)."""
    bsz, t, _ = proj.shape
    inner, ch, n = cfg.ssm_inner, cfg.conv_channels, cfg.ssm_state
    z, xbc, dt = proj[..., :inner], proj[..., inner:inner + ch], \
        proj[..., inner + ch:]
    with jax.named_scope("layer/state/conv"):
        ext = jnp.concatenate([tail, xbc.astype(tail.dtype)], axis=1)
        taps = layer["conv_w"].astype(jnp.float32)
        conv = jax.nn.silu(sum(ext[:, j:j + t].astype(jnp.float32) * taps[j]
                               for j in range(cfg.ssm_conv))
                           + layer["conv_b"].astype(jnp.float32))
    with jax.named_scope("layer/state/gate"):
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + layer["dt_bias"].astype(jnp.float32))
    return (z, ext,
            conv[..., :inner].reshape(bsz, t, cfg.ssm_heads, cfg.ssm_head_dim),
            jnp.where(live[..., None], dt, 0.0),
            conv[..., inner:inner + n], conv[..., inner + n:])


def _ssm_output(cfg: GraniteHybridConfig, layer, z, x, y, dtype):
    """``W_out RMSNorm((y + d_skip x) * SiLU(z); gate_norm)`` from the
    scan's float32 ``y [B, T, H, P]``: one norm over all inner channels."""
    bsz, t = y.shape[:2]
    with jax.named_scope("layer/state/gate"):
        y = y + layer["d_skip"].astype(jnp.float32)[:, None] * x
        g = y.reshape(bsz, t, cfg.ssm_inner) \
            * jax.nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + cfg.rms_eps) * layer["gate_norm"].astype(
                                  jnp.float32)
    with jax.named_scope("layer/attn/out"):
        return qmm(g.astype(dtype), layer["out_w"], dtype)


@jax.named_scope("layer/attn/qkv")
def _in_proj(layer, y):
    """``z | xBC | dt`` of ``y`` (the barrier: the later head split moves
    this product, not the weight — ``llama._attend_cached``)."""
    return jax.lax.optimization_barrier(qmm(y, layer["in_w"]))


def _a(layer):
    return -jnp.exp(layer["a_log"].astype(jnp.float32))


def _ssm_cached(cfg: GraniteHybridConfig, layer, y, state, conv, index, slot,
                base, live):
    """An ssm layer's window against the row-indexed leaves (module
    docstring): ``-> (mixer output [B, T, d], state, conv)``."""
    taps = cfg.ssm_conv - 1
    proj = _in_proj(layer, y)
    if slot is None:
        # a decode step (one token a row): row b is row b of the leaves
        with jax.named_scope("layer/attn/kv_write"):
            tail = jax.lax.dynamic_index_in_dim(conv, index,
                                                keepdims=False)[:, 0]
        z, ext, x, dt, b, c = _ssm_inputs(cfg, layer, proj, tail, live)
        with jax.named_scope("layer/attn/kv_write"):
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, jnp.where(live[:, :, None], ext[:, 1:], tail)[:, None],
                index, 0)
        o, state = ssd.step(x[:, 0], dt[:, 0], _a(layer), b[:, 0], c[:, 0],
                            state, index)
        return _ssm_output(cfg, layer, z, x, o[:, None], y.dtype), state, conv
    # a prefill window: the rows' leaves by ``slot`` (a pad row's is out of
    # range: read clamped, written nowhere); a window at base 0 starts from
    # nothing
    with jax.named_scope("layer/attn/kv_write"):
        rows = jnp.clip(slot, 0, state.shape[1] - 1)
        fresh = (jnp.asarray(base, jnp.int32) == 0).reshape(-1)
        tail = jnp.where(fresh[:, None, None], 0, conv[index, rows, 0])
        s0 = jnp.where(fresh[:, None, None, None], 0.0, state[index, rows])
    z, ext, x, dt, b, c = _ssm_inputs(cfg, layer, proj, tail, live)
    with jax.named_scope("layer/attn/kv_write"):
        valid = live.sum(axis=1, dtype=jnp.int32)
        tail = jnp.take_along_axis(
            ext, (valid[:, None] + jnp.arange(taps))[:, :, None], axis=1)
    o, s1 = ssd.chunked(x, dt, _a(layer), b, c, s0)
    with jax.named_scope("layer/attn/kv_write"):
        state = state.at[index, slot].set(s1, mode="drop")
        conv = conv.at[index, slot, 0].set(tail, mode="drop")
    return _ssm_output(cfg, layer, z, x, o, y.dtype), state, conv


# ------------------------------------------------------------------ full layer
@jax.named_scope("layer/attn/qkv")
def _qkv(cfg: GraniteHybridConfig, layer, y):
    """``q [B, H, T, hd]``, ``k``, ``v`` ``[B, HKV, T, hd]`` of the normed
    input, unrotated (the barrier: ``llama._attend_cached``)."""
    bsz, t, _ = y.shape
    q, k, v = jax.lax.optimization_barrier(
        (qmm(y, layer["q_w"]), qmm(y, layer["k_w"]), qmm(y, layer["v_w"])))
    split = lambda a, h: a.reshape(bsz, t, h, cfg.head_dim) \
        .transpose(0, 2, 1, 3)
    return split(q, cfg.num_heads), split(k, cfg.num_kv_heads), \
        split(v, cfg.num_kv_heads)


@jax.named_scope("layer/attn/out")
def _merge(cfg: GraniteHybridConfig, layer, attn, dtype):
    bsz, _, t, _ = attn.shape
    return qmm(attn.transpose(0, 2, 1, 3).reshape(
        bsz, t, cfg.num_heads * cfg.head_dim), layer["o_w"], dtype)


def _ffn(cfg: GraniteHybridConfig, layer, x):
    """``x + residual_multiplier * SwiGLU(norm(x))``."""
    with jax.named_scope("layer/mlp"):
        with jax.named_scope("layer/norm"):
            y = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        gu = qmm(y, layer["ffn_in_w"])
        out = qmm(jax.nn.silu(gu[..., :cfg.ffn_size])
                  * gu[..., cfg.ffn_size:], layer["ffn_out_w"], x.dtype)
        return x + cfg.residual_multiplier * out


# --------------------------------------------------------------------- forward
def _head(cfg: GraniteHybridConfig, params, x):
    with jax.named_scope("layer/norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    with jax.named_scope("head"):
        logits = jnp.einsum("...d,vd->...v", x,
                            params["embed"].astype(x.dtype))
        return logits / cfg.logits_scaling


@jax.named_scope("embed")
def _embed(cfg: GraniteHybridConfig, params, input_ids):
    return params["embed"][input_ids] * jnp.asarray(
        cfg.embedding_multiplier, params["embed"].dtype)


def forward_cached(cfg: GraniteHybridConfig, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False):
    """The cached forward (module docstring; ``cached.window`` has the
    contract of ``lengths`` / ``block_tables`` / ``all_positions``)."""
    if not isinstance(block_tables, dict):
        raise NotImplementedError(
            "a model with state-space layers is served through init_serving "
            "/ ServingEngine, whose cache holds a recurrent state a row "
            "beside the block-paged pool (block_tables {'full', 'slot'}); "
            "the contiguous cache of InferenceEngine.generate has one kind "
            "of state")
    w = cached.window(input_ids, pos, lengths, block_tables["full"])
    live = live_tokens(input_ids, lengths, block_tables)
    x = _embed(cfg, params, input_ids)
    res = cfg.residual_multiplier

    # ONE trace and one lowered function a program for the layers that
    # repeat: a period writes its layers out, and its nine ssm layers are
    # the same computation at the same shapes (``kimi_linear.forward_cached``;
    # no donation of their own: they are calls inside the engine's program,
    # whose jit donates the cache)
    @functools.partial(jax.jit, donate_argnums=())
    def ssm_layer(x, layer, state, conv, index, slot, base, live):
        with jax.named_scope("layer/attn"):
            with jax.named_scope("layer/norm"):
                y = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
            out, state, conv = _ssm_cached(cfg, layer, y, state, conv, index,
                                           slot, base, live)
            x = x + res * out
        return _ffn(cfg, layer, x), state, conv

    def step(x, layer, ck, cv, index, table, kind, number):
        del number
        index = jnp.asarray(index, jnp.int32)
        if kind == "ssm":
            x, ck, cv = ssm_layer(x, layer, ck, cv, index, table,
                                  w.step_pos, live)
        else:
            with jax.named_scope("layer/attn"):
                with jax.named_scope("layer/norm"):
                    y = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
                attn, ck, cv = cached.cached_attention(
                    *_qkv(cfg, layer, y), ck, cv, w.step_pos, table,
                    w.chunk_valid, index, sm_scale=cfg.attention_multiplier)
                x = x + res * _merge(cfg, layer, attn, x.dtype)
            x = _ffn(cfg, layer, x)
        return x, ck, cv, jnp.zeros((), jnp.int32)

    x, cache, _ = scan_periods_cached(
        cfg.layer_kinds, cfg.num_layers, step, x,
        {kind: params["blocks"][kind] for kind in set(cfg.layer_kinds)},
        cache, block_tables)
    if not all_positions:
        x = cached.gather_last(x, w.gather)
    return _head(cfg, params, x), cache


def forward(cfg: GraniteHybridConfig, params, input_ids):
    """The uncached forward over whole sequences from position 0 (zero
    states, zero convolution tails), layer by layer: the scan token by token
    (``ssd.recurrent``), the full layers by plain causal attention.  ``[B,
    S, V]``."""
    bsz, s = input_ids.shape
    x = _embed(cfg, params, input_ids)
    live = jnp.ones((bsz, s), bool)
    mask = jnp.tril(jnp.ones((s, s), bool))
    seen = dict.fromkeys(KINDS, 0)
    rep = cfg.num_heads // cfg.num_kv_heads
    for number in range(cfg.num_layers):
        kind = cfg.layer_kinds[number % len(cfg.layer_kinds)]
        layer = jax.tree_util.tree_map(lambda a: a[seen[kind]],
                                       params["blocks"][kind])
        seen[kind] += 1
        y = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        if kind == "ssm":
            tail = jnp.zeros((bsz, cfg.ssm_conv - 1, cfg.conv_channels),
                             y.dtype)
            z, _, xs, dt, b, c = _ssm_inputs(cfg, layer, _in_proj(layer, y),
                                             tail, live)
            o, _ = ssd.recurrent(xs, dt, _a(layer), b, c, jnp.zeros(
                (bsz, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)))
            out = _ssm_output(cfg, layer, z, xs, o, y.dtype)
        else:
            q, k, v = _qkv(cfg, layer, y)
            k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) \
                * cfg.attention_multiplier
            probs = jax.nn.softmax(jnp.where(mask, scores, -1e9), axis=-1)
            out = _merge(cfg, layer, jnp.einsum(
                "bhqk,bhkd->bhqd", probs.astype(q.dtype), v), y.dtype)
        x = _ffn(cfg, layer, x + cfg.residual_multiplier * out)
    return _head(cfg, params, x)


def init_cache(cfg: GraniteHybridConfig, num_blocks: int, block_size: int,
               dtype=jnp.bfloat16, state_rows: Optional[int] = None):
    """The cache of a serving engine (block-paged only): ``k``, ``v``
    ``[L_full, num_blocks, HKV, block_size, hd]`` beside, for ``state_rows``
    rows, ``state [L_ssm, rows, H / g, N, g P]`` float32 (head-packed:
    ``ops/ssd.py``) and ``conv [L_ssm, rows, 1, K - 1, channels]``
    (``ops/paged_kv.py`` "The state kind")."""
    if state_rows is None:
        raise NotImplementedError(
            "a model with state-space layers is served through init_serving "
            "/ ServingEngine (init_cache(..., state_rows=)): the contiguous "
            "cache of InferenceEngine.generate has one kind of state")
    cache = {}
    if cfg.layers_of("full"):
        cache.update(cached.init_kv_cache(
            cfg.layers_of("full"), num_blocks, cfg.num_kv_heads, block_size,
            cfg.head_dim, dtype))
    ls = cfg.layers_of("ssm")
    if ls:
        cache["state"] = jnp.zeros(
            (ls, state_rows) + ssd.packed_shape(
                cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
        cache["conv"] = jnp.zeros(
            (ls, state_rows, 1, cfg.ssm_conv - 1, cfg.conv_channels), dtype)
    return cache


def build(cfg: Optional[GraniteHybridConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or GraniteHybridConfig(**overrides)

    def loss_fn(params, batch, rng=None, train=True):
        if train:
            raise NotImplementedError(
                "Granite 4.0-H is an inference path: the chunked state-space "
                "scan's backward is not built")
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logp = jax.nn.log_softmax(
            forward(cfg, params, ids[:, :-1]).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1).mean()

    def apply_fn(params, batch, rng=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return forward(cfg, params, ids)

    decode_hooks = {
        "init_cache": lambda b, s, dtype=jnp.bfloat16, **kinds: init_cache(
            cfg, b, s, dtype, **kinds),
        "forward_cached": lambda params, ids, cache, pos, lengths=None,
            block_tables=None, all_positions=False:
            forward_cached(cfg, params, ids, cache, pos, lengths,
                           block_tables, all_positions),
        "max_seq_len": cfg.max_seq_len,
        "supports_lengths": True,
        "supports_paged": True,
        # a rejected draft token has already moved the state: no verify
        # window (the engine refuses speculation by name)
        "supports_verify": False,
        "supports_kv_quant": False,
        "supports_sampling": True,
        # the state kind: leaves indexed by ROW, no block ids, no table;
        # ``bodies``: the prefix of the names its scan's bodies go by
        "state_layers": {
            "layers": cfg.layers_of("ssm"), "heads": cfg.ssm_heads,
            "key_dim": cfg.ssm_head_dim, "value_dim": cfg.ssm_state,
            "conv_taps": cfg.ssm_conv - 1, "channels": cfg.conv_channels,
            "bodies": "ssd"},
    }
    active = cfg.num_params()
    return ModelSpec(
        init_fn=lambda rng: init_params(cfg, rng), model_config=cfg,
        loss_fn=loss_fn, apply_fn=apply_fn,
        # served on one shard: every leaf whole on every chip
        tp_rules=lambda ap: jax.tree_util.tree_map(lambda _: P(), ap),
        flops_per_token=6.0 * active,
        decode_hooks=decode_hooks, quant_aware=False,
        name=f"granite-hybrid-{cfg.num_layers}l")
