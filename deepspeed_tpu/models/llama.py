"""Llama family (Llama-2/3), TPU-native.

Driver configs #2/#3 (Llama-3-8B ZeRO-3, Llama-3-70B 3D).
Same structural choices as gpt2.py — stacked [L, ...] blocks + ``lax.scan``
(ZeRO-3 gathers one layer ahead), optional remat, Megatron-style TP specs,
pipeline hooks — with the Llama specifics: RMSNorm, rotary embeddings, grouped-
query attention (GQA), SwiGLU MLP, no biases, untied LM head.

The reference serves these archs through ``module_inject`` policy injection onto
HF modules; here the model IS the TPU-optimised implementation.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.sp_attention import shard_seq
from ..parallel.topology import TP_AXIS
from ..runtime.model import ModelSpec
from ..runtime.remat import checkpoint_block
from ..utils.platform import on_tpu
from . import cached          # (``window`` names a layer's reach in this file)
from .cached import (cached_attention, decode_over_layers, dequant_resident,
                     gather_last, init_kv_cache, layer_accessors, qmm,
                     scan_layers_cached, scan_periods_cached)

PyTree = Any


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 128256
    max_seq_len: int = 8192
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    hidden_size: int = 4096
    ffn_size: int = 14336
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    #: q/k-norm, an RMSNorm of the projected queries and keys before RoPE:
    #: ``True`` (OLMoE) is ONE norm over all ``heads * head_dim`` projected
    #: features, before the split into heads; ``"head"`` (Qwen3) is one
    #: ``[head_dim]`` scale applied to each head's features after the split
    qk_norm: Any = False
    remat: bool = True
    use_flash: Optional[bool] = None
    #: ZeRO-3 liveness: gather this many layers per scan step (engine sets
    #: it from stage3_prefetch_bucket_size / stage3_max_live_parameters)
    scan_group_size: int = 1
    #: the blocks' shardings when the engine pipelines the layer loop
    #: (``overlap_comm`` at ZeRO-3, ``liveness.scan_layers_prefetched``)
    scan_prefetch: Any = None
    #: sequence-parallel attention impl when mesh sp>1: auto|ulysses|ring
    sp_impl: str = "auto"
    #: width of one head; ``None`` is ``hidden_size // num_heads``, read
    #: through :attr:`head_dim` (so that ``dataclasses.replace`` of the
    #: hidden size or the head count carries no stale width forward)
    head_width: Optional[int] = None
    #: the block norms: ``"rms"`` or ``"layernorm"`` (Cohere's: the mean
    #: subtracted, a scale and no bias), both at ``rms_eps``
    norm: str = "rms"
    #: ``True``: attention and the FFN both read ONE norm of the block's
    #: input and both add to the residual (``x + a + m``; no ``mlp_norm``)
    parallel_block: bool = False
    #: rotary pairs ``(2i, 2i + 1)`` (GPT-J's convention) instead of
    #: ``(i, i + head_dim / 2)``
    rope_interleaved: bool = False
    #: one period of the layer pattern, ``"sliding"`` | ``"full"`` each
    #: (``()``: every layer attends everything, rotated).  A ``"sliding"``
    #: layer rotates q and k and a query keeps its ``sliding_window`` newest
    #: keys, itself included; a ``"full"`` layer of a patterned model
    #: attends every key and takes NO rotation.  ``num_layers`` is a whole
    #: number of periods.  ``"latent"`` (a latent layer INSIDE a pattern)
    #: and ``"kda"`` (a gated delta-rule layer, whose state is a matrix a
    #: head a ROW and no cache a token) are ``models/kimi_linear.py``'s,
    #: ``"latent_indexed"`` (a latent layer under a learned selection) and
    #: ``"latent_sliding"`` (one of its own widths under a window)
    #: ``models/dots3.py``'s: their weights are stacked BY KIND
    #: (:data:`BY_KIND`), and such a family says itself how its layers
    #: divide into periods.
    layer_kinds: tuple = ()
    sliding_window: int = 0
    #: the head is the token table transposed (no ``lm_head`` leaf)
    tie_embeddings: bool = False
    #: LATENT ATTENTION (MLA; ``kv_lora_rank > 0``, else today's ``q_w / k_w
    #: / v_w``): queries through a low-rank pair with a norm between
    #: (``q_a_w [d, q_lora_rank]``, ``q_a_norm``, ``q_b_w``), keys and values
    #: through ONE joint down-projection ``kv_a_w [d, kv_lora_rank +
    #: qk_rope_dim]`` whose first ``kv_lora_rank`` outputs are normed
    #: (``kv_a_norm``: the latent ``c``) and whose last ``qk_rope_dim`` are
    #: the one rotated key every head shares; ``kv_b_w [kv_lora_rank, H *
    #: (qk_nope_dim + v_dim)]`` expands ``c`` to each head's unrotated key
    #: part and its value.  A head's score width is ``qk_nope_dim +
    #: qk_rope_dim`` (= :attr:`head_dim`), its value width ``v_head_dim``
    #: (0: ``head_dim``) — kept apart.  The cache holds ``[c | k_r]`` a
    #: token a layer and nothing else (``ops/paged_kv.py`` "The latent kind")
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    #: rotary scaling: ``None``, or YaRN's six numbers ``{"factor",
    #: "original_max_position_embeddings", "beta_fast", "beta_slow",
    #: "mscale", "mscale_all_dim"}`` (:func:`rope_inv_freq`)
    rope_scaling: Optional[dict] = None
    #: position-dependent query temperature ``(beta, period)``: the query at
    #: position ``p`` is multiplied by ``1 + beta * ln(1 + floor(p /
    #: period))``; ``None``: none
    query_temperature: Optional[tuple] = None
    #: a latent layer's ``qk_rope_dim`` values of q and of the shared key
    #: are rotated (``False``: used as they are, NoPE)
    latent_rope: bool = True
    #: the weights are stacked BY KIND of layer, not ``[L, ...]`` (set by
    #: the family whose layers differ in shape)
    by_kind: ClassVar[bool] = False
    #: ``num_layers`` is a whole number of periods of ``layer_kinds`` (a
    #: family that says itself how its layers divide sets this False)
    whole_periods: ClassVar[bool] = True

    def __post_init__(self):
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm={self.qk_norm!r}: False, True (one "
                             "norm over all heads' features) or 'head'")
        if self.norm not in ("rms", "layernorm"):
            raise ValueError(f"norm={self.norm!r}: 'rms' or 'layernorm'")
        self.layer_kinds = tuple(self.layer_kinds)
        if any(k not in ("sliding", "full") + BY_KIND
               for k in self.layer_kinds):
            raise ValueError(f"layer_kinds={self.layer_kinds!r}: 'sliding', "
                             f"'full' or one of {BY_KIND} each")
        if set(BY_KIND) & set(self.layer_kinds) and (
                {"sliding", "full"} & set(self.layer_kinds)
                or not self.by_kind):
            raise ValueError(
                f"layer_kinds={self.layer_kinds!r}: {BY_KIND} layers have "
                "weights of their own shapes, stacked by kind "
                "(models/kimi_linear.py, models/dots3.py), and are not "
                "mixed with 'sliding' or 'full' ones, whose K / V weights "
                "lie in one [L, ...] stack")
        if self.layer_kinds and self.num_layers % len(self.layer_kinds) \
                and self.whole_periods:
            raise ValueError(
                f"num_layers={self.num_layers} is not a whole number of "
                f"periods of {len(self.layer_kinds)} layers")
        if "sliding" in self.layer_kinds and self.sliding_window < 1:
            raise ValueError("sliding layers need sliding_window >= 1")
        if self.rope_scaling is not None:
            missing = [k for k in YARN_KEYS if k not in self.rope_scaling]
            if missing:
                raise ValueError(f"rope_scaling (YaRN) lacks {missing}; it "
                                 f"holds {list(YARN_KEYS)}")
        if self.query_temperature is not None:
            self.query_temperature = tuple(self.query_temperature)
        if self.latent:
            if min(self.qk_nope_dim, self.qk_rope_dim) < 1 \
                    or self.q_lora_rank < 0 or self.qk_rope_dim % 2:
                raise ValueError(
                    "latent attention (kv_lora_rank > 0) needs qk_nope_dim, "
                    "an even qk_rope_dim and q_lora_rank >= 0 (0: a "
                    "full-rank q_w)")
            if self.head_dim != self.qk_nope_dim + self.qk_rope_dim:
                raise ValueError(
                    f"head_dim {self.head_dim} is not qk_nope_dim + "
                    f"qk_rope_dim ({self.qk_nope_dim} + {self.qk_rope_dim}):"
                    " pass head_width")
            if self.qk_norm or (self.layer_kinds
                                and not set(self.layer_kinds) <= set(BY_KIND)):
                raise ValueError(
                    "latent attention is built without qk_norm, and inside "
                    "a layer pattern only as one of the kinds whose weights "
                    f"are stacked by kind {BY_KIND}")

    @property
    def head_dim(self) -> int:
        return self.head_width or self.hidden_size // self.num_heads

    @property
    def latent(self) -> bool:
        """Latent attention (MLA): the cache holds one latent a token."""
        return self.kv_lora_rank > 0

    @property
    def rope_dim(self) -> int:
        """The rotated width of a head: all of it, or ``qk_rope_dim``."""
        return self.qk_rope_dim if self.latent else self.head_dim

    @property
    def value_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def latent_width(self) -> int:
        """Values cached a token a layer: ``[c | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_dim

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        return LlamaConfig(num_layers=80, num_heads=64, num_kv_heads=8,
                           hidden_size=8192, ffn_size=28672)

    @staticmethod
    def tiny(vocab_size: int = 512, max_seq_len: int = 128) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab_size, max_seq_len=max_seq_len,
                           num_layers=2, num_heads=4, num_kv_heads=2,
                           hidden_size=64, ffn_size=128, rope_theta=10000.0,
                           remat=False)

    def num_params(self) -> int:
        d, f, l, v = self.hidden_size, self.ffn_size, self.num_layers, \
            self.vocab_size
        hd = self.head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd + \
            self.num_heads * hd * d
        if self.latent:
            attn = sum(math.prod(shape) for shape in
                       latent_shapes(self).values())
        mlp = 3 * d * f
        norms = (1 if self.parallel_block else 2) * d \
            + sum(qk_norm_widths(self))
        head = 0 if self.tie_embeddings else d * v
        return v * d + l * (attn + mlp + norms) + d + head


#: the layer kinds whose weights are stacked BY KIND (``LlamaConfig.by_kind``):
#: a latent layer inside a pattern, a gated delta-rule layer
#: (``models/kimi_linear.py``), a latent layer under a learned selection and
#: one under a window (``models/dots3.py``)
BY_KIND = ("latent", "kda", "latent_indexed", "latent_sliding")
#: what ``LlamaConfig.rope_scaling`` holds
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


def latent_shapes(cfg: LlamaConfig):
    """One layer's attention leaves of a latent model, by name (module of
    the published names: ``q_a_proj``, ``q_a_layernorm``, ``q_b_proj``,
    ``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj``, ``o_proj``;
    a projection is stored ``[in, out]``)."""
    d, h = cfg.hidden_size, cfg.num_heads
    queries = {"q_a_w": (d, cfg.q_lora_rank), "q_a_norm": (cfg.q_lora_rank,),
               "q_b_w": (cfg.q_lora_rank, h * cfg.head_dim)} \
        if cfg.q_lora_rank else {"q_w": (d, h * cfg.head_dim)}
    return {**queries,
            "kv_a_w": (d, cfg.latent_width),
            "kv_a_norm": (cfg.kv_lora_rank,),
            "kv_b_w": (cfg.kv_lora_rank,
                       h * (cfg.qk_nope_dim + cfg.value_dim)),
            "o_w": (h * cfg.value_dim, d)}


def qk_norm_widths(cfg: LlamaConfig):
    """Widths of the (q_norm, k_norm) scales: none without q/k-norm, every
    projected feature for ``True``, one head's for ``"head"``."""
    if not cfg.qk_norm:
        return ()
    if cfg.qk_norm == "head":
        return (cfg.head_dim, cfg.head_dim)
    return (cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim)


def qk_normed(cfg: LlamaConfig, q, k, get):
    """q/k-norm of the projections ``q [..., H*hd]`` / ``k [..., HKV*hd]``
    as ``cfg.qk_norm`` says; ``get(name)`` gives the layer's scales."""
    if not cfg.qk_norm:
        return q, k
    if cfg.qk_norm == "head":
        hd = cfg.head_dim
        return tuple(
            rms_norm(x.reshape(x.shape[:-1] + (-1, hd)), get(name),
                     cfg.rms_eps).reshape(x.shape)
            for x, name in ((q, "q_norm"), (k, "k_norm")))
    # over ALL heads' features; under GSPMD tp the mean is global (XLA
    # reduces the sum of squares over the tp axis)
    return (rms_norm(q, get("q_norm"), cfg.rms_eps),
            rms_norm(k, get("k_norm"), cfg.rms_eps))


def init_params(cfg: LlamaConfig, rng) -> PyTree:
    d, f, l = cfg.hidden_size, cfg.ffn_size, cfg.num_layers
    hq = cfg.num_heads * cfg.head_dim
    hkv = cfg.num_kv_heads * cfg.head_dim
    keys = jax.random.split(rng, 9)
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(jnp.float32)

    params = {
        "embed": normal(keys[0], (cfg.vocab_size, d)),
        "blocks": {
            "attn_norm": jnp.ones((l, d)),
            "q_w": normal(keys[1], (l, d, hq)),
            "k_w": normal(keys[2], (l, d, hkv)),
            "v_w": normal(keys[3], (l, d, hkv)),
            "o_w": normal(keys[4], (l, hq, d), std / math.sqrt(2 * l)),
            "mlp_norm": jnp.ones((l, d)),
            "w1": normal(keys[5], (l, d, f)),
            "w3": normal(keys[6], (l, d, f)),
            "w2": normal(keys[7], (l, f, d), std / math.sqrt(2 * l)),
        },
        "final_norm": jnp.ones((d,)),
        "lm_head": normal(keys[8], (d, cfg.vocab_size)),
    }
    for name, width in zip(("q_norm", "k_norm"), qk_norm_widths(cfg)):
        params["blocks"][name] = jnp.ones((l, width))
    if cfg.latent:
        blocks = params["blocks"]
        for name in ("q_w", "k_w", "v_w"):
            del blocks[name]
        shapes = latent_shapes(cfg)
        lkeys = jax.random.split(jax.random.fold_in(rng, 17), len(shapes))
        for key, (name, shape) in zip(lkeys, shapes.items()):
            if name.endswith("_norm"):
                blocks[name] = jnp.ones((l,) + shape)
            else:
                blocks[name] = normal(
                    key, (l,) + shape,
                    std / math.sqrt(2 * l) if name == "o_w" else std)
    if cfg.parallel_block:
        del params["blocks"]["mlp_norm"]
    if cfg.tie_embeddings:
        del params["lm_head"]
    return params


def rms_norm(x, scale, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def layer_norm(x, scale, eps: float = 1e-5):
    """Cohere's LayerNorm: the mean subtracted, a scale, no bias."""
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


@jax.named_scope("layer/norm")
def block_norm(cfg: LlamaConfig, x, scale):
    """The norm ``cfg.norm`` names, at ``cfg.rms_eps``."""
    if cfg.norm == "layernorm":
        return layer_norm(x, scale, cfg.rms_eps)
    return rms_norm(x, scale, cfg.rms_eps)


@jax.named_scope("head")
def head_logits(cfg: LlamaConfig, params, x):
    """The final norm's output times the head — the token table itself for
    ``tie_embeddings``."""
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x,
                          params["embed"].astype(x.dtype))
    return x @ params["lm_head"].astype(x.dtype)


def kind_of(cfg: LlamaConfig, kind):
    """``(rotated, window)`` of a layer of ``kind`` (``None``: a model
    without a pattern)."""
    if kind is None:
        return True, 0
    return kind == "sliding", cfg.sliding_window if kind == "sliding" else 0


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg: LlamaConfig, dim: int):
    """Inverse frequencies ``[dim/2]`` of a rotation over ``dim`` values:
    ``theta^(-2i/dim)``, or under ``cfg.rope_scaling`` YaRN's blend of that
    (extrapolation) with ``1 / (factor * theta^(2i/dim))`` (interpolation)
    along a linear ramp between the two correction dimensions (HF
    ``_compute_yarn_parameters``, bounds truncated to whole dimensions)."""
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, dim, 2,
                                                    dtype=jnp.float32) / dim))
    if cfg.rope_scaling is None:
        return inv_freq
    y = cfg.rope_scaling
    factor = float(y["factor"])

    def correction_dim(rotations):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)


def rope_attention_factor(cfg: LlamaConfig) -> float:
    """What YaRN multiplies cos and sin by: ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)`` (1 without scaling)."""
    y = cfg.rope_scaling
    if y is None:
        return 1.0
    return _yarn_mscale(y["factor"], y["mscale"]) \
        / _yarn_mscale(y["factor"], y["mscale_all_dim"])


def _cos_sin(cfg: LlamaConfig, angles):
    f = rope_attention_factor(cfg)
    if f == 1.0:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * f, jnp.sin(angles) * f


def latent_scale(cfg: LlamaConfig) -> float:
    """The softmax scale of a latent model: ``1 / sqrt(head_dim)``, times
    ``m^2`` under YaRN with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``
    (DeepSeek-V3's convention for these keys)."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    y = cfg.rope_scaling
    if y is not None and y["mscale_all_dim"]:
        scale *= _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def query_temperature(cfg: LlamaConfig, positions):
    """float32 ``t(p) = 1 + beta * ln(1 + floor(p / period))`` at integer
    ``positions`` (``cfg.query_temperature``)."""
    beta, period = cfg.query_temperature
    return 1.0 + beta * jnp.log1p(
        (jnp.asarray(positions, jnp.int32) // int(period)).astype(jnp.float32))


def rope_angles(cfg: LlamaConfig, seq_len: int, offset: int = 0,
                dim: Optional[int] = None):
    """cos / sin ``[S, dim/2]`` (``dim``: the rotated width, a head's by
    default — of a latent model its ``qk_rope_dim``)."""
    hd = dim or cfg.rope_dim
    inv_freq = rope_inv_freq(cfg, hd)
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)
    angles = pos[:, None] * inv_freq[None, :]          # [S, hd/2]
    return _cos_sin(cfg, angles)


def apply_rope(x, cos, sin, interleaved: bool = False):
    """x: [B, H, S, hd]; rotate pairs (HF half-split convention; with
    ``interleaved`` the pairs are ``(2i, 2i + 1)``).

    ``cos``/``sin`` are [S, hd/2] (shared across the batch) or [B, S, hd/2]
    (per-sequence positions — continuous-batching slots each sit at their
    own decode offset).

    Rotation math runs in fp32 (cos/sin tables are fp32) but the result is
    cast back to x's dtype so bf16 activations stay bf16 through the block —
    scan-over-layers carries require a fixed dtype, and keeping the residual
    stream in bf16 is what makes the MXU path fast.
    """
    hd = x.shape[-1]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
    else:
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    if cos.ndim == 3:
        c = cos[:, None, :, :]
        s = sin[:, None, :, :]
    else:
        c = cos[None, None, :, :]
        s = sin[None, None, :, :]
    if interleaved:
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                        axis=-1).reshape(x.shape)
    else:
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _attention(cfg: LlamaConfig, q, k, v, window: int = 0):
    from ..parallel import sequence as seq_parallel

    if seq_parallel.sp_size() > 1:
        if window:
            # no sequence-parallel attention knows a window: the plain
            # masked one
            return _dense_attention(cfg, q, k, v, window)
        return seq_parallel.sequence_parallel_attention(
            q, k, v, causal=True, impl=cfg.sp_impl)
    use_flash = cfg.use_flash
    if use_flash is None:
        use_flash = on_tpu()
    if use_flash:
        # a sliding layer's window is the flash kernels' own (blocks outside
        # the band skipped, forward and backward); the dense masked path is
        # the CPU's and the tests' oracle
        return seq_parallel.mesh_flash_attention(
            q, k, v, causal=True, **({"window": window} if window else {}))
    return _dense_attention(cfg, q, k, v, window)


def _dense_attention(cfg: LlamaConfig, q, k, v, window: int = 0):
    rep = cfg.num_heads // cfg.num_kv_heads
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s_len = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(cfg.head_dim)
    mask = jnp.tril(jnp.ones((s_len, k.shape[2]), bool))
    if window:
        mask = mask & ~jnp.tril(mask, -window)
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _latent_project(cfg: LlamaConfig, y, get, mm, rope):
    """The projections of a latent layer over its normed input ``y [B, T,
    d]``: the heads' unrotated and rotated query parts ``qn [B, H, T,
    qk_nope_dim]`` / ``qr [B, H, T, qk_rope_dim]``, the normed latent ``c
    [B, T, kv_lora_rank]`` and the ONE rotated key ``kr [B, 1, T,
    qk_rope_dim]`` all heads share.  ``rope(a)`` rotates ``[B, *, T,
    qk_rope_dim]`` at the tokens' positions."""
    b, t, _ = y.shape
    h, nope = cfg.num_heads, cfg.qk_nope_dim
    if cfg.q_lora_rank:
        cq = rms_norm(mm(y, "q_a_w", None), get("q_a_norm"), cfg.rms_eps)
        q = mm(cq, "q_b_w", None)
    else:
        # a full-rank projection; the head split moves the product
        # (``_attend_cached``)
        q = jax.lax.optimization_barrier(mm(y, "q_w", None))
    q = q.reshape(b, t, h, cfg.head_dim).transpose(0, 2, 1, 3)
    kv = mm(y, "kv_a_w", None)
    c = rms_norm(kv[..., :cfg.kv_lora_rank], get("kv_a_norm"), cfg.rms_eps)
    kr = rope(kv[:, None, :, cfg.kv_lora_rank:])
    return q[..., :nope], rope(q[..., nope:]), c, kr


def _latent_up(cfg: LlamaConfig, kv_b_w, dtype):
    """``kv_b_w [kv_lora_rank, H * (qk_nope_dim + v_dim)]`` read as the two
    up-projections it holds, ``W_uk [c, H, nope]`` and ``W_uv [c, H, v]``
    (views of the ONE stored leaf: no second copy of it is a parameter)."""
    w = kv_b_w.astype(dtype).reshape(
        cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_dim + cfg.value_dim)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def _latent_temperature(cfg: LlamaConfig, positions):
    """The factor on a latent layer's scores, float32, broadcastable over
    ``[B, H, T, *]``: the softmax scale times ``t(p)`` at ``positions``
    (``[T]`` or ``[B, T]``)."""
    if cfg.query_temperature is None:
        return latent_scale(cfg)
    t = query_temperature(cfg, positions) * latent_scale(cfg)
    return t[:, None] if t.ndim == 1 else t[:, None, :, None]


def _latent_attention(cfg: LlamaConfig, layer, y, cos, sin):
    """A latent layer's UNCACHED attention over ``y [B, S, d]`` in the
    EXPANDED form — every head's keys ``[kn | k_r]`` and values written out
    from the latent — positions ``0 .. S-1``; ``[B, S, H * v_dim]``."""
    b, s, _ = y.shape
    h = cfg.num_heads
    get, mm = layer_accessors(layer)
    with jax.named_scope("layer/attn/qkv"):
        qn, qr, c, kr = _latent_project(
            cfg, y, get, mm, (lambda a: apply_rope(a, cos, sin,
                                                   cfg.rope_interleaved))
            if cfg.latent_rope else (lambda a: a))
    with jax.named_scope("layer/attn/latent_up"):
        w_uk, w_uv = _latent_up(cfg, get("kv_b_w"), y.dtype)
        kn = jnp.einsum("bsc,chn->bhsn", c, w_uk)
        v = jnp.einsum("bsc,chv->bhsv", c, w_uv)
    with jax.named_scope("layer/attn/core"):
        scores = (jnp.einsum("bhqn,bhkn->bhqk", qn, kn)
                  + jnp.einsum("bhqr,bkr->bhqk", qr, kr[:, 0])) \
            .astype(jnp.float32) * _latent_temperature(cfg, jnp.arange(s))
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None],
                           scores, -1e9)
        probs = jax.nn.softmax(scores, axis=-1).astype(y.dtype)
        return jnp.einsum("bhqk,bhkv->bqhv", probs, v).reshape(
            b, s, h * cfg.value_dim)


def _latent_cached(cfg: LlamaConfig, y, get, mm, pool, pos, block_tables,
                   chunk_valid, layer):
    """A latent layer's cache write + attention over the block-paged pool,
    in the ABSORBED form: the window's ``[c | k_r]`` goes to ``(layer,
    block, offset)`` of the latent leaf (``ops/paged_kv.py`` "The latent
    kind"), the unrotated query part goes through ``W_uk`` into latent
    space, every head scores the ONE shared tile, the output is ``p . c``
    in latent space and leaves through ``W_uv``
    (``ops/decode_attention.paged_latent_attention``).  -> ``([B, T, H *
    v_dim], pool)``."""
    from ..ops.decode_attention import paged_latent_attention
    from ..ops.paged_kv import paged_window_update

    b, t, _ = y.shape
    rank = cfg.kv_lora_rank
    with jax.named_scope("layer/attn/qkv"):
        qn, qr, c, kr = _latent_project(
            cfg, y, get, mm, (lambda a: _rope_cached(cfg, a, pos))
            if cfg.latent_rope else (lambda a: a))
    pad = pool.shape[-1] - cfg.latent_width
    pool = paged_window_update(
        pool, jnp.pad(jnp.concatenate([c[:, None], kr], axis=-1),
                      ((0, 0),) * 3 + ((0, pad),)),
        pos, block_tables, valid=chunk_valid, layer=layer)
    w_uk, w_uv = _latent_up(cfg, get("kv_b_w"), y.dtype)
    p = jnp.asarray(pos, jnp.int32)
    positions = (p + jnp.arange(t)) if p.ndim == 0 \
        else p[:, None] + jnp.arange(t)[None, :]
    with jax.named_scope("layer/attn/latent_up"):
        ql = jnp.einsum("bhtn,chn->bhtc", qn, w_uk)
        q = jnp.concatenate([ql, qr], axis=-1).astype(jnp.float32) \
            * _latent_temperature(cfg, positions)
        q = jnp.pad(q.astype(y.dtype), ((0, 0),) * 3 + ((0, pad),))
    with jax.named_scope("layer/attn/core"):
        o = paged_latent_attention(q, pool, block_tables, pos, rank=rank,
                                   layer=layer, valid=chunk_valid)
    with jax.named_scope("layer/attn/latent_up"):
        out = jnp.einsum("bhtc,chv->bthv", o, w_uv)
    return out.reshape(b, t, cfg.num_heads * cfg.value_dim), pool


def attn_apply(cfg: LlamaConfig, layer: PyTree, x, cos, sin, attention=None,
               kind=None, delta: bool = False):
    """The attention half of a block (pre-norm, q/k/v, optional q/k-norm,
    RoPE, causal attention, output projection, residual) — llama's and
    mixtral's uncached forwards share it.  ``attention(y, q, k, v)``
    replaces the dense causal attention (mixtral's learned sparse one,
    which reads the normed input ``y``).  ``kind``: the layer's kind in a
    patterned model (:func:`kind_of`).  ``delta`` (a parallel block): the
    pair ``(normed input, attention output)`` instead of the new
    residual."""
    # matmuls route through cached.qmm: dense leaves trace to the identical
    # ``x @ w.astype`` HLO; INT8 records (quant-aware serving prefill)
    # dequantize at point of use instead of crashing on a dict leaf
    b, s, d = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    rotated, window = kind_of(cfg, kind)
    if cfg.latent:
        with jax.named_scope("layer/attn"):
            y = block_norm(cfg, x, layer["attn_norm"])
            attn = _latent_attention(cfg, layer, y, cos, sin)
            with jax.named_scope("layer/attn/out"):
                out = qmm(attn, layer["o_w"], x.dtype)
            return (y, out) if delta else x + out
    with jax.named_scope("layer/attn"):
        y = block_norm(cfg, x, layer["attn_norm"])
        with jax.named_scope("layer/attn/qkv"):
            q, k = qk_normed(cfg, qmm(y, layer["q_w"]), qmm(y, layer["k_w"]),
                             layer.__getitem__)
            q = q.reshape(b, s, h, hd)
            k = k.reshape(b, s, hkv, hd)
            v = qmm(y, layer["v_w"]).reshape(b, s, hkv, hd)
            q = q.transpose(0, 2, 1, 3)
            if rotated:
                q = apply_rope(q, cos, sin, cfg.rope_interleaved)
            k = k.transpose(0, 2, 1, 3)
            if rotated:
                k = apply_rope(k, cos, sin, cfg.rope_interleaved)
            v = v.transpose(0, 2, 1, 3)
        with jax.named_scope("layer/attn/core"):
            attn = _attention(cfg, q, k, v, window) if attention is None \
                else attention(y, q, k, v)
        with jax.named_scope("layer/attn/out"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
            out = qmm(attn, layer["o_w"], x.dtype)
        return (y, out) if delta else x + out


def block_apply(cfg: LlamaConfig, layer: PyTree, x, cos, sin):
    if cfg.layer_kinds:
        raise NotImplementedError(
            "a layer pattern (layer_kinds) is built by models/mixtral.py; "
            "the dense Llama block has none")
    if cfg.parallel_block:
        y, a = attn_apply(cfg, layer, x, cos, sin, delta=True)
        x = x + a
    else:
        x = attn_apply(cfg, layer, x, cos, sin)
        y = block_norm(cfg, x, layer["mlp_norm"])
    with jax.named_scope("layer/mlp"):
        gate = jax.nn.silu(qmm(y, layer["w1"]))
        up = qmm(y, layer["w3"])
        return x + qmm(gate * up, layer["w2"], x.dtype)


def forward(cfg: LlamaConfig, params: PyTree, input_ids, rng=None,
            train: bool = True):
    del rng, train  # no dropout in llama pretraining config
    params = dequant_resident(params)
    b, s = input_ids.shape
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(params["embed"].dtype)
    cos, sin = rope_angles(cfg, s)

    def step(x, layer):
        fn = block_apply
        if cfg.remat:
            fn = checkpoint_block(block_apply, static_argnums=(0,))
        return fn(cfg, layer, x, cos, sin)

    # ZeRO-3 liveness: scan_group_size > 1 gathers G layers per scan step,
    # scan_prefetch pipelines the loop (overlap_comm)
    from ..runtime.zero.liveness import scan_layers_prefetched

    x = scan_layers_prefetched(step, x, params["blocks"],
                               getattr(cfg, "scan_group_size", 1),
                               getattr(cfg, "scan_prefetch", None))
    x = block_norm(cfg, x, params["final_norm"])
    return head_logits(cfg, params, x)


def init_cache(cfg: LlamaConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16):
    """Static KV workspace: [L, B, HKV, S, hd] (GQA — KV heads only).  A
    latent model (``cfg.latent``; block-paged only) holds ONE leaf and no
    ``k`` / ``v``: ``latent [L, B, 1, S, W]``, a token's ``[c | k_r]``
    zero-padded to whole 128-lane rows (``ops/paged_kv.py`` "The latent
    kind")."""
    if cfg.latent:
        from ..ops.paged_kv import latent_pool_width

        return {"latent": jnp.zeros(
            (cfg.num_layers, batch_size, 1, max_len,
             latent_pool_width(cfg.latent_width)), dtype)}
    return init_kv_cache(cfg.num_layers, batch_size, cfg.num_kv_heads,
                         max_len, cfg.head_dim, dtype)


def _rope_cached(cfg: LlamaConfig, x, pos):
    """Rotary embedding at traced offset ``pos`` (scalar, or int32 [B] for
    per-sequence decode positions).  x: [B, H, T, hd], rotated over its
    whole last dim (in the pairing ``cfg.rope_interleaved`` names)."""
    hd = x.shape[-1]
    inv_freq = rope_inv_freq(cfg, hd)
    pos = jnp.asarray(pos)
    t = jnp.arange(x.shape[2], dtype=jnp.float32)
    if pos.ndim == 0:
        angles = (pos + t)[:, None] * inv_freq[None, :]          # [T, hd/2]
    else:
        p = pos.astype(jnp.float32)[:, None] + t[None, :]        # [B, T]
        angles = p[..., None] * inv_freq[None, None, :]          # [B, T, hd/2]
    return apply_rope(x, *_cos_sin(cfg, angles), cfg.rope_interleaved)


def _attend_cached(cfg: LlamaConfig, x, get, mm, ck, cv, pos, block_tables,
                   chunk_valid, layer, attend, extra, kind):
    """The attention half of :func:`_block_cached_body` for a model that
    caches a key and a value a KV head: ``(x + attention, the normed input,
    ck, cv, extra)``."""
    b, t, d = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rotated, window = kind_of(cfg, kind)
    with jax.named_scope("layer/attn"):
        y = block_norm(cfg, x, get("attn_norm"))
        # the head split below (and a per-head q/k-norm's) moves these
        # PRODUCTS, [rows, H hd] of a serving call's few rows: left free, XLA
        # folds it into the dots and transposes their WEIGHTS instead, every
        # call (Command A+: 134 MB of q_w a layer; test_chip_lowering.py
        # ..._write_no_weight_sized_value)
        with jax.named_scope("layer/attn/qkv"):
            q, k, v = jax.lax.optimization_barrier(
                (mm(y, "q_w", None), mm(y, "k_w", None), mm(y, "v_w", None)))
            q, k = qk_normed(cfg, q, k, get)
            q = q.reshape(b, t, h, hd)
            k = k.reshape(b, t, hkv, hd)
            v = v.reshape(b, t, hkv, hd)
            q = q.transpose(0, 2, 1, 3)
            if rotated:
                q = _rope_cached(cfg, q, pos)
            k = k.transpose(0, 2, 1, 3)
            if rotated:
                k = _rope_cached(cfg, k, pos)
            v = v.transpose(0, 2, 1, 3)
        if attend is not None:
            attn, ck, cv, extra = attend(y, q, k, v, ck, cv, extra)
        else:
            attn, ck, cv = cached_attention(
                q, k, v, ck, cv, pos, block_tables, chunk_valid, layer,
                window=window)
        with jax.named_scope("layer/attn/out"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, t, h * hd)
            return x + mm(attn, "o_w", x.dtype), y, ck, cv, extra


def _block_cached_body(cfg: LlamaConfig, x, get, mm, ck, cv, pos,
                       mlp=None, block_tables=None, chunk_valid=None,
                       layer=None, attend=None, extra=None, kind=None):
    """Cached-attention block parameterized by weight access (``get(name)``
    small leaf, ``mm(y, name, dtype)`` matmul — shared by the scan and
    layer-indexed quantized decode paths, see cached.decode_over_layers).
    ``mlp(y) -> (y, aux)`` overrides the dense SwiGLU (mixtral's routed
    FFN; ``aux`` is its per-layer routing record) and makes this return
    ``(x, ck, cv, aux)``.  ``attend(y, q, k, v, ck, cv, extra) -> (attn, ck,
    cv, extra)`` overrides the cache write + attention (mixtral's learned
    sparse attention, which reads the block's normed input ``y`` and keeps
    state of its own — a third pool leaf — in ``extra``) and makes this
    return ``(x, ck, cv, extra, aux)``.
    ``block_tables``/``chunk_valid`` switch ck/cv to the whole paged pool,
    addressed in place at ``layer`` (contract in cached.cached_attention).
    ``kind`` (a patterned model, :func:`kind_of`): whether q and k are
    rotated and how far a query reaches; ck/cv, ``block_tables`` and
    ``layer`` are then that kind's own."""
    if cfg.latent:
        # its own projections and its own cache write: ``ck`` is the latent
        # leaf, ``cv`` rides through untouched (the pool has no second leaf)
        with jax.named_scope("layer/attn"):
            y = block_norm(cfg, x, get("attn_norm"))
            attn, ck = _latent_cached(cfg, y, get, mm, ck, pos, block_tables,
                                      chunk_valid, layer)
            with jax.named_scope("layer/attn/out"):
                x = x + mm(attn, "o_w", x.dtype)
    else:
        x, y, ck, cv, extra = _attend_cached(
            cfg, x, get, mm, ck, cv, pos, block_tables, chunk_valid, layer,
            attend, extra, kind)

    if not cfg.parallel_block:
        # (a parallel block's FFN reads the ONE norm of the block's input)
        y = block_norm(cfg, x, get("mlp_norm"))
    if mlp is not None:
        out, aux = mlp(y)
        if attend is not None:
            return x + out, ck, cv, extra, aux
        return x + out, ck, cv, aux
    with jax.named_scope("layer/mlp"):
        gate = jax.nn.silu(mm(y, "w1", None))
        up = mm(y, "w3", None)
        x = x + mm(gate * up, "w2", x.dtype)
    return x, ck, cv


def _block_cached(cfg: LlamaConfig, x, layer, ck, cv, pos, mlp_fn=None,
                  block_tables=None, chunk_valid=None, index=None,
                  attend_fn=None, kind=None):
    """``layer`` is the pre-sliced weight dict, ``index`` its position in
    the stack (paged pools only; in its KIND's stack for a patterned
    model).  With ``attend_fn``, ``ck`` is the pair ``(K, extra)`` the
    layer loop carries (:func:`forward_cached`)."""
    body = functools.partial(
        _block_cached_body, cfg, x, *layer_accessors(layer),
        mlp=None if mlp_fn is None else (lambda y: mlp_fn(layer, y)),
        block_tables=block_tables, chunk_valid=chunk_valid, layer=index,
        kind=kind)
    if attend_fn is None:
        return body(ck, cv, pos)
    x, ck, cv, extra, aux = body(
        ck[0], cv, pos, extra=ck[1],
        attend=lambda y, q, k, v, ck, cv, extra: attend_fn(
            layer, y, q, k, v, ck, cv, extra, pos, block_tables, chunk_valid,
            index))
    return x, (ck, extra), cv, aux


def forward_cached(cfg: LlamaConfig, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, mlp_fn=None,
                   all_positions=False, attend_fn=None, extra=None):
    """Incremental forward: logits for the LAST input position + updated
    cache — or for EVERY position when ``all_positions`` is set ([B, T, V],
    the speculative-verify head).  ``mlp_fn(layer, y) -> (y, record)``
    threads through to :func:`_block_cached` (mixtral delegates here with
    its routed FFN) and adds a third result, the per-layer records stacked
    ``[L, ...]``.  ``attend_fn(layer, y, q, k, v, ck, cv, extra, pos,
    block_tables, chunk_valid, index) -> (attn, ck, cv, extra)`` (with
    ``mlp_fn``) replaces the cache write + attention of every block:
    ``extra`` is any pytree of the caller's, carried through the layers
    beside K and V and handed back as a last result.
    Quantized serving (no mlp_fn) takes the layer-indexed stacked-kernel
    path via ``cached.decode_over_layers``.  The contract of ``lengths`` /
    ``block_tables`` is ``cached.window``'s; the rope offsets follow each
    row's position."""
    params = dequant_resident(params)
    step_pos, chunk_valid, gather, paged = cached.window(
        input_ids, pos, lengths, block_tables)
    # sequence-parallel prefill hook (no-op outside an sp context)
    with jax.named_scope("embed"):
        x = shard_seq(params["embed"][input_ids].astype(
            params["embed"].dtype))
    # a latent model's pool is ONE leaf; it rides where K does and nothing
    # rides where V does
    first = "latent" if cfg.latent else "k"
    if cfg.latent and (not paged or attend_fn is not None):
        raise NotImplementedError(
            "latent attention (kv_lora_rank > 0) is served through the "
            "block-paged pool (init_serving / ServingEngine): the "
            "contiguous cache of InferenceEngine.generate holds a key and "
            "a value a head, not a latent")

    if cfg.layer_kinds:
        if not paged or mlp_fn is None or attend_fn is not None:
            raise NotImplementedError(
                "a layer pattern with sliding-window layers (layer_kinds) "
                "is served through the block-paged pool (init_serving / "
                "ServingEngine) by models/mixtral.py: the contiguous cache "
                "of InferenceEngine.generate has one kind of state")
        x, kv, records = scan_periods_cached(
            cfg.layer_kinds, cfg.num_layers,
            lambda x, layer, ck, cv, l, table, kind: _block_cached(
                cfg, x, layer, ck, cv, step_pos, mlp_fn=mlp_fn,
                block_tables=table, chunk_valid=chunk_valid, index=l,
                kind=kind),
            x, params["blocks"], cache, block_tables)
    elif mlp_fn is None:
        x, ks, vs = decode_over_layers(
            lambda x, get, mm, ck, cv, layer: _block_cached_body(
                cfg, x, get, mm, ck, cv, step_pos,
                block_tables=block_tables, chunk_valid=chunk_valid,
                layer=layer),
            x, params["blocks"], cache[first], cache.get("v"),
            cfg.num_layers, probe="q_a_w" if cfg.q_lora_rank else "q_w",
            paged=paged)
    else:
        # mixtral's MoE FFN needs the whole layer dict: scan path only
        x, ks, vs, records = scan_layers_cached(
            lambda x, layer, ck, cv, l: _block_cached(
                cfg, x, layer, ck, cv, step_pos, mlp_fn=mlp_fn,
                block_tables=block_tables, chunk_valid=chunk_valid,
                index=l, attend_fn=attend_fn),
            x, params["blocks"],
            cache[first] if attend_fn is None else (cache["k"], extra),
            cache.get("v"), paged)
        if attend_fn is not None:
            ks, extra = ks
    if not all_positions:
        x = gather_last(x, gather)
    x = block_norm(cfg, x, params["final_norm"])
    logits = head_logits(cfg, params, x)
    if cfg.layer_kinds:
        return logits, kv, records
    kv = {"latent": ks} if cfg.latent else {"k": ks, "v": vs}
    if mlp_fn is None:
        return logits, kv
    if attend_fn is not None:
        return logits, kv, records, extra
    return logits, kv, records


def loss_from_batch(cfg: LlamaConfig, params, batch, rng=None,
                    train: bool = True):
    if isinstance(batch, (tuple, list)):
        input_ids, labels = batch
    else:
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
    if labels is None:
        labels = input_ids[:, 1:]
        input_ids = input_ids[:, :-1]
    logits = forward(cfg, params, input_ids, rng=rng, train=train)
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        valid = labels >= 0
        safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return jnp.where(valid, nll, 0.0).sum() \
            / jnp.maximum(valid.sum(), 1)


def tp_rules(cfg: LlamaConfig, abstract_params: PyTree) -> PyTree:
    rules = {
        "embed": P(TP_AXIS, None),
        "blocks": {
            "attn_norm": P(),
            # q/k-norm scales stay whole: the norm spans every head
            **({"q_norm": P(), "k_norm": P()} if cfg.qk_norm else {}),
            "q_w": P(None, None, TP_AXIS),
            "k_w": P(None, None, TP_AXIS),
            "v_w": P(None, None, TP_AXIS),
            "o_w": P(None, TP_AXIS, None),
            "mlp_norm": P(),
            "w1": P(None, None, TP_AXIS),
            "w3": P(None, None, TP_AXIS),
            "w2": P(None, TP_AXIS, None),
        },
        "final_norm": P(),
        "lm_head": P(None, TP_AXIS),
    }
    if cfg.latent:
        # every engine turns this tree into its parameters' shardings at
        # construction, tp or not (``InferenceEngine``, ``DeepSpeedEngine``),
        # so it has to name the leaves a latent model has.  The
        # down-projections and their norms are whole on every chip (the
        # latent is replicated under tp); the up-projections and ``o_w``
        # split by head: what the uncached forward runs under a tp mesh
        # (the SERVING engine refuses a tp mesh for a latent model)
        blocks = rules["blocks"]
        for name in ("k_w", "v_w"):
            del blocks[name]
        if cfg.q_lora_rank:             # (a full-rank query keeps ``q_w``)
            del blocks["q_w"]
            blocks.update(q_a_w=P(), q_a_norm=P(),
                          q_b_w=P(None, None, TP_AXIS))
        blocks.update(kv_a_w=P(), kv_a_norm=P(),
                      kv_b_w=P(None, None, TP_AXIS))
    return rules


def latent_hook(cfg: LlamaConfig) -> dict:
    """The decode hook a latent model carries (none otherwise):
    ``{"latent_attention": {"rank", "rope", "width"}}`` — the pool has ONE
    leaf, ``width = rank + rope`` values a token a layer."""
    if not cfg.latent:
        return {}
    return {"latent_attention": {"rank": cfg.kv_lora_rank,
                                 "rope": cfg.qk_rope_dim,
                                 "width": cfg.latent_width}}


def build(cfg: Optional[LlamaConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or LlamaConfig(**overrides)

    def init_fn(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch, rng=None, train=True):
        return loss_from_batch(cfg, params, batch, rng=rng, train=train)

    def apply_fn(params, batch, rng=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return forward(cfg, params, ids, rng=rng, train=False)

    def pp_embed(params, ids):
        return params["embed"][ids].astype(params["embed"].dtype)

    def pp_block(layer, x, rng=None):
        cos, sin = rope_angles(cfg, x.shape[1])
        return block_apply(cfg, layer, x, cos, sin)

    def pp_head_loss(params, x, targets):
        x = block_norm(cfg, x, params["final_norm"])
        logits = head_logits(cfg, params, x).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        valid = targets >= 0  # -100 = ignore (HF convention)
        safe = jnp.where(valid, targets, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return jnp.where(valid, nll, 0.0).sum() / jnp.maximum(valid.sum(), 1)

    return ModelSpec(
        init_fn=init_fn, model_config=cfg, loss_fn=loss_fn, apply_fn=apply_fn,
        tp_rules=lambda ap: tp_rules(cfg, ap),
        flops_per_token=6.0 * cfg.num_params(),
        pipeline_hooks={
            "blocks_key": ("blocks",),
            "embed_fn": pp_embed,
            "block_fn": pp_block,
            "head_loss_fn": pp_head_loss,
        },
        decode_hooks={
            **latent_hook(cfg),
            "init_cache": lambda b, s, dtype=jnp.bfloat16: init_cache(
                cfg, b, s, dtype),
            "forward_cached": lambda params, ids, cache, pos, lengths=None,
                block_tables=None, all_positions=False:
                forward_cached(cfg, params, ids, cache, pos, lengths,
                               block_tables, all_positions=all_positions),
            "supports_lengths": True,
            "supports_paged": True,
            "supports_verify": True,
            # int8 KV pool records pass through ops/paged_kv untouched by
            # this family (rope applies before the cache write), so the
            # serving engine may quantize the pool (quantize="kv8")
            "supports_kv_quant": True,
            # raw next-token logits reach the serving engine's on-device
            # sampler unchanged (per-slot temperature/top-k/top-p)
            "supports_sampling": True,
        },
        quant_aware=True,  # per-layer point-of-use dequant / w8a8 records
        name=f"llama-{cfg.num_layers}l-{cfg.hidden_size}d")
