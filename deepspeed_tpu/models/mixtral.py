"""Mixtral and OLMoE (sparse-MoE Llama), TPU-native.

Driver config #4 (Mixtral 8x7B expert-parallel + ZeRO-2).
Llama attention blocks with the FFN replaced by a top-k-gated MoE
(``deepspeed_tpu.moe``): expert weights are stacked [L, E, ...] with the expert
dim sharded over the ``ep`` mesh axis, so scan-over-layers + vmapped experts +
all-to-all dispatch compose with ZeRO and TP.  Reference analog:
``deepspeed/moe/layer.py`` MoE inserted per-block + MoE-aware ZeRO.

Two FFN paths: every INFERENCE forward — uncached, cached, paged — takes the
dropless ``moe/routed.py`` (any ``top_k``, with or without renormalising
the chosen weights), so the cached and uncached forwards agree by
construction; TRAINING takes the same dropless layer, differentiated, where
the configuration says so (``capacity_factor=None``: any ``top_k``, a held
share, the Switch-form balance loss over the router's full softmax, the
head's loss in chunks, the routing's counters returned beside the loss), and
DeepSpeed's capacity gating (``moe/layer.py``: top-1/top-2, tokens over
capacity dropped, load-balancing loss) where it names a capacity (Mixtral
8x7B's 1.25).
OLMoE (``MixtralConfig.olmoe_1b_7b``) is the same block with q/k-norm
(``LlamaConfig.qk_norm``), 64 experts, top-8 and no renormalisation.
Keye-VL-2.0's language model (``MixtralConfig.keye_vl2_30b_a3b``) is the
same block at a head width of its own (``heads x head_dim != hidden_size``),
per-head q/k-norm, 128 experts top-8 renormalised, and LEARNED SPARSE
ATTENTION: a config with ``index_heads > 0`` gives every layer an indexer
(``idx_q_w`` / ``idx_k_w`` / ``idx_w_w`` / ``idx_k_norm``) whose key is a
third leaf of the cache and whose scores choose the ``index_topk`` keys a
query attends (``ops/sparse_index_attention.py``).
Command A+ (``MixtralConfig.command_a_plus``) is the PARALLEL block
(``parallel_block``: attention and the expert layer read one Cohere
LayerNorm of the block's input and both add to the residual) over a layer
PATTERN (``layer_kinds``: three sliding-window layers with interleaved
rotary to one full-attention layer without rotation, each kind on pool
leaves and a block table of its own), 128 sigmoid-scored experts top-8
(``router_score``) beside four SHARED experts every token runs, combined by
averaging (``shared_experts``), a tied head — and an
expert layer that can be told which experts it HOLDS (``experts_held``: one
chip's share of an expert-parallel group, ``moe/routed.py``).
Mistral Small 4 (``MixtralConfig.mistral_small_4``) is the sequential block
with LATENT ATTENTION (``llama.LlamaConfig.kv_lora_rank``: low-rank queries,
one joint key / value latent a token, rotary on half of a head under YaRN,
a position-dependent query temperature; the cache holds ``[c | k_r]`` a
token a layer, ``ops/paged_kv.py`` "The latent kind", read absorbed) over
128 softmax-scored experts top-4 renormalised beside one shared expert.
SmallThinker (``MixtralConfig.smallthinker_21b_a3b``) is the sequential
RMSNorm block over the pattern ``[full, sliding, sliding, sliding]`` (a
4,096-key window and rotate-half rotary on the sliding layers, no rotation on
the full one), 28 query heads on 4 KV heads, 64 softmax-scored ReGLU experts
(``ffn_act="relu"``) top-6 renormalised, and a router that reads the
ATTENTION's normed input (``router_input="attn"``), so that routing does not
wait on attention; it trains dropless.
What is NOT built here, and refused by name: an indexer (``index_heads``)
beside a layer pattern or over a latent pool.  That combination is a family
of its own whose weights are stacked by kind (``models/dots3.py``: the
``"latent_indexed"`` and ``"latent_sliding"`` kinds, over this file's
``MixtralConfig``, ``_routed`` and ``_shared``); ``models/kimi_linear.py`` is
the other such family.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe.layer import MoEConfig, moe_apply
from ..moe.routed import ACTS, RECORD, RECORD_HELD, routed_ffn
from ..ops.chunked_ce import chunked_ce, whole_chunks
from ..ops import sparse_index_attention as sparse_attention
from ..parallel.topology import EP_AXIS, TP_AXIS
from ..runtime.model import ModelSpec
from ..runtime.remat import checkpoint_block
from . import llama as L
from .cached import KIND_LEAVES, layer_accessors, live_tokens, qmm

PyTree = Any
#: dropless training takes the head's loss over ``[tokens / CE_CHUNKS,
#: vocab]`` float32 logits at a time (``ops/chunked_ce.py``)
CE_CHUNKS = 8


@dataclasses.dataclass
class MixtralConfig(L.LlamaConfig):
    num_experts: int = 8
    #: experts per token: any value in [1, num_experts] for inference;
    #: training's capacity gating takes 1 or 2
    top_k: int = 2
    #: renormalise the chosen top-k router weights to sum to 1 (Mixtral);
    #: False keeps the softmax-over-all-experts weights (OLMoE)
    norm_topk_prob: bool = True
    #: training capacity (tokens over it are dropped); ``None``: training is
    #: dropless too (``moe/routed.py``).  Inference is always dropless
    capacity_factor: Optional[float] = 1.25
    router_aux_loss_coef: float = 0.02
    #: what the router reads: ``"ffn"``, the expert layer's own normed
    #: input; ``"attn"``, the attention's normed input of the same block
    #: (sequential blocks: routing no longer waits on attention)
    router_input: str = "ffn"
    #: the experts' gate activation: ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU)
    ffn_act: str = "silu"
    #: learned sparse attention (``ops/sparse_index_attention.py``): heads of the
    #: indexer (0 = dense attention, no indexer), their width, and the keys
    #: a query attends
    index_heads: int = 0
    index_head_dim: int = 64
    index_topk: int = 2048
    #: the router's scores over all experts: their ``"softmax"``, or each
    #: logit's own ``"sigmoid"``
    router_score: str = "softmax"
    #: dense SwiGLU experts of width ``ffn_size`` that EVERY token runs,
    #: beside the routed ones; their outputs' AVERAGE is added to the
    #: routed sum
    shared_experts: int = 0
    #: ``(first, count)``: the routed experts whose weights this model
    #: HOLDS (one chip's share of an expert-parallel group); the router
    #: still scores all ``num_experts`` and the expert layer returns the
    #: held experts' partial sum (``moe/routed.py``).  ``None``: all.
    experts_held: Optional[tuple] = None
    #: a per-expert SELECTION bias (leaf ``gate_bias [L, E]``): the top-k is
    #: taken of ``scores + bias``, the weights stay the unbiased scores
    router_bias: bool = False
    #: a factor on the chosen (renormalised) router weights
    routed_scale: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k={self.top_k} outside "
                             f"[1, num_experts={self.num_experts}]")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score={self.router_score!r}: "
                             "'softmax' or 'sigmoid'")
        if self.router_input not in ("ffn", "attn"):
            raise ValueError(f"router_input={self.router_input!r}: 'ffn' "
                             "or 'attn'")
        if self.ffn_act not in ACTS:
            raise ValueError(f"ffn_act={self.ffn_act!r}: one of "
                             f"{sorted(ACTS)}")
        if self.experts_held is not None:
            first, count = self.experts_held = tuple(
                int(v) for v in self.experts_held)
            if not (0 <= first and 1 <= count
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held={self.experts_held} outside the "
                    f"{self.num_experts} experts")
        if self.index_heads and (self.layer_kinds or self.latent) \
                and not self.by_kind:
            raise ValueError(
                "a learned indexer (index_heads) beside a layer pattern "
                "(layer_kinds) or latent attention (kv_lora_rank) is built "
                "as the 'latent_indexed' kind of a model whose weights are "
                "stacked by kind (models/dots3.py): an indexer over the K / "
                "V of a pattern's 'full' layers, or over a latent pool with "
                "no pattern, is not")

    @property
    def experts_here(self) -> int:
        """The routed experts whose weights the model holds."""
        return self.experts_held[1] if self.experts_held else \
            self.num_experts

    @staticmethod
    def mixtral_8x7b() -> "MixtralConfig":
        return MixtralConfig(vocab_size=32000, num_layers=32, num_heads=32,
                             num_kv_heads=8, hidden_size=4096, ffn_size=14336,
                             rope_theta=1e6, num_experts=8, top_k=2)

    @staticmethod
    def olmoe_1b_7b() -> "MixtralConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct: 16 layers, d 2048, 16 MHA
        heads x 128 with q/k-norm, 64 SwiGLU experts of width 1024, top-8
        of a softmax over all 64 without renormalisation."""
        return MixtralConfig(vocab_size=50304, max_seq_len=4096,
                             num_layers=16, num_heads=16, num_kv_heads=16,
                             hidden_size=2048, ffn_size=1024,
                             rope_theta=10000.0, rms_eps=1e-5, qk_norm=True,
                             num_experts=64, top_k=8, norm_topk_prob=False,
                             router_aux_loss_coef=0.01)

    @staticmethod
    def keye_vl2_30b_a3b() -> "MixtralConfig":
        """Kwai-Keye/Keye-VL-2.0-30B-A3B's language model (text positions;
        the vision tower is not built): 48 layers, d 2048, 32 query / 4 KV
        heads x 128 with per-head q/k-norm, 128 SwiGLU experts of width 768,
        top-8 renormalised, and an indexer of 16 heads x 64 choosing 2,048
        keys a query."""
        return MixtralConfig(vocab_size=151936, max_seq_len=262144,
                             num_layers=48, num_heads=32, num_kv_heads=4,
                             head_width=128, hidden_size=2048, ffn_size=768,
                             rope_theta=1e7, rms_eps=1e-6, qk_norm="head",
                             num_experts=128, top_k=8, norm_topk_prob=True,
                             router_aux_loss_coef=0.001, index_heads=16,
                             index_head_dim=64, index_topk=2048)

    @staticmethod
    def command_a_plus() -> "MixtralConfig":
        """CohereLabs/command-a-plus-05-2026's language model
        (``cohere2_moe``, 218B-A25B; the vision tower is not built): 32
        parallel blocks under Cohere's LayerNorm, d 4096, 128 query / 8 KV
        heads x 128, ``[sliding, sliding, sliding, full] x 8`` with a
        4,096-key window and interleaved rotary (theta 50,000) on the
        sliding layers and no rotation on the full ones, 128 sigmoid-scored
        SwiGLU experts of width 4,096 top-8 renormalised beside four
        averaged shared experts, a tied head.  The published model: one
        chip's share of it (``experts_held``, fewer layers, a vocabulary
        slice) is a deployment's to state."""
        return MixtralConfig(vocab_size=262144, max_seq_len=200000,
                             num_layers=32, num_heads=128, num_kv_heads=8,
                             head_width=128, hidden_size=4096, ffn_size=4096,
                             rope_theta=50000.0, rms_eps=1e-5,
                             norm="layernorm", parallel_block=True,
                             rope_interleaved=True,
                             layer_kinds=("sliding",) * 3 + ("full",),
                             sliding_window=4096, tie_embeddings=True,
                             num_experts=128, top_k=8, norm_topk_prob=True,
                             router_score="sigmoid", shared_experts=4)

    @staticmethod
    def mistral_small_4() -> "MixtralConfig":
        """mistralai/Mistral-Small-4-119B-2603's language model
        (``mistral4``, 119B-A6.5B; the vision encoder is not built): 36
        sequential RMSNorm blocks, d 4096, 32 heads of latent attention —
        queries through rank 1,024, keys and values through ONE latent of
        256 beside one shared rotated key of 64 (scores over 64 + 64, values
        of 128), pairwise rotary under YaRN (theta 10,000, factor 128 over
        8,192) and the query temperature ``1 + 0.1 ln(1 + floor(p /
        8192))`` — 128 softmax-scored SwiGLU experts of width 2,048 top-4
        renormalised beside one shared expert, an untied head.  The
        published model: one chip's share of it (``experts_held``, fewer
        layers, a vocabulary slice) is a deployment's to state."""
        return MixtralConfig(
            vocab_size=131072, max_seq_len=1048576, num_layers=36,
            num_heads=32, num_kv_heads=32, head_width=128, hidden_size=4096,
            ffn_size=2048, rope_theta=10000.0, rms_eps=1e-6,
            rope_interleaved=True, q_lora_rank=1024, kv_lora_rank=256,
            qk_nope_dim=64, qk_rope_dim=64, v_head_dim=128,
            rope_scaling={"factor": 128.0,
                          "original_max_position_embeddings": 8192,
                          "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
                          "mscale_all_dim": 1.0},
            query_temperature=(0.1, 8192), num_experts=128, top_k=4,
            norm_topk_prob=True, router_score="softmax", shared_experts=1)

    @staticmethod
    def smallthinker_21b_a3b() -> "MixtralConfig":
        """PowerInfer/SmallThinker-21BA3B-Instruct: 52 sequential RMSNorm
        blocks, d 2560, 28 query / 4 KV heads x 128, ``[full, sliding,
        sliding, sliding] x 13`` — the full layer unrotated, the sliding ones
        under a 4,096-key window and rotate-half rotary (theta 1.5e6) — 64
        softmax-scored ReGLU experts of width 768 top-6 renormalised, the
        router fed the attention's normed input, no shared expert, an untied
        head.  Trains dropless (balance coefficient 0.001: assumed, the
        published config names none).  The published model: one chip's share
        of it (``experts_held``, fewer layers, a vocabulary slice) is a
        deployment's to state."""
        return MixtralConfig(vocab_size=151936, max_seq_len=16384,
                             num_layers=52, num_heads=28, num_kv_heads=4,
                             head_width=128, hidden_size=2560, ffn_size=768,
                             rope_theta=1.5e6, rms_eps=1e-6,
                             layer_kinds=("full",) + ("sliding",) * 3,
                             sliding_window=4096, num_experts=64, top_k=6,
                             norm_topk_prob=True, router_score="softmax",
                             router_input="attn", ffn_act="relu",
                             capacity_factor=None,
                             router_aux_loss_coef=0.001)

    @property
    def dropless(self) -> bool:
        """Training routes without a capacity (``capacity_factor=None``)."""
        return self.capacity_factor is None

    @staticmethod
    def tiny(vocab_size: int = 512) -> "MixtralConfig":
        return MixtralConfig(vocab_size=vocab_size, max_seq_len=128,
                             num_layers=2, num_heads=4, num_kv_heads=2,
                             hidden_size=64, ffn_size=128, rope_theta=10000.0,
                             num_experts=4, top_k=2, remat=False)

    def num_params(self) -> int:
        base = super().num_params()
        d, f = self.hidden_size, self.ffn_size
        # swap the dense MLP for E experts + router
        per_layer_mlp = 3 * d * f
        # the indexer: its queries, its key, its head weights, the key's
        # LayerNorm (scale and bias)
        di = self.index_head_dim
        indexer = (d * (self.index_heads * di + di + self.index_heads)
                   + 2 * di) if self.index_heads else 0
        return base + self.num_layers * (
            (self.experts_here + self.shared_experts - 1) * per_layer_mlp
            + d * self.num_experts + indexer)

    def active_params(self) -> int:
        """Parameters one token multiplies with: everything but the
        experts it was not routed to (of a held share, in proportion)."""
        idle = (self.num_experts - self.top_k) * self.experts_here \
            // self.num_experts * 3 * self.hidden_size * self.ffn_size
        return self.num_params() - self.num_layers * idle

    def moe_cfg(self) -> MoEConfig:
        """The TRAINING gate (capacity buckets, k in {1, 2})."""
        return MoEConfig(hidden_size=self.hidden_size,
                         ffn_hidden_size=self.ffn_size,
                         num_experts=self.num_experts, k=self.top_k,
                         capacity_factor=self.capacity_factor,
                         activation="silu_glu")


def init_params(cfg: MixtralConfig, rng) -> PyTree:
    params = L.init_params(cfg, rng)
    blocks = params["blocks"]
    d, f, l, e = cfg.hidden_size, cfg.ffn_size, cfg.num_layers, cfg.num_experts
    keys = jax.random.split(jax.random.fold_in(rng, 7), 4)
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(jnp.float32)

    for k in ("w1", "w2", "w3"):
        del blocks[k]
    blocks["gate_w"] = normal(keys[0], (l, d, e))
    if cfg.router_bias:
        blocks["gate_bias"] = normal(jax.random.fold_in(rng, 19), (l, e))
    e = cfg.experts_here
    blocks["experts_w1"] = normal(keys[1], (l, e, d, f))
    blocks["experts_w3"] = normal(keys[2], (l, e, d, f))
    blocks["experts_w2"] = normal(keys[3], (l, e, f, d))
    if cfg.shared_experts:
        # the shared experts side by side: one SwiGLU of width Sh * f
        # (expert j is columns / rows ``j * f .. (j + 1) * f``)
        sf = cfg.shared_experts * f
        skeys = jax.random.split(jax.random.fold_in(rng, 13), 3)
        blocks["shared_w1"] = normal(skeys[0], (l, d, sf))
        blocks["shared_w3"] = normal(skeys[1], (l, d, sf))
        blocks["shared_w2"] = normal(skeys[2], (l, sf, d))
    if cfg.index_heads:
        hi, di = cfg.index_heads, cfg.index_head_dim
        ikeys = jax.random.split(jax.random.fold_in(rng, 11), 3)
        blocks["idx_q_w"] = normal(ikeys[0], (l, d, hi * di))
        blocks["idx_k_w"] = normal(ikeys[1], (l, d, di))
        blocks["idx_w_w"] = normal(ikeys[2], (l, d, hi))
        # the key's LayerNorm: scale row, bias row
        blocks["idx_k_norm"] = jnp.stack(
            [jnp.ones((l, di)), jnp.zeros((l, di))], axis=1)
    return params


def _indexer(cfg: MixtralConfig, get, mm, y, rope):
    """The indexer's projections of the normed block input ``y [B, T, d]``:
    queries ``[B, HI, T, DI]`` and the one key head ``[B, 1, T, DI]``
    (LayerNorm, then ``rope`` over the whole width) in ``y``'s dtype, head
    weights float32 ``[B, T, HI]``."""
    b, t, _ = y.shape
    hi, di = cfg.index_heads, cfg.index_head_dim
    with jax.named_scope("layer/attn/qkv"):
        # (the barrier: the head split moves the product, not ``idx_q_w`` —
        # ``llama._attend_cached``)
        qi = jax.lax.optimization_barrier(mm(y, "idx_q_w", None))
        qi = qi.reshape(b, t, hi, di).transpose(0, 2, 1, 3)
        ki = sparse_attention.layer_norm(mm(y, "idx_k_w", None),
                                         get("idx_k_norm"))[:, None]
        wi = mm(y, "idx_w_w", None).astype(jnp.float32)
        return rope(qi), rope(ki), wi


def _moe_block(cfg: MixtralConfig, layer: PyTree, x, cos, sin,
               train: bool = True, kind=None, choices: bool = False):
    """Llama attention + MoE FFN; returns (x, aux_loss) — (x, aux_loss,
    routing record) from a dropless training layer, (x, aux_loss, the
    tokens' experts ``[B, S, top_k]``) with ``choices`` (inference).
    ``kind``: the layer's kind in a patterned model."""
    attention = None
    if cfg.index_heads:
        icos, isin = L.rope_angles(cfg, x.shape[1], dim=cfg.index_head_dim)

        def attention(y, q, k, v):
            qi, ki, wi = _indexer(cfg, *layer_accessors(layer), y,
                                  lambda a: L.apply_rope(a, icos, isin))
            return sparse_attention.sparse_attention_uncached(
                q, k, v, qi, wi, ki[:, 0], cfg.index_topk)

    router_x = None
    if cfg.parallel_block:
        y, a = L.attn_apply(cfg, layer, x, cos, sin, attention, kind,
                            delta=True)
        x = x + a
    elif cfg.router_input == "attn":
        router_x, a = L.attn_apply(cfg, layer, x, cos, sin, attention, kind,
                                   delta=True)
        x = x + a
        y = L.block_norm(cfg, x, layer["mlp_norm"])
    else:
        x = L.attn_apply(cfg, layer, x, cos, sin, attention, kind)
        y = L.block_norm(cfg, x, layer["mlp_norm"])
    if train and cfg.dropless:
        moe_out, (record, aux) = _routed(cfg, layer, y, router_x=router_x,
                                         train=True)
        return x + moe_out, aux, record
    if train:
        moe_out, aux = _moe_ffn(cfg, layer, y)
    else:
        moe_out, chosen = _routed(cfg, layer, y, router_x=router_x,
                                  choices=choices)
        aux = jnp.zeros((), jnp.float32)
        if choices:
            return x + moe_out, aux, chosen[1]
    return x + moe_out, aux


def _trunk(cfg: MixtralConfig, params: PyTree, input_ids, train: bool,
           choices: bool = False):
    """Embedding, the blocks, the final norm -> ``(x [B, S, d], the layers'
    mean balance loss, record)``; ``record`` is the layers' routing records
    summed (int32, ``moe/routed.py RECORD`` / ``RECORD_HELD``) from dropless
    training, with ``choices`` (inference) every layer's chosen experts
    ``[L, B, S, top_k]``, else ``None``."""
    b, s = input_ids.shape
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(params["embed"].dtype)
    cos, sin = L.rope_angles(cfg, s)

    counted = train and cfg.dropless
    if counted:
        from .. import comm

        if cfg.shared_experts or cfg.router_score != "softmax":
            raise NotImplementedError(
                "dropless training's balance loss is the softmax router's, "
                "over routed experts alone: shared experts and a sigmoid "
                "router are inference paths")
        if comm.get_topology().expert_parallel_size > 1:
            raise NotImplementedError(
                "dropless training under an ep mesh axis: the exchange of "
                "the routed rows between the chips of an expert-parallel "
                "group is not built (one chip trains its held share, "
                "experts_held, without it)")
    elif train and (cfg.experts_held is not None or cfg.shared_experts
                    or cfg.router_score != "softmax"
                    or cfg.router_input != "ffn" or cfg.ffn_act != "silu"):
        raise NotImplementedError(
            "training's capacity gate (moe/layer.py) is a softmax router "
            "over SwiGLU experts that are all here, fed the expert layer's "
            "own input; shared experts, a sigmoid router, a held share "
            "(experts_held), router_input and ffn_act are the dropless "
            "layer's (capacity_factor=None)")
    kinds = cfg.layer_kinds or (None,)
    blocks = params["blocks"]
    if cfg.layer_kinds:
        # a scan over periods, the period's layers written out (static kinds)
        blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((cfg.num_layers // len(kinds), len(kinds))
                                + a.shape[1:]), blocks)

    def body(carry, layers):
        x, aux_sum, *rec_sum = carry
        fn = _moe_block
        if cfg.remat:
            fn = checkpoint_block(_moe_block, static_argnums=(0, 5, 6, 7))
        chosen = []
        for j, kind in enumerate(kinds):
            layer = layers if kind is None else jax.tree_util.tree_map(
                lambda a: a[j], layers)
            x, aux, *rec = fn(cfg, layer, x, cos, sin, train, kind, choices)
            aux_sum = aux_sum + aux
            if choices:
                chosen.append(rec[0])
            else:
                rec_sum = [a + r for a, r in zip(rec_sum, rec)]
        return (x, aux_sum, *rec_sum), (jnp.stack(chosen) if choices
                                        else None)

    init = (x, jnp.zeros((), jnp.float32))
    if counted:
        init += (jnp.zeros(len(record_columns(cfg)), jnp.int32),)
    with jax.named_scope("layer"):
        (x, aux_sum, *rec_sum), chosen = jax.lax.scan(body, init, blocks)
    x = L.block_norm(cfg, x, params["final_norm"])
    if choices:
        # [periods, layers a period, B, S, k] -> [L, B, S, k]
        return x, aux_sum / cfg.num_layers, chosen.reshape(
            (cfg.num_layers,) + chosen.shape[2:])
    return x, aux_sum / cfg.num_layers, rec_sum[0] if counted else None


def record_columns(cfg: MixtralConfig):
    """The routing record's columns (``moe/routed.py``)."""
    return RECORD if cfg.experts_held is None else RECORD_HELD


def forward_with_aux(cfg: MixtralConfig, params: PyTree, input_ids,
                     train: bool = True):
    x, aux, _ = _trunk(cfg, params, input_ids, train)
    return L.head_logits(cfg, params, x), aux


def forward_hidden(cfg: MixtralConfig, params: PyTree, input_ids):
    """The uncached inference forward up to the head: ``(the final norm's
    output [B, S, d], every layer's chosen experts int32 [L, B, S, top_k])``
    — what a comparison with a plain reference takes where a sequence's
    logits would not fit whole (it multiplies the positions it compares
    with the head itself) and hands that reference the program's own expert
    sets."""
    x, _, chosen = _trunk(cfg, params, input_ids, False, choices=True)
    return x, chosen


def loss_from_batch(cfg: MixtralConfig, params, batch, rng=None,
                    train: bool = True):
    """The next-token loss plus ``router_aux_loss_coef`` x the balance loss.
    A dropless configuration in training returns ``(loss, record)``:
    ``record`` is float32 scalars — the routing record's columns summed over
    the layers, ``lm_loss`` and ``router_aux`` (the engine carries it out of
    the step, ``runtime/engine.py``) — and takes the head's loss in chunks
    (``ops/chunked_ce.py``: a sequence's float32 logits are never whole)."""
    if isinstance(batch, (tuple, list)):
        input_ids, labels = batch
    else:
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
    if labels is None:
        labels = input_ids[:, 1:]
        input_ids = input_ids[:, :-1]
    if train and cfg.dropless:
        x, aux, rec = _trunk(cfg, params, input_ids, train)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        n = labels.size
        with jax.named_scope("loss"):
            # (the products inside it are ``head``'s: ops/chunked_ce.py)
            lm_loss = chunked_ce(head.astype(x.dtype),
                                 x.reshape(n, x.shape[-1]),
                                 labels.reshape(n),
                                 whole_chunks(n, CE_CHUNKS))
        record = dict(zip(record_columns(cfg), rec.astype(jnp.float32)))
        record.update(lm_loss=lm_loss, router_aux=aux)
        return lm_loss + cfg.router_aux_loss_coef * aux, record
    logits, aux = forward_with_aux(cfg, params, input_ids, train=train)
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        valid = labels >= 0
        safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        lm_loss = jnp.where(valid, nll, 0.0).sum() \
            / jnp.maximum(valid.sum(), 1)
        return lm_loss + cfg.router_aux_loss_coef * aux


def _moe_ffn(cfg: MixtralConfig, layer, y):
    """Training FFN: DeepSpeed's capacity gating -> (y, aux_loss)."""
    moe_params = {
        "gate_w": layer["gate_w"],
        "experts": {"w1": layer["experts_w1"], "w3": layer["experts_w3"],
                    "w2": layer["experts_w2"]},
    }
    return moe_apply(cfg.moe_cfg(), moe_params, y, train=True)


_EXPERT_LEAVES = ("experts_w1", "experts_w3", "experts_w2")


def _expert_kernel(blocks) -> bool:
    """Whether the inference FFN runs the Pallas grouped matmul
    (``moe/grouped_matmul.py``) or ``jax.lax.ragged_dot``: the kernel needs
    dense expert leaves (an INT8 record is expanded a layer at a time) on
    one shard (a Pallas call has no partitioning rule — under a ``tp`` or
    ``ep`` mesh GSPMD would gather the experts onto every chip;
    ``ragged_dot`` partitions)."""
    from .. import comm
    from ..ops import paged_kv
    from ..ops import quantization as quant

    topo = comm.get_topology()
    sharded = paged_kv.tp_mesh() is not None \
        or topo.tensor_parallel_size * topo.expert_parallel_size > 1
    return not sharded and not any(quant.is_record(blocks[k])
                                   for k in _EXPERT_LEAVES)


def _routed(cfg: MixtralConfig, layer, y, live=None, stacks=None,
            choices: bool = False, router_x=None, train: bool = False):
    """The dropless FFN: -> (y, routing record [3]); with ``choices``, (y,
    (record, the tokens' experts ``[..., top_k]``)); with ``train`` (the
    differentiated layer: its balance term asked for, its pairs combined
    choice-major), (y, (record, the layer's balance term)).  ``router_x``: the
    router's own input (``router_input="attn"``).
    ``stacks``: the whole ``[L, E, ..]`` expert leaves, read in place at
    ``layer["layer_index"]`` by the grouped-matmul kernel; without them
    ``layer`` holds its own ``[E, ..]`` slices."""
    whole = stacks is not None
    w1, w3, w2 = ((stacks if whole else layer)[k] for k in _EXPERT_LEAVES)
    out, *record = routed_ffn(
        y, layer["gate_w"], w1, w3, w2, cfg.top_k, cfg.norm_topk_prob,
        live=live, layer=layer["layer_index"] if whole else None,
        kernel=whole or _expert_kernel(layer), choices=choices,
        held=cfg.experts_held, score=cfg.router_score, act=cfg.ffn_act,
        router_x=router_x, balance=train, choice_major=train,
        bias=layer["gate_bias"] if cfg.router_bias else None,
        scale=cfg.routed_scale)
    if cfg.shared_experts:
        out = out + _shared(cfg, layer, y)
    return out, (tuple(record) if choices or train else record[0])


def _shared(cfg: MixtralConfig, layer, y):
    """The shared experts every token runs: plain dense SwiGLU matmuls over
    the experts side by side (``shared_w1`` / ``shared_w3`` ``[d, Sh * f]``,
    ``shared_w2 [Sh * f, d]``: the SUM of the ``Sh`` experts' outputs),
    averaged."""
    with jax.named_scope("layer/moe/shared"):
        gate = jax.nn.silu(qmm(y, layer["shared_w1"]))
        out = qmm(gate * qmm(y, layer["shared_w3"]), layer["shared_w2"],
                  y.dtype)
        return out / cfg.shared_experts


def init_cache(cfg: MixtralConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16, window_blocks: Optional[int] = None):
    """llama's K and V — plus, for a config with an indexer, its key: a
    third leaf ``[L, B, 1, S, index_head_dim]`` of the same layout (the
    block-paged pool's ``[L, NB, 1, block_size, DI]``).  A patterned model
    (``layer_kinds``; block-paged only) holds leaves BY LAYER KIND, each
    kind with a block-id space of its own (``ops/paged_kv.py`` "Layer
    kinds"): ``k`` / ``v`` ``[L_full, num_blocks, ...]`` for the
    full-attention layers, ``kw`` / ``vw`` ``[L_win, window_blocks, ...]``
    for the sliding-window ones."""
    if cfg.layer_kinds:
        if window_blocks is None:
            raise NotImplementedError(
                "a layer pattern with sliding-window layers (layer_kinds) "
                "is served through the block-paged pool (init_serving / "
                "ServingEngine): the contiguous cache of "
                "InferenceEngine.generate has one kind of state")
        periods = cfg.num_layers // len(cfg.layer_kinds)
        cache = {}
        for kind, blocks in (("full", batch_size), ("sliding", window_blocks)):
            n = periods * cfg.layer_kinds.count(kind)
            if n:
                shape = (n, blocks, cfg.num_kv_heads, max_len, cfg.head_dim)
                ck, cv, _ = KIND_LEAVES[kind]
                cache[ck] = jnp.zeros(shape, dtype)
                cache[cv] = jnp.zeros(shape, dtype)
        return cache
    cache = L.init_cache(cfg, batch_size, max_len, dtype)
    if cfg.index_heads:
        cache["idx"] = jnp.zeros((cfg.num_layers, batch_size, 1, max_len,
                                  cfg.index_head_dim), dtype)
    return cache


def _sparse_attend(cfg: MixtralConfig, layer, y, q, k, v, ck, cv, extra, pos,
                   block_tables, chunk_valid, index):
    """``llama.forward_cached``'s ``attend_fn`` for a config with an
    indexer.  ``extra`` is what the layers carry beside K and V: ``"idx"``
    the indexer-key pool, ``"counts"`` what the selections did so far
    (``sparse_index_attention.COUNTS``, summed over the layers) and, asked
    for, ``"keys"``, the buffer of every layer's chosen keys.  The window's
    K, V and indexer keys go to the same ``(layer, block, offset)``, then
    the read selects (``ops/sparse_index_attention.py``)."""
    from ..ops import paged_kv
    qi, ki, wi = _indexer(cfg, *layer_accessors(layer), y,
                          lambda a: L._rope_cached(cfg, a, pos))
    ck, cv = paged_kv.paged_cache_update(
        ck, cv, k, v, pos, block_tables, valid=chunk_valid, layer=index)
    idx_pool = paged_kv.paged_window_update(
        extra["idx"], ki, pos, block_tables, valid=chunk_valid, layer=index)
    attn, counts, *keep = sparse_attention.paged_sparse_attention(
        q, ck, cv, idx_pool, qi, wi, block_tables, pos,
        topk=cfg.index_topk, layer=index, valid=chunk_valid,
        return_keep="keys" in extra)
    extra = {**extra, "idx": idx_pool, "counts": extra["counts"] + counts}
    if keep:
        extra["keys"] = extra["keys"].at[index].set(keep[0])
    return attn, ck, cv, extra


def forward_cached(cfg: MixtralConfig, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False,
                   routing: bool = False, choices: bool = False):
    """Incremental MoE forward (reference ``moe_inference.py``: expert
    routing runs per decode token too) — llama's cached path with the
    routed FFN hooked in.  ``lengths`` (per-sequence positions for
    continuous-batching slots), ``block_tables`` (block-paged cache
    layout), and ``all_positions`` (speculative K+1 verify head) pass
    straight through: expert routing is position- and
    layout-independent.  ``routing`` adds a third result: int32
    ``[L, 3]``, per layer the experts with at least one row, the routed
    rows and the largest group (``moe/routed.py RECORD``), counted over the
    live tokens (``cached.live_tokens``) — with an indexer the pair of that
    and int32 ``[5]``, what the layers' selections scored, chose and read
    (``sparse_index_attention.COUNTS``, summed over the layers).
    ``choices`` (paged caches) adds
    a last result, the discrete choices of every layer: ``{"experts": int32
    [L, B, T, top_k]}`` and, with an indexer, ``"keys": bool [L, B, T,
    max_seq_len]`` (the keys each query attended) — what a comparison with
    a plain reference hands that reference, so that near-ties the two sides
    break differently do not count as a difference."""
    if cfg.layer_kinds and not isinstance(block_tables, dict):
        raise NotImplementedError(
            "a layer pattern with sliding-window layers (layer_kinds) is "
            "served through the block-paged pool with a block table per "
            "layer kind (init_serving / ServingEngine); the contiguous "
            "cache of InferenceEngine.generate has one kind of state")
    live = live_tokens(input_ids, lengths, block_tables)
    blocks, stacks = params["blocks"], None
    if _expert_kernel(blocks):
        # the expert stacks stay out of the layer scan: each layer's slice
        # of them would be copied out for the kernel (134 MB a matmul at
        # OLMoE's widths); the scan carries the layer's index instead
        stacks = {k: blocks[k] for k in _EXPERT_LEAVES}
        blocks = {k: v for k, v in blocks.items() if k not in stacks}
        blocks["layer_index"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        params = {**params, "blocks": blocks}
    attend_fn = extra = None
    if cfg.index_heads:
        if block_tables is None:
            raise NotImplementedError(
                "learned sparse attention (index_heads > 0) is served "
                "through the block-paged pool (init_serving / "
                "ServingEngine); the contiguous cache of "
                "InferenceEngine.generate has no indexer-key leaf")
        # what the layers carry beside K and V (:func:`_sparse_attend`)
        extra = {"idx": cache["idx"],
                 "counts": jnp.zeros(len(sparse_attention.COUNTS), jnp.int32)}
        if choices:
            s_max = block_tables.shape[1] * (
                math.prod(cache["k"].shape[3:]) // cfg.head_dim)
            extra["keys"] = jnp.zeros(
                (cfg.num_layers,) + input_ids.shape + (s_max,), bool)
        attend_fn = functools.partial(_sparse_attend, cfg)
    logits, kv, records, *carried = L.forward_cached(
        cfg, params, input_ids,
        cache if cfg.layer_kinds or cfg.latent
        else {"k": cache["k"], "v": cache["v"]},
        pos, lengths=lengths, block_tables=block_tables,
        mlp_fn=lambda lyr, y: _routed(cfg, lyr, y, live, stacks, choices),
        all_positions=all_positions, attend_fn=attend_fn, extra=extra)
    chosen = {}
    if choices:
        records, chosen["experts"] = records
    if carried:
        extra, = carried
        kv["idx"] = extra["idx"]
        records = (records, extra["counts"])
        if choices:
            chosen["keys"] = extra["keys"]
    out = (logits, kv, records) if routing else (logits, kv)
    return out + (chosen,) if choices else out


def tp_rules(cfg: MixtralConfig, abstract_params: PyTree) -> PyTree:
    rules = L.tp_rules(cfg, abstract_params)
    blocks = rules["blocks"]
    for k in ("w1", "w2", "w3"):
        del blocks[k]
    blocks["gate_w"] = P()
    if cfg.router_bias:
        blocks["gate_bias"] = P()
    blocks["experts_w1"] = P(None, EP_AXIS, None, TP_AXIS)
    blocks["experts_w3"] = P(None, EP_AXIS, None, TP_AXIS)
    blocks["experts_w2"] = P(None, EP_AXIS, TP_AXIS, None)
    if cfg.shared_experts:
        blocks["shared_w1"] = P(None, None, TP_AXIS)
        blocks["shared_w3"] = P(None, None, TP_AXIS)
        blocks["shared_w2"] = P(None, TP_AXIS, None)
    if cfg.parallel_block:
        del blocks["mlp_norm"]
    if cfg.tie_embeddings:
        del rules["lm_head"]
    if cfg.index_heads:
        # the indexer is small and its one key head has nothing to split
        for k in ("idx_q_w", "idx_k_w", "idx_w_w", "idx_k_norm"):
            blocks[k] = P()
    return rules


def build(cfg: Optional[MixtralConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or MixtralConfig(**overrides)

    def init_fn(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch, rng=None, train=True):
        return loss_from_batch(cfg, params, batch, rng=rng, train=train)

    def apply_fn(params, batch, rng=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return forward_with_aux(cfg, params, ids, train=False)[0]

    decode_hooks = {
        "init_cache": lambda b, s, dtype=jnp.bfloat16, **kinds: init_cache(
            cfg, b, s, dtype, **kinds),
        "forward_cached": lambda params, ids, cache, pos, lengths=None,
            block_tables=None, all_positions=False, routing=False,
            choices=False:
            forward_cached(cfg, params, ids, cache, pos, lengths,
                           block_tables, all_positions, routing, choices),
        # ``forward_cached(..., routing=True)`` returns the per-layer
        # routing record as a third result (the serving engine's ring)
        "routing_record": True,
        "max_seq_len": cfg.max_seq_len,
        "supports_lengths": True,
        "supports_paged": True,
        "supports_verify": True,
        # the MoE path reads the pool only through the shared llama cached
        # attention (ops/paged_kv), so int8 records pass through untouched
        # (an indexer's selection reads a float pool: the engine refuses
        # that pair by name)
        "supports_kv_quant": True,
        # raw next-token logits reach the serving engine's on-device
        # sampler unchanged (per-slot temperature/top-k/top-p)
        "supports_sampling": True,
    }
    # latent attention: the pool is ONE leaf, a latent a token
    decode_hooks.update(L.latent_hook(cfg))
    if cfg.index_heads:
        # learned sparse attention: the cache has a third leaf, and a row
        # past ``topk`` keys reads ``topk`` of them (the engine's counters)
        decode_hooks["sparse_attention"] = {"topk": cfg.index_topk}
    if cfg.experts_held is not None:
        # the routing record's fourth column (``routed.RECORD_HELD``)
        decode_hooks["experts_held"] = cfg.experts_held
    if "sliding" in cfg.layer_kinds:
        # the pool holds leaves by layer kind, each kind under a block
        # table of its own (``init_cache(..., window_blocks=)``;
        # ``forward_cached`` takes ``{"full", "window"}`` tables)
        decode_hooks["window_layers"] = {
            "window": cfg.sliding_window,
            "layers": {kind: cfg.num_layers // len(cfg.layer_kinds)
                       * cfg.layer_kinds.count(kind)
                       for kind in ("full", "sliding")}}

    return ModelSpec(
        init_fn=init_fn, model_config=cfg, loss_fn=loss_fn, apply_fn=apply_fn,
        tp_rules=lambda ap: tp_rules(cfg, ap),
        flops_per_token=6.0 * cfg.active_params(),
        decode_hooks=decode_hooks,
        # w8a8 serving: attention projections run the s8 path through the
        # shared mm accessors; stacked expert weights store int8 and
        # dequantize per layer at point of use inside routed_ffn (the
        # grouped matmuls have no s8 kernel — yet)
        quant_aware=True,
        blocks_key=("blocks",),
        name=f"mixtral-{cfg.num_layers}l-{cfg.num_experts}e")
