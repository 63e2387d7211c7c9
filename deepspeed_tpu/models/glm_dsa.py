"""GLM-5's language model (``zai-org/GLM-5``, ``model_type: glm_moe_dsa``)
with its multi-token-prediction module, served through ``init_serving`` /
``ServingEngine``.

**The trunk** is ``models/dots3.py``'s FULL kind in every layer and nothing
else of that family: latent attention (low-rank queries, one joint key /
value latent ``c`` a token beside one rotated key ``k_r`` all heads share,
interleaved rotary pairs, read absorbed) under a learned selection
(DeepSeek-V3.2's indexer: ``index_heads`` heads of ``index_head_dim`` choose
``index_topk`` keys a query), with NO head gate and NO rescale of the two
latents, a value head wider than the unrotated key part (``v_head_dim`` 256
beside ``qk_nope_dim`` 192) and an indexer that rotates its first
``qk_rope_dim`` values as INTERLEAVED pairs (``indexer_rope_interleave``).
The block, the cache write, the scoring, the selection and the selected read
are that file's one implementation (``dots3.block_cached``,
:class:`GlmDsaConfig` a ``Dots3Config`` whose every layer is
``"full_attention"``); the FFN is its pair too — the first ``first_dense``
layers a dense SwiGLU, every other layer sigmoid scores over all experts,
the top-k of ``score + gate_bias``, renormalised, times ``routed_scale``,
beside one shared expert, on a held share (``experts_held``).

**The module** (``num_nextn_predict_layers`` 1; DeepSeek-V3 section 2.2 as
the family's serving code reads it) is one more block of the routed kind fed
by the trunk: for position ``t`` with the NEXT token ``x_{t+1}``,

    u_t = [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(h_t)] W_eh        (2d -> d)
    logits^mtp_t = Head(RMSNorm_s(block(u)_t))    a distribution for x_{t+2}

with ``h_t`` the trunk's FINAL-NORMED hidden state, ``Emb`` and ``Head`` the
trunk's own table and head, rotary position ``t``, and attention, indexer,
router, experts and shared expert of its own (``params["mtp"]``).  Its cache
rows are ONE MORE LAYER of the trunk's two leaves (``latent`` and ``idx``,
layer ``L`` behind the trunk's ``0 .. L - 1``) under the trunk's block table:
entry ``t`` is made from ``h_t`` AND ``x_{t+1}``, so it is written once the
next token is known (:func:`draft_cached`; the decode hook ``self_draft``
describes it to the engine, which names no family).

Served on one shard through the block-paged pool only; what else such a
model is refused is ``inference/options.py KIND_REFUSES`` — the ``latent``
and ``indexer`` kinds' rows.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..runtime.model import ModelSpec
from . import cached
from . import dots3 as D
from . import llama as L
from .cached import live_tokens, qmm

PyTree = Any
FULL = D.FULL


@dataclasses.dataclass
class GlmDsaConfig(D.Dots3Config):
    """``Dots3Config`` with every layer a full one (``layer_types`` is
    filled in from ``num_layers`` where it is not given), no gate, no
    rescale, the indexer's rotary interleaved; ``mtp_layers`` modules (0:
    the trunk alone; 1: the published one)."""
    head_gate: bool = False
    lora_rescale: bool = False
    index_rope_interleaved: bool = True
    mtp_layers: int = 1

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = ("full_attention",) * int(self.num_layers)
        if set(self.layer_types) != {"full_attention"}:
            raise ValueError("every GLM-5 layer is a full one")
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers={self.mtp_layers}: the published "
                             "model has ONE module; deeper ones are not "
                             "built")
        super().__post_init__()

    @staticmethod
    def glm_5() -> "GlmDsaConfig":
        """zai-org/GLM-5's language model at its published widths: 78
        layers, d 6,144; 64 heads x (192 + 64) over a latent of 512 + 64,
        values 256 wide, queries through rank 2,048, theta 1e6, an indexer of
        32 heads x 128 choosing 2,048 keys; three leading dense SwiGLUs of
        12,288, then 256 sigmoid-scored SwiGLU experts of 2,048 top-8 with a
        selection bias, renormalised, times 2.5, beside one shared expert;
        an untied head over 154,880 rows; one multi-token-prediction module.
        One chip's share of it (``experts_held``, fewer layers, a vocabulary
        slice) is a deployment's to state."""
        return GlmDsaConfig(
            vocab_size=154880, max_seq_len=202752, hidden_size=6144,
            num_layers=78, num_heads=64, num_kv_heads=64, head_width=256,
            q_lora_rank=2048, kv_lora_rank=512, qk_nope_dim=192,
            qk_rope_dim=64, v_head_dim=256, rope_theta=1e6, rms_eps=1e-5,
            rope_interleaved=True, index_heads=32, index_head_dim=128,
            index_topk=2048, first_dense=3, dense_ffn_size=12288,
            ffn_size=2048, num_experts=256, top_k=8, norm_topk_prob=True,
            router_score="sigmoid", router_bias=True, routed_scale=2.5,
            shared_experts=1, capacity_factor=None, mtp_layers=1)

    def mtp_params(self) -> int:
        """The module's own parameters: its two norms, ``W_eh``, its closing
        norm and one routed block."""
        d, f = self.hidden_size, self.ffn_size
        moe = d * self.num_experts + self.num_experts * self.router_bias \
            + (self.experts_here + self.shared_experts) * 3 * d * f
        return self.mtp_layers * (3 * d + 2 * d * d
                                  + self.attn_params(FULL) + moe)

    def num_params(self) -> int:
        return super().num_params() + self.mtp_params()


# ------------------------------------------------------------------ parameters
def init_params(cfg: GlmDsaConfig, rng) -> PyTree:
    """``dots3.init_params`` for the trunk; the module's block from the same
    rule at one layer (``params["mtp"]["blocks"]``: stacks of one), its norms
    at one, ``eh_w`` N(0, 0.02)."""
    params = D.init_params(cfg, rng)
    if cfg.mtp_layers:
        d = cfg.hidden_size
        one = dataclasses.replace(cfg, layer_types=("full_attention",),
                                  first_dense=0, mtp_layers=0)
        rng = jax.random.fold_in(rng, cfg.num_layers)
        block = D.init_params(
            dataclasses.replace(one, vocab_size=1), rng)["blocks"]
        params["mtp"] = {
            "enorm": jnp.ones((d,)), "hnorm": jnp.ones((d,)),
            "eh_w": (jax.random.normal(jax.random.fold_in(rng, 1),
                                       (2 * d, d)) * 0.02)
            .astype(jnp.float32),
            "final_norm": jnp.ones((d,)), "blocks": block}
    return params


# ---------------------------------------------------------------------- module
def draft_cached(cfg: GlmDsaConfig, params, hidden, next_ids, cache, pos,
                 lengths=None, block_tables=None, all_positions=False,
                 at=None, routing: bool = False, choices: bool = False):
    """The module over a window (module docstring): ``hidden [B, T, d]`` the
    trunk's final-normed states at the window's positions, ``next_ids [B,
    T]`` the token AFTER each; ``pos`` / ``lengths`` / ``block_tables`` /
    ``all_positions`` as ``forward_cached`` takes them (``cached.window``),
    ``cache`` the trunk's tree with the module's layer behind the trunk's;
    ``at`` (int32 ``[B]``): the window offset whose logits a row gets, where
    that is not its last real one.
    -> ``(logits for the token after next, cache[, (routing record [cols],
    selection counts [5])][, {"experts", "keys"} of the one layer])``."""
    m = params["mtp"]
    tables = D.kind_tables(cfg, block_tables)
    w = cached.window(next_ids, pos, lengths, tables["full"])
    live = live_tokens(next_ids, lengths, tables)
    with jax.named_scope("embed"):
        emb = params["embed"][next_ids].astype(params["embed"].dtype)
    with jax.named_scope("mtp/join"):
        u = qmm(jnp.concatenate(
            [L.rms_norm(emb, m["enorm"], cfg.rms_eps),
             L.rms_norm(hidden.astype(emb.dtype), m["hnorm"], cfg.rms_eps)],
            axis=-1), m["eh_w"])
    blocks = m["blocks"]
    layer = jax.tree_util.tree_map(lambda a: a[0], blocks[FULL])
    s_max = tables["full"].shape[1] * cache["latent"].shape[3]
    with jax.named_scope("mtp"):
        x, latent, idx, aux = D.block_cached(
            cfg, blocks, D.expert_stacks(blocks), w, live, choices, s_max,
            u, layer, cache["latent"], cache["idx"], cfg.layers_of(FULL),
            tables["full"], FULL, cfg.first_dense)
    cache = {**cache, "latent": latent, "idx": idx}
    with jax.named_scope("layer/norm"):
        x = L.rms_norm(x, m["final_norm"], cfg.rms_eps)
    if at is not None:
        x = cached.gather_last(x, jnp.asarray(at, jnp.int32) + 1)
    elif not all_positions:
        x = cached.gather_last(x, w.gather)
    with jax.named_scope("head"):
        logits = x @ params["lm_head"].astype(x.dtype)
    out = (logits, cache)
    if routing:
        out += ((aux["record"], aux["counts"]),)
    if choices:
        out += ({"experts": aux["experts"], "keys": aux["keys"]},)
    return out


def init_cache(cfg: GlmDsaConfig, num_blocks: int, block_size: int,
               dtype=jnp.bfloat16, draft_layers: int = 0):
    """``dots3.init_cache`` with ``draft_layers`` more layers of the two
    leaves: the module's rows, asked for by an engine that drafts with it
    (decode hook ``self_draft["cache"]``)."""
    return D.init_cache(cfg, num_blocks, block_size, dtype,
                        more_full=draft_layers)


def build(cfg: Optional[GlmDsaConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or GlmDsaConfig(**overrides)

    def loss_fn(params, batch, rng=None, train=True):
        if train:
            raise NotImplementedError(
                "glm_dsa is an inference path: a backward through the "
                "learned selection and the absorbed latent reads is not "
                "built")
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logp = jax.nn.log_softmax(
            D.forward(cfg, params, ids[:, :-1]).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1).mean()

    def apply_fn(params, batch, rng=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return D.forward(cfg, params, ids)

    decode_hooks = {
        "init_cache": lambda b, s, dtype=jnp.bfloat16, **more: init_cache(
            cfg, b, s, dtype, **more),
        "forward_cached": lambda params, ids, cache, pos, lengths=None,
            block_tables=None, all_positions=False, routing=False,
            choices=False, hidden=False:
            D.forward_cached(cfg, params, ids, cache, pos, lengths,
                             block_tables, all_positions, routing, choices,
                             hidden),
        "routing_record": True,
        "max_seq_len": cfg.max_seq_len,
        "supports_lengths": True,
        "supports_paged": True,
        # every window position's logits AND hidden state
        # (``all_positions=True, hidden=True``): the selected read takes a
        # ``[slots, K + 1]`` window (``ops/sparse_index_attention.py``)
        "supports_verify": True,
        "supports_kv_quant": False,
        "supports_sampling": True,
        **L.latent_hook(cfg.attn(FULL)),
        "sparse_attention": {"topk": cfg.index_topk},
    }
    if cfg.mtp_layers:
        # a module of the model's own that drafts for the engine's verify
        # round: how many tokens ahead it guesses, the layers its rows add
        # to the two leaves and the keywords ``init_cache`` takes for them,
        # and its forward from ``(hidden, next ids)`` (``draft_cached``'s
        # contract)
        decode_hooks["self_draft"] = {
            "depth": cfg.mtp_layers, "layers": cfg.mtp_layers,
            "cache": {"draft_layers": cfg.mtp_layers},
            "forward": lambda params, hidden, next_ids, cache, pos,
            lengths=None, block_tables=None, all_positions=False, at=None,
            routing=False, choices=False:
            draft_cached(cfg, params, hidden, next_ids, cache, pos, lengths,
                         block_tables, all_positions, at, routing, choices)}
    if cfg.experts_held is not None:
        decode_hooks["experts_held"] = cfg.experts_held
    return ModelSpec(
        init_fn=lambda rng: init_params(cfg, rng), model_config=cfg,
        loss_fn=loss_fn, apply_fn=apply_fn,
        # served on one shard: every leaf whole on every chip
        tp_rules=lambda ap: jax.tree_util.tree_map(lambda _: P(), ap),
        flops_per_token=6.0 * cfg.active_params(),
        decode_hooks=decode_hooks, quant_aware=False,
        name=f"glm-dsa-{cfg.num_layers}l-{cfg.num_experts}e")
