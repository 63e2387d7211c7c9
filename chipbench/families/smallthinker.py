"""``family: smallthinker`` — a ``chipbench/configs`` file (the published
configuration of PowerInfer's SmallThinker-21BA3B-Instruct) to the program's
``models/mixtral.py`` configuration: the sequential RMSNorm block over
``[full, sliding, sliding, sliding]`` (no rotation on the full layer, a
4,096-key window and rotate-half rotary on the sliding ones), 28 query heads
on 4 KV heads, softmax-scored ReGLU experts top-6 renormalised, the router
fed the attention's normed input, an untied head, dropless TRAINING — its
sizes and parameter counts, its plain reference
(``chipbench/reference_smallthinker.py``), and the FLOP functions its
readers divide by.

What is BUILT is one chip's share of a deployment in which four chips share
each layer (the configuration file's ``deployment``): ``depth`` layers of
the published ``num_hidden_layers``, the ``moe_num_primary_experts`` routed
experts from ``experts_first`` on of the published
``moe_num_primary_experts_published`` (the router keeps its published width
and its experts per token, and the expert layer returns the held experts'
partial sum), ``vocab_size`` rows of the published
``vocab_size_published``.  ``overrides`` are the cell's ``model`` settings,
applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import reference_smallthinker as ref

#: a period of the published layouts: one full layer, three sliding ones
PERIOD = 4


def _kinds(config: Dict[str, Any]) -> Sequence[str]:
    """The built layers' kinds."""
    return ref.layer_kinds(config, config["depth"])


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import mixtral

    kinds = _kinds(config)
    if kinds != list(kinds[:PERIOD]) * (len(kinds) // PERIOD):
        raise ValueError(f"sliding_window_layout is no repetition of its "
                         f"first {PERIOD} layers: {kinds}")
    if config["rope_layout"] != config["sliding_window_layout"] \
            or not config["moe_primary_router_apply_softmax"] \
            or config["rope_scaling"] is not None \
            or config["tie_word_embeddings"]:
        raise ValueError("family smallthinker builds the published block: "
                         "rotated iff sliding, a softmax router, no rotary "
                         "scaling, an untied head")
    cfg = mixtral.MixtralConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["depth"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_width=config["head_dim"],
        hidden_size=config["hidden_size"],
        ffn_size=config["moe_ffn_hidden_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        layer_kinds=tuple(kinds[:PERIOD]),
        sliding_window=config["sliding_window_size"],
        num_experts=config["moe_num_primary_experts_published"],
        top_k=config["moe_num_active_primary_experts"],
        norm_topk_prob=config["norm_topk_prob"],
        router_score="softmax", router_input="attn", ffn_act="relu",
        capacity_factor=None,
        router_aux_loss_coef=config["router_aux_loss_coef"],
        experts_held=(config["experts_first"],
                      config["moe_num_primary_experts"]))
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"MixtralConfig has no field {key!r}")
        setattr(cfg, key, value)
    return mixtral.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = _kinds(config)
    return {"layers": config["depth"], "d": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "ffn": config["moe_ffn_hidden_size"],
            "experts": config["moe_num_primary_experts"],
            "experts_published": config["moe_num_primary_experts_published"],
            "top_k": config["moe_num_active_primary_experts"],
            "window": config["sliding_window_size"],
            "sliding_layers": kinds.count("sliding"),
            "full_layers": kinds.count("full"),
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def expert_params(a: Dict[str, int]) -> int:
    """One expert: the three ReGLU matrices."""
    return 3 * a["d"] * a["ffn"]


def _layer_matrices(a: Dict[str, int]) -> int:
    """What every token multiplies with in one layer outside its experts:
    q, k, v, o (``heads x head_dim`` is 1.4 ``d``) and the router over all
    published experts."""
    hq, hkv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return 2 * a["d"] * hq + 2 * a["d"] * hkv \
        + a["d"] * a["experts_published"]


def num_params(config: Dict[str, Any]) -> int:
    """What this chip holds: the vocabulary slice of the token table and of
    the untied head + per layer (attention, two norms, router, the HELD
    routed experts) + the final norm."""
    a = arch(config)
    return 2 * a["vocab"] * a["d"] + a["layers"] * (
        _layer_matrices(a) + 2 * a["d"]
        + a["experts"] * expert_params(a)) + a["d"]


def held_pairs_per_token(config: Dict[str, Any]) -> float:
    """Pairs a token has on HELD experts in one layer under even routing:
    ``top_k`` chosen of the published experts, of which ``experts /
    experts_published`` are held."""
    a = arch(config)
    return a["top_k"] * a["experts"] / a["experts_published"]


def visible_pairs(config: Dict[str, Any], seq_len: int) -> int:
    """(query, key) pairs one ``seq_len``-token sequence attends, summed
    over the built layers, exactly: the causal triangle in a full layer,
    ``min(p + 1, window)`` keys for the query at ``p`` in a sliding one."""
    a = arch(config)
    s, w = int(seq_len), min(a["window"], int(seq_len))
    return a["full_layers"] * (s * (s + 1) // 2) \
        + a["sliding_layers"] * (w * (w + 1) // 2 + (s - w) * w)


def train_flops_per_token(config: Dict[str, Any], seq_len: int,
                          held_pairs: Optional[float] = None) -> float:
    """Needed FLOPs a token, forward + backward, recomputation not counted:
    6 x the parameters a token multiplies with HERE — attention, router,
    the head's slice, and its pairs on held experts (``held_pairs``: a
    token's pairs on held experts summed over the layers, from the step's
    own ``expert_rows``; even routing without it) — + 12 x ``heads x
    head_dim`` x the keys a query can see, summed over the layers."""
    a = arch(config)
    if held_pairs is None:
        held_pairs = a["layers"] * held_pairs_per_token(config)
    multiplied = a["layers"] * _layer_matrices(a) + a["d"] * a["vocab"] \
        + held_pairs * expert_params(a)
    return 6.0 * multiplied + 12.0 * a["heads"] * a["head_dim"] \
        * visible_pairs(config, seq_len) / seq_len


def expert_train_flops(config: Dict[str, Any], expert_rows: float) -> float:
    """FLOPs the grouped matmuls need for ``expert_rows`` routed rows: three
    matmuls, each forward, ``d_lhs`` and ``d_rhs``, 2 FLOPs a
    multiply-add."""
    a = arch(config)
    return 18.0 * expert_rows * a["d"] * a["ffn"]


def flash_train_flops(config: Dict[str, Any], seq_len: int,
                      rows: int) -> float:
    """FLOPs the flash kernels need for ``rows`` sequences: the forward's
    two matmuls and the backward's five (its score recomputation
    included) over the visible (query, key) pairs."""
    a = arch(config)
    return 14.0 * a["heads"] * a["head_dim"] * rows \
        * visible_pairs(config, seq_len)


def program_hidden(model, params, tokens, dtype: str):
    """The PROGRAM's uncached forward up to the head on ``tokens [B, S]``,
    a row at a time (a row's float32 logits would not fit beside a training
    engine): ``(final norm's output [B, S, d] in the compute dtype, the
    layers' chosen experts int32 [L, B, S, k])`` from the engine's master
    weights cast to ``dtype`` as its step casts them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import mixtral

    cfg = model.model_config
    compute = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[dtype]

    @jax.jit
    def one(params, row):
        p = jax.tree_util.tree_map(
            lambda a: a.astype(compute)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
        return mixtral.forward_hidden(cfg, p, row[None])

    out = [one(params, jnp.asarray(row)) for row in np.asarray(tokens)]
    return (jnp.concatenate([x for x, _ in out], axis=0),
            jnp.concatenate([c for _, c in out], axis=1))


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None, variant=None):
    """``forced``: the program's own expert sets for the reference to take
    (``reference_smallthinker.logits``); the result is then ``(logits,
    report)``."""
    return ref.logits(config, params, tokens, at=at, forced=forced,
                      variant=variant)


def next_token_loss(config: Dict[str, Any], params: Any, tokens, **how):
    """``how``: ``reference_smallthinker.next_token_loss``'s keywords."""
    return ref.next_token_loss(config, params, tokens, **how)
