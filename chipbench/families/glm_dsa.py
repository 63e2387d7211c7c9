"""``family: glm_dsa`` — a ``chipbench/configs`` file (the published
``glm_moe_dsa`` configuration of zai-org/GLM-5) to the program's
``models/glm_dsa.py`` configuration: sequential RMSNorm blocks whose every
layer is latent attention under a learned selection (no gate, no rescale,
values wider than the unrotated key part, an interleaved indexer rotary),
leading dense FFNs, sigmoid-scored experts top-k with a selection bias times
2.5 beside one shared expert, an untied head, and ONE multi-token-prediction
module that is one more routed block fed by the trunk's hidden state and the
next token — its sizes and parameter counts, its plain reference
(``chipbench/reference_glm5.py``), and the byte and FLOP functions its
readers divide by.

What is BUILT is one chip's share of a deployment in which sixteen chips
share each layer (the configuration file's ``deployment``): ``dense_depth``
of the leading dense layers and ``depth - dense_depth`` routed layers, the
module whole, the ``n_routed_experts`` routed experts from ``experts_first``
on of the published ``n_routed_experts_published`` in every routed block (the
router keeps its published width and its experts per token, and the expert
layer returns the held experts' partial sum), ``vocab_size`` rows of the
published ``vocab_size_published``.  ``overrides`` are the cell's ``model``
settings, applied as attributes.

**What the readers divide by** is what the ALGORITHM needs, whatever
implements it (``families/dots3.py``'s convention): a selected read needs
the chosen keys' latents (``kv_selected`` x 1,152 B) and the absorbed
products over them, scoring needs every valid index key once (256 B) and its
dot products.  The program lands whole blocks, so its time covers more bytes
than these: that lowers a share and cannot raise it."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import costs, reference_glm5


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import glm_dsa

    reference_glm5._check(config, None)
    cfg = glm_dsa.GlmDsaConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        hidden_size=config["hidden_size"], num_layers=config["depth"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_width=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rms_eps=config["rms_norm_eps"], rope_interleaved=True,
        index_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        first_dense=reference_glm5.dense_layers(config),
        dense_ffn_size=config["intermediate_size"],
        ffn_size=config["moe_intermediate_size"],
        num_experts=config["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        router_score="sigmoid", router_bias=True,
        routed_scale=float(config["routed_scaling_factor"]),
        shared_experts=config["n_shared_experts"],
        experts_held=(config["experts_first"], config["n_routed_experts"]),
        capacity_factor=None,
        mtp_layers=config["num_nextn_predict_layers"])
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"GlmDsaConfig has no field {key!r}")
        setattr(cfg, key, value)
    return glm_dsa.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, Any]:
    """``heads`` / ``kv_heads`` / ``head_dim`` describe the EXPANDED form;
    ``layers`` the trunk's layers built (the module is ``mtp_layers``
    more)."""
    return {"layers": config["depth"], "d": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["qk_nope_head_dim"]
            + config["qk_rope_head_dim"],
            "q_rank": config["q_lora_rank"], "rank": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "v": config["v_head_dim"],
            "index_heads": config["index_n_heads"],
            "index_head_dim": config["index_head_dim"],
            "index_topk": config["index_topk"],
            "mtp_layers": config["num_nextn_predict_layers"],
            "dense_layers": reference_glm5.dense_layers(config),
            "dense_ffn": config["intermediate_size"],
            "ffn": config["moe_intermediate_size"],
            "experts": config["n_routed_experts"],
            "experts_published": config["n_routed_experts_published"],
            "shared_experts": config["n_shared_experts"],
            "top_k": config["num_experts_per_tok"],
            # plain rotary, no scaling: every position is as the first
            "original_positions": config["max_position_embeddings"],
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def _expert_params(a: Dict[str, Any]) -> int:
    return 3 * a["d"] * a["ffn"]


def _attn_params(a: Dict[str, Any]) -> int:
    """A layer's attention: the low-rank query pair and its norm, the joint
    down-projection and its norm, the up-projection, the output projection,
    the indexer (queries from the query latent, one key, the head weights,
    the key's LayerNorm)."""
    d, hi, di = a["d"], a["index_heads"], a["index_head_dim"]
    return d * a["q_rank"] + a["q_rank"] \
        + a["q_rank"] * a["heads"] * (a["nope"] + a["rope"]) \
        + d * (a["rank"] + a["rope"]) + a["rank"] \
        + a["rank"] * a["heads"] * (a["nope"] + a["v"]) \
        + a["heads"] * a["v"] * d \
        + a["q_rank"] * hi * di + d * di + d * hi + 2 * di


def _routed_rest(a: Dict[str, Any]) -> int:
    """A routed block without its routed experts: attention, both norms, the
    router over all published experts with its bias, the shared expert."""
    return _attn_params(a) + 2 * a["d"] \
        + a["d"] * a["experts_published"] + a["experts_published"] \
        + a["shared_experts"] * _expert_params(a)


def _trunk_outside_experts(a: Dict[str, Any]) -> int:
    return a["dense_layers"] * (_attn_params(a) + 2 * a["d"]
                                + 3 * a["d"] * a["dense_ffn"]) \
        + (a["layers"] - a["dense_layers"]) * _routed_rest(a)


def _module_outside_experts(a: Dict[str, Any]) -> int:
    """The module without its routed experts: its two norms, ``W_eh``, its
    closing norm, one routed block's rest."""
    return a["mtp_layers"] * (3 * a["d"] + 2 * a["d"] * a["d"]
                              + _routed_rest(a))


def num_params(config: Dict[str, Any]) -> int:
    """What this chip holds: the vocabulary slice of the token table and of
    the untied head, the trunk's layers and the module (the HELD routed
    experts of each routed block), the final norm."""
    a = arch(config)
    routed = a["layers"] - a["dense_layers"] + a["mtp_layers"]
    return 2 * a["vocab"] * a["d"] + a["d"] + _trunk_outside_experts(a) \
        + _module_outside_experts(a) \
        + routed * a["experts"] * _expert_params(a)


def active_params(config: Dict[str, Any]) -> int:
    """What one token of a ROUND multiplies with: the trunk AND the module,
    each routed block's expected held share of the token's top-k."""
    a = arch(config)
    routed = a["layers"] - a["dense_layers"] + a["mtp_layers"]
    held = a["top_k"] * a["experts"] / a["experts_published"]
    return int(num_params(config)
               - routed * (a["experts"] - held) * _expert_params(a))


def round_means(names: Sequence[str]) -> Optional[Dict[str, float]]:
    """Means of the named arguments over the ring's ``spec_round`` spans
    (None without a ring or a round)."""
    from deepspeed_tpu.telemetry import trace

    tl = trace.kept("serve")
    events = [e for e in (tl.events() if tl is not None else ())
              if e.get("ph") == "X" and e.get("name") == "spec_round"]
    if not events:
        return None
    return {n: sum(float(e["args"].get(n, 0)) for e in events) / len(events)
            for n in names}


def decode_weight_bytes(config: Dict[str, Any],
                        counters: Dict[str, Any]) -> float:
    """Weight bytes one ROUND must read: trunk and module outside the routed
    experts, the head (twice: the trunk's and the module's logits) — and the
    held experts its rows were routed to (the mean ``experts_touched`` of
    the ring's ``spec_round`` spans; without a ring, every held expert)."""
    a = arch(config)
    routed = a["layers"] - a["dense_layers"] + a["mtp_layers"]
    means = round_means(("experts_touched",))
    sets = means["experts_touched"] if means else float(routed * a["experts"])
    rest = _trunk_outside_experts(a) + _module_outside_experts(a) + a["d"] \
        + (1 + a["mtp_layers"]) * a["d"] * a["vocab"]
    return (rest + sets * _expert_params(a)) * costs.dtype_bytes(config)


# ---- what the new readers divide by (module docstring) -------------------
def latent_bytes_per_key(config: Dict[str, Any]) -> int:
    """What an absorbed read NEEDS of one key in ONE layer: the latent and
    the one rotated key (576 values = 1,152 B)."""
    a = arch(config)
    return (a["rank"] + a["rope"]) * costs.dtype_bytes(config)


def latent_flops_per_key(config: Dict[str, Any]) -> int:
    """FLOPs of one (query position, key) pair in one layer, absorbed:
    every head's score over ``rank + rope`` values and its output over
    ``rank``."""
    a = arch(config)
    return 2 * a["heads"] * (2 * a["rank"] + a["rope"])


def index_bytes_per_key(config: Dict[str, Any]) -> int:
    """The indexer's key of one token in one layer (128 values)."""
    return arch(config)["index_head_dim"] * costs.dtype_bytes(config)


def index_flops_per_key(config: Dict[str, Any]) -> int:
    """FLOPs of scoring one (query position, key) pair in one layer: every
    index head's dot product, its ReLU and its weighted sum."""
    a = arch(config)
    return a["index_heads"] * (2 * a["index_head_dim"] + 2)


def window_read_needs(config: Dict[str, Any], index_keys: float,
                      kv_selected: float):
    """``(bytes, FLOPs)`` ONE layer's scoring + selected read of a verify
    window needs, from the spans' counters of one layer (``index_keys``:
    (position, key) pairs scored; ``kv_selected``: keys attended, summed
    over the window's positions): every scored pair's index key and dot
    products — a key both positions score is needed once a position here,
    which can only lower a share — and every chosen key's latent and
    absorbed products."""
    return (index_keys * index_bytes_per_key(config)
            + kv_selected * latent_bytes_per_key(config),
            index_keys * index_flops_per_key(config)
            + kv_selected * latent_flops_per_key(config))


def module_flops_per_token(config: Dict[str, Any]) -> float:
    """FLOPs the module's matmuls make of one committed position: twice
    what it multiplies with (its block's held share of the top-k, ``W_eh``,
    the head)."""
    a = arch(config)
    held = a["top_k"] * a["experts"] / a["experts_published"]
    return 2.0 * (_module_outside_experts(a) + held * _expert_params(a)
                  + a["d"] * a["vocab"])


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a cached token NEEDS: a latent and an index key in every layer
    of the trunk and in the module's."""
    a = arch(config)
    return (a["layers"] + a["mtp_layers"]) * (
        latent_bytes_per_key(config) + index_bytes_per_key(config))


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None, variant=None,
           seconds: Sequence[int] = (), rows_at: Sequence[int] = (),
           after=None):
    """``reference_glm5.logits``: ``{"trunk", "module", "rows"}`` (with
    ``forced`` — the program's own expert and key sets — beside the
    agreement of the reference's own sets with them)."""
    return reference_glm5.logits(config, params, tokens, at=at,
                                 forced=forced, variant=variant,
                                 seconds=seconds, rows_at=rows_at,
                                 after=after)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_glm5.next_token_loss(config, params, tokens)
