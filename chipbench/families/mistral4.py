"""``family: mistral4`` — a ``chipbench/configs`` file (the published
``mistral4`` configuration of Mistral Small 4) to the program's
``models/mixtral.py`` configuration: sequential RMSNorm blocks with LATENT
ATTENTION (low-rank queries, one joint key / value latent a token, rotary on
the ``qk_rope_head_dim`` half of a head under YaRN, a position-dependent
query temperature), softmax-scored experts top-k renormalised beside one
shared expert, an untied head — its sizes and parameter counts, its plain
reference (``chipbench/reference_mistral4.py``), and the byte and FLOP
functions its readers divide by.

What is BUILT is one chip's share of a deployment in which eight chips
share each layer (the configuration file's ``deployment``): ``depth`` layers
of the published ``num_hidden_layers``, the ``n_routed_experts`` routed
experts from ``experts_first`` on of the published
``n_routed_experts_published`` (the router keeps its published width and
its experts per token, and the expert layer returns the held experts'
partial sum), ``vocab_size`` rows of the published
``vocab_size_published``.  The vision encoder is not built.  ``overrides``
are the cell's ``model`` settings, applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import costs, reference_mistral4
from chipbench.layer_metrics import _program_spans as ps


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import mixtral

    rope = config["rope_parameters"]
    if rope["rope_type"] != "yarn" or not config["rope_interleave"] \
            or config["first_k_dense_replace"] != 0 \
            or config["n_shared_experts"] != 1 \
            or config["routed_scaling_factor"] != 1 \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["tie_word_embeddings"] \
            or config["sliding_window"] is not None \
            or config["qk_head_dim"] != config["qk_nope_head_dim"] \
            + config["qk_rope_head_dim"]:
        raise ValueError("family mistral4 builds the published block: YaRN "
                         "rotary, rope_interleave, no leading dense layer, "
                         "one shared expert, routed_scaling_factor 1, no "
                         "expert groups, an untied head, no sliding window")
    cfg = mixtral.MixtralConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["depth"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_width=config["qk_head_dim"],
        hidden_size=config["hidden_size"],
        ffn_size=config["moe_intermediate_size"],
        rope_theta=float(rope["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        rope_interleaved=True,
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_scaling={k: float(rope[k]) for k in (
            "factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim")}
        | {"original_max_position_embeddings":
           rope["original_max_position_embeddings"]},
        query_temperature=(rope["llama_4_scaling_beta"],
                           rope["original_max_position_embeddings"]),
        num_experts=config["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        router_score="softmax",
        shared_experts=config["n_shared_experts"],
        experts_held=(config["experts_first"], config["n_routed_experts"]))
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"MixtralConfig has no field {key!r}")
        setattr(cfg, key, value)
    return mixtral.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    """``kv_heads`` / ``head_dim`` describe the EXPANDED form (what
    ``costs.kv_bytes_per_token`` would count: 25.6 x what is cached); the
    latent's own sizes are beside them."""
    return {"layers": config["depth"], "d": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["qk_head_dim"],
            "v_head_dim": config["v_head_dim"],
            "q_lora_rank": config["q_lora_rank"],
            "kv_lora_rank": config["kv_lora_rank"],
            "qk_nope": config["qk_nope_head_dim"],
            "qk_rope": config["qk_rope_head_dim"],
            "ffn": config["moe_intermediate_size"],
            "experts": config["n_routed_experts"],
            "experts_published": config["n_routed_experts_published"],
            "shared_experts": config["n_shared_experts"],
            "top_k": config["num_experts_per_tok"],
            "original_positions": config["rope_parameters"][
                "original_max_position_embeddings"],
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def _expert_params(a: Dict[str, int]) -> int:
    """One expert, routed or shared: the three SwiGLU matrices."""
    return 3 * a["d"] * a["ffn"]


def _layer_rest(a: Dict[str, int]) -> int:
    """One layer without its routed experts: the latent attention's seven
    leaves (``q_a_w``, ``q_a_norm``, ``q_b_w``, ``kv_a_w``, ``kv_a_norm``,
    ``kv_b_w``, ``o_w``), the two block norms, the router over all published
    experts, the shared expert."""
    d, h = a["d"], a["heads"]
    latent = a["kv_lora_rank"] + a["qk_rope"]
    attn = d * a["q_lora_rank"] + a["q_lora_rank"] \
        + a["q_lora_rank"] * h * a["head_dim"] \
        + d * latent + a["kv_lora_rank"] \
        + a["kv_lora_rank"] * h * (a["qk_nope"] + a["v_head_dim"]) \
        + h * a["v_head_dim"] * d
    return attn + 2 * d + d * a["experts_published"] \
        + a["shared_experts"] * _expert_params(a)


def num_params(config: Dict[str, Any]) -> int:
    """What this chip holds: the vocabulary slice of the token table and of
    the untied head + per layer (attention, norms, router, shared expert,
    the HELD routed experts) + the final norm."""
    a = arch(config)
    return 2 * a["vocab"] * a["d"] + a["layers"] * (
        _layer_rest(a) + a["experts"] * _expert_params(a)) + a["d"]


def active_params(config: Dict[str, Any]) -> int:
    """What one token multiplies with HERE: everything but the held routed
    experts, plus its share of them — ``top_k`` chosen of the published
    experts, of which ``experts / experts_published`` are held."""
    a = arch(config)
    held = a["top_k"] * a["experts"] / a["experts_published"]
    return int(num_params(config) - a["layers"]
               * (a["experts"] - held) * _expert_params(a))


def _decode_means(names: Sequence[str]) -> Optional[Dict[str, float]]:
    """Means of the named counters over the ``decode`` spans of the
    program's ring that carry them; None without such spans."""
    ring = ps.serve_ring()
    seen = [e["args"] for e in (ring[0] if ring else ())
            if e["ph"] == "X" and e["name"] == "decode"
            and all(n in e.get("args", {}) for n in names)
            and not e["args"].get("fused")]
    if not seen:
        return None
    return {n: sum(a[n] for a in seen) / len(seen) for n in names}


def expert_bytes_touched(config: Dict[str, Any],
                         counters: Dict[str, Any]) -> float:
    """Routed-expert weight bytes one decode step must read: each touched
    (layer, HELD expert) set once — the mean ``experts_touched`` of the
    ring's ``decode`` spans (held experts only: no other has weights here);
    without a ring, every held expert."""
    a = arch(config)
    if "experts_touched_share" in counters:
        sets = a["layers"] * a["experts"] \
            * float(counters["experts_touched_share"])
    else:
        means = _decode_means(("experts_touched",))
        sets = means["experts_touched"] if means \
            else float(a["layers"] * a["experts"])
    return sets * _expert_params(a) * costs.dtype_bytes(config)


def decode_weight_bytes(config: Dict[str, Any],
                        counters: Dict[str, Any]) -> float:
    """Weight bytes one decode step must read: everything outside the
    routed experts and the token table (the untied head is read whole; a
    step reads only its rows' embeddings) + the held experts its live rows
    were routed to."""
    a = arch(config)
    rest = a["layers"] * _layer_rest(a) + a["d"] + a["d"] * a["vocab"]
    return rest * costs.dtype_bytes(config) \
        + expert_bytes_touched(config, counters)


def latent_bytes_per_key(config: Dict[str, Any]) -> int:
    """What an absorbed read NEEDS of one key in ONE layer: the latent and
    the one rotated key, ``kv_lora_rank + qk_rope_head_dim`` values (640 B
    in bf16; the pool pads them to whole lane rows, which the read then
    also moves: lost time, not needed bytes)."""
    a = arch(config)
    return (a["kv_lora_rank"] + a["qk_rope"]) * costs.dtype_bytes(config)


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a token's state is, all layers: 6 x 640 = 3,840 B here."""
    return config["depth"] * latent_bytes_per_key(config)


def latent_flops_per_key(config: Dict[str, Any]) -> int:
    """FLOPs the absorbed read spends on one key in ONE layer for ONE query
    position: every head's score over the latent and the rope key, and its
    value over the latent — ``2 H ((rank + rope) + rank)``."""
    a = arch(config)
    return 2 * a["heads"] * (2 * a["kv_lora_rank"] + a["qk_rope"])


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None, variant=None):
    """``forced``: the program's own expert sets for the reference to take
    (``reference_mistral4.hidden_states``); the result is then ``(logits,
    agreement of the reference's own sets with them)``."""
    return reference_mistral4.logits(config, params, tokens, at=at,
                                     forced=forced, variant=variant)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_mistral4.next_token_loss(config, params, tokens)
