"""``family: dots3`` — a ``chipbench/configs`` file (the published
``dots3_note`` configuration of dots3-note-prev) to the program's
``models/dots3.py`` configuration: sequential RMSNorm blocks over the
published ``layer_types`` — full layers of latent attention under a learned
selection, sliding layers of latent attention at sizes of their own under a
window, a head gate and the two latents' rescale in both — a leading dense
FFN, sigmoid-scored experts top-k with a selection bias beside one shared
expert, an untied head — its sizes and parameter counts, its plain reference
(``chipbench/reference_dots3.py``), and the byte and FLOP functions its
readers divide by.

What is BUILT is one chip's share of a deployment in which eight chips share
each layer (the configuration file's ``deployment``): the first ``depth``
layers of the published ``layer_types``, the ``n_routed_experts`` routed
experts from ``experts_first`` on of the published
``n_routed_experts_published`` (the router keeps its published width and its
experts per token, and the expert layer returns the held experts' partial
sum), ``vocab_size`` rows of the published ``vocab_size_published``.  The
towers and the multi-token-prediction module are not built.  ``overrides``
are the cell's ``model`` settings, applied as attributes.

**What the readers divide by** is what the ALGORITHM needs, whatever
implements it: a selected read needs the chosen keys' latents (``kv_selected``
x 1,152 B) and the absorbed products over them, scoring needs every valid
index key once (256 B) and its dot products, a windowed read the visible
keys' latents (2,176 B).  The program lands whole blocks, so its time covers
more bytes than these: that lowers a share and cannot raise it."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import costs, reference_dots3
from chipbench.families.mistral4 import _decode_means

FULL, SLIDING = "latent_indexed", "latent_sliding"


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import dots3

    if config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or config["rope_scaling"] is not None \
            or config["tie_word_embeddings"] or config["moe_layer_freq"] != 1 \
            or config["attention_bias"] or config["hidden_act"] != "silu" \
            or config["attention_gate_type"] != "headwise" \
            or config["swa_attention_gate_type"] != "headwise":
        raise ValueError("family dots3 builds the published block: a sigmoid "
                         "router with a correction bias and no groups, plain "
                         "rotary, every layer past the dense ones routed, no "
                         "biases, SiLU, a headwise gate in both layer kinds, "
                         "an untied head")
    full, swa = reference_dots3.sizes(config, FULL), \
        reference_dots3.sizes(config, SLIDING)
    cfg = dots3.Dots3Config(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"][:config["depth"]]),
        num_heads=full["heads"], num_kv_heads=config["num_key_value_heads"],
        head_width=full["nope"] + full["rope"], q_lora_rank=full["q_rank"],
        kv_lora_rank=full["rank"], qk_nope_dim=full["nope"],
        qk_rope_dim=full["rope"], v_head_dim=full["v"],
        rope_theta=full["theta"], rms_eps=config["rms_norm_eps"],
        rope_interleaved=True,
        index_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        sliding_window=config["sliding_window_size"],
        swa_num_heads=swa["heads"], swa_q_lora_rank=swa["q_rank"],
        swa_kv_lora_rank=swa["rank"], swa_qk_nope_dim=swa["nope"],
        swa_qk_rope_dim=swa["rope"], swa_v_head_dim=swa["v"],
        swa_rope_theta=swa["theta"],
        first_dense=config["first_k_dense_replace"],
        dense_ffn_size=config["intermediate_size"],
        head_gate=True,
        lora_rescale=bool(config["apply_mla_qkv_lora_rescale"]),
        ffn_size=config["moe_intermediate_size"],
        num_experts=config["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        router_score="sigmoid", router_bias=True,
        routed_scale=float(config["routed_scaling_factor"]),
        shared_experts=config["n_shared_experts"],
        experts_held=(config["experts_first"], config["n_routed_experts"]),
        capacity_factor=None)
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"Dots3Config has no field {key!r}")
        setattr(cfg, key, value)
    return dots3.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, Any]:
    """``heads`` / ``kv_heads`` / ``head_dim`` describe the full layers'
    EXPANDED form; both kinds' own sizes are under ``full`` / ``sliding``."""
    kinds = reference_dots3.layer_kinds(config)
    full = reference_dots3.sizes(config, FULL)
    return {"layers": config["depth"], "d": config["hidden_size"],
            "heads": full["heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": full["nope"] + full["rope"],
            "full": full, "sliding": reference_dots3.sizes(config, SLIDING),
            "full_layers": kinds.count(FULL),
            "sliding_layers": kinds.count(SLIDING),
            "window": config["sliding_window_size"],
            "index_heads": config["index_n_heads"],
            "index_head_dim": config["index_head_dim"],
            "index_topk": config["index_topk"],
            "dense_layers": min(config["first_k_dense_replace"],
                                config["depth"]),
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


def _attn_params(a: Dict[str, Any], kind: str) -> int:
    """A layer's attention: the low-rank query pair and its norm, the joint
    down-projection and its norm, the up-projection, the output projection,
    the head gate — and a full layer's indexer (queries from the query
    latent, one key, the head weights, the key's LayerNorm)."""
    z, d = a[kind], a["d"]
    n = d * z["q_rank"] + z["q_rank"] \
        + z["q_rank"] * z["heads"] * (z["nope"] + z["rope"]) \
        + d * (z["rank"] + z["rope"]) + z["rank"] \
        + z["rank"] * z["heads"] * (z["nope"] + z["v"]) \
        + z["heads"] * z["v"] * d + d * z["heads"]
    if kind == "full":
        hi, di = a["index_heads"], a["index_head_dim"]
        n += z["q_rank"] * hi * di + d * di + d * hi + 2 * di
    return n


def _routed_rest(a: Dict[str, Any]) -> int:
    """A routed layer's FFN without its routed experts: the router over all
    published experts with its bias, the shared expert."""
    return a["d"] * a["experts_published"] + a["experts_published"] \
        + a["shared_experts"] * _expert_params(a)


def _outside_experts(a: Dict[str, Any]) -> int:
    """Every layer's parameters outside the routed experts."""
    routed = a["layers"] - a["dense_layers"]
    return a["full_layers"] * _attn_params(a, "full") \
        + a["sliding_layers"] * _attn_params(a, "sliding") \
        + 2 * a["d"] * a["layers"] \
        + a["dense_layers"] * 3 * a["d"] * a["dense_ffn"] \
        + routed * _routed_rest(a)


def num_params(config: Dict[str, Any]) -> int:
    """What this chip holds: the vocabulary slice of the token table and of
    the untied head, every layer's attention, norms and FFN (the HELD routed
    experts), the final norm."""
    a = arch(config)
    routed = a["layers"] - a["dense_layers"]
    return 2 * a["vocab"] * a["d"] + a["d"] + _outside_experts(a) \
        + routed * a["experts"] * _expert_params(a)


def active_params(config: Dict[str, Any]) -> int:
    a = arch(config)
    held = a["top_k"] * a["experts"] / a["experts_published"]
    return int(num_params(config) - (a["layers"] - a["dense_layers"])
               * (a["experts"] - held) * _expert_params(a))


def expert_bytes_touched(config: Dict[str, Any],
                         counters: Dict[str, Any]) -> float:
    """Routed-expert weight bytes one decode step must read: each touched
    (layer, HELD expert) set once — the mean ``experts_touched`` of the
    ring's ``decode`` spans; without a ring, every held expert."""
    a = arch(config)
    routed = a["layers"] - a["dense_layers"]
    if "experts_touched_share" in counters:
        sets = routed * a["experts"] * float(counters["experts_touched_share"])
    else:
        means = _decode_means(("experts_touched",))
        sets = means["experts_touched"] if means \
            else float(routed * a["experts"])
    return sets * _expert_params(a) * costs.dtype_bytes(config)


def decode_weight_bytes(config: Dict[str, Any],
                        counters: Dict[str, Any]) -> float:
    """Weight bytes one decode step must read: everything outside the routed
    experts and the token table + the held experts its live rows were routed
    to."""
    a = arch(config)
    rest = _outside_experts(a) + a["d"] + a["d"] * a["vocab"]
    return rest * costs.dtype_bytes(config) \
        + expert_bytes_touched(config, counters)


# ---- what the new readers divide by (module docstring) -------------------
def latent_bytes_per_key(config: Dict[str, Any], kind: str = "full") -> int:
    """What an absorbed read NEEDS of one key in ONE layer of ``kind``: the
    latent and the one rotated key (576 values = 1,152 B in a full layer,
    1,088 = 2,176 B in a sliding one)."""
    z = arch(config)[kind]
    return (z["rank"] + z["rope"]) * costs.dtype_bytes(config)


def latent_flops_per_key(config: Dict[str, Any], kind: str = "full") -> int:
    """FLOPs of one (query position, key) pair in one layer of ``kind``,
    absorbed: every head's score over ``rank + rope`` values and its output
    over ``rank``."""
    z = arch(config)[kind]
    return 2 * z["heads"] * (2 * z["rank"] + z["rope"])


def index_bytes_per_key(config: Dict[str, Any]) -> int:
    """The indexer's key of one token in one full layer (128 values)."""
    return arch(config)["index_head_dim"] * costs.dtype_bytes(config)


def index_flops_per_key(config: Dict[str, Any]) -> int:
    """FLOPs of scoring one (query position, key) pair in one full layer:
    every index head's dot product, its ReLU and its weighted sum."""
    a = arch(config)
    return a["index_heads"] * (2 * a["index_head_dim"] + 2)


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a cached token NEEDS in the full layers (a latent and an index
    key each): it stays for the row's whole life.  The sliding layers keep
    a token for ``window`` positions only (:func:`window_bytes_per_slot`)."""
    a = arch(config)
    return a["full_layers"] * (latent_bytes_per_key(config)
                               + index_bytes_per_key(config))


def window_bytes_per_slot(config: Dict[str, Any]) -> int:
    """What the sliding layers NEED of one row, whatever its length: the
    ``window`` newest keys' latents, every sliding layer."""
    a = arch(config)
    return a["sliding_layers"] * a["window"] \
        * latent_bytes_per_key(config, "sliding")


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None, variant=None,
           window_shift: int = 0):
    """``forced``: the program's own expert sets and key sets for the
    reference to take (``reference_dots3.hidden_states``); the result is
    then ``(logits, agreement of the reference's own sets with them)``.
    ``window_shift``: the reference's twin whose window is that many keys
    longer."""
    return reference_dots3.logits(config, params, tokens, at=at,
                                  forced=forced, variant=variant,
                                  window_shift=window_shift)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_dots3.next_token_loss(config, params, tokens)
