"""``family: commanda`` — a ``chipbench/configs`` file (the published
``cohere2_moe`` configuration of Command A+) to the program's
``models/mixtral.py`` configuration: the parallel block under Cohere's
LayerNorm, the layer pattern of three sliding-window layers to one full
layer, sigmoid-scored experts beside averaged shared experts, a tied head —
its sizes and parameter counts, its plain reference
(``chipbench/reference_commanda.py``), and the byte functions its readers
divide by.

What is BUILT is one chip's share of a deployment in which eight chips
share each layer (the configuration file's ``deployment``): ``depth`` layers
of the published ``num_hidden_layers``, the ``num_experts`` routed experts
from ``experts_first`` on of the published ``num_experts_published`` (the
router keeps its published width and its experts per token, and the expert
layer returns the held experts' partial sum), ``vocab_size`` rows of the
published ``vocab_size_published``.  The vision tower is not built.
``overrides`` are the cell's ``model`` settings, applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import costs, reference_commanda
from chipbench.layer_metrics import _program_spans as ps


def _kinds(config: Dict[str, Any]) -> Sequence[str]:
    """The built layers' kinds, and their one period."""
    return reference_commanda.layer_kinds(config, config["depth"])


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import mixtral

    period = int(config["layer_switch"])
    kinds = _kinds(config)
    if kinds != list(kinds[:period]) * (len(kinds) // period):
        raise ValueError(f"layer_types is no repetition of its first "
                         f"{period} layers: {kinds}")
    if config["position_embedding_type"] != "rope_gptj" \
            or not config["use_parallel_block"] \
            or config["shared_expert_combination_strategy"] != "average" \
            or config["logit_scale"] != 1:
        raise ValueError("family commanda builds the published block: "
                         "rope_gptj, use_parallel_block, shared experts "
                         "averaged, logit_scale 1")
    cfg = mixtral.MixtralConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["depth"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_width=config["head_dim"],
        hidden_size=config["hidden_size"],
        ffn_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["layer_norm_eps"],
        norm="layernorm", parallel_block=True, rope_interleaved=True,
        layer_kinds=tuple(kinds[:period]),
        sliding_window=config["sliding_window"],
        tie_embeddings=config["tie_word_embeddings"],
        num_experts=config["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        router_score=config["expert_selection_fn"],
        shared_experts=config["num_shared_experts"],
        experts_held=(config["experts_first"], config["num_experts"]))
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
            "ffn": config["intermediate_size"],
            "experts": config["num_experts"],
            "experts_published": config["num_experts_published"],
            "shared_experts": config["num_shared_experts"],
            "top_k": config["num_experts_per_tok"],
            "window": config["sliding_window"],
            "sliding_layers": kinds.count("sliding"),
            "full_layers": kinds.count("full"),
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def _expert_params(a: Dict[str, int]) -> int:
    """One expert, routed or shared: the three SwiGLU matrices."""
    return 3 * a["d"] * a["ffn"]


def _layer_rest(a: Dict[str, int]) -> int:
    """One layer without its routed experts: q, k, v, o (``heads x
    head_dim`` is 4 ``d``), the one block norm, the router over all
    published experts, the shared experts."""
    d, hd = a["d"], a["head_dim"]
    hq, hkv = a["heads"] * hd, a["kv_heads"] * hd
    return 2 * d * hq + 2 * d * hkv + d + d * a["experts_published"] \
        + a["shared_experts"] * _expert_params(a)


def num_params(config: Dict[str, Any]) -> int:
    """What this chip holds: the vocabulary slice of the tied token table +
    per layer (attention, norm, router, shared experts, the HELD routed
    experts) + the final norm."""
    a = arch(config)
    return a["vocab"] * a["d"] + a["layers"] * (
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
    program's ring (``layer_metrics/_program_spans.py``) that carry them;
    None without such spans."""
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
    ring's ``decode`` spans (which counts held experts only: no other has
    weights here); without a ring, every held expert."""
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
    routed experts (the tied token table is the head: read whole) + the
    held experts its live rows were routed to."""
    a = arch(config)
    rest = a["layers"] * _layer_rest(a) + a["d"] + a["d"] * a["vocab"]
    return rest * costs.dtype_bytes(config) \
        + expert_bytes_touched(config, counters)


def kv_bytes_per_key(config: Dict[str, Any]) -> int:
    """K and V of one key in ONE layer: every KV head, both sides."""
    a = arch(config)
    return 2 * a["kv_heads"] * a["head_dim"] * costs.dtype_bytes(config)


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """What the pool holds for a token inside every layer's reach: K and V
    in every layer (a token behind a sliding layer's window is held by the
    full layers alone)."""
    return costs.kv_bytes_per_token(config)


def visible_kv_bytes(config: Dict[str, Any], kv_visible: float) -> float:
    """K and V bytes a step's attention must read: ``kv_visible`` keys as
    the program counts them — summed over the rows AND the layers, all of a
    row's keys in a full layer, at most ``sliding_window`` of them in a
    sliding one."""
    return kv_visible * kv_bytes_per_key(config)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None, variant=None):
    """``forced``: the program's own expert sets for the reference to take
    (``reference_commanda.hidden_states``); the result is then ``(logits,
    agreement of the reference's own sets with them)``."""
    return reference_commanda.logits(config, params, tokens, at=at,
                                     forced=forced, variant=variant)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_commanda.next_token_loss(config, params, tokens)
