"""``family: keye`` — a ``chipbench/configs`` file (the published
``KeyeVL2`` text configuration) to the program's ``models/mixtral.py``
configuration with a head width of its own, per-head q/k-norm, the
renormalised top-k router and the indexer of ``sa_config`` (learned sparse
attention), its sizes and parameter counts, its plain reference
(``chipbench/reference_keye.py``), and the byte functions the sparse
attention readers divide by.  The layers BUILT are the configuration's
``depth`` (``num_hidden_layers`` stays the published 48): one chip holds one
stage of an eight-chip layer split.  The vision tower is not built.
``overrides`` are the cell's ``model`` settings, applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import costs, reference_keye
from chipbench.layer_metrics import _program_spans as ps


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import mixtral

    sa = config["sa_config"]
    cfg = mixtral.MixtralConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["depth"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_width=config["head_dim"],
        hidden_size=config["hidden_size"],
        ffn_size=config["moe_intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        qk_norm="head",
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"])
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"MixtralConfig has no field {key!r}")
        setattr(cfg, key, value)
    return mixtral.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    sa = config["sa_config"]
    return {"layers": config["depth"], "d": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "ffn": config["moe_intermediate_size"],
            "experts": config["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "index_heads": sa["indexer_num_heads"],
            "index_head_dim": sa["indexer_head_dim"],
            "index_topk": sa["topk"],
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def _expert_params(a: Dict[str, int]) -> int:
    """One expert: the three SwiGLU matrices."""
    return 3 * a["d"] * a["ffn"]


def _layer_rest(a: Dict[str, int]) -> int:
    """One layer without its experts: q, k, v, o (``heads x head_dim`` is
    not ``d``), the two block norms, the per-head q/k-norm scales, the
    router, and the indexer (its queries, its key, its head weights, the
    key's LayerNorm scale and bias)."""
    d, hd = a["d"], a["head_dim"]
    hq, hkv = a["heads"] * hd, a["kv_heads"] * hd
    hi, di = a["index_heads"], a["index_head_dim"]
    return 2 * d * hq + 2 * d * hkv + 2 * d + 2 * hd + d * a["experts"] \
        + d * (hi * di + di + hi) + 2 * di


def num_params(config: Dict[str, Any]) -> int:
    """No biases, no position table, an untied head: token table + per
    layer (attention, norms, router, indexer, every expert) + final norm +
    head."""
    a = arch(config)
    per_layer = _layer_rest(a) + a["experts"] * _expert_params(a)
    return a["vocab"] * a["d"] + a["layers"] * per_layer \
        + a["d"] + a["d"] * a["vocab"]


def active_params(config: Dict[str, Any]) -> int:
    """What one token multiplies with: everything but the experts it was
    not routed to (``costs.py``'s convention keeps the embeddings in)."""
    a = arch(config)
    return num_params(config) - a["layers"] \
        * (a["experts"] - a["top_k"]) * _expert_params(a)


def _touched_sets_per_step(config: Dict[str, Any],
                           counters: Dict[str, Any]) -> float:
    """(layer, expert) weight sets one decode step read, of ``layers x
    experts``: from the counters' ``experts_touched_share`` if given, else
    the mean ``experts_touched`` of the ``decode`` spans in the program's
    ring (``layer_metrics/_program_spans.py``); without a ring, every
    expert (16 live rows x top-8 of 128 touch ~64 % in expectation, so a
    run with a ring reads fewer)."""
    a = arch(config)
    every = a["layers"] * a["experts"]
    if "experts_touched_share" in counters:
        return every * float(counters["experts_touched_share"])
    ring = ps.serve_ring()
    seen = [e["args"]["experts_touched"] for e in (ring[0] if ring else ())
            if e["ph"] == "X" and e["name"] == "decode"
            and "experts_touched" in e.get("args", {})
            and not e["args"].get("fused")]
    return sum(seen) / len(seen) if seen else float(every)


def expert_bytes_touched(config: Dict[str, Any],
                         counters: Dict[str, Any]) -> float:
    """Expert weight bytes one decode step must read: each touched
    (layer, expert) set once."""
    return _touched_sets_per_step(config, counters) \
        * _expert_params(arch(config)) * costs.dtype_bytes(config)


def decode_weight_bytes(config: Dict[str, Any],
                        counters: Dict[str, Any]) -> float:
    """Weight bytes one decode step must read: everything outside the
    experts except the token table (a step gathers ``slots`` rows of it) +
    the experts its live rows were routed to."""
    a = arch(config)
    rest = a["layers"] * _layer_rest(a) + a["d"] + a["d"] * a["vocab"]
    return rest * costs.dtype_bytes(config) \
        + expert_bytes_touched(config, counters)


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """What the pool holds a token: K and V of every KV head plus the
    indexer's one key, in every layer."""
    a = arch(config)
    return costs.kv_bytes_per_token(config) \
        + a["layers"] * a["index_head_dim"] * costs.dtype_bytes(config)


def index_bytes(config: Dict[str, Any], ctx_tokens: float) -> float:
    """Bytes of indexer keys a selection over ``ctx_tokens`` valid keys
    (summed over rows, one layer's worth, as the program's spans count
    them) must read: one ``index_head_dim`` key a valid key and layer."""
    a = arch(config)
    return ctx_tokens * a["layers"] * a["index_head_dim"] \
        * costs.dtype_bytes(config)


def selected_kv_bytes(config: Dict[str, Any],
                      selected_tokens: float) -> float:
    """K and V bytes of ``selected_tokens`` chosen keys (summed over rows,
    one layer's worth): every KV head, both sides, every layer."""
    return selected_tokens * costs.kv_bytes_per_token(config)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None):
    """``forced``: the program's own expert and key sets for the reference
    to take (``reference_keye.hidden_states``); the result is then
    ``(logits, agreement of the reference's own sets with them)``."""
    return reference_keye.logits(config, params, tokens, at=at,
                                 forced=forced)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_keye.next_token_loss(config, params, tokens)
