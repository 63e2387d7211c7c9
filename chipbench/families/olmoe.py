"""``family: olmoe`` — a ``chipbench/configs`` file to the program's
``models/mixtral.py`` configuration with q/k-norm and the published router
(softmax over all experts, top-k, renormalised only if ``norm_topk_prob``),
its sizes and parameter counts, and its plain reference
(``chipbench/reference_olmoe.py``).  The layers BUILT are the
configuration's ``depth`` (``num_hidden_layers`` stays the published 16):
one chip holds one stage of a two-chip layer split.  ``overrides`` are the
cell's ``model`` settings, applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import costs, reference_olmoe
from chipbench.layer_metrics import _program_spans as ps


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import mixtral

    cfg = mixtral.MixtralConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["depth"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        hidden_size=config["hidden_size"],
        ffn_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        qk_norm=True,
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        router_aux_loss_coef=config.get("router_aux_loss_coef", 0.01))
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"MixtralConfig has no field {key!r}")
        setattr(cfg, key, value)
    return mixtral.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    return {"layers": config["depth"], "d": d, "heads": heads,
            "kv_heads": config["num_key_value_heads"],
            "head_dim": d // heads, "ffn": config["intermediate_size"],
            "experts": config["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def _expert_params(a: Dict[str, int]) -> int:
    """One expert: the three SwiGLU matrices."""
    return 3 * a["d"] * a["ffn"]


def _layer_rest(a: Dict[str, int]) -> int:
    """One layer without its experts: q, k, v, o, the two block norms, the
    q/k-norm scales (one per projected feature) and the router."""
    d, hd = a["d"], a["head_dim"]
    hq, hkv = a["heads"] * hd, a["kv_heads"] * hd
    return 2 * d * hq + 2 * d * hkv + 2 * d + hq + hkv + d * a["experts"]


def num_params(config: Dict[str, Any]) -> int:
    """No biases, no position table, an untied head: token table + per
    layer (attention, norms, router, every expert) + final norm + head."""
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
    """(layer, expert) weight sets one decode step read, of
    ``layers x experts``: from the counters' ``experts_touched_share`` if
    given, else the mean ``experts_touched`` of the ``decode`` spans in the
    program's ring (``layer_metrics/_program_spans.py``; all of the ring:
    the driver's counters carry no window); without a ring, every expert
    (64 live rows x top-8 of 64 touch 99.98 % in expectation)."""
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
    experts except the token table (a step gathers ``slots`` rows of it,
    under 0.1 %) + the experts its live rows were routed to."""
    a = arch(config)
    rest = a["layers"] * _layer_rest(a) + a["d"] + a["d"] * a["vocab"]
    return rest * costs.dtype_bytes(config) \
        + expert_bytes_touched(config, counters)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None):
    return reference_olmoe.logits(config, params, tokens, at=at)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_olmoe.next_token_loss(config, params, tokens)
