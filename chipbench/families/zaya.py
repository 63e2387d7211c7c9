"""``family: zaya`` — a ``chipbench/configs`` file (the published ``zaya``
configuration of ZAYA1-8B) to the program's ``models/zaya.py``
configuration: compressed convolutional attention over the paged pool's
``full`` kind with per-slot convolution tails, a top-1 expert layer behind
an MLP router that reads the layer before, a tied head — its sizes and
parameter counts, its plain reference (``chipbench/reference_zaya.py``), and
the byte functions its readers divide by.

The layers BUILT are the configuration's ``depth`` (``num_hidden_layers``
stays the published 40): one chip holds one stage of a four-stage layer
split, every layer whole.  ``overrides`` are the cell's ``model`` settings,
applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import costs, reference_zaya
from chipbench.layer_metrics import _program_spans as ps


def _checked(config: Dict[str, Any]) -> None:
    """Raises on a published key this family does not build."""
    rope = config["rope_parameters"]["hybrid"]
    if set(config["layer_types"]) != {"hybrid"} \
            or len(config["layer_types"]) != config["num_hidden_layers"] \
            or config["sliding_window"] is not None \
            or config["attention_bias"] or config["lm_head_bias"] \
            or not config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu" \
            or config["num_experts_per_tok"] != 1 \
            or rope["rope_type"] != "default" \
            or rope["partial_rotary_factor"] \
            != config["partial_rotary_factor"]:
        raise ValueError("family zaya builds the published block: every "
                         "layer 'hybrid' (attention + experts), no window, "
                         "no bias, a tied head, SiLU experts, top-1, default "
                         "rotary over partial_rotary_factor of a head")


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import zaya

    _checked(config)
    cfg = zaya.ZayaConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["depth"],
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        ffn_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        router_size=config["router_hidden_size"],
        cca_time0=config["cca_time0"], cca_time1=config["cca_time1"],
        rope_theta=float(config["rope_parameters"]["hybrid"]["rope_theta"]),
        partial_rotary=float(config["partial_rotary_factor"]),
        rms_eps=config["rms_norm_eps"])
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"ZayaConfig has no field {key!r}")
        setattr(cfg, key, value)
    return zaya.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    return {"layers": config["depth"], "d": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "ffn": config["moe_intermediate_size"],
            "experts": config["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "router": config["router_hidden_size"],
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def _expert_params(a: Dict[str, int]) -> int:
    """One expert: the three SwiGLU matrices."""
    return 3 * a["d"] * a["ffn"]


def layer_params(config: Dict[str, Any]) -> int:
    """One layer, every expert: 207,579,651 at the published widths."""
    return reference_zaya.num_params(config, 1) \
        - reference_zaya.num_params(config, 0)


def num_params(config: Dict[str, Any], layers: Optional[int] = None) -> int:
    """Every parameter of the ``depth`` layers built (``layers``: of that
    many — 40 is the published model): the tied table, the final norm and a
    layer's attention, router, experts, norms and merges."""
    return reference_zaya.num_params(config, layers)


def active_params(config: Dict[str, Any]) -> int:
    """What one token multiplies with: everything but the 15 experts a
    layer it was not routed to."""
    a = arch(config)
    return num_params(config) - a["layers"] \
        * (a["experts"] - a["top_k"]) * _expert_params(a)


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a cached token needs: the finished K and V a KV head a layer —
    10 x 2 x 2 x 128 x 2 B = 10,240 B here."""
    return costs.kv_bytes_per_token(config)


def tail_bytes_per_slot(config: Dict[str, Any]) -> int:
    """The tails one slot holds, all layers: ``c[t-1]`` and ``c1[t-1]`` (2 x
    1,280 channels) and ``h[t-1] W_vb`` (128) a layer in the serving dtype —
    10 x 5,376 B here, whatever the row's length."""
    a = arch(config)
    channels = (a["heads"] + a["kv_heads"]) * a["head_dim"]
    return a["layers"] * (2 * channels + a["kv_heads"] * a["head_dim"] // 2) \
        * costs.dtype_bytes(config)


def _touched_sets_per_step(config: Dict[str, Any],
                           counters: Dict[str, Any]) -> float:
    """(layer, expert) weight sets one decode step read, of ``layers x
    experts``: the mean ``experts_touched`` of the ``decode`` spans in the
    program's ring; without a ring, every expert (128 rows x top-1 of 16
    touch 99.97 % in expectation)."""
    a = arch(config)
    if "experts_touched_share" in counters:
        return a["layers"] * a["experts"] \
            * float(counters["experts_touched_share"])
    ring = ps.serve_ring()
    seen = [e["args"]["experts_touched"] for e in (ring[0] if ring else ())
            if e["ph"] == "X" and e["name"] == "decode"
            and "experts_touched" in e.get("args", {})]
    return sum(seen) / len(seen) if seen \
        else float(a["layers"] * a["experts"])


def expert_bytes_touched(config: Dict[str, Any],
                         counters: Dict[str, Any]) -> float:
    """Expert weight bytes one decode step must read: each touched (layer,
    expert) set once."""
    return _touched_sets_per_step(config, counters) \
        * _expert_params(arch(config)) * costs.dtype_bytes(config)


def decode_weight_bytes(config: Dict[str, Any],
                        counters: Dict[str, Any]) -> float:
    """Weight bytes one decode step must read: a layer's attention, router,
    norms and merges, the final norm, the tied table ONCE (the head; the
    embedding gathers ``slots`` rows of it, under 0.1 %) and the experts its
    live rows were routed to.  (``costs.decode_bytes_per_step`` adds 10,240 B
    a valid key.)"""
    a = arch(config)
    rest = a["layers"] * (layer_params(config)
                          - a["experts"] * _expert_params(a)) \
        + a["d"] + a["d"] * a["vocab"]
    return rest * costs.dtype_bytes(config) \
        + expert_bytes_touched(config, counters)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, route=None, variant=None):
    """``route`` (int32 ``[L, B, S]``): the expert each token runs a layer,
    in place of the reference's own; the result is then ``(logits, the
    reference's own scores [L, B, S, E])``."""
    return reference_zaya.logits(config, params, tokens, at=at, route=route,
                                 variant=variant)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_zaya.next_token_loss(config, params, tokens)
