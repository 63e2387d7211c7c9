"""``family: kimi_linear`` — a ``chipbench/configs`` file (the published
``kimi_linear`` configuration of Kimi-Linear-48B-A3B-Instruct) to the
program's ``models/kimi_linear.py`` configuration: sequential RMSNorm blocks
over the pattern of gated delta-rule (KDA) layers and NoPE latent-attention
layers, a leading dense FFN, sigmoid-scored experts top-k with a selection
bias beside one shared expert, an untied head — its sizes and parameter
counts, its plain reference (``chipbench/reference_kimi_linear.py``), and
the byte and FLOP functions its readers divide by.

What is BUILT is one chip's share of a deployment in which eight chips
share each layer (the configuration file's ``deployment``): ``depth`` layers
of the published ``num_hidden_layers`` (their kinds read off the published
1-based ``kda_layers`` / ``full_attn_layers``), the ``num_experts`` routed
experts from ``experts_first`` on of the published
``num_experts_published`` (the router keeps its published width and its
experts per token, and the expert layer returns the held experts' partial
sum), ``vocab_size`` rows of the published ``vocab_size_published``.
``overrides`` are the cell's ``model`` settings, applied as attributes.

**What a per-SLOT cache kind adds to a family's contract** (for a later
``benchmark`` issue to fold into ``chipbench/families/__init__.py``): a
family whose model keeps state that does not grow with the sequence also
provides

    state_bytes_per_slot(config) -> int
        bytes of recurrent state one serving slot holds, all layers,
        whatever the row's length (here: the float32 matrices and the
        convolution tails)
    cached_bytes_per_token(config) -> int
        bytes one cached token NEEDS, all layers that cache tokens (here:
        the latent layers alone)

so that a driver can size a cell (``slots x state_bytes_per_slot +
blocks x block bytes``) and a reader can say what share of the cache is
state (``kv_state_share``); ``costs.kv_bytes_per_token`` (K and V a KV head
a layer) describes neither and is not used for such a family."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import costs, reference_kimi_linear
from chipbench.families.mistral4 import _decode_means


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import kimi_linear

    lin = config["linear_attn_config"]
    kinds = reference_kimi_linear.layer_kinds(config)
    period = next(p for p in range(1, len(kinds) + 1)
                  if len(kinds) % p == 0
                  and all(k == kinds[i % p] for i, k in enumerate(kinds)))
    if config["q_lora_rank"] is not None or not config["mla_use_nope"] \
            or config["rope_scaling"] is not None \
            or config["moe_router_activation_func"] != "sigmoid" \
            or config["num_expert_group"] != 1 or config["topk_group"] != 1 \
            or config["moe_layer_freq"] != 1 \
            or config["tie_word_embeddings"] \
            or config["num_nextn_predict_layers"] \
            or config["hidden_act"] != "silu":
        raise ValueError("family kimi_linear builds the published block: a "
                         "full-rank query and no rotation in the latent "
                         "layers, a sigmoid router without groups, every "
                         "layer past the dense ones routed, SiLU, an untied "
                         "head, no multi-token prediction")
    cfg = kimi_linear.KimiLinearConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["model_max_length"],
        num_layers=config["depth"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_width=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        hidden_size=config["hidden_size"],
        ffn_size=config["moe_intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        latent_rope=False,
        layer_kinds=tuple(kinds[:period]),
        num_experts=config["num_experts_published"],
        top_k=config["num_experts_per_token"],
        norm_topk_prob=config["moe_renormalize"],
        router_score="sigmoid", router_bias=True,
        routed_scale=config["routed_scaling_factor"],
        shared_experts=config["num_shared_experts"],
        experts_held=(config["experts_first"], config["num_experts"]),
        capacity_factor=None,
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        first_dense=config["first_k_dense_replace"],
        dense_ffn_size=config["intermediate_size"])
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"KimiLinearConfig has no field {key!r}")
        setattr(cfg, key, value)
    return kimi_linear.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    """``kv_heads`` / ``head_dim`` describe the latent layers' EXPANDED
    form; the latent's own sizes and the KDA layers' are beside them."""
    lin = config["linear_attn_config"]
    kinds = reference_kimi_linear.layer_kinds(config)
    return {"layers": config["depth"], "d": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["qk_nope_head_dim"]
            + config["qk_rope_head_dim"],
            "v_head_dim": config["v_head_dim"],
            "kv_lora_rank": config["kv_lora_rank"],
            "qk_nope": config["qk_nope_head_dim"],
            "qk_rope": config["qk_rope_head_dim"],
            "kda_layers": kinds.count("kda"),
            "latent_layers": kinds.count("latent"),
            "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
            "kda_conv": lin["short_conv_kernel_size"],
            "dense_layers": min(config["first_k_dense_replace"],
                                config["depth"]),
            "dense_ffn": config["intermediate_size"],
            "ffn": config["moe_intermediate_size"],
            "experts": config["num_experts"],
            "experts_published": config["num_experts_published"],
            "shared_experts": config["num_shared_experts"],
            "top_k": config["num_experts_per_token"],
            # no rotation and no scaling: every position is as the first
            "original_positions": config["model_max_length"],
            "vocab": config["vocab_size"],
            "positions": config["model_max_length"]}


def _expert_params(a: Dict[str, int]) -> int:
    return 3 * a["d"] * a["ffn"]


def _kda_params(a: Dict[str, int]) -> int:
    """q / k / v / o, the two rank-``head_dim`` gates, ``b_w``, the three
    convolutions, ``a_log``, ``dt_bias``, the head norm."""
    d, h, hd = a["d"], a["kda_heads"], a["kda_head_dim"]
    c = h * hd
    return 4 * d * c + 2 * (d * hd + hd * c) + d * h + 3 * a["kda_conv"] * c \
        + h + c + hd


def _latent_params(a: Dict[str, int]) -> int:
    d, h = a["d"], a["heads"]
    return d * h * a["head_dim"] + d * (a["kv_lora_rank"] + a["qk_rope"]) \
        + a["kv_lora_rank"] \
        + a["kv_lora_rank"] * h * (a["qk_nope"] + a["v_head_dim"]) \
        + h * a["v_head_dim"] * d


def _routed_rest(a: Dict[str, int]) -> int:
    """A routed layer's FFN without its routed experts: the router over all
    published experts with its bias, the shared expert."""
    return a["d"] * a["experts_published"] + a["experts_published"] \
        + a["shared_experts"] * _expert_params(a)


def _outside_experts(a: Dict[str, int]) -> int:
    """Every layer's parameters outside the routed experts."""
    routed = a["layers"] - a["dense_layers"]
    return a["kda_layers"] * _kda_params(a) \
        + a["latent_layers"] * _latent_params(a) + 2 * a["d"] * a["layers"] \
        + a["dense_layers"] * 3 * a["d"] * a["dense_ffn"] \
        + routed * _routed_rest(a)


def num_params(config: Dict[str, Any]) -> int:
    """What this chip holds: the vocabulary slice of the token table and of
    the untied head, every layer's attention, norms and FFN (the HELD
    routed experts), the final norm."""
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
    """Weight bytes one decode step must read: everything outside the
    routed experts and the token table + the held experts its live rows
    were routed to."""
    a = arch(config)
    rest = _outside_experts(a) + a["d"] + a["d"] * a["vocab"]
    return rest * costs.dtype_bytes(config) \
        + expert_bytes_touched(config, counters)


def latent_bytes_per_key(config: Dict[str, Any]) -> int:
    a = arch(config)
    return (a["kv_lora_rank"] + a["qk_rope"]) * costs.dtype_bytes(config)


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a cached token NEEDS, the latent layers alone: 2 x 576 values =
    2,304 B here (the KDA layers cache no token)."""
    return arch(config)["latent_layers"] * latent_bytes_per_key(config)


def latent_flops_per_key(config: Dict[str, Any]) -> int:
    a = arch(config)
    return 2 * a["heads"] * (2 * a["kv_lora_rank"] + a["qk_rope"])


def state_bytes_per_slot(config: Dict[str, Any]) -> int:
    """The recurrent state one slot holds, all KDA layers: a float32 ``[H,
    dk, dv]`` matrix and ``K - 1`` tokens of the three convolutions' inputs
    (the serving dtype) a layer — 6 x (2 MiB + 72 KiB) = 12.4 MiB here."""
    a = arch(config)
    h, hd = a["kda_heads"], a["kda_head_dim"]
    return a["kda_layers"] * (
        4 * h * hd * hd
        + (a["kda_conv"] - 1) * 3 * h * hd * costs.dtype_bytes(config))


def kda_step_bytes(config: Dict[str, Any], rows: float) -> float:
    """Bytes the ``kda_step`` kernels of ONE decode step must move for
    ``rows`` live rows, all KDA layers: each state matrix in and out, the
    four key-side columns (decay, k, beta k, q) and ``beta v`` in, ``o``
    out, float32."""
    a = arch(config)
    h, hd = a["kda_heads"], a["kda_head_dim"]
    return a["kda_layers"] * 4.0 * rows * h * (2 * hd * hd + 4 * hd + 2 * hd)


#: tokens of one chunk of the program's chunked delta rule
CHUNK = 64


def kda_chunk_flops(config: Dict[str, Any], tokens: float) -> float:
    """FLOPs of the ``kda_chunk_state`` kernels over ``tokens`` valid prompt
    tokens, all KDA layers: a chunk of 64 tokens a head multiplies ``W S``,
    ``Q S`` and ``K^T U`` (``2 x 64 dk dv`` each), ``B U`` (``2 x 64 x 64
    dv``) and ``Diag(decay) S`` (``2 dk dk dv``).  The chunk's scores and
    its triangular solve run in XLA outside the kernel and are NOT counted
    here, as their time is not in ``kda_chunk_state_ms``."""
    a = arch(config)
    h, hd, c = a["kda_heads"], a["kda_head_dim"], CHUNK
    return a["kda_layers"] * tokens / c * h * (
        6 * c * hd * hd + 2 * c * c * hd + 2 * hd * hd * hd)


def kda_chunk_bytes(config: Dict[str, Any], tokens: float) -> float:
    """Bytes the same kernels move (float32): three ``[64, dk]`` and one
    ``[64, dv]`` operand in, ``B [64, 64]`` and the decay in, ``O [64, dv]``
    out, a chunk a head (the state itself stays in VMEM over a call's
    chunks)."""
    a = arch(config)
    h, hd, c = a["kda_heads"], a["kda_head_dim"], CHUNK
    return a["kda_layers"] * tokens / c * h * 4.0 * (
        5 * c * hd + c * c + hd)


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, forced=None, variant=None,
           lengths=None):
    """``forced``: the program's own expert sets for the reference to take
    (``reference_kimi_linear.hidden_states``); the result is then ``(logits,
    agreement of the reference's own sets with them)``.  ``lengths``: the
    sequences' real tokens where they differ (padding after them)."""
    return reference_kimi_linear.logits(config, params, tokens, at=at,
                                        forced=forced, variant=variant,
                                        lengths=lengths)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_kimi_linear.next_token_loss(config, params, tokens)
