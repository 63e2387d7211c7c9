"""``family: granite_hybrid`` — a ``chipbench/configs`` file (the published
``granitemoehybrid`` configuration of granite-4.0-h-micro) to the program's
``models/granite_hybrid.py`` configuration: sequential RMSNorm blocks with
scaled residuals over the published ``layer_types`` (Mamba-2 state-space
layers and grouped-query attention layers without positional encoding), one
dense SwiGLU a layer, a tied head — its sizes and parameter counts, its plain
reference (``chipbench/reference_granite_hybrid.py``), and the byte and FLOP
functions its readers divide by.

What is BUILT is the published model whole: nothing is cut.  ``overrides``
are the cell's ``model`` settings, applied as attributes.

It is the SECOND family with a per-slot cache kind, and provides what
``families/kimi_linear.py`` says such a family owes (a later ``benchmark``
issue folds that into ``chipbench/families/__init__.py``):
``state_bytes_per_slot(config)`` and ``cached_bytes_per_token(config)``."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import costs, reference_granite_hybrid


def _checked(config: Dict[str, Any]):
    """The period of ``layer_types``; raises on a published key this family
    does not build."""
    if config["position_embedding_type"] != "nope" \
            or config["mamba_n_groups"] != 1 or config["num_local_experts"] \
            or config["num_experts_per_tok"] \
            or not config["tie_word_embeddings"] or config["attention_bias"] \
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"] \
            or config["hidden_act"] != "silu" \
            or config["normalization_function"] != "rmsnorm" \
            or config["rope_scaling"] is not None \
            or config["shared_intermediate_size"] \
            != config["intermediate_size"] \
            or config["mamba_n_heads"] * config["mamba_d_head"] \
            != config["mamba_expand"] * config["hidden_size"] \
            or config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("family granite_hybrid builds the published block: "
                         "no positional encoding, one group of B and C, no "
                         "routed experts, a tied head, a convolution bias and "
                         "no other, SiLU, RMSNorm, an inner width of "
                         "mamba_expand x hidden_size")
    return reference_granite_hybrid.period(config)


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import granite_hybrid

    cfg = granite_hybrid.GraniteHybridConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["num_hidden_layers"],
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        ffn_size=config["shared_intermediate_size"],
        rms_eps=config["rms_norm_eps"],
        layer_kinds=_checked(config),
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_conv=config["mamba_d_conv"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]))
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"GraniteHybridConfig has no field {key!r}")
        setattr(cfg, key, value)
    return granite_hybrid.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    """``layers`` counts all of them; ``kv_heads`` / ``head_dim`` describe
    the ``attention_layers`` alone (the ``ssm_layers`` cache no token)."""
    kinds = [reference_granite_hybrid.KIND[t] for t in config["layer_types"]]
    return {"layers": config["num_hidden_layers"], "d": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"]
            // config["num_attention_heads"],
            "ssm_layers": kinds.count("ssm"),
            "attention_layers": kinds.count("full"),
            "ssm_heads": config["mamba_n_heads"],
            "ssm_head_dim": config["mamba_d_head"],
            "ssm_state": config["mamba_d_state"],
            "ssm_conv": config["mamba_d_conv"],
            "ffn": config["shared_intermediate_size"],
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def _conv_channels(a: Dict[str, int]) -> int:
    return a["ssm_heads"] * a["ssm_head_dim"] + 2 * a["ssm_state"]


def num_params(config: Dict[str, Any]) -> int:
    """Every parameter: the (tied) token table, the final norm, and a layer:
    two norms + the SwiGLU + either ``in_proj``, the convolution's taps and
    bias, ``dt_bias`` / ``A_log`` / ``D``, the gated norm and ``out_proj``, or
    q / k / v / o."""
    a = arch(config)
    d, inner, h = a["d"], a["ssm_heads"] * a["ssm_head_dim"], a["ssm_heads"]
    ffn = 2 * d + 3 * d * a["ffn"]
    ch = _conv_channels(a)
    ssm = d * (inner + ch + h) + (a["ssm_conv"] + 1) * ch + 3 * h + inner \
        + inner * d
    attn = 2 * d * a["heads"] * a["head_dim"] \
        + 2 * d * a["kv_heads"] * a["head_dim"]
    return a["vocab"] * d + d + a["ssm_layers"] * (ssm + ffn) \
        + a["attention_layers"] * (attn + ffn)


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a cached token NEEDS, the attention layers alone: K and V a KV
    head — 4 x 2 x 8 x 64 x 2 B = 8,192 B here (the mamba layers cache no
    token)."""
    a = arch(config)
    return a["attention_layers"] * 2 * a["kv_heads"] * a["head_dim"] \
        * costs.dtype_bytes(config)


def state_bytes_per_slot(config: Dict[str, Any]) -> int:
    """The recurrent state one slot holds, all mamba layers: a float32 ``[H,
    P, N]`` matrix and ``K - 1`` tokens of the convolution's input (the
    serving dtype) a layer — 36 x (2 MiB + 26 KB) = 76.4 MB here."""
    a = arch(config)
    return a["ssm_layers"] * (
        4 * a["ssm_heads"] * a["ssm_head_dim"] * a["ssm_state"]
        + (a["ssm_conv"] - 1) * _conv_channels(a) * costs.dtype_bytes(config))


def ssd_step_bytes(config: Dict[str, Any], state_rows: float) -> float:
    """Bytes the ``ssd_step`` kernels of ONE decode step must move for
    ``state_rows`` live rows, all mamba layers: each state matrix in and
    out, a head's decay and ``dt x`` over its lanes and ``B``, ``C`` in, ``y``
    out, float32."""
    a = arch(config)
    h, p, n = a["ssm_heads"], a["ssm_head_dim"], a["ssm_state"]
    return a["ssm_layers"] * 4.0 * state_rows * (
        2 * h * p * n + 3 * h * p + 2 * n)


#: tokens of one chunk of the program's chunked scan (``ops/ssd.py``)
CHUNK = 128


def ssd_chunk_cost(config: Dict[str, Any], tokens: float):
    """``(FLOPs, bytes)`` of the ``ssd_chunk_state`` kernels over ``tokens``
    valid prompt tokens, all mamba layers.  A chunk of ``C`` = 128 tokens
    multiplies, a head, ``(G * L)(dt X)`` (``2 C C P``), ``C S0`` and the
    state's update ``B^T (w X)`` (``2 C N P`` each), and ``G = C B^T`` once
    for all heads (``2 C C N``); it moves ``x`` in and ``y`` out (``C H P``
    each), ``B``, ``C`` (``C N`` each) and three ``[C, H]`` vectors, float32
    (the state stays in VMEM over a call's chunks).  What the kernel
    multiplies beyond that — both heads of a packed lane row where one is
    wanted, the scores above the diagonal — is not counted."""
    a = arch(config)
    h, p, n, c = a["ssm_heads"], a["ssm_head_dim"], a["ssm_state"], CHUNK
    chunks = a["ssm_layers"] * tokens / c
    flops = chunks * (h * (2 * c * c * p + 4 * c * n * p) + 2 * c * c * n)
    nbytes = chunks * 4.0 * (2 * c * h * p + 2 * c * n + 3 * c * h)
    return flops, nbytes


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, variant=None, lengths=None):
    """``lengths``: the sequences' real tokens where they differ (padding
    after them)."""
    return reference_granite_hybrid.logits(config, params, tokens, at=at,
                                           variant=variant, lengths=lengths)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_granite_hybrid.next_token_loss(config, params, tokens)
