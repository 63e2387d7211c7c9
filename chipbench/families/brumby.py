"""``family: brumby`` — a ``chipbench/configs`` file (the published ``brumby``
configuration of manifestai/Brumby-14B-Base) to the program's
``models/brumby.py`` configuration: the Qwen3-14B block (sequential RMSNorm,
grouped-query projections without bias, a per-head q/k-norm, rotate-half
rotary, SwiGLU, an untied head) with POWER RETENTION of degree 2 in the
attention's place — its sizes and parameter counts, its plain reference
(``chipbench/reference_brumby.py``), and the byte and FLOP functions its
readers divide by.

What is BUILT is the configuration's ``depth`` (``num_hidden_layers`` stays
the published 40): one chip holds one stage of a four-chip pipeline — ten
layers — and, to close the decode loop, the embedding, the final norm and
the head (the configuration file's ``deployment``).  ``overrides`` are the
cell's ``model`` settings, applied as attributes.

It is the THIRD family with a per-slot cache kind and the first that caches
no token at all; it provides what ``families/kimi_linear.py`` says such a
family owes (a later ``benchmark`` issue folds that into
``chipbench/families/__init__.py``): ``state_bytes_per_slot(config)``, and
``cached_bytes_per_token(config)`` = 0."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import reference_brumby


def _checked(config: Dict[str, Any]) -> None:
    """Raises on a published key this family does not build."""
    if config["attention_bias"] or config["hidden_act"] != "silu" \
            or config["rope_scaling"] is not None \
            or config["use_sliding_window"] or config["sliding_window"] \
            or config["tie_word_embeddings"] \
            or config["model_type"] != "brumby" \
            or config["depth"] > config["num_hidden_layers"]:
        raise ValueError("family brumby builds the published block: no "
                         "attention bias, SiLU, unscaled rotary, no window, "
                         "an untied head, depth <= num_hidden_layers")


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import brumby

    _checked(config)
    cfg = brumby.BrumbyConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["depth"],
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_width=config["head_dim"],
        ffn_size=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"])
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"BrumbyConfig has no field {key!r}")
        setattr(cfg, key, value)
    return brumby.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    """``layers`` is what is built (``depth``); ``kv_heads`` / ``head_dim``
    describe the retention's KV heads (which cache no token)."""
    hd = config["head_dim"]
    return {"layers": config["depth"], "d": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"], "head_dim": hd,
            "state_rows_a_head": hd * (hd + 1) // 2,
            "ffn": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def num_params(config: Dict[str, Any]) -> int:
    """Every parameter of what is built: the token table, the head, the
    final norm, and a layer: two norms, q / k / v / o, the two ``[hd]``
    q/k-norm scales, the gate's projection and bias, the SwiGLU."""
    a = arch(config)
    d, hq, hkv = a["d"], a["heads"] * a["head_dim"], \
        a["kv_heads"] * a["head_dim"]
    layer = 2 * d + 2 * d * hq + 2 * d * hkv + 2 * a["head_dim"] \
        + (d + 1) * a["kv_heads"] + 3 * d * a["ffn"]
    return 2 * a["vocab"] * d + d + a["layers"] * layer


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """No layer caches a token."""
    del config
    return 0


def state_bytes_per_slot(config: Dict[str, Any]) -> int:
    """The recurrent state one slot NEEDS, all layers: a float32 ``[D, hd]``
    matrix and a ``[D]`` normaliser a KV head, ``D = hd (hd + 1) / 2`` — 10 x
    8 x (8,256 x 128 + 8,256) x 4 B = 340.8 MB here.  (The program stores
    8,320 rows a head, 0.8 % more: ``ops/power_retention.py`` "The stored
    state"; the configuration's ``deployment`` has the bytes as built.)"""
    a = arch(config)
    return a["layers"] * a["kv_heads"] * 4 * a["state_rows_a_head"] \
        * (a["head_dim"] + 1)


def power_step_bytes(config: Dict[str, Any], state_rows: float) -> float:
    """Bytes the ``power_step`` kernels of ONE decode step must move for
    ``state_rows`` live rows, all layers: each (row, KV head) state and
    normaliser in and out at ``D`` = 8,256 rows a head, float32.  (The
    token's q, k, v and y are a 17,000th of that and left out; rows the
    stored layout pads and lanes run for idle rows are NOT counted: they
    lower a share read against this and cannot raise it.)"""
    return 2.0 * state_rows * state_bytes_per_slot(config)


#: tokens of one chunk of the program's chunked form
#: (``ops/power_retention.py``)
CHUNK = 128


def power_chunk_cost(config: Dict[str, Any], tokens: float):
    """``(FLOPs, bytes)`` of the ``power_chunk_state`` kernels over
    ``tokens`` valid prompt tokens, all layers.  A token multiplies, a KV
    head, ``phi(q) S0`` for each of its ``G`` query heads and ``phi(k)
    v^T`` (``2 D hd`` each: ``2 (G + 1) D hd``) and, inside its chunk of
    ``C`` = 128, ``Q K^T`` and ``A V`` for each query head (``2 C hd`` each,
    the triangle counted whole as the kernel computes it); a chunk moves q
    and y (``C G hd`` each a KV head), k and v (``C hd`` each), float32; a
    CALL moves each row's state and normaliser in and out once (they stay
    in VMEM over a call's chunks) — counted a chunk of 512 / 128 = 4 here
    as a quarter of that.  What the kernel multiplies beyond it — the 64
    duplicate rows, ``phi`` itself, the float32 products' six bfloat16
    passes — is not counted."""
    a = arch(config)
    hd, kv, g = a["head_dim"], a["kv_heads"], a["heads"] // a["kv_heads"]
    d, c = a["state_rows_a_head"], CHUNK
    flops = a["layers"] * tokens * kv * (
        2 * (g + 1) * d * hd + g * 4 * c * hd)
    nbytes = a["layers"] * (tokens / c) * kv * 4.0 * (
        2 * c * g * hd + 2 * c * hd + 2 * d * (hd + 1) / 4)
    return flops, nbytes


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None, variant=None, lengths=None):
    """``lengths``: the sequences' real tokens where they differ (padding
    after them)."""
    return reference_brumby.logits(config, params, tokens, at=at,
                                   variant=variant, lengths=lengths)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_brumby.next_token_loss(config, params, tokens)
