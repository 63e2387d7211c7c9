"""``family: toyllama`` — a test fixture, not a benchmark configuration:
the family glue a later ``model_config`` PR would add, for the program's
``models/llama.py`` (RMSNorm, RoPE, grouped-query attention, SwiGLU, untied
head) with a configuration file whose keys are neither OPT's nor GPT-2's.
``tests/chipbench/test_chipbench.py`` copies this directory INTO a copy of
``chipbench/`` and runs both drivers on it without editing a file that was
there."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import reference_toyllama


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        hidden_size=config["hidden_size"],
        ffn_size=config["intermediate_size"],
        rope_theta=config["rope_theta"],
        rms_eps=config["rms_norm_eps"])
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"LlamaConfig has no field {key!r}")
        setattr(cfg, key, value)
    return llama.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    return {"layers": config["num_hidden_layers"], "d": d, "heads": heads,
            "kv_heads": config["num_key_value_heads"],
            "head_dim": d // heads, "ffn": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"]}


def num_params(config: Dict[str, Any]) -> int:
    """No biases, no position table, an untied head: token table + per
    layer (q, k, v, o, the three SwiGLU matrices, two RMSNorms) + the final
    RMSNorm + the head."""
    a = arch(config)
    d, f, hd = a["d"], a["ffn"], a["head_dim"]
    attn = 2 * d * a["heads"] * hd + 2 * d * a["kv_heads"] * hd
    return a["vocab"] * d + a["layers"] * (attn + 3 * d * f + 2 * d) \
        + d + d * a["vocab"]


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None):
    return reference_toyllama.logits(config, params, tokens, at=at)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference_toyllama.next_token_loss(config, params, tokens)
