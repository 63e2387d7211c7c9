"""``family: opt`` — a ``chipbench/configs`` file to the program's
``models/opt.py`` configuration, its sizes and parameter count, and its
plain reference (``chipbench/reference.py``, the ``opt`` row).
``overrides`` are the cell's ``model`` settings (kernel and remat choices),
applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import reference
from chipbench.families import _preln


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import opt

    if not config["do_layer_norm_before"]:
        raise ValueError("the reference is a pre-LN decoder; OPT-350m's "
                         "post-LN layout is not covered")
    proj = config["word_embed_proj_dim"]
    cfg = opt.OPTConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        hidden_size=config["hidden_size"],
        ffn_size=config["ffn_dim"],
        word_embed_proj_dim=None if proj == config["hidden_size"] else proj,
        do_layer_norm_before=True,
        dropout=config["dropout"])
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"OPTConfig has no field {key!r}")
        setattr(cfg, key, value)
    return opt.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    return {"layers": config["num_hidden_layers"], "d": d,
            "heads": heads, "kv_heads": heads, "head_dim": d // heads,
            "ffn": config["ffn_dim"], "vocab": config["vocab_size"],
            "positions": config["max_position_embeddings"],
            # HF OPTLearnedPositionalEmbedding carries 2 extra rows
            "position_rows": config["max_position_embeddings"] + 2}


def num_params(config: Dict[str, Any]) -> int:
    return _preln.num_params(arch(config))


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None):
    return reference.logits("opt", params, tokens,
                            config["num_attention_heads"], at=at)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference.next_token_loss("opt", params, tokens,
                                     config["num_attention_heads"])
