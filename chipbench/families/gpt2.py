"""``family: gpt2`` — a ``chipbench/configs`` file to the program's
``models/gpt2.py`` configuration, its sizes and parameter count, and its
plain reference (``chipbench/reference.py``, the ``gpt2`` row).
``overrides`` are the cell's ``model`` settings (kernel and remat choices),
applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from chipbench import reference
from chipbench.families import _preln


def _inner(config: Dict[str, Any]) -> int:
    return config.get("n_inner") or 4 * config["n_embd"]


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> ModelSpec"""
    from deepspeed_tpu.models import gpt2

    d, inner = config["n_embd"], _inner(config)
    if inner % d:
        raise ValueError(f"n_inner {inner} is not a multiple of n_embd {d}")
    cfg = gpt2.GPT2Config(
        vocab_size=config["vocab_size"],
        max_seq_len=config["n_positions"],
        num_layers=config["n_layer"],
        num_heads=config["n_head"],
        hidden_size=d,
        mlp_ratio=inner // d,
        dropout=max(config["attn_pdrop"], config["embd_pdrop"],
                    config["resid_pdrop"]))
    for key, value in (overrides or {}).items():
        if not hasattr(cfg, key):
            raise ValueError(f"GPT2Config has no field {key!r}")
        setattr(cfg, key, value)
    return gpt2.build(cfg)


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    d, heads = config["n_embd"], config["n_head"]
    return {"layers": config["n_layer"], "d": d,
            "heads": heads, "kv_heads": heads, "head_dim": d // heads,
            "ffn": _inner(config), "vocab": config["vocab_size"],
            "positions": config["n_positions"],
            "position_rows": config["n_positions"]}


def num_params(config: Dict[str, Any]) -> int:
    return _preln.num_params(arch(config))


def logits(config: Dict[str, Any], params: Any, tokens,
           at: Optional[Sequence[int]] = None):
    return reference.logits("gpt2", params, tokens, config["n_head"], at=at)


def next_token_loss(config: Dict[str, Any], params: Any, tokens):
    return reference.next_token_loss("gpt2", params, tokens, config["n_head"])
