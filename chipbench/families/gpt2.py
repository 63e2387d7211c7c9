"""``family: gpt2`` — a ``chipbench/configs`` file to the program's
``models/gpt2.py`` configuration.  ``overrides`` are the cell's ``model``
settings (kernel and remat choices), applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> (ModelSpec, number of attention heads)"""
    from deepspeed_tpu.models import gpt2

    d = config["n_embd"]
    inner = config.get("n_inner") or 4 * d
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
    return gpt2.build(cfg), cfg.num_heads
