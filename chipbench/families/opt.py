"""``family: opt`` — a ``chipbench/configs`` file to the program's
``models/opt.py`` configuration.  ``overrides`` are the cell's ``model``
settings (kernel and remat choices), applied as attributes."""

from __future__ import annotations

from typing import Any, Dict, Optional


def build(config: Dict[str, Any], overrides: Optional[Dict[str, Any]] = None):
    """-> (ModelSpec, number of attention heads)"""
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
    return opt.build(cfg), cfg.num_heads
