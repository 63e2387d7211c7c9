"""Operations and bytes the algorithm needs, computed from a
configuration's sizes.  Kept with the benchmark so that every PR divides
by the same numbers; nothing here looks at the program.

Conventions
-----------
* Training FLOPs per token: ``6 * N + 12 * L * d * S`` (PaLM appendix B /
  ``bench.py``): 2 FLOPs per parameter per token forward, twice that
  backward, plus attention's ``QK^T`` and ``PV`` over a length-``S``
  context.  Recomputed operations (remat) do NOT count: this is model
  FLOPs, the numerator of MFU.  ``N`` counts every parameter, embeddings
  included (the tied head is a real matmul; the position table is
  <0.4% of either model).
* Decode bytes per step: every weight byte is read once, and the valid KV
  of every live sequence is read once.  Bytes written (one new KV row per
  sequence) are below 0.1% of that and left out.
"""

from __future__ import annotations

from typing import Any, Dict

_DTYPE_BYTES = {"bf16": 2, "fp16": 2, "fp32": 4, "int8": 1}


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    """Family-independent sizes from a ``chipbench/configs`` file."""
    fam = config["family"]
    if fam == "opt":
        d = config["hidden_size"]
        return {"layers": config["num_hidden_layers"], "d": d,
                "heads": config["num_attention_heads"],
                "ffn": config["ffn_dim"], "vocab": config["vocab_size"],
                "positions": config["max_position_embeddings"],
                # HF OPTLearnedPositionalEmbedding carries 2 extra rows
                "position_rows": config["max_position_embeddings"] + 2}
    if fam == "gpt2":
        d = config["n_embd"]
        return {"layers": config["n_layer"], "d": d,
                "heads": config["n_head"],
                "ffn": config.get("n_inner") or 4 * d,
                "vocab": config["vocab_size"],
                "positions": config["n_positions"],
                "position_rows": config["n_positions"]}
    raise ValueError(f"unknown model family {fam!r}")


def num_params(config: Dict[str, Any]) -> int:
    """Parameters of a pre-LN decoder with biases, learned positions, a
    final LayerNorm and a tied head (OPT >= 1.3B and GPT-2 alike)."""
    a = arch(config)
    d, f = a["d"], a["ffn"]
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * f + f) \
        + (f * d + d) + 4 * d
    return a["vocab"] * d + a["position_rows"] * d \
        + a["layers"] * per_layer + 2 * d


def dtype_bytes(config: Dict[str, Any]) -> int:
    return _DTYPE_BYTES[config["dtype"]]


def weight_bytes(config: Dict[str, Any]) -> int:
    return num_params(config) * dtype_bytes(config)


def kv_bytes_per_token(config: Dict[str, Any]) -> int:
    """K and V, every layer, every head, in the serving dtype."""
    a = arch(config)
    return 2 * a["layers"] * a["d"] * dtype_bytes(config)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    a = arch(config)
    return 6.0 * num_params(config) + 12.0 * a["layers"] * a["d"] * seq_len


def decode_bytes_per_step(config: Dict[str, Any], valid_kv_tokens: float
                          ) -> float:
    """``valid_kv_tokens``: KV positions attended to in the step, summed
    over the live sequences."""
    return weight_bytes(config) + kv_bytes_per_token(config) * valid_kv_tokens
