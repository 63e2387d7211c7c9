"""What ``opt`` and ``gpt2`` share: both are pre-LN decoders with biases,
learned positions, a final LayerNorm and a head tied to the token table,
so one closed formula counts their parameters and ``chipbench/reference.py``
is the plain reference of both."""

from __future__ import annotations

from typing import Dict


def num_params(a: Dict[str, int]) -> int:
    """From a family's ``arch()``, which adds ``ffn`` and ``position_rows``
    to the common sizes: per layer the fused qkv, the output projection and
    the two MLP matrices with their biases, and two LayerNorms."""
    d, f = a["d"], a["ffn"]
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * f + f) \
        + (f * d + d) + 4 * d
    return a["vocab"] * d + a["position_rows"] * d \
        + a["layers"] * per_layer + 2 * d
