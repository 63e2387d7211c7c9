"""Operations and bytes the algorithm needs, computed from what a
configuration's family reports (``chipbench/families/<family>.py``: sizes
and parameter counts).  Kept with the benchmark so that every PR divides by
the same numbers; nothing here looks at the program, and nothing here names
a family: the conventions are written once, below, for all of them.

Conventions
-----------
* Training FLOPs per token: ``6 * N_active + 12 * L * H * hd * S`` (PaLM
  appendix B / ``bench.py``): 2 FLOPs per parameter the token multiplies
  with per token forward, twice that backward, plus attention's ``QK^T``
  and ``PV`` over a length-``S`` context and ``H`` query heads of width
  ``hd`` (``H * hd`` is the model width ``d`` in every dense family here,
  hence "``6N + 12LdS``").  Recomputed operations (remat) do NOT count:
  this is model FLOPs, the numerator of MFU.  ``N_active`` is every
  parameter, embeddings included (a tied head is a real matmul; a position
  table is <0.4% of a model), unless the family reports fewer
  (``active_params``: an expert layer multiplies a token with its top-k
  experts and the router, not with all experts).
* Decode bytes per step: every weight byte the step needs is read once, and
  the valid KV of every live sequence is read once.  The weights a step
  needs are all of them unless the family says otherwise
  (``decode_weight_bytes``: experts no live token was routed to are not
  read).  Bytes written (one new KV row per sequence) are below 0.1% of
  that and left out.
* KV bytes per token: K and V, every layer, every KV head
  (``2 * L * kv_heads * hd``), in the serving dtype.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from chipbench import families

_DTYPE_BYTES = {"bf16": 2, "fp16": 2, "fp32": 4, "int8": 1}


def arch(config: Dict[str, Any]) -> Dict[str, int]:
    """The family's sizes; at least ``families.SIZES``."""
    a = families.load(config).arch(config)
    missing = [k for k in families.SIZES if k not in a]
    if missing:
        raise NotImplementedError(
            f"chipbench/families/{config['family']}.py: arch() reports no "
            f"{missing}; every family reports {list(families.SIZES)}")
    return a


def num_params(config: Dict[str, Any]) -> int:
    return int(families.load(config).num_params(config))


def active_params(config: Dict[str, Any]) -> int:
    """Parameters one token multiplies with: all, unless the family
    reports fewer."""
    fn = getattr(families.load(config), "active_params", num_params)
    return int(fn(config))


def dtype_bytes(config: Dict[str, Any]) -> int:
    return _DTYPE_BYTES[config["dtype"]]


def weight_bytes(config: Dict[str, Any]) -> int:
    return num_params(config) * dtype_bytes(config)


def kv_bytes_per_token(config: Dict[str, Any]) -> int:
    a = arch(config)
    return 2 * a["layers"] * a["kv_heads"] * a["head_dim"] \
        * dtype_bytes(config)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    a = arch(config)
    return 6.0 * active_params(config) \
        + 12.0 * a["layers"] * a["heads"] * a["head_dim"] * seq_len


def decode_bytes_per_step(config: Dict[str, Any], valid_kv_tokens: float,
                          counters: Optional[Dict[str, Any]] = None
                          ) -> float:
    """``valid_kv_tokens``: KV positions attended to in the step, summed
    over the live sequences.  ``counters``: the driver's, for a family
    whose step reads only the weights its tokens were routed to."""
    needed = getattr(families.load(config), "decode_weight_bytes", None)
    weights = weight_bytes(config) if needed is None \
        else needed(config, counters or {})
    return weights + kv_bytes_per_token(config) * valid_kv_tokens
