"""A model family is a plug-in: ``families/<family>.py``, found by the
``family`` key of a ``chipbench/configs`` file, is the ONE place that knows
the family.  The shared files (``costs.py``, ``run.py``, the drivers, the
per-layer readers) ask it and name no family themselves.

What a family file provides (``config`` is the configuration file's dict,
with its ``rehearse`` block applied in a rehearsal):

    build(config, overrides=None) -> ModelSpec
        the program's model; ``overrides`` are the cell's ``model`` settings
    arch(config) -> dict
        the sizes of ``SIZES`` (more keys are the family's own business)
    num_params(config) -> int
        every parameter, embeddings and head included
    logits(config, params, tokens, at=None) -> float32 [B, S or len(at), V]
    next_token_loss(config, params, tokens) -> float32 scalar
        the plain reference, on the PROGRAM's parameter pytree: either
        ``chipbench/reference.py`` (pre-LN, learned positions) or a new
        ``chipbench/reference_<x>.py`` that the family file points at

and may provide, where the answer is not "all of them":

    active_params(config) -> int
        parameters one token multiplies with (experts: top-k of them)
    decode_weight_bytes(config, counters) -> float
        weight bytes one decode step must read (experts: those touched,
        which the family may take from the driver's ``counters``)
"""

from __future__ import annotations

import importlib
from typing import Any, Dict

#: every family's ``arch()`` reports at least these
SIZES = ("layers", "d", "heads", "kv_heads", "head_dim", "vocab", "positions")
REQUIRED = ("build", "arch", "num_params", "logits", "next_token_loss")


def load(config: Dict[str, Any]):
    """The family module of a configuration.  A family that lacks a
    required function is an error that names the file and the function."""
    name = config["family"]
    path = f"chipbench/families/{name}.py"
    try:
        module = importlib.import_module("chipbench.families." + name)
    except ModuleNotFoundError as e:
        if e.name != "chipbench.families." + name:
            raise
        raise NotImplementedError(
            f"family {name!r} has no {path}: add it (its contract is in "
            "chipbench/families/__init__.py)") from None
    for fn in REQUIRED:
        if not callable(getattr(module, fn, None)):
            raise NotImplementedError(
                f"{path} lacks {fn}(): add it (its contract is in "
                "chipbench/families/__init__.py)")
    return module
