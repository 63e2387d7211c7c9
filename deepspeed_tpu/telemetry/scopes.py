"""The scope vocabulary: what a ``jax.named_scope`` in a program body may be
called, said once.  No jax: a reader of compiled text or of a profile
imports this file alone.

Every jitted program a cell runs — the serving programs' bodies under
``models/``, ``moe/``, ``ops/`` and ``inference/serving.py``, the training
step of ``runtime/engine.py`` and ``runtime/zero/`` — wraps its work in
``jax.named_scope(<a key of VOCABULARY>)``.  XLA carries the name stack into
every instruction's ``metadata={op_name=...}``, through fusion, through the
backward pass and through a checkpointed block's recomputation, so the
COMPILED step can say which layer asked for each of its fusions
(``telemetry/hlo_text.py scope_table``) and a profile's device seconds can
be summed by layer (``telemetry/device_scopes.py``).  A scope is metadata:
it moves no instruction of any program
(``tests/unit/test_chip_lowering.py OLD_PROGRAMS``).

``VOCABULARY[name] = (layer, what)``: ``layer`` is the row of ``PERF.md``
section 3 the scope's seconds are filed under, ``what`` the work that
belongs there.  Scopes nest by their full names (``layer/attn/qkv`` is
entered inside ``layer/attn``); an instruction belongs to the INNERMOST
entry of its stack.  A new family adds scopes FROM this table; a name the
table lacks is added here first (``tests/unit/test_scope_vocabulary.py``
holds every ``named_scope`` literal of the package to it).

:func:`normalise` turns an ``op_name`` into ``(scope, pass)``: the wrappers
JAX puts around a stack — ``jit(..)``, ``jvp(..)``, ``transpose(..)``,
``vmap(..)``, ``checkpoint`` / ``rematted_computation``, ``while/body``,
``closed_call``, ``custom_vjp_call`` ... — are taken away, and what they say
of the PASS is kept: ``bwd`` under a ``transpose``, ``remat`` under a
``rematted_computation`` (the forward run again inside the backward), else
``fwd``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

__all__ = ["VOCABULARY", "UNSCOPED", "PASSES", "normalise", "common_scope"]

MODEL, KERNELS, ENGINE, ZERO = "model step", "kernels", "engine", "ZeRO"

#: scope -> (PERF.md section 3 layer, the work that belongs under it)
VOCABULARY: Dict[str, Tuple[str, str]] = {
    "embed": (MODEL, "token (and position) embedding rows gathered, their "
              "norm or scale"),
    "layer": (MODEL, "a layer loop's own plumbing: a layer's weights read out "
              "of their stacks, the carry, what a layer hands on stacked"),
    "layer/norm": (MODEL, "a norm that is a pass of its own (pre-attention, "
                   "pre-FFN, final)"),
    "layer/attn": (MODEL, "an attention block: what of it no leaf below "
                   "names (residual add, gates)"),
    "layer/attn/qkv": (MODEL, "q / k / v projection, bias, q/k norm, rope, "
                       "the head split"),
    "layer/attn/latent_up": (MODEL, "latent attention's absorbed "
                             "up-projections (query into the latent space, "
                             "context out of it)"),
    "layer/attn/kv_write": (KERNELS, "the cache write: paged blocks gathered, "
                            "merged and scattered back; a state leaf's or a "
                            "tail's row update"),
    "layer/attn/core": (KERNELS, "scores, softmax and the weighted sum: the "
                        "attention kernel, or XLA's where there is none"),
    "layer/attn/select/score": (KERNELS, "learned sparse attention: the "
                                "indexer's scores over the pool"),
    "layer/attn/select/select": (KERNELS, "learned sparse attention: the "
                                 "top-k of the scores"),
    "layer/attn/select/read": (KERNELS, "learned sparse attention: the read "
                               "of the selected keys"),
    "layer/attn/out": (MODEL, "the output projection and its bias"),
    "layer/mlp": (MODEL, "a dense feed-forward block: both products, the "
                  "activation, the residual"),
    "layer/moe/route": (MODEL, "router scores, top-k, the sort and the group "
                        "sizes ONLY"),
    "layer/moe/gather": (MODEL, "rows gathered into expert order; whatever "
                         "packs the grouped matmul's operands"),
    "layer/moe/experts": (KERNELS, "the experts' grouped matmuls and their "
                          "activation"),
    "layer/moe/combine": (MODEL, "rows scattered back, weighted and summed "
                          "over the k choices"),
    "layer/moe/shared": (MODEL, "the shared (always-on) experts"),
    "layer/state/conv": (MODEL, "a state layer's short convolution and its "
                         "tail"),
    "layer/state/gate": (MODEL, "a state layer's gates, decays and "
                         "normalisers outside the recurrence"),
    "layer/state/step": (KERNELS, "the recurrence's one-token update and "
                         "read (decode)"),
    "layer/state/chunk": (KERNELS, "the recurrence over a chunk: the state "
                          "carried between chunks (prefill)"),
    "layer/state/chunk_intra": (KERNELS, "the part of a chunk that is "
                                "attention inside it"),
    "mtp": (MODEL, "a multi-token-prediction module's block"),
    "mtp/join": (MODEL, "the module's join of the trunk's hidden state with "
                 "the next token's embedding"),
    "head": (MODEL, "the final product with the vocabulary matrix ONLY"),
    "sample": (MODEL, "the sampler: what of it no leaf below names"),
    "sample/filter": (MODEL, "temperature, masks, the top-k / top-p threshold "
                      "searches (`nucleus_search` is a kernel inside it)"),
    "sample/softmax": (MODEL, "the normalisation over the vocabulary"),
    "sample/draw": (MODEL, "keys, threefry, the Gumbel / inverse-CDF draw"),
    "sample/argmax": (MODEL, "the argmax over the vocabulary"),
    "verdict": (MODEL, "a speculative round's accept / reject"),
    "loss": (ENGINE, "the loss from the logits (chunked cross-entropy "
             "included) and its scaling"),
    "grad/merge": (ENGINE, "gradient accumulation over micro-batches, the "
                   "scan's stacked-leaf merge, clipping's norm"),
    "optim/update": (ENGINE, "the optimizer's moments and the weight update"),
    "optim/cast": (ENGINE, "the low-precision copy of the weights"),
    "zero/gather": (ZERO, "a parameter shard gathered for use"),
    "zero/reduce": (ZERO, "gradients reduced or reduce-scattered to their "
                    "owners"),
}

#: what an instruction under no entry of the vocabulary is filed as
UNSCOPED = "unscoped"
PASSES = ("fwd", "bwd", "remat")

#: the entries as path elements, deepest first
_BY_LENGTH = sorted(((name, name.split("/")) for name in VOCABULARY),
                    key=lambda entry: -len(entry[1]))


def _unwrap(op_name: str) -> Tuple[List[str], set]:
    """``op_name`` with every ``wrapper(...)`` opened — its content stays in
    the path, its name goes into the set — split on ``/``.  ``jit(f)`` /
    ``pjit(f)`` name a function, not a scope: their content goes too."""
    out: List[str] = []
    wrappers: set = set()
    token: List[str] = []
    drop = [False]               # per open paren: is its content dropped?
    for ch in op_name:
        if ch == "(":
            name = "".join(token)
            token = []
            wrappers.add(name)
            drop.append(drop[-1] or name in ("jit", "pjit", "xla_call"))
        elif ch == ")":
            if token and not drop[-1]:
                out.append("".join(token))
            token = []
            if len(drop) > 1:
                drop.pop()
        elif ch == "/":
            if token and not drop[-1]:
                out.append("".join(token))
            token = []
        else:
            token.append(ch)
    if token:
        out.append("".join(token))
    return out, wrappers


def normalise(op_name: Optional[str]) -> Tuple[str, str]:
    """``(scope, pass)`` of an instruction's ``op_name``: the innermost
    entry of :data:`VOCABULARY` on its name stack (:data:`UNSCOPED` without
    one) and ``fwd`` | ``bwd`` | ``remat``.  The stack's last element is
    the primitive's own name and never a scope."""
    if not op_name:
        return UNSCOPED, "fwd"
    parts, wrappers = _unwrap(op_name)
    which = "remat" if "rematted_computation" in parts else \
        "bwd" if "transpose" in wrappers else "fwd"
    path = parts[:-1]
    # innermost: the entry whose last element lies deepest in the stack;
    # of those that end there the longest
    for end in range(len(path), 0, -1):
        for name, want in _BY_LENGTH:
            if path[max(end - len(want), 0):end] == want:
                return name, which
    return UNSCOPED, which


def common_scope(scopes) -> Optional[str]:
    """The longest common ``/``-prefix of ``scopes`` if it is an entry of
    the vocabulary (``layer/attn/qkv`` + ``layer/attn/out`` ->
    ``layer/attn``), else None.  :data:`UNSCOPED` has no prefix."""
    split = [s.split("/") for s in scopes]
    if not split or any(s == [UNSCOPED] for s in split):
        return None
    common = split[0]
    for s in split[1:]:
        n = 0
        while n < min(len(common), len(s)) and common[n] == s[n]:
            n += 1
        common = common[:n]
    name = "/".join(common)
    return name if name in VOCABULARY else None
