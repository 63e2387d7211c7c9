"""Activation-checkpointing (remat) policy selection.

TPU-native analog of the reference's activation checkpointing subsystem
(``deepspeed/runtime/activation_checkpointing/checkpointing.py:749``
``configure()``): instead of wrapping module forwards in a checkpoint
autograd Function, models wrap their block body in ``jax.checkpoint`` and
this module maps the ``activation_checkpointing`` config block (plus the
per-model ``remat_policy`` knob) to a jax checkpoint policy.

Key mapping from the reference config block:
 - ``partition_activations`` — subsumed: under ``jit`` saved residuals
   inherit the activation sharding, so they are already partitioned across
   the mesh (no gather/scatter pass is needed).
 - ``cpu_checkpointing`` — maps to XLA host offload of the saved dot
   outputs (``offload_dot_with_no_batch_dims``): residuals live in pinned
   host memory between forward and backward.
 - ``number_checkpoints / contiguous_memory_optimization /
   synchronize_checkpoint_boundary`` — allocator/stream knobs with no TPU
   analog (XLA owns scheduling); accepted and ignored.

**What a checkpointed block keeps** (``checkpoint_block``: the rule behind
every family's ``remat: true``): its input AND the two named outputs of its
flash kernel, ``flash_out`` / ``flash_lse`` (``ops/flash_attention.py``
names them in every kernel generation) — the two values the flash backward
is a function of, so the backward is handed them and the Pallas forward
runs once a step, not twice.  Everything else (norms, QKV, rotary,
projections, router, grouped matmuls, combine) is recomputed.  Kept bytes a
token-layer go from ``2 d`` (the bf16 input) to ``2 d + 2 H hd + 4 H``
(``o`` in bf16, ``lse`` in float32): the cost for a user at the memory
limit — and on the chip ``o`` is held as the kernel wrote it, ``[B H, S,
hd]`` in 128-lane rows, so a head narrower than 128 costs ``2 H x 128``
(OPT-1.3B, hd 64: 3.27 GB a chip kept where the logical bytes are 1.66;
PERF.md section 6, PR 49).  A block whose attention did not go through ``flash_attention``
(``use_flash=False``, the CPU default; BLOOM's alibi attention) holds no
such name and keeps its input alone: the names decide, there is no option.
GPT-2's ``dots_flash`` is this rule plus the dots.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Dict, NamedTuple, Tuple

import jax
from jax.extend.core import Literal

#: offload target for cpu_checkpointing (XLA memories API)
_OFFLOAD_SRC, _OFFLOAD_DST = "device", "pinned_host"

_FLASH_NAMES = ("flash_out", "flash_lse")
#: THE rule: beside its input a checkpointed block keeps its flash kernel's
#: two named outputs.  One object: every block call site and ``dots_flash``.
KEEP_FLASH = jax.checkpoint_policies.save_only_these_names(*_FLASH_NAMES)


def remat_policy(policy: str | None, offload: bool = False):
    """Resolve a policy name to a ``jax.checkpoint`` policy callable.

    ``policy``: ``"full"`` (recompute everything, reference default),
    ``"dots"`` (save projection/matmul outputs, recompute attention and
    elementwise), ``"dots_flash"`` (dots + pin the flash kernel's o/lse so
    the backward reuses them).  ``offload=True`` moves the saved residuals
    to pinned host memory (reference ``cpu_checkpointing``).
    """
    if policy in (None, "full"):
        # nothing saved -> nothing to offload
        return None
    if policy not in ("dots", "dots_flash"):
        raise ValueError(f"unknown remat policy {policy!r} "
                         "(expected full|dots|dots_flash)")
    if offload:
        dots = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            _OFFLOAD_SRC, _OFFLOAD_DST)
    else:
        dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if policy == "dots":
        return dots
    if offload:
        names = jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(_FLASH_NAMES),
            offload_src=_OFFLOAD_SRC, offload_dst=_OFFLOAD_DST)
    else:
        names = KEEP_FLASH
    return jax.checkpoint_policies.save_from_both_policies(dots, names)


class Kept(NamedTuple):
    """What one checkpointed block call keeps for its backward, in bytes of
    one micro-batch as the program is written (logical arrays, before any
    sharding).  ``input``: the kept arguments of the shape and dtype of
    something the block returns — the residual stream, the value a layer
    loop holds once per layer (the layer's weights and the rotary tables are
    arguments too; they are held anyway and not counted).  ``named``: each
    kept ``checkpoint_name`` with its bytes.  ``other``: whatever else a
    policy let through (0 under ``KEEP_FLASH``)."""
    block: str
    input: int
    named: Tuple[Tuple[str, int], ...]
    other: int

    @property
    def what(self) -> str:
        return "+".join(["input", *(name for name, _ in self.named)]
                        + ["other"] * bool(self.other))

    @property
    def bytes(self) -> int:
        return self.input + sum(b for _, b in self.named) + self.other


_listener = threading.local()


@contextlib.contextmanager
def listen():
    """Note the checkpointed block calls traced inside the ``with`` (on this
    thread) for ``kept`` to read once the trace is over; with nobody
    listening a call notes nothing."""
    outer = getattr(_listener, "calls", None)
    _listener.calls = calls = []
    try:
        yield calls
    finally:
        _listener.calls = outer


def _abstract(tree):
    """The types of a call's arrays, sharding and all: traced again with
    these, the block's own trace is found in ``jax.checkpoint``'s cache."""
    def one(a):
        if not isinstance(a, jax.Array):
            return a
        t = jax.typeof(a)
        return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=t.sharding,
                                    weak_type=t.weak_type)

    return jax.tree_util.tree_map(one, tree)


def checkpoint_block(fn, static_argnums=()):
    """``jax.checkpoint`` of a transformer block under THE rule,
    ``KEEP_FLASH``.  A call traced under ``listen()`` is noted by its
    abstract arguments and results."""
    ck = jax.checkpoint(fn, policy=KEEP_FLASH, static_argnums=static_argnums)

    @functools.wraps(fn)
    def block(*args):
        out = ck(*args)
        calls = getattr(_listener, "calls", None)
        if calls is not None:
            calls.append((ck, static_argnums, _abstract(args), _abstract(out)))
        return out

    return block


def kept(calls) -> Dict[Kept, int]:
    """What the noted block calls keep: each distinct ``Kept`` with the
    number of calls it stands for.  It costs one more abstract trace of a
    block, so it is made after the program's own trace, where it cannot add
    to what that trace counted (``ops/flash_attention.choices``)."""
    found: Dict[Kept, int] = {}
    memo: Dict[Any, Kept] = {}
    for ck, static, args, out in calls:
        leaves, tree = jax.tree_util.tree_flatten(
            [None if i in static else a for i, a in enumerate(args)])
        key = (ck, tuple(id(args[i]) for i in static), tree, tuple(leaves))
        if key not in memo:
            memo[key] = _kept(ck, static, args, out)
        found[memo[key]] = found.get(memo[key], 0) + 1
    return found


def _names(jaxpr) -> Dict[Any, str]:
    """The variables of ``jaxpr`` that carry a ``checkpoint_name``."""
    named: Dict[Any, str] = {}
    for e in jaxpr.eqns:
        inner = e.params.get("jaxpr")
        if e.primitive.name == "name":
            named[e.outvars[0]] = e.params["name"]
        elif e.primitive.name == "reduce_precision":
            # how a checkpoint pins a saved name: the residual is this copy
            if e.invars[0] in named:
                named[e.outvars[0]] = named[e.invars[0]]
        elif inner is not None:
            # jit, shard_map (a kernel placed on the mesh): outputs one to one
            inner = getattr(inner, "jaxpr", inner)
            deep = _names(inner)
            for o, i in zip(e.outvars, inner.outvars):
                if not isinstance(i, Literal) and i in deep:
                    named[o] = deep[i]
    return named


def _kept(ck, static, args, out) -> Kept:
    """The residuals of ``jax.linearize`` of one call (the recipe of
    ``jax.ad_checkpoint.print_saved_residuals``), sorted into ``Kept``."""
    dynamic = [i for i in range(len(args)) if i not in static]

    def call(*dyn):
        full = list(args)
        for i, a in zip(dynamic, dyn):
            full[i] = a
        return ck(*full)

    closed, (_, residuals) = jax.make_jaxpr(
        lambda *dyn: jax.linearize(call, *dyn), return_shape=True)(
            *(args[i] for i in dynamic))
    jaxpr = closed.jaxpr
    n = len(jax.tree_util.tree_leaves(residuals))
    named = _names(jaxpr)
    stream = {(o.shape, o.dtype) for o in jax.tree_util.tree_leaves(out)}
    stream_bytes = other = 0
    names: Dict[str, int] = {}
    for v in jaxpr.outvars[len(jaxpr.outvars) - n:]:
        nbytes = v.aval.size * v.aval.dtype.itemsize
        if isinstance(v, Literal) or v not in named and v not in jaxpr.invars:
            other += nbytes
        elif v in named:
            names[named[v]] = names.get(named[v], 0) + nbytes
        elif (v.aval.shape, v.aval.dtype) in stream:
            stream_bytes += nbytes          # else weights, tables: held anyway
    return Kept(ck.__name__, stream_bytes, tuple(sorted(names.items())), other)


def apply_config_to_model(ac_config, model_spec, log=None,
                          n_devices: int = 1) -> bool:
    """Apply an ``activation_checkpointing`` config block to a model.

    Returns True when the model's remat knobs were switched.  The model must
    expose its config object via ``ModelSpec.model_config`` with ``remat``
    (bool) and optionally ``remat_policy`` / ``remat_offload`` attributes —
    all ``models/`` builders do.

    ``cpu_checkpointing`` host offload is honored only on a single-device
    program: XLA's SPMD partitioner currently rejects the offload
    placement custom-calls under a >1-device mesh ("Side-effect HLO must
    have sharding"); remat itself still applies there.
    """
    requested = (ac_config.enabled or ac_config.partition_activations
                 or ac_config.cpu_checkpointing
                 or ac_config.policy is not None
                 or ac_config.number_checkpoints is not None)
    if not requested:
        return False
    mc = getattr(model_spec, "model_config", None)
    if mc is None or not hasattr(mc, "remat"):
        if log is not None:
            log("activation_checkpointing is configured but the model does "
                "not expose remat knobs (ModelSpec.model_config); ignoring")
        return False
    mc.remat = True
    if ac_config.policy is not None and hasattr(mc, "remat_policy"):
        mc.remat_policy = ac_config.policy
    if ac_config.cpu_checkpointing:
        if n_devices > 1:
            if log is not None:
                log("activation_checkpointing.cpu_checkpointing: host "
                    "offload is single-device-only under current XLA SPMD; "
                    "keeping remat WITHOUT host offload on this "
                    f"{n_devices}-device mesh")
        else:
            mc.remat_offload = True
    if log is not None:
        log(f"activation checkpointing: remat=True "
            f"policy={getattr(mc, 'remat_policy', 'full')} "
            f"cpu_offload={ac_config.cpu_checkpointing}")
    return True
