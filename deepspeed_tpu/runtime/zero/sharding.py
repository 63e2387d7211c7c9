"""ZeRO as sharding specs.

This module is where the reference's imperative ZeRO machinery (hook forests +
eager NCCL calls in ``runtime/zero/stage_1_and_2.py`` / ``stage3.py``) becomes
declarative: each ZeRO stage is a rule for which parts of the train state are
sharded over the mesh data axes ``("dp", "ep")``.  XLA SPMD then *derives* the
reference's communication schedule:

 - stage 1 (opt-state sharded): grads are reduce-scattered into the update and the
   fresh params all-gathered after — exactly ``stage_1_and_2.py:1772 step``.
 - stage 2 (+grad buffers sharded): the gradient accumulation buffer lives
   scattered, matching ``reduce_independent_p_g_buckets_and_remove_grads``.
 - stage 3 (+params sharded): weights are all-gathered per use (per scan step when
   the model stacks layers), matching ``PartitionedParameterCoordinator.fetch_sub_module``;
   freeing after use falls out of XLA liveness instead of explicit ``free_param``.

Partitioning rule: for each array we shard the largest dimension divisible by the
ZeRO world size that is not already claimed by a model-parallel axis; arrays with
no such dimension stay replicated (the reference pads flat buffers instead — with
per-tensor specs, padding is unnecessary).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...parallel.topology import ZERO_AXES, MeshTopology

PyTree = Any


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _flatten_spec_entry(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def shard_over_zero_axes(shape: Tuple[int, ...], base_spec: Optional[P], mesh: Mesh,
                         zero_axes: Tuple[str, ...] = ZERO_AXES) -> P:
    """Add ZeRO sharding over ``zero_axes`` to ``base_spec`` (the TP spec).

    Picks the largest dim whose per-(existing-shard) size is divisible by the ZeRO
    world size and which leaves existing axes intact; returns ``base_spec``
    unchanged if nothing fits.
    """
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    used = set()
    for entry in base:
        used.update(_flatten_spec_entry(entry))
    # shard over whichever zero axes the TP spec leaves free: an expert
    # leaf already sharded over ep still gets its opt/grad shards divided
    # over dp, so no expert parameter keeps dp-redundant optimizer copies
    remaining = tuple(a for a in zero_axes if a not in used)
    zero_ws = _axes_size(mesh, remaining)
    if zero_ws == 1 or len(shape) == 0:
        return P(*base) if base else P()

    # candidate dims: free (unsharded) with size divisible by zero world size,
    # or already-sharded dims whose residual size is divisible
    best_dim, best_size = -1, -1
    for d, size in enumerate(shape):
        entry_axes = _flatten_spec_entry(base[d])
        residual = size
        for a in entry_axes:
            residual //= mesh.shape[a]
        if residual % zero_ws == 0 and residual >= zero_ws and size > best_size:
            best_dim, best_size = d, size
    if best_dim < 0:
        return P(*base)
    new = list(base)
    existing = _flatten_spec_entry(new[best_dim])
    new[best_dim] = tuple(existing) + tuple(remaining)
    if len(new[best_dim]) == 1:
        new[best_dim] = new[best_dim][0]
    return P(*[tuple(e) if isinstance(e, tuple) else e for e in new])


class ZeroShardingPlan:
    """Per-state-component shardings for a given ZeRO stage.

    ``tp_specs`` is a pytree (matching params) of PartitionSpecs carrying
    model-parallel sharding (tp/ep/pp axes); ZeRO composes on top of it.
    """

    def __init__(self, stage: int, mesh: Mesh,
                 zero_axes: Tuple[str, ...] = ZERO_AXES,
                 param_persistence_threshold: int = 0):
        assert 0 <= stage <= 3
        self.stage = stage
        self.mesh = mesh
        self.zero_axes = zero_axes
        #: stage-3 persistent params (reference
        #: ``parameter_offload.py:316 mark_persistent_parameters``): arrays
        #: with <= this many elements stay replicated instead of
        #: ZeRO-sharded, so small tensors (norms, biases) are never
        #: all-gathered per use
        self.param_persistence_threshold = param_persistence_threshold

    def _named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def param_spec(self, shape: Tuple[int, ...], tp_spec: Optional[P]) -> P:
        if self.stage >= 3:
            if int(np.prod(shape)) <= self.param_persistence_threshold:
                return tp_spec if tp_spec is not None else P()
            return shard_over_zero_axes(shape, tp_spec, self.mesh, self.zero_axes)
        return tp_spec if tp_spec is not None else P()

    def grad_spec(self, shape: Tuple[int, ...], tp_spec: Optional[P]) -> P:
        if self.stage >= 2:
            return shard_over_zero_axes(shape, tp_spec, self.mesh, self.zero_axes)
        return tp_spec if tp_spec is not None else P()

    def opt_spec(self, shape: Tuple[int, ...], tp_spec: Optional[P]) -> P:
        if self.stage >= 1:
            return shard_over_zero_axes(shape, tp_spec, self.mesh, self.zero_axes)
        return tp_spec if tp_spec is not None else P()

    # -- pytree-level helpers -------------------------------------------------
    def param_shardings(self, params: PyTree, tp_specs: Optional[PyTree] = None):
        return self._tree(params, tp_specs, self.param_spec)

    def grad_shardings(self, params: PyTree, tp_specs: Optional[PyTree] = None):
        return self._tree(params, tp_specs, self.grad_spec)

    def opt_shardings_like(self, params: PyTree, opt_state: PyTree,
                           tp_specs: Optional[PyTree] = None):
        """Shardings for an optax-style state.

        Optimizer moment buffers are sub-trees structured exactly like ``params``
        (optax invariant), so we match *structurally*: any sub-tree of the state
        with the params treedef gets per-param opt specs; everything else
        (step counters, scalars) is replicated.
        """
        params_treedef = jax.tree_util.tree_structure(params)
        tp_tree = self._resolve_tp(params, tp_specs)
        per_param = jax.tree_util.tree_map(
            lambda p, tp: self._named(self.opt_spec(tuple(np.shape(p)), tp)),
            params, tp_tree, is_leaf=lambda x: x is None)

        def is_params_like(node) -> bool:
            try:
                return jax.tree_util.tree_structure(node) == params_treedef
            except Exception:
                return False

        def go(node):
            if is_params_like(node):
                return per_param
            return self._named(P())

        return jax.tree_util.tree_map(go, opt_state, is_leaf=is_params_like)

    def _resolve_tp(self, params: PyTree, tp_specs: Optional[PyTree]):
        if tp_specs is None:
            return jax.tree_util.tree_map(lambda _: None, params)
        return tp_specs

    def _tree(self, params: PyTree, tp_specs: Optional[PyTree], spec_fn):
        tp_tree = self._resolve_tp(params, tp_specs)
        return jax.tree_util.tree_map(
            lambda p, tp: self._named(spec_fn(tuple(np.shape(p)), tp)),
            params, tp_tree, is_leaf=lambda x: x is None)


def _is_spec_leaf(x) -> bool:
    return x is None or isinstance(x, P)


def constrain(tree: PyTree, shardings: PyTree):
    """``with_sharding_constraint`` over a pytree (no-op outside jit)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, shardings)
