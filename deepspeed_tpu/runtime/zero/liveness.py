"""ZeRO-3's layer loop: gather granularity and the prefetch pipeline.

Reference semantics: ``stage3_prefetch_bucket_size`` sets how many params the
coordinator all-gathers ahead of use and ``stage3_max_live_parameters`` caps
how many gathered params may be resident at once
(``zero/partitioned_param_coordinator.py:239 fetch_sub_module``,
``zero/config.py:79``).  Under jit there is no eager coordinator — the layer
stack is consumed by ``lax.scan`` and the SPMD partitioner derives the
collectives from the sharding rules.  It does NOT derive the coordinator's
prefetch: a collective the partitioner puts in a loop body starts when its
iteration does, so the first gather of an iteration and the last reduction
of one have nothing to run under (on four chips every such collective was
wholly exposed, and the partitioner moved activations where ZeRO-3 moves
weights: PERF.md section 6, PR 60).  Two mechanisms live here:

- the SCAN GRANULARITY (``scan_layers_grouped``): scanning groups of ``G``
  layers gathers ``G`` layers per step (bigger collectives, more compute to
  hide them under) at the cost of up to ``2 * G`` layers of gathered weights
  resident.  ``stage3_group_size`` maps the two reference knobs onto ``G``.
- the PIPELINE (``scan_layers_prefetched``, what ``overlap_comm`` means):
  the loop carries unit *i*'s gathered weights and asks for unit *i+1*'s
  before it computes, forward and (re-gathering, downwards) backward.

Contract: ``scan_group_size`` and ``scan_prefetch`` on a model config are
TRACE-TIME state owned by whichever engine was constructed from the model
most recently — every engine init site sets them (the training engine to its
computed ``G`` and, at stage 3 with ``overlap_comm``, its blocks'
``LayerShardings``; non-ZeRO-3 and inference engines to 1 and ``None``).
Two concurrently-live engines sharing one model object would fight over
them; that sharing is unsupported (as for the other engine-applied
model-config knobs, e.g. remat selection).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .sharding import constrain


def stage3_group_size(zero_config, layer_param_count: int,
                      num_layers: int) -> int:
    """Largest ``G`` dividing ``num_layers`` with
    ``G * layer_param_count <= prefetch_bucket_size`` (elements, like the
    reference's counts) and ``2 * G * layer_param_count <=
    max_live_parameters``."""
    if layer_param_count <= 0 or num_layers <= 0:
        return 1
    g_pref = max(1, int(zero_config.prefetch_bucket_size) // layer_param_count)
    g_live = max(1, int(zero_config.max_live_parameters) //
                 (2 * layer_param_count))
    g = max(1, min(g_pref, g_live, num_layers))
    while num_layers % g:
        g -= 1
    return g


def scan_layers_grouped(step, carry, blocks, group_size: int = 1):
    """``lax.scan`` over ``[L, ...]``-stacked blocks, ``group_size`` layers
    per scan step.  ``step(carry, layer_tree) -> carry``.  With
    ``group_size=1`` this is a plain scan; otherwise each leaf is reshaped
    to ``[L/G, G, ...]`` and the inner G layers run unrolled inside one
    step, so sharded (ZeRO-3) weights are all-gathered G layers at a time.
    """
    leaves = jax.tree_util.tree_leaves(blocks)
    if not leaves:
        return carry
    num_layers = leaves[0].shape[0]
    g = int(group_size)
    if g <= 1 or num_layers % g:
        def body(c, layer):
            return step(c, layer), None
        with jax.named_scope("layer"):
            carry, _ = jax.lax.scan(body, carry, blocks)
        return carry

    grouped = jax.tree_util.tree_map(
        lambda p: p.reshape((num_layers // g, g) + p.shape[1:]), blocks)

    def gbody(c, grp):
        for i in range(g):
            c = step(c, jax.tree_util.tree_map(lambda p: p[i], grp))
        return c, None

    with jax.named_scope("layer"):
        carry, _ = jax.lax.scan(gbody, carry, grouped)
    return carry


#: elements a layer under which a stacked leaf is gathered whole, once,
#: and not a layer a step (``scan_layers_prefetched``)
_GATHER_WHOLE_BELOW = 1 << 16


class LayerShardings(NamedTuple):
    """Where a STACKED ``[L, ...]`` block leaf lives, as trees of
    ``NamedSharding`` shaped like the blocks: ``sharded`` is the ZeRO-3
    spec the state holds it in, ``gathered`` the same spec without the ZeRO
    axes (a ``tp`` split stays) — what a layer computes on.  The engine
    builds the pair (``engine._configure_stage3_liveness``) and the model's
    config carries it to the trace as ``scan_prefetch``; ``None`` there is
    the plain scan."""
    sharded: Any
    gathered: Any


def _unit_sharding(stacked):
    """A stacked ``[L, ...]`` leaf's sharding for a ``[g, ...]`` unit of the
    loop: the same, or ``None`` (no constraint) where the layer dimension is
    itself split."""
    return stacked if not stacked.spec or stacked.spec[0] is None else None


def _constrain(tree, shardings):
    return jax.tree_util.tree_map(
        lambda s, x: x if s is None
        else jax.lax.with_sharding_constraint(x, s), shardings, tree,
        is_leaf=lambda s: s is None)


def _split_carry(carry):
    """(inexact leaves, the rest, rebuild): a loop differentiates the first
    and threads the second (a layer counter) beside them."""
    leaves, tree = jax.tree_util.tree_flatten(carry)
    diff = [i for i, x in enumerate(leaves)
            if jnp.issubdtype(jnp.result_type(x), jnp.inexact)]

    def merge(d, rest):
        d, rest = iter(d), iter(rest)
        return jax.tree_util.tree_unflatten(
            tree, [next(d) if i in diff else next(rest)
                   for i in range(len(leaves))])

    return ([leaves[i] for i in diff],
            [x for i, x in enumerate(leaves) if i not in diff], merge)


def scan_layers_prefetched(step, carry, blocks, group_size: int = 1,
                           shardings: Optional[LayerShardings] = None):
    """``scan_layers_grouped`` with the layer loop software-pipelined — what
    ``overlap_comm`` means for a ZeRO-3 step.  ``shardings=None`` IS
    ``scan_layers_grouped`` (the same jaxpr): the engine hands a
    ``LayerShardings`` only at stage 3, on a ZeRO world above one device,
    with ``overlap_comm`` resolved true.

    Forward: the loop's carry holds unit *i*'s weights GATHERED (constrained
    to ``gathered``); the body first asks for unit *i+1*'s gather — nothing
    in it waits for the result, so the collective is in flight while unit
    *i* computes — then runs ``step`` on the gathered weights (a layer's
    matmuls are local: no activation crosses a chip).  Backward: a
    ``custom_vjp`` over the stack, because a scan differentiated by JAX
    would keep its carry — every layer's gathered weights — as a residual.
    Its loop walks the units downwards, re-gathers one ahead, runs ``step``'s
    own backward on unit *i* and constrains unit *i*'s weight gradient to
    ``sharded`` (the reduction) as it writes it into the stacked gradient.
    What ``step``'s backward keeps is what JAX would keep — the residuals of
    ``jax.vjp(step, ...)``, a checkpointed block's input and named values —
    LESS the weights, which the backward loop re-gathers."""
    leaves = jax.tree_util.tree_leaves(blocks)
    if shardings is None or not leaves:
        return scan_layers_grouped(step, carry, blocks, group_size)
    num_layers = leaves[0].shape[0]
    g = int(group_size)
    if g <= 1 or num_layers % g:
        g = 1
    units = num_layers // g
    sharded = jax.tree_util.tree_map(_unit_sharding, shardings.sharded)
    gathered = jax.tree_util.tree_map(_unit_sharding, shardings.gathered)

    def unit_of(stack, i):
        """Unit ``i`` of the stacked blocks: ``[g, ...]`` leaves."""
        return jax.tree_util.tree_map(
            lambda p: jax.lax.dynamic_slice_in_dim(p, i * g, g), stack)

    # a vector a layer (a bias, a norm's scale) is not worth a collective a
    # step, each a latency and one of the few the compiler overlaps in a
    # body: its whole stack (L x a few KB) is gathered once, before the loop
    whole = jax.tree_util.tree_map(
        lambda p: p.size // num_layers <= _GATHER_WHOLE_BELOW, blocks)

    @jax.named_scope("zero/gather")
    def gather_small(stack):
        return jax.tree_util.tree_map(
            lambda p, w, s: jax.lax.with_sharding_constraint(p, s) if w
            else p, stack, whole, shardings.gathered)

    @jax.named_scope("zero/gather")
    def gather(stack, i):
        # the slice is pinned to the stack's own sharding first: left to
        # propagation, the gathered constraint reaches back through the
        # slice and the partitioner gathers the whole stack a step
        return jax.tree_util.tree_map(
            lambda p, w, s, t: p if w or s is None or t is None
            else jax.lax.with_sharding_constraint(
                jax.lax.with_sharding_constraint(p, s), t),
            unit_of(stack, i), whole, sharded, gathered,
            is_leaf=lambda x: x is None)

    def layers_of(unit):
        """A ``[g, ...]`` unit as its ``g`` layers: what ``step`` is handed,
        and so what its backward names as a residual."""
        return [jax.tree_util.tree_map(lambda p: p[j], unit)
                for j in range(g)]

    d0, rest0, merge = _split_carry(carry)

    def diff_step(d, layers, rest):
        """``step`` over a unit's layers on the inexact leaves of the
        carry: -> (those of the result, the rest of it)."""
        c = merge(d, rest)
        for layer in layers:
            c = step(c, layer)
        out, after, _ = _split_carry(c)
        return out, after

    def forward(d, rest, stack, keep: bool):
        """The pipelined forward loop; ``keep``: also return, per unit,
        what its backward needs (its input and ``step``'s residuals less
        the weights)."""
        def body(c, i):
            d, rest, cur = c
            ahead = gather(stack, jnp.minimum(i + 1, units - 1))
            layers = layers_of(cur)
            if keep:
                d_out, pullback, after = jax.vjp(
                    lambda d, w: diff_step(d, w, rest), d, layers,
                    has_aux=True)
                kept = _residuals(pullback, (d, layers))
            else:
                (d_out, after), kept = diff_step(d, layers, rest), None
            return (d_out, after, ahead), (d, rest, kept)

        stack = gather_small(stack)
        with jax.named_scope("layer"):
            (d, rest, _), res = jax.lax.scan(
                body, (d, rest, gather(stack, 0)), jnp.arange(units))
        return (d, rest), res

    @jax.custom_vjp
    def run(d, rest, stack):
        return forward(d, rest, stack, False)[0]

    def run_fwd(d, rest, stack):
        out, res = forward(d, rest, stack, True)
        return out, (res, stack)

    def run_bwd(saved, cot):
        (d_in, rest_in, kept), stack = saved
        d_cot, _ = cot
        stack = gather_small(stack)

        def body(c, xs):
            d_cot, cur, grads = c
            i, d, rest, kept = xs
            ahead = gather(stack, jnp.maximum(i - 1, 0))
            layers = layers_of(cur)
            _, pullback, _ = jax.vjp(
                lambda d, w: diff_step(d, w, rest), d, layers, has_aux=True)
            d_cot, cots = _with_residuals(pullback, (d, layers), kept)(d_cot)
            with jax.named_scope("zero/reduce"):
                unit_cot = _constrain(jax.tree_util.tree_map(
                    lambda *q: jnp.stack(q), *cots), sharded)
            with jax.named_scope("grad/merge"):
                grads = jax.tree_util.tree_map(
                    lambda buf, q: jax.lax.dynamic_update_slice_in_dim(
                        buf, q.astype(buf.dtype), i * g, 0), grads, unit_cot)
            return (d_cot, ahead, grads), None

        # (a backward rule is a function of its own: the forward's scopes do
        # not reach it — telemetry/scopes.py)
        with jax.named_scope("grad/merge"):
            zeros = constrain(jax.tree_util.tree_map(jnp.zeros_like,
                                                     saved[1]),
                              shardings.sharded)
        with jax.named_scope("layer"):
            (d_cot, _, grads), _ = jax.lax.scan(
                body, (list(d_cot), gather(stack, units - 1), zeros),
                (jnp.arange(units), d_in, rest_in, kept), reverse=True)
        return d_cot, None, grads

    run.defvjp(run_fwd, run_bwd)
    d, rest = run(d0, rest0, blocks)
    return merge(d, rest)


def _residuals(pullback, inputs):
    """The leaves of a ``jax.vjp`` pullback that the loop body made and that
    are NOT one of ``inputs``' own leaves (by identity: a checkpointed
    function keeps its arguments as they came): what a loop has to carry
    from its forward to its backward.  A value ``step`` closes over (a
    rotary table, a dropout key) is a tracer of an enclosing trace, is there
    for the backward too, and is not kept a layer."""
    given = jax.tree_util.tree_leaves(inputs)
    here = {id(getattr(x, "_trace", None)) for x in given} - {id(None)}
    return [leaf for leaf in jax.tree_util.tree_leaves(pullback)
            if id(getattr(leaf, "_trace", None)) in here
            and not any(leaf is x for x in given)]


def _with_residuals(pullback, inputs, kept):
    """``pullback`` (traced again, on a later loop's own ``inputs``) with
    the residuals an earlier trace of the same call kept in place of its
    own: what computed those is then dead code."""
    mine = _residuals(pullback, inputs)
    if [(x.shape, x.dtype) for x in mine] != \
            [(x.shape, x.dtype) for x in kept]:
        raise AssertionError(
            "scan_layers_prefetched: the backward's trace of the step keeps "
            f"{[(x.shape, str(x.dtype)) for x in mine]}, the forward's kept "
            f"{[(x.shape, str(x.dtype)) for x in kept]}")
    swap = {id(m): k for m, k in zip(mine, kept)}
    return jax.tree_util.tree_map(lambda x: swap.get(id(x), x), pullback)


def blocks_param_count(abstract_blocks) -> tuple:
    """(num_layers, per-layer element count) of a stacked blocks subtree."""
    leaves = jax.tree_util.tree_leaves(abstract_blocks)
    if not leaves or leaves[0].ndim < 1:
        return 0, 0
    num_layers = leaves[0].shape[0]
    per_layer = sum(int(np.prod(x.shape[1:])) for x in leaves
                    if x.ndim >= 1 and x.shape[0] == num_layers)
    return num_layers, per_layer
