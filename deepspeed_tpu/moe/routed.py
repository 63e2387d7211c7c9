"""Dropless routed FFN: every routed (token, expert) pair is computed, for
any ``k`` in ``[1, E]`` — every inference forward, and training where the
configuration says dropless (``models/mixtral.py``: ``capacity_factor=None``).

``sharded_moe.py`` buckets tokens into per-expert capacity slots with dense
one-hot einsums over ``[G, S, E, C]`` and drops what overflows — the right
shape for training under ``ep`` (fixed-size all-to-all), the wrong one for
serving: a decode batch arrives as ``[slots, 1, D]``, every slot is its own
group, and each of the E experts runs over ``slots x min_capacity`` rows of
which ``slots x k / E`` are real.  Here the ``T x k`` pairs are sorted by
expert and the three expert matmuls run as **grouped** matmuls over the
ragged groups, so an expert's weights are read once whatever its group's
size and an expert nobody chose is not read at all.  Two grouped matmuls,
chosen by the caller from what it can observe (``models/mixtral.py``):

* ``moe/grouped_matmul.moe_gmm`` — the Pallas kernel (interpreted on the
  CPU, so the tests run the same code).  It takes the WHOLE ``[L, E, ..]``
  weight stacks and the layer index, so a layer scan never slices 134 MB
  operands out of them.
* ``jax.lax.ragged_dot`` — XLA's own (a Mosaic kernel of its own on the
  TPU, a reference loop on the CPU).  It has a partitioning rule, so it
  serves ``tp``/``ep`` meshes, and it takes one layer's weights, so it
  serves INT8 records that are expanded a layer at a time.

    p = softmax_fp32(r Wr);  S = top-k of p;  (renormalize: p_S / sum p_S)
    out = sum_{e in S} p_e * (act(y W1_e) * (y W3_e)) W2_e

(``r`` is ``y`` unless the caller hands the router tokens of its own,
``router_x``; ``act`` is ``silu`` or ``relu``.  A model whose router is not
``r Wr`` at all — an MLP, a score that reads the layer before — hands in the
router's OUTPUT, ``routed = (p_S, S)``, and the first line is the caller's.)

**Differentiable.**  The gather into expert order, the scatter back and the
float32 combine are plain XLA; ``moe_gmm`` carries a ``custom_vjp`` (its two
transposes are kernels of their own) and ``ragged_dot`` XLA's.  The top-k
choice is piecewise constant: the router learns through the chosen weights
and, with ``balance=True``, through the Switch-form balance term ``E sum_e
f_e P_e`` (``f_e``: the share of the ``T k`` pairs routed to ``e``, a
constant; ``P_e``: the mean over tokens of the float32 scores over ALL ``E``
outputs, whichever experts are held).

**An expert layer that holds a share of its experts** (``held = (first,
count)``: this chip's experts ``first .. first + count - 1`` of the ``E``
the router scores — one chip of an expert-parallel group).  The router is
whole: scores and top-k over ALL ``E`` outputs, the chosen weights
normalised over all ``k`` chosen.  The pairs whose expert is not held are
sorted BEHIND the held groups and multiplied with nothing (``group_sizes``
covers the held experts only, ``w1`` / ``w3`` / ``w2`` are ``[.., count,
..]``; ``moe_gmm``: "rows past ``sum(group_sizes)`` belong to nobody"), and
the result is the held experts' PARTIAL sum — what this chip would send
into the group's exchange.  No code stands in for the absent chips: on one
chip the layer runs without its exchange.  ``held=None`` is the program
above, bit for bit.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .grouped_matmul import moe_gmm

#: columns of the routing record ``routed_ffn`` returns; with ``held`` a
#: fourth, the live pairs routed to experts that are not held
RECORD = ("experts_touched", "expert_rows", "expert_rows_max")
RECORD_HELD = RECORD + ("expert_rows_absent",)


def _dense(w, dtype):
    """Expert weights may arrive as INT8 records (quant-aware serving):
    expanded here, per layer at point of use, as ``moe_apply`` does."""
    from ..ops import quantization as quant

    if quant.is_k_quantized(w):
        return quant.dequantize_k(w, dtype)
    if quant.is_quantized(w):
        return quant.dequantize(w, dtype)
    return w.astype(dtype)


#: the experts' gate activation, by the configuration's name for it
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def route(y2d, gate_w, k: int, renormalize: bool, score: str = "softmax",
          scores: bool = False, bias=None, scale: float = 1.0):
    """Router of ``[T, D]`` tokens: float32 logits and scores over ALL
    experts — their softmax, or with ``score="sigmoid"`` each logit's own
    sigmoid — then top-k (ties to the lower expert id).  ``bias`` (float
    ``[E]``): a per-expert SELECTION bias — the top-k is taken of ``scores +
    bias``, the weights are the unbiased scores at the chosen (it chooses
    and does not weigh).  ``scale``: a factor on the chosen weights, after
    the renormalisation.
    -> (weights float32 [T, k], experts int32 [T, k]); with ``scores`` a
    third, the float32 scores ``[T, E]`` (what a balance loss averages)."""
    logits = jnp.dot(y2d, _dense(gate_w, y2d.dtype),
                     preferred_element_type=jnp.float32)
    if score == "sigmoid":
        all_p = jax.nn.sigmoid(logits)
    elif score == "softmax":
        all_p = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"router score {score!r}: 'softmax' or 'sigmoid'")
    if bias is None:
        top_p, top_e = jax.lax.top_k(all_p, k)
    else:
        _, top_e = jax.lax.top_k(all_p + bias.astype(jnp.float32), k)
        top_p = jnp.take_along_axis(all_p, top_e, axis=-1)
    if renormalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if scale != 1.0:
        top_p = top_p * scale
    if scores:
        return top_p, top_e.astype(jnp.int32), all_p
    return top_p, top_e.astype(jnp.int32)


def _grouped(xs, w, group_sizes, layer, kernel: bool):
    """``xs`` rows sorted by expert x that expert's ``w[layer]``."""
    if kernel:
        return moe_gmm(xs, w.astype(xs.dtype), group_sizes, layer)
    if layer is not None:
        w = jax.tree_util.tree_map(lambda a: a[layer], w)
    return jax.lax.ragged_dot(xs, _dense(w, xs.dtype), group_sizes)


def routed_ffn(y, gate_w, w1, w3, w2, k: int, renormalize: bool,
               live=None, layer=None, kernel: bool = True,
               choices: bool = False, held=None,
               score: str = "softmax", act: str = "silu", router_x=None,
               balance: bool = False,
               choice_major: bool = False, bias=None,
               scale: float = 1.0, routed=None) -> Tuple[jnp.ndarray, ...]:
    """Gated (``act``: SwiGLU, ReGLU) experts over ``y [..., D]``: ``gate_w [D, E]``, ``w1``/``w3``
    ``[E, D, F]``, ``w2 [E, F, D]`` — or, with ``layer`` (traced index),
    the whole stacks ``[L, E, ..]``.  No capacity, no drop.  ``kernel``
    picks the grouped matmul (module docstring).

    Returns ``(out, record)``: ``out`` like ``y``; ``record`` int32 ``[3]``
    (``RECORD``): experts with at least one row, routed rows, the largest
    group — counted over the ``live`` tokens only (bool, ``y``'s leading
    shape; padding rows and idle slots are computed like any row but are
    nobody's traffic).  ``live=None`` counts every token.  ``choices`` adds
    a third result, the experts each token was routed to: int32 ``[..., k]``
    (what a comparison with a reference is teacher-forced with; ids among
    all ``E``).  ``held = (first, count)``: the weights are this chip's
    ``count`` experts and ``out`` their partial sum (module docstring); the
    record is ``RECORD_HELD``, its first three columns over the held
    experts.  ``score``: the router's (:func:`route`).  ``router_x``: the
    tokens the router reads, ``y``'s shape (``None``: ``y`` itself).
    ``bias`` / ``scale``: the router's selection bias and the factor on its
    chosen weights (:func:`route`).  ``routed``: the router's own OUTPUT,
    made by the caller — ``(weights float32 [T, k], experts int32 [T, k])``,
    what :func:`route` returns — in place of every router option above
    (``gate_w`` is then None and ``E`` the expert stacks'; no ``balance``).
    ``balance`` adds a last result, this layer's balance term (module
    docstring; float32 scalar, 1 under even routing).  ``choice_major``:
    the layout the combine gathers the pairs in — ``[k, T, D]``, what a
    differentiated layer wants on a TPU, for the serving programs'
    ``[T, k, D]``; the same float32 sum either way."""
    shape, d = y.shape, y.shape[-1]
    x = y.reshape(-1, d)
    t = x.shape[0]
    e = gate_w.shape[-1] if routed is None else w1.shape[-3]
    if not 1 <= k <= e:
        raise ValueError(f"top_k={k} outside [1, num_experts={e}]")
    if act not in ACTS:
        raise ValueError(f"expert activation {act!r}: one of {sorted(ACTS)}")

    with jax.named_scope("layer/moe/route"):
        if routed is None:
            rx = x if router_x is None else router_x.reshape(-1, d)
            top_p, top_e, *all_p = route(rx, gate_w, k, renormalize, score,
                                         scores=balance, bias=bias,
                                         scale=scale)
        else:
            if balance:
                raise ValueError("routed=: the caller's router has its own "
                                 "balance term")
            top_p, top_e = (a.reshape(t, k) for a in routed)
        flat_e = top_e.reshape(-1)                               # [T*k]
        if balance:
            share = jnp.zeros(e, jnp.float32).at[flat_e].add(1.0 / (t * k))
            aux = e * jnp.sum(jax.lax.stop_gradient(share)
                              * jnp.mean(all_p[0], axis=0))
        if held is not None:
            # this chip's experts as groups 0 .. count-1; a pair of any
            # other expert takes the key ``count`` and sorts behind them
            first, e = held
            flat_e = flat_e - first
            flat_e = jnp.where((flat_e >= 0) & (flat_e < e), flat_e, e)
        # stable: pairs of one expert keep token order (deterministic sums)
        order = jnp.argsort(flat_e, stable=True)
        hits = flat_e[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :]
        group_sizes = hits.sum(0, dtype=jnp.int32)               # [E]

    with jax.named_scope("layer/moe/gather"):
        xs = x[order // k]                                       # [T*k, D]

    with jax.named_scope("layer/moe/experts"):
        gate = _grouped(xs, w1, group_sizes, layer, kernel)
        up = _grouped(xs, w3, group_sizes, layer, kernel)
        out = _grouped(ACTS[act](gate) * up, w2, group_sizes, layer,
                       kernel)
        if held is not None:
            # the rows behind the held groups were multiplied with nothing
            # and hold whatever the kernel's output buffer held
            mine = jnp.arange(t * k, dtype=jnp.int32) < group_sizes.sum()
            out = jnp.where(mine[:, None], out, 0)

    with jax.named_scope("layer/moe/combine"):
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype))
        if choice_major:
            # the same float32 sum, written so that XLA folds the
            # widening into the reduction — a float32 [T, k, D] (0.5 GB a
            # layer at 8,192 tokens, top-6, d 2,560), and its cotangent,
            # are never whole — over pairs gathered CHOICE-major, [k, T, D]:
            # a [T, 6, D] view of [T * 6, D] is a relayout on a TPU (six
            # rows in an eight-row tile), forward and backward
            pairs = out[inverse.reshape(t, k).T]
            mixed = jnp.sum(top_p.T[:, :, None] * pairs.astype(jnp.float32),
                            axis=0).astype(y.dtype)
        else:
            pairs = out[inverse].reshape(t, k, d)
            mixed = jnp.einsum("tk,tkd->td", top_p,
                               pairs.astype(jnp.float32)).astype(y.dtype)

        if live is None:
            counts = group_sizes
        else:
            alive = jnp.repeat(live.reshape(-1), k)              # [T*k]
            counts = (hits & alive[:, None]).sum(0, dtype=jnp.int32)
        record = [(counts > 0).sum(dtype=jnp.int32),
                  counts.sum(dtype=jnp.int32), counts.max()]
        if held is not None:
            pairs_live = t * k if live is None \
                else k * live.sum(dtype=jnp.int32)
            record.append(pairs_live - record[1])
        record = jnp.stack(record)
    result = (mixed.reshape(shape), record)
    if choices:
        result += (top_e.reshape(shape[:-1] + (k,)),)
    return result + (aux,) if balance else result
