"""On-device sampling primitives for the serving engine (PR 20).

Everything here is shaped so the scheduler can thread *per-slot* sampling
state through the compiled decode/fused/verify programs as fixed-shape
operands — ``[slots]`` knob vectors, ``[slots]`` counter vectors, and an
optional ``[slots, vocab]`` logit mask — with **zero recompiles**: a slot
changing temperature, or a mixed greedy+sampled batch, only changes
operand *values*, never program shapes.

Greedy is the ``temperature == 0`` row of the SAME program:
:func:`filtered_logprobs` returns the exact one-hot (``0 / -inf``)
distribution at the (masked) argmax for those rows, so every downstream
draw — :func:`sample_tokens`, the rejection-sampler accept test, the
residual fallback — degenerates bit-exactly to argmax without a single
branch in the traced program.

**Counter-based PRNG.** Each request owns one integer ``seed``; the key
for any draw is ``fold_in(fold_in(PRNGKey(seed), salt), position)`` where
``position`` is the absolute emitted-token index.  Keys are therefore a
pure function of ``(seed, salt, position)`` — no mutable RNG state lives
anywhere — which is what makes crash re-homing and preemption replay
token-exact: the salvage path only needs to carry the request seed and
the emitted count (``docs/inference.md`` "Sampled decoding").  The salts
separate the independent sub-streams one emission position consumes:

========  ===========================================================
TOKEN     plain next-token draws (decode / fused decode / the prefill
          first-token emit)
ACCEPT    the rejection sampler's accept uniforms (verify program)
RESIDUAL  residual + bonus categorical draws (verify fallback row)
DRAFT     draft-model rollout draws (speculative propose program)
========  ===========================================================
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..utils.platform import interpret_kernels
from . import paged_kv
from .paged_kv import LANES

__all__ = [
    "SALT_TOKEN", "SALT_ACCEPT", "SALT_RESIDUAL", "SALT_DRAFT", "thresholds",
    "slot_keys", "grid_keys", "filtered_logprobs", "sample_tokens",
    "accept_uniforms", "token_probs", "residual_logits",
]

#: the vocabulary width from which the two searches run over a row tile kept
#: in VMEM (:func:`_tiled_search`) and not as plain loops over ``[rows,
#: vocab]``: between OLMoE's 50,304 (whose program stays as it was) and
#: Granite's 100,352.  The nucleus search alone on a v5e, ms, plain loop /
#: tiled (PR 67, ``benchmarks/nucleus_search_alone.py``): ``[24, 50272]``
#: 0.26 / 0.19, ``[64, 50304]`` 0.58 / 0.20, ``[64, 100352]`` 1.09 / 0.23,
#: ``[16, 151936]`` 0.46 / 0.19, ``[128, 262272]`` 5.40 / 1.06 (30 passes
#: over HBM: 4.92).  Inside ``filtered_logprobs`` XLA keeps a row set of
#: 10-26 MB resident by itself (whole function, plain / tiled: ``[64,
#: 100352]`` 0.40 / 0.47, ``[16, 151936]`` 0.37 / 0.36) and cannot at 134 MB
#: (6.98 / 2.64): the tile costs a wide vocabulary 0.07 ms where the loop
#: was resident and takes 4.3 ms where it was not — and a vocabulary says
#: nothing of the rows beside it, so the rule is the width's alone.
TILED_FROM = 65536


def thresholds(vocab: int) -> str:
    """How :func:`filtered_logprobs` finds its top-k / top-p thresholds at
    a vocabulary of ``vocab`` entries — what a serving program built on it
    reports (``ServingEngine.stats()["sampler"]``)."""
    return "bitwise_search_tiled" if vocab >= TILED_FROM else "bitwise_search"


SALT_TOKEN = 1
SALT_ACCEPT = 2
SALT_RESIDUAL = 3
SALT_DRAFT = 4


@jax.named_scope("sample/draw")
def slot_keys(seeds, counts, salt):
    """``[rows]`` PRNG keys: ``fold_in(fold_in(PRNGKey(seed), salt),
    count)`` per row — the whole counter-based scheme in one place."""
    def one(seed, count):
        key = jax.random.PRNGKey(seed)
        return jax.random.fold_in(jax.random.fold_in(key, salt), count)

    return jax.vmap(one)(jnp.asarray(seeds, jnp.uint32),
                         jnp.asarray(counts, jnp.int32))


def grid_keys(seeds, counts, salt, width):
    """``[rows, width]`` keys for window draws: row ``s`` position ``i``
    keys the emission index ``counts[s] + i`` (the verify/rollout window
    grid — position ``i`` of the window IS absolute count ``c + i``)."""
    offs = jnp.arange(int(width), dtype=jnp.int32)[None, :]
    cnts = (jnp.asarray(counts, jnp.int32)[:, None] + offs).reshape(-1)
    seeds2 = jnp.repeat(jnp.asarray(seeds, jnp.uint32), int(width))
    flat = slot_keys(seeds2, cnts, salt)
    return flat.reshape((-1, int(width)) + flat.shape[1:])


def _key_value(t):
    """The float32 whose order-preserving int32 key (:func:`_kth_largest`)
    is ``t``."""
    return jax.lax.bitcast_convert_type(
        jnp.where(t < 0, t ^ jnp.int32(0x7FFFFFFF), t), jnp.float32)


def _kth_largest(x, k):
    """``[rows, 1]`` float32: the ``k[row]``-th largest of each row of
    float32 ``x [rows, vocab]`` (``k`` int32 ``[rows, 1]``, ``1 <= k <=
    vocab``), exactly, without a sort.  Float32 bits map to int32 keys of
    the same order (``-0.0`` ties with ``0.0``, ``-inf`` orders lowest);
    the answer is the largest key ``t`` with ``count(key >= t) >= k``,
    built from the sign bit down: 32 fused compare-and-count passes over
    the row, whatever its values (``ops/decode_attention.py``'s
    ``_sparse_select_kernel`` selects the same way).  The keys are formed
    inside each pass, so no ``[rows, vocab]`` temporary is held."""
    def reaches(cand):
        z = jnp.where(x == 0.0, 0.0, x)
        u = jax.lax.bitcast_convert_type(z, jnp.int32)
        key = jnp.where(u < 0, u ^ jnp.int32(0x7FFFFFFF), u)
        return jnp.sum(key >= cand, axis=-1, keepdims=True,
                       dtype=jnp.int32) >= k

    t = jnp.where(reaches(jnp.int32(0)), jnp.int32(0),
                  jnp.iinfo(jnp.int32).min)

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(reaches(cand), cand, t)

    return _key_value(jax.lax.fori_loop(0, 31, bit, t))


def _nucleus_threshold(probs, p):
    """``[rows, 1]`` float32: the largest value ``t`` of each row of
    ``probs [rows, vocab]`` (float32, in ``[0, 1]``) whose upper set
    reaches the nucleus mass, ``sum(probs[probs >= t]) >= p`` (``p``
    float32 ``[rows, 1]``), or 0 where none does.  The bits of a
    non-negative float32 order as its value, so ``t`` is built bit by bit
    like :func:`_kth_largest`'s key, with a masked float32 sum for the
    count (a sum of non-negative terms in a fixed order never falls when
    a term is added, so the search is sound in floating point — and so
    for any fixed reduction tree, every rounded add being monotone in its
    operands: XLA's here, the tile's in :func:`_tiled_search`); bit 30
    would be a value of 2 or more and is never set: 30 passes."""
    def bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 29 - i)
        value = jax.lax.bitcast_convert_type(cand, jnp.float32)
        mass = jnp.sum(jnp.where(probs >= value, probs, 0.0), axis=-1,
                       keepdims=True)
        return jnp.where(mass >= p, cand, t)

    t = jax.lax.fori_loop(0, 30, bit,
                          jnp.zeros(probs.shape[:-1] + (1,), jnp.int32))
    return jax.lax.bitcast_convert_type(t, jnp.float32)


_TILE_ROWS = 16
_TILE_UNROLL = 8
_TILE_VMEM = 48 << 20


def _search_tile_kernel(x_ref, goal_ref, t_ref, *, unroll, top_k):
    """One tile of :func:`_tiled_search`: ``x_ref [tile, width]`` float32
    in VMEM, ``goal_ref`` / ``t_ref`` ``[tile, LANES]`` (every lane of a
    row the same).  A pass adds the 128-lane chunks' terms into ``unroll``
    ``[tile, LANES]`` float32 accumulators (chunk ``j`` into accumulator
    ``j % unroll``), adds those in order and reduces over the lanes once:
    a row's sum depends on its own sublane alone, so neither its place in
    the tile nor its neighbours move it.  The term is the entry at or
    above the candidate's value (the nucleus mass), or with ``top_k`` a
    one for it (a count, exact in float32 under 2**24 entries): a key
    orders as its float, so the entries are compared as floats, and a
    candidate under ``-inf``'s key (no float: a NaN's bits) has every
    entry above it."""
    rows, width = x_ref.shape
    span = unroll * LANES
    groups, tail = divmod(width // LANES, unroll)
    goal = goal_ref[...]

    def reaches(cand):
        if top_k:
            value = _key_value(cand)
            value = jnp.where((cand < 0) & (value != value), -jnp.inf, value)
        else:
            value = jax.lax.bitcast_convert_type(cand, jnp.float32)

        def term(x):
            return jnp.where(x >= value, 1.0 if top_k else x, 0.0)

        def group(g, accs):
            slab = x_ref[:, pl.ds(pl.multiple_of(g * span, span), span)]
            return tuple(a + term(slab[:, u * LANES:(u + 1) * LANES])
                         for u, a in enumerate(accs))

        accs = [jnp.zeros((rows, LANES), jnp.float32)] * unroll
        if groups:
            accs = list(jax.lax.fori_loop(0, groups, group, tuple(accs)))
        for u in range(tail):
            start = groups * span + u * LANES
            accs[u] = accs[u] + term(x_ref[:, start:start + LANES])
        acc = functools.reduce(jnp.add, accs)
        return jnp.sum(acc, axis=-1, keepdims=True) >= goal

    bits = 31 if top_k else 30      # a probability's bit 30 is never set

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(reaches(cand), cand, t)

    t = jnp.zeros((rows, LANES), jnp.int32)
    if top_k:                       # the sign first: bit 31 is no magnitude
        t = jnp.where(reaches(t), t, jnp.iinfo(jnp.int32).min)
    t_ref[...] = jax.lax.fori_loop(0, bits, bit, t)


def _tiled_search(x, goal, *, top_k, tile=_TILE_ROWS, unroll=_TILE_UNROLL,
                  interpret=None):
    """``[rows, 1]`` int32: the bits of :func:`_nucleus_threshold`'s or
    (``top_k``) the key of :func:`_kth_largest`'s answer, found with the
    row tile resident: a Pallas kernel over tiles of ``tile`` rows whose
    passes read the ``[tile, vocab]`` block from VMEM, so ``x`` crosses HBM
    once.  Rows pad to a whole tile and the vocabulary to whole lanes with
    what no answer feels: zeros under a goal of 1 (a zero adds nothing to
    a mass), ``-inf`` for ``top_k`` (``k <= vocab`` never reaches a
    pad)."""
    if interpret is None:
        interpret = interpret_kernels()
    rows, vocab = x.shape
    padded = jnp.pad(x, ((0, -rows % tile), (0, -vocab % LANES)),
                     constant_values=-jnp.inf if top_k else 0.0)
    r, v = padded.shape
    goal = jnp.pad(goal.astype(jnp.float32), ((0, r - rows), (0, 0)),
                   constant_values=1.0)
    lanes = pl.BlockSpec((tile, LANES), lambda i: (i, 0))
    kernel = functools.partial(_search_tile_kernel, unroll=unroll,
                               top_k=top_k)
    launch = dict(
        grid=(r // tile,),
        in_specs=[pl.BlockSpec((tile, v), lambda i: (i, 0)), lanes],
        out_specs=lanes,
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_TILE_VMEM),
        interpret=interpret)
    # (a site each: a trace names a kernel by the constant at its site)
    call = pl.pallas_call(kernel, name="kth_search", **launch) if top_k \
        else pl.pallas_call(kernel, name="nucleus_search", **launch)
    if paged_kv.tp_mesh() is not None:
        # a program over several chips (the serving engine's tp context):
        # Mosaic partitions no kernel, so every chip searches every row
        call = jax.shard_map(call, mesh=paged_kv.tp_mesh(),
                             in_specs=(P(), P()), out_specs=P(),
                             check_vma=False)
    return call(padded, jnp.broadcast_to(goal, (r, LANES)))[:rows, :1]


def _kth_largest_tiled(x, k, **tiling):
    """:func:`_kth_largest` with the row tile resident (the same 32
    passes, the count a float32)."""
    return _key_value(_tiled_search(x, k, top_k=True, **tiling))


def _nucleus_threshold_tiled(probs, p, **tiling):
    """:func:`_nucleus_threshold` with the row tile resident (the same 30
    passes, the same test; the masked sum in the tile's order)."""
    return jax.lax.bitcast_convert_type(
        _tiled_search(probs, p, top_k=False, **tiling), jnp.float32)


#: the two searches by what :func:`thresholds` says of a width
_SEARCHES = {
    "bitwise_search": (_kth_largest, _nucleus_threshold),
    "bitwise_search_tiled": (_kth_largest_tiled, _nucleus_threshold_tiled)}


def filtered_logprobs(logits, temps, top_k, top_p, masks=None):
    """Per-row log-probs of the filtered sampling distribution.

    ``[rows, vocab]`` logits + ``[rows]`` knobs -> ``(greedy [rows] i32,
    logprobs [rows, vocab] f32)``.  The pipeline per row: apply the bool
    logit mask (``-inf`` outside it; an all-False row is treated as
    unmasked rather than poisoning the softmax), scale by ``1/temp``,
    keep the top-k by kth-largest threshold (``top_k == 0`` = off; ties
    at the threshold stay in), then nucleus-filter at ``top_p`` over the
    renormalized top-k distribution (``top_p == 1`` = off; the token
    that crosses the boundary stays in).  Rows with ``temps == 0``
    return the exact one-hot (``0 / -inf``) at the masked argmax —
    the greedy row of the same traced program.

    Both thresholds are found by a bitwise search over the row
    (:func:`_kth_largest`, :func:`_nucleus_threshold`), not by sorting
    the vocabulary, and only when a sampled row asks: each search sits
    under a ``lax.cond`` on the knob operands, so a batch with no
    ``top_k`` set (or no filter at all) skips it at run time, in the one
    compiled program.  Where the passes read from is decided by the
    vocabulary width alone (:func:`thresholds`): the plain loops under
    ``TILED_FROM`` entries a row, the same searches over a row tile kept
    in VMEM (:func:`_tiled_search`) from there on — the same in every
    program of an engine, whatever its rows.  The kept sets are the
    sorted formulation's — a token stays iff the mass of strictly larger
    probabilities is below ``top_p`` — in float32 throughout; the
    nucleus mass is summed in the reduction's order rather than a sorted
    cumsum's, so a token whose boundary mass lies within float32 rounding
    of ``top_p`` may fall on the other side.  Ties, the
    boundary-crossing token, ``top_p == 1`` and the one-hot greedy rows
    are exact."""
    # every pass over the ``[rows, vocab]`` logits under its own scope
    # (telemetry/scopes.py): every caller's program gets them
    with jax.named_scope("sample/filter"):
        logits = logits.astype(jnp.float32)
        rows, vocab = logits.shape
        if masks is not None:
            ok = jnp.any(masks, axis=-1, keepdims=True)
            logits = jnp.where(jnp.where(ok, masks, True), logits, -jnp.inf)
    with jax.named_scope("sample/argmax"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    with jax.named_scope("sample/filter"):
        temps = jnp.asarray(temps, jnp.float32)[:, None]
        scaled = logits / jnp.maximum(temps, 1e-6)
        k = jnp.asarray(top_k, jnp.int32)[:, None]
        p = jnp.asarray(top_p, jnp.float32)[:, None]
        k_on = (temps > 0) & (k > 0) & (k < vocab)
        p_on = (temps > 0) & (p < 1)
        kth_largest, nucleus_threshold = _SEARCHES[thresholds(vocab)]
        # a row that does not ask searches for its minimum: everything stays
        kth = jax.lax.cond(
            jnp.any(k_on),
            lambda: kth_largest(scaled, jnp.where(k_on, k, vocab)),
            lambda: jnp.full((rows, 1), -jnp.inf))
        keep = scaled >= kth
    with jax.named_scope("sample/softmax"):
        probs = jax.nn.softmax(jnp.where(keep, scaled, -jnp.inf), axis=-1)
    with jax.named_scope("sample/filter"):
        thr = jax.lax.cond(jnp.any(p_on),
                           lambda: nucleus_threshold(probs, p),
                           lambda: jnp.zeros((rows, 1)))
        keep = keep & (probs >= jnp.where(p_on, thr, 0.0))
    with jax.named_scope("sample/softmax"):
        logprobs = jax.nn.log_softmax(jnp.where(keep, scaled, -jnp.inf),
                                      axis=-1)
    with jax.named_scope("sample/filter"):
        onehot = jnp.where(
            jnp.arange(vocab)[None, :] == greedy[:, None], 0.0, -jnp.inf)
        return greedy, jnp.where(temps > 0, logprobs, onehot)


@jax.named_scope("sample/draw")
def sample_tokens(logprobs, keys):
    """One categorical draw per row (``[rows, vocab]`` log-probs +
    ``[rows]`` keys -> ``[rows]`` i32).  One-hot rows (greedy /
    degenerate residual) come out deterministic — the single finite
    entry wins every Gumbel race."""
    return jax.vmap(jax.random.categorical)(keys, logprobs) \
        .astype(jnp.int32)


@jax.named_scope("sample/draw")
def accept_uniforms(keys):
    """One ``U[0, 1)`` per key (any leading shape).  ``u < p(token)``
    against a one-hot row is exact: ``p`` is exactly 1.0 or 0.0, so the
    test never depends on ``u`` for greedy rows."""
    flat = keys.reshape((-1,) + keys.shape[-1:])
    u = jax.vmap(lambda k: jax.random.uniform(k))(flat)
    return u.reshape(keys.shape[:-1])


def token_probs(logprobs, tokens):
    """``p(token)`` per row under the filtered distribution (``exp`` of
    the gathered log-prob: exactly 1.0 / 0.0 on one-hot rows)."""
    lp = jnp.take_along_axis(
        logprobs, tokens.astype(jnp.int32)[:, None], axis=-1)[:, 0]
    return jnp.exp(lp)


@jax.named_scope("sample/filter")
def residual_logits(logprobs, tokens):
    """Rejection-sampler residual per row: the filtered distribution
    with the rejected ``token`` removed (categorical renormalizes, so
    raw ``-inf``-masked log-probs suffice).  A row with nothing left
    (a temp=0 row whose draft WAS the argmax — only reachable when the
    accept test already passed) falls back to the one-hot argmax so the
    unused lane stays NaN-free."""
    vocab = logprobs.shape[-1]
    idx = jnp.arange(vocab)[None, :]
    resid = jnp.where(idx == tokens[:, None].astype(jnp.int32),
                      -jnp.inf, logprobs)
    dead = ~jnp.any(jnp.isfinite(resid), axis=-1, keepdims=True)
    onehot = jnp.where(idx == jnp.argmax(logprobs, axis=-1)[:, None],
                       0.0, -jnp.inf)
    return jnp.where(dead, onehot, resid)
