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

import jax
import jax.numpy as jnp

__all__ = [
    "SALT_TOKEN", "SALT_ACCEPT", "SALT_RESIDUAL", "SALT_DRAFT", "THRESHOLDS",
    "slot_keys", "grid_keys", "filtered_logprobs", "sample_tokens",
    "accept_uniforms", "token_probs", "residual_logits",
]

#: how :func:`filtered_logprobs` finds its top-k / top-p thresholds — what a
#: serving program built on it reports (``ServingEngine.stats()["sampler"]``)
THRESHOLDS = "bitwise_search"

SALT_TOKEN = 1
SALT_ACCEPT = 2
SALT_RESIDUAL = 3
SALT_DRAFT = 4


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

    t = jax.lax.fori_loop(0, 31, bit, t)
    return jax.lax.bitcast_convert_type(
        jnp.where(t < 0, t ^ jnp.int32(0x7FFFFFFF), t), jnp.float32)


def _nucleus_threshold(probs, p):
    """``[rows, 1]`` float32: the largest value ``t`` of each row of
    ``probs [rows, vocab]`` (float32, in ``[0, 1]``) whose upper set
    reaches the nucleus mass, ``sum(probs[probs >= t]) >= p`` (``p``
    float32 ``[rows, 1]``), or 0 where none does.  The bits of a
    non-negative float32 order as its value, so ``t`` is built bit by bit
    like :func:`_kth_largest`'s key, with a masked float32 sum for the
    count (a sum of non-negative terms in a fixed order never falls when
    a term is added, so the search is sound in floating point); bit 30
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
    compiled program.  The kept sets are the sorted formulation's —
    a token stays iff the mass of strictly larger probabilities is below
    ``top_p`` — in float32 throughout; the nucleus mass is summed in the
    reduction's order rather than a sorted cumsum's, so a token whose
    boundary mass lies within float32 rounding of ``top_p`` may fall on
    the other side.  Ties, the boundary-crossing token, ``top_p == 1``
    and the one-hot greedy rows are exact."""
    logits = logits.astype(jnp.float32)
    rows, vocab = logits.shape
    if masks is not None:
        ok = jnp.any(masks, axis=-1, keepdims=True)
        logits = jnp.where(jnp.where(ok, masks, True), logits, -jnp.inf)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temps = jnp.asarray(temps, jnp.float32)[:, None]
    scaled = logits / jnp.maximum(temps, 1e-6)
    k = jnp.asarray(top_k, jnp.int32)[:, None]
    p = jnp.asarray(top_p, jnp.float32)[:, None]
    k_on = (temps > 0) & (k > 0) & (k < vocab)
    p_on = (temps > 0) & (p < 1)
    # a row that does not ask searches for its minimum: everything stays
    kth = jax.lax.cond(
        jnp.any(k_on),
        lambda: _kth_largest(scaled, jnp.where(k_on, k, vocab)),
        lambda: jnp.full((rows, 1), -jnp.inf))
    keep = scaled >= kth
    probs = jax.nn.softmax(jnp.where(keep, scaled, -jnp.inf), axis=-1)
    thr = jax.lax.cond(jnp.any(p_on),
                       lambda: _nucleus_threshold(probs, p),
                       lambda: jnp.zeros((rows, 1)))
    keep = keep & (probs >= jnp.where(p_on, thr, 0.0))
    logprobs = jax.nn.log_softmax(jnp.where(keep, scaled, -jnp.inf),
                                  axis=-1)
    onehot = jnp.where(
        jnp.arange(vocab)[None, :] == greedy[:, None], 0.0, -jnp.inf)
    return greedy, jnp.where(temps > 0, logprobs, onehot)


def sample_tokens(logprobs, keys):
    """One categorical draw per row (``[rows, vocab]`` log-probs +
    ``[rows]`` keys -> ``[rows]`` i32).  One-hot rows (greedy /
    degenerate residual) come out deterministic — the single finite
    entry wins every Gumbel race."""
    return jax.vmap(jax.random.categorical)(keys, logprobs) \
        .astype(jnp.int32)


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
