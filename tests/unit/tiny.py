"""What the serving tests share, so that the suite builds it once: the check
of greedy tokens against a reference without rolling the reference out, and
(``conftest.py`` beside this file) the tiny models and their engines.

Not a test module (no ``test_`` prefix) — pytest imports it from the tests'
own directory, as it does ``quant_divergence.py``.
"""

from __future__ import annotations

import weakref
from typing import Callable, Dict, Sequence

import numpy as np

#: sequences are padded to a multiple of this, so that requests of many
#: lengths cost the reference few shapes (a shape is a compile)
_PAD = 32


def assert_greedy(logits_of: Callable, reqs: Sequence, out) -> None:
    """``out`` (``serve()``'s: uid -> prompt + generated tokens) holds, for
    every request of ``reqs``, all the tokens it asked for, and each is the
    one ``logits_of`` puts first after the tokens before it.
    ``logits_of(int32 [B, S]) -> [B, S, V]``, a causal reference, is called
    ONCE, teacher-forced, over every prompt + output, zero-padded on the
    right to one length (a position's logits depend on nothing after it).
    By induction from the prompt the outputs are then the reference's own
    greedy roll-out — the same guarantee from one call at one shape, where
    a roll-out compiles the reference once a length.  (At the first
    mismatch the reference disagrees; what follows it is conditioned on a
    token the reference did not choose.)"""
    sequences = [np.asarray(out[r.uid]) for r in reqs]
    for r, s in zip(reqs, sequences):
        assert len(s) == len(r.prompt) + r.max_new_tokens, r.uid
    width = -(-max(len(s) for s in sequences) // _PAD) * _PAD
    ids = np.zeros((len(sequences), width), np.int32)
    for row, s in zip(ids, sequences):
        row[:len(s)] = s
    logits = np.asarray(logits_of(ids))
    for i, (r, s) in enumerate(zip(reqs, sequences)):
        p = len(r.prompt)
        np.testing.assert_array_equal(
            s[p:], logits[i, p - 1:len(s) - 1].argmax(-1),
            err_msg=f"uid {r.uid}")


#: engine -> {(prompt, tokens asked for, eos): tokens}
_SEQUENTIAL: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
#: ``generate`` is asked for a multiple of this many tokens
_ASK = 16


def sequential(engine, reqs: Sequence, eos_token_id=None) -> Dict:
    """uid -> ``engine.generate``'s greedy tokens for each request alone:
    what the parity tests hold a served trace to.  ``generate`` compiles a
    program a (prompt length, max_new_tokens), so it is asked for the next
    multiple of ``_ASK`` tokens (as far as the context has room) and the
    request's own number is cut from the front — greedy decoding is the
    same program step after step, so its first n tokens do not depend on
    how many were asked for (``test_harness.py`` holds ``generate`` to
    that) — and what an engine generated is kept with the engine: traces
    of many lengths share a few programs, and a trace that several tests
    replay is generated once."""
    kept = _SEQUENTIAL.setdefault(engine, {})
    room = (engine.module.decode_hooks or {}).get("max_seq_len")
    out = {}
    for r in reqs:
        prompt = np.asarray(r.prompt)
        ask = -(-r.max_new_tokens // _ASK) * _ASK
        if room is not None:
            ask = max(min(ask, room - len(prompt)), r.max_new_tokens)
        key = (prompt.tobytes(), str(prompt.dtype), ask, eos_token_id)
        if key not in kept:
            kept[key] = engine.generate(prompt[None, :], max_new_tokens=ask,
                                        eos_token_id=eos_token_id)[0]
        out[r.uid] = kept[key][:len(prompt) + r.max_new_tokens].copy()
    return out


def assert_sequential(engine, reqs: Sequence, *served,
                      eos_token_id=None) -> None:
    """Each of ``served`` (results of ``serve()``) holds, for every request
    of ``reqs``, exactly :func:`sequential`'s tokens."""
    want = sequential(engine, reqs, eos_token_id)
    for n, res in enumerate(served):
        for r in reqs:
            np.testing.assert_array_equal(
                res[r.uid], want[r.uid], err_msg=f"result {n} uid {r.uid}")
