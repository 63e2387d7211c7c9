"""Bounded-divergence helpers for quantized serving — the ONE definition
of "close enough" for ``tests/unit/test_quant_serving.py``.

Quantized lanes (int8 KV, w8a8 weights) cannot promise the bit-exact
greedy parity the full-precision serving stack pins: int8 rounding can
flip a near-tie argmax, and greedy decoding then cascades — every token
after the first flip may differ while still being a perfectly valid
greedy continuation of the *quantized* model.  So the contract is two
measurements, neither of which a cascade can game:

 - **token match rate**: positionwise agreement over the whole trace
   (prompt + completion, prompt always matches).  Cascades hurt it, so a
   high rate is strong evidence; thresholds are set per-trace-length.
 - **max logit RMSE**: teacher-forced — both engines score the SAME
   input, so there is no cascade.  This bounds the actual numeric
   perturbation independent of argmax luck.

Not a test module (no ``test_`` prefix) — pytest imports it from the
tests' own directory; the bench inserts ``tests/unit`` on ``sys.path``.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def token_match_rate(ref: Dict[Any, np.ndarray],
                     got: Dict[Any, np.ndarray]) -> float:
    """Mean positionwise token agreement across a trace's result dicts
    (``uid -> int32 [prompt + completion]``, the ``serve()`` /
    ``generate`` output shape).  Requests average with equal weight so a
    single long cascade cannot hide behind many short exact requests."""
    if set(ref) != set(got):
        raise ValueError(f"uid sets differ: {set(ref) ^ set(got)}")
    rates = []
    for uid in ref:
        a, b = np.asarray(ref[uid]), np.asarray(got[uid])
        if a.shape != b.shape:
            raise ValueError(f"uid {uid}: shape {a.shape} vs {b.shape}")
        rates.append(float((a == b).mean()))
    return float(np.mean(rates))


def max_logit_rmse(ref_engine, quant_engine, prompts) -> float:
    """Teacher-forced logit error: both engines score the same token
    batches (one forward per prompt); returns the max over prompts of
    the per-prompt RMSE.  No generation, so quantization error is
    measured directly rather than through argmax cascades."""
    worst = 0.0
    for p in prompts:
        ids = np.asarray(p, np.int32)[None, :]
        la = np.asarray(ref_engine.forward({"input_ids": ids}),
                        np.float32)
        lb = np.asarray(quant_engine.forward({"input_ids": ids}),
                        np.float32)
        worst = max(worst, float(np.sqrt(np.mean((la - lb) ** 2))))
    return worst


def assert_bounded_divergence(ref: Dict[Any, np.ndarray],
                              got: Dict[Any, np.ndarray],
                              min_match: float,
                              label: str = "quantized lane") -> float:
    """Assert the trace-level token bound; returns the measured rate so
    callers can log it (the bench records it in the JSON)."""
    rate = token_match_rate(ref, got)
    assert rate >= min_match, (
        f"{label}: token match rate {rate:.3f} below the documented "
        f"bound {min_match}")
    return rate
