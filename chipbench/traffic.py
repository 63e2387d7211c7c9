"""The one general traffic generator.  A traffic mix is a data file under
``chipbench/traffic/``; everything here is driven by its parameters and
``--seed``, so a later cell is a new data file and no new code.

Every seed gets the SAME sizes in the SAME cyclic order, entered at another
point.  Lengths are not sampled: a mix names a distribution and a deck size
``n``; the deck holds the distribution's ``n`` quantile midpoints, laid out
in a low-discrepancy order (position ``k`` holds prompt quantile
``k * s1 mod n`` and output quantile ``k * s2 mod n``, two strides near
``0.618 n`` and ``0.382 n`` coprime with ``n``), so that ANY run of
consecutive requests holds short and long prompts and outputs in nearly the
deck's own proportions.  The seed picks where in the cycle a run starts,
fills in the token ids and draws the per-request sampling seeds.  A window
that ends after 40-odd requests has then done the same work whatever the
seed (a seeded shuffle left 2.5 % between seeds against 0.1 % between two
runs of one seed: my chip runs, PR 24).  (The idea of seeded length draws
is ``benchmarks/serving_bench.py``'s; nothing else of it is used.)
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np


def quantile_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """The ``n`` quantile midpoints of a length distribution, as whole
    tokens.  ``spec``: ``{"dist": "loguniform"|"uniform", "lo", "hi"}`` or
    ``{"dist": "fixed", "value"}``."""
    dist = spec["dist"]
    if dist == "fixed":
        return [int(spec["value"])] * n
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if not 0 < lo <= hi:
        raise ValueError(f"bad length range in {spec}")
    qs = [(i + 0.5) / n for i in range(n)]
    if dist == "loguniform":
        vals = [math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
                for q in qs]
    elif dist == "uniform":
        vals = [lo + q * (hi - lo) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return [max(1, int(round(v))) for v in vals]


def _coprime_stride(n: int, ratio: float, avoid: int = 0) -> int:
    """A stride near ``ratio * n`` that is coprime with ``n`` (so that
    ``k -> k * stride mod n`` visits every index once) and is not
    ``avoid``."""
    s = max(1, int(n * ratio))
    while math.gcd(s, n) != 1 or s == avoid:
        s += 1
    return s


def length_deck(mix: Dict[str, Any]) -> List[Tuple[int, int]]:
    """The mix's fixed cycle of (prompt_len, output_len) pairs."""
    n = int(mix["deck"])
    prompts = quantile_lengths(mix["prompt_tokens"], n)
    outputs = quantile_lengths(mix["output_tokens"], n)
    s1 = _coprime_stride(n, 0.6180339887)
    s2 = _coprime_stride(n, 0.3819660113, avoid=s1)
    return [(prompts[(k * s1) % n], outputs[(k * s2) % n])
            for k in range(n)]


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """``--seed`` is any whole number up to a little over 2**31."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return np.random.SeedSequence(int(seed))


class RequestStream:
    """An endless, seeded stream of request parameters: the mix's deck,
    cycled from a seeded starting point."""

    def __init__(self, mix: Dict[str, Any], vocab: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab)
        self._rng = np.random.default_rng(seed_sequence(seed))
        self._deck = length_deck(mix)
        self._at = int(self._rng.integers(len(self._deck)))
        self._shared = self._rng.integers(
            0, self.vocab, int(mix.get("shared_prefix_tokens", 0)))
        self.issued = 0

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return self

    def __next__(self) -> Dict[str, Any]:
        plen, olen = self._deck[self._at]
        self._at = (self._at + 1) % len(self._deck)
        n_shared = min(len(self._shared), plen - 1)
        own = self._rng.integers(0, self.vocab, plen - n_shared)
        prompt = np.concatenate([self._shared[:n_shared], own]).astype(
            np.int32)
        samp = dict(self.mix.get("sampling") or {})
        if samp.get("temperature", 0.0) > 0:
            samp["seed"] = int(self._rng.integers(0, 2 ** 31))
        self.issued += 1
        return {"uid": f"r{self.issued}", "prompt": prompt,
                "max_new_tokens": int(olen), **samp}

    def warm_in_fractions(self, clients: int) -> List[float]:
        """Where in its first request each client starts the window: the
        ``clients`` midpoints of (0, 1] in a fixed scattered order, so that
        clients are out of step by the same amounts in every run."""
        stride = _coprime_stride(clients, 0.6180339887)
        return [((k * stride) % clients + 0.5) / clients
                for k in range(clients)]


def token_batches(seed: int, vocab: int, rows: int, cols: int
                  ) -> Iterator[np.ndarray]:
    """Endless seeded ``[rows, cols]`` int32 batches of uniform token ids
    (training data made on the host, inside the window)."""
    rng = np.random.default_rng(seed_sequence(seed))
    while True:
        yield rng.integers(0, vocab, (rows, cols), dtype=np.int32)
