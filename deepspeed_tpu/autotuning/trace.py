"""Replayable serving traces: the workload half of the serving autotuner.

A tuner is only as honest as its workload.  The reference framework
replays *training* steps (one batch looks like the next); serving has no
such luxury — throughput depends on the *trace*: prompt lengths, decode
budgets, arrival order, and above all the session/prefix structure that
decides what the prefix cache and the host tier can reuse.  This module
makes that workload a first-class, replayable artifact:

 - :class:`ServingTrace`: a JSON-able trace whose prompts are
   **deterministic functions of seeds** — a session's shared prefix is
   drawn from ``rng([seed, _SESSION_SALT, session])`` and each request's
   unique tail from ``rng([seed, _TAIL_SALT, index])`` — so a trace file
   is a few hundred bytes yet materializes the exact same token arrays on
   every machine, forever.  Entries keep arrival order, per-request
   decode budgets, ``slo_class``/``priority``, and the session id that
   encodes the prefix-sharing structure.  Recorded (non-synthetic)
   entries may instead carry their literal tokens.
 - :class:`TraceRecorder`: attaches to a live :class:`~deepspeed_tpu
   .inference.serving.ServingEngine` or :class:`~deepspeed_tpu.serving
   .ReplicaRouter` via the ``_submit_observer`` hook and captures every
   ``submit()`` (verbatim tokens, budgets, SLO class, arrival order) into
   a replayable trace — record production traffic once, tune against it
   offline.
 - :func:`fit_trace`: builds a *synthetic* trace from a telemetry
   snapshot (``engine.metrics.snapshot()`` — the PR 8 registry): mean
   prompt/decode lengths from the token counters, the SLO-class mix from
   ``serving_slo_requests_total``, and the session structure
   (``sessions``, ``prefix_len``) fitted against the observed
   prefix-cache hit rate — for fleets where recording raw tokens is not
   an option, the scrape you already have is enough to tune against.

``ServingTrace.slice(n)`` is the successive-halving budget unit: the
first ``n`` entries in arrival order, session structure intact.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["TraceEntry", "ServingTrace", "TraceRecorder", "fit_trace",
           "sessions_trace"]

#: Format v2 (PR 20) adds per-request sampling params (``temperature``,
#: ``top_k``, ``top_p``, ``seed``).  Absent fields default to greedy
#: (``temperature=0``), so every committed v1 trace loads unchanged and
#: replays byte-identically — the defaults ARE the v1 semantics.
TRACE_VERSION = 2

# rng stream salts: sessions and tails must never collide even when a
# session id equals an entry index
_SESSION_SALT = 7919
_TAIL_SALT = 104729


@dataclasses.dataclass
class TraceEntry:
    """One request in the trace (arrival order = list order).

    Synthetic entries describe their prompt (``session``/``tail_len`` or
    a sessionless ``prompt_len``); recorded entries carry ``tokens``
    verbatim and ignore the synthetic fields.
    """
    uid: Any
    max_new_tokens: int
    session: Optional[int] = None     # shared-prefix group; None = no prefix
    tail_len: int = 0                 # unique tokens after the prefix
    prompt_len: int = 0               # sessionless synthetic prompt length
    slo_class: Optional[str] = None
    priority: int = 0
    eos_token_id: Optional[int] = None   # submit-time eos (early stop)
    tokens: Optional[List[int]] = None   # recorded verbatim prompt
    # v2 sampling params — defaults are exactly the greedy v1 semantics
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0                        # per-request sampling seed

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        for k in ("session", "slo_class", "eos_token_id",
                  "tokens"):                           # None = default
            if d[k] is None:
                del d[k]
        for k in ("tail_len", "prompt_len", "priority",
                  "temperature", "top_k", "seed"):         # 0 = default
            if not d[k]:
                del d[k]
        if d["top_p"] == 1.0:                          # 1.0 = default
            del d["top_p"]
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TraceEntry":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)
                      if f.name in d})


class ServingTrace:
    """A replayable request trace (module docstring).

    Parameters
    ----------
    vocab:      token-id range for synthetic prompts (``[0, vocab)``).
    seed:       root seed every synthetic prompt derives from.
    prefix_len: shared-prefix length of every session (tokens).
    entries:    arrival-ordered :class:`TraceEntry` list.
    meta:       free-form provenance (recorded-from, fitted-from, ...).
    """

    def __init__(self, *, vocab: int, seed: int = 0, prefix_len: int = 0,
                 entries: Optional[Sequence[TraceEntry]] = None,
                 meta: Optional[Dict[str, Any]] = None):
        self.vocab = int(vocab)
        if self.vocab < 1:
            raise ValueError(f"vocab must be >= 1, got {vocab}")
        self.seed = int(seed)
        self.prefix_len = int(prefix_len)
        self.entries: List[TraceEntry] = list(entries or [])
        self.meta: Dict[str, Any] = dict(meta or {})
        uids = [e.uid for e in self.entries]
        if len(set(uids)) != len(uids):
            raise ValueError("duplicate uids in trace entries")

    # ------------------------------------------------------------ shape
    def __len__(self) -> int:
        return len(self.entries)

    @property
    def sessions(self) -> int:
        """Distinct session (shared-prefix) groups referenced."""
        return len({e.session for e in self.entries
                    if e.session is not None})

    def slice(self, n: int) -> "ServingTrace":
        """The replay-budget unit: the first ``n`` entries in arrival
        order (session structure intact — request ``i`` still returns to
        its session)."""
        return ServingTrace(vocab=self.vocab, seed=self.seed,
                            prefix_len=self.prefix_len,
                            entries=self.entries[: int(n)],
                            meta=self.meta)

    def max_total_len(self) -> int:
        """Largest prompt + completion over the trace — what
        ``max_seq_len`` must cover."""
        longest = 0
        for e in self.entries:
            if e.tokens is not None:
                plen = len(e.tokens)
            elif e.session is not None:
                plen = self.prefix_len + e.tail_len
            else:
                plen = e.prompt_len
            longest = max(longest, plen + int(e.max_new_tokens))
        return longest

    def working_set_tokens(self) -> int:
        """Unique KV tokens the whole trace touches — each session
        prefix counted ONCE, plus every unique tail and completion
        (recorded entries count their full prompt; shared structure is
        not recoverable from verbatim tokens without re-hashing).  This
        is the pool-pressure sizing unit: a device pool at a fraction of
        it forces eviction/preemption/tiering."""
        toks = self.sessions * self.prefix_len
        for e in self.entries:
            if e.tokens is not None:
                toks += len(e.tokens) + int(e.max_new_tokens)
            elif e.session is not None:
                toks += int(e.tail_len) + int(e.max_new_tokens)
            else:
                toks += int(e.prompt_len) + int(e.max_new_tokens)
        return toks

    # ------------------------------------------------------- materialize
    def _prefix_tokens(self, session: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, _SESSION_SALT, int(session)])
        return rng.integers(0, self.vocab, self.prefix_len, dtype=np.int64)

    def prompt_for(self, index: int) -> np.ndarray:
        """Deterministic int32 prompt for entry ``index`` (same tokens on
        every call, every process — the replay-determinism contract)."""
        e = self.entries[index]
        if e.tokens is not None:
            return np.asarray(e.tokens, np.int32)
        rng = np.random.default_rng([self.seed, _TAIL_SALT, int(index)])
        if e.session is not None:
            tail = rng.integers(0, self.vocab, int(e.tail_len),
                                dtype=np.int64)
            return np.concatenate(
                [self._prefix_tokens(e.session), tail]).astype(np.int32)
        return rng.integers(0, self.vocab, int(e.prompt_len),
                            dtype=np.int64).astype(np.int32)

    def requests(self):
        """Materialize ``[(Request, TraceEntry), ...]`` in arrival order
        (import is local: trace files must load in jax-free tooling)."""
        from ..inference.serving import Request

        return [(Request(uid=e.uid, prompt=self.prompt_for(i),
                         max_new_tokens=e.max_new_tokens,
                         temperature=e.temperature, top_k=e.top_k,
                         top_p=e.top_p, seed=e.seed), e)
                for i, e in enumerate(self.entries)]

    def submit_all(self, target, eos_token_id=None) -> list:
        """Replay the arrival order into ``target`` (engine or router)
        ``submit()``; returns the handles.  A recorded per-entry
        ``eos_token_id`` wins over the call-level default — replay must
        stop early exactly where the recorded traffic did."""
        return [target.submit(req, priority=e.priority,
                              slo_class=e.slo_class,
                              eos_token_id=e.eos_token_id
                              if e.eos_token_id is not None
                              else eos_token_id)
                for req, e in self.requests()]

    # ---------------------------------------------------------- persist
    def to_dict(self) -> Dict[str, Any]:
        return {"version": TRACE_VERSION, "vocab": self.vocab,
                "seed": self.seed, "prefix_len": self.prefix_len,
                "meta": self.meta,
                "entries": [e.to_dict() for e in self.entries]}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ServingTrace":
        if int(d.get("version", 1)) > TRACE_VERSION:
            raise ValueError(
                f"trace version {d['version']} is newer than this "
                f"reader ({TRACE_VERSION})")
        return cls(vocab=d["vocab"], seed=d.get("seed", 0),
                   prefix_len=d.get("prefix_len", 0),
                   entries=[TraceEntry.from_dict(e)
                            for e in d.get("entries", [])],
                   meta=d.get("meta"))

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, default=str)
        return path

    @classmethod
    def load(cls, path: str) -> "ServingTrace":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def sessions_trace(n_requests: int, *, vocab: int, seed: int = 0,
                   sessions: int = 0, prefix_len: int = 0,
                   tail_range: Tuple[int, int] = (16, 64),
                   new_range: Tuple[int, int] = (8, 32),
                   slo_classes: Optional[Sequence[Optional[str]]] = None,
                   temperature: float = 0.0, top_k: int = 0,
                   top_p: float = 1.0) -> ServingTrace:
    """A returning-session workload as a :class:`ServingTrace`:
    ``sessions`` distinct shared prefixes dealt round-robin (request ``i``
    returns to session ``i % sessions`` with a fresh tail), per-request
    tail/decode budgets drawn deterministically from ``seed``.
    ``sessions=0`` produces a sessionless mixed trace with prompt lengths
    in ``tail_range``.  ``temperature > 0`` makes every request sampled
    with the given params and a per-request sampling seed drawn from the
    SAME deterministic stream — the trace file fully determines the
    sampled token streams (the engine's counter-based PRNG is keyed only
    by request seed + emission position)."""
    rng = np.random.default_rng([int(seed), 39916801])
    classes = list(slo_classes or [None])
    entries = []
    for i in range(int(n_requests)):
        tail = int(rng.integers(tail_range[0], tail_range[1] + 1))
        mnew = int(rng.integers(new_range[0], new_range[1] + 1))
        samp_seed = int(rng.integers(1, 2 ** 31 - 1)) \
            if float(temperature) > 0 else 0
        entries.append(TraceEntry(
            uid=i, max_new_tokens=mnew,
            session=(i % sessions) if sessions else None,
            tail_len=tail if sessions else 0,
            prompt_len=0 if sessions else tail,
            slo_class=classes[i % len(classes)],
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p), seed=samp_seed))
    return ServingTrace(vocab=vocab, seed=seed,
                        prefix_len=prefix_len if sessions else 0,
                        entries=entries,
                        meta={"generator": "sessions_trace",
                              "sessions": int(sessions),
                              "tail_range": list(tail_range),
                              "new_range": list(new_range),
                              "temperature": float(temperature)})


class TraceRecorder:
    """Capture a replayable trace from a live engine or router.

    ``attach(target)`` installs this recorder as the target's
    ``_submit_observer``; every subsequent ``submit()`` appends one
    verbatim-token entry in arrival order.  ``trace()`` snapshots the
    recording; ``detach()`` removes the hook.  One recorder per target
    (attaching over a foreign observer raises — silently dropping
    someone else's recording would be worse than failing) — unless
    ``chain=True``, which wraps the prior observer instead: the
    incumbent keeps seeing every submit FIRST, this recorder second,
    and ``detach()`` restores the incumbent (the incident recorder's
    always-on capture must not evict a user's own recording)."""

    def __init__(self, vocab: int):
        self.vocab = int(vocab)
        self.entries: List[TraceEntry] = []
        #: (target, previous observer, installed observer) per attach
        self._targets: list = []

    def attach(self, target, chain: bool = False) -> "TraceRecorder":
        current = getattr(target, "_submit_observer", "missing")
        if current == "missing":
            raise TypeError(
                f"{type(target).__name__} has no _submit_observer hook — "
                "expected a ServingEngine or ReplicaRouter")
        if current is not None and current != self._observe:
            if not chain:
                raise RuntimeError(
                    f"{type(target).__name__} already has a submit "
                    "observer attached — detach it first (or attach "
                    "with chain=True)")
            prev = current

            def chained(request, *, priority=0, slo_class=None,
                        eos_token_id=None):
                prev(request, priority=priority, slo_class=slo_class,
                     eos_token_id=eos_token_id)
                self._observe(request, priority=priority,
                              slo_class=slo_class,
                              eos_token_id=eos_token_id)

            target._submit_observer = chained
            self._targets.append((target, prev, chained))
            return self
        target._submit_observer = self._observe
        self._targets.append((target, None, self._observe))
        return self

    def detach(self) -> None:
        for t, prev, installed in self._targets:
            if getattr(t, "_submit_observer", None) == installed:
                t._submit_observer = prev
        self._targets = []

    def _observe(self, request, *, priority=0, slo_class=None,
                 eos_token_id=None) -> None:
        self.entries.append(TraceEntry(
            uid=request.uid,
            max_new_tokens=int(request.max_new_tokens),
            slo_class=slo_class, priority=int(priority),
            eos_token_id=None if eos_token_id is None else int(eos_token_id),
            tokens=[int(t) for t in np.asarray(request.prompt).reshape(-1)],
            temperature=float(getattr(request, "temperature", 0.0)),
            top_k=int(getattr(request, "top_k", 0)),
            top_p=float(getattr(request, "top_p", 1.0)),
            seed=int(getattr(request, "seed", 0))))

    def __len__(self) -> int:
        return len(self.entries)

    def trace(self, meta: Optional[Dict[str, Any]] = None) -> ServingTrace:
        return ServingTrace(vocab=self.vocab, entries=list(self.entries),
                            meta={"recorded": True, **(meta or {})})


# --------------------------------------------------------------- fitting
def _counter_total(snapshot: Dict[str, Any], name: str) -> float:
    """Sum a counter family over all its labeled series (0.0 if the
    family never registered)."""
    fam = snapshot.get(name)
    if not fam:
        return 0.0
    return float(sum(s.get("value", 0.0) for s in fam.get("series", [])))


def _slo_mix(snapshot: Dict[str, Any]) -> Dict[str, float]:
    """Observed ``slo_class`` request mix (empty when untracked)."""
    fam = snapshot.get("serving_slo_requests_total")
    out: Dict[str, float] = {}
    for s in (fam or {}).get("series", []):
        cls = s.get("labels", {}).get("slo_class")
        if cls:
            out[cls] = out.get(cls, 0.0) + float(s.get("value", 0.0))
    total = sum(out.values())
    return {c: v / total for c, v in out.items()} if total else {}


def fit_trace(snapshot: Dict[str, Any], *, vocab: int, n_requests: int = 64,
              seed: int = 0, block_size: int = 32,
              spread: float = 0.25) -> ServingTrace:
    """Fit a synthetic :class:`ServingTrace` to a telemetry snapshot
    (``engine.metrics.snapshot()`` / the ``/stats`` scrape's
    ``registry`` section).

    The registry carries exact totals, so the first moments are exact:
    mean prompt length = ``serving_prompt_tokens_total / admitted`` and
    mean decode budget = ``serving_generated_tokens_total / finished``.
    The session structure is fitted: in the steady state of an
    ``S``-session round-robin workload, roughly every request past each
    session's first admission hits its block-aligned shared prefix, so
    the expected hit rate is ``(1 - S/N) * prefix_blocks*B / mean_prompt``
    — :func:`fit_trace` grid-searches ``(S, prefix_blocks)`` for the
    closest match to the observed ``serving_prefix_hit_tokens_total /
    serving_prompt_tokens_total`` (deterministic tie-break: smaller
    error, then longer prefix, then fewer sessions).  Per-request
    tail/decode lengths spread ``±spread`` uniformly around the fitted
    means; the ``slo_class`` mix replays the observed
    ``serving_slo_requests_total`` proportions round-robin."""
    admitted = _counter_total(snapshot, "serving_requests_admitted_total")
    finished = _counter_total(snapshot, "serving_requests_finished_total")
    prompt_tokens = _counter_total(snapshot, "serving_prompt_tokens_total")
    hit_tokens = _counter_total(snapshot, "serving_prefix_hit_tokens_total")
    gen_tokens = _counter_total(snapshot, "serving_generated_tokens_total")
    if admitted < 1 or prompt_tokens < 1:
        raise ValueError(
            "snapshot records no admitted traffic "
            "(serving_requests_admitted_total / serving_prompt_tokens_total"
            " empty) — nothing to fit a trace to")
    mean_prompt = prompt_tokens / admitted
    mean_new = max(1.0, gen_tokens / finished) if finished else 16.0
    observed_hit = hit_tokens / prompt_tokens

    n = int(n_requests)
    best = None  # (err, -prefix_len, sessions)
    if observed_hit > 0:
        max_pb = max(1, int((mean_prompt - 1) // block_size))
        for s in range(1, n + 1):
            for pb in range(1, max_pb + 1):
                pl = pb * block_size
                predicted = max(0.0, 1.0 - s / n) * pl / mean_prompt
                key = (abs(predicted - observed_hit), -pl, s)
                if best is None or key < best[0]:
                    best = (key, s, pl)
    if best is not None:
        _, sessions, prefix_len = best
    else:
        sessions, prefix_len = 0, 0

    mean_tail = max(1.0, mean_prompt - prefix_len)
    mix = _slo_mix(snapshot)
    classes: List[Optional[str]] = []
    if mix:
        # integer class counts by largest remainder...
        counts = {c: int(n * f) for c, f in mix.items()}
        rem = sorted(mix, key=lambda c: -(n * mix[c] - counts[c]))
        for c in rem:
            if sum(counts.values()) >= n:
                break
            counts[c] += 1
        # ...then INTERLEAVED proportionally (always pick the most
        # under-served class) — a budgeted trace.slice(b) replay must
        # see the same mix as the full trace, not one class per block
        filled = {c: 0 for c in counts if counts[c]}
        for _ in range(n):
            c = min(filled, key=lambda c: (filled[c] / counts[c], c))
            filled[c] += 1
            classes.append(c)

    rng = np.random.default_rng([int(seed), 2147483629])
    lo, hi = 1.0 - spread, 1.0 + spread
    entries = []
    for i in range(n):
        tail = max(1, int(round(mean_tail * rng.uniform(lo, hi))))
        mnew = max(1, int(round(mean_new * rng.uniform(lo, hi))))
        entries.append(TraceEntry(
            uid=i, max_new_tokens=mnew,
            session=(i % sessions) if sessions else None,
            tail_len=tail if sessions else 0,
            prompt_len=0 if sessions else tail,
            slo_class=classes[i % len(classes)] if classes else None))
    return ServingTrace(
        vocab=vocab, seed=seed, prefix_len=prefix_len, entries=entries,
        meta={"fitted": True, "observed_hit_rate": observed_hit,
              "mean_prompt": mean_prompt, "mean_new": mean_new,
              "fitted_sessions": sessions, "fitted_prefix_len": prefix_len,
              "slo_mix": mix})
