"""The serving knob space: per-knob domains + constraint predicates.

``init_serving`` has grown ~10 interacting knobs (ROADMAP item 5's "knob
explosion"); most combinations are either invalid (a host KV tier
without the prefix cache), physically impossible (a KV pool past the HBM
ceiling), or violate a checked contract (compile budget, HKV
divisibility).  Searching them naively wastes most of the trial budget
discovering what static reasoning already knows, so this module encodes
the space *with* its constraints:

 - :class:`ModelGeom`: the model geometry the KV block formulae need
   (layers, KV heads, head dim, KV dtype bytes) — from a live engine or
   a model config.
 - :func:`kv_pool_bytes` / :func:`compile_budget`: closed-form mirrors
   of the engine's own accounting (``ServingEngine._kv_footprint`` /
   the ctor's budget arithmetic) over a *candidate dict*, evaluated
   before anything is built.
 - :class:`ServingKnobSpace`: base config + per-knob domains →
   cartesian candidates, each screened by the ``CONSTRAINTS`` predicates;
   ``prune()`` reports how many candidates each constraint removed (the
   searcher's "pruned before any trial ran" accounting).  The special
   ``num_blocks`` value ``"mem"`` resolves to the largest pool that fits
   ``mem_ceiling_bytes`` at the candidate's own ``block_size``/
   ``quantize`` — candidates trade block granularity against pool depth
   under one fixed memory envelope, the way a real chip does.

What the constructor refuses — an option out of its range, options that
do not combine — is not restated here: the defaults are
``inference/options.py OPTIONS``', and one predicate a rule group reads
its ``EXCLUDES`` (the sentence a pruned candidate carries is the one the
constructor's ``ValueError`` would).  A predicate of this module's own
says what only the space knows: the memory ceiling, the program budget,
the fleet's prefill:decode ratio, and the two checks that need the
model's geometry (the pool's floor, KV heads over tp).  Pruning is an
optimization, not the safety net — a config that somehow slips through
still fails in the constructor with a diagnosis naming the knob, never a
mid-trial crash (``tests/unit/test_serving_autotune.py``,
``tests/unit/test_serving_options.py``).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..inference import options

__all__ = ["ModelGeom", "ServingKnobSpace", "kv_pool_bytes",
           "compile_budget", "prefill_rungs", "workload_space",
           "DEFAULT_DOMAINS", "CONSTRAINTS", "BASE_SERVING_CONFIG",
           "FLEET_KNOBS"]

#: the hand-picked config every search starts from (and the yardstick the
#: winner must beat): every serving option at its default — the two a plain
#: model's ``None`` resolves to stated, the block formulae need them — and
#: the fleet's knobs (``init_serving``'s ``topology``, ``init_router``'s
#: ``replicas`` / ``prefill_workers``)
BASE_SERVING_CONFIG: Dict[str, Any] = {
    **{name: opt.default for name, opt in options.OPTIONS.items()},
    "block_size": 32,
    "prefix_caching": True,
    "topology": 1,
    "replicas": 1,
    "prefill_workers": 0,
}
#: the knobs of a candidate that are ``init_router``'s, not an engine's
FLEET_KNOBS = ("replicas", "prefill_workers")

#: conservative default domains — callers override per workload (the
#: bench lane, for one, adds trace-sized ``host_blocks`` choices)
DEFAULT_DOMAINS: Dict[str, Tuple[Any, ...]] = {
    "block_size": (16, 32, 64),
    "prefill_chunk": (64, 128, 256),
    "prefill_batch": (2, 4, 8),
    "spec_tokens": (0, 4),
}


@dataclasses.dataclass(frozen=True)
class ModelGeom:
    """KV-geometry inputs to the block formulae."""
    layers: int
    kv_heads: int
    head_dim: int
    dtype_bytes: int = 4          # KV pool element size (fp32=4, bf16=2)

    @classmethod
    def from_model_config(cls, mc, dtype_bytes: int = 4) -> "ModelGeom":
        heads = int(getattr(mc, "num_heads"))
        kv_heads = int(getattr(mc, "num_kv_heads", heads))
        head_dim = int(getattr(mc, "head_dim",
                               getattr(mc, "hidden_size") // heads))
        return cls(layers=int(mc.num_layers), kv_heads=kv_heads,
                   head_dim=head_dim, dtype_bytes=int(dtype_bytes))

    @classmethod
    def from_engine(cls, engine) -> "ModelGeom":
        """From a live ``init_inference`` engine (reads the engine's KV
        dtype — the serving pool is built in it)."""
        import jax.numpy as jnp

        return cls.from_model_config(
            engine.module.model_config,
            dtype_bytes=jnp.dtype(engine._config.jnp_dtype).itemsize)


def _blocks_per_seq(config: Dict[str, Any]) -> int:
    return int(math.ceil(int(config["max_seq_len"]) /
                         int(config["block_size"])))


def resolved_num_blocks(config: Dict[str, Any]) -> int:
    """``num_blocks`` with the engine's own default applied (``None`` =
    scratch + full residency for every slot)."""
    nb = config.get("num_blocks")
    if nb is None:
        return 1 + int(config["slots"]) * _blocks_per_seq(config)
    return int(nb)


def block_bytes(config: Dict[str, Any], geom: ModelGeom) -> int:
    """Bytes of ONE physical KV block under the candidate's knobs —
    the k + v payload leaves (``[L, NB, HKV, bs, hd]`` per leaf), plus
    the per-block bf16 scale rows (``[L, NB, HKV, bs]``) when the pool
    quantizes to int8 codes (``quantize`` includes ``kv8``)."""
    bs = int(config["block_size"])
    elems = geom.layers * geom.kv_heads * bs * geom.head_dim
    quant = str(config.get("quantize") or "")
    if "kv8" in quant:
        return 2 * (elems * 1 + geom.layers * geom.kv_heads * bs * 2)
    return 2 * elems * geom.dtype_bytes


def kv_pool_bytes(config: Dict[str, Any], geom: ModelGeom) -> int:
    """Device KV pool footprint of a candidate (block formula x pool
    depth) — the quantity ``mem_ceiling_bytes`` caps.  Host-tier blocks
    live in host DRAM and do not count against the device ceiling."""
    return resolved_num_blocks(config) * block_bytes(config, geom)


def prefill_rungs(config: Dict[str, Any]) -> int:
    """Mirror of the ctor's prefill ladder (``inference/serving.py
    prefill_ladder``): the prefill programs of a candidate — ``[prefill_batch,
    prefill_chunk]`` and, for a row alone in its call, ``[1, prefill_batch *
    prefill_chunk]`` where that row stays within the cache; ONE with a
    resident window or a batch of 1.  (The prefill kernel's VMEM plan, which
    can also refuse the wide row, needs the model's heads and is not
    mirrored.)"""
    batch = int(config["prefill_batch"])
    if config.get("resident_window_blocks") or batch < 2:
        return 1
    bs = int(config.get("block_size") or 0)
    if config.get("max_seq_len") and bs > 0:   # (bs < 1: another predicate's)
        cache = -(-int(config["max_seq_len"]) // bs) * bs
        if batch * int(config["prefill_chunk"]) > cache:
            return 1
    return 2


def compile_budget(config: Dict[str, Any]) -> int:
    """Mirror of the ctor's compiled-program budget: 1 (decode / n-gram
    verify) + a prefill program a rung of the ladder
    (:func:`prefill_rungs`), + 2 swap programs with a host tier.  (A draft
    model would add 1; the space searches the zero-extra-programs n-gram
    proposer.)

    ``engine_mode="dp_tp"`` compiles the same programs — one dp-sharded
    decode instead of N per-replica copies.  ``nvme_blocks`` and ``role``
    add NOTHING: the NVMe tier spills/promotes through the host arena's
    existing two swap programs (the file I/O is host-side ``ops/aio``),
    and a role only gates which host-side scheduler phases run.
    ``sp > 1`` and ``resident_window_blocks > 0`` are likewise +0: the
    sp prefill reshapes the SAME chunked prefill program through
    shard_map, and the windowed decode/prefill bodies REPLACE the plain
    ones one-for-one (one extra traced operand, same sentry names).
    ``sampling`` / ``logit_masks`` are +0 too: the per-slot sampling
    params (and the optional ``[slots, vocab]`` mask) ride as extra
    fixed-shape operands of the SAME programs, and a sampling engine's
    rejection verifier replaces the greedy matcher inside the one verify
    program."""
    return (3 if config.get("host_blocks") else 1) + prefill_rungs(config)


# ---------------------------------------------------------- constraints
def _c_memory(config, space) -> Optional[str]:
    if space.mem_ceiling_bytes is None:
        return None
    got = kv_pool_bytes(config, space.geom)
    if got > space.mem_ceiling_bytes:
        return (f"kv pool {got} bytes exceeds the ceiling "
                f"{space.mem_ceiling_bytes} (num_blocks="
                f"{resolved_num_blocks(config)}, block_size="
                f"{config['block_size']}, quantize="
                f"{config.get('quantize')})")
    return None


def _c_compile(config, space) -> Optional[str]:
    got = compile_budget(config)
    if got > space.max_programs:
        return (f"compile budget {got} exceeds max_programs="
                f"{space.max_programs}")
    return None


def _c_shard_kv(config, space) -> Optional[str]:
    tp = int(config.get("topology") or 1)
    if config.get("shard_kv") and tp > 1 and space.geom.kv_heads % tp:
        return (f"shard_kv=True but kv_heads={space.geom.kv_heads} does "
                f"not divide tp={tp}")
    return None


def _c_pool_min(config, space) -> Optional[str]:
    if int(config.get("block_size") or 0) < 1:
        return None                    # option_ranges owns this failure
    need = 1 + _blocks_per_seq(config)
    win = int(config.get("resident_window_blocks") or 0)
    if win:
        # a windowed slot never holds more than landmark + window +
        # one in-flight prefill chunk on device — the pool floor drops
        # accordingly (the ctor applies the same min())
        chunk_blocks = int(math.ceil(int(config.get("prefill_chunk") or 1)
                                     / int(config["block_size"])))
        need = min(need, 1 + 1 + win + chunk_blocks)
    if resolved_num_blocks(config) < need:
        return (f"num_blocks={resolved_num_blocks(config)} cannot hold "
                f"one {'resident window' if win else 'full sequence'} "
                f"({need} blocks incl. scratch)")
    return None


def _c_prefill_ratio(config, space) -> Optional[str]:
    pw = int(config.get("prefill_workers") or 0)
    reps = int(config.get("replicas") or 1)
    if pw < 0:
        return f"prefill_workers must be >= 0, got {pw}"
    if pw and pw >= reps:
        return (f"prefill_workers={pw} with replicas={reps}: the "
                "prefill_workers:decode_workers ratio must keep at least "
                "one worker on each side")
    if pw and not int(config.get("host_blocks") or 0):
        return ("a disaggregated fleet (prefill_workers > 0) needs "
                "host_blocks > 0 on every replica — the handoff is a "
                "host-tier chain pull")
    return None


def _degrees(config) -> Dict[str, Any]:
    """The mesh a candidate would be built on, as ``options.check`` takes
    it: ``init_serving`` gives the engine the tp and sp axes the candidate
    names and the int8 weights a ``w8a8`` candidate needs; what ``dp`` a
    ``dp_tp`` candidate finds is the host's, and more than one group is
    what such a candidate is searched for."""
    return {"tp": int(config.get("topology") or 1),
            "dp": 2 if config.get("engine_mode") == "dp_tp" else 1,
            "mesh_sp": int(config.get("sp") or 1),
            "weights": "w8a8" if "w8a8" in str(config.get("quantize") or "")
            else None}


def _c_ranges(config, space) -> Optional[str]:
    try:
        options.check_ranges(config)
    except ValueError as e:
        return str(e)
    return None


def _c_excludes(group: str) -> Callable:
    """The predicate of one rule group of ``options.EXCLUDES``: the
    sentence of the first of its rules the candidate breaks."""
    def broken(config, space) -> Optional[str]:
        if _c_ranges(config, space):
            return None                # option_ranges owns this failure
        return next((says for name, says in options.violations(
            config, _degrees(config)) if name == group), None)
    return broken


#: ``(name, predicate)`` — predicate returns a violation message or None:
#: the options' ranges, this module's own five, then a predicate a rule
#: group of ``options.EXCLUDES`` (in the table's order)
CONSTRAINTS: Tuple[Tuple[str, Callable], ...] = (
    ("option_ranges", _c_ranges),
    ("kv_pool_memory", _c_memory),
    ("compile_budget", _c_compile),
    ("shard_kv_divisibility", _c_shard_kv),
    ("prefill_decode_ratio", _c_prefill_ratio),
    ("pool_min_blocks", _c_pool_min),
    *((group, _c_excludes(group)) for group in dict.fromkeys(
        rule.group for rule in options.EXCLUDES)),
)


class ServingKnobSpace:
    """Base config + domains -> constraint-screened candidates.

    Parameters
    ----------
    geom:             :class:`ModelGeom` for the block formulae.
    max_seq_len:      the trace's required sequence budget (every
                      candidate carries it; a knob only via ``domains``).
    base:             overrides onto :data:`BASE_SERVING_CONFIG` — the
                      "hand-picked default" candidate 0 of every search.
    domains:          ``knob -> tuple of values`` (unlisted knobs stay at
                      the base value).  ``num_blocks`` accepts the
                      special value ``"mem"`` (module docstring);
                      ``host_blocks`` accepts ``"ws"`` — the trace's
                      unique working set in the candidate's OWN block
                      size, plus one sequence of slack (needs
                      ``ws_tokens``).
    mem_ceiling_bytes: device KV-pool byte ceiling (None = uncapped).
    max_programs:     compile-budget ceiling (sentry-aligned).
    """

    def __init__(self, geom: ModelGeom, *, max_seq_len: int,
                 base: Optional[Dict[str, Any]] = None,
                 domains: Optional[Dict[str, Sequence[Any]]] = None,
                 mem_ceiling_bytes: Optional[int] = None,
                 max_programs: int = 8,
                 ws_tokens: Optional[int] = None):
        self.geom = geom
        self.base = dict(BASE_SERVING_CONFIG)
        self.base["max_seq_len"] = int(max_seq_len)
        self.base.update(base or {})
        self.domains: Dict[str, Tuple[Any, ...]] = {
            k: tuple(v) for k, v in (domains if domains is not None
                                     else DEFAULT_DOMAINS).items()}
        unknown = set(self.domains) - set(self.base)
        if unknown:
            raise ValueError(
                f"domain(s) over unknown knob(s) {sorted(unknown)} — "
                f"knobs: {sorted(self.base)}")
        self.mem_ceiling_bytes = mem_ceiling_bytes
        self.max_programs = int(max_programs)
        self.ws_tokens = None if ws_tokens is None else int(ws_tokens)

    # ------------------------------------------------------- candidates
    def size(self) -> int:
        out = 1
        for v in self.domains.values():
            out *= len(v)
        return out

    def _resolve(self, config: Dict[str, Any]) -> Dict[str, Any]:
        if config.get("num_blocks") == "mem":
            if self.mem_ceiling_bytes is None:
                raise ValueError(
                    'num_blocks="mem" needs mem_ceiling_bytes')
            config["num_blocks"] = max(
                1, self.mem_ceiling_bytes // block_bytes(config, self.geom))
        if config.get("host_blocks") == "ws":
            if self.ws_tokens is None:
                raise ValueError('host_blocks="ws" needs ws_tokens')
            config["host_blocks"] = int(
                math.ceil(self.ws_tokens / int(config["block_size"]))
                + _blocks_per_seq(config))
        return config

    def default_config(self) -> Dict[str, Any]:
        return self._resolve(dict(self.base))

    def candidates(self) -> List[Dict[str, Any]]:
        """Cartesian product of the domains overlaid on the base,
        deduplicated, default config first — UNSCREENED (``prune()``
        applies the constraints and reports per-constraint counts)."""
        keys = sorted(self.domains)
        seen = set()
        out: List[Dict[str, Any]] = []
        default = self.default_config()
        for cfg in [default] + [
                self._resolve({**self.base,
                               **dict(zip(keys, combo))})
                for combo in itertools.product(
                    *(self.domains[k] for k in keys))]:
            key = tuple(sorted((k, repr(v)) for k, v in cfg.items()))
            if key not in seen:
                seen.add(key)
                out.append(cfg)
        return out

    # ------------------------------------------------------ constraints
    def check(self, config: Dict[str, Any]) -> List[Tuple[str, str]]:
        """``(constraint name, violation message)`` for every violated
        predicate (empty = admissible)."""
        out = []
        for name, pred in CONSTRAINTS:
            msg = pred(config, self)
            if msg:
                out.append((name, msg))
        return out

    def prune(self, candidates: Optional[List[Dict[str, Any]]] = None
              ) -> Tuple[List[Dict[str, Any]], Dict[str, int]]:
        """Screen candidates; returns ``(kept, pruned_by_constraint)``
        where the counts attribute each rejection to the FIRST violated
        constraint (a candidate is pruned once)."""
        if candidates is None:
            candidates = self.candidates()
        kept: List[Dict[str, Any]] = []
        pruned: Dict[str, int] = {name: 0 for name, _ in CONSTRAINTS}
        for cfg in candidates:
            bad = self.check(cfg)
            if bad:
                pruned[bad[0][0]] += 1
            else:
                kept.append(cfg)
        return kept, {k: v for k, v in pruned.items() if v}


def workload_space(geom: ModelGeom, trace, *, pool_frac: float = 0.0,
                   mem_ceiling_bytes: Optional[int] = None,
                   base: Optional[Dict[str, Any]] = None,
                   domains: Optional[Dict[str, Sequence[Any]]] = None
                   ) -> ServingKnobSpace:
    """A :class:`ServingKnobSpace` sized to a :class:`~deepspeed_tpu
    .autotuning.trace.ServingTrace` workload.

    ``pool_frac > 0`` puts the pool under pressure: the
    device memory ceiling is set to the bytes of a default-``block_size``
    pool holding ``pool_frac`` of the trace's unique working set, the
    base ``num_blocks`` becomes ``"mem"`` (every candidate fills its own
    block geometry to the SAME byte envelope), and the ``host_blocks``
    domain offers the tiered escape hatch (0, or the full working set
    plus one sequence of slack — host DRAM is not under the device
    ceiling).  An explicit ``mem_ceiling_bytes`` overrides the
    ``pool_frac`` sizing.  With neither, the space is unpressured: the
    engine's default full-residency pool and no memory constraint."""
    base = dict(base or {})
    base.setdefault("max_seq_len", trace.max_total_len())
    bs = int(base.get("block_size", BASE_SERVING_CONFIG["block_size"]))
    ws_blocks = max(1, math.ceil(trace.working_set_tokens() / bs))
    probe = {**BASE_SERVING_CONFIG, **base}
    if mem_ceiling_bytes is None and pool_frac > 0:
        bps = _blocks_per_seq(probe)
        pressured = max(1 + bps, int(pool_frac * ws_blocks) + 1)
        mem_ceiling_bytes = pressured * block_bytes(probe, geom)
    if mem_ceiling_bytes is not None:
        base.setdefault("num_blocks", "mem")
        if domains is None:
            domains = dict(DEFAULT_DOMAINS)
            domains["host_blocks"] = (0, "ws")
    return ServingKnobSpace(geom, max_seq_len=base.pop("max_seq_len"),
                            base=base, domains=domains,
                            mem_ceiling_bytes=mem_ceiling_bytes,
                            ws_tokens=trace.working_set_tokens())
