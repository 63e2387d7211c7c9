"""Serving-autotuner trial execution: build → replay → gate → score.

One trial = one candidate config at one replay budget:

 1. **Build** a :class:`~deepspeed_tpu.inference.serving.ServingEngine`
    over the SHARED ``init_inference`` engine (weights are built once for
    the whole search) with ``debug_checks=True`` — the recompile sentry
    runs *strict*, so a candidate that would compile past its declared
    budget raises at trace time and the trial records an infeasible
    ``compile_budget`` constraint instead of silently burning programs.
 2. **Replay** the trace slice twice — a cold pass (compiles included)
    and a warm pass (the steady state: compile-warm, prefix-warm, host
    tier populated).  The warm pass's aggregate tok/s is the score; both
    walls are recorded.
 3. **Parity-gate** BOTH passes against the reference outputs (the
    default config run once per budget and cached): exact token equality
    for full-precision candidates, completion-token match rate >=
    ``min_token_match`` for quantized ones (int8 rounding may flip
    near-tie argmaxes — the PR 7 bounded-divergence contract).  A trial
    that fails parity is infeasible, never ranked.
 4. **SLO**: the per-class attainment report rides every record;
    ``min_slo_attainment`` turns it into a hard constraint and
    ``slo_penalty`` into a score multiplier
    (``score *= 1 - penalty * (1 - attainment)``).

:func:`tune_serving` wires it together: knob space → constraint pruning
→ successive halving → full-budget re-run of the winner AND the default
(the predicted-vs-measured block) → ``exps.json`` / ``best_config.json``
/ ``report.md`` artifacts.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..analysis.sentry import RetraceError
from ..utils.logging import log_dist
from . import report as report_mod
from .search import SuccessiveHalving
from .space import FLEET_KNOBS, ModelGeom, ServingKnobSpace
from .trace import ServingTrace

__all__ = ["ParityError", "TrialRunner", "tune_serving"]

#: config keys forwarded to the ServingEngine ctor (``topology`` is an
#: ``init_serving``-level knob — the shared engine already has its mesh)
_SERVING_KEYS = (
    "slots", "max_seq_len", "prefill_batch",
    "block_size", "num_blocks", "prefill_chunk",
    "prefix_caching", "spec_tokens", "quantize", "host_blocks",
    "swap_batch", "ngram_max", "ngram_min", "shard_kv", "trace_capacity",
    "slo_targets", "peak_flops",
)


class ParityError(AssertionError):
    """A trial's replayed tokens diverged past the gate."""


def _completion_match(outs, ref, requests) -> float:
    """Per-token match rate over the COMPLETION region (prompts always
    agree — counting them would flatter the rate)."""
    match = total = 0
    for req, _ in requests:
        a = np.asarray(outs[req.uid]).reshape(-1)[len(req.prompt):]
        b = np.asarray(ref[req.uid]).reshape(-1)[len(req.prompt):]
        n = min(a.size, b.size)
        match += int(np.sum(a[:n] == b[:n]))
        total += n
    return match / total if total else 1.0


class TrialRunner:
    """The successive-halving objective (module docstring).

    Parameters
    ----------
    engine:          shared ``init_inference`` engine (one weight pytree
                     for the whole search).
    trace:           the :class:`ServingTrace` to replay; ``budget`` =
                     entries replayed (``trace.slice``).
    base_config:     the reference/default config trials are
                     parity-gated against.
    min_token_match: completion-token match-rate floor for quantized
                     candidates (full-precision candidates require 1.0).
    slo_penalty:     score multiplier weight on missed attainment.
    min_slo_attainment: hard attainment floor (None = off).
    """

    def __init__(self, engine, trace: ServingTrace, *,
                 base_config: Dict[str, Any],
                 min_token_match: float = 0.90,
                 slo_penalty: float = 0.0,
                 min_slo_attainment: Optional[float] = None):
        self.engine = engine
        self.trace = trace
        self.base_config = dict(base_config)
        self.min_token_match = float(min_token_match)
        self.slo_penalty = float(slo_penalty)
        self.min_slo_attainment = min_slo_attainment
        self._ref_outputs: Dict[int, Dict[Any, np.ndarray]] = {}
        self._ref_engine = None

    # ------------------------------------------------------------ build
    def build(self, config: Dict[str, Any]):
        from ..inference.serving import ServingEngine
        from ..parallel.topology import TP_AXIS

        # topology is an init_serving-level knob and trials share ONE
        # engine: a candidate asking for a different tp than the engine
        # carries would silently measure at the wrong parallelism (and
        # best_config.json would ship an unmeasured deployment) — fail
        # the trial with the diagnosis instead
        want_tp = int(config.get("topology") or 1)
        have_tp = int(dict(self.engine.mesh.shape).get(TP_AXIS, 1))
        if want_tp != have_tp:
            raise ValueError(
                f"candidate topology={want_tp} does not match the shared "
                f"search engine's tp={have_tp} — trials share one "
                "init_inference engine; build it at the topology you "
                "want to search (space base {'topology': ...})")
        kwargs = {k: config[k] for k in _SERVING_KEYS if k in config}
        return ServingEngine(self.engine, debug_checks=True, **kwargs)

    # ----------------------------------------------------------- replay
    def _replay(self, srv, budget: int):
        """One full pass over the trace slice through the incremental
        API (per-entry ``slo_class``/``priority`` ride along); returns
        ``(outputs, wall_s, generated_tokens)``."""
        sliced = self.trace.slice(budget)
        requests = sliced.requests()
        t0 = time.perf_counter()
        handles = [srv.submit(req, priority=e.priority,
                              slo_class=e.slo_class,
                              eos_token_id=e.eos_token_id)
                   for req, e in requests]
        while srv.step():
            pass
        wall = time.perf_counter() - t0
        outs = {h.uid: h.result(timeout=0) for h in handles}
        gen = sum(req.max_new_tokens for req, _ in requests)
        return outs, wall, gen, requests

    def reference(self, budget: int) -> Dict[Any, np.ndarray]:
        """Default-config outputs for this budget (cached; the ref
        engine lives across budgets so its compiles amortize)."""
        if budget not in self._ref_outputs:
            if self._ref_engine is None:
                self._ref_engine = self.build(self.base_config)
            outs, _, _, _ = self._replay(self._ref_engine, budget)
            self._ref_outputs[budget] = outs
        return self._ref_outputs[budget]

    def _gate_parity(self, config, outs, requests, budget) -> float:
        ref = self.reference(budget)
        rate = _completion_match(outs, ref, requests)
        exact = not config.get("quantize")
        floor = 1.0 if exact else self.min_token_match
        if rate < floor:
            gate = "the exact-parity gate" if exact else \
                f"min_token_match={self.min_token_match}"
            raise ParityError(
                f"completion token match {rate:.4f} below {gate}")
        return rate

    # -------------------------------------------------------- objective
    def __call__(self, config: Dict[str, Any], budget: int
                 ) -> Dict[str, Any]:
        rec: Dict[str, Any] = {"config": dict(config)}
        srv = None
        try:
            srv = self.build(config)
            outs_cold, wall_cold, gen, requests = self._replay(srv, budget)
            match_cold = self._gate_parity(config, outs_cold, requests,
                                           budget)
            outs_warm, wall_warm, _, _ = self._replay(srv, budget)
            match_warm = self._gate_parity(config, outs_warm, requests,
                                           budget)
            slo = srv.slo_report()
            atts = [min(c["ttft_attainment"], c["tpot_attainment"])
                    for c in slo.values() if c["requests"]]
            attainment = min(atts) if atts else 1.0
            if self.min_slo_attainment is not None and \
                    attainment < self.min_slo_attainment:
                rec.update(feasible=False, constraint="slo",
                           slo_attainment=attainment,
                           error=f"attainment {attainment:.3f} < "
                                 f"{self.min_slo_attainment}")
                return rec
            score = (gen / wall_warm) * (
                1.0 - self.slo_penalty * (1.0 - attainment))
            st = srv.stats()
            rec.update(
                feasible=True, throughput=score,
                tok_s_cold=gen / wall_cold, tok_s_warm=gen / wall_warm,
                wall_s=wall_cold, wall_warm_s=wall_warm,
                generated_tokens=gen,
                token_match=min(match_cold, match_warm),
                slo_attainment=attainment,
                compiled_programs=st["compile_count"],
                prefix_cache_hit_rate=st["prefix_cache_hit_rate"],
                preemptions=st["evicted"], swap_in=st["swap_in"],
                resolved_config=st["config"])
            return rec
        except ParityError as e:
            rec.update(feasible=False, constraint="parity", error=str(e))
            return rec
        except RetraceError as e:
            rec.update(feasible=False, constraint="compile_budget",
                       error=str(e)[:300])
            return rec
        except ValueError as e:
            rec.update(feasible=False, constraint="validation",
                       error=str(e)[:300])
            return rec
        except Exception as e:   # OOM and friends: infeasible, not fatal
            rec.update(feasible=False, constraint=type(e).__name__,
                       error=str(e)[:300])
            return rec
        finally:
            del srv
            gc.collect()


def tune_serving(engine, trace: ServingTrace, *,
                 space: Optional[ServingKnobSpace] = None,
                 domains: Optional[Dict[str, Any]] = None,
                 base: Optional[Dict[str, Any]] = None,
                 mem_ceiling_bytes: Optional[int] = None,
                 eta: int = 2, min_budget: Optional[int] = None,
                 max_budget: Optional[int] = None,
                 max_trials: Optional[int] = None,
                 results_dir: str = "autotuning_results_serving",
                 resume: bool = False,
                 min_token_match: float = 0.90,
                 slo_penalty: float = 0.0,
                 min_slo_attainment: Optional[float] = None
                 ) -> Dict[str, Any]:
    """Closed-loop serving autotune (module docstring): returns the
    summary dict and writes the ``results_dir`` artifact trio.

    ``space`` defaults to :class:`ServingKnobSpace` over the engine's
    geometry and the trace's required ``max_seq_len`` (pass ``domains``/
    ``base``/``mem_ceiling_bytes`` to shape it).  ``min_budget``/
    ``max_budget`` default to a quarter of / the whole trace."""
    if space is None:
        from ..parallel.topology import TP_AXIS

        base = dict(base or {})
        # the candidates describe THIS engine: pin the space's topology
        # to its mesh so every trial (and best_config.json) matches the
        # parallelism that was actually measured
        base.setdefault("topology",
                        int(dict(engine.mesh.shape).get(TP_AXIS, 1)))
        space = ServingKnobSpace(
            ModelGeom.from_engine(engine),
            max_seq_len=trace.max_total_len(),
            base=base, domains=domains,
            mem_ceiling_bytes=mem_ceiling_bytes)
    n = len(trace)
    max_budget = n if max_budget is None else min(int(max_budget), n)
    if min_budget is None:
        min_budget = max(2, max_budget // 4)
    candidates = space.candidates()
    kept, pruned = space.prune(candidates)
    if not kept:
        raise RuntimeError(
            f"every candidate was pruned by constraints: {pruned}")
    log_dist(
        f"autotune[serving]: {len(candidates)} candidates, "
        f"{len(kept)} admissible (pruned {pruned}), budgets "
        f"{min_budget}..{max_budget} x eta={eta}", ranks=[0])
    runner = TrialRunner(engine, trace, base_config=space.default_config(),
                         min_token_match=min_token_match,
                         slo_penalty=slo_penalty,
                         min_slo_attainment=min_slo_attainment)
    sh = SuccessiveHalving(eta=eta, min_budget=min_budget,
                           max_budget=max_budget, max_trials=max_trials,
                           results_dir=results_dir)
    out = sh.run(kept, runner, resume=resume)
    if out["best"] is None:
        raise RuntimeError(
            "autotuning found no feasible serving configuration; "
            f"see {results_dir}/exps.json")
    # ``best_config.json`` is ``init_serving`` keywords, which refuses the
    # fleet's by name (the trials ran ONE engine)
    winner_cfg = {k: v for k, v in out["best"]["config"].items()
                  if k not in FLEET_KNOBS}
    predicted = float(out["best"]["throughput"])

    # predicted-vs-measured: fresh full-budget re-runs of the winner and
    # the hand-picked default (same gates as every trial)
    rerun_w = runner(winner_cfg, max_budget)
    rerun_w.update(stage="rerun_winner", budget=max_budget)
    default_cfg = space.default_config()
    rerun_d = runner(default_cfg, max_budget)
    rerun_d.update(stage="rerun_default", budget=max_budget)
    results = out["results"] + [rerun_w, rerun_d]
    measured = float(rerun_w.get("throughput") or 0.0)
    default_tok_s = float(rerun_d.get("throughput") or 0.0)
    speedup = measured / default_tok_s if default_tok_s else None

    pvm = [
        "## Predicted vs measured",
        "",
        "| config | predicted tok/s | measured tok/s (full budget) |",
        "|---|---|---|",
        f"| winner | {predicted:.0f} | {measured:.0f} |",
        f"| default | - | {default_tok_s:.0f} |",
        "",
        f"Winner / default speedup: "
        f"**{speedup:.2f}x**" if speedup else "Default re-run infeasible.",
    ]
    prune_md = ["## Constraint pruning", ""] + (
        [f"- `{k}`: {v} candidate(s)" for k, v in sorted(pruned.items())]
        or ["- nothing pruned"])
    sched = ["## Search schedule", ""] + [
        f"- rung {r['rung']}: {r['candidates']} candidate(s) at budget "
        f"{r['budget']} ({r['feasible']} feasible, {r['resumed']} resumed)"
        for r in out["rungs"]]
    report_mod.write_results(
        results_dir, results, winner_cfg,
        title="Serving autotuning report",
        extra_sections=["\n".join(prune_md), "\n".join(sched),
                        "\n".join(pvm)])
    summary = {
        "results_dir": results_dir,
        "candidates": len(candidates),
        "admissible": len(kept),
        "pruned_by_constraint": pruned,
        "trials_executed": out["trials_executed"],
        "trials_total": out["trials_total"],
        "budget_spent_requests": out["budget_spent"],
        "rungs": out["rungs"],
        "exhausted": out["exhausted"],
        "best_config": winner_cfg,
        "winner": {"predicted_tok_s": predicted,
                   "measured_tok_s": measured,
                   "record": rerun_w},
        "default": {"measured_tok_s": default_tok_s,
                    "record": rerun_d},
        "speedup": speedup,
    }
    log_dist(
        f"autotune[serving]: winner {measured:.1f} tok/s vs default "
        f"{default_tok_s:.1f} tok/s "
        f"({speedup:.2f}x) -> {results_dir}/best_config.json"
        if speedup else
        f"autotune[serving]: winner {measured:.1f} tok/s -> "
        f"{results_dir}/best_config.json", ranks=[0])
    return summary
