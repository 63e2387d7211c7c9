"""Autotuner — measured search over ZeRO stage x micro-batch (x remat).

Reference ``autotuning/autotuner.py:423 Autotuner.tune``: builds an
experiment space from the user config ("fast" mode: ZeRO stage and
micro-batch size), launches short training runs per experiment, records
throughput, prunes infeasible points, and emits the best config.  The
reference spawns cluster jobs per experiment; here each experiment is an
in-process engine build + a few measured ``train_batch`` steps (XLA compile
cache makes repeats cheap), with HBM OOM treated as infeasible-and-prune
(larger micro batches of the same stage are skipped — the reference's
memory-based pruning).
"""

from __future__ import annotations

import gc
import time
from typing import Any, Callable, Dict, List, Optional

from ..utils.logging import log_dist
from . import report
from .config import AutotuningConfig
from .search import run_candidates

OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Allocation", "exceed", "out of memory")


def _merge_overrides(base: dict, overrides: dict) -> dict:
    """One-level deep merge: dict-valued overrides update the base sub-dict
    instead of replacing it (zero_optimization.stage must not drop the
    user's offload/bucket options)."""
    out = dict(base)
    for k, v in overrides.items():
        if isinstance(v, dict):
            merged = dict(out.get(k, {}))
            merged.update(v)
            out[k] = merged
        else:
            out[k] = v
    return out


class Autotuner:

    def __init__(self, model_factory: Callable[[], Any], base_config: dict,
                 batch_factory: Callable[[int, int], dict],
                 seq_len: int = 128):
        """``model_factory()`` -> fresh ModelSpec per experiment;
        ``batch_factory(global_batch, seq_len)`` -> host batch dict."""
        self.model_factory = model_factory
        self.base_config = dict(base_config)
        self.cfg = AutotuningConfig(**self.base_config.get("autotuning", {}))
        self.batch_factory = batch_factory
        self.seq_len = seq_len
        self.results: List[Dict[str, Any]] = []

    # ----------------------------------------------------------- exp space
    def experiment_space(self) -> List[dict]:
        """Fast-mode space (reference ``_generate_experiments``): ZeRO
        stages x micro-batch powers of two."""
        base_micro = int(self.base_config.get(
            "train_micro_batch_size_per_gpu", 1))
        micros = []
        m = max(self.cfg.min_train_micro_batch_size_per_gpu, base_micro)
        for _ in range(self.cfg.num_tuning_micro_batch_sizes):
            if m > self.cfg.max_train_micro_batch_size_per_gpu:
                break
            micros.append(m)
            m *= 2
        stages = self.base_config.get("autotuning", {}).get(
            "zero_stages", [0, 1, 2, 3])
        exps = []
        for stage in stages:
            for micro in micros:
                overrides = {
                    "train_micro_batch_size_per_gpu": micro,
                    "zero_optimization": {"stage": stage},
                }
                exps.append(overrides)
        return exps[: self.cfg.tuner_num_trials]

    # ------------------------------------------------------------ measure
    def _run_experiment(self, overrides: dict) -> Dict[str, Any]:
        import jax

        import deepspeed_tpu

        config = dict(self.base_config)
        config.pop("autotuning", None)
        model_overrides = dict(overrides.get("_model", {}))
        config = _merge_overrides(
            config, {k: v for k, v in overrides.items() if k != "_model"})
        rec: Dict[str, Any] = {"config": overrides}
        deepspeed_tpu.comm.reset_topology()
        engine = None
        try:
            model = self.model_factory()
            mc = getattr(model, "model_config", None)
            for k, v in model_overrides.items():
                if mc is None or not hasattr(mc, k):
                    raise ValueError(f"model does not expose knob {k!r}")
                setattr(mc, k, v)
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, config=config)
            warm = self.cfg.start_profile_step
            steps = max(self.cfg.end_profile_step - warm, 1)
            for _ in range(warm):
                engine.train_batch(self.batch_factory(
                    engine.train_batch_size(), self.seq_len))
            # drain the async warm-step backlog BEFORE starting the clock
            # (dispatch returns at enqueue; without this the measured
            # window absorbs the warm steps' device time)
            jax.device_get(jax.tree_util.tree_leaves(
                engine.state["params"])[0].sum())
            t0 = time.perf_counter()
            for _ in range(steps):
                _, m = engine.train_batch(self.batch_factory(
                    engine.train_batch_size(), self.seq_len))
            jax.device_get(jax.tree_util.tree_leaves(
                engine.state["params"])[0].sum())
            dt = (time.perf_counter() - t0) / steps
            toks = engine.train_batch_size() * self.seq_len
            rec.update(feasible=True, step_s=dt,
                       throughput=toks / dt, loss=float(m["loss"]))
        except Exception as e:  # infeasible (OOM / invalid combo)
            msg = str(e)
            rec.update(feasible=False,
                       oom=any(s in msg for s in OOM_MARKERS),
                       error=msg[:300])
        finally:
            del engine
            gc.collect()
        return rec

    # -------------------------------------------------- staged search (v2)
    def _model_knob_space(self, stage_name: str) -> List[dict]:
        """Candidate ``_model`` overrides for one staged knob group.  These
        are the knobs that actually set TPU throughput (the rounds 1–4
        measured winners: remat policy, layer-loop unrolling, gas, flash
        block sizes) — the reference's fast mode never touches them."""
        probe = self.model_factory()
        mc = getattr(probe, "model_config", None)

        def has(k):
            return mc is not None and hasattr(mc, k)

        if stage_name == "remat":
            cands = []
            for pol in (self.cfg.remat_policies if has("remat_policy")
                        else [None]):
                for scan in ([True, False] if has("scan_layers") else [None]):
                    m = {}
                    if pol is not None:
                        m["remat"] = pol != "off"
                        m["remat_policy"] = pol
                    if scan is not None:
                        m["scan_layers"] = scan
                    if m:
                        cands.append({"_model": m})
            return cands
        if stage_name == "gas":
            return [{"gradient_accumulation_steps": g}
                    for g in self.cfg.gas_candidates]
        if stage_name == "flash":
            if not (has("flash_block_q") and has("flash_block_k")):
                return []
            return [{"_model": {"flash_block_q": bq, "flash_block_k": bk}}
                    for bq, bk in self.cfg.flash_blocks]
        raise ValueError(stage_name)

    def _predict(self, features: List[float],
                 measured: List[tuple]) -> Optional[float]:
        """Model-based trial ordering (reference
        ``tuner/model_based_tuner.py``): inverse-distance-weighted
        prediction of throughput from the experiments measured so far."""
        if len(measured) < 2:
            return None
        num = den = 0.0
        for f, y in measured:
            d = sum((a - b) ** 2 for a, b in zip(features, f)) ** 0.5
            w = 1.0 / (d + 1e-3)
            num += w * y
            den += w
        return num / den

    def _features(self, overrides: dict) -> List[float]:
        m = overrides.get("_model", {})
        return [
            float(overrides.get("train_micro_batch_size_per_gpu", 0)),
            float(overrides.get("zero_optimization", {}).get("stage", -1)),
            float(overrides.get("gradient_accumulation_steps", 0)),
            {"full": 0, "dots": 1, "dots_flash": 2}.get(
                m.get("remat_policy"), -1),
            1.0 if m.get("scan_layers") else 0.0,
            float(m.get("flash_block_q", 0)),
            float(m.get("flash_block_k", 0)),
        ]

    def _measure(self, overrides: dict, stage_name: Optional[str],
                 measured: Optional[List[tuple]] = None) -> Dict[str, Any]:
        """Run + record + log ONE experiment (the shared per-candidate
        body both search modes hand to ``search.run_candidates``)."""
        rec = self._run_experiment(overrides)
        if stage_name is not None:
            rec["stage"] = stage_name
        self.results.append(rec)
        log_dist(
            f"autotuning{'[' + stage_name + ']' if stage_name else ''} "
            f"{overrides}: "
            f"{'%.1f tok/s' % rec['throughput'] if rec.get('feasible') else 'infeasible'}",
            ranks=[0])
        if measured is not None and rec.get("feasible"):
            measured.append((self._features(overrides), rec["throughput"]))
        return rec

    def _tune_staged(self) -> Dict[str, Any]:
        """Greedy coordinate descent over knob groups: tune batch geometry
        first (memory-dominant), then remat policy, then gas, then flash
        blocks — each stage keeps the winners of the previous ones.  A
        model-based tuner orders within-stage candidates and early-stops
        when its prediction falls far behind the incumbent."""
        best_over: Dict[str, Any] = {}
        best_rec: Optional[Dict[str, Any]] = None
        measured: List[tuple] = []
        for stage_name in self.cfg.stages:
            if stage_name == "batch":
                cands = self.experiment_space()
            else:
                cands = self._model_knob_space(stage_name)
            cands = [c for c in cands if c]
            if not cands:
                continue
            # model-based ordering: try predicted-best first
            if self.cfg.tuner_type == "model_based" and len(measured) >= 2:
                cands.sort(key=lambda c: -(self._predict(
                    self._features(_merge_overrides(best_over, c)), measured)
                    or 0.0))
            stage_best = run_candidates(
                cands,
                lambda cand: self._measure(
                    _merge_overrides(best_over, cand), stage_name,
                    measured),
                early_stopping=self.cfg.tuner_early_stopping)
            if stage_best is not None and (
                    best_rec is None or
                    stage_best["throughput"] >= best_rec["throughput"]):
                best_rec = stage_best
                best_over = stage_best["config"]
        if best_rec is None:
            raise RuntimeError(
                "autotuning found no feasible configuration; "
                f"records: {self.results}")
        self._write_results(best_rec)
        return best_rec

    # ---------------------------------------------------------------- tune
    def tune(self) -> Dict[str, Any]:
        """Run the space; returns the best record (reference ``tune``:423).

        ``tuner_type``: "staged"/"model_based" run the v2 coordinate
        search over batch -> remat -> gas -> flash blocks; "gridsearch"
        keeps the reference-style stage x micro-batch grid.  Pruning: an
        OOM at micro batch m skips larger micros for the same stage;
        ``tuner_early_stopping`` consecutive non-improving trials end the
        search."""
        if self.cfg.tuner_type in ("staged", "model_based"):
            return self._tune_staged()
        pruned_stage_micro: Dict[int, int] = {}

        def _skip(overrides):
            stage = overrides["zero_optimization"]["stage"]
            micro = overrides["train_micro_batch_size_per_gpu"]
            return stage in pruned_stage_micro and \
                micro >= pruned_stage_micro[stage]

        def _run(overrides):
            rec = self._measure(overrides, None)
            if not rec.get("feasible") and rec.get("oom"):
                pruned_stage_micro[
                    overrides["zero_optimization"]["stage"]] = \
                    overrides["train_micro_batch_size_per_gpu"]
            return rec

        best = run_candidates(
            self.experiment_space(), _run,
            early_stopping=self.cfg.tuner_early_stopping, skip=_skip)
        if best is None:
            raise RuntimeError(
                "autotuning found no feasible configuration; "
                f"records: {self.results}")
        self._write_results(best)
        return best

    def _write_results(self, best) -> None:
        """Emit the shared artifact trio (``autotuning/report.py`` — the
        ranked table and exps schema are identical to the serving
        tuner's).  ``best_config.json`` stays a full merged DeepSpeed
        config, ``_model`` builder knobs alongside."""
        cfg = dict(self.base_config)
        cfg.pop("autotuning", None)
        model_over = best["config"].get("_model")
        cfg = _merge_overrides(
            cfg, {k: v for k, v in best["config"].items()
                  if k != "_model"})
        if model_over:
            cfg["_model"] = model_over  # builder knobs (GPT2Config etc.)
        report.write_results(self.cfg.results_dir, self.results, cfg)
        log_dist(f"autotuning: best {best['config']} at "
                 f"{best['throughput']:.1f} tok/s -> "
                 f"{self.cfg.results_dir}/best_config.json", ranks=[0])


def autotune(model_factory, base_config, batch_factory, seq_len=128):
    """One-call entry (the ``deepspeed --autotuning run`` analog)."""
    return Autotuner(model_factory, base_config, batch_factory,
                     seq_len).tune()
