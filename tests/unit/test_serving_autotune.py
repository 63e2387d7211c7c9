"""Serving autotuner tests (ISSUE 13 / ROADMAP item 5).

Covers the search core on a fake objective (rung sizes, top-1/eta
survival, budget accounting, determinism, resume-from-exps.json
mid-rung), trace record→replay determinism, constraint pruning counts,
the constraint↔ctor-validation audit (every ``space.py`` predicate has a
loud ``ServingEngine`` twin naming the knob), synthetic-trace fitting
against both a hand-built and a live telemetry snapshot, the
``stats()['config']`` round-trip, and a micro end-to-end
``tune_serving`` run with artifact checks.
"""

import json
import os

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.autotuning import (ModelGeom, ServingKnobSpace,
                                      ServingTrace, SuccessiveHalving,
                                      TraceRecorder, config_key, fit_trace,
                                      sessions_trace, tune_serving)
from deepspeed_tpu.autotuning.space import (BASE_SERVING_CONFIG,
                                            compile_budget, kv_pool_bytes,
                                            workload_space)
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2


# ------------------------------------------------------------ fixtures
@pytest.fixture(scope="module")
def tiny_engine():
    cfg = gpt2.GPT2Config.tiny(max_seq_len=256)
    deepspeed_tpu.comm.reset_topology()
    engine = deepspeed_tpu.init_inference(
        gpt2.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}})
    return engine, cfg


def _fake_objective(log=None):
    """Deterministic fake: score = 10*x + budget (ranking by x at every
    budget), even x infeasible."""
    def objective(config, budget):
        if log is not None:
            log.append((config["x"], budget))
        if config["x"] % 2 == 0:
            return {"feasible": False, "error": "even"}
        return {"feasible": True, "throughput": 10.0 * config["x"] + budget}
    return objective


# ------------------------------------------------- successive halving
def test_sh_rung_sizes_survival_and_budget_accounting(tmp_path):
    cands = [{"x": i} for i in range(8)]
    log = []
    sh = SuccessiveHalving(eta=2, min_budget=4, max_budget=16,
                           results_dir=str(tmp_path))
    out = sh.run(cands, _fake_objective(log))
    # rung 0: all 8 at budget 4 -> 4 feasible (odd x); keep ceil(4/2)=2
    # rung 1: 2 at budget 8; keep 1 -> rung 2 would be 1 survivor, but
    # budget doubles to 16 == max and runs, then stops
    assert [r["candidates"] for r in out["rungs"]] == [8, 2, 1]
    assert [r["budget"] for r in out["rungs"]] == [4, 8, 16]
    assert [r["feasible"] for r in out["rungs"]] == [4, 2, 1]
    # survivors of rung 0 are the top-1/eta by score: x = 7, 5
    assert sorted(x for x, b in log if b == 8) == [5, 7]
    assert [x for x, b in log if b == 16] == [7]
    assert out["best"]["config"] == {"x": 7}
    assert out["best"]["budget"] == 16
    assert out["trials_executed"] == 8 + 2 + 1
    assert out["budget_spent"] == 8 * 4 + 2 * 8 + 1 * 16
    # exps.json persisted every record
    exps = json.load(open(tmp_path / "exps.json"))
    assert len(exps) == out["trials_total"] == 11
    assert all("budget" in r and "stage" in r for r in exps)


def test_sh_deterministic():
    cands = [{"x": i} for i in range(6)]
    runs = []
    for _ in range(2):
        out = SuccessiveHalving(eta=2, min_budget=2, max_budget=8).run(
            cands, _fake_objective())
        runs.append([(config_key(r["config"]), r["budget"],
                      r.get("throughput")) for r in out["results"]])
    assert runs[0] == runs[1]


def test_sh_resume_mid_rung(tmp_path):
    cands = [{"x": i} for i in range(8)]
    # interrupted run: budget for 5 executed trials ends mid-rung-0
    log1 = []
    sh1 = SuccessiveHalving(eta=2, min_budget=4, max_budget=16,
                            max_trials=5, results_dir=str(tmp_path))
    out1 = sh1.run(cands, _fake_objective(log1))
    assert out1["exhausted"] and out1["trials_executed"] == 5
    assert len(json.load(open(tmp_path / "exps.json"))) == 5
    # resumed run replays the 5 persisted trials, executes only the rest
    log2 = []
    sh2 = SuccessiveHalving(eta=2, min_budget=4, max_budget=16,
                            results_dir=str(tmp_path))
    out2 = sh2.run(cands, _fake_objective(log2), resume=True)
    assert not out2["exhausted"]
    assert out2["trials_executed"] == 11 - 5
    assert out2["rungs"][0]["resumed"] == 5
    assert [x for x, b in log2 if b == 4] == [5, 6, 7]   # only the tail
    # and the final state matches an uninterrupted run
    clean = SuccessiveHalving(eta=2, min_budget=4, max_budget=16).run(
        cands, _fake_objective())
    strip = lambda rs: [(config_key(r["config"]), r["budget"],
                         r.get("throughput")) for r in rs]
    assert strip(out2["results"]) == strip(clean["results"])
    assert out2["best"]["config"] == clean["best"]["config"]


def test_sh_all_infeasible_returns_none():
    out = SuccessiveHalving(eta=2, min_budget=1, max_budget=2).run(
        [{"x": 0}, {"x": 2}], _fake_objective())
    assert out["best"] is None


# ------------------------------------------------------------- traces
def test_trace_determinism_slice_and_roundtrip(tmp_path):
    t = sessions_trace(12, vocab=512, seed=3, sessions=4, prefix_len=64)
    a = [t.prompt_for(i) for i in range(len(t))]
    b = [t.prompt_for(i) for i in range(len(t))]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    # same session -> same prefix; different sessions differ
    assert np.array_equal(a[0][:64], a[4][:64])
    assert not np.array_equal(a[0][:64], a[1][:64])
    # slice keeps entries and prompts identical
    s = t.slice(5)
    assert len(s) == 5
    assert all(np.array_equal(s.prompt_for(i), a[i]) for i in range(5))
    # JSON round-trip materializes the same tokens
    path = str(tmp_path / "trace.json")
    t.save(path)
    t2 = ServingTrace.load(path)
    assert len(t2) == len(t) and t2.sessions == t.sessions
    assert all(np.array_equal(t2.prompt_for(i), a[i])
               for i in range(len(t)))
    assert t2.working_set_tokens() == t.working_set_tokens()


def test_trace_v2_sampling_roundtrip_and_v1_load(tmp_path):
    from deepspeed_tpu.autotuning.trace import TRACE_VERSION, TraceEntry

    # v2: sampled traces carry per-request params + deterministic seeds
    t = sessions_trace(8, vocab=128, seed=5, temperature=0.8, top_k=20,
                       top_p=0.9)
    assert all(e.temperature == 0.8 and e.top_k == 20 and e.top_p == 0.9
               and e.seed > 0 for e in t.entries)
    # seeds are deterministic functions of the trace seed
    t_again = sessions_trace(8, vocab=128, seed=5, temperature=0.8,
                             top_k=20, top_p=0.9)
    assert [e.seed for e in t.entries] == [e.seed for e in t_again.entries]
    d = t.to_dict()
    assert d["version"] == TRACE_VERSION == 2
    t2 = ServingTrace.from_dict(json.loads(json.dumps(d)))
    for e, e2 in zip(t.entries, t2.entries):
        assert (e.temperature, e.top_k, e.top_p, e.seed) == \
            (e2.temperature, e2.top_k, e2.top_p, e2.seed)
    req = t2.requests()[0][0]
    assert req.temperature == 0.8 and req.top_k == 20 \
        and req.top_p == 0.9 and req.seed == t.entries[0].seed

    # greedy traces serialize WITHOUT the sampling keys — a committed
    # v1 BENCH trace and its v2 re-save are entry-for-entry identical
    g = sessions_trace(4, vocab=128, seed=5)
    for e in g.to_dict()["entries"]:
        assert not ({"temperature", "top_k", "top_p", "seed"} & set(e))

    # old-format (v1) files load and replay as greedy
    v1 = {"version": 1, "vocab": 128, "seed": 5, "prefix_len": 0,
          "meta": {}, "entries": [{"uid": 0, "max_new_tokens": 4,
                                   "prompt_len": 8}]}
    path = str(tmp_path / "v1.json")
    json.dump(v1, open(path, "w"))
    old = ServingTrace.load(path)
    e = old.entries[0]
    assert (e.temperature, e.top_k, e.top_p, e.seed) == (0.0, 0, 1.0, 0)
    assert not old.requests()[0][0].sampled


def test_trace_record_then_replay_same_tokens(tiny_engine):
    engine, cfg = tiny_engine
    trace = sessions_trace(6, vocab=cfg.vocab_size, seed=7, sessions=2,
                           prefix_len=32, tail_range=(8, 16),
                           new_range=(4, 8))
    kw = dict(slots=2, max_seq_len=trace.max_total_len(), block_size=8,
              prefill_chunk=16, debug_checks=True)
    srv = ServingEngine(engine, **kw)
    rec = TraceRecorder(vocab=cfg.vocab_size).attach(srv)
    outs = srv.serve([r for r, _ in trace.requests()], eos_token_id=7)
    rec.detach()
    assert srv._submit_observer is None and len(rec) == 6
    recorded = rec.trace()
    # recorded prompts match what was submitted, arrival order intact,
    # and the submit-time eos rides along (replay stops where the
    # recorded traffic did)
    for i, (req, _) in enumerate(trace.requests()):
        assert recorded.entries[i].uid == req.uid
        assert recorded.entries[i].eos_token_id == 7
        assert np.array_equal(recorded.prompt_for(i), req.prompt)
    # replaying the RECORDED trace on a fresh engine reproduces the
    # exact tokens (same trace -> same tokens), per-entry eos honored
    # through submit_all + the JSON round-trip
    recorded = ServingTrace.from_dict(recorded.to_dict())
    srv2 = ServingEngine(engine, **kw)
    handles = recorded.submit_all(srv2)
    while srv2.step():
        pass
    outs2 = {h.uid: h.result(timeout=0) for h in handles}
    assert set(outs) == set(outs2)
    assert all(np.array_equal(outs[u], outs2[u]) for u in outs)


def test_recorder_refuses_to_clobber_foreign_observer(tiny_engine):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                        prefill_chunk=16)
    srv._submit_observer = lambda *a, **k: None
    with pytest.raises(RuntimeError, match="observer"):
        TraceRecorder(vocab=cfg.vocab_size).attach(srv)
    with pytest.raises(TypeError, match="_submit_observer"):
        TraceRecorder(vocab=cfg.vocab_size).attach(object())


# ------------------------------------------------------- space pruning
def _geom():
    return ModelGeom(layers=2, kv_heads=4, head_dim=16, dtype_bytes=4)


def test_constraint_pruning_counts():
    geom = _geom()
    base = {"num_blocks": 40, "host_blocks": 8, "swap_batch": 4}
    # 40-block fp32 pool at block_size 32: 40 * 2*2*4*32*16*4 bytes
    ceiling = 40 * (2 * 2 * 4 * 32 * 16 * 4)
    space = ServingKnobSpace(
        geom, max_seq_len=256, base=base, mem_ceiling_bytes=ceiling,
        domains={"block_size": (32, 64),
                 "spec_tokens": (0, 4, 31),
                 "prefix_caching": (True, False)})
    cands = space.candidates()
    assert len(cands) == 2 * 3 * 2
    kept, pruned = space.prune(cands)
    # block_size=64 doubles block bytes past the ceiling: 6 candidates
    # pruned by memory.  Of the remaining block_size=32 half:
    # spec_tokens=31 kills both its variants (window > 16, first match)
    # and prefix_caching=False kills spec 0/4 (the host tier needs it).
    assert pruned["kv_pool_memory"] == 6
    assert pruned["spec_window"] == 2
    assert pruned["tiered_needs_prefix_cache"] == 2
    assert len(kept) + sum(pruned.values()) == len(cands)
    # every kept candidate passes every predicate
    assert all(not space.check(c) for c in kept)


def test_mem_sentinel_fills_ceiling_per_block_size():
    geom = _geom()
    ceiling = 20 * (2 * 2 * 4 * 32 * 16 * 4)       # 20 blocks at bs=32
    space = ServingKnobSpace(
        geom, max_seq_len=128, base={"num_blocks": "mem"},
        mem_ceiling_bytes=ceiling, domains={"block_size": (16, 32, 64)})
    by_bs = {c["block_size"]: c["num_blocks"]
             for c in space.candidates()}
    assert by_bs == {16: 40, 32: 20, 64: 10}
    for c in space.candidates():
        assert kv_pool_bytes(c, geom) <= ceiling


def test_compile_budget_mirror(tiny_engine):
    """space.compile_budget must agree with the ctor's sentry budget for
    every mode the space can emit."""
    engine, _ = tiny_engine
    cases = [
        dict(),
        dict(spec_tokens=4),                           # ngram spec
        dict(host_blocks=16, swap_batch=4),            # tiered
        dict(spec_tokens=4, host_blocks=16, swap_batch=4),
        # the prefill ladder: a program a rung
        dict(prefill_batch=1), dict(prefill_batch=6),
        dict(prefill_chunk=32), dict(max_seq_len=20),
        dict(host_blocks=16, swap_batch=4, resident_window_blocks=4),
    ]
    for kw in cases:
        kw = {**dict(slots=2, max_seq_len=64, block_size=8,
                     prefill_chunk=16), **kw}
        srv = ServingEngine(engine, **kw)
        cfg = {**BASE_SERVING_CONFIG, **kw}
        assert compile_budget(cfg) == srv.compile_budget, kw
        srv.close()


# ----------------------------- constraint <-> ctor validation audit
def test_every_constraint_has_a_loud_ctor_twin(tiny_engine):
    """A tuner-proposed config that slips past pruning must fail the
    ServingEngine ctor with a message naming the offending knob — one
    case per space.py predicate with a ctor-reachable violation
    (``test_serving_options.py`` holds every rule of ``options.EXCLUDES``
    to the same sentence on both sides)."""
    engine, _ = tiny_engine
    base = dict(slots=2, max_seq_len=64, block_size=8, prefill_chunk=16)
    cases = [
        # (space constraint, ctor kwargs, message fragment)
        ("spec_window", {**base, "spec_tokens": 31}, "spec_tokens"),
        ("tiered_needs_prefix_cache",
         {**base, "host_blocks": 8, "swap_batch": 4,
          "prefix_caching": False}, "prefix_caching"),
        ("swap_batch_bounds",
         {**base, "host_blocks": 4, "swap_batch": 8}, "swap_batch"),
        ("pool_min_blocks", {**base, "num_blocks": 4}, "num_blocks"),
        ("option_ranges", {**base, "slots": 0}, "slots"),
        ("option_ranges", {**base, "prefill_batch": 0}, "prefill_batch"),
        ("option_ranges", {**base, "block_size": 0}, "block_size"),
        # PR 17: disaggregated role + NVMe third tier
        ("role_needs_tiered_kv", {**base, "role": "prefill"},
         "host_blocks"),
        ("option_ranges", {**base, "role": "sideways"}, "role"),
        ("nvme_needs_host_tier", {**base, "nvme_blocks": 8},
         "host tier"),
        ("option_ranges",
         {**base, "host_blocks": 8, "swap_batch": 4, "nvme_blocks": 8,
          "nvme_high_watermark": 1.5}, "nvme_high_watermark"),
        ("nvme_watermark_window",
         {**base, "host_blocks": 8, "swap_batch": 4, "nvme_blocks": 8,
          "nvme_high_watermark": 0.2}, "watermark budget"),
        # PR 19: long-context lane — sp prefill + resident window
        # (tiny_engine carries no sp mesh axis, so the ctor's loud sp
        # failure is the mesh-shape check; the space predicate prunes
        # the same config on its chunk-divisibility rule)
        ("sp_prefill_exclusive", {**base, "sp": 3}, "sp=3"),
        ("resident_window_span",
         {**base, "resident_window_blocks": 4, "swap_batch": 4},
         "host_blocks"),
        ("resident_window_span",
         {**base, "resident_window_blocks": 2, "host_blocks": 8,
          "swap_batch": 4}, "must be >= 3"),
        ("resident_window_span",
         {**base, "resident_window_blocks": 8, "host_blocks": 8,
          "swap_batch": 4, "spec_tokens": 2}, "speculative"),
        ("pool_min_blocks",
         {**base, "resident_window_blocks": 4, "host_blocks": 8,
          "swap_batch": 4, "num_blocks": 5}, "resident"),
        # PR 20: on-device sampling stack + constrained decoding
        ("logit_masks_excludes_dp_tp",
         {**base, "logit_masks": True, "sampling": False},
         "sampling"),
        ("logit_masks_excludes_dp_tp",
         {**base, "logit_masks": True, "engine_mode": "dp_tp",
          "prefix_caching": False}, "logit_masks"),
    ]
    for name, kwargs, fragment in cases:
        with pytest.raises(ValueError, match=fragment):
            ServingEngine(engine, **kwargs)
        # and the space predicate agrees the config is inadmissible
        space = ServingKnobSpace(_geom(), max_seq_len=64)
        cfg = {**BASE_SERVING_CONFIG, **kwargs}
        cfg.pop("draft", None)
        assert any(n == name for n, _ in space.check(cfg)), name


def test_prefill_ratio_constraint_has_router_twins(tiny_engine):
    """``prefill_decode_ratio`` lives at the FLEET layer, so its loud
    twins are ``plan_roles`` (the launcher/init_router assignment) and
    the ``ReplicaRouter`` ctor (a hand-built all-prefill fleet), not the
    engine ctor."""
    from deepspeed_tpu.serving import ReplicaRouter, plan_roles

    engine, _ = tiny_engine
    space = ServingKnobSpace(_geom(), max_seq_len=64)
    cfg = {**BASE_SERVING_CONFIG, "max_seq_len": 64, "replicas": 2,
           "prefill_workers": 2, "host_blocks": 8}
    assert any(n == "prefill_decode_ratio" for n, _ in space.check(cfg))
    with pytest.raises(ValueError,
                       match="prefill_workers:decode_workers ratio"):
        plan_roles(2, 2)
    # a disaggregated fleet without host_blocks is inadmissible too
    cfg2 = {**BASE_SERVING_CONFIG, "max_seq_len": 64, "replicas": 2,
            "prefill_workers": 1}
    assert any(n == "prefill_decode_ratio" for n, _ in space.check(cfg2))
    # hand-built fleet twins: one-sided roles, and kv_pull=False
    mk = lambda role: ServingEngine(  # noqa: E731
        engine, slots=2, max_seq_len=64, block_size=8, prefill_chunk=16,
        host_blocks=8, swap_batch=4, role=role)
    with pytest.raises(ValueError, match="ratio must keep at least one"):
        ReplicaRouter([mk("prefill"), mk("prefill")])
    with pytest.raises(ValueError, match="kv_pull"):
        ReplicaRouter([mk("prefill"), mk("decode")], kv_pull=False)


# ---------------------------------------------------------- fitting
def test_fit_trace_recovers_handmade_snapshot():
    """Exact-arithmetic fit: 24 requests over 6 sessions of 64-token
    prefixes (block 32), mean prompt 96, mean decode 10."""
    n, sessions, prefix, mean_prompt, mean_new = 24, 6, 64, 96.0, 10.0
    hit = (1 - sessions / n) * prefix / mean_prompt
    snap = {
        "serving_requests_admitted_total": {
            "series": [{"labels": {}, "value": n}]},
        "serving_requests_finished_total": {
            "series": [{"labels": {}, "value": n}]},
        "serving_prompt_tokens_total": {
            "series": [{"labels": {}, "value": n * mean_prompt}]},
        "serving_prefix_hit_tokens_total": {
            "series": [{"labels": {}, "value": hit * n * mean_prompt}]},
        "serving_generated_tokens_total": {
            "series": [{"labels": {}, "value": n * mean_new}]},
        "serving_slo_requests_total": {
            "series": [{"labels": {"slo_class": "interactive"},
                        "value": 2 * n / 3},
                       {"labels": {"slo_class": "batch"},
                        "value": n / 3}]},
    }
    t = fit_trace(snap, vocab=512, n_requests=n, seed=0, block_size=32)
    assert t.meta["fitted_sessions"] == sessions
    assert t.meta["fitted_prefix_len"] == prefix
    assert t.sessions == sessions and t.prefix_len == prefix
    plens = [t.prompt_for(i).size for i in range(n)]
    assert abs(np.mean(plens) - mean_prompt) / mean_prompt < 0.15
    mnews = [e.max_new_tokens for e in t.entries]
    assert abs(np.mean(mnews) - mean_new) / mean_new < 0.15
    classes = [e.slo_class for e in t.entries]
    assert classes.count("interactive") == 16
    assert classes.count("batch") == 8


def test_fit_trace_from_live_snapshot(tiny_engine):
    """Fit against a REAL engine's registry after a known sessions
    trace: the fitted structure lands near the ground truth."""
    engine, cfg = tiny_engine
    truth = sessions_trace(18, vocab=cfg.vocab_size, seed=11, sessions=6,
                           prefix_len=64, tail_range=(8, 24),
                           new_range=(4, 8))
    # unpressured pool: the trie must retain every session chain, or
    # LRU eviction suppresses the hit rate the fit reads (the fitter
    # models the cache-retaining steady state)
    srv = ServingEngine(engine, slots=4,
                        max_seq_len=truth.max_total_len(), block_size=16,
                        num_blocks=160, prefill_chunk=32)
    srv.serve([r for r, _ in truth.requests()])
    fitted = fit_trace(srv.metrics.snapshot(), vocab=cfg.vocab_size,
                       n_requests=18, seed=11, block_size=16)
    assert 0 < fitted.sessions <= 18
    assert abs(fitted.sessions - 6) <= 3
    assert fitted.prefix_len % 16 == 0
    assert 32 <= fitted.prefix_len <= 80
    mean_p = np.mean([fitted.prompt_for(i).size for i in range(18)])
    truth_p = np.mean([truth.prompt_for(i).size for i in range(18)])
    assert abs(mean_p - truth_p) / truth_p < 0.25


def test_fit_trace_empty_snapshot_raises():
    with pytest.raises(ValueError, match="nothing to fit"):
        fit_trace({}, vocab=512)


# --------------------------------------------------- config round-trip
def test_resolved_config_roundtrips_through_init_serving(tiny_engine):
    _, cfg = tiny_engine
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        gpt2.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
        slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
        spec_tokens=2, host_blocks=12, swap_batch=4)
    rc = srv.stats()["config"]
    assert rc == srv.resolved_config()
    json.dumps(rc)                       # artifact-ready
    deepspeed_tpu.comm.reset_topology()
    srv2 = deepspeed_tpu.init_serving(
        gpt2.build(cfg), config={"dtype": "fp32"}, **rc)
    # rebuilt engine resolves to the identical config (fixpoint)
    assert srv2.resolved_config() == rc


# ------------------------------------------------------ micro e2e tune
def test_tune_serving_micro_end_to_end(tmp_path, tiny_engine):
    engine, cfg = tiny_engine
    trace = sessions_trace(8, vocab=cfg.vocab_size, seed=5, sessions=3,
                           prefix_len=32, tail_range=(8, 16),
                           new_range=(4, 8))
    space = workload_space(
        ModelGeom.from_engine(engine), trace, pool_frac=0.5,
        base={"slots": 3, "block_size": 16, "prefill_chunk": 32},
        domains={"spec_tokens": (0, 2), "host_blocks": (0, "ws")})
    rd = str(tmp_path / "results")
    summary = tune_serving(engine, trace, space=space, min_budget=4,
                           results_dir=rd)
    assert summary["admissible"] == 4
    assert summary["winner"]["measured_tok_s"] > 0
    assert summary["default"]["measured_tok_s"] > 0
    # every feasible trial was parity-gated exact and sentry-clean
    exps = json.load(open(os.path.join(rd, "exps.json")))
    assert all(r.get("token_match") == 1.0
               for r in exps if r.get("feasible"))
    report = open(os.path.join(rd, "report.md")).read()
    assert "| rank |" in report and "tok/s" in report
    assert "Predicted vs measured" in report
    best = json.load(open(os.path.join(rd, "best_config.json")))
    assert best == summary["best_config"]
    # the artifact is ready-to-pass init_serving kwargs
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        gpt2.build(cfg), config={"dtype": "fp32"}, **best)
    outs = srv.serve([r for r, _ in trace.slice(3).requests()])
    assert len(outs) == 3
