"""Black-box flight recorder (telemetry/incident.py, ISSUE 18):
trigger classes, atomic bundle structure, deterministic replay
(token-exact, fp32 + kv8), the stall watchdog, and the windowed
burn-rate signal it polls.

The real-fleet lanes (crash -> bundle -> replay) run once on a
module-scoped 2-replica tiny fleet; everything else drives the
recorder/watchdog deterministically through injected clocks and
duck-typed fakes (the ``test_replica_router.py`` idiom)."""

import json
import os
import threading

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.invariants import (PagedStateError,
                                               audit_incident_bundle)
from deepspeed_tpu.analysis.sentry import RetraceError
from deepspeed_tpu.autotuning.trace import TraceRecorder
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving import FaultPlan, ReplicaRouter
from deepspeed_tpu.telemetry.incident import (MANIFEST_KEYS,
                                              TRIGGER_KINDS,
                                              IncidentRecorder,
                                              StallWatchdog,
                                              gpt2_model_meta, is_bundle,
                                              load_bundle, replay_bundle)
from deepspeed_tpu.telemetry.metrics import MetricsRegistry
from deepspeed_tpu.telemetry.slo import (SLOTracker, merged_slo_report,
                                         merged_windowed_burn)
from deepspeed_tpu.telemetry.trace import TraceTimeline


CFG = gpt2.GPT2Config.tiny(max_seq_len=128)


def _mk_fleet(n=2, quantize=None, threaded=False, **router_kw):
    deepspeed_tpu.comm.reset_topology()
    srvs, params = [], None
    for _ in range(n):
        eng = deepspeed_tpu.init_inference(
            gpt2.build(CFG),
            config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
            params=params)
        params = eng.params
        kw = dict(slots=2, max_seq_len=64, block_size=8,
                  prefill_chunk=16)
        if quantize:
            kw["quantize"] = quantize
        srvs.append(ServingEngine(eng, **kw))
    return ReplicaRouter(srvs, threaded=threaded, **router_kw)


def _reqs(n=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=f"u{i}",
                    prompt=rng.integers(0, CFG.vocab_size, 9 + i % 3),
                    max_new_tokens=4) for i in range(n)]


@pytest.fixture(scope="module")
def crashed(tmp_path_factory):
    """One recorded crash: 2-replica fleet, seeded kill at iteration 3,
    recorder armed -> (bundle_path, finished token streams)."""
    out = tmp_path_factory.mktemp("bundles")
    router = _mk_fleet()
    rec = IncidentRecorder(str(out), vocab=CFG.vocab_size,
                           model_meta=gpt2_model_meta(CFG))
    rec.attach(router)
    router.arm_faults(FaultPlan(
        seed=7, crashes=[{"replica": 1, "at_step": 3}]))
    handles = [router.submit(r) for r in _reqs()]
    while router.step():
        pass
    rec.detach()
    outs = {h.uid: h.tokens() for h in handles}
    assert len(rec.bundles) == 1
    return rec.bundles[0], outs


# ----------------------------------------------------------- bundle shape
def test_crash_dumps_audited_bundle(crashed):
    bpath, _ = crashed
    assert is_bundle(bpath)
    audit_incident_bundle(bpath)        # raises PagedStateError on rot
    b = load_bundle(bpath)
    m = b["manifest"]
    assert set(m) == MANIFEST_KEYS
    assert m["trigger"]["kind"] == "replica_fail"
    assert m["trigger"]["replica"] == 1
    assert m["trigger"]["step"] == 3
    assert m["trigger"]["exception_type"] == "SimulatedCrash"
    assert m["replayable"] is True
    # the capture carries every submitted request and the fault plan
    assert len(b["request_trace"]["entries"]) == 6
    assert b["fault_plan"]["crashes"] == [{"replica": 1, "at_step": 3}]
    assert b["fault_report"]["seed"] == 7
    # per-replica resolved configs rebuild engines (replay's input)
    assert len(b["replica_configs"]) == 2
    assert all("slots" in c for c in b["replica_configs"])


def test_bundle_files_match_manifest(crashed):
    bpath, _ = crashed
    m = load_bundle(bpath)["manifest"]
    assert sorted(m["files"]) == sorted(os.listdir(bpath))


def test_progress_snapshot_is_pre_incident(crashed):
    bpath, outs = crashed
    prog = load_bundle(bpath)["progress"]
    assert set(prog) == {f"u{i}" for i in range(6)}
    for uid, entry in prog.items():
        # dumped at the fail hook: a prefix of the final stream (KV
        # salvage + re-home never rewrites already-committed tokens)
        assert entry["tokens"] == outs[uid][:len(entry["tokens"])]


def test_partial_tmp_dir_is_never_a_bundle(tmp_path):
    tmp = tmp_path / ".incident-001-replica_fail.tmp-123"
    tmp.mkdir()
    (tmp / "router_stats.json").write_text("{}")
    assert not is_bundle(str(tmp))
    done = tmp_path / "incident-002-replica_fail"
    done.mkdir()
    (done / "manifest.json").write_text(json.dumps(
        {"bundle_format": "something-else", "schema_version": 1}))
    assert not is_bundle(str(done))
    (done / "manifest.json").write_text("not json {")
    assert not is_bundle(str(done))
    with pytest.raises(ValueError, match="not a complete"):
        load_bundle(str(done))


def test_bundle_of_another_schema_version_is_refused_by_version(tmp_path):
    """A version-1 bundle's replica configs carry options the engine no
    longer takes: it is refused whole, by version, not read with keys
    stripped."""
    from deepspeed_tpu.telemetry.incident import (BUNDLE_FORMAT,
                                                  BUNDLE_SCHEMA_VERSION)

    old = tmp_path / "incident-001-replica_fail"
    old.mkdir()
    (old / "manifest.json").write_text(json.dumps(
        {"bundle_format": BUNDLE_FORMAT,
         "schema_version": BUNDLE_SCHEMA_VERSION - 1}))
    assert not is_bundle(str(old))
    with pytest.raises(ValueError, match="schema_version 1"):
        load_bundle(str(old))


def test_audit_rejects_missing_file(crashed, tmp_path):
    import shutil
    bpath, _ = crashed
    broken = tmp_path / "broken"
    shutil.copytree(bpath, broken)
    os.unlink(broken / "request_trace.json")
    with pytest.raises(PagedStateError, match="bundle-file-list"):
        audit_incident_bundle(str(broken))


# ---------------------------------------------------------------- replay
def test_replay_reproduces_trigger_and_tokens(crashed):
    bpath, _ = crashed
    report = replay_bundle(bpath)
    assert report["reproduced"], report["mismatches"]
    assert report["trigger"]["kind"] == "replica_fail"
    assert report["trigger"]["replica"] == 1
    assert report["trigger"]["step"] == 3
    assert report["uids"] == 6


@pytest.mark.slow
def test_replay_kv8_lane(tmp_path):
    """A kv8 fleet's crash bundle replays bit-exactly too: the resolved
    configs carry ``quantize``, so the rebuilt fleet quantizes the same
    pools the original did."""
    router = _mk_fleet(quantize="kv8")
    rec = IncidentRecorder(str(tmp_path), vocab=CFG.vocab_size,
                           model_meta=gpt2_model_meta(CFG))
    rec.attach(router)
    router.arm_faults(FaultPlan(
        seed=11, crashes=[{"replica": 1, "at_step": 3}]))
    for r in _reqs(4, seed=1):
        router.submit(r)
    while router.step():
        pass
    rec.detach()
    assert len(rec.bundles) == 1
    assert load_bundle(rec.bundles[0])["replica_configs"][0][
        "quantize"] == "kv8"
    report = replay_bundle(rec.bundles[0])
    assert report["reproduced"], report["mismatches"]


def test_replay_refuses_non_replayable(tmp_path):
    router = _FakeRouter()
    rec = IncidentRecorder(str(tmp_path))   # no vocab => no capture
    path = rec.dump(router, "watchdog_stall", detail={"outstanding": 1},
                    stacks="--- thread MainThread\n", lockless=True)
    assert is_bundle(path)
    assert load_bundle(path)["manifest"]["replayable"] is False
    with pytest.raises(ValueError, match="not replayable"):
        replay_bundle(path)


# ------------------------------------------------------- trigger classes
class _FakeHandle:
    def __init__(self, uid, status="active", tokens=()):
        self.uid = uid
        self.status = status
        self._tokens = list(tokens)


class _FakeReplica:
    def __init__(self):
        self.iterations = 0
        self._c_checksum_fail = type("C", (), {"value": 0.0})()
        self._slo = None


class _FakeRouter:
    """Duck-typed dump/watchdog target: the recorder's gather sections
    degrade into ``gather_errors`` on whatever surface is missing — the
    bundle still lands atomically (partial beats none)."""

    def __init__(self, n=2):
        self.replicas = [_FakeReplica() for _ in range(n)]
        self.metrics = MetricsRegistry()
        self.timeline = TraceTimeline(capacity=64)
        self._handles = {}
        self._injector = None
        self._worker_errors = {}
        self._failed = set()
        self._drained = set()
        self._incident = None
        self._lock = threading.RLock()

    def _all_locks(self):
        return self._lock

    def stats(self):
        return {"replicas": len(self.replicas)}

    def resolved_config(self):
        return {"threaded": False}


def test_trigger_classification_per_exception(tmp_path):
    router = _FakeRouter()
    rec = IncidentRecorder(str(tmp_path), cooldown_s=0.0, max_bundles=8)
    rec.attach(router)
    rec.on_engine_error(router, 0, PagedStateError("x", "detail"))
    rec.on_engine_error(router, 1, RetraceError("budget", name="decode"))
    rec.on_replica_fail(router, 0, RuntimeError("worker died"))
    kinds = [os.path.basename(p).split("-", 2)[2] for p in rec.bundles]
    assert kinds == ["invariant_violation", "retrace", "replica_fail"]
    for p, kind in zip(rec.bundles, kinds):
        m = load_bundle(p)["manifest"]
        assert m["trigger"]["kind"] == kind
        assert kind in TRIGGER_KINDS
        audit_incident_bundle(p)
    assert int(router.metrics.counter(
        "serving_incident_bundles_total").value) == 3
    rec.detach()
    assert router._incident is None


def test_checksum_burst_trigger(tmp_path):
    t = {"now": 0.0}
    router = _FakeRouter()
    rec = IncidentRecorder(str(tmp_path), checksum_burst=8,
                           checksum_window_s=2.0, cooldown_s=0.0,
                           poll_min_s=0.0, clock=lambda: t["now"])
    rec.attach(router)
    rec.on_step_poll(router)            # baseline sample
    t["now"] = 0.5
    router.replicas[0]._c_checksum_fail.value = 5
    rec.on_step_poll(router)
    assert rec.bundles == []            # 5 < 8 in window
    t["now"] = 1.0
    router.replicas[1]._c_checksum_fail.value = 4
    rec.on_step_poll(router)            # 9 failures in 1s
    assert len(rec.bundles) == 1
    trig = load_bundle(rec.bundles[0])["manifest"]["trigger"]
    assert trig["kind"] == "checksum_burst"
    assert trig["detail"]["failures_in_window"] == 9


def test_burn_rate_breach_trigger(tmp_path):
    t = {"now": 100.0}
    clock = lambda: t["now"]  # noqa: E731
    router = _FakeRouter()
    tr = SLOTracker(MetricsRegistry(), clock=clock)
    router.replicas[0]._slo = tr
    rec = IncidentRecorder(str(tmp_path), burn_threshold=10.0,
                           burn_window_s=10.0, burn_min_requests=4,
                           cooldown_s=0.0, poll_min_s=0.0, clock=clock)
    rec.attach(router)
    for _ in range(4):                  # all miss the realtime TTFT SLO
        tr.observe("realtime", ttft_s=10.0, tpot_s=1.0)
    rec.on_step_poll(router)
    assert len(rec.bundles) == 1
    trig = load_bundle(rec.bundles[0])["manifest"]["trigger"]
    assert trig["kind"] == "burn_rate_breach"
    assert trig["detail"]["slo_class"] == "realtime"


def test_cooldown_and_max_bundles(tmp_path):
    t = {"now": 0.0}
    router = _FakeRouter()
    rec = IncidentRecorder(str(tmp_path), cooldown_s=30.0, max_bundles=2,
                           clock=lambda: t["now"])
    rec.attach(router)
    assert rec.dump(router, "replica_fail", replica=0) is not None
    assert rec.dump(router, "replica_fail", replica=0) is None  # cooldown
    t["now"] = 31.0
    assert rec.dump(router, "replica_fail", replica=0) is not None
    t["now"] = 62.0
    assert rec.dump(router, "replica_fail", replica=0) is None  # cap
    assert len(rec.bundles) == 2
    with pytest.raises(ValueError, match="unknown trigger kind"):
        rec.dump(router, "nonsense")


def test_foreign_recorder_attach_rejected(tmp_path):
    router = _FakeRouter()
    IncidentRecorder(str(tmp_path / "a")).attach(router)
    with pytest.raises(RuntimeError, match="already has an incident"):
        IncidentRecorder(str(tmp_path / "b")).attach(router)
    with pytest.raises(TypeError, match="no _incident hook"):
        IncidentRecorder(str(tmp_path / "c")).attach(object())


# --------------------------------------------------------------- watchdog
def test_watchdog_fires_once_on_stalled_fake():
    t = {"now": 0.0}
    router = _FakeRouter()
    router._handles["u0"] = (_FakeHandle("u0"), 0)
    wd = StallWatchdog(router, deadline_s=5.0, poll_s=0.1,
                       clock=lambda: t["now"])
    assert wd.check() is False          # fresh: nothing aged yet
    t["now"] = 6.0
    assert wd.check() is True           # aged + frozen past deadline
    assert wd.stalls == 1
    t["now"] = 12.0
    assert wd.check() is False          # once per episode
    assert wd.stalls == 1
    evs = [e for e in router.timeline.events()
           if e["name"] == "watchdog_stall"]
    assert len(evs) == 1 and evs[0]["args"]["outstanding"] == 1
    assert int(router.metrics.counter(
        "serving_watchdog_stalls_total").value) == 1


def test_watchdog_rearms_after_progress_and_stays_quiet_when_healthy():
    t = {"now": 0.0}
    router = _FakeRouter()
    h = _FakeHandle("u0")
    router._handles["u0"] = (h, 0)
    wd = StallWatchdog(router, deadline_s=5.0, clock=lambda: t["now"])
    wd.check()
    # healthy: progress every tick (tokens stream, iterations move)
    for i in range(1, 20):
        t["now"] = float(i)
        h._tokens.append(i)
        router.replicas[0].iterations += 1
        assert wd.check() is False
    assert wd.stalls == 0
    # then the fleet wedges: fires once the signal freezes past deadline
    t["now"] = 30.0
    assert wd.check() is True
    # progress resumes -> episode ends -> a later stall fires AGAIN
    t["now"] = 31.0
    h._tokens.append(99)
    assert wd.check() is False
    t["now"] = 40.0
    assert wd.check() is True
    assert wd.stalls == 2


def test_watchdog_dumps_stall_bundle_with_stacks(tmp_path):
    t = {"now": 0.0}
    router = _FakeRouter()
    router._handles["u0"] = (_FakeHandle("u0"), 0)
    rec = IncidentRecorder(str(tmp_path), clock=lambda: t["now"])
    rec.attach(router)
    wd = StallWatchdog(router, deadline_s=1.0, recorder=rec,
                       clock=lambda: t["now"])
    wd.check()
    t["now"] = 2.0
    assert wd.check() is True
    assert len(rec.bundles) == 1
    b = load_bundle(rec.bundles[0])
    assert b["manifest"]["trigger"]["kind"] == "watchdog_stall"
    assert "MainThread" in b["threads"]
    assert b["manifest"]["trigger"]["detail"]["outstanding"] == 1
    audit_incident_bundle(rec.bundles[0])


@pytest.mark.slow
def test_watchdog_silent_on_healthy_threaded_fleet():
    router = _mk_fleet(n=1, threaded=True)
    router.start()
    wd = StallWatchdog(router, deadline_s=15.0, poll_s=0.02).start()
    try:
        outs = router.serve(_reqs(4, seed=2))
        assert all(v is not None for v in outs.values())
        assert wd.stalls == 0
    finally:
        wd.stop()
        router.stop()


# ------------------------------------------------- supporting subsystems
def test_trace_recorder_chain_preserves_foreign_observer():
    calls = []

    class _Target:
        _submit_observer = None

    tgt = _Target()
    tgt._submit_observer = lambda req, **kw: calls.append(req.uid)
    tr = TraceRecorder(512)
    tr.attach(tgt, chain=True)
    req = Request(uid="c0", prompt=np.array([1, 2, 3]), max_new_tokens=2)
    tgt._submit_observer(req, priority=1, slo_class="batch")
    assert calls == ["c0"]                       # incumbent fired first
    assert tr.entries[0].uid == "c0"
    assert tr.entries[0].slo_class == "batch"
    tr.detach()
    tgt._submit_observer(req, priority=0)
    assert calls == ["c0", "c0"]                 # restored, not wrapped
    assert len(tr.entries) == 1
    # without chain=True a foreign observer still refuses loudly
    with pytest.raises(RuntimeError, match="chain=True"):
        TraceRecorder(512).attach(tgt)


def test_windowed_burn_decays_where_cumulative_never_does():
    t = {"now": 1000.0}
    tr = SLOTracker(MetricsRegistry(), window_s=60.0,
                    clock=lambda: t["now"])
    tr.observe("realtime", ttft_s=10.0, tpot_s=10.0)     # total miss
    w = tr.windowed_burn()["realtime"]
    assert w["ttft_burn_rate"] > 1.0 and w["requests"] == 1
    t["now"] += 30.0
    for _ in range(3):
        tr.observe("realtime", ttft_s=0.0, tpot_s=0.0)   # recovered
    t["now"] += 45.0            # the miss ages out of the window
    w = tr.windowed_burn()["realtime"]
    assert w["ttft_burn_rate"] == 0.0 and w["requests"] == 3
    # cumulative burn still remembers the miss (1/4 missed)
    cum = merged_slo_report([tr])["realtime"]["ttft_burn_rate"]
    assert cum > 0.0
    # empty window: no traffic, no burn, attainment undefined
    t["now"] += 120.0
    w = tr.windowed_burn()["realtime"]
    assert w["requests"] == 0 and w["ttft_attainment"] is None


def test_merged_windowed_burn_sums_trackers():
    t = {"now": 0.0}
    a = SLOTracker(MetricsRegistry(), window_s=60.0,
                   clock=lambda: t["now"])
    b = SLOTracker(MetricsRegistry(), window_s=60.0,
                   clock=lambda: t["now"])
    a.observe("batch", ttft_s=0.0, tpot_s=0.0)
    b.observe("batch", ttft_s=1e9, tpot_s=0.0)
    m = merged_windowed_burn([a, b])["batch"]
    assert m["requests"] == 2
    assert m["ttft_attainment"] == 0.5
