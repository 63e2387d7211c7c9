"""Telemetry layer (``deepspeed_tpu/telemetry/``): metrics registry,
streaming-histogram quantile accuracy, Chrome trace_event export schema,
and the engine wiring.

Tier-1 (fast) coverage:
 - registry units: counter/gauge/histogram cells, label series identity,
   type-conflict rejection, Prometheus text exposition shape, JSON
   snapshot serializability, ``to_events`` monitor routing.
 - histogram quantiles: p50/p95/p99 against ``np.percentile`` on known
   distributions, within one bucket width (the documented accuracy
   contract); monotone in q; empty/overflow edges.
 - trace timeline: bounded ring + dropped accounting, ``capacity=0``
   no-op mode, span/instant/complete emission, ``validate_chrome_trace``
   accepting exports and rejecting seeded schema violations.
 - ``ServingEngine``: ``stats()`` keys byte-for-byte backed by the
   registry, per-request spans + scheduler/sentry/audit events in
   ``dump_trace`` output, ``serve(profile_dir=)``, spec-decode events.
 - ``DeepSpeedEngine``: loss/lr/throughput gauges + wall-clock timer
   histograms routed through the MonitorMaster CSV backend to disk.
"""

import json
import os

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import (DEFAULT_TIME_BUCKETS_S, Histogram,
                                     MetricsRegistry, TraceTimeline,
                                     validate_chrome_trace)
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2


# ---------------------------------------------------------------- registry
def test_counter_gauge_basics_and_type_conflicts():
    r = MetricsRegistry()
    c = r.counter("reqs_total", "requests")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError, match=">= 0"):
        c.inc(-1)
    g = r.gauge("blocks_in_use")
    g.set(7)
    g.add(-2)
    assert g.value == 5
    # one name, one type — a silent re-kind is two subsystems colliding
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("reqs_total")
    # get-or-create returns the SAME cell
    assert r.counter("reqs_total") is c


def test_histogram_bucket_conflict_rejected():
    r = MetricsRegistry()
    h = r.histogram("x_ms", buckets=(1.0, 10.0))
    assert r.histogram("x_ms", buckets=(1.0, 10.0)) is h   # same scale: ok
    with pytest.raises(ValueError, match="already exists with buckets"):
        r.histogram("x_ms", buckets=(100.0, 1000.0))


def test_timer_elapsed_probe_keeps_one_histogram_sample():
    """SynchronizedWallClockTimer.log()/elapsed() probing a RUNNING timer
    must not split its interval into two histogram observations."""
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer

    r = MetricsRegistry()
    timers = SynchronizedWallClockTimer(registry=r)
    t = timers("fwd")
    t.start()
    t.elapsed(reset=False)                # mid-interval probe
    t.stop()
    h = r.snapshot()["train_wall_clock_ms"]["series"][0]
    assert h["count"] == 1                # one logical interval, one sample


def test_registry_label_series_identity():
    r = MetricsRegistry()
    a = r.counter("hits_total", family="gpt2")
    b = r.counter("hits_total", family="llama")
    assert a is not b
    assert r.counter("hits_total", family="gpt2") is a
    a.inc(2)
    b.inc(5)
    snap = r.snapshot()["hits_total"]
    by_label = {s["labels"]["family"]: s["value"] for s in snap["series"]}
    assert by_label == {"gpt2": 2, "llama": 5}


def test_prometheus_text_exposition_shape():
    r = MetricsRegistry()
    r.counter("c_total", "help text").inc(2)
    h = r.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = r.prometheus_text()
    assert "# HELP c_total help text" in text
    assert "# TYPE c_total counter" in text
    assert "c_total 2.0" in text
    assert "# TYPE lat_seconds histogram" in text
    # cumulative le buckets ending at +Inf == count, plus _sum/_count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1.0"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    # snapshot is JSON-able as-is (the --emit-metrics artifact)
    json.dumps(r.snapshot())


def test_registry_to_events_monitor_routing():
    r = MetricsRegistry()
    r.gauge("train_loss", monitor_name="Train/Samples/train_loss").set(1.5)
    h = r.histogram("step_ms", buckets=(1.0, 10.0), timer="fwd")
    h.observe(2.0)
    r.histogram("empty_ms", buckets=(1.0,))       # no samples: no events
    events = {name: v for name, v, _ in r.to_events(step=7)}
    assert events["Train/Samples/train_loss"] == 1.5
    assert events["step_ms/fwd_count"] == 1.0
    assert "step_ms/fwd_p50" in events and "step_ms/fwd_p95" in events
    assert not any(n.startswith("empty_ms") for n in events)
    assert all(s == 7 for _, _, s in r.to_events(step=7))


# -------------------------------------------------------------- histograms
@pytest.mark.parametrize("dist", ["uniform", "normal", "exponential"])
def test_histogram_quantiles_within_one_bucket_width(dist):
    """The accuracy contract: p50/p95/p99 within one bucket width of
    ``np.percentile`` on known distributions."""
    rng = np.random.default_rng(0)
    if dist == "uniform":
        vals = rng.uniform(0.0, 100.0, 4000)
    elif dist == "normal":
        vals = np.clip(rng.normal(50.0, 15.0, 4000), 0.0, None)
    else:
        vals = rng.exponential(20.0, 4000)
    width = 4.0
    h = Histogram(bounds=[width * i for i in range(1, 64)])
    for v in vals:
        h.observe(v)
    for q in (50, 95, 99):
        est = h.quantile(q / 100)
        ref = float(np.percentile(vals, q))
        assert abs(est - ref) <= width, (dist, q, est, ref)
    # monotone in q
    qs = [h.quantile(q) for q in (0.1, 0.5, 0.9, 0.95, 0.99)]
    assert qs == sorted(qs)


def test_histogram_edges():
    h = Histogram(bounds=(1.0, 2.0))
    assert h.quantile(0.5) is None and h.mean() is None
    h.observe(10.0)                       # overflow clamps to last edge
    assert h.quantile(0.99) == 2.0
    assert h.bucket_counts() == [(1.0, 0), (2.0, 0), (float("inf"), 1)]
    with pytest.raises(ValueError, match="ascending"):
        Histogram(bounds=(2.0, 1.0))
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        h.quantile(1.5)
    # defaults cover sub-ms..minute latencies
    assert DEFAULT_TIME_BUCKETS_S[0] <= 1e-4 < 60 <= DEFAULT_TIME_BUCKETS_S[-1]


# ---------------------------------------------------------------- timeline
def test_timeline_ring_bounds_and_disabled_mode():
    t = TraceTimeline(capacity=4)
    for i in range(7):
        t.instant(f"e{i}")
    assert len(t) == 4 and t.dropped == 3 and t.emitted == 7
    assert [e["name"] for e in t.events()] == ["e3", "e4", "e5", "e6"]

    off = TraceTimeline(capacity=0)
    assert not off.enabled
    off.instant("x")
    off.complete("y", 0.0)
    with off.span("z"):
        pass
    assert len(off) == 0 and off.emitted == 0


def test_timeline_span_and_chrome_export_schema():
    t = TraceTimeline(capacity=64, pid=3)
    tid = t.thread("req a")
    with t.span("work", tid=tid, k=1):
        t.instant("inside")
    t.complete("req a", 0.0, tid=tid, uid="a")
    doc = t.to_chrome(process_name="test")
    json.dumps(doc)                       # valid JSON document
    summary = validate_chrome_trace(doc)
    assert summary["complete"] == 2 and summary["instant"] == 1
    assert summary["request_spans"] == 1
    names = [e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"]
    assert "test" in names and "req a" in names and "scheduler" in names
    assert all(e["pid"] == 3 for e in doc["traceEvents"])


def test_validate_chrome_trace_rejects_schema_violations():
    def ev(**kw):
        base = {"name": "e", "ph": "i", "ts": 1.0, "pid": 0, "tid": 0}
        base.update(kw)
        return base

    with pytest.raises(ValueError, match="non-empty list"):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError, match="missing 'pid'"):
        validate_chrome_trace({"traceEvents": [
            {"name": "e", "ph": "i", "ts": 0.0, "tid": 0}]})
    with pytest.raises(ValueError, match="sorted"):
        validate_chrome_trace({"traceEvents": [ev(ts=5.0), ev(ts=1.0)]})
    with pytest.raises(ValueError, match="unknown phase"):
        validate_chrome_trace({"traceEvents": [ev(ph="Q")]})
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace({"traceEvents": [ev(ph="X")]})
    with pytest.raises(ValueError, match="E without a matching B"):
        validate_chrome_trace({"traceEvents": [ev(ph="E")]})
    with pytest.raises(ValueError, match="unclosed"):
        validate_chrome_trace({"traceEvents": [ev(ph="B")]})
    # paired B/E and complete X both pass
    validate_chrome_trace({"traceEvents": [
        ev(ph="B"), ev(ph="E", ts=2.0), ev(ph="X", ts=3.0, dur=1.0)]})


def test_validate_chrome_trace_pairs_disagg_handoffs():
    """PR 18 regression: ``handoff`` instants pair per uid — engine park
    half (args carry ``slot``) first, router pump half (``src``/``dst``)
    second.  A router half with no preceding park is a fabricated hop
    (error under strict, counted otherwise); a park the pump never
    collected is legal at dump time and only counts."""
    def ev(ts, **args):
        return {"name": "handoff", "ph": "i", "s": "t", "ts": ts,
                "pid": 0, "tid": 0, "args": args}

    strict = {"otherData": {"sources": ["router", "replica 0"]}}
    paired = {"traceEvents": [ev(1.0, uid="a", slot=2),
                              ev(2.0, uid="a", src=0, dst=1)], **strict}
    s = validate_chrome_trace(paired)
    assert s["handoffs"] == 1 and s["handoff_unmatched"] == 0

    fabricated = {"traceEvents": [ev(1.0, uid="a", src=0, dst=1)],
                  **strict}
    with pytest.raises(ValueError, match="never parked"):
        validate_chrome_trace(fabricated)
    s = validate_chrome_trace(fabricated, strict_flows=False)
    assert s["handoffs"] == 1 and s["handoff_unmatched"] == 1

    # parked-but-not-pumped tolerated EVEN under strict (dump mid-park),
    # but visible in the summary; pairing is per-uid, order per event
    parked = {"traceEvents": [ev(1.0, uid="a", slot=2),
                              ev(2.0, uid="b", slot=3),
                              ev(3.0, uid="b", src=1, dst=0)], **strict}
    s = validate_chrome_trace(parked)
    assert s["handoffs"] == 1 and s["handoff_unmatched"] == 1


# ----------------------------------------------------------- serving engine
def _trace(cfg, n, seed=0, max_new=(2, 12)):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               int(rng.integers(3, 40))),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


def test_serving_stats_backed_by_registry(tiny_engine):
    """stats() values and the registry cells are the same data — the
    PR 2–7 key set rides on telemetry/ now."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2,
                        debug_checks=True)
    reqs = _trace(cfg, 6)
    srv.serve(reqs)
    st = srv.stats()
    snap = srv.metrics.snapshot()

    def val(name):
        return snap[name]["series"][0]["value"]

    assert st["admitted"] == srv.admitted == int(val(
        "serving_requests_admitted_total")) == len(reqs)
    assert st["decode_steps"] == int(val("serving_decode_steps_total"))
    assert st["prefill_calls"] == int(val("serving_prefill_calls_total"))
    assert st["iterations"] == int(val("serving_iterations_total"))
    assert st["invariant_checks_run"] == int(val(
        "serving_invariant_checks_total")) > 0
    # latency percentiles come from the streaming histograms (bounded
    # memory), and the per-request debug view is a bounded deque
    ttft = snap["serving_ttft_seconds"]["series"][0]
    assert ttft["count"] == st["requests_finished"] == len(reqs)
    assert st["ttft_p50_s"] == ttft["p50"] > 0
    assert srv._latencies.maxlen is not None
    # the Prometheus exposition renders the same counters
    assert "serving_requests_finished_total 6.0" in \
        srv.metrics.prometheus_text()
    # ring health keys
    assert st["trace_capacity"] > 0 and st["trace_events"] > 0
    assert st["trace_events_dropped"] == 0


def test_serving_dump_trace_schema_and_event_flow(tiny_engine, tmp_path):
    """The exported timeline is valid Chrome trace JSON carrying the full
    scheduler event flow: per-request spans, prefill/decode phases, the
    sentry's jit_trace events, and the invariant audits."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2, num_blocks=12,
                        debug_checks=True)
    reqs = _trace(cfg, 6, seed=1)
    srv.serve(reqs)
    path = srv.dump_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    summary = validate_chrome_trace(doc)
    assert summary["request_spans"] == len(reqs)
    names = {e["name"] for e in doc["traceEvents"]}
    for expected in ("admit", "prefill", "decode", "invariant_audit",
                     "jit_trace"):
        assert expected in names, (expected, sorted(names))
    # every admission (including preemption resumes) records the prefix
    # hit/miss outcome; every request uid admits at least once
    admits = [e for e in doc["traceEvents"] if e["name"] == "admit"]
    assert {a["args"]["uid"] for a in admits} == \
        {str(r.uid) for r in reqs}
    assert all("prefix_hit_tokens" in a["args"] for a in admits)
    # request spans live on their slot's lane and carry latency args
    span = next(e for e in doc["traceEvents"]
                if e["name"].startswith("req ") and e["ph"] == "X")
    assert span["tid"] >= 1 and span["args"]["new_tokens"] >= 1
    assert span["args"]["ttft_s"] > 0


def test_serving_trace_capacity_zero_disables_ring(tiny_engine):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                        prefill_chunk=16, trace_capacity=0)
    srv.serve(_trace(cfg, 3, seed=2))
    st = srv.stats()
    assert st["trace_capacity"] == 0 and st["trace_events"] == 0
    assert st["requests_finished"] == 3       # registry stays on
    assert st["ttft_p50_s"] > 0


def test_serving_spec_decode_timeline_events(tiny_engine):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=16, prefill_batch=2, spec_tokens=3,
                        debug_checks=True)
    srv.serve(_trace(cfg, 4, seed=3, max_new=(4, 10)))
    names = [e["name"] for e in srv.timeline.events()]
    for expected in ("spec_propose", "spec_verify", "spec_accept"):
        assert expected in names, (expected, sorted(set(names)))
    accept = next(e for e in srv.timeline.events()
                  if e["name"] == "spec_accept")
    assert all(0 <= a <= 3 for a in accept["args"]["accept_lens"])
    validate_chrome_trace(srv.timeline.to_chrome())


def test_serve_profile_dir_window(tiny_engine, tmp_path):
    """serve(profile_dir=) brackets scheduler iterations with the
    jax.profiler window, stamping start/stop on the timeline."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                        prefill_chunk=16)
    srv.serve(_trace(cfg, 3, seed=4), profile_dir=str(tmp_path / "prof"),
              profile_iters=2)
    names = [e["name"] for e in srv.timeline.events()]
    # start always stamps; stop stamps when the profiler actually opened
    # (unavailable backends degrade to a warning, never an error)
    if "profiler_start" in names:
        assert "profiler_stop" in names


def test_preemption_and_eviction_land_on_timeline(tiny_engine):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=32, prefill_batch=2, num_blocks=12,
                        debug_checks=True)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 17),
                    max_new_tokens=28) for i in range(5)]
    srv.serve(reqs)
    assert srv.preempted > 0
    names = {e["name"] for e in srv.timeline.events()}
    assert "preempt" in names
    # preempted-and-resumed requests still close exactly one span each
    assert validate_chrome_trace(
        srv.timeline.to_chrome())["request_spans"] == len(reqs)


# ---------------------------------------------------------- training engine
def test_training_engine_registry_routes_monitor_csv(tmp_path):
    """The train loop's loss/lr/throughput gauges and wall-clock timer
    histograms live in engine.metrics and land on disk through the
    MonitorMaster CSV backend (the registry-snapshot routing)."""
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(gpt2.GPT2Config.tiny()),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "csv_monitor": {"enabled": True,
                                "output_path": str(tmp_path),
                                "job_name": "t"},
                "mesh": {}})
    rng = np.random.default_rng(0)
    for _ in range(3):
        batch = {"input_ids": rng.integers(
            0, 512, size=(engine.train_batch_size(), 33)).astype(np.int32)}
        engine.train_batch(batch)
    snap = engine.metrics.snapshot()
    assert snap["train_loss"]["series"][0]["value"] > 0
    assert snap["train_global_steps"]["series"][0]["value"] == 3
    timers = {s["labels"]["timer"]: s
              for s in snap["train_wall_clock_ms"]["series"]}
    assert timers["train_batch"]["count"] == 3
    files = sorted(os.listdir(tmp_path / "t"))
    # historical event names preserved (monitor_name), plus throughput
    # and the timer breakdown finally on disk
    assert "Train_Samples_train_loss.csv" in files
    assert "Train_Samples_lr.csv" in files
    assert "Train_Samples_throughput.csv" in files
    assert any(f.startswith("train_wall_clock_ms_train_batch") for f in files)
    # no fp16 in this run: no dead loss_scale series/file
    assert "train_loss_scale" not in snap
    assert "Train_Samples_loss_scale.csv" not in files
    rows = (tmp_path / "t" / "Train_Samples_train_loss.csv").read_text()
    assert rows.splitlines()[0] == "step,Train/Samples/train_loss"
    assert len(rows.splitlines()) == 4        # header + 3 report steps


def test_inference_profile_model_time_feeds_histogram(tiny):
    # a fresh engine (on the shared one's parameters): profiling, once on,
    # stays on, and every later forward of the engine would be timed
    engine = deepspeed_tpu.init_inference(
        tiny[0], config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
        params=tiny[2].params)
    engine.profile_model_time()
    engine.forward({"input_ids": np.zeros((1, 8), np.int32)})
    times = engine.model_times()
    assert len(times) == 1 and times[0] > 0
    hist = engine.metrics.snapshot()["inference_forward_seconds"]
    assert hist["series"][0]["count"] >= 1    # survives the drain
