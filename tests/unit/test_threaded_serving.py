"""Threaded serving fleet stress (PR 14 satellite): a 2-replica
``init_router(threaded=True)`` fleet under ``debug_checks=True`` driven
by concurrent submitter threads, mid-flight cancels, a drain +
re-admit, and a live ``/metrics``/``/stats``/``/trace`` scraper thread
— all while the lock sanitizer order-checks every fleet/replica/handle
acquisition.

Asserts: zero sanitizer trips (``lock_violations == 0`` with a nonzero
check count), EXACT token parity for every non-cancelled request vs the
single-threaded sequential run (greedy resume keeps outputs token-exact
across the drain handoff), clean router audits, per-replica compile
budgets unchanged (the strict sentry would have raised mid-run
otherwise), and at least one successful live scrape carrying the
instrumented-lock families.
"""

import json
import threading
import urllib.request

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.analysis.invariants import audit_router
from deepspeed_tpu.inference.serving import Request
from tiny import sequential


def _session_trace(cfg, n=10, sessions=3, seed=3, prefix_len=24):
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, cfg.vocab_size, prefix_len)
                for _ in range(sessions)]
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [prefixes[i % sessions],
                         rng.integers(0, cfg.vocab_size,
                                      int(rng.integers(3, 8)))]),
                    max_new_tokens=8)
            for i in range(n)]


def test_threaded_fleet_parity_under_sanitizer(tiny):
    spec, cfg, engine = tiny
    reqs = _session_trace(cfg)
    want = sequential(engine, reqs)

    deepspeed_tpu.comm.reset_topology()
    router = deepspeed_tpu.init_router(
        spec, config={"dtype": "fp32",
                      "tensor_parallel": {"tp_size": 1}},
        params=engine.params, replicas=2, threaded=True,
        slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
        prefill_batch=2, debug_checks=True)
    server = router.start_metrics_server(port=0)

    # ---- live scraper: hammers every endpoint while the fleet runs
    stop_scraping = threading.Event()
    scrapes = {"metrics": 0, "stats": 0, "trace": 0}
    scrape_errors = []

    def scraper():
        while not stop_scraping.is_set():
            for ep in ("metrics", "stats", "trace"):
                try:
                    # /stats and /trace take the fleet and replica locks,
                    # which a worker holds through a step that compiles:
                    # on a loaded CPU (tier-1 runs six workers) that wait
                    # is the test's longest, so it gets the waits the
                    # results below get, not 10 s
                    with urllib.request.urlopen(
                            f"{server.url}/{ep}", timeout=120) as resp:
                        body = resp.read().decode("utf-8")
                except Exception as e:   # noqa: BLE001 — surfaced below
                    scrape_errors.append((ep, repr(e)))
                    return
                if ep == "metrics":
                    if "serving_lock_wait_seconds" in body and \
                            "serving_lock_order_checks_total" in body:
                        scrapes["metrics"] += 1
                else:
                    json.loads(body)
                    scrapes[ep] += 1

    scraper_t = threading.Thread(target=scraper, daemon=True)

    # ---- concurrent submitters (3 threads interleave the trace)
    handles = {}
    handles_mu = threading.Lock()
    submit_errors = []

    def submitter(chunk):
        try:
            for r in chunk:
                h = router.submit(r)
                with handles_mu:
                    handles[r.uid] = h
        except Exception as e:           # noqa: BLE001 — surfaced below
            submit_errors.append(repr(e))

    router.start()
    scraper_t.start()
    chunks = [reqs[0::3], reqs[1::3], reqs[2::3]]
    subs = [threading.Thread(target=submitter, args=(c,)) for c in chunks]
    for t in subs:
        t.start()
    for t in subs:
        t.join(timeout=120)
    assert submit_errors == []

    # ---- cancels racing the workers: two extra requests, cancelled
    # right after submit (either outcome — cancelled or already
    # finished — is legal; the handle must reach a terminal state)
    extras = _session_trace(cfg, n=2, seed=11)
    for i, r in enumerate(extras):
        r.uid = 100 + i
    extra_handles = [router.submit(r) for r in extras]
    cancel_rc = [h.cancel() for h in extra_handles]
    assert all(isinstance(c, bool) for c in cancel_rc)

    # ---- mid-flight drain + re-admit while workers step
    handed = router.drain(0)
    assert handed >= 0
    router.readmit(0)
    # post-handoff cancels still route through the router (fleet +
    # replica locks) — never straight into an engine a worker is
    # stepping
    for h in handles.values():
        assert h._canceller == router.cancel

    # ---- collect: streams finish on the ORIGINAL handles
    for r in reqs:
        out = handles[r.uid].result(timeout=120)
        assert out is not None
        np.testing.assert_array_equal(out, want[r.uid])
    for h in extra_handles:
        if h.status != "cancelled":
            assert h.result(timeout=120) is not None
    stop_scraping.set()
    scraper_t.join(timeout=150)
    router.stop()

    # ---- sanitizer: plenty of cross-lock checks, zero violations
    st = router.stats()
    assert st["lock_order_checks"] > 0
    assert st["lock_violations"] == 0
    # the counter family agrees with stats()
    snap = router.metrics.snapshot()
    checks_total = snap["serving_lock_order_checks_total"]["series"][0]
    assert int(checks_total["value"]) == st["lock_order_checks"]
    # contended-or-not, every instrumented acquire observed its wait
    waits = snap["serving_lock_wait_seconds"]["series"]
    assert sum(s["count"] for s in waits) > 0

    # ---- fleet stayed correct: audits, budgets, live scrapes
    audit_router(router)
    for rep in st["per_replica"]:
        assert rep["compile_count"] <= rep["compile_budget"]
    assert scrape_errors == []
    assert scrapes["metrics"] >= 1
    assert scrapes["stats"] >= 1 and scrapes["trace"] >= 1
