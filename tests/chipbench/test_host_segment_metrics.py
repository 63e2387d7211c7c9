"""The per-layer metrics that read the host segments of PR 36
(``chipbench/layer_metrics/{host_plan_ms,host_upload_ms,host_commit_ms,
call_enqueue_ms,call_overhead_ms,host_offcpu_share,host_gc_share,
step_stall_share}.py``), on rings built by hand like
``test_program_span_metrics.py``'s; and a rehearsal of the serving cells
that report them.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import run as cb_run
from chipbench.layer_metrics import _program_spans as ps

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
READERS = cb_run.layer_metric_readers()
EPOCH = 1000.0                     # the ring's epoch on perf_counter, s
NEW = ("host_plan_ms", "host_upload_ms", "host_commit_ms",
       "call_enqueue_ms", "call_overhead_ms", "host_offcpu_share",
       "host_gc_share", "step_stall_share")
RING = tuple(n for n in NEW if n != "call_overhead_ms")


def X(name, t0_ms, dur_ms, **args):
    return {"name": name, "ph": "X", "ts": t0_ms * 1e3, "dur": dur_ms * 1e3,
            "pid": 0, "tid": 0, "args": args}


def I(name, t_ms, **args):
    return {"name": name, "ph": "i", "s": "t", "ts": t_ms * 1e3, "pid": 0,
            "tid": 0, "args": args}


def ms(**kw):
    """Arguments given in milliseconds, stored in seconds (``plan`` ->
    ``plan_s``)."""
    return {k + "_s": v * 1e-3 for k, v in kw.items()}


@pytest.fixture
def ring(monkeypatch):
    def put(events, dropped=0):
        held = sorted(events, key=lambda e: e["ts"] + e.get("dur", 0.0))
        monkeypatch.setattr(ps, "serve_ring",
                            lambda: (held, EPOCH, dropped))
    return put


def ctx_of(lo_ms, hi_ms, **more):
    return {"window": (EPOCH + lo_ms * 1e-3, EPOCH + hi_ms * 1e-3),
            "trace": None, **more}


#: two steps of 100 ms.  Step 1: a prefill call in flight 20-50 inside
#: step.prefill 10-55 (self 15: plan 4, upload 6, commit 3), a decode call
#: 60-90 inside step.decode 55-95 (self 10: plan 2, upload 5, commit 2).
#: Step 2: no prefill call (step.prefill 105-106: plan 1), a decode call
#: 110-190 inside step.decode 106-196 (self 10: plan 1, upload 3, commit 4).
#: Step 1's thread had the CPU for 70 ms, 42 of them inside its calls: of
#: its 40 ms of self time 28 on a CPU, 12 off it; step 2 for 25 ms, 6
#: inside the call: 19 of its 20 ms.
STEPS = [
    X("step", 0, 100, iteration=1, step=1, gc_n=2,
      **ms(cpu=70, flight_cpu=42, gc=3, kv=1)),
    X("step.admit", 0, 10, step=1),
    X("step.prefill", 10, 45, step=1, groups=1,
      **ms(plan=4, upload=6, commit=3)),
    X("step.decode", 55, 40, step=1, slots=4,
      **ms(plan=2, upload=5, commit=2)),
    X("step.post", 95, 5, step=1),
    X("prefill", 20, 30, step=1, **ms(enqueue=1, wait=29)),
    X("decode", 60, 30, step=1, **ms(enqueue=0.5, wait=29.5)),
    X("step", 100, 100, iteration=2, step=2, gc_n=0,
      **ms(cpu=25, flight_cpu=6, gc=0, kv=1)),
    X("step.admit", 100, 5, step=2),
    X("step.prefill", 105, 1, step=2, groups=0, **ms(plan=1)),
    X("step.decode", 106, 90, step=2, slots=4,
      **ms(plan=1, upload=3, commit=4)),
    X("step.post", 196, 4, step=2),
    X("decode", 110, 80, step=2, **ms(enqueue=0.7, wait=79.3)),
]


def _without(events, *keys):
    """The same ring from a program that does not record ``keys``."""
    return [{**e, "args": {k: v for k, v in e["args"].items()
                           if k not in keys}} for e in events]


def test_the_three_host_segments_are_means_per_step(ring, capsys):
    ring(STEPS)
    ctx = ctx_of(-1, 1000)
    assert READERS["host_plan_ms"](ctx) == pytest.approx((4 + 2 + 1 + 1) / 2)
    assert READERS["host_upload_ms"](ctx) == pytest.approx((6 + 5 + 3) / 2)
    assert READERS["host_commit_ms"](ctx) == pytest.approx((3 + 2 + 4) / 2)
    line = capsys.readouterr().out
    # 31 ms in segments over 15 + 10 + 1 + 10 = 36 ms of phase self time
    assert "plan_s 4.000; upload_s 7.000; commit_s 4.500" in line
    assert f"cover {100 * 31 / 36:.2f} %" in line and "over 2 steps" in line
    # only step 2 starts inside this window: its phases alone
    assert READERS["host_plan_ms"](ctx_of(50, 1000)) == pytest.approx(2.0)
    assert READERS["host_commit_ms"](ctx_of(50, 1000)) == pytest.approx(4.0)


def test_the_decode_calls_enqueue_and_the_idle_time_inside_it(ring):
    ring(STEPS)
    assert READERS["call_enqueue_ms"](ctx_of(-1, 1000)) == \
        pytest.approx(0.6)                        # median of 0.5, 0.7
    assert READERS["call_enqueue_ms"](ctx_of(100, 1000)) == \
        pytest.approx(0.7)
    # decode calls last 30 and 80 ms on the host (median 55); the decode
    # program runs 50 ms on the device, a prefill program does not count
    trace = {"programs": {"jit_decode_step": [0.049, 0.050, 0.051],
                          "jit_prefill": [0.2]}}
    assert READERS["call_overhead_ms"](
        ctx_of(-1, 1000, trace=trace)) == pytest.approx(5.0)
    assert READERS["call_overhead_ms"](ctx_of(-1, 1000)) is None
    assert READERS["call_overhead_ms"](ctx_of(-1, 1000, trace={
        "programs": {"jit_prefill": [0.2]}})) is None


def test_time_off_the_cpu_and_in_the_collector(ring, capsys):
    ring(STEPS)
    ctx = ctx_of(-1, 1000)
    # self 40 and 20 ms; on a CPU outside the calls 28 and 19
    assert READERS["host_offcpu_share"](ctx) == \
        pytest.approx(100 * (12 + 1) / 60)
    assert READERS["host_gc_share"](ctx) == pytest.approx(100 * 3 / 200)
    assert "collector runs inside the window's 2 steps: 2, 3.000 ms" in \
        capsys.readouterr().out
    # more CPU than wall clock outside the calls (clock granularity):
    # floored at 0 per step, not netted against another step's loss
    greedy = [dict(e) for e in STEPS]
    greedy[7] = X("step", 100, 100, iteration=2, step=2, gc_n=0,
                  **ms(cpu=40, flight_cpu=6, gc=0, kv=1))
    ring(greedy)
    assert READERS["host_offcpu_share"](ctx) == pytest.approx(100 * 12 / 60)


def _steps(durations_ms, prefilled=()):
    """Back-to-back steps; those in ``prefilled`` also make a prefill
    call."""
    out, t = [], 0.0
    for i, d in enumerate(durations_ms, 1):
        out.append(X("step", t, d, iteration=i, step=i, gc_n=0,
                     **ms(cpu=1, flight_cpu=0, gc=0)))
        if i in prefilled:
            out.append(X("prefill", t + 0.1 * d, 0.5 * d, step=i))
        out.append(X("decode", t + 0.6 * d, 0.3 * d, step=i,
                     **ms(enqueue=0.1, wait=0.1)))
        t += d
    return out


def test_stalls_are_the_excess_over_the_median_of_a_steps_own_shape(
        ring, capsys):
    # 40 % of the steps also prefill and take 35 ms where the others take
    # 10: over 3 x ONE median of all (10 ms) every one of them would be a
    # stall; per shape nothing is
    ring(_steps([10, 35, 10, 10, 35] * 4, prefilled={2, 5, 7, 10, 12, 15,
                                                      17, 20}))
    assert READERS["step_stall_share"](ctx_of(-1, 10_000)) == 0.0
    assert "0 of 20 steps" in capsys.readouterr().out
    # one decode-only step of 50 ms (median 10: 40 lost) and one prefill
    # step of 135 (median 35: 100 lost); a step at exactly 3 x is not one
    durs = [10, 35, 10, 10, 35] * 4
    durs[0], durs[2], durs[1] = 50, 30, 135
    events = _steps(durs, prefilled={2, 5, 7, 10, 12, 15, 17, 20})
    events += [I("stall", 40, cause="offcpu", iteration=1),
               I("stall", 200, cause="host", iteration=2),
               I("stall", 99_000, cause="gc", iteration=99)]
    ring(events)
    assert READERS["step_stall_share"](ctx_of(-1, 10_000)) == \
        pytest.approx(100 * 140 / sum(durs))
    line = capsys.readouterr().out
    assert "2 of 20 steps" in line and "lost 0.1400 s" in line
    assert "{'host': 1, 'offcpu': 1}" in line


def test_the_parents_ring_a_wrapped_ring_and_no_ring_read_as_nothing(ring):
    trace = {"programs": {"jit_decode_step": [0.05]}}
    ctx = ctx_of(-1, 1000, trace=trace)
    ring(STEPS)
    assert all(READERS[n](ctx) is not None for n in NEW)
    # the parent of PR 36: the same spans without the new arguments
    ring(_without(STEPS, "plan_s", "upload_s", "commit_s", "enqueue_s",
                  "wait_s", "cpu_s", "flight_cpu_s", "gc_s", "gc_n"))
    assert all(READERS[n](ctx) is None for n in NEW)
    assert READERS["sched_host_share"](ctx) is not None   # the old readers
    # events were dropped and the oldest one left ended inside the window
    ring(STEPS, dropped=3)
    assert all(READERS[n](ctx_of(5, 1000, trace=trace)) is None for n in NEW)
    ring(STEPS, dropped=3)
    assert all(READERS[n](ctx_of(99, 1000, trace=trace)) is not None
               for n in NEW)
    ring([])
    assert all(READERS[n](ctx) is None for n in NEW)


def test_a_rehearsals_zero_share_is_left_out(ring):
    """``test_chipbench.py`` holds every value a rehearsal prints above 0;
    a share of a one-second window can be exactly 0."""
    quiet = _without(STEPS, "gc_s")
    quiet = [{**e, "args": {**e["args"], "gc_s": 0.0}}
             if e["name"] == "step" else e for e in quiet]
    ring(quiet)
    assert READERS["host_gc_share"](ctx_of(-1, 1000)) == 0.0
    assert READERS["host_gc_share"](ctx_of(-1, 1000, rehearse=True)) is None
    assert READERS["step_stall_share"](ctx_of(-1, 1000)) == 0.0
    assert READERS["step_stall_share"](
        ctx_of(-1, 1000, rehearse=True)) is None


# ------------------------------------------------------- BENCHMARK.json
def test_the_entries_are_appended_and_name_the_cells_that_can_take_them():
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(NEW[0])
    assert tuple(names[first:first + len(NEW)]) == NEW
    serving = {w["name"] for w in BENCH["workloads"]
               if any(w["name"] in m.get("workloads", ())
                      for m in BENCH["end_to_end"]
                      if m["name"] == "serve_tok_s")}
    for m in BENCH["per_layer"][first:first + len(NEW)]:
        assert (m["layer"], m["moves"], m["better"]) == \
            ("scheduler", "serve_tok_s", "lower")
        assert m["source"] == ("device_trace" if m["name"]
                               == "call_overhead_ms" else "program_span")
        # the two cells left out pin the exact set of their metrics in
        # tests/chipbench/test_keye.py and test_commanda.py (PERF.md 7)
        assert set(m["workloads"]) == serving - {
            "keye-longctx-closed", "commanda-ragchat-closed"}
        assert len(m["workloads"]) == 3


# ------------------------------------------------------------- rehearsal
@pytest.mark.parametrize("cell", ["opt13b-chat-closed",
                                  "opt13b-longprompt-closed",
                                  "olmoe-decode-closed"])
def test_a_traced_rehearsal_prints_the_ring_metrics(cell, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 36), "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    got = res["metrics"]
    assert set(RING) - {"host_gc_share", "step_stall_share"} <= set(got)
    assert "call_overhead_ms" not in got        # no device trace on a CPU
    for name in set(RING) & set(got):
        assert got[name]["value"] > 0, name
    assert "they cover" in proc.stdout and "steps over 3 x" in proc.stdout
    # the three segments are within the phases' self time
    cover = float(proc.stdout.split("they cover ")[1].split(" %")[0])
    assert 50 < cover <= 100
