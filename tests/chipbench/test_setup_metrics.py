"""The per-layer metrics that move ``setup_s``
(``chipbench/layer_metrics/{setup_trace_lower_s,setup_compile_s,
setup_first_run_s,setup_other_jit_s,setup_engine_s}.py`` over
``_setup_spans.py``), on start-up rings built by hand like
``test_program_span_metrics.py``'s serving rings; their entries in
``BENCHMARK.json``; a rehearsal of a serving and of a training cell that
report them; and the files the benchmark had before them, unchanged.
"""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from chipbench import run as cb_run
from chipbench.layer_metrics import _setup_spans as ss
from chipbench.spans import Spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
READERS = cb_run.layer_metric_readers()
EPOCH = 500.0                      # the ring's epoch on perf_counter, s
START = 490.0                      # the process's start on the same clock
COMMON = ("setup_trace_lower_s", "setup_compile_s", "setup_first_run_s",
          "setup_other_jit_s")
NEW = COMMON + ("setup_engine_s.serve", "setup_engine_s.train")
#: (ISSUE 53 also named ``kimilinear-statedecode-closed``; its own test,
#: ``test_kimi_linear.py::test_benchmark_entries_of_this_family``, holds
#: that cell to the lists PR 51 named "and no other" — the fifth such pin,
#: PERF.md section 7 (91))
SERVING = ["opt13b-chat-closed", "opt13b-longprompt-closed",
           "olmoe-decode-closed"]
TRAINING = ["gpt2m-train-1k", "opt13b-zero3-x4"]


def X(name, t0_s, dur_s, **args):
    return {"name": name, "ph": "X", "ts": t0_s * 1e6, "dur": dur_s * 1e6,
            "pid": 0, "tid": 0, "args": args}


def built(fn, t0_s, trace_s, lower_s, compile_s, program=None,
          cache="hit", **more):
    """The three events JAX hands over for one function, back to back."""
    stamp = {"program": program} if program else {}
    return [X("trace", t0_s, trace_s, fn=fn, **stamp),
            X("lower", t0_s + trace_s, lower_s, fn=fn, **stamp),
            X("compile", t0_s + trace_s + lower_s, compile_s, fn=fn,
              cache=cache, **more, **stamp)]


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setattr(ss, "process_start", lambda: START)

    def put(events, dropped=0):
        held = sorted(events, key=lambda e: e["ts"] + e.get("dur", 0.0))
        monkeypatch.setattr(ss, "setup_ring",
                            lambda: (held, EPOCH, dropped))
    return put


def ctx_of(open_s, cb=(), **more):
    """A window that opens ``open_s`` seconds after the ring's epoch, and
    the benchmark's own spans ``cb``: (name, start, duration) in seconds
    after the epoch."""
    spans = Spans()
    for name, t0, dur in cb:
        spans.starts.setdefault(name, []).append(EPOCH + t0)
        spans.durations.setdefault(name, []).append(dur)
    return {"window": (EPOCH + open_s, EPOCH + open_s + 50.0),
            "spans": spans, **more}


#: a serving start, seconds after the ring's epoch (the process began 10 s
#: before it).  import 0-2; cb.setup.weights 3-5 builds the benchmark's
#: initialiser (3.1-4.6); init_serving 5-9 holds params_cast 5.2-6.2 (an
#: eager cast built in it, 5.3-5.9), pool 6.5-7.0 (its one program,
#: 6.55-6.85) and nothing else; cb.setup.check_logits 9-14 builds the
#: comparison's own prefill (9.5-12.5); cb.setup.warm_in 14-24: the
#: prefill rung's build span 14.5-18.5 (trace 1.0, lower 0.8, compile 1.2:
#: 1.0 of first run), an eager operation of the engine's 18.6-18.9, the
#: decode build 19-22 (0.7 + 0.6 + 0.9: 0.8 of first run).  After the
#: window opened (24): a driver's second engine, which counts nowhere.
SERVE = (
    [X("import", 0.0, 2.0, package="deepspeed_tpu")]
    + built("init_fn", 3.1, 0.4, 0.3, 0.8)
    + built("convert_element_type", 5.3, 0.1, 0.1, 0.4, cache="off")
    + [X("params_cast", 5.2, 1.0, dtype="bf16", given=True),
       X("params_place", 6.2, 0.2, bytes=10, leaves=3)]
    + built("packed", 6.55, 0.05, 0.05, 0.2)
    + [X("pool", 6.5, 0.5, pool="target", blocks=9, bytes=100),
       X("init_serving", 5.0, 4.0)]
    + built("prefill", 9.5, 1.0, 0.5, 1.5)           # check_logits' own
    + built("prefill", 14.5, 1.0, 0.8, 1.2, program="prefill[4x128]",
            retrieval_s=0.3)
    + [X("build", 14.5, 4.0, program="prefill[4x128]", shape="4x128")]
    + built("_where", 18.6, 0.1, 0.1, 0.1, cache="off")
    + built("decode_step", 19.0, 0.7, 0.6, 0.9, program="decode",
            cache="miss")
    + [X("build", 19.0, 3.0, program="decode", slots=8)]
    + built("prefill", 30.0, 1.0, 1.0, 1.0, program="prefill[4x128]")
    + [X("build", 30.0, 4.0, program="prefill[4x128]"),
       X("init_serving", 26.0, 3.0)])
SERVE_CB = (("cb.setup.weights", 3.0, 2.0),
            ("cb.setup.init_serving", 5.0, 4.0),
            ("cb.setup.check_logits", 9.0, 5.0),
            ("cb.setup.warm_in", 14.0, 10.0))


def test_each_reader_on_a_serving_start(ring, capsys):
    ring(SERVE)
    ctx = ctx_of(24.0, SERVE_CB)
    assert READERS["setup_trace_lower_s"](ctx) == \
        pytest.approx(1.0 + 0.8 + 0.7 + 0.6)
    assert READERS["setup_compile_s"](ctx) == pytest.approx(1.2 + 0.9)
    # self time of the build spans: duration less the children inside
    assert READERS["setup_first_run_s"](ctx) == pytest.approx(
        (4.0 - 3.0) + (3.0 - 2.2))
    # the engine's own eager builds: the cast and the pool's program
    # inside init_serving, the operation inside the warm-in — not the
    # benchmark's initialiser, nor its comparison's prefill
    assert READERS["setup_other_jit_s"](ctx) == pytest.approx(
        0.6 + 0.3 + 0.3)
    # init_serving, all of it, less what JAX built inside it
    assert READERS["setup_engine_s.serve"](ctx) == pytest.approx(
        4.0 - 0.6 - 0.3)
    assert READERS["setup_engine_s.train"] is READERS["setup_engine_s.serve"]
    out = capsys.readouterr().out
    assert ("compile cache over the registered programs: 1 hits, 1 misses, "
            "0 uncached; retrieval_s 0.300; missed: decode") in out
    assert "set-up account (s) of setup_s 34.000" in out
    assert "bench_jit_s 4.500 (cb.setup.check_logits 3.000, " \
        "cb.setup.weights 1.500)" in out
    assert "uncovered_s 11.000 (before the package's import 10.000, " \
        "after it 1.000)" in out


def test_the_rows_are_disjoint_and_sum_to_setup_s(ring):
    """Every instant from the process's start to the window's opening
    belongs to ONE row — the first of ``ROWS`` that covers it."""
    ring(SERVE)
    got = ss.account(ctx_of(24.0, SERVE_CB))
    assert got["setup_s"] == pytest.approx(24.0 + EPOCH - START)
    assert sum(got[row] for row in ss.ROWS) == pytest.approx(got["setup_s"])
    want = {"trace_lower_s": 3.1, "compile_s": 2.1, "other_jit_s": 1.2,
            "bench_jit_s": 1.5 + 3.0, "first_run_s": 1.8, "engine_s": 3.1,
            "ring_other_s": 2.0,                 # the import, and no more
            # what is left of each cb.setup.* span outside the rows above:
            # weights 2 - 1.5, check_logits 5 - 3, warm_in 10 - 7 - 0.3;
            # cb.setup.init_serving is all init_serving's
            "cb_left_s": 0.5 + 2.0 + 2.7,
            # 10 s before the ring's epoch, and 2-3 between the spans
            "uncovered_s": 10.0 + 1.0}
    assert {row: got[row] for row in ss.ROWS} == pytest.approx(want)
    assert got["by"]["cb_left_s", "cb.setup.warm_in"] == pytest.approx(2.7)
    assert ("cb_left_s", "cb.setup.init_serving") not in got["by"]
    assert got["by"]["ring_other_s", "import"] == pytest.approx(2.0)
    # rows that overlap by a clock's error are still counted once: a
    # ``compile`` that sticks 50 ms out of its build span takes them from
    # what lay behind the span, not twice
    late = [dict(e) for e in SERVE]
    at = next(i for i, e in enumerate(late) if e["name"] == "compile"
              and e["args"].get("program") == "decode")
    late[at] = X("compile", 20.3, 1.75, fn="decode_step", program="decode",
                 cache="miss")
    ring(late)
    got = ss.account(ctx_of(24.0, SERVE_CB))
    assert sum(got[row] for row in ss.ROWS) == pytest.approx(got["setup_s"])
    assert got["compile_s"] == pytest.approx(1.2 + 1.75)
    assert got["cb_left_s"] == pytest.approx(5.2 - 0.05)


def test_events_after_the_window_opened_are_ignored(ring):
    ring(SERVE)
    late = ss.account(ctx_of(24.0, SERVE_CB))
    # the driver's second engine (26-34) moved nothing above; a window
    # that opens after it counts it
    later = ss.account(ctx_of(40.0, SERVE_CB))
    assert later["trace_lower_s"] == pytest.approx(
        late["trace_lower_s"] + 2.0)
    assert later["engine_s"] == pytest.approx(late["engine_s"] + 3.0)
    # an event that had not ENDED when the window opened is not there:
    # the decode build (19-22) and its compile (20.3-21.2)
    early = ss.account(ctx_of(21.0, SERVE_CB))
    assert early["compile_s"] == pytest.approx(1.2)
    assert early["first_run_s"] == pytest.approx(1.0)
    assert early["trace_lower_s"] == pytest.approx(1.8 + 1.3)


#: a training start: initialize 2.5-12.5 holds configure, build_state with
#: the state's one program (init_state, 3.1-6.1), build_step_fns and the
#: checkpoint manager; cb.setup.reference 12.5-15 builds its own loss;
#: cb.setup.warm_steps 15-25: the train step's build span 15-24.
TRAIN = (
    [X("import", 0.0, 2.0, package="deepspeed_tpu"),
     X("configure", 2.6, 0.1)]
    + built("init_state", 3.1, 0.5, 0.5, 2.0, cache="off")
    + [X("build_state", 3.0, 3.5, n_params=10, params_bytes=40,
         opt_state_bytes=80),
       X("build_step_fns", 6.5, 0.1),
       X("checkpoint_manager", 7.0, 5.0),
       X("initialize", 2.5, 10.0)]
    + built("next_token_loss", 12.6, 0.4, 0.4, 1.2)
    + built("train_step", 15.0, 2.0, 2.5, 3.5, program="train_step")
    + [X("build", 15.0, 9.0, program="train_step", gas=4, micro_batch=8)])
TRAIN_CB = (("cb.setup.initialize", 2.4, 10.2),
            ("cb.setup.reference", 12.6, 2.4),
            ("cb.setup.warm_steps", 15.0, 10.0))


def test_each_reader_on_a_training_start(ring):
    ring(TRAIN)
    ctx = ctx_of(25.0, TRAIN_CB)
    assert READERS["setup_trace_lower_s"](ctx) == pytest.approx(4.5)
    assert READERS["setup_compile_s"](ctx) == pytest.approx(3.5)
    assert READERS["setup_first_run_s"](ctx) == pytest.approx(1.0)
    assert READERS["setup_other_jit_s"](ctx) == pytest.approx(3.0)
    assert READERS["setup_engine_s.train"](ctx) == pytest.approx(7.0)
    got = ss.account(ctx)
    assert got["bench_jit_s"] == pytest.approx(2.0)
    assert got["by"]["bench_jit_s", "cb.setup.reference"] == \
        pytest.approx(2.0)
    assert sum(got[row] for row in ss.ROWS) == pytest.approx(35.0)


def test_no_ring_a_ring_that_lost_events_and_an_empty_one_read_as_nothing(
        ring, monkeypatch):
    ctx = ctx_of(24.0, SERVE_CB)
    ring(SERVE)
    assert all(READERS[n](ctx) is not None for n in NEW)
    ring(SERVE, dropped=1)
    assert all(READERS[n](ctx) is None for n in NEW)
    ring([])
    assert all(READERS[n](ctx) is None for n in NEW)
    # the parent of PR 53: a program whose telemetry keeps no such ring
    monkeypatch.setattr(ss, "setup_ring", lambda: None)
    assert all(READERS[n](ctx) is None for n in NEW)
    # a window that opened before anything ended
    ring(SERVE)
    assert all(READERS[n](ctx_of(-1.0)) is None for n in NEW)


def test_the_real_ring_is_read_through_kept_and_a_program_without_one(
        monkeypatch):
    from deepspeed_tpu.telemetry import trace

    timeline = trace.TraceTimeline(capacity=8)
    timeline.complete("build", 0.0, end_us=5.0, program="decode")
    monkeypatch.setitem(trace._KEPT, "setup", timeline)
    events, epoch_s, dropped = ss.setup_ring()
    assert [e["name"] for e in events] == ["build"] and dropped == 0
    assert epoch_s == timeline.epoch_s
    monkeypatch.delitem(trace._KEPT, "setup")
    assert ss.setup_ring() is None
    monkeypatch.delattr(trace, "kept")
    assert ss.setup_ring() is None


def test_without_the_runs_clock_the_account_starts_at_the_rings_first_event(
        ring, monkeypatch):
    ring(SERVE)
    monkeypatch.setattr(ss, "process_start", lambda: None)
    got = ss.account(ctx_of(24.0, SERVE_CB))
    assert got["setup_s"] == pytest.approx(24.0)
    assert got["uncovered_s"] == pytest.approx(1.0)
    monkeypatch.undo()
    # as a module the run's clock is chipbench.run's own
    assert ss.process_start() == cb_run.T_PROCESS


def test_a_rehearsals_zero_row_is_left_out(ring):
    """``test_chipbench.py`` holds every value a rehearsal prints above 0."""
    quiet = [e for e in SERVE if e["name"] != "build"
             and "program" not in e["args"]]
    ring(quiet)
    ctx = ctx_of(24.0, SERVE_CB)
    assert READERS["setup_first_run_s"](ctx) == 0.0
    assert READERS["setup_first_run_s"]({**ctx, "rehearse": True}) is None
    assert READERS["setup_engine_s.serve"]({**ctx, "rehearse": True}) > 0


# ------------------------------------------------------- BENCHMARK.json
def test_the_six_entries_are_appended_and_name_their_cells():
    entries = BENCH["per_layer"]
    first = [m["name"] for m in entries].index(NEW[0])
    assert first >= 57                          # behind what was there
    mine = entries[first:first + len(NEW)]
    assert tuple(m["name"] for m in mine) == NEW
    layers = dict(zip(NEW, ("model step", "model step", "model step",
                            "engine", "scheduler", "engine")))
    had = {m["layer"] for m in entries[:first]}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("s", "lower", "program_span", "setup_s")
        assert m["layer"] == layers[m["name"]] and m["layer"] in had
        assert m["workloads"] == {
            "setup_engine_s.serve": SERVING,
            "setup_engine_s.train": TRAINING}.get(
                m["name"], SERVING + TRAINING)
    # they are the only metrics that move setup_s, which every cell reports
    assert {m["name"] for m in entries if m["moves"] == "setup_s"} == set(NEW)
    assert "workloads" not in next(
        m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert set(NEW) <= set(READERS)
    # one reader file a metric, or a group under one ``read``
    for cell in SERVING + TRAINING:
        listed = {m["name"] for m in cb_run.load_cell(cell)["per_layer"]}
        assert set(COMMON) <= listed
        assert ("setup_engine_s.serve" in listed) == (cell in SERVING)
        assert ("setup_engine_s.train" in listed) == (cell in TRAINING)


def test_every_file_the_benchmark_had_hashes_as_before():
    """``chipbench/`` and ``tests/chipbench/`` as PR 52 left them
    (``files_at_pr52.json``: path -> sha256 at the parent commit): this
    PR's readers and tests are new files beside them."""
    was = json.load(open(os.path.join(HERE, "files_at_pr52.json")))
    assert len(was) == 133
    now = {}
    for path in was:
        with open(os.path.join(ROOT, path), "rb") as f:
            now[path] = hashlib.sha256(f.read()).hexdigest()
    assert {p for p in was if now[p] != was[p]} == set()


# ------------------------------------------------------------- rehearsal
@pytest.mark.parametrize("cell", ["opt13b-chat-closed", "gpt2m-train-1k"])
def test_a_traced_rehearsal_prints_every_setup_metric_of_the_cell(
        cell, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 53), "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    got = res["metrics"]
    want = [m["name"] for m in cb_run.load_cell(cell)["per_layer"]
            if m["moves"] == "setup_s"]
    assert len(want) == 5 and set(want) <= set(got), sorted(got)
    for name in want:
        assert got[name] == {"value": got[name]["value"], "unit": "s"}
        assert 0 < got[name]["value"] < 600, name
    out = proc.stdout
    # an empty cache directory: every registered program was compiled
    assert "compile cache over the registered programs: 0 hits, " in out
    line = next(x for x in out.splitlines() if "set-up account (s)" in x)
    rows = {row: float(line.split(f"{row} ")[1].split()[0].rstrip(";"))
            for row in ss.ROWS}
    total = float(line.split("of setup_s ")[1].split(":")[0])
    assert sum(rows.values()) == pytest.approx(total, abs=0.01)
    for row, name in (("trace_lower_s", "setup_trace_lower_s"),
                      ("compile_s", "setup_compile_s"),
                      ("first_run_s", "setup_first_run_s"),
                      ("other_jit_s", "setup_other_jit_s")):
        assert rows[row] == pytest.approx(got[name]["value"], abs=1e-3)
    # the seconds no span covers lie before the package was imported:
    # the interpreter, ``import jax``, the backend's start
    assert "before the package's import" in line
    assert rows["uncovered_s"] < total
