"""The start-up timeline: the process's ``setup`` ring
(``telemetry/trace.py setup_timeline``), what the engines' constructors and
their programs' first calls put on it as spans, what the ``jax.monitoring``
listener (``analysis/sentry.py BuildListener``) puts on it for every
function JAX builds, and ``setup_summary()`` over both.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis import sentry
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.telemetry import MetricsRegistry, trace
from deepspeed_tpu.telemetry.metrics import process_registry

BUILT = trace.BUILD_PHASES
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def fresh_ring():
    """A start-up ring of this test's own (the listener asks for the ring
    at every event, so it follows)."""
    trace._KEPT.pop("setup", None)
    return trace.setup_timeline()


def spans_of(timeline):
    """The ring's X-events with ``t0`` / ``t1`` in seconds."""
    return [{**e, "args": e.get("args", {}), "t0": e["ts"] * 1e-6,
             "t1": (e["ts"] + e["dur"]) * 1e-6}
            for e in timeline.events() if e["ph"] == "X"]


def inside(e, outer, slack=2e-3):
    return outer["t0"] - slack <= e["t0"] and e["t1"] <= outer["t1"] + slack


def one(events, name, **args):
    got = [e for e in events if e["name"] == name
           and all(e["args"].get(k) == v for k, v in args.items())]
    assert len(got) == 1, (name, args, [e["args"] for e in got])
    return got[0]


def _requests(cfg, n, new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=f"{seed}-{i}",
                    prompt=rng.integers(0, cfg.vocab_size, 5 + 3 * i),
                    max_new_tokens=new) for i in range(n)]


@pytest.fixture(scope="module")
def served():
    """A tiny engine through ``init_serving`` on a ring of its own, every
    program of it run once: ``(srv, cfg, ring, the ring's spans)``."""
    ring = fresh_ring()
    deepspeed_tpu.comm.reset_topology()
    # (a vocabulary no other test file's model has: what ``params_cast``
    # builds is then never already built by a file this worker ran before)
    cfg = gpt2.GPT2Config.tiny(vocab_size=520, max_seq_len=64)
    srv = deepspeed_tpu.init_serving(
        gpt2.build(cfg), config={"dtype": "fp32"}, slots=4, max_seq_len=64,
        block_size=8, prefill_chunk=16)
    srv.serve(_requests(cfg, 3))
    yield srv, cfg, ring, spans_of(ring)
    srv.close()


@pytest.fixture(scope="module")
def trained():
    """A tiny engine through ``initialize`` on a ring of its own, two
    steps in: ``(engine, batch, ring, the ring's spans)``."""
    ring = fresh_ring()
    deepspeed_tpu.comm.reset_topology()
    cfg = gpt2.GPT2Config.tiny(max_seq_len=32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(cfg),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1}})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size(), 33)).astype(np.int32)}
    for _ in range(2):
        engine.train_batch(batch)
    return engine, batch, ring, spans_of(ring)


# ------------------------------------------------------ the engines' spans
SERVE_PHASES = ("params_cast", "params_place", "pool")
TRAIN_PHASES = ("configure", "build_state", "build_step_fns")


@pytest.mark.parametrize("phase", SERVE_PHASES)
def test_init_serving_holds_each_phase_and_the_phases_do_not_overlap(
        served, phase):
    _, _, ring, events = served
    assert ring is trace.kept("setup") and ring.role == "setup"
    whole = one(events, "init_serving")
    mine = one(events, phase)
    assert inside(mine, whole, slack=0.0)
    for other in SERVE_PHASES:
        if other != phase:
            e = one(events, other)
            assert e["t1"] <= mine["t0"] or mine["t1"] <= e["t0"]
    # what sizes it rides on it
    sized = {"params_cast": ("dtype", "given"),
             "params_place": ("bytes", "leaves"),
             "pool": ("bytes", "blocks", "kinds", "pool")}[phase]
    assert set(sized) <= set(mine["args"]), mine["args"]
    if "bytes" in sized:
        assert mine["args"]["bytes"] > 0


def test_the_pool_span_says_its_bytes_by_kind(served):
    srv, _, _, events = served
    pool = one(events, "pool")["args"]
    assert pool["pool"] == "target" \
        and pool["blocks"] == srv.stats()["num_blocks"]
    assert sum(pool["kinds"].values()) == pool["bytes"] \
        == srv.stats()["kv_pool_bytes"]


@pytest.mark.parametrize("program", ["prefill[4x16]", "prefill[1x64]",
                                     "decode"])
def test_a_serving_program_has_a_build_span_with_its_three_children(
        served, program):
    srv, _, _, events = served
    assert program in srv.sentry.report()       # the name the sentry has
    build = one(events, "build", program=program)
    assert not inside(build, one(events, "init_serving"))   # built later
    fn = "decode_step" if program == "decode" else "prefill"
    at = build["t0"]
    for phase in BUILT:
        child = one(events, phase, program=program)
        assert child["args"]["fn"] == fn
        assert inside(child, build) and child["t0"] >= at - 2e-3
        at = child["t1"]                        # trace, lower, compile
    assert one(events, "compile", program=program)["args"]["cache"] in (
        "hit", "miss", "off")
    if program != "decode":
        assert build["args"]["shape"] == program[8:-1]
    # the sentry's instants stay on the SERVE ring, under the same name
    assert [e for e in srv.timeline.events() if e["name"] == "jit_trace"
            and e["args"]["entry"] == program]
    assert not [e for e in events if e["name"] in ("jit_trace", "retrace")]


@pytest.mark.parametrize("phase", TRAIN_PHASES)
def test_initialize_holds_each_phase_in_order(trained, phase):
    _, _, _, events = trained
    whole = one(events, "initialize")
    spans = [one(events, name) for name in TRAIN_PHASES]
    assert all(inside(e, whole, slack=0.0) for e in spans)
    assert all(a["t1"] <= b["t0"] for a, b in zip(spans, spans[1:]))
    mine = spans[TRAIN_PHASES.index(phase)]
    if phase == "build_state":
        engine = trained[0]
        n = sum(x.size for x in
                jax.tree_util.tree_leaves(engine.state["params"]))
        assert mine["args"]["n_params"] == n
        assert mine["args"]["params_bytes"] == 4 * n      # fp32 master
        assert mine["args"]["opt_state_bytes"] > mine["args"]["params_bytes"]
        # the state is born in ONE program, which is none of the engine's
        # registered ones: it carries no ``program``
        init = one(events, "compile", fn="init_state")
        assert inside(init, mine) and "program" not in init["args"]


def test_the_train_step_has_a_build_span_with_its_three_children(trained):
    engine, _, _, events = trained
    build = one(events, "build", program="train_step")
    assert build["t0"] >= one(events, "initialize")["t1"]
    assert build["args"]["gas"] == 1 and build["args"]["micro_batch"] == 1
    for phase in BUILT:
        children = [e for e in events if e["name"] == phase
                    and e["args"].get("program") == "train_step"]
        # ISSUE 60: the engine reads the compiled step's text inside the
        # span, before the call (``engine.collectives``): the call then
        # finds the program lowered and compiled (no event) and its trace
        # made (an event of microseconds)
        child = max(children, key=lambda e: e["dur"])
        assert len(children) == 1 or phase == "trace" and all(
            e is child or e["dur"] < 5e3 for e in children), children
        assert child["args"]["fn"] == "train_step" and inside(child, build)
    # the wrapper left with the first call
    assert not isinstance(engine._train_step_fn, trace.FirstCall)
    assert engine.sentry.report()["train_step"]["traces"] == 1


# ------------------------------------------------- a window pays nothing
@pytest.mark.parametrize("which", ["serving", "training"])
def test_once_every_program_has_run_further_steps_push_nothing(
        which, served, trained):
    trace._KEPT["setup"] = (served if which == "serving" else trained)[2]
    if which == "serving":
        srv, cfg, ring, _ = served
        before = (ring.emitted, sentry.backend_compiles())
        srv.serve(_requests(cfg, 5, new=7, seed=3))     # other shapes
        for fn in (srv._decode_fn, *srv._prefill_fns.values()):
            assert not isinstance(fn, trace.FirstCall)
    else:
        engine, batch, ring, _ = trained
        before = (ring.emitted, sentry.backend_compiles())
        for _ in range(3):
            engine.train_batch(batch)
    assert (ring.emitted, sentry.backend_compiles()) == before
    assert ring.dropped == 0


# ------------------------------------------------------------ the summary
@pytest.mark.parametrize("which", ["serving", "training"])
def test_the_summarys_parts_sum_to_their_top_level_span(
        which, served, trained):
    if which == "serving":
        srv, _, ring, events = served
        trace._KEPT["setup"] = ring
        summary = srv.stats()["setup"]
        top, phases, programs = "init_serving", SERVE_PHASES, (
            "prefill[4x16]", "prefill[1x64]", "decode")
    else:
        engine, _, ring, events = trained
        trace._KEPT["setup"] = ring
        summary = engine.setup_report()
        top, phases, programs = "initialize", TRAIN_PHASES, ("train_step",)
    whole = one(events, top)
    assert summary["phases"][top] == pytest.approx(whole["t1"] - whole["t0"])
    parts = summary["within"][top]
    assert set(phases) | {"jit", "self"} == set(parts)
    assert sum(parts.values()) == pytest.approx(summary["phases"][top],
                                                abs=1e-3)
    assert all(v >= -1e-3 for v in parts.values()), parts
    assert set(summary["programs"]) == set(programs)
    for name, row in summary["programs"].items():
        build = one(events, "build", program=name)
        assert row["build_s"] == pytest.approx(build["t1"] - build["t0"])
        assert row["trace_s"] > 0 and row["lower_s"] > 0 \
            and row["compile_s"] > 0 and row["first_run_s"] > -1e-3
        assert row["trace_s"] + row["lower_s"] + row["compile_s"] \
            + row["first_run_s"] == pytest.approx(row["build_s"], abs=1e-3)
    assert summary["phases"]["build"] == pytest.approx(
        sum(r["build_s"] for r in summary["programs"].values()))
    # what JAX built that is no registered program, by the span it fell in
    where = "params_cast" if which == "serving" else "build_state"
    assert summary["other_jit"][where]["seconds"] > 0
    assert summary["other_jit"][where]["functions"] >= 1
    assert "build" not in summary["other_jit"]
    cache = summary["cache"]
    n = len([e for e in events if e["name"] == "compile"])
    assert cache["hits"] + cache["misses"] + cache["off"] == n
    assert summary["dropped"] == 0 and summary["events"] == len(ring)
    assert json.dumps(summary)                  # a stats() value
    line = trace.setup_line(summary)
    assert line.startswith("start-up: ") and top in line \
        and all(p in line for p in programs) and "compile cache" in line


def test_the_summary_is_computed_again_only_when_the_ring_has_grown(served):
    _, _, ring, _ = served
    trace._KEPT["setup"] = ring
    first = trace.setup_summary()
    assert trace.setup_summary() is first
    ring.instant("mark")
    assert trace.setup_summary() is not first
    trace._KEPT.pop("setup")
    assert trace.setup_summary() is None
    trace._KEPT["setup"] = ring


def test_a_warm_start_that_misses_names_the_function():
    ring = fresh_ring()
    ring.complete("compile", 0.0, end_us=5e5, fn="steady", cache="hit",
                  retrieval_s=0.25)
    ring.complete("compile", 6e5, end_us=9e5, fn="wanders", cache="miss")
    ring.complete("compile", 9e5, end_us=9.5e5, fn="tiny", cache="off")
    cache = trace.setup_summary()["cache"]
    assert cache == {"hits": 1, "misses": 1, "off": 1, "retrieval_s": 0.25,
                     "missed": ["wanders"], "warm": True}
    assert "MISSED on a warm start" in trace.setup_line() \
        and "wanders" in trace.setup_line()


# ------------------------------------------------------------ the listener
class _Clock:
    def __init__(self, at):
        self.at = at

    def __call__(self):
        return self.at


def test_jaxs_wall_clock_stamps_land_on_the_rings_clock():
    """One offset pair, read as the listener is made: an event JAX stamped
    ``d`` seconds of wall clock after that read lies ``d`` seconds after it
    on the ring's clock, whatever the two clocks' origins."""
    clock, wall = _Clock(1000.0), _Clock(1.7e9)
    trace._KEPT.pop("setup", None)
    ring = trace.TraceTimeline(capacity=16, clock=clock)     # epoch 1000
    ring.role = "setup"
    trace.keep("setup", ring)
    clock.at, wall.at = 1003.0, 1.7e9 + 77.0
    listener = sentry.BuildListener(clock=clock, wall=wall,
                                    registry=MetricsRegistry())
    assert listener.offset_s == pytest.approx(1003.0 - (1.7e9 + 77.0))
    ev = "/jax/core/compile/backend_compile_duration"
    listener.on_begin(ev, 0.0, fun_name="jit(f)")
    listener.on_event("/jax/compilation_cache/cache_hits")
    listener.on_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
    listener.on_duration(ev, 0.5, fun_name="jit(f)")
    listener.on_span(ev, 1.7e9 + 79.0, 1.7e9 + 79.5, fun_name="jit(f)")
    e, = ring.events()
    assert e["name"] == "compile" and e["ph"] == "X"
    assert e["ts"] == pytest.approx(5.0e6) and e["dur"] == pytest.approx(5e5)
    assert e["args"] == {"fn": "f", "cache": "hit", "retrieval_s": 0.125}
    assert listener.count == 1
    # the next compile heard nothing from the cache: ``off``, no retrieval
    listener.on_begin(ev, 0.0, fun_name="jit(g)")
    listener.on_span(ev, 1.7e9 + 80.0, 1.7e9 + 80.25, fun_name="jit(g)")
    assert ring.events()[-1]["args"] == {"fn": "g", "cache": "off"}
    # an event that is none of the three phases is not the ring's
    listener.on_span("/jax/other", 0.0, 1.0)
    assert len(ring) == 2
    fresh_ring()


def test_only_the_outermost_phase_of_a_build_is_kept():
    """``jnp`` functions called while a function is traced are traced
    themselves; the ring gets the function's own events and no others."""
    ring = fresh_ring()
    sentry.install_compile_listener()

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    def outermost_only(x):
        return inner(x) + inner(x + 1) + jnp.cos(x)

    x = jnp.ones(7)                 # (its own eager build is not the test's)
    before = len(ring)
    jax.jit(outermost_only)(x).block_until_ready()
    mine = [e for e in spans_of(ring)[before:]]
    assert [(e["name"], e["args"]["fn"]) for e in mine] == [
        (phase, "outermost_only") for phase in BUILT]
    assert all("program" not in e["args"] for e in mine)
    # and the process's counters moved by what the ring shows
    reg = process_registry()
    text = reg.prometheus_text()
    for phase in BUILT:
        assert f'program_build_seconds_total{{phase="{phase}"}}' in text
    assert reg.counter("program_build_seconds_total",
                       phase="trace").value >= mine[0]["dur"] * 1e-6


def test_a_miss_then_a_hit_for_the_same_function(tmp_path):
    """Against a cache directory of its own: the first build of a function
    is written (``miss``), the same function built again — another
    function object, so nothing in the process remembers it — is read back
    (``hit``, with what the retrieval took)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    ring = fresh_ring()
    listener = sentry.install_compile_listener()
    x = jnp.ones((8, 8))            # (its own eager build is not the test's)
    reg = process_registry()
    hits = reg.counter("compile_cache_hits_total")
    misses = reg.counter("compile_cache_misses_total")
    h0, m0, n0 = hits.value, misses.value, listener.count
    was = jax.config.jax_compilation_cache_dir

    def make():
        def cached_or_not(x):
            return jnp.tanh(x) @ x.T + 53.0

        return jax.jit(cached_or_not)

    try:
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        before = len(ring)
        make()(x).block_until_ready()
        make()(x).block_until_ready()
    finally:
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", was)
    first, second = [e for e in spans_of(ring)[before:]
                     if e["name"] == "compile"]
    assert first["args"] == {"fn": "cached_or_not", "cache": "miss"}
    assert second["args"]["cache"] == "hit"
    assert second["args"]["retrieval_s"] > 0
    assert (hits.value - h0, misses.value - m0) == (1, 1)
    assert listener.count - n0 == 2 == sentry.backend_compiles() - n0
    summary = trace.setup_summary()["cache"]
    assert summary["warm"] and summary["missed"] == ["cached_or_not"]


def test_one_listener_for_the_process_and_the_engines_install_it(served):
    srv, _, _, _ = served
    first = sentry.install_compile_listener()
    assert sentry.install_compile_listener() is first
    import jax._src.monitoring as monitoring

    assert monitoring.get_event_time_span_listeners().count(
        first.on_span) == 1
    # no debug_checks, and the count is there all the same
    assert srv.debug_checks is False
    assert srv.stats()["backend_compiles"] == first.count > 0
    # the process's families ride in the engine's exposition, once
    text = srv.metrics.prometheus_text()
    assert text.count("# TYPE program_build_seconds_total counter") == 1
    assert "compile_cache_hits_total" in text \
        and "compile_cache_misses_total" in text
    assert "program_build_seconds_total" in srv.metrics.snapshot()
    assert "program_build_seconds_total" not in {
        f.name for f in srv.metrics.families()}     # a federation's source


# ---------------------------------------------------------------- the ring
def test_the_ring_is_bounded_and_counts_what_fell_off(monkeypatch):
    monkeypatch.setattr(trace, "SETUP_CAPACITY", 8)
    ring = fresh_ring()
    assert ring.capacity == 8
    for i in range(11):
        with ring.span("phase", i=i):
            pass
    assert len(ring) == 8 and ring.dropped == 3 and ring.emitted == 11
    assert [e["args"]["i"] for e in ring.events()] == list(range(3, 11))
    assert trace.setup_summary()["dropped"] == 3
    monkeypatch.undo()
    assert fresh_ring().capacity == trace.SETUP_CAPACITY >= 4096


@pytest.mark.parametrize("which", ["serving", "training"])
def test_the_rings_dump_is_a_valid_chrome_trace(which, served, trained,
                                                tmp_path):
    ring, events = (served if which == "serving" else trained)[2:]
    path = ring.dump(str(tmp_path / "setup.json"))
    doc = json.load(open(path))
    summary = trace.validate_chrome_trace(doc)
    assert summary["complete"] == len(events) and summary["metadata"] >= 2
    assert doc["otherData"]["dropped_events"] == 0
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"build", *BUILT, "process_name", "thread_name",
            "init_serving" if which == "serving" else "initialize"} <= names


def test_the_packages_import_is_the_rings_first_span():
    """``deepspeed_tpu/__init__.py`` top to bottom, from the ring's epoch:
    the process's ring begins with the import, before any engine."""
    code = ("import json, deepspeed_tpu\n"
            "from deepspeed_tpu.telemetry import trace\n"
            "ring = trace.kept('setup')\n"
            "print(json.dumps([ring.events(), ring.role, ring.capacity,"
            " ring.epoch_s == deepspeed_tpu._T_IMPORT]))")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=110)
    assert out.returncode == 0, out.stderr[-2000:]
    events, role, capacity, from_the_top = json.loads(
        out.stdout.strip().splitlines()[-1])
    first, = events
    assert first["name"] == "import" and first["ph"] == "X"
    assert first["ts"] == 0.0 and first["dur"] > 0 and from_the_top
    assert first["args"] == {"package": "deepspeed_tpu"}
    assert (role, capacity) == ("setup", trace.SETUP_CAPACITY)


# ------------------------------------------------------------ FirstCall
def test_first_call_is_one_build_span_and_then_steps_aside():
    ring = fresh_ring()
    sentry.install_compile_listener()
    holder = {}

    def triple(x):
        return x * 3

    holder["fn"] = trace.FirstCall(
        jax.jit(triple), "triple",
        lambda bare: holder.__setitem__("fn", bare), rows=5)
    held = holder["fn"]                         # a reference taken early
    assert held._cache_size() == 0              # the function's own
    x = jnp.arange(5.0)
    before = len(ring)
    np.testing.assert_array_equal(holder["fn"](x), 3 * np.arange(5.0))
    assert not isinstance(holder["fn"], trace.FirstCall)
    assert ring.building is None
    events = spans_of(ring)[before:]
    build = one(events, "build")
    assert build["args"] == {"program": "triple", "rows": 5}
    assert [e["name"] for e in events] == [*BUILT, "build"]
    assert all(e["args"]["program"] == "triple" for e in events)
    # through the early reference, and through the bare function: nothing
    n = len(ring)
    np.testing.assert_array_equal(held(x), holder["fn"](x))
    assert len(ring) == n and held._cache_size() == 1


def test_a_first_call_that_raises_still_closes_its_span():
    ring = fresh_ring()

    def refuses(x):
        raise ValueError("not today")

    fn = trace.FirstCall(jax.jit(refuses), "refuses")
    with pytest.raises(ValueError, match="not today"):
        fn(jnp.ones(2))
    assert ring.building is None
    assert one(spans_of(ring), "build")["args"] == {"program": "refuses"}
