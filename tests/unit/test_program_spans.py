"""The program's own spans (``telemetry/trace.py`` + ``ServingEngine.step``
+ ``train_batch``): one primitive on two clocks.

Ring side (host clock): one ``step`` span per scheduler iteration, tiled by
the four host phases, the in-flight spans nested inside a phase (of the
step that made the call or, harvested behind the next one, the step after), the KV
manager's seconds on the step, ``step`` on every span, ``submit``/``admit``
paired by ``uid``, and the ring readable after the engine is closed.  Inside the phases and the
in-flight spans the SEGMENTS (``TraceTimeline.segment``): seconds on the
spans' arguments, annotations in a profile, no ring event; the step's
thread-CPU and collector seconds; a stalled step named as it happens.
Profiler side: the same spans as ``ds.serve.*`` / ``ds.train.*`` annotations
in a ``jax.profiler`` trace, ring on or off.  Plus the names the benchmark's
reduction finds things by: the jitted programs' module names and the Pallas
kernels' own names.
"""

import gc
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import serving
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.telemetry import idle_gaps, trace
from deepspeed_tpu.telemetry.flops import ServingFlopsProfiler

PHASES = ("step.admit", "step.prefill", "step.decode", "step.post")
IN_FLIGHT = {"prefill": "step.prefill", "decode": "step.decode"}
SERVE_KW = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16)


def _requests(cfg, n=7, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(5, 30)),
                                        dtype=np.int32),
                    max_new_tokens=int(rng.integers(2, 7)))
            for i in range(n)]


@pytest.fixture(scope="module")
def tiny():
    cfg = gpt2.GPT2Config.tiny()
    engine = deepspeed_tpu.init_inference(gpt2.build(cfg),
                                          config={"dtype": "fp32"})
    return engine, cfg


@pytest.fixture(scope="module")
def served(tiny):
    """A short ``serve()`` (more requests than slots, so some queue) and
    the ring it left, events with ``end`` added."""
    engine, cfg = tiny
    srv = ServingEngine(engine, **SERVE_KW)
    log = []
    srv.serve(_requests(cfg), step_log=log)
    events = [{**e, "end": e["ts"] + e.get("dur", 0.0)}
              for e in srv.timeline.events()]
    return srv, events, log


def _named(events, name):
    return [e for e in events if e["ph"] == "X" and e["name"] == name]


def test_one_step_span_per_iteration(served):
    srv, events, log = served
    steps = _named(events, "step")
    assert [s["args"]["iteration"] for s in steps] == \
        list(range(1, srv.iterations + 1))
    for s in steps:
        assert set(s["args"]) >= {"iteration", "active", "pending",
                                  "admitted", "evicted", "blocks_in_use",
                                  "kv_s", "step"}
    # the step log is built from the span's arguments
    assert log == [{k: s["args"][k] for k in (
        "iteration", "admitted", "evicted", "blocks_in_use")} for s in steps]


def test_the_four_phases_tile_the_step(served):
    _, events, _ = served
    steps = _named(events, "step")
    covered = 0.0
    for s in steps:
        it = s["args"]["step"]
        mine = sorted((e for e in events if e["ph"] == "X"
                       and e["name"] in PHASES and e["args"]["step"] == it),
                      key=lambda e: e["ts"])
        assert [e["name"] for e in mine] == list(PHASES), it
        assert mine[0]["ts"] >= s["ts"] and mine[-1]["end"] <= s["end"]
        for a, b in zip(mine, mine[1:]):
            assert a["end"] <= b["ts"], (it, a["name"], b["name"])
        covered += sum(e["dur"] for e in mine)
    total = sum(s["dur"] for s in steps)
    assert covered <= total
    assert (total - covered) / total < 0.02


def test_in_flight_spans_nest_inside_a_phase(served):
    """A call's span is the host's stay in the runtime that ended with its
    tokens (ISSUE 44): it lies inside ONE host phase — of the step that
    made the call (``step``) or, the call having been harvested behind the
    next one, of the step after — and no two overlap."""
    _, events, _ = served
    seen = set()
    hosts = [p for name in PHASES[1:] for p in _named(events, name)]
    for name in IN_FLIGHT:
        for e in _named(events, name):
            host = [p for p in hosts
                    if p["ts"] <= e["ts"] and e["end"] <= p["end"]]
            assert len(host) == 1, e
            assert host[0]["args"]["step"] - e["args"]["step"] in (0, 1)
            assert e["args"]["ahead"] in (0, 1)
            seen.add(name)
    assert seen == set(IN_FLIGHT)
    flights = sorted((e for name in IN_FLIGHT for e in _named(events, name)),
                     key=lambda e: e["ts"])
    assert all(a["end"] <= b["ts"] for a, b in zip(flights, flights[1:]))
    assert sum(e["args"]["ahead"] for e in flights) == len(flights) - 1
    # the phases say what they ran
    for p in _named(events, "step.prefill"):
        inside = [e for e in _named(events, "prefill")
                  if e["args"]["step"] == p["args"]["step"]]
        assert p["args"]["groups"] == len(inside)
    for p in _named(events, "step.decode"):
        inside = [e for e in _named(events, "decode")
                  if e["args"]["step"] == p["args"]["step"]]
        assert (p["args"]["slots"] > 0) == bool(inside)


def _prefill_rows(events):
    """``(uid, prompt tokens cached after the call)`` of every row of every
    ``prefill`` span, replayed from the ring: an ``admit`` instant puts a
    request in a slot (past its prefix hit), a span advances each of its
    ``slots`` by
    ``min(width, prompt left)`` — a call's width follows its ready rows
    (ISSUE 52), so a prompt's calls are not ``cdiv(prompt, prefill_chunk)``."""
    held, rows = {}, []
    for e in sorted((e for e in events if e["name"] in ("admit", "prefill")),
                    key=lambda e: e["ts"]):
        a = e["args"]
        if e["name"] == "admit":
            held[a["slot"]] = [a["uid"], a["prompt_tokens"],
                               a["prefix_hit_tokens"]]
            continue
        for slot in a["slots"]:
            row = held[slot]
            row[2] += min(a["width"], row[1] - row[2])
            rows.append((row[0], row[2]))
    assert all(done == total for _, total, done in held.values())
    return rows


def test_prefill_spans_count_the_blocks_their_reads_walk(served, tiny):
    """ISSUE 31: each ``prefill`` span carries ``kv_blocks``, the sum over
    its rows of ``cdiv(base + valid, block_size)`` — what the prefill
    kernel walks — and the program notes, as it is traced, which read it
    was built with (on a CPU the gather)."""
    srv, events, _ = served
    bs = SERVE_KW["block_size"]
    rows = _prefill_rows(events)
    # a request's last call ends at its prompt's length
    assert dict(rows) == {str(r.uid): len(r.prompt)
                          for r in _requests(tiny[1])}
    want = sum(-(-done // bs) for _, done in rows)
    spans = _named(events, "prefill")
    assert all(e["args"]["kv_blocks"] >= e["args"]["rows"] for e in spans)
    assert sum(e["args"]["kv_blocks"] for e in spans) == want
    assert srv.stats()["prefill_attn"] == "gather"


def test_decode_spans_count_the_blocks_and_tiles_their_reads_walk(served,
                                                                  tiny):
    """ISSUE 45: each ``decode`` span carries ``kv_blocks``, the sum over
    its rows of ``cdiv(valid, block_size)`` — what the decode walk copies —
    and ``kv_tiles``, the loop iterations it makes of them at the tile
    ``stats()["decode_attn"]`` names (read off the pool's stored shapes)."""
    from deepspeed_tpu.ops import decode_attention, paged_kv

    srv, events, _ = served
    bs = SERVE_KW["block_size"]
    hd = tiny[1].hidden_size // tiny[1].num_heads
    r = bs // paged_kv.lane_pack(bs, hd)
    nt = decode_attention.walk_tile_blocks(r, SERVE_KW["max_seq_len"] // bs)
    assert srv.stats()["decode_attn"] == {"tile_blocks": nt, "cols": nt * r,
                                          "rows_ahead": 1}
    spans = _named(events, "decode")
    for a in (e["args"] for e in spans):
        assert a["slots"] <= a["kv_tiles"] <= a["kv_blocks"] \
            <= a["kv_tiles"] * nt
    # a request's decode calls read prompt + 1 .. prompt + budget - 1 keys
    want = sum(-(-(len(r.prompt) + j) // bs) for r in _requests(tiny[1])
               for j in range(1, r.max_new_tokens))
    assert sum(e["args"]["kv_blocks"] for e in spans) == want


@pytest.mark.parametrize("budgets,freed", [((9, 3, 9), [0, 2]),
                                           ((3, 9, 9), [1, 2])],
                         ids=["a-free-slot-in-the-middle", "row-0-free"])
def test_decode_spans_count_the_first_tiles_started_ahead(tiny, budgets,
                                                          freed, monkeypatch):
    """ISSUE 56: ``kv_first_tiles_ahead`` on a ``decode`` span is the live
    rows of its call whose first tile the grid step before theirs started:
    every live row but row 0 of the call — exact over a run whose calls see
    all three slots live, then a free slot between two live ones (or row 0
    free: then every live row's)."""
    engine, cfg = tiny
    srv = ServingEngine(engine, **SERVE_KW)
    assert srv.stats()["decode_attn"]["rows_ahead"] == 1
    calls, walk = [], srv._kv_walk
    monkeypatch.setattr(srv, "_kv_walk", lambda rows: (
        calls.append([int(r) for r in rows]), walk(rows))[1])
    rng = np.random.default_rng(56)
    srv.serve([Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 11,
                                                  dtype=np.int32),
                       max_new_tokens=n) for i, n in enumerate(budgets)])
    args = [e["args"] for e in _named(srv.timeline.events(), "decode")]
    assert [0, 1, 2] in calls and freed in calls
    assert [(a["slots"], a["kv_first_tiles_ahead"]) for a in args] \
        == [(len(rows), len(rows) - (0 in rows)) for rows in calls]


#: (temperature, top_k, top_p) a request: greedy, sampled unfiltered, top-p,
#: top-k, both, and a greedy request whose filter knobs select nothing
MIXED_KNOBS = [(0.0, 0, 1.0), (1.0, 0, 1.0), (0.7, 0, 0.9), (1.2, 5, 1.0),
               (0.7, 9, 0.8), (0.0, 4, 0.5), (0.7, 0, 0.9)]


@pytest.mark.parametrize("sampling", [True, False],
                         ids=["mixed-batch", "greedy-only-engine"])
def test_sampler_rows_ride_the_spans_and_stats_name_the_sampler(
        tiny, sampling):
    """ISSUE 33: every built program says how it picks its tokens
    (``stats()["sampler"]``), and the ``decode`` / ``prefill`` spans carry
    ``sampled_rows`` (temperature > 0) and ``filtered_rows`` (of those, a
    ``top_k`` or ``top_p`` set: the rows the threshold searches run for),
    counted from the knob vectors the dispatch uploads.  A decode span
    counts a request once per token it emits there (all but its first), a
    prefill span once per call its prompt went through."""
    engine, cfg = tiny
    srv = ServingEngine(engine, sampling=sampling, **SERVE_KW)
    assert srv.stats()["sampler"] == {}
    reqs = _requests(cfg)
    if sampling:
        reqs = [Request(uid=r.uid, prompt=r.prompt,
                        max_new_tokens=r.max_new_tokens, temperature=t,
                        top_k=k, top_p=p, seed=11 + r.uid)
                for r, (t, k, p) in zip(reqs, MIXED_KNOBS)]
    srv.serve(reqs)
    how = "bitwise_search" if sampling else "argmax"
    assert srv.stats()["sampler"] == {"prefill": how, "decode": how}
    events = srv.timeline.events()
    calls = [uid for uid, _ in _prefill_rows(events)]
    sampled = [r for r in reqs if r.temperature > 0]
    filtered = [r for r in sampled if r.top_k > 0 or r.top_p < 1]
    assert (len(sampled), len(filtered)) == ((5, 4) if sampling else (0, 0))
    for key, of in (("sampled_rows", sampled), ("filtered_rows", filtered)):
        assert sum(e["args"][key] for e in _named(events, "decode")) == \
            sum(r.max_new_tokens - 1 for r in of)
        assert sum(e["args"][key] for e in _named(events, "prefill")) == \
            sum(calls.count(str(r.uid)) for r in of)
    for e in _named(events, "decode") + _named(events, "prefill"):
        assert e["args"]["filtered_rows"] <= e["args"]["sampled_rows"] \
            <= e["args"].get("rows", e["args"]["slots"])


def test_stats_name_the_tiled_search_for_a_wide_vocabulary(tiny):
    """ISSUE 67: ``stats()["sampler"]`` says where the nucleus search's
    passes read from, by the engine's vocabulary width alone
    (``ops/sampling.py thresholds``): a toy engine of 65,664 entries a row
    takes the tiled kernel in ``prefill`` as in ``decode``, the tiny one of
    512 the plain loop, and a greedy-only engine is ``"argmax"`` at any
    width."""
    from deepspeed_tpu.ops import sampling

    wide = gpt2.GPT2Config.tiny(vocab_size=sampling.TILED_FROM + 128)
    engine = deepspeed_tpu.init_inference(gpt2.build(wide),
                                          config={"dtype": "fp32"})
    rng = np.random.default_rng(3)

    def serve(engine, cfg, **kw):
        srv = ServingEngine(engine, **SERVE_KW, **kw)
        out = srv.serve([
            Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 9,
                                               dtype=np.int32),
                    max_new_tokens=3, temperature=t, top_p=0.9, seed=5 + i)
            for i, t in enumerate((0.7, 0.0) if kw == {} else (0.0, 0.0))])
        assert all(len(v) == 9 + 3 for v in out.values())
        return srv.stats()["sampler"]

    assert sampling.thresholds(wide.vocab_size) == "bitwise_search_tiled"
    assert serve(engine, wide) == dict.fromkeys(("prefill", "decode"),
                                                "bitwise_search_tiled")
    assert serve(engine, wide, sampling=False) == dict.fromkeys(
        ("prefill", "decode"), "argmax")
    assert serve(*tiny) == dict.fromkeys(("prefill", "decode"),
                                         "bitwise_search")


def test_kv_seconds_and_step_numbers(served):
    _, events, _ = served
    for s in _named(events, "step"):
        assert 0.0 < s["args"]["kv_s"] <= s["dur"] * 1e-6
    spans = [e for e in events if e["ph"] == "X"]
    assert spans and all("step" in e.get("args", {}) for e in spans)
    # request spans keep their uid
    assert all("uid" in e["args"] for e in spans
               if e["name"].startswith("req "))


def test_submit_and_admit_pair_by_uid(served):
    _, events, _ = served
    submits = {e["args"]["uid"]: e["ts"] for e in events
               if e["ph"] == "i" and e["name"] == "submit"}
    admits = {e["args"]["uid"]: e["ts"] for e in events
              if e["ph"] == "i" and e["name"] == "admit"}
    assert set(submits) == set(admits) == {str(i) for i in range(7)}
    assert all(admits[u] >= submits[u] for u in submits)


def _reaches(root, cls) -> bool:
    """Whether an instance of ``cls`` is reachable from ``root`` through
    object references (classes, modules and functions not followed)."""
    seen, todo = set(), [root]
    while todo:
        o = todo.pop()
        if id(o) in seen:
            continue
        seen.add(id(o))
        if isinstance(o, cls):
            return True
        if isinstance(o, (type, types.ModuleType, types.FunctionType,
                          types.BuiltinFunctionType, types.MethodType)):
            continue
        todo.extend(gc.get_referents(o))
    return False


def test_the_ring_outlives_the_engine_and_holds_none_of_it(tiny):
    engine, cfg = tiny
    srv = ServingEngine(engine, **SERVE_KW)
    srv.serve(_requests(cfg, n=3))
    n_events, epoch = len(srv.timeline), srv.timeline.epoch_s
    srv.close()
    kept = trace.kept("serve")
    assert kept is srv.timeline
    del srv
    gc.collect()
    assert len(kept.events()) == n_events > 0 and kept.epoch_s == epoch
    assert not _reaches(kept, ServingEngine)
    # the next engine replaces it
    other = ServingEngine(engine, **SERVE_KW)
    assert trace.kept("serve") is other.timeline


# ------------------------------------------------- segments (ISSUE 36)
PHASE_SEGMENTS = ("plan_s", "upload_s", "commit_s")
CALL_SEGMENTS = ("enqueue_s", "wait_s")


def _runner_engine(tiny, runner):
    engine, cfg = tiny
    kw = dict(SERVE_KW)
    if runner == "spec-ngram":
        kw["spec_tokens"] = 2
    elif runner == "spec-draft":
        dcfg = gpt2.GPT2Config(vocab_size=cfg.vocab_size, max_seq_len=64,
                               num_layers=1, num_heads=2, hidden_size=32)
        kw.update(spec_tokens=2, draft=gpt2.build(dcfg))
    return ServingEngine(engine, **kw)


@pytest.mark.parametrize("runner", ["plain", "spec-ngram", "spec-draft"])
def test_every_runner_times_its_segments(tiny, runner):
    """Table B of ISSUE 36, one vocabulary in every runner: a phase that
    made a call carries ``plan_s`` / ``upload_s`` / ``commit_s`` and their
    sum is within the phase's time outside its in-flight spans; an
    in-flight span carries ``enqueue_s`` / ``wait_s`` within its duration;
    the step carries its thread-CPU and collector seconds.  And a segment
    is not an event: the ring holds the same names as before."""
    srv = _runner_engine(tiny, runner)
    srv.serve(_requests(tiny[1]))
    events = srv.timeline.events()
    flights = [e for e in events if e["ph"] == "X" and e["name"] in (
        "prefill", "decode", "spec_propose", "spec_verify")
        and e["args"].get("mode") != "ngram"]
    want = {"prefill", "decode"} if runner == "plain" else \
        {"prefill", "spec_verify"} | (
            {"spec_propose"} if runner == "spec-draft" else set())
    assert {f["name"] for f in flights} == want
    for f in flights:
        assert set(CALL_SEGMENTS) <= set(f["args"]), f
        assert f["args"]["enqueue_s"] > 0
        assert 0 <= f["args"]["wait_s"] <= f["dur"] * 1e-6
        if runner != "plain":
            # made and harvested in one stay: both lie inside it (the plain
            # runner harvests a call behind the next one's enqueue: it
            # was handed over in the stay before)
            assert f["args"]["enqueue_s"] + f["args"]["wait_s"] \
                <= f["dur"] * 1e-6
    called = 0
    for phase in _named(events, "step.prefill") + \
            _named(events, "step.decode"):
        inside = [f for f in flights
                  if phase["ts"] <= f["ts"]
                  and f["ts"] + f["dur"] <= phase["ts"] + phase["dur"]]
        if not inside:
            continue
        called += 1
        assert set(PHASE_SEGMENTS) <= set(phase["args"]), phase
        own = phase["dur"] - sum(f["dur"] for f in inside)
        assert 0 < sum(phase["args"][k] for k in PHASE_SEGMENTS) \
            <= own * 1e-6
    assert called >= len(_named(events, "step"))
    for s in _named(events, "step"):
        a = s["args"]
        assert set(a) >= {"cpu_s", "flight_cpu_s", "flight_s", "gc_s",
                          "gc_n", "kv_s"}
        assert 0 <= a["flight_cpu_s"] <= a["cpu_s"]
        # (the first call of an idle engine is handed over with nothing
        # to harvest, inside ``upload``: a step of the plain runner that
        # made only that call stayed in the runtime for nothing)
        assert (runner == "plain" or a["flight_s"] > 0) \
            and 0 <= a["flight_s"] <= s["dur"] * 1e-6
        assert a["gc_s"] >= 0 and (a["gc_n"] > 0) == (a["gc_s"] > 0)
    names = {e["name"].split(" ")[0] for e in events}
    assert names <= {"step", *PHASES, "prefill", "decode", "spec_propose",
                     "spec_verify", "spec_accept", "submit", "admit", "req",
                     "jit_trace", "gc"}, names
    srv.close()


def test_a_segment_pushes_no_ring_event_and_evictions_come_one_a_call(tiny):
    """Events per step are what they were before the segments: ``step``,
    four phases and one X-event a call, whatever ran inside them.  A
    request's three (``submit``, ``admit``, ``req``) and, in a pool that
    evicts, ONE ``evict_block`` per ``_ensure_blocks`` call that evicted,
    carrying how many blocks (one instant a block before ISSUE 36)."""
    engine, cfg = tiny
    srv = ServingEngine(engine, num_blocks=13, **SERVE_KW)
    reqs = _requests(cfg, n=12, seed=3)
    srv.serve(reqs)
    tl, events = srv.timeline, srv.timeline.events()
    assert tl.dropped == 0 and tl.emitted == len(events)
    x = [e for e in events if e["ph"] == "X" and e["name"] != "gc"
         and not e["name"].startswith("req ")]
    calls = len(_named(events, "prefill")) + len(_named(events, "decode"))
    assert len(x) == 5 * srv.iterations + calls
    evictions = [e for e in events if e["name"] == "evict_block"]
    assert evictions and all(e["ph"] == "i" for e in evictions)
    assert all(set(e["args"]) == {"blocks", "demoted"} for e in evictions)
    blocks = sum(e["args"]["blocks"] for e in evictions)
    assert blocks == srv.stats()["prefix_cache_evictions"] > len(evictions)
    other = [e for e in events if e["ph"] == "i" and e["name"] not in (
        "evict_block", "jit_trace", "preempt")]
    assert sorted(e["name"] for e in other) == \
        ["admit"] * len(reqs) + ["submit"] * len(reqs)
    srv.close()


def test_a_segment_adds_to_its_spans_argument_and_kv_s_does_the_same(tiny):
    """A segment adds its seconds to an argument of the span it is given,
    pushes nothing, and with the ring off leaves the dict alone; the KV
    manager's ``kv_s`` is the same accumulation on the ``step`` span,
    within the step and over zero when the step reserved blocks."""
    tl = trace.TraceTimeline(capacity=8)
    args = {}
    for _ in range(2):
        with tl.segment("step.decode.plan", args) as into:
            assert into is args
            time.sleep(0.002)
    assert 0.004 <= args["plan_s"] < 0.1 and set(args) == {"plan_s"}
    assert tl.emitted == 0
    off = trace.TraceTimeline(capacity=0)
    with off.segment("step.decode.upload", args) as into:
        assert into is None
    assert set(args) == {"plan_s"}
    srv = ServingEngine(tiny[0], **SERVE_KW)
    srv.serve(_requests(tiny[1], n=3))
    for s in _named(srv.timeline.events(), "step"):
        assert 0 < s["args"]["kv_s"] < s["dur"] * 1e-6
    srv.close()


# ---------------------------------------------------------- a stalled step
def _steady(tiny, n_tokens=56):
    """An engine on three long requests: after their prefill step, some
    ``n_tokens`` decode-only steps of one shape."""
    engine, cfg = tiny
    srv = ServingEngine(engine, **SERVE_KW)
    rng = np.random.default_rng(1)
    handles = [srv.submit(Request(
        uid=i, prompt=rng.integers(0, cfg.vocab_size, 6, dtype=np.int32),
        max_new_tokens=n_tokens)) for i in range(3)]
    return srv, handles


def _stalls(srv):
    return [e for e in srv.timeline.events()
            if e["ph"] == "i" and e["name"] == "stall"]


def _run_until_done(srv):
    while srv.step():
        pass
    srv.close()


def test_a_step_that_slept_is_a_stall_off_the_cpu_in_its_commit(tiny):
    srv, handles = _steady(tiny)
    on_tokens = handles[0]._on_tokens

    def slow_client(toks):
        on_tokens(toks)
        if len(handles[0].tokens()) == 45:
            time.sleep(0.2)

    handles[0]._on_tokens = slow_client
    _run_until_done(srv)
    stall, = [e["args"] for e in _stalls(srv) if e["args"]["wall_ms"] > 200]
    assert stall["cause"] == "offcpu" and stall["segment"] == "commit"
    assert stall["offcpu_ms"] > 150 and stall["gc_ms"] < 50
    assert stall["wall_ms"] > serving.STALL_FACTOR * stall["median_ms"] > 0
    step, = [s for s in _named(srv.timeline.events(), "step")
             if s["args"]["iteration"] == stall["iteration"]]
    assert step["dur"] * 1e-3 == pytest.approx(stall["wall_ms"], rel=0.05)
    counts = {c: v.value for c, v in srv._c_step_stalls.items()}
    assert counts["offcpu"] >= 1 and set(counts) == set(serving.STALL_CAUSES)
    assert sum(counts.values()) == len(_stalls(srv))
    assert 'serving_step_stalls_total{cause="offcpu"}' in \
        srv.metrics.prometheus_text()


def test_a_collection_inside_a_step_fills_gc_s_and_is_an_event(tiny):
    srv, handles = _steady(tiny)
    on_tokens = handles[1]._on_tokens

    def littering_client(toks):
        on_tokens(toks)
        if len(handles[1].tokens()) == 45:
            junk = []
            for _ in range(300_000):        # cycles only the collector frees
                a = []
                a.append(a)
                junk.append(a)
            del junk
            gc.collect()

    handles[1]._on_tokens = littering_client
    _run_until_done(srv)
    events = srv.timeline.events()
    runs = [e for e in events if e["ph"] == "X" and e["name"] == "gc"
            and e["args"]["collected"] >= 299_000]
    assert len(runs) == 1 and runs[0]["args"]["generation"] == 2
    step, = [s for s in _named(events, "step")
             if s["args"]["iteration"] == runs[0]["args"]["step"]]
    assert step["args"]["gc_n"] >= 1
    assert step["args"]["gc_s"] >= runs[0]["dur"] * 1e-6 > 1e-3
    assert step["ts"] <= runs[0]["ts"] and \
        runs[0]["ts"] + runs[0]["dur"] <= step["ts"] + step["dur"]
    # a collection in ANOTHER thread's time, or outside a step, is not ours
    before = srv._gc.seconds
    gc.collect()
    assert srv._gc.seconds == before


def test_a_call_whose_tokens_come_late_is_a_device_wait(tiny):
    srv, _ = _steady(tiny)
    decode_fn = srv._get_decode_fn()
    calls = [0]

    class Late:
        """Tokens that take 0.2 s to reach the host."""

        def __init__(self, array):
            self.array = array

        def __array__(self, dtype=None, copy=None):
            time.sleep(0.2)
            return np.asarray(self.array)

    def stubbed(*args):
        nxt, cache, tokens = decode_fn(*args)
        calls[0] += 1
        return (Late(nxt) if calls[0] == 45 else nxt), cache, tokens

    srv._get_decode_fn = lambda: stubbed
    _run_until_done(srv)
    stall, = [e["args"] for e in _stalls(srv) if e["args"]["wall_ms"] > 200]
    assert stall["cause"] == "device_wait" and stall["segment"] == "wait"
    assert stall["wait_ms"] > 150 and stall["offcpu_ms"] < 50


def test_close_takes_the_collector_hook_out(tiny):
    engine, cfg = tiny
    before = list(gc.callbacks)
    a = ServingEngine(engine, **SERVE_KW)
    b = ServingEngine(engine, **SERVE_KW)
    off = ServingEngine(engine, trace_capacity=0, **SERVE_KW)
    assert len(gc.callbacks) == len(before) + 2      # the ring off: no hook
    a.serve(_requests(cfg, n=2))
    a.close()
    a.close()                                        # idempotent
    assert len(gc.callbacks) == len(before) + 1
    # an engine nobody closed takes its hook with it when it is collected
    del b, off
    gc.collect()
    assert gc.callbacks == before


def test_a_ring_that_wrapped_says_so_once_at_close(tiny, caplog):
    import logging

    engine, cfg = tiny
    srv = ServingEngine(engine, trace_capacity=16, **SERVE_KW)
    srv.serve(_requests(cfg, n=3))
    assert srv.stats()["trace_events_dropped"] > 0
    log = logging.getLogger("deepspeed_tpu")
    log.addHandler(caplog.handler)
    try:
        srv.close()
        srv.close()
    finally:
        log.removeHandler(caplog.handler)
    said = [r.getMessage() for r in caplog.records
            if "trace ring" in r.getMessage()]
    assert len(said) == 1 and "trace_capacity=16" in said[0] \
        and "events/s" in said[0]


def test_the_default_ring_holds_two_minutes_of_the_densest_cell():
    """ISSUE 36 E: the capacity follows from a requirement (twice the chat
    cell's ~47 steps/s of PR 35, ~7.5 events a step, plus ~20 requests/s
    x 3, for 120 s), and every default names the same number."""
    import inspect

    from deepspeed_tpu.autotuning import space

    need = 120 * (95 * 7.5 + 20 * 3)
    assert need <= trace.DEFAULT_CAPACITY == 131072 < 2 * need
    assert trace.TraceTimeline().capacity == trace.DEFAULT_CAPACITY
    for fn in (ServingEngine.__init__, deepspeed_tpu.init_serving):
        assert inspect.signature(fn).parameters["trace_capacity"].default \
            == trace.DEFAULT_CAPACITY
    assert space.BASE_SERVING_CONFIG["trace_capacity"] == trace.DEFAULT_CAPACITY


# ------------------------------------------------------- the profiler's clock
def _host_event_names(profile_dir):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(idle_gaps.find_xplane(str(profile_dir)))
    return {ev.name for plane in data.planes
            if not idle_gaps.DEVICE_PLANE.match(plane.name)
            for line in plane.lines for ev in line.events
            if ev.name.startswith("ds.")}


def test_spans_land_in_a_profile_with_the_ring_off(tiny, tmp_path):
    """A ``jax.profiler`` trace on the CPU holds ``ds.serve.*`` and
    ``ds.train.*`` on a host line — from an engine whose ring is off
    (``trace_capacity=0`` turns the ring off, not the annotations)."""
    engine, cfg = tiny
    srv = ServingEngine(engine, trace_capacity=0, **SERVE_KW)
    train, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(cfg), config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}}})
    batch = {"input_ids": np.zeros(
        (train.train_batch_size(), 16), np.int32)}
    # every span and segment has run before the profile starts: an
    # annotation kept from then would be silent in it
    srv.serve(_requests(cfg, n=2, seed=1))
    srv.serve(_requests(cfg, n=2), profile_dir=str(tmp_path))
    window = trace.ProfilerWindow(str(tmp_path / "train"))
    assert window.start()
    train.train_batch(batch)
    window.stop()
    assert len(srv.timeline) == 0 and srv.timeline.events() == []
    names = _host_event_names(tmp_path / "plugins") \
        | _host_event_names(tmp_path / "train")
    assert {"ds.serve.step", "ds.serve.step.admit", "ds.serve.step.prefill",
            "ds.serve.step.decode", "ds.serve.step.post", "ds.serve.prefill",
            "ds.serve.decode"} <= names
    # ... and the segments inside them (ISSUE 36): annotations only
    assert {f"ds.serve.step.{phase}.{seg}"
            for phase in ("prefill", "decode")
            for seg in ("plan", "upload", "commit")} <= names
    assert {f"ds.serve.{call}.{seg}" for call in ("prefill", "decode")
            for seg in ("enqueue", "wait")} <= names
    assert {"ds.train.step", "ds.train.batch_prep",
            "ds.train.dispatch"} <= names


# --------------------------------------------------------- idle gaps by span
def test_idle_gaps_are_partitioned_by_the_innermost_span():
    spans = [("cb.window", 0, 100), ("cb.step", 10, 50),
             ("ds.serve.step", 11, 49), ("ds.serve.step.admit", 11, 15),
             ("ds.serve.step.decode", 15, 45), ("ds.serve.decode", 20, 40),
             ("cb.harvest", 50, 60)]
    ops = [(22, 30), (30, 38), (70, 80), (75, 78)]
    res = idle_gaps.idle_by_span(ops, spans)
    ns = {name: round(sec * 1e9, 6) for name, sec, _ in res["by_span"]}
    # idle: 0-22, 38-70, 80-100.  One gap (38-70) crosses six spans.
    assert ns == {"outside_any_span": 40, "cb.step": 2,
                  "ds.serve.step.admit": 4, "ds.serve.step.decode": 10,
                  "ds.serve.decode": 4, "ds.serve.step": 4, "cb.harvest": 10}
    assert round(res["idle_s"] * 1e9, 6) == 74
    assert round(res["window_s"] * 1e9, 6) == 100
    assert abs(sum(share for _, _, share in res["by_span"]) - 1.0) < 1e-12
    # no window span: first to last device operation
    res = idle_gaps.idle_by_span(ops, spans[1:])
    assert round(res["window_s"] * 1e9, 6) == 58
    assert round(res["idle_s"] * 1e9, 6) == 32


def test_in_call_puts_lead_and_lag_on_one_clock():
    """``idle_gaps.in_call``: per in-flight annotation, the module
    executions that belong to it (midpoint inside); lead = annotation start
    to the first module, lag = the last module's end to the annotation's
    end, both SIGNED; a module that reaches outside its annotation is
    counted, with the worst offset, and so is a call no module ran in.
    Segments are not calls."""
    ms = 1e6                                        # the profile is in ns
    spans = [("cb.window", 0, 1000 * ms),
             ("ds.serve.decode", 10 * ms, 30 * ms),
             ("ds.serve.decode.enqueue", 10 * ms, 11 * ms),
             ("ds.serve.decode.wait", 11 * ms, 30 * ms),
             ("ds.serve.decode", 40 * ms, 62 * ms),
             ("ds.serve.decode", 70 * ms, 95 * ms),
             ("ds.serve.decode", 100 * ms, 120 * ms),    # clocks disagree
             ("ds.serve.decode", 130 * ms, 150 * ms),    # nothing ran
             ("ds.serve.decode", 2000 * ms, 2020 * ms),  # outside window
             ("ds.serve.prefill", 200 * ms, 260 * ms)]
    modules = [("jit_decode_step", 12 * ms, 29 * ms),     # lead 2, lag 1
               ("jit_decode_step", 43 * ms, 60 * ms),     # lead 3, lag 2
               ("jit_decode_step", 74 * ms, 92 * ms),     # lead 4, lag 3
               ("jit_decode_step", 99.5 * ms, 115 * ms),  # lead -0.5, lag 5
               ("jit_prefill", 205 * ms, 230 * ms),       # two modules in
               ("jit_other", 231 * ms, 250 * ms)]         # one call
    res = idle_gaps.in_call(spans, modules, (0, 1000 * ms))
    assert set(res) == {"ds.serve.decode", "ds.serve.prefill"}
    dec = res["ds.serve.decode"]
    assert dec["calls"] == 4
    assert dec["lead_ms"] == pytest.approx([2.5, 3.85])   # -0.5, 2, 3, 4
    assert dec["lag_ms"] == pytest.approx([2.5, 4.7])     # 1, 2, 3, 5
    assert dec["overhead_ms"] == pytest.approx([4.75, 6.7])   # 3, 4.5, 5, 7
    assert dec["device_ms"] == pytest.approx(17.0)
    assert (dec["outside"], dec["empty"]) == (1, 1)
    assert dec["worst_outside_ms"] == pytest.approx(0.5)
    pre = res["ds.serve.prefill"]
    assert pre["calls"] == 1 and pre["outside"] == pre["empty"] == 0
    assert pre["lead_ms"] == pytest.approx([5.0, 5.0])
    assert pre["lag_ms"] == pytest.approx([10.0, 10.0])
    assert pre["device_ms"] == pytest.approx(45.0)
    # no window: every call is read
    assert idle_gaps.in_call(spans, modules)["ds.serve.decode"]["empty"] == 2


# ------------------------------------------------ names the benchmark reads
def _module_name(lowered) -> str:
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


def _serving_module(tiny, kind):
    engine, cfg = tiny
    kw = dict(SERVE_KW)
    if kind == "jit_decode_windowed":
        kw.update(host_blocks=16, swap_batch=4, resident_window_blocks=4)
    elif kind == "jit_prefill_fused":
        dcfg = gpt2.GPT2Config(vocab_size=cfg.vocab_size, max_seq_len=64,
                               num_layers=1, num_heads=2, hidden_size=32)
        kw.update(spec_tokens=2, draft=gpt2.build(dcfg))
    srv = ServingEngine(engine, **kw)
    prof = ServingFlopsProfiler(srv)
    if "prefill" in kind:
        srv._get_prefill_fn()
        return _module_name(prof.lower("prefill"))
    srv._get_decode_fn()
    if kind != "jit_decode_windowed":
        return _module_name(prof.lower("decode"))
    args = prof._abstract_args("decode") + (
        jax.ShapeDtypeStruct((srv.slots,), jnp.int32),)    # window_start
    with srv._decode_ctx():
        return _module_name(
            jax.jit(srv._program_bodies["decode"]).lower(*args))


def _train_module(tiny, kind):
    _, cfg = tiny
    train, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(cfg), config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}}})
    batch = train._shard_batch(train._reshape_global_batch(
        {"input_ids": np.zeros((train.train_batch_size(), 16), np.int32)}),
        leading_gas_dim=True)
    return _module_name(train._train_step_fn.lower(
        train.state, batch, train._dropout_rng))


@pytest.mark.parametrize("kind,lower", [
    ("jit_decode_step", _serving_module), ("jit_prefill", _serving_module),
    ("jit_decode_windowed", _serving_module),
    ("jit_prefill_fused", _serving_module), ("jit_train_step", _train_module)])
def test_the_programs_keep_the_names_the_reduction_finds_them_by(
        tiny, kind, lower):
    """``chipbench/layer_metrics`` match ``^jit_decode``, ``^jit_prefill``
    and ``^jit_train_step`` against the XLA module names, which are the
    Python function names behind ``sentry.wrap``."""
    from deepspeed_tpu import comm

    comm.reset_topology()
    assert lower(tiny, kind) == kind


def test_an_expert_familys_spans_carry_the_routing_names():
    """``chipbench``'s ``expert_rows_per_read`` and ``families/olmoe.py``
    read ``experts_touched`` / ``expert_rows`` off the in-flight ``decode``
    and ``prefill`` spans; ``stats()`` and the registry carry the totals;
    the routed FFN's three phases are named scopes of the programs."""
    from deepspeed_tpu import comm
    from deepspeed_tpu.models import mixtral
    from deepspeed_tpu.moe import routed

    comm.reset_topology()
    cfg = mixtral.MixtralConfig.tiny()
    cfg.use_flash = False
    srv = deepspeed_tpu.init_serving(mixtral.build(cfg),
                                     config={"dtype": "fp32"}, **SERVE_KW)
    srv.serve(_requests(cfg, n=3))
    flights = [e for e in srv.timeline.events() if e["ph"] == "X"
               and e["name"] in IN_FLIGHT]
    assert {e["name"] for e in flights} == set(IN_FLIGHT)
    for e in flights:
        assert set(routed.RECORD) <= set(e["args"]), e
        assert e["args"]["expert_rows"] >= e["args"]["experts_touched"] > 0
        assert e["args"]["expert_rows"] >= e["args"]["expert_rows_max"] > 0
    st = srv.stats()
    assert st["moe_expert_rows"] == sum(e["args"]["expert_rows"]
                                        for e in flights)
    assert st["moe_experts_touched"] == sum(e["args"]["experts_touched"]
                                            for e in flights)
    text = jax.jit(srv._program_bodies["decode"]).lower(
        srv.engine.params, srv._cache, jnp.zeros(3, jnp.int32),
        jnp.zeros(3, jnp.int32), jnp.zeros((3, srv._nbper), jnp.int32),
        *srv._samp_args(np.zeros(3, np.int32))).as_text(debug_info=True)
    for scope in ("layer/moe/route", "layer/moe/experts",
                  "layer/moe/combine", "layer/attn"):
        assert scope in text, scope
    srv.close()


def _lower_tpu(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args) \
        .mlir_module()


def _kernel_names(mlir_text):
    return set(re.findall(r'kernel_name = "([^"]+)"', mlir_text))


def test_lowered_kernels_carry_their_own_names():
    """The Mosaic custom calls of the main paths, lowered for the TPU from
    here, are named after the kernel — what a device trace then prints as
    ``mosaic:<name>`` (every ``pallas_call`` site's ``name=`` is checked in
    ``tests/chipbench/test_program_span_metrics.py``)."""
    from deepspeed_tpu.ops import decode_attention as da
    from deepspeed_tpu.ops import flash_attention as fa

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    slots, h, hd, bs, nbper = 8, 4, 64, 32, 4
    pool = sds((1 + slots * nbper, h, bs, hd), jnp.bfloat16)
    bt, pos = sds((slots, nbper), jnp.int32), sds((slots,), jnp.int32)
    got = set()
    for t, kernel in ((1, da.paged_decode_attention_pallas),
                      (4, da.paged_verify_attention_pallas)):
        got |= _kernel_names(_lower_tpu(
            lambda q, k, v, bt, pos, kernel=kernel: kernel(
                q, k, v, bt, pos, interpret=False),
            sds((slots, h, t, hd), jnp.bfloat16), pool, pool, bt, pos))
    assert got == {"paged_decode_attn", "paged_verify_attn"}

    # the experts' grouped matmul (chipbench's expert_ffn_ms selects it)
    from deepspeed_tpu.moe.grouped_matmul import moe_gmm

    assert _kernel_names(_lower_tpu(
        lambda x, w, gs: moe_gmm(x, w, gs, jnp.int32(1), interpret=False),
        sds((64, 128), jnp.bfloat16), sds((2, 8, 128, 256), jnp.bfloat16),
        sds((8,), jnp.int32))) == {"moe_gmm"}

    def loss(q, k, v, block):
        o = fa.flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block, interpret=False)
        return o.astype(jnp.float32).sum()

    for seq, block, want in (
            (1024, 1024, {"flash_fwd_resident", "flash_bwd_fused"}),
            (2048, 512, {"flash_fwd_chunked", "flash_bwd_dq_chunked",
                         "flash_bwd_dkv_chunked"})):
        q = sds((2, 4, seq, 64), jnp.bfloat16)
        names = _kernel_names(_lower_tpu(
            jax.grad(lambda q, k, v: loss(q, k, v, block),
                     argnums=(0, 1, 2)), q, q, q))
        assert names == want, (seq, names)
