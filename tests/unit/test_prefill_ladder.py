"""The prefill call as a BUDGET of ``prefill_batch * prefill_chunk`` tokens
(ISSUE 52) at the rung of the ladder that carries the most real tokens
(ISSUE 54): a row alone, or in its turn among two or three long ones, runs
``[1, 4w]``; a full group, or one that is nearly done, shares ``[4, w]``.
Tiny float32 engines on the CPU: a request's tokens do not depend on the
shapes or the order of the calls its prompt went through, for every layer
kind the engine serves; the rule, the bound on waiting, the counters and
what is sized by the widest row."""

import contextlib
import json
import logging
import os
import sys
import types

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.sentry import backend_compiles
from deepspeed_tpu.inference import serving
from deepspeed_tpu.inference.serving import Request, prefill_ladder
from deepspeed_tpu.ops import decode_attention
from deepspeed_tpu.utils.logging import logger

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import families  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

pytestmark = pytest.mark.limit(110)

#: layer kind -> the benchmark's configuration whose rehearsal widths have
#: it: dense K / V, a window ring (window 24) beside a full layer, a latent
#: pool, a learned selector (top 32) over a third leaf, a recurrent state a
#: slot beside a latent pool
KINDS = {"dense": "opt-1.3b", "window": "command-a-plus-05-2026",
         "latent": "mistral-small-4-119b-2603", "sparse": "keye-vl2-30b-a3b",
         "state": "kimi-linear-48b-a3b"}
CHUNK, BATCH = 8, 4
RUNGS = [(4, 8), (1, 32)]
#: prompts past the window (24) and the selection (32), one a multiple of
#: no rung (37), more of them than slots (a slot is used twice)
LENGTHS = [37, 70, 9, 52, 33]


@pytest.fixture(scope="module")
def engines():
    """kind -> ``(engine, vocab)``, built on first use and kept for the
    module: a sampling engine serves greedy requests (temperature 0) too."""
    built = {}

    def get(kind):
        if kind not in built:
            deepspeed_tpu.comm.reset_topology()
            config = cb_run._rehearsed(json.load(open(os.path.join(
                ROOT, "chipbench", "configs", KINDS[kind] + ".json"))), True)
            spec = families.load(config).build(config)
            params = spec.init_fn(jax.random.PRNGKey(3))
            built[kind] = (deepspeed_tpu.init_serving(
                spec, config={"dtype": "fp32"}, params=params, slots=3,
                max_seq_len=128, block_size=8, prefill_chunk=CHUNK,
                prefill_batch=BATCH), int(config["vocab_size"]))
        return built[kind]

    yield get
    for srv, _ in built.values():
        srv.close()


def _requests(vocab, lengths, sampled, new=6, draw=0):
    rng = np.random.default_rng(draw)
    how = dict(temperature=0.9, top_k=40, top_p=0.95) if sampled else {}
    return [Request(uid=i, prompt=rng.integers(0, vocab, n).astype(np.int32),
                    max_new_tokens=new, seed=2 ** 31 + 97 * i, **how)
            for i, n in enumerate(lengths)]


@contextlib.contextmanager
def _only(srv, rung):
    """``srv`` making every prefill call at ``rung``, its groups taken
    ``rung[0]`` rows at a time."""
    batch = srv.prefill_batch
    srv._rung_for, srv.prefill_batch = (lambda group: (rung, group)), rung[0]
    try:
        yield
    finally:
        del srv._rung_for
        srv.prefill_batch = batch


def _forget(srv):
    """Empty ``srv``'s prefix trie, so the next run prefills whole prompts
    again (an idle engine: the trie alone holds its blocks)."""
    while srv._prefix is not None and srv._prefix.evict_one(srv._alloc):
        pass


def _calls(srv, since=0):
    """The span arguments of every prefill call after ``since`` events, in
    the order the calls were made."""
    return [e["args"] for e in srv.timeline.events()[since:]
            if e["ph"] == "X" and e["name"] == "prefill"]


def _shapes(srv, since):
    """The ``shape`` of every prefill call after ``since`` events."""
    return [c["shape"] for c in _calls(srv, since)]


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_requests_tokens_do_not_depend_on_its_prefill_calls_shapes(
        engines, kind, sampled):
    """Every request through ``[4, w]`` calls and through ``[1, 4w]``
    calls, through the ladder beside other prefilling rows (which take
    turns at the wide row), and through the ladder alone: the same
    tokens."""
    srv, vocab = engines(kind)
    assert srv._rungs == RUNGS and srv._ladder_stop is None

    def reqs():
        _forget(srv)
        return _requests(vocab, LENGTHS, sampled)

    with _only(srv, RUNGS[0]):
        since = len(srv.timeline.events())
        want = srv.serve(reqs())
        assert set(_shapes(srv, since)) == {"4x8"}
    for rung in RUNGS[1:]:
        with _only(srv, rung):
            since = len(srv.timeline.events())
            got = srv.serve(reqs())
            assert set(_shapes(srv, since)) == {srv._rung_name(rung)}
        for uid in want:
            np.testing.assert_array_equal(got[uid], want[uid],
                                          err_msg=f"{rung}: uid {uid}")
    # the ladder itself: rows together (three long rows take turns at the
    # wide rung; what is left of them shares the narrow one)
    since, turns = len(srv.timeline.events()), srv.stats()["prefill_turns"]
    together = srv.serve(reqs())
    assert set(_shapes(srv, since)) == {"4x8", "1x32"}
    assert srv.stats()["prefill_turns"] > turns
    # alone: wide calls, and the narrow rung for a tail it carries as well
    alone = {}
    since, turns = len(srv.timeline.events()), srv.stats()["prefill_turns"]
    for r in reqs():
        alone.update(srv.serve([r]))
    # (37, 70, 9, 52, 33 tokens: 1 + 2 + 1 + 2 + 1 calls carry more than
    # the narrow rung would)
    assert [c["shape"] for c in _calls(srv, since)
            if c["tokens"] > CHUNK] == ["1x32"] * 7
    assert srv.stats()["prefill_turns"] == turns
    for uid in want:
        np.testing.assert_array_equal(together[uid], want[uid])
        np.testing.assert_array_equal(alone[uid], want[uid])
    if kind == "state":
        assert srv.stats()["kv_state"]["resets"] > len(LENGTHS)
    if kind == "window":
        assert srv.stats()["kv_kinds"]["sliding"]["released"] > 0


def _tiny(tiny_engine, **kw):
    engine, cfg = tiny_engine
    kw = {"slots": 10, "max_seq_len": 128, "block_size": 8,
          "prefill_chunk": 8, **kw}
    return serving.ServingEngine(engine, **kw), cfg


def test_a_preempted_request_resumes_through_a_wide_rung(tiny_engine):
    """A pool too small for three long rows: the latest is preempted and,
    re-admitted when the others are done, re-prefills its prompt and what
    it generated ALONE, ``[1, 4w]`` a call; every token is the roomy
    engine's."""
    roomy, cfg = _tiny(tiny_engine, slots=3)
    want = roomy.serve(_requests(cfg.vocab_size, [60, 58, 62], False, 30))
    roomy.close()
    srv, _ = _tiny(tiny_engine, slots=3, num_blocks=1 + 28)
    got = srv.serve(_requests(cfg.vocab_size, [60, 58, 62], False, 30))
    st = srv.stats()
    assert st["evicted"] > 0 and st["prefill_shapes"]["1x32"] > 0
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])
    srv.close()


@pytest.mark.parametrize("rows,steps", [
    (1, [["1x32"]]),
    (2, [["1x32"], ["1x32"], ["4x8"]]),
    (3, [["1x32"], ["1x32"], ["1x32"], ["4x8"]]),
    (4, [["4x8"]]),
    (5, [["4x8", "1x32"]]),
    (9, [["4x8", "4x8", "1x32"]])])
def test_ready_rows_are_cut_into_the_calls_the_issue_names(tiny_engine, rows,
                                                           steps):
    """``rows`` fresh prompts of 40 tokens: groups of ``prefill_batch`` in
    admission order, each at the rung that carries most — a full group
    ``[4, 8]``, a row alone ``[1, 32]``, two or three one ``[1, 32]`` each
    by turns and then what is left of them (8 each) in one ``[4, 8]``.
    ``steps``: the shapes of the calls of the first steps that made any."""
    srv, cfg = _tiny(tiny_engine)
    handles = [srv.submit(r) for r in _requests(
        cfg.vocab_size, [40] * rows, False, 2)]
    while srv.step():                      # (a call's span lands as it ends)
        pass
    spans = _calls(srv)
    # ``step``: the iteration that MADE the call, not the one that
    # harvested it
    first = spans[0]["step"]
    calls = [[c for c in spans if c["step"] == first + i]
             for i in range(len(steps))]
    assert [[c["shape"] for c in step] for step in calls] == steps
    # admission order: the slots of a step's calls are 0 .. rows-1, less
    # those that wait their turn (a group of two or three: one runs)
    turns = 1 < rows < 4
    for i, step in enumerate(calls):
        assert sum((c["slots"] for c in step), []) == (
            list(range(rows)) if not turns or i == rows else [i])
    left = dict.fromkeys(range(rows), 40)
    for c in spans:
        j, width = map(int, c["shape"].split("x"))
        assert c["width"] == width and c["rows"] == len(c["slots"]) <= j
        assert c["rows"] <= c["ready"] <= BATCH
        assert c["tokens"] == sum(min(width, left[s]) for s in c["slots"])
        for s in c["slots"]:
            left[s] -= min(width, left[s])
    assert not any(left.values()) and all(h.done for h in handles)
    assert (srv.stats()["prefill_turns"] > 0) == turns
    srv.close()


def _rule(left, waited=None, rungs=RUNGS, batch=BATCH):
    """``_rung_for`` on a group of rows with ``left`` prompt tokens to go
    and ``waited`` calls passed over, slots 0.. in admission order."""
    waited = waited or [0] * len(left)
    stub = types.SimpleNamespace(
        _rungs=rungs, prefill_batch=batch,
        _active={s: types.SimpleNamespace(plen_eff=n, base=0, waited=w)
                 for s, (n, w) in enumerate(zip(left, waited))})
    return serving.ServingEngine._rung_for(stub, list(range(len(left))))


@pytest.mark.parametrize("left,waited,want", [
    ([3, 6], None, ((4, 8), [0, 1])),      # rows nearly done: 9 against 3
    ([5, 100], None, ((4, 8), [0, 1])),    # 5 + 8 against 5
    ([2, 3, 4], None, ((4, 8), [0, 1, 2])),
    ([8, 9, 9], None, ((4, 8), [0, 1, 2])),        # 24 against 8
    ([30, 9, 9], [0, 1, 1], ((4, 8), [0, 1, 2])),  # in turn: 25 against 9
])
def test_the_rule_picks_the_narrow_rung_when_it_carries_more(left, waited,
                                                             want):
    assert _rule(left, waited) == want


@pytest.mark.parametrize("left,waited,want", [
    ([40, 40], None, ((1, 32), [0])),      # 32 against 16
    ([40, 40], [0, 1], ((1, 32), [1])),    # whoever waited longest
    ([100, 5], None, ((1, 32), [0])),      # 32 against 13
    ([40, 40, 40], [1, 0, 1], ((1, 32), [0])),   # admission between equals
    ([9, 30, 9], [1, 2, 1], ((1, 32), [1])),     # 30 against 24
])
def test_the_rule_picks_the_wide_rung_when_it_carries_more(left, waited,
                                                           want):
    assert _rule(left, waited) == want


@pytest.mark.parametrize("left,rungs,want", [
    ([40] * 4, RUNGS, ((4, 8), [0, 1, 2, 3])),   # 32 = 32: today's call
    ([16, 16], RUNGS, ((4, 8), [0, 1])),         # 16 = 16
    ([5], RUNGS, ((4, 8), [0])),                 # a tail either carries
    ([40], RUNGS, ((1, 32), [0])),
    # the rule is written on the ladder: with the rung between, three long
    # rows run two of them (32 = 32 against the wide row, and more rows)
    ([40] * 3, [(4, 8), (2, 16), (1, 32)], ((2, 16), [0, 1])),
    ([40, 9, 9], [(4, 8), (2, 16), (1, 32)], ((1, 32), [0])),
    ([300, 300], [(4, 128), (1, 512)], ((1, 512), [0])),
    ([50, 100], [(4, 128), (1, 512)], ((4, 128), [0, 1])),
    ([7], [(1, 64)], ((1, 64), [0])),            # prefill_batch 1
])
def test_on_a_tie_the_rung_with_more_rows_runs(left, rungs, want):
    assert _rule(left, rungs=rungs, batch=rungs[0][0]) == want


@pytest.mark.parametrize("waited,want", [
    # two rows have waited prefill_batch - 1 calls: the wide rung would pass
    # one of them over again, so it is not eligible
    ([3, 3, 0], ((4, 8), [0, 1, 2])),
    ([0, 3, 0], ((1, 32), [1])),
    ([2, 2, 0], ((1, 32), [0])),
])
def test_no_rung_passes_over_a_row_that_has_waited_its_bound(waited, want):
    assert _rule([40, 40, 40], waited) == want


def _watched(srv):
    """``srv`` stepped until idle; -> for every slot the steps in which a
    call ran it, and the largest ``waited`` any prefilling row showed."""
    worst = 0
    while srv.step():
        worst = max([worst] + [st.waited for st in srv._active.values()
                               if st.phase == "prefill"])
    ran = {}
    for c in _calls(srv):
        for slot in c["slots"]:
            ran.setdefault(slot, []).append(c["step"])
    return ran, worst


@pytest.mark.parametrize("lengths", [
    [100, 5], [90, 70, 50], [90, 70, 50, 5, 100, 30, 64],
    [120, 16, 120], [64, 120, 16, 120, 16, 120, 120, 30, 90]])
def test_a_ready_row_is_passed_over_a_bounded_number_of_calls(tiny_engine,
                                                              lengths):
    """Every group makes a call a step, so a row's turns lie at most
    ``len(group)`` steps apart: it is passed over at most ``len(group) - 1
    <= prefill_batch - 1`` calls in a row, whatever the rows around it
    have left."""
    srv, cfg = _tiny(tiny_engine)
    handles = [srv.submit(r) for r in _requests(
        cfg.vocab_size, lengths, False, 2)]
    ran, worst = _watched(srv)
    assert srv.stats()["prefill_turns"] > 0
    assert worst <= min(len(lengths), BATCH) - 1
    first = min(min(steps) for steps in ran.values())
    for slot, steps in ran.items():
        gaps = np.diff([first - 1] + steps)
        assert gaps.max() <= min(len(lengths), BATCH), (slot, steps)
    assert all(h.done for h in handles)
    srv.close()


def test_a_short_prompt_behind_a_long_one_waits_one_call(tiny_engine):
    """5 tokens admitted behind 100: the long row takes the first call
    whole, the second call is the short row's (it went to the front of the
    turn order) — its first token after two calls, not after the long
    prompt's thirteen chunks."""
    srv, cfg = _tiny(tiny_engine)
    long, short = [srv.submit(r) for r in _requests(
        cfg.vocab_size, [100, 5], False, 2)]
    while not short.tokens():
        assert srv.step()
    assert srv.stats()["prefill_calls"] <= 2 and not long.tokens()
    while srv.step():
        pass
    assert [(c["shape"], c["slots"]) for c in _calls(srv)] == [
        ("1x32", [0]), ("4x8", [0, 1]), ("1x32", [0]), ("1x32", [0])]
    srv.close()


def test_stats_count_the_shapes_and_the_fill(tiny_engine):
    srv, cfg = _tiny(tiny_engine)
    st = srv.stats()
    assert st["prefill_shapes"] == {"4x8": 0, "1x32": 0}
    assert st["prefill_fill"] is None and st["prefill_turns"] == 0
    srv.serve(_requests(cfg.vocab_size, [40, 40, 40, 40, 40], False, 2))
    st = srv.stats()
    # five rows: [4, 8] x 5 chunks beside [1, 32] + [4, 8] (the fifth row's
    # last 8 tokens: either rung carries them, the one with more rows runs)
    assert st["prefill_shapes"] == {"4x8": 6, "1x32": 1}
    assert st["prefill_calls"] == 7 and st["prefill_turns"] == 0
    assert st["prefill_fill"] == pytest.approx(5 * 40 / (7 * 32))
    text = srv.metrics.prometheus_text()
    assert 'serving_prefill_calls_by_shape_total{shape="1x32"} 1' in text
    srv.close()


def test_stats_spans_and_the_exposition_count_the_turns(tiny_engine):
    """Two rows of 64: four ``[1, 32]`` calls by turns; the last runs with
    its row alone (the other is done), so three of them made a row wait."""
    srv, cfg = _tiny(tiny_engine)
    srv.serve(_requests(cfg.vocab_size, [64, 64], False, 2))
    st = srv.stats()
    assert st["prefill_shapes"] == {"4x8": 0, "1x32": 4}
    assert st["prefill_turns"] == 3 and st["prefill_fill"] == 1.0
    assert [(c["slots"], c["ready"], c["rows"]) for c in _calls(srv)] == [
        ([0], 2, 1), ([1], 2, 1), ([0], 2, 1), ([1], 1, 1)]
    text = srv.metrics.prometheus_text()
    assert "serving_prefill_turns_total 3" in text
    assert "# TYPE serving_prefill_turns_total counter" in text
    srv.close()


def test_tokens_are_a_roomy_engines_with_turns_and_a_preemption(tiny_engine):
    """Sampled requests through a pool that cannot hold them all: rows take
    turns at the wide rung, one is preempted and resumes, and every token
    is the roomy engine's (whose rows take turns too, at other times)."""
    lengths = [61, 47, 66, 59]
    roomy, cfg = _tiny(tiny_engine, slots=4)
    want = roomy.serve(_requests(cfg.vocab_size, lengths, True, 24))
    assert roomy.stats()["evicted"] == 0
    roomy.close()
    srv, _ = _tiny(tiny_engine, slots=4, num_blocks=1 + 30)
    got = srv.serve(_requests(cfg.vocab_size, lengths, True, 24))
    st = srv.stats()
    assert st["evicted"] > 0 and st["prefill_turns"] > 0
    assert st["prefill_shapes"]["1x32"] > 0 and st["prefill_shapes"]["4x8"] > 0
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])
    srv.close()


def test_nothing_compiles_after_the_first_prefill_call(tiny_engine):
    """Every rung is built, and has run once on pad rows, with the first
    prefill call: a rung first used much later traces and compiles
    nothing."""
    srv, cfg = _tiny(tiny_engine, debug_checks=True)
    assert srv.compile_budget == 2 + 1 and srv.compile_count == 0
    srv.serve(_requests(cfg.vocab_size, [20, 20, 20, 20], False, 3))
    st = srv.stats()
    assert st["prefill_shapes"] == {"4x8": 3, "1x32": 0}
    assert st["compile_count"] == 3 and sorted(srv.compiled_programs) == [
        ("decode", 10), ("prefill", 8, 4), ("prefill", 32, 1)]
    assert sorted(srv.sentry.report()) == [
        "decode", "prefill[1x32]", "prefill[4x8]"]
    traces, compiled = srv.sentry.traces, backend_compiles()
    srv.serve(_requests(cfg.vocab_size, [50], False, 3, draw=1))
    st = srv.stats()
    assert st["prefill_shapes"]["1x32"] > 0
    assert st["compile_count"] == 3 and srv.sentry.traces == traces
    assert backend_compiles() == compiled and st["retraces_observed"] == 0
    # one host buffer a rung
    assert set(st["operands"]) == {"decode", "prefill", "prefill[1x32]"}
    srv.close()


def test_the_window_ring_holds_the_window_and_the_widest_row(engines):
    srv, _ = engines("window")
    window = srv._windows["window"]
    assert srv._prefill_width == 32
    assert srv._ring.width == -(-(window + 32) // srv.block_size) + 1
    assert srv.stats()["kv_kinds"]["sliding"]["table_width"] \
        == srv._ring.width


def _narrow_plan(monkeypatch, cfg):
    """The prefill kernel's VMEM budget made the smallest under which its
    plan takes the tiny engine's narrow rows (8 tokens): it then takes no
    32-token row.  -> the budget."""
    heads, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    fits = lambda t: decode_attention.prefill_row_fits(  # noqa: E731
        heads, heads, 8, hd, 4, t, 128 // 8)
    budget = next(b for b in range(1 << 10, 1 << 24, 1 << 10)
                  if monkeypatch.setattr(
                      decode_attention, "_PREFILL_VMEM_BUDGET", b) or fits(8))
    assert fits(8) and not fits(32)
    return budget


def _logged(caplog, build):
    """``build()`` and the constructor's log line."""
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            srv = build()
    finally:
        logger.removeHandler(caplog.handler)
    return srv, next(r.getMessage() for r in caplog.records
                     if "ServingEngine: slots=" in r.getMessage())


def test_a_ladder_stops_where_the_kernel_takes_no_wider_rows(
        tiny_engine, monkeypatch, caplog):
    """The prefill kernel's VMEM plan bounds the ladder (its budget made
    small here): where one KV head's share of a wide row does not fit a
    grid step, the wide rung is not built, and the engine's log line and
    ``stats()["prefill_shapes"]`` name the rungs built."""
    srv, cfg = _tiny(tiny_engine)
    assert srv._rungs == [(4, 8), (1, 32)] and srv._ladder_stop is None
    srv.close()
    budget = _narrow_plan(monkeypatch, cfg)
    (srv, cfg), line = _logged(caplog, lambda: _tiny(tiny_engine))
    assert srv._rungs == [(4, 8)], budget
    assert "the prefill kernel's plan takes no 1 x 32 query rows" \
        in srv._ladder_stop
    assert "prefill calls 4x8 (no wider rows: the prefill kernel" in line
    assert srv.compile_budget == 2
    out = srv.serve(_requests(cfg.vocab_size, [30], False, 3))
    assert srv.stats()["prefill_shapes"] == {"4x8": 4} and len(out) == 1
    assert srv.compile_count == 2
    srv.close()


def test_a_ladder_stops_at_the_cache(tiny_engine, caplog):
    (srv, cfg), line = _logged(
        caplog, lambda: _tiny(tiny_engine, max_seq_len=24))
    assert srv._rungs == [(4, 8)]
    assert "a row of 32 tokens passes the cache (24)" in srv._ladder_stop
    assert "prefill calls 4x8 (no wider rows: a row of 32" in line
    assert list(srv.stats()["prefill_shapes"]) == ["4x8"]
    assert srv.compile_budget == 2
    srv.close()


#: (query heads, KV heads, block, head dim, slots' table width) of the
#: cells' float pools read by ``paged_prefill_attn``: chat's OPT-1.3B,
#: OLMoE, Keye, Command A+
CELL_POOLS = {"opt": (32, 32, 32, 64, 32), "olmoe": (16, 16, 32, 128, 32),
              "keye": (32, 4, 32, 128, 512), "commanda": (128, 8, 32, 128, 512)}


@pytest.mark.parametrize("family,widest", [
    ("opt", 512), ("olmoe", 512), ("keye", 128), ("commanda", 128)])
def test_the_kernels_plan_says_which_cells_take_the_wide_row(family, widest):
    """``prefill_row_fits`` at the cells' shapes, bfloat16: every family
    plans the ``[4, 128]`` rows it runs today; OPT and OLMoE (one query head
    a KV head) plan a 512-token row, Keye (8 a KV head) and Command A+ (16)
    do not — 4,096 / 8,192 query rows' float32 accumulators pass a grid
    step's VMEM — so their ladders stop at ``[4, 128]``."""
    heads, hkv, bs, hd, nbper = CELL_POOLS[family]
    for t in (128, 512):
        assert decode_attention.prefill_row_fits(
            heads, hkv, bs, hd, 2, t, nbper) == (t <= widest), t


@pytest.mark.parametrize("batch,chunk,want", [
    (4, 128, [(4, 128), (1, 512)]),
    (6, 16, [(6, 16), (1, 96)]),
    (1, 64, [(1, 64)]),
    (8, 2, [(8, 2), (1, 16)]),
    (2, 8, [(2, 8), (1, 16)])])
def test_the_ladder_is_the_batch_and_the_row_alone(batch, chunk, want):
    assert prefill_ladder(batch, chunk, lambda width: None) == (want, None)
    # a refusal leaves the one shape, and is handed back
    rungs, why = prefill_ladder(batch, chunk,
                                lambda width: "no" if width > chunk else None)
    assert rungs == want[:1] and why == ("no" if len(want) > 1 else None)


def test_resolved_config_rebuilds_the_same_ladder(tiny):
    spec, cfg, engine = tiny
    srv = deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, slots=4, max_seq_len=96,
        block_size=8, prefill_chunk=12, prefill_batch=6)
    assert srv._rungs == [(6, 12), (1, 72)]
    again = deepspeed_tpu.init_serving(spec, **srv.resolved_config())
    assert again._rungs == srv._rungs
    assert again.resolved_config() == srv.resolved_config()
    assert again.compile_budget == srv.compile_budget == 3
    srv.close()
    again.close()


def test_a_resident_window_keeps_the_one_shape(tiny_engine):
    """The window slides once a call, so a call's width is part of what
    its queries see: such an engine has the rung it always had."""
    srv, _ = _tiny(tiny_engine, slots=2, host_blocks=32, swap_batch=4,
                   resident_window_blocks=4, max_seq_len=128)
    assert srv._rungs == [(4, 8)] and "resident window" in srv._ladder_stop
    assert srv.compile_budget == 4
    srv.close()


def _one_rung(tiny_engine, monkeypatch, how):
    if how == "kernel_plan":
        _narrow_plan(monkeypatch, tiny_engine[1])
        return _tiny(tiny_engine)
    if how == "resident_window":
        return _tiny(tiny_engine, host_blocks=32, swap_batch=4,
                     resident_window_blocks=6)
    return _tiny(tiny_engine, **how)


@pytest.mark.parametrize("how,lengths", [
    ("kernel_plan", [40, 23, 64, 9, 30, 17]),
    ("resident_window", [40, 23, 64, 9, 30, 17]),
    ({"max_seq_len": 24}, [20, 13, 22, 9, 17, 5]),
    ({"prefill_batch": 1}, [40, 23, 9])],
    ids=["kernel_plan", "resident_window", "cache", "batch_1"])
def test_a_one_rung_engine_makes_the_parents_calls(tiny_engine, monkeypatch,
                                                   how, lengths):
    """A ladder of one rung has nothing to choose between: groups of
    ``prefill_batch`` ready rows in admission order, every row of a group
    in its call, a chunk each — the parent's calls, shape for shape; no row
    ever waits."""
    srv, cfg = _one_rung(tiny_engine, monkeypatch, how)
    (j, width), = srv._rungs
    handles = [srv.submit(r) for r in _requests(
        cfg.vocab_size, lengths, False, 2)]
    _, worst = _watched(srv)
    assert worst == 0 and srv.stats()["prefill_turns"] == 0
    # the parent's rule, replayed: slot i holds request i
    left, want = dict(enumerate(lengths)), []
    while any(left.values()):
        ready = [s for s in sorted(left) if left[s]]
        for i in range(0, len(ready), j):
            group = ready[i:i + j]
            want.append((group, sum(min(width, left[s]) for s in group)))
        for s in ready:
            left[s] -= min(width, left[s])
    calls = _calls(srv)
    assert [(c["slots"], c["tokens"]) for c in calls] == want
    assert all(c["shape"] == srv._rung_name((j, width))
               and c["ready"] == c["rows"] for c in calls)
    assert all(h.done for h in handles)
    srv.close()
