"""The prefill call as a BUDGET of ``prefill_batch * prefill_chunk`` tokens
(ISSUE 52): a call whose row is alone runs ``[1, 4w]``, the wide rung of
the ladder, where several rows run ``[4, w]``.  Tiny float32 engines on the
CPU: a request's tokens do not depend on the shapes its prompt went through,
for every layer kind the engine serves; the policy, its counters and what is
sized by the widest row."""

import contextlib
import json
import logging
import os
import sys

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
    srv._rung_for, srv.prefill_batch = (lambda rows: rung), rung[0]
    try:
        yield
    finally:
        del srv._rung_for
        srv.prefill_batch = batch


def _shapes(srv, since):
    """The ``shape`` of every prefill call after ``since`` events."""
    return [e["args"]["shape"] for e in srv.timeline.events()[since:]
            if e["ph"] == "X" and e["name"] == "prefill"]


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_requests_tokens_do_not_depend_on_its_prefill_calls_shapes(
        engines, kind, sampled):
    """Every request through ``[4, w]`` calls and through ``[1, 4w]``
    calls, through the ladder beside other prefilling rows, and through the
    ladder alone: the same tokens."""
    srv, vocab = engines(kind)
    assert srv._rungs == RUNGS and srv._ladder_stop is None
    reqs = lambda: _requests(vocab, LENGTHS, sampled)  # noqa: E731
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
    # the ladder itself: rows together (a row left alone runs the wide rung)
    since = len(srv.timeline.events())
    together = srv.serve(reqs())
    assert "4x8" in _shapes(srv, since)
    alone = {}
    since = len(srv.timeline.events())
    for r in reqs():
        alone.update(srv.serve([r]))
    assert set(_shapes(srv, since)) == {"1x32"}
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


@pytest.mark.parametrize("rows,shapes", [
    (1, ["1x32"]), (2, ["4x8"]), (3, ["4x8"]), (4, ["4x8"]),
    (5, ["4x8", "1x32"]), (9, ["4x8", "4x8", "1x32"])])
def test_ready_rows_are_cut_into_the_calls_the_issue_names(tiny_engine, rows,
                                                           shapes):
    """One step with ``rows`` fresh prompts: groups of ``prefill_batch`` in
    admission order, each at the rung with the fewest rows that holds it."""
    srv, cfg = _tiny(tiny_engine)
    handles = [srv.submit(r) for r in _requests(
        cfg.vocab_size, [40] * rows, False, 2)]
    while srv.step():                      # (a call's span lands as it ends)
        pass
    spans = [e["args"] for e in srv.timeline.events()
             if e["ph"] == "X" and e["name"] == "prefill"]
    # the calls of the first step that made any (``step``: the iteration
    # that MADE the call, not the one that harvested it)
    calls = [c for c in spans if c["step"] == spans[0]["step"]]
    assert [c["shape"] for c in calls] == shapes
    # admission order: the slots of the calls, concatenated, are 0 .. rows-1
    assert sum((c["slots"] for c in calls), []) == list(range(rows))
    for c in calls:
        j, width = map(int, c["shape"].split("x"))
        assert c["width"] == width and c["rows"] <= j
        assert c["tokens"] == c["rows"] * min(width, 40)
    assert all(h.done for h in handles)
    srv.close()


def test_stats_count_the_shapes_and_the_fill(tiny_engine):
    srv, cfg = _tiny(tiny_engine)
    st = srv.stats()
    assert st["prefill_shapes"] == {"4x8": 0, "1x32": 0}
    assert st["prefill_fill"] is None
    srv.serve(_requests(cfg.vocab_size, [40, 40, 40, 40, 40], False, 2))
    st = srv.stats()
    # five rows: [4, 8] x 5 chunks beside [1, 32] + [1, 32] (8 of its 32)
    assert st["prefill_shapes"] == {"4x8": 5, "1x32": 2}
    assert st["prefill_calls"] == 7
    assert st["prefill_fill"] == pytest.approx(5 * 40 / (7 * 32))
    text = srv.metrics.prometheus_text()
    assert 'serving_prefill_calls_by_shape_total{shape="1x32"} 2' in text
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
    heads, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    fits = lambda t: decode_attention.prefill_row_fits(  # noqa: E731
        heads, heads, 8, hd, 4, t, 128 // 8)
    budget = next(b for b in range(1 << 10, 1 << 24, 1 << 10)
                  if monkeypatch.setattr(
                      decode_attention, "_PREFILL_VMEM_BUDGET", b) or fits(8))
    assert fits(8) and not fits(32)
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
