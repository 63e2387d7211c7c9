"""ISSUE 38: a serving call's small operands ride into the program as ONE
host buffer (``inference/operands.py``).

 - the layout: every field where it was put, bit for bit (negative zero,
   denormals, NaN payloads, seeds past 2^31, bools, a table per layer kind);
 - every runner — plain decode, a prefill group with pad rows,
   speculative verify with the n-gram and the draft proposer, a
   resident window, a model with two layer kinds, a mask matrix beside the
   buffer — emits the tokens it emits when each field is fed separately;
 - a call hands over ONE small host array (two with a mask matrix) and
   makes no ``jnp.asarray`` / ``jax.device_put``; the layout's buffer may be
   overwritten right after the enqueue; a layout is made once a program and
   nothing compiles over fifty mixed steps; the three host segments still
   cover the two phases' self time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import operands as operands_mod
from deepspeed_tpu.inference.operands import OperandLayout
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2, mixtral
from deepspeed_tpu.telemetry.trace import TraceTimeline

SERVE_KW = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16)
IN_FLIGHT = ("prefill", "decode", "spec_propose", "spec_verify")


def sds(shape, dtype=np.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------------------ layout
def _bits(x):
    x = np.asarray(x)
    return x.astype(np.uint32) if x.dtype == bool else x.view(np.uint32)


SPECIALS = {
    "float32": np.array([-0.0, 0.0, 1e-45, -1e-40, 1.17549435e-38, 0.7, 1.0,
                         np.inf, -np.inf, np.nan], np.float32),
    "uint32": np.array([0, 1, 2 ** 31, 2 ** 31 + 7, 2 ** 32 - 1], np.uint32),
    "int32": np.array([0, -1, 2 ** 31 - 1, -2 ** 31], np.int32),
    "bool": np.array([True, False, True, True], bool),
}


@pytest.mark.parametrize("kind", list(SPECIALS))
def test_a_field_arrives_with_the_bits_it_was_written_with(kind):
    """float32 and uint32 fields are bitcast, not converted: negative
    zero, denormals (which arithmetic may flush) and a NaN's payload come
    out as they went in; a bool travels as a 0 / 1 word."""
    value = SPECIALS[kind]
    if kind == "float32":
        value = value.copy()
        value.view(np.uint32)[-1] = 0x7FC12345        # a NaN with a payload
    layout = OperandLayout({"pad": sds((3,)), "x": sds(value.shape,
                                                       value.dtype),
                            "tail": sds((2, 2))})
    buf = layout.fill(np.arange(3), value, np.ones((2, 2)))
    assert buf.dtype == np.int32 and buf.shape == (layout.words,)
    pad, x, tail = jax.jit(layout.unpack)(buf)
    assert x.dtype == value.dtype and x.shape == value.shape
    np.testing.assert_array_equal(_bits(x), _bits(value))
    np.testing.assert_array_equal(pad, np.arange(3))
    np.testing.assert_array_equal(tail, np.ones((2, 2), np.int32))


def test_fields_lie_end_to_end_in_the_bodys_order_with_a_table_per_kind():
    layout = OperandLayout({
        "ids": sds((2, 5)),
        "block_tables": {"window": sds((2, 3)), "full": sds((2, 4))},
        "temps": sds((2,), np.float32)})
    assert [(f.name, f.shape, f.offset) for f in layout.fields] == [
        ("ids", (2, 5), 0), ("block_tables.full", (2, 4), 10),
        ("block_tables.window", (2, 3), 18), ("temps", (2,), 24)]
    assert layout.words == 26 and layout.nbytes == 104
    ids = np.arange(10).reshape(2, 5)
    tables = {"full": np.full((2, 4), 7), "window": np.full((2, 3), 9)}
    got_ids, got_tables, temps = jax.jit(layout.unpack)(
        layout.fill(ids, tables, np.array([0.5, 2.0], np.float32)))
    np.testing.assert_array_equal(got_ids, ids)
    assert set(got_tables) == {"full", "window"}
    np.testing.assert_array_equal(got_tables["full"], tables["full"])
    np.testing.assert_array_equal(got_tables["window"], tables["window"])
    np.testing.assert_array_equal(temps, [0.5, 2.0])


def test_the_unpacking_is_slices_and_bitcasts_and_no_arithmetic():
    layout = OperandLayout({"a": sds((4,)), "t": sds((4,), np.float32),
                            "s": sds((4,), np.uint32)})
    text = jax.jit(layout.unpack).lower(sds((12,))).as_text()
    for op in ("add", "multiply", "convert", "gather", "dynamic_slice"):
        assert f"stablehlo.{op}" not in text, op
    assert text.count("stablehlo.slice") == 3
    assert text.count("stablehlo.bitcast_convert") == 2


@pytest.mark.parametrize("bad,error", [
    (lambda lay: lay.fill(np.zeros(3)), ValueError),            # one short
    (lambda lay: lay.fill(np.zeros(3), np.zeros(3)), ValueError),   # shape
    (lambda lay: lay.fill(np.zeros(3), 1.0), ValueError),       # a scalar
    (lambda lay: OperandLayout({"x": sds((2,), np.int64)}), TypeError),
    (lambda lay: OperandLayout({"x": sds((2,), np.float16)}), TypeError),
], ids=["missing", "shape", "broadcast", "int64", "float16"])
def test_a_layout_refuses_what_does_not_fit_it(bad, error):
    layout = OperandLayout({"a": sds((3,)), "b": sds((2,), np.float32)})
    with pytest.raises(error):
        bad(layout)


def test_each_call_gets_a_snapshot_the_buffer_is_the_layouts_own():
    layout = OperandLayout({"a": sds((3,))})
    first = layout.fill(np.array([1, 2, 3]))
    second = layout.fill(np.array([4, 5, 6]))
    assert first is not second and first is not layout.buffer
    np.testing.assert_array_equal(first, [1, 2, 3])
    np.testing.assert_array_equal(layout.buffer, [4, 5, 6])


# ----------------------------------------------------------------- engines
@pytest.fixture(scope="module")
def models():
    """``models(name)`` -> (inference engine, model config), built once:
    ``tiny`` GPT-2 (64 positions), ``long`` (256), and ``two-kinds`` —
    Command A+'s block at tiny widths: three sliding-window layers to one
    full layer, a block table per kind."""
    built = {}

    def get(name):
        if name not in built:
            if name == "two-kinds":
                cfg = mixtral.MixtralConfig(
                    vocab_size=128, max_seq_len=256, num_layers=4,
                    num_heads=8, num_kv_heads=2, head_width=16,
                    hidden_size=32, ffn_size=16, rope_theta=50000.0,
                    rms_eps=1e-5, norm="layernorm", parallel_block=True,
                    rope_interleaved=True,
                    layer_kinds=("sliding", "sliding", "sliding", "full"),
                    sliding_window=24, tie_embeddings=True, num_experts=16,
                    top_k=4, router_score="sigmoid", shared_experts=2,
                    experts_held=(4, 4), remat=False)
                spec = mixtral.build(cfg)
            else:
                cfg = gpt2.GPT2Config.tiny(
                    max_seq_len=256 if name == "long" else 64)
                spec = gpt2.build(cfg)
            built[name] = deepspeed_tpu.init_inference(
                spec, config={"dtype": "fp32"}), cfg
        return built[name]

    return get


class EvenTokens:
    """A mask builder: only even token ids."""

    def __init__(self, vocab):
        self.row = np.arange(vocab) % 2 == 0

    def allowed(self, tokens, remaining):
        return self.row


def _engine(models, runner):
    engine, cfg = models({"two-kinds": "two-kinds",
                          "window": "long"}.get(runner, "tiny"))
    kw = dict(SERVE_KW)
    if runner == "spec-ngram":
        kw["spec_tokens"] = 3
    elif runner == "spec-draft":
        dcfg = gpt2.GPT2Config(vocab_size=cfg.vocab_size, max_seq_len=64,
                               num_layers=1, num_heads=2, hidden_size=32)
        kw.update(spec_tokens=2, draft=gpt2.build(dcfg))
    elif runner == "window":
        kw.update(max_seq_len=128, num_blocks=30, host_blocks=64,
                  swap_batch=4, resident_window_blocks=4)
    elif runner == "two-kinds":
        kw.update(max_seq_len=128)
    elif runner == "masks":
        kw["logit_masks"] = True
    return ServingEngine(engine, **kw), cfg


def _requests(cfg, runner, n=7, seed=0):
    """More requests than slots and prompts of 5-40 tokens: prefill groups
    with pad rows, second chunks, queueing.  Greedy rows beside sampled
    ones — seeds past 2^31, ``top_p`` 1.0, a negative-zero temperature (a
    greedy row) and a top-k row."""
    rng = np.random.default_rng(seed)
    long = runner in ("window", "two-kinds")
    knobs = [(0.0, 0, 1.0), (0.7, 0, 0.9), (1.0, 0, 1.0), (-0.0, 0, 1.0),
             (1.3, 5, 1.0), (0.7, 7, 0.5), (0.9, 0, 0.95)]
    reqs = []
    for i in range(n):
        t, k, p = knobs[i % len(knobs)]
        plen = int(rng.integers(60, 100) if long and i % 2 == 0
                   else rng.integers(5, 40))
        reqs.append(Request(
            uid=i, prompt=rng.integers(0, cfg.vocab_size, plen,
                                       dtype=np.int32),
            max_new_tokens=int(rng.integers(3, 9)), temperature=t, top_k=k,
            top_p=p, seed=2 ** 31 + 1000 * i + 17,
            mask_builder=EvenTokens(cfg.vocab_size)
            if runner == "masks" and i % 2 else None))
    return reqs


def _separately(srv):
    """``srv`` fed as engines were before the layout: every program its
    body as it is, every operand a device array of its own."""
    def packed(program, body, spec, device_operands=2):
        srv._layouts[program] = OperandLayout(spec)
        return body

    def host_operands(program, *operands):
        # a COPY of each: the engine writes its vectors again (lengths, a
        # released row) while the call is still in flight (ISSUE 44), and
        # XLA:CPU aliases an aligned numpy operand
        dev = jax.tree_util.tree_map(jnp.array, operands)
        return dev, {"puts": len(jax.tree_util.tree_leaves(dev)),
                     "operand_bytes": 0}

    srv._packed, srv._host_operands = packed, host_operands
    return srv


RUNNERS = ["plain", "spec-ngram", "spec-draft", "window", "two-kinds",
           "masks"]


@pytest.mark.parametrize("runner", RUNNERS)
def test_packed_operands_emit_the_tokens_of_separate_operands(
        models, runner):
    srv, cfg = _engine(models, runner)
    ref, _ = _engine(models, runner)
    _separately(ref)
    got = srv.serve(_requests(cfg, runner))
    want = ref.serve(_requests(cfg, runner))
    assert sorted(got) == sorted(want) == list(range(7))
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid],
                                      err_msg=f"{runner}: uid {uid}")
    assert srv.compile_count == ref.compile_count
    # the reference did feed every field alone, the engine one buffer
    flights = {name: [e["args"]["puts"] for e in tl.timeline.events()
                      if e["ph"] == "X" and e["name"] in IN_FLIGHT
                      and e["args"].get("mode") != "ngram"]
               for name, tl in (("srv", srv), ("ref", ref))}
    beside = 2 if runner == "masks" else 1
    assert set(flights["srv"]) == {beside}
    assert min(flights["ref"]) >= 4
    if runner == "window":
        assert srv.stats()["context_window_slides"] > 0
    if runner == "two-kinds":
        names = [f.name for f in srv._layouts["decode"].fields]
        assert names[2:4] == ["block_tables.full", "block_tables.window"]
        assert srv.stats()["kv_kinds"]["sliding"]["released"] > 0   # the rings moved
    if runner == "masks":
        for r in _requests(cfg, runner):
            if r.mask_builder is not None:
                new = got[r.uid][len(r.prompt):]
                assert (new % 2 == 0).all(), (r.uid, new)
    srv.close()
    ref.close()


def _steady(models, runner, steps=4):
    """An engine past its warm-in (every program built and run), with
    requests waiting and running."""
    srv, cfg = _engine(models, runner)
    srv.serve(_requests(cfg, runner, n=4, seed=1))
    handles = [srv.submit(r) for r in _requests(cfg, runner, n=12, seed=2)]
    for _ in range(steps):
        srv.step()
    return srv, handles


@pytest.mark.parametrize("runner", ["plain", "spec-ngram", "spec-draft",
                                    "masks"])
def test_a_call_hands_over_one_host_array_and_puts_nothing(
        models, runner, monkeypatch):
    """Between the plan and the results a runner makes no ``jnp.asarray``
    and no ``jax.device_put``: the jitted call is handed ONE numpy buffer
    (and the mask matrix where one rides), and its in-flight span says so
    (``puts``, ``operand_bytes``)."""
    srv, _ = _steady(models, runner)
    puts = []
    for fn in ("asarray", "array"):
        real = getattr(jnp, fn)
        monkeypatch.setattr(jnp, fn, lambda *a, _r=real, _f=fn, **k:
                            (puts.append(_f), _r(*a, **k))[1])
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda *a, **k:
                        (puts.append("device_put"), real_put(*a, **k))[1])
    handed = {}

    def spy_on(program, fn):
        def spy(*args):
            handed.setdefault(program, []).append(
                [a for a in args if isinstance(a, np.ndarray)])
            return fn(*args)
        return spy

    for program, getter in (("decode", "_get_decode_fn"),
                            ("verify", "_get_verify_fn"),
                            ("draft", "_get_draft_fn")):
        if program in srv._layouts:
            monkeypatch.setattr(
                srv, getter,
                lambda _s=spy_on(program, getattr(srv, getter)()): _s)
    # a prefill program a rung of the ladder, each with a layout of its own
    rung_of = {srv._prefill_program(rung): rung for rung in srv._rungs}
    spies = {rung: spy_on(program, srv._get_prefill_fn(rung))
             for program, rung in rung_of.items()}
    monkeypatch.setattr(srv, "_get_prefill_fn",
                        lambda rung=None: spies[rung or srv._rungs[0]])
    before = len(srv.timeline.events())
    for _ in range(12):
        srv.step()
    assert puts == []
    beside = 2 if runner == "masks" else 1
    assert set(srv._layouts) - set(rung_of) <= set(handed) \
        <= set(srv._layouts)
    assert set(handed) & set(rung_of) and all(handed.values())
    for program, calls in handed.items():
        layout = srv._layouts[program]
        want = 1 if program == "draft" else beside
        for arrays in calls:
            assert len(arrays) == want
            assert arrays[0].dtype == np.int32
            assert arrays[0].shape == (layout.words,)
    flights = [e for e in srv.timeline.events()[before:]
               if e["ph"] == "X" and e["name"] in IN_FLIGHT]
    assert {e["name"] for e in flights} >= {"prefill"}
    span_of = {"decode": "decode", "verify": "spec_verify",
               "draft": "spec_propose"}
    for program in handed:
        layout, rung = srv._layouts[program], rung_of.get(program)
        mine = [e["args"] for e in flights
                if e["name"] == span_of.get(program, "prefill")
                and e["args"].get("mode") != "ngram"
                and (rung is None
                     or e["args"]["shape"] == srv._rung_name(rung))]
        assert mine, program
        masks = srv.slots if rung is None else rung[0]
        extra = masks * srv._vocab \
            if runner == "masks" and program != "draft" else 0
        for args in mine:
            assert args["puts"] == (2 if extra else 1)
            assert args["operand_bytes"] == layout.nbytes + extra
    assert srv.stats()["operands"] == {
        program: {"fields": len(layout.fields), "bytes": layout.nbytes,
                  "puts": 2 if runner == "masks" and program != "draft"
                  else 1}
        for program, layout in srv._layouts.items()}
    srv.close()


def test_the_ngram_proposers_span_says_it_hands_nothing_over(models):
    srv, _ = _steady(models, "spec-ngram")
    spans = [e["args"] for e in srv.timeline.events()
             if e["ph"] == "X" and e["name"] == "spec_propose"]
    assert spans and all(a["mode"] == "ngram" and a["puts"] == 0
                         and a["operand_bytes"] == 0 for a in spans)
    srv.close()


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_buffer_may_be_overwritten_right_after_the_enqueue(
        models, program):
    """Every call gets a snapshot: scribbling over the layout's buffer the
    moment the jitted call has returned — its program still running — does
    not change a token."""
    srv, cfg = _engine(models, "plain")
    want = srv.serve(_requests(cfg, "plain"))
    srv.close()
    srv, _ = _engine(models, "plain")
    getter = f"_get_{program}_fn"
    real = getattr(srv, getter)
    real()                                 # builds the program(s)
    calls = [0]

    def scribbling(*rung):
        """The program (of ``rung``, for a prefill call), scribbling."""
        fn = real(*rung)
        layout = srv._layouts[srv._prefill_program(rung[0])
                              if rung and rung[0] else program]

        def call(*args):
            out = fn(*args)
            layout.buffer[:] = -1
            calls[0] += 1
            return out

        return call

    setattr(srv, getter, scribbling)
    got = srv.serve(_requests(cfg, "plain"))
    assert calls[0] > 5
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])
    srv.close()


@pytest.mark.parametrize("runner", ["plain", "spec-draft"])
def test_a_layout_is_made_once_a_program_and_nothing_compiles_in_fifty_steps(
        models, runner, monkeypatch):
    made = []
    real = OperandLayout.__init__
    monkeypatch.setattr(
        operands_mod.OperandLayout, "__init__",
        lambda self, spec: (made.append(tuple(spec)), real(self, spec))[1])
    srv, cfg = _engine(models, runner)
    assert made == []                      # with the program, not the engine
    srv.serve(_requests(cfg, runner, n=4, seed=1))
    programs = {"plain": {"decode"},
                "spec-draft": {"draft", "verify"}}[runner] \
        | {srv._prefill_program(rung) for rung in srv._rungs}
    assert len(srv._rungs) == 2            # every rung's layout, at once
    assert set(srv._layouts) == programs and len(made) == len(programs)
    layouts = dict(srv._layouts)
    built, traces = srv.compile_count, srv.sentry.traces
    handles = [srv.submit(r) for r in _requests(cfg, runner, n=40, seed=5)]
    kinds = set()
    for _ in range(50):
        before = len(srv.timeline.events())
        srv.step()
        kinds |= {e["name"] for e in srv.timeline.events()[before:]
                  if e["ph"] == "X" and e["name"] in IN_FLIGHT}
    assert kinds >= ({"prefill", "decode"} if runner == "plain"
                     else {"prefill", "spec_propose", "spec_verify"})
    assert any(not h.done for h in handles) or len(handles) == 40
    assert len(made) == len(programs)
    assert all(srv._layouts[p] is layouts[p] for p in programs)
    assert srv.compile_count == built and srv.sentry.traces == traces
    assert srv.stats()["retraces_observed"] == 0
    # one int32 operand behind the device's own, whatever the step held
    for name, fn in (("decode", srv._decode_fn), *srv._prefill_fns.items(),
                     ("verify", srv._verify_fn), ("draft", srv._draft_fn)):
        if fn is not None:
            assert fn._cache_size() == 1, name
    srv.close()


class _Ticks:
    """A clock that advances one second a read."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 1.0
        return self.now


def test_segments_tile_each_begins_where_the_last_boundary_was():
    """A segment entered with nothing but bookkeeping since the last
    boundary begins AT it: the exit of the segment before, the end of the
    span before.  A span's entry clears the boundary, so the first segment
    inside a span begins at its own clock read."""
    tl = TraceTimeline(64, clock=_Ticks())                 # read 1: epoch
    with tl.span("step.decode") as phase:                  # 2: start
        with tl.segment("step.decode.plan", phase):        # 3: its own read
            pass                                           # 4: exit
        with tl.segment("step.decode.upload", phase):      # begins at 4
            pass                                           # 5
        with tl.span("decode") as flight:                  # 6: start
            with tl.segment("decode.enqueue", flight):     # 7: its own read
                pass                                       # 8
            with tl.segment("decode.wait", flight):        # begins at 8
                pass                                       # 9
        # 10: the in-flight span's end, where the commit begins
        with tl.segment("step.decode.commit", phase):
            pass                                           # 11
    assert phase["plan_s"] == 1.0 and phase["upload_s"] == 1.0
    assert flight["enqueue_s"] == 1.0 and flight["wait_s"] == 1.0
    assert phase["commit_s"] == pytest.approx(1.0)
    decode, step = [e for e in tl.events() if e["ph"] == "X"]
    assert (decode["ts"], decode["dur"]) == (5e6, 4e6)     # reads 6 .. 10
    assert (step["ts"], step["dur"]) == (1e6, 10e6)        # reads 2 .. 12
    # the three tile the phase's self time but for its first and last read
    own = (step["dur"] - decode["dur"]) * 1e-6
    assert phase["plan_s"] + phase["upload_s"] + phase["commit_s"] \
        == pytest.approx(own - 3.0)


def test_a_segment_does_not_reach_back_past_a_spans_entry():
    tl = TraceTimeline(64, clock=_Ticks())
    into = {}
    with tl.segment("step.decode.plan", into):             # 2 .. 3
        pass
    with tl.span("step.post"):                             # 4 .. (6)
        with tl.segment("step.decode.plan", into):         # 5 .. 6: not 3
            pass
    assert into["plan_s"] == 2.0
    off = TraceTimeline(0, clock=_Ticks())                 # ring off: no clock
    with off.span("step"), off.segment("step.decode.plan", into) as got:
        assert got is None
    assert off._clock.now == 1.0 and into["plan_s"] == 2.0


def test_the_three_host_segments_still_cover_the_phases_self_time(
        models):
    """``plan_s + upload_s + commit_s`` over the self time of
    ``step.prefill`` + ``step.decode`` (duration less in-flight spans),
    at a batch of the cells' order (16 slots): with the puts gone the
    rest — entering and leaving the phase's own span — is a larger share
    of a smaller time, and still under a tenth of it, because the
    segments tile: each begins where the last one, or the in-flight span,
    ended."""
    engine, cfg = models("tiny")
    srv = ServingEngine(engine, **{**SERVE_KW, "slots": 16})
    srv.serve(_requests(cfg, "plain", n=4, seed=1))
    warm = len(srv.timeline.events())      # both programs built and run
    for r in _requests(cfg, "plain", n=80, seed=2):
        srv.submit(r)
    for _ in range(60):
        srv.step()
    events = srv.timeline.events()[warm:]
    flights = [e for e in events if e["ph"] == "X"
               and e["name"] in ("prefill", "decode")]
    own = covered = 0.0
    for phase in (e for e in events if e["ph"] == "X"
                  and e["name"] in ("step.prefill", "step.decode")):
        # a call's span lies where its harvest did: in the phase that
        # made it or, behind the next call's enqueue, in a later one
        inside = [f for f in flights
                  if phase["ts"] <= f["ts"]
                  and f["ts"] + f["dur"] <= phase["ts"] + phase["dur"]]
        if not inside:
            continue
        own += (phase["dur"] - sum(f["dur"] for f in inside)) * 1e-6
        covered += sum(phase["args"][k]
                       for k in ("plan_s", "upload_s", "commit_s"))
        assert phase["args"]["upload_s"] > 0
    # (a twentieth before ISSUE 44; the harvest of the call before now
    # runs behind the enqueue, three frames deeper, and the first call
    # after an idle engine is handed over with nothing to harvest)
    assert 0.90 * own <= covered <= own, (covered, own)
    srv.close()
