"""The program's own spans (``telemetry/trace.py`` + ``ServingEngine.step``
+ ``train_batch``): one primitive on two clocks.

Ring side (host clock): one ``step`` span per scheduler iteration, tiled by
the four host phases, the in-flight spans nested inside their phase, the KV
manager's seconds on the step, ``step`` on every span, ``submit``/``admit``
paired by ``uid``, and the ring readable after the engine is closed.
Profiler side: the same spans as ``ds.serve.*`` / ``ds.train.*`` annotations
in a ``jax.profiler`` trace, ring on or off.  Plus the names the benchmark's
reduction finds things by: the jitted programs' module names and the Pallas
kernels' own names.
"""

import gc
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
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


def test_in_flight_spans_nest_inside_their_phase(served):
    _, events, _ = served
    seen = set()
    for name, phase in IN_FLIGHT.items():
        for e in _named(events, name):
            host = [p for p in _named(events, phase)
                    if p["args"]["step"] == e["args"]["step"]]
            assert len(host) == 1
            assert host[0]["ts"] <= e["ts"] and e["end"] <= host[0]["end"]
            seen.add(name)
    assert seen == set(IN_FLIGHT)
    # the phases say what they ran
    for p in _named(events, "step.prefill"):
        inside = [e for e in _named(events, "prefill")
                  if e["args"]["step"] == p["args"]["step"]]
        assert p["args"]["groups"] == len(inside)
    for p in _named(events, "step.decode"):
        inside = [e for e in _named(events, "decode")
                  if e["args"]["step"] == p["args"]["step"]]
        assert (p["args"]["slots"] > 0) == bool(inside)


def test_prefill_spans_count_the_blocks_their_reads_walk(served, tiny):
    """ISSUE 31: each ``prefill`` span carries ``kv_blocks``, the sum over
    its rows of ``cdiv(base + valid, block_size)`` — what the prefill
    kernel walks — and the program notes, as it is traced, which read it
    was built with (on a CPU the gather)."""
    srv, events, _ = served
    bs, chunk = SERVE_KW["block_size"], SERVE_KW["prefill_chunk"]
    want = sum(-(-min(end, len(r.prompt)) // bs)
               for r in _requests(tiny[1])
               for end in range(chunk, len(r.prompt) + chunk, chunk))
    spans = _named(events, "prefill")
    assert all(e["args"]["kv_blocks"] >= e["args"]["rows"] for e in spans)
    assert sum(e["args"]["kv_blocks"] for e in spans) == want
    assert srv.stats()["prefill_attn"] == "gather"


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
    prefill span once per chunk of its prompt."""
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
    chunk = SERVE_KW["prefill_chunk"]
    sampled = [r for r in reqs if r.temperature > 0]
    filtered = [r for r in sampled if r.top_k > 0 or r.top_p < 1]
    assert (len(sampled), len(filtered)) == ((5, 4) if sampling else (0, 0))
    for key, of in (("sampled_rows", sampled), ("filtered_rows", filtered)):
        assert sum(e["args"][key] for e in _named(events, "decode")) == \
            sum(r.max_new_tokens - 1 for r in of)
        assert sum(e["args"][key] for e in _named(events, "prefill")) == \
            sum(-(-len(r.prompt) // chunk) for r in of)
    for e in _named(events, "decode") + _named(events, "prefill"):
        assert e["args"]["filtered_rows"] <= e["args"]["sampled_rows"] \
            <= e["args"].get("rows", e["args"]["slots"])


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


# ------------------------------------------------ names the benchmark reads
def _module_name(lowered) -> str:
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


def _serving_module(tiny, kind):
    engine, cfg = tiny
    kw = dict(SERVE_KW)
    if kind == "jit_decode_fused":
        kw["decode_steps"] = 4
    elif kind == "jit_decode_windowed":
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
    ("jit_decode_fused", _serving_module),
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
