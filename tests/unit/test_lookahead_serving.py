"""One call of lookahead (``ServingEngine._launch`` / ``_settle``): the
scheduler enqueues call n+1 before it harvests call n, a decode row's next
token stays on the device.  Every test holds an engine that looks ahead to
the SAME requests on a twin whose every call is settled at once — through
an observable cause (``debug_checks=True``), there being no option — token
for token and in per-request order."""

import json

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.constrain import (JsonMaskBuilder,
                                               ascii_token_strings)
from deepspeed_tpu.inference.serving import (EARLY_SETTLE_CAUSES, Request,
                                             ServingEngine)
from deepspeed_tpu.models import mixtral

KW = dict(slots=4, max_seq_len=128, block_size=8, prefill_chunk=16,
          prefill_batch=2)
IN_FLIGHT = ("decode", "prefill")


def _pair(engine, **kw):
    """(an engine that looks ahead, its twin that settles every call at
    once) over one set of weights."""
    kw = {**KW, **kw}
    return ServingEngine(engine, **kw), \
        ServingEngine(engine, **kw, debug_checks=True)


@pytest.fixture(scope="module")
def pair(tiny_engine):
    engine, cfg = tiny_engine
    ahead, serial = _pair(engine)
    yield ahead, serial, cfg
    ahead.close()
    serial.close()


def _requests(cfg, n=9, seed=0, sampled=(), lo=3, hi=40, new=(2, 12), **kw):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        knobs = dict(temperature=0.8, top_p=0.9, top_k=0,
                     seed=2 ** 31 + 97 * i) if i in sampled else {}
        out.append(Request(
            uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                       int(rng.integers(lo, hi)),
                                       dtype=np.int32),
            max_new_tokens=int(rng.integers(*new)), **knobs, **kw))
    return out


def _streams(srv, reqs, **kw):
    """uid -> the tokens its handle streamed, in order, driving ``step()``
    by hand; also that ``step()`` said False only on a settled engine."""
    eos = kw.pop("eos", {})
    handles = [srv.submit(r, eos_token_id=eos[r.uid], **kw)
               if r.uid in eos else srv.submit(r, **kw) for r in reqs]
    while True:
        more = srv.step()
        if not more:
            assert srv._flight is None and not srv._active
            break
    assert all(h.done for h in handles)
    return {h.uid: list(h.tokens()) for h in handles}


def _same(ahead, serial, reqs_of, **kw):
    got, want = _streams(ahead, reqs_of(), **kw), \
        _streams(serial, reqs_of(), **kw)
    assert got == want
    return got


def _calls(srv, since=0):
    return [e for e in srv.timeline.events()[since:]
            if e["ph"] == "X" and e["name"] in IN_FLIGHT]


# ------------------------------------------------------------ token identity
@pytest.mark.parametrize("sampled", [(), range(9), (1, 4, 5, 8)],
                         ids=["greedy", "sampled-top_p", "mixed"])
def test_streams_equal_those_of_calls_settled_at_once(pair, sampled):
    ahead, serial, cfg = pair
    before = ahead.stats()["lookahead"]
    got = _same(ahead, serial,
                lambda: _requests(cfg, sampled=tuple(sampled)))
    for r in _requests(cfg, sampled=tuple(sampled)):
        assert len(got[r.uid]) == r.max_new_tokens
    after, twin = ahead.stats()["lookahead"], serial.stats()["lookahead"]
    calls = after["calls"] - before["calls"]
    # all but the first call of the trace were enqueued behind one in flight
    assert after["ahead"] - before["ahead"] >= calls - 2 and calls > 10
    assert after["early"] == {}
    assert twin["ahead"] == 0
    assert twin["early"] == {"debug_checks": twin["calls"]}


@pytest.mark.parametrize("new", [1, 2])
def test_a_budget_of_one_or_two_tokens(pair, new):
    """The token in flight spends the budget: the row is left out of the
    next call, and finishes when that token is seen."""
    ahead, serial, cfg = pair
    got = _same(ahead, serial,
                lambda: _requests(cfg, n=7, seed=new, new=(new, new + 1)))
    assert all(len(t) == new for t in got.values())


def test_a_prompt_of_several_chunks(pair):
    ahead, serial, cfg = pair
    before = len(ahead.timeline.events())
    _same(ahead, serial,
          lambda: _requests(cfg, n=5, seed=3, lo=50, hi=100, new=(3, 8)))
    chunks = [e for e in _calls(ahead, before) if e["name"] == "prefill"]
    assert len(chunks) >= 8
    assert sum(e["args"]["ahead"] for e in chunks) >= len(chunks) - 1


def test_rows_that_end_on_eos_mid_batch_leave_nothing_behind(tiny_engine):
    """The host cannot know that the token in flight is a row's eos: the
    row rides the next call once more.  What that call makes of it is
    dropped — not streamed, not counted, and in no block a later request
    reads through the prefix trie."""
    engine, cfg = tiny_engine
    ahead, serial = _pair(engine)
    # sampled: the tiny model's greedy streams repeat one token
    reqs = lambda: _requests(cfg, n=8, seed=5, new=(6, 14),  # noqa: E731
                             sampled=tuple(range(8)))
    free = _streams(serial, reqs())
    # rows end early, each on an eos of its own that a DECODE call makes
    # (the first token past the prompt's that the stream had not held yet)
    eos = {}
    for uid, toks in free.items():
        new = [k for k in range(1, len(toks) - 1) if toks[k] not in toks[:k]]
        if uid % 3 and new:
            eos[uid] = toks[new[len(new) // 2]]
    assert len(eos) >= 3
    counted = ahead.stats()["generated_tokens"], \
        serial.stats()["generated_tokens"]
    since = len(ahead.timeline.events()), len(serial.timeline.events())
    got = _same(ahead, serial, reqs, eos=eos)
    cut = [u for u, toks in got.items() if len(toks) < len(free[u])]
    assert sorted(cut) == sorted(eos)
    for uid, toks in got.items():
        # the stream is the free-running one up to its first eos
        assert toks == free[uid][:len(toks)]
        if uid in eos:
            assert toks[-1] == eos[uid] and eos[uid] not in toks[:-1]
    emitted = sum(map(len, got.values()))
    assert ahead.stats()["generated_tokens"] - counted[0] == emitted
    assert serial.stats()["generated_tokens"] - counted[1] == emitted
    # the rows rode: more decode rows ran than tokens were kept ...
    rode = sum(e["args"]["slots"] for e in _calls(ahead, since[0])
               if e["name"] == "decode")
    kept = sum(e["args"]["slots"] for e in _calls(serial, since[1])
               if e["name"] == "decode")
    # each rode once — but for a row whose eos was harvested behind a
    # prefill call's enqueue, before the step's decode was planned
    assert kept < rode <= kept + len(eos)
    # ... and every block is back, none of it reachable through the trie:
    # the same prompts again hit exactly what the twin's hit, and stream
    # exactly the same
    assert ahead._alloc.blocks_in_use == serial._alloc.blocks_in_use
    hits = ahead.prefix_hit_tokens, serial.prefix_hit_tokens
    assert _same(ahead, serial, reqs, eos=eos) == got
    assert ahead.prefix_hit_tokens - hits[0] == \
        serial.prefix_hit_tokens - hits[1] > 0
    ahead.close()
    serial.close()


def test_a_pool_tight_enough_to_preempt(tiny_engine):
    engine, cfg = tiny_engine
    ahead, serial = _pair(engine, num_blocks=20, prefix_caching=False)
    reqs = lambda: _requests(cfg, n=8, seed=7, lo=20, hi=40,  # noqa: E731
                             new=(10, 24))
    _same(ahead, serial, reqs)
    assert ahead.preempted > 0 and serial.preempted > 0
    # a victim is chosen among committed rows
    assert ahead.stats()["lookahead"]["early"] == \
        {"preempt": ahead.stats()["lookahead"]["early"]["preempt"]}
    ahead.close()
    serial.close()


def test_cancelling_a_row_that_is_in_flight(pair):
    ahead, serial, cfg = pair

    def run(srv):
        handles = [srv.submit(r) for r in _requests(cfg, n=4, seed=11,
                                                    new=(10, 12))]
        for _ in range(4):
            srv.step()
        victim = handles[1]
        assert not victim.done and len(victim.tokens()) > 0
        pending = srv._flight is not None
        victim.cancel()
        while srv.step():
            pass
        return pending, victim.status, \
            {h.uid: list(h.tokens()) for h in handles}

    early = ahead.stats()["lookahead"]["early"].get("cancel", 0)
    was_pending, status, got = run(ahead)
    _, _, want = run(serial)
    assert was_pending and status == "cancelled"
    assert ahead.stats()["lookahead"]["early"]["cancel"] == early + 1
    # the cancelled row keeps what was streamed (one token more where the
    # call in flight was settled for it); every other row is untouched
    for uid in got:
        if uid == 1:
            assert got[uid][:len(want[uid])] == want[uid]
            assert len(got[uid]) - len(want[uid]) in (0, 1)
        else:
            assert got[uid] == want[uid]
    assert ahead._alloc.blocks_in_use == serial._alloc.blocks_in_use


def test_a_constrained_row_beside_free_rows_settles_every_call(tiny_engine):
    engine, cfg = tiny_engine
    ahead, serial = _pair(engine, logit_masks=True)
    strings = ascii_token_strings(cfg.vocab_size)

    def reqs():
        out = _requests(cfg, n=5, seed=13, new=(6, 10))
        out[2] = Request(uid=2, prompt=out[2].prompt, max_new_tokens=12,
                         mask_builder=JsonMaskBuilder(strings,
                                                      eos_token_id=0))
        # a free row that outlives the constrained one, however few calls
        # its prompt took
        out[4] = Request(uid=4, prompt=out[4].prompt, max_new_tokens=24)
        return out

    got = _same(ahead, serial, reqs, eos_token_id=0)
    text = "".join(strings[t] for t in got[2] if t != 0)
    if got[2][-1] == 0:
        json.loads(text)
    look = ahead.stats()["lookahead"]
    # while the constrained row lived its mask was a host function of its
    # tokens; before and after it the engine looked ahead
    assert look["early"]["mask_builder"] > 0
    assert set(look["early"]) == {"mask_builder"}
    assert look["ahead"] > 0
    ahead.close()
    serial.close()


# ---------------------------------------------------------------- families
FAMILIES = {
    # a routing record behind the tokens
    "experts": dict(
        vocab_size=128, max_seq_len=128, num_layers=2, num_heads=4,
        num_kv_heads=4, hidden_size=64, ffn_size=32, rope_theta=10000.0,
        num_experts=8, top_k=4, norm_topk_prob=False, qk_norm=True,
        remat=False),
    # the selections' counts behind the record, a third pool leaf
    "sparse": dict(
        vocab_size=128, max_seq_len=128, num_layers=2, num_heads=4,
        num_kv_heads=2, head_width=16, hidden_size=64, ffn_size=32,
        rope_theta=1e7, rms_eps=1e-6, qk_norm="head", num_experts=8,
        top_k=4, norm_topk_prob=True, index_heads=2, index_head_dim=16,
        index_topk=16, remat=False),
    # a table a layer kind, the ring advanced at plan time from lengths
    "windows": dict(
        vocab_size=128, max_seq_len=128, num_layers=4, num_heads=8,
        num_kv_heads=2, head_width=16, hidden_size=32, ffn_size=16,
        rope_theta=50000.0, rms_eps=1e-5, norm="layernorm",
        parallel_block=True, rope_interleaved=True,
        layer_kinds=("sliding", "sliding", "sliding", "full"),
        sliding_window=24, tie_embeddings=True, num_experts=16, top_k=4,
        router_score="sigmoid", shared_experts=2, experts_held=(4, 4),
        remat=False),
    # the latent pool
    "latent": dict(
        vocab_size=128, max_seq_len=128, num_layers=2, num_heads=4,
        num_kv_heads=4, head_width=16, hidden_size=32, ffn_size=16,
        rope_theta=10000.0, rms_eps=1e-6, rope_interleaved=True,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8,
        v_head_dim=12, num_experts=16, top_k=4, router_score="softmax",
        shared_experts=1, experts_held=(4, 4), remat=False),
}
#: what of a family's call rides back behind its tokens, or is reckoned at
#: plan time, and has to be on every span of the deferred harvest
SPAN_ARGS = {
    "experts": ("experts_touched", "expert_rows", "expert_rows_max"),
    "sparse": ("experts_touched", "index_keys", "kv_selected", "kv_valid"),
    "windows": ("experts_touched", "kv_valid", "kv_visible"),
    "latent": ("experts_touched", "kv_valid", "kv_blocks", "latent_bytes"),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_family_the_benchmark_serves(family):
    cfg = mixtral.MixtralConfig(**FAMILIES[family])
    cfg.use_flash = False
    spec = mixtral.build(cfg)
    params = jax.tree_util.tree_map(
        lambda a: a * 4 if a.ndim > 1 else a,
        spec.init_fn(jax.random.PRNGKey(1)))
    kw = dict(slots=3, max_seq_len=128, prefill_chunk=16, prefill_batch=2)
    if family != "latent":
        kw["block_size"] = 8
    ahead, serial = (deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, params=params, **kw, **more)
        for more in ({}, {"debug_checks": True}))
    reqs = lambda: _requests(cfg, n=6, seed=17, lo=5, hi=60,  # noqa: E731
                             new=(3, 12), sampled=(2, 3))
    _same(ahead, serial, reqs)
    look = ahead.stats()["lookahead"]
    assert look["early"] == {} and look["ahead"] >= look["calls"] - 1
    assert ahead.compile_count == serial.compile_count == 1 + len(serial._rungs)
    for a, b in zip(_calls(ahead), _calls(serial)):
        for key in SPAN_ARGS[family]:
            assert key in a["args"] and key in b["args"], key
    # the totals the spans feed: the same work was done, but for the step a
    # freed slot waits for its finish to be seen
    for key in ("moe", "sparse_attn", "kv_kinds", "kv_latent"):
        a, b = ahead.stats().get(key), serial.stats().get(key)
        assert (a is None) == (b is None), key
    if family == "windows":
        assert ahead.stats()["kv_kinds"]["sliding"]["released"] > 0
        assert ahead._ring.alloc.blocks_in_use == 0
    ahead.close()
    serial.close()


# ---------------------------------------------------------- the engine's edge
def test_step_says_false_only_on_a_settled_engine(pair):
    ahead, _, cfg = pair
    h = ahead.submit(Request(uid="one", prompt=np.arange(5, dtype=np.int32),
                             max_new_tokens=3))
    said = []
    for _ in range(12):
        said.append((ahead.step(), ahead._flight is not None, h.done))
        if not said[-1][0]:
            break
    # a call was in flight when step() returned, more than once
    assert sum(pending for _, pending, _ in said) >= 2
    for more, pending, done in said:
        assert more or (not pending and done)
        assert not (pending and done)
    assert len(h.tokens()) == 3 and ahead.step() is False


@pytest.mark.parametrize("how", ["close", "drain"])
def test_leaving_with_a_call_pending(tiny_engine, how):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, **KW)
    handles = [srv.submit(r) for r in _requests(cfg, n=3, seed=19,
                                                new=(8, 10))]
    for _ in range(3):
        srv.step()
    assert srv._flight is not None
    seen = [len(h.tokens()) for h in handles]
    if how == "close":
        srv.close()
        # the tokens the device had made are the handles' now
        assert [len(h.tokens()) for h in handles] == [n + 1 for n in seen]
    else:
        items = srv.drain()
        assert sorted(i.req.uid for i in items) == [0, 1, 2]
        for item, h in zip(sorted(items, key=lambda i: i.req.uid), handles):
            assert len(item.prior) == len(h.tokens()) > seen[item.req.uid]
        assert not srv._active and not any(srv._held)
    assert srv._flight is None
    assert srv.stats()["lookahead"]["early"] == {how: 1}
    srv.close()


def test_nothing_compiles_after_the_first_two_steps(tiny_engine):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, **KW)
    handles = [srv.submit(r) for r in _requests(cfg, n=10, seed=23)]
    srv.step()
    srv.step()
    built, traces = srv.compile_count, srv.sentry.traces
    assert built == 1 + len(srv._rungs)    # decode + a prefill program a rung
    while srv.step():
        pass
    _streams(srv, _requests(cfg, n=6, seed=29, lo=30, hi=90))
    assert all(h.done for h in handles)
    assert srv.compile_count == built and srv.sentry.traces == traces
    assert srv.stats()["retraces_observed"] == 0
    # the token vector goes from either program into either: one executable
    for fn in (srv._decode_fn, *srv._prefill_fns.values()):
        assert fn._cache_size() == 1
    srv.close()


def test_the_spans_and_the_counters_say_what_happened(tiny_engine):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, **KW)
    _streams(srv, _requests(cfg, n=6, seed=31))
    calls = _calls(srv)
    look = srv.stats()["lookahead"]
    assert look["calls"] == len(calls)
    assert look["ahead"] == sum(e["args"]["ahead"] for e in calls)
    assert calls[0]["args"]["ahead"] == 0 and look["ahead"] == len(calls) - 1
    for e in calls:
        assert e["args"]["enqueue_s"] > 0 and e["args"]["wait_s"] >= 0
        assert e["args"]["puts"] == 1
    # a span is the host's stay in the runtime that ended with the call's
    # tokens: the stays are disjoint and in order, and the host's own
    # segments lie outside them
    edges = [(e["ts"], e["ts"] + e["dur"]) for e in calls]
    assert all(a[1] <= b[0] for a, b in zip(edges, edges[1:]))
    text = srv.metrics.prometheus_text()
    assert f"serving_calls_ahead_total {look['ahead']}" in text
    assert "serving_early_settles_total" in text
    assert set(look) == {"calls", "ahead", "early"}
    assert set(EARLY_SETTLE_CAUSES) >= {"preempt", "cancel", "mask_builder",
                                        "debug_checks"}
    srv.close()
