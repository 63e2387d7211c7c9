"""GLM-5's block and its multi-token-prediction module served through the
normal path, at tiny widths in float32 on the CPU, against the plain
reference (``chipbench/reference_glm5.py``, the EXPANDED form): latent
attention under a learned selection in every layer (no gate, no rescale,
values wider than the unrotated key part, an interleaved indexer rotary), a
leading dense FFN, sigmoid-scored experts with a selection bias beside one
shared expert on a held share — and the module as the proposer of the
engine's own verify round (``draft="self"``): its rows in the target's pool,
its draft on the device, one harvest a round, the trie's rule beside it.
The tiny ``index_topk`` (24) puts positions on both sides of it inside one
16-token chunk; a vocabulary of 16 gets drafts ACCEPTED with seeded
weights."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from chipbench import reference_glm5 as ref
from chipbench.drivers import serve_mtp as driver
from chipbench.families import glm_dsa as family
from deepspeed_tpu.inference import options
from deepspeed_tpu.inference.serving import Request
from tiny import assert_greedy

BLOCK, CHUNK, TOPK = 16, 16, 24
#: the published keys at tiny widths: one dense + two routed trunk layers
CONFIG = {
    "family": "glm_dsa", "dtype": "fp32", "attention_bias": False,
    "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu",
    "head_dim": 8, "hidden_size": 64, "index_head_dim": 16,
    "index_n_heads": 4, "index_topk": TOPK, "indexer_rope_interleave": True,
    "intermediate_size": 96, "kv_lora_rank": 16,
    "max_position_embeddings": 256, "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "model_type": "glm_moe_dsa", "n_group": 1,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "experts_first": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts_per_tok": 4,
    "num_hidden_layers": 78, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "q_lora_rank": 32, "qk_head_dim": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 20, "vocab_size": 128, "vocab_size_published": 1024,
    "depth": 3, "dense_depth": 1}
#: a vocabulary at which a seeded module's drafts ARE accepted
SMALL = {**CONFIG, "vocab_size": 16, "vocab_size_published": 128}
SELF = dict(draft="self", spec_tokens=1)


def _params(spec, seed=0):
    # N(0, 0.02) at width 64 leaves the residual stream the token's own
    # embedding: scaled up, every part of the block moves the logits
    return jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim > 1 and a.shape[-2:] != (2, 16) else a,
        spec.init_fn(jax.random.PRNGKey(seed)))


@pytest.fixture(scope="module")
def model():
    spec = family.build(CONFIG)
    return spec, _params(spec)


@pytest.fixture(scope="module")
def small():
    spec = family.build(SMALL)
    return spec, _params(spec, seed=1)


def _engine(spec, params, **how):
    return deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, params=params, **{**dict(
            slots=3, max_seq_len=128, block_size=BLOCK, prefill_chunk=CHUNK,
            debug_checks=True), **how})


def _requests(lengths, new, vocab, seed=0, **more):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab, n).astype(np.int32), m, **more)
            for i, (n, m) in enumerate(zip(lengths, new))]


def _rel(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def _job(config, seed=5, tokens=112, prefix=48):
    return types.SimpleNamespace(
        config=config, seed=seed, family=family, note=lambda text: None,
        traffic={"score_tokens": tokens, "score_prefix": prefix})


# --------------------------------------------------------------- the model
def test_the_trunk_is_dots3s_full_kind_and_the_module_one_more_block(model):
    spec, params = model
    cfg = spec.model_config
    assert cfg.stretches == (("latent_indexed",), 2, ())
    assert cfg.layer_kinds == ("latent_indexed",)
    assert (cfg.head_gate, cfg.lora_rescale, cfg.index_rope_interleaved) \
        == (False, False, True)
    blocks, mtp = params["blocks"], params["mtp"]
    assert blocks["latent_indexed"]["q_b_w"].shape == (3, 32, 8 * 24)
    assert blocks["latent_indexed"]["kv_b_w"].shape == (3, 16, 8 * (16 + 20))
    assert "head_gate_w" not in blocks["latent_indexed"]
    assert blocks["dense"]["w1"].shape == (1, 64, 96)
    assert blocks["moe"]["experts_w1"].shape == (2, 4, 64, 32)
    assert mtp["eh_w"].shape == (128, 64)
    assert mtp["blocks"]["latent_indexed"]["q_b_w"].shape == (1, 32, 192)
    assert mtp["blocks"]["moe"]["experts_w1"].shape == (1, 4, 64, 32)
    assert cfg.num_params() == family.num_params(CONFIG) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    published = type(cfg).glm_5()
    assert published.stretches == (("latent_indexed",) * 3, 75, ())
    assert 743.8e9 < published.num_params() - published.mtp_params() \
        < 744.0e9
    hook = spec.decode_hooks["self_draft"]
    assert (hook["depth"], hook["layers"], hook["cache"]) \
        == (1, 1, {"draft_layers": 1})
    assert spec.decode_hooks["supports_verify"] is True
    with pytest.raises(NotImplementedError, match="inference path"):
        spec.loss_fn(params, jnp.zeros((1, 8), jnp.int32))


def test_uncached_forward_equals_the_reference(model):
    spec, params = model
    toks = np.random.default_rng(0).integers(0, 128, (2, 70)).astype(np.int32)
    want = np.asarray(ref.logits(CONFIG, params, toks)["trunk"])
    got = np.asarray(spec.apply_fn(params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=2e-4)


# ------------------------------------------------- engine vs the reference
@pytest.fixture(scope="module")
def compared(model):
    """The driver's own comparison on a tiny engine: a prefix prefilled as a
    first row, served to a second through its table all but the last block,
    both rungs, 16 rounds — trunk, module, both window positions, the
    module's row at the prefix's end."""
    spec, params = model
    srv = _engine(spec, params, debug_checks=False, **SELF)
    return srv, driver.check_logits(_job(CONFIG), srv)


def test_prefill_then_rounds_agree_with_the_references_full_forward(compared):
    srv, check = compared
    assert check["ok"], {k: v for k, v in check.items() if k != "engine"}
    for key in ("logit_rel_rmse", "module_rel_rmse",
                "logit_rel_rmse_second", "module_rel_rmse_second",
                "module_row_rel_rmse"):
        assert check[key] < driver.LOGIT_REL_RMSE["fp32"]["trunk"], key
    engine = check["engine"]
    # positions on both sides of index_topk, both rungs, 16 rounds
    assert min(engine["at"]) < TOPK < max(engine["at"])
    assert len(engine["seconds"]) == driver.ROUNDS
    assert engine["compared"].count(False) == 1
    # every block is free again: the comparison leaves the engine as it was
    assert srv._alloc.blocks_in_use == 0


@pytest.mark.parametrize("variant", driver.VARIANTS)
def test_every_control_is_refused_at_the_limit_used(model, compared, variant):
    srv, check = compared
    other = driver.check_logits(_job(CONFIG), srv, variant, check["engine"])
    assert not other["ok"], variant


# ------------------------------------------------ the round, token for token
CASES = {
    "plain": dict(lengths=[40, 33, 50, 20, 64], new=[12, 9, 7, 15, 5]),
    "eos_inside_a_window": dict(lengths=[40, 33, 50], new=[40, 40, 40],
                                eos=3),
    "a_budget_that_ends_mid_window": dict(lengths=[30, 31, 32, 33],
                                          new=[2, 3, 1, 4]),
    "preemption_between_rounds": dict(lengths=[60, 58, 62], new=[30, 30, 30],
                                      how=dict(num_blocks=1 + 14)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_greedy_tokens_are_those_of_speculation_off(small, case):
    """Speculation on against off, token for token, at a vocabulary of 16 so
    that drafts ARE accepted; ``debug_checks`` audits after every round."""
    spec, params = small
    c = CASES[case]
    outs, stats = [], []
    for how in ({}, SELF):
        srv = _engine(spec, params, **how, **c.get("how", {}))
        reqs = _requests(c["lengths"], c["new"], 16)
        outs.append(srv.serve(reqs, eos_token_id=c.get("eos")))
        stats.append(srv.stats())
        srv.close()
        deepspeed_tpu.comm.reset_topology()
    for uid in outs[0]:
        np.testing.assert_array_equal(outs[0][uid], outs[1][uid], str(uid))
    on = stats[1]
    assert on["speculative"] == "self" and on["spec_rounds"] > 0
    assert on["invariant_checks_run"] > 0
    if case == "plain":
        assert on["accepted_tokens"] > 0
        assert 1.0 < on["tokens_per_round"] <= 2.0
        assert on["acceptance_rate"] == pytest.approx(
            on["accepted_tokens"] / on["drafted_tokens"])
    if case == "preemption_between_rounds":
        assert on["evicted"] > 0


def test_greedy_tokens_are_the_references(model):
    spec, params = model
    srv = _engine(spec, params, **SELF)
    reqs = _requests([70, 33, 50, 9], [8] * 4, 128)
    out = srv.serve(reqs)
    assert_greedy(lambda ids: ref.logits(CONFIG, params, ids)["trunk"], reqs,
                  out)


def test_sampled_requests_are_served_and_twins_agree(small):
    """Sampled rows through the rejection sampler: two engines built alike
    stream the same tokens (counter-keyed draws), greedy rows beside them
    stay greedy."""
    spec, params = small
    outs = []
    for _ in range(2):
        srv = _engine(spec, params, **SELF)
        reqs = _requests([40, 33, 50], [16, 16, 16], 16)
        for i, r in enumerate(reqs[:2]):
            r.temperature, r.top_p, r.seed = 0.7, 0.9, 11 + i
        outs.append(srv.serve(reqs))
        st = srv.stats()
        assert st["sampled_requests"] == 2 and st["spec_rounds"] > 0
        srv.close()
        deepspeed_tpu.comm.reset_topology()
    for uid in outs[0]:
        np.testing.assert_array_equal(outs[0][uid], outs[1][uid])
    off = _engine(spec, params)
    np.testing.assert_array_equal(
        off.serve(_requests([40, 33, 50], [16, 16, 16], 16))[2], outs[0][2])


def test_a_rejected_drafts_entries_are_never_read_by_a_later_query(model):
    """A window whose second position is junk, then the true token at that
    position: the logits of trunk and module are those of a cache that never
    saw the junk (its latent, its index key and the module's row are
    overwritten in place)."""
    spec, params = model
    hooks = spec.decode_hooks
    fwd, dfwd = hooks["forward_cached"], hooks["self_draft"]["forward"]
    toks = np.random.default_rng(4).integers(0, 128, 44).astype(np.int32)
    p = 40
    bt = jnp.asarray(1 + np.arange(8)[None], jnp.int32)

    def start():
        cache = hooks["init_cache"](9, BLOCK, jnp.float32, draft_layers=1)
        ids = jnp.asarray(toks[None, :p])
        _, cache, hidden = fwd(params, ids, cache, jnp.zeros(1, jnp.int32),
                               lengths=jnp.full(1, p), block_tables=bt,
                               hidden=True)
        _, cache = dfwd(params, hidden, jnp.asarray(toks[None, 1:p + 1]),
                        cache, jnp.zeros(1, jnp.int32),
                        lengths=jnp.full(1, p), block_tables=bt)
        return cache

    def window(cache, base, ids, after, trunk=2, module=2):
        """A round by hand: the trunk over ``trunk`` real positions of a
        window of two, the module over the ``module`` it committed."""
        logits, cache, hidden = fwd(
            params, jnp.asarray(ids[None]), cache,
            jnp.full(1, base, jnp.int32), lengths=jnp.full(1, trunk),
            block_tables=bt, all_positions=True, hidden=True)
        guess, cache = dfwd(
            params, hidden, jnp.asarray(after[None]), cache,
            jnp.full(1, base, jnp.int32), lengths=jnp.full(1, module),
            block_tables=bt, all_positions=True)
        return np.asarray(logits[0]), np.asarray(guess[0]), cache

    pair = lambda a, b: np.asarray([a, b], np.int32)      # noqa: E731
    # the draft at p + 1 is junk and is REJECTED: the trunk wrote both
    # positions, the module the one committed (its next token: the true one)
    _, _, seen = window(start(), p, pair(toks[p], (toks[p + 1] + 5) % 128),
                        pair(toks[p + 1], 0), module=1)
    got = window(seen, p + 1, toks[p + 1:p + 3], toks[p + 2:p + 4])
    _, _, clean = window(start(), p, pair(toks[p], 0), pair(toks[p + 1], 0),
                         trunk=1, module=1)
    want = window(clean, p + 1, toks[p + 1:p + 3], toks[p + 2:p + 4])
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)


# ------------------------------------------------------------------ the trie
def test_a_shared_prefix_on_a_block_boundary_gives_the_unshared_twins(small):
    """Two prompts share 32 tokens — two whole blocks — and differ in the
    next: the second is handed ONE block (the hit ends a block early), and
    its tokens, its accepted drafts and the module's row at the prefix's last
    position are those of a twin served with no trie."""
    spec, params = small
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 16, 2 * BLOCK).astype(np.int32)
    prompts = [np.concatenate([shared, [t], rng.integers(0, 16, 9)])
               .astype(np.int32) for t in (2, 11)]

    def serve(srv, i):
        out = srv.serve([Request(i, prompts[i], 12)])[i]
        st = srv.stats()
        return out, st["accepted_tokens"], st["prefix_hit_tokens"]

    def row(srv, slot_blocks):
        module = srv._cache["latent"].shape[0] - 1
        return np.asarray(srv._cache["latent"][module, slot_blocks[1], 0,
                                               BLOCK - 1])

    both = _engine(spec, params, slots=1, **SELF)
    first = serve(both, 0)
    # (a finished request's blocks stay in the trie: its table row is read
    # before the second request takes the slot)
    second = serve(both, 1)
    assert first[2] == 0 and second[2] - first[2] == BLOCK
    assert both.stats()["prefix_query_tokens"] == 2 * len(prompts[0])
    held = list(both._held[0]) or None
    twins = []
    for i in (0, 1):
        srv = _engine(spec, params, slots=1, prefix_caching=False, **SELF)
        twins.append(serve(srv, i))
        srv.close()
        deepspeed_tpu.comm.reset_topology()
    np.testing.assert_array_equal(first[0], twins[0][0])
    np.testing.assert_array_equal(second[0], twins[1][0])
    assert second[1] - first[1] == twins[1][1]
    assert held is None     # the slot is free again
    # the registered second block's last module row belongs to the FIRST
    # request (made from ITS next token): what a hit that kept it would read
    assert not np.array_equal(first[0][2 * BLOCK], second[0][2 * BLOCK])


# ------------------------------------------------------- what the engine says
def test_a_round_is_one_span_and_one_harvest(small):
    spec, params = small
    srv = _engine(spec, params, debug_checks=False, **SELF)
    reqs = _requests([40, 33, 50], [10, 10, 10], 16)
    srv.serve(reqs)
    st = srv.stats()
    events = [e for e in srv.timeline.events() if e["ph"] == "X"]
    names = {e["name"] for e in events}
    assert "spec_round" in names
    assert not names & {"spec_propose", "spec_verify", "decode"}
    rounds = [e["args"] for e in events if e["name"] == "spec_round"]
    assert len(rounds) == st["spec_rounds"]
    for a in rounds:
        assert {"window", "drafted", "accepted", "emitted", "index_keys",
                "kv_selected", "kv_read", "kv_blocks", "latent_bytes",
                "expert_rows", "experts_touched", "enqueue_s", "wait_s",
                "puts"} <= set(a), sorted(a)
        assert a["window"] == 2 and a["puts"] == 1
        assert a["drafted"] == a["slots"] <= a["emitted"] <= 2 * a["slots"]
        assert a["accepted"] == a["emitted"] - a["slots"]
    assert sum(a["accepted"] for a in rounds) >= st["accepted_tokens"] > 0
    # two programs a round beside the prefill rungs, steady after warm-in
    assert st["compile_count"] == len(srv._rungs) + 2 == st["compile_budget"]
    assert st["retraces_observed"] == 0
    assert st["speculative"] == "self"
    assert st["kv_kinds"]["draft"] == {"layers": 1, "table": "full",
                                       "depth": 1}
    assert st["kv_kinds"]["full"]["layers"] == 3
    # the round rides the one call of lookahead: every call but the first
    # was enqueued behind one in flight, and none was settled for a cause
    look = st["lookahead"]
    assert look["early"] == {} and look["ahead"] >= look["calls"] - 2 > 0
    assert look["ahead"] == sum(e["args"]["ahead"] for e in events
                                if e["name"] in ("spec_round", "prefill"))
    assert sum(a["ahead"] for a in rounds) >= len(rounds) - 2
    assert srv.resolved_config()["draft"] == "self"
    assert srv._cache["latent"].shape[0] == 4
    prefills = [e["args"] for e in events if e["name"] == "prefill"]
    assert all("index_keys" in a and "expert_rows" in a for a in prefills)
    admits = [e["args"] for e in srv.timeline.events()
              if e["name"] == "admit"]
    assert all("prefix_hit_tokens" in a for a in admits)
    report = srv.flops_report(peak_flops=1e12)
    assert {"prefill", "verify", "draft"} <= set(report["programs"])
    assert report["program_calls"]["draft"] == st["spec_rounds"]
    assert report["programs"]["draft"]["flops_per_call"] > 0
    srv.serve(_requests([40, 33], [6, 6], 16, seed=3))
    assert srv.stats()["compile_count"] == st["compile_count"]


# ------------------------------------------ a round planned one call ahead
def _pair(spec, params, **how):
    """(a self-drafting engine that looks ahead, its twin that settles every
    call at once — ``debug_checks=True``, there being no option)."""
    return _engine(spec, params, debug_checks=False, **SELF, **how), \
        _engine(spec, params, **SELF, **how)


@pytest.fixture(scope="module")
def pair(small):
    ahead, serial = _pair(*small)
    yield ahead, serial
    ahead.close()
    serial.close()


def _mixed(lengths, new, sampled=(), seed=0):
    reqs = _requests(lengths, new, 16, seed=seed)
    for i in sampled:
        reqs[i].temperature, reqs[i].top_p = 0.7, 0.9
        reqs[i].seed = 2 ** 31 + 97 * i
    return reqs


def _streams(srv, reqs, eos=None, late=()):
    """uid -> the tokens its handle streamed, ``step()`` driven by hand;
    ``late``: (step, request) pairs submitted once that many steps ran."""
    eos = eos or {}
    submit = lambda r: srv.submit(r, eos_token_id=eos[r.uid]) \
        if r.uid in eos else srv.submit(r)                 # noqa: E731
    handles = [submit(r) for r in reqs]
    late, steps = sorted(late, key=lambda p: p[0]), 0
    while True:
        while late and late[0][0] <= steps:
            handles.append(submit(late.pop(0)[1]))
        more = srv.step()
        steps += 1
        if not more and not late:
            assert srv._flight is None and not srv._active
            break
    assert all(h.done for h in handles)
    return {h.uid: list(h.tokens()) for h in handles}


def _same(ahead, serial, reqs_of, **kw):
    got, want = _streams(ahead, reqs_of(), **kw), \
        _streams(serial, reqs_of(), **kw)
    assert got == want
    return got


LOOK = dict(lengths=[40, 33, 50, 20, 64, 27, 45], new=[12, 9, 17, 15, 5, 20, 8])


@pytest.mark.parametrize("sampled", [(), range(7), (1, 4, 5)],
                         ids=["greedy", "sampled", "mixed"])
def test_streams_under_lookahead_are_those_of_rounds_settled_at_once(
        pair, sampled):
    ahead, serial = pair
    before = ahead.stats()
    got = _same(ahead, serial, lambda: _mixed(**LOOK, sampled=sampled))
    assert [len(got[i]) for i in range(7)] == LOOK["new"]
    after, twin = ahead.stats(), serial.stats()["lookahead"]
    calls = after["lookahead"]["calls"] - before["lookahead"]["calls"]
    ran = after["lookahead"]["ahead"] - before["lookahead"]["ahead"]
    # all but the first call of the trace was enqueued behind one in flight
    assert ran >= calls - 2 and calls > 20
    assert after["lookahead"]["early"] == {}
    assert twin["ahead"] == 0
    assert twin["early"] == {"debug_checks": twin["calls"]}
    # drafts WERE accepted: rows advanced by counts only the device knew
    assert after["accepted_tokens"] > before["accepted_tokens"]
    assert after["tokens_per_round"] > 1.0


@pytest.mark.parametrize("new", [1, 2, 3])
def test_a_budget_the_round_in_flight_spends_or_may_spend(pair, new):
    """K = 1: a budget of one is spent by the prefill call's token, of two
    by the first round for certain (the row sits the second out), of three
    perhaps (the row rides, and is dropped if the first round took both)."""
    ahead, serial = pair
    got = _same(ahead, serial, lambda: _requests(
        [30, 31, 32, 33, 34, 35], [new] * 6, 16, seed=new))
    assert all(len(t) == new for t in got.values())
    assert ahead._alloc.blocks_in_use == serial._alloc.blocks_in_use


def test_a_row_that_ends_on_eos_mid_batch_leaves_nothing_behind(small):
    """The host cannot know that a round in flight holds a row's ``eos``:
    the row rides the next round, at a base the device advanced by the whole
    walk.  What that round makes of it is dropped — not streamed, not
    counted — and every block comes back."""
    ahead, serial = _pair(*small)
    reqs = lambda: _mixed([40, 33, 50, 20, 64, 27], [24] * 6,  # noqa: E731
                          sampled=range(6), seed=5)
    free = _streams(serial, reqs())
    eos = {}
    for uid, toks in free.items():
        new = [k for k in range(2, len(toks) - 1) if toks[k] not in toks[:k]]
        if uid % 3 and new:
            eos[uid] = toks[new[len(new) // 2]]
    assert len(eos) >= 3
    counted = ahead.stats()["generated_tokens"], \
        serial.stats()["generated_tokens"]
    got = _same(ahead, serial, reqs, eos=eos)
    for uid, toks in got.items():
        assert toks == free[uid][:len(toks)]
        if uid in eos:
            assert toks[-1] == eos[uid] and eos[uid] not in toks[:-1]
            assert len(toks) < len(free[uid])
    emitted = sum(map(len, got.values()))
    assert ahead.stats()["generated_tokens"] - counted[0] == emitted
    assert serial.stats()["generated_tokens"] - counted[1] == emitted
    # rows rode past their end: the spans' rows outnumber the rows committed
    rode = sum(e["args"]["slots"] for e in ahead.timeline.events()
               if e["ph"] == "X" and e["name"] == "spec_round")
    assert rode > ahead._c_round_rows.value > 0
    assert ahead._alloc.blocks_in_use == serial._alloc.blocks_in_use
    # the same prompts again hit what the twin's hit and stream the same
    hits = ahead.prefix_hit_tokens, serial.prefix_hit_tokens
    assert _same(ahead, serial, reqs, eos=eos) == got
    assert ahead.prefix_hit_tokens - hits[0] == \
        serial.prefix_hit_tokens - hits[1] > 0
    ahead.close()
    serial.close()


def test_a_prompt_of_several_chunks_admitted_while_a_round_is_in_flight(pair):
    ahead, serial = pair
    prompt = np.random.default_rng(21).integers(0, 16, 100).astype(np.int32)
    long = lambda: [(3, Request("late", prompt, 10))]      # noqa: E731
    since = len(ahead.timeline.events())
    first = lambda: _mixed([40, 33], [30, 30], sampled=(1,),  # noqa: E731
                           seed=8)
    got = _streams(ahead, first(), late=long())
    want = _streams(serial, first(), late=long())
    assert got == want and len(got["late"]) == 10
    calls = [e for e in ahead.timeline.events()[since:]
             if e["ph"] == "X" and e["name"] in ("prefill", "spec_round")]
    chunks = [e for e in calls if e["name"] == "prefill"
              and e["args"]["step"] > calls[0]["args"]["step"] + 2]
    # the late prompt's chunks went out behind rounds in flight, and rounds
    # behind them
    assert len(chunks) >= 2 and all(e["args"]["ahead"] for e in chunks)
    assert all(e["args"]["ahead"] for e in calls[1:])


def test_a_shared_prefix_on_a_block_boundary_under_lookahead(small):
    spec, params = small
    rng = np.random.default_rng(9)
    shared = rng.integers(0, 16, 2 * BLOCK).astype(np.int32)
    prompts = [np.concatenate([shared, [t], rng.integers(0, 16, 9)])
               .astype(np.int32) for t in (2, 11)]
    ahead, serial = _pair(spec, params, slots=2)
    outs = []
    for srv in (ahead, serial):
        # the second arrives while the first decodes: its hit is one block
        # (two matched, the last dropped) on both engines
        outs.append(_streams(srv, [Request(0, prompts[0], 12)],
                             late=[(4, Request(1, prompts[1], 12))]))
        assert srv.stats()["prefix_hit_tokens"] == BLOCK
    assert outs[0] == outs[1]
    assert ahead._alloc.blocks_in_use == serial._alloc.blocks_in_use
    ahead.close()
    serial.close()


def test_a_pool_tight_enough_to_preempt_under_lookahead(small):
    ahead, serial = _pair(*small, num_blocks=1 + 14)
    reqs = lambda: _mixed([60, 58, 62], [30, 30, 30],  # noqa: E731
                          sampled=(1,), seed=2)
    _same(ahead, serial, reqs)
    assert ahead.preempted > 0 and serial.preempted > 0
    # a victim is chosen among committed rows: the round in flight settles
    early = ahead.stats()["lookahead"]["early"]
    assert set(early) == {"preempt"} and early["preempt"] > 0
    assert ahead._alloc.blocks_in_use == serial._alloc.blocks_in_use
    ahead.close()
    serial.close()


def test_cancelling_a_row_whose_round_is_in_flight(pair):
    ahead, serial = pair

    def run(srv):
        handles = [srv.submit(r) for r in _requests(
            [40, 33, 50], [20, 20, 20], 16, seed=11)]
        for _ in range(6):
            srv.step()
        victim = handles[1]
        assert not victim.done and len(victim.tokens()) > 0
        pending = srv._flight is not None and \
            srv._flight.name == "spec_round"
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
    for uid in got:
        if uid == 1:
            # what was streamed stands, and the round settled for the cancel
            # gave it at most one window more
            assert got[uid][:len(want[uid])] == want[uid]
            assert 0 <= len(got[uid]) - len(want[uid]) <= 2
        else:
            assert got[uid] == want[uid]
    assert ahead._alloc.blocks_in_use == serial._alloc.blocks_in_use


def test_nothing_compiles_after_the_first_round(small):
    spec, params = small
    srv = _engine(spec, params, debug_checks=False, **SELF)
    handles = [srv.submit(r) for r in _mixed(**LOOK, sampled=(2, 3))]
    for _ in range(4):
        srv.step()
    built, traces = srv.compile_count, srv.sentry.traces
    assert built == len(srv._rungs) + 2 == srv.compile_budget
    while srv.step():
        pass
    _streams(srv, _mixed([90, 20], [6, 9], seed=29))
    assert all(h.done for h in handles)
    assert srv.compile_count == built and srv.sentry.traces == traces
    assert srv.stats()["retraces_observed"] == 0
    # the three vectors go from either program into either: one executable
    for fn in (srv._verify_fn, srv._draft_fn, *srv._prefill_fns.values()):
        assert fn._cache_size() == 1
    look = srv.stats()["lookahead"]
    assert look["ahead"] > 0 and "speculative" not in look["early"]
    srv.close()


@pytest.mark.parametrize("how,match", [
    (dict(draft="self"), "spec_tokens is 0"),
    (dict(draft="self", spec_tokens=2), "drafts 1 token"),
    (dict(draft="self", spec_tokens=1, logit_masks=True), "logit_masks"),
    (dict(draft="self", spec_tokens=1, host_blocks=8), "host_blocks"),
    (dict(draft="model", spec_tokens=1), "draft='self'.*is served"),
])
def test_what_is_not_served_is_refused_by_name(small, how, match):
    spec, params = small
    if how.get("draft") == "model":
        how = {**how, "draft": spec}
    with pytest.raises(ValueError, match=match):
        _engine(spec, params, **how)


def test_a_model_without_a_module_cannot_draft_for_itself():
    from deepspeed_tpu.models import gpt2

    with pytest.raises(ValueError, match="no drafting module of its own"):
        deepspeed_tpu.init_serving(
            gpt2.build(gpt2.GPT2Config.tiny()), config={"dtype": "fp32"},
            slots=2, max_seq_len=64, block_size=8, **SELF)
    assert options.SELF_DRAFT == "self"
    assert "draft='self'" in options.KIND_REFUSES["latent"]["a draft model"]


# ------------------------------------------------------------------ the share
@pytest.mark.parametrize("where", ["a_trunk_layer", "the_module"])
def test_the_shares_partial_sums_add_up_to_the_uncut_layer(where):
    """THE SHARE TEST: four chips' routed partial sums, the shared expert
    counted once, equal the uncut layer — for a routed trunk layer and for
    the module's block."""
    uncut = {**CONFIG, "n_routed_experts": 16, "experts_first": 0}
    spec = family.build(uncut)
    params = _params(spec, seed=3)
    moe = params["blocks"]["moe"] if where == "a_trunk_layer" \
        else params["mtp"]["blocks"]["moe"]
    x = jnp.asarray(np.random.default_rng(3).standard_normal((40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        layer = np.asarray(ref._experts(uncut, x, moe, 0))
        shared = np.asarray(
            (jax.nn.silu(x @ moe["shared_w1"][0]) * (x @ moe["shared_w3"][0]))
            @ moe["shared_w2"][0])
        parts = []
        for chip in range(4):
            share = {**uncut, "n_routed_experts": 4, "experts_first": 4 * chip}
            held = {k: v[:, 4 * chip:4 * chip + 4] if k.startswith("experts_")
                    else v for k, v in moe.items()}
            parts.append(np.asarray(ref._experts(share, x, held, 0)) - shared)
    np.testing.assert_allclose(sum(parts) + shared, layer, atol=1e-5)
    assert np.abs(parts[0]).max() > 1e-3
