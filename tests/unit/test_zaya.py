"""ZAYA1 through ``init_serving`` / ``ServingEngine`` (``models/zaya.py``):
compressed convolutional attention over the paged pool's ``full`` kind with
per-slot convolution TAILS, a top-1 expert layer behind an MLP router that
carries its stream from layer to layer — tiny widths, seeded weights, the
plain reference ``chipbench/reference_zaya.py`` on logits, routes and
scores."""

import argparse
import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import options
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.models import zaya as Z
from deepspeed_tpu.moe import routed
from deepspeed_tpu.ops import paged_kv

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference_zaya as ref  # noqa: E402
from chipbench import run as cb_run  # noqa: E402
from chipbench.drivers import serve_tails  # noqa: E402
from chipbench.families import zaya as family  # noqa: E402

pytestmark = pytest.mark.limit(90)
CELL = "zaya1-reasoning-closed"


def _config(rehearse=True):
    return cb_run._rehearsed(json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "ZAYA1-8B.json"))), rehearse)


@pytest.fixture(scope="module")
def tiny():
    """(config file's dict at the rehearsal's widths, ModelSpec, float32
    params)."""
    config = _config()
    spec = family.build(config)
    return config, spec, spec.init_fn(jax.random.PRNGKey(0))


def _serve(spec, params, **kw):
    kw = {"slots": 3, "max_seq_len": 128, "block_size": 16,
          "prefill_chunk": 16, **kw}
    return deepspeed_tpu.init_serving(spec, config={"dtype": "fp32"},
                                      params=params, **kw)


@pytest.fixture(scope="module")
def served(tiny):
    """Five requests through three slots (two slots are used twice)."""
    config, spec, params = tiny
    srv = _serve(spec, params)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, 512, n), max_new_tokens=6)
            for i, n in enumerate([40, 7, 33, 20, 50])]
    out = srv.serve(reqs)
    snapshot = (srv.stats(), list(srv.timeline.events()))
    yield srv, reqs, out, snapshot
    srv.close()


# ----------------------------------------------------------------- the counts
def test_the_parameter_function_gives_the_issues_counts(tiny):
    config, spec, params = tiny
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == spec.model_config.num_params() == family.num_params(config)
    published = _config(False)
    assert published["reduced"] == ["depth"] and published["depth"] == 10
    assert family.layer_params(published) == 207_579_651       # 207.58 M
    assert family.num_params(published, 40) == 8_840_321_144    # 8.84 G
    assert family.num_params(published) == 2_612_931_614        # 5.23 GB
    assert family.active_params(published) - 262272 * 2048 - 2048 \
        == 10 * (5_573_122 + 659_457 + 12_582_912 + 20_480)
    cfg = family.build(published).model_config
    assert cfg == dataclasses.replace(Z.ZayaConfig.zaya1_8b(), num_layers=10)
    assert cfg.layer_params() == 207_579_651
    assert Z.ZayaConfig.zaya1_8b().num_params() == 8_840_321_144
    assert family.cached_bytes_per_token(published) == 10 * 1024
    assert family.tail_bytes_per_slot(published) == 10 * 5376


def test_published_cache_tree_and_hook():
    spec = family.build(_config(False))
    assert spec.decode_hooks["tail_layers"] == {
        "layers": 10, "taps": {"conv": 2, "shift": 1}}
    assert "state_layers" not in spec.decode_hooks
    cache = jax.eval_shape(lambda: spec.decode_hooks["init_cache"](
        9, 32, jnp.bfloat16, state_rows=128))
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "k": ((10, 9, 2, 32, 128), "bfloat16"),
        "v": ((10, 9, 2, 32, 128), "bfloat16"),
        "conv": ((10, 128, 1, 2, 1280), "bfloat16"),
        "shift": ((10, 128, 1, 1, 128), "bfloat16")}
    assert set(paged_kv.TAIL_LEAVES) <= set(paged_kv.ROW_LEAVES)
    assert set(paged_kv.STATE_LEAVES) <= set(paged_kv.ROW_LEAVES)


# ------------------------------------------------------ against the reference
def test_uncached_forward_is_the_reference_and_training_is_refused(tiny):
    config, spec, params = tiny
    tokens = np.random.default_rng(2).integers(0, 512, (2, 24))
    got = Z.forward(spec.model_config, params, jnp.asarray(tokens))
    np.testing.assert_allclose(got, ref.logits(config, params, tokens),
                               atol=2e-5)
    np.testing.assert_allclose(
        spec.loss_fn(params, jnp.asarray(tokens), train=False),
        ref.next_token_loss(config, params, tokens), rtol=1e-5)
    with pytest.raises(NotImplementedError, match="backward"):
        spec.loss_fn(params, jnp.asarray(tokens))


def _job(config, seed=5, **traffic):
    args = argparse.Namespace(seed=seed, seconds=0.0, rehearse=True, trace=0,
                              keep_trace=None)
    job = cb_run.Job(args, cb_run.load_cell(CELL, True))
    job.traffic.update(traffic)
    return job


def test_engine_logits_routes_and_scores_are_the_references(tiny):
    """The benchmark's own comparison on a tiny engine: chunked prefill in
    calls of ``prefill_batch`` rows (two of them pads) then decode steps at
    every slot (two of them idle) against the reference's full pass under
    the engine's routes, (a)-(c) of ``serve_tails``."""
    config, spec, params = tiny
    srv = _serve(spec, params, slots=4)
    job = _job(config)
    check = serve_tails.check_logits(job, srv)
    got, made = check.pop("engine")
    assert check["ok"], check
    assert check["route_flips"] == 0 and check["logit_rel_rmse"] < 2e-5
    assert made["experts"].shape == (3, 2, 64)
    assert made["scores"].shape == (3, 2, 64, 4)
    # every expert is somebody's, and a score is a softmax
    assert set(np.unique(made["experts"])) == {0, 1, 2, 3}
    np.testing.assert_allclose(made["scores"].sum(-1), 1.0, atol=1e-5)
    # forced routes: the reference under ROTATED routes is another model,
    # and the near-tie rule names it
    turned = {"experts": (made["experts"] + 1) % 4, "scores": made["scores"]}
    wrong = serve_tails.check_logits(job, srv, engine=(got, turned))
    assert not wrong["ok"] and wrong["route_flips"] > 0.5
    assert wrong["route_tie"] > 0.01
    assert wrong["logit_rel_rmse"] > 1e-3
    srv.close()


@pytest.mark.parametrize("variant", serve_tails.VARIANTS + serve_tails.SHOWN)
def test_every_shortcut_of_the_reference_is_refused(tiny, variant):
    """At float32 limits every variant of the reference — a part in a lower
    precision, a mechanism left out — is another model."""
    config, spec, params = tiny
    tokens = np.random.default_rng(3).integers(0, 512, (1, 48))
    want = np.asarray(ref.logits(config, params, tokens))
    got = np.asarray(ref.logits(config, params, tokens, variant=variant))
    rel = np.sqrt(np.mean((got - want) ** 2)) / np.std(want)
    assert rel > 10 * serve_tails.LIMITS["fp32"]["logit"], (variant, rel)


def _window(spec, params, cache, ids, base, slot, nbper=4):
    """One prefill window of ONE row through the hook at ``slot``."""
    bt = jnp.asarray(1 + slot * nbper + np.arange(nbper)[None], jnp.int32)
    t = ids.shape[1]
    return spec.decode_hooks["forward_cached"](
        params, jnp.asarray(ids), cache, jnp.asarray([base], jnp.int32),
        lengths=jnp.asarray([t], jnp.int32),
        block_tables={"full": bt, "slot": jnp.asarray([slot], jnp.int32)},
        all_positions=True)


@pytest.mark.parametrize("chunk", [5, 16])
def test_chunks_carry_the_tails_across_their_boundary(tiny, chunk):
    """One prompt prefilled in chunks of 5, of 16 and whole: the same K, V,
    tails and logits."""
    config, spec, params = tiny
    ids = np.random.default_rng(6).integers(0, 512, (1, 48))
    init = spec.decode_hooks["init_cache"]
    whole_logits, whole = _window(
        spec, params, init(1 + 3 * 4, 16, jnp.float32, state_rows=3), ids, 0,
        slot=1)
    cache, parts = init(1 + 3 * 4, 16, jnp.float32, state_rows=3), []
    for base in range(0, 48, chunk):
        logits, cache = _window(spec, params, cache,
                                ids[:, base:base + chunk], base, slot=1)
        parts.append(logits)
    np.testing.assert_allclose(jnp.concatenate(parts, 1), whole_logits,
                               atol=2e-5)
    for name in ("k", "v", "conv", "shift"):
        np.testing.assert_allclose(cache[name], whole[name], atol=2e-5,
                                   err_msg=name)
    assert float(jnp.abs(whole["conv"][:, 1]).min()) > 0
    # the other rows' tails were never touched
    for name in ("conv", "shift"):
        assert not np.asarray(whole[name][:, [0, 2]]).any()


def test_pads_and_idle_rows_move_no_tail(tiny):
    config, spec, params = tiny
    fwd, init = spec.decode_hooks["forward_cached"], \
        spec.decode_hooks["init_cache"]
    rng = np.random.default_rng(7)
    cache = init(1 + 3 * 4, 16, jnp.float32, state_rows=3)
    cache = {**cache, "conv": cache["conv"] + 7.0,
             "shift": cache["shift"] - 3.0}
    bt = np.zeros((3, 4), np.int32)
    bt[1] = 1 + np.arange(4)
    # a prefill call of three rows: row 0 real at slot 1 (9 tokens, 7
    # pads), rows 1-2 pads (slot out of range)
    ids = rng.integers(0, 512, (3, 16))
    _, after = fwd(params, jnp.asarray(ids), cache, jnp.zeros(3, jnp.int32),
                   lengths=jnp.asarray([9, 0, 0], jnp.int32),
                   block_tables={"full": jnp.asarray(bt[[1, 0, 0]]),
                                 "slot": jnp.asarray([1, 3, 3], jnp.int32)})
    for name, held in (("conv", 7.0), ("shift", -3.0)):
        np.testing.assert_array_equal(after[name][:, [0, 2]], held)
        assert not np.isclose(after[name][:, 1], held).any()
    # the tails are the NINTH token's, whatever the pads behind it hold
    _, again = fwd(params, jnp.asarray(ids[:1, :9]), cache,
                   jnp.zeros(1, jnp.int32), lengths=jnp.asarray([9]),
                   block_tables={"full": jnp.asarray(bt[[1]]),
                                 "slot": jnp.asarray([1], jnp.int32)})
    np.testing.assert_allclose(after["conv"][:, 1], again["conv"][:, 1],
                               atol=1e-6)
    # a decode step: row 1 live, rows 0 and 2 idle (an all-scratch table)
    _, stepped = fwd(params, jnp.asarray(ids[:, :1]), after, 0,
                     lengths=jnp.asarray([0, 9, 0], jnp.int32),
                     block_tables={"full": jnp.asarray(bt)})
    for name, held in (("conv", 7.0), ("shift", -3.0)):
        np.testing.assert_array_equal(stepped[name][:, [0, 2]], held)
        assert not np.allclose(stepped[name][:, 1], after[name][:, 1])


# --------------------------------------------------------- through the engine
def test_served_tokens_are_greedy_of_the_reference(tiny, served):
    """Token-exact against the reference's teacher-forced argmax, for the
    requests that entered a fresh slot and for those that entered a slot
    RELEASED by another (whose tails still lie there: a window at base 0
    starts from zero inside the program)."""
    config, spec, params = tiny
    srv, reqs, out, (st, events) = served
    for r in reqs:
        full = np.asarray(out[r.uid])
        logits = np.asarray(ref.logits(
            config, params, full[None, :-1])[0, len(r.prompt) - 1:])
        np.testing.assert_array_equal(full[len(r.prompt):],
                                      logits.argmax(-1))
    admits = [e["args"]["slot"] for e in events
              if e["name"] == "admit" and "slot" in e.get("args", {})]
    assert len(reqs) > srv.slots
    assert not admits or len(set(admits)) < len(admits)     # a slot reused
    assert np.asarray(srv._cache["conv"]).any()            # tails left behind


def test_lookahead_on_and_off_and_a_preemption_agree(tiny, served):
    """Sampled rows: ``debug_checks`` settles every call before the next is
    planned (no lookahead), and a pool too small for both rows preempts the
    later one and re-prefills it from base 0 — the same tokens."""
    config, spec, params = tiny
    sizes = [30, 28]

    def reqs():
        r = np.random.default_rng(4)
        return [Request(uid=i, prompt=r.integers(0, 512, n),
                        max_new_tokens=12, temperature=0.7, top_p=0.9,
                        seed=11 + i) for i, n in enumerate(sizes)]

    want = served[0].serve(reqs())
    tight = _serve(spec, params, slots=2, max_seq_len=64, num_blocks=5,
                   debug_checks=True)
    got = tight.serve(reqs())
    st = tight.stats()
    assert st["evicted"] >= 1 and st["kv_tails"]["resets"] >= 3
    assert st["lookahead"]["ahead"] == 0
    assert served[0].stats()["lookahead"]["ahead"] > 0
    tight.close()
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])


def test_stats_name_the_tails_beside_the_full_kind(tiny, served):
    srv, reqs, out, (st, events) = served
    tails = st["kv_tails"]
    assert tails["kind"] == "tails" and tails["layers"] == 3
    assert tails["leaves"] == {"conv": [3, 3, 1, 2, 96],
                               "shift": [3, 3, 1, 1, 16]}
    assert tails["taps"] == {"conv": 2, "shift": 1}
    assert tails["bytes"] == 3 * 3 * (2 * 96 + 16) * 4
    assert tails["bytes_per_slot"] * 3 == tails["bytes"]
    assert tails["resets"] == len(reqs)          # one a request entering
    assert tails["refused"] == list(options.KIND_REFUSES["tails"])
    kinds = st["kv_kinds"]
    assert kinds["tails"] == {"layers": 3, "slots": 3,
                              "bytes": tails["bytes"]}
    assert kinds["full"]["layers"] == 3 and "state" not in kinds
    assert kinds["refused"] == tails["refused"]
    assert st["kv_state"] is None and st["kv_latent"] is None
    assert st["compile_count"] == 1 + len(srv._rungs) == 3 \
        and st["prefix_cache_entries"] == 0
    assert st["moe_expert_rows"] > 0
    spans = [e for e in events if e["ph"] == "X"
             and e["name"] in ("prefill", "decode")]
    assert all({"tail_bytes", "tail_resets", "expert_rows",
                "expert_rows_max", "expert_rows_max_sum", "kv_blocks"}
               <= set(e["args"]) for e in spans)
    assert sum(e["args"]["tail_resets"] for e in spans) == len(reqs)
    assert sum(e["args"]["tail_bytes"] for e in spans) \
        == tails["tail_bytes"]
    per_row = 2 * tails["bytes_per_slot"]
    assert all(e["args"]["tail_bytes"] % per_row == 0 for e in spans)
    # top-1: a row a layer; the layers' fullest groups sum to at most that
    for e in spans:
        a = e["args"]
        assert a["expert_rows_max"] <= a["expert_rows_max_sum"] \
            <= a["expert_rows"]
    # the start-up ring's pool span carries the tails' bytes by leaf
    pools = [e["args"]["kinds"] for e in
             deepspeed_tpu.telemetry.trace.kept("setup").events()
             if e["name"] == "pool" and "shift" in e["args"].get("kinds", {})]
    assert any(k["conv"] + k["shift"] == tails["bytes"] for k in pools)


def test_the_contiguous_cache_is_refused_by_name(tiny):
    config, spec, params = tiny
    with pytest.raises(NotImplementedError, match="state_rows"):
        spec.decode_hooks["init_cache"](2, 64, jnp.float32)
    with pytest.raises(NotImplementedError, match="tails a row"):
        spec.decode_hooks["forward_cached"](
            params, jnp.zeros((1, 4), jnp.int32), {}, 0)
    with pytest.raises(NotImplementedError, match="two taps"):
        Z.ZayaConfig(cca_time0=4)
    with pytest.raises(NotImplementedError, match="top-1"):
        Z.ZayaConfig(top_k=2)


# ------------------------------------------------------- the router's entry
#: sha256 of ``routed_ffn``'s lowered text for one existing family's tiny
#: layer WITHOUT ``routed=``, taken on the parent commit: the entry for a
#: caller's scores moves nothing of the program that does not use it
PARENT_ROUTED_FFN = \
    "f89340434c4c6e8988d88d15ff342e8a016f9880e14afe9b8cdaa1e95e288d32"


def _routed_text(**kw):
    cfg = mixtral.MixtralConfig(
        vocab_size=64, max_seq_len=64, num_layers=1, num_heads=2,
        num_kv_heads=2, hidden_size=32, ffn_size=16, num_experts=8, top_k=2)
    shapes = {"y": (6, 32), "gate": (32, 8), "w1": (8, 32, 16),
              "w3": (8, 32, 16), "w2": (8, 16, 32)}
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes.values()]
    return jax.jit(lambda y, g, w1, w3, w2: routed.routed_ffn(
        y, g, w1, w3, w2, cfg.top_k, cfg.norm_topk_prob, kernel=False,
        **kw)).lower(*args).as_text()


def test_routed_ffn_without_callers_scores_is_the_parents_program():
    text = _routed_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_ROUTED_FFN


def test_routed_ffn_takes_the_routers_output_from_its_caller():
    """``routed=(weights, experts)``: what ``route`` would have returned,
    handed in, gives ``route``'s own result; ``gate_w`` is then unused."""
    rng = np.random.default_rng(8)
    y, gate = rng.normal(size=(6, 32)), rng.normal(size=(32, 8))
    w1, w3 = rng.normal(size=(2, 8, 32, 16)) * 0.2
    w2 = rng.normal(size=(8, 16, 32)) * 0.2
    y, gate, w1, w3, w2 = (jnp.asarray(a, jnp.float32)
                           for a in (y, gate, w1, w3, w2))
    want, rec = routed.routed_ffn(y, gate, w1, w3, w2, 2, True, kernel=False)
    got, rec2 = routed.routed_ffn(
        y, None, w1, w3, w2, 2, True, kernel=False,
        routed=routed.route(y, gate, 2, True))
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(rec, rec2)
    with pytest.raises(ValueError, match="balance"):
        routed.routed_ffn(y, None, w1, w3, w2, 2, True, kernel=False,
                          routed=routed.route(y, gate, 2, True), balance=True)
