"""Kimi Linear through ``init_serving`` / ``ServingEngine``
(``models/kimi_linear.py``): gated delta-rule layers on a per-SLOT recurrent
state beside a NoPE latent pool, a leading dense layer, sigmoid top-k routing
with a selection bias on a held share — tiny widths, seeded weights, the
plain reference ``chipbench/reference_kimi_linear.py`` on logits."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import kimi_linear as K

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference_kimi_linear as ref  # noqa: E402
from chipbench import run as cb_run  # noqa: E402
from chipbench.families import kimi_linear as family  # noqa: E402

pytestmark = pytest.mark.limit(90)


def _config():
    data = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "kimi-linear-48b-a3b.json")))
    return cb_run._rehearsed(data, True)


@pytest.fixture(scope="module")
def tiny():
    """(config file's dict at the rehearsal's widths, ModelSpec, float32
    params with a selection bias large enough to move choices)."""
    config = _config()
    spec = family.build(config)
    params = spec.init_fn(jax.random.PRNGKey(0))
    moe = params["blocks"]["moe"]
    moe["gate_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(5),
                                               moe["gate_bias"].shape)
    return config, spec, params


def _serve(spec, params, **kw):
    kw = {"slots": 3, "max_seq_len": 128, "block_size": 16,
          "prefill_chunk": 16, **kw}
    return deepspeed_tpu.init_serving(spec, config={"dtype": "fp32"},
                                      params=params, **kw)


def _requests(sizes, new=6, draw=0, **kw):
    rng = np.random.default_rng(draw)
    return [Request(uid=i, prompt=rng.integers(0, 512, int(n)),
                    max_new_tokens=new, **kw) for i, n in enumerate(sizes)]


@pytest.fixture(scope="module")
def served(tiny):
    """Five requests through three slots (two slots are used twice)."""
    config, spec, params = tiny
    srv = _serve(spec, params)
    reqs = _requests([40, 7, 33, 20, 50])
    out = srv.serve(reqs)
    # (what the engine counted for THESE requests: later tests serve more)
    snapshot = (srv.stats(), list(srv.timeline.events()))
    yield srv, reqs, out, snapshot
    srv.close()


def test_parameters_are_stacked_by_kind_and_counted(tiny):
    config, spec, params = tiny
    cfg = spec.model_config
    blocks = params["blocks"]
    assert set(blocks) == {"kda", "latent", "dense", "moe"}
    assert blocks["kda"]["q_w"].shape[0] == 6
    assert blocks["latent"]["q_w"].shape[0] == 2
    assert blocks["dense"]["w1"].shape[0] == 1
    assert blocks["moe"]["gate_w"].shape[0] == 7
    assert blocks["moe"]["experts_w1"].shape[:2] == (7, 4)      # held
    assert blocks["moe"]["gate_w"].shape[-1] == 16              # published
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == cfg.num_params() == family.num_params(config)


def test_published_widths_count_as_the_issue_says():
    config = cb_run._rehearsed(json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "kimi-linear-48b-a3b.json"))), False)
    a = family.arch(config)
    assert family._kda_params(a) == 39_514_272          # 39.51 M
    assert family._latent_params(a) == 29_114_880       # 29.11 M
    assert 3 * a["d"] * a["dense_ffn"] == 63_700_992
    assert round(family.num_params(config) / 1e9, 2) == 2.09
    assert family.state_bytes_per_slot(config) \
        == 6 * (2 * 2 ** 20 + 72 * 1024)                # 12.4 MiB
    assert family.cached_bytes_per_token(config) == 2 * 576 * 2
    spec = family.build(config)
    assert spec.model_config.num_params() == family.num_params(config)
    assert spec.model_config.layer_kinds == ("kda",) * 3 + ("latent",)


def test_engine_logits_are_the_references(tiny):
    """Chunked prefill + decode through the engine's own cache kinds (the
    benchmark's comparison: two sequences one after the other through ONE
    slot) against the reference's full forward, logits."""
    from chipbench.drivers import serve_state

    config, spec, params = tiny
    srv = _serve(spec, params)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 512, (2, 64)).astype(np.int32)
    got, chosen = serve_state.state_choices(srv, tokens, 16)
    at = [15, 31, 47] + list(range(48, 64))
    want = np.asarray(ref.logits(config, params, tokens, at=at))
    assert got.shape == want.shape == (2, 19, 512)
    assert np.sqrt(np.mean((got - want) ** 2)) / np.std(want) < 2e-5
    # the reference makes the engine's choices (float32 on both sides)
    own, agreement = ref.logits(config, params, tokens, at=at, forced=chosen)
    assert agreement["experts"] == 1.0
    np.testing.assert_allclose(own, want, atol=1e-6)
    # a slot handed on without a reset is NOT the reference
    stale = np.asarray(ref.logits(config, params, tokens, at=at,
                                  variant="no_reset"))
    np.testing.assert_allclose(stale[0], want[0], atol=1e-6)
    assert np.sqrt(np.mean((stale[1] - want[1]) ** 2)) / np.std(want) > 1e-2
    srv.close()


def test_uncached_forward_is_the_reference(tiny):
    config, spec, params = tiny
    tokens = np.random.default_rng(2).integers(0, 512, (1, 24))
    got = K.forward(spec.model_config, params, jnp.asarray(tokens))
    want = ref.logits(config, params, tokens)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_served_tokens_are_greedy_of_the_reference(tiny, served):
    """Token-exact against the reference's teacher-forced argmax, for the
    requests that entered a fresh slot and for those that entered a USED
    one alike."""
    config, spec, params = tiny
    srv, reqs, out, _ = served
    for r in reqs:
        full = np.asarray(out[r.uid])
        want = np.asarray(jnp.argmax(ref.logits(
            config, params, full[None, :-1])[0, len(r.prompt) - 1:], -1))
        np.testing.assert_array_equal(full[len(r.prompt):], want)


def test_a_reused_slot_gives_what_a_fresh_engine_gives(tiny, served):
    config, spec, params = tiny
    srv, reqs, out, _ = served
    fresh = _serve(spec, params, slots=1)
    for r in reqs[3:]:                       # the ones that entered used slots
        again = fresh.serve([Request(uid="x", prompt=r.prompt,
                                     max_new_tokens=r.max_new_tokens)])
        np.testing.assert_array_equal(again["x"], out[r.uid])
    fresh.close()


def test_preemptions_recompute_is_token_exact(tiny, served):
    """A pool too small for both rows: the later one is preempted and
    re-prefilled from base 0 with its generated tokens folded in."""
    config, spec, params = tiny
    want = served[0].serve(_requests([30, 28], new=12, draw=3))
    tight = _serve(spec, params, slots=2, max_seq_len=64, num_blocks=5)
    got = tight.serve(_requests([30, 28], new=12, draw=3))
    assert tight.stats()["evicted"] >= 1
    assert tight.stats()["kv_state"]["resets"] >= 3
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])
    tight.close()


def test_lookahead_on_and_off_agree(tiny, served):
    """``debug_checks`` settles every call before the next is planned (no
    lookahead): the same tokens, sampled rows included."""
    config, spec, params = tiny
    kw = dict(temperature=0.7, top_p=0.9, seed=11)
    ahead = served[0]
    before = ahead.stats()["lookahead"]["ahead"]
    got = ahead.serve(_requests([21, 9, 35, 14], draw=4, **kw))
    assert ahead.stats()["lookahead"]["ahead"] > before
    plain = _serve(spec, params, debug_checks=True)
    want = plain.serve(_requests([21, 9, 35, 14], draw=4, **kw))
    assert plain.stats()["lookahead"]["ahead"] == 0
    plain.close()
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])


REFUSED = [
    ("prefix_caching", dict(prefix_caching=True), "snapshotted"),
    ("host_blocks", dict(host_blocks=8, prefix_caching=True), "tiers"),
    ("spec_tokens", dict(spec_tokens=2), "rollback is free"),
    ("quantize", dict(quantize="kv8"), "float32 by construction"),
    ("resident_window_blocks", dict(resident_window_blocks=4, host_blocks=8,
                                    prefix_caching=True), "window slides"),
    ("sp", dict(sp=2), "along the sequence"),
]


@pytest.mark.parametrize("name,kw,why", REFUSED, ids=[r[0] for r in REFUSED])
def test_each_refusal_raises_by_name_with_its_reason(tiny, name, kw, why):
    config, spec, params = tiny
    with pytest.raises(ValueError) as e:
        _serve(spec, params, **kw)
    assert "state_layers" in str(e.value) and name in str(e.value)
    assert why in str(e.value)


def test_a_draft_model_and_quantized_weights_are_refused(tiny):
    config, spec, params = tiny
    with pytest.raises(ValueError, match="a draft model"):
        _serve(spec, params, spec_tokens=2, draft=spec)
    with pytest.raises(ValueError, match="quantized weights"):
        deepspeed_tpu.init_serving(
            spec, config={"dtype": "fp32", "quant": {
                "enabled": True, "type": "int8"}}, params=params, slots=2,
            max_seq_len=64, block_size=16)


def test_a_tp_mesh_is_refused(tiny):
    config, spec, params = tiny
    with pytest.raises(ValueError, match=r"a tp mesh \(tp=2\)"):
        _serve(spec, params, topology=2)


def test_the_contiguous_cache_is_refused_by_name(tiny):
    config, spec, params = tiny
    with pytest.raises(NotImplementedError, match="state_rows"):
        spec.decode_hooks["init_cache"](2, 64, jnp.float32)
    with pytest.raises(NotImplementedError, match="recurrent state a row"):
        spec.decode_hooks["forward_cached"](
            params, jnp.zeros((1, 4), jnp.int32), {}, 0)


def test_stats_name_the_state_kind(tiny, served):
    srv, reqs, out, (st, events) = served
    state = st["kv_state"]
    assert state["kind"] == "state" and state["layers"] == 6
    assert state["slots"] == 3
    assert state["leaves"] == {"state": [6, 3, 4, 16, 16],
                               "conv": [6, 3, 1, 3, 192]}
    assert state["bytes"] == 6 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert state["bytes_per_slot"] * 3 == state["bytes"]
    assert state["resets"] == len(reqs)          # one a request entering
    assert state["kda"] == {"prefill": "kda_chunk_plain",
                            "decode": "kda_step_plain"}
    assert set(state["refused"]) >= {
        "prefix_caching", "host_blocks", "nvme_blocks", "spec_tokens",
        "a draft model", "quantize", "quantized weights",
        "a tp mesh", "engine_mode", "sp", "resident_window_blocks"}
    kinds = st["kv_kinds"]
    assert set(kinds) >= {"latent", "state"}
    assert kinds["state"] == {"layers": 6, "slots": 3,
                              "bytes": state["bytes"]}
    assert kinds["latent"]["layers"] == 2
    assert st["kv_latent"]["token_width"] == 24
    assert st["kv_latent"]["latent_attn"] == {"prefill": "latent_gather",
                                              "decode": "latent_gather"}
    assert st["compile_count"] == 1 + len(srv._rungs) == 3 \
        and st["prefix_cache_entries"] == 0
    # the spans carry the rows, resets and tokens of the state kind
    spans = [e for e in events if e["ph"] == "X"
             and e["name"] in ("prefill", "decode")]
    # ... and the tiles of the latent layers' walk (ISSUE 58) at the tile
    # the rule gives these shapes
    from deepspeed_tpu.ops import decode_attention as da

    lat = st["kv_latent"]
    tile = {t: da.latent_walk_shape(
        tiny[1].model_config.num_heads, t, srv.block_size, lat["pool_width"],
        4, srv.max_seq_len // srv.block_size)[1] for t in (1, 16, 64)}
    assert tile[1] == da._LATENT_TILE_MAX
    assert lat["tile_blocks"] == {
        "decode": tile[1],
        "prefill": {srv._rung_name(r): tile[r[1]] for r in srv._rungs}}
    for e in spans:
        a = e["args"]
        if e["name"] == "decode":
            assert a["slots"] <= a["kv_tiles"] <= a["kv_blocks"] \
                <= tile[1] * a["kv_tiles"]
            assert a["slots"] - 1 <= a["kv_first_tiles_ahead"] <= a["slots"]
        else:
            assert 0 < a["kv_tiles"] <= a["kv_blocks"] * -(-a["width"] // 16)
            assert a["kv_tiles"] - 1 == a["kv_first_tiles_ahead"]
    assert st["kv_latent"]["kv_tiles"] == sum(
        e["args"]["kv_tiles"] for e in spans)
    assert all({"state_rows", "state_resets", "state_tokens"}
               <= set(e["args"]) for e in spans)
    assert sum(e["args"]["state_resets"] for e in spans) == len(reqs)
    assert sum(e["args"]["state_tokens"] for e in spans
               if e["name"] == "prefill") \
        == sum(len(r.prompt) for r in reqs)


def test_eight_shares_sum_to_the_uncut_layer(tiny):
    """The expert layer told which experts it holds returns their partial
    sum plus the shared expert: eight shares, the shared expert counted
    once, are the layer that holds all sixteen — in the program and in the
    reference."""
    from deepspeed_tpu.models import mixtral as M

    config, spec, params = tiny
    cfg = spec.model_config
    whole_cfg = dataclasses.replace(cfg, experts_held=None)
    whole = K.init_params(whole_cfg, jax.random.PRNGKey(3))["blocks"]["moe"]
    whole["gate_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(5),
                                                 whole["gate_bias"].shape)
    layer = jax.tree_util.tree_map(lambda a: a[2], whole)
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.hidden_size))
    full, _ = M._routed(whole_cfg, layer, y)
    shared = M._shared(whole_cfg, layer, y)
    want = ref._experts({**config, "experts_first": 0}, y.reshape(18, -1),
                        layer).reshape(y.shape)
    np.testing.assert_allclose(full, want, atol=2e-6)
    total = 0
    for first in range(0, 16, 2):
        held = dataclasses.replace(cfg, experts_held=(first, 2))
        share = {k: v[first:first + 2] if k.startswith("experts_") else v
                 for k, v in layer.items()}
        part, record = M._routed(held, share, y)
        in_ref = ref._experts({**config, "experts_first": first},
                              y.reshape(18, -1), share).reshape(y.shape)
        np.testing.assert_allclose(part, in_ref, atol=2e-6)
        total = total + part - shared
    np.testing.assert_allclose(total + shared, full, atol=5e-6)
    # the bias chose: without it other experts are taken
    flat = {**layer, "gate_bias": jnp.zeros_like(layer["gate_bias"])}
    unbiased, _ = M._routed(whole_cfg, flat, y)
    assert float(jnp.abs(unbiased - full).max()) > 1e-4
