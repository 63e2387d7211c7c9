"""Granite 4.0-H through ``init_serving`` / ``ServingEngine``
(``models/granite_hybrid.py``): state-space layers on a per-SLOT recurrent
state beside NoPE grouped-query layers on the paged pool's ``full`` kind,
scaled residuals, a tied head — tiny widths, seeded weights, the plain
reference ``chipbench/reference_granite_hybrid.py`` on logits."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import granite_hybrid as G

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference_granite_hybrid as ref  # noqa: E402
from chipbench import run as cb_run  # noqa: E402
from chipbench.families import granite_hybrid as family  # noqa: E402

pytestmark = pytest.mark.limit(90)


def _config(rehearse=True):
    return cb_run._rehearsed(json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "granite-4.0-h-micro.json"))), rehearse)


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


def test_parameters_are_stacked_by_kind_and_counted(tiny):
    config, spec, params = tiny
    cfg, blocks = spec.model_config, params["blocks"]
    assert set(blocks) == {"ssm", "full"} and "lm_head" not in params
    assert blocks["ssm"]["in_w"].shape == (9, 64, 128 + 160 + 8)
    assert blocks["full"]["q_w"].shape[0] == 1
    assert all("ffn_in_w" in blocks[k] for k in blocks)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == cfg.num_params() == family.num_params(config)


def test_published_widths_count_as_the_issue_says():
    config = _config(False)
    assert config["reduced"] == []
    assert family.num_params(config) == 3_191_396_096            # 3.19 G
    assert family.state_bytes_per_slot(config) \
        == 36 * (2 * 2 ** 20 + 3 * 4352 * 2)                     # 76.4 MB
    assert family.cached_bytes_per_token(config) == 8192
    spec = family.build(config)
    cfg = spec.model_config
    assert cfg == G.GraniteHybridConfig.granite_4_0_h_micro()
    assert cfg.num_params() == family.num_params(config)
    assert cfg.layer_kinds == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert spec.decode_hooks["state_layers"] == {
        "layers": 36, "heads": 64, "key_dim": 64, "value_dim": 128,
        "conv_taps": 3, "channels": 4352, "bodies": "ssd"}
    assert "latent_attention" not in spec.decode_hooks
    cache = jax.eval_shape(lambda: spec.decode_hooks["init_cache"](
        9, 32, jnp.bfloat16, state_rows=64))
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "k": ((4, 9, 8, 32, 64), "bfloat16"),
        "v": ((4, 9, 8, 32, 64), "bfloat16"),
        "state": ((36, 64, 32, 128, 128), "float32"),
        "conv": ((36, 64, 1, 3, 4352), "bfloat16")}


def test_engine_logits_are_the_references(tiny):
    """Chunked prefill + decode through the engine's own cache (the
    benchmark's comparison: two sequences one after the other through ONE
    slot and the same blocks, the first's prompt through the ``[4, 16]``
    rung, the second's through the wide row a lone prompt takes — a first
    call of 16 tokens and 48 pads, then 32 more —, the decode steps at
    every slot's row) against the reference's full forward,
    logits; the cache goes back to the engine."""
    from chipbench.drivers import serve_ssm

    config, spec, params = tiny
    srv = _serve(spec, params)
    tokens = np.random.default_rng(1).integers(0, 512, (2, 64)) \
        .astype(np.int32)
    leaves = {k: v.shape for k, v in srv._cache.items()}
    got, at, programs = serve_ssm.state_logits(srv, tokens, 16, slot=2)
    assert {k: v.shape for k, v in srv._cache.items()} == leaves
    decode = list(range(48, 64))
    assert at == [[15, 31, 47] + decode, [15, 47] + decode]
    assert {k: (v["family"], v["rung"], v["bodies"], v["kernels"])
            for k, v in programs.items()} == {
        "prefill[4x16]": ("prefill", (4, 16), "ssd_chunk_plain", []),
        "prefill[1x64]": ("prefill", (1, 64), "ssd_chunk_plain", []),
        "decode": ("decode", None, "ssd_step_plain", [])}
    want = np.asarray(ref.logits(config, params, tokens, at=at[0]))
    # a slot handed on without a reset is NOT the reference
    stale = np.asarray(ref.logits(config, params, tokens, at=at[0],
                                  variant="no_reset"))
    np.testing.assert_allclose(stale[0], want[0], atol=1e-6)
    for row, keep in enumerate(([0, 1, 2], [0, 2])):
        keep = keep + list(range(3, 19))
        assert got[row].shape == (len(keep), 512)
        assert np.sqrt(np.mean((got[row] - want[row, keep]) ** 2)) \
            / np.std(want) < 2e-5
    assert np.sqrt(np.mean((stale[1] - want[1]) ** 2)) / np.std(want) > 1e-2
    # the float32 pass (here the same dtype) keeps to the narrow rung, on a
    # cache of its own
    _, at, programs = serve_ssm.state_logits(srv, tokens[:, :40], 8,
                                             exact=True)
    assert at == [[15, 31] + list(range(32, 40))] * 2
    assert set(programs) == {"prefill[4x16]", "decode"}
    srv.close()


def test_uncached_forward_is_the_reference_and_training_is_refused(tiny):
    config, spec, params = tiny
    tokens = np.random.default_rng(2).integers(0, 512, (1, 24))
    got = G.forward(spec.model_config, params, jnp.asarray(tokens))
    np.testing.assert_allclose(got, ref.logits(config, params, tokens),
                               atol=2e-5)
    np.testing.assert_allclose(
        spec.loss_fn(params, jnp.asarray(tokens), train=False),
        ref.next_token_loss(config, params, tokens), rtol=1e-5)
    with pytest.raises(NotImplementedError, match="scan's backward"):
        spec.loss_fn(params, jnp.asarray(tokens))


def test_served_tokens_are_greedy_of_the_reference(tiny, served):
    """Token-exact against the reference's teacher-forced argmax, for the
    requests that entered a fresh slot and for those that entered a USED one
    alike (no argmax of the compared positions is a near-tie)."""
    config, spec, params = tiny
    srv, reqs, out, _ = served
    for r in reqs:
        full = np.asarray(out[r.uid])
        logits = np.asarray(ref.logits(
            config, params, full[None, :-1])[0, len(r.prompt) - 1:])
        top = np.sort(logits, axis=-1)
        assert (top[:, -1] - top[:, -2]).min() > 1e-4
        np.testing.assert_array_equal(full[len(r.prompt):],
                                      logits.argmax(-1))


def test_lookahead_on_and_off_and_a_tight_pool_agree(tiny, served):
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
    assert st["evicted"] >= 1 and st["kv_state"]["resets"] >= 3
    assert st["lookahead"]["ahead"] == 0
    tight.close()
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])


#: ``options.KIND_REFUSES["state"]``'s eleven refusals, as they stand for a model whose
#: state lies beside the ``full`` kind: (refusal, options, a word of its why)
REFUSED = [
    ("prefix_caching", dict(prefix_caching=True), "snapshotted"),
    ("host_blocks", dict(host_blocks=8, prefix_caching=True), "tiers"),
    ("nvme_blocks", dict(nvme_blocks=8, host_blocks=8, prefix_caching=True),
     "tiers"),
    ("spec_tokens", dict(spec_tokens=2), "rollback is free"),
    ("a draft model", dict(spec_tokens=2, draft="self"), "already moved"),
    ("quantize", dict(quantize="kv8"), "float32 by construction"),
    ("quantized weights", dict(quant="int8"), "the state kind's leaves"),
    ("resident_window_blocks", dict(resident_window_blocks=4, host_blocks=8,
                                    prefix_caching=True), "window slides"),
    ("a tp mesh", dict(topology=2), "heads are not sharded"),
    ("engine_mode", dict(engine_mode="dp_tp", topology=1), "rows are not"),
    ("sp", dict(sp=2), "along the sequence"),
]


@pytest.mark.parametrize("name,kw,why", REFUSED, ids=[r[0] for r in REFUSED])
def test_each_refusal_raises_by_name_with_its_reason(tiny, name, kw, why):
    config, spec, params = tiny
    kw = dict(kw)
    if kw.get("draft") == "self":
        kw["draft"] = spec
    config_kw = {"dtype": "fp32"}
    if kw.pop("quant", None):
        config_kw["quant"] = {"enabled": True, "type": "int8"}
    with pytest.raises(ValueError) as e:
        deepspeed_tpu.init_serving(
            spec, config=config_kw, params=params, **{
                "slots": 2, "max_seq_len": 64, "block_size": 16,
                "prefill_chunk": 16, **kw})
    message = str(e.value)
    assert "state_layers" in message and name in message and why in message
    assert "delta" not in message and "Kimi" not in message


def test_the_contiguous_cache_is_refused_by_name(tiny):
    config, spec, params = tiny
    with pytest.raises(NotImplementedError, match="state_rows"):
        spec.decode_hooks["init_cache"](2, 64, jnp.float32)
    with pytest.raises(NotImplementedError, match="recurrent state a row"):
        spec.decode_hooks["forward_cached"](
            params, jnp.zeros((1, 4), jnp.int32), {}, 0)


def test_stats_name_the_state_kind_beside_the_full_kind(tiny, served):
    srv, reqs, out, (st, events) = served
    state = st["kv_state"]
    assert state["kind"] == "state" and state["layers"] == 9
    assert state["slots"] == 3
    # eight heads of 16 x 16 packed on one 128-lane row
    assert state["leaves"] == {"state": [9, 3, 1, 16, 128],
                               "conv": [9, 3, 1, 3, 160]}
    assert state["bytes"] == 9 * 3 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert state["bytes_per_slot"] * 3 == state["bytes"]
    assert state["resets"] == len(reqs)          # one a request entering
    assert state["ssd"] == {"prefill": "ssd_chunk_plain",
                            "decode": "ssd_step_plain"}
    assert "kda" not in state
    assert len(state["refused"]) == 11
    kinds = st["kv_kinds"]
    assert kinds["state"] == {"layers": 9, "slots": 3,
                              "bytes": state["bytes"]}
    assert kinds["full"]["layers"] == 1 and "latent" not in kinds
    assert st["kv_latent"] is None
    assert st["compile_count"] == 1 + len(srv._rungs) == 3 \
        and st["prefix_cache_entries"] == 0
    assert set(st["prefill_shapes"]) == {"4x16", "1x64"}
    spans = [e for e in events if e["ph"] == "X"
             and e["name"] in ("prefill", "decode")]
    assert all({"state_rows", "state_resets", "state_tokens"}
               <= set(e["args"]) for e in spans)
    assert sum(e["args"]["state_resets"] for e in spans) == len(reqs)
    assert sum(e["args"]["state_tokens"] for e in spans
               if e["name"] == "prefill") \
        == sum(len(r.prompt) for r in reqs)
