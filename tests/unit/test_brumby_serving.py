"""Brumby through ``init_serving`` / ``ServingEngine`` (``models/brumby.py``):
power-retention layers on a per-SLOT recurrent state and NO paged pool at
all — tiny widths, seeded weights, the plain reference
``chipbench/reference_brumby.py`` (the attention form) on logits."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import brumby as M

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference_brumby as ref  # noqa: E402
from chipbench import run as cb_run  # noqa: E402
from chipbench.families import brumby as family  # noqa: E402

pytestmark = pytest.mark.limit(90)


def _config(rehearse=True):
    return cb_run._rehearsed(json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "Brumby-14B-Base.json"))), rehearse)


@pytest.fixture(scope="module")
def tiny():
    """(config file's dict at the rehearsal's widths, ModelSpec, float32
    params)."""
    config = _config()
    spec = family.build(config)
    return config, spec, spec.init_fn(jax.random.PRNGKey(0))


def _rel(got, want):
    """Relative RMSE (a sequence's first tokens divide by a sum of one or
    two weights: their quotients, not the code, are ill-conditioned, and
    the largest difference is theirs)."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def _serve(spec, params, **kw):
    kw = {"slots": 3, "max_seq_len": 128, "prefill_chunk": 16, **kw}
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


def test_published_widths_count_as_the_issue_says(tiny):
    config = _config(False)
    assert config["reduced"] == ["depth"] and config["depth"] == 10
    assert family.num_params(config) == 4_859_358_800            # 4.86 G
    assert family.state_bytes_per_slot(config) \
        == 10 * 8 * (8256 * 128 + 8256) * 4                      # 340.8 MB
    assert family.cached_bytes_per_token(config) == 0
    spec = family.build(config)
    cfg = spec.model_config
    assert cfg == M.BrumbyConfig(num_layers=10)
    assert cfg.num_params() == family.num_params(config)
    assert M.BrumbyConfig.brumby_14b_base().num_layers == 40
    assert spec.decode_hooks["state_layers"] == {
        "layers": 10, "heads": 8, "key_dim": 8256, "value_dim": 128,
        "bodies": "power"}
    cache = jax.eval_shape(lambda: spec.decode_hooks["init_cache"](
        9, 32, jnp.bfloat16, state_rows=12))
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "state": ((10, 12, 8, 65, 128, 128), "float32"),
        "z": ((10, 12, 8, 65, 128), "float32")}
    # the rehearsal's: llama's leaves plus the gate's, counted
    config, spec, params = tiny
    assert params["blocks"]["gate_w"].shape == (2, 64, 2)
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == spec.model_config.num_params() == family.num_params(config)


def test_engine_logits_are_the_references(tiny):
    """Chunked prefill + decode through the engine's own cache (the
    benchmark's comparison: two sequences one after the other through ONE
    slot, the first's prompt through the ``[4, 16]`` rung, the second's
    through the wide row, the decode steps at every slot's row) against the
    reference's attention form, logits; the cache goes back to the engine,
    and a slot handed on without a reset is NOT the reference."""
    from chipbench.drivers import serve_power

    config, spec, params = tiny
    srv = _serve(spec, params)
    tokens = np.random.default_rng(1).integers(0, 512, (2, 64)) \
        .astype(np.int32)
    leaves = {k: v.shape for k, v in srv._cache.items()}
    got, at, programs = serve_power.state_logits(srv, tokens, 16, slot=2)
    assert {k: v.shape for k, v in srv._cache.items()} == leaves
    decode = list(range(48, 64))
    assert at == [[15, 31, 47] + decode, [15, 47] + decode]
    assert {k: (v["family"], v["rung"], v["bodies"], v["kernels"])
            for k, v in programs.items()} == {
        "prefill[4x16]": ("prefill", (4, 16), "power_chunk_plain", []),
        "prefill[1x64]": ("prefill", (1, 64), "power_chunk_plain", []),
        "decode": ("decode", None, "power_step_plain", [])}
    want = np.asarray(ref.logits(config, params, tokens, at=at[0]))
    stale = np.asarray(ref.logits(config, params, tokens, at=at[0],
                                  variant="no_reset"))
    assert _rel(stale[0], want[0]) < 2e-4      # the per-token body
    for row, keep in enumerate(([0, 1, 2], [0, 2])):
        keep = keep + list(range(3, 19))
        assert got[row].shape == (len(keep), 512)
        assert _rel(got[row], want[row, keep]) < 1e-4
    assert _rel(stale[1], want[1]) > 1e-2
    # the float32 pass runs on the same cache and on both rungs
    exact, at2, programs2 = serve_power.state_logits(srv, tokens, 16,
                                                     exact=True, slot=2)
    assert at2 == at and set(programs2) == set(programs)
    assert _rel(exact[1], got[1]) < 1e-4
    srv.close()


def test_uncached_forward_is_the_reference_and_training_is_refused(tiny):
    config, spec, params = tiny
    tokens = np.random.default_rng(2).integers(0, 512, (1, 24))
    got = M.forward(spec.model_config, params, jnp.asarray(tokens))
    assert _rel(got, ref.logits(config, params, tokens)) < 1e-4
    np.testing.assert_allclose(
        spec.loss_fn(params, jnp.asarray(tokens), train=False),
        ref.next_token_loss(config, params, tokens), rtol=1e-5)
    with pytest.raises(NotImplementedError, match="retention's backward"):
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


def test_an_engine_with_no_paged_leaf_admits_by_slot(tiny, served):
    """No pool is committed and no block is ever asked for: two rows of 100
    tokens each run side by side where ``num_blocks`` says 0, sampled rows
    agree with lookahead on and off, and the pool's numbers are zeros, none
    of them divided by."""
    from deepspeed_tpu.inference.paged import NoBlocks

    config, spec, params = tiny
    srv, reqs, out, (st, events) = served
    assert isinstance(srv._alloc, NoBlocks) and not srv._paged
    assert set(srv._cache) == {"state", "z"}
    assert srv._tables.shape == (3, 0) and srv._pool_shape == ()
    assert not hasattr(srv._alloc, "alloc")     # no block to ask for
    assert (st["num_blocks"], st["blocks_in_use"], st["free_blocks"]) \
        == (0, 0, 0)
    assert st["kv_pool_bytes"] == 0 and st["kv_pool_shape"] == []
    assert st["kv_pool_bytes_per_chip"] == 0 and st["evicted"] == 0
    assert st["admitted"] == len(reqs) and st["decode_attn"] is None
    assert srv.resolved_config()["num_blocks"] == 0

    def long_reqs():
        r = np.random.default_rng(4)
        return [Request(uid=i, prompt=r.integers(0, 512, 100),
                        max_new_tokens=12, temperature=0.7, top_p=0.9,
                        seed=11 + i) for i in range(2)]

    want = srv.serve(long_reqs())
    checked = _serve(spec, params, slots=2, num_blocks=0, debug_checks=True)
    got = checked.serve(long_reqs())
    assert checked.stats()["lookahead"]["ahead"] == 0
    checked.close()
    for uid in want:
        np.testing.assert_array_equal(got[uid], want[uid])
    with pytest.raises(ValueError, match="max_seq_len|exceeds"):
        srv.submit(Request(uid="long", prompt=np.zeros(128, np.int32),
                           max_new_tokens=8))


#: ``options.KIND_REFUSES["state"]``'s eleven refusals, as they stand for a model with no
#: paged pool at all: (refusal, options, a word of its why)
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
                "slots": 2, "max_seq_len": 64, "prefill_chunk": 16, **kw})
    message = str(e.value)
    assert "state_layers" in message and name in message and why in message
    assert "paged pool" not in message and "Brumby" not in message


def test_the_contiguous_cache_is_refused_by_name(tiny):
    config, spec, params = tiny
    with pytest.raises(NotImplementedError, match="state_rows"):
        spec.decode_hooks["init_cache"](2, 64, jnp.float32)
    with pytest.raises(NotImplementedError, match="recurrent state a row"):
        spec.decode_hooks["forward_cached"](
            params, jnp.zeros((1, 4), jnp.int32), {}, 0)


def test_stats_name_the_state_kind_and_no_other(tiny, served):
    srv, reqs, out, (st, events) = served
    state = st["kv_state"]
    assert state["kind"] == "state" and state["layers"] == 2
    assert state["slots"] == 3
    # 16-wide heads: 9 cyclic distances x 16 value channels x 16 lanes
    assert state["leaves"] == {"state": [2, 3, 2, 9, 16, 16],
                               "z": [2, 3, 2, 9, 16]}
    assert state["bytes"] == 2 * 3 * 2 * 9 * (16 * 16 + 16) * 4
    assert state["bytes_per_slot"] * 3 == state["bytes"]
    assert state["resets"] == len(reqs)          # one a request entering
    assert state["power"] == {"prefill": "power_chunk_plain",
                              "decode": "power_step_plain"}
    assert len(state["refused"]) == 11
    assert st["kv_kinds"] == {
        "state": {"layers": 2, "slots": 3, "bytes": state["bytes"]},
        "expert_rows_absent": 0, "refused": state["refused"]}
    assert st["kv_latent"] is None
    assert st["compile_count"] == 1 + len(srv._rungs) == 3 \
        and st["prefix_cache_entries"] == 0
    assert set(st["prefill_shapes"]) == {"4x16", "1x64"}
    spans = [e for e in events if e["ph"] == "X"
             and e["name"] in ("prefill", "decode")]
    assert all({"state_rows", "state_resets", "state_tokens"}
               <= set(e["args"]) for e in spans)
    assert not any("kv_tiles" in e["args"] for e in spans)
    assert all(e["args"]["kv_blocks"] == 0 for e in spans
               if e["name"] == "prefill")
    assert sum(e["args"]["state_resets"] for e in spans) == len(reqs)
    assert sum(e["args"]["state_tokens"] for e in spans
               if e["name"] == "prefill") \
        == sum(len(r.prompt) for r in reqs)
    # the start-up ring's ``pool`` span carries the state's bytes: there is
    # no pool to commit
    from deepspeed_tpu.telemetry import trace as trace_mod

    pools = [e for e in trace_mod.setup_timeline().events()
             if e["ph"] == "X" and e["name"] == "pool"
             and e["args"].get("kinds", {}).keys() == {"state", "z"}]
    assert pools and pools[-1]["args"]["blocks"] == 0
    assert sum(pools[-1]["args"]["kinds"].values()) \
        == pools[-1]["args"]["bytes"]
