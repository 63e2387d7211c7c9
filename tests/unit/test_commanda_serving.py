"""Command A+'s block served through the normal path, at tiny widths in
float32 on the CPU, against the plain reference
(``chipbench/reference_commanda.py``): the parallel block under Cohere's
LayerNorm, three sliding-window layers (interleaved rotary) to one full
layer (no rotation) on a pool with leaves and a block table per layer kind,
sigmoid-scored experts beside averaged shared experts, an expert layer that
holds a share of its experts, a tied head."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from chipbench import reference_commanda as ref
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.moe import routed
from deepspeed_tpu.ops import paged_kv
from tiny import assert_greedy

WINDOW, BLOCK, CHUNK = 24, 8, 16
HELD = (4, 4)


def _cfg(**over):
    return mixtral.MixtralConfig(**{**dict(
        vocab_size=128, max_seq_len=256, num_layers=4, num_heads=8,
        num_kv_heads=2, head_width=16, hidden_size=32, ffn_size=16,
        rope_theta=50000.0, rms_eps=1e-5, norm="layernorm",
        parallel_block=True, rope_interleaved=True,
        layer_kinds=("sliding", "sliding", "sliding", "full"),
        sliding_window=WINDOW, tie_embeddings=True, num_experts=16, top_k=4,
        router_score="sigmoid", shared_experts=2, experts_held=HELD,
        remat=False), **over})


def _config(cfg):
    """The reference's view of ``cfg`` (a configuration file's keys)."""
    return dict(
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        layer_norm_eps=cfg.rms_eps, rope_theta=cfg.rope_theta,
        sliding_window=cfg.sliding_window, num_experts_per_tok=cfg.top_k,
        experts_first=cfg.experts_held[0] if cfg.experts_held else 0,
        num_shared_experts=cfg.shared_experts,
        layer_types=[k + "_attention" for k in cfg.layer_kinds]
        * (cfg.num_layers // len(cfg.layer_kinds)))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    spec = mixtral.build(cfg)
    # N(0, 0.02) at width 32 leaves the residual stream the token's own
    # embedding: scaled up, every part of the block moves the logits
    params = jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim > 1 else a,
        spec.init_fn(jax.random.PRNGKey(0)))
    return cfg, spec, params


def _exact(cfg, params, reqs, out):
    """Every served token is the reference's greedy one (``tiny.py``: one
    teacher-forced call over prompt + output, not a roll-out)."""
    assert_greedy(lambda ids: ref.logits(_config(cfg), params, ids), reqs,
                  out)


def test_uncached_forward_equals_the_reference(model):
    cfg, spec, params = model
    toks = np.random.default_rng(0).integers(0, 128, (2, 70)).astype(np.int32)
    want = np.asarray(ref.logits(_config(cfg), params, toks))
    got = np.asarray(spec.apply_fn(params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_paged_prefill_and_decode_equal_the_reference_across_a_wrapped_ring(
        model):
    """Chunked prefill then decode steps through the hooks, a table per
    layer kind: 70 positions through a ring of 6 blocks x 8 (the window
    kind's; it wraps at 48) equal the reference's full forward."""
    cfg, spec, params = model
    toks = np.random.default_rng(0).integers(0, 128, (2, 70)).astype(np.int32)
    want = np.asarray(ref.logits(_config(cfg), params, toks))
    hooks = spec.decode_hooks
    b, s = toks.shape
    nbper = -(-s // BLOCK)
    ring = -(-(WINDOW + CHUNK) // BLOCK) + 1
    cache = paged_kv.pack_pool(hooks["init_cache"](
        1 + b * nbper, BLOCK, jnp.float32, window_blocks=1 + b * ring))
    assert cache["k"].shape[:2] == (1, 1 + b * nbper)
    assert cache["kw"].shape[:2] == (3, 1 + b * ring)
    bt = {"full": jnp.asarray(1 + np.arange(b * nbper).reshape(b, nbper),
                              jnp.int32),
          "window": jnp.asarray(1 + np.arange(b * ring).reshape(b, ring),
                                jnp.int32)}
    # (jitted: a program a shape, not a compile an op)
    fwd, got, at = jax.jit(hooks["forward_cached"]), [], []
    for base in range(0, 48, CHUNK):
        lg, cache = fwd(params, jnp.asarray(toks[:, base:base + CHUNK]),
                        cache, jnp.full((b,), base, jnp.int32),
                        lengths=jnp.full((b,), CHUNK, jnp.int32),
                        block_tables=bt)
        got.append(np.asarray(lg))
        at.append(base + CHUNK - 1)
    for p in range(48, s):
        lg, cache = fwd(params, jnp.asarray(toks[:, p:p + 1]), cache, 0,
                        lengths=jnp.full((b,), p, jnp.int32),
                        block_tables=bt)
        got.append(np.asarray(lg))
        at.append(p)
    np.testing.assert_allclose(np.stack(got, 1), want[:, at], atol=2e-4)


def _serve(spec, params, lengths, new=12, **how):
    srv = deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, params=params, slots=3,
        max_seq_len=128, block_size=BLOCK, prefill_chunk=CHUNK,
        debug_checks=True, **how)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, 128, n).astype(np.int32), new)
            for i, n in enumerate(lengths)]
    return srv, reqs, srv.serve(reqs)


def test_engine_serves_it_token_exact_and_the_ring_releases_blocks(model):
    """Four requests over three slots through ``ServingEngine``: greedy
    tokens equal the reference's, the window kind's ring wrapped and
    released blocks behind the rows, and ``stats()`` names both pools."""
    cfg, spec, params = model
    srv, reqs, out = _serve(spec, params, [70, 33, 50, 9])
    _exact(cfg, params, reqs, out)
    st = srv.stats()
    kinds = st["kv_kinds"]
    assert kinds["full"]["layers"] == 1 and kinds["sliding"]["layers"] == 3
    # a ring holds the window and the widest row of a prefill call
    ring = -(-(WINDOW + 4 * CHUNK) // BLOCK) + 1
    assert kinds["sliding"]["table_width"] == ring == 12
    assert kinds["sliding"]["num_blocks"] == 1 + 3 * ring
    assert kinds["sliding"]["released"] > 0          # the ring wrapped
    assert kinds["sliding"]["blocks_in_use"] == 0
    assert kinds["full"]["blocks_in_use"] == 0
    assert kinds["sliding"]["peak_blocks_in_use"] <= 3 * ring
    assert 0 < kinds["kv_visible"] < kinds["kv_valid"]
    assert kinds["expert_rows_absent"] > 0
    assert st["compile_count"] == 1 + len(srv._rungs) == 3 \
        and st["prefix_cache_entries"] == 0
    assert any("prefix_caching" in what for what in kinds["refused"])
    # the spans carry what the readers read
    spans = [e["args"] for e in srv.timeline.events()
             if e["ph"] == "X" and e["name"] == "decode"]
    assert all({"kv_valid", "kv_visible", "expert_rows_absent",
                "experts_touched"} <= set(a) for a in spans)
    steps = [e["args"] for e in srv.timeline.events()
             if e["ph"] == "X" and e["name"] == "step"]
    assert sum(a["window_blocks_released"] for a in steps) \
        == kinds["sliding"]["released"]


def test_preempted_row_past_its_window_is_readmitted_token_exact(model):
    """A full-kind pool too small for three long rows: a row is preempted
    past its window, its ring freed, and re-admitted (its prompt and what
    it generated re-prefilled from position 0); every token still equals
    the reference's."""
    cfg, spec, params = model
    srv, reqs, out = _serve(spec, params, [60, 58, 62], new=30,
                            num_blocks=1 + 28)
    assert srv.stats()["evicted"] > 0
    _exact(cfg, params, reqs, out)
    assert srv.stats()["kv_kinds"]["sliding"]["blocks_in_use"] == 0


def test_the_eight_shares_sum_to_the_uncut_layer(model):
    """The share test: the partial routed sums of every share of the
    experts (four shares of 4 of 16 here), plus the shared experts' average
    counted once, equal the uncut reference's whole expert layer — and each
    share equals the reference given the same share."""
    cfg, _, params = model
    whole = mixtral.build(dataclasses.replace(cfg, experts_held=None))
    layer = jax.tree_util.tree_map(
        lambda a: a[1] * 8 if a.ndim > 2 else a[1],
        whole.init_fn(jax.random.PRNGKey(3))["blocks"])
    y = jnp.asarray(np.random.default_rng(2).standard_normal((37, 32)),
                    jnp.float32)
    uncut = ref._experts(y, layer, cfg.top_k, 0) \
        + ref._shared(y, layer, cfg.shared_experts, True)
    total = mixtral._shared(cfg, layer, y)
    for first in range(0, cfg.num_experts, 4):
        mine = {k: layer[k][first:first + 4]
                for k in ("experts_w1", "experts_w3", "experts_w2")}
        part, record = routed.routed_ffn(
            y, layer["gate_w"], mine["experts_w1"], mine["experts_w3"],
            mine["experts_w2"], cfg.top_k, True, held=(first, 4),
            score="sigmoid")
        np.testing.assert_allclose(
            part, ref._experts(y, {**layer, **mine}, cfg.top_k, first),
            atol=1e-5)
        assert int(record[1]) + int(record[3]) == 37 * cfg.top_k
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=1e-5)


def test_held_none_and_softmax_are_the_program_they_were():
    """``held=None`` / ``score="softmax"`` trace the jaxpr they traced
    before the options existed (no new operation on the old path)."""
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((4, 16, 8)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((4, 8, 16)), jnp.float32)

    def old(y):
        return routed.routed_ffn(y, gate, w1, w1, w2, 2, True)

    def new(y):
        return routed.routed_ffn(y, gate, w1, w1, w2, 2, True, held=None,
                                 score="softmax")

    assert str(jax.make_jaxpr(old)(y)) == str(jax.make_jaxpr(new)(y))
    out, record = old(y)
    assert record.shape == (len(routed.RECORD),)


@pytest.mark.parametrize("how,named", [
    (dict(prefix_caching=True), "prefix_caching=True"),
    (dict(host_blocks=8, swap_batch=2), "host_blocks=8"),
    (dict(quantize="kv8"), "kv8"),
    (dict(spec_tokens=3), "spec_tokens=3"),
])
def test_what_window_layers_are_not_served_with_is_refused_by_name(
        model, how, named):
    _, spec, params = model
    with pytest.raises(ValueError, match="window_layers") as e:
        deepspeed_tpu.init_serving(
            spec, config={"dtype": "fp32"}, params=params, slots=2,
            max_seq_len=64, block_size=BLOCK, prefill_chunk=CHUNK, **how)
    assert named in str(e.value)


def test_generate_refuses_window_layers_by_name(model):
    _, spec, params = model
    engine = deepspeed_tpu.init_inference(spec, config={"dtype": "fp32"},
                                          params=params)
    with pytest.raises(NotImplementedError, match="block-paged pool"):
        engine.generate(jnp.zeros((1, 4), jnp.int32), max_new_tokens=2)


def test_training_refuses_a_held_share_by_name(model):
    _, spec, params = model
    with pytest.raises(NotImplementedError, match="experts_held"):
        spec.loss_fn(params, {"input_ids": jnp.zeros((1, 8), jnp.int32)})


def test_the_published_model_and_this_chips_share_count_their_parameters():
    """``command_a_plus()`` states the published model; a share's
    ``num_params`` is what ``init_params`` builds and what the benchmark's
    family counts."""
    import json
    import os

    from chipbench.families import commanda

    pub = mixtral.MixtralConfig.command_a_plus()
    assert (pub.num_layers, pub.num_experts, pub.vocab_size) \
        == (32, 128, 262144)
    assert pub.layer_kinds == ("sliding",) * 3 + ("full",)
    assert 217e9 < pub.num_params() < 219e9          # "218B-A25B"
    assert 24e9 < pub.active_params() < 26e9
    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    with open(os.path.join(root, "chipbench", "configs",
                           "command-a-plus-05-2026.json")) as f:
        config = json.load(f)
    share = dataclasses.replace(pub, num_layers=4, vocab_size=32768,
                                experts_held=(0, 16))
    assert share.num_params() == commanda.num_params(config) == 4733292544
    tiny = _cfg()
    built = jax.eval_shape(lambda: mixtral.init_params(
        tiny, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(built)) == tiny.num_params()
