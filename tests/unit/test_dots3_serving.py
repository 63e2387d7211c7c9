"""dots3-note-prev's block served through the normal path, at tiny widths in
float32 on the CPU, against the plain reference
(``chipbench/reference_dots3.py``, the EXPANDED form): latent attention of
two kinds — under a learned selection on the full kind's table, at sizes of
their own under a sliding window on the window kind's ring — the head gate,
the two rescales, a leading dense FFN, sigmoid-scored experts with a
selection bias beside one shared expert, an expert layer that holds a share
of its experts, an untied head.  The tiny ``index_topk`` (24) and window (17)
put positions on both sides of both inside one 16-token chunk, and the ring
wraps within 70 tokens."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from chipbench import reference_dots3 as ref
from chipbench.drivers import serve_sparselatent as driver
from chipbench.families import dots3 as family
from deepspeed_tpu.inference import options
from deepspeed_tpu.inference.serving import Request
from tiny import assert_greedy

BLOCK, CHUNK, TOPK, WINDOW = 8, 16, 24, 17
#: the published keys at tiny widths; six layers: the leading full layer
#: (dense FFN), one whole period, a partial closing period (one full layer)
CONFIG = {
    "family": "dots3", "dtype": "fp32",
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64,
    "index_head_dim": 16, "index_n_heads": 4, "index_topk": TOPK,
    "intermediate_size": 96, "kv_lora_rank": 16,
    "layer_types": ["full_attention", "full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "depth": 6, "max_position_embeddings": 256, "model_type": "dots3_note",
    "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "experts_first": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 6, "num_key_value_heads": 4, "q_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": WINDOW, "swa_kv_lora_rank": 24,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
    "swa_q_lora_rank": 32, "swa_qk_nope_head_dim": 20,
    "swa_qk_rope_head_dim": 8, "swa_rope_theta": 50000, "swa_v_head_dim": 12,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 12, "vocab_size": 128, "vocab_size_published": 1024}


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


def _serve(spec, params, lengths, new=8, **how):
    srv = deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, params=params, **{**dict(
            slots=3, max_seq_len=128, block_size=BLOCK, prefill_chunk=CHUNK,
            debug_checks=True), **how})
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, 128, n).astype(np.int32), new)
            for i, n in enumerate(lengths)]
    return srv, reqs, srv.serve(reqs)


def _rel(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def test_the_published_list_is_taken_as_given(model):
    """A head layer outside the period, whole periods, a partial closing
    period: the config accepts what ``num_layers % period`` refused, and the
    stacks are by kind."""
    spec, params = model
    cfg = spec.model_config
    assert cfg.stretches == (("latent_indexed",), 1, ("latent_indexed",))
    assert cfg.layer_kinds == ("latent_indexed",) + ("latent_sliding",) * 3
    assert (cfg.layers_of("latent_indexed"),
            cfg.layers_of("latent_sliding")) == (3, 3)
    blocks = params["blocks"]
    assert blocks["latent_indexed"]["q_b_w"].shape == (3, 32, 4 * 24)
    assert blocks["latent_sliding"]["q_b_w"].shape == (3, 32, 2 * 28)
    assert blocks["latent_indexed"]["idx_q_w"].shape == (3, 32, 4 * 16)
    assert "idx_q_w" not in blocks["latent_sliding"]
    assert blocks["dense"]["w1"].shape == (1, 64, 96)
    assert blocks["moe"]["experts_w1"].shape == (5, 4, 64, 32)
    assert cfg.num_params() == family.num_params(CONFIG) == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    published = type(cfg).dots3_note_prev()
    assert published.stretches == (("latent_indexed",), 11,
                                   ("latent_indexed",))
    assert 279.5e9 < published.num_params() < 279.7e9
    with pytest.raises(NotImplementedError, match="inference path"):
        spec.loss_fn(params, jnp.zeros((1, 8), jnp.int32))


def test_combinations_still_not_built_are_refused_by_name():
    from deepspeed_tpu.models import llama, mixtral

    with pytest.raises(ValueError, match="'latent_indexed' kind"):
        mixtral.MixtralConfig.tiny().__class__(
            num_layers=2, hidden_size=64, num_heads=4, index_heads=2,
            layer_kinds=("full", "sliding"), sliding_window=8)
    with pytest.raises(ValueError, match="'latent_indexed' kind"):
        mixtral.MixtralConfig(
            num_layers=2, hidden_size=64, num_heads=4, head_width=24,
            kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8, index_heads=2)
    with pytest.raises(ValueError, match="stacked by kind"):
        llama.LlamaConfig(num_layers=2, hidden_size=64, num_heads=4,
                          layer_kinds=("latent_sliding", "full"))


def test_uncached_forward_equals_the_reference(model):
    spec, params = model
    toks = np.random.default_rng(0).integers(0, 128, (2, 70)).astype(np.int32)
    want = np.asarray(ref.logits(CONFIG, params, toks))
    got = np.asarray(spec.apply_fn(params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=2e-4)
    # the comparison can tell: each shortcut of the reference moves it
    for variant in ref.VARIANTS[1:]:
        other = np.asarray(ref.logits(CONFIG, params, toks, variant=variant))
        assert _rel(other, want) > 1e-3, variant


@pytest.fixture(scope="module")
def compared(model):
    """Two sequences of 102 positions through THE ENGINE'S OWN cache and
    both its tables (the driver's comparison path): chunked prefill through
    the ``[4, 16]`` rung beside pad rows, 16 decode steps at all three
    rows."""
    spec, params = model
    srv = deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, params=params, slots=3,
        max_seq_len=128, block_size=BLOCK, prefill_chunk=CHUNK)
    toks = np.random.default_rng(1).integers(0, 128, (2, 102)).astype(np.int32)
    got, chosen, at, released = driver.paged_choices(srv, toks, 16)
    return srv, toks, got, chosen, at, released


def test_engines_logits_equal_the_reference(model, compared):
    """Prefill then decode on the engine's own pool, LOGITS after every call
    against the reference's full forward: contexts past ``index_topk`` and
    past the window, a chunk boundary inside both, a ring that has wrapped
    and released blocks — and the sets the engine chose are the
    reference's."""
    spec, params = model
    srv, toks, got, chosen, at, released = compared
    assert released > 0 and srv._ring.width * BLOCK < 102
    assert min(at) < TOPK < max(at) and min(at) < WINDOW
    want = np.asarray(ref.logits(CONFIG, params, toks, at=at))
    # (six layers of weights scaled up eightfold: float32 summation order)
    np.testing.assert_allclose(got, want, atol=1e-3)
    assert _rel(got, want) < driver.LOGIT_REL_RMSE["fp32"]
    forced, agreement = ref.logits(CONFIG, params, toks, at=at, forced=chosen)
    np.testing.assert_allclose(np.asarray(forced), want, atol=1e-5)
    assert agreement["keys"] == agreement["experts"] == 1.0
    assert agreement["key_gap"] == agreement["expert_gap_max"] == 0.0
    # every block is free again: the comparison leaves the engine as it was
    assert srv._alloc.blocks_in_use == srv._ring.alloc.blocks_in_use == 0


@pytest.mark.parametrize("variant", ref.VARIANTS[1:])
def test_every_control_is_refused_at_the_limit_used(model, compared, variant):
    _, params = model
    _, toks, got, _, at, _ = compared
    other = np.asarray(ref.logits(CONFIG, params, toks, at=at,
                                  variant=variant))
    assert _rel(got, other) > driver.LOGIT_REL_RMSE["fp32"], variant


def test_engine_serves_it_token_exact_on_two_tables(model):
    """Four requests of four lengths over three slots (rows of different
    lengths in one prefill call and one decode step): greedy tokens equal
    the reference's; ``stats()`` names all three kinds and the spans carry
    what the readers read."""
    spec, params = model
    srv, reqs, out = _serve(spec, params, [70, 33, 50, 9])
    assert_greedy(lambda ids: ref.logits(CONFIG, params, ids), reqs, out)
    assert set(srv._cache) == {"latent", "idx", "latw"}
    assert srv._cache["latent"].shape[2:] == (1, BLOCK, 128)
    assert srv._cache["latw"].shape[0] == 3
    st = srv.stats()
    lat, kinds, sparse = st["kv_latent"], st["kv_kinds"], st["sparse_attn"]
    assert lat["kind"] == "latent" and lat["layers"] == 3
    assert (lat["token_width"], lat["token_bytes"]) == (24, 96)
    assert lat["latent_attn"] == {
        name: "latent_gather+window_latent_gather"
        for name in ("decode", "prefill")}
    assert sparse["decode"] == sparse["prefill"] == \
        "gather+top_k+latent_walk"
    assert 0 < sparse["kv_selected"] < sparse["index_keys"]
    assert kinds["window"] == WINDOW
    assert kinds["full"]["layers"] == kinds["sliding"]["layers"] == 3
    assert kinds["sliding"]["block_size"] == BLOCK
    assert kinds["sliding"]["token_bytes"] == 32 * 4
    assert kinds["sliding"]["released"] > 0 and kinds["kv_window"] > 0
    # the union of what the three kinds refuse, each under its own key
    for got, kind in ((lat, "latent"), (kinds, "window")):
        assert got["refused"] == list(options.KIND_REFUSES[kind])
    assert srv._refusals["indexer"] == list(options.KIND_REFUSES["indexer"])
    assert st["compile_count"] == 1 + len(srv._rungs)
    for name in ("decode", "prefill"):
        spans = [e["args"] for e in srv.timeline.events()
                 if e["ph"] == "X" and e["name"] == name]
        assert spans and all(
            {"index_keys", "kv_selected", "kv_read", "sparse_rows",
             "kv_blocks", "kv_tiles", "kv_pairs", "latent_bytes",
             "kv_window", "kv_window_blocks", "experts_touched",
             "expert_rows_absent"} <= set(a) for a in spans), name
        assert all(a["kv_window"] <= 3 * WINDOW * a.get("slots", 3)
                   for a in spans if name == "decode")
    dec = [e["args"] for e in srv.timeline.events()
           if e["ph"] == "X" and e["name"] == "decode"]
    assert any(a["kv_selected"] < a["index_keys"] for a in dec)


def test_window_leaf_takes_the_block_its_own_bytes_give(model):
    """Two latent leaves of two widths in one pool, each with its own block:
    the engine reads the window kind's off the cache tree."""
    spec, params = model
    srv, reqs, out = _serve(spec, params, [100, 90], new=6, block_size=None,
                            max_seq_len=None)
    assert (srv.block_size, srv._ring.block_size) == (32, 32)
    assert_greedy(lambda ids: ref.logits(CONFIG, params, ids), reqs, out)
    srv = deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, params=params, slots=2,
        max_seq_len=256, block_size=64, prefill_chunk=32, debug_checks=True)
    assert (srv.block_size, srv._ring.block_size) == (64, 32)
    assert srv._cache["latent"].shape[3] == 64
    assert srv._cache["latw"].shape[3] == 32
    reqs = [Request(i, np.random.default_rng(i).integers(0, 128, n)
                    .astype(np.int32), 5) for i, n in enumerate((150, 97))]
    assert_greedy(lambda ids: ref.logits(CONFIG, params, ids), reqs,
                  srv.serve(reqs))


def test_preempted_row_is_readmitted_token_exact(model):
    """A full-kind pool too small for three long rows: a row is preempted,
    its ring released, and re-admitted; every token still equals the
    reference's."""
    spec, params = model
    srv, reqs, out = _serve(spec, params, [60, 58, 62], new=30,
                            num_blocks=1 + 28)
    assert srv.stats()["evicted"] > 0
    assert_greedy(lambda ids: ref.logits(CONFIG, params, ids), reqs, out)


@pytest.mark.parametrize("how,kind,label", [
    (dict(prefix_caching=True), "window", "prefix_caching"),
    (dict(host_blocks=8, prefix_caching=True), "window", "host_blocks"),
    (dict(spec_tokens=2), "window", "spec_tokens"),
    (dict(quantize="kv8"), "window", "quantize"),
    (dict(resident_window_blocks=4), "window", "resident_window_blocks"),
    (dict(sp=2), "window", "sp=2"),
])
def test_the_union_of_the_kinds_refusals_is_raised_by_name(model, how, kind,
                                                           label):
    spec, params = model
    with pytest.raises(ValueError, match=label) as e:
        deepspeed_tpu.init_serving(
            spec, config={"dtype": "fp32"}, params=params, slots=2,
            max_seq_len=64, block_size=BLOCK, prefill_chunk=CHUNK, **how)
    assert "window_layers" in str(e.value)
    # each kind's row is checked: what only the latent kind refuses too
    refusals = options.check(
        {"block_size": BLOCK, "prefill_chunk": CHUNK,
         "prefix_caching": False},
        {"tp": 1, "dp": 1, "mesh_sp": 1, "weights": None},
        ["window", "indexer", "latent"], "dots3")
    assert set(refusals) == {"window", "indexer", "latent"}
    with pytest.raises(ValueError, match="latent_attention.*quantized "
                                         "weights"):
        options.check(
            {"block_size": BLOCK, "prefill_chunk": CHUNK,
             "prefix_caching": False},
            {"tp": 1, "dp": 1, "mesh_sp": 1, "weights": "int8"},
            ["window", "indexer", "latent"], "dots3")


def test_contiguous_generate_is_refused(model):
    spec, params = model
    engine = deepspeed_tpu.init_inference(spec, config={"dtype": "fp32"},
                                          params=params)
    with pytest.raises(NotImplementedError, match="block-paged pool"):
        engine.generate(np.zeros((1, 4), np.int32), max_new_tokens=2)


def test_eight_shares_add_up_to_the_uncut_layer_and_head():
    """THE SHARE TEST: eight chips' routed partial sums, the shared expert
    counted once, and eight vocabulary slices' logits side by side equal the
    uncut reference's layer and head."""
    uncut = {**CONFIG, "depth": 2, "n_routed_experts": 16,
             "experts_first": 0, "vocab_size": 128,
             "layer_types": CONFIG["layer_types"][:2]}
    spec = family.build(uncut)
    params = _params(spec, seed=3)
    toks = np.random.default_rng(2).integers(0, 128, (1, 40)).astype(np.int32)
    whole = np.asarray(ref.logits(uncut, params, toks))
    moe = params["blocks"]["moe"]
    x = jnp.asarray(np.random.default_rng(3).standard_normal((40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        layer = np.asarray(ref._experts(uncut, x, moe, 0))
        shared = np.asarray(
            (jax.nn.silu(x @ moe["shared_w1"][0]) * (x @ moe["shared_w3"][0]))
            @ moe["shared_w2"][0])
        parts, slices = [], []
        for chip in range(8):
            share = {**uncut, "n_routed_experts": 2, "experts_first": 2 * chip,
                     "vocab_size": 16}
            held = {k: v[:, 2 * chip:2 * chip + 2] if k.startswith("experts_")
                    else v for k, v in moe.items()}
            parts.append(np.asarray(ref._experts(share, x, held, 0)) - shared)
            # the vocabulary slice: its rows of the head (the ids a slice's
            # traffic sends are its own rows of the token table; the uncut
            # table stands in for the other chips' rows here)
            sliced = {**params, "lm_head":
                      params["lm_head"][:, 16 * chip:16 * (chip + 1)],
                      "blocks": {**params["blocks"], "moe": held}}
            slices.append(sliced)
    np.testing.assert_allclose(sum(parts) + shared, layer, atol=1e-5)
    # the head: with every chip's partial sums exchanged the hidden state is
    # the uncut one, and the slices' logits lie side by side
    hidden = np.asarray(jax.jit(lambda p, t: ref.hidden_states(uncut, p, t))(
        params, jnp.asarray(toks)))
    side = np.concatenate(
        [hidden @ np.asarray(s["lm_head"], np.float32) for s in slices], -1)
    np.testing.assert_allclose(side, whole, atol=2e-4)
    # and a share's own forward (no exchange) is the program's: the held
    # experts' partial sum goes on to the next layer on both sides
    share = {**uncut, "n_routed_experts": 2, "experts_first": 6}
    spec2 = family.build(share)
    got = np.asarray(spec2.apply_fn(
        {**params, "blocks": {**params["blocks"], "moe": {
            k: v[:, 6:8] if k.startswith("experts_") else v
            for k, v in moe.items()}}}, jnp.asarray(toks)))
    want = np.asarray(ref.logits(share, {**params, "blocks": {
        **params["blocks"], "moe": {
            k: v[:, 6:8] if k.startswith("experts_") else v
            for k, v in moe.items()}}}, toks))
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert np.abs(got - whole).max() > 1e-3
