"""Mistral Small 4's block served through the normal path, at tiny widths
in float32 on the CPU, against the plain reference
(``chipbench/reference_mistral4.py``, the EXPANDED form): latent attention
(low-rank queries, one joint key / value latent a token, rotary on half of
a head under YaRN, a position-dependent query temperature) on a pool of ONE
leaf read ABSORBED, softmax-scored experts beside one shared expert, an
expert layer that holds a share of its experts, an untied head.  The tiny
``original_max_position_embeddings`` (16) puts positions on both sides of
it, so ``t(p)`` and YaRN's blend are exercised."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from chipbench import reference_mistral4 as ref
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import llama, mixtral
from deepspeed_tpu.moe import routed
from deepspeed_tpu.ops import paged_kv
from tiny import assert_greedy

BLOCK, CHUNK, ORIGINAL = 8, 16, 16
HELD = (4, 4)
ROPE = {"factor": 8.0, "original_max_position_embeddings": ORIGINAL,
        "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
        "mscale_all_dim": 1.0}


def _cfg(**over):
    return mixtral.MixtralConfig(**{**dict(
        vocab_size=128, max_seq_len=256, num_layers=2, num_heads=4,
        num_kv_heads=4, head_width=16, hidden_size=32, ffn_size=16,
        rope_theta=10000.0, rms_eps=1e-6, rope_interleaved=True,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8,
        v_head_dim=12, rope_scaling=ROPE, query_temperature=(0.1, ORIGINAL),
        num_experts=16, top_k=4, router_score="softmax", shared_experts=1,
        experts_held=HELD, remat=False), **over})


def _config(cfg):
    """The reference's view of ``cfg`` (a configuration file's keys)."""
    return dict(
        num_attention_heads=cfg.num_heads, rms_norm_eps=cfg.rms_eps,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_dim, qk_rope_head_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim, num_experts_per_tok=cfg.top_k,
        experts_first=cfg.experts_held[0] if cfg.experts_held else 0,
        n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1,
        n_group=1, topk_group=1,
        rope_parameters={**cfg.rope_scaling, "rope_theta": cfg.rope_theta,
                         "llama_4_scaling_beta": cfg.query_temperature[0]})


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
    # the comparison can tell: each shortcut of the reference moves it
    for variant in ref.VARIANTS[1:]:
        other = np.asarray(ref.logits(_config(cfg), params, toks,
                                      variant=variant))
        assert np.abs(other - want).max() > 1e-3, variant


def test_the_query_temperature_shows_only_past_the_original_context(model):
    cfg, _, params = model
    toks = np.random.default_rng(0).integers(0, 128, (1, 40)).astype(np.int32)
    want = np.asarray(ref.logits(_config(cfg), params, toks))
    flat = np.asarray(ref.logits(_config(cfg), params, toks,
                                 variant="no_temperature"))
    np.testing.assert_allclose(flat[:, :ORIGINAL], want[:, :ORIGINAL],
                               atol=1e-6)
    assert np.abs(flat[:, ORIGINAL:] - want[:, ORIGINAL:]).max() > 1e-3


def _paged(spec, params, toks, block):
    """Chunked prefill of 48 positions then decode steps through the hooks
    on a packed pool of ``block``-token blocks: (logits, positions)."""
    hooks = spec.decode_hooks
    b, s = toks.shape
    nbper = -(-s // block)
    cache = paged_kv.pack_pool(hooks["init_cache"](1 + b * nbper, block,
                                                   jnp.float32))
    assert set(cache) == {"latent"}
    assert cache["latent"].shape == (2, 1 + b * nbper, 1, block, 128)
    bt = jnp.asarray(1 + np.arange(b * nbper).reshape(b, nbper), jnp.int32)
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
    return np.stack(got, 1), at


@pytest.mark.parametrize("block", [8, 32])
def test_paged_prefill_and_decode_equal_the_reference(model, block):
    """Chunked prefill then decode steps through the hooks — the ABSORBED
    read of the one-leaf pool — equal the reference's EXPANDED full
    forward."""
    cfg, spec, params = model
    toks = np.random.default_rng(0).integers(0, 128, (2, 70)).astype(np.int32)
    want = np.asarray(ref.logits(_config(cfg), params, toks))
    got, at = _paged(spec, params, toks, block)
    np.testing.assert_allclose(got, want[:, at], atol=2e-4)


def test_absorbed_equals_expanded_layer_by_layer(model):
    """Each layer's attention alone: the cached ABSORBED body over a paged
    pool (one chunk, then a decode step) against the uncached EXPANDED
    body, on the same normed input — float32 rounding apart."""
    from deepspeed_tpu.models.cached import layer_accessors

    cfg, _, params = model
    b, s = 2, 41
    y = jnp.asarray(np.random.default_rng(1).standard_normal((b, s, 32)),
                    jnp.float32)
    cos, sin = llama.rope_angles(cfg, s)
    nbper = -(-s // BLOCK)
    bt = jnp.asarray(1 + np.arange(b * nbper).reshape(b, nbper), jnp.int32)

    def both(l):
        """Layer ``l``'s two bodies as one program (not an op at a time)."""
        layer = jax.tree_util.tree_map(lambda a: a[l], params["blocks"])
        want = llama._latent_attention(cfg, layer, y, cos, sin)
        pool = mixtral.init_cache(cfg, 1 + b * nbper, BLOCK,
                                  jnp.float32)["latent"]
        head, pool = llama._latent_cached(
            cfg, y[:, :s - 1], *layer_accessors(layer), pool,
            jnp.zeros((b,), jnp.int32), bt, jnp.full((b,), s - 1, jnp.int32),
            l)
        last, pool = llama._latent_cached(
            cfg, y[:, s - 1:], *layer_accessors(layer), pool,
            jnp.full((b,), s - 1, jnp.int32), bt, None, l)
        return want, head, last, pool

    for l in range(cfg.num_layers):
        want, head, last, pool = jax.jit(both, static_argnums=0)(l)
        np.testing.assert_allclose(jnp.concatenate([head, last], 1), want,
                                   rtol=1e-4, atol=1e-4)
        # what is cached is the latent and the one rotated key, 24 of the
        # 128 lanes, and nothing in any other layer
        assert float(jnp.abs(pool[l, 1:, 0, :, :24]).min()) >= 0
        assert float(jnp.abs(pool[l, :, 0, :, 24:]).max()) == 0
        assert float(jnp.abs(pool[1 - l]).max()) == 0


def _serve(spec, params, lengths, new=12, prompts=None, **how):
    srv = deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, params=params, slots=3,
        max_seq_len=128, block_size=BLOCK, prefill_chunk=CHUNK,
        debug_checks=True, **how)
    rng = np.random.default_rng(0)
    prompts = prompts or [rng.integers(0, 128, n).astype(np.int32)
                          for n in lengths]
    reqs = [Request(i, p, new) for i, p in enumerate(prompts)]
    return srv, reqs, srv.serve(reqs)


def test_engine_serves_it_token_exact_and_names_the_pool(model):
    """Four requests over three slots through ``ServingEngine``: greedy
    tokens equal the reference's, ``stats()`` names the latent kind, and
    the spans carry what the readers read."""
    cfg, spec, params = model
    srv, reqs, out = _serve(spec, params, [70, 33, 50, 9])
    _exact(cfg, params, reqs, out)
    st = srv.stats()
    assert set(srv._cache) == {"latent"}
    lat = st["kv_latent"]
    assert lat["kind"] == "latent" and lat["layers"] == 2
    assert (lat["token_width"], lat["pool_width"]) == (24, 128)
    assert (lat["token_bytes"], lat["block_size"]) == (96, BLOCK)
    assert lat["block_bytes"] == BLOCK * 128 * 4
    assert lat["latent_attn"] == {"decode": "latent_gather",
                                  "prefill": "latent_gather"}
    assert lat["latent_bytes"] == lat["kv_valid"] * 96
    assert {"quantize", "a tp mesh", "a draft model"} <= set(lat["refused"])
    assert st["kv_kinds"] is None and st["sparse_attn"] is None
    # (the blocks still in use are the prefix trie's: a latent block is
    # kept for the next request like any other)
    assert st["compile_count"] == 1 + len(srv._rungs) == 3
    assert st["blocks_in_use"] == st["prefix_cache_entries"] > 0
    for name in ("decode", "prefill"):
        spans = [e["args"] for e in srv.timeline.events()
                 if e["ph"] == "X" and e["name"] == name]
        assert spans and all(
            {"kv_valid", "kv_blocks", "kv_pairs", "latent_bytes",
             "experts_touched", "expert_rows_absent"} <= set(a)
            for a in spans), name
        assert all(a["latent_bytes"] == a["kv_valid"] * 96 for a in spans)
        assert all(a["kv_blocks"] * BLOCK * 2 >= a["kv_valid"]
                   for a in spans)
    dec = [e["args"] for e in srv.timeline.events()
           if e["ph"] == "X" and e["name"] == "decode"]
    assert all(a["kv_pairs"] == a["kv_valid"] for a in dec)


def _walks(t, tq, nt, rows):
    """Grid step by grid step: ``(kv_tiles, kv_first_tiles_ahead)`` of ONE
    layer's latent walk over ``rows`` = ``(base, real queries, place in the
    call)`` a window of ``t`` positions, ``tq`` a grid step, ``nt`` blocks
    a loop iteration."""
    tiles = ahead = 0
    for base, queries, at in rows:
        for first in range(0, min(t, queries), tq):
            n = -(-(base + min(first + tq, queries)) // BLOCK)
            tiles += -(-n // nt)
            ahead += (at, first) != (0, 0)
    return tiles, ahead


def test_the_spans_count_the_latent_walks_tiles(model, monkeypatch):
    """ISSUE 58: a latent engine's ``decode`` / ``prefill`` / ``spec_verify``
    spans carry ``kv_tiles`` (the loop iterations ONE layer's walk makes:
    ``cdiv(blocks, nt)`` over rows and query tiles) and
    ``kv_first_tiles_ahead`` (the grid steps whose first tile the step
    before starts: all that hold one but the call's first), at the tile
    ``stats()["kv_latent"]["tile_blocks"]`` names — ``latent_tile_blocks``
    of the pool's shapes, here 3 blocks a decode step and 2 a prefill
    step."""
    from deepspeed_tpu.ops import decode_attention as da

    cfg, spec, params = model
    monkeypatch.setattr(da, "_LATENT_VMEM_BUDGET", 30_000)
    shapes = dict(bs=BLOCK, w=128, itemsize=4, nbper=128 // BLOCK)
    tiles = {t: da.latent_walk_shape(cfg.num_heads, t, **shapes)
             for t in (1, 4, CHUNK, 4 * CHUNK)}
    assert tiles == {1: (1, 3), 4: (4, 3), CHUNK: (16, 2),
                     4 * CHUNK: (16, 2)}
    # one request alone, in slot 0: every span is reckoned exactly
    srv, _, _ = _serve(spec, params, [70], spec_tokens=0)
    assert srv.stats()["kv_latent"]["tile_blocks"] == {
        "decode": 3, "prefill": {srv._rung_name(r): 2 for r in srv._rungs}}
    spans = [e["args"] for e in srv.timeline.events()
             if e["ph"] == "X" and e["name"] in ("prefill", "decode")]
    base = 0
    for a in spans:
        assert {"kv_tiles", "kv_first_tiles_ahead"} <= set(a)
        if "width" in a:                                        # prefill
            want = _walks(a["width"], *tiles[a["width"]],
                          [(base, a["tokens"], 0)])
            base += a["tokens"]
        else:
            keys = a["kv_valid"] // 2                           # 2 layers
            assert a["kv_blocks"] == -(-keys // BLOCK)
            want = (-(-a["kv_blocks"] // 3), 0)
        assert (a["kv_tiles"], a["kv_first_tiles_ahead"]) == want, a
    assert base == 70 and max(a["kv_blocks"] for a in spans) == 11
    lat = srv.stats()["kv_latent"]
    assert lat["kv_tiles"] == sum(a["kv_tiles"] for a in spans)
    assert lat["kv_first_tiles_ahead"] == sum(
        a["kv_first_tiles_ahead"] for a in spans) > 0
    # rows side by side: the tiles run full (kv_blocks / kv_tiles up to
    # nt), and every live row but the call's first finds its tile started
    srv, _, _ = _serve(spec, params, [70, 33, 50, 9], spec_tokens=3)
    assert srv.stats()["kv_latent"]["tile_blocks"]["verify"] == 3
    for name in ("decode", "spec_verify"):
        for e in srv.timeline.events():
            a = e["args"]
            if e["ph"] != "X" or e["name"] != name:
                continue
            assert a["slots"] <= a["kv_tiles"] <= a["kv_blocks"] \
                <= 3 * a["kv_tiles"], a
            assert a["slots"] - 1 <= a["kv_first_tiles_ahead"] \
                <= a["slots"], a
    # the arithmetic itself, rows of mixed lengths in one call
    for t, rows in ((1, [(0, 1, 0), (23, 1, 2), (24, 1, 5), (100, 1, 7)]),
                    (4, [(30, 4, 1), (5, 4, 2)]),
                    (64, [(0, 64, 0), (40, 17, 1), (7, 3, 2), (64, 33, 3)])):
        base, queries, at = map(np.asarray, zip(*rows))
        got = srv._kv_reach(base + queries, queries, t=t, at=at)
        assert (got["kv_tiles"], got["kv_first_tiles_ahead"]) \
            == _walks(t, *tiles[t], rows), (t, rows)


def test_preempted_row_is_readmitted_token_exact(model):
    """A pool too small for three long rows: a row is preempted and
    re-admitted (its prompt and what it generated re-prefilled from
    position 0); every token still equals the reference's."""
    cfg, spec, params = model
    srv, reqs, out = _serve(spec, params, [60, 58, 62], new=30,
                            num_blocks=1 + 28)
    assert srv.stats()["evicted"] > 0
    _exact(cfg, params, reqs, out)


def test_two_requests_share_a_prefix_through_the_trie(model):
    """A latent block is a block: the second request re-uses the first's
    full blocks through the prefix trie and still decodes token-exact."""
    cfg, spec, params = model
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 128, 40).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, 128, n)
                               .astype(np.int32)]) for n in (9, 13)]
    srv = deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, params=params, slots=1,
        max_seq_len=128, block_size=BLOCK, prefill_chunk=CHUNK,
        debug_checks=True)
    reqs = [Request(i, p, 8) for i, p in enumerate(prompts)]
    out = srv.serve(reqs)
    _exact(cfg, params, reqs, out)
    st = srv.stats()
    assert st["prefix_hit_tokens"] == 40 and st["prefix_cache_entries"] > 0


def test_a_verify_window_is_the_same_kernel_at_k_plus_one(model):
    """``spec_tokens=3``: the n-gram proposer's windows go through the
    absorbed body at T = 4 and the emitted tokens stay the reference's."""
    cfg, spec, params = model
    srv, reqs, out = _serve(spec, params, [33, 21], new=16, spec_tokens=3)
    _exact(cfg, params, reqs, out)
    assert srv.stats()["kv_latent"]["latent_attn"]["verify"] \
        == "latent_gather"


def test_the_host_tier_moves_the_leaf_as_it_is(model):
    """``host_blocks``: a preempted row's latent blocks are demoted to the
    host arena and promoted back by tree; tokens stay exact."""
    cfg, spec, params = model
    srv, reqs, out = _serve(spec, params, [60, 58, 62], new=30,
                            num_blocks=1 + 28, host_blocks=64, swap_batch=4)
    _exact(cfg, params, reqs, out)
    st = srv.stats()
    assert st["swap_out"] > 0 and st["swap_in"] > 0


def test_the_eight_shares_sum_to_the_uncut_layer(model):
    """The share test: the partial routed sums of every share of the
    experts (four shares of 4 of 16 here), plus the shared expert counted
    once, equal the uncut reference's whole expert layer — and each share
    equals the reference given the same share."""
    cfg, _, _ = model
    whole = mixtral.build(dataclasses.replace(cfg, experts_held=None))
    layer = jax.tree_util.tree_map(
        lambda a: a[1] * 8 if a.ndim > 2 else a[1],
        whole.init_fn(jax.random.PRNGKey(3))["blocks"])
    y = jnp.asarray(np.random.default_rng(2).standard_normal((37, 32)),
                    jnp.float32)
    uncut = ref._experts(y, layer, cfg.top_k, 0) + ref._shared(y, layer)
    total = mixtral._shared(cfg, layer, y)
    for first in range(0, cfg.num_experts, 4):
        mine = {k: layer[k][first:first + 4]
                for k in ("experts_w1", "experts_w3", "experts_w2")}
        part, record = routed.routed_ffn(
            y, layer["gate_w"], mine["experts_w1"], mine["experts_w3"],
            mine["experts_w2"], cfg.top_k, True, held=(first, 4),
            score="softmax")
        np.testing.assert_allclose(
            part, ref._experts(y, {**layer, **mine}, cfg.top_k, first),
            atol=1e-5)
        assert int(record[1]) + int(record[3]) == 37 * cfg.top_k
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=1e-5)


@pytest.mark.parametrize("how,named", [
    (dict(quantize="kv8"), "quantize='kv8'"),
    (dict(topology=2), "a tp mesh (tp=2)"),
    (dict(host_blocks=8, swap_batch=2, resident_window_blocks=4),
     "resident_window_blocks"),
])
def test_what_a_latent_pool_is_not_served_with_is_refused_by_name(
        model, how, named):
    _, spec, params = model
    with pytest.raises(ValueError, match="latent_attention") as e:
        deepspeed_tpu.init_serving(
            spec, config={"dtype": "fp32"}, params=params, slots=2,
            max_seq_len=64, block_size=BLOCK, prefill_chunk=CHUNK, **how)
    assert named in str(e.value)
    deepspeed_tpu.comm.reset_topology()


@pytest.mark.parametrize("width,itemsize,max_seq_len,block", [
    (320, 2, 16384, 512),     # the benchmark's cell: 768 B a token in bf16
    (320, 4, 16384, 256),     # the same pool in float32
    (320, 2, 2048, 256),      # an eighth of a short context
    (320, 2, 1024, 128),
    (24, 4, 128, 32),         # never under the other kinds' default
    (576, 2, 131072, 256),    # a wider latent: 640 lanes, 1,280 B
])
def test_a_latent_pools_default_block_follows_the_leafs_bytes(
        width, itemsize, max_seq_len, block):
    from deepspeed_tpu.ops import paged_kv

    got = paged_kv.latent_block_tokens(width, itemsize, max_seq_len)
    assert got == block and got & (got - 1) == 0
    assert got == paged_kv.DEFAULT_BLOCK_TOKENS or (
        got * paged_kv.latent_pool_width(width) * itemsize
        <= paged_kv.LATENT_BLOCK_BYTES and 8 * got <= max_seq_len)


def test_an_engine_given_no_block_size_derives_a_latent_pools(model):
    """``init_serving`` with no ``block_size``: a latent pool takes
    ``latent_block_tokens``, a pool with K and V a head the default it
    always had, and a block that is given is honoured."""
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.ops import paged_kv

    cfg, spec, params = model
    how = dict(config={"dtype": "fp32"}, slots=2, prefill_chunk=CHUNK)
    want = paged_kv.latent_block_tokens(cfg.latent_width, 4, 1024)
    assert want == 128
    srv = deepspeed_tpu.init_serving(
        mixtral.build(_cfg(max_seq_len=1024)), params=params,
        max_seq_len=1024, **how)
    assert srv.stats()["block_size"] == srv.block_size == want
    assert srv.stats()["kv_latent"]["block_size"] == want
    reqs = [Request(0, np.arange(70, dtype=np.int32) % 128, 6)]
    _exact(cfg, params, reqs, srv.serve(reqs))
    srv.close()
    given = deepspeed_tpu.init_serving(spec, params=params, max_seq_len=128,
                                       block_size=BLOCK, **how)
    assert given.block_size == BLOCK
    given.close()
    dense = llama.build(llama.LlamaConfig(
        vocab_size=128, max_seq_len=256, num_layers=1, num_heads=2,
        num_kv_heads=2, hidden_size=32, ffn_size=32))
    plain = deepspeed_tpu.init_serving(dense, max_seq_len=128, **how)
    assert plain.block_size == paged_kv.DEFAULT_BLOCK_TOKENS == 32
    plain.close()


def test_generate_refuses_a_latent_model_by_name(model):
    _, spec, params = model
    engine = deepspeed_tpu.init_inference(spec, config={"dtype": "fp32"},
                                          params=params)
    with pytest.raises(NotImplementedError, match="block-paged pool"):
        engine.generate(jnp.zeros((1, 4), jnp.int32), max_new_tokens=2)


def test_the_dense_llama_block_takes_the_latent_fields_too():
    """The fields are ``LlamaConfig``'s: a dense-FFN model with latent
    attention builds, counts its parameters and agrees cached (absorbed)
    with uncached (expanded)."""
    cfg = llama.LlamaConfig(
        vocab_size=64, max_seq_len=64, num_layers=2, num_heads=4,
        num_kv_heads=4, head_width=16, hidden_size=32, ffn_size=48,
        rope_theta=10000.0, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8,
        qk_rope_dim=8, v_head_dim=12, rope_scaling=ROPE,
        query_temperature=(0.1, ORIGINAL), remat=False)
    spec = llama.build(cfg)
    params = spec.init_fn(jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(params)) == cfg.num_params()
    assert spec.decode_hooks["latent_attention"]["width"] == 24
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 24)),
                       jnp.int32)
    want = spec.apply_fn(params, toks)
    cache = spec.decode_hooks["init_cache"](1 + 2 * 3, BLOCK, jnp.float32)
    bt = jnp.asarray(1 + np.arange(6).reshape(2, 3), jnp.int32)
    got, cache = spec.decode_hooks["forward_cached"](
        params, toks, cache, jnp.zeros((2,), jnp.int32),
        lengths=jnp.full((2,), 24, jnp.int32), block_tables=bt)
    np.testing.assert_allclose(got, want[:, -1], atol=1e-5)


def test_yarn_frequencies_blend_between_the_correction_dimensions():
    """The program's inverse frequencies equal the reference's (written
    apart, float64): the fast dimensions keep theta^(-2i/d), the slow ones
    are divided by the factor, a ramp lies between; without scaling they
    are the plain ones, as every other family's."""
    pub = mixtral.MixtralConfig.mistral_small_4()
    got = np.asarray(llama.rope_inv_freq(pub, 64))
    want = ref.yarn_inv_freq({**pub.rope_scaling, "rope_theta": 10000.0}, 64)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction dimensions 12 (32 turns in 8,192) and 25 (one turn)
    np.testing.assert_allclose(got[:13], plain[:13], rtol=1e-6)
    np.testing.assert_allclose(got[25:], plain[25:] / 128, rtol=1e-6)
    assert np.all(np.diff(got) < 0)
    assert not np.allclose(got, plain) and not np.allclose(got, plain / 128)
    assert llama.rope_attention_factor(pub) == 1.0
    np.testing.assert_allclose(llama.latent_scale(pub),
                               (0.1 * np.log(128) + 1) ** 2 / np.sqrt(128))
    np.testing.assert_allclose(
        llama.query_temperature(pub, jnp.asarray([0, 8191, 8192, 16384])),
        [1, 1, 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(3)], rtol=1e-6)
    old = llama.LlamaConfig.tiny()
    np.testing.assert_array_equal(
        llama.rope_inv_freq(old, 16),
        1.0 / (old.rope_theta ** (jnp.arange(0, 16, 2, dtype=jnp.float32)
                                  / 16)))


def test_the_published_model_and_this_chips_share_count_their_parameters():
    """``mistral_small_4()`` states the published model; a share's
    ``num_params`` is what ``init_params`` builds and what the benchmark's
    family counts."""
    import json
    import os

    from chipbench.families import mistral4

    pub = mixtral.MixtralConfig.mistral_small_4()
    assert (pub.num_layers, pub.num_experts, pub.vocab_size, pub.top_k) \
        == (36, 128, 131072, 4)
    assert (pub.latent_width, pub.head_dim, pub.value_dim) == (320, 128, 128)
    assert 118e9 < pub.num_params() < 120e9          # "119B-A6.5B"
    assert 6e9 < pub.active_params() < 7e9
    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    with open(os.path.join(root, "chipbench", "configs",
                           "mistral-small-4-119b-2603.json")) as f:
        config = json.load(f)
    share = dataclasses.replace(pub, num_layers=6, vocab_size=16384,
                                experts_held=(0, 16))
    assert share.num_params() == mistral4.num_params(config) == 2872634880
    tiny = _cfg()
    built = jax.eval_shape(lambda: mixtral.init_params(
        tiny, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(built)) == tiny.num_params()
