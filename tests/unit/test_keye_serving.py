"""Keye-VL-2.0's language model through the normal path: ``LlamaConfig``'s
head width and per-head q/k-norm, ``MixtralConfig``'s indexer, the third
pool leaf through ``ServingEngine`` (prefix reuse, eviction, swap), the
ring's counters, the refusals, and Mixtral / OLMoE left as they were."""

import dataclasses
import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import llama, mixtral
from deepspeed_tpu.ops import paged_kv
from tiny import assert_greedy

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import reference_keye  # noqa: E402
from chipbench.drivers import serve_closed  # noqa: E402

TOPK = 32
#: the benchmark configuration's ``rehearse`` widths: heads x head_dim != d,
#: rep = 2, 8 experts of 32 top-4, an indexer of 2 x 16, topk 32
CFG = mixtral.MixtralConfig(
    vocab_size=512, max_seq_len=512, num_layers=2, num_heads=4,
    num_kv_heads=2, head_width=16, hidden_size=64, ffn_size=32, rope_theta=1e7,
    rms_eps=1e-6, qk_norm="head", num_experts=8, top_k=4,
    norm_topk_prob=True, index_heads=2, index_head_dim=16, index_topk=TOPK,
    remat=False)
REFERENCE = {
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 1e7,
    "rope_scaling": {"mrope_section": [2, 3, 3]}, "num_experts_per_tok": 4,
    "norm_topk_prob": True,
    "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 16,
                  "topk": TOPK}}


@pytest.fixture(scope="module")
def model():
    spec = mixtral.build(CFG)
    params = spec.init_fn(jax.random.PRNGKey(0))
    blocks = params["blocks"]
    for i, name in enumerate(("q_norm", "k_norm", "idx_k_norm")):
        blocks[name] = blocks[name] + 0.1 * jax.random.normal(
            jax.random.PRNGKey(5 + i), blocks[name].shape)
    return spec, params


def _serving(model, **kw):
    spec, params = model
    kw = {"slots": 3, "max_seq_len": 192, "block_size": 8,
          "prefill_chunk": 16, **kw}
    return deepspeed_tpu.init_serving(spec, config={"dtype": "fp32"},
                                      params=params, **kw)


@functools.lru_cache(maxsize=None)
def _uncached(apply_fn):
    return jax.jit(lambda params, ids: apply_fn(params, ids))


def _exact(model, reqs, out):
    """Every served token is the greedy one of the UNCACHED forward (itself
    held to the reference below; ``tiny.py``: one teacher-forced call over
    prompt + output, not a roll-out)."""
    spec, params = model

    def logits_of(ids):
        with jax.default_matmul_precision("highest"):
            return _uncached(spec.apply_fn)(params, jnp.asarray(ids))

    assert_greedy(logits_of, reqs, out)


# ----------------------------------------------------------------- configs
def test_head_width_is_a_field_and_defaults_to_the_quotient():
    assert llama.LlamaConfig.tiny().head_dim == 16
    assert llama.LlamaConfig(hidden_size=64, num_heads=4,
                             head_width=32).head_dim == 32
    keye = mixtral.MixtralConfig.keye_vl2_30b_a3b()
    assert keye.num_heads * keye.head_dim == 2 * keye.hidden_size
    with pytest.raises(ValueError, match="qk_norm"):
        llama.LlamaConfig(qk_norm="rows")


@pytest.mark.parametrize("name", ["llama38b", "llama370b", "mixtral8x7b"])
def test_a_replaced_width_or_head_count_moves_the_head_width(name):
    # the quotient is read, not stored: ``dataclasses.replace`` and the
    # named presets' overrides (``models._with``) carry no width forward
    import dataclasses

    import deepspeed_tpu.models as models

    spec = models.get_model(name, hidden_size=512, num_heads=8,
                            num_kv_heads=8, num_layers=1, ffn_size=64,
                            vocab_size=64)
    cfg = spec.model_config
    assert cfg.head_dim == 64
    shapes = jax.eval_shape(lambda: spec.init_fn(jax.random.PRNGKey(0)))
    assert shapes["blocks"]["q_w"].shape == (1, 512, 512)
    assert dataclasses.replace(cfg, num_heads=4).head_dim == 128
    fixed = dataclasses.replace(cfg, head_width=32)
    assert dataclasses.replace(fixed, hidden_size=1024).head_dim == 32


def test_the_presets_parameters_as_integers():
    keye = mixtral.MixtralConfig.keye_vl2_30b_a3b()
    one = dataclasses.replace(keye, num_layers=1).num_params()
    two = dataclasses.replace(keye, num_layers=2).num_params()
    assert two - one == 625_381_760
    assert dataclasses.replace(keye, num_layers=6).num_params() \
        == 4_374_622_464
    # what the program allocates is what it counts
    tiny = mixtral.build(CFG)
    shapes = jax.eval_shape(lambda: tiny.init_fn(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == CFG.num_params()
    assert shapes["blocks"]["q_norm"].shape == (2, 16)
    assert shapes["blocks"]["idx_k_norm"].shape == (2, 2, 16)
    # llama at a head width of its own
    wide = llama.LlamaConfig(vocab_size=64, num_layers=1, num_heads=4,
                             num_kv_heads=2, hidden_size=32, head_width=16,
                             ffn_size=16, qk_norm="head")
    got = jax.eval_shape(lambda: llama.init_params(wide,
                                                   jax.random.PRNGKey(0)))
    assert got["blocks"]["q_w"].shape == (1, 32, 64)
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(got)) == wide.num_params()


def test_cache_has_a_third_leaf_only_with_an_indexer():
    hooks = mixtral.build(CFG).decode_hooks
    cache = jax.eval_shape(lambda: hooks["init_cache"](5, 8, jnp.float32))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, 5, 2, 8, 16), "v": (2, 5, 2, 8, 16),
        "idx": (2, 5, 1, 8, 16)}
    assert hooks["sparse_attention"] == {"topk": TOPK}
    plain = mixtral.build(mixtral.MixtralConfig.tiny()).decode_hooks
    assert set(jax.eval_shape(lambda: plain["init_cache"](
        5, 8, jnp.float32))) == {"k", "v"}
    assert "sparse_attention" not in plain


# -------------------------------------------------- program vs the reference
def test_uncached_forward_agrees_with_the_reference(model):
    spec, params = model
    toks = np.random.default_rng(0).integers(0, 512, (2, 120)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        got = spec.apply_fn(params, jnp.asarray(toks))
    want = reference_keye.logits(REFERENCE, params, toks)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.std(want))


@pytest.mark.parametrize("what", ["decode", "verify",
                                  "prefill-chunk-straddles-topk"])
def test_paged_path_agrees_with_the_reference(model, what):
    """Chunked prefill then paged decode on the engine's own programs'
    path (``serve_closed.paged_logits``), 100+ positions with topk 32:
    fp32 to 1e-4."""
    spec, params = model
    rng = np.random.default_rng(1)
    if what == "verify":
        # a speculative engine serves token-exact with the plain greedy
        # continuation: its K+1 verify window selects per query
        prompt = rng.integers(0, 512, 70).astype(np.int32)
        srv = _serving(model, spec_tokens=3, sampling=False)
        reqs = [Request(uid=0, prompt=prompt, max_new_tokens=10)]
        _exact(model, reqs, srv.serve(reqs))
        assert srv.stats()["sparse_attn"]["verify"] is not None
        assert srv.stats()["spec_rounds"] > 0
        return
    srv = _serving(model, prefill_chunk=24 if what != "decode" else 16)
    s = 120
    toks = rng.integers(0, 512, (2, s)).astype(np.int32)
    got = serve_closed.paged_logits(srv, toks, 16)
    chunk, n_prefill = srv.prefill_chunk, s - 16
    at = [min(base + chunk, n_prefill) - 1
          for base in range(0, n_prefill, chunk)] + list(range(n_prefill, s))
    # the second chunk (24..47) straddles topk = 32
    assert what == "decode" or at[1] == 47
    want = np.asarray(reference_keye.logits(REFERENCE, params, toks, at=at))
    assert np.sqrt(np.mean((got - want) ** 2)) < 1e-4 * want.std()


# ------------------------------------------------- through the ServingEngine
def test_served_requests_are_the_greedy_continuation_and_counters_add_up(
        model):
    srv = _serving(model)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, 512, n).astype(np.int32),
                    max_new_tokens=8) for i, n in enumerate((20, 100, 70))]
    # (the first call holds all three under topk, ``[4, 16]``: 16 + 16 + 16
    # tokens against the 20 a wide row would carry; uid 1 is the longest
    # and finishes last, alone)
    out = srv.serve(reqs)
    _exact(model, reqs, out)
    stats = srv.stats()
    assert stats["sparse_attn"]["decode"] == "gather+top_k+walk"
    assert stats["sparse_attn"]["prefill"] == "gather+top_k+walk"
    spans = [e for e in srv.timeline.events() if e["ph"] == "X"
             and e["name"] in ("decode", "prefill")]
    # (counted on the device from the selection itself, and brought back
    # behind the step's tokens)
    totals = dict.fromkeys(("index_keys", "kv_selected", "kv_valid",
                            "sparse_rows", "kv_read"), 0)
    for e in spans:
        for key in totals:
            totals[key] += e["args"][key]
    assert {k: stats["sparse_attn"][k] for k in totals} == totals
    # the last decode step ran the one row still alive, uid 1, alone: its
    # context is prompt + generated - 1 keys, of which it attends topk
    last = [e for e in spans if e["name"] == "decode"][-1]["args"]
    assert last["slots"] == 1 and last["kv_valid"] == 100 + 7
    assert last["kv_selected"] == min(107, TOPK) == TOPK
    assert last["index_keys"] == 107 and last["sparse_rows"] == 1
    assert TOPK <= last["kv_read"] <= 112         # whole blocks of 8
    # a prefill call whose rows are all under topk scores nothing
    first = [e for e in spans if e["name"] == "prefill"][0]["args"]
    assert first["index_keys"] == 0 and first["sparse_rows"] == 0
    assert first["kv_selected"] == first["kv_valid"] > 0
    # a model without an indexer carries none of it
    plain = deepspeed_tpu.init_serving(
        mixtral.build(mixtral.MixtralConfig.tiny()),
        config={"dtype": "fp32"}, slots=2, max_seq_len=64, block_size=8)
    plain.serve([Request(uid=0, prompt=np.arange(9, dtype=np.int32),
                         max_new_tokens=2)])
    assert plain.stats()["sparse_attn"] is None
    assert all("kv_selected" not in e.get("args", {})
               for e in plain.timeline.events())


def test_third_leaf_survives_prefix_reuse_eviction_and_swap(model):
    rng = np.random.default_rng(2)
    shared = rng.integers(0, 512, 40).astype(np.int32)       # past topk
    prompts = [np.concatenate([shared, rng.integers(0, 512, n).astype(
        np.int32)]) for n in (5, 9, 3, 7, 4, 8)]

    def reqs(base):
        return [Request(uid=base + i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]

    def check(out, base):
        _exact(model, reqs(base), out)

    # prefix reuse: later requests reuse the shared prefix's blocks — K, V
    # AND the indexer's keys (an indexer key that was lost, stale or another
    # block's selects other keys past topk and changes tokens)
    srv = _serving(model, slots=2, max_seq_len=64)
    check(srv.serve(reqs(0)), 0)
    hits0 = srv.stats()["prefix_hit_tokens"]
    check(srv.serve(reqs(10)), 10)
    assert srv.stats()["prefix_hit_tokens"] - hits0 >= len(prompts) * 40
    # a pressure pool with a host tier (test_tiered_kv's recipe): rows are
    # preempted, blocks evicted, demoted to the host and promoted back, all
    # three leaves by tree
    tight = _serving(model, slots=3, max_seq_len=64, prefill_batch=2,
                     num_blocks=10, host_blocks=64, swap_batch=4,
                     debug_checks=True)
    check(tight.serve(reqs(0)), 0)
    check(tight.serve(reqs(10)), 10)
    stats = tight.stats()
    assert stats["swap_out"] > 0 and stats["swap_in"] > 0
    assert stats["evicted"] > 0
    assert set(tight._cache) == {"k", "v", "idx"}
    assert stats["kv_pool_shape"] == [2, 10, 2, 8, 16]        # of K
    host = jax.tree_util.tree_leaves(tight._host.arena) \
        if hasattr(tight._host, "arena") else None
    assert host is None or len(host) == 3


# ----------------------------------------------------------------- refusals
def test_what_the_selection_does_not_serve_is_refused_by_name(model):
    spec, params = model
    with pytest.raises(ValueError, match="learned.*indexer.*kv8"):
        _serving(model, quantize="kv8")
    with pytest.raises(ValueError, match="learned.*indexer.*tp mesh"):
        _serving(model, topology=2)
    with pytest.raises(ValueError, match="learned.*indexer.*draft model"):
        _serving(model, spec_tokens=2,
                 draft=mixtral.build(mixtral.MixtralConfig.tiny()))
    # the contiguous cache of InferenceEngine.generate has no third leaf
    engine = deepspeed_tpu.init_inference(spec, config={"dtype": "fp32"},
                                          params=params)
    with pytest.raises(NotImplementedError, match="block-paged pool"):
        engine.generate(jnp.zeros((1, 8), jnp.int32), max_new_tokens=2)


# ------------------------------------------- Mixtral and OLMoE are untouched
#: sha256[:16] of the lowered (StableHLO) paged decode and prefill programs,
#: produced by this very function (jax 0.9.0, CPU lowering).  Taken at the
#: PARENT of PR 32 (d7d610f) and held by every tree up to PR 39; re-taken on
#: the tree of PR 41, whose paged write merges its tokens in the blocks'
#: stored view — both families pack here (hd 16 under a block of 8: g = 8),
#: so the write's ops, and nothing else of these programs, changed; and on
#: the tree of PR 50: one ``optimization_barrier`` a traced block, on its q,
#: k and v products (``llama._attend_cached``), and nothing else
PARENT_PROGRAMS = {
    ("mixtral", "decode"): "7a4974795a81b135",
    ("mixtral", "prefill"): "3f11818526973750",
    ("olmoe", "decode"): "f3fc86115cc7e2e3",
    ("olmoe", "prefill"): "d00c0cce053dad56",
}
OLD_FAMILIES = {
    "mixtral": mixtral.MixtralConfig.tiny(),
    "olmoe": mixtral.MixtralConfig(
        vocab_size=512, max_seq_len=128, num_layers=2, num_heads=4,
        num_kv_heads=4, hidden_size=64, ffn_size=32, rope_theta=10000.0,
        rms_eps=1e-5, qk_norm=True, num_experts=8, top_k=4,
        norm_topk_prob=False, remat=False),
}


def _paged_programs(cfg, slots=3, nbper=4, block=8, chunk=(2, 16)):
    spec = mixtral.build(cfg)
    fwd = spec.decode_hooks["forward_cached"]
    params = jax.eval_shape(lambda: spec.init_fn(jax.random.PRNGKey(0)))
    pool = jax.eval_shape(lambda: paged_kv.pack_pool(
        spec.decode_hooks["init_cache"](1 + slots * nbper, block,
                                        jnp.float32)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731

    def decode(params, cache, tokens, lengths, bt):
        return fwd(params, tokens[:, None], cache, 0, lengths=lengths,
                   block_tables=bt, routing=True)

    def prefill(params, cache, ids, bt, base, valid):
        return fwd(params, ids, cache, base, lengths=valid, block_tables=bt,
                   routing=True)

    j, w = chunk
    return {"decode": (decode, (params, pool, i32(slots), i32(slots),
                                i32(slots, nbper))),
            "prefill": (prefill, (params, pool, i32(j, w), i32(j, nbper),
                                  i32(j), i32(j)))}


@pytest.mark.parametrize("family,program", sorted(PARENT_PROGRAMS))
def test_mixtral_and_olmoe_lower_to_the_parents_programs(family, program):
    fn, args = _paged_programs(OLD_FAMILIES[family])[program]
    text = jax.jit(fn).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_PROGRAMS[(family, program)]
