"""Dropless routed experts (``moe/routed.py``, ``moe/grouped_matmul.py``):
the routed FFN against a dense per-token loop, the grouped-matmul kernel
against per-group matmuls, the routing record on the serving ring against a
host recount, and the hd-128 (``g = 1``) branch of the lane-packed pool."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import llama as L
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.moe import routed
from deepspeed_tpu.ops import paged_kv
from deepspeed_tpu.moe.grouped_matmul import moe_gmm, work_items

T, D, F, E = 12, 32, 16, 8
E_ALL, E_NONE = 5, 2          # an expert every token picks, one nobody does


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    y = jax.random.normal(ks[0], (3, T // 3, D))
    y = y.at[..., 0].set(4.0)                    # a constant feature ...
    gate = jax.random.normal(ks[1], (D, E)) * 0.3
    gate = gate.at[0, E_ALL].set(8.0).at[0, E_NONE].set(-8.0)   # ... routes
    w1, w3 = (jax.random.normal(k, (E, D, F)) * 0.2 for k in ks[2:4])
    w2 = jax.random.normal(ks[4], (E, F, D)) * 0.2
    return y, gate, w1, w3, w2


def _dense_loop(y, gate, w1, w3, w2, k, renormalize):
    """Per token: softmax over all experts, its top-k, each chosen expert's
    SwiGLU MLP on that one token — numpy, no sort, no groups."""
    x = np.asarray(y, np.float64).reshape(-1, D)
    gate, w1, w3, w2 = (np.asarray(a, np.float64) for a in (gate, w1, w3, w2))
    out, chosen = np.zeros_like(x), []
    for t, row in enumerate(x):
        logits = row @ gate
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p, kind="stable")[:k]
        weights = p[top] / (p[top].sum() if renormalize else 1.0)
        for e, w in zip(top, weights):
            a, b = row @ w1[e], row @ w3[e]
            out[t] += w * ((a / (1 + np.exp(-a)) * b) @ w2[e])
        chosen.append(top)
    return out.reshape(y.shape), np.asarray(chosen)


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["moe_gmm", "ragged_dot"])
@pytest.mark.parametrize("renormalize", [True, False],
                         ids=["renormalised", "raw"])
@pytest.mark.parametrize("k", [1, 2, 8])
def test_routed_ffn_matches_a_dense_per_token_loop(k, renormalize, kernel):
    y, gate, w1, w3, w2 = _weights()
    want, chosen = _dense_loop(y, gate, w1, w3, w2, k, renormalize)
    got, record = jax.jit(lambda *a: routed.routed_ffn(
        *a, k, renormalize, kernel=kernel))(y, gate, w1, w3, w2)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    counts = np.bincount(chosen.reshape(-1), minlength=E)
    assert counts[E_ALL] == T                    # one expert takes every row
    assert k == E or counts[E_NONE] == 0         # one takes none (k < E)
    assert list(np.asarray(record)) == [int((counts > 0).sum()), T * k,
                                        int(counts.max())]
    # whole stacks + a layer index read the same weights in place
    stack = [jnp.stack([jnp.zeros_like(w), w]) for w in (w1, w3, w2)]
    again, _ = jax.jit(lambda y, g, a, b, c: routed.routed_ffn(
        y, g, a, b, c, k, renormalize, layer=jnp.int32(1), kernel=kernel))(
            y, gate, *stack)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_routed_ffn_counts_live_rows_only_and_refuses_a_bad_k():
    y, gate, w1, w3, w2 = _weights(1)
    live = jnp.zeros(y.shape[:-1], bool).at[0, :2].set(True)
    out_all, _ = routed.routed_ffn(y, gate, w1, w3, w2, 2, False)
    out, record = routed.routed_ffn(y, gate, w1, w3, w2, 2, False, live=live)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_all))
    assert int(record[1]) == 2 * 2 and int(record[0]) <= 3    # E_ALL shared
    for k in (0, E + 1):
        with pytest.raises(ValueError, match="top_k"):
            routed.routed_ffn(y, gate, w1, w3, w2, k, False)
    with pytest.raises(ValueError, match="top_k"):
        mixtral.MixtralConfig(num_experts=4, top_k=5)


@pytest.mark.parametrize("m,k,n,g,sizes", [
    (40, 64, 32, 8, None), (512, 64, 256, 64, None), (300, 32, 128, 5, None),
    (40, 64, 32, 8, "one group takes every row"),
    (16, 32, 32, 4, "rows past the groups")])
def test_grouped_matmul_kernel_matches_per_group_matmuls(m, k, n, g, sizes):
    rng = np.random.default_rng(m + g)
    if sizes is None:
        cuts = np.sort(rng.integers(0, m + 1, g - 1))
        gs = np.diff(np.concatenate([[0], cuts, [m]]))
    elif sizes.startswith("one"):
        gs = np.zeros(g, int)
        gs[g // 2] = m
    else:
        gs = np.asarray([3, 0, 5, 2])            # 10 of 16 rows
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = rng.standard_normal((2, g, k, n)).astype(np.float32)
    got = np.asarray(jax.jit(moe_gmm)(lhs, rhs, jnp.asarray(gs, jnp.int32),
                                      jnp.int32(1)))
    row = 0
    for e, size in enumerate(gs):
        np.testing.assert_allclose(got[row:row + size],
                                   lhs[row:row + size] @ rhs[1, e],
                                   rtol=1e-4, atol=1e-4)
        row += size
    # the walk: one item per (row tile, group) overlap, in row order
    tm = 16
    mp = -(-m // tm) * tm
    offsets, gids, tids, count = (np.asarray(a) for a in work_items(
        jnp.asarray(gs, jnp.int32), mp, tm))
    want = [(e, t) for e in range(g) if gs[e]
            for t in range(offsets[e] // tm, (offsets[e + 1] - 1) // tm + 1)]
    assert int(count[0]) == len(want) <= mp // tm + g - 1
    assert list(zip(gids[:len(want)], tids[:len(want)])) == want
    assert np.all(np.diff(tids[:len(want)]) >= 0)      # revisits are adjacent


def test_head_dim_128_pool_packs_one_span_a_row_and_round_trips():
    """hd 128 fills the 128 lanes by itself: ``g = 1``, the packed view IS
    the token-ordered view, and a write + gather through it round-trips."""
    assert paged_kv.lane_pack(32, 128) == 1 and paged_kv.lane_pack(32, 64) == 2
    cfg = mixtral.MixtralConfig(vocab_size=64, max_seq_len=64, num_layers=2,
                                num_heads=2, num_kv_heads=2, hidden_size=256,
                                ffn_size=32, num_experts=4, top_k=2)
    assert cfg.head_dim == 128
    pool = L.init_cache(cfg, 1 + 2 * 4, 8, jnp.float32)    # [L, NB, H, bs, hd]
    packed = paged_kv.pack_pool(pool)
    assert packed["k"].shape == pool["k"].shape
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    k = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 20, 128))
    v = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 20, 128))
    ck, cv = paged_kv.paged_cache_update(
        packed["k"], packed["v"], k, v, jnp.zeros(2, jnp.int32), bt,
        valid=jnp.asarray([20, 13], jnp.int32), layer=1)
    got = paged_kv.paged_gather(ck, bt, layer=1, head_dim=128)
    np.testing.assert_array_equal(np.asarray(got[0, :, :20]),
                                  np.asarray(k[0]))
    np.testing.assert_array_equal(np.asarray(got[1, :, :13]),
                                  np.asarray(k[1, :, :13]))
    assert not np.asarray(ck[0]).any()                    # layer 0 untouched
    assert not np.asarray(got[1, :, 16:]).any()           # pads went to scratch


# ------------------------------------------------- the ring's routing record
def _tiny_olmoe():
    cfg = mixtral.MixtralConfig(
        vocab_size=512, max_seq_len=128, num_layers=2, num_heads=4,
        num_kv_heads=4, hidden_size=64, ffn_size=32, rope_theta=10000.0,
        num_experts=8, top_k=4, norm_topk_prob=False, qk_norm=True,
        remat=False)
    cfg.use_flash = False
    return cfg


def _expert_sets(cfg, params, ids):
    """Host recount: the experts each position picks at each layer, from
    the uncached forward — int [L, B, S, k]."""
    b, s = ids.shape
    x = params["embed"][ids]
    cos, sin = L.rope_angles(cfg, s)
    sets = []
    for l in range(cfg.num_layers):
        layer = jax.tree_util.tree_map(lambda a: a[l], params["blocks"])
        x = L.attn_apply(cfg, layer, x, cos, sin)
        y = L.rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        _, top_e = routed.route(y.reshape(b * s, -1), layer["gate_w"],
                                cfg.top_k, cfg.norm_topk_prob)
        sets.append(np.asarray(top_e).reshape(b, s, cfg.top_k))
        x = x + mixtral._routed(cfg, layer, y)[0]
    return np.stack(sets)


def test_the_ring_carries_the_routing_and_sums_to_a_host_recount():
    cfg = _tiny_olmoe()
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        mixtral.build(cfg), config={"dtype": "fp32"}, slots=3,
        max_seq_len=64, block_size=8, prefill_chunk=16)
    plen, new = 20, 5
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(0, 512, plen, dtype=np.int32),
                    max_new_tokens=new) for i in range(2)]
    out = srv.serve(reqs)
    seqs = np.stack([np.asarray(out[i]) for i in range(2)])    # prompt + new
    sets = _expert_sets(cfg, srv.engine.params, seqs)
    flights = [e for e in srv.timeline.events() if e["ph"] == "X"
               and e["name"] in ("prefill", "decode")]
    # two rows in lockstep: chunks [0,16) and [16,20), then one decode step
    # per fed token at positions 20 .. 23 (the last token is never fed)
    spans = [range(0, 16), range(16, 20)] + [range(p, p + 1)
                                             for p in range(plen,
                                                            plen + new - 1)]
    assert [e["name"] for e in flights] == ["prefill"] * 2 + ["decode"] * 4
    assert all(e["args"].get("rows", 2) == 2 for e in flights)
    for e, positions in zip(flights, spans):
        picked = sets[:, :, list(positions)]                # [L, B, P, k]
        per_layer = [np.bincount(layer.reshape(-1), minlength=cfg.num_experts)
                     for layer in picked]
        assert e["args"]["experts_touched"] == sum(
            int((c > 0).sum()) for c in per_layer), e
        assert e["args"]["expert_rows"] == picked.size
        assert e["args"]["expert_rows_max"] == max(
            int(c.max()) for c in per_layer)
    st = srv.stats()
    assert st["moe_experts_touched"] == sum(
        e["args"]["experts_touched"] for e in flights) > 0
    assert st["moe_expert_rows"] == sum(
        e["args"]["expert_rows"] for e in flights) \
        == cfg.num_layers * cfg.top_k * 2 * (plen + new - 1)
    text = srv.metrics.prometheus_text()
    assert "serving_moe_expert_rows_total" in text
    assert "serving_moe_experts_touched_total" in text
    assert st["compile_count"] <= st["compile_budget"]
    srv.close()


def test_a_dense_family_reports_no_routing():
    from deepspeed_tpu.models import gpt2

    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        gpt2.build(gpt2.GPT2Config.tiny()), config={"dtype": "fp32"},
        slots=2, max_seq_len=64, block_size=8, prefill_chunk=16)
    srv.serve([Request(uid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=3)])
    assert srv.stats()["moe_expert_rows"] == 0
    assert not any("experts_touched" in e.get("args", {})
                   for e in srv.timeline.events())
    srv.close()


def test_qk_norm_and_routing_hold_under_tensor_parallelism():
    """q/k-norm spans ALL heads: under tp=2 the heads' features are split
    over two shards and the mean of squares must still be the global one
    (GSPMD reduces it; a per-shard norm would change every logit).  The
    sharded engine takes ``ragged_dot`` (a Pallas call has no partitioning
    rule), the single-shard one ``moe_gmm``: greedy tokens agree."""
    from deepspeed_tpu.inference.serving import ServingEngine

    cfg = _tiny_olmoe()
    model = mixtral.build(cfg)

    def build(tp):
        deepspeed_tpu.comm.reset_topology()
        return deepspeed_tpu.init_inference(
            model, config={"dtype": "fp32",
                           "tensor_parallel": {"tp_size": tp}})

    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, int(rng.integers(5, 14)))
               for _ in range(3)]
    kw = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16)
    one = ServingEngine(build(1), **kw)
    r1 = one.serve([Request(uid=i, prompt=p, max_new_tokens=5)
                    for i, p in enumerate(prompts)])

    def grouped_matmul_of(srv):
        text = str(jax.make_jaxpr(srv._program_bodies["decode"])(
            srv.engine.params, srv._cache, jnp.zeros(3, jnp.int32),
            jnp.zeros(3, jnp.int32), jnp.zeros((3, srv._nbper), jnp.int32),
            *srv._samp_args(np.zeros(3, np.int32))))
        return {name for name in ("moe_gmm", "ragged_dot") if name in text}

    assert grouped_matmul_of(one) == {"moe_gmm"}
    two = ServingEngine(build(2), **kw)
    r2 = two.serve([Request(uid=i, prompt=p, max_new_tokens=5)
                    for i, p in enumerate(prompts)])
    assert two.kv_sharded and two.tp_degree == 2
    with two._tp_ctx():
        assert grouped_matmul_of(two) == {"ragged_dot"}
    for uid in r1:
        np.testing.assert_array_equal(r1[uid], r2[uid], err_msg=f"uid {uid}")
    assert one.stats()["moe_expert_rows"] == two.stats()["moe_expert_rows"]
