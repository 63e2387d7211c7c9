"""Fused int8 dequant-matmul kernel (ops/quantized_matmul) — parity with the
dequantize+matmul reference path, eligibility fallbacks, and the quant-aware
model wiring (reference: DS-Inference int8 GEMMs never materialize an fp16
weight copy; ``module_inject/replace_module.py:152`` GroupQuantizer)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import quantization as quant
from deepspeed_tpu.ops.quantized_matmul import quantized_matmul


def _mk(k, n, g, rows=1, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(k, n)).astype(np.float32)
    x = rng.normal(size=(rows, k)).astype(np.float32)
    rec = quant.quantize(jnp.asarray(w), group_size=g)
    return jnp.asarray(x, dtype), rec


@pytest.fixture(autouse=True)
def _kernel_on(monkeypatch):
    # the fused kernel is opt-in (it loses to XLA's dequant path end-to-end
    # on this chip — see module docstring); these tests exercise it anyway
    monkeypatch.setenv("DS_QMM", "1")


@pytest.mark.parametrize("rows", [1, 8, 128])
def test_kernel_matches_dequant_matmul(rows):
    x, rec = _mk(512, 1024, 128, rows=rows)
    ref = x @ quant.dequantize(rec, x.dtype)
    out = quantized_matmul(x, rec)
    assert out.shape == (rows, 1024)
    # kernel dequantizes in bf16 (scale rounding ~2^-8, below the int8
    # quantization error itself); reference path computes in f32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=3e-1)


def test_kernel_3d_rows_and_bf16():
    x, rec = _mk(512, 512, 128, rows=6, dtype=jnp.bfloat16)
    x3 = x.reshape(2, 3, 512)
    ref = x3 @ quant.dequantize(rec, x3.dtype)
    out = quantized_matmul(x3, rec)
    assert out.shape == (2, 3, 512) and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=6e-2, atol=6e-1)


def test_off_lane_group_size_falls_back():
    # reference GroupQuantizer group sizes (64) are honored via fallback
    x, rec = _mk(512, 1024, 64)
    ref = x @ quant.dequantize(rec, x.dtype)
    np.testing.assert_allclose(np.asarray(quantized_matmul(x, rec)),
                               np.asarray(ref), rtol=1e-6)


def test_non_tiling_shapes_fall_back():
    # N=192 has no 128-multiple divisor block: must fall back, still correct
    x, rec = _mk(512, 192, 64)
    ref = x @ quant.dequantize(rec, x.dtype)
    np.testing.assert_allclose(np.asarray(quantized_matmul(x, rec)),
                               np.asarray(ref), rtol=1e-6)


def test_kill_switch_and_row_cap(monkeypatch):
    x, rec = _mk(512, 1024, 128, rows=4)
    ref = x @ quant.dequantize(rec, x.dtype)
    monkeypatch.setenv("DS_QMM", "0")
    np.testing.assert_allclose(np.asarray(quantized_matmul(x, rec)),
                               np.asarray(ref), rtol=1e-6)
    monkeypatch.delenv("DS_QMM")
    xl, _ = _mk(512, 1024, 128, rows=512)  # > max_rows: long-prefill fallback
    np.testing.assert_allclose(
        np.asarray(quantized_matmul(xl, rec)),
        np.asarray(xl @ quant.dequantize(rec, xl.dtype)), rtol=1e-6)


def test_model_decode_parity_kernel_vs_fallback(monkeypatch):
    """An int8-served OPT (tileable dims: hidden 128) must generate the
    same tokens with the fused kernel and with the dequant fallback."""
    import deepspeed_tpu
    from deepspeed_tpu.models import opt

    cfg = opt.OPTConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                        num_heads=4, hidden_size=128, ffn_size=512)
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = opt.build(cfg).init_fn(jax.random.PRNGKey(0))
    params = jax.device_get(params)
    ids = np.ones((1, 6), dtype=np.int32)

    outs, logits = {}, {}
    for tag, env in (("kernel", "1"), ("fallback", "0")):
        monkeypatch.setenv("DS_QMM", env)
        deepspeed_tpu.comm.reset_topology()
        eng = deepspeed_tpu.init_inference(
            model=opt.build(cfg), params=params,
            config={"dtype": "float32",
                    "quant": {"enabled": True, "group_size": 128}})
        outs[tag] = np.asarray(eng.generate(ids, max_new_tokens=8))
        logits[tag] = np.asarray(eng.forward({"input_ids": ids}))
    # bf16 in-kernel dequant vs f32 fallback: logits agree to bf16-level
    # tolerance and greedy decode stays on the same tokens
    np.testing.assert_allclose(logits["kernel"], logits["fallback"],
                               rtol=5e-2, atol=5e-2)
    agree = (outs["kernel"] == outs["fallback"]).mean()
    assert agree >= 0.9, (agree, outs)


# ------------------------------------------------------------------ W8A8
def test_w8a8_matmul_matches_dequant():
    from deepspeed_tpu.ops.quantized_matmul import w8a8_matmul

    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(512, 1024)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 512)), jnp.float32)
    rec = quant.quantize_k_grouped(w, k_group=256)
    ref = np.asarray(x @ quant.dequantize_k(rec, jnp.float32))
    out = np.asarray(w8a8_matmul(x, rec))
    # activation quantization adds ~1% error on top of the weight int8
    np.testing.assert_allclose(out, ref, rtol=5e-2, atol=5e-1)
    # prefill-sized rows fall back to exact dequant+matmul
    xl = jnp.asarray(rng.normal(size=(64, 512)), jnp.float32)
    refl = np.asarray(xl @ quant.dequantize_k(rec, xl.dtype))
    np.testing.assert_allclose(np.asarray(w8a8_matmul(xl, rec)), refl,
                               rtol=1e-5)


def test_w8a8_engine_decode(monkeypatch):
    """Tiny OPT served with quant.type=w8a8: decode runs, logits track the
    bf16 model, greedy tokens mostly agree."""
    import deepspeed_tpu
    from deepspeed_tpu.models import opt

    cfg = opt.OPTConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                        num_heads=4, hidden_size=128, ffn_size=512)
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = opt.build(cfg).init_fn(jax.random.PRNGKey(0))
    params = jax.device_get(params)
    ids = np.ones((1, 6), dtype=np.int32)

    deepspeed_tpu.comm.reset_topology()
    ref_eng = deepspeed_tpu.init_inference(
        model=opt.build(cfg), params=params, config={"dtype": "float32"})
    ref_tok = np.asarray(ref_eng.generate(ids, max_new_tokens=8))
    ref_logits = np.asarray(ref_eng.forward({"input_ids": ids}))

    deepspeed_tpu.comm.reset_topology()
    eng = deepspeed_tpu.init_inference(
        model=opt.build(cfg), params=params,
        config={"dtype": "float32",
                "quant": {"enabled": True, "type": "w8a8"}})
    from deepspeed_tpu.ops import quantization as q
    recs = [x for x in jax.tree_util.tree_leaves(
        eng.params, is_leaf=q.is_k_quantized) if q.is_k_quantized(x)]
    assert recs, "w8a8 quantization did not produce K-grouped records"
    tok = np.asarray(eng.generate(ids, max_new_tokens=8))
    logits = np.asarray(eng.forward({"input_ids": ids}))
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-1, atol=2e-1)
    assert (tok == ref_tok).mean() >= 0.75, (tok, ref_tok)


def test_w8a8_rejects_non_quant_aware_model():
    # unet's forwards don't dequantize at point of use and carry no
    # stacked-blocks key (mixtral — the previous example here — became
    # quant-aware in PR 7: attention records via the shared mm accessors,
    # experts dequantizing per layer inside moe_apply)
    import deepspeed_tpu
    from deepspeed_tpu.models import unet

    deepspeed_tpu.comm.reset_topology()
    with pytest.raises(ValueError, match="w8a8"):
        deepspeed_tpu.init_inference(
            model=unet.build(unet.UNetConfig.tiny()),
            config={"dtype": "float32",
                    "quant": {"enabled": True, "type": "w8a8"}})


def test_stacked_biases_stay_dense_at_64_layers():
    """[L, 3d] stacked biases pass the 2D weight-matrix shape tests once
    L >= 64 (they are not caught by the name filter either: 'qkv_b' does
    not contain 'bias'); the blocks-subtree quantizers must exclude them
    via min_ndim=3 or the block matmul wrappers crash on a record where
    a bias array is expected."""
    L, d = 64, 128
    blocks = {"qkv_w": jnp.zeros((L, d, 3 * d)),
              "qkv_b": jnp.ones((L, 3 * d)),
              "ln1_scale": jnp.ones((L, d))}
    for fn in (lambda t: quant.quantize_pytree(t, group_size=128,
                                               min_ndim=3),
               lambda t: quant.quantize_pytree_k_grouped(t, k_group=128,
                                                         min_ndim=3)):
        out = fn(blocks)
        assert not isinstance(out["qkv_b"], dict), "bias was quantized"
        assert not isinstance(out["ln1_scale"], dict)
        assert isinstance(out["qkv_w"], dict), "weight was NOT quantized"


def test_engine_serves_64_layer_quant_aware_model(monkeypatch):
    """End-to-end: a 64-layer tiny GPT-2 with quant.enabled must build and
    decode (regression: stacked biases became records and .astype crashed
    at trace time)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.GPT2Config(vocab_size=256, max_seq_len=32, num_layers=64,
                          num_heads=2, hidden_size=128)
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = gpt2.build(cfg).init_fn(jax.random.PRNGKey(0))
    params = jax.device_get(params)
    deepspeed_tpu.comm.reset_topology()
    eng = deepspeed_tpu.init_inference(
        model=gpt2.build(cfg), params=params,
        config={"dtype": "float32", "quant": {"enabled": True}})
    out = eng.generate(np.ones((1, 4), np.int32), max_new_tokens=2)
    assert out.shape == (1, 6)


# ------------------------------------------------------------- w8a8 under TP
# The s8-MXU kernel is opaque to GSPMD, so TP serving routes it through a
# custom_partitioning wrapper (ops/quantized_matmul._w8a8_tp_call): column
# shards (N sharded) each run the kernel on their weight slice with no
# communication; row shards (K sharded) psum a local partial.  The reference
# analog is DS-Inference's INT8 GEMMs running on module_inject-sliced
# weights (replace_module.py:25 ReplaceWithTensorSlicing).


@pytest.fixture
def _w8a8_tp():
    from deepspeed_tpu.ops import quantized_matmul as qmm_mod

    qmm_mod.configure(kernel_ok=True, w8a8_tp=True)
    yield qmm_mod
    qmm_mod.configure(kernel_ok=True, w8a8_tp=False)


def _mk_k_grouped(k, n, g, rows, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(k, n)).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(rows, k)).astype(np.float32))
    rec = quant.quantize_k_grouped(jnp.asarray(w), k_group=g)
    return x, rec


@pytest.mark.parametrize("wspec,kspec", [
    (("tp_n", (None, "tp")), (None, None, "tp")),   # column parallel
    (("tp_k", ("tp", None)), ("tp", None, None)),   # row parallel
])
def test_w8a8_tp_matches_unsharded_kernel(_w8a8_tp, wspec, kspec):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.quantized_matmul import w8a8_matmul

    _, wspec = wspec
    x, rec = _mk_k_grouped(512, 256, 128, rows=4)
    _w8a8_tp.configure(kernel_ok=True, w8a8_tp=False)
    ref = np.asarray(w8a8_matmul(x, rec), np.float32)  # unsharded kernel
    _w8a8_tp.configure(kernel_ok=True, w8a8_tp=True)

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    qk = jax.device_put(rec["qk"], NamedSharding(mesh, P(*wspec)))
    ks = jax.device_put(rec["kscale"], NamedSharding(mesh, P(*kspec)))
    xs = jax.device_put(x, NamedSharding(mesh, P()))
    out = jax.jit(
        lambda a, b, c: w8a8_matmul(a, {"qk": b, "kscale": c}))(xs, qk, ks)
    # column: same per-chunk math and accumulation order -> near-exact;
    # row: one psum reorders the f32 chunk sums
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=1e-5, atol=1e-4)


def test_w8a8_tp_misaligned_shard_still_correct(_w8a8_tp):
    """A K sharding that splits k-groups unevenly (K/G=3 blocks over tp=2)
    must degrade to a gathered-but-correct lowering, not wrong math."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.quantized_matmul import w8a8_matmul

    from deepspeed_tpu.ops import quantized_matmul as qmm_mod

    x, rec = _mk_k_grouped(384, 256, 128, rows=2)   # K/G = 3 blocks
    # reference is the UNSHARDED kernel (the replicated lowering runs the
    # same activation-quantizing math on full shapes)
    qmm_mod.configure(kernel_ok=True, w8a8_tp=False)
    ref = qmm_mod.w8a8_matmul(x, rec)
    qmm_mod.configure(kernel_ok=True, w8a8_tp=True)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    qk = jax.device_put(rec["qk"], NamedSharding(mesh, P("tp", None)))
    ks = jax.device_put(rec["kscale"], NamedSharding(mesh, P()))
    xs = jax.device_put(x, NamedSharding(mesh, P()))
    out = jax.jit(
        lambda a, b, c: w8a8_matmul(a, {"qk": b, "kscale": c}))(xs, qk, ks)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-5, atol=1e-4)


def test_w8a8_tp_engine_decode_parity():
    """init_inference(tp=2/4, w8a8) decodes the same tokens as tp=1 w8a8
    on a 128-aligned quant-aware OPT (the driver dryrun asserts the same
    parity for the bf16 auto-TP path; this covers the quantized one).
    ``shard_multiple: 4`` pins the group refinement so every tp degree
    serves bit-identical weight records (hidden K=128 refines to g=32 —
    whole groups on every row-parallel shard)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import opt as opt_model
    from deepspeed_tpu.ops import quantized_matmul as qmm_mod

    cfg = opt_model.OPTConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                              num_heads=2, hidden_size=128, ffn_size=512)
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = opt_model.build(cfg).init_fn(jax.random.PRNGKey(0))
    params = jax.device_get(params)
    ids = np.ones((1, 4), np.int32)
    outs = {}
    try:
        for tp in (1, 2, 4):
            deepspeed_tpu.comm.reset_topology()
            eng = deepspeed_tpu.init_inference(
                model=opt_model.build(cfg), params=params,
                config={"dtype": "float32",
                        "tensor_parallel": {"tp_size": tp},
                        "quant": {"enabled": True, "type": "w8a8",
                                  "shard_multiple": 4}})
            outs[tp] = eng.generate(ids, max_new_tokens=4)
    finally:
        # engine init set the module gates (kernel_ok=False at tp=2);
        # restore so later tests exercise the single-device kernel path
        qmm_mod.configure(kernel_ok=True, w8a8_tp=False)
        deepspeed_tpu.comm.reset_topology()
    np.testing.assert_array_equal(outs[1], outs[2])
    np.testing.assert_array_equal(outs[1], outs[4])


def test_w8a8_tp_engine_mixed_gathered_parity():
    """``shard_multiple: 1`` pins g=128 so the hidden-K weights (o_w,
    K=128 -> ONE quant group) cannot be K-sharded at tp=4: the engine's
    kscale divisibility fallback replicates the scale tree and
    _w8a8_partition takes the gathered-but-correct lowering for those
    weights while the column-parallel ones stay sharded — the mixed-path
    parity the refined default no longer exercises."""
    import deepspeed_tpu
    from deepspeed_tpu.models import opt as opt_model
    from deepspeed_tpu.ops import quantized_matmul as qmm_mod

    cfg = opt_model.OPTConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                              num_heads=2, hidden_size=128, ffn_size=512)
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = opt_model.build(cfg).init_fn(jax.random.PRNGKey(0))
    params = jax.device_get(params)
    ids = np.ones((1, 4), np.int32)
    outs = {}
    try:
        for tp in (1, 4):
            deepspeed_tpu.comm.reset_topology()
            eng = deepspeed_tpu.init_inference(
                model=opt_model.build(cfg), params=params,
                config={"dtype": "float32",
                        "tensor_parallel": {"tp_size": tp},
                        "quant": {"enabled": True, "type": "w8a8",
                                  "shard_multiple": 1}})
            # unrefined: o_w keeps ONE group (the gathered case at tp=4)
            assert eng.params["blocks"]["o_w"]["kscale"].shape[-3] == 1
            outs[tp] = eng.generate(ids, max_new_tokens=4)
    finally:
        qmm_mod.configure(kernel_ok=True, w8a8_tp=False)
        deepspeed_tpu.comm.reset_topology()
    np.testing.assert_array_equal(outs[1], outs[4])


def test_w8a8_engine_spec_aware_refinement():
    """With shard_multiple DERIVED from tp (the default), only K-sharded
    (row-parallel) weights refine: o_w (K=128, P(None, tp, None)) splits
    into 4 groups of 32 so tp=4 shards hold whole groups; the
    column-parallel qkv_w keeps the g=128 cap (refining it would buy
    nothing and cost scale storage + kernel trip count)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import opt as opt_model
    from deepspeed_tpu.ops import quantized_matmul as qmm_mod

    cfg = opt_model.OPTConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                              num_heads=2, hidden_size=128, ffn_size=512)
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = opt_model.build(cfg).init_fn(jax.random.PRNGKey(0))
    params = jax.device_get(params)
    try:
        deepspeed_tpu.comm.reset_topology()
        eng = deepspeed_tpu.init_inference(
            model=opt_model.build(cfg), params=params,
            config={"dtype": "float32",
                    "tensor_parallel": {"tp_size": 4},
                    "quant": {"enabled": True, "type": "w8a8"}})
        blocks = eng.params["blocks"]
        assert blocks["o_w"]["kscale"].shape[-3] == 4      # g=32, K-sharded
        assert blocks["proj_w"]["kscale"].shape[-3] == 4   # K=512, g=128 ok
        assert blocks["qkv_w"]["kscale"].shape[-3] == 1    # column: cap
        out = eng.generate(np.ones((1, 4), np.int32), max_new_tokens=4)
        assert out.shape == (1, 8)
    finally:
        qmm_mod.configure(kernel_ok=True, w8a8_tp=False)
        deepspeed_tpu.comm.reset_topology()


def test_pick_k_group_alignment():
    """pick_k_group refines groups so row-parallel shards hold whole
    groups: OPT-2.7B's K=2560 has 20 groups at the g=128 cap (20 % 8 != 0
    -> would gather at tp=8); g=80 gives 32 groups and stays sharded."""
    assert quant.pick_k_group(2560, 128) == 128
    assert quant.pick_k_group(2560, 128, shard_multiple=8) == 80
    # already aligned: keep the cap
    assert quant.pick_k_group(4096, 128, shard_multiple=8) == 128
    # K=384: 3 groups at 128; tp=2 needs an even count -> g=96 (4 groups)
    assert quant.pick_k_group(384, 128, shard_multiple=2) == 96
    # K not divisible by the shard degree: no K sharding is possible
    # anyway, so no refinement constraint applies
    assert quant.pick_k_group(384, 128, shard_multiple=7) == 128
    # nothing admissible (odd K)
    assert quant.pick_k_group(2050, 128) == 0


def test_w8a8_tp_refined_groups_stay_sharded(_w8a8_tp, monkeypatch):
    """A K=384 weight refined to g=96 (shard_multiple=2) runs the ROW-
    PARALLEL sharded lowering — no gathered-fallback warning — and matches
    the unsharded kernel."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops import quantized_matmul as qmm_mod
    from deepspeed_tpu.utils import logging as ds_logging

    gathered = []
    monkeypatch.setattr(ds_logging, "warning_once",
                        lambda msg, *a, **k: gathered.append(msg))
    g = quant.pick_k_group(384, 128, shard_multiple=2)
    assert g == 96
    x, rec = _mk_k_grouped(384, 256, g, rows=2)
    qmm_mod.configure(kernel_ok=True, w8a8_tp=False)
    ref = qmm_mod.w8a8_matmul(x, rec)
    qmm_mod.configure(kernel_ok=True, w8a8_tp=True)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    qk = jax.device_put(rec["qk"], NamedSharding(mesh, P("tp", None)))
    ks = jax.device_put(rec["kscale"], NamedSharding(mesh, P("tp", None, None)))
    xs = jax.device_put(x, NamedSharding(mesh, P()))
    out = jax.jit(
        lambda a, b, c: qmm_mod.w8a8_matmul(a, {"qk": b, "kscale": c})
    )(xs, qk, ks)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-5, atol=1e-4)
    assert not [m for m in gathered if "GATHERED" in m], gathered


def test_quantize_k_grouped_host_chunked_matches_jnp(monkeypatch):
    """The chunked numpy path (multi-billion host trees: bounds the
    transient that OOM-killed a 125GB host on OPT-13B) must produce the
    records of the jnp path bit-for-bit, without mutating the input."""
    monkeypatch.setattr(quant, "_HOST_QUANT_CHUNK_BYTES", 1024)
    rng = np.random.default_rng(3)
    w = rng.normal(size=(3, 64, 128)).astype(np.float32)
    w_orig = w.copy()
    rec_np = quant.quantize_k_grouped(w, k_group=32)       # numpy path
    rec_jnp = quant.quantize_k_grouped(jnp.asarray(w), k_group=32)
    assert isinstance(rec_np["qk"], np.ndarray)
    np.testing.assert_array_equal(w, w_orig)
    np.testing.assert_array_equal(rec_np["qk"], np.asarray(rec_jnp["qk"]))
    np.testing.assert_array_equal(rec_np["kscale"],
                                  np.asarray(rec_jnp["kscale"]))
    # bf16 host leaves (the engine casts before quantizing) also go
    # through the numpy path via ml_dtypes
    wb = np.asarray(jax.device_get(jnp.asarray(w, jnp.bfloat16)))
    rec_b = quant.quantize_k_grouped(wb, k_group=32)
    rec_bj = quant.quantize_k_grouped(jnp.asarray(wb), k_group=32)
    np.testing.assert_array_equal(rec_b["qk"], np.asarray(rec_bj["qk"]))


def test_quantize_pytree_k_grouped_shard_multiple():
    """Leaf SELECTION is shard_multiple-independent (every tp degree
    quantizes the same leaves); only the group size refines."""
    tree = {"w": jnp.ones((2560, 128)), "odd": jnp.ones((100, 128))}
    base = quant.quantize_pytree_k_grouped(tree, k_group=128)
    ref8 = quant.quantize_pytree_k_grouped(tree, k_group=128,
                                           shard_multiple=8)
    assert quant.is_k_quantized(base["w"]) and quant.is_k_quantized(ref8["w"])
    assert base["w"]["kscale"].shape[0] == 20    # g=128
    assert ref8["w"]["kscale"].shape[0] == 32    # g=80: 32 % 8 == 0
    # ineligible leaf stays dense under every shard_multiple
    assert not quant.is_k_quantized(base["odd"])
    assert not quant.is_k_quantized(ref8["odd"])


def test_w8a8_stacked_matches_per_layer():
    """The stacked (scalar-prefetch layer index) kernel returns EXACTLY the
    per-layer kernel's result for every layer, including traced indices."""
    from deepspeed_tpu.ops.quantized_matmul import (w8a8_matmul,
                                                    w8a8_matmul_stacked)

    rng = np.random.default_rng(3)
    L, K, N, G = 3, 512, 256, 128
    w = jnp.asarray(rng.standard_normal((L, K, N)), jnp.float32) * 0.05
    rec = quant.quantize_k_grouped(w, k_group=G)
    x = jnp.asarray(rng.standard_normal((1, K)), jnp.bfloat16)
    for l in range(L):
        layer = {"qk": rec["qk"][l], "kscale": rec["kscale"][l]}
        a = np.asarray(w8a8_matmul(x, layer, out_dtype=jnp.float32))
        b = np.asarray(w8a8_matmul_stacked(x, rec, jnp.int32(l),
                                           out_dtype=jnp.float32))
        np.testing.assert_array_equal(a, b)

    def body(l, acc):
        return acc + w8a8_matmul_stacked(x, rec, l, out_dtype=jnp.float32)

    tot = np.asarray(jax.lax.fori_loop(0, L, body,
                                       jnp.zeros((1, N), jnp.float32)))
    want = sum(np.asarray(w8a8_matmul(
        x, {"qk": rec["qk"][l], "kscale": rec["kscale"][l]},
        out_dtype=jnp.float32)) for l in range(L))
    np.testing.assert_allclose(tot, want, rtol=1e-5, atol=1e-5)


def test_w8a8_stacked_ineligible_falls_back():
    """Off-lane N and TP mode route the stacked call to the sliced-layer
    path (same math, no kernel)."""
    from deepspeed_tpu.ops import quantized_matmul as qmm

    rng = np.random.default_rng(4)
    L, K, N = 2, 256, 96          # N % 128 != 0 -> ineligible
    w = jnp.asarray(rng.standard_normal((L, K, N)), jnp.float32)
    rec = quant.quantize_k_grouped(w, k_group=128)
    x = jnp.asarray(rng.standard_normal((1, K)), jnp.float32)
    out = np.asarray(qmm.w8a8_matmul_stacked(x, rec, 1))
    ref = np.asarray(x @ quant.dequantize_k(
        {"qk": rec["qk"][1], "kscale": rec["kscale"][1]}, x.dtype))
    np.testing.assert_allclose(out, ref, rtol=5e-2, atol=5e-1)


def _tiny_model(family):
    if family == "opt":
        from deepspeed_tpu.models import opt as m

        cfg = m.OPTConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                          num_heads=4, hidden_size=128, ffn_size=512)
    elif family == "gpt2":
        from deepspeed_tpu.models import gpt2 as m

        cfg = m.GPT2Config(vocab_size=512, max_seq_len=64, num_layers=2,
                           num_heads=4, hidden_size=128, remat=False)
    elif family == "bloom":
        from deepspeed_tpu.models import bloom as m

        cfg = m.BloomConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                            num_heads=4, hidden_size=128)
    elif family == "gptj":
        from deepspeed_tpu.models import gptj as m

        cfg = m.GPTJConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                           num_heads=4, hidden_size=128, rotary_dim=16)
    elif family == "gptneox":
        from deepspeed_tpu.models import gptneox as m

        cfg = m.GPTNeoXConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                              num_heads=4, hidden_size=128)
    elif family == "gptneo":
        from deepspeed_tpu.models import gptneo as m

        cfg = m.GPTNeoConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                             num_heads=4, hidden_size=128, window_size=16)
    else:
        raise ValueError(family)
    return m, cfg


@pytest.mark.parametrize("family", ["opt", "gpt2", "bloom", "gptj",
                                    "gptneox"])
def test_indexed_decode_matches_scan_path(family, monkeypatch):
    """forward_cached's layer-indexed loop (quantized serving) produces the
    same tokens as the scan path (DS_INDEXED_DECODE=0 kill switch) over the
    same quantized records — the dispatch is shared (cached.decode_over_layers)
    so every quant-aware family goes through it."""
    import deepspeed_tpu

    m, cfg = _tiny_model(family)
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = m.build(cfg).init_fn(jax.random.PRNGKey(0))
    params = jax.device_get(params)
    ids = np.ones((1, 6), dtype=np.int32)
    qcfg = {"dtype": "float32", "quant": {"enabled": True, "type": "w8a8"}}

    monkeypatch.setenv("DS_INDEXED_DECODE", "1")  # ambient =0 would make
    deepspeed_tpu.comm.reset_topology()           # this test vacuous
    eng = deepspeed_tpu.init_inference(model=m.build(cfg), params=params,
                                       config=qcfg)
    tok_indexed = np.asarray(eng.generate(ids, max_new_tokens=8))

    monkeypatch.setenv("DS_INDEXED_DECODE", "0")
    deepspeed_tpu.comm.reset_topology()
    eng2 = deepspeed_tpu.init_inference(model=m.build(cfg), params=params,
                                        config=qcfg)
    tok_scan = np.asarray(eng2.generate(ids, max_new_tokens=8))
    np.testing.assert_array_equal(tok_indexed, tok_scan)


def test_indexed_decode_gate_respects_kernel_state(monkeypatch):
    """use_indexed_decode is False whenever the stacked kernel would fall
    back (TP mode, kernel off, DS_W8A8=0, unquantized blocks) — the indexed
    loop must not run without its benefit."""
    from deepspeed_tpu.models.cached import use_indexed_decode
    from deepspeed_tpu.ops import quantized_matmul as qmm
    from deepspeed_tpu.ops import quantization as quant

    w = jnp.ones((2, 256, 128), jnp.float32)
    blocks = {"qkv_w": quant.quantize_k_grouped(w, k_group=128)}
    monkeypatch.setenv("DS_INDEXED_DECODE", "1")
    monkeypatch.setenv("DS_W8A8", "1")

    try:
        qmm.configure(kernel_ok=True, w8a8_tp=False)
        assert use_indexed_decode(blocks)
        qmm.configure(kernel_ok=True, w8a8_tp=True)    # TP serving
        assert not use_indexed_decode(blocks)
        qmm.configure(kernel_ok=False, w8a8_tp=False)  # kernel unavailable
        assert not use_indexed_decode(blocks)
        qmm.configure(kernel_ok=True, w8a8_tp=False)
        monkeypatch.setenv("DS_W8A8", "0")             # w8a8 disabled
        assert not use_indexed_decode(blocks)
        monkeypatch.setenv("DS_W8A8", "1")
        assert not use_indexed_decode({"qkv_w": w})    # dense blocks
        assert use_indexed_decode(blocks, rows=8)      # batched decode
        assert not use_indexed_decode(blocks, rows=9)  # prefill/big batch
        monkeypatch.setenv("DS_INDEXED_DECODE", "0")   # kill switch
        assert not use_indexed_decode(blocks)
    finally:
        # module-global kernel state: a failed assert must not leak TP
        # mode into later tests
        qmm.configure(kernel_ok=True, w8a8_tp=False)


def test_llama_w8a8_serving(monkeypatch):
    """Llama is quant-aware (round 4): w8a8 serving decodes through the
    stacked-kernel indexed path with token parity vs the scan kill switch,
    and logits track the dense model."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=512, max_seq_len=64, num_layers=2,
                            num_heads=4, num_kv_heads=2, hidden_size=128,
                            ffn_size=256, rope_theta=10000.0, remat=False)
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = llama.build(cfg).init_fn(jax.random.PRNGKey(0))
    params = jax.device_get(params)
    ids = np.ones((1, 6), dtype=np.int32)

    deepspeed_tpu.comm.reset_topology()
    ref_eng = deepspeed_tpu.init_inference(
        model=llama.build(cfg), params=params, config={"dtype": "float32"})
    ref_tok = np.asarray(ref_eng.generate(ids, max_new_tokens=8))
    ref_logits = np.asarray(ref_eng.forward({"input_ids": ids}))

    qcfg = {"dtype": "float32", "quant": {"enabled": True, "type": "w8a8"}}
    monkeypatch.setenv("DS_INDEXED_DECODE", "1")
    deepspeed_tpu.comm.reset_topology()
    eng = deepspeed_tpu.init_inference(model=llama.build(cfg),
                                       params=params, config=qcfg)
    from deepspeed_tpu.ops import quantization as q
    recs = [x for x in jax.tree_util.tree_leaves(
        eng.params, is_leaf=q.is_k_quantized) if q.is_k_quantized(x)]
    assert recs, "llama w8a8 quantization produced no K-grouped records"
    tok = np.asarray(eng.generate(ids, max_new_tokens=8))
    logits = np.asarray(eng.forward({"input_ids": ids}))
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-1, atol=2e-1)
    assert (tok == ref_tok).mean() >= 0.75, (tok, ref_tok)

    monkeypatch.setenv("DS_INDEXED_DECODE", "0")
    deepspeed_tpu.comm.reset_topology()
    eng2 = deepspeed_tpu.init_inference(model=llama.build(cfg),
                                        params=params, config=qcfg)
    tok_scan = np.asarray(eng2.generate(ids, max_new_tokens=8))
    np.testing.assert_array_equal(tok, tok_scan)


@pytest.mark.parametrize("family", ["bloom", "gptj", "gptneox", "gptneo"])
def test_w8a8_serving_new_families(family, monkeypatch):
    """Round-4 quant-aware families: w8a8 serving decodes with logits
    tracking the dense model and mostly-agreeing greedy tokens (bloom/
    gptj/gptneox ride the shared indexed dispatch; gptneo's static
    local/global loop uses per-layer records)."""
    import deepspeed_tpu

    m, cfg = _tiny_model(family)
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = m.build(cfg).init_fn(jax.random.PRNGKey(0))
    params = jax.device_get(params)
    ids = np.ones((1, 6), dtype=np.int32)

    deepspeed_tpu.comm.reset_topology()
    ref_eng = deepspeed_tpu.init_inference(
        model=m.build(cfg), params=params, config={"dtype": "float32"})
    ref_tok = np.asarray(ref_eng.generate(ids, max_new_tokens=8))
    ref_logits = np.asarray(ref_eng.forward({"input_ids": ids}))

    monkeypatch.setenv("DS_INDEXED_DECODE", "1")
    deepspeed_tpu.comm.reset_topology()
    eng = deepspeed_tpu.init_inference(
        model=m.build(cfg), params=params,
        config={"dtype": "float32",
                "quant": {"enabled": True, "type": "w8a8"}})
    recs = [x for x in jax.tree_util.tree_leaves(
        eng.params, is_leaf=quant.is_k_quantized)
        if quant.is_k_quantized(x)]
    assert recs, f"{family}: w8a8 produced no K-grouped records"
    tok = np.asarray(eng.generate(ids, max_new_tokens=8))
    logits = np.asarray(eng.forward({"input_ids": ids}))
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-1, atol=2e-1)
    assert (tok == ref_tok).mean() >= 0.75, (tok, ref_tok)
