"""ZeRO-Inference streamed serving (inference/zero_inference.py).

Reference parity: ZeRO-Inference — zero stage-3 ``offload_param: cpu``
driving inference-only forwards (the reference's OPT-30B-on-one-GPU
configuration).  The TPU analog keeps stacked blocks host-resident and
streams one layer at a time through the jitted KV-cache decode step;
these tests pin token-level parity against the resident engine, which is
the whole correctness contract of the streamed path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import opt as opt_model


def _tiny_cfg():
    return opt_model.OPTConfig(vocab_size=512, max_seq_len=64, num_layers=3,
                               num_heads=2, hidden_size=128, ffn_size=256)


@pytest.fixture
def _params():
    cfg = _tiny_cfg()
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        params = opt_model.build(cfg).init_fn(jax.random.PRNGKey(0))
    yield cfg, jax.device_get(params)
    deepspeed_tpu.comm.reset_topology()


def _engine(cfg, params, **zi):
    deepspeed_tpu.comm.reset_topology()
    config = {"dtype": "float32"}
    if zi:
        config["zero_inference"] = zi
    return deepspeed_tpu.init_inference(
        model=opt_model.build(cfg), params=params, config=config)


def test_streamed_matches_resident_greedy(_params):
    cfg, params = _params
    ids = np.arange(2 * 5, dtype=np.int32).reshape(2, 5) % 512
    ref = _engine(cfg, params).generate(ids, max_new_tokens=6)
    out = _engine(cfg, params, enabled=True, prefetch=2).generate(
        ids, max_new_tokens=6)
    np.testing.assert_array_equal(ref, out)


def test_streamed_matches_resident_sampling_and_eos(_params):
    cfg, params = _params
    ids = np.ones((1, 4), np.int32)
    kw = dict(max_new_tokens=5, do_sample=True, temperature=0.7, top_k=7,
              top_p=0.9, seed=123, eos_token_id=3)
    ref = _engine(cfg, params).generate(ids, **kw)
    out = _engine(cfg, params, enabled=True).generate(ids, **kw)
    np.testing.assert_array_equal(ref, out)


def test_streamed_pinned_layers_parity(_params):
    """pin_layers keeps a device-resident prefix; tokens must not change."""
    cfg, params = _params
    ids = np.ones((1, 4), np.int32)
    ref = _engine(cfg, params).generate(ids, max_new_tokens=4)
    eng = _engine(cfg, params, enabled=True, pin_layers=2, sync_every=2)
    assert eng._streamed.pin_layers == 2
    out = eng.generate(ids, max_new_tokens=4)
    np.testing.assert_array_equal(ref, out)


def test_streamed_w8a8_parity(_params):
    """Streaming int8 records (the 1 byte/param wire format) must decode
    the tokens of the RESIDENT w8a8 engine — same records, same kernels,
    different residency."""
    cfg, params = _params
    ids = np.ones((1, 4), np.int32)
    q = {"enabled": True, "type": "w8a8"}
    deepspeed_tpu.comm.reset_topology()
    ref = deepspeed_tpu.init_inference(
        model=opt_model.build(cfg), params=params,
        config={"dtype": "float32", "quant": q}).generate(
            ids, max_new_tokens=4)
    deepspeed_tpu.comm.reset_topology()
    eng = deepspeed_tpu.init_inference(
        model=opt_model.build(cfg), params=params,
        config={"dtype": "float32", "quant": q,
                "zero_inference": {"enabled": True}})
    # the streamed layers really are int8 records on the host
    from deepspeed_tpu.ops import quantization as quant
    layer0 = eng._streamed.host_layers[0]
    assert quant.is_k_quantized(layer0["qkv_w"])
    assert isinstance(layer0["qkv_w"]["qk"], np.ndarray)
    out = eng.generate(ids, max_new_tokens=4)
    np.testing.assert_array_equal(ref, out)
    from deepspeed_tpu.ops import quantized_matmul as qmm_mod
    qmm_mod.configure(kernel_ok=True, w8a8_tp=False)


def test_engine_accepts_prequantized_params(_params):
    """A tree that already carries K-grouped records (quantized checkpoint
    / 30B-scale bench init) is served as-is: no re-quantization, scales
    stay f32 through the dtype cast, tokens match the engine-quantized
    path; a record-kind/config mismatch raises."""
    cfg, params = _params
    from deepspeed_tpu.ops import quantization as quant
    from deepspeed_tpu.ops import quantized_matmul as qmm_mod

    ids = np.ones((1, 4), np.int32)
    q = {"enabled": True, "type": "w8a8"}
    try:
        deepspeed_tpu.comm.reset_topology()
        ref = deepspeed_tpu.init_inference(
            model=opt_model.build(cfg), params=params,
            config={"dtype": "bfloat16", "quant": q}).generate(
                ids, max_new_tokens=4)
        cast = jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(
                jnp.asarray(a, jnp.bfloat16)))
            if a.dtype == np.float32 else a, params)
        pre = dict(cast)
        pre["blocks"] = quant.quantize_pytree_k_grouped(
            cast["blocks"], k_group=128, min_ndim=3)
        assert pre["blocks"]["qkv_w"]["kscale"].dtype == np.float32
        deepspeed_tpu.comm.reset_topology()
        eng = deepspeed_tpu.init_inference(
            model=opt_model.build(cfg), params=pre,
            config={"dtype": "bfloat16", "quant": q})
        # scales survived the cast in f32
        assert eng.params["blocks"]["qkv_w"]["kscale"].dtype == jnp.float32
        out = eng.generate(ids, max_new_tokens=4)
        np.testing.assert_array_equal(ref, out)
        with pytest.raises(ValueError):
            deepspeed_tpu.comm.reset_topology()
            deepspeed_tpu.init_inference(
                model=opt_model.build(cfg), params=pre,
                config={"dtype": "bfloat16",
                        "quant": {"enabled": True, "type": "weight"}})
    finally:
        qmm_mod.configure(kernel_ok=True, w8a8_tp=False)
        deepspeed_tpu.comm.reset_topology()


def test_streamed_rejects_unsupported(_params):
    cfg, params = _params
    eng = _engine(cfg, params, enabled=True)
    with pytest.raises(NotImplementedError):
        eng.forward({"input_ids": np.ones((1, 4), np.int32)})
    # over-length requests fail loudly, same as the resident path
    with pytest.raises(ValueError, match="context length"):
        eng.generate(np.ones((1, 60), np.int32), max_new_tokens=10)
    with pytest.raises(ValueError):
        deepspeed_tpu.comm.reset_topology()
        deepspeed_tpu.init_inference(
            model=opt_model.build(cfg), params=params,
            config={"dtype": "float32",
                    "tensor_parallel": {"tp_size": 2},
                    "zero_inference": {"enabled": True}})
