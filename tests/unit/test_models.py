"""Model zoo tests: llama + mixtral E2E on the CPU-sim mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import get_model, gpt2, llama, mixtral


def make_batch(rng, n, seq=33, vocab=512):
    return {"input_ids": rng.integers(0, vocab, size=(n, seq)).astype(np.int32)}


def run(model, config, steps=4, seed=0):
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        _, m = engine.train_batch(make_batch(rng, engine.train_batch_size()))
        losses.append(m["loss"])
    return engine, losses


def base_config(**over):
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }
    cfg.update(over)
    return cfg


def test_gpt2_fused_ce_matches_checkpointed_head():
    """fused_ce computes identical loss AND grads to the lse head,
    including -100 label masking."""
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, (4, 33)).astype(np.int32)
    labels = ids[:, 1:].copy()
    labels[0, :5] = -100
    batch = {"input_ids": ids[:, :-1], "labels": labels}

    cfg.fused_ce = False
    l_ref, g_ref = jax.value_and_grad(
        lambda p: gpt2.loss_from_batch(cfg, p, batch, train=False))(params)
    cfg2 = gpt2.GPT2Config.tiny()
    cfg2.fused_ce = True
    cfg2.ce_chunks = 4
    l_f, g_f = jax.value_and_grad(
        lambda p: gpt2.loss_from_batch(cfg2, p, batch, train=False))(params)
    np.testing.assert_allclose(float(l_ref), float(l_f), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_llama_rope_rotation_identity():
    cfg = llama.LlamaConfig.tiny()
    cos, sin = llama.rope_angles(cfg, 8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, cfg.head_dim))
    rotated = llama.apply_rope(x, cos, sin)
    # norms preserved by rotation
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(rotated, axis=-1)),
        np.asarray(jnp.linalg.norm(x, axis=-1)), rtol=1e-5)
    # position 0 is unrotated
    np.testing.assert_allclose(np.asarray(rotated[:, :, 0]),
                               np.asarray(x[:, :, 0]), rtol=1e-6)


def overfit(model, config, steps=6, seed=0):
    """Train repeatedly on ONE fixed batch — loss must drop well below the
    uniform-token entropy floor (ln V), which fresh random batches can't."""
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    batch = make_batch(np.random.default_rng(seed), engine.train_batch_size())
    losses = []
    for _ in range(steps):
        _, m = engine.train_batch(batch)
        losses.append(m["loss"])
    return engine, losses


def test_llama_trains():
    _, losses = overfit(llama.build(llama.LlamaConfig.tiny()), base_config(),
                        steps=8)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.05, f"no overfit progress: {losses}"


def test_llama_zero3_tp():
    _, base = run(llama.build(llama.LlamaConfig.tiny()),
                  base_config(train_batch_size=8,
                              train_micro_batch_size_per_gpu=None))
    _, z3 = run(llama.build(llama.LlamaConfig.tiny()),
                base_config(train_batch_size=8,
                            train_micro_batch_size_per_gpu=None,
                            zero_optimization={"stage": 3}, mesh={"tp": 2}))
    np.testing.assert_allclose(base, z3, rtol=3e-4, atol=1e-4)


def test_llama_pipeline():
    _, base = run(llama.build(llama.LlamaConfig.tiny()),
                  base_config(train_batch_size=16,
                              train_micro_batch_size_per_gpu=None,
                              gradient_accumulation_steps=2))
    _, pp = run(llama.build(llama.LlamaConfig.tiny()),
                base_config(train_batch_size=16,
                            train_micro_batch_size_per_gpu=None,
                            gradient_accumulation_steps=2, mesh={"pp": 2}))
    np.testing.assert_allclose(base, pp, rtol=3e-4, atol=1e-4)


def test_mixtral_trains_with_ep():
    cfg = base_config(train_batch_size=8, train_micro_batch_size_per_gpu=None,
                      zero_optimization={"stage": 2}, mesh={"ep": 4})
    _, losses = overfit(mixtral.build(mixtral.MixtralConfig.tiny()), cfg,
                        steps=8)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.05, f"no overfit progress: {losses}"


def test_mixtral_experts_sharded(eight_devices):
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mixtral.build(mixtral.MixtralConfig.tiny()),
        config=base_config(mesh={"ep": 4}))
    w1 = engine.state["params"]["blocks"]["experts_w1"]  # [L, E, d, f]
    assert w1.addressable_shards[0].data.shape[1] == 1  # 4 experts / ep=4


def test_mixtral_matches_hf():
    """HF MixtralForCausalLM ingestion: the inference forward routes
    droplessly (``moe/routed.py``: softmax over all experts, top-2,
    renormalised), which is HF's top-2 expert mixing — no capacity to set."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")

    cfg = transformers.MixtralConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, rope_theta=10000.0,
        attention_dropout=0.0)
    with torch.no_grad():
        hf = transformers.MixtralForCausalLM(cfg)
    hf.eval()
    spec, params = deepspeed_tpu.module_inject.replace_module(hf_model=hf)
    assert spec.model_config.norm_topk_prob and spec.model_config.top_k == 2
    assert not hasattr(spec.model_config, "eval_capacity_factor")
    ids = np.random.default_rng(0).integers(2, 96, (2, 12)).astype(np.int32)
    ours = np.asarray(spec.apply_fn(params, {"input_ids": ids}))
    with torch.no_grad():
        theirs = hf(torch.tensor(ids.astype(np.int64))).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=5e-3, rtol=5e-3)


def test_get_model_registry():
    assert get_model("gpt2", **{"vocab_size": 128, "max_seq_len": 32,
                                "num_layers": 1, "num_heads": 2,
                                "hidden_size": 32}) is not None
    with pytest.raises(ValueError):
        get_model("nonexistent-model")


def test_llama_mixtral_bf16_keeps_activation_dtype():
    """bf16 compute must stay bf16 through rope/MoE (scan carries need a
    fixed dtype; fp32 promotion also silently halves MXU throughput)."""
    for mod, cfg in ((llama, llama.LlamaConfig.tiny()),
                     (mixtral, mixtral.MixtralConfig.tiny())):
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, params)
        ids = jnp.zeros((1, 9), jnp.int32)
        loss = mod.loss_from_batch(cfg, params, {"input_ids": ids})
        assert np.isfinite(float(loss)), mod.__name__
        # Direct dtype check: logits must come out bf16, not fp32-promoted.
        if mod is llama:
            logits = mod.forward(cfg, params, ids)
        else:
            logits = mod.forward_with_aux(cfg, params, ids)[0]
        assert logits.dtype == jnp.bfloat16, (mod.__name__, logits.dtype)


def test_llama_mixtral_bf16_train(eight_devices):
    for model in (llama.build(llama.LlamaConfig.tiny()),
                  mixtral.build(mixtral.MixtralConfig.tiny())):
        _, losses = run(model, base_config(bf16={"enabled": True},
                                           zero_optimization={"stage": 2}),
                        steps=3)
        assert np.isfinite(losses).all()
