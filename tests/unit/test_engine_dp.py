"""End-to-end engine tests: tiny GPT-2 over the 8-device CPU-sim mesh.

Model: reference tests/unit/runtime/zero/test_zero.py (stage-vs-baseline loss
parity) and tests/unit/runtime/half_precision tests.
"""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2


def tiny_model():
    return gpt2.build(gpt2.GPT2Config.tiny())


def make_batch(rng, n, seq=33, vocab=512):
    return {"input_ids": rng.integers(0, vocab, size=(n, seq)).astype(np.int32)}


def base_config(**over):
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "mesh": {},
    }
    cfg.update(over)
    return cfg


def run_steps(config, steps=5, seed=0):
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config)
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        batch = make_batch(rng, engine.train_batch_size())
        _, metrics = engine.train_batch(batch)
        losses.append(metrics["loss"])
    return engine, losses


def test_train_loss_decreases():
    _, losses = run_steps(base_config(), steps=8)
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"


@pytest.mark.parametrize("family,how", [("opt", "chosen"), ("gpt2", "given")])
def test_compiled_step_names_its_flash_kernels_and_blocks(family, how,
                                                          caplog):
    """ISSUE 35: the blocks are resolved when the step is traced, so the
    engine can say once which kernels at which blocks its program runs —
    OPT names none (the rule chooses), GPT-2 names its own."""
    import logging

    from deepspeed_tpu.models import opt
    from deepspeed_tpu.utils.logging import logger

    if family == "opt":
        cfg = opt.OPTConfig.tiny()
        cfg.use_flash = True
        spec = opt.build(cfg)
    else:
        cfg = gpt2.GPT2Config.tiny()
        cfg.use_flash = True
        spec = gpt2.build(cfg)
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec,
                                               config=base_config())
    assert engine.flash_choices == {}               # nothing traced yet
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            for _ in range(2):                      # the second call: no trace
                engine.train_batch(make_batch(np.random.default_rng(0),
                                              engine.train_batch_size()))
    finally:
        logger.removeHandler(caplog.handler)
    (choice, calls), = engine.flash_choices["train_step"].items()
    assert (choice.q_len, choice.kv_len, choice.how) == (32, 32, how)
    assert (choice.block_q, choice.block_k) == (32, 32) and calls >= 1
    lines = [r.getMessage() for r in caplog.records
             if "flash attention" in r.getMessage()]
    assert len(lines) == 1, lines                   # once, at the compile
    assert f"blocks 32 x 32 ({how})" in lines[0]
    assert "train_step: flash attention v2 (flash_fwd_resident + " \
        "flash_bwd_fused)" in lines[0]
    # ISSUE 62: the strip and what the kernels compute for what the mask
    # lets through — S = 32 in one 32 x 32 tile: no strip, 528 of 1,024
    from deepspeed_tpu.ops import flash_attention as fa
    assert (choice.strip, choice.window) == (0, 0)
    assert fa.computed_pairs(choice) == (528, 1024)
    assert ", whole tiles: 528 visible of 1,024 computed pairs a head " \
        "(51.6 %)" in lines[0]
    snap = engine.metrics.prometheus_text()
    labels = f'{{mode="v2_{how}",phase="train_step"}}'
    for side in "qk":
        assert f'train_flash_block_{side}{labels} 32' in snap, snap
    assert f'train_flash_strip{labels} 0' in snap, snap
    assert f'train_flash_computed_share{labels} 51.5625' in snap, snap


def test_train_batches_matches_per_step():
    """k steps via one train_batches dispatch == k train_batch calls."""
    deepspeed_tpu.comm.reset_topology()
    engine_a, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(),
                                                 config=base_config())
    rng = np.random.default_rng(7)
    batches = [make_batch(rng, engine_a.train_batch_size())
               for _ in range(4)]
    for b in batches:
        _, m_a = engine_a.train_batch(b)

    deepspeed_tpu.comm.reset_topology()
    engine_b, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(),
                                                 config=base_config())
    _, m_b = engine_b.train_batches(batches)

    assert engine_b.global_steps == engine_a.global_steps == 4
    np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]),
                               rtol=1e-5)
    pa = jax.tree_util.tree_leaves(engine_a.state["params"])
    pb = jax.tree_util.tree_leaves(engine_b.state["params"])
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_unrolled_layers_match_scan():
    """cfg.scan_layers=False is numerically identical to the scan path."""
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    ids = np.arange(2 * 17, dtype=np.int32).reshape(2, 17) % cfg.vocab_size
    logits_scan = gpt2.forward(cfg, params, ids, train=False)
    cfg_u = gpt2.GPT2Config.tiny()
    cfg_u.scan_layers = False
    logits_unroll = gpt2.forward(cfg_u, params, ids, train=False)
    np.testing.assert_allclose(np.asarray(logits_scan),
                               np.asarray(logits_unroll),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_zero_stage_matches_baseline(stage):
    _, base_losses = run_steps(base_config(), steps=4)
    _, z_losses = run_steps(
        base_config(zero_optimization={"stage": stage}), steps=4)
    np.testing.assert_allclose(base_losses, z_losses, rtol=2e-4, atol=1e-5)


def test_zero3_small_params_stay_persistent(eight_devices):
    """Default stage3_param_persistence_threshold (1e5, reference
    ``parameter_offload.py:316``) keeps tiny params replicated."""
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=tiny_model(), config=base_config(zero_optimization={"stage": 3}))
    qkv = engine.state["params"]["blocks"]["qkv_w"]  # 24k elems < 1e5
    assert qkv.addressable_shards[0].data.size == qkv.size


def test_zero3_state_is_sharded(eight_devices):
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=tiny_model(), config=base_config(zero_optimization={
            "stage": 3, "stage3_param_persistence_threshold": 0}))
    qkv = engine.state["params"]["blocks"]["qkv_w"]
    # 8-way dp: each device holds 1/8 of the tensor
    shard_size = qkv.addressable_shards[0].data.size
    assert shard_size == qkv.size // 8
    m = engine.state["opt_state"]
    leaves = [x for x in jax.tree_util.tree_leaves(m)
              if x.ndim > 0 and x.size > 8]
    assert leaves, "no optimizer moment buffers found"
    for leaf in leaves:
        assert leaf.addressable_shards[0].data.size < leaf.size


def test_gradient_accumulation_equivalence():
    # gas=2 with half micro-batch == gas=1 with full batch (same global batch)
    _, l1 = run_steps(base_config(train_micro_batch_size_per_gpu=2,
                                  gradient_accumulation_steps=1), steps=3)
    _, l2 = run_steps(base_config(train_micro_batch_size_per_gpu=1,
                                  gradient_accumulation_steps=2), steps=3)
    np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=1e-5)


def test_micro_step_shims():
    """The reference-style forward/backward/step loop trains equivalently."""
    deepspeed_tpu.comm.reset_topology()
    config = base_config(gradient_accumulation_steps=2)
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config)
    rng = np.random.default_rng(0)
    for i in range(2):
        for g in range(2):
            batch = make_batch(rng, engine.micro_batch_global())
            loss = engine.forward(batch)
            engine.backward(loss)
            if engine.is_gradient_accumulation_boundary():
                engine.step()
    assert engine.global_steps == 2
    assert engine.micro_steps == 4


def test_bf16_training():
    _, losses = run_steps(base_config(bf16={"enabled": True}), steps=5)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_fp16_dynamic_loss_scale():
    deepspeed_tpu.comm.reset_topology()
    config = base_config(fp16={"enabled": True, "initial_scale_power": 8})
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config)
    rng = np.random.default_rng(0)
    for _ in range(3):
        _, metrics = engine.train_batch(make_batch(rng, engine.train_batch_size()))
    assert metrics["loss_scale"] == 256.0
    assert engine.loss_scale() == 256.0


def test_tp_mesh_training(eight_devices):
    """tp=2 x dp=4: model-parallel matmuls + data-parallel grads, same loss.

    train_batch_size is pinned so both runs consume identical global batches
    (micro-batch per chip derives to 1 vs 2)."""
    _, base_losses = run_steps(base_config(train_batch_size=8,
                                           train_micro_batch_size_per_gpu=None,
                                           gradient_accumulation_steps=None), steps=3)
    _, tp_losses = run_steps(base_config(train_batch_size=8,
                                         train_micro_batch_size_per_gpu=None,
                                         gradient_accumulation_steps=None,
                                         mesh={"tp": 2}), steps=3)
    np.testing.assert_allclose(base_losses, tp_losses, rtol=2e-4, atol=1e-5)


def test_dataloader_path():
    deepspeed_tpu.comm.reset_topology()
    rng = np.random.default_rng(1)
    data = [{"input_ids": rng.integers(0, 512, size=(33,)).astype(np.int32)}
            for _ in range(64)]
    engine, _, loader, _ = deepspeed_tpu.initialize(
        model=tiny_model(), config=base_config(), training_data=data)
    assert loader is not None
    _, metrics = engine.train_batch()  # pulls from its own loader
    assert np.isfinite(metrics["loss"])


def test_checkpoint_save_load_resume(tmp_path):
    deepspeed_tpu.comm.reset_topology()
    config = base_config()
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config)
    rng = np.random.default_rng(0)
    for _ in range(2):
        engine.train_batch(make_batch(rng, engine.train_batch_size()))
    engine.save_checkpoint(str(tmp_path), client_state={"note": "hi"})

    deepspeed_tpu.comm.reset_topology()
    engine2, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config=config)
    path, client_state = engine2.load_checkpoint(str(tmp_path))
    assert path is not None
    assert client_state == {"note": "hi"}
    assert engine2.global_steps == 2
    # resumed state trains identically to continuing the original
    batch = make_batch(np.random.default_rng(9), engine.train_batch_size())
    _, m1 = engine.train_batch(batch)
    _, m2 = engine2.train_batch(batch)
    np.testing.assert_allclose(m1["loss"], m2["loss"], rtol=1e-5)


def test_checkpoint_elastic_reshard(tmp_path):
    """Save under zero-3 sharding, load under zero-0 (replicated) — the orbax
    restore reshards: this is the universal-checkpoint capability (SURVEY §5.4)."""
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=tiny_model(), config=base_config(zero_optimization={"stage": 3}))
    rng = np.random.default_rng(0)
    engine.train_batch(make_batch(rng, engine.train_batch_size()))
    engine.save_checkpoint(str(tmp_path))

    deepspeed_tpu.comm.reset_topology()
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=tiny_model(), config=base_config())
    engine2.load_checkpoint(str(tmp_path))
    batch = make_batch(np.random.default_rng(5), engine.train_batch_size())
    _, m1 = engine.train_batch(batch)
    _, m2 = engine2.train_batch(batch)
    np.testing.assert_allclose(m1["loss"], m2["loss"], rtol=2e-4, atol=1e-5)
