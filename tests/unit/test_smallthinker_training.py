"""ISSUE 47: SmallThinker's block TRAINS through the normal path — dropless
top-k routing on a held share, a window inside flash attention, the router
fed the attention's normed input, ReGLU experts — and the system is held to
``chipbench/reference_smallthinker.py`` on logits, loss and every leaf
group's gradient; the four shares of one expert layer add up to the uncut
layer, outputs and gradients; the engine carries the model's record out of
the step."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_smallthinker as ref
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.moe import routed

#: the reference's view of the tiny model below (2 periods, window < sequence,
#: 7 query heads a KV head, top-3 of 8 with 4 held from id 2)
CONFIG = {"num_attention_heads": 7, "num_key_value_heads": 1, "head_dim": 8,
          "rms_norm_eps": 1e-6, "rope_theta": 1e4, "sliding_window_size": 12,
          "moe_num_active_primary_experts": 3, "experts_first": 2,
          "sliding_window_layout": [0, 1, 1, 1] * 2}
SEQ = 40


def tiny(**over):
    return mixtral.MixtralConfig(**{**dict(
        vocab_size=128, max_seq_len=64, num_layers=8, num_heads=7,
        num_kv_heads=1, head_width=8, hidden_size=56, ffn_size=32,
        rope_theta=1e4, rms_eps=1e-6,
        layer_kinds=("full", "sliding", "sliding", "sliding"),
        sliding_window=12, num_experts=8, top_k=3, router_input="attn",
        ffn_act="relu", capacity_factor=None, router_aux_loss_coef=0.001,
        experts_held=(2, 4), remat=True, use_flash=True), **over})


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    spec = mixtral.build(cfg)
    params = spec.init_fn(jax.random.PRNGKey(0))
    # the seeded N(0, 0.02) weights leave the router's scores nearly even:
    # spread them, so that a wrong router input moves the top-k sets
    params["blocks"]["gate_w"] = params["blocks"]["gate_w"] * 20.0
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ + 1), 0, 128)
    return cfg, spec, params, tokens


def test_logits_are_the_references(model):
    cfg, spec, params, tokens = model
    got = spec.apply_fn(params, tokens[:, :-1])
    want = ref.logits(CONFIG, params, tokens[:, :-1])
    assert got.shape == want.shape == (2, SEQ, 128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    hidden, chosen = mixtral.forward_hidden(cfg, params, tokens[:, :-1])
    assert chosen.shape == (8, 2, SEQ, 3)
    forced, report = ref.logits(CONFIG, params, tokens[:, :-1],
                                forced={"experts": chosen})
    assert report["experts"] == 1.0 and report["expert_gap"] == 0.0
    np.testing.assert_allclose(hidden @ params["lm_head"], forced,
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v])
def test_every_shortcut_of_the_reference_moves_the_logits(model, variant):
    _, spec, params, tokens = model
    got = spec.apply_fn(params, tokens[:, :-1])
    short = ref.logits(CONFIG, params, tokens[:, :-1], variant=variant)
    assert float(jnp.abs(got - short).max()) > 1e-3, variant


def test_loss_record_and_every_leafs_gradient_are_the_references(model):
    cfg, spec, params, tokens = model
    row = tokens[:1]                     # the balance term is a row's

    def program(p):
        return spec.loss_fn(p, {"input_ids": row}, None, True)

    (loss, record), grads = jax.value_and_grad(program, has_aux=True)(params)
    want, parts = ref.next_token_loss(CONFIG, params, row, report=True)
    assert abs(float(loss) - float(want)) < 2e-6
    assert abs(float(record["lm_loss"]) - parts["lm_loss"]) < 2e-6
    assert abs(float(record["router_aux"]) - parts["router_aux"]) < 2e-6
    assert float(record["expert_rows"]) == parts["expert_rows"]
    assert float(record["expert_rows"] + record["expert_rows_absent"]) \
        == 8 * SEQ * 3
    assert set(record) == {"experts_touched", "expert_rows",
                           "expert_rows_max", "expert_rows_absent",
                           "lm_loss", "router_aux"}
    want_grads = jax.grad(lambda p: ref.next_token_loss(CONFIG, p, row))(
        params)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == 13               # embed, head, final norm, 10 a block
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-8, \
            (jax.tree_util.keystr(path), float(jnp.abs(g - w).max()), scale)


def test_flash_and_dense_paths_train_alike(model):
    cfg, _, params, tokens = model
    losses = []
    for use_flash in (True, False):
        spec = mixtral.build(dataclasses.replace(cfg, use_flash=use_flash))
        (loss, _), g = jax.value_and_grad(
            lambda p: spec.loss_fn(p, {"input_ids": tokens}, None, True),
            has_aux=True)(params)
        losses.append((loss, g))
    assert abs(float(losses[0][0] - losses[1][0])) < 1e-6
    for a, b in zip(*(jax.tree_util.tree_leaves(g) for _, g in losses)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


def test_four_shares_add_up_to_the_uncut_layer_outputs_and_gradients():
    """One expert layer: the partial outputs of the four shares of 16
    experts add up to the uncut layer's, and so do their gradients with
    respect to the layer's input, the router's input and the router."""
    t, d, f, e, k = 48, 32, 24, 16, 6
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    y, h, ct = (jax.random.normal(ks[i], (t, d)) for i in (0, 1, 2))
    gate = jax.random.normal(ks[3], (d, e))
    w1, w3 = (jax.random.normal(ks[i], (e, d, f)) * 0.2 for i in (4, 5))
    w2 = jax.random.normal(ks[6], (e, f, d)) * 0.2

    def layer(held):
        sl = slice(None) if held is None else slice(held[0], sum(held))

        def out(y, h, gate):
            return routed.routed_ffn(y, gate, w1[sl], w3[sl], w2[sl], k,
                                     True, held=held, act="relu",
                                     router_x=h)[0]

        o, vjp = jax.vjp(out, y, h, gate)
        return (o,) + vjp(ct)

    whole = layer(None)
    shares = [layer((first, 4)) for first in range(0, e, 4)]
    for i, name in enumerate(("output", "d input", "d router input",
                              "d router")):
        total = sum(s[i] for s in shares)
        np.testing.assert_allclose(total, whole[i], atol=5e-5, rtol=1e-4,
                                   err_msg=name)
    assert float(jnp.abs(whole[2]).max()) > 0      # the router does learn


def _program_hash(spec, cfg):
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 17), jnp.int32)
    text = jax.jit(lambda p, i: jax.grad(
        lambda p: spec.loss_fn(p, {"input_ids": i}, None, True))(p)).lower(
        params, ids).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_new_fields_at_their_defaults_change_no_program():
    """``router_input`` / ``ffn_act`` named at their defaults, and a
    capacity named as Mixtral's, lower Mixtral's training step to the text
    it lowers to with none of them named; the inference forward of a
    dropless configuration is the one it has with a capacity."""
    base = mixtral.MixtralConfig.tiny()
    named = dataclasses.replace(base, router_input="ffn", ffn_act="silu",
                                capacity_factor=1.25)
    assert _program_hash(mixtral.build(base), base) \
        == _program_hash(mixtral.build(named), named)
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    texts = []
    for cfg in (base, dataclasses.replace(base, capacity_factor=None)):
        spec = mixtral.build(cfg)
        params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
        texts.append(jax.jit(spec.apply_fn).lower(params, ids).as_text())
    assert texts[0] == texts[1]


def test_what_dropless_training_does_not_build_raises_by_name(model):
    cfg, _, params, tokens = model
    for over, word in (({"shared_experts": 1}, "shared experts"),
                       ({"router_score": "sigmoid"}, "sigmoid")):
        spec = mixtral.build(dataclasses.replace(cfg, **over))
        with pytest.raises(NotImplementedError, match=word):
            jax.eval_shape(lambda p: spec.loss_fn(
                p, {"input_ids": tokens}, None, True),
                spec.init_fn(jax.random.PRNGKey(0)))
    gated = mixtral.build(dataclasses.replace(cfg, capacity_factor=1.25))
    with pytest.raises(NotImplementedError, match="capacity_factor=None"):
        jax.eval_shape(lambda p: gated.loss_fn(
            p, {"input_ids": tokens}, None, True), params)
    with pytest.raises(ValueError, match="router_input"):
        tiny(router_input="mlp")
    with pytest.raises(ValueError, match="ffn_act"):
        tiny(ffn_act="gelu")


def test_the_published_configuration():
    cfg = mixtral.MixtralConfig.smallthinker_21b_a3b()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.ffn_size, cfg.num_experts, cfg.top_k) \
        == (52, 2560, 28, 4, 128, 768, 64, 6)
    assert cfg.layer_kinds == ("full", "sliding", "sliding", "sliding")
    assert cfg.sliding_window == 4096 and cfg.dropless
    assert (cfg.router_input, cfg.ffn_act) == ("attn", "relu")
    share = dataclasses.replace(cfg, num_layers=4, vocab_size=37984,
                                experts_held=(0, 16))
    assert share.num_params() == 656_529_920


@pytest.mark.parametrize("gas", [1, 2])
def test_engine_carries_the_models_record_out_of_the_step(gas):
    import deepspeed_tpu
    from deepspeed_tpu import comm

    comm.reset_topology()
    cfg = tiny(num_layers=4, vocab_size=64)
    engine, *_ = deepspeed_tpu.initialize(
        model=mixtral.build(cfg),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": gas, "steps_per_print": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}})
    rows = engine.train_batch_size()
    ids = np.random.default_rng(0).integers(0, 64, (rows, SEQ + 1),
                                            dtype=np.int32)
    _, first = engine.train_batch({"input_ids": ids})
    record = {k: float(v) for k, v in first["model"].items()}
    # summed over the micro-batches: every pair of every layer is counted
    assert record["expert_rows"] + record["expert_rows_absent"] \
        == rows * 4 * SEQ * 3
    assert abs(record["lm_loss"] + 0.001 * record["router_aux"]
               - gas * float(first["loss"])) < 1e-4 * gas
    gauges = engine.metrics.snapshot()
    assert "train_model_expert_rows" in gauges
    _, second = engine.train_batch({"input_ids": ids})
    assert float(second["loss"]) < float(first["loss"])
    comm.reset_topology()


@pytest.mark.parametrize("use_flash", [False, True])
def test_engine_says_what_a_checkpointed_block_keeps(use_flash, monkeypatch,
                                                     caplog):
    """ISSUE 49: beside the flash kernels a compiled step got, the engine
    records what each checkpointed block keeps for its backward — the input
    alone without a flash kernel (the step re-runs its attention), the input
    and the kernel's two named outputs with one — in ``remat_kept``, one log
    line and the ``train_remat_kept_bytes`` gauge."""
    import logging

    import deepspeed_tpu
    from deepspeed_tpu import comm
    from deepspeed_tpu.utils.logging import logger

    # the chunked generation at this length: the one with an lse to keep
    monkeypatch.setenv("DS_FLASH_V2", "0")
    monkeypatch.setenv("DS_FLASH_V3_MIN_KV", "8")
    comm.reset_topology()
    cfg = tiny(num_layers=4, vocab_size=64, use_flash=use_flash)
    engine, *_ = deepspeed_tpu.initialize(
        model=mixtral.build(cfg),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}})
    rows = engine.train_batch_size()
    ids = np.random.default_rng(0).integers(0, 64, (rows, SEQ + 1),
                                            dtype=np.int32)
    assert engine.remat_kept == {}                  # nothing traced yet
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            for _ in range(2):                      # the second call: no trace
                engine.train_batch({"input_ids": ids})
    finally:
        logger.removeHandler(caplog.handler)
    (kept, calls), = engine.remat_kept["train_step"].items()
    tokens = rows * SEQ
    stream = tokens * cfg.hidden_size * 4           # float32 parameters here
    assert kept.block == "_moe_block" and calls == 4    # one period's layers
    assert kept.input == stream and kept.other == 0
    want = {"flash_out": tokens * cfg.num_heads * cfg.head_dim * 4,
            "flash_lse": tokens * cfg.num_heads * 4} if use_flash else {}
    assert dict(kept.named) == want
    what = "input+flash_lse+flash_out" if use_flash else "input"
    assert kept.what == what
    lines = [r.getMessage() for r in caplog.records
             if "a checkpointed block" in r.getMessage()]
    assert len(lines) == 1, lines                   # once, at the compile
    assert f"keeps input {stream:,} B" in lines[0]
    assert ("flash_out" in lines[0]) == ("flash_lse" in lines[0]) == use_flash
    assert (f'train_remat_kept_bytes{{mode="{what}",phase="train_step"}} '
            f'{float(kept.bytes)}') in engine.metrics.prometheus_text()
    # one full and one sliding resolution, as before the record was made
    assert len(engine.flash_choices["train_step"]) == (2 if use_flash else 0)
    comm.reset_topology()


def test_a_scalar_loss_has_no_record():
    import deepspeed_tpu
    from deepspeed_tpu import comm
    from deepspeed_tpu.models import gpt2

    comm.reset_topology()
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2.build(gpt2.GPT2Config.tiny()),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}})
    ids = np.random.default_rng(0).integers(
        0, 64, (engine.train_batch_size(), 17), dtype=np.int32)
    _, metrics = engine.train_batch({"input_ids": ids})
    assert "model" not in metrics and float(metrics["loss"]) > 0
    comm.reset_topology()
