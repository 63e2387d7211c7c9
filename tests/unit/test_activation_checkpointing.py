"""activation_checkpointing config block -> model remat selection.

Reference behavior: ``deepspeed.checkpointing.configure`` consumes the
``activation_checkpointing`` json block (checkpointing.py:749).  Here the
engine maps it onto the model's ``remat`` / ``remat_policy`` /
``remat_offload`` knobs (runtime/remat.py) before the first trace.
"""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime.remat import remat_policy


def _cfg(extra=None):
    c = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }
    if extra:
        c.update(extra)
    return c


def _batch(vocab, engine, s=33):
    rng = np.random.default_rng(0)
    return {"input_ids": rng.integers(
        0, vocab, size=(engine.train_batch_size(), s)).astype(np.int32)}


def _fresh_model():
    deepspeed_tpu.comm.reset_topology()
    cfg = gpt2.GPT2Config.tiny()
    assert cfg.remat is False
    return cfg, gpt2.build(cfg)


def test_config_switches_remat_on():
    cfg, model = _fresh_model()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config=_cfg({"activation_checkpointing": {"enabled": True,
                                                  "policy": "dots"}}))
    assert cfg.remat is True
    assert cfg.remat_policy == "dots"
    _, m = engine.train_batch(_batch(cfg.vocab_size, engine))
    assert np.isfinite(float(m["loss"]))


def test_reference_keys_switch_remat_on():
    # a reference-style block with only partition_activations set must
    # still enable checkpointing (no silent no-op)
    cfg, model = _fresh_model()
    deepspeed_tpu.initialize(
        model=model,
        config=_cfg({"activation_checkpointing":
                     {"partition_activations": True}}))
    assert cfg.remat is True


def test_absent_block_leaves_model_alone():
    cfg, model = _fresh_model()
    deepspeed_tpu.initialize(model=model, config=_cfg())
    assert cfg.remat is False


def test_loss_parity_with_and_without_remat():
    cfg, model = _fresh_model()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=_cfg())
    batch = _batch(cfg.vocab_size, engine)
    _, m0 = engine.train_batch(batch)

    cfg2, model2 = _fresh_model()
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=model2,
        config=_cfg({"activation_checkpointing": {"enabled": True}}))
    _, m1 = engine2.train_batch(batch)
    # remat changes scheduling, not math
    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=2e-5)


def test_cpu_checkpointing_offload_single_device():
    # cpu_checkpointing -> host offload of saved residuals.  XLA's SPMD
    # partitioner rejects the placement custom-calls under a >1-device
    # mesh, so offload is honored single-device (the engine gates it);
    # here: model-level grad parity with the offload policy active.
    cfg = gpt2.GPT2Config.tiny()
    cfg.remat, cfg.remat_policy, cfg.remat_offload = True, "dots", True
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 33)).astype(np.int32)}
    g_off = jax.jit(jax.grad(
        lambda p: gpt2.loss_from_batch(cfg, p, batch)))(params)
    cfg.remat_offload = False
    g_dev = jax.jit(jax.grad(
        lambda p: gpt2.loss_from_batch(cfg, p, batch)))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_off),
                    jax.tree_util.tree_leaves(g_dev)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_cpu_checkpointing_gated_on_mesh():
    # on the 8-device sim the engine must keep remat but drop the offload
    cfg, model = _fresh_model()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config=_cfg({"activation_checkpointing": {"enabled": True,
                                                  "policy": "dots",
                                                  "cpu_checkpointing": True}}))
    assert cfg.remat is True
    assert cfg.remat_offload is False
    _, m = engine.train_batch(_batch(cfg.vocab_size, engine))
    assert np.isfinite(float(m["loss"]))


def test_policy_resolution():
    from deepspeed_tpu.runtime import remat

    # GPT-2's dots_flash composes from THE rule's own object, as before it
    # composed from a save_only_these_names of the same two names
    for offload in (False, True):
        both = remat_policy("dots_flash", offload=offload)
        cells = [c.cell_contents for c in both.__closure__]
        assert (remat.KEEP_FLASH in cells) == (not offload)
    assert remat_policy(None) is None
    assert remat_policy("full") is None
    assert remat_policy("dots") is not None
    assert remat_policy("dots_flash") is not None
    assert remat_policy("dots", offload=True) is not None
    with pytest.raises(ValueError):
        remat_policy("bogus")


# ---------------------------------------------------------------------------
# ISSUE 49: what a checkpointed block keeps (runtime/remat.py KEEP_FLASH)
# ---------------------------------------------------------------------------
def _opt(use_flash):
    from deepspeed_tpu.models import opt

    cfg = opt.OPTConfig.tiny(max_seq_len=2048)
    cfg.remat, cfg.use_flash = True, use_flash
    # the training cell's 2,048 tokens: the chunked generation, no padding
    return opt.build(cfg), (1, 2049), (cfg.hidden_size, cfg.num_heads,
                                       cfg.head_dim)


def _llama(use_flash):
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    cfg.remat, cfg.use_flash = True, use_flash
    # a row a device of the tests' mesh: the kernel sits inside a shard_map
    return llama.build(cfg), (8, 65), (cfg.hidden_size, cfg.num_heads,
                                       cfg.head_dim)


def _smallthinker(use_flash):
    from deepspeed_tpu.models import mixtral
    from tests.unit.test_smallthinker_training import SEQ, tiny

    cfg = tiny(use_flash=use_flash)
    return mixtral.build(cfg), (2, SEQ + 1), (cfg.hidden_size, cfg.num_heads,
                                              cfg.head_dim)


FAMILIES = {"opt": _opt, "llama": _llama, "smallthinker": _smallthinker}


def _program(family, use_flash=True):
    """(loss of the parameters, parameters) of a tiny checkpointed model."""
    spec, ids_shape, widths = FAMILIES[family](use_flash)
    params = spec.init_fn(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), ids_shape, 0, 128)

    def loss(p):
        out = spec.loss_fn(p, {"input_ids": ids}, None, True)
        return out[0] if isinstance(out, tuple) else out

    return loss, params, ids_shape, widths


def _kernels(jaxpr, found=None):
    """Every ``pallas_call`` of a jaxpr by name, the sub-programs' included."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
            continue                        # not into the kernel's own body
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernels(sub, found)
    return found


@pytest.mark.parametrize("family", FAMILIES)
def test_a_checkpointed_block_keeps_its_flash_kernels_output(family,
                                                             monkeypatch):
    """The flash forward stands in the step ONCE (the bare checkpoint re-ran
    it in the backward to rebuild ``o`` and ``lse``); every other kernel, the
    loss and every gradient leaf are the bare checkpoint's; the block keeps
    its input + ``flash_out`` + ``flash_lse`` and nothing else."""
    from deepspeed_tpu.runtime import remat

    deepspeed_tpu.comm.reset_topology()
    loss, params, (b, s), (d, h, hd) = _program(family)
    got = {}
    for rule in ("kept", "bare"):
        if rule == "bare":                # the parent's jax.checkpoint(block)
            monkeypatch.setattr(remat, "KEEP_FLASH", None)
        with remat.listen() as calls:
            jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params)
        got[rule] = (_kernels(jaxpr.jaxpr), remat.kept(calls),
                     jax.jit(jax.value_and_grad(loss))(params))
        assert ("shard_map" in str(jaxpr)) == (family == "llama")
    kernels, bare = got["kept"][0], got["bare"][0]
    forward = {k: n for k, n in kernels.items() if k.startswith("flash_fwd")}
    assert forward and set(kernels) == set(bare), (kernels, bare)
    for name, n in kernels.items():
        # the bare checkpoint: once in the forward, once more in the backward
        assert bare[name] == (2 * n if name in forward else n), (kernels, bare)
    if family == "smallthinker":
        assert {"moe_gmm", "moe_gmm_dlhs", "moe_gmm_drhs"} <= set(kernels)

    (l_kept, g_kept), (l_bare, g_bare) = got["kept"][2], got["bare"][2]
    assert float(l_kept) == float(l_bare)
    for (path, a), w in zip(jax.tree_util.tree_leaves_with_path(g_kept),
                            jax.tree_util.tree_leaves(g_bare)):
        assert np.array_equal(np.asarray(a), np.asarray(w)), \
            jax.tree_util.keystr(path)

    tokens, item = b * (s - 1), 4         # float32 parameters here
    for rule, names in (("kept", {"flash_out": tokens * h * hd * item}),
                        ("bare", {})):
        for kept in got[rule][1]:
            assert kept.input == tokens * d * item and kept.other == 0
            named = dict(kept.named)
            # the resident kernels' backward rebuilds the row sums: no lse
            lse = named.pop("flash_lse", None)
            assert named == names, (rule, kept)
            assert lse in (None, tokens * h * 4), kept
        assert got[rule][1], rule
    chunked = "flash_fwd_chunked" in kernels
    assert chunked == (family == "opt")
    assert all(("flash_lse" in dict(k.named)) == chunked
               for k in got["kept"][1])


@pytest.mark.parametrize("family", FAMILIES)
def test_a_block_without_a_flash_kernel_is_the_parents_program(family,
                                                               monkeypatch):
    """``use_flash=False`` (the CPU default, the dense masked attention):
    no name to keep, so the block keeps its input alone and the gradient
    program is the bare checkpoint's, but for the policy's own parameter."""
    import re

    from deepspeed_tpu.runtime import remat

    deepspeed_tpu.comm.reset_topology()
    loss, params, (b, s), (d, _, _) = _program(family, use_flash=False)
    with remat.listen() as calls:
        text = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert calls
    for kept in remat.kept(calls):
        assert (kept.what, kept.bytes) == ("input", b * (s - 1) * d * 4), kept
    assert "pallas_call" not in text or family == "smallthinker"  # moe_gmm
    assert "flash_" not in text
    monkeypatch.setattr(remat, "KEEP_FLASH", None)
    bare = str(jax.make_jaxpr(jax.grad(loss))(params))
    rule = r"policy=<function save_only_these_names\.<locals>\.policy at \w+>"
    assert len(re.findall(rule, text)) >= 1 and not re.findall(rule, bare)
    assert re.sub(rule, "policy=None", text) == bare
