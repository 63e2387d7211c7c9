"""ISSUE 60: ZeRO-3's layer loop, software-pipelined
(``runtime/zero/liveness.py scan_layers_prefetched``) — what ``overlap_comm``
means.  The 8-device CPU mesh: the programs' text, their numbers against the
plain scan, what the backward keeps, and the collectives' count
(``runtime/zero/collectives.py``).  The compile for a described chip is in
``test_chip_lowering.py``."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2, llama, mixtral, opt
from deepspeed_tpu.parallel.topology import (MeshTopology,
                                             normalize_mesh_config)
from deepspeed_tpu.runtime import engine as engine_mod
from deepspeed_tpu.runtime.zero import collectives, liveness
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.telemetry import trace as trace_mod

LAYERS = 4


def _smallthinker():
    # the routed training cell's shape (tests/unit/test_smallthinker_
    # training.py), its attention through XLA
    return mixtral.MixtralConfig(
        vocab_size=128, max_seq_len=64, num_layers=8, num_heads=7,
        num_kv_heads=1, head_width=8, hidden_size=56, ffn_size=32,
        rope_theta=1e4, rms_eps=1e-6,
        layer_kinds=("full", "sliding", "sliding", "sliding"),
        sliding_window=12, num_experts=8, top_k=3, router_input="attn",
        ffn_act="relu", capacity_factor=None, router_aux_loss_coef=0.001,
        experts_held=(2, 4), remat=True, use_flash=False)


def _family(name, **over):
    if name == "smallthinker":
        return mixtral.build(_smallthinker())
    module, config = {"opt": (opt, opt.OPTConfig.tiny()),
                      "gpt2": (gpt2, gpt2.GPT2Config.tiny()),
                      "llama": (llama, llama.LlamaConfig.tiny())}[name]
    over = {"num_layers": LAYERS, **over}
    if name == "opt":
        over.setdefault("remat", True)
    return module.build(dataclasses.replace(config, **over))


def _engine(monkeypatch, model, stage=3, devices=8, mesh=None, **zero):
    """An engine on the first ``devices`` CPU devices, one layer a scan
    step (the tiny layers would all fit one prefetch bucket), its matrices
    gathered a layer a step and its vectors whole, as at the published
    widths (a tiny model's matrices are under the real threshold)."""
    deepspeed_tpu.comm.reset_topology()
    monkeypatch.setattr(liveness, "_GATHER_WHOLE_BELOW", 64)
    monkeypatch.setattr(
        engine_mod, "topology_from_config",
        lambda cfg: MeshTopology(devices=jax.devices()[:devices],
                                 **normalize_mesh_config(cfg)))
    config = {"train_micro_batch_size_per_gpu": 1,
              "gradient_accumulation_steps": 1,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": stage,
                                    "stage3_prefetch_bucket_size": 1,
                                    "stage3_param_persistence_threshold": 0,
                                    **zero}}
    if mesh:
        config["mesh"] = mesh
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    return engine


def _step_text(engine, seq=17):
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (1, engine.train_batch_size(), seq), jnp.int32,
        sharding=engine._batch_sharding(True, None))}
    with engine.mesh:
        return engine._train_step_fn.lower(
            engine.state, batch, engine._dropout_rng).as_text()


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# ---------------------------------------------------------------- (a) texts
@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_overlap_comm_resolves_by_stage(stage):
    """The reference's default: true for stage 3, false below; a given value
    stands."""
    assert DeepSpeedZeroConfig(stage=stage).overlap_comm is (stage == 3)
    assert DeepSpeedZeroConfig(stage=stage, overlap_comm=False) \
        .overlap_comm is False
    assert DeepSpeedZeroConfig(stage=stage, overlap_comm=True) \
        .overlap_comm is True


#: SHA-256 (16 hex) of ``_step_text`` on the PARENT of ISSUE 60 (9c9fead),
#: where nothing read ``overlap_comm``: tiny 4-layer models, stage 3 over 8
#: CPU devices, one layer a scan step, no persistent parameter.
PARENT_STAGE3 = {"opt": "85b543781cbabb18", "gpt2": "bc7f0863d94b0fc3",
                 "llama": "6ad1410c452494ab"}
#: the same on ONE device at stage 0: the one-chip training cells' shape
PARENT_ONE_CHIP = {"gpt2": "5d689407156d9d68", "opt": "584efe07af177dfb",
                   "smallthinker": "aa5b6816fd5e0df0"}


@pytest.mark.parametrize("family", sorted(PARENT_STAGE3))
def test_overlap_off_is_the_parents_program(family, monkeypatch):
    """``overlap_comm: false`` keeps the program it replaces, text for text
    (OPT's too, which joins the helper its siblings called); the default at
    stage 3 is another program, with the pipelined loop in it."""
    off = _engine(monkeypatch, _family(family), overlap_comm=False)
    assert off.model_spec.model_config.scan_prefetch is None
    assert _sha(_step_text(off)) == PARENT_STAGE3[family]
    on = _engine(monkeypatch, _family(family))
    assert isinstance(on.model_spec.model_config.scan_prefetch,
                      liveness.LayerShardings)
    assert _sha(_step_text(on)) != PARENT_STAGE3[family]


@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("family", sorted(PARENT_ONE_CHIP))
def test_one_chip_stage0_programs_are_the_parents(family, overlap,
                                                  monkeypatch):
    """The control cells (one chip, stage 0, no collective) are untouched:
    the step lowers to the parent's text with either value."""
    engine = _engine(monkeypatch, _family(family), stage=0, devices=1,
                     overlap_comm=overlap)
    assert getattr(engine.model_spec.model_config, "scan_prefetch",
                   None) is None
    assert _sha(_step_text(engine)) == PARENT_ONE_CHIP[family]


def test_a_zero_world_of_one_device_takes_the_plain_scan(monkeypatch):
    """Stage 3 with nothing to gather from: the helper is the plain scan."""
    engine = _engine(monkeypatch, _family("opt"), devices=1)
    assert engine.model_spec.model_config.scan_prefetch is None


def test_opt_reads_the_liveness_keys_and_the_cell_scans_one_layer():
    """OPT's training forward had bypassed ``scan_layers_grouped``: the
    bucket and live-parameter keys did nothing for it.  The four-chip
    cell's own numbers: 50.36 M elements a layer against the default
    5e7-element bucket is ONE layer a scan step — the cell's program
    depends on it."""
    cfg = opt.OPTConfig(vocab_size=50272, max_seq_len=2048, num_layers=24,
                        num_heads=32, hidden_size=2048, ffn_size=8192)
    blocks = jax.eval_shape(
        lambda: opt.build(cfg).init_fn(jax.random.PRNGKey(0)))["blocks"]
    layers, per_layer = liveness.blocks_param_count(blocks)
    assert (layers, per_layer) == (24, 50_358_272)
    assert liveness.stage3_group_size(
        DeepSpeedZeroConfig(stage=3), per_layer, layers) == 1
    assert liveness.stage3_group_size(
        DeepSpeedZeroConfig(stage=3, stage3_prefetch_bucket_size=int(2e8),
                            stage3_max_live_parameters=int(1e9)),
        per_layer, layers) == 3


def test_opt_groups_its_layers_by_the_bucket(monkeypatch):
    engine = _engine(monkeypatch, _family("opt"),
                     stage3_prefetch_bucket_size=int(5e7))
    assert engine.model_spec.model_config.scan_group_size == LAYERS


# ------------------------------------------------- (b) against the plain scan
def _grads(engine, batch):
    micro = jax.tree_util.tree_map(
        lambda x: x[0], engine._shard_batch(
            engine._reshape_global_batch(batch), leading_gas_dim=True))
    with engine.mesh:
        return engine._micro_grads_fn(
            engine.state["params"], engine.state["scaler"], micro,
            engine._dropout_rng, 0)


def _assert_same_three_steps(monkeypatch, family, dp, tp, group, **over):
    """Loss and every gradient leaf of the pipelined loop against the plain
    scan's, three optimizer steps on one batch (the tolerance of
    ``test_stage3_grouped_scan_loss_parity``)."""
    engines = []
    for overlap in (False, True):
        engine = _engine(monkeypatch, _family(family, **over),
                         devices=dp * tp, mesh={"tp": tp} if tp > 1 else None,
                         overlap_comm=overlap)
        mc = engine.model_spec.model_config
        mc.scan_group_size = group
        if overlap and mc.scan_prefetch is None:
            # a ZeRO world of one device: the engine takes the plain scan;
            # the helper is still held to it
            assert dp == 1
            mc.scan_prefetch = engine._blocks_shardings(("blocks",))
        assert (mc.scan_prefetch is not None) == overlap
        engines.append(engine)
    plain, piped = engines
    batch = {"input_ids": np.random.default_rng(7).integers(
        0, 512, (plain.train_batch_size(), 17)).astype(np.int32)}
    for _ in range(3):
        for engine in engines:
            engine.model_spec.model_config.scan_group_size = group
        want_loss, want = _grads(plain, batch)
        got_loss, got = _grads(piped, batch)
        np.testing.assert_allclose(got_loss, want_loss, rtol=2e-4)
        scale = max(float(jnp.abs(g).max())
                    for g in jax.tree_util.tree_leaves(want))
        for (path, w), g in zip(
                jax.tree_util.tree_leaves_with_path(want),
                jax.tree_util.tree_leaves(got)):
            assert g.sharding == w.sharding, path
            np.testing.assert_allclose(
                g, w, rtol=2e-4, atol=2e-4 * scale,
                err_msg=jax.tree_util.keystr(path))
        losses = [float(e.train_batch(batch)[1]["loss"]) for e in engines]
        np.testing.assert_allclose(losses[1], losses[0], rtol=2e-4)


@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("dp,tp", [(1, 1), (2, 1), (4, 1), (4, 2)])
def test_prefetched_scan_matches_the_plain_scan(dp, tp, remat, group,
                                                monkeypatch):
    _assert_same_three_steps(monkeypatch, "llama", dp, tp, group,
                             remat=remat)


@pytest.mark.parametrize("family", ["opt", "gpt2"])
def test_prefetched_scan_matches_the_plain_scan_in_each_family(
        family, monkeypatch):
    """GPT-2's carry holds a layer counter beside the stream (an integer
    the loop threads and does not differentiate)."""
    _assert_same_three_steps(monkeypatch, family, 4, 1, 1)


# ------------------------------------------------- (c) what the backward keeps
@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("family", ["opt", "gpt2", "llama"])
def test_the_backward_keeps_no_gathered_stack(family, remat, monkeypatch):
    """A scan differentiated by JAX would keep its carry — each layer's
    GATHERED weights, the whole bf16 model a chip — as a residual.  The
    residuals of the differentiated loss hold no array of a stacked weight's
    shape but the (sharded) parameters themselves."""
    engine = _engine(monkeypatch, _family(family, remat=remat), devices=4)
    spec = engine.model_spec
    assert spec.model_config.scan_prefetch is not None
    params = jax.eval_shape(lambda: spec.init_fn(jax.random.PRNGKey(0)))
    batch = {"input_ids": jnp.zeros((4, 17), jnp.int32)}

    def loss(p):
        out = spec.loss_fn(p, batch, jax.random.PRNGKey(1), True)
        return out[0] if isinstance(out, tuple) else out

    with engine.mesh:
        closed, (_, residuals) = jax.make_jaxpr(
            lambda p: jax.linearize(loss, p), return_shape=True)(params)
    jaxpr = closed.jaxpr
    kept = jaxpr.outvars[len(jaxpr.outvars)
                         - len(jax.tree_util.tree_leaves(residuals)):]
    stacked = {tuple(leaf.shape)
               for leaf in jax.tree_util.tree_leaves(params["blocks"])
               if leaf.ndim >= 3}
    assert stacked
    held = [v for v in kept if v not in jaxpr.invars
            and tuple(v.aval.shape) in stacked]
    assert not held, [v.aval for v in held]
    # the residuals do hold a layer's worth a layer: the stream's stack
    assert any(v.aval.shape[:1] == (LAYERS,) for v in kept
               if v not in jaxpr.invars)


# ---------------------------------------------------------------- (d) count
SCHEDULED = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p: bf16[8,4]) -> bf16[8,16] {
  %p = bf16[8,4]{1,0} parameter(0)
  ROOT %all-gather.1 = bf16[8,16]{1,0} all-gather(%p), channel_id=1, dimensions={1}, frontend_attributes={chain_id="0"}
}

%async_collective_fusion.2 (q: bf16[8,16]) -> bf16[8,16] {
  %q = bf16[8,16]{1,0} parameter(0)
  ROOT %all-gather.2 = bf16[8,16]{1,0} all-gather(%q), channel_id=1, dimensions={1}, frontend_attributes={chain_id="0"}
}

%all-reduce-scatter (r: bf16[8,16]) -> bf16[8,4] {
  %r = bf16[8,16]{1,0} parameter(0)
  ROOT %all-reduce.9 = bf16[8,16]{1,0} all-reduce(%r), channel_id=5, to_apply=%add
}

%body (c: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {
  %c = (s32[], bf16[8,4]) parameter(0)
  %w = bf16[8,4]{1,0} get-tuple-element(%c), index=1
  %start = bf16[8,16]{1,0} fusion(%w), kind=kCustom, calls=%fused_computation.1
  %done = bf16[8,16]{1,0} fusion(%start), kind=kCustom, calls=%async_collective_fusion.2
  %all-gather.3 = bf16[8,16]{1,0} all-gather(%w), channel_id=2, dimensions={1}, frontend_attributes={async_collective_name="all-gather-start.1"}
  %rs = bf16[8,4]{1,0} fusion(%done), kind=kCustom, calls=%all-reduce-scatter
  %all-to-all.1 = bf16[8,4]{1,0} all-to-all(%rs), channel_id=3, dimensions={0}
  ROOT %t = (s32[], bf16[8,4]) tuple(%i, %all-to-all.1)
}

%cond (c: (s32[], bf16[8,4])) -> pred[] {
  %c = (s32[], bf16[8,4]) parameter(0)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: bf16[8,4]) -> bf16[8,16] {
  %a = bf16[8,4]{1,0} parameter(0)
  %all-gather-start.7 = (bf16[8,4], bf16[8,16]) all-gather-start(%a), channel_id=4, dimensions={1}
  %all-gather-done.7 = bf16[8,16]{1,0} all-gather-done(%all-gather-start.7)
  %loop = (s32[], bf16[8,4]) while(%init), condition=%cond, body=%body
  ROOT %all-gather.8 = bf16[8,16]{1,0} all-gather(%a), channel_id=6, dimensions={1}
}
"""


def test_collectives_are_counted_from_a_scheduled_text():
    """A fused chain (one, whatever carries its ``chain_id``), a plain gather
    turned back from asynchronous, a reduce-scatter in the TPU's fused form
    and an all-to-all inside the ``while`` body; a started pair and a plain
    gather outside it."""
    found = collectives.count(SCHEDULED)
    assert found["all-gather"] == {"total": 4, "in_loop": 2, "fused": 1,
                                   "started": 0, "plain": 1}
    assert found["reduce-scatter"] == {"total": 1, "in_loop": 1, "fused": 0,
                                       "started": 0, "plain": 1}
    assert found["all-to-all"] == {"total": 1, "in_loop": 1, "fused": 0,
                                   "started": 0, "plain": 1}
    assert found["all-reduce"]["total"] == 0
    assert collectives.line(found).startswith(
        "all-gather 4 (2 in a loop: 1 fused, 0 started, 1 plain); ")
    assert collectives.line(collectives.count("")) == "none"


def test_engine_counts_the_collectives_of_its_compiled_step(monkeypatch,
                                                            caplog):
    """``engine.collectives``: read once, at the step's first call, from the
    text of the step compiled for that call's arguments — the CPU mesh's
    compiler makes nothing asynchronous, so each layer's gathers sit plain
    in the two loops — with the program still built ONCE."""
    import logging

    from deepspeed_tpu.utils.logging import logger

    # a start-up ring of this test's own: the process's holds every engine
    # an earlier test of this worker built
    trace_mod._KEPT.pop("setup", None)
    trace_mod.setup_timeline()
    engine = _engine(monkeypatch, _family("opt"), devices=4)
    assert engine.collectives == {}                  # nothing compiled yet
    batch = {"input_ids": np.random.default_rng(3).integers(
        0, 512, (engine.train_batch_size(), 17)).astype(np.int32)}
    logger.propagate = True
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            engine.train_batch(batch)
    finally:
        logger.propagate = False
    found = engine.collectives["train_step"]
    assert set(found) == set(collectives.KINDS)
    gathers = found["all-gather"]
    assert gathers["in_loop"] == gathers["plain"] >= 2      # both loops
    assert gathers["total"] > gathers["in_loop"]
    assert found["all-to-all"]["in_loop"] == 0
    line, = [r.getMessage() for r in caplog.records
             if "train_step: collectives " in r.getMessage()]
    assert f"all-gather {gathers['total']} (" in line
    text = engine.metrics.prometheus_text()
    assert ('train_collectives_in_loop_plain{mode="all-gather",'
            f'phase="train_step"}} {gathers["plain"]}') in text
    built = engine.setup_report()["programs"]["train_step"]
    assert built["compile_s"] > 0
    compiles = [e for e in trace_mod.kept("setup")
                .events() if e["name"] == "compile"
                and e.get("args", {}).get("program") == "train_step"]
    assert len(compiles) == 1, compiles
    engine.train_batch(batch)
    assert engine.sentry.retraces_observed == 0


def test_a_step_on_one_device_is_not_read(monkeypatch):
    engine = _engine(monkeypatch, _family("gpt2"), stage=0, devices=1)
    batch = {"input_ids": np.zeros((1, 17), np.int32)}
    engine.train_batch(batch)
    assert all(row["total"] == 0
               for row in engine.collectives["train_step"].values())
