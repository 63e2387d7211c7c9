"""ISSUE 59: what a ``ServingEngine`` may be built as is said ONCE
(``deepspeed_tpu/inference/options.py``), and its four readers agree with it:

 (a) every ``(kind, feature)`` of ``KIND_REFUSES``: a tiny engine of that
     kind asked for that feature raises by the feature's label with the
     table's reason, and an engine built without it publishes the name;
 (b) every rule of ``EXCLUDES``: ``options.check``, the constructor (where a
     one-shard engine can reach the rule) and the autotuner's space (where a
     candidate can) refuse it with the rule's own sentence;
 (c) ``OPTIONS`` == the keywords and defaults of ``ServingEngine.__init__``
     == ``init_serving``'s == ``resolved_config()``'s keys ==
     ``space.BASE_SERVING_CONFIG``'s serving keys;
 (d) a keyword that is no option is a ``TypeError`` that names it;
 (e) ``init_serving(model, **srv.resolved_config())`` round-trips for one
     engine of each cache kind.
"""

import ast
import functools
import importlib
import inspect
import json
import os
import re
import sys

import jax
import pytest

import deepspeed_tpu
from deepspeed_tpu.autotuning import space as space_mod
from deepspeed_tpu.inference import options
from deepspeed_tpu.inference.serving import EARLY_SETTLE_CAUSES, ServingEngine
from deepspeed_tpu.models import mixtral, opt
from deepspeed_tpu.ops import decode_attention, paged_kv
from deepspeed_tpu.telemetry import trace as trace_mod

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import run as cb_run  # noqa: E402

DEFAULTS = {name: opt_.default for name, opt_ in options.OPTIONS.items()}


# ------------------------------------------------- one tiny model a kind
def _family(name, config_file):
    family = importlib.import_module(f"chipbench.families.{name}")
    config = cb_run._rehearsed(json.load(open(os.path.join(
        ROOT, "chipbench", "configs", config_file))), True)
    return family.build(config)


_EXPERTS = dict(
    vocab_size=512, max_seq_len=512, num_layers=2, num_heads=4,
    num_kv_heads=2, head_width=16, hidden_size=64, ffn_size=32,
    rope_theta=1e7, rms_eps=1e-6, qk_norm="head", num_experts=8, top_k=4,
    norm_topk_prob=True, remat=False)
#: cache kinds -> (the model, serving keywords): the shapes the families'
#: own test files serve (``test_<family>_serving.py``)
MODELS = {
    "plain": (lambda: opt.build(opt.OPTConfig(
        vocab_size=128, max_seq_len=128, num_layers=2, num_heads=4,
        hidden_size=32, ffn_size=64)), dict(block_size=8)),
    "experts": (lambda: mixtral.build(mixtral.MixtralConfig(**_EXPERTS)),
                dict(block_size=8)),
    "window": (lambda: mixtral.build(mixtral.MixtralConfig(
        vocab_size=128, max_seq_len=256, num_layers=4, num_heads=8,
        num_kv_heads=2, head_width=16, hidden_size=32, ffn_size=16,
        rope_theta=50000.0, rms_eps=1e-5, norm="layernorm",
        parallel_block=True, rope_interleaved=True,
        layer_kinds=("sliding", "sliding", "sliding", "full"),
        sliding_window=24, tie_embeddings=True, num_experts=16, top_k=4,
        router_score="sigmoid", shared_experts=2, experts_held=(4, 4),
        remat=False)), dict(block_size=8)),
    "indexer": (lambda: mixtral.build(mixtral.MixtralConfig(
        **_EXPERTS, index_heads=2, index_head_dim=16, index_topk=32)),
        dict(block_size=8)),
    "latent": (lambda: mixtral.build(mixtral.MixtralConfig(
        vocab_size=128, max_seq_len=256, num_layers=2, num_heads=4,
        num_kv_heads=4, head_width=16, hidden_size=32, ffn_size=16,
        rope_theta=10000.0, rms_eps=1e-6, rope_interleaved=True,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8,
        v_head_dim=12, num_experts=16, top_k=4, router_score="softmax",
        shared_experts=1, experts_held=(4, 4), remat=False)),
        dict(block_size=8)),
    "latent+state": (lambda: _family(
        "kimi_linear", "kimi-linear-48b-a3b.json"), dict(block_size=16)),
    "state": (lambda: _family(
        "granite_hybrid", "granite-4.0-h-micro.json"), dict(block_size=16)),
    "state only": (lambda: _family("brumby", "Brumby-14B-Base.json"), {}),
    "tails": (lambda: _family("zaya", "ZAYA1-8B.json"), dict(block_size=16)),
}
BASE = dict(slots=2, max_seq_len=64, prefill_chunk=16)


@functools.lru_cache(maxsize=None)
def _model(kind):
    """``(spec, params, serving keywords, a one-shard inference engine)``."""
    build, kw = MODELS[kind]
    spec = build()
    params = spec.init_fn(jax.random.PRNGKey(0))
    deepspeed_tpu.comm.reset_topology()
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
        params=params, device_group=0)
    return spec, params, {**BASE, **kw}, engine


@functools.lru_cache(maxsize=None)
def _published(kind):
    """``stats()`` and ``resolved_config()`` of an engine of ``kind`` built
    with nothing asked, and what its constructor was told it refuses."""
    spec, params, kw, engine = _model(kind)
    srv = ServingEngine(engine, **kw)
    try:
        return srv.stats(), srv.resolved_config(), srv._refusals
    finally:
        srv.close()


# ------------------------------------------ (a) what a kind is refused
#: how a test asks for each feature (a feature of ``options.FEATURES``
#: without an entry here fails ``test_every_feature_can_be_asked_for``)
TIERS = dict(host_blocks=8, swap_batch=2, prefix_caching=True)
ASK = {
    "prefix_caching": dict(prefix_caching=True),
    "host_blocks": TIERS,
    "nvme_blocks": dict(nvme_blocks=8, **TIERS),
    "spec_tokens": dict(spec_tokens=2),
    "a draft model": dict(spec_tokens=2, draft="self"),
    "quantize": dict(quantize="kv8"),
    "quantized weights": dict(config={"quant": {"enabled": True,
                                                "type": "int8"}}),
    "resident_window_blocks": dict(resident_window_blocks=4, **TIERS),
    "a tp mesh": dict(topology=2),
    "engine_mode": dict(engine_mode="dp_tp"),
    "sp": dict(sp=2),
}
#: the kind a model of :data:`MODELS` shows each of ``KIND_REFUSES``' kinds by,
#: and where ``stats()`` publishes what it refuses
SHOWN_BY = {"state": ("state", "kv_state"), "tails": ("tails", "kv_tails"),
            "window": ("window", "kv_kinds"),
            "indexer": ("indexer", None), "latent": ("latent", "kv_latent")}
ROWS = [(kind, name) for kind, refuses in options.KIND_REFUSES.items()
        for name in refuses]


def test_every_feature_can_be_asked_for():
    assert set(ASK) == set(options.FEATURES)
    assert set(SHOWN_BY) == set(options.KIND_REFUSES) \
        == set(options.KIND_SAYS)
    for refuses in options.KIND_REFUSES.values():
        assert set(refuses) <= set(options.FEATURES)


@pytest.mark.parametrize("kind,name", ROWS,
                         ids=[f"{k}-{n.replace(' ', '_')}" for k, n in ROWS])
def test_a_kind_refuses_each_feature_by_name_with_its_reason(kind, name):
    spec, params, kw, engine = _model(SHOWN_BY[kind][0])
    how = dict(ASK[name])
    if how.get("draft") == "self":
        how["draft"] = spec
    config = {"dtype": "fp32", **how.pop("config", {})}
    with pytest.raises(ValueError) as e:
        if set(how) & {"topology", "engine_mode", "sp"} or len(config) > 1:
            # another mesh, or other weights: an inference engine of its own
            try:
                deepspeed_tpu.init_serving(spec, config=config, params=params,
                                           **kw, **how)
            finally:
                deepspeed_tpu.comm.reset_topology()
        else:
            ServingEngine(engine, **kw, **how)
    message = str(e.value)
    label = options.FEATURES[name].label.format(**{
        **DEFAULTS, **how, "tp": 2, "dp": jax.device_count(),
        "weights": "int8"})
    assert message.startswith(options.KIND_SAYS[kind].format(
        model=spec.name)), message
    assert label in message
    why = options.KIND_REFUSES[kind][name]
    assert f"{label} ({why})" in message if why else True
    # and an engine built without it says so
    stats, _, refusals = _published(SHOWN_BY[kind][0])
    assert name in refusals[kind]
    if SHOWN_BY[kind][1]:
        assert stats[SHOWN_BY[kind][1]]["refused"] == list(
            options.KIND_REFUSES[kind])


# ------------------------------------------------ (b) what does not combine
HOST = dict(host_blocks=8, swap_batch=4)
DP_TP = dict(engine_mode="dp_tp", prefix_caching=False)
#: one construction a rule of ``options.EXCLUDES``, in the table's order,
#: that breaks it and no rule before it where it can: (options, the degrees
#: that differ from one shard's, whether the constructor reaches the rule on
#: a one-shard engine, whether a candidate of the space does)
BREAKS = [
    (dict(spec_tokens=31), {}, True, True),
    (dict(draft="a model"), {}, True, True),
    (dict(logit_masks=True, sampling=False), {}, True, True),
    # (init_serving gives a w8a8 candidate its weights: not the space's)
    (dict(quantize="w8a8"), {}, True, False),
    (dict(**DP_TP, spec_tokens=2), {"dp": 2}, True, True),
    (dict(engine_mode="dp_tp", prefix_caching=True), {"dp": 2}, True, True),
    (dict(**DP_TP, logit_masks=True), {"dp": 2}, True, True),
    (dict(**DP_TP, slots=3), {"dp": 2}, True, True),
    # (init_serving gives an sp candidate its mesh: not the space's; and a
    # one-shard engine has no sp axis, so the constructor stops at this one)
    (dict(sp=2), {"mesh_sp": 1}, True, False),
    (dict(sp=3), {"mesh_sp": 3}, False, True),
    (dict(**DP_TP, sp=2), {"dp": 2, "mesh_sp": 2}, False, True),
    (dict(sp=2, spec_tokens=2), {"mesh_sp": 2}, False, True),
    (dict(resident_window_blocks=4), {}, True, True),
    (dict(resident_window_blocks=8, **HOST, spec_tokens=2), {}, True, True),
    # (dp_tp excludes the host tier a window needs: a rule no construction
    # reaches first)
    (dict(resident_window_blocks=8, **HOST, **DP_TP), {"dp": 2}, False,
     True),
    (dict(resident_window_blocks=8, **HOST, sp=2), {"mesh_sp": 2}, False,
     True),
    (dict(resident_window_blocks=2, **HOST), {}, True, True),
    (dict(host_blocks=8, swap_batch=0), {}, True, True),
    (dict(host_blocks=4, swap_batch=8), {}, True, True),
    (dict(**HOST, prefix_caching=False), {}, True, True),
    (dict(role="prefill"), {}, True, True),
    (dict(nvme_blocks=8), {}, True, True),
    (dict(**HOST, nvme_blocks=8, nvme_high_watermark=0.2), {}, True, True),
    # (the model's own module as the proposer: the tiny engine's model has
    # none, which its constructor says first)
    (dict(draft="self", spec_tokens=1, logit_masks=True), {}, False, True),
    (dict(draft="self", spec_tokens=1, **HOST), {}, False, True),
]
ONE_SHARD = {"tp": 1, "dp": 1, "mesh_sp": 1, "weights": None}


def test_every_rule_has_a_construction_that_breaks_it():
    assert len(BREAKS) == len(options.EXCLUDES)


@pytest.mark.parametrize("index", range(len(options.EXCLUDES)), ids=[
    f"{i}-{rule.group}" for i, rule in enumerate(options.EXCLUDES)])
def test_a_rule_is_refused_with_one_sentence_by_every_reader(
        index, tiny_engine):
    rule = options.EXCLUDES[index]
    how, degrees, in_ctor, in_space = BREAKS[index]
    given = {**DEFAULTS, **BASE, "block_size": 8, "prefix_caching": True,
             **how}

    def says(degrees):
        return rule.says(options._asked(given, degrees))

    # the table itself: this rule and, where no rule stands before it,
    # check()'s error
    mesh = {**ONE_SHARD, **degrees}
    broken = list(options.violations(given, mesh))
    assert (rule.group, says(mesh)) in broken
    if broken[0] == (rule.group, says(mesh)):
        with pytest.raises(ValueError, match=re.escape(says(mesh))):
            options.check(given, mesh)
    else:
        assert not in_ctor
    if in_ctor:
        engine, _ = tiny_engine
        dp = int(dict(engine.mesh.shape).get("dp", 1)) \
            if how.get("engine_mode") == "dp_tp" else 1
        kw = {k: v for k, v in given.items()
              if k in how or k in BASE or k == "block_size"}
        with pytest.raises(ValueError, match=re.escape(
                says({**ONE_SHARD, "dp": dp}))):
            ServingEngine(engine, **kw)
    if in_space:
        space = space_mod.ServingKnobSpace(
            space_mod.ModelGeom(layers=2, kv_heads=2, head_dim=16),
            max_seq_len=64)
        cfg = {**space_mod.BASE_SERVING_CONFIG, **BASE, "block_size": 8,
               **how}
        assert (rule.group, says(space_mod._degrees(cfg))) \
            in space.check(cfg)


def test_an_option_out_of_its_range_is_refused_by_every_reader(tiny_engine):
    engine, _ = tiny_engine
    space = space_mod.ServingKnobSpace(
        space_mod.ModelGeom(layers=2, kv_heads=2, head_dim=16),
        max_seq_len=64)
    for how, sentence in [
            (dict(slots=0), "slots must be >= 1, got 0"),
            (dict(block_size=0), "block_size must be >= 1, got 0"),
            (dict(spec_tokens=-1), "spec_tokens must be >= 0, got -1"),
            (dict(engine_mode="both"),
             "engine_mode must be 'replicas' or 'dp_tp', got 'both'"),
            (dict(role="sideways"),
             "role must be 'prefill', 'decode' or 'both', got 'sideways'"),
            (dict(nvme_high_watermark=1.5),
             "nvme_high_watermark must be in (0, 1], got 1.5"),
            (dict(quantize="kv4"), "quantize='kv4' — expected one of")]:
        with pytest.raises(ValueError, match=re.escape(sentence)):
            ServingEngine(engine, **{**BASE, "block_size": 8, **how})
        (name, said), = space.check({**space_mod.BASE_SERVING_CONFIG,
                                     **BASE, **how})[:1]
        assert name == "option_ranges" and sentence in said


# --------------------------------------------- (c) one statement, four readers
def _keywords(fn):
    return {name: p.default for name, p in
            inspect.signature(fn).parameters.items()
            if p.kind is p.KEYWORD_ONLY}


def test_the_four_readers_state_the_options_table_and_nothing_else():
    assert len(options.OPTIONS) == 28 and "decode_steps" not in DEFAULTS
    assert _keywords(ServingEngine.__init__) == DEFAULTS
    assert _keywords(ServingEngine) == DEFAULTS
    entry = _keywords(deepspeed_tpu.init_serving)
    assert {entry.pop("topology"), entry.pop("device_group")} == {None}
    assert entry == DEFAULTS
    _, resolved, _ = _published("plain")
    # (a draft is a model object: the one option that is not captured)
    assert set(resolved) == set(DEFAULTS) - {"draft"} | {"topology"}
    assert list(resolved)[-1] == "topology"
    json.dumps(resolved)
    base = dict(space_mod.BASE_SERVING_CONFIG)
    assert {base.pop(k) for k in space_mod.FLEET_KNOBS} == {0, 1}
    assert base.pop("topology") == 1
    assert set(base) == set(DEFAULTS)
    # the two a plain model's None resolves to, which the formulae need
    assert {k for k in base if base[k] != DEFAULTS[k]} == {
        "block_size", "prefix_caching"}
    assert (base["block_size"], base["prefix_caching"]) == (
        paged_kv.DEFAULT_BLOCK_TOKENS, resolved["prefix_caching"])


def test_the_table_imports_nothing_of_jax_or_its_readers():
    tree = ast.parse(open(options.__file__).read())
    imported = {alias.name for node in ast.walk(tree)
                if isinstance(node, ast.Import) for alias in node.names} | {
        ("." * node.level) + (node.module or "") for node in ast.walk(tree)
        if isinstance(node, ast.ImportFrom)}
    assert imported == {"__future__", "copy", "dataclasses", "inspect",
                        "operator", "types", "typing"}
    # what it states of modules it cannot import
    assert options.VERIFY_T_MAX == decode_attention.VERIFY_T_MAX
    assert DEFAULTS["trace_capacity"] == trace_mod.DEFAULT_CAPACITY


def test_the_fused_runner_is_gone():
    assert len(EARLY_SETTLE_CAUSES) == 9 and "fused" not in EARLY_SETTLE_CAUSES
    for name in ("_run_fused_decode", "_fence_harvest", "_K"):
        assert not hasattr(ServingEngine, name)
    source = inspect.getsource(sys.modules[ServingEngine.__module__])
    for name in ("fused_core", "jit_decode_fused", "decode_fused",
                 "self._K"):
        assert name not in source
    stats, _, _ = _published("plain")
    assert "decode_steps" in stats           # the COUNTER of iterations
    assert not {"fused_iterations", "host_fence_waits"} & set(stats)
    for refuses in options.KIND_REFUSES.values():
        assert "decode_steps" not in refuses


# ------------------------------------ (d) an unknown keyword is named, loudly
@pytest.mark.parametrize("how", [dict(slots=2, decode_steps=4),
                                 dict(no_such_option=1)],
                         ids=["decode_steps", "no_such_option"])
def test_a_keyword_that_is_no_option_is_a_type_error_that_names_it(
        how, tiny):
    spec, _, engine = tiny
    unknown = (set(how) - {"slots"}).pop()
    with pytest.raises(TypeError, match=f"unexpected keyword.*{unknown}"):
        deepspeed_tpu.init_serving(spec, **how)
    # (with a config too: where it used to be dropped without a word)
    with pytest.raises(TypeError, match=f"unexpected keyword.*{unknown}"):
        deepspeed_tpu.init_serving(spec, config={"dtype": "fp32"}, **how)
    with pytest.raises(TypeError, match=f"unexpected keyword.*{unknown}"):
        ServingEngine(engine, **how)


def test_a_keyword_of_the_engine_config_still_passes(tiny):
    spec, cfg, _ = tiny
    try:
        srv = deepspeed_tpu.init_serving(spec, dtype="fp32", slots=2,
                                         max_seq_len=32)
        assert srv.engine._config.dtype == "fp32" and srv.slots == 2
        srv.close()
    finally:
        deepspeed_tpu.comm.reset_topology()


# ------------------------------------------- (e) resolved_config round-trips
@pytest.mark.parametrize("kind", list(MODELS))
def test_resolved_config_round_trips_for_an_engine_of_each_kind(kind):
    spec, params, kw, _ = _model(kind)
    _, resolved, _ = _published(kind)
    try:
        again = deepspeed_tpu.init_serving(
            spec, config={"dtype": "fp32"}, params=params, **resolved)
        assert again.resolved_config() == resolved
        again.close()
    finally:
        deepspeed_tpu.comm.reset_topology()
