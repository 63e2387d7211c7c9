"""``family: smallthinker`` (PR 47): the configuration file against the
catalog row and the cut it states, the family's counts by hand, the cell's
files against the issue's table, its rehearsal, the six new readers on
hand-countable traces and counters, the controls, and the benchmark's
entries — every entry looked up BY NAME, so that the next cell does not turn
this red."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import costs, families, reference_smallthinker  # noqa: E402
from chipbench import run as cb_run  # noqa: E402
from chipbench.drivers import train_routed  # noqa: E402

CELL = "smallthinker-train-8k"
NAME = "smallthinker-21b-a3b"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("routed_train_mfu", "expert_train_ms", "expert_train_roofline",
       "window_flash_ms", "window_flash_roofline", "expert_rows_per_expert")
JOINED = ("train_tok_s", "train_dispatch_ms", "peak_hbm.train",
          "device_idle.train", "train_step_ms", "flash_share.train")


def _config(rehearse=False):
    data = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                       NAME + ".json")))
    return cb_run._rehearsed(data, rehearse)


# ------------------------------------------------------- the configuration
def test_configuration_states_the_cut_and_the_published_counts():
    data = _config()
    assert data["reduced"] == ["depth", "moe_num_primary_experts",
                               "vocab_size"]
    assert (data["depth"], data["num_hidden_layers"]) == (4, 52)
    assert (data["moe_num_primary_experts"],
            data["moe_num_primary_experts_published"],
            data["experts_first"]) == (16, 64, 0)
    assert (data["vocab_size"], data["vocab_size_published"]) \
        == (37984, 151936)
    # the floors: one whole period, 8 experts, an eighth of the vocabulary
    assert data["depth"] % 4 == 0
    assert data["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert data["moe_num_primary_experts"] >= 8
    assert data["vocab_size"] * 8 >= data["vocab_size_published"]
    # no width moved
    assert (data["hidden_size"], data["num_attention_heads"],
            data["num_key_value_heads"], data["head_dim"],
            data["moe_ffn_hidden_size"],
            data["moe_num_active_primary_experts"],
            data["sliding_window_size"]) == (2560, 28, 4, 128, 768, 6, 4096)
    assert data["family"] == "smallthinker" and data["dtype"] == "bf16"
    for key in ("depth", "moe_num_primary_experts", "vocab_size",
                "router_input", "router_aux_loss_coef", "sliding_window_size",
                "rope_layout", "dense_feed_forward_width", "weights", "data"):
        assert key in data["assumed"], key
    assert "thirteen" in data["deployment"]
    tiny = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", NAME + ".json")))["rehearse"]
    # the rehearsal keeps 7 query heads a KV head and a window under its
    # sequence
    assert tiny["num_attention_heads"] // tiny["num_key_value_heads"] == 7
    mix = json.load(open(os.path.join(ROOT, "chipbench", "traffic",
                                      "train-8k.json")))
    assert tiny["sliding_window_size"] < mix["rehearse"]["seq_len"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_file_holds_the_catalog_rows_numbers():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "SmallThinker-21BA3B-Instruct")
    data = _config()
    assert data["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in data["reduced"]:
            assert data[key + "_published"] == value, key
        else:
            assert data[key] == value, key
    assert data["num_hidden_layers"] == row["layers"]
    assert len(data["rope_layout"]) == len(data["sliding_window_layout"]) \
        == 52


# -------------------------------------------------------------------- counts
def test_counts_of_the_configuration_by_hand():
    cfg = _config()
    fam = families.load(cfg)
    assert costs.num_params(cfg) == 656_529_920
    layer = 2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64      # 21,135,360
    expert = 3 * 2560 * 768                                    # 5,898,240
    assert costs.num_params(cfg) == 2 * 37984 * 2560 + 4 * (
        layer + 2 * 2560 + 16 * expert) + 2560
    # S = 8,192: a sliding query sees min(p + 1, 4096) keys, a full one p + 1
    band = 4096 * 4097 // 2 + 4096 * 4096
    triangle = 8192 * 8193 // 2
    assert fam.visible_pairs(cfg, 8192) == 3 * band + triangle
    assert band / 8192 == 3072.25 and triangle / 8192 == 4096.5
    want = 6 * (4 * layer + 2560 * 37984 + 4 * 1.5 * expert) \
        + 12 * 3584 * (3 * 3072.25 + 4096.5)
    assert fam.train_flops_per_token(cfg, 8192) == pytest.approx(want)
    assert 1.87e9 < want < 1.89e9                  # the issue's 1.88 GFLOP
    # S = W: every layer sees the whole triangle, the window does nothing
    assert fam.visible_pairs(cfg, 4096) == 4 * (4096 * 4097 // 2)
    assert fam.train_flops_per_token(cfg, 4096) == pytest.approx(
        6 * (4 * layer + 2560 * 37984 + 6 * expert)
        + 12 * 3584 * 4 * 2048.5)
    # the step's own rows in place of even routing
    assert fam.train_flops_per_token(cfg, 8192, held_pairs=4.0) \
        == pytest.approx(want - 6 * 2 * expert)
    assert fam.expert_train_flops(cfg, 1000) == 18 * 1000 * 2560 * 768
    assert fam.flash_train_flops(cfg, 8192, 4) \
        == 14 * 3584 * 4 * (3 * band + triangle)
    assert costs.arch(cfg)["window"] == 4096


def test_family_meets_the_contract():
    fam = families.load(_config())
    for fn in families.REQUIRED:
        assert callable(getattr(fam, fn)), fn
    spec = fam.build(_config(True), {"remat": True, "use_flash": False})
    cfg = spec.model_config
    assert (cfg.top_k, cfg.experts_held, cfg.router_input, cfg.ffn_act) \
        == (3, (0, 4), "attn", "relu")
    assert cfg.layer_kinds == ("full", "sliding", "sliding", "sliding")
    assert cfg.dropless and cfg.num_experts == 16
    with pytest.raises(ValueError, match="no field"):
        fam.build(_config(True), {"remat_policy": "dots"})


def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    assert spec["traffic"]["kind"] == "train_routed"
    assert (spec["traffic"]["seq_len"], spec["traffic"]["tokens_per_step"]) \
        == (8192, 32768)
    ds = spec["sizing"]["ds_config"]
    assert ds["train_micro_batch_size_per_gpu"] == 1
    assert ds["zero_optimization"] == {"stage": 0}
    assert ds["bf16"] == {"enabled": True}
    assert ds["optimizer"] == {"type": "Adam", "params": {"lr": 0.0001}}
    assert spec["sizing"]["model"] == {"remat": True, "use_flash": True}
    # the issue's optimizer as it stands: a constant rate, no scheduler
    assert set(ds) == {"train_micro_batch_size_per_gpu", "optimizer", "bf16",
                       "zero_optimization"}
    assert "memory_analysis" in spec["sizing"]["note"] \
        or "compile" in spec["sizing"]["note"]
    assert {m["name"] for m in spec["end_to_end"]} == {"train_tok_s",
                                                       "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == set(NEW) | (
        set(JOINED) - {"train_tok_s"})
    at = train_routed.positions(8192, 4096)
    assert len(at) == 384 and at[0] == 0 and at[-1] == 8191
    assert sum(4032 <= p < 4160 for p in at) == 128


# ---------------------------------------------------------------- rehearsal
def _run(args, tmp_path, module=False):
    # one CPU device, as the cell has one chip (the suite's own processes
    # have eight)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    head = [sys.executable, "-m", "chipbench.drivers.train_routed"] \
        if module else [sys.executable,
                        os.path.join(ROOT, "chipbench", "run.py")]
    return subprocess.run(head + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.limit(600)
def test_rehearsal_of_the_cell_is_correct(tmp_path):
    proc = _run(["--workload", CELL, "--seed", "2147483999", "--seconds",
                 "2", "--trace", "1", "--rehearse"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    # on the CPU there is no device trace: the counter's metric is there
    assert result["metrics"]["expert_rows_per_expert"]["value"] > 0
    detail = json.loads(next(
        line for line in proc.stdout.splitlines()
        if line.startswith("chipbench: detail ")).split("detail ", 1)[1])
    c = detail["counters"]
    assert (c["gas"], c["seq_len"], c["rows_per_step"]) == (4, 64, 4)
    # every pair of every layer of every micro-batch is counted
    assert c["expert_rows"] + c["expert_rows_absent"] \
        == pytest.approx(4 * 4 * 64 * 3)
    verdict = c["comparison"]
    assert verdict["ok"] and verdict["experts"] == 1.0
    assert len(verdict["grad_rel_err_by_leaf"]) == 13
    assert verdict["grad_rel_err"] < 1e-4 and verdict["program_fell"] > 0
    assert verdict["update_rel_err"] < 1e-3
    assert abs(verdict["expert_rows"] - verdict["reference_expert_rows"]) \
        <= verdict["expert_rows_tolerance"]
    first = _run(["--workload", CELL, "--seed", "2147483999", "--seconds",
                  "1", "--trace", "0", "--rehearse"], tmp_path)
    line = json.loads(first.stdout.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"setup_s", "train_tok_s"}


@pytest.mark.limit(900)
def test_controls_each_shortcut_is_refused_by_the_comparison(tmp_path):
    proc = _run(["--workload", CELL, "--seed", "2147483999", "--rehearse"],
                tmp_path, module=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1] == {"controls_held": True}
    assert [(c["variant"], c["ok"]) for c in lines[:-1]] == [
        (v, v is None) for v in reference_smallthinker.VARIANTS]


#: a fault planted in the cell's own engine (one CPU device, the rehearsal's
#: sizes), then the driver's comparison: its verdict as a JSON line
PLANT = """
import argparse, dataclasses, json, sys
import jax, jax.numpy as jnp, numpy as np
import deepspeed_tpu
from chipbench import costs, traffic
from chipbench import run as cb
from chipbench.drivers import train_routed

fault = sys.argv[1]
job = cb.Job(argparse.Namespace(seed=2147483999, seconds=0.0, rehearse=True,
                                trace=0, keep_trace=None),
             cb.load_cell("smallthinker-train-8k", True))
# the second of the comparison's two rows, as ``train_routed.build`` draws it
rng = np.random.default_rng(traffic.seed_sequence(job.seed).spawn(1)[0])
second = rng.integers(0, costs.arch(job.config)["vocab"],
                      (2, job.traffic["seq_len"] + 1), dtype=np.int32)[1]
build = job.family.build


def faulty(config, overrides=None):
    spec = build(config, overrides)

    def loss_fn(params, micro, rng, train):
        if fault == "expert_gradients_zeroed":
            params = {**params, "blocks": {
                k: jax.lax.stop_gradient(v) if k.startswith("experts_")
                else v for k, v in params["blocks"].items()}}
        loss, record = spec.loss_fn(params, micro, rng, train)
        if fault == "half_of_the_accumulator_dropped":
            # the micro-batches of the batch's second half hand the
            # accumulator nothing; the loss they report stays
            dropped = jnp.all(micro["input_ids"] == second[None])
            loss = jnp.where(dropped, jax.lax.stop_gradient(loss), loss)
        return loss, record

    return dataclasses.replace(spec, loss_fn=loss_fn)


def initialize(**kw):
    engine, *rest = deepspeed_tpu_initialize(**kw)
    step = engine.train_batch

    def stuck(batch):
        kept = jax.tree_util.tree_map(np.asarray, engine.state)
        where = jax.tree_util.tree_map(lambda a: a.sharding, engine.state)
        out = step(batch)
        engine.state = jax.tree_util.tree_map(jax.device_put, kept, where)
        return out

    engine.train_batch = stuck
    return (engine, *rest)


if fault == "state_unchanged":
    deepspeed_tpu_initialize = deepspeed_tpu.initialize
    deepspeed_tpu.initialize = initialize
else:
    job.family.build = faulty
print(json.dumps(train_routed.compare(job)[3][None]))
"""


@pytest.mark.limit(600)
@pytest.mark.parametrize("fault, least", [
    ("state_unchanged", 1.0), ("half_of_the_accumulator_dropped", 0.3),
    ("expert_gradients_zeroed", 1.0)])
def test_a_wrong_first_update_is_refused_by_the_gradient_check(
        tmp_path, fault, least):
    """(d) of ``train_routed``: the forward readings (a)-(c) all pass on a
    program whose first update is wrong, and the gradient its optimizer was
    handed does not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run([sys.executable, "-c", PLANT, fault], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    v = json.loads(proc.stdout.strip().splitlines()[-1])
    assert v["ok"] is False
    assert v["logit_rel_rmse"] <= v["tolerance"] and v["experts"] == 1.0
    assert v["loss_diff"] <= v["loss_tolerance"]
    assert abs(v["expert_rows"] - v["reference_expert_rows"]) \
        <= v["expert_rows_tolerance"]
    assert v["grad_rel_err"] >= least > v["grad_rel_err_limit"]
    by_leaf = v["grad_rel_err_by_leaf"]
    if fault == "expert_gradients_zeroed":
        assert {k for k, e in by_leaf.items() if e > 1e-4} == {
            "blocks.experts_w1", "blocks.experts_w2", "blocks.experts_w3"}
    else:
        assert min(by_leaf.values()) >= least
    if fault == "state_unchanged":
        assert v["update_rel_err"] == 1.0 and v["program_fell"] == 0.0


def _readings():
    """Hand-made readings that :func:`train_routed.check` passes."""
    import numpy as np
    from types import SimpleNamespace

    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 3, 5)).astype(np.float32)
    grads = {"blocks": {"gate_w": rng.normal(size=(4, 6)).astype(np.float32),
                        "k_w": rng.normal(size=(4, 9)).astype(np.float32)}}
    mine = {"blocks." + k: v.copy() for k, v in grads["blocks"].items()}
    lr = 1e-4
    job = SimpleNamespace(sizing={"ds_config": {"optimizer": {
        "params": {"lr": lr}}}})
    side = {"logits": logits}
    before = {"logits": logits.copy(), "loss": 5.0, "grads": grads,
              "report": {"experts": 1.0, "expert_gap": 0.0,
                         "expert_rows": 100.0}}
    g = mine["blocks.gate_w"]
    steps = {"losses": [5.0, 4.9], "rows": 4, "grads": mine,
             "records": [{"expert_rows": 200.0, "expert_rows_absent": 600.0}],
             "moved": {"blocks.gate_w": -lr * g / (np.abs(g) + 1e-8)}}
    return job, {"bf16": False}, side, before, steps


@pytest.mark.parametrize("fault", [
    None, "nan_leaf", "zero_leaf", "tenth_of_the_rate", "loss_rose",
    "counted_every_pair"])
def test_check_passes_sound_readings_and_refuses_each_planted_one(fault):
    import numpy as np

    job, shape, side, before, steps = _readings()
    if fault == "nan_leaf":       # the last leaf: Python's max() skips it
        steps["grads"]["blocks.k_w"] = np.full((4, 9), np.nan, np.float32)
    elif fault == "zero_leaf":
        steps["grads"]["blocks.k_w"] = np.zeros((4, 9), np.float32)
    elif fault == "tenth_of_the_rate":
        steps["moved"] = {k: v / 10 for k, v in steps["moved"].items()}
    elif fault == "loss_rose":
        steps["losses"] = [5.0, 5.1]
    elif fault == "counted_every_pair":
        steps["records"][0]["expert_rows"] = 800.0
    verdict = train_routed.check(job, shape, side, before, steps)
    assert verdict["ok"] is (fault is None), verdict
    if fault == "zero_leaf":
        assert verdict["grad_rel_err_by_leaf"]["blocks.k_w"] == 1.0
    if fault == "tenth_of_the_rate":
        assert verdict["update_rel_err"] == pytest.approx(0.9, abs=1e-3)


# ------------------------------------------------------------------ readers
READERS = cb_run.layer_metric_readers()


def test_new_readers_on_a_hand_made_context():
    cfg = _config()
    # two executions of the step; the kernels' seconds are the trace's totals
    trace = {
        "programs": {"jit_train_step": [1.0, 1.2]},
        "custom_call_s": {
            "jit_train_step:mosaic:moe_gmm": 0.20,
            "jit_train_step:mosaic:moe_gmm_dlhs": 0.10,
            "jit_train_step:mosaic:moe_gmm_drhs": 0.10,
            "jit_train_step:mosaic:flash_fwd_chunked": 0.30,
            "jit_train_step:mosaic:flash_bwd_dq_chunked": 0.15,
            "jit_train_step:mosaic:flash_bwd_dkv_chunked": 0.15,
            "jit_decode_step:mosaic:moe_gmm": 9.0,        # another program
            "jit_train_step:mosaic:paged_decode_attn": 9.0}}
    rows = 4 * 4 * 16 * 768                    # layers x gas x held x 768
    counters = {"chips": 1, "gas": 4, "seq_len": 8192, "rows_per_step": 4,
                "expert_rows": float(rows), "flops_per_step": 59.1e12}
    ctx = {"trace": trace, "counters": counters, "config": cfg,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    assert READERS["expert_train_ms"](ctx) == pytest.approx(200.0)
    assert READERS["window_flash_ms"](ctx) == pytest.approx(300.0)
    assert READERS["routed_train_mfu"](ctx) == pytest.approx(
        100.0 * 59.1e12 / 197e12 / 1.1)
    assert READERS["expert_train_roofline"](ctx) == pytest.approx(
        100.0 * 18 * rows * 2560 * 768 / 197e12 / 0.2)
    pairs = 3 * (4096 * 4097 // 2 + 4096 * 4096) + 8192 * 8193 // 2
    assert READERS["window_flash_roofline"](ctx) == pytest.approx(
        100.0 * 14 * 3584 * 4 * pairs / 197e12 / 0.3)
    assert READERS["expert_rows_per_expert"](ctx) == pytest.approx(768.0)
    for name in ("routed_train_mfu", "expert_train_roofline",
                 "window_flash_roofline"):
        assert 0 < READERS[name](ctx) < 100, name


def test_new_readers_find_nothing_where_the_program_has_nothing():
    """The parent of PR 47, any other cell: no ``moe_gmm*`` in a train
    step, no record among the counters — nothing read, nothing raised."""
    gpt2 = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                       "gpt2-medium.json")))
    parent = {"trace": {"programs": {"jit_train_step": [0.5]},
                        "custom_call_s": {
                            "jit_train_step:mosaic:flash_fwd_resident": 0.1}},
              "counters": {"chips": 1, "gas": 4, "flops_per_step": 1e12},
              "config": gpt2,
              "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    for name in NEW:
        if name == "window_flash_ms":       # flash kernels ARE there
            assert READERS[name](parent) == pytest.approx(100.0)
        else:
            assert READERS[name](parent) is None, name
    empty = {"trace": None, "counters": {}, "config": _config(),
             "peaks": None}
    for name in NEW:
        assert READERS[name](empty) is None, name


# ------------------------------------------------------ the benchmark's entries
def _named(key, name):
    return next(e for e in BENCH[key] if e["name"] == name)


def test_benchmark_entries_of_this_family():
    """Looked up BY NAME, never by position: a later PR appends behind
    these and this stays green."""
    entry = _named("configs", NAME)
    assert entry["reduced"] == ["depth", "moe_num_primary_experts",
                                "vocab_size"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "train-8k", 1)
    assert len(cell["why"]) <= 200
    for word in ("8,192", "32,768", "top-6 of 64", "16 held", "4,096 window",
                 "depth 4/52"):
        assert word in cell["why"], word
    for name in NEW:
        m = _named("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
    assert {n: _named("per_layer", n)["layer"] for n in NEW} == {
        "routed_train_mfu": "kernels", "expert_train_ms": "model step",
        "expert_train_roofline": "kernels", "window_flash_ms": "model step",
        "window_flash_roofline": "kernels",
        "expert_rows_per_expert": "model step"}
    # in the lists the issue names and in no other: not train_mxu_roofline
    # (its 12 L H hd S counts every key), not collective_exposed (one chip)
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] in JOINED + NEW:
            assert CELL in m["workloads"], m["name"]
        else:
            assert CELL not in m.get("workloads", ()), m["name"]
    assert BENCH["run_seconds"] == 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
