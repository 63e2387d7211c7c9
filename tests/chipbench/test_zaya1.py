"""``family: zaya`` (PR 66): the configuration file against the catalog row
and the cut it states, the cell's files against the issue's table, its
rehearsal (both ``--trace`` values), the two new readers on a hand-made
context, the controls, and the benchmark's entries — every entry looked up
BY NAME and every list by MEMBERSHIP (never position, never equality with a
list a later cell may join), so that the next PR that appends does not turn
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

from chipbench import costs, families, reference_zaya  # noqa: E402
from chipbench import run as cb_run  # noqa: E402
from chipbench.drivers import serve_tails  # noqa: E402

CELL = "zaya1-reasoning-closed"
NAME = "ZAYA1-8B"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"expert_rows_max_share": ("%", "program_span", "model step"),
       "kv_tail_share": ("%", "program_span", "KV manager")}
#: the accepted metrics whose readers, as they are, read something here
JOINED = ("serve_tok_s", "decode_occupancy", "kv_pool_peak_used",
          "peak_hbm.serve", "device_idle.serve", "sched_host_share",
          "kv_host_ms", "host_plan_ms", "host_upload_ms", "host_commit_ms",
          "step_stall_share", "prefill_chunk_ms.longprompt", "expert_ffn_ms",
          "expert_ffn_roofline", "expert_rows_per_read",
          "paged_attn_roofline", "decode_roofline")

pytestmark = pytest.mark.limit(30)


def _config(rehearse=True):
    data = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                       NAME + ".json")))
    return cb_run._rehearsed(data, rehearse)


def _named(section, name):
    found = [e for e in BENCH[section] if e["name"] == name]
    assert len(found) == 1, (section, name)
    return found[0]


# ------------------------------------------------------- the configuration
def test_configuration_states_the_cut_and_the_published_widths():
    data = _config(False)
    assert data["family"] == "zaya" and data["dtype"] == "bf16"
    assert data["reduced"] == ["depth"]
    assert (data["depth"], data["num_hidden_layers"]) == (10, 40)
    assert data["depth"] >= 4                  # the floor: four layers
    # every width as published: nothing but the depth is cut
    assert (data["hidden_size"], data["num_attention_heads"],
            data["num_key_value_heads"], data["head_dim"],
            data["num_experts"], data["moe_intermediate_size"],
            data["num_experts_per_tok"], data["router_hidden_size"],
            data["vocab_size"], data["tie_word_embeddings"],
            data["cca_time0"], data["cca_time1"],
            data["partial_rotary_factor"],
            data["rope_parameters"]["hybrid"]["rope_theta"]) \
        == (2048, 8, 2, 128, 16, 2048, 1, 256, 262272, True, 2, 2, 0.5,
            5000000)
    for key in ("convolutions", "qk_mean", "l2_norm", "value_shift",
                "rotary", "router", "top1_weight", "residual_merge",
                "parameters", "initialisation", "dtype"):
        assert key in data["assumed"], key
    assert "arXiv:2510.04476" in data["assumed"]["convolutions"]
    assert "arXiv:2511.17127" in data["assumed"]["router"]
    assert "four pipeline stages" in data["deployment"]
    assert "ONE chip holding each layer whole" in data["deployment"]
    assert "5.23 GB" in data["deployment"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_file_holds_the_catalog_rows_keys_letter_for_letter():
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == NAME)
    data = _config(False)
    assert data["source"] == row["source_url"] \
        == _named("configs", NAME)["source"]
    for key, value in row["config"].items():
        assert data[key] == value, key       # ``depth`` is a key of its own


def test_costs_of_the_configuration_as_integers():
    config = _config(False)
    fam = families.load(config)
    a = costs.arch(config)
    assert (a["layers"], a["experts"], a["top_k"], a["vocab"]) \
        == (10, 16, 1, 262272)
    # the issue's arithmetic
    assert fam.layer_params(config) == 207_579_651
    assert fam.num_params(config, 40) == 8_840_321_144
    assert fam.num_params(config) == reference_zaya.num_params(config) \
        == 2_612_931_614
    assert round(costs.weight_bytes(config) / 1e9, 2) == 5.23
    assert costs.kv_bytes_per_token(config) == 10 * 1024
    assert fam.tail_bytes_per_slot(config) == 10 * 5376
    # a decode step's weights with every expert touched: everything once
    # (the tied table once: it is the head)
    assert fam.decode_weight_bytes(config, {"experts_touched_share": 1.0}) \
        == costs.weight_bytes(config)
    half = fam.decode_weight_bytes(config, {"experts_touched_share": 0.5})
    assert costs.weight_bytes(config) - half \
        == 0.5 * 10 * 16 * 3 * 2048 * 2048 * 2
    # by the bytes a step of 125 rows at 1,400 keys is ~8.5 ms
    step = costs.decode_bytes_per_step(
        config, 125 * 1400, {"experts_touched_share": 1.0})
    assert 8.4e-3 < step / 819e9 < 8.7e-3


def test_family_meets_the_contract():
    fam = families.load(_config())
    for fn in families.REQUIRED + (
            "active_params", "decode_weight_bytes", "expert_bytes_touched",
            "cached_bytes_per_token", "tail_bytes_per_slot",
            "layer_params"):
        assert callable(getattr(fam, fn)), fn
    with pytest.raises(ValueError, match="published block"):
        fam.build({**_config(), "sliding_window": 4096})


def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    mix, sizing = spec["traffic"], spec["sizing"]["serving"]
    assert mix["kind"] == "serve_tails" and mix["clients"] == 128
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 128,
                                    "hi": 1024}
    assert mix["output_tokens"] == {"dist": "loguniform", "lo": 768,
                                    "hi": 3072}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (mix["deck"], mix["settle_s"], mix["shared_prefix_tokens"]) \
        == (96, 45, 0)
    assert (mix["score_rows"], mix["score_tokens"]) == (2, 4096)
    assert sizing == {"slots": 128, "max_seq_len": 4096}
    assert 1024 + 3072 <= sizing["max_seq_len"]
    config = spec["config"]
    # the pool + weights, before temporaries: 10.6 GB of 16
    pool = (1 + 128 * 128) * 32 * costs.kv_bytes_per_token(config)
    total = pool + costs.weight_bytes(config) \
        + 128 * families.load(config).tail_bytes_per_slot(config)
    assert round(pool / 1e9, 2) == 5.37
    assert 0.25 * 16e9 < 10.5e9 < total < 10.7e9
    assert [m["name"] for m in spec["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]


@pytest.fixture(scope="module")
def tmp_path(tmp_path_factory):
    """ONE compile cache for the module's three child processes: the second
    and third start warm."""
    return tmp_path_factory.mktemp("jax_cache")


def _run(args, tmp_path, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.limit(240)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_is_correct(tmp_path, trace):
    proc = _run([os.path.join(ROOT, "chipbench", "run.py"), "--workload",
                 CELL, "--seed", "2147483999", "--seconds", "2", "--trace",
                 str(trace), "--rehearse"], tmp_path, 220)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    note = next(line for line in lines if "chipbench: comparison:" in line)
    assert "2 x 64 tokens at block 16, chunks of 16 in calls of 4 rows " \
        "then 16 steps at 4 rows" in note
    assert "(a) logits under the engine's routes" in note \
        and "(b) 0 of 384 (token, layer) routes" in note \
        and "(c) router scores" in note
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == {"serve_tok_s", "setup_s"}
        return
    # three layers of four experts, top-1
    assert 25.0 <= metrics["expert_rows_max_share"]["value"] <= 100.0
    assert 0.0 < metrics["kv_tail_share"]["value"] < 100.0
    assert 1.0 <= metrics["expert_rows_per_read"]["value"] <= 4.0
    assert 0.0 < metrics["kv_pool_peak_used"]["value"] <= 100.0
    assert 0.0 < metrics["decode_occupancy"]["value"] <= 100.0


# ------------------------------------------------------------------ readers
READERS = cb_run.layer_metric_readers()
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


class _Ring:
    epoch_s, dropped = 0.0, 0

    def __init__(self, events):
        self._events = events

    def events(self):
        return self._events


def _span(name, t0_s, **args):
    return {"ph": "X", "name": name, "ts": t0_s * 1e6, "dur": 1e3,
            "args": args}


def test_new_readers_on_a_hand_made_context(monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    per_row = 2 * 10 * 5376
    ring = _Ring([
        _span("decode", 1.0, slots=125, expert_rows=1250,
              expert_rows_max=19, expert_rows_max_sum=150,
              experts_touched=160, tail_bytes=125 * per_row),
        _span("decode", 2.0, slots=123, expert_rows=1230,
              expert_rows_max=17, expert_rows_max_sum=160,
              experts_touched=160, tail_bytes=123 * per_row),
        _span("decode", 9.0, slots=1, expert_rows=10, expert_rows_max=1,
              expert_rows_max_sum=10, experts_touched=10,
              tail_bytes=per_row),                    # outside the window
        _span("prefill", 1.5, expert_rows=5120, expert_rows_max=60,
              expert_rows_max_sum=500, tail_bytes=4 * per_row)])
    monkeypatch.setattr(program_trace, "kept", lambda name: ring)
    cfg = _config(False)
    ctx = {"trace": None, "window": (0.5, 5.0), "config": cfg,
           "counters": {"mean_valid_kv_tokens": 175000.0,
                        "experts_touched_share": 1.0},
           "samples": {}, "peaks": PEAKS}
    assert READERS["expert_rows_max_share"](ctx) == pytest.approx(
        100.0 * 310 / 2480)
    tail = 124 * per_row
    needed = costs.weight_bytes(cfg) + 175000.0 * 10240
    assert READERS["kv_tail_share"](ctx) == pytest.approx(
        100.0 * tail / (tail + needed))
    assert 0.1 < READERS["kv_tail_share"](ctx) < 0.3


def test_new_readers_find_nothing_where_the_program_has_no_such_counter(
        monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    monkeypatch.setattr(program_trace, "kept", lambda name: None)
    empty = {"trace": None, "window": (0.0, 1.0), "counters": {},
             "samples": {}, "config": _config(), "peaks": None}
    for name in NEW:
        assert READERS[name](empty) is None, name
    # the parent of PR 66: routing on the span, neither new counter
    monkeypatch.setattr(program_trace, "kept", lambda name: _Ring(
        [_span("decode", 0.5, slots=3, expert_rows=24, expert_rows_max=5,
               experts_touched=20, kv_valid=900)]))
    parent = {**empty, "peaks": PEAKS,
              "counters": {"mean_valid_kv_tokens": 10.0}}
    for name in NEW:
        assert READERS[name](parent) is None, name


@pytest.mark.limit(240)
def test_controls_each_shortcut_is_refused_by_the_comparison(tmp_path):
    """The harness mode PERF.md's table of controls is made with, at the
    rehearsal's widths: the plain reference passes and every shortcut is
    refused."""
    proc = _run(["-m", "chipbench.drivers.serve_tails", "--workload", CELL,
                 "--seed", "2147483999", "--rehearse"], tmp_path, 220)
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1] == {"controls_held": True}
    got = {c["variant"]: c for c in lines[:-1]}
    assert list(got) == [None] + list(serve_tails.VARIANTS
                                      + serve_tails.SHOWN)
    assert set(serve_tails.VARIANTS + serve_tails.SHOWN) \
        == set(reference_zaya.VARIANTS[1:])
    assert got[None]["ok"] and got[None]["logit_rel_rmse"] < 1e-5
    assert got[None]["route_flips"] == 0
    for v in serve_tails.VARIANTS:
        assert not got[v]["ok"], v
    # a router in a lower precision shows in its SCORES; a mechanism of the
    # attention left out shows in the logits
    assert got["router_fp8"]["score_rms"] > 5 * got["router_bf16"][
        "score_rms"] > 5e-5
    assert not got["router_bf16"]["ok"]      # at float32 limits it IS told
    assert got["no_eda"]["score_rms"] > 1e-3
    assert got["no_shift"]["logit_rel_rmse"] > 1e-3
    assert got["tails_fp8"]["logit_rel_rmse"] > 1e-3


def test_benchmark_entries_of_this_family():
    """Looked up BY NAME, lists by MEMBERSHIP: a later PR appends behind
    these, or joins a list, and this stays green."""
    entry = _named("configs", NAME)
    assert entry["reduced"] == ["depth"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "reasoning-closed", 1)
    assert len(cell["why"]) <= 200
    for word in ("128 callers", "768-3,072", "262,272", "top-1", "tails",
                 "10 of 40 layers"):
        assert word in cell["why"], word
    for name, (unit, source, layer) in NEW.items():
        m = _named("per_layer", name)
        assert CELL in m["workloads"] and m["moves"] == "serve_tok_s"
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
    for name in JOINED:
        section = "end_to_end" if name == "serve_tok_s" else "per_layer"
        assert CELL in _named(section, name)["workloads"], name
    # a closed loop of long replies reports no tail latency
    for name in ("ttft_p95_ms", "itl_p95_ms"):
        assert CELL not in _named("end_to_end", name)["workloads"]
    assert BENCH["run_seconds"] == 51
    assert len(BENCH["workloads"]) <= 24 and len(BENCH["configs"]) <= 24
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
