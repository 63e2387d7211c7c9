"""``family: dots3`` (PR 61): the configuration file against the catalog row
and the cut it states, the cell's files against the issue's table, its
rehearsal (both ``--trace`` values), the nine new readers on a synthetic
trace, the controls, and the benchmark's entries — every entry looked up BY
NAME, so that the next cell does not turn this red."""

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

from chipbench import costs, families, reference_dots3  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL = "dots3-longnote-closed"
NAME = "dots3-note-prev"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("sparse_latent_attn_ms", "sparse_latent_attn_roofline",
       "latent_index_ms", "latent_index_roofline", "window_latent_attn_ms",
       "window_latent_attn_roofline", "sparse_latent_prefill_ms",
       "latent_selected_share", "latent_read_share")
JOINED = ("serve_tok_s", "decode_occupancy", "kv_pool_peak_used",
          "peak_hbm.serve", "device_idle.serve", "sched_host_share",
          "kv_host_ms", "expert_ffn_ms", "expert_ffn_roofline",
          "expert_rows_per_read", "prefill_chunk_ms.longprompt")

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
def test_configuration_states_the_cut_and_the_published_counts():
    data = _config(False)
    assert data["reduced"] == ["depth", "n_routed_experts", "vocab_size"]
    assert (data["depth"], data["num_hidden_layers"],
            len(data["layer_types"])) == (5, 46, 46)
    assert (data["n_routed_experts"], data["n_routed_experts_published"],
            data["experts_first"]) == (32, 256, 0)
    assert (data["vocab_size"], data["vocab_size_published"]) \
        == (19008, 152064)
    # the floors: the leading layer + a whole period of four, 8 experts, an
    # eighth of the vocabulary
    assert reference_dots3.layer_kinds(data) == [
        "latent_indexed", "latent_indexed"] + ["latent_sliding"] * 3
    assert data["vocab_size"] * 8 >= data["vocab_size_published"]
    # no width, rank, head count, window or index_topk moved
    assert (data["hidden_size"], data["intermediate_size"],
            data["moe_intermediate_size"], data["num_attention_heads"],
            data["q_lora_rank"], data["kv_lora_rank"],
            data["qk_nope_head_dim"], data["qk_rope_head_dim"],
            data["v_head_dim"], data["index_n_heads"],
            data["index_head_dim"], data["index_topk"],
            data["sliding_window_size"], data["num_experts_per_tok"]) \
        == (5120, 13824, 1536, 128, 1024, 512, 128, 64, 128, 64, 128, 2048,
            513, 8)
    assert (data["swa_num_attention_heads"], data["swa_q_lora_rank"],
            data["swa_kv_lora_rank"], data["swa_qk_nope_head_dim"],
            data["swa_qk_rope_head_dim"], data["swa_v_head_dim"]) \
        == (64, 1024, 1024, 192, 64, 128)
    for key in ("depth", "n_routed_experts", "vocab_size",
                "apply_mla_qkv_lora_rescale", "indexer_input", "rotary",
                "attention_gate_type", "sliding_window_size",
                "index_key_dtype", "selection_bias", "towers_and_mtp",
                "weights"):
        assert key in data["assumed"], key
    assert "eight v5e chips" in data["deployment"]
    assert "8.17 GB" in data["deployment"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_file_holds_the_catalog_rows_keys_letter_for_letter():
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == NAME)
    data = _config(False)
    assert data["source"] == row["source_url"] \
        == _named("configs", NAME)["source"]
    for key, value in row["config"].items():
        if key in data["reduced"]:
            continue
        assert data[key] == value, key


def test_costs_of_the_configuration_as_integers():
    config = _config(False)
    fam = families.load(config)
    a = costs.arch(config)
    assert (a["full_layers"], a["sliding_layers"], a["dense_layers"]) \
        == (2, 3, 1)
    # the issue's table, to a tenth of a million parameters
    full, sliding = fam._attn_params(a, "full"), \
        fam._attn_params(a, "sliding")
    assert (round(full / 1e6, 1), round(sliding / 1e6, 1)) == (144.0, 90.8)
    assert round(3 * 5120 * 13824 / 1e6, 1) == 212.3
    assert round(fam.num_params(config) / 1e9, 2) == 4.09
    assert round(costs.weight_bytes(config) / 1e9, 2) == 8.17
    # what the readers divide by
    assert fam.latent_bytes_per_key(config) == 1152
    assert fam.latent_bytes_per_key(config, "sliding") == 2176
    assert fam.index_bytes_per_key(config) == 256
    assert fam.latent_flops_per_key(config) == 2 * 128 * (2 * 512 + 64)
    assert fam.latent_flops_per_key(config, "sliding") \
        == 2 * 64 * (2 * 1024 + 64)
    assert fam.index_flops_per_key(config) == 64 * (2 * 128 + 2)
    assert fam.cached_bytes_per_token(config) == 2 * (1152 + 256)
    assert fam.window_bytes_per_slot(config) == 3 * 513 * 2176
    # the whole language model by the same formula (the issue's 279.6 B)
    whole = {**config, "depth": 46, "n_routed_experts": 256,
             "vocab_size": 152064}
    assert round(fam.num_params(whole) / 1e9, 1) == 279.6


def test_family_meets_the_contract():
    fam = families.load(_config())
    for fn in families.REQUIRED + (
            "active_params", "decode_weight_bytes", "latent_bytes_per_key",
            "latent_flops_per_key", "index_bytes_per_key",
            "index_flops_per_key", "cached_bytes_per_token",
            "window_bytes_per_slot"):
        assert callable(getattr(fam, fn)), fn


def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    mix, sizing = spec["traffic"], spec["sizing"]["serving"]
    assert mix["kind"] == "serve_sparselatent" and mix["clients"] == 24
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 12288,
                                    "hi": 28672}
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 512,
                                    "hi": 1024}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (mix["deck"], mix["score_rows"], mix["shared_prefix_tokens"]) \
        == (24, 1, 0)
    assert mix["score_tokens"] > 16384
    assert sizing == {"slots": 24, "max_seq_len": 32768}
    assert 28672 + 1024 <= sizing["max_seq_len"]
    config = spec["config"]
    # both pools + weights, before temporaries: 10.8 GB of 16
    full = (1 + 24 * 128) * 256 * (640 + 128) * 2 * 2
    ring = (1 + 24 * 10) * 128 * 1152 * 2 * 3
    total = full + ring + costs.weight_bytes(config)
    assert round(full / 1e9, 2) == 2.42 and round(ring / 1e9, 2) == 0.21
    assert 0.25 * 16e9 < 10.7e9 < total < 10.9e9
    assert [m["name"] for m in spec["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]


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
    note = next(line for line in lines if "on the engine's own cache" in line)
    assert "1 x 112 tokens on the engine's own cache (blocks 16 / 16)" in note
    assert "19 select and 20 lie past the window" in note
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == {"serve_tok_s", "setup_s"}
        return
    assert 0.0 < metrics["latent_selected_share"]["value"] < 100.0
    assert metrics["latent_read_share"]["value"] > 0.0
    assert 0.0 < metrics["kv_pool_peak_used"]["value"] <= 100.0
    assert metrics["expert_rows_per_read"]["value"] > 0
    detail = json.loads(next(
        line for line in lines
        if line.startswith("chipbench: detail ")).split("detail ", 1)[1])
    kinds = detail["counters"]["kv_kinds"]
    assert kinds["full"]["num_blocks"] == 1 + 4 * 8
    assert 0 < kinds["sliding"]["peak_blocks_in_use"] \
        <= kinds["sliding"]["num_blocks"]
    assert detail["counters"]["window_blocks_released"] > 0


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

    ring = _Ring([
        _span("decode", 1.0, index_keys=480000, kv_selected=49152,
              kv_read=490000, kv_window=36936),
        _span("decode", 2.0, index_keys=520000, kv_selected=49152,
              kv_read=530000, kv_window=36936),
        _span("decode", 2.5, index_keys=0, kv_selected=900, kv_read=1024,
              kv_window=300),                       # no row past index_topk
        _span("decode", 9.0, index_keys=1, kv_selected=1, kv_read=1,
              kv_window=1),                         # outside the window
        _span("prefill", 1.5, index_keys=7, kv_selected=7, kv_read=7)])
    monkeypatch.setattr(program_trace, "kept", lambda name: ring)
    trace = {
        "programs": {"jit_decode_step": [0.02, 0.02], "jit_prefill": [0.05]},
        "custom_call_s": {
            "jit_decode_step:mosaic:paged_sparse_latent_attn": 0.008,
            "jit_decode_step:mosaic:paged_index_scores": 0.003,
            "jit_decode_step:mosaic:paged_sparse_select": 0.001,
            "jit_decode_step:mosaic:paged_window_latent_attn": 0.002,
            "jit_decode_step:mosaic:paged_latent_attn": 0.5,  # the dense walk
            "jit_decode_step:mosaic:paged_sparse_attn": 0.5,  # K/V, not latent
            "jit_decode_step:mosaic:moe_gmm": 0.5,
            "jit_prefill:mosaic:paged_sparse_latent_attn": 0.020,
            "jit_prefill:mosaic:paged_index_scores": 0.004,
            "jit_prefill:mosaic:paged_sparse_select": 0.002,
            "jit_prefill:mosaic:paged_window_latent_prefill": 0.5,
            "jit_prefill:mosaic:moe_gmm": 0.5}}
    cfg = _config(False)
    ctx = {"trace": trace, "window": (0.5, 5.0), "config": cfg,
           "counters": {}, "samples": {}, "peaks": PEAKS}
    assert READERS["sparse_latent_attn_ms"](ctx) == pytest.approx(4.0)
    assert READERS["latent_index_ms"](ctx) == pytest.approx(2.0)
    assert READERS["window_latent_attn_ms"](ctx) == pytest.approx(1.0)
    assert READERS["sparse_latent_prefill_ms"](ctx) == pytest.approx(26.0)
    # 49,152 chosen keys x 2 full layers: 113 MB against 26.8 GFLOP: FLOPs
    keys = 49152 * 2
    assert keys * 1152 / 819e9 < keys * 2 * 128 * 1088 / 197e12
    assert READERS["sparse_latent_attn_roofline"](ctx) == pytest.approx(
        100.0 * keys * 2 * 128 * 1088 / 197e12 / 0.004)
    scored = 500000 * 2
    assert READERS["latent_index_roofline"](ctx) == pytest.approx(
        100.0 * max(scored * 256 / 819e9, scored * 64 * 258 / 197e12)
        / 0.002)
    assert READERS["window_latent_attn_roofline"](ctx) == pytest.approx(
        100.0 * max(36936 * 2176 / 819e9, 36936 * 2 * 64 * 2112 / 197e12)
        / 0.001)
    assert READERS["latent_selected_share"](ctx) == pytest.approx(
        100.0 * 49152 / 500000)
    assert READERS["latent_read_share"](ctx) == pytest.approx(
        100.0 * 510000 / 500000)
    # a family without the functions: no share of a roofline
    other = {**ctx, "config": {**_config(False), "family": "olmoe"}}
    for name in NEW:
        if name.endswith("_roofline"):
            assert READERS[name](other) is None, name


def test_new_readers_find_nothing_on_an_empty_context(monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    monkeypatch.setattr(program_trace, "kept", lambda name: None)
    empty = {"trace": None, "window": (0.0, 1.0), "counters": {},
             "samples": {}, "config": _config(), "peaks": None}
    for name in NEW:
        assert READERS[name](empty) is None, name
    # a ring without the counters and a trace without the kernels (any other
    # model; the parent of PR 61)
    monkeypatch.setattr(program_trace, "kept", lambda name: _Ring(
        [_span("decode", 0.5, slots=3, kv_valid=900, kv_selected=300),
         _span("prefill", 0.6, kv_blocks=4, rows=2)]))
    parent = {**empty, "peaks": PEAKS,
              "trace": {"programs": {"jit_decode_step": [0.01],
                                     "jit_prefill": [0.01]},
                        "custom_call_s": {
                            "jit_decode_step:mosaic:paged_latent_attn": 1.0,
                            "jit_decode_step:mosaic:paged_sparse_attn": 1.0,
                            "jit_prefill:mosaic:moe_gmm": 1.0}}}
    for name in NEW:
        assert READERS[name](parent) is None, name


@pytest.mark.limit(240)
def test_controls_each_shortcut_is_refused_by_the_comparison(tmp_path):
    """The harness mode PERF.md's table of controls is made with, at the
    rehearsal's widths: the plain reference passes and every shortcut is
    refused."""
    proc = _run(["-m", "chipbench.drivers.serve_sparselatent", "--workload",
                 CELL, "--seed", "2147483999", "--rehearse"], tmp_path, 220)
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1] == {"controls_held": True}
    got = {c["variant"]: c for c in lines[:-1]}
    assert list(got) == list(reference_dots3.VARIANTS)
    assert got[None]["ok"] and got[None]["logit_rel_rmse"] < 1e-5
    for v in reference_dots3.VARIANTS[1:]:
        assert not got[v]["ok"], v


def test_benchmark_entries_of_this_family():
    """Looked up BY NAME, never by position: a later PR appends behind
    these and this stays green."""
    entry = _named("configs", NAME)
    assert entry["reduced"] == ["depth", "n_routed_experts", "vocab_size"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "longnote-closed", 1)
    assert len(cell["why"]) <= 200
    for word in ("24 x 32,768", "12.8k-29.7k", "2,048 keys", "8.17 GB",
                 "1/8", "5 of 46 layers"):
        assert word in cell["why"], word
    for name in NEW:
        m = _named("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert m["layer"] == ("kernels" if name.endswith("_roofline") else
                              "KV manager" if name.endswith("_share") else
                              "model step")
        assert m["source"] == ("program_span" if name.endswith("_share")
                               else "device_trace")
        assert m["unit"] == ("ms" if name.endswith("_ms") else "%")
    # in the lists the issue names and in no other
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] in JOINED + NEW:
            assert CELL in m["workloads"], m["name"]
        else:
            assert CELL not in m.get("workloads", ()), m["name"]
    assert BENCH["run_seconds"] == 51
    assert len(BENCH["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
