"""``family: glm_dsa`` (PR 64): the configuration file against the catalog row
and the cut it states, the cell's files against the issue's table, its
rehearsal (both ``--trace`` values), the seven new readers on a synthetic
trace, the controls, and the benchmark's entries — every entry looked up BY
NAME (membership, never position), so that the next cell does not turn this
red."""

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

from chipbench import costs, families, reference_glm5  # noqa: E402
from chipbench import run as cb_run  # noqa: E402
from chipbench.drivers import serve_mtp  # noqa: E402

CELL = "glm5-agentloop-closed"
NAME = "GLM-5"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"mtp_accept_rate": ("%", "program_counter", "scheduler"),
       "spec_tokens_per_round": ("tokens", "program_counter", "scheduler"),
       "spec_round_ms": ("ms", "device_trace", "model step"),
       "mtp_draft_ms": ("ms", "device_trace", "model step"),
       "sparse_latent_verify_ms": ("ms", "device_trace", "model step"),
       "sparse_latent_verify_roofline": ("%", "device_trace", "kernels"),
       "prefix_hit_share": ("%", "program_counter", "KV manager")}
#: the accepted metrics whose readers, as they are, read something here
JOINED = ("serve_tok_s", "kv_pool_peak_used", "peak_hbm.serve",
          "device_idle.serve", "prefill_chunk_ms.longprompt",
          "sched_host_share", "kv_host_ms", "host_plan_ms", "host_upload_ms",
          "host_commit_ms", "host_offcpu_share", "host_gc_share",
          "step_stall_share")

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
    assert data["family"] == "glm_dsa" and data["dtype"] == "bf16"
    assert data["reduced"] == ["depth", "n_routed_experts", "vocab_size"]
    assert (data["depth"], data["dense_depth"], data["num_hidden_layers"],
            data["first_k_dense_replace"]) == (5, 1, 78, 3)
    assert (data["n_routed_experts"], data["n_routed_experts_published"],
            data["experts_first"]) == (16, 256, 0)
    assert (data["vocab_size"], data["vocab_size_published"]) \
        == (19360, 154880)
    # the floors: a dense layer + four routed, 8 experts, an eighth of the
    # vocabulary
    assert data["depth"] - data["dense_depth"] >= 4
    assert data["n_routed_experts"] >= 8
    assert data["vocab_size"] * 8 >= data["vocab_size_published"]
    # no width, rank, head count or index_topk moved
    assert (data["hidden_size"], data["intermediate_size"],
            data["moe_intermediate_size"], data["num_attention_heads"],
            data["q_lora_rank"], data["kv_lora_rank"],
            data["qk_nope_head_dim"], data["qk_rope_head_dim"],
            data["v_head_dim"], data["index_n_heads"],
            data["index_head_dim"], data["index_topk"],
            data["num_experts_per_tok"], data["num_nextn_predict_layers"]) \
        == (6144, 12288, 2048, 64, 2048, 512, 192, 64, 256, 32, 128, 2048,
            8, 1)
    for key in ("depth", "n_routed_experts", "vocab_size", "mtp_input",
                "mtp_hidden", "mtp_position", "mtp_sharing", "mtp_draft",
                "indexer_input", "rotary", "softmax_scale",
                "index_key_dtype", "selection_bias", "weights"):
        assert key in data["assumed"], key
    assert "FLOOR of what a deployment sees" in data["assumed"]["weights"]
    assert "sixteen v5e chips" in data["deployment"]
    assert "9.61 GB" in data["deployment"]


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
    assert (a["layers"], a["dense_layers"], a["mtp_layers"]) == (5, 1, 1)
    # the issue's table, to a tenth of a million parameters
    assert round(fam._attn_params(a) / 1e6, 1) == 174.4
    assert round((fam._attn_params(a) + 2 * a["d"]
                  + 3 * 6144 * 12288) / 1e6, 1) == 400.9
    assert round(fam._routed_rest(a) / 1e6, 1) == 213.7
    assert round(fam._expert_params(a) / 1e6, 2) == 37.75
    assert fam.num_params(config) == 4802856704
    assert round(costs.weight_bytes(config) / 1e9, 2) == 9.61
    # what the readers divide by
    assert fam.latent_bytes_per_key(config) == 1152
    assert fam.index_bytes_per_key(config) == 256
    assert fam.latent_flops_per_key(config) == 2 * 64 * (2 * 512 + 64)
    assert fam.index_flops_per_key(config) == 32 * (2 * 128 + 2)
    assert fam.cached_bytes_per_token(config) == 6 * (1152 + 256)
    assert fam.window_read_needs(config, 10, 3) == (
        10 * 256 + 3 * 1152, 10 * 32 * 258 + 3 * 2 * 64 * 1088)
    # the whole language model by the same formula, its module apart (the
    # issue's 743.9 B and A40.8 B)
    whole = {**config, "depth": 78, "dense_depth": 3,
             "n_routed_experts": 256, "vocab_size": 154880}
    module = fam._module_outside_experts(costs.arch(whole)) \
        + 256 * fam._expert_params(a)
    assert round((fam.num_params(whole) - module) / 1e9, 1) == 743.9
    # (40.8 G of matmuls + the 0.95 G token table a token reads one row of)
    assert round((fam.active_params(whole) - (
        module - 248 * fam._expert_params(a))) / 1e9, 1) == 41.8


def test_family_meets_the_contract():
    fam = families.load(_config())
    for fn in families.REQUIRED + (
            "active_params", "decode_weight_bytes", "latent_bytes_per_key",
            "latent_flops_per_key", "index_bytes_per_key",
            "index_flops_per_key", "cached_bytes_per_token",
            "window_read_needs", "module_flops_per_token"):
        assert callable(getattr(fam, fn)), fn


def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    mix, sizing = spec["traffic"], spec["sizing"]["serving"]
    assert mix["kind"] == "serve_mtp" and mix["clients"] == 24
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 8448,
                                    "hi": 10240}
    assert mix["output_tokens"] == {"dist": "loguniform", "lo": 128,
                                    "hi": 1024}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (mix["deck"], mix["settle_s"], mix["shared_prefix_tokens"]) \
        == (48, 20, 8192)
    assert (mix["score_tokens"], mix["score_prefix"]) == (10240, 8192)
    assert sizing == {"slots": 24, "max_seq_len": 12288, "spec_tokens": 1,
                      "draft": "self"}
    assert 10240 + 1024 + 2 <= sizing["max_seq_len"]
    config = spec["config"]
    # the pool + weights, before temporaries: 12.3 GB of 16
    pool = (1 + 24 * 48) * 256 * (640 + 128) * 2 * 6
    total = pool + costs.weight_bytes(config)
    assert round(pool / 1e9, 2) == 2.72
    assert 0.25 * 16e9 < 12.2e9 < total < 12.4e9
    assert [m["name"] for m in spec["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]
    # the trie serves the prefix's blocks but the last: ~85 % of a prompt
    assert round(100 * (8192 - 256) / ((8448 + 10240) / 2), 1) == 84.9


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
    assert "128 positions on the engine's own cache (blocks of 16)" in note
    assert "a prefix of 64" in note and "16 rounds" in note
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == {"serve_tok_s", "setup_s"}
        return
    assert 0.0 <= metrics["mtp_accept_rate"]["value"] <= 100.0
    assert 1.0 <= metrics["spec_tokens_per_round"]["value"] <= 2.0
    # a prefix of four blocks of 16, the last left to the request
    assert 40.0 < metrics["prefix_hit_share"]["value"] < 60.0
    assert 0.0 < metrics["kv_pool_peak_used"]["value"] <= 100.0


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
        _span("spec_round", 1.0, slots=24, window=2, drafted=24, accepted=1,
              emitted=25, index_keys=460000, kv_selected=98304),
        _span("spec_round", 2.0, slots=20, window=2, drafted=20, accepted=0,
              emitted=20, index_keys=380000, kv_selected=81920),
        _span("spec_round", 9.0, slots=1, window=2, drafted=1, accepted=1,
              emitted=2, index_keys=1, kv_selected=1),  # outside the window
        _span("prefill", 1.5, index_keys=7, kv_selected=7)])
    monkeypatch.setattr(program_trace, "kept", lambda name: ring)
    trace = {
        "programs": {"jit_verify": [0.018, 0.020, 0.019],
                     "jit_draft": [0.003, 0.003, 0.004],
                     "jit_prefill": [0.05]},
        "custom_call_s": {
            "jit_verify:mosaic:paged_sparse_latent_attn": 0.0111,
            "jit_verify:mosaic:paged_index_scores": 0.0054,
            "jit_verify:mosaic:paged_sparse_select": 0.0033,
            "jit_verify:mosaic:moe_gmm": 0.5,
            "jit_draft:mosaic:paged_sparse_latent_attn": 0.00225,
            "jit_draft:mosaic:paged_index_scores": 0.00105,
            "jit_draft:mosaic:paged_sparse_select": 0.0006,
            "jit_prefill:mosaic:paged_sparse_latent_attn": 0.5}}
    cfg = _config(False)
    ctx = {"trace": trace, "window": (0.5, 5.0), "config": cfg,
           "counters": {"prompt_tokens": 934400, "prefix_hit_tokens": 793600},
           "samples": {}, "peaks": PEAKS}
    assert READERS["mtp_accept_rate"](ctx) == pytest.approx(100.0 / 44)
    assert READERS["spec_tokens_per_round"](ctx) == pytest.approx(45 / 44)
    assert READERS["spec_round_ms"](ctx) == pytest.approx(19.0 + 3.0)
    assert READERS["mtp_draft_ms"](ctx) == pytest.approx(3.0)
    per_round = (0.0111 + 0.0054 + 0.0033) / 3 + 0.0039 / 3
    assert READERS["sparse_latent_verify_ms"](ctx) == pytest.approx(
        per_round * 1e3)
    keys, chosen = 6 * 420000, 6 * 90112
    nbytes = keys * 256 + chosen * 1152
    flops = keys * 32 * 258 + chosen * 2 * 64 * 1088
    assert READERS["sparse_latent_verify_roofline"](ctx) == pytest.approx(
        100.0 * max(nbytes / 819e9, flops / 197e12) / per_round)
    assert READERS["sparse_latent_verify_roofline"](ctx) < 100.0
    assert READERS["prefix_hit_share"](ctx) == pytest.approx(
        100.0 * 793600 / 934400)
    # a family without the function: no share of a roofline
    other = {**ctx, "config": {**cfg, "family": "olmoe"}}
    assert READERS["sparse_latent_verify_roofline"](other) is None


def test_new_readers_find_nothing_on_an_empty_context(monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    monkeypatch.setattr(program_trace, "kept", lambda name: None)
    empty = {"trace": None, "window": (0.0, 1.0), "counters": {},
             "samples": {}, "config": _config(), "peaks": None}
    for name in NEW:
        assert READERS[name](empty) is None, name
    # a ring without the round and a trace without its programs (any other
    # engine; the parent of PR 64)
    monkeypatch.setattr(program_trace, "kept", lambda name: _Ring(
        [_span("decode", 0.5, slots=3, kv_valid=900, kv_selected=300),
         _span("spec_verify", 0.6, slots=3, window=3)]))
    parent = {**empty, "peaks": PEAKS, "counters": {"prompt_tokens": 0},
              "trace": {"programs": {"jit_decode_step": [0.01],
                                     "jit_verify": [0.01],
                                     "jit_prefill": [0.01]},
                        "custom_call_s": {
                            "jit_verify:mosaic:paged_sparse_latent_attn": 1.0,
                            "jit_prefill:mosaic:moe_gmm": 1.0}}}
    for name in NEW:
        assert READERS[name](parent) is None, name


@pytest.mark.limit(240)
def test_controls_each_shortcut_is_refused_by_the_comparison(tmp_path):
    """The harness mode PERF.md's table of controls is made with, at the
    rehearsal's widths: the plain reference passes and every shortcut is
    refused."""
    proc = _run(["-m", "chipbench.drivers.serve_mtp", "--workload", CELL,
                 "--seed", "2147483999", "--rehearse"], tmp_path, 220)
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1] == {"controls_held": True}
    got = {c["variant"]: c for c in lines[:-1]}
    assert list(got) == [None] + list(serve_mtp.VARIANTS)
    assert set(serve_mtp.VARIANTS) == set(reference_glm5.VARIANTS[1:]) \
        | {"trie_keeps_last"}
    assert got[None]["ok"] and got[None]["logit_rel_rmse"] < 1e-5
    for v in serve_mtp.VARIANTS:
        assert not got[v]["ok"], v
    # each by the limit that is its own
    assert got["trie_keeps_last"]["module_row_rel_rmse"] > 0.1
    assert got["trie_keeps_last"]["logit_rel_rmse"] < 1e-5
    assert got["mtp_no_rows"]["module_rel_rmse"] > 1e-3 \
        > got["mtp_no_rows"]["logit_rel_rmse"]


def test_benchmark_entries_of_this_family():
    """Looked up BY NAME, never by position: a later PR appends behind
    these and this stays green."""
    entry = _named("configs", NAME)
    assert entry["reduced"] == ["depth", "n_routed_experts", "vocab_size"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "agentloop-closed", 1)
    assert len(cell["why"]) <= 200
    for word in ("8,192-token prefix", "~85 %", "2,048 of ~10k keys",
                 "~0 accepted", "attention overweighs"):
        assert word in cell["why"], word
    for name, (unit, source, layer) in NEW.items():
        m = _named("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
    assert CELL not in _named("per_layer", "decode_occupancy")["workloads"]
    # in the lists whose readers read something here and in no other
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] in JOINED + tuple(NEW):
            assert CELL in m["workloads"], m["name"]
        else:
            assert CELL not in m.get("workloads", ()), m["name"]
    assert BENCH["run_seconds"] == 51
    assert len(BENCH["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
