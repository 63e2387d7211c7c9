"""``family: commanda`` (PR 34): the configuration file against the catalog
row and the cut it states, the family's costs as integers, the cell's files
against the issue's table, its rehearsal, the four new readers, the
reference's variants, and the benchmark's entries."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import costs, families, reference_commanda  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL = "commanda-ragchat-closed"
NAME = "command-a-plus-05-2026"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("kv_visible_share", "mixed_attn_ms", "mixed_attn_roofline",
       "expert_absent_share")


def _config(rehearse=True):
    data = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                       NAME + ".json")))
    return cb_run._rehearsed(data, rehearse)


# ------------------------------------------------------- the configuration
def test_configuration_states_the_cut_and_the_published_counts():
    data = _config(False)
    assert data["reduced"] == ["depth", "num_experts", "vocab_size"]
    assert (data["depth"], data["num_hidden_layers"]) == (4, 32)
    assert (data["num_experts"], data["num_experts_published"],
            data["experts_first"]) == (16, 128, 0)
    assert (data["vocab_size"], data["vocab_size_published"]) \
        == (32768, 262144)
    # the floors: a whole period and four layers, 8 experts, an eighth of
    # the vocabulary
    assert data["depth"] % data["layer_switch"] == 0 and data["depth"] >= 4
    assert data["num_experts"] >= 8
    assert data["vocab_size"] * 8 >= data["vocab_size_published"]
    # no width moved
    assert (data["hidden_size"], data["num_attention_heads"],
            data["num_key_value_heads"], data["head_dim"],
            data["intermediate_size"], data["num_experts_per_tok"],
            data["sliding_window"], data["rope_theta"]) \
        == (4096, 128, 8, 128, 4096, 8, 4096, 50000)
    assert "eight v5e chips share each layer" in data["deployment"]
    assert "4,733,292,544 parameters" in data["deployment"]
    for key in ("depth", "num_experts", "vocab_size", "intermediate_size",
                "shared_expert_combination_strategy", "layer_norm",
                "sliding_window", "full_layers_rotary", "router",
                "deployment_layout", "weights", "vision_tower"):
        assert key in data["assumed"], key
    assert data["dtype"] == "bf16" and data["family"] == "commanda"
    assert len(data["source"]) <= 200


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_file_holds_the_catalog_rows_numbers():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == NAME)
    data = _config(False)
    assert data["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in data["reduced"]:
            assert data[key + "_published"] == value, key
        else:
            assert data[key] == value, key
    assert data["num_hidden_layers"] == row["layers"]


# -------------------------------------------------------------------- costs
def test_costs_of_the_configuration_as_integers():
    cfg = _config(False)
    family = families.load(cfg)
    a = family.arch(cfg)
    assert (a["layers"], a["d"], a["heads"], a["kv_heads"], a["head_dim"],
            a["vocab"]) == (4, 4096, 128, 8, 128, 32768)
    assert (a["sliding_layers"], a["full_layers"]) == (3, 1)
    assert family._expert_params(a) == 50_331_648
    # attention 142.6 M + norm + router 0.5 M + four shared experts 201.3 M
    assert family._layer_rest(a) == 142_606_336 + 4_096 + 524_288 \
        + 201_326_592
    assert costs.num_params(cfg) == 4_733_292_544
    assert costs.weight_bytes(cfg) == 9_466_585_088
    assert family.kv_bytes_per_key(cfg) == 4_096
    assert costs.kv_bytes_per_token(cfg) == 16_384
    # top-8 of 128, 16 held: one held expert a token a layer in expectation
    assert costs.active_params(cfg) == 4_733_292_544 \
        - 4 * 15 * 50_331_648
    assert family.visible_kv_bytes(cfg, 1000) == 4_096_000
    assert family.expert_bytes_touched(cfg, {"experts_touched_share": 0.5}) \
        == 32 * 50_331_648 * 2
    # every held expert touched: all weights (the tied table is the head)
    assert family.decode_weight_bytes(cfg, {"experts_touched_share": 1.0}) \
        == 9_466_585_088
    spec = family.build(cfg)
    mc = spec.model_config
    assert mc.num_params() == 4_733_292_544
    assert (mc.num_experts, mc.experts_held, mc.shared_experts) \
        == (128, (0, 16), 4)
    assert mc.layer_kinds == ("sliding", "sliding", "sliding", "full")
    assert (mc.norm, mc.parallel_block, mc.rope_interleaved,
            mc.router_score, mc.tie_embeddings) \
        == ("layernorm", True, True, "sigmoid", True)
    import dataclasses

    from deepspeed_tpu.models import mixtral

    preset = mixtral.MixtralConfig.command_a_plus()
    built = dataclasses.replace(mc, num_layers=32, vocab_size=262144,
                                experts_held=None)
    assert dataclasses.asdict(preset) == dataclasses.asdict(built)
    assert spec.decode_hooks["window_layers"]["window"] == 4096
    assert spec.decode_hooks["window_layers"]["layers"] \
        == {"full": 1, "sliding": 3}


def test_family_meets_the_contract():
    cfg = _config()
    family = families.load(cfg)
    for fn in families.REQUIRED + ("active_params", "decode_weight_bytes",
                                   "expert_bytes_touched",
                                   "cached_bytes_per_token",
                                   "visible_kv_bytes"):
        assert callable(getattr(family, fn)), fn
    assert set(families.SIZES) <= set(costs.arch(cfg))


# ------------------------------------------------------------ the cell's files
def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    mix, sizing = spec["traffic"], spec["sizing"]
    assert mix["kind"] == "serve_mixedattn" and mix["clients"] == 24
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 4096,
                                    "hi": 12288}
    assert mix["output_tokens"] == {"dist": "loguniform", "lo": 256,
                                    "hi": 768}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (mix["deck"], mix["shared_prefix_tokens"]) == (48, 0)
    assert (mix["score_rows"], mix["score_tokens"]) == (2, 6144)
    assert 2 * mix["score_tokens"] == 3 * spec["config"]["sliding_window"]
    assert sizing["serving"] == {"slots": 24, "max_seq_len": 16384}
    assert spec["cell"]["chips"] == 1 and len(spec["cell"]["why"]) <= 200
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tok_s",
                                                       "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == {
        "decode_occupancy", "kv_pool_peak_used", "peak_hbm.serve",
        "device_idle.serve", "sched_host_share", "kv_host_ms",
        "expert_ffn_ms", "expert_ffn_roofline", "expert_rows_per_read",
        "prefill_chunk_ms.longprompt", *NEW}
    # every prompt is past the window; the longest request fits its slot
    from chipbench import traffic
    deck = traffic.length_deck(mix)
    assert len(deck) == 48
    assert min(p for p, _ in deck) >= 4096
    assert max(p + o for p, o in deck) <= 16384
    # the two pools beside the weights: 12.3 GB of the chip's 16
    per_block = 32 * families.load(spec["config"]).kv_bytes_per_key(
        spec["config"])
    ring = -(-(4096 + 128) // 32) + 1
    full = (1 + 24 * 512) * per_block * 1
    window = (1 + 24 * ring) * per_block * 3
    assert (ring, full, window) == (133, 1_610_743_808, 1_255_538_688)
    total = full + window + costs.weight_bytes(spec["config"])
    assert 0.25 * 16e9 < total < 13e9
    # a window pool sized like the full one would not fit beside the rest
    assert 4 * full + costs.weight_bytes(spec["config"]) > 15.75e9


def test_rehearsal_of_the_cell_is_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    note = next(line for line in proc.stdout.splitlines()
                if "lie past the window" in line)
    assert "2 x 64 tokens, 19 positions a row of which 18" in note
    assert "released 0 blocks" not in note
    metrics = result["metrics"]
    assert 30.0 < metrics["kv_visible_share"]["value"] < 100.0
    # 4 held of 16 under a random router: about three quarters absent
    assert 60.0 < metrics["expert_absent_share"]["value"] < 90.0
    # the full kind's peak, of its own pool
    assert 0.0 < metrics["kv_pool_peak_used"]["value"] <= 100.0
    detail = json.loads(next(
        line for line in proc.stdout.splitlines()
        if line.startswith("chipbench: detail ")).split("detail ", 1)[1])
    kinds = detail["counters"]["kv_kinds"]
    assert kinds["full"]["num_blocks"] == 1 + 4 * 16
    assert kinds["sliding"]["num_blocks"] == 1 + 4 * 6
    assert detail["counters"]["window_blocks_released"] > 0


# ------------------------------------------------------------------ readers
READERS = cb_run.layer_metric_readers()


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
        _span("decode", 1.0, kv_valid=800_000, kv_visible=500_000,
              expert_rows=24, expert_rows_absent=168, experts_touched=13),
        _span("decode", 2.0, kv_valid=400_000, kv_visible=300_000,
              expert_rows=30, expert_rows_absent=162, experts_touched=14),
        _span("decode", 9.0, kv_valid=1, kv_visible=1, expert_rows=1,
              expert_rows_absent=0, experts_touched=1),   # outside the window
        _span("prefill", 1.5, kv_valid=5, kv_visible=5, expert_rows=7,
              expert_rows_absent=1, experts_touched=1)])
    monkeypatch.setattr(program_trace, "kept", lambda name: ring)
    trace = {
        "programs": {"jit_decode_step": [0.02, 0.02], "jit_prefill": [0.03]},
        "custom_call_s": {
            "jit_decode_step:mosaic:paged_decode_attn": 0.014,
            "jit_decode_step:mosaic:moe_gmm": 0.5,       # not the walk
            "jit_prefill:mosaic:paged_prefill_attn": 0.5}}
    cfg = _config(False)
    ctx = {"trace": trace, "window": (0.5, 5.0), "counters": {},
           "config": cfg, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert READERS["kv_visible_share"](ctx) == pytest.approx(
        100.0 * 800_000 / 1_200_000)
    assert READERS["expert_absent_share"](ctx) == pytest.approx(
        100.0 * 330 / 384)
    assert READERS["mixed_attn_ms"](ctx) == pytest.approx(7.0)
    assert READERS["mixed_attn_roofline"](ctx) == pytest.approx(
        100.0 * 400_000 * 4096 / 819e9 / 0.007)
    # a family without the byte function: no share of a roofline
    assert READERS["mixed_attn_roofline"]({**ctx, "config": {
        **cfg, "family": "olmoe"}}) is None


def test_new_readers_find_nothing_on_an_empty_context(monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    monkeypatch.setattr(program_trace, "kept", lambda name: None)
    empty = {"trace": None, "window": (0.0, 1.0), "counters": {},
             "config": _config(), "peaks": None}
    for name in NEW:
        assert READERS[name](empty) is None, name
    # a ring without the counters and a trace with the walk kernel (any
    # model of one layer kind; the parent of PR 34)
    monkeypatch.setattr(program_trace, "kept", lambda name: _Ring(
        [_span("decode", 0.5, slots=3, expert_rows=5, experts_touched=2)]))
    parent = {**empty, "peaks": {"hbm_bytes_per_s": 819e9},
              "trace": {"programs": {"jit_decode_step": [0.01]},
                        "custom_call_s": {
                            "jit_decode_step:mosaic:paged_decode_attn": 1.0}}}
    for name in NEW:
        assert READERS[name](parent) is None, name


def test_the_driver_reports_both_pools_and_judges_the_full_kind(monkeypatch):
    from chipbench.drivers import serve_mixedattn
    from deepspeed_tpu.telemetry import trace as program_trace

    def step(t0, full, window):
        return _span("step", t0, blocks_in_use=full,
                     window_blocks_in_use=window, window_num_blocks=3193,
                     window_blocks_released=3)

    monkeypatch.setattr(program_trace, "kept", lambda name: _Ring(
        [step(1.0, 5000, 3000), step(2.0, 5600, 3100), step(9.0, 1, 1)]))
    out = {"window": (0.5, 5.0), "counters": {"num_blocks": 12289},
           "samples": {"blocks_in_use": [5000, 5600]}}
    serve_mixedattn.both_pools(out)
    assert out["counters"]["kv_kinds"] == {
        "full": {"num_blocks": 12289, "peak_blocks_in_use": 5600},
        "sliding": {"num_blocks": 3193, "peak_blocks_in_use": 3100}}
    assert out["counters"]["window_blocks_released"] == 6
    # kv_pool_peak_used stays on the kind that can run dry
    assert out["counters"]["num_blocks"] == 12289
    assert out["samples"]["blocks_in_use"] == [5000, 5600]
    assert READERS["kv_pool_peak_used"](out) == pytest.approx(
        100.0 * 5600 / 12289)
    # no ring: nothing added
    monkeypatch.setattr(program_trace, "kept", lambda name: None)
    plain = {"window": (0.5, 5.0), "counters": {"num_blocks": 12289},
             "samples": {"blocks_in_use": [5000]}}
    serve_mixedattn.both_pools(plain)
    assert plain["counters"] == {"num_blocks": 12289}


def test_controls_each_shortcut_is_refused_by_the_comparison(tmp_path):
    """The harness mode PERF.md's table of controls is made with, at the
    rehearsal's widths: the plain reference passes ``check_logits`` and
    every shortcut variant comes out ``ok: false``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.drivers.serve_mixedattn",
         "--workload", CELL, "--seed", "2147483999", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1] == {"controls_held": True}
    assert [(c["variant"], c["ok"]) for c in lines[:-1]] == [
        (None, True), ("router_fp8", False), ("no_window", False),
        ("rope_full", False), ("shared_sum", False)]
    assert all(c["window_blocks_released"] > 0 for c in lines[:-1])


# --------------------------------------------------- the reference's variants
@pytest.mark.parametrize("variant", reference_commanda.VARIANTS[1:])
def test_each_shortcut_variant_moves_the_reference(variant):
    """The variants the comparison is checked with (PERF.md section 6) are
    different functions: at tiny widths each moves the logits by far more
    than rounding, or (``router_fp8``) the expert sets."""
    cfg = _config()
    rng = np.random.default_rng(0)
    import jax

    params = jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim > 1 else a,
        families.load(cfg).build(cfg).init_fn(jax.random.PRNGKey(1)))
    tokens = rng.integers(0, cfg["vocab_size"], (1, 48)).astype(np.int32)
    want = np.asarray(reference_commanda.logits(cfg, params, tokens))
    got = np.asarray(reference_commanda.logits(cfg, params, tokens,
                                               variant=variant))
    rel = float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))
    assert rel > 0.02, (variant, rel)
    with pytest.raises(ValueError, match="variant"):
        reference_commanda.hidden_states(cfg, params, tokens,
                                         variant="no_such")


# ------------------------------------------------------ the benchmark's entries
def test_benchmark_entries_of_this_family():
    """Looked up BY NAME: a later PR appends behind these."""
    def at(key, name):
        return [e["name"] for e in BENCH[key]].index(name)

    entry = BENCH["configs"][at("configs", NAME)]
    assert entry["reduced"] == ["depth", "num_experts", "vocab_size"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = BENCH["workloads"][at("workloads", CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "ragchat-closed", 1)
    first = at("per_layer", NEW[0])
    assert [m["name"] for m in BENCH["per_layer"][first:first + 4]] \
        == list(NEW)
    for m in BENCH["per_layer"][first:first + 4]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    # appended to the lists the issue names, behind the cells that were
    # there, and to no other
    joined = {"serve_tok_s", "decode_occupancy", "kv_pool_peak_used",
              "kv_host_ms", "sched_host_share", "peak_hbm.serve",
              "device_idle.serve", "expert_ffn_ms", "expert_ffn_roofline",
              "expert_rows_per_read", "prefill_chunk_ms.longprompt", *NEW}
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] in joined:
            assert CELL in m["workloads"], m["name"]
            assert "keye-longctx-closed" not in \
                m["workloads"][m["workloads"].index(CELL):], m["name"]
        else:
            assert CELL not in m.get("workloads", [CELL] * (
                m["name"] != "setup_s")), m["name"]
    # what was there before PR 34 is where it was, and PR 34's follow it
    assert [c["name"] for c in BENCH["configs"][:at("configs", NAME)]] == [
        "opt-1.3b", "gpt2-medium", "olmoe-1b-7b", "keye-vl2-30b-a3b"]
    assert [w["name"] for w in BENCH["workloads"][:at("workloads", CELL)]] \
        == ["opt13b-chat-closed", "gpt2m-train-1k", "opt13b-zero3-x4",
            "opt13b-longprompt-closed", "olmoe-decode-closed",
            "keye-longctx-closed"]
    assert BENCH["per_layer"][first - 1]["name"] == "kv_read_share"
    assert BENCH["run_seconds"] == 51
