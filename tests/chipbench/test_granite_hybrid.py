"""``family: granite_hybrid`` (PR 55): the configuration file against the
catalog row (nothing reduced), the family's contract, the cell's files
against the issue's table, its rehearsal, the controls, the four new readers
on a made-up trace, and the benchmark's entries — every entry looked up BY
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

from chipbench import costs, families, reference_granite_hybrid  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL = "granite4h-chat-closed"
NAME = "granite-4.0-h-micro"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("ssd_decode_ms", "ssd_decode_roofline", "ssd_chunk_ms",
       "ssd_chunk_roofline")
JOINED = ("serve_tok_s", "decode_occupancy", "kv_pool_peak_used",
          "peak_hbm.serve", "device_idle.serve", "sched_host_share",
          "kv_host_ms", "kv_state_share", "prefill_chunk_ms.longprompt")
READERS = cb_run.layer_metric_readers()

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
@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_file_holds_the_catalog_rows_numbers():
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == NAME)
    data = _config(False)
    assert data["source"] == row["source_url"] \
        == _named("configs", NAME)["source"]
    assert data["reduced"] == [] == _named("configs", NAME)["reduced"]
    for key, value in row["config"].items():
        assert data[key] == value, key


def test_configuration_states_what_it_assumes_and_what_it_holds():
    data = _config(False)
    assert data["family"] == "granite_hybrid" and data["dtype"] == "bf16"
    for key in ("state_float32", "time_step_limit", "mamba_chunk_size",
                "initialisation", "rope_theta", "num_local_experts"):
        assert key in data["assumed"], key
    for word in ("whole", "40 layers", "100,352 rows", "6.38 GB", "76.4 MB",
                 "8,192 B"):
        assert word in data["deployment"], word
    tiny = _config(True)
    assert tiny["layer_types"] == data["layer_types"][:10]     # the period
    assert (tiny["hidden_size"], tiny["dtype"]) == (64, "fp32")


def test_family_meets_the_contract_and_counts_as_the_issue_says():
    cfg = _config(False)
    fam = families.load(cfg)
    a = costs.arch(cfg)
    assert all(k in a for k in families.SIZES)
    assert (a["layers"], a["ssm_layers"], a["attention_layers"]) == (40, 36, 4)
    assert fam.num_params(cfg) == 3_191_396_096
    assert round(fam.num_params(cfg) * 2 / 1e9, 2) == 6.38
    assert fam.state_bytes_per_slot(cfg) == 76_437_504
    assert fam.cached_bytes_per_token(cfg) == 8192
    assert reference_granite_hybrid.period(cfg) \
        == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    # 64 live rows: each 2 MiB matrix in and out, 36 layers: 9.7 GB a step
    assert fam.ssd_step_bytes(cfg, 64) == pytest.approx(9.78e9, rel=1e-3)
    flops, nbytes = fam.ssd_chunk_cost(cfg, 512)
    # float32 operands a chunk: 92 FLOPs a byte, under the chip's ridge of
    # 240 — the bytes bound
    assert flops / nbytes == pytest.approx(92, abs=1)
    with pytest.raises(ValueError, match="published block"):
        fam.build({**cfg, "position_embedding_type": "rope"})


def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    mix, sizing = spec["traffic"], spec["sizing"]
    assert mix["kind"] == "serve_ssm" and mix["clients"] == 64
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 32, "hi": 512}
    assert mix["output_tokens"] == {"dist": "loguniform", "lo": 32, "hi": 256}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (mix["deck"], mix["shared_prefix_tokens"]) == (96, 0)
    assert (mix["score_rows"], mix["score_tokens"], mix["served_pairs"]) \
        == (2, 784, 2)
    assert mix["settle_s"] > 0
    assert sizing["serving"] == {"slots": 64, "max_seq_len": 1024}
    chat = json.load(open(os.path.join(ROOT, "chipbench", "traffic",
                                       "chat-closed.json")))
    assert mix["prompt_tokens"] == chat["prompt_tokens"] \
        and mix["output_tokens"] == chat["output_tokens"]


def _run(args, tmp_path, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.limit(240)
def test_rehearsal_of_the_cell_is_correct(tmp_path):
    proc = _run([os.path.join(ROOT, "chipbench", "run.py"), "--workload",
                 CELL, "--seed", "2147483999", "--seconds", "2", "--trace",
                 "1", "--rehearse"], tmp_path, 220)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    note = next(line for line in lines if "through ONE slot" in line)
    assert "2 x 64 tokens through ONE slot (" in note and (
        "the engine's own cache) at block 16: 3 calls of prefill[4x16] then "
        "2 calls of prefill[1x64], each + 16 decode steps at 4 rows") in note
    tie = next(line for line in lines if "timed programs vs" in line)
    assert tie.endswith(": ok") and '"state_leaf": "float32"' in tie
    served = next(line for line in lines
                  if line.startswith("chipbench: served tokens: "))
    rows = json.loads(served.split("): ", 1)[1])
    assert len(rows) == 6 and all(r["replay"] == 1.0 and r["outside"] == 0.0
                                  for r in rows)
    metrics = result["metrics"]
    assert 0.0 < metrics["kv_state_share"]["value"] < 100.0
    assert 0.0 < metrics["kv_pool_peak_used"]["value"] <= 100.0
    # judged on serve_tok_s alone: the tails are printed, unjudged
    assert "ttft_p95_ms" not in metrics and "itl_p95_ms" not in metrics
    assert any("TTFT median" in line for line in lines)
    detail = json.loads(next(
        line for line in lines
        if line.startswith("chipbench: detail ")).split("detail ", 1)[1])
    counters = detail["counters"]
    assert counters["num_blocks"] == 1 + 4 * 8
    assert counters["state_programs_held"] is True
    # four slots x nine layers x (8 heads x 16 x 16 float32 + 3 x 160 tails)
    assert counters["state_bytes"] == 4 * 9 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    # one attention layer: K and V, 2 KV heads x 16 tokens x 16, float32
    assert counters["block_bytes_all_layers"] == 2 * 2 * 16 * 16 * 4


@pytest.mark.limit(240)
def test_controls_each_shortcut_is_refused_by_the_comparison(tmp_path):
    """The harness mode PERF.md's table of controls is made with, at the
    rehearsal's widths: the plain reference passes BOTH comparisons and
    every shortcut is refused by at least one — the dropped reset by the
    SECOND sequence's logits alone (the first entered a fresh slot)."""
    proc = _run(["-m", "chipbench.drivers.serve_ssm", "--workload", CELL,
                 "--seed", "2147483999", "--seconds", "2", "--rehearse"],
                tmp_path, 220)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1] == {"controls_held": True}
    # the timed programs against the pass held to the float32 limit
    tie = lines[-2]["state_programs"]
    assert tie["ok"] and tie["state_leaf"] == "float32"
    assert {k for k, v in tie.items() if isinstance(v, dict) and v["held"]} \
        == {"decode", "prefill[4x16]", "prefill[1x64]"}
    got = {c["variant"]: c for c in lines[:-2]}
    assert list(got) == list(reference_granite_hybrid.VARIANTS)
    assert got[None]["logits_ok"] and got[None]["served_ok"]
    assert got[None]["served"]["tokens"] > 0 \
        and got[None]["served"]["replay"] == 1.0
    for v in reference_granite_hybrid.VARIANTS[1:]:
        assert not got[v]["logits_ok"], v
    parts, tol = got["no_reset"]["logits"]["logit_rel_rmse_parts"], \
        got["no_reset"]["logits"]["tolerance"]
    assert max(parts["row0.prefill"], parts["row0.decode"]) <= tol \
        < min(parts["row1.prefill"], parts["row1.decode"])


def test_served_limits_are_this_cells_and_held_to_the_sample_together(
        monkeypatch):
    """The served-token limits are this cell's own, each between its sound
    and its unsound chip readings (``serve_state``'s stand above what the
    dropped decay reads here).  A 35-token reply with two tokens outside the
    nucleus (5.7 %) does not refuse a sound sample of 300 tokens (0.7 %);
    a sample whose tokens TOGETHER pass a limit is refused; so is one sound
    together whose one request replays too little; each request's line is
    kept."""
    from chipbench.drivers import serve_ssm, serve_state

    # sound, the dropped decay: PERF.md section 6, PR 55
    for limit, sound, unsound in (
            (serve_ssm.SERVED_REPLAY, 0.9853, 0.7656),
            (serve_ssm.SERVED_OUTSIDE, 0.0089, 0.0920),
            (serve_ssm.SERVED_GAP, 0.0096, 0.0984),
            (serve_ssm.SERVED_REPLAY_A_REQUEST, 0.961, 0.814)):
        lo, hi = sorted((sound, unsound))
        assert lo < limit["bf16"] < hi
    assert serve_ssm.SERVED_GAP["bf16"] < serve_state.SERVED_GAP["bf16"]
    notes = []
    job = type("Job", (), {"note": staticmethod(notes.append),
                           "config": {"dtype": "bf16"}})

    def sample(*rows):
        rows = [dict(zip(("tokens", "replay", "outside", "gap"), r))
                for r in rows]
        monkeypatch.setattr(serve_ssm, "check_served",
                            lambda *a: {"ok": False, "rows": rows,
                                        "limits": {}, "tokens": 0})
        return serve_ssm.check_served_sample(job, None, rows)

    sound = sample((35, 33 / 35, 2 / 35, 0.01), (100, 1.0, 0.0, 0.0),
                   (65, 1.0, 0.0, 0.0), (100, 0.99, 0.0, 0.0))
    assert sound["ok"] and len(sound["rows"]) == 4
    assert sound["outside"] == pytest.approx(2 / 300)
    assert sound["replay"] == pytest.approx(297 / 300)
    assert sound["replay_a_request"] == pytest.approx(33 / 35)
    assert sound["limits"] == {"replay": 0.88, "outside": 0.03,
                               "gap": 0.03, "replay_a_request": 0.85}
    assert "ok" in notes[-1] and "300 tokens together" in notes[-1]
    # the dropped decay's mildest sample on the chip, and one limit at a time
    for bad in ((100, 0.7656, 0.092, 0.0984), (100, 0.8, 0.0, 0.0),
                (100, 1.0, 0.04, 0.0), (100, 1.0, 0.0, 0.04)):
        assert not sample(bad, (20, 1.0, 0.0, 0.0))["ok"], bad
    assert "REFUSED" in notes[-1]
    # one slot at fault beside three sound requests: 0.96 together
    one = sample((40, 0.8, 0.0, 0.0), (100, 1.0, 0.0, 0.0),
                 (100, 0.99, 0.0, 0.0), (60, 0.98, 0.0, 0.0))
    assert one["replay"] > 0.95 and not one["ok"]
    empty = {"ok": False, "rows": [], "limits": {}, "tokens": 0}
    monkeypatch.setattr(serve_ssm, "check_served", lambda *a: empty)
    assert serve_ssm.check_served_sample(job, None, []) is empty


def test_state_kernels_reads_a_lowered_programs_mosaic_calls():
    """``serve_ssm.state_kernels``: the state kind's Mosaic calls of a
    lowered program's text, each with its operands' and results' element
    types; other kernels and other operations are passed over."""
    from chipbench.drivers import serve_ssm

    call = ('    %21:2 = stablehlo.custom_call @tpu_custom_call(%20, %16, '
            '%arg5) {{backend_config = "x", kernel_name = "{}", '
            'operand_layouts = [dense<0> : tensor<1xindex>]}} : '
            '(tensor<1xi32>, tensor<4x2x128x{}>, tensor<3x4x1x16x128xf32>) '
            '-> (tensor<4x1x128xf32>, tensor<3x4x1x16x128xf32>)')
    text = "\n".join([
        "module @jit_decode_step {", call.format("ssd_step", "f32"),
        call.format("ssd_step", "f32"), call.format("ssd_step", "bf16"),
        call.format("paged_decode_attn", "bf16"),
        "    %3 = stablehlo.add %1, %2 : tensor<4xf32>", "}"])
    assert serve_ssm.state_kernels(text, "ssd") == [
        ["ssd_step", ["i32", "bf16", "f32"], ["f32", "f32"]],
        ["ssd_step", ["i32", "f32", "f32"], ["f32", "f32"]]]
    assert serve_ssm.state_kernels(text, "kda") == []


# ------------------------------------------------------------------ readers
class _Ring:
    epoch_s, dropped = 0.0, 0

    def __init__(self, events):
        self._events = events

    def events(self):
        return self._events


def _span(name, t0_s, **args):
    return {"ph": "X", "name": name, "ts": t0_s * 1e6, "dur": 1e3,
            "args": args}


def test_new_readers_on_a_made_up_trace(monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    ring = _Ring([
        _span("decode", 1.0, state_rows=64, state_resets=0, state_tokens=64),
        _span("decode", 2.0, state_rows=60, state_resets=0, state_tokens=60),
        _span("decode", 9.0, state_rows=1, state_resets=0,
              state_tokens=1),                         # outside the window
        _span("prefill", 1.5, state_rows=4, state_resets=1,
              state_tokens=448)])
    monkeypatch.setattr(program_trace, "kept", lambda name: ring)
    trace = {
        "programs": {"jit_decode_step": [0.03, 0.03],
                     "jit_prefill": [0.03, 0.05]},
        "custom_call_s": {
            "jit_decode_step:mosaic:ssd_step": 0.032,
            "jit_decode_step:mosaic:paged_decode_attn": 0.5,  # not the scan
            "jit_prefill:mosaic:ssd_chunk_state": 0.006,
            "jit_prefill:mosaic:ssd_chunk_states": 0.5,   # another kernel
            "jit_prefill:mosaic:ssd_step": 0.5,           # not a chunk kernel
            "jit_prefill:mosaic:paged_prefill_attn": 0.5}}
    cfg = _config(False)
    fam = families.load(cfg)
    ctx = {"trace": trace, "window": (0.5, 5.0), "config": cfg,
           "counters": {}, "samples": {},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    assert READERS["ssd_decode_ms"](ctx) == pytest.approx(16.0)
    assert READERS["ssd_chunk_ms"](ctx) == pytest.approx(3.0)
    assert READERS["ssd_decode_roofline"](ctx) == pytest.approx(
        100.0 * fam.ssd_step_bytes(cfg, 62) / 819e9 / 0.016)
    flops, nbytes = fam.ssd_chunk_cost(cfg, 448)
    assert flops / 197e12 < nbytes / 819e9
    assert READERS["ssd_chunk_roofline"](ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.003)
    # a family without the functions: no share of a roofline
    other = {**ctx, "config": {**cfg, "family": "olmoe"}}
    assert READERS["ssd_decode_roofline"](other) is None
    assert READERS["ssd_chunk_roofline"](other) is None


def test_new_readers_find_nothing_where_the_program_lacks_the_kernels(
        monkeypatch):
    """The parent of PR 55, and every other model: no ``ssd_*`` kernel in the
    trace, no ``state_rows`` on the ring — ``None``, never a raise."""
    from deepspeed_tpu.telemetry import trace as program_trace

    monkeypatch.setattr(program_trace, "kept", lambda name: None)
    empty = {"trace": None, "window": (0.0, 1.0), "counters": {},
             "samples": {}, "config": _config(), "peaks": None}
    for name in NEW:
        assert READERS[name](empty) is None, name
    monkeypatch.setattr(program_trace, "kept", lambda name: _Ring(
        [_span("decode", 0.5, slots=3), _span("prefill", 0.6, rows=2)]))
    parent = {**empty,
              "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
              "trace": {"programs": {"jit_decode_step": [0.01],
                                     "jit_prefill": [0.01]},
                        "custom_call_s": {
                            "jit_decode_step:mosaic:kda_step": 1.0,
                            "jit_prefill:mosaic:kda_chunk_state": 1.0}}}
    for name in NEW:
        assert READERS[name](parent) is None, name


def test_benchmark_entries_of_this_family():
    """Looked up BY NAME, never by position: a later PR appends behind
    these and this stays green."""
    entry = _named("configs", NAME)
    assert entry["reduced"] == []
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "statechat-closed", 1)
    assert len(cell["why"]) <= 200
    for word in ("64 callers", "32-512", "32-256", "64 x 1,024"):
        assert word in cell["why"], word
    for name in NEW:
        m = _named("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert m["source"] == "device_trace"
        assert m["layer"] == ("kernels" if name.endswith("roofline")
                              else "model step")
    # in the lists the issue names and in no other
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] in JOINED + NEW:
            assert CELL in m["workloads"], m["name"]
        else:
            assert CELL not in m.get("workloads", ()), m["name"]
    assert BENCH["run_seconds"] == 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
