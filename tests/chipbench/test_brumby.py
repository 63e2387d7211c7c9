"""``family: brumby`` (PR 57): the configuration file against the catalog row
(``depth`` alone reduced), the family's contract, the cell's files against
the issue's table, its rehearsal, the controls, the four new readers on a
made-up trace, and the benchmark's entries — every entry looked up BY NAME
and every shared list with ``<=``, so that the next cell does not turn this
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

from chipbench import costs, families, reference_brumby  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL = "brumby-longdoc-closed"
NAME = "Brumby-14B-Base"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("power_decode_ms", "power_decode_roofline", "power_chunk_ms",
       "power_chunk_roofline")
JOINED = ("serve_tok_s", "decode_occupancy", "peak_hbm.serve",
          "device_idle.serve", "sched_host_share", "kv_host_ms",
          "prefill_chunk_ms.longprompt")
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
    assert data["reduced"] == ["depth"] == _named("configs", NAME)["reduced"]
    for key, value in row["config"].items():
        assert data[key] == value, key
    assert data["num_hidden_layers"] == 40 and data["depth"] == 10


def test_configuration_states_what_it_assumes_and_what_it_holds():
    data = _config(False)
    assert data["family"] == "brumby" and data["dtype"] == "bf16"
    for key in ("power_degree", "gate", "gate_bias", "qk_norm_and_rotation",
                "normalisation", "state_float32", "no_kv_phase",
                "state_layout", "initialisation"):
        assert key in data["assumed"], key
    for word in ("four-chip pipeline", "4 x 10 layers", "4.86 G", "9.72 GB",
                 "340.8 MB", "343.4 MB", "no token is cached"):
        assert word in data["deployment"], word
    tiny = _config(True)
    assert (tiny["hidden_size"], tiny["head_dim"], tiny["depth"],
            tiny["dtype"]) == (64, 16, 2, "fp32")
    assert (tiny["num_attention_heads"], tiny["num_key_value_heads"]) \
        == (4, 2)


def test_family_meets_the_contract_and_counts_as_the_issue_says():
    cfg = _config(False)
    fam = families.load(cfg)
    a = costs.arch(cfg)
    assert all(k in a for k in families.SIZES)
    assert (a["layers"], a["heads"], a["kv_heads"], a["head_dim"]) \
        == (10, 40, 8, 128)
    assert a["state_rows_a_head"] == 8256 and a["vocab"] == 151936
    assert fam.num_params(cfg) == 4_859_358_800
    assert round(fam.num_params(cfg) * 2 / 1e9, 2) == 9.72
    assert fam.state_bytes_per_slot(cfg) == 340_807_680
    assert fam.cached_bytes_per_token(cfg) == 0
    # 12 live rows: each state and normaliser in and out: 8.2 GB a step,
    # as much as the weights
    assert fam.power_step_bytes(cfg, 12) == pytest.approx(8.18e9, rel=1e-3)
    flops, nbytes = fam.power_chunk_cost(cfg, 512)
    # 6 x 8 heads x 2 x 8,256 x 128 FLOPs a token a layer and the chunk's
    # own scores: the arithmetic bounds (far over the chip's ridge of 240)
    assert flops == pytest.approx(10 * 512 * 8 * (
        12 * 8256 * 128 + 5 * 4 * 128 * 128), rel=1e-9)
    assert flops / nbytes > 240
    with pytest.raises(ValueError, match="published block"):
        fam.build({**cfg, "attention_bias": True})
    assert reference_brumby.VARIANTS == (
        None, "state_bf16", "no_gate", "no_norm", "unit_offdiag", "no_reset")


def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    mix, sizing = spec["traffic"], spec["sizing"]
    assert mix["kind"] == "serve_power" and mix["clients"] == 12
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 2048,
                                    "hi": 16384}
    assert mix["output_tokens"] == {"dist": "loguniform", "lo": 256,
                                    "hi": 1024}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (mix["deck"], mix["shared_prefix_tokens"]) == (48, 0)
    assert (mix["score_rows"], mix["score_tokens"], mix["served_pairs"]) \
        == (2, 4112, 2)
    assert mix["settle_s"] > 0
    assert sizing["serving"] == {"slots": 12, "max_seq_len": 17408}
    assert sizing["serving"]["max_seq_len"] == 16384 + 1024


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
        "the engine's own cache): 3 calls of prefill[4x16] then 2 calls of "
        "prefill[1x64], each + 16 decode steps at 4 rows") in note
    tie = next(line for line in lines if "timed programs vs" in line)
    assert tie.endswith(": ok") and '"state_leaf": "float32"' in tie
    served = next(line for line in lines
                  if line.startswith("chipbench: served tokens: "))
    rows = json.loads(served.split("): ", 1)[1])
    assert len(rows) == 6 and all(r["replay"] == 1.0 and r["outside"] == 0.0
                                  for r in rows)
    metrics = result["metrics"]
    # every list the cell joins whose reader needs no device trace
    for name in ("decode_occupancy", "sched_host_share", "kv_host_ms"):
        assert metrics[name]["value"] >= 0.0, name
    # there is no pool: nothing of one is reported
    assert "kv_pool_peak_used" not in metrics \
        and "kv_state_share" not in metrics
    # judged on serve_tok_s alone: the tails are printed, unjudged
    assert "ttft_p95_ms" not in metrics and "itl_p95_ms" not in metrics
    assert any("TTFT median" in line for line in lines)
    detail = json.loads(next(
        line for line in lines
        if line.startswith("chipbench: detail ")).split("detail ", 1)[1])
    counters = detail["counters"]
    assert counters["num_blocks"] == 0 and counters["evicted"] == 0
    assert counters["state_programs_held"] is True
    # four slots x two layers x 2 KV heads x 9 distances x (16 x 16 + 16)
    assert counters["state_bytes"] == 4 * 2 * 2 * 9 * (16 * 16 + 16) * 4


@pytest.mark.limit(240)
def test_controls_each_shortcut_is_refused_by_the_comparison(tmp_path):
    """The harness mode PERF.md's table of controls is made with, at the
    rehearsal's widths: the plain reference passes BOTH comparisons and
    every shortcut is refused by at least one — the dropped reset by the
    SECOND sequence's logits alone (the first entered a fresh slot)."""
    proc = _run(["-m", "chipbench.drivers.serve_power", "--workload", CELL,
                 "--seed", "2147483999", "--seconds", "2", "--rehearse"],
                tmp_path, 220)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1] == {"controls_held": True}
    tie = lines[-2]["state_programs"]
    assert tie["ok"] and tie["state_leaf"] == "float32"
    assert {k for k, v in tie.items() if isinstance(v, dict) and v["held"]} \
        == {"decode", "prefill[4x16]", "prefill[1x64]"}
    got = {c["variant"]: c for c in lines[:-2]}
    assert list(got) == list(reference_brumby.VARIANTS)
    assert got[None]["logits_ok"] and got[None]["served_ok"]
    assert got[None]["served"]["tokens"] > 0 \
        and got[None]["served"]["replay"] == 1.0
    for v in reference_brumby.VARIANTS[1:]:
        assert not got[v]["logits_ok"], v
    parts, tol = got["no_reset"]["logits"]["logit_rel_rmse_parts"], \
        got["no_reset"]["logits"]["tolerance"]
    assert max(parts["row0.prefill"], parts["row0.decode"]) <= tol \
        < min(parts["row1.prefill"], parts["row1.decode"])


def test_the_limits_stand_between_their_written_readings():
    """Each bf16 limit between the largest sound chip reading and the
    smallest of the nearest shortcut it refuses (PERF.md section 6, PR 57)."""
    from chipbench.drivers import serve_power

    for limit, sound, unsound in SOUND_AND_UNSOUND:
        lo, hi = sorted((sound, unsound))
        assert lo < getattr(serve_power, limit[0])[limit[1]] < hi, limit


#: (limit, the worst sound reading, the mildest reading of the nearest
#: shortcut), TPU v5 lite, PR 57
SOUND_AND_UNSOUND = (
    (("LOGIT_REL_RMSE", "bf16"), 0.0175, 0.357),
    (("LOGIT_REL_RMSE", "exact"), 1.3e-5, 0.357),
    (("SERVED_REPLAY", "bf16"), 0.9780, 0.7824),
    (("SERVED_OUTSIDE", "bf16"), 0.0037, 0.0308),
    (("SERVED_GAP", "bf16"), 0.0028, 0.132),
    (("SERVED_REPLAY_A_REQUEST", "bf16"), 0.9709, 0.6265))


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
        _span("decode", 1.0, state_rows=12, state_resets=0, state_tokens=12),
        _span("decode", 2.0, state_rows=10, state_resets=0, state_tokens=10),
        _span("decode", 9.0, state_rows=1, state_resets=0,
              state_tokens=1),                         # outside the window
        _span("prefill", 1.5, state_rows=1, state_resets=1,
              state_tokens=448)])
    monkeypatch.setattr(program_trace, "kept", lambda name: ring)
    trace = {
        "programs": {"jit_decode_step": [0.03, 0.03],
                     "jit_prefill": [0.05, 0.05]},
        "custom_call_s": {
            "jit_decode_step:mosaic:power_step": 0.028,
            "jit_decode_step:mosaic:ssd_step": 0.5,       # not this family's
            "jit_prefill:mosaic:power_chunk_state": 0.044,
            "jit_prefill:mosaic:power_chunk_states": 0.5,  # another kernel
            "jit_prefill:mosaic:power_step": 0.5}}     # not a chunk kernel
    cfg = _config(False)
    fam = families.load(cfg)
    ctx = {"trace": trace, "window": (0.5, 5.0), "config": cfg,
           "counters": {}, "samples": {},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    assert READERS["power_decode_ms"](ctx) == pytest.approx(14.0)
    assert READERS["power_chunk_ms"](ctx) == pytest.approx(22.0)
    assert READERS["power_decode_roofline"](ctx) == pytest.approx(
        100.0 * fam.power_step_bytes(cfg, 11) / 819e9 / 0.014)
    flops, nbytes = fam.power_chunk_cost(cfg, 448)
    assert flops / 197e12 > nbytes / 819e9
    assert READERS["power_chunk_roofline"](ctx) == pytest.approx(
        100.0 * flops / 197e12 / 0.022)
    assert 0 < READERS["power_decode_roofline"](ctx) < 100
    assert 0 < READERS["power_chunk_roofline"](ctx) < 100
    # a family without the functions: no share of a roofline
    other = {**ctx, "config": {**cfg, "family": "olmoe"}}
    assert READERS["power_decode_roofline"](other) is None
    assert READERS["power_chunk_roofline"](other) is None


def test_new_readers_find_nothing_where_the_program_lacks_the_kernels(
        monkeypatch):
    """The parent of PR 57, and every other model: no ``power_*`` kernel in
    the trace, no ``state_rows`` on the ring — ``None``, never a raise."""
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
                            "jit_decode_step:mosaic:ssd_step": 1.0,
                            "jit_prefill:mosaic:ssd_chunk_state": 1.0}}}
    for name in NEW:
        assert READERS[name](parent) is None, name


def test_benchmark_entries_of_this_family():
    """Looked up BY NAME, never by position, and a list other cells may join
    held with ``<=``: a later PR appends behind these and this stays
    green."""
    entry = _named("configs", NAME)
    assert entry["reduced"] == ["depth"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "powerdoc-closed", 1)
    assert len(cell["why"]) <= 200
    for word in ("12 callers", "2,048-16,384", "256-1,024", "12 x 17,408",
                 "10 of 40 layers"):
        assert word in cell["why"], word
    for name in NEW:
        m = _named("per_layer", name)
        assert {CELL} <= set(m["workloads"]) and m["moves"] == "serve_tok_s"
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
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(BENCH["workloads"]) // 4)
