"""``family: kimi_linear`` (PR 51): the configuration file against the
catalog row and the cut it states, the cell's files against the issue's
table, its rehearsal (both ``--trace`` values), the five new readers on a
synthetic trace, the controls, and the benchmark's entries — every entry
looked up BY NAME, so that the next cell does not turn this red."""

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

from chipbench import costs, families, reference_kimi_linear  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL = "kimilinear-statedecode-closed"
NAME = "kimi-linear-48b-a3b"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("kda_decode_ms", "kda_decode_roofline", "kda_chunk_state_ms",
       "kda_chunk_state_roofline", "kv_state_share")
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
    assert data["reduced"] == ["depth", "num_experts", "vocab_size"]
    assert (data["depth"], data["num_hidden_layers"]) == (8, 27)
    assert (data["num_experts"], data["num_experts_published"],
            data["experts_first"]) == (32, 256, 0)
    assert (data["vocab_size"], data["vocab_size_published"]) \
        == (20480, 163840)
    # the floors: a period + four, 8 experts, an eighth of the vocabulary
    assert data["depth"] >= 4 + 4 and data["num_experts"] >= 8
    assert data["vocab_size"] * 8 >= data["vocab_size_published"]
    # two whole periods of 3 KDA : 1 MLA, the leading dense layer once
    assert reference_kimi_linear.layer_kinds(data) \
        == ["kda", "kda", "kda", "latent"] * 2
    assert data["first_k_dense_replace"] == 1
    # no width moved
    lin = data["linear_attn_config"]
    assert (data["hidden_size"], data["intermediate_size"],
            data["moe_intermediate_size"], data["kv_lora_rank"],
            data["qk_nope_head_dim"], data["qk_rope_head_dim"],
            data["v_head_dim"], data["num_attention_heads"],
            data["num_experts_per_token"], lin["num_heads"],
            lin["head_dim"], lin["short_conv_kernel_size"]) \
        == (2304, 9216, 1024, 512, 128, 64, 128, 32, 8, 32, 128, 4)
    for key in ("depth", "num_experts", "vocab_size", "kda_gate_rank",
                "kda_float32", "kda_conv", "selection_bias", "softmax_scale",
                "num_key_value_heads", "weights", "deployment_layout"):
        assert key in data["assumed"], key
    assert "eight v5e chips" in data["deployment"]
    assert "12.4 MiB" in data["deployment"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_file_holds_the_catalog_rows_numbers():
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
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
    assert (a["kda_layers"], a["latent_layers"], a["dense_layers"]) \
        == (6, 2, 1)
    assert fam.num_params(config) == 2_092_550_080
    assert fam.num_params(config) * 2 == costs.weight_bytes(config)
    assert fam.state_bytes_per_slot(config) == 13_025_280
    assert fam.cached_bytes_per_token(config) == 2304
    # a decode step of 192 rows: 4.8 GB of state in and out
    assert fam.kda_step_bytes(config, 192) == 6 * 4 * 192 * 32 * (
        2 * 128 * 128 + 6 * 128)
    assert round(fam.kda_step_bytes(config, 192) / 1e9, 2) == 4.95
    # a [4, 128] call's 512 valid tokens: 8 chunks a head a layer
    assert fam.kda_chunk_flops(config, 512) == 6 * 8 * 32 * (
        6 * 64 * 128 * 128 + 2 * 64 * 64 * 128 + 2 * 128 ** 3)
    assert fam.kda_chunk_bytes(config, 512) == 6 * 8 * 32 * 4 * (
        5 * 64 * 128 + 64 * 64 + 128)
    # the whole model by the same formula (the issue's 49.1 G)
    whole = {**config, "depth": 27, "num_experts": 256, "vocab_size": 163840}
    assert round(fam.num_params(whole) / 1e9, 1) == 49.1


def test_family_meets_the_contract():
    fam = families.load(_config())
    for fn in families.REQUIRED + (
            "active_params", "decode_weight_bytes", "state_bytes_per_slot",
            "cached_bytes_per_token", "kda_step_bytes", "kda_chunk_flops",
            "kda_chunk_bytes"):
        assert callable(getattr(fam, fn)), fn
    assert "state_bytes_per_slot" in fam.__doc__


def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    mix, sizing = spec["traffic"], spec["sizing"]["serving"]
    assert mix["kind"] == "serve_state" and mix["clients"] == 192
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 512,
                                    "hi": 2048}
    assert mix["output_tokens"] == {"dist": "loguniform", "lo": 512,
                                    "hi": 2048}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (mix["deck"], mix["score_rows"], mix["score_tokens"],
            mix["shared_prefix_tokens"]) == (96, 2, 2064, 0)
    assert sizing == {"slots": 192, "max_seq_len": 4352}
    assert 2048 + 2048 + 256 == sizing["max_seq_len"]
    config = spec["config"]
    fam = families.load(config)
    # state + latent pool + weights, before temporaries: 8.8 GB of 16
    state = 192 * fam.state_bytes_per_slot(config)
    pool = (1 + 192 * 17) * 256 * 640 * 2 * 2
    total = state + pool + costs.weight_bytes(config)
    assert round(state / 1e9, 2) == 2.5 and round(pool / 1e9, 2) == 2.14
    assert 0.25 * 16e9 < 8.7e9 < total < 8.9e9
    assert [m["name"] for m in spec["end_to_end"]] == ["serve_tok_s",
                                                       "setup_s"]


def _run(args, tmp_path, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
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
    note = next(line for line in lines if "through ONE slot" in line)
    assert "2 x 64 tokens through ONE slot at block 16, 19 positions" in note
    # ... and the tokens the timed engine served: a float32 engine's ARE the
    # reference's draws
    served = next(line for line in lines
                  if line.startswith("chipbench: served tokens: "))
    rows = json.loads(served.split("): ", 1)[1])
    assert len(rows) == 6 and all(r["replay"] == 1.0 and r["outside"] == 0.0
                                  for r in rows)
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == {"serve_tok_s", "setup_s"}
        return
    assert 0.0 < metrics["kv_state_share"]["value"] < 100.0
    assert 0.0 < metrics["kv_pool_peak_used"]["value"] <= 100.0
    assert metrics["expert_rows_per_read"]["value"] > 0
    detail = json.loads(next(
        line for line in lines
        if line.startswith("chipbench: detail ")).split("detail ", 1)[1])
    counters = detail["counters"]
    assert counters["block_size"] == 16
    assert counters["num_blocks"] == 1 + 4 * 8
    # four slots x six layers x (a 4 x 16 x 16 float32 state + 3 x 192 tails)
    assert counters["state_bytes"] == 4 * 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert counters["block_bytes_all_layers"] == 2 * 16 * 128 * 4
    settled = next(line for line in lines
                   if line.startswith("chipbench: settled "))
    assert float(settled.split()[2]) >= 0.5 and "requests ended" in settled


# ------------------------------------------------- the served-token comparison
def _finished(uid, slot, at, cut=False):
    return {"uid": uid, "slot": slot, "at": at, "cut": cut}


def test_served_sample_pairs_a_request_with_its_slots_earlier_one():
    from chipbench.drivers.serve_state import served_sample

    served = [_finished("a", 0, 1.0), _finished("b", 1, 2.0),
              _finished("c", 0, 11.0),         # in the window, after "a"
              _finished("d", 2, 12.0),         # its slot's first: no pair
              _finished("e", 0, 13.0),         # slot 0 again: not twice
              _finished("f", 1, 14.0),         # after "b"
              _finished("g", None, 15.0), _finished("h", None, 16.0),
              _finished("i", 2, 17.0)]         # a third pair: over the two
    got = served_sample(served, (10.0, 20.0), 2)
    assert [r["uid"] for r in got] == ["a", "c", "b", "f"]
    # a window that ended too few: the latest pairs before it
    got = served_sample(served, (16.5, 20.0), 2)
    assert [r["uid"] for r in got] == ["d", "i", "b", "f"]
    assert served_sample(served[:2], (0.0, 20.0), 2) == []
    # a warm-in request, cut to a few tokens, is in no pair
    served[0]["cut"] = True
    got = served_sample(served, (10.0, 20.0), 2)
    assert [r["uid"] for r in got] == ["c", "e", "b", "f"]


@pytest.mark.limit(240)
def test_a_wrong_slot_operand_passes_the_logits_and_fails_the_served_tokens(
        monkeypatch):
    """What the served-token comparison is FOR: a fault of the engine's own
    plumbing.  The prefill call of the timed engine is handed each row's
    slot off by one (``ServingEngine._bt``): its chunks advance a
    neighbour's state and the decode steps read one no prompt went through.
    The logits comparison drives the model's programs with operands of its
    own and passes; the tokens the engine served do not replay."""
    import argparse

    from chipbench.drivers import serve_state
    from deepspeed_tpu.inference.serving import ServingEngine

    sound = ServingEngine._bt

    def off_by_one(self, tables, rows=None):
        bt = sound(self, tables, rows)
        if rows is not None:
            bt["slot"] = (bt["slot"] + 1) % (self.slots + 1)
        return bt

    monkeypatch.setattr(ServingEngine, "_bt", off_by_one)
    job = cb_run.Job(argparse.Namespace(
        seed=2147483999, seconds=4.0, rehearse=True, trace=0,
        keep_trace=None), cb_run.load_cell(CELL, True))
    # replies long enough that no request replays by chance
    job.traffic["output_tokens"] = {"dist": "loguniform", "lo": 16, "hi": 32}
    result = serve_state.run(job)
    assert result["failed"] == 0 and result["correct"] is False
    note = next(n for n in job.notes if "through ONE slot" in n)
    assert max(json.loads(note.split("RMSE ", 1)[1]).values()) < 2e-4
    counters = result["counters"]
    assert counters["served_tokens"] > 0
    assert counters["served_replay"] < 0.8 and counters["served_gap"] > 0.25


@pytest.mark.parametrize("top_p", [0.9, 1.0])
def test_replay_is_the_samplers_own_draw_and_measures_a_lost_race(top_p):
    """The reference's draw under a request's key IS what the program's
    sampler (``ops/sampling.py``) draws from the same logits, token for
    token; a token that is not the draw lost the race by a margin, and one
    the nucleus excludes is counted outside it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.drivers.serve_state import TOKEN_SALT, _replay
    from deepspeed_tpu.ops import sampling

    assert TOKEN_SALT == sampling.SALT_TOKEN
    n, vocab, seed, temp = 64, 512, 2147483999, 0.7
    logits = 1.5 * jax.random.normal(jax.random.PRNGKey(3), (n, vocab))
    _, lp = sampling.filtered_logprobs(
        logits, jnp.full(n, temp), jnp.zeros(n, jnp.int32),
        jnp.full(n, top_p))
    drawn = sampling.sample_tokens(lp, sampling.slot_keys(
        jnp.full(n, seed, jnp.uint32), jnp.arange(n), sampling.SALT_TOKEN))
    same, outside, gap = _replay(logits, drawn, jnp.uint32(seed),
                                 jnp.float32(temp), jnp.float32(top_p))
    assert bool(same.all()) and not bool(outside.any())
    assert float(gap.max()) == 0.0
    # other tokens: the least probable one is outside a nucleus of 0.9
    worst = jnp.argmin(logits, axis=-1).astype(jnp.int32)
    same, outside, gap = _replay(logits, worst, jnp.uint32(seed),
                                 jnp.float32(temp), jnp.float32(top_p))
    assert not bool(same.any()) and float(gap.min()) > 0.0
    assert bool(outside.all()) == (top_p < 1.0)
    assert np.isfinite(np.asarray(gap)).all()


def test_reference_pads_leave_a_sequence_and_its_carry_as_they_were():
    """``lengths``: a sequence padded past its end gives the logits of the
    sequence alone at its own positions, and under ``no_reset`` hands on the
    state it had at its LAST REAL token."""
    import jax
    import numpy as np

    cfg = _config()
    fam = families.load(cfg)
    params = fam.build(cfg).init_fn(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    a, b = rng.integers(0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    plain = np.asarray(reference_kimi_linear.logits(cfg, params, a[None, :17]))
    padded = np.asarray(reference_kimi_linear.logits(
        cfg, params, np.stack([a, b]), lengths=[17, 24]))
    np.testing.assert_allclose(padded[0, :17], plain[0], rtol=1e-5, atol=1e-5)
    at = np.asarray([[3, 16], [5, 23]])
    np.testing.assert_array_equal(np.asarray(reference_kimi_linear.logits(
        cfg, params, np.stack([a, b]), at=at, lengths=[17, 24]))[0],
        padded[0, [3, 16]])
    # the carry: b starts from a's state after its 17 real tokens
    stale = np.asarray(reference_kimi_linear.logits(
        cfg, params, np.stack([a, b]), variant="no_reset",
        lengths=[17, 24]))[1]
    # (what follows a's 17th token in its row changes nothing)
    again = np.asarray(reference_kimi_linear.logits(
        cfg, params, np.stack([np.concatenate([a[:17], a[:7]]), b]),
        variant="no_reset", lengths=[17, 24]))[1]
    np.testing.assert_array_equal(stale, again)
    assert np.abs(stale - padded[1]).max() > 1e-3


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
        _span("decode", 1.0, state_rows=192, state_resets=0,
              state_tokens=192),
        _span("decode", 2.0, state_rows=188, state_resets=0,
              state_tokens=188),
        _span("decode", 9.0, state_rows=1, state_resets=0,
              state_tokens=1),                         # outside the window
        _span("prefill", 1.5, state_rows=4, state_resets=1,
              state_tokens=448)])
    monkeypatch.setattr(program_trace, "kept", lambda name: ring)
    trace = {
        "programs": {"jit_decode_step": [0.02, 0.02], "jit_prefill": [0.03]},
        "custom_call_s": {
            "jit_decode_step:mosaic:kda_step": 0.016,
            "jit_decode_step:mosaic:moe_gmm": 0.5,      # not the rule
            "jit_prefill:mosaic:kda_chunk_state": 0.003,
            "jit_prefill:mosaic:kda_chunk_states": 0.5,  # another kernel
            "jit_prefill:mosaic:kda_step": 0.5,         # not a chunk kernel
            "jit_prefill:mosaic:moe_gmm": 0.5}}
    cfg = _config(False)
    fam = families.load(cfg)
    ctx = {"trace": trace, "window": (0.5, 5.0), "config": cfg,
           "counters": {"state_bytes": 2_500_000_000,
                        "block_bytes_all_layers": 655_360},
           "samples": {"blocks_in_use": [1000, 1500, 1200]},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    assert READERS["kda_decode_ms"](ctx) == pytest.approx(8.0)
    assert READERS["kda_chunk_state_ms"](ctx) == pytest.approx(3.0)
    assert READERS["kda_decode_roofline"](ctx) == pytest.approx(
        100.0 * fam.kda_step_bytes(cfg, 190) / 819e9 / 0.008)
    # float32 operands a chunk: 64 FLOPs a byte, under the chip's ridge of
    # 240 — the bytes bound
    flops, nbytes = fam.kda_chunk_flops(cfg, 448), \
        fam.kda_chunk_bytes(cfg, 448)
    assert flops / 197e12 < nbytes / 819e9
    assert READERS["kda_chunk_state_roofline"](ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.003)
    assert READERS["kv_state_share"](ctx) == pytest.approx(
        100.0 * 2.5e9 / (2.5e9 + 1500 * 655_360))
    # a family without the functions: no share of a roofline
    other = {**ctx, "config": {**cfg, "family": "olmoe"}}
    assert READERS["kda_decode_roofline"](other) is None
    assert READERS["kda_chunk_state_roofline"](other) is None


def test_new_readers_find_nothing_on_an_empty_context(monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    monkeypatch.setattr(program_trace, "kept", lambda name: None)
    empty = {"trace": None, "window": (0.0, 1.0), "counters": {},
             "samples": {}, "config": _config(), "peaks": None}
    for name in NEW:
        assert READERS[name](empty) is None, name
    # a ring without the counters, a trace without the kernels and a driver
    # without the state's counters (any other model; the parent of PR 51)
    monkeypatch.setattr(program_trace, "kept", lambda name: _Ring(
        [_span("decode", 0.5, slots=3, expert_rows=5, experts_touched=2),
         _span("prefill", 0.6, kv_blocks=4, rows=2)]))
    parent = {**empty, "counters": {"block_size": 32, "num_blocks": 9},
              "samples": {"blocks_in_use": [3, 4]},
              "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
              "trace": {"programs": {"jit_decode_step": [0.01],
                                     "jit_prefill": [0.01]},
                        "custom_call_s": {
                            "jit_decode_step:mosaic:paged_latent_attn": 1.0,
                            "jit_prefill:mosaic:moe_gmm": 1.0}}}
    for name in NEW:
        assert READERS[name](parent) is None, name


@pytest.mark.limit(240)
def test_controls_each_shortcut_is_refused_by_the_comparison(tmp_path):
    """The harness mode PERF.md's table of controls is made with, at the
    rehearsal's widths: the cell is run as ``run`` runs it; the plain
    reference passes BOTH comparisons — the logits through one slot, the
    tokens the timed engine served — and every shortcut is refused by at
    least one: the dropped reset by the SECOND sequence's logits alone (the
    first entered a fresh slot), the dropped decay by the served tokens
    too."""
    proc = _run(["-m", "chipbench.drivers.serve_state", "--workload", CELL,
                 "--seed", "2147483999", "--seconds", "2", "--rehearse"],
                tmp_path, 220)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1] == {"controls_held": True}
    got = {c["variant"]: c for c in lines[:-1]}
    assert list(got) == list(reference_kimi_linear.VARIANTS)
    assert got[None]["logits_ok"] and got[None]["served_ok"]
    assert got[None]["served"]["tokens"] > 0 \
        and got[None]["served"]["replay"] == 1.0
    # a slot's earlier request, then its later
    rows = got[None]["served"]["rows"]
    assert len(rows) % 2 == 0 and all(
        a["slot"] == b["slot"] for a, b in zip(rows[::2], rows[1::2]))
    for v in reference_kimi_linear.VARIANTS[1:]:
        assert not got[v]["logits_ok"], v
    assert not got["no_decay"]["served_ok"]
    parts, tol = got["no_reset"]["logits"]["logit_rel_rmse_parts"], \
        got["no_reset"]["logits"]["tolerance"]
    assert max(parts["row0.prefill"], parts["row0.decode"]) <= tol \
        < min(parts["row1.prefill"], parts["row1.decode"])


def test_benchmark_entries_of_this_family():
    """Looked up BY NAME, never by position: a later PR appends behind
    these and this stays green."""
    entry = _named("configs", NAME)
    assert entry["reduced"] == ["depth", "num_experts", "vocab_size"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "statedecode-closed", 1)
    assert len(cell["why"]) <= 200
    for word in ("192 callers", "512-2,048", "192 x 4,352", "1/8",
                 "8 of 27 layers"):
        assert word in cell["why"], word
    for name in NEW:
        m = _named("per_layer", name)
        assert CELL in m["workloads"] and m["moves"] == "serve_tok_s"
    assert {n: _named("per_layer", n)["layer"] for n in NEW} == {
        "kda_decode_ms": "model step", "kda_decode_roofline": "kernels",
        "kda_chunk_state_ms": "model step",
        "kda_chunk_state_roofline": "kernels",
        "kv_state_share": "KV manager"}
    assert {n: _named("per_layer", n)["source"] for n in NEW} == {
        "kda_decode_ms": "device_trace",
        "kda_decode_roofline": "device_trace",
        "kda_chunk_state_ms": "device_trace",
        "kda_chunk_state_roofline": "device_trace",
        "kv_state_share": "program_counter"}
    # in the lists the issue names and in no other
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] in JOINED + NEW:
            assert CELL in m["workloads"], m["name"]
        else:
            assert CELL not in m.get("workloads", ()), m["name"]
    assert BENCH["run_seconds"] == 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
