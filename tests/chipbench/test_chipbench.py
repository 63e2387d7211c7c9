"""The benchmark's own tests (tier-1, CPU): the command's contract in
rehearsal, the data files against ``BENCHMARK.json``, the reference against
the program's own float32 forward, and the arithmetic of ``costs`` and
``trace_reduce`` against numbers counted by hand.

The rehearsals run as child processes: the command turns on the
persistent compile cache and registers a compile listener, which must not
leak into the other tests of this pytest worker.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import costs, families, traffic, trace_reduce  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


# ------------------------------------------------------------ the command
def _run(args, root=ROOT, tmp=None, timeout=300, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    if tmp is not None:          # keep the rehearsal's cache out of the repo
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp)
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def _result(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _cells_of_kind(kind):
    out = []
    for w in BENCH["workloads"]:
        mix = json.load(open(os.path.join(
            ROOT, "chipbench", "traffic", w["traffic"] + ".json")))
        if mix["kind"] == kind:
            out.append(w)
    return out


@pytest.mark.parametrize("kind", ["serve_closed", "train_steps"])
def test_rehearsal_prints_the_contracts_line(kind, tmp_path):
    """--rehearse: same code, tiny widths, CPU named in ``device``; the
    last line has exactly the contract's keys, --trace 0 the cell's
    end-to-end metrics and --trace 1 per-layer metrics."""
    cells = _cells_of_kind(kind)
    if not cells:
        pytest.skip(f"no cell of kind {kind} in BENCHMARK.json")
    cell = min(cells, key=lambda w: w["chips"])
    spec = cb_run.load_cell(cell["name"])
    for trace in (0, 1):
        res = _result(_run(
            ["--workload", cell["name"], "--seed", str(2 ** 31 + 12345),
             "--seconds", "1", "--trace", str(trace), "--rehearse"],
            tmp=tmp_path, devices=cell["chips"]))
        assert set(res) == RESULT_KEYS, set(res)
        assert set(res["device"]) == DEVICE_KEYS
        assert res["device"]["platform"] == "cpu"
        assert res["correct"] is True and res["failed"] == 0
        assert res["attempted"] > 0
        want = spec["per_layer"] if trace else spec["end_to_end"]
        units = {m["name"]: m["unit"] for m in want}
        assert res["metrics"], "no metric reported"
        assert set(res["metrics"]) <= set(units)
        for name, m in res["metrics"].items():
            assert set(m) == {"value", "unit"} and m["unit"] == units[name]
            assert isinstance(m["value"], float) and m["value"] > 0
        if not trace:
            assert set(res["metrics"]) == set(units)   # every one, setup_s too


def test_refuses_to_run_without_a_tpu(tmp_path):
    proc = _run(["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
                 "--seconds", "1", "--trace", "0"], tmp=tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout, proc.stdout


def test_unknown_workload_is_an_error(tmp_path):
    proc = _run(["--workload", "no-such-cell", "--seed", "1", "--seconds",
                 "1", "--trace", "0", "--rehearse"], tmp=tmp_path)
    assert proc.returncode != 0 and "{" not in proc.stdout


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A later PR's cell: a new configuration, traffic mix, sizing file and
    per-layer metric, plus entries in BENCHMARK.json — and no edit to any
    file the harness already has."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cb = root / "chipbench"
    cfg = json.load(open(cb / "configs" / "opt-1.3b.json"))
    cfg["rehearse"]["num_hidden_layers"] = 3
    (cb / "configs" / "opt-new.json").write_text(json.dumps(cfg))
    mix = json.load(open(cb / "traffic" / "chat-closed.json"))
    mix["rehearse"]["clients"] = 3
    (cb / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (cb / "workloads" / "new-cell.json").write_text(json.dumps(
        {"serving": {"slots": 3, "max_seq_len": 64, "block_size": 8,
                     "prefill_chunk": 16}}))
    (cb / "layer_metrics" / "steps_counted.py").write_text(
        'SPECS = [{"name": "steps_counted", "unit": "steps", "better": '
        '"higher", "source": "program_counter", "layer": "scheduler", '
        '"moves": "serve_tok_s"}]\n\n\n'
        'def read(ctx):\n    return float(ctx["counters"]["iterations"])\n')
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "opt-new", "source": cfg["source"],
                             "file": "chipbench/configs/opt-new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new-cell", "config": "opt-new",
                               "traffic": "new-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] in (
                "serve_tok_s", "ttft_p95_ms", "itl_p95_ms", "step_wall_ms"):
            m["workloads"].append("new-cell")
    bench["per_layer"].append(
        {"name": "steps_counted", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "serve_tok_s", "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = _result(_run(["--workload", "new-cell", "--seed", "3", "--seconds",
                        "1", "--trace", "1", "--rehearse"], root=str(root),
                       tmp=tmp_path / "cache"))
    assert res["correct"] is True
    assert set(res["metrics"]) == {"steps_counted", "step_wall_ms"}
    assert res["metrics"]["steps_counted"]["value"] >= 1


def _hashes(folder):
    out = {}
    for base, dirs, files in os.walk(folder):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            path = os.path.join(base, f)
            out[os.path.relpath(path, folder)] = hashlib.sha256(
                open(path, "rb").read()).hexdigest()
    return out


def test_a_new_family_is_files_and_entries_only(tmp_path):
    """chipbench/README.md, "Adding things": "new files + new entries, no
    edit to a file that exists" — for a FAMILY.  ``new_family/`` holds what
    a later ``model_config`` PR would bring: its own ``families/<family>.py``
    and reference file, a configuration whose keys are neither OPT's nor
    GPT-2's (KV heads != heads, untied head, RoPE) and two sizing files.
    Both drivers run it with ``correct: true`` and every file the copy
    started with hashes the same afterwards."""
    assert "new files + new entries, no edit to a file that exists" in open(
        os.path.join(ROOT, "chipbench", "README.md")).read()
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(root / "chipbench")
    shutil.copytree(os.path.join(HERE, "new_family"), root / "chipbench",
                    dirs_exist_ok=True)
    added = set(_hashes(root / "chipbench")) - set(before)
    assert added == set(_hashes(os.path.join(HERE, "new_family")))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(root / "chipbench" / "configs" / "toy-llama.json"))
    assert not {"n_embd", "n_head", "ffn_dim", "word_embed_proj_dim"} \
        & set(cfg)
    bench["configs"].append({"name": "toy-llama", "source": cfg["source"],
                             "file": "chipbench/configs/toy-llama.json",
                             "reduced": [], "why": "test"})
    cells = {"toy-serve": ("chat-closed", "serve_tok_s"),
             "toy-train": ("train-1k", "train_tok_s")}
    for name, (mix, _) in cells.items():
        bench["workloads"].append({"name": name, "config": "toy-llama",
                                   "traffic": mix, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        for name, (_, e2e) in cells.items():
            if "workloads" in m and e2e in (m["name"], m.get("moves")):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for name, (_, e2e) in cells.items():
        for trace in (0, 1):
            res = _result(_run(
                ["--workload", name, "--seed", str(2 ** 31 + 77),
                 "--seconds", "1", "--trace", str(trace), "--rehearse"],
                root=str(root), tmp=tmp_path / "cache"))
            assert res["correct"] is True and res["failed"] == 0, name
            assert res["metrics"], name
            if not trace:
                assert res["metrics"][e2e]["value"] > 0
    after = _hashes(root / "chipbench")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == added


# -------------------------------------------------- data vs BENCHMARK.json
def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        for e in BENCH[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]), e["name"]
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])


def test_cells_configs_and_files_agree():
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = {c["name"]: c for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert four <= max(1, len(cells) // 4)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        data = json.load(open(os.path.join(ROOT, c["file"])))
        assert data["source"] == c["source"] and len(c["source"]) <= 200
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:     # a width is never reduced
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head|"
                                 r"n_embd|n_inner|ffn)", key), key
    for name in cells:
        spec = cb_run.load_cell(name)        # every file is found by name
        kind = spec["traffic"]["kind"]
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "drivers", kind + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "families", spec["config"]["family"] + ".py"))
        reported = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec["per_layer"], f"{name} reports no per-layer metric"
        for m in spec["per_layer"]:
            assert m["moves"] in reported, (name, m["name"], m["moves"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells), m["name"]


def test_every_layer_metric_has_a_reader_that_agrees():
    folder = os.path.join(ROOT, "chipbench", "layer_metrics")
    specs = {}
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".py") and not fname.startswith("_"):
            text = open(os.path.join(folder, fname)).read()
            scope = {}
            exec(compile(text.split("\ndef read")[0], fname, "exec"), scope)
            for s in scope["SPECS"]:
                specs[s["name"]] = s
    readers = cb_run.layer_metric_readers()
    assert set(readers) == set(specs)
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["name"] in readers, f"no reader for {m['name']}"
        s = specs[m["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert s[key] == m[key], (m["name"], key)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_readers_return_nothing_when_there_is_nothing_to_read():
    ctx = {"trace": None, "peaks": None, "counters": {}, "samples": {},
           "device": {"memory_peak_bytes": 0}, "window": (0.0, 1.0),
           "spans": __import__("chipbench.spans", fromlist=["Spans"]).Spans(),
           "config": {}}
    for name, read in cb_run.layer_metric_readers().items():
        assert read(ctx) is None, name


# ------------------------------------------------------------------ traffic
def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = json.load(open(os.path.join(ROOT, "chipbench", "traffic",
                                      "chat-closed.json")))
    deck = traffic.length_deck(mix)
    assert len(deck) == mix["deck"]
    assert min(p for p, _ in deck) >= 32 and max(p for p, _ in deck) <= 512
    assert min(o for _, o in deck) >= 32 and max(o for _, o in deck) <= 256
    assert 150 < statistics.mean(p for p, _ in deck) < 195     # ~173
    assert 95 < statistics.mean(o for _, o in deck) < 120      # ~108

    def draw(seed):
        s = traffic.RequestStream(mix, 50272, seed)
        return [next(s) for _ in range(len(deck))]

    a, b, c = draw(7), draw(7), draw(2 ** 31 + 99)
    sizes = lambda rs: sorted((r["prompt"].size, r["max_new_tokens"])  # noqa
                              for r in rs)
    assert sizes(a) == sizes(c) == sorted(deck)
    assert [r["prompt"].size for r in a] != [r["prompt"].size for r in c]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["seed"] == y["seed"] for x, y in zip(a, b))
    assert all(0 <= r["seed"] < 2 ** 32 and r["temperature"] == 0.7
               and r["top_p"] == 0.9 for r in a)
    fr = traffic.RequestStream(mix, 100, 5).warm_in_fractions(32)
    assert sorted(fr) == [(i + 0.5) / 32 for i in range(32)]


def test_token_batches_are_seeded():
    a = next(traffic.token_batches(2 ** 31 + 5, 1000, 4, 9))
    b = next(traffic.token_batches(2 ** 31 + 5, 1000, 4, 9))
    assert a.shape == (4, 9) and a.dtype == np.int32
    assert np.array_equal(a, b) and a.min() >= 0 and a.max() < 1000


# -------------------------------------------------------------------- costs
def _config(name):
    return json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                       name + ".json")))


def test_costs_against_hand_arithmetic():
    opt, gpt = _config("opt-1.3b"), _config("gpt2-medium")
    # OPT-1.3B: 50272*2048 + 2050*2048 + 24*(4*2048^2 + 2*2048*8192
    #           + 9*2048 + 8192) + 2*2048
    per = 4 * 2048 ** 2 + 2 * 2048 * 8192 + 9 * 2048 + 8192
    n_opt = 50272 * 2048 + 2050 * 2048 + 24 * per + 2 * 2048
    assert costs.num_params(opt) == n_opt == 1_315_758_080
    per = 4 * 1024 ** 2 + 2 * 1024 * 4096 + 9 * 1024 + 4096
    n_gpt = 50257 * 1024 + 1024 * 1024 + 24 * per + 2 * 1024
    assert costs.num_params(gpt) == n_gpt == 354_823_168
    assert costs.weight_bytes(opt) == 2 * n_opt                 # 2.63 GB
    assert costs.kv_bytes_per_token(opt) == 2 * 24 * 2048 * 2 == 196_608
    f_opt = costs.train_flops_per_token(opt, 2048)
    f_gpt = costs.train_flops_per_token(gpt, 1024)
    assert f_opt == 6 * n_opt + 12 * 24 * 2048 * 2048
    assert round(f_opt / 1e9, 2) == 9.10 and round(f_gpt / 1e9, 2) == 2.43
    assert costs.decode_bytes_per_step(opt, 1000) == \
        2 * n_opt + 196_608 * 1000


def test_costs_count_the_programs_parameters():
    for name in ("opt-1.3b", "gpt2-medium"):
        cfg = _config(name)
        program = families.load(cfg).build(cfg).model_config
        assert program.num_params() == costs.num_params(cfg)
        assert program.num_heads == costs.arch(cfg)["heads"]


#: what ``costs`` returned for the two configurations before a family was a
#: plug-in (PR 26): the rooflines and ``train_tok_s``'s FLOPs divide by these
PINS = {
    "opt-1.3b": {"num_params": 1_315_758_080, "weight_bytes": 2_631_516_160,
                 "kv_bytes_per_token": 196_608, "seq": 2048,
                 "train_flops_per_token": 9_102_508_032.0,
                 "arch": {"layers": 24, "d": 2048, "heads": 32,
                          "kv_heads": 32, "head_dim": 64, "ffn": 8192,
                          "vocab": 50272, "positions": 2048,
                          "position_rows": 2050}},
    "gpt2-medium": {"num_params": 354_823_168, "weight_bytes": 709_646_336,
                    "kv_bytes_per_token": 98_304, "seq": 1024,
                    "train_flops_per_token": 2_430_928_896.0,
                    "arch": {"layers": 24, "d": 1024, "heads": 16,
                             "kv_heads": 16, "head_dim": 64, "ffn": 4096,
                             "vocab": 50257, "positions": 1024,
                             "position_rows": 1024}},
}


@pytest.mark.parametrize("name", sorted(PINS))
def test_costs_return_the_integers_they_returned(name):
    cfg, pin = _config(name), PINS[name]
    assert costs.arch(cfg) == pin["arch"]
    assert costs.num_params(cfg) == pin["num_params"]
    assert costs.active_params(cfg) == pin["num_params"]    # dense: all
    assert costs.weight_bytes(cfg) == pin["weight_bytes"]
    assert costs.kv_bytes_per_token(cfg) == pin["kv_bytes_per_token"]
    assert costs.train_flops_per_token(cfg, pin["seq"]) == \
        pin["train_flops_per_token"]
    assert costs.decode_bytes_per_step(cfg, 1000.0, {"anything": 1}) == \
        pin["weight_bytes"] + 1000.0 * pin["kv_bytes_per_token"]


def _toy_family(monkeypatch, name, **functions):
    """A family that exists only in this process."""
    module = types.ModuleType("chipbench.families." + name)
    sizes = {"layers": 4, "d": 512, "heads": 8, "kv_heads": 2,
             "head_dim": 64, "vocab": 1000, "positions": 256}
    base = {"build": lambda config, overrides=None: None,
            "arch": lambda config: dict(sizes),
            "num_params": lambda config: 10_000_000,
            "logits": lambda config, params, tokens, at=None: None,
            "next_token_loss": lambda config, params, tokens: None}
    for fn, body in {**base, **functions}.items():
        if body is not None:
            setattr(module, fn, body)
    monkeypatch.setitem(sys.modules, module.__name__, module)
    return {"family": name, "dtype": "bf16"}


def test_costs_conventions_on_a_gqa_expert_toy(monkeypatch):
    """KV bytes follow ``kv_heads x head_dim`` (not ``d``), training FLOPs
    the parameters a token multiplies with, and a decode step's weight
    bytes what the family says the step reads — from the counters."""
    dense = _toy_family(monkeypatch, "toy_gqa")
    assert costs.kv_bytes_per_token(dense) == 2 * 4 * 2 * 64 * 2 == 2048
    assert costs.kv_bytes_per_token(dense) != 2 * 4 * 512 * 2
    assert costs.active_params(dense) == 10_000_000
    assert costs.train_flops_per_token(dense, 128) == \
        6.0 * 10_000_000 + 12.0 * 4 * (8 * 64) * 128
    assert costs.decode_bytes_per_step(dense, 10.0) == \
        2 * 10_000_000 + 2048 * 10.0
    sparse = _toy_family(
        monkeypatch, "toy_experts",
        active_params=lambda config: 3_000_000,
        decode_weight_bytes=lambda config, counters:
            2 * (1_000_000 + 500_000 * counters["experts_touched"]))
    assert costs.num_params(sparse) == 10_000_000
    assert costs.weight_bytes(sparse) == 20_000_000
    assert costs.train_flops_per_token(sparse, 128) == \
        6.0 * 3_000_000 + 12.0 * 4 * (8 * 64) * 128
    assert costs.decode_bytes_per_step(
        sparse, 10.0, {"experts_touched": 6}) == 8_000_000 + 2048 * 10.0


@pytest.mark.parametrize("lacks", ["num_params", "logits", "the file",
                                   "a size"])
def test_a_family_that_lacks_a_function_is_a_named_error(monkeypatch, lacks):
    """The error names the file and the function to add."""
    if lacks == "the file":
        cfg, want = {"family": "no_such_family"}, \
            r"no chipbench/families/no_such_family\.py"
    elif lacks == "a size":
        cfg = _toy_family(monkeypatch, "toy_short",
                          arch=lambda config: {"layers": 2, "d": 64})
        want = r"chipbench/families/toy_short\.py: arch\(\) reports no " \
            r".*'kv_heads'"
    else:
        cfg = _toy_family(monkeypatch, "toy_lacks", **{lacks: None})
        want = rf"chipbench/families/toy_lacks\.py lacks {lacks}\(\)"
    with pytest.raises(NotImplementedError, match=want):
        costs.kv_bytes_per_token(cfg)


def test_the_shared_files_name_no_family():
    """ISSUE 27's grep: the families are named only under ``families/``,
    ``configs/`` and in ``reference.py``'s own two-row table."""
    cb = os.path.join(ROOT, "chipbench")
    shared = [os.path.join(cb, f) for f in ("costs.py", "run.py")]
    for folder in ("drivers", "layer_metrics"):
        shared += [os.path.join(cb, folder, f)
                   for f in sorted(os.listdir(os.path.join(cb, folder)))
                   if f.endswith(".py")]
    names = {f[:-3] for f in os.listdir(os.path.join(cb, "families"))
             if f.endswith(".py") and not f.startswith("_")}
    assert {"opt", "gpt2"} <= names
    quoted = re.compile("|".join(rf"[\"']{re.escape(n)}[\"']"
                                 for n in sorted(names)))
    for path in shared:
        text = open(path).read()
        assert not quoted.search(text), path
        assert "fam ==" not in text and "import reference" not in text, path


def test_peaks_refuse_an_unknown_chip():
    from chipbench import peaks

    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")


# ---------------------------------------------------------------- reference
@pytest.mark.parametrize("family", ["opt", "gpt2"])
def test_reference_agrees_with_the_programs_float32_forward(family):
    """Same seeded weights, tiny widths, CPU: a layout mismatch (fused qkv
    order, position offset, activation) shows here and not on the chip."""
    import jax

    from deepspeed_tpu.models import gpt2, opt

    mod, name = {"opt": (opt, "opt-1.3b"),
                 "gpt2": (gpt2, "gpt2-medium")}[family]
    cfg = cb_run._rehearsed(_config(name), True)
    fam = families.load(cfg)
    assert cfg["family"] == family
    model = fam.build(cfg, {"use_flash": False})
    params = model.init_fn(jax.random.PRNGKey(3))
    # biases and LayerNorm offsets are zero at init: make them matter
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype),
        params)
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (3, 17)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(mod.forward(model.model_config, params, ids))
        want_loss = float(model.loss_fn(params, {"input_ids": ids},
                                        train=False))
    got = np.asarray(fam.logits(cfg, params, ids))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    at = [0, 5, 16]
    some = np.asarray(fam.logits(cfg, params, ids, at=at))
    np.testing.assert_allclose(some, want[:, at], rtol=2e-4, atol=2e-4)
    got_loss = float(fam.next_token_loss(cfg, params, ids))
    assert abs(got_loss - want_loss) < 1e-4, (got_loss, want_loss)


# ------------------------------------------------------------- trace_reduce
@pytest.fixture(scope="module")
def small_trace():
    return json.load(open(os.path.join(HERE, "small_trace.json")))


def test_interval_arithmetic():
    u = trace_reduce.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)])
    assert u == [(1, 4), (5, 8)] and trace_reduce.total(u) == 6
    assert trace_reduce.clip(u, 2, 6) == [(2, 4), (5, 6)]
    assert trace_reduce.subtract([(0, 10)], u) == [(0, 1), (4, 5), (8, 10)]
    assert trace_reduce.subtract([(2, 6), (7, 9)], [(0, 3), (5, 8)]) == \
        [(3, 5), (8, 9)]
    assert trace_reduce.base_name("%fusion.123 = f32[8]") == "fusion"
    assert trace_reduce.self_times(
        [["w", 0, 10, {}], ["a", 0, 4, {}], ["b", 4, 5, {}], ["c", 12, 3, {}]]
    ) == [1, 4, 5, 3]
    kernel = ('%jvp__.335 = bf16[128,1024,64]{2,1,0:T(8,128)(2,1)} '
              'custom-call(bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} '
              '%bitcast.6233), custom_call_target="tpu_custom_call", '
              'operand_layout_constraints={bf16[128,1024,64]{2,1,0}}')
    assert trace_reduce.parse_op(kernel) == (
        "jvp__.335", {"opcode": "custom-call", "mosaic": True})
    assert trace_reduce.parse_op(
        '%while.7 = (s32[]{:T(128)}, f32[24,4096]{1,0:T(8,128)}) '
        'while((s32[]{:T(128)}, f32[24,4096]{1,0:T(8,128)}) %tuple.1), '
        'condition=%cond, body=%body') == ("while.7", {"opcode": "while"})
    assert trace_reduce.parse_op(
        '%custom-call.78 = bf16[8,1024]{1,0:T(8,128)(2,1)S(1)} custom-call('
        'bf16[2,1024]{1,0} %slice-done.1), custom_call_target="ConcatBitcast"'
    ) == ("custom-call.78", {"opcode": "custom-call"})
    assert trace_reduce.module_name("jit_decode_step(12345)") == \
        "jit_decode_step"


def test_reduce_on_the_small_trace(small_trace):
    """Every number below is counted by hand from small_trace.json (ns):
    window [95, 650); device 0 busy [100,260) [300,400) [500,600)."""
    r = trace_reduce.reduce(small_trace)
    ns = 1e-9
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(555 * ns)
    # device 0 is busy 360 ns, device 1 (one fusion [100, 200)) 100 ns
    assert r["busy_s"] == pytest.approx((360 + 100) / 2 * ns)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"cb.harvest": 140 * ns, "cb.step": 55 * ns})
    assert r["longest_gaps"][0] == ["cb.harvest", pytest.approx(100 * ns)]
    assert r["programs"]["jit_decode_step"] == pytest.approx(
        [160 * ns, 100 * ns])
    assert r["programs"]["jit_train_step"] == pytest.approx([100 * ns])
    assert r["custom_call_s"] == pytest.approx(
        {"jit_decode_step:mosaic:paged_decode_attn": 60 * ns})
    # device 0: all-gather-done [300, 340) holds the core up for 40 ns; the
    # collective itself runs from its start on the async line, [280, 340)
    assert r["collective_s"] == pytest.approx(60 / 2 * ns)
    assert r["collective_exposed_s"] == pytest.approx(40 / 2 * ns)
    # self time: the while [300, 400) is all its two children's
    ops = dict(map(tuple, r["device_ops"]))
    assert ops == pytest.approx({
        "jit_decode_step:fusion": (200 + 100) / 2 * ns,
        "jit_decode_step:mosaic:paged_decode_attn": 60 / 2 * ns,
        "jit_train_step:fusion": 60 / 2 * ns,
        "jit_train_step:all-gather-done": 40 / 2 * ns,
        "jit_train_step:while": 0.0})
    assert trace_reduce.program_times(r, r"^jit_decode") == pytest.approx(
        [160 * ns, 100 * ns])
    assert trace_reduce.program_median(r, r"^jit_nothing") is None
    assert trace_reduce.program_median(None, r"^jit_decode") is None


def test_layer_metrics_on_the_small_trace(small_trace):
    r = trace_reduce.reduce(small_trace)
    readers = cb_run.layer_metric_readers()
    opt = _config("opt-1.3b")
    ctx = {"trace": r, "config": opt,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "counters": {"mean_valid_kv_tokens": 1000.0, "chips": 2,
                        "flops_per_step": 2 * 197e12 * 50e-9}}
    assert readers["device_idle.serve"](ctx) == pytest.approx(
        100 * (1 - 230 / 555))
    assert readers["decode_step_ms"](ctx) == pytest.approx(130e-6)
    assert readers["train_step_ms"](ctx) == pytest.approx(100e-6)
    assert readers["prefill_chunk_ms"](ctx) is None     # no such program
    # 50 ns of model FLOPs at peak over a 100 ns step
    assert readers["train_mxu_roofline"](ctx) == pytest.approx(50.0)
    assert readers["collective_exposed"](ctx) == pytest.approx(
        100 * 20 / 555)
    kv = 196_608 * 1000 / 819e9
    assert readers["paged_attn_roofline"](ctx) == pytest.approx(
        100 * kv / 30e-9)
    assert readers["decode_roofline"](ctx) == pytest.approx(
        100 * (kv + costs.weight_bytes(opt) / 819e9) / 130e-9)
    # a second Mosaic kernel in the decode program (an expert matmul), or
    # the attention kernel of another program, is not charged to attention:
    # the reading above summed every Mosaic call of ^jit_decode before
    more = dict(r["custom_call_s"])
    more["jit_decode_step:mosaic:moe_grouped_matmul"] = 45e-9
    more["jit_verify:mosaic:paged_decode_attn"] = 45e-9
    assert readers["paged_attn_roofline"](
        {**ctx, "trace": {**r, "custom_call_s": more}}) == pytest.approx(
        100 * kv / 30e-9)
    assert readers["paged_attn_roofline"]({**ctx, "trace": {
        **r, "custom_call_s": {"jit_decode_step:mosaic:other": 9e-9}}}) \
        is None


def test_a_trace_without_device_operations_is_refused(small_trace):
    host_only = {"planes": [p for p in small_trace["planes"]
                            if not p["name"].startswith("/device")]}
    with pytest.raises(ValueError):
        trace_reduce.reduce(host_only)


def test_load_xplane_reads_the_profilers_file(tmp_path):
    """The loader on a real (CPU) profile: the benchmark's spans come back
    from the host plane; there is no device plane to reduce."""
    import jax
    import jax.numpy as jnp

    from chipbench.spans import Spans

    spans = Spans()
    jax.profiler.start_trace(str(tmp_path))
    with spans("cb.window"):
        with spans("cb.step"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(str(tmp_path))
    names = {ev[0] for p in trace["planes"] for ln in p["lines"]
             for ev in ln["events"]}
    assert {"cb.window", "cb.step"} <= names
    assert len(spans.within("cb.step", 0.0, float("inf"))) == 1
    with pytest.raises(ValueError):
        trace_reduce.reduce(trace)
