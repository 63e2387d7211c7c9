"""The per-layer metrics that read the compiled programs' scope tables
(``chipbench/layer_metrics/{sample_vocab_passes,scope_cover}.py`` over
``_scope_tables.py``): ``None`` without a table, the arithmetic on a table
written by hand, their entries in ``BENCHMARK.json``, and a rehearsal of a
serving and of a training cell that print them.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import run as cb_run
from chipbench.layer_metrics import _scope_tables as st

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
READERS = cb_run.layer_metric_readers()
NEW = ("sample_vocab_passes", "scope_cover.serve", "scope_cover.train")
SERVING = ["opt13b-chat-closed", "opt13b-longprompt-closed",
           "olmoe-decode-closed", "zaya1-reasoning-closed"]
TRAINING = ["gpt2m-train-1k", "opt13b-zero3-x4"]
#: cells whose own tests hold their per-layer lists to what their PR named
#: "and no other" (PERF.md section 7): they stay out of the new lists
PINNED = ["keye-longctx-closed", "commanda-ragchat-closed",
          "mistral4-longdecode-closed", "smallthinker-train-8k",
          "kimilinear-statedecode-closed", "granite4h-chat-closed",
          "brumby-longdoc-closed", "dots3-longnote-closed",
          "glm5-agentloop-closed"]


def row(scope, bytes_=0, onchip=0, kernel=0, mixed=0, which="fwd"):
    return {"scope": scope, "pass": which, "instructions": 1,
            "bytes": bytes_, "onchip_bytes": onchip, "kernel_bytes": kernel,
            "flops": 0, "mixed_bytes": mixed, "kernels": {}}


class _Programs:
    """What ``trace.kept("programs")`` gives a reader: records and tables."""

    def __init__(self, tables):
        self.records = dict.fromkeys(tables)
        self._tables = tables

    def table(self, name):
        return self._tables[name]


@pytest.fixture
def kept(monkeypatch):
    from deepspeed_tpu.telemetry import trace

    def put(tables):
        held = {"programs": _Programs(tables) if tables is not None
                else None}
        monkeypatch.setattr(trace, "kept", held.get)

    return put


def ctx(slots=None, vocab=None, cell=True):
    counters = {} if slots is None else {"slots": slots}
    config = {} if vocab is None else {"vocab_size": vocab}
    return {"cell": {"name": "a-cell"} if cell else None,
            "counters": counters, "config": config}


def table(rows, instructions=None):
    return {"module": "jit_x", "scopes": rows, "build_s": 0.25,
            "backend_compiles": 0, "instructions": instructions or {}}


def test_without_a_table_every_reader_says_nothing(kept):
    kept(None)
    for name in NEW:
        assert READERS[name](ctx(4, 100)) is None, name
    # a program that was recorded under another name (a prefill rung alone)
    kept({"prefill[4x128]": table([row("head", 100)])})
    for name in NEW:
        assert READERS[name](ctx(4, 100)) is None, name
    # tables, but a context that names no cell: whose programs would they be
    kept({"decode": table([row("sample/filter", 1600)])})
    for name in NEW:
        assert READERS[name](ctx(4, 100, cell=False)) is None, name
    # a sampler that moved nothing is no reading of 0
    kept({"decode": table([row("head", 1600)])})
    assert READERS["sample_vocab_passes"](ctx(4, 100)) is None


def test_the_passes_are_the_samplers_bytes_over_one_logits_array(
        kept, capsys):
    # [4, 100] float32 = 1,600 B a pass: 2 passes through HBM under the
    # filter, 3 on the chip under the draw, 1 handed to a kernel under the
    # filter, half a pass under the bare ``sample``; the head's and an
    # unscoped row's bytes are nobody's passes
    kept({"decode": table([
        row("sample/filter", 3200, kernel=1600), row("sample/draw", 0, 4800),
        row("sample", 800), row("head", 99999), row("unscoped", 1600)])})
    got = READERS["sample_vocab_passes"](ctx(4, 100))
    assert got == pytest.approx(6.5)
    said = capsys.readouterr().out
    assert "6.5 times a decode call, 2.5 of them through HBM" in said
    assert "sample/draw 3.0" in said and "sample/filter 3.0" in said
    # a self-drafting engine's round is its two programs
    kept({"verify": table([row("sample/filter", 1600)]),
          "draft": table([row("sample/argmax", 1600)]),
          "prefill[4x128]": table([row("sample/filter", 16000)])})
    assert READERS["sample_vocab_passes"](ctx(4, 100)) == pytest.approx(2.0)
    # no slots (a training cell) or no vocabulary: nothing to divide by
    assert READERS["sample_vocab_passes"](ctx(None, 100)) is None
    assert READERS["sample_vocab_passes"](ctx(4, None)) is None


def test_the_cover_is_the_share_of_hbm_bytes_under_the_vocabulary(
        kept, capsys):
    insts = {"fusion.7": {"scope": "unscoped", "bytes": 50, "trips": 4},
             "fusion.8": {"scope": "head", "bytes": 300, "trips": 1},
             "copy.1": {"scope": "unscoped", "bytes": 0, "trips": 1}}
    kept({"decode": table([
        row("head", 300), row("layer/mlp", 500, mixed=100),
        row("layer/attn/core", 0, kernel=10 ** 9),      # a kernel's: apart
        row("unscoped", 200)], insts)})
    assert READERS["scope_cover.serve"](ctx(4, 100)) == pytest.approx(80.0)
    said = capsys.readouterr().out
    assert "10.00 % in mixed fusions" in said
    assert "decode:fusion.7 0.000 MB" in said and "copy.1" not in said
    assert "[0] backend compiles" in said
    # the same reader under its training name reads the step
    kept({"train_step": table([row("loss", 900, which="bwd"),
                               row("unscoped", 100)]),
          "decode": table([row("unscoped", 100)])})
    assert READERS["scope_cover.train"](ctx()) == pytest.approx(90.0)
    # a program that moves nothing through HBM is no reading of 0
    kept({"decode": table([row("head", 0, onchip=10)])})
    assert READERS["scope_cover.serve"](ctx(4, 100)) is None


def test_the_three_entries_are_appended_and_name_the_cells_that_can_take_them():
    entries = BENCH["per_layer"]
    names = [m["name"] for m in entries]
    first = names.index(NEW[0])
    assert first >= 89                          # behind what was there
    assert tuple(names[first:first + 3]) == NEW
    had = {m["layer"] for m in entries[:first]}
    want = {"sample_vocab_passes": ("passes", "lower", "model step",
                                    "serve_tok_s", SERVING),
            "scope_cover.serve": ("%", "higher", "device", "serve_tok_s",
                                  SERVING),
            "scope_cover.train": ("%", "higher", "device", "train_tok_s",
                                  TRAINING)}
    for m in entries[first:first + 3]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["layer"], m["moves"],
                m["workloads"]) == want[m["name"]]
        assert m["source"] == "program_counter" and m["layer"] in had
        assert not set(m["workloads"]) & set(PINNED)
        for cell in m["workloads"]:
            listed = {x["name"] for x in cb_run.load_cell(cell)["per_layer"]}
            assert m["name"] in listed
    assert set(NEW) <= set(READERS)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(SERVING + TRAINING + PINNED) == cells


@pytest.mark.parametrize("cell,names", [
    ("opt13b-chat-closed", ("sample_vocab_passes", "scope_cover.serve")),
    ("gpt2m-train-1k", ("scope_cover.train",))])
def test_a_traced_rehearsal_prints_the_scope_metrics_of_the_cell(
        cell, names, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 68), "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    for name in names:
        assert res["metrics"][name]["value"] > 0, name
    for name in ("scope_cover.serve", "scope_cover.train"):
        if name in names:
            assert res["metrics"][name] == {
                "value": res["metrics"][name]["value"], "unit": "%"}
            assert res["metrics"][name]["value"] <= 100.0
    assert "chipbench: scope tables of the" in proc.stdout
    assert "backend compiles" in proc.stdout
    # built from the executable that ran: no compile of its own
    note = next(line for line in proc.stdout.splitlines()
                if line.startswith("chipbench: scope tables of the"))
    assert "with [0] backend compiles" in note
