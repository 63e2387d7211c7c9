"""ISSUE 63: the checkpoint storage backend is built at its FIRST USE.

``initialize`` makes the ``CheckpointManager`` and checks that the checkpoint
library is installed WITHOUT importing it; the first ``save_checkpoint`` or
``load_checkpoint`` of a process builds ``OrbaxCheckpointEngine()`` once,
under a ``checkpoint_engine`` span of the start-up ring whose ``first_use``
says who reached for it.
"""

import importlib.machinery
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime import checkpointing
from deepspeed_tpu.runtime.checkpointing import (CheckpointEngine,
                                                 CheckpointManager,
                                                 OrbaxCheckpointEngine)
from deepspeed_tpu.telemetry import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = {"train_micro_batch_size_per_gpu": 1,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
          "zero_optimization": {"stage": 1}}


def fresh_engine():
    """A tiny engine through ``initialize`` on a start-up ring of its own."""
    trace._KEPT.pop("setup", None)
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(gpt2.GPT2Config.tiny(max_seq_len=32)), config=CONFIG)
    return engine


def batch_of(engine, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(
        0, 512, (engine.train_batch_size(), 33)).astype(np.int32)}


def backend_spans():
    return [e for e in trace.kept("setup").events()
            if e["ph"] == "X" and e["name"] == "checkpoint_engine"]


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    """An engine two steps in that saved twice: ``(engine, directory, what
    the first save left, what the second left)``, what a save left being
    the ring's ``checkpoint_engine`` spans and the manager's backend."""
    engine = fresh_engine()
    for _ in range(2):
        engine.train_batch(batch_of(engine))
    manager = engine.checkpoint_manager
    assert manager._checkpoint_engine is None
    assert not [e for e in trace.kept("setup").events()
                if e["name"] in ("checkpoint_engine", "checkpoint_manager")]
    where = str(tmp_path_factory.mktemp("first_use"))
    engine.save_checkpoint(where, tag="first", client_state={"note": "hi"})
    first = (backend_spans(), manager._checkpoint_engine)
    engine.save_checkpoint(where, tag="second")
    second = (backend_spans(), manager._checkpoint_engine)
    return engine, where, first, second


# (a) in a process of its own: other tests of this worker may have imported
# the library already
@pytest.mark.limit(200)
def test_initialize_and_two_steps_import_no_checkpoint_library(tmp_path):
    code = f"""
import json, sys
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.telemetry import trace

def loaded():
    return sorted(m for m in sys.modules if m.startswith("orbax"))

def spans():
    return [e["args"] for e in trace.kept("setup").events()
            if e["name"] == "checkpoint_engine"]

engine, _, _, _ = deepspeed_tpu.initialize(
    model=gpt2.build(gpt2.GPT2Config.tiny(max_seq_len=32)),
    config={CONFIG!r})
batch = {{"input_ids": np.zeros((engine.train_batch_size(), 33), np.int32)}}
for _ in range(2):
    engine.train_batch(batch)
before = (loaded(), spans())
engine.save_checkpoint({str(tmp_path)!r})
print(json.dumps([before, ["orbax.checkpoint" in sys.modules, spans()]]))
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=190)
    assert out.returncode == 0, out.stderr[-2000:]
    before, after = json.loads(out.stdout.strip().splitlines()[-1])
    assert before == [[], []]
    assert after == [True, [{"first_use": "save"}]]


# (b)
def test_the_first_save_builds_the_backend_once_under_its_span(saved):
    engine, where, (spans, backend), (spans_2, backend_2) = saved
    span, = spans
    assert span["args"] == {"first_use": "save"} and span["dur"] > 0
    assert isinstance(backend, OrbaxCheckpointEngine)
    # a second save builds nothing and pushes nothing
    assert spans_2 == spans and backend_2 is backend
    assert engine.checkpoint_manager.checkpoint_engine is backend
    assert open(os.path.join(where, "latest")).read() == "second"
    assert sorted(os.listdir(os.path.join(where, "first"))) == [
        "ds_meta.json", "state"]


# (c)
def test_a_fresh_engine_whose_first_use_is_a_load_reads_it_back(saved):
    engine, where, _, _ = saved
    other = fresh_engine()
    assert other.checkpoint_manager._checkpoint_engine is None
    # (nothing to load builds nothing)
    assert other.load_checkpoint(os.path.join(where, "nowhere")) == (None, {})
    assert not backend_spans()
    path, client_state = other.load_checkpoint(where, tag="first")
    span, = backend_spans()
    assert span["args"] == {"first_use": "load"}
    assert path == os.path.join(where, "first")
    assert client_state == {"note": "hi"}
    assert other.global_steps == engine.global_steps == 2
    assert int(other.state["step"]) == int(engine.state["step"])
    for mine, theirs in zip(jax.tree_util.tree_leaves(other.state["params"]),
                            jax.tree_util.tree_leaves(engine.state["params"])):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    other.load_checkpoint(where)                # ``latest``: the same backend
    assert backend_spans() == [span]


class _Recording(CheckpointEngine):
    def __init__(self):
        super().__init__()
        self.calls = []

    def save(self, state_tree, path):
        self.calls.append(("save", path))

    def load(self, path, abstract_target=None):
        self.calls.append(("load", path))
        return abstract_target


# (d)
def test_an_injected_backend_is_used_as_given_and_none_is_built(
        saved, tmp_path, monkeypatch):
    engine = saved[0]
    monkeypatch.setattr(
        checkpointing, "OrbaxCheckpointEngine",
        lambda *a, **k: pytest.fail("built a backend beside the given one"))
    before = backend_spans()
    given = _Recording()
    manager = CheckpointManager(engine, checkpoint_engine=given)
    assert manager.checkpoint_engine is given
    manager.save(str(tmp_path), tag="t")
    assert given.calls == [("save", str(tmp_path / "t" / "state"))]
    assert os.path.isfile(tmp_path / "t" / "ds_meta.json")
    assert manager.checkpoint_engine is given and backend_spans() == before


# (e)
def test_a_missing_library_fails_at_initialize_not_at_the_first_save(
        saved, monkeypatch):
    engine = saved[0]
    monkeypatch.setattr(importlib.util, "find_spec", lambda *a, **k: None)
    with pytest.raises(ImportError, match="No module named 'orbax'"):
        fresh_engine()
    with pytest.raises(ModuleNotFoundError) as err:
        CheckpointManager(engine)
    assert err.value.name == "orbax"
    given = _Recording()
    assert CheckpointManager(engine, given).checkpoint_engine is given


def test_the_check_imports_nothing_and_names_the_missing_part(monkeypatch,
                                                             tmp_path):
    """``orbax`` is a namespace package: the check finds the library under
    the parent's paths, so not even the (empty) parent is imported; with
    another ``orbax.*`` distribution installed and this one missing, the
    error is the one ``import orbax.checkpoint`` gives."""
    code = ("import sys\n"
            "from deepspeed_tpu.runtime import checkpointing\n"
            "checkpointing._require_orbax()\n"
            "print(sorted(m for m in sys.modules if m.startswith('orbax')))")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=110)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
    parent = importlib.machinery.ModuleSpec("orbax", None, is_package=True)
    parent.submodule_search_locations = [str(tmp_path)]     # an empty one
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: parent)
    with pytest.raises(ModuleNotFoundError,
                       match="No module named 'orbax.checkpoint'") as err:
        checkpointing._require_orbax()
    assert err.value.name == "orbax.checkpoint"
