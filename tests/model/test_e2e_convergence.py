"""End-to-end convergence lane (reference
``tests/model/Megatron_GPT2/run_func_test.py``): a REAL byte-level-BPE
tokenizer trained on a synthetic corpus, a small GPT-2 trained through the
public engine to a target loss, checkpoint-resume mid-run, and a
perf/structural check of the headline bench entrypoint.

CPU-sim, marked slow; chip numbers are the driver's to take (it runs
``bench.py`` and ``chip_smoke.py`` on the TPU itself).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2

pytestmark = pytest.mark.slow


def _synthetic_corpus(n_sentences=400, seed=0):
    rng = np.random.default_rng(seed)
    subjects = ["the pipeline", "a tensor", "the optimizer", "our mesh",
                "the scheduler", "a kernel", "the compiler", "the runtime"]
    verbs = ["shards", "gathers", "reduces", "streams", "compiles",
             "fuses", "overlaps", "checkpoints"]
    objects = ["the gradients", "a layer", "the activations", "the weights",
               "every block", "the cache", "the batch", "the tokens"]
    lines = []
    for _ in range(n_sentences):
        lines.append(f"{rng.choice(subjects)} {rng.choice(verbs)} "
                     f"{rng.choice(objects)} .")
    return lines


def _train_tokenizer(lines, vocab_size=384):
    from tokenizers import ByteLevelBPETokenizer

    tok = ByteLevelBPETokenizer()
    tok.train_from_iterator(lines, vocab_size=vocab_size, min_frequency=1)
    return tok


def test_gpt2_converges_on_real_tokenized_corpus(tmp_path):
    lines = _synthetic_corpus()
    tok = _train_tokenizer(lines)
    vocab = tok.get_vocab_size()
    ids = [tok.encode(" ".join(lines[i:i + 4])).ids for i in range(0, 64, 4)]
    seq = 33
    data = np.stack([np.asarray((x * seq)[:seq], np.int32) for x in ids])

    deepspeed_tpu.comm.reset_topology()
    cfg = gpt2.GPT2Config(vocab_size=vocab, max_seq_len=seq, num_layers=2,
                          num_heads=2, hidden_size=64)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 1000})
    bs = engine.train_batch_size()
    rng = np.random.default_rng(0)

    losses = []
    for step in range(60):
        take = rng.integers(0, len(data), bs)
        _, m = engine.train_batch({"input_ids": data[take]})
        losses.append(float(m["loss"]))
        if step == 30:
            engine.save_checkpoint(str(tmp_path / "ck"))
    start = float(np.mean(losses[:3]))
    end = float(np.mean(losses[-3:]))
    # target-loss gate (reference run_func_test asserts a loss ceiling):
    # a 2-layer model must fit this 8-sentence corpus well below start
    assert end < start - 2.0, (start, end, losses[-5:])
    assert end < 2.5, losses[-5:]

    # checkpoint-resume continues the curve (no re-warmup spike)
    deepspeed_tpu.comm.reset_topology()
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 1000})
    engine2.load_checkpoint(str(tmp_path / "ck"))
    take = rng.integers(0, len(data), bs)
    _, m = engine2.train_batch({"input_ids": data[take]})
    assert float(m["loss"]) < start - 1.0  # resumed mid-curve, not fresh


def test_bench_entrypoint_smoke_and_contract():
    """The headline bench must emit its one-line JSON contract on the
    asked-for CPU smoke path, name the device beside it, and claim no
    device number there (``vs_baseline`` is null off the chip)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), os.pardir,
                                      os.pardir, "bench.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    assert set(rec) == {"metric", "value", "unit", "vs_baseline"}
    assert rec["value"] > 0
    assert rec["vs_baseline"] is None
    assert "platform=cpu device_kind=cpu count=1" in out.stdout


def test_bench_refuses_unknown_device_kind():
    """A device the peak table does not know is an error, never a default
    (the old table assumed 197e12 for any TPU and 1e12 for a CPU)."""
    import importlib.util
    import types

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(__file__), os.pardir,
                              os.pardir, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.peak_flops_per_chip(
        types.SimpleNamespace(device_kind="TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        bench.peak_flops_per_chip(types.SimpleNamespace(device_kind="cpu"))
