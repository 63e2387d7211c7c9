"""End-to-end convergence lane (reference
``tests/model/Megatron_GPT2/run_func_test.py``): a REAL byte-level-BPE
tokenizer trained on a synthetic corpus, a small GPT-2 trained through the
public engine to a target loss, and checkpoint-resume mid-run.

CPU-sim, marked slow; chip numbers are the benchmark's (``BENCHMARK.json``
+ ``chipbench/``).
"""

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
