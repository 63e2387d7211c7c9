"""``kind: train_steps`` — optimizer steps through ``initialize`` /
``DeepSpeedEngine.train_batch`` on the cell's chips, on fresh seeded
random-token batches made by a host generator inside the window.

Set-up: ``initialize`` (state born sharded on the device from the seed),
then warm-up steps on ONE batch that is two seeded sequences tiled over the
global batch: its mean loss is the mean over just those two sequences, so
the first step's loss can be held against the plain reference's float32
loss on the same two sequences and the engine's own (pre-update) weights.
The second warm-up step must lower it.

Window: one step is dispatched while the previous step's loss is fetched
(``float``), so the device always has a step queued and the window can
close on a fetched loss at most one step after ``--seconds``.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import costs, traffic

KIND = "train_steps"

#: first-step loss, engine (bf16 compute, fp32 master weights) against the
#: float32 reference on the same two sequences.  At initialisation the
#: logits have std ~0.6 and the loss is a mean over >= 2 x 1,023 positions;
#: bf16 rounding of the logits (2^-9 relative, ~2e-3 absolute each) averages
#: down to ~1e-4, while a wrong mask, position offset or activation moves
#: the picked logits by their own scale and the mean by ~0.6 / sqrt(2046)
#: = 1.3e-2.  5e-3 sits between; a float32 engine (rehearsal) needs 1e-4.
LOSS_ABS_TOL = {"bf16": 5e-3, "fp32": 1e-4}
WARM_STEPS = 3


def run(job) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu

    mix = job.traffic
    seq = int(mix["seq_len"])
    model = job.family.build(job.config, job.sizing.get("model"))
    vocab = costs.arch(job.config)["vocab"]
    n_dev = len(jax.devices())
    ds = dict(job.sizing["ds_config"])
    micro = int(ds["train_micro_batch_size_per_gpu"])
    per_micro = micro * n_dev * seq
    gas = max(1, int(mix["tokens_per_step"]) // per_micro)
    if gas * per_micro != int(mix["tokens_per_step"]) and not job.rehearse:
        raise ValueError(
            f"tokens_per_step {mix['tokens_per_step']} is not a whole "
            f"number of {micro} x {n_dev} x {seq}-token micro-batches")
    ds["gradient_accumulation_steps"] = gas
    # the engine keys its PRNG with a 32-bit seed and 0 means "default"
    ds["seed"] = job.seed % (2 ** 31 - 1) + 1

    with job.spans("cb.setup.initialize"):
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds)
        jax.block_until_ready(engine.state)
    rows = engine.train_batch_size()
    tokens_per_step = rows * seq

    # warm-up: two seeded sequences tiled over the batch
    rng = np.random.default_rng(traffic.seed_sequence(job.seed).spawn(1)[0])
    two = rng.integers(0, vocab, (2, seq + 1), dtype=np.int32)
    tiled = {"input_ids": np.tile(two, (math.ceil(rows / 2), 1))[:rows]}
    with job.spans("cb.setup.reference"):
        want = float(job.family.next_token_loss(
            job.config, engine.state["params"], two))
    losses = []
    with job.spans("cb.setup.warm_steps"):
        for _ in range(WARM_STEPS):
            _, m = engine.train_batch(tiled)
            losses.append(float(m["loss"]))
    tol = LOSS_ABS_TOL["bf16" if ds.get("bf16", {}).get("enabled")
                       else "fp32"]
    loss_ok = abs(losses[0] - want) <= tol and losses[-1] < losses[0]
    job.note(f"first-step loss {losses[0]:.6f} vs float32 reference "
             f"{want:.6f} on the same two sequences (|diff| "
             f"{abs(losses[0] - want):.2e}, tolerance {tol}); warm-up "
             f"losses {losses}")

    batches = traffic.token_batches(job.seed, vocab, rows, seq + 1)
    step_losses: List[float] = []
    started = 0
    compiles0 = job.compiles()
    t_open = time.perf_counter()
    job.window_opened(t_open)
    pending = None
    while True:
        with job.spans("cb.make_batch"):
            batch = {"input_ids": next(batches)}
        with job.spans("cb.train_batch"):
            _, m = engine.train_batch(batch)
        started += 1
        if pending is not None:
            with job.spans("cb.fetch_loss"):
                step_losses.append(float(pending["loss"]))
        pending = m
        since = time.perf_counter() - t_open
        job.tracer.poll(since)
        if since >= job.seconds:
            break
    with job.spans("cb.fetch_loss"):
        step_losses.append(float(pending["loss"]))
    t_close = time.perf_counter()
    job.tracer.finish()
    compiles1 = job.compiles()

    window = t_close - t_open
    bad = sum(not math.isfinite(x) for x in step_losses)
    no_compile = compiles1 == compiles0 \
        and engine.sentry.retraces_observed == 0
    if not no_compile:
        job.note(f"compiled inside the window: backend compiles "
                 f"{compiles0} -> {compiles1}, retraces "
                 f"{engine.sentry.retraces_observed}")
    job.note(f"window {window:.3f} s: {len(step_losses)} steps of "
             f"{tokens_per_step} tokens (micro {micro} x {n_dev} chips x "
             f"gas {gas}), {bad} non-finite losses; loss first "
             f"{step_losses[0]:.4f} last {step_losses[-1]:.4f}; step "
             f"median {window / len(step_losses) * 1e3:.1f} ms")
    flops_per_step = costs.train_flops_per_token(job.config, seq) \
        * tokens_per_step
    return {
        "correct": bool(loss_ok and no_compile and bad == 0),
        "attempted": started, "failed": int(bad),
        "end_to_end": {
            "train_tok_s": len(step_losses) * tokens_per_step / window},
        "window_s": window, "window": (t_open, t_close),
        "counters": {"steps": len(step_losses),
                     "tokens_per_step": tokens_per_step,
                     "micro_batch": micro, "gas": gas, "chips": n_dev,
                     "flops_per_step": flops_per_step,
                     "first_loss": losses[0], "reference_loss": want},
        "samples": {},
        "devices": list(jax.devices()),
    }
