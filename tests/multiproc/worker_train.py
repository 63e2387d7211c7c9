"""Multi-process test worker: train tiny GPT-2 under a 2-device-per-process
mesh and dump per-step losses.  Launched by test_multiprocess.py with
``argv = pid nprocs port steps outfile [save_dir] [load_dir]`` (the
DistributedExec/DistributedFixture analog, reference tests/unit/common.py:71
and :202 — real cross-process collectives, no GPU; checkpoints written
under one world shape are resumed under another).
"""

import json
import os
import sys

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2")

import jax

jax.config.update("jax_platforms", "cpu")

pid, nprocs, port, steps = (int(sys.argv[1]), int(sys.argv[2]),
                            int(sys.argv[3]), int(sys.argv[4]))
outfile = sys.argv[5]
save_dir = sys.argv[6] if len(sys.argv) > 6 and sys.argv[6] != "-" else None
load_dir = sys.argv[7] if len(sys.argv) > 7 and sys.argv[7] != "-" else None

if nprocs > 1:
    jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                               num_processes=nprocs, process_id=pid)

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import deepspeed_tpu
from deepspeed_tpu.models import gpt2

GLOBAL_BS = 4
mode = sys.argv[8] if len(sys.argv) > 8 and sys.argv[8] != "-" else "dense"

if mode == "stream":
    # ZeRO-Infinity param streaming: block params host-resident, host CPU
    # optimizer; exercises the multi-host grad-push combine
    config = {"train_micro_batch_size_per_gpu": 1,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {
                  "stage": 0,
                  "offload_optimizer": {"device": "cpu"},
                  "offload_param": {"device": "cpu"},
              },
              "steps_per_print": 100,
              "mesh": {}}
else:
    config = {"train_micro_batch_size_per_gpu": 1,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 2},
              "steps_per_print": 100,
              "mesh": {}}

engine, _, _, _ = deepspeed_tpu.initialize(
    model=gpt2.build(gpt2.GPT2Config.tiny()), config=config)
assert engine.train_batch_size() == GLOBAL_BS, engine.train_batch_size()

if load_dir:
    path, _ = engine.load_checkpoint(load_dir)
    assert path is not None, f"checkpoint load silently no-oped: {load_dir}"

rng = np.random.default_rng(0)  # same batches in every process
rows_per_proc = GLOBAL_BS // nprocs
losses = []
for _ in range(steps):
    full = rng.integers(0, 512, size=(GLOBAL_BS, 17)).astype(np.int32)
    local = full[pid * rows_per_proc:(pid + 1) * rows_per_proc]
    # multi-process contract (DeepSpeedDataLoader process_shard): each
    # controller passes its LOCAL rows, stacked [gas, local_rows, ...]
    _, m = engine.train_batch({"input_ids": local[None]})
    losses.append(float(m["loss"]))

if save_dir:
    engine.save_checkpoint(save_dir)

# exercise the host-level collective surface too
deepspeed_tpu.comm.barrier("test")
red = deepspeed_tpu.comm.host_all_reduce_sum([np.ones(3) * (pid + 1)])
with open(outfile, "w") as f:
    json.dump({"losses": losses, "host_sum": red[0].tolist(),
               "world": jax.device_count(),
               "procs": jax.process_count()}, f)
