"""Launcher pure-unit tests (model: reference tests/unit/launcher/test_run.py
and test_multinode_runner.py — no ssh, just parsing + command construction)."""

import base64
import json

import pytest

from deepspeed_tpu.launcher.launch import build_env, decode_world_info
from deepspeed_tpu.launcher.runner import (OpenMPIRunner, PDSHRunner,
                                           SlurmRunner, encode_world_info,
                                           fetch_hostfile, parse_args,
                                           parse_resource_filter)


@pytest.fixture
def hostfile(tmp_path):
    p = tmp_path / "hostfile"
    p.write_text("""
worker-0 slots=4
worker-1 slots=4
# a comment
worker-2 slots=8
""")
    return str(p)


def test_fetch_hostfile(hostfile):
    pool = fetch_hostfile(hostfile)
    assert pool == {"worker-0": 4, "worker-1": 4, "worker-2": 8}


def test_fetch_hostfile_missing(tmp_path):
    assert fetch_hostfile(str(tmp_path / "nope")) is None


def test_fetch_hostfile_bad_format(tmp_path):
    p = tmp_path / "hf"
    p.write_text("worker-0 slots=four\n")
    with pytest.raises(ValueError):
        fetch_hostfile(str(p))


def test_fetch_hostfile_duplicate(tmp_path):
    p = tmp_path / "hf"
    p.write_text("w slots=2\nw slots=2\n")
    with pytest.raises(ValueError):
        fetch_hostfile(str(p))


def test_resource_filter_include():
    pool = {"worker-0": 4, "worker-1": 4}
    active = parse_resource_filter(pool, include_str="worker-1:0,2")
    assert active == {"worker-1": [0, 2]}
    active = parse_resource_filter(pool, include_str="worker-0")
    assert active == {"worker-0": [0, 1, 2, 3]}


def test_resource_filter_exclude():
    pool = {"worker-0": 4, "worker-1": 4}
    active = parse_resource_filter(pool, exclude_str="worker-1")
    assert list(active.keys()) == ["worker-0"]
    active = parse_resource_filter(pool, exclude_str="worker-0:1,3")
    assert active["worker-0"] == [0, 2]


def test_resource_filter_conflicts():
    with pytest.raises(ValueError):
        parse_resource_filter({"w": 2}, include_str="w", exclude_str="w")
    with pytest.raises(ValueError):
        parse_resource_filter({"w": 2}, include_str="bogus-host")


def test_world_info_roundtrip():
    active = {"worker-0": [0, 1], "worker-1": [0]}
    encoded = encode_world_info(active)
    assert decode_world_info(encoded) == active


def _args(extra=None):
    return parse_args((extra or []) + ["train.py", "--foo", "bar"])


def test_pdsh_cmd_construction():
    args = _args(["--master_addr", "worker-0"])
    runner = PDSHRunner(args, encode_world_info({"worker-0": [0], "worker-1": [0]}))
    cmd = runner.get_cmd({}, {"worker-0": [0], "worker-1": [0]})
    assert cmd[0] == "pdsh"
    assert "worker-0,worker-1" in cmd
    joined = " ".join(cmd)
    assert "deepspeed_tpu.launcher.launch" in joined
    assert "--master_addr=worker-0" in joined
    assert "train.py" in joined and "--foo bar" in joined


def test_openmpi_cmd_construction():
    args = _args()
    runner = OpenMPIRunner(args, "x")
    cmd = runner.get_cmd({}, {"a": [0], "b": [0]})
    assert cmd[0] == "mpirun"
    assert "-n" in cmd and cmd[cmd.index("-n") + 1] == "2"
    assert "train.py" in cmd


def test_mpich_cmd_construction():
    from deepspeed_tpu.launcher.runner import MPICHRunner

    args = _args()
    runner = MPICHRunner(args, "x")
    cmd = runner.get_cmd({}, {"a": [0], "b": [0]})
    assert cmd[0] == "mpirun"
    assert cmd[cmd.index("-n") + 1] == "2"
    assert cmd[cmd.index("-ppn") + 1] == "1"
    assert "train.py" in cmd


def test_mvapich_cmd_construction(tmp_path, monkeypatch):
    from deepspeed_tpu.launcher.runner import MVAPICHRunner

    args = _args()
    runner = MVAPICHRunner(args, "x")
    monkeypatch.setattr(MVAPICHRunner, "hostfile_path",
                        str(tmp_path / "mvapich_hosts"))
    cmd = runner.get_cmd({}, {"a": [0], "b": [0], "c": [0]})
    assert cmd[0] == "mpirun_rsh"
    assert cmd[cmd.index("-np") + 1] == "3"
    hosts = (tmp_path / "mvapich_hosts").read_text().split()
    assert hosts == ["a", "b", "c"]


def test_slurm_cmd_construction():
    args = _args()
    runner = SlurmRunner(args, "x")
    cmd = runner.get_cmd({}, {"a": [0], "b": [0], "c": [0]})
    assert cmd[0] == "srun"
    assert cmd[cmd.index("-N") + 1] == "3"


def test_build_env():
    world = {"worker-0": [0, 1], "worker-1": [0, 1]}
    env = build_env(world, node_rank=1, master_addr="worker-0",
                    master_port=1234, base_env={})
    assert env["JAX_COORDINATOR_ADDRESS"] == "worker-0:1234"
    assert env["JAX_NUM_PROCESSES"] == "2"
    assert env["JAX_PROCESS_ID"] == "1"
    assert env["WORLD_SIZE"] == "4"


# --------------------------------------------------------------------------
# elastic training through the CLI (reference launcher/launch.py:257-310:
# --enable_elastic_training starts the elastic agent)
# --------------------------------------------------------------------------
def test_elastic_flag_requires_config(tmp_path):
    from deepspeed_tpu.launcher import runner

    hf = tmp_path / "hostfile"
    hf.write_text("a slots=1\nb slots=1\n")
    with pytest.raises(ValueError, match="elastic_config"):
        runner.main(["--hostfile", str(hf), "--enable_elastic_training",
                     "--launcher", "local", "train.py"])


def test_elastic_cli_restarts_dead_worker(tmp_path, workers_reaped):
    """CLI path end to end: a worker dies mid-run, the agent re-elects and
    restarts the group; workers of the second generation (keyed off the
    agent-injected DS_ELASTIC_RESTART_COUNT) finish cleanly."""
    import sys as _sys

    from deepspeed_tpu.launcher import runner

    hf = tmp_path / "hostfile"
    hf.write_text("hostA slots=1\nhostB slots=1\n")
    cfg = tmp_path / "ds.json"
    cfg.write_text(json.dumps({
        "elasticity": {"enabled": True, "max_train_batch_size": 8,
                       "micro_batch_sizes": [1, 2], "min_gpus": 1,
                       "max_gpus": 8, "min_time": 0, "version": 0.2},
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
    }))
    log = tmp_path / "gens.jsonl"
    script = tmp_path / "worker.py"
    # generation 0: rank 1 crashes mid-run (the "killed worker"), rank 0
    # idles so only the agent's restart can reap it; generation 1+ exits 0
    script.write_text(f"""
import json, os, sys, time
with open({str(log)!r}, "a") as f:
    json.dump({{"gen": os.environ["DS_ELASTIC_RESTART_COUNT"],
               "n": os.environ["JAX_NUM_PROCESSES"],
               "rank": os.environ["JAX_PROCESS_ID"]}}, f)
    f.write("\\n")
if os.environ["DS_ELASTIC_RESTART_COUNT"] == "0":
    if os.environ["JAX_PROCESS_ID"] == "1":
        time.sleep(0.3)
        sys.exit(1)
    time.sleep(120)
""")
    code = None
    try:
        runner.main(["--hostfile", str(hf), "--enable_elastic_training",
                     "--elastic_config", str(cfg),
                     "--elastic_monitor_interval", "0.2",
                     "--launcher", "local", str(script)])
    except SystemExit as e:
        code = e.code
    assert code == 0
    gens = [json.loads(l) for l in log.read_text().splitlines()]
    g0 = [g for g in gens if g["gen"] == "0"]
    g1 = [g for g in gens if g["gen"] != "0"]
    assert len(g0) == 2 and len(g1) >= 2, gens
    assert {g["n"] for g in gens} == {"2"}  # both hosts elected each time


# --------------------------------------------------------------------------
# serving-replica mode (--serve): ElasticAgent supervision without elastic
# batch election — one replica worker per host / --replicas N local workers
# --------------------------------------------------------------------------
def test_serve_flag_parses():
    args = parse_args(["--serve", "--replicas", "3", "serve_worker.py"])
    assert args.serve and args.replicas == 3
    assert parse_args(["train.py"]).serve is False


def test_serve_mode_supervises_local_replicas(tmp_path):
    """--serve --replicas 2 without a hostfile: two local replica workers
    run under the agent, each seeing its DS_REPLICA_ID / DS_NUM_REPLICAS,
    and a clean fleet exit returns 0 with no restart burned."""
    from deepspeed_tpu.launcher import runner

    log = tmp_path / "replicas.jsonl"
    script = tmp_path / "replica.py"
    script.write_text(f"""
import json, os
with open({str(log)!r}, "a") as f:
    json.dump({{"rid": os.environ["DS_REPLICA_ID"],
               "n": os.environ["DS_NUM_REPLICAS"]}}, f)
    f.write("\\n")
""")
    code = None
    try:
        runner.main(["--serve", "--replicas", "2",
                     "--hostfile", str(tmp_path / "no_hostfile"),
                     "--elastic_monitor_interval", "0.2",
                     "--launcher", "local", str(script)])
    except SystemExit as e:
        code = e.code
    assert code == 0
    seen = [json.loads(l) for l in log.read_text().splitlines()]
    assert {s["rid"] for s in seen} == {"0", "1"}
    assert {s["n"] for s in seen} == {"2"}


def test_serve_mode_restarts_dead_replica_alone(tmp_path):
    """PR 15: a crashed replica worker is restarted ALONE (generation
    keyed off DS_ELASTIC_RESTART_COUNT) — the healthy replica keeps
    running through the restart instead of being killed with the group
    (the process-level half of the fail/readmit crash protocol)."""
    from deepspeed_tpu.launcher import runner

    log = tmp_path / "gens.jsonl"
    script = tmp_path / "replica.py"
    script.write_text(f"""
import json, os, sys, time
with open({str(log)!r}, "a") as f:
    json.dump({{"gen": os.environ["DS_ELASTIC_RESTART_COUNT"],
               "rid": os.environ["DS_REPLICA_ID"]}}, f)
    f.write("\\n")
if os.environ["DS_ELASTIC_RESTART_COUNT"] == "0":
    if os.environ["DS_REPLICA_ID"] == "1":
        time.sleep(0.1)
        sys.exit(1)
    time.sleep(0.6)
""")
    code = None
    try:
        runner.main(["--serve", "--replicas", "2",
                     "--hostfile", str(tmp_path / "no_hostfile"),
                     "--elastic_monitor_interval", "0.2",
                     "--launcher", "local", str(script)])
    except SystemExit as e:
        code = e.code
    assert code == 0
    gens = [json.loads(l) for l in log.read_text().splitlines()]
    assert {g["rid"] for g in gens if g["gen"] == "0"} == {"0", "1"}
    # the dead replica came back at a later generation...
    assert any(g["gen"] != "0" and g["rid"] == "1" for g in gens)
    # ...and the healthy one was NEVER killed/relaunched (single-worker
    # restart — the whole point): replica 0 only ever logged gen 0
    assert all(g["gen"] == "0" for g in gens if g["rid"] == "0")
