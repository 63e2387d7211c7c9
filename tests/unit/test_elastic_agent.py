"""ElasticAgent tests (reference elastic_agent.py DSElasticAgent):
supervision, restart-on-failure, membership-change restart, world election.
Workers are tiny subprocesses — no jax involved."""

import json
import sys

import pytest

from deepspeed_tpu.elasticity.elastic_agent import ElasticAgent

pytestmark = pytest.mark.usefixtures("workers_reaped")

CFG = {"elasticity": {"enabled": True, "max_train_batch_size": 16,
                      "micro_batch_sizes": [1, 2], "min_gpus": 1,
                      "max_gpus": 16, "min_time": 0,
                      "prefer_larger_batch": True, "version": 0.2},
       "train_micro_batch_size_per_gpu": 2,
       "gradient_accumulation_steps": 1}


def _agent(probe, launch, **kw):
    kw.setdefault("monitor_interval", 0.1)
    return ElasticAgent(CFG, probe, launch, **kw)


def test_elect_world_picks_largest_valid():
    agent = _agent(lambda: [], lambda h, e: [])
    hosts = [f"h{i}" for i in range(5)]
    # valid chip counts include 4 (16/4=4 micro 2 gas 2 etc.); 5 is not a
    # divisor-friendly count for batch 16 -> largest valid <= 5 is 4
    elected = agent.elect_world(hosts)
    assert len(elected) == 4
    assert elected == hosts[:4]


def test_elect_world_incompatible_raises():
    agent = _agent(lambda: [], lambda h, e: [], chips_per_host=32)
    with pytest.raises(RuntimeError):
        agent.elect_world(["h0"])


def test_run_succeeds_when_workers_exit_zero():
    agent = _agent(lambda: ["a", "b"],
                   lambda host, env: [sys.executable, "-c", "pass"])
    assert agent.run() == 0
    assert agent.restart_count == 0


def test_run_restarts_on_failure(tmp_path):
    """First generation fails; after the flag file exists workers succeed."""
    flag = tmp_path / "ok"
    prog = (f"import os,sys;"
            f"sys.exit(0 if os.path.exists({str(flag)!r}) else "
            f"(open({str(flag)!r},'w').close() or 1))")
    agent = _agent(lambda: ["a", "b"],
                   lambda host, env: [sys.executable, "-c", prog])
    assert agent.run() == 0
    assert agent.restart_count >= 1
    # restart count surfaced to workers via env
    env = agent._env_for("a", 0, ["a", "b"])
    assert env["DS_ELASTIC_RESTART_COUNT"] == str(agent.restart_count)


def test_membership_change_triggers_restart(tmp_path):
    """Hosts shrink 4 -> 2 mid-run: the group restarts on 2 hosts.

    Load-independent by construction (the 1-core box makes wall-clock
    margins flaky): the probe keeps reporting 4 hosts until all four
    first-group workers have provably written their line, and workers key
    their lifetime off the agent-injected DS_ELASTIC_RESTART_COUNT — the
    first group idles until killed by the restart, the second exits
    immediately so the agent observes SUCCEEDED."""
    log = tmp_path / "worlds.jsonl"

    def probe():
        lines = log.read_text().splitlines() if log.exists() else []
        if len(lines) < 4:
            return ["a", "b", "c", "d"]
        return ["a", "b"]

    prog = ("import os,time,json;"
            f"f=open({str(log)!r},'a');"
            "json.dump({'n': os.environ['JAX_NUM_PROCESSES']}, f);"
            "f.write('\\n');f.close();"
            "time.sleep(120.0) if os.environ['DS_ELASTIC_RESTART_COUNT'] "
            "== '0' else None")
    agent = _agent(probe, lambda host, env: [sys.executable, "-c", prog],
                   monitor_interval=2.0)
    assert agent.run() == 0
    worlds = [json.loads(l)["n"] for l in log.read_text().splitlines()]
    assert worlds.count("4") == 4 and worlds.count("2") == 2, worlds
    assert agent.restart_count >= 1


def test_slot_count_change_triggers_restart(tmp_path):
    """Dict probe: hostfile slot edits must take effect at the next election
    — chips_per_host is re-derived per probe, and a capacity change with an
    IDENTICAL host set restarts the group with the new WORLD_SIZE.  Same
    load-independence construction as the membership-change test."""
    log = tmp_path / "worlds.jsonl"

    def probe():
        lines = log.read_text().splitlines() if log.exists() else []
        if len(lines) < 2:
            return {"a": 1, "b": 1}
        return {"a": 4, "b": 4}   # slice grew: 4 chips/host now

    prog = ("import os,time,json;"
            f"f=open({str(log)!r},'a');"
            "json.dump({'ws': os.environ['WORLD_SIZE']}, f);"
            "f.write('\\n');f.close();"
            "time.sleep(120.0) if os.environ['DS_ELASTIC_RESTART_COUNT'] "
            "== '0' else None")
    agent = _agent(probe, lambda host, env: [sys.executable, "-c", prog],
                   monitor_interval=2.0)
    assert agent.run() == 0
    worlds = [json.loads(l)["ws"] for l in log.read_text().splitlines()]
    # first group: 2 hosts x 1 chip = WS 2; second: 2 hosts x 4 = WS 8
    assert worlds.count("2") == 2 and worlds.count("8") == 2, worlds
    assert agent.restart_count >= 1


def test_het_dict_probe_shrinks_mid_run(tmp_path):
    """Heterogeneous probe dict SHRINKING mid-run: the pool loses its
    2-chip members, chips_per_host re-derives to the new minimum (4),
    and the group restarts at the higher per-host capacity with the
    smaller host set — the elastic slice-resize path."""
    log = tmp_path / "worlds.jsonl"

    def probe():
        lines = log.read_text().splitlines() if log.exists() else []
        if len(lines) < 4:
            # 4 hosts, min capacity 1 => WORLD_SIZE 4*1 = 4
            return {"a": 4, "b": 1, "c": 4, "d": 1}
        return {"a": 4, "c": 4}   # 1-chip hosts died: 2 hosts x 4 chips

    prog = ("import os,time,json;"
            f"f=open({str(log)!r},'a');"
            "json.dump({'ws': os.environ['WORLD_SIZE']}, f);"
            "f.write('\\n');f.close();"
            "time.sleep(120.0) if os.environ['DS_ELASTIC_RESTART_COUNT'] "
            "== '0' else None")
    agent = _agent(probe, lambda host, env: [sys.executable, "-c", prog],
                   monitor_interval=2.0)
    assert agent.run() == 0
    worlds = [json.loads(l)["ws"] for l in log.read_text().splitlines()]
    assert worlds[:4] == ["4"] * 4, worlds      # gen 1: 4 hosts x 1 chip
    assert worlds[4:] == ["8"] * 2, worlds      # gen 2: 2 hosts x 4
    assert agent.chips_per_host == 4
    assert agent.restart_count >= 1


def test_partial_grace_ticks_expiry():
    """One worker exits 0 while its peer hangs: PARTIAL persists past
    ``partial_grace_ticks`` monitor ticks, the group restarts, and the
    second generation (both exiting 0) SUCCEEDS.  Within-grace completion
    skew must NOT have burned more than one restart."""
    prog = ("import os,time,sys;"
            "hang = (os.environ['DS_ELASTIC_RESTART_COUNT'] == '0' and "
            "os.environ['JAX_PROCESS_ID'] == '1');"
            "time.sleep(120.0) if hang else sys.exit(0)")
    agent = _agent(lambda: ["a", "b"],
                   lambda host, env: [sys.executable, "-c", prog],
                   monitor_interval=0.2, partial_grace_ticks=2)
    assert agent.run() == 0
    # exactly one restart: the grace window absorbed the skew ticks, the
    # expiry (tick 3) restarted the hung survivor's group once
    assert agent.restart_count == 1


def test_elect_all_flag_elects_every_host():
    """elect_all=True (the launcher --serve replica-supervision mode):
    every live host is elected, no batch constraint; WITHOUT the flag a
    missing/disabled elasticity block still fails fast — a typo'd
    training config must not silently launch on every host."""
    agent = ElasticAgent({}, lambda: [], lambda h, e: [],
                         monitor_interval=0.1, elect_all=True)
    hosts = [f"r{i}" for i in range(5)]
    assert agent.elect_world(hosts) == hosts
    with pytest.raises(RuntimeError):
        agent.elect_world([])
    for cfg in ({}, {"elasticity": {"enabled": False}}):
        strict = ElasticAgent(cfg, lambda: [], lambda h, e: [],
                              monitor_interval=0.1)
        with pytest.raises(Exception):
            strict.elect_world(["x"])


def test_zero_slot_hosts_excluded():
    """A slots=0 hostfile line behaves like an excluded host: it is not
    elected and does not drag chips_per_host to 1."""
    agent = _agent(lambda: {"a": 4, "b": 0, "c": 4},
                   lambda host, env: [sys.executable, "-c", "pass"])
    hosts = agent._probe()
    assert hosts == ["a", "c"]
    assert agent.chips_per_host == 4
