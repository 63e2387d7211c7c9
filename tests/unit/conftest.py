"""Fixtures the unit tests share (``tiny.py`` beside this file holds what
they share that is no fixture)."""

import subprocess

import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2


@pytest.fixture
def workers_reaped(monkeypatch):
    """No process the test starts through ``subprocess.Popen`` outlives it,
    whatever the test's outcome: the elastic agent's workers idle for 120 s
    until the agent stops them, and an agent that raised stops nobody."""
    started = []
    popen = subprocess.Popen

    def recorded(*args, **kwargs):
        started.append(popen(*args, **kwargs))
        return started[-1]

    monkeypatch.setattr(subprocess, "Popen", recorded)
    try:
        yield
    finally:
        for proc in started:
            if proc.poll() is None:
                proc.kill()
            proc.wait()


@pytest.fixture(scope="session")
def tiny():
    """``(spec, cfg, engine)``: THE tiny GPT-2 (128 positions, float32, one
    shard) and its inference engine, built once a worker for every file
    that serves it.  Shared by tests that do not change it: ``generate``
    and ``forward`` only add programs to it, and a ``ServingEngine`` on top
    of it has a pool and slots of its own, which ``serve()`` drains."""
    deepspeed_tpu.comm.reset_topology()
    cfg = gpt2.GPT2Config.tiny(max_seq_len=128)
    spec = gpt2.build(cfg)
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}})
    return spec, cfg, engine


@pytest.fixture(scope="session")
def tiny_engine(tiny):
    """``(engine, cfg)`` of :func:`tiny`."""
    spec, cfg, engine = tiny
    return engine, cfg
