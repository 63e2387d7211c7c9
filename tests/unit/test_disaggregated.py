"""Disaggregated prefill/decode serving + NVMe third KV tier (ISSUE 17).

Covers: ``plan_roles`` fleet planning, role-aware routing with token
parity against the colocated twin, the ``role="both"`` +
``nvme_blocks=0`` bit-identity guarantee, the ``serve()`` guard on
dedicated roles, NVMe spill/promote with zero-prefix-recompute session
resume, spill-file lifecycle (tempfile mint/cleanup vs operator-owned
path), the three-tier residency audit (green on live spilled state,
loud on crafted violations), and the new telemetry surface (handoff /
nvme timeline events, tier-labeled swap counters).
"""

import os

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.invariants import (PagedStateError,
                                               audit_host_store,
                                               audit_router)
from deepspeed_tpu.inference.paged import (HostBlockStore, NvmeBlockStore,
                                           block_checksum)
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.serving import ReplicaRouter, plan_roles
from tiny import sequential


# ---------------------------------------------------------------- fixtures
_SRV_KW = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
               prefill_batch=2, debug_checks=True)


def _mk_srv(spec, params, **kw):
    merged = dict(_SRV_KW, host_blocks=32, swap_batch=4)
    merged.update(kw)
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
        params=params)
    return ServingEngine(engine, **merged)


def _trace(cfg, n=8, seed=0, prompt_len=24, max_new=8):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, prompt_len),
                    max_new_tokens=max_new) for i in range(n)]


def _run(router, reqs):
    handles = [router.submit(r) for r in reqs]
    while router.step():
        pass
    return {r.uid: np.asarray(h.result(timeout=0))
            for r, h in zip(reqs, handles)}


# -------------------------------------------------------------- plan_roles
def test_plan_roles_assignment_and_validation():
    assert plan_roles(3) == ["both"] * 3
    assert plan_roles(3, 0) == ["both"] * 3
    assert plan_roles(3, 1) == ["prefill", "decode", "decode"]
    assert plan_roles(4, 3) == ["prefill"] * 3 + ["decode"]
    with pytest.raises(ValueError,
                       match="prefill_workers:decode_workers ratio"):
        plan_roles(2, 2)
    with pytest.raises(ValueError, match="ratio"):
        plan_roles(1, 1)
    with pytest.raises(ValueError, match="prefill_workers"):
        plan_roles(2, -1)
    with pytest.raises(ValueError, match="replicas"):
        plan_roles(0)


def test_prefill_first_keeps_decode_ids_stable():
    """Growing the prefill pool must not re-role existing decode ids'
    tail positions: decode workers (long-lived session KV) stay decode."""
    assert plan_roles(4, 1)[-2:] == ["decode", "decode"]
    assert plan_roles(4, 2)[-2:] == ["decode", "decode"]


# -------------------------------------------------- role-aware scheduling
def test_disaggregated_token_parity_and_handoffs(tiny):
    """The tentpole acceptance path: a 1 prefill + 1 decode fleet serves
    a trace token-identically to the colocated 2x"both" twin; every
    request crosses exactly one handoff; both sides' timelines record
    it; the audit stays green throughout (debug_checks on)."""
    spec, cfg, engine = tiny
    reqs = _trace(cfg, n=8)
    seq = sequential(engine, reqs)

    colo = ReplicaRouter([_mk_srv(spec, engine.params) for _ in range(2)],
                         debug_checks=True)
    ref = _run(colo, reqs)
    for r in reqs:
        np.testing.assert_array_equal(ref[r.uid], seq[r.uid])

    dis = ReplicaRouter(
        [_mk_srv(spec, engine.params, role=r)
         for r in ("prefill", "decode")], debug_checks=True)
    out = _run(dis, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], seq[r.uid],
                                      err_msg=f"uid {r.uid}")
    st = dis.stats()
    assert st["handoffs"] == len(reqs)
    assert [p["role"] for p in st["per_replica"]] == ["prefill", "decode"]
    assert st["requests_failed"] == 0
    # timeline: the router and the prefill engine both record handoffs
    assert any(e["name"] == "handoff" for e in dis.timeline.events())
    assert any(e["name"] == "handoff"
               for e in dis.replicas[0].timeline.events())
    # the prefill engine's own counter agrees
    assert dis.replicas[0].stats()["handoffs"] == len(reqs)
    audit_router(dis)


def test_decode_worker_never_prefills_prompts(tiny):
    """TPOT isolation, structurally: the decode worker's recompute is
    bounded by each handoff's sub-block tail — it never re-runs a
    prompt's prefill (the prefill worker's prompt_tokens carries the
    whole trace; the decode worker's recompute stays < block_size per
    admission)."""
    spec, cfg, engine = tiny
    reqs = _trace(cfg, n=6, prompt_len=31)
    dis = ReplicaRouter(
        [_mk_srv(spec, engine.params, role=r)
         for r in ("prefill", "decode")], debug_checks=True)
    _run(dis, reqs)
    pre, dec = dis.replicas
    assert pre.stats()["prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    ds = dec.stats()
    assert ds["admitted"] == len(reqs)
    assert ds["resume_recompute_tokens"] <= ds["admitted"] * dec.block_size
    assert ds["prefix_hit_tokens"] > 0     # the chain pull did the work


def test_role_both_and_nvme_off_bit_identical(tiny):
    """Acceptance gate: explicit ``role="both"``, ``nvme_blocks=0``
    serves bit-identically to an engine built without the PR 17 knobs —
    same tokens, same swap counters, same compile budget — and the new
    stats keys idle at their zeros."""
    spec, cfg, engine = tiny
    reqs = _trace(cfg, n=6)
    base = _mk_srv(spec, engine.params)
    new = _mk_srv(spec, engine.params, role="both", nvme_blocks=0,
                  nvme_high_watermark=0.9, nvme_path=None)
    out_b, out_n = base.serve(reqs), new.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out_b[r.uid], out_n[r.uid])
    sb, sn = base.stats(), new.stats()
    for k in ("swap_out", "swap_in", "swap_bytes", "compile_budget",
              "iterations", "generated_tokens", "prefix_hit_tokens"):
        assert sb[k] == sn[k], k
    assert sn["role"] == "both" and sn["handoffs"] == 0
    assert sn["nvme_blocks"] == 0 and sn["nvme_blocks_in_use"] == 0
    assert sn["nvme_spills"] == 0 and sn["nvme_loads"] == 0
    assert new.nvme_path is None


def test_serve_refuses_dedicated_roles(tiny):
    spec, cfg, engine = tiny
    srv = _mk_srv(spec, engine.params, role="prefill")
    with pytest.raises(RuntimeError, match="ReplicaRouter"):
        srv.serve(_trace(cfg, n=1))


def test_role_validation_is_loud(tiny):
    spec, cfg, engine = tiny
    with pytest.raises(ValueError, match="role"):
        _mk_srv(spec, engine.params, role="sideways")
    with pytest.raises(ValueError, match="host_blocks"):
        _mk_srv(spec, engine.params, role="decode", host_blocks=0)


# --------------------------------------------------------- nvme third tier
_NVME_KW = dict(slots=2, num_blocks=12, host_blocks=8, swap_batch=2,
                nvme_blocks=32, nvme_high_watermark=0.5)


def test_nvme_session_resume_zero_prefix_recompute(tiny):
    """A session whose prefix spilled all the way to NVMe resumes with
    the prefix riding promotion (loads > 0), recompute bounded by the
    unfinished tail, and token output exactly matching the fault-free
    sequential run."""
    spec, cfg, engine = tiny
    reqs = _trace(cfg, n=8, prompt_len=32, max_new=6)
    seq = sequential(engine, reqs)
    srv = _mk_srv(spec, engine.params, **_NVME_KW)
    out = srv.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid], seq[r.uid])
    st = srv.stats()
    assert st["nvme_spills"] > 0
    assert st["nvme_blocks_in_use"] > 0

    # resume session 0: its 32-token prompt is 4 committed blocks — all
    # spilled by now.  The resume must promote (nvme_loads grows), not
    # recompute: the recompute delta stays under one block.
    rec0 = srv.stats()["resume_recompute_tokens"]
    resumed = srv.serve([Request(uid="resume", prompt=reqs[0].prompt,
                                 max_new_tokens=6)])
    np.testing.assert_array_equal(resumed["resume"], seq[0])
    st2 = srv.stats()
    assert st2["nvme_loads"] > 0
    assert st2["resume_recompute_tokens"] - rec0 < srv.block_size
    # tier-labeled swap metrics: host and nvme directions both moved
    prom = srv.metrics.prometheus_text()
    assert 'serving_kv_swaps_total{direction="out",tier="nvme"}' in prom
    assert 'serving_kv_swaps_total{direction="in",tier="nvme"}' in prom
    assert 'tier="host"' in prom
    assert "serving_nvme_blocks_in_use" in prom
    names = {e["name"] for e in srv.timeline.events()}
    assert {"nvme_spill", "nvme_load"} <= names
    srv.close()


def test_nvme_spill_file_lifecycle(tiny, tmp_path):
    """An auto-minted spill tempfile dies with the engine; an
    operator-named path survives close() (their file, their lifecycle)."""
    spec, cfg, engine = tiny
    auto = _mk_srv(spec, engine.params, **_NVME_KW)
    path = auto.nvme_path
    assert os.path.exists(path)
    auto.close()
    assert not os.path.exists(path)

    mine = str(tmp_path / "operator.bin")
    owned = _mk_srv(spec, engine.params, **{**_NVME_KW,
                                            "nvme_path": mine})
    owned.serve(_trace(cfg, n=6, prompt_len=32))
    assert owned.stats()["nvme_spills"] > 0
    owned.close()
    assert os.path.exists(mine)            # operator-owned file retained


def test_nvme_knob_validation_is_loud(tiny):
    spec, cfg, engine = tiny
    with pytest.raises(ValueError, match="host tier"):
        _mk_srv(spec, engine.params, host_blocks=0, nvme_blocks=8)
    with pytest.raises(ValueError, match="nvme_high_watermark"):
        _mk_srv(spec, engine.params, nvme_blocks=8,
                nvme_high_watermark=1.5)
    with pytest.raises(ValueError, match="watermark budget"):
        _mk_srv(spec, engine.params, host_blocks=8, swap_batch=4,
                nvme_blocks=8, nvme_high_watermark=0.2)


# ------------------------------------------------------- residency audit
_SPECS = [((4,), np.float32), ((4,), np.float32)]


def _blk(seed):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=s).astype(dt) for s, dt in _SPECS]


def _spilled_store(tmp_path, n_put=6):
    nvme = NvmeBlockStore(8, _SPECS, str(tmp_path / "s.bin"))
    store = HostBlockStore(4, _SPECS, nvme=nvme, nvme_watermark=0.5)
    for i in range(n_put):
        store.put(f"k{i}".encode(), _blk(i))
    return store, nvme


def test_residency_audit_green_on_live_spilled_state(tmp_path):
    store, nvme = _spilled_store(tmp_path)
    assert store.nvme_blocks_in_use > 0        # the watermark spilled
    audit_host_store(store, ())
    # promotion back up the ladder keeps it green too
    spilled = [k for k, _ in nvme.nvme_snapshot()[1].items()]
    store.promote_spilled(spilled[:1])
    audit_host_store(store, ())
    nvme.close()


def test_residency_audit_catches_dual_tier_residency(tmp_path):
    store, nvme = _spilled_store(tmp_path)
    resident = next(iter(store.snapshot()[1]))
    nvme.swap_out(resident, _blk(99), block_checksum(_blk(99)))
    with pytest.raises(PagedStateError, match="BOTH"):
        audit_host_store(store, ())
    nvme.close()


def test_residency_audit_catches_nvme_slot_leaks(tmp_path):
    store, nvme = _spilled_store(tmp_path)
    # leaked slot: neither free nor owned
    spilled_key = next(iter(nvme.nvme_snapshot()[1]))
    del nvme._entries[spilled_key]             # drop without freeing
    with pytest.raises(PagedStateError, match="neither free nor owned"):
        audit_host_store(store, ())
    nvme.close()


def test_residency_audit_catches_double_owned_file_slot(tmp_path):
    store, nvme = _spilled_store(tmp_path)
    snap = nvme.nvme_snapshot()[1]
    keys = list(snap)
    assert len(keys) >= 2
    nvme._entries[keys[1]].slot = nvme._entries[keys[0]].slot
    with pytest.raises(PagedStateError, match="residency-conservation"):
        audit_host_store(store, ())
    nvme.close()
