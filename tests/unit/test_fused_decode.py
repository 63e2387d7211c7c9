"""Fused multi-step on-device decode (``decode_steps=K``): one
``lax.while_loop`` program runs K decode iterations per host fence with
per-slot eos/budget exits ON-DEVICE, and the host scheduler catches up
in one bookkeeping batch at the fence.

Tier-1 (fast) CPU-sim coverage:
 - exact token parity vs the K=1 per-token loop (and vs sequential
   ``generate``) for chunked + prefix-cache, eos-inside-window, kv8
   (bit-exact between the K=1/K>1 quantized twins), tiered host-DRAM
   KV, and preemption-under-pressure traces — every lane with
   ``debug_checks=True`` so the paged-state invariants are audited at
   each fence and the recompile sentry enforces the budget live.
 - compile contract: the fused program REPLACES the per-token decode
   program (2 programs total, budget unchanged, zero retraces).
 - host-fence accounting: ``host_fence_waits`` ~ ``decode_steps``/K,
   ``fused_iterations`` == device decode iterations, and the new stats
   keys are present.
 - speculative dispatch wins: ``spec_tokens > 0`` makes ``decode_steps``
   inert (no fused program is ever built).
 - ctor validation for the ``engine_mode="dp_tp"`` restrictions (the
   8-device dp×tp parity lane lives in ``test_tp_serving.py``).
"""

import numpy as np
import pytest

from deepspeed_tpu.inference.serving import Request, ServingEngine
from tiny import assert_sequential


def _trace(cfg, n, prefix_len=24, seed=0, tail=(3, 10), max_new=(2, 12)):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len)
    return [Request(uid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, cfg.vocab_size,
                                              int(rng.integers(*tail)))]),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


def _fresh(reqs):
    """New Request objects for a second serve of the same trace."""
    return [Request(uid=r.uid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens) for r in reqs]


def _assert_same(res_a, res_b, reqs):
    for r in reqs:
        np.testing.assert_array_equal(res_a[r.uid], res_b[r.uid],
                                      err_msg=f"uid {r.uid}")


def test_fused_parity_chunked_and_fence_accounting(tiny_engine):
    """Acceptance: K=4 fused decode is token-identical to the K=1 loop
    AND to sequential generate on a shared-prefix chunked trace, with
    ~K fewer host fences and an unchanged 2-program compile contract."""
    engine, cfg = tiny_engine
    kw = dict(slots=4, max_seq_len=128, block_size=8, prefill_chunk=16,
              prefill_batch=2, debug_checks=True)
    reqs = _trace(cfg, 6)
    s1 = ServingEngine(engine, **kw)
    r1 = s1.serve(reqs)
    sK = ServingEngine(engine, decode_steps=4, **kw)
    rK = sK.serve(_fresh(reqs))
    _assert_same(r1, rK, reqs)
    assert_sequential(engine, reqs, rK)
    st1, stK = s1.stats(), sK.stats()
    # fused REPLACES the per-token program: same budget, no extra compile
    assert stK["compile_count"] == 1 + len(sK._rungs) \
        == st1["compile_count"]
    assert stK["compile_budget"] == st1["compile_budget"]
    assert stK["retraces_observed"] == 0
    # the new stats keys, live
    assert stK["engine_mode"] == "replicas"
    assert stK["fused_iterations"] == stK["decode_steps"] > 0
    assert st1["fused_iterations"] == 0
    # one fence per <=K-iteration window vs one host sync per iteration
    assert 0 < stK["host_fence_waits"] <= stK["decode_steps"]
    assert stK["host_fence_waits"] <= -(-st1["decode_steps"] // 4) + \
        len(reqs)        # slack: windows clipped by per-slot budgets
    assert stK["generated_tokens"] == st1["generated_tokens"]
    assert sK.resolved_config()["decode_steps"] == 4
    assert s1.resolved_config()["decode_steps"] == 1


def test_fused_parity_eos_inside_window(tiny_engine):
    """An eos fired at iteration i < K must stop THAT slot's emission
    mid-window (device ``active`` mask) without disturbing the others —
    token-exact vs sequential generate with the same eos."""
    engine, cfg = tiny_engine
    kw = dict(slots=3, max_seq_len=128, block_size=8, prefill_chunk=16,
              prefill_batch=2, debug_checks=True)
    reqs = _trace(cfg, 4, seed=1, max_new=(6, 12))
    probe = engine.generate(reqs[0].prompt[None, :], max_new_tokens=1)
    eos = int(probe[0, len(reqs[0].prompt)])   # fires on request 0's 1st
    sK = ServingEngine(engine, decode_steps=8, **kw)
    rK = sK.serve(reqs, eos_token_id=eos)
    assert_sequential(engine, reqs, rK, eos_token_id=eos)
    # request 0's FIRST generated token is eos — the stop fired at
    # iteration 0 of an 8-wide window (mid-window, not at the fence
    # boundary), and the post-eos fill matches generate's contract
    gen0 = rK[reqs[0].uid][len(reqs[0].prompt):]
    assert gen0[0] == eos and np.all(gen0 == eos)


def test_fused_parity_kv8_bit_exact(tiny_engine):
    """Quantized greedy is a different (equally valid) stream than fp32
    — but between the kv8 twins the fused program must be BIT-exact:
    same int8 codes, same scales, same argmax at every position."""
    engine, cfg = tiny_engine
    kw = dict(slots=4, max_seq_len=128, block_size=8, prefill_chunk=16,
              prefill_batch=2, quantize="kv8", debug_checks=True)
    reqs = _trace(cfg, 6, seed=2)
    r1 = ServingEngine(engine, **kw).serve(reqs)
    rK = ServingEngine(engine, decode_steps=4, **kw).serve(_fresh(reqs))
    _assert_same(r1, rK, reqs)


def test_fused_parity_tiered_host_kv(tiny_engine):
    """Fused decode composes with the host-DRAM KV tier: swaps happen,
    parity holds vs the K=1 tiered twin and sequential generate."""
    engine, cfg = tiny_engine
    kw = dict(slots=3, max_seq_len=64, block_size=8, prefill_chunk=16,
              prefill_batch=2, num_blocks=10, host_blocks=64,
              swap_batch=4, debug_checks=True)
    reqs = _trace(cfg, 6, seed=5, max_new=(20, 29))
    s1 = ServingEngine(engine, **kw)
    r1 = s1.serve(reqs)
    sK = ServingEngine(engine, decode_steps=4, **kw)
    rK = sK.serve(_fresh(reqs))
    _assert_same(r1, rK, reqs)
    assert_sequential(engine, reqs, rK)
    st = sK.stats()
    assert st["swap_out"] > 0 and st["swap_in"] > 0
    # decode + a prefill program a rung + demote + promote
    assert st["compile_count"] == 3 + len(sK._rungs)


def test_fused_preemption_at_fence_keeps_parity(tiny_engine):
    """Block pressure mid-trace: preemption decisions happen at the
    fence (never mid-window on-device), evicted sequences re-queue and
    recompute, and greedy outputs stay identical to generate."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=32, prefill_batch=2, num_blocks=12,
                        decode_steps=4, debug_checks=True)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 17),
                    max_new_tokens=28) for i in range(5)]
    res = srv.serve(reqs)
    assert srv.preempted > 0, srv.stats()  # pressure actually happened
    assert set(res) == set(range(5))
    assert_sequential(engine, reqs, res)


def test_spec_dispatch_wins_over_decode_steps(tiny_engine):
    """``spec_tokens > 0`` routes every decode through draft-verify:
    ``decode_steps`` must be inert (no fused program, no fused
    iterations) and parity vs the plain speculative engine holds."""
    engine, cfg = tiny_engine
    kw = dict(slots=4, max_seq_len=128, block_size=8, prefill_chunk=16,
              prefill_batch=2, spec_tokens=3, debug_checks=True)
    reqs = _trace(cfg, 5, seed=3)
    r_spec = ServingEngine(engine, **kw).serve(reqs)
    s_both = ServingEngine(engine, decode_steps=8, **kw)
    r_both = s_both.serve(_fresh(reqs))
    _assert_same(r_spec, r_both, reqs)
    st = s_both.stats()
    assert st["fused_iterations"] == 0 and st["host_fence_waits"] == 0
    assert st["spec_rounds"] > 0
    assert ("decode", s_both.slots, 8) not in s_both.compiled_programs


def test_decode_steps_validation(tiny_engine):
    engine, _ = tiny_engine
    kw = dict(slots=4, max_seq_len=128, block_size=8, prefill_chunk=16)
    with pytest.raises(ValueError, match="decode_steps"):
        ServingEngine(engine, decode_steps=0, **kw)
    with pytest.raises(ValueError, match="decode_steps"):
        ServingEngine(engine, decode_steps=-3, **kw)


def test_dp_tp_ctor_restrictions(tiny_engine):
    """The v1 dp×tp composition rules fail loudly at the ctor (mirrored
    by ``autotuning/space.py`` ``engine_mode_exclusive``)."""
    engine, _ = tiny_engine
    kw = dict(slots=8, max_seq_len=128, block_size=8, prefill_chunk=16,
              prefix_caching=False)
    with pytest.raises(ValueError, match="engine_mode"):
        ServingEngine(engine, engine_mode="shards", **kw)
    with pytest.raises(ValueError, match="spec"):
        ServingEngine(engine, engine_mode="dp_tp", spec_tokens=3, **kw)
    with pytest.raises(ValueError, match="quantiz"):
        ServingEngine(engine, engine_mode="dp_tp", quantize="kv8", **kw)
    with pytest.raises(ValueError, match="host KV tier"):
        ServingEngine(engine, engine_mode="dp_tp", host_blocks=16, **kw)
    with pytest.raises(ValueError, match="prefix_caching"):
        ServingEngine(engine, engine_mode="dp_tp", slots=8,
                      max_seq_len=128, block_size=8, prefill_chunk=16)
