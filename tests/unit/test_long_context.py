"""Long-context serving lane: sequence-parallel (Ulysses) prefill over
the ``sp`` mesh axis + resident-window context paging.

Tier-1 (fast) CPU-sim coverage:
 - ``sp=4`` prefill is token-IDENTICAL to ``sp=1`` on a mixed-length
   trace (the all-to-all is a pure layout move), the a2a byte counter
   advances, and the compile contract stays 2 programs — sp reshapes
   the SAME chunked prefill program through shard_map.
 - ``sp=2 x tp=2`` composes on the 8-device CI mesh with the same
   token parity.
 - resident-window decode is BIT-exact with full attention whenever the
   window covers the whole context (the mask reduces to the identity).
 - under tier pressure a giant prompt slides its window: cold blocks
   demote to the host arena, ``serving_context_window_slides_total``
   advances, ``window_slide`` timeline events land, and the paged-state
   invariant audits pass at every step (``debug_checks=True``).
 - the windowed programs REPLACE the plain bodies one-for-one: the
   sentry budget is unchanged and never trips.
 - chain-key regression: keys are fixed-width rolling digests — no
   position-dependent width, prefix-dependence preserved, the batch
   :func:`chain_keys` byte-identical to per-block :func:`chain_key`.

The loud ctor twins of the ``sp_prefill_exclusive`` /
``resident_window_span`` space constraints are audited in
``test_serving_autotune.py``.
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.paged import (CHAIN_KEY_BYTES, chain_key,
                                           chain_keys)
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import gpt2

CFG = gpt2.GPT2Config.tiny(max_seq_len=256)


def _trace(seed, lens, max_new=6):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, CFG.vocab_size, int(n)),
                    max_new_tokens=max_new)
            for i, n in enumerate(lens)]


def _serve(trace_seed, lens, *, config=None, max_new=6, **kw):
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        gpt2.build(CFG), config={"dtype": "fp32", **(config or {})},
        slots=4, max_seq_len=256, block_size=8, prefill_chunk=16,
        debug_checks=True, **kw)
    return srv, srv.serve(_trace(trace_seed, lens, max_new))


def _assert_same(a, b, lens):
    for uid in range(len(lens)):
        np.testing.assert_array_equal(a[uid], b[uid],
                                      err_msg=f"uid {uid}")


# ------------------------------------------------------ sp prefill
def test_sp4_prefill_token_parity_and_a2a_accounting():
    """Acceptance: sp=4 Ulysses prefill is exactly token-identical to
    the sp=1 engine, moves bytes through the all-to-all counter, and
    compiles the same 2 programs (the sp budget amendment is +0)."""
    lens = (40, 70, 100, 25)
    s1, out1 = _serve(11, lens)
    s4, out4 = _serve(11, lens, sp=4)
    _assert_same(out1, out4, lens)
    st = s4.stats()
    assert st["sp"] == 4 and s1.stats()["sp"] == 1
    assert st["sp_alltoall_bytes"] > 0
    assert s1.stats()["sp_alltoall_bytes"] == 0
    # same compile contract as the plain engine — sp reshapes the SAME
    # prefill program through shard_map (budget amendment is zero)
    assert s4.compile_budget == s1.compile_budget
    assert s4.compile_count <= s4.compile_budget
    assert any(e["name"] == "sp_prefill" for e in s4.timeline.events())
    assert s4.resolved_config()["sp"] == 4


def test_sp_composes_with_tp_on_8_device_mesh(eight_devices):
    """sp=2 x tp=2 shares the 8-device CI mesh: heads shard over tp,
    the chunk shards over sp, and tokens still match the 1x1 engine."""
    lens = (40, 70)
    s1, out1 = _serve(13, lens)
    s22, out22 = _serve(
        13, lens, sp=2, config={"tensor_parallel": {"tp_size": 2}})
    _assert_same(out1, out22, lens)
    assert s22.stats()["sp"] == 2
    assert s22.stats()["sp_alltoall_bytes"] > 0


def test_sp_ctor_validations():
    deepspeed_tpu.comm.reset_topology()
    engine = deepspeed_tpu.init_inference(
        gpt2.build(CFG), config={"dtype": "fp32"})
    from deepspeed_tpu.inference.serving import ServingEngine

    with pytest.raises(ValueError, match="sp must be >= 1"):
        ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                      prefill_chunk=16, sp=0)
    # mesh carries no sp axis -> loud shape mismatch with guidance
    with pytest.raises(ValueError, match="sequence_parallel"):
        ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                      prefill_chunk=16, sp=2)


# ------------------------------------------------- resident window
def test_full_window_is_bit_exact_with_full_attention():
    """A window wide enough to cover the whole context never slides,
    and the windowed decode/prefill programs are BIT-identical to the
    plain ones (window_start=0 masks nothing)."""
    lens = (40, 60, 30)
    sp_, outp = _serve(17, lens)
    sw, outw = _serve(17, lens, host_blocks=64, swap_batch=8,
                      resident_window_blocks=32)
    _assert_same(outp, outw, lens)
    st = sw.stats()
    assert st["resident_window_blocks"] == 32
    assert st["context_window_slides"] == 0


def test_window_slides_under_tier_pressure():
    """Acceptance: prompts far wider than the device window stream
    through — the window slides, cold blocks demote host-side, the
    slide counter and timeline events advance, and every step passes
    the paged-state invariant audit (debug_checks=True)."""
    lens = (100, 80, 120)
    sw, outw = _serve(19, lens, max_new=8, num_blocks=40,
                      host_blocks=96, swap_batch=8,
                      resident_window_blocks=4)
    st = sw.stats()
    assert st["context_window_slides"] > 0
    # device residency stayed under the window cap: landmark + window +
    # one chunk span (+ scratch) is the per-slot ceiling, far below the
    # 100+-token contexts served
    slides = [e for e in sw.timeline.events()
              if e["name"] == "window_slide"]
    assert slides and all(e["args"]["window_start"] > 0 for e in slides)
    assert any(e["args"]["demoted"] > 0 or e["args"]["blocks_freed"] > 0
               for e in slides)
    # cold context actually reached the host tier
    assert st["host_blocks_in_use"] > 0 or st["swap_out"] > 0
    # every request still produced its full token budget
    for uid, n in enumerate(lens):
        assert len(outw[uid]) == n + 8
    # compile contract: windowed bodies REPLACE the plain ones — the
    # sentry budget is the plain tiered budget, and it held
    assert sw.compile_count <= sw.compile_budget
    assert sw.resolved_config()["resident_window_blocks"] == 4


def test_window_ctor_validations():
    deepspeed_tpu.comm.reset_topology()
    engine = deepspeed_tpu.init_inference(
        gpt2.build(CFG), config={"dtype": "fp32"})
    from deepspeed_tpu.inference.serving import ServingEngine

    base = dict(slots=2, max_seq_len=64, block_size=8, prefill_chunk=16)
    with pytest.raises(ValueError, match="host_blocks"):
        ServingEngine(engine, resident_window_blocks=4, **base)
    with pytest.raises(ValueError, match="must be >= 3"):
        ServingEngine(engine, resident_window_blocks=2, host_blocks=8,
                      swap_batch=4, **base)
    with pytest.raises(ValueError, match="speculative"):
        ServingEngine(engine, resident_window_blocks=4, host_blocks=8,
                      swap_batch=4, spec_tokens=2, **base)
    with pytest.raises(TypeError, match="decode_steps"):  # gone at PR 59
        ServingEngine(engine, resident_window_blocks=4, host_blocks=8,
                      swap_batch=4, decode_steps=4, **base)


# ------------------------------------------------- chain-key regression
def test_chain_keys_fixed_width_and_prefix_dependent():
    """Regression for the unbounded-key bug: every chain key is exactly
    CHAIN_KEY_BYTES wide at ANY chain depth (the old raw-chain encoding
    grew linearly with block index), identical token suffixes under
    different prefixes never alias, and the batch helper matches the
    per-block function byte-for-byte."""
    bs = 4
    rng = np.random.default_rng(23)
    toks = rng.integers(0, 512, 64 * bs).astype(np.int32)
    keys = chain_keys(toks, 64, bs)
    assert len(keys) == 64
    assert all(len(k) == CHAIN_KEY_BYTES for k in keys)
    assert len(set(keys)) == 64
    for i in (0, 1, 31, 63):
        assert chain_key(toks, i, bs) == keys[i]
    # prefix-dependence: same block-2 tokens, different block-0 prefix
    a = np.array([1, 2, 3, 4, 5, 6, 7, 8], np.int32)
    b = np.array([9, 2, 3, 4, 5, 6, 7, 8], np.int32)
    assert chain_key(a, 1, bs) != chain_key(b, 1, bs)
    # and equal chains agree
    assert chain_key(a, 1, bs) == chain_key(a.copy(), 1, bs)


def test_chain_keys_no_depth_aliasing():
    """A shallow chain's key can never equal a deep chain's key built
    from different tokens even when the OLD encoding would have made
    their raw byte strings collide-prone; with fixed-width rolling
    digests the (tokens, depth) -> key map stays injective in practice."""
    bs = 2
    x = np.arange(40, dtype=np.int32)
    all_keys = set()
    for depth in range(1, 20):
        all_keys.add(chain_key(x, depth - 1, bs))
    assert len(all_keys) == 19


# ------------------------------------------------- router giant lane
def test_router_giant_context_affinity_and_slo_class():
    """Prompts over the giant_context_tokens threshold force affinity
    routing (even under round_robin), land in the 'giant_context' SLO
    class, and show up in the router's giant counter + timeline."""
    from deepspeed_tpu.serving.router import ReplicaRouter

    deepspeed_tpu.comm.reset_topology()

    def mk():
        return deepspeed_tpu.init_serving(
            gpt2.build(CFG), config={"dtype": "fp32"}, slots=2,
            max_seq_len=256, block_size=8, prefill_chunk=16,
            host_blocks=32, swap_batch=8)

    rt = ReplicaRouter([mk(), mk()], policy="round_robin",
                       giant_context_tokens=64)
    rng = np.random.default_rng(29)
    out = rt.serve([
        Request(uid=0, prompt=rng.integers(0, CFG.vocab_size, 100),
                max_new_tokens=4),
        Request(uid=1, prompt=rng.integers(0, CFG.vocab_size, 20),
                max_new_tokens=4),
    ])
    assert len(out) == 2
    st = rt.stats()
    assert st["giant_context"] == 1
    assert rt.resolved_config()["giant_context_tokens"] == 64
    assert any(e["name"] == "giant_context"
               for e in rt.timeline.events())
    with pytest.raises(ValueError, match="giant_context_tokens"):
        ReplicaRouter([mk(), mk()], giant_context_tokens=-1)
