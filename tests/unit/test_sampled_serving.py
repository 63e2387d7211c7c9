"""Per-slot sampling through the serving engine (PR 20 tentpole).

End-to-end contracts on a tiny gpt2:
 - sampled streams are DETERMINISTIC: two fresh engines replay the same
   requests (same per-request seeds) token-identically — the sampler's
   PRNG is counter-based, keyed only by (request seed, emission index);
 - ``temperature=0`` requests through a sampling engine are bit-identical
   to a ``sampling=False`` engine AND to sequential ``generate`` (greedy
   is the zero row of the same program, not a separate lane);
 - the compile contract is unchanged: mixed greedy+sampled+constrained
   traces compile the same <= 2 / <= 3 programs (chunked / draft-spec),
   sentry-strict — sampling params ride as fixed-shape operands;
 - speculative decoding composes through the rejection verifier for both
   proposers (n-gram: 2 programs, draft model: 3), temp-0 rows staying
   exactly greedy;
 - constrained decoding (``logit_masks=True`` + ``JsonMaskBuilder``)
   emits valid JSON for EVERY request;
 - preemption/resume replays sampled streams token-exactly (the chaos
   crash lane is ``test_serving_faults.py``);
 - loud validation at the ctor and at ``submit``.
"""

import json

import numpy as np
import pytest

from deepspeed_tpu.inference.constrain import (JsonMaskBuilder,
                                               ascii_token_strings)
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2
from tiny import assert_sequential


_KW = dict(slots=4, max_seq_len=128, block_size=8, prefill_chunk=16,
           prefill_batch=2, debug_checks=True)


def _sampled_trace(cfg, n, seed=0, temperature=0.8, top_k=20, top_p=0.95,
                   plen=(5, 30), max_new=(6, 20), greedy_every=0):
    """n requests, all sampled unless ``greedy_every`` interleaves greedy
    rows (uid % greedy_every == 0)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        greedy = greedy_every and i % greedy_every == 0
        out.append(Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, int(rng.integers(*plen))),
            max_new_tokens=int(rng.integers(*max_new)),
            temperature=0.0 if greedy else temperature,
            top_k=0 if greedy else top_k,
            top_p=1.0 if greedy else top_p,
            seed=0 if greedy else int(rng.integers(1, 2 ** 31 - 1))))
    return out


# ------------------------------------------------------------ determinism
def test_sampled_streams_deterministic_and_two_programs(tiny_engine):
    engine, cfg = tiny_engine
    reqs = _sampled_trace(cfg, 6)
    a = ServingEngine(engine, **_KW)
    b = ServingEngine(engine, **_KW)
    res_a, res_b = a.serve(reqs), b.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(res_a[r.uid], res_b[r.uid],
                                      err_msg=f"uid {r.uid}")
        # sampled != greedy almost surely on at least one request
    want_greedy = {r.uid: engine.generate(
        r.prompt[None, :], max_new_tokens=r.max_new_tokens)[0]
        for r in reqs}
    assert any(not np.array_equal(res_a[r.uid], want_greedy[r.uid])
               for r in reqs), "sampling never deviated from greedy"
    assert a.compile_count == 1 + len(a._rungs), a.compiled_programs
    assert a.sentry.retraces_observed == 0
    st = a.stats()
    assert st["sampling"] is True
    assert st["sampled_requests"] == len(reqs)


def test_temp0_rows_bit_identical_to_greedy_engine(tiny_engine):
    engine, cfg = tiny_engine
    reqs = _sampled_trace(cfg, 5, seed=1, greedy_every=1)   # all greedy
    assert all(not r.sampled for r in reqs)
    on = ServingEngine(engine, **_KW)
    off = ServingEngine(engine, sampling=False, **_KW)
    res_on, res_off = on.serve(reqs), off.serve(reqs)
    assert_sequential(engine, reqs, res_on, res_off)
    assert on.stats()["sampled_requests"] == 0


# ----------------------------------------------------------- speculative
def test_spec_ngram_sampled_deterministic_two_programs(tiny_engine):
    engine, cfg = tiny_engine
    reqs = _sampled_trace(cfg, 6, seed=3, temperature=0.5, greedy_every=3)
    mk = lambda: ServingEngine(engine, spec_tokens=3, **_KW)  # noqa: E731
    a, b = mk(), mk()
    res_a, res_b = a.serve(reqs), b.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(res_a[r.uid], res_b[r.uid],
                                      err_msg=f"uid {r.uid}")
    assert_sequential(engine, [r for r in reqs if not r.sampled],
                      res_a)  # temp-0 rows stay greedy
    assert a.compile_count == 1 + len(a._rungs), a.compiled_programs
    st = a.stats()
    assert st["spec_rounds"] > 0 and 0.0 <= st["acceptance_rate"] <= 1.0
    assert st["spec_draft_rejected"] >= 0
    assert st["spec_draft_rejected"] == \
        st["drafted_tokens"] - st["accepted_tokens"]


def test_spec_draft_sampled_three_programs_and_temp0_parity(tiny_engine):
    engine, cfg = tiny_engine
    dcfg = gpt2.GPT2Config(vocab_size=cfg.vocab_size, max_seq_len=128,
                           num_layers=1, num_heads=2, hidden_size=32)
    mk = lambda: ServingEngine(engine, spec_tokens=3,  # noqa: E731
                               draft=gpt2.build(dcfg), **_KW)
    reqs = _sampled_trace(cfg, 5, seed=4, temperature=0.6, greedy_every=2)
    a, b = mk(), mk()
    res_a, res_b = a.serve(reqs), b.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(res_a[r.uid], res_b[r.uid],
                                      err_msg=f"uid {r.uid}")
    assert_sequential(engine, [r for r in reqs if not r.sampled],
                      res_a)
    assert a.compile_count == 2 + len(a._rungs), a.compiled_programs
    assert sorted({p[0] for p in a.compiled_programs}) == \
        ["draft", "prefill", "verify"]


def test_greedy_only_spec_engine_matches_plain_greedy(tiny_engine):
    """``sampling=False`` with ``spec_tokens``: the verify program takes the
    greedy prefix-matcher (no sampling operands), token-exact with plain
    greedy decode."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, spec_tokens=3, sampling=False, **_KW)
    reqs = _sampled_trace(cfg, 4, seed=6, greedy_every=1)   # all greedy
    res = srv.serve(reqs)
    assert_sequential(engine, reqs, res)
    assert srv.stats()["sampling"] is False
    assert sorted({p[0] for p in srv.compiled_programs}) == \
        ["prefill", "verify"]


# ------------------------------------------------------------ constrained
def _constrained_reqs(cfg, n, seed=0, temperature=0.7, max_new=24):
    rng = np.random.default_rng(seed)
    toks = ascii_token_strings(cfg.vocab_size)
    return toks, [Request(
        uid=i, prompt=rng.integers(0, cfg.vocab_size, 12),
        max_new_tokens=max_new,
        temperature=temperature, top_k=0, top_p=1.0,
        seed=int(rng.integers(1, 2 ** 31 - 1)),
        mask_builder=JsonMaskBuilder(toks, eos_token_id=0))
        for i in range(n)]


def _decode_json(toks, out, plen, eos=0):
    gen = [int(t) for t in out[plen:]]
    if eos in gen:
        gen = gen[: gen.index(eos)]
    return json.loads("".join(toks[t] for t in gen))


def test_constrained_lane_emits_valid_json_every_request(tiny_engine):
    engine, cfg = tiny_engine
    toks, reqs = _constrained_reqs(cfg, 4, seed=5)
    srv = ServingEngine(engine, logit_masks=True, **_KW)
    res = srv.serve(reqs, eos_token_id=0)
    for r in reqs:
        _decode_json(toks, res[r.uid], len(r.prompt))   # raises if invalid
    assert srv.compile_count == 1 + len(srv._rungs), srv.compiled_programs
    assert srv.stats()["logit_masks"] is True


def test_json_mask_bans_leading_zero_numbers():
    """JSON forbids leading zeros: ``0`` / ``-0`` are COMPLETE integers
    (``json.loads("01")`` raises), so after one the mask must offer the
    terminators/eos and never another digit — regression for the bench
    lane emitting ``019...`` at full scale."""
    toks = ascii_token_strings(128)
    tid = {s: i for i, s in enumerate(toks) if s}
    digits = [tid[d] for d in "0123456789"]

    m = JsonMaskBuilder(toks, eos_token_id=0).allowed([tid["0"]], 8)
    assert not m[digits].any() and m[0] and m.sum() == 1  # eos only

    m = JsonMaskBuilder(toks, eos_token_id=0).allowed(
        [tid["-"], tid["0"]], 8)
    assert not m[digits].any() and m[0]

    m = JsonMaskBuilder(toks, eos_token_id=0).allowed(
        [tid["["], tid["0"]], 8)
    assert not m[digits].any() and m[tid[","]] and m[tid["]"]]

    m = JsonMaskBuilder(toks, eos_token_id=0).allowed([tid["1"]], 8)
    assert m[digits].all()                 # non-zero lead still extends

    bad = JsonMaskBuilder(toks, eos_token_id=0)
    with pytest.raises(ValueError):        # a violating stream is loud
        bad.allowed([tid["0"], tid["1"]], 8)


def test_mixed_trace_keeps_compile_contract_sentry_strict(tiny_engine):
    """The zero-recompile acceptance gate: ONE engine serving greedy,
    sampled, and constrained requests in the same trace compiles the
    same 2 programs as a greedy-only trace — strict sentry, no silent
    retraces.  Same check on a speculative engine (still 2: prefill +
    verify)."""
    engine, cfg = tiny_engine
    toks, constrained = _constrained_reqs(cfg, 2, seed=6)
    mixed = _sampled_trace(cfg, 4, seed=7, greedy_every=2)
    for r in constrained:                    # disjoint uids
        r.uid += 100
    srv = ServingEngine(engine, logit_masks=True, **_KW)
    res = srv.serve(mixed + constrained, eos_token_id=0)
    for r in constrained:
        _decode_json(toks, res[r.uid], len(r.prompt))
    assert srv.compile_count == 1 + len(srv._rungs), srv.compiled_programs
    assert srv.sentry.retraces_observed == 0
    st = srv.stats()
    assert st["sampled_requests"] == len(mixed) - 2 + len(constrained)

    toks, constrained = _constrained_reqs(cfg, 2, seed=8)
    for r in constrained:
        r.uid += 100
    spec = ServingEngine(engine, spec_tokens=3, logit_masks=True, **_KW)
    res = spec.serve(mixed + constrained, eos_token_id=0)
    for r in constrained:
        _decode_json(toks, res[r.uid], len(r.prompt))
    assert spec.compile_count == 1 + len(spec._rungs), spec.compiled_programs
    assert spec.sentry.retraces_observed == 0


# ------------------------------------------------------- preempt / resume
def test_preemption_replays_sampled_streams_token_exact(tiny_engine):
    """A tight pool forces preempt -> resume mid-stream; the resumed
    sampled continuation must re-derive the exact keys from (seed,
    emitted count) and match an unpressured run token-for-token."""
    engine, cfg = tiny_engine
    rng = np.random.default_rng(9)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 17),
                    max_new_tokens=28, temperature=0.8, top_k=30,
                    top_p=0.9, seed=int(rng.integers(1, 2 ** 31 - 1)))
            for i in range(5)]
    roomy = ServingEngine(engine, **_KW)
    want = roomy.serve(reqs)
    tight = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                          prefill_chunk=32, prefill_batch=2, num_blocks=12,
                          debug_checks=True)
    got = tight.serve(reqs)
    assert tight.preempted > 0, tight.stats()
    for r in reqs:
        np.testing.assert_array_equal(got[r.uid], want[r.uid],
                                      err_msg=f"uid {r.uid}")


def test_preemption_replays_token_exact_through_the_tiled_search(
        tiny_engine, monkeypatch):
    """ISSUE 67: with the nucleus search's row tile resident (the line
    ``TILED_FROM`` put under this engine's width, so every program of both
    engines takes the kernel) a preempted request's replay through
    ``prefill``'s ``[2, vocab]`` emit draws what ``decode_step`` drew among
    its 4 or 3 rows: a row's threshold is its own wherever it sits."""
    from deepspeed_tpu.ops import sampling

    engine, cfg = tiny_engine
    monkeypatch.setattr(sampling, "TILED_FROM", cfg.vocab_size)
    rng = np.random.default_rng(67)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 17),
                    max_new_tokens=28, temperature=0.7, top_p=0.9,
                    seed=int(rng.integers(1, 2 ** 31 - 1)))
            for i in range(5)]
    roomy = ServingEngine(engine, **_KW)
    want = roomy.serve(reqs)
    tight = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                          prefill_chunk=32, prefill_batch=2, num_blocks=12,
                          debug_checks=True)
    got = tight.serve(reqs)
    assert tight.preempted > 0, tight.stats()
    for srv in (roomy, tight):
        assert srv.stats()["sampler"] == dict.fromkeys(
            ("prefill", "decode"), "bitwise_search_tiled")
    for r in reqs:
        np.testing.assert_array_equal(got[r.uid], want[r.uid],
                                      err_msg=f"uid {r.uid}")


def test_preemption_replays_sampled_spec_token_exact(tiny_engine):
    engine, cfg = tiny_engine
    rng = np.random.default_rng(10)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 17),
                    max_new_tokens=28, temperature=0.6,
                    seed=int(rng.integers(1, 2 ** 31 - 1)))
            for i in range(5)]
    roomy = ServingEngine(engine, spec_tokens=3, **_KW)
    want = roomy.serve(reqs)
    tight = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                          prefill_chunk=32, prefill_batch=2, num_blocks=12,
                          spec_tokens=3, debug_checks=True)
    got = tight.serve(reqs)
    assert tight.preempted > 0, tight.stats()
    for r in reqs:
        np.testing.assert_array_equal(got[r.uid], want[r.uid],
                                      err_msg=f"uid {r.uid}")


# -------------------------------------------------------------- validation
def test_request_and_engine_validation(tiny_engine):
    engine, cfg = tiny_engine
    prompt = np.arange(5)
    with pytest.raises(ValueError, match="temperature"):
        Request(uid=0, prompt=prompt, max_new_tokens=4, temperature=-0.5)
    with pytest.raises(ValueError, match="top_k"):
        Request(uid=0, prompt=prompt, max_new_tokens=4, top_k=-1)
    with pytest.raises(ValueError, match="top_p"):
        Request(uid=0, prompt=prompt, max_new_tokens=4, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        Request(uid=0, prompt=prompt, max_new_tokens=4, top_p=1.5)
    # seed lands in a np.uint32 slot array at admission: out-of-range
    # values must be refused at construction, not crash step() later
    with pytest.raises(ValueError, match="seed"):
        Request(uid=0, prompt=prompt, max_new_tokens=4, seed=-1)
    with pytest.raises(ValueError, match="seed"):
        Request(uid=0, prompt=prompt, max_new_tokens=4, seed=2 ** 32)
    Request(uid=0, prompt=prompt, max_new_tokens=4, seed=2 ** 32 - 1)

    with pytest.raises(ValueError, match="sampling"):
        ServingEngine(engine, logit_masks=True, sampling=False, **_KW)

    off = ServingEngine(engine, sampling=False, **_KW)
    with pytest.raises(ValueError, match="sampling=False"):
        off.submit(Request(uid=1, prompt=prompt, max_new_tokens=4,
                           temperature=0.7, seed=3))
    masked = Request(uid=2, prompt=prompt, max_new_tokens=4,
                     mask_builder=JsonMaskBuilder(
                         ascii_token_strings(cfg.vocab_size), 0))
    unmasked_engine = ServingEngine(engine, **_KW)
    with pytest.raises(ValueError, match="logit_masks"):
        unmasked_engine.submit(masked)
