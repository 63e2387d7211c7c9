"""Speculative decoding for the paged serving engine: accept/rollback
units, proposer units, greedy token parity, and the compile contract.

Tier-1 (fast) CPU-sim coverage:
 - ``spec.greedy_accept`` ragged acceptance arithmetic: longest matching
   prefix + correction, eos INSIDE an accepted window, budget truncation,
   and the draft-model K-1 acceptance cap.
 - ``spec.NGramProposer`` prompt-lookup drafting (longest match first,
   most recent occurrence, fallback).
 - ``ServingEngine(spec_tokens=K)`` end-to-end: token parity with the
   non-speculative chunked path AND sequential ``generate`` across
   families (gpt2 + the newly paged bloom in tier-1; llama/opt slow),
   with both proposers (n-gram and a small same-family draft model).
 - The <= 3 compiled-programs contract: prefill + verify (n-gram), plus
   the draft rollout (draft model) — stable across serve calls and new
   request shapes.
 - Constructor validation: clear errors naming the missing hook / bad
   configuration combinations.

The Pallas K+1 verify-attention kernel's interpret-mode twin lives in
``test_decode_attention.py`` (slow lane).
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.inference.spec import NGramProposer, greedy_accept
from deepspeed_tpu.models import gpt2
from tiny import assert_sequential


# -------------------------------------------------------------- greedy_accept
def test_greedy_accept_longest_prefix_plus_correction():
    # window [pending, d1..d4]; target scores: d1, d2 match, d3 diverges
    window = [10, 11, 12, 13, 14]
    scored = [11, 12, 99, 7, 8]            # scored[2]=99 != d3=13
    emitted, accepted, finished = greedy_accept(window, scored, 4, None, 100)
    assert emitted == [11, 12, 99]         # 2 accepted drafts + correction
    assert accepted == 2 and not finished


def test_greedy_accept_no_match_still_progresses():
    emitted, accepted, finished = greedy_accept(
        [5, 1, 2], [7, 9, 9], 2, None, 100)
    assert emitted == [7] and accepted == 0 and not finished


def test_greedy_accept_full_match_and_draft_cap():
    window = [1, 2, 3, 4]
    scored = [2, 3, 4, 55]                 # every draft matches
    emitted, accepted, _ = greedy_accept(window, scored, 3, None, 100)
    # all K drafts + the target's continuation after the last one
    assert emitted == [2, 3, 4, 55] and accepted == 3
    # draft-model cap K-1: the K-th draft becomes the "correction" token,
    # acceptance stops one earlier so the draft cache stays
    # position-aligned (its K-th KV entry was never written)
    emitted, accepted, _ = greedy_accept(window, scored, 2, None, 100)
    assert emitted == [2, 3, 4] and accepted == 2


def test_greedy_accept_eos_inside_accepted_window():
    window = [1, 7, 8, 9]
    scored = [7, 8, 9, 5]                  # all accepted; 8 is eos
    emitted, accepted, finished = greedy_accept(window, scored, 3, 8, 100)
    assert emitted == [7, 8]               # truncated AT the eos
    assert finished


def test_greedy_accept_budget_truncation():
    window = [1, 7, 8, 9]
    scored = [7, 8, 9, 5]
    emitted, accepted, finished = greedy_accept(window, scored, 3, None, 2)
    assert emitted == [7, 8] and finished
    with pytest.raises(ValueError):
        greedy_accept(window, scored, 3, None, 0)
    with pytest.raises(ValueError):
        greedy_accept(window, scored[:-1], 3, None, 4)  # length mismatch


# -------------------------------------------------------------- NGramProposer
def test_ngram_proposer_prefers_longest_then_most_recent():
    p = NGramProposer(k=3, max_n=2, min_n=1)
    # tail 2-gram (7, 8) occurred earlier, followed by 5, 6
    ctx = [7, 8, 5, 6, 1, 7, 8]
    np.testing.assert_array_equal(p.propose(ctx), [5, 6, 1])
    # two occurrences of the tail: the most recent one wins
    ctx = [7, 8, 1, 0, 7, 8, 2, 3, 7, 8]
    np.testing.assert_array_equal(p.propose(ctx), [2, 3, 7])


def test_ngram_proposer_backoff_and_fallback():
    p = NGramProposer(k=2, max_n=3, min_n=1)
    # no 3/2-gram match, 1-gram (4) matched -> continuation [9, 4]
    np.testing.assert_array_equal(p.propose([4, 9, 4]), [9, 4])
    # nothing matches: repeat the final token
    np.testing.assert_array_equal(p.propose([1, 2, 3]), [3, 3])
    np.testing.assert_array_equal(p.propose([5]), [5, 5])
    with pytest.raises(ValueError):
        NGramProposer(k=0)
    with pytest.raises(ValueError):
        NGramProposer(k=2, max_n=1, min_n=2)


# --------------------------------------------------------------- end-to-end
def _trace(cfg, n, seed=0, plen=(5, 30), max_new=(6, 24)):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(*plen))),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


def test_spec_ngram_matches_plain_and_sequential(tiny_engine):
    """Acceptance: speculative (n-gram) outputs are token-identical to the
    non-speculative chunked path and to sequential generate, and the new
    stats fire."""
    engine, cfg = tiny_engine
    reqs = _trace(cfg, 6)
    plain = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                          prefill_chunk=16, prefill_batch=2,
                          debug_checks=True)
    spec = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                         prefill_chunk=16, prefill_batch=2, spec_tokens=4,
                         debug_checks=True)
    res_p = plain.serve(reqs)
    res_s = spec.serve(reqs)
    assert_sequential(engine, reqs, res_p, res_s)
    st = spec.stats()
    assert st["speculative"] == "ngram" and st["spec_tokens"] == 4
    assert st["spec_rounds"] > 0
    # every round drafts K tokens per participating decode slot
    assert st["drafted_tokens"] >= 4 * st["spec_rounds"]
    assert 0.0 <= st["acceptance_rate"] <= 1.0
    assert st["accepted_tokens"] <= st["drafted_tokens"]
    # speculative rounds replace single-token decode steps entirely
    assert st["decode_steps"] == 0
    # per-request latency percentiles (recorded for every finished request)
    assert st["requests_finished"] == len(reqs)
    assert st["ttft_p50_s"] > 0 and st["ttft_p95_s"] >= st["ttft_p50_s"]
    assert st["tpot_p50_s"] >= 0 and st["tpot_p95_s"] >= st["tpot_p50_s"]


def test_spec_draft_model_matches_sequential(tiny_engine):
    """A small same-family draft model proposes; greedy parity holds at
    whatever acceptance rate the draft earns, and the trace compiles
    exactly 3 programs (fused prefill + draft rollout + verify)."""
    engine, cfg = tiny_engine
    dcfg = gpt2.GPT2Config(vocab_size=cfg.vocab_size, max_seq_len=128,
                           num_layers=1, num_heads=2, hidden_size=32)
    spec = ServingEngine(engine, slots=3, max_seq_len=128, block_size=8,
                         prefill_chunk=16, prefill_batch=2, spec_tokens=3,
                         draft=gpt2.build(dcfg), debug_checks=True)
    reqs = _trace(cfg, 5, seed=1)
    res = spec.serve(reqs)
    assert_sequential(engine, reqs, res)
    assert spec.compile_count == 2 + len(spec._rungs), spec.compiled_programs
    kinds = sorted({p[0] for p in spec.compiled_programs})
    assert kinds == ["draft", "prefill", "verify"]
    assert spec.stats()["speculative"].startswith("draft:")


@pytest.mark.parametrize("proposer", ["ngram", "draft"])
def test_a_draft_model_or_ngram_round_keeps_its_fence(tiny_engine, proposer):
    """With no audit to settle them (``debug_checks`` off) these runners
    still take a call's results before the next is planned: the n-gram
    lookup is host work on the tokens, a draft model's rollout starts from
    them.  ``stats()["lookahead"]`` says so by name — a self-drafting
    engine's rounds alone ride the one call of lookahead
    (``tests/unit/test_glm5_serving.py``)."""
    engine, cfg = tiny_engine
    how = {}
    if proposer == "draft":
        dcfg = gpt2.GPT2Config(vocab_size=cfg.vocab_size, max_seq_len=128,
                               num_layers=1, num_heads=2, hidden_size=32)
        how["draft"] = gpt2.build(dcfg)
    spec = ServingEngine(engine, slots=3, max_seq_len=128, block_size=8,
                         prefill_chunk=16, prefill_batch=2, spec_tokens=3,
                         **how)
    reqs = _trace(cfg, 5, seed=6)
    assert_sequential(engine, reqs, spec.serve(reqs))
    look = spec.stats()["lookahead"]
    assert set(look["early"]) == {"speculative"}
    assert look["early"]["speculative"] == look["calls"] > 0
    assert look["ahead"] == 0
    spec.close()


def test_spec_eos_inside_window_end_to_end(tiny_engine):
    """eos emitted mid-window truncates the accepted run exactly where
    sequential generate stops (back-fill semantics included)."""
    engine, cfg = tiny_engine
    reqs = _trace(cfg, 4, seed=2, max_new=(6, 16))
    probe = engine.generate(reqs[0].prompt[None, :], max_new_tokens=6)
    eos = int(probe[0, len(reqs[0].prompt) + 3])   # mid-stream token as eos
    spec = ServingEngine(engine, slots=3, max_seq_len=128, block_size=8,
                         prefill_chunk=16, prefill_batch=2, spec_tokens=4,
                         debug_checks=True)
    res = spec.serve(reqs, eos_token_id=eos)
    assert_sequential(engine, reqs, res, eos_token_id=eos)


def test_spec_compile_contract_holds_across_traces(tiny_engine):
    """Acceptance: a full speculative trace compiles <= 3 programs —
    n-gram mode needs exactly 2 (prefill + verify), and new request shapes
    in a second serve call add none."""
    engine, cfg = tiny_engine
    spec = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                         prefill_chunk=16, prefill_batch=2, spec_tokens=4,
                         debug_checks=True)
    spec.serve(_trace(cfg, 6, seed=3))
    assert spec.compile_count == 1 + len(spec._rungs), spec.compiled_programs
    assert sorted({p[0] for p in spec.compiled_programs}) == \
        ["prefill", "verify"]
    spec.serve(_trace(cfg, 4, seed=4, plen=(30, 60), max_new=(2, 30)))
    assert spec.compile_count == 1 + len(spec._rungs), spec.compiled_programs
    assert spec.compile_count <= 2 + len(spec._rungs)
    # no silent retraces inside the jitted fns either: the sentry counts
    # actual Python-body traces against the 2-program budget (and, with
    # debug_checks on above, would have raised at trace time)
    assert spec.sentry.traces == 1 + len(spec._rungs), spec.sentry.report()
    assert spec.sentry.retraces_observed == 0


def test_spec_preemption_pressure_keeps_parity(tiny_engine):
    """Speculative block demand (K+1-token windows) under an oversubscribed
    pool: preemption + recompute still yields exact greedy outputs."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=32, prefill_batch=2, num_blocks=12,
                        spec_tokens=4, debug_checks=True)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 17),
                    max_new_tokens=28) for i in range(5)]
    res = srv.serve(reqs)
    assert srv.preempted > 0, srv.stats()
    assert_sequential(engine, reqs, res)


def test_spec_parity_bloom_family():
    """The newly ported bloom family (ALiBi, paged lengths/block_tables)
    serves under the engine — plain chunked AND speculative."""
    deepspeed_tpu.comm.reset_topology()
    from deepspeed_tpu.models import bloom

    cfg = bloom.BloomConfig.tiny(max_seq_len=64)
    engine = deepspeed_tpu.init_inference(
        bloom.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}})
    rng = np.random.default_rng(6)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(4, 20))),
                    max_new_tokens=int(rng.integers(3, 10)))
            for i in range(4)]
    spec = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                         prefill_chunk=16, prefill_batch=2, spec_tokens=3,
                         debug_checks=True)
    res = spec.serve(reqs)
    assert_sequential(engine, reqs, res)
    assert spec.compile_count == 1 + len(spec._rungs)


@pytest.mark.slow  # extra engine builds — gpt2/bloom cover tier-1
@pytest.mark.parametrize("family", ["llama", "opt"])
def test_spec_parity_other_families(family):
    """Per-row rope offsets (llama) / offset learned positions (opt) hold
    through the K+1 verify window."""
    deepspeed_tpu.comm.reset_topology()
    if family == "llama":
        from deepspeed_tpu.models import llama as m

        cfg = m.LlamaConfig.tiny()
    else:
        from deepspeed_tpu.models import opt as m

        cfg = m.OPTConfig.tiny()
    engine = deepspeed_tpu.init_inference(
        m.build(cfg), config={"dtype": "fp32",
                              "tensor_parallel": {"tp_size": 1}})
    spec = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                         prefill_chunk=16, prefill_batch=2, spec_tokens=3)
    rng = np.random.default_rng(7)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(4, 16))),
                    max_new_tokens=int(rng.integers(3, 10)))
            for i in range(4)]
    res = spec.serve(reqs)
    assert_sequential(engine, reqs, res)


# ---------------------------------------------------------------- validation
def test_ctor_validation_names_the_problem(tiny_engine):
    engine, cfg = tiny_engine
    with pytest.raises(ValueError, match="spec_tokens"):
        ServingEngine(engine, draft=object())   # draft without spec_tokens
    with pytest.raises(ValueError, match="spec_tokens"):
        ServingEngine(engine, spec_tokens=-1)

    deepspeed_tpu.comm.reset_topology()
    from deepspeed_tpu.models import gptj

    legacy = deepspeed_tpu.init_inference(
        gptj.build(gptj.GPTJConfig.tiny()),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}})
    # pre-lengths model: the error names the missing hook up front
    with pytest.raises(ValueError, match="supports_lengths"):
        ServingEngine(legacy)
    with pytest.raises(ValueError, match="supports_lengths"):
        ServingEngine(legacy, spec_tokens=4)


def test_ctor_validation_rejects_mismatched_draft_vocab(tiny_engine):
    engine, cfg = tiny_engine
    dcfg = gpt2.GPT2Config(vocab_size=cfg.vocab_size + 1, max_seq_len=128,
                           num_layers=1, num_heads=2, hidden_size=32)
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(engine, spec_tokens=3, draft=gpt2.build(dcfg))


def test_plain_serving_latency_stats(tiny_engine):
    """TTFT/TPOT percentiles are recorded for the non-speculative path
    too (the satellite metric — not tied to speculation)."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2)
    srv.serve(_trace(cfg, 3, seed=8))
    st = srv.stats()
    assert st["requests_finished"] == 3
    assert st["ttft_p50_s"] > 0 and st["tpot_p95_s"] >= 0
    assert len(srv._latencies) == 3 and \
        all(m["new_tokens"] >= 1 for m in srv._latencies)
