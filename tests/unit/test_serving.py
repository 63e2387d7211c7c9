"""Continuous-batching serving layer (inference/serving.py) + the generate
satellites that ride with it.

Deterministic CPU tests: scheduler admission/free ordering, no starvation,
request validation, and per-request token parity with sequential
``generate`` for greedy decoding.  The ragged
``lengths`` decode-attention contract is covered here on the XLA reference
path; the Pallas-interpret twin lives in test_decode_attention.py (slow).
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.engine import _fill_after_eos
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2
from tiny import assert_sequential


def _tiny_engine(max_seq_len=128):
    deepspeed_tpu.comm.reset_topology()
    cfg = gpt2.GPT2Config.tiny(max_seq_len=max_seq_len)
    return deepspeed_tpu.init_inference(
        gpt2.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}}), cfg


def _trace(cfg, n, seed=0, lo=3, hi=30, max_new=(1, 12)):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(lo, hi))),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


# --------------------------------------------------------------- _fill_after_eos
def test_fill_after_eos_backfill_semantics():
    """HF back-fill: everything strictly after the first eos in the GENERATED
    region becomes eos; the eos itself, the prompt (even if it contains eos),
    and rows without eos are untouched."""
    eos = 9
    out = np.array([
        [1, 9, 2, 3, 9, 5, 6],    # eos in prompt ignored; first gen eos at 4
        [1, 2, 3, 4, 5, 6, 7],    # no eos: untouched
        [1, 2, 9, 8, 7, 6, 5],    # eos at gen position 0
        [1, 2, 3, 4, 5, 6, 9],    # eos at the last position: nothing after
    ], np.int32)
    got = _fill_after_eos(out.copy(), 2, eos)
    want = np.array([
        [1, 9, 2, 3, 9, 9, 9],
        [1, 2, 3, 4, 5, 6, 7],
        [1, 2, 9, 9, 9, 9, 9],
        [1, 2, 3, 4, 5, 6, 9],
    ], np.int32)
    np.testing.assert_array_equal(got, want)


def test_fill_after_eos_matches_rowwise_loop():
    """Pin the vectorized expression against the per-row np.where original."""
    def rowwise(out, prompt_len, eos):
        for row in range(out.shape[0]):
            hits = np.where(out[row, prompt_len:] == eos)[0]
            if hits.size:
                out[row, prompt_len + hits[0] + 1:] = eos
        return out

    rng = np.random.default_rng(0)
    for _ in range(50):
        out = rng.integers(0, 5, (4, 12)).astype(np.int32)
        np.testing.assert_array_equal(
            _fill_after_eos(out.copy(), 4, 2), rowwise(out.copy(), 4, 2))
    # degenerate: no generated region
    out = rng.integers(0, 5, (2, 6)).astype(np.int32)
    np.testing.assert_array_equal(_fill_after_eos(out.copy(), 6, 2), out)


# -------------------------------------------------------------------- scheduler
def test_serving_matches_sequential_generate_greedy(tiny_engine):
    """Acceptance: per-request outputs token-identical to sequential
    ``generate`` (greedy), across mixed prompt lengths and budgets."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=4, max_seq_len=128,
                        prefill_chunk=16, prefill_batch=2)
    reqs = _trace(cfg, 10)
    res = srv.serve(reqs)
    assert_sequential(engine, reqs, res)


def test_serving_matches_sequential_generate_with_eos(tiny_engine):
    """Same parity when sequences stop early at eos (slot frees early and
    the output is eos back-filled like generate's)."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=3, max_seq_len=128,
                        prefill_chunk=16, prefill_batch=2)
    reqs = _trace(cfg, 6, seed=1, max_new=(4, 10))
    # pick an eos that actually occurs: the first generated token of req 0
    probe = engine.generate(reqs[0].prompt[None, :], max_new_tokens=1)
    eos = int(probe[0, len(reqs[0].prompt)])
    res = srv.serve(reqs, eos_token_id=eos)
    assert_sequential(engine, reqs, res, eos_token_id=eos)


@pytest.mark.parametrize("family", ["llama", "opt"])
def test_serving_parity_other_families(family):
    """The lengths contract holds beyond gpt2: rope offsets (llama) and
    offset learned positions (opt) decode per-slot correctly."""
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
    srv = ServingEngine(engine, slots=3, max_seq_len=64,
                        prefill_chunk=16, prefill_batch=2)
    reqs = _trace(cfg, 5, seed=2, lo=3, hi=14, max_new=(2, 8))
    res = srv.serve(reqs)
    assert_sequential(engine, reqs, res)


def test_admission_fifo_and_immediate_slot_reuse(tiny_engine):
    """Slots: strict FIFO admission (no starvation), and a freed slot is
    reacquired by the next waiting request."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=128,
                        prefill_chunk=8, prefill_batch=2)
    rng = np.random.default_rng(5)
    # short budgets so slots churn: 6 requests through 2 slots
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 4),
                    max_new_tokens=2 + (i % 3)) for i in range(6)]
    log = []
    res = srv.serve(reqs, admission_log=log)
    assert set(res) == set(range(6))                    # nothing starved
    assert [uid for uid, _ in log] == list(range(6))    # FIFO admission
    slots_seen = {s for _, s in log}
    assert slots_seen == {0, 1}                         # both slots reused
    # with 2 slots and 6 requests, each slot must have served >= 2 requests
    for s in slots_seen:
        assert sum(1 for _, slot in log if slot == s) >= 2


def test_serving_rejects_oversized_and_invalid(tiny_engine):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=64,
                        prefill_chunk=16, prefill_batch=2)
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        srv.serve([Request(uid=0, prompt=np.arange(16), max_new_tokens=60)])
    with pytest.raises(ValueError, match="duplicate"):
        srv.serve([Request(uid=0, prompt=np.arange(4), max_new_tokens=2),
                   Request(uid=0, prompt=np.arange(4), max_new_tokens=2)])
    with pytest.raises(ValueError, match="empty prompt"):
        Request(uid=1, prompt=np.zeros(0), max_new_tokens=2)
    with pytest.raises(ValueError, match="supports_lengths"):
        from deepspeed_tpu.models import gptj

        deepspeed_tpu.comm.reset_topology()
        legacy = deepspeed_tpu.init_inference(
            gptj.build(gptj.GPTJConfig.tiny()),
            config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}})
        ServingEngine(legacy)


# ------------------------------------------------------------ removed options
#: options ``ServingEngine`` / ``init_serving`` took until PR 30: the bucket
#: ladder's two and a verifier name that selected nothing
_REMOVED = {"prompt_buckets": (8, 16), "chunked_prefill": True,
            "spec_verifier": "rejection"}


@pytest.mark.parametrize("entry", ["ServingEngine", "init_serving"])
@pytest.mark.parametrize("option", sorted(_REMOVED))
def test_removed_option_is_an_unknown_keyword(option, entry):
    """No alias, no shim: the constructor refuses a removed option by name
    like any unknown keyword, and ``init_serving`` has no such parameter
    and forwards none (what lands in its ``**kwargs`` is engine config:
    ``init_inference`` treats an unknown key there as it always has)."""
    import inspect

    if entry == "ServingEngine":
        with pytest.raises(TypeError, match=option):
            ServingEngine(object(), slots=2, **{option: _REMOVED[option]})
    else:
        assert option not in inspect.signature(
            deepspeed_tpu.init_serving).parameters
        assert option not in inspect.getsource(deepspeed_tpu.init_serving)


# ------------------------------------------------- generate early-exit satellite
def test_generate_early_exit_matches_full_loop():
    """The eos-keyed while_loop generate == fori_loop generate + back-fill,
    on both the KV-cache and full-recompute paths."""
    engine, cfg = _tiny_engine(max_seq_len=256)
    rng = np.random.default_rng(6)
    ids = rng.integers(0, cfg.vocab_size, (2, 7)).astype(np.int32)
    base = engine.generate(ids, max_new_tokens=8)           # no-eos fori path
    eos = int(base[0, 9])                                    # occurs mid-run
    want = _fill_after_eos(base.copy(), 7, eos)
    got = engine.generate(ids, max_new_tokens=8, eos_token_id=eos)
    np.testing.assert_array_equal(got, want)

    model = gpt2.build(cfg)
    model.decode_hooks = None                                # recompute path
    deepspeed_tpu.comm.reset_topology()
    engine2 = deepspeed_tpu.init_inference(
        model, config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
        params=engine.params)
    got2 = engine2.generate(ids, max_new_tokens=8, eos_token_id=eos)
    np.testing.assert_array_equal(got2, want)


def test_generate_fns_lru_moves_hit_to_end():
    """Satellite: a cache hit refreshes the entry, so hot shapes survive
    eviction pressure (true LRU, not insertion-order FIFO)."""
    engine, cfg = _tiny_engine()                # fresh: its LRU's order is read
    ids = np.ones((1, 4), np.int32)
    engine.generate(ids, max_new_tokens=2)      # key A
    engine.generate(ids, max_new_tokens=3)      # key B
    key_a = (1, 4, 2, None, None)
    assert list(engine._generate_fns)[0] == key_a
    engine.generate(ids, max_new_tokens=2)      # hit A: moves to end
    assert list(engine._generate_fns)[-1] == key_a
    fn_a = engine._generate_fns[key_a]
    engine.generate(ids, max_new_tokens=2)
    assert engine._generate_fns[key_a] is fn_a  # hit reused, not rebuilt
