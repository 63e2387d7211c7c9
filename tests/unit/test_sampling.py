"""On-device sampling primitives (``ops/sampling.py``) and the
distribution-exact rejection verifier's accept walker (``spec.
rejection_accept``) — the PR 20 unit layer under the serving tests in
``test_sampled_serving.py``.

Covers: the temperature=0 exact-one-hot contract (greedy is the zero row
of the SAME filtered-logprobs program), top-k/top-p filtering on known
distributions (ties-in kth threshold, nucleus boundary), logit-mask
application, the counter-based PRNG key schedule (pure function of
(seed, emission position, salt) — the crash re-homing determinism
contract), empirical total-variation checks of the categorical draws,
and the delta-form rejection identity: accept the proposed token with
probability ``p_target(d)``, else draw from the renormalized residual —
marginal EXACTLY ``p_target`` for ANY proposer, no draft probabilities
needed.
"""

import hashlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.spec import rejection_accept
from deepspeed_tpu.ops import sampling as S


def _np(x):
    return np.asarray(x)


# ------------------------------------------------------ filtered_logprobs
def test_temp0_rows_are_exact_onehot():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(5, 17)).astype(np.float32))
    temps = jnp.zeros(5, jnp.float32)
    greedy, lp = S.filtered_logprobs(logits, temps,
                                     jnp.zeros(5, jnp.int32),
                                     jnp.ones(5, jnp.float32))
    np.testing.assert_array_equal(_np(greedy), _np(logits).argmax(-1))
    lp = _np(lp)
    for i, g in enumerate(_np(greedy)):
        assert lp[i, g] == 0.0                       # exact, not approx
        row = np.delete(lp[i], g)
        assert np.all(np.isneginf(row))


def test_topk_threshold_keeps_ties():
    logits = jnp.asarray([[4.0, 3.0, 3.0, 1.0, 0.0]])
    temps = jnp.ones(1, jnp.float32)
    _, lp = S.filtered_logprobs(logits, temps, jnp.asarray([2]),
                                jnp.ones(1, jnp.float32))
    lp = _np(lp)[0]
    # kth-largest (k=2) is 3.0; BOTH ties at the threshold stay in
    assert np.isfinite(lp[[0, 1, 2]]).all()
    assert np.isneginf(lp[[3, 4]]).all()
    # kept mass renormalizes to 1
    assert np.isclose(np.exp(lp[np.isfinite(lp)]).sum(), 1.0, atol=1e-6)


def test_topp_nucleus_boundary():
    probs = np.array([0.5, 0.3, 0.15, 0.05], np.float32)
    logits = jnp.asarray(np.log(probs)[None, :])
    temps = jnp.ones(1, jnp.float32)
    for p, want in ((0.7, [0, 1]), (0.85, [0, 1, 2]), (1.0, [0, 1, 2, 3])):
        _, lp = S.filtered_logprobs(logits, temps, jnp.zeros(1, jnp.int32),
                                    jnp.asarray([p], jnp.float32))
        kept = np.flatnonzero(np.isfinite(_np(lp)[0]))
        assert kept.tolist() == want, (p, kept)


def test_mask_applies_before_filtering_and_empty_row_is_inert():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(2, 9)).astype(np.float32))
    masks = np.zeros((2, 9), bool)
    masks[0, [2, 5]] = True                 # row 0: constrained to {2, 5}
    # row 1 all-False = the unconstrained-slot sentinel: treated unmasked
    temps = jnp.zeros(2, jnp.float32)
    greedy, lp = S.filtered_logprobs(logits, temps,
                                     jnp.zeros(2, jnp.int32),
                                     jnp.ones(2, jnp.float32),
                                     jnp.asarray(masks))
    assert int(greedy[0]) in (2, 5)
    assert int(greedy[0]) == (2 if logits[0, 2] >= logits[0, 5] else 5)
    assert int(greedy[1]) == int(_np(logits)[1].argmax())
    lp0 = _np(lp)[0]
    assert np.isneginf(np.delete(lp0, [int(greedy[0])])).all()


# ----------------------------------- the thresholds against a sorted reference
BAND = 1e-6      # |mass strictly above a token - top_p| under which a row is
                 # "at the boundary": float32 summation order may decide it


def _sorted_reference(logits, temps, top_k, top_p, masks=None):
    """The sorted formulation ``filtered_logprobs`` had until PR 33, in
    NumPy: the k-th largest read off a full descending sort, the nucleus
    threshold off a sorted exclusive cumsum — summed in float64.  The
    probabilities the cumsum runs over are the float32 softmax the
    function itself takes (so ties are the same ties).  Returns the kept
    sets at ``top_p`` and at ``top_p -/+ 2 BAND``, the float64 log-probs,
    and each row's distance from the boundary."""
    logits = np.asarray(logits, np.float32)
    rows, vocab = logits.shape
    if masks is not None:
        ok = masks.any(-1, keepdims=True)
        logits = np.where(np.where(ok, masks, True), logits, -np.inf)
    t = np.asarray(temps, np.float32)[:, None]
    scaled = (logits / np.maximum(t, np.float32(1e-6))).astype(np.float32)
    srt = np.sort(scaled, axis=-1)[:, ::-1]
    k = np.asarray(top_k, np.int64)
    kidx = np.clip(np.where(k > 0, k, vocab) - 1, 0, vocab - 1)
    keep = scaled >= np.take_along_axis(srt, kidx[:, None], axis=-1)
    probs = np.asarray(jax.nn.softmax(
        jnp.where(jnp.asarray(keep), jnp.asarray(scaled), -jnp.inf),
        axis=-1)).astype(np.float64)
    psort = np.sort(probs, axis=-1)[:, ::-1]
    before = np.cumsum(psort, axis=-1) - psort
    # mass strictly above each entry's VALUE: ties share their first's
    first = np.concatenate([np.ones((rows, 1), bool),
                            psort[:, 1:] != psort[:, :-1]], axis=-1)
    above = np.maximum.accumulate(np.where(first, before, 0.0), axis=-1)
    p = np.asarray(top_p, np.float64)[:, None]

    def kept(p):
        thr = np.min(np.where(above < p, psort, np.inf), axis=-1,
                     keepdims=True)
        return keep & (probs >= thr)

    sets = [kept(np.where(p >= 1, np.inf, q)) for q in
            (p, p - 2 * BAND, p + 2 * BAND)]
    z = np.where(sets[0], scaled.astype(np.float64), -np.inf)
    z = z - z.max(-1, keepdims=True)
    lp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    edge = np.abs(np.concatenate(
        [above, psort.sum(-1, keepdims=True)], axis=-1) - p).min(-1)
    return sets, lp, np.where(p[:, 0] >= 1, np.inf, edge)


def _peaked(rng, rows, vocab):
    """A nucleus of 3 tokens: three logits far above a flat rest."""
    x = rng.normal(size=(rows, vocab)).astype(np.float32) * 0.1
    for r in range(rows):
        x[r, rng.choice(vocab, 3, replace=False)] += (12.0, 11.5, 11.0)
    return x


def _flat(rng, rows, vocab, std=0.1):
    """The benchmark cells' regime: near-uniform logits, a nucleus of
    ~90 % of the vocabulary at T 0.7 / top-p 0.9."""
    return rng.normal(size=(rows, vocab)).astype(np.float32) * std


def _knob_case(top_k, top_p):
    def build(rng):
        x = _flat(rng, 3, 64, 2.0)
        return x, np.full(3, 0.7), np.full(3, top_k), np.full(3, top_p), None
    return build


def _regime_case(make, vocab):
    def build(rng):
        return (make(rng, 3, vocab), np.full(3, 0.7), np.zeros(3, int),
                np.full(3, 0.9), None)
    return build


def _all_equal(rng):
    return (np.full((3, 64), 1.25, np.float32), np.full(3, 0.7),
            np.asarray([0, 7, 0]), np.asarray([0.9, 0.9, 0.1]), None)


def _topk_ties(rng):
    x = np.tile(np.asarray([4.0, 3.0, 3.0, 3.0, 1.0, 0.0, -1.0, 3.0],
                           np.float32), (3, 1))
    return x, np.ones(3), np.asarray([2, 3, 5]), np.ones(3), None


def _nucleus_ties(rng):
    pr = np.asarray([0.5, 0.2, 0.2, 0.05, 0.05], np.float32)
    x = np.tile(np.log(pr), (3, 1))
    return x, np.ones(3), np.zeros(3, int), np.asarray([0.6, 0.45, 0.92]), \
        None


def _mixed_rows(rng):
    """Greedy, sampled-unfiltered, top-k, top-p and both, in one batch."""
    x = np.concatenate([_flat(rng, 3, 257, 2.5), _peaked(rng, 3, 257)])
    return (x, np.asarray([0.0, 1.0, 0.7, 0.7, 1.3, 0.0]),
            np.asarray([5, 0, 7, 0, 20, 0]),
            np.asarray([0.5, 1.0, 1.0, 0.9, 0.8, 1.0]), None)


def _masked(rng):
    """Logit masks (one row all-False = unconstrained), ``-inf`` and
    ``-0.0`` logits inside the kept range."""
    x = _flat(rng, 4, 64, 1.5)
    x[:, 3] = -np.inf
    x[:, 5], x[:, 6], x[:, 9] = -0.0, 0.0, -0.0
    masks = rng.random((4, 64)) < 0.5
    masks[:, [5, 6, 9]] = True
    masks[1] = False
    masks[3] = True
    return (x, np.asarray([0.7, 0.7, 1.0, 0.0]), np.asarray([0, 9, 4, 0]),
            np.asarray([0.9, 0.9, 1.0, 1.0]), masks)


SEARCH_CASES = {
    **{f"peaked-vocab{v}": _regime_case(_peaked, v)
       for v in (17, 64, 50272, 151936)},
    **{f"flat-vocab{v}": _regime_case(_flat, v)
       for v in (17, 64, 50272, 151936)},
    **{f"top_k{k}-top_p{p}": _knob_case(k, p)
       for k in (0, 1, 7, 64) for p in (0.1, 0.9, 1.0)},
    "all-equal": _all_equal, "ties-at-top-k": _topk_ties,
    "ties-at-nucleus": _nucleus_ties, "mixed-rows": _mixed_rows,
    "masks-inf-negzero": _masked,
}


#: where ``TILED_FROM`` is put to send every width of a test down one path
PATHS = {"plain": 1 << 62, "tiled": 1}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("case", sorted(SEARCH_CASES))
def test_searched_thresholds_keep_the_sorted_references_set(case, path,
                                                            monkeypatch):
    """ISSUE 33: the bitwise searches keep exactly the set the sorted
    formulation keeps, and give its log-probs.  A row whose boundary mass
    lies within ``BAND`` of ``top_p`` (named in ``at_boundary``) is held
    to the band instead: its set lies between the reference's sets at
    ``top_p -/+ 2 BAND``.  ISSUE 67: so with the nucleus search's row tile
    resident (``tiled``: the kernel interpreted) as with the plain loop."""
    monkeypatch.setattr(S, "TILED_FROM", PATHS[path])
    logits, temps, top_k, top_p, masks = SEARCH_CASES[case](
        np.random.default_rng(zlib.crc32(case.encode())))
    temps = np.asarray(temps, np.float32)
    greedy, lp = S.filtered_logprobs(
        jnp.asarray(logits), jnp.asarray(temps),
        jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
        None if masks is None else jnp.asarray(masks))
    lp = _np(lp)
    (want, low, high), want_lp, edge = _sorted_reference(
        logits, temps, top_k, top_p, masks)
    sampled = np.flatnonzero(temps > 0)
    at_boundary = [int(r) for r in sampled if edge[r] < BAND]
    assert len(at_boundary) < max(len(sampled), 1), at_boundary
    for r in sampled:
        got = np.isfinite(lp[r])
        if r in at_boundary:
            assert (low[r] <= got).all() and (got <= high[r]).all(), r
            continue
        np.testing.assert_array_equal(got, want[r], err_msg=f"row {r}")
        np.testing.assert_allclose(lp[r][got], want_lp[r][got], atol=2e-5)
    # greedy rows: the exact one-hot at the masked argmax
    for r in np.flatnonzero(temps == 0):
        assert lp[r, int(greedy[r])] == 0.0
        assert np.isneginf(np.delete(lp[r], int(greedy[r]))).all()


def test_searches_give_the_sorted_values_bit_for_bit():
    """The two searches alone against a sort: the k-th largest of rows
    with ``-inf``, ``-0.0``, ties and negative values, for every k; the
    nucleus threshold an entry of the row."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(4, 50)).astype(np.float32)
    x[0, :7], x[1, 3], x[2, 4:9], x[3, 11] = -np.inf, -0.0, 0.5, 0.0
    x[3, 12] = -0.0
    srt = np.sort(x, axis=-1)[:, ::-1]
    for k in (1, 2, 7, 43, 44, 50):
        got = _np(S._kth_largest(jnp.asarray(x),
                                 jnp.full((4, 1), k, jnp.int32)))[:, 0]
        np.testing.assert_array_equal(got, srt[:, k - 1])
    pr = rng.dirichlet(np.ones(50), size=4).astype(np.float32)
    for p in (0.05, 0.5, 0.97):
        thr = _np(S._nucleus_threshold(
            jnp.asarray(pr), jnp.full((4, 1), p, jnp.float32)))[:, 0]
        for r in range(4):
            assert thr[r] in pr[r]
            assert pr[r][pr[r] >= thr[r]].sum(dtype=np.float64) >= p - 1e-6
            assert pr[r][pr[r] > thr[r]].sum(dtype=np.float64) < p + 1e-6
    # no upper set reaches the mass: 0, which keeps everything
    thr = S._nucleus_threshold(jnp.asarray(pr * 0.5),
                               jnp.full((4, 1), 0.9, jnp.float32))
    assert (_np(thr) == 0.0).all()


# ------------------------------------------------- the tile kept resident
def _case_probs(case):
    """The probabilities and nucleus masses ``filtered_logprobs`` hands its
    nucleus search for a ``SEARCH_CASES`` entry (top-k left out)."""
    logits, temps, _, top_p, masks = SEARCH_CASES[case](
        np.random.default_rng(zlib.crc32(case.encode())))
    logits = jnp.asarray(logits, jnp.float32)
    if masks is not None:
        ok = jnp.any(masks, axis=-1, keepdims=True)
        logits = jnp.where(jnp.where(ok, masks, True), logits, -jnp.inf)
    t = jnp.maximum(jnp.asarray(temps, jnp.float32)[:, None], 1e-6)
    return jax.nn.softmax(logits / t, axis=-1), \
        jnp.asarray(top_p, jnp.float32)[:, None]


@pytest.mark.parametrize("case", sorted(
    c for c in SEARCH_CASES if not c.startswith("top_k")))
def test_tiled_search_finds_the_plain_loops_threshold(case):
    """ISSUE 67: the kernel whose passes read a resident row tile gives the
    plain loop's threshold on every regime — rows no multiple of a tile,
    widths no multiple of a lane, ties at the nucleus, all-equal rows,
    greedy / sampled / ``top_p == 1`` rows in one batch, masks.  Where the
    two differ, the larger threshold's upper set has a mass within ``BAND``
    of ``top_p``: one order of summation reached it and the other did not
    (one row of ``flat-vocab151936``, 152 k terms of 6e-6)."""
    probs, p = _case_probs(case)
    want = _np(S._nucleus_threshold(probs, p))[:, 0]
    got = _np(S._nucleus_threshold_tiled(probs, p))[:, 0]
    differ = np.flatnonzero(got != want)
    assert len(differ) < len(want), differ
    for r in differ:
        row = _np(probs)[r].astype(np.float64)
        upper = row[row >= max(got[r], want[r])].sum()
        assert abs(upper - float(p[r, 0])) < BAND, (r, upper)
    assert len(differ) == (1 if case == "flat-vocab151936" else 0)


@pytest.mark.parametrize("tile,unroll", [(8, 8), (16, 8), (8, 3)])
def test_a_rows_threshold_is_its_own_wherever_it_sits(tile, unroll):
    """A row's threshold is the same alone (padded to a tile), among other
    rows at any place of any tile, and beside rows of zeros or a one-hot:
    the replay of a preempted request through ``prefill``'s ``[4, vocab]``
    emit meets what ``decode_step`` found among 128 rows."""
    rng = np.random.default_rng(67)
    vocab = 1000
    row = rng.dirichlet(np.full(vocab, 0.3)).astype(np.float32)
    p = np.float32(0.9)
    search = lambda probs, ps: _np(S._nucleus_threshold_tiled(
        jnp.asarray(probs), jnp.asarray(ps), tile=tile, unroll=unroll))
    alone = search(row[None], np.full((1, 1), p))[0, 0]
    assert alone in row
    others = rng.dirichlet(np.ones(vocab), size=2 * tile + 3) \
        .astype(np.float32)
    others[1] = 0.0
    others[2] = np.eye(vocab, dtype=np.float32)[5]
    ps = rng.uniform(0.1, 1.0, size=(len(others), 1)).astype(np.float32)
    for at in (0, 3, tile - 1, tile, 2 * tile + 2):
        batch, goal = others.copy(), ps.copy()
        batch[at], goal[at] = row, p
        got = search(batch, goal)
        assert got[at, 0] == alone, at
        keep = np.arange(len(others)) != at
        np.testing.assert_array_equal(got[keep], search(others, ps)[keep])


@pytest.mark.parametrize("search", ["_nucleus_threshold",
                                    "_nucleus_threshold_tiled"])
def test_nucleus_search_gives_an_entry_of_the_row(search):
    """``test_searches_give_the_sorted_values_bit_for_bit``'s nucleus half
    for either place the passes read from."""
    search = getattr(S, search)
    rng = np.random.default_rng(5)
    pr = rng.dirichlet(np.ones(50), size=4).astype(np.float32)
    for p in (0.05, 0.5, 0.97):
        thr = _np(search(jnp.asarray(pr),
                         jnp.full((4, 1), p, jnp.float32)))[:, 0]
        for r in range(4):
            assert thr[r] in pr[r]
            assert pr[r][pr[r] >= thr[r]].sum(dtype=np.float64) >= p - 1e-6
            assert pr[r][pr[r] > thr[r]].sum(dtype=np.float64) < p + 1e-6
    thr = search(jnp.asarray(pr * 0.5), jnp.full((4, 1), 0.9, jnp.float32))
    assert (_np(thr) == 0.0).all()


def test_tiled_top_k_search_gives_the_sorted_value_bit_for_bit():
    """``test_searches_give_the_sorted_values_bit_for_bit``'s top-k half
    through the same tile loop: ``-inf``, ``-0.0``, ties and negative
    values, every k, a per-row k, a width of 50 padded to a lane."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(4, 50)).astype(np.float32)
    x[0, :7], x[1, 3], x[2, 4:9], x[3, 11] = -np.inf, -0.0, 0.5, 0.0
    x[3, 12] = -0.0
    srt = np.sort(x, axis=-1)[:, ::-1]
    for k in (1, 2, 7, 43, 44, 50):
        got = _np(S._kth_largest_tiled(
            jnp.asarray(x), jnp.full((4, 1), k, jnp.int32)))[:, 0]
        np.testing.assert_array_equal(got, srt[:, k - 1])
    ks = np.asarray([[1], [50], [9], [44]], np.int32)
    np.testing.assert_array_equal(
        _np(S._kth_largest_tiled(jnp.asarray(x), jnp.asarray(ks))),
        np.take_along_axis(srt, ks - 1, axis=-1))


def test_the_width_alone_says_where_the_passes_read():
    assert S.thresholds(50304) == "bitwise_search"
    assert S.thresholds(100352) == S.thresholds(262272) \
        == "bitwise_search_tiled"
    assert 50304 < S.TILED_FROM <= 100352


#: sha256 of ``filtered_logprobs``' lowered text at the chat cell's and
#: OLMoE's decode shapes, taken on the parent commit (9d1f9c7): under
#: ``TILED_FROM`` entries a row the sampler is the parent's program
PARENT_FILTERED_LOGPROBS = {
    (24, 50272):
    "d04211d1cda5cc01911c8592228787e28b443e0516fa261a0b02c4a902e0e2d3",
    (64, 50304):
    "356a87ceea9f879edbe1f19d39a176535169c1bd5f997b2d0057b0956fa11d31"}


@pytest.mark.parametrize("rows,vocab", sorted(PARENT_FILTERED_LOGPROBS))
def test_a_narrow_vocabularys_sampler_is_the_parents_program(rows, vocab):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = jax.jit(S.filtered_logprobs).lower(
        sds((rows, vocab), jnp.bfloat16), sds((rows,), jnp.float32),
        sds((rows,), jnp.int32), sds((rows,), jnp.float32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_FILTERED_LOGPROBS[rows, vocab]


# -------------------------------------------------------- key schedule
def test_keys_are_pure_functions_of_seed_count_salt():
    seeds = jnp.asarray([7, 7, 9], jnp.uint32)
    counts = jnp.asarray([0, 3, 3], jnp.int32)
    a = _np(S.slot_keys(seeds, counts, S.SALT_TOKEN))
    b = _np(S.slot_keys(seeds, counts, S.SALT_TOKEN))
    np.testing.assert_array_equal(a, b)             # pure
    assert not np.array_equal(a[0], a[1])           # count matters
    assert not np.array_equal(a[1], a[2])           # seed matters
    c = _np(S.slot_keys(seeds, counts, S.SALT_RESIDUAL))
    assert not np.array_equal(a, c)                 # salt streams disjoint
    # grid keys ARE slot keys at offset emission counts — the fused
    # while_loop and a step-at-a-time replay draw identical streams
    g = _np(S.grid_keys(seeds, counts, S.SALT_TOKEN, 4))
    for i in range(4):
        np.testing.assert_array_equal(
            g[:, i], _np(S.slot_keys(seeds, counts + i, S.SALT_TOKEN)))


def _tv(counts, probs):
    freq = counts / counts.sum()
    return 0.5 * np.abs(freq - probs).sum()


def test_categorical_draws_match_distribution():
    probs = np.array([0.45, 0.25, 0.15, 0.1, 0.05], np.float32)
    n = 4000
    lp = jnp.asarray(np.tile(np.log(probs), (n, 1)))
    keys = S.slot_keys(jnp.full(n, 7, jnp.uint32),
                       jnp.arange(n, dtype=jnp.int32), S.SALT_TOKEN)
    draws = _np(S.sample_tokens(lp, keys))
    counts = np.bincount(draws, minlength=5).astype(float)
    assert _tv(counts, probs) < 0.05, counts


def test_delta_rejection_marginal_is_target_distribution():
    """The verifier identity, adversarial case: a proposer that ALWAYS
    proposes the same token.  accept w.p. p_target(d); reject -> draw
    from the d-zeroed renormalized residual.  The marginal must still be
    exactly p_target (here: empirically, TV < 0.05 at n=4000)."""
    probs = np.array([0.4, 0.3, 0.2, 0.1], np.float32)
    d = 3                                   # propose the LEAST likely token
    n = 4000
    lp = jnp.asarray(np.tile(np.log(probs), (n, 1)))
    drafts = jnp.full((n,), d, jnp.int32)
    seeds = jnp.full(n, 11, jnp.uint32)
    counts = jnp.arange(n, dtype=jnp.int32)
    u = _np(S.accept_uniforms(S.slot_keys(seeds, counts, S.SALT_ACCEPT)))
    p_d = _np(S.token_probs(lp, drafts))
    accept = u < p_d
    resid_lp = S.residual_logits(lp, drafts)
    rkeys = S.slot_keys(seeds, counts, S.SALT_RESIDUAL)
    resid_draw = _np(S.sample_tokens(resid_lp, rkeys))
    final = np.where(accept, d, resid_draw)
    # rejected rows never re-emit the proposed token
    assert not np.any(resid_draw[~accept] == d)
    counts_f = np.bincount(final, minlength=4).astype(float)
    assert _tv(counts_f, probs) < 0.05, counts_f
    # acceptance rate ~ p_target(d)
    assert abs(accept.mean() - probs[d]) < 0.03


def test_residual_logits_masks_draft_and_dead_row_falls_back():
    # normal row: the rejected draft goes to -inf, survivors untouched
    lp = jnp.asarray(np.log(np.array([[0.5, 0.3, 0.2]], np.float32)))
    out = _np(S.residual_logits(lp, jnp.asarray([1])))
    assert np.isneginf(out[0, 1])
    np.testing.assert_allclose(out[0, [0, 2]], _np(lp)[0, [0, 2]])
    # one-hot row whose only token IS the draft: nothing survives, so
    # the helper emits the argmax one-hot instead of an all--inf row
    # (the lane is unreachable — the accept prob was exactly 1 — but it
    # must stay NaN-free inside the traced program)
    onehot = jnp.asarray([[0.0, -np.inf, -np.inf]], jnp.float32)
    out = _np(S.residual_logits(onehot, jnp.asarray([0])))
    assert out[0, 0] == 0.0 and np.isneginf(out[0, 1:]).all()


# ------------------------------------------------------ rejection_accept
def test_rejection_accept_walker_prefix_and_rejection_stop():
    # window [pending, d1..d3]; drafts 1..2 accepted, d3 rejected
    window = [10, 11, 12, 13]
    accept = [True, True, False]
    plain = [21, 22, 23, 24]
    resid = [31, 32, 33]
    emitted, accepted, finished = rejection_accept(
        window, accept, plain, resid, 3, None, 100)
    # 2 accepted drafts + the RESIDUAL draw at the rejection position
    assert emitted == [11, 12, 33] and accepted == 2 and not finished


def test_rejection_accept_all_accepted_gets_bonus_and_cap():
    window = [1, 2, 3, 4]
    plain = [9, 9, 55, 77]
    resid = [41, 42, 43]
    emitted, accepted, _ = rejection_accept(
        window, [True, True, True], plain, resid, 3, None, 100)
    assert emitted == [2, 3, 4, 77] and accepted == 3   # bonus plain draw
    # draft-model cap K-1: position K's plain draw replaces the K-th
    # draft (its KV was never written in the draft cache)
    emitted, accepted, _ = rejection_accept(
        window, [True, True, True], plain, resid, 2, None, 100)
    assert emitted == [2, 3, 55] and accepted == 2


def test_rejection_accept_cap_stop_ignores_unconsumed_verdict():
    """REGRESSION: a walk stopped by the accept cap (draft-model K-1,
    constrained 0) must emit the unconditional PLAIN target draw even
    when the verdict at the stop position happens to be False — that
    verdict was never consumed, and conditioning on it (the old
    device-side ``where(accept, plain, resid)`` blend) yields marginal
    ``p(x)(1 + q)`` / ``q^2`` instead of the target distribution."""
    window = [1, 2, 3, 4]
    plain = [50, 51, 52, 53]
    resid = [60, 61, 62]
    # draft-model cap 2: accept[2] is False but the walk stopped at the
    # cap, not on the verdict -> plain[2], never resid[2]
    emitted, accepted, _ = rejection_accept(
        window, [True, True, False], plain, resid, 2, None, 100)
    assert emitted == [2, 3, 52] and accepted == 2
    # constrained cap 0: every round is a cap stop at position 0
    emitted, accepted, _ = rejection_accept(
        window, [False, False, False], plain, resid, 0, None, 100)
    assert emitted == [50] and accepted == 0


def test_rejection_accept_eos_and_budget_truncate():
    window = [1, 7, 8, 9]
    accept = [True, True, True]
    plain = [0, 0, 0, 5]
    resid = [1, 1, 1]
    emitted, accepted, finished = rejection_accept(
        window, accept, plain, resid, 3, 8, 100)
    assert emitted == [7, 8] and finished           # truncated AT eos
    emitted, accepted, finished = rejection_accept(
        window, accept, plain, resid, 3, None, 2)
    assert emitted == [7, 8] and finished           # budget
    with pytest.raises(ValueError):
        rejection_accept(window, accept, plain, resid, 3, None, 0)
    with pytest.raises(ValueError):
        rejection_accept(window, accept, plain[:-1], resid, 3, None, 4)
    with pytest.raises(ValueError):
        rejection_accept(window, accept, plain, resid[:-1], 3, None, 4)
    with pytest.raises(ValueError):
        rejection_accept(window, accept[:-1], plain, resid, 3, None, 4)


def test_rejection_accept_immediate_reject_still_progresses():
    emitted, accepted, finished = rejection_accept(
        [5, 1, 2], [False, False], [40, 41, 42], [45, 46], 2, None, 100)
    assert emitted == [45] and accepted == 0 and not finished
