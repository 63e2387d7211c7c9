"""The paged write against two oracles, bit for bit (ISSUE 41).

``_write_blocks`` merges a window's tokens into the touched blocks in the
view the pool stores them in (``paged_kv.pack_pool``: ``[R, W]`` a block,
token offset ``o`` at row ``o % R``, lane group ``o // R``).  A write into
the packed pool must therefore equal ``pack_pool`` of the same write into
the pool as ``init_cache`` built it — and both must equal a token-by-token
numpy write that knows nothing of blocks' views: ``array_equal``, no
tolerance.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import paged_kv
from deepspeed_tpu.ops import quantization as quant

LAYERS, HEADS, BLOCK, NBPER = 3, 2, 32, 8       # 256 positions a row
LAYER = 1
#: rows of every case: block-aligned, mid-block, two tokens before a
#: block's end, deep in the table — a T = 128 window from 77 crosses five
#: blocks, a T = 5 one from 30 two, a T = 1 one
POSITIONS = (0, 13, 30, 77)


def _tables(ring_width=0):
    """Row b owns blocks ``1 + b * NBPER ..`` (block 0 is the scratch)."""
    width = ring_width or NBPER
    b = len(POSITIONS)
    return 1 + np.arange(b * width, dtype=np.int32).reshape(b, width)


def _case(kind, t):
    """``(pos, tables, valid, ring)`` of a case."""
    pos = np.array(POSITIONS, np.int32)
    bt, valid, ring = _tables(), None, False
    if kind == "nvalid":
        # fewer real tokens than the window holds; a row with none at all
        valid = np.array([t, max(t - 3, 0), 0, (t + 1) // 2], np.int32)
    elif kind == "past_reach":
        # row 1 starts inside its last block and runs off the table, row 2
        # starts past it; row 3's table ends early (unset entries are the
        # scratch block 0): ITS tokens past entry 3 land in the scratch
        pos = np.array([0, NBPER * BLOCK - 2, NBPER * BLOCK + 40, 77],
                       np.int32)
        bt[3, 3:] = 0
    elif kind == "ring":
        # a window layer's ring of 6 entries, rows several laps in
        bt, ring = _tables(ring_width=6), True
        pos = pos + np.array([6, 7, 12, 25], np.int32) * BLOCK
    return pos, bt, valid, ring


def _token_write(pool, win, pos, bt, valid, ring):
    """The oracle: token i of row b lands at ``(LAYER, table[b, p // bs],
    :, p % bs)``, ``p = pos[b] + i`` — on the pool as ``init_cache`` built
    it, ``[L, NB, H, bs, ...]`` (payload or scale table)."""
    out = np.array(pool)
    win = np.asarray(win)
    bs, width = out.shape[3], bt.shape[1]
    for b in range(win.shape[0]):
        for i in range(win.shape[2] if valid is None else int(valid[b])):
            p = int(pos[b]) + i
            entry = p // bs
            if ring:
                entry %= width
            elif entry >= width:
                continue                       # past the table's reach
            out[LAYER, bt[b, entry], :, p % bs] = win[b, :, i]
    return out


def _random_pool(key, hd, dtype):
    nb = 1 + len(POSITIONS) * NBPER
    shape = (LAYERS, nb, HEADS, BLOCK, hd)
    if dtype == jnp.int8:
        return jax.random.randint(key, shape, -127, 128, jnp.int8)
    return jax.random.normal(key, shape, dtype)


def _same_blocks(got, want):
    """Every real block of every leaf, bit for bit.  The scratch block 0 is
    where rows whose tables end early meet, in any order: never read
    unmasked, not compared."""
    got, want = (jax.tree_util.tree_leaves(t) for t in (got, want))
    return len(got) == len(want) and all(
        a.shape == b.shape and a.dtype == b.dtype and
        np.array_equal(np.asarray(a)[:, 1:], np.asarray(b)[:, 1:])
        for a, b in zip(got, want))


@pytest.mark.parametrize("kind", ["mid_block", "nvalid", "past_reach",
                                  "ring", "kv8"])
@pytest.mark.parametrize("t", [1, 5, 128])
@pytest.mark.parametrize("hd", [128, 64, 32, 16],
                         ids=["g1", "g2", "g4", "g8"])
def test_a_write_into_the_packed_pool_is_the_packed_write(hd, t, kind):
    """``paged_cache_update`` on ``pack_pool(pool)`` == ``pack_pool`` of it
    on ``pool`` == the token-by-token write, for K and V (and, ``kv8``, an
    int8 record's codes and scale rows), every real block of the pool
    compared — so what a masked or out-of-reach token must NOT touch is held
    too."""
    g = paged_kv.lane_pack(BLOCK, hd)
    assert g == 128 // hd
    pos, bt, valid, ring = _case(kind, t)
    keys = jax.random.split(jax.random.PRNGKey(hd * 1000 + t), 6)
    b = len(POSITIONS)
    k = jax.random.normal(keys[0], (b, HEADS, t, hd), jnp.bfloat16)
    v = jax.random.normal(keys[1], (b, HEADS, t, hd), jnp.bfloat16)
    if kind == "kv8":
        pool = tuple(
            {"qp": _random_pool(kq, hd, jnp.int8),
             "ps": jnp.abs(jax.random.normal(
                 ks, (LAYERS, 1 + b * NBPER, HEADS, BLOCK),
                 paged_kv.SCALE_DTYPE))}
            for kq, ks in ((keys[2], keys[3]), (keys[4], keys[5])))
    else:
        pool = (_random_pool(keys[2], hd, jnp.bfloat16),
                _random_pool(keys[3], hd, jnp.bfloat16))

    def write(ck, cv):
        return paged_kv.paged_cache_update(ck, cv, k, v, pos, bt, valid,
                                           layer=LAYER, ring=ring)

    plain = jax.jit(write)(*pool)
    packed = jax.jit(write)(*paged_kv.pack_pool(pool))
    assert paged_kv.pool_payload(packed[0]).shape[-2:] == (BLOCK // g, 128)

    for leaf, win, got, got_packed in zip(pool, (k, v), plain, packed):
        if kind == "kv8":
            codes, scale = quant.quantize_kv(win, leaf["ps"].dtype)
            want = {"qp": _token_write(leaf["qp"], codes, pos, bt, valid,
                                       ring),
                    "ps": _token_write(leaf["ps"], scale, pos, bt, valid,
                                       ring)}
        else:
            want = _token_write(leaf, win, pos, bt, valid, ring)
        assert _same_blocks(got, want)
        assert _same_blocks(got_packed, paged_kv.pack_pool(want))
    if kind == "past_reach":
        # rows 1 and 2 ran off the table: nothing of theirs reached a block
        # of another row, and row 2 wrote nothing at all
        before, after = np.asarray(paged_kv.pool_payload(pool[0])), \
            np.asarray(paged_kv.pool_payload(plain[0]))
        assert np.array_equal(before[:, bt[2]], after[:, bt[2]])
        assert np.array_equal(before[:, bt[0, 5:]], after[:, bt[0, 5:]])


@pytest.mark.parametrize("kind", ["mid_block", "nvalid", "past_reach"])
@pytest.mark.parametrize("t", [1, 5, 128])
def test_a_one_head_64_wide_leaf_is_written_in_its_packed_view(t, kind):
    """``paged_window_update`` on the indexer's kind of leaf — one head, 64
    wide, two tokens a lane row — against the same two oracles."""
    pos, bt, valid, _ = _case(kind, t)
    b, width = len(POSITIONS), 64
    keys = jax.random.split(jax.random.PRNGKey(t), 2)
    leaf = jax.random.normal(
        keys[0], (LAYERS, 1 + b * NBPER, 1, BLOCK, width), jnp.bfloat16)
    win = jax.random.normal(keys[1], (b, 1, t, width), jnp.bfloat16)

    def write(leaf):
        return paged_kv.paged_window_update(leaf, win, pos, bt, valid,
                                            layer=LAYER)

    want = _token_write(leaf, win, pos, bt, valid, False)
    assert _same_blocks(jax.jit(write)(leaf), want)
    packed = jax.jit(write)(paged_kv.pack_pool(leaf))
    assert packed.shape[-2:] == (BLOCK // 2, 128)
    assert _same_blocks(packed, paged_kv.pack_pool(want))
