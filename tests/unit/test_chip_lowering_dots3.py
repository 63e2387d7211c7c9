"""ISSUE 61: Mosaic's own compile, for a described v5e (no chip), of what
``dots3-longnote-closed`` adds at the cell's shapes — the indexer's scoring
and selection at 64 index heads x 128 over 32,768 keys, the absorbed latent
read under the selection at 128 heads over ``[256, 640]`` blocks, the
windowed latent read at 64 heads over ``[128, 1152]`` ring blocks — and of
the cell's decode and prefill programs whole (dots3-note-prev at its
published widths, this chip's share), in ``test_chip_lowering.py``'s manner:
a block shape Mosaic refuses, or a kernel over its VMEM, fails here and not
on the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import decode_attention as da

#: the cell: 24 slots x 32,768; full layers 128 heads over a latent of 512 +
#: 64 in 640 lanes at blocks of 256, an index key of 128; sliding layers 64
#: heads over 1,024 + 64 in 1,152 lanes at blocks of 128 on a ring of 10
CELL = dict(slots=24, ctx=32768, topk=2048, window=513,
            full=dict(heads=128, width=640, rank=512, block=256, layers=2),
            index=dict(heads=64, width=128),
            sliding=dict(heads=64, width=1152, rank=1024, block=128,
                         layers=3, ring=10))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


ROWS = [(24, 1), (4, 128), (1, 512)]
IDS = ["decode", "prefill-4x128", "prefill-1x512"]


@pytest.mark.parametrize("rows,t", ROWS, ids=IDS)
def test_selection_kernels_compile_at_the_long_note_cells_shapes(rows, t,
                                                                 one_chip):
    """Scoring (``paged_index_scores``: 64 heads x 128 against blocks of 256
    keys, fewer blocks a tile and fewer queries a step than Keye's 16 heads
    x 64 over blocks of 32 take), selection (``paged_sparse_select`` over
    32,768 scores a query) and the read under the selection
    (``paged_sparse_latent_attn``), each with no temporary of its own beside
    the scores."""
    c, f, ix = CELL, CELL["full"], CELL["index"]
    nbper = c["ctx"] // f["block"]
    nb = 1 + c["slots"] * nbper

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    idx = sds((f["layers"], nb, 1, f["block"], ix["width"]), jnp.bfloat16)
    scores = jax.jit(lambda qi, wi, p, bt, last: da.paged_index_scores_pallas(
        qi, wi, p, bt, last, layer=1, interpret=False)).lower(
            sds((rows, ix["heads"], t, ix["width"]), jnp.bfloat16),
            sds((rows, t, ix["heads"]), jnp.float32), idx,
            sds((rows, nbper), jnp.int32), sds((rows, t), jnp.int32))
    assert 'kernel_name = "paged_index_scores"' in scores.as_text()
    assert scores.compile().memory_analysis().temp_size_in_bytes \
        <= 2 * rows * t * c["ctx"] * 4
    select = jax.jit(lambda s: da.paged_sparse_select_pallas(
        s, c["topk"], interpret=False)).lower(
            sds((rows, t, c["ctx"]), jnp.float32))
    assert 'kernel_name = "paged_sparse_select"' in select.as_text()
    assert select.compile().memory_analysis().temp_size_in_bytes < 1 << 20
    pool = sds((f["layers"], nb, 1, f["block"], f["width"]), jnp.bfloat16)
    read = jax.jit(lambda q, p, bt, s, th, sl, last:
                   da.paged_sparse_latent_attention_pallas(
                       q, p, bt, s, th, sl, last, rank=f["rank"], layer=1,
                       interpret=False)).lower(
        sds((rows, f["heads"], t, f["width"]), jnp.bfloat16), pool,
        sds((rows, nbper), jnp.int32), sds((rows, t, c["ctx"]), jnp.float32),
        sds((rows, t), jnp.float32), sds((rows, t), jnp.int32),
        sds((rows, t), jnp.int32))
    assert 'kernel_name = "paged_sparse_latent_attn"' in read.as_text()
    # the hits are reduced from the [rows, T, ctx] mask in XLA: its bytes
    assert read.compile().memory_analysis().temp_size_in_bytes \
        <= 2 * rows * t * c["ctx"] * 4 + (1 << 20)


@pytest.mark.parametrize("kind", ["full", "sliding"])
@pytest.mark.parametrize("rows,t", ROWS, ids=IDS)
def test_latent_walks_compile_at_the_long_note_cells_shapes(kind, rows, t,
                                                            one_chip):
    """The dense latent walk at 128 heads (the ``lax.cond``'s other branch:
    no row past ``index_topk``) and the windowed one at 64 heads over the
    ring's ``[128, 1152]`` blocks: the query tile follows the heads, the
    tile of blocks its VMEM budget."""
    c, k = CELL, CELL[kind]
    window = c["window"] if kind == "sliding" else 0
    nbper = k["ring"] if window else c["ctx"] // k["block"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((k["layers"], 1 + c["slots"] * nbper, 1, k["block"],
                k["width"]), jnp.bfloat16)
    lowered = jax.jit(lambda q, p, bt, pos, valid:
                      da.paged_latent_attention_pallas(
                          q, p, bt, pos, rank=k["rank"], layer=0, valid=valid,
                          window=window, interpret=False)).lower(
        sds((rows, k["heads"], t, k["width"]), jnp.bfloat16), pool,
        sds((rows, nbper), jnp.int32), sds((rows,), jnp.int32),
        sds((rows,), jnp.int32))
    assert da.latent_kernel_name(t, window) in lowered.as_text()
    # the queries into and the output out of the kernel's row order: theirs
    queries = rows * t * k["heads"] * (k["width"] + k["rank"]) * 2
    assert lowered.compile().memory_analysis().temp_size_in_bytes \
        <= queries + (1 << 20)
    tq, nt = da.latent_walk_shape(k["heads"], t, k["block"], k["width"], 2,
                                  nbper)
    assert tq * k["heads"] <= da._LATENT_QUERY_ROWS or t == 1
    assert 2 * nt * k["block"] * k["width"] * 2 \
        + tq * k["heads"] * nt * k["block"] * 6 <= da._LATENT_VMEM_BUDGET


@pytest.mark.limit(600)
def test_compiled_long_note_programs_fit_and_alias_every_leaf(
        one_chip, monkeypatch):
    """The cell's decode and two prefill programs compile for a described
    v5e with the new kernels in them, alias all three leaves of the cache
    (2.6 GB) and hold their temporaries inside what 16 GB leave beside 8.17
    GB of weights."""
    from chipbench.families import dots3 as family
    from deepspeed_tpu.moe import grouped_matmul
    from deepspeed_tpu.ops import paged_kv
    from deepspeed_tpu.utils import platform

    for mod in (platform, da):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
        monkeypatch.setattr(mod, "interpret_kernels", lambda: False)
    from deepspeed_tpu.ops import sparse_index_attention as sia
    monkeypatch.setattr(sia, "on_tpu", lambda: True)
    monkeypatch.setattr(grouped_matmul, "interpret_kernels", lambda: False)
    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    with open(os.path.join(root, "chipbench", "configs",
                           "dots3-note-prev.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    spec = family.build(config)
    assert spec.model_config.num_params() == family.num_params(config)
    fwd = spec.decode_hooks["forward_cached"]
    c, full, swa = CELL, CELL["full"], CELL["sliding"]
    nbper = c["ctx"] // full["block"]

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return sds(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            spec.init_fn(jax.random.PRNGKey(0)))))
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: paged_kv.pack_pool(spec.decode_hooks["init_cache"](
            1 + c["slots"] * nbper, full["block"], jnp.bfloat16,
            window_blocks=1 + c["slots"] * swa["ring"]))))
    assert {k: v.shape for k, v in pool.items()} == {
        "latent": (2, 3073, 1, 256, 640), "idx": (2, 3073, 1, 256, 128),
        "latw": (3, 241, 1, 128, 1152)}

    def tables(rows):
        return {"full": i32(rows, nbper), "window": i32(rows, swa["ring"])}

    def decode_step(params, cache, tokens, lengths, bt):
        logits, cache, rec = fwd(params, tokens[:, None], cache, 0,
                                 lengths=lengths, block_tables=bt,
                                 routing=True)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache, rec

    def prefill(params, cache, ids, bt, base, valid):
        logits, cache, rec = fwd(params, ids, cache, base, lengths=valid,
                                 block_tables=bt, routing=True)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache, rec

    slots = c["slots"]
    programs = {
        "decode": (decode_step, (params, pool, i32(slots), i32(slots),
                                 tables(slots)),
                   ("paged_sparse_latent_attn", "paged_window_latent_attn")),
        "prefill 4x128": (prefill, (params, pool, i32(4, 128), tables(4),
                                    i32(4), i32(4)),
                          ("paged_sparse_latent_attn",
                           "paged_window_latent_prefill")),
        "prefill 1x512": (prefill, (params, pool, i32(1, 512), tables(1),
                                    i32(1), i32(1)),
                          ("paged_sparse_latent_attn",
                           "paged_window_latent_prefill"))}
    pool_bytes = sum(int(np.prod(v.shape)) * 2 for v in pool.values())
    for name, (fn, args, kernels) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        text = compiled.as_text()
        for kernel in kernels + ("paged_index_scores", "paged_sparse_select",
                                 "moe_gmm"):
            assert kernel in text, (name, kernel)
        mem = compiled.memory_analysis()
        print(name, "temporaries", mem.temp_size_in_bytes / 1e6, "MB")
        assert mem.temp_size_in_bytes < 3 << 30, (name, mem)
        assert mem.alias_size_in_bytes >= pool_bytes, (name, mem)
