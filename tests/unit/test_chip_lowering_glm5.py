"""ISSUE 64: Mosaic's own compile, for a described v5e (no chip), of what
``glm5-agentloop-closed`` adds at the cell's shapes — the indexer's scoring
and selection and the absorbed latent read under the selection at a VERIFY
WINDOW (24 rows x 2 positions, 32 index heads x 128, 64 heads over ``[256,
640]`` blocks, 12,288 keys a row), other windows the ops take (4 as it is; 3
padded to 8) — and of the round's two programs and both prefill rungs whole
(GLM-5 at its published widths, this chip's share, the module's rows in the
pool), in ``test_chip_lowering_dots3.py``'s manner.  The kernels are the
ones every selecting model launches, under the names the trace keeps
(``paged_index_scores``, ``paged_sparse_select``, ``paged_sparse_latent_attn``:
``tests/chipbench/test_program_span_metrics.py`` states the rule)."""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import decode_attention as da
from deepspeed_tpu.ops import sparse_index_attention as sia

CELL = dict(slots=24, ctx=12288, topk=2048, heads=64, width=640, rank=512,
            block=256, layers=6, index_heads=32, index_width=128)
ROOT = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)


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


def _on_chip(monkeypatch):
    from deepspeed_tpu.moe import grouped_matmul
    from deepspeed_tpu.utils import platform

    for mod in (platform, da):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
        monkeypatch.setattr(mod, "interpret_kernels", lambda: False)
    monkeypatch.setattr(sia, "on_tpu", lambda: True)
    monkeypatch.setattr(grouped_matmul, "interpret_kernels", lambda: False)


def test_a_short_window_is_one_grid_step_a_row():
    """1, 2 and 4 positions as they are; a prefill chunk in steps of 8."""
    nbper = CELL["ctx"] // CELL["block"]
    for t, tq in ((1, 1), (2, 2), (4, 4), (8, 8), (128, 8), (512, 8)):
        assert da.sparse_latent_walk_shape(t, CELL["block"], nbper)[0] == tq
    # a short window lands twice a prefill tile's keys a loop iteration
    assert da.sparse_latent_walk_shape(2, 256, nbper)[1] == 4
    assert da.sparse_latent_walk_shape(512, 256, nbper)[1] == 2


@pytest.mark.parametrize("t", [2, 4])
def test_window_kernels_compile_at_the_agent_cells_shapes(t, one_chip):
    c = CELL
    rows, nbper = c["slots"], c["ctx"] // c["block"]
    nb = 1 + rows * nbper

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    idx = sds((c["layers"], nb, 1, c["block"], c["index_width"]),
              jnp.bfloat16)
    scores = jax.jit(lambda qi, wi, p, bt, last: da.paged_index_scores_pallas(
        qi, wi, p, bt, last, layer=5, interpret=False)).lower(
            sds((rows, c["index_heads"], t, c["index_width"]), jnp.bfloat16),
            sds((rows, t, c["index_heads"]), jnp.float32), idx,
            sds((rows, nbper), jnp.int32), sds((rows, t), jnp.int32))
    assert 'kernel_name = "paged_index_scores"' in scores.as_text()
    scores.compile()
    select = jax.jit(lambda s: da.paged_sparse_select_pallas(
        s, c["topk"], interpret=False)).lower(
            sds((rows, t, c["ctx"]), jnp.float32))
    assert 'kernel_name = "paged_sparse_select"' in select.as_text()
    select.compile()
    pool = sds((c["layers"], nb, 1, c["block"], c["width"]), jnp.bfloat16)
    read = jax.jit(lambda q, p, bt, s, th, sl, last:
                   da.paged_sparse_latent_attention_pallas(
                       q, p, bt, s, th, sl, last, rank=c["rank"], layer=5,
                       interpret=False)).lower(
        sds((rows, c["heads"], t, c["width"]), jnp.bfloat16), pool,
        sds((rows, nbper), jnp.int32), sds((rows, t, c["ctx"]), jnp.float32),
        sds((rows, t), jnp.float32), sds((rows, t), jnp.int32),
        sds((rows, t), jnp.int32))
    assert 'kernel_name = "paged_sparse_latent_attn"' in read.as_text()
    # the hits are reduced from the [rows, T, ctx] mask in XLA: its bytes
    assert read.compile().memory_analysis().temp_size_in_bytes \
        <= 2 * rows * t * c["ctx"] * 4 + (1 << 20)


def test_a_window_of_three_is_padded_to_the_prefill_tile(one_chip,
                                                         monkeypatch):
    """Mosaic slices a score slab by 4 rows: a window that is neither 1, 2,
    4 nor a multiple of 8 goes through the call padded with positions that
    see no key (``K = 1`` is the cell's; the ops assume no K)."""
    _on_chip(monkeypatch)
    c, t = CELL, 3
    rows, nbper = c["slots"], c["ctx"] // c["block"]
    nb = 1 + rows * nbper

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(lambda q, p, ip, qi, wi, bt, pos, valid:
                      sia.paged_sparse_latent_attention(
                          q, p, ip, qi, wi, bt, pos, rank=c["rank"],
                          topk=c["topk"], layer=2, valid=valid)).lower(
        sds((rows, c["heads"], t, c["width"]), jnp.bfloat16),
        sds((c["layers"], nb, 1, c["block"], c["width"]), jnp.bfloat16),
        sds((c["layers"], nb, 1, c["block"], c["index_width"]), jnp.bfloat16),
        sds((rows, c["index_heads"], t, c["index_width"]), jnp.bfloat16),
        sds((rows, t, c["index_heads"]), jnp.float32),
        sds((rows, nbper), jnp.int32), sds((rows,), jnp.int32),
        sds((rows,), jnp.int32))
    assert "paged_sparse_latent_attn" in lowered.compile().as_text()
    assert lowered.out_info[0].shape == (rows, c["heads"], t, c["rank"])


def test_no_pallas_call_site_of_the_ops_is_without_a_constant_name():
    """The rule ``test_program_span_metrics`` states (its own table of files
    is stale): every ``pl.pallas_call`` of the ops file this PR touches
    names its kernel with a string constant, no two alike — a name the
    trace's reduction keeps."""
    names = []
    tree = ast.parse(open(os.path.join(
        ROOT, "deepspeed_tpu", "ops", "decode_attention.py")).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and getattr(node.func, "attr", "") == "pallas_call":
            name = next((k.value for k in node.keywords if k.arg == "name"),
                        None)
            assert isinstance(name, ast.Constant) \
                and isinstance(name.value, str), node.lineno
            names.append(name.value)
    assert len(names) == len(set(names)) >= 12
    assert {"paged_index_scores", "paged_sparse_select",
            "paged_sparse_latent_attn"} <= set(names)
    assert all(n.startswith(("decode_attn", "paged_")) for n in names)


@pytest.mark.limit(900)
def test_compiled_round_and_prefill_programs_fit_and_alias_the_pool(
        one_chip, monkeypatch):
    """The round's two programs (the window's forward; the module) and both
    prefill rungs (trunk + module) compile for a described v5e with the
    kernels in them, alias both leaves of the pool (2.72 GB, the module's
    layer among them) and hold their temporaries inside what 16 GB leave
    beside 9.61 GB of weights."""
    from chipbench.families import glm_dsa as family
    from deepspeed_tpu.ops import paged_kv

    _on_chip(monkeypatch)
    with open(os.path.join(ROOT, "chipbench", "configs", "GLM-5.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    spec = family.build(config)
    assert spec.model_config.num_params() == family.num_params(config) \
        == 4802856704
    hooks = spec.decode_hooks
    fwd, dfwd = hooks["forward_cached"], hooks["self_draft"]["forward"]
    c = CELL
    nbper = c["ctx"] // c["block"]

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return sds(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            spec.init_fn(jax.random.PRNGKey(0)))))
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: paged_kv.pack_pool(hooks["init_cache"](
            1 + c["slots"] * nbper, c["block"], jnp.bfloat16,
            **hooks["self_draft"]["cache"]))))
    assert {k: v.shape for k, v in pool.items()} == {
        "latent": (6, 1153, 1, 256, 640), "idx": (6, 1153, 1, 256, 128)}

    def verify(params, cache, ids, bt, base, valid):
        logits, cache, rec, hidden = fwd(
            params, ids, cache, base, lengths=valid, block_tables=bt,
            all_positions=True, routing=True, hidden=True)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache, rec, hidden

    def draft(params, cache, hidden, after, bt, base, count):
        guess, cache, rec = dfwd(params, hidden, after, cache, base,
                                 lengths=count, block_tables=bt, routing=True)
        return jnp.argmax(guess, -1).astype(jnp.int32), cache, rec

    def prefill(params, cache, ids, bt, base, valid):
        logits, cache, rec, hidden = fwd(
            params, ids, cache, base, lengths=valid, block_tables=bt,
            routing=True, hidden=True)
        guess, cache, more = dfwd(params, hidden, jnp.roll(ids, -1, 1), cache,
                                  base, lengths=valid, block_tables=bt,
                                  at=valid - 1, routing=True)
        return jnp.argmax(logits, -1).astype(jnp.int32), \
            jnp.argmax(guess, -1).astype(jnp.int32), cache, rec, more

    slots = c["slots"]
    hidden = sds(jax.ShapeDtypeStruct((slots, 2, 6144), jnp.bfloat16))
    programs = {
        "verify": (verify, (params, pool, i32(slots, 2), i32(slots, nbper),
                            i32(slots), i32(slots))),
        "draft": (draft, (params, pool, hidden, i32(slots, 2),
                          i32(slots, nbper), i32(slots), i32(slots))),
        "prefill 4x128": (prefill, (params, pool, i32(4, 128), i32(4, nbper),
                                    i32(4), i32(4))),
        "prefill 1x512": (prefill, (params, pool, i32(1, 512), i32(1, nbper),
                                    i32(1), i32(1)))}
    pool_bytes = sum(int(np.prod(v.shape)) * 2 for v in pool.values())
    assert round(pool_bytes / 1e9, 2) == 2.72
    for name, (fn, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        text = compiled.as_text()
        for kernel in ("paged_sparse_latent_attn", "paged_index_scores",
                       "paged_sparse_select", "moe_gmm"):
            assert kernel in text, (name, kernel)
        mem = compiled.memory_analysis()
        print(name, "temporaries", mem.temp_size_in_bytes / 1e6, "MB")
        assert mem.temp_size_in_bytes < 2.5 * (1 << 30), (name, mem)
        assert mem.alias_size_in_bytes >= pool_bytes, (name, mem)
