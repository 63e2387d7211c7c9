"""Cross-lower every Pallas kernel of ``chip_smoke.py``'s two phases for
platform ``tpu`` from the CPU (``jax.export`` with ``interpret=False``), at
the shapes the smoke and ROADMAP 1.1's first cells use.

Interpret mode hides Pallas->Mosaic lowering refusals (block shapes that
violate the (8, 128) tiling rule, unsupported ops): the kv8 paged kernels
were refused outright until their scale operand became tile-legal.  This is
the first of two stages — Mosaic's own compile (VMEM budget, layouts) only
happens on the chip, where ``chip_smoke.py`` checks it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import decode_attention as da
from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.ops import quantized_matmul as qmm

#: (name, query heads == KV heads, head_dim, context): MHA families
ATTN_SHAPES = [("opt-1.3b", 32, 64, 2048), ("opt-6.7b", 32, 128, 2048),
               ("gpt2-125m", 12, 64, 1024), ("olmoe-1b-7b", 16, 128, 1024)]
SLOTS, BLOCK = 8, 32          # init_serving defaults


def _lower_tpu(fn, *args):
    """Mosaic-lowered StableHLO text of ``fn`` for platform tpu."""
    exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    text = exp.mlir_module()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"
    return text


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _pool(nb, h, hd, kv8):
    if not kv8:
        return _sds((nb, h, BLOCK, hd), jnp.bfloat16)
    return {"qp": _sds((nb, h, BLOCK, hd), jnp.int8),
            "ps": _sds((nb, h, BLOCK), jnp.bfloat16)}


@pytest.mark.parametrize("kv8", [False, True], ids=["bf16", "kv8"])
@pytest.mark.parametrize("name,h,hd,ctx", ATTN_SHAPES)
def test_paged_decode_and_verify_lower(name, h, hd, ctx, kv8):
    nbper = ctx // BLOCK
    pool = _pool(1 + SLOTS * nbper, h, hd, kv8)
    bt = _sds((SLOTS, nbper), jnp.int32)
    pos = _sds((SLOTS,), jnp.int32)
    for t, kernel in ((1, da.paged_decode_attention_pallas),
                      (4, da.paged_verify_attention_pallas)):
        q = _sds((SLOTS, h, t, hd), jnp.bfloat16)
        _lower_tpu(lambda q, k, v, bt, pos, kernel=kernel: kernel(
            q, k, v, bt, pos, interpret=False), q, pool, pool, bt, pos)


#: (cell, slots, KV heads, head_dim, max_seq_len, layers): the serving cells
CELL_SHAPES = [("opt13b-chat-closed", 24, 32, 64, 1024, 24),
               ("opt13b-longprompt-closed", 8, 32, 64, 2048, 24),
               ("olmoe-decode-closed", 64, 16, 128, 1024, 8)]


def _cell_operands(slots, h, hd, ctx, layers, kv8, t, sharding=None):
    """(q, pool, block table, positions) of a cell: the stacked pool
    lane-packed as the engine holds it, ``[L, NB, H, bs/g, g*hd]``."""
    from deepspeed_tpu.ops import paged_kv

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    nbper = ctx // BLOCK
    nb = 1 + slots * nbper
    g = paged_kv.lane_pack(BLOCK, hd)
    packed = (layers, nb, h, BLOCK // g, g * hd)
    pool = sds(packed, jnp.bfloat16) if not kv8 else {
        "qp": sds(packed, jnp.int8),
        "ps": sds((layers, nb, h, BLOCK), jnp.bfloat16)}
    return (sds((slots, h, t, hd), jnp.bfloat16), pool,
            sds((slots, nbper), jnp.int32), sds((slots,), jnp.int32))


@pytest.mark.parametrize("kv8", [False, True], ids=["bf16", "kv8"])
@pytest.mark.parametrize("cell,slots,h,hd,ctx,layers", CELL_SHAPES)
def test_paged_walk_lowers_at_the_cells_shapes(cell, slots, h, hd, ctx,
                                               layers, kv8):
    """ISSUE 29: decode and verify at the shapes the benchmark's serving
    cells run, the packed pool a whole ``ANY`` operand at a layer index."""
    for t, kernel, name in ((1, da.paged_decode_attention_pallas,
                             "paged_decode_attn"),
                            (4, da.paged_verify_attention_pallas,
                             "paged_verify_attn")):
        q, pool, bt, pos = _cell_operands(slots, h, hd, ctx, layers, kv8, t)
        text = _lower_tpu(lambda q, k, v, bt, pos, kernel=kernel: kernel(
            q, k, v, bt, pos, interpret=False, layer=1), q, pool, pool, bt,
            pos)
        assert f'kernel_name = "{name}"' in text


@pytest.mark.parametrize("name,h,hd,ctx", ATTN_SHAPES)
def test_contiguous_decode_lowers(name, h, hd, ctx):
    q = _sds((SLOTS, h, 1, hd), jnp.bfloat16)
    cache = _sds((SLOTS, h, ctx, hd), jnp.bfloat16)
    _lower_tpu(lambda q, k, v, pos: da.decode_attention_pallas(
        q, k, v, pos, interpret=False), q, cache, cache,
        _sds((SLOTS,), jnp.int32))


@pytest.mark.parametrize("rows", [512, 4096], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)],
                         ids=["gate-up", "down"])
def test_grouped_expert_matmul_lowers(rows, k, n):
    """``moe_gmm`` at OLMoE's widths: 64 experts' whole 8-layer stacks, a
    decode step's 64 x top-8 rows and a [4, 128] prefill chunk's."""
    from deepspeed_tpu.moe.grouped_matmul import moe_gmm

    text = _lower_tpu(
        lambda x, w, gs, l: moe_gmm(x, w, gs, l, interpret=False),
        _sds((rows, k), jnp.bfloat16), _sds((8, 64, k, n), jnp.bfloat16),
        _sds((64,), jnp.int32), _sds((), jnp.int32))
    assert 'kernel_name = "moe_gmm"' in text


def test_flash_train_step_kernels_lower():
    """chip_smoke.py training config: flash v2, 1024x1024
    blocks, micro-batch 32 x S=1024, forward and fused backward."""
    q = _sds((32, 12, 1024, 64), jnp.bfloat16)

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, block_q=1024,
                               block_k=1024, interpret=False)
        return o.astype(jnp.float32).sum()

    text = _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert text.count("tpu_custom_call") >= 2      # fwd + fused bwd


@pytest.mark.parametrize("d,f", [(2048, 8192), (4096, 16384), (5120, 20480)],
                         ids=["opt-1.3b", "opt-6.7b", "opt-13b"])
def test_w8a8_kernels_lower(d, f, monkeypatch):
    monkeypatch.setattr(qmm, "interpret_kernels", lambda: False)
    layers, kg = 2, 128
    x_rows = SLOTS
    for k_dim, n_dim in ((d, 3 * d), (d, d), (d, f), (f, d)):
        x = _sds((x_rows, k_dim), jnp.bfloat16)
        rec = {"qk": _sds((k_dim, n_dim), jnp.int8),
               "kscale": _sds((k_dim // kg, 1, n_dim), jnp.float32)}
        _lower_tpu(lambda x, rec: qmm.w8a8_matmul(x, rec), x, rec)
        stacked = {"qk": _sds((layers, k_dim, n_dim), jnp.int8),
                   "kscale": _sds((layers, k_dim // kg, 1, n_dim),
                                  jnp.float32)}
        _lower_tpu(lambda x, rec, l: qmm.w8a8_matmul_stacked(x, rec, l),
                   x, stacked, _sds((), jnp.int32))


# ----------------------------------------------------------------------------
# ISSUE 26: the paged pool is carried whole and updated in place.  The three
# serving programs, at the benchmark's chat-cell shapes, for platform tpu.
# ----------------------------------------------------------------------------
OPT13B = dict(vocab_size=50272, max_seq_len=2048, num_layers=24, num_heads=32,
              hidden_size=2048, ffn_size=8192)
#: StableHLO ops that slice a layer out of the pool, re-stack it or re-lay it
#: out; ``copy`` only exists after layout assignment (the compiled check)
RESHAPERS = ("dynamic_slice", "dynamic_update_slice", "transpose",
             "concatenate", "copy")


@pytest.fixture(scope="module")
def smoke():
    import importlib.util
    import os
    import sys

    root = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir, os.pardir))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(root, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod        # dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Trace the TPU branches (Mosaic kernels, the layout pin) from here:
    the dispatch asks ``on_tpu()``, which sees the CPU this suite runs on."""
    from deepspeed_tpu.utils import platform

    for mod in (platform, da):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
        monkeypatch.setattr(mod, "interpret_kernels", lambda: False)


def _dims(tensor_type):
    """Dims of an MLIR ``tensor<24x769x32xbf16>`` type string."""
    return [int(d) for d in
            tensor_type.split("<", 1)[1].rsplit("x", 1)[0].split("x")]


def _elements(tensor_type):
    return int(np.prod(_dims(tensor_type)))


def _same_extent(tensor_type, shape):
    """Same dims up to order and size-1 dims: a slice, a transposed slice."""
    return sorted(d for d in _dims(tensor_type) if d != 1) == \
        sorted(d for d in shape if d != 1)


def _types(line):
    import re

    return re.findall(r"tensor<[0-9x]+x\w+>", line.split(" : ", 1)[1])


@pytest.mark.parametrize("kv8", [False, True], ids=["bf16", "kv8"])
@pytest.mark.parametrize("program", ["decode_step", "prefill", "verify"])
def test_pool_is_donated_and_never_sliced_or_restacked(program, kv8, smoke,
                                                       as_on_tpu):
    """The exported module (a) aliases every pool leaf to an output —
    donation reaches the module — and (b) has no dynamic_slice /
    dynamic_update_slice / transpose / concatenate that takes or produces a
    layer's slice of the pool or the pool itself: the pool is only ever
    gathered from (whole blocks, or through the kernels) and written by the
    in-place write, one ``scatter`` of whole blocks per leaf.  So the
    per-layer slice and the re-stack cannot come back unseen."""
    import re

    from deepspeed_tpu.models import opt

    fn, args = smoke.serving_programs(
        opt.OPTConfig(**OPT13B), None, kv8=kv8)[program]
    text = jax.export.export(jax.jit(fn, donate_argnums=(1,)),
                             platforms=["tpu"])(*args).mlir_module()
    # all three read the pool through a Mosaic kernel: decode and verify
    # the walk, prefill a float pool's chunk kernel (an int8 record's
    # chunk stays on the gather reference)
    kernel = {"decode_step": "paged_decode_attn",
              "verify": "paged_verify_attn",
              "prefill": "paged_prefill_attn"}[program]
    assert (f'kernel_name = "{kernel}"' in text) == \
        (not (kv8 and program == "prefill"))
    assert ("tpu_custom_call" in text) == (f'"{kernel}"' in text)
    payload = smoke.pool_payload_struct(args[1]).shape    # [L,NB,H,bs,hd]

    main = next(l for l in text.splitlines() if "func.func public @main" in l)
    leaf_shapes = {tuple(a.shape) for a in jax.tree_util.tree_leaves(args[1])}
    n_leaves = len(jax.tree_util.tree_leaves(args[1]))
    assert len(re.findall(r"tf\.aliasing_output", main)) == n_leaves, main

    for line in text.splitlines():
        m = re.search(r"stablehlo\.(\w+)", line)
        if not m or m.group(1) not in RESHAPERS or " : " not in line:
            continue
        for t in _types(line):
            assert not (_same_extent(t, payload) or
                        _same_extent(t, payload[1:])), \
                f"{m.group(1)} of a pool slice: {line}"

    # the in-place write: ONE scatter per pool leaf, of whole blocks at
    # (layer, physical block) — its index dims the leaf's two major dims
    scatters = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \((tensor<[^>]+>), '
        r'(tensor<[^>]+>), (tensor<[^>]+>)\) -> (tensor<[^>]+>)',
        text, flags=re.S)
    writes = [(upd, out) for _, _, upd, out in scatters
              if tuple(_dims(out)) in leaf_shapes]
    assert len(writes) == n_leaves, (len(writes), n_leaves)
    for upd, out in writes:
        assert _dims(upd)[2:] == _dims(out)[2:], (upd, out)


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


@pytest.mark.parametrize("kv8", [False, True], ids=["bf16", "kv8"])
def test_compiled_serving_programs_hold_no_pool_sized_temporary(
        kv8, smoke, as_on_tpu, one_chip):
    """The chip's own compiler on the same programs (a described v5e, no
    chip attached), the pool lane-packed as the engine holds it: every
    program aliases the whole pool, holds temporaries far below one
    layer's slice of it (parent: 7.25 GB in decode, 6.59 GB in prefill) and
    has no ``copy`` of a pool slice (parent: six per layer)."""
    from deepspeed_tpu.models import opt

    progs = smoke.serving_programs(opt.OPTConfig(**OPT13B), one_chip, kv8=kv8)
    for name, (fn, args) in progs.items():
        compiled, copies = smoke.compile_serving_program(fn, args)
        leaves = jax.tree_util.tree_leaves(args[1])
        payload = smoke.pool_payload_struct(args[1])
        # bf16: under ONE layer's slice of the pool (100.8 MB), and prefill
        # — its gathered K/V views of 34 MB gone with the chunk kernel —
        # under a QUARTER of one (what is left is the chunk's own
        # activations).  kv8: the int8 record's scale
        # table [L, NB, HKV, bs] still enters and leaves in XLA's layout
        # (4 copies of 38 MB, padded, a step: 303 MB) and prefill holds
        # dequantized f32 views (707 MB) — bound it by the codes of ONE of
        # K and V instead; a copy of a pool slice is caught by name above
        layer_slice = int(np.prod(payload.shape[1:])) * payload.dtype.itemsize
        limit = int(np.prod(payload.shape)) if kv8 else layer_slice
        if name == "prefill" and not kv8:
            limit = layer_slice // 4
        mem = compiled.memory_analysis()
        assert not copies, (name, copies[:2])
        assert mem.temp_size_in_bytes < limit, (name, mem.temp_size_in_bytes)
        unpadded = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in leaves)
        assert mem.alias_size_in_bytes >= unpadded, (name, mem)


@pytest.mark.parametrize("kv8", [False, True], ids=["bf16", "kv8"])
@pytest.mark.parametrize("cell,slots,h,hd,ctx,layers", CELL_SHAPES)
def test_paged_walk_compiles_at_the_cells_shapes(cell, slots, h, hd, ctx,
                                                 layers, kv8, one_chip):
    """Mosaic's own compile of the walk — decode, verify and, for a float
    pool, a prefill chunk — for a described v5e (its DMA alignment rules
    and VMEM budget: what lowering alone does not check), and no temporary
    beside a packed float pool: the kernel reads the whole stack where it
    lies."""
    kernels = [(1, slots, da.paged_decode_attention_pallas),
               (4, slots, da.paged_verify_attention_pallas)]
    if not kv8:
        # a [4, 128] prefill chunk (the cells' prefill_batch x prefill_chunk)
        kernels.append((128, 4, da.paged_prefill_attention_pallas))
    for t, rows, kernel in kernels:
        args = _cell_operands(rows, h, hd, ctx, layers, kv8, t, one_chip)
        q, pool, bt, pos = args
        compiled = jax.jit(lambda q, k, v, bt, pos, kernel=kernel: kernel(
            q, k, v, bt, pos, interpret=False, layer=1)).lower(
                q, pool, pool, bt, pos).compile()
        if not kv8:
            # kv8: this layer's scale rows ride lane-padded (a copy of
            # 1/L of the small table)
            assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
