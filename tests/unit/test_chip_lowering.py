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


def _kernel_primitives(jaxpr, acc):
    """Primitive -> count over a kernel's jaxpr, loop bodies included."""
    for eqn in jaxpr.eqns:
        acc[eqn.primitive.name] = acc.get(eqn.primitive.name, 0) + 1
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_primitives(sub, acc)
    return acc


@pytest.mark.parametrize("kv8", [False, True], ids=["bf16", "kv8"])
def test_paged_walk_program_does_not_grow_with_its_tile(kv8, monkeypatch):
    """ISSUE 45: the walk's body is a TILE's, not a block's — at 1, 8 and 16
    blocks a loop iteration (chat's shapes) the kernel holds the same two
    matmuls, the same max / exp / sum and the same copy sites; only the
    loads of the landed blocks are one a block (PR 29 unrolled the update
    per block: 2.3 s of every start at 8)."""
    q, pool, bt, pos = _cell_operands(24, 32, 64, 1024, 24, kv8, 1)
    counts = {}
    for cols in (16, 128, 256):
        monkeypatch.setattr(da, "_WALK_COLS", cols)
        assert da.walk_tile_blocks(16, 32) == cols // 16
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, bt, pos: da.paged_decode_attention_pallas(
                q, k, v, bt, pos, interpret=False, layer=1))(
                    q, pool, pool, bt, pos).jaxpr
        kernel, = (eqn.params["jaxpr"] for eqn in jaxpr.eqns
                   if eqn.primitive.name == "pallas_call")
        counts[cols] = _kernel_primitives(kernel, {})
    work = ("dot_general", "exp", "reduce_max", "reduce_sum", "dma_start",
            "dma_wait", "select_n", "mul", "swap")
    assert counts[16]["dot_general"] == 2
    for cols in (128, 256):
        assert {k: counts[cols][k] for k in work} \
            == {k: counts[16][k] for k in work}, (cols, counts)
    if not kv8:
        # an int8 pool's scale rows are sliced a block as well
        assert sum(counts[128].values()) < 1.1 * sum(counts[16].values())


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
    # ISSUE 62: strips inside a tile change no name — the benchmark's
    # ``flash_share.train`` / ``window_flash_ms`` find the kernels by it
    assert fa.KERNELS == {
        "v1": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
        "v2": ("flash_fwd_resident", "flash_bwd_fused"),
        "v3": ("flash_fwd_chunked", "flash_bwd_dq_chunked",
               "flash_bwd_dkv_chunked")}
    for kernel in fa.KERNELS["v2"]:
        assert text.count(f'kernel_name = "{kernel}"') == 1, kernel


def test_given_flash_blocks_lower_as_if_no_rule_existed(monkeypatch):
    """ISSUE 35: ``gpt2m-train-1k`` names its blocks (1024 x 1024), so the
    rule that chooses blocks is never asked: the call lowers to the same
    text with ``_resolve_blocks`` replaced by the arithmetic
    ``flash_attention`` did before the rule existed."""
    q = _sds((8, 16, 1024, 64), jnp.bfloat16)       # the cell's micro-batch

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, block_q=1024,
                               block_k=1024, interpret=False)
        return o.astype(jnp.float32).sum()

    def as_before(q_len, kv_len, d, itemsize, block_q, block_k):
        bq, bk = min(block_q, max(q_len, 1)), min(block_k, max(kv_len, 1))
        pad_q, pad_k = (-q_len) % bq, (-kv_len) % bk
        if fa._v2_eligible(kv_len + pad_k, d):
            bq = max(8, min(bq, fa._V2_MAX_SCORE_ELEMS // (kv_len + pad_k)))
        return fa.Choice(q_len, kv_len, d, "-", bq, bk, "-"), pad_q, pad_k

    texts, noted = [], []
    # one call site for both: the kernels' payloads embed the call stack
    for resolve in (fa._resolve_blocks, as_before):
        monkeypatch.setattr(fa, "_resolve_blocks", resolve)
        before = fa.choices()
        texts.append(_lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q))
        noted.append(list(fa.choices(since=before)))
    with_rule, without_rule = texts
    assert noted[0] == [fa.Choice(1024, 1024, 64, "v2", 1024, 1024, "given",
                                  window=0, strip=256)]
    for kernel in fa.KERNELS["v2"]:
        assert f'kernel_name = "{kernel}"' in with_rule
    assert with_rule == without_rule


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


def _trace_as_on_tpu(patch):
    from deepspeed_tpu.ops import sampling
    from deepspeed_tpu.utils import platform

    for mod in (platform, da):
        patch.setattr(mod, "on_tpu", lambda: True)
        patch.setattr(mod, "interpret_kernels", lambda: False)
    patch.setattr(sampling, "interpret_kernels", lambda: False)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Trace the TPU branches (Mosaic kernels, the layout pin) from here:
    the dispatch asks ``on_tpu()``, which sees the CPU this suite runs on."""
    _trace_as_on_tpu(monkeypatch)


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
def v5e_2x2():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


#: (shape [B, H, S, hd], KV heads, generation, window): the four-chip
#: training cell's call a chip (opt13b-zero3-x4), Llama-class calls at hd 128
#: (one GQA), the resident path OPT / Llama take at S <= 1024; from ISSUE 62
#: the other two training cells' calls — gpt2m-train-1k's, and
#: smallthinker-train-8k's full and windowed layers
FLASH_TRAIN_SHAPES = [((8, 32, 2048, 64), 32, "v3", 0),
                      ((2, 32, 4096, 128), 32, "v3", 0),
                      ((2, 32, 4096, 128), 8, "v3", 0),
                      ((1, 16, 8192, 128), 16, "v3", 0),
                      ((16, 32, 1024, 64), 32, "v2", 0),
                      ((8, 16, 1024, 128), 16, "v2", 0),
                      ((8, 16, 1024, 64), 16, "v2", 0),
                      ((1, 28, 8192, 128), 4, "v3", 0),
                      ((1, 28, 8192, 128), 4, "v3", 4096)]


@pytest.mark.parametrize("shape,hkv,generation,window", FLASH_TRAIN_SHAPES)
def test_flash_default_blocks_compile_for_a_v5e(shape, hkv, generation,
                                                window, one_chip):
    """ISSUE 35: forward + backward at the blocks ``flash_attention``
    chooses for itself, through Mosaic's own compile for a described v5e —
    the scoped-VMEM cliff (2048-row blocks are refused) caught without a
    chip.  ISSUE 62: in the strips the shapes give, every kernel under the
    name it had."""
    b, h, s_len, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, hkv, s_len, d), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, window=window,
                               interpret=False)
        return o.astype(jnp.float32).sum()

    before = fa.choices()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    (choice,) = fa.choices(since=before)
    assert choice.how == "chosen" and choice.generation == generation
    assert min(choice.block_q, choice.block_k) >= 512, choice
    assert (choice.window, choice.strip) == (window, fa._STRIP), choice
    for kernel in fa.KERNELS[generation]:
        assert kernel in text, (kernel, choice)


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


def _narrow_block_passes(text, blocks):
    """``copy`` / ``transpose`` instructions of a compiled program that
    hold as many elements as the write's gathered ``blocks`` ``[B, J, HKV,
    R, W]`` under a minor dim narrower than the 128 lanes: blocks being
    brought to token order ``[.., bs, hd]`` or back."""
    import re

    found = []
    for line in text.splitlines():
        m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose)\(", line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if int(np.prod(dims)) == int(np.prod(blocks)) and dims[-1] < 128:
            found.append(line.strip()[:120])
    return found


def test_compiled_write_never_brings_a_block_to_token_order(
        smoke, as_on_tpu, one_chip):
    """ISSUE 41: the chat cell's decode and prefill programs at hd 64 (two
    tokens a lane row), compiled for a described v5e, merge the window's
    tokens in the blocks' STORED view.  The parent (PR 39) un-packed the
    gathered blocks to token order and packed the merged ones again: this
    count read **8 in decode** (six ``copy`` kernels a layer —
    ``[24,1,32,2,16,64]``, ``[24,1,32,32,64]``, ``[24,1,32,16,2,64]``, for K
    and for V, beside four ``reshape`` kernels — and the window's two
    token-order gathers ``[24,32,32,64]``) and **6 in prefill**
    (``[4,5,32,2,16,64]``, ``[4,5,32,16,2,64]``, ``[4,5,32,32,64]``).  Now
    0 and 0; the pool is still aliased whole and no temporary comes near a
    layer's slice of it.  (What a chunk keeps: four full-lane passes a
    layer, the gathered blocks to the window gather's ``[.., R, HKV, W]``
    order and back, as a 128-wide head always had them.)"""
    from deepspeed_tpu.models import opt

    progs = smoke.serving_programs(opt.OPTConfig(**OPT13B), one_chip)
    payload = smoke.pool_payload_struct(progs["prefill"][1][1])
    assert payload.shape[-2:] == (smoke.CHAT_BLOCK // 2, 128)     # g = 2
    layer_slice = int(np.prod(payload.shape[1:])) * payload.dtype.itemsize
    for name in ("decode_step", "prefill"):
        fn, args = progs[name]
        compiled, copies = smoke.compile_serving_program(fn, args)
        rows, t = args[2].shape if name == "prefill" else (*args[2].shape, 1)
        bs = smoke.CHAT_BLOCK
        nj = 1 if t == 1 else (t + bs - 2) // bs + 1    # _window_blocks
        blocks = (rows, nj) + payload.shape[2:]
        text = compiled.as_text()
        # not vacuous: the touched blocks are gathered, as they are stored
        gathered = ",".join(map(str, (rows * nj,) + blocks[2:]))
        assert f"bf16[{gathered}]" in text, name
        assert _narrow_block_passes(text, blocks) == [], name
        mem = compiled.memory_analysis()
        assert not copies, (name, copies[:2])
        assert mem.temp_size_in_bytes < layer_slice // 4, name
        assert mem.alias_size_in_bytes >= 2 * int(np.prod(payload.shape)) \
            * payload.dtype.itemsize, (name, mem)


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
        # a prefill call at both rungs of the cells' ladder (prefill_batch x
        # prefill_chunk = [4, 128], and a row alone: [1, 512])
        kernels += [(t, 512 // t, da.paged_prefill_attention_pallas)
                    for t in (128, 512) if t <= ctx]
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


# ------------------------------------------------ learned sparse attention
#: the long-context cell (keye-longctx-closed): 16 slots x 16,384 positions,
#: 6 layers, an indexer of 16 heads x 64 choosing 2,048 keys
LONGCTX = dict(slots=16, ctx=16384, layers=6, heads=16, width=64, topk=2048)


@pytest.mark.parametrize("rows,t", [(16, 1), (4, 128), (16, 4)],
                         ids=["decode", "prefill-chunk", "verify"])
def test_sparse_attention_kernels_compile_at_the_long_context_cells_shapes(
        rows, t, one_chip):
    """Mosaic's own compile, for a described v5e, of the indexer's scoring
    walk over the packed third pool leaf (read where it lies: no temporary
    beside it) and of the threshold selection over its scores."""
    c = LONGCTX

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    nbper = c["ctx"] // BLOCK
    pool = sds((c["layers"], 1 + c["slots"] * nbper, 1, BLOCK // 2,
                2 * c["width"]), jnp.bfloat16)
    scores = jax.jit(lambda qi, wi, p, bt, last: da.paged_index_scores_pallas(
        qi, wi, p, bt, last, layer=1, interpret=False)).lower(
            sds((rows, c["heads"], t, c["width"]), jnp.bfloat16),
            sds((rows, t, c["heads"]), jnp.float32), pool,
            sds((rows, nbper), jnp.int32), sds((rows, t), jnp.int32))
    assert 'kernel_name = "paged_index_scores"' in scores.as_text()
    compiled = scores.compile()
    # the scores themselves, once more for the transpose into token order
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 2 * rows * t * c["ctx"] * 4
    select = jax.jit(lambda s: da.paged_sparse_select_pallas(
        s, c["topk"], interpret=False)).lower(
            sds((rows, t, c["ctx"]), jnp.float32))
    assert 'kernel_name = "paged_sparse_select"' in select.as_text()
    assert select.compile().memory_analysis().temp_size_in_bytes < 1 << 20
    if t % 8 and t != 1:
        return          # a verify window's read is the XLA walk
    # the read: K and V blocks of GQA 32/4 x 128 out of the whole stack
    kv = sds((c["layers"], 1 + c["slots"] * nbper, 4, BLOCK, 128),
             jnp.bfloat16)
    read = jax.jit(lambda q, k, v, bt, s, th, sl, last, hit:
                   da.paged_sparse_attention_pallas(
                       q, k, v, bt, s, th, sl, last, hit, layer=1,
                       interpret=False)).lower(
        sds((rows, 32, t, 128), jnp.bfloat16), kv, kv,
        sds((rows, nbper), jnp.int32), sds((rows, t, c["ctx"]), jnp.float32),
        sds((rows, t), jnp.float32), sds((rows, t), jnp.int32),
        sds((rows, t), jnp.int32), sds((rows, nbper), jnp.int32))
    assert 'kernel_name = "paged_sparse_attn"' in read.as_text()
    assert read.compile().memory_analysis().temp_size_in_bytes < 1 << 20


def test_compiled_sparse_serving_programs_hold_no_pool_sized_temporary(
        as_on_tpu, one_chip, monkeypatch):
    """``test_compiled_serving_programs_hold_no_pool_sized_temporary`` for a
    model with an indexer, at the long-context cell's shapes: decode and
    prefill alias all THREE pool leaves, copy no slice of any, and hold
    temporaries far below one layer's slice of the K pool (268 MB) — the
    chosen tokens' copies (34 MB) in decode, one layer's indexer scores
    (``[4, 128, 16384]`` float32, 34 MB) and a walk step in prefill.  A
    gather indexed ``[layer, block, :, row]`` had XLA re-lay-out the whole
    K pool first: 1.6 GB of temporaries in decode."""
    import dataclasses

    from deepspeed_tpu.models import mixtral
    from deepspeed_tpu.moe import grouped_matmul
    from deepspeed_tpu.ops import paged_kv, sparse_index_attention

    monkeypatch.setattr(sparse_index_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(grouped_matmul, "interpret_kernels", lambda: False)
    c = LONGCTX
    cfg = dataclasses.replace(mixtral.MixtralConfig.keye_vl2_30b_a3b(),
                              num_layers=c["layers"], max_seq_len=c["ctx"])
    spec = mixtral.build(cfg)
    fwd = spec.decode_hooks["forward_cached"]
    nbper = c["ctx"] // BLOCK

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
            1 + c["slots"] * nbper, BLOCK, jnp.bfloat16))))
    assert set(pool) == {"k", "v", "idx"}

    def decode_step(params, cache, tokens, lengths, bt):
        logits, cache = fwd(params, tokens[:, None], cache, 0,
                            lengths=lengths, block_tables=bt)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def prefill(params, cache, ids, bt, base, valid):
        logits, cache = fwd(params, ids, cache, base, lengths=valid,
                            block_tables=bt)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    slots = c["slots"]
    programs = {
        "decode_step": (decode_step, (params, pool, i32(slots), i32(slots),
                                      i32(slots, nbper))),
        "prefill": (prefill, (params, pool, i32(4, 128), i32(4, nbper),
                              i32(4), i32(4)))}
    layer_slice = int(np.prod(pool["k"].shape[1:])) * 2
    for name, (fn, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        text = compiled.as_text()
        for kernel in ("paged_index_scores", "paged_sparse_select"):
            assert kernel in text, (name, kernel)
        for leaf in pool.values():
            dims = ",".join(str(d) for d in leaf.shape[1:])
            copies = [line for line in text.splitlines() if " copy(" in line
                      and dims in line.split(" copy(")[0]]
            assert not copies, (name, copies[:2])
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < layer_slice // 4, (
            name, mem.temp_size_in_bytes)
        assert mem.alias_size_in_bytes >= sum(
            int(np.prod(a.shape)) * 2 for a in pool.values()), (name, mem)


# ------------------------------------------------------------- the sampler
#: ``[rows, vocab]`` of the serving cells' sampler calls: chat decode, OLMoE
#: decode, the long-context cell's decode and its [4, 128] prefill call, and
#: a verify window of the chat cell (24 slots x K + 1 = 5 positions)
SAMPLER_SHAPES = [(24, 50272), (64, 50304), (16, 151936), (4, 151936),
                  (24 * 5, 50272)]


@pytest.mark.parametrize("rows,vocab", SAMPLER_SHAPES)
def test_sampler_compiles_without_a_sort_at_the_cells_shapes(rows, vocab,
                                                             one_chip,
                                                             monkeypatch):
    """ISSUE 33: ``filtered_logprobs`` compiled for a described v5e at the
    cells' shapes (a vocabulary that is no lane multiple among them) holds
    no ``sort`` — the thresholds come from the two searches' loops — and,
    beside the log-probs it returns, temporaries under ONE ``[rows, vocab]``
    float32 array: the searches form their keys inside each pass and hold
    nothing of that size (the parent's two sorts each held the sorted row
    set and its permutation).  ISSUE 67: from ``TILED_FROM`` entries a row
    the two searches are the ``kth_search`` / ``nucleus_search`` kernels."""
    from deepspeed_tpu.ops import sampling

    monkeypatch.setattr(sampling, "interpret_kernels", lambda: False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(sampling.filtered_logprobs).lower(
        sds((rows, vocab), jnp.bfloat16), sds((rows,), jnp.float32),
        sds((rows,), jnp.int32), sds((rows,), jnp.float32))
    text = lowered.as_text()
    assert "stablehlo.sort" not in text
    assert text.count("stablehlo.case") + text.count("stablehlo.if") == 2
    tiled = sampling.thresholds(vocab) == "bitwise_search_tiled"
    assert tiled == (vocab > 100000)
    for kernel in ("nucleus_search", "kth_search"):
        assert (kernel in text) == tiled
    assert ("stablehlo.while" in text) != tiled    # the plain loops
    compiled = lowered.compile()
    assert " sort(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < rows * vocab * 4


@pytest.mark.parametrize("rows,vocab", [(128, 262272), (64, 100352),
                                        (4, 151936)])
def test_nucleus_search_lowers_at_the_wide_cells_shapes(rows, vocab):
    """ISSUE 67: the tiled nucleus search cross-lowered for platform ``tpu``
    at ZAYA1's and Granite's decode and at Keye's ``[4, vocab]`` prefill
    emit (4 rows pad to a tile of 16): one Mosaic kernel whose block is
    the ``[16, vocab]`` float32 row tile."""
    from deepspeed_tpu.ops import sampling

    text = _lower_tpu(
        lambda probs, p: sampling._nucleus_threshold_tiled(
            probs, p, interpret=False),
        _sds((rows, vocab), jnp.float32), _sds((rows, 1), jnp.float32))
    assert text.count("tpu_custom_call") == 1 and "nucleus_search" in text


def test_tiled_searches_lower_inside_a_program_over_two_chips():
    """A tensor-parallel engine's program spans its chips and Mosaic
    partitions no kernel: under the serving engine's ``tp_context`` both
    searches sit in a ``shard_map`` (every chip searches every row) and
    ``filtered_logprobs`` lowers for platform ``tpu`` as it does bare on
    one chip; on the CPU the interpreted kernel there gives the bare
    kernel's thresholds."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops import paged_kv, sampling

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    args = (sds((16, 100352), jnp.bfloat16), sds((16,), jnp.float32),
            sds((16,), jnp.int32), sds((16,), jnp.float32))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sampling, "interpret_kernels", lambda: False)
        with pytest.raises(NotImplementedError, match="partitioned"):
            _lower_tpu(lambda *a: sampling.filtered_logprobs(*a), *args)
        with paged_kv.tp_context(mesh):     # (a trace of its own)
            text = _lower_tpu(lambda *a: sampling.filtered_logprobs(*a),
                              *args)
    assert text.count("tpu_custom_call") == 2
    rng = np.random.default_rng(2)
    probs = jax.nn.softmax(jnp.asarray(
        rng.normal(size=(5, 300)).astype(np.float32)), axis=-1)
    p = jnp.full((5, 1), 0.8, jnp.float32)
    with paged_kv.tp_context(mesh):
        got = jax.jit(sampling._nucleus_threshold_tiled)(
            jax.device_put(probs, rep), jax.device_put(p, rep))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(sampling._nucleus_threshold_tiled(
            probs, p)))


def test_nucleus_search_compiles_within_its_vmem_for_a_v5e(one_chip):
    """Mosaic's own compile of the kernel for a described v5e at ZAYA1's
    decode shape: two buffers of the 16.8 MB tile inside the limit the call
    raises (``sampling._TILE_VMEM``)."""
    from deepspeed_tpu.ops import sampling

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(lambda probs, p: sampling._nucleus_threshold_tiled(
        probs, p, interpret=False)).lower(
            sds((128, 262272)), sds((128, 1))).compile()
    assert "nucleus_search" in compiled.as_text()
    assert 2 * sampling._TILE_ROWS * 262272 * 4 < sampling._TILE_VMEM


@pytest.mark.parametrize("spec_tokens,programs",
                         [(0, {"decode", "prefill"}),
                          (3, {"prefill", "verify"})],
                         ids=["plain", "speculative"])
def test_serving_programs_hold_no_sort_and_knobs_never_recompile(
        spec_tokens, programs):
    """The decode / prefill / verify programs a dense family's engine built
    hold no ``sort`` (their bodies lowered at the live shapes, the sampling
    operands included), and a slot changing its knobs — greedy, top-k,
    top-p, none — changes operand values only: ``compile_count`` stays."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request, ServingEngine
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.telemetry.flops import ServingFlopsProfiler

    cfg = gpt2.GPT2Config.tiny()
    engine = deepspeed_tpu.init_inference(gpt2.build(cfg),
                                          config={"dtype": "fp32"})
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=16, spec_tokens=spec_tokens)
    rng = np.random.default_rng(0)

    def serve(knobs):
        reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 9,
                                                   dtype=np.int32),
                        max_new_tokens=6, temperature=t, top_k=k, top_p=p,
                        seed=i)
                for i, (t, k, p) in enumerate(knobs)]
        out = srv.serve(reqs)
        assert len(out) == len(knobs) and all(len(v) for v in out.values())

    serve([(0.7, 0, 0.9), (0.0, 0, 1.0)])
    built = srv.compile_count
    assert srv.stats()["sampler"] == dict.fromkeys(programs,
                                                   "bitwise_search")
    serve([(1.3, 5, 1.0), (0.7, 7, 0.5), (1.0, 0, 1.0)])
    serve([(0.0, 0, 1.0)])
    assert srv.compile_count == built
    assert srv.stats()["retraces_observed"] == 0

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    abstract = ServingFlopsProfiler(srv)._abstract_args
    samp = {"decode": srv._samp_args(np.zeros(srv.slots)),
            "verify": srv._samp_args(np.zeros(srv.slots)),
            "prefill": srv._samp_args_rows([], srv.prefill_batch)}
    assert set(srv._program_bodies) == programs
    for name in programs:
        text = jax.jit(srv._program_bodies[name]).lower(
            *abstract(name), *sds(samp[name])).as_text()
        assert "stablehlo.while" in text, name      # the searches are there
        assert "stablehlo.sort" not in text, name


# ------------------------------------- layers of two kinds (window + full)
#: the RAG-chat cell (commanda-ragchat-closed): 24 slots x 16,384 positions,
#: 128 query / 8 KV heads x 128, a 4,096-key window behind a 128-token chunk
MIXED = dict(slots=24, ctx=16384, heads=128, kv_heads=8, hd=128,
             window=4096, chunk=128)


@pytest.mark.parametrize("kind", ["full", "sliding"])
@pytest.mark.parametrize("rows,t", [(24, 1), (4, 128), (24, 4)],
                         ids=["decode", "prefill-chunk", "verify"])
def test_window_walks_compile_at_the_rag_chat_cells_shapes(kind, rows, t,
                                                           one_chip):
    """Mosaic's own compile, for a described v5e, of the decode / verify
    walk and the prefill walk at GQA 128 / 8 x 128 (2,048 query rows a KV
    head in a ``[4, 128]`` chunk: one head a grid step) for both layer
    kinds: a full layer over the full kind's table, a sliding layer with
    its first-visible-key bound over the window kind's ring.  Each reads
    the whole stack where it lies."""
    c = MIXED
    ring = -(-(c["window"] + c["chunk"]) // BLOCK) + 1
    window = c["window"] if kind == "sliding" else 0
    layers, nbper = (3, ring) if window else (1, c["ctx"] // BLOCK)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((layers, 1 + c["slots"] * nbper, c["kv_heads"], BLOCK,
                c["hd"]), jnp.bfloat16)
    kernel = {1: da.paged_decode_attention_pallas,
              4: da.paged_verify_attention_pallas,
              128: da.paged_prefill_attention_pallas}[t]
    how = {"window": window} if window else {}
    lowered = jax.jit(lambda q, k, v, bt, pos: kernel(
        q, k, v, bt, pos, interpret=False, layer=0, **how)).lower(
            sds((rows, c["heads"], t, c["hd"]), jnp.bfloat16), pool, pool,
            sds((rows, nbper), jnp.int32), sds((rows,), jnp.int32))
    assert "tpu_custom_call" in lowered.as_text()
    assert lowered.compile().memory_analysis().temp_size_in_bytes < 1 << 20


#: sha256 (first 16 hex digits) of the decode / prefill programs of the
#: families the benchmark served before layers had kinds and experts could
#: be held, lowered for a described v5e at the small shapes of
#: ``_old_family_programs`` — each Mosaic kernel's payload masked: it embeds
#: the source lines of ``ops/decode_attention.py`` / ``moe/routed.py``,
#: which moved.  ``opt.*`` are taken on the tree of PR 41 (tiny config, hd
#: 16: g = 8 merges in the stored view; a one-token window is broadcast
#: over its block); ``mistral4.*`` (the latent pool kind, ONE leaf),
#: ``gpt2.*`` and ``bloom.*`` (ALiBi, pure XLA on the pool, no kernel) on
#: the PARENT of PR 46 (``a0db4cf``), before the cached forward moved to
#: ``models/cached.py``.  The ten that pass through
#: ``llama._attend_cached`` — ``mixtral.*``, ``olmoe.*``, ``keye.*``,
#: ``commanda.*`` and ``llama.*`` (dense, GQA 4 / 2) — are taken on the tree
#: of PR 50: each layer's q, k and v products (and Keye's indexer queries)
#: pass an ``optimization_barrier`` before their head split, and nothing
#: else of the text moved.  ``smallthinker.*`` on the PARENT of PR 51 (``23ed2e6``),
#: whose stacks-by-kind change of ``cached.scan_periods_cached``, selection
#: bias and scale of ``routed.route`` and full-rank / unrotated latent
#: queries of ``llama._latent_project`` move none of these
OLD_PROGRAMS = {
    "opt.decode": "b52e4bc5d86a603e", "opt.prefill": "0fe6ed036104ea84",
    "mixtral.decode": "f936948485165684",
    "mixtral.prefill": "d01b1d839f49b6ec",
    "olmoe.decode": "2eb416656948bc0f", "olmoe.prefill": "7388632270416586",
    "keye.decode": "1bb7ff462ebd729f", "keye.prefill": "93c37d6a3e887546",
    "commanda.decode": "16dda96d9bd57be2",
    "commanda.prefill": "82fe8e3e6324c1c8",
    "mistral4.decode": "2d73686d9b1d63a7",
    "mistral4.prefill": "33a83a8b55a6460a",
    "smallthinker.decode": "e2cd920468027b8b",
    "smallthinker.prefill": "a9c1689a9b5317d9",
    "gpt2.decode": "56db7e72e98e31c7", "gpt2.prefill": "aaca73ee22c8ad8f",
    "llama.decode": "12867882289301b8", "llama.prefill": "a7ecf11445f38e19",
    "bloom.decode": "05eea69436e8aefe", "bloom.prefill": "9e7f54fb3e7d2def"}


def _assert_pinned(pins, name, text):
    import hashlib

    found = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert found == pins[name], \
        f"{name} lowers to a text that hashes to {found}, not {pins[name]}"


def _old_family(name):
    import dataclasses

    from deepspeed_tpu.models import bloom, gpt2, llama, mixtral, opt

    small = dict(num_layers=2, max_seq_len=256, vocab_size=512,
                 hidden_size=256, ffn_size=128, num_experts=8)
    dense = {"opt": (opt, opt.OPTConfig), "gpt2": (gpt2, gpt2.GPT2Config),
             "llama": (llama, llama.LlamaConfig),
             "bloom": (bloom, bloom.BloomConfig)}
    if name in dense:
        module, config = dense[name]
        return module.build(dataclasses.replace(config.tiny(),
                                                max_seq_len=256))
    if name == "mistral4":
        # the latent widths as published (a token is 256 + 64 values in
        # three lane rows, ONE pool leaf); fewer heads, a narrower query rank
        return mixtral.build(dataclasses.replace(
            mixtral.MixtralConfig.mistral_small_4(), num_heads=4,
            num_kv_heads=4, q_lora_rank=128, **small))
    if name == "mixtral":
        return mixtral.build(dataclasses.replace(
            mixtral.MixtralConfig.tiny(), max_seq_len=256))
    if name == "olmoe":
        return mixtral.build(dataclasses.replace(
            mixtral.MixtralConfig.olmoe_1b_7b(), num_heads=2,
            num_kv_heads=2, **small))
    if name == "commanda":
        return mixtral.build(dataclasses.replace(
            mixtral.MixtralConfig.command_a_plus(), num_heads=4,
            num_kv_heads=2, sliding_window=64, shared_experts=2,
            **{**small, "num_layers": 4}))
    if name == "smallthinker":
        # [full, sliding x 3] on two pool kinds, ReGLU experts top-6, the
        # router fed the attention's input; trained dropless, served here
        return mixtral.build(dataclasses.replace(
            mixtral.MixtralConfig.smallthinker_21b_a3b(), num_heads=4,
            num_kv_heads=2, head_width=None, sliding_window=64,
            **{**small, "num_layers": 4}))
    return mixtral.build(dataclasses.replace(
        mixtral.MixtralConfig.keye_vl2_30b_a3b(), num_heads=4,
        num_kv_heads=2, index_heads=2, index_topk=64, **small))


@pytest.mark.parametrize("name", sorted(OLD_PROGRAMS))
def test_the_old_programs_are_the_old_programs(name, as_on_tpu, one_chip,
                                               monkeypatch):
    """ISSUE 34, 39, 41: with every new field at its default (``held=None``;
    no latent ranks, no rope scaling, no query temperature), OPT, Mixtral,
    OLMoE, Keye and Command A+ lower, for a described v5e, to the text
    pinned in ``OLD_PROGRAMS`` (which says on which tree each was taken).
    ISSUE 46: so do Mistral Small 4 (the latent kind), GPT-2, dense Llama
    and BLOOM — a move of the cached forward's library changes no program
    a cell runs."""
    import re

    from deepspeed_tpu.moe import grouped_matmul
    from deepspeed_tpu.ops import paged_kv, sparse_index_attention

    monkeypatch.setattr(sparse_index_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(grouped_matmul, "interpret_kernels", lambda: False)
    family, program = name.split(".")
    spec = _old_family(family)
    fwd = spec.decode_hooks["forward_cached"]
    kw = {"routing": True} if spec.decode_hooks.get("routing_record") else {}
    slots, nbper = 4, 256 // BLOCK

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return sds(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            spec.init_fn(jax.random.PRNGKey(0)))))
    kinds, ring = {}, 0
    if "window_layers" in spec.decode_hooks:
        # a table a layer kind: the window kind's a ring of 6 blocks a row
        ring = (64 + 128) // BLOCK
        kinds = {"window_blocks": 1 + slots * ring}
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: paged_kv.pack_pool(spec.decode_hooks["init_cache"](
            1 + slots * nbper, BLOCK, jnp.bfloat16, **kinds))))

    def table(rows):
        return {"full": i32(rows, nbper), "window": i32(rows, ring)} \
            if ring else i32(rows, nbper)

    def decode_step(params, cache, tokens, lengths, bt):
        return fwd(params, tokens[:, None], cache, 0, lengths=lengths,
                   block_tables=bt, **kw)

    def prefill(params, cache, ids, bt, base, valid):
        return fwd(params, ids, cache, base, lengths=valid, block_tables=bt,
                   **kw)

    fn, args = {
        "decode": (decode_step, (params, pool, i32(slots), i32(slots),
                                 table(slots))),
        "prefill": (prefill, (params, pool, i32(2, 128), table(2),
                              i32(2), i32(2)))}[program]
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).as_text()
    # (BLOOM's ALiBi read is plain XLA over the gathered pool)
    assert ("tpu_custom_call" in text) == (family != "bloom")
    _assert_pinned(OLD_PROGRAMS, name, re.sub(
        r'backend_config = "[^"]*"', 'backend_config = "<kernel>"', text))


#: sha256 (first 16 hex digits) of the CONTIGUOUS ``forward_cached`` — what
#: ``InferenceEngine.generate`` runs: the prefill call (T = 8, ``pos`` the
#: constant 0) and the decode call (T = 1, ``pos`` a traced scalar) — of the
#: seven families that serve it, lowered on the CPU (the reference
#: attention, no kernel) at their ``tiny`` shapes, batch 2, a 128-token
#: float32 cache.  Taken on the PARENT of PR 46 (``a0db4cf``); ``llama.*``
#: on the tree of PR 50 (the barrier of ``_attend_cached``).  To re-take
#: a pin (of this table or of ``OLD_PROGRAMS``), run its case: the failure
#: names the hash the tree lowers to
OLD_CONTIGUOUS = {
    "gpt2.prefill": "cf1732aa2e5283b6", "gpt2.decode": "b5813769756b1ec5",
    "opt.prefill": "78300f29f61f0b7b", "opt.decode": "104b70c90eb19130",
    "llama.prefill": "82fd4b2ce11e3ee0", "llama.decode": "be6a053f8d4994c2",
    "bloom.prefill": "f6678df8b3ca7100", "bloom.decode": "a90ed9b2d761d990",
    "gptj.prefill": "fb999b79d2cc2edc", "gptj.decode": "1a8a5159d88af014",
    "gptneo.prefill": "832f31a013eb992b", "gptneo.decode": "1995354317bdee54",
    "gptneox.prefill": "1d03ba0a28de78f1",
    "gptneox.decode": "6db43c40a2c1d350"}


@pytest.mark.parametrize("name", sorted(OLD_CONTIGUOUS))
def test_the_contiguous_forward_cached_is_the_old_one(name):
    """ISSUE 46: the static-batch cached forward of every family lowers to
    the StableHLO text it lowered to before its library moved."""
    import importlib

    family, program = name.split(".")
    module = importlib.import_module(f"deepspeed_tpu.models.{family}")
    config = {"gpt2": "GPT2Config", "opt": "OPTConfig",
              "llama": "LlamaConfig", "bloom": "BloomConfig",
              "gptj": "GPTJConfig", "gptneo": "GPTNeoConfig",
              "gptneox": "GPTNeoXConfig"}[family]
    spec = module.build(getattr(module, config).tiny())
    hooks = spec.decode_hooks
    params = jax.eval_shape(lambda: spec.init_fn(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: hooks["init_cache"](2, 128, jnp.float32))
    if program == "prefill":
        lowered = jax.jit(lambda p, ids, c: hooks["forward_cached"](
            p, ids, c, 0)).lower(params, _sds((2, 8), jnp.int32), cache)
    else:
        lowered = jax.jit(hooks["forward_cached"]).lower(
            params, _sds((2, 1), jnp.int32), cache, _sds((), jnp.int32))
    _assert_pinned(OLD_CONTIGUOUS, name, lowered.as_text())


@pytest.fixture(scope="module")
def two_kind_programs(one_chip):
    """The RAG-chat cell's decode and prefill programs (Command A+ at its
    published widths, this chip's share: 4 layers, 16 held of 128 experts,
    32,768 vocabulary rows), compiled for a described v5e: ``{kernel:
    compiled}`` + the abstract parameters and pool they were lowered at."""
    import json
    import os

    from chipbench.families import commanda
    from deepspeed_tpu.moe import grouped_matmul
    from deepspeed_tpu.ops import paged_kv

    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    with open(os.path.join(root, "chipbench", "configs",
                           "command-a-plus-05-2026.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    spec = commanda.build(config)
    fwd = spec.decode_hooks["forward_cached"]
    c = MIXED
    nbper = c["ctx"] // BLOCK
    ring = -(-(c["window"] + c["chunk"]) // BLOCK) + 1

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def tables(rows):
        return {"full": sds(jax.ShapeDtypeStruct((rows, nbper), jnp.int32)),
                "window": sds(jax.ShapeDtypeStruct((rows, ring), jnp.int32))}

    def i32(*shape):
        return sds(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            spec.init_fn(jax.random.PRNGKey(0)))))
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: paged_kv.pack_pool(spec.decode_hooks["init_cache"](
            1 + c["slots"] * nbper, BLOCK, jnp.bfloat16,
            window_blocks=1 + c["slots"] * ring))))

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
        "paged_decode_attn": (decode_step, (
            params, pool, i32(slots), i32(slots), tables(slots))),
        "paged_prefill_attn": (prefill, (
            params, pool, i32(4, 128), tables(4), i32(4), i32(4)))}
    with pytest.MonkeyPatch.context() as patch:
        _trace_as_on_tpu(patch)
        patch.setattr(grouped_matmul, "interpret_kernels", lambda: False)
        compiled = {
            kernel: jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
            for kernel, (fn, args) in programs.items()}
    return compiled, params, pool


def test_compiled_two_kind_serving_programs_fit_and_alias_both_pools(
        two_kind_programs):
    """The RAG-chat cell's decode and prefill programs compile for a
    described v5e, alias all four pool leaves — two kinds — and hold
    temporaries under a gigabyte beside 9.47 GB of weights and 2.87 GB of
    pools."""
    compiled, _, pool = two_kind_programs
    assert set(pool) == {"k", "v", "kw", "vw"}
    assert pool["k"].shape[:2] == (1, 12289)
    assert pool["kw"].shape[:2] == (3, 3193)
    pool_bytes = sum(int(np.prod(a.shape)) * 2 for a in pool.values())
    for kernel, program in compiled.items():
        text = program.as_text()
        assert kernel in text and "moe_gmm" in text
        mem = program.memory_analysis()
        assert mem.temp_size_in_bytes < 1 << 30, (kernel, mem)
        assert mem.alias_size_in_bytes >= pool_bytes, (kernel, mem)


#: HLO element type -> bytes, for :func:`_writes_of_size`
_HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
                 "u32": 4, "f32": 4}


def _writes_of_size(compiled_text, sizes):
    """``(opcode, name)`` of every instruction of a compiled program's ENTRY
    computation that WRITES an array of one of ``sizes`` bytes — no
    ``parameter``, ``bitcast`` or ``get-tuple-element`` (names for what is
    already there).  What a fusion holds inside costs no memory traffic of
    its own; these do."""
    import re

    lines = compiled_text.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if line.startswith("ENTRY "))
    found = []
    for line in lines[start + 1:lines.index("}", start)]:
        parts = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if not parts:
            continue
        name, result, opcode = parts.groups()
        if opcode in ("parameter", "bitcast", "get-tuple-element"):
            continue
        written = {
            int(np.prod([int(d) for d in dims.split(",") if d]))
            * _HLO_ITEMSIZE[dtype]
            for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", result)
            if dtype in _HLO_ITEMSIZE}
        if written & set(sizes):
            found.append((opcode, name))
    return found


def test_compiled_two_kind_serving_programs_write_no_weight_sized_value(
        two_kind_programs):
    """ISSUE 50: neither program holds an ENTRY-level operation that writes
    a value of a block weight's size — a layer's ``q_w`` / ``o_w`` /
    ``shared_w*`` (134 MB) or its held experts' (537 MB).  The parent held
    eight in each: one ``slice_bitcast_fusion`` writing every layer's
    ``q_w[j]`` as its own transpose and four ``copy`` bringing each to row
    major — XLA folds the head split after the q projection
    (``llama._attend_cached``) into the dot and then re-lays-out the dot's
    WEIGHT ``[H, hd, D]`` on every call, 3.5 ms of each, where the product
    is 0.8 MB (decode) / 16.8 MB (a chunk).  (The slices themselves fuse
    into their matmuls: a layer of a one-period scan costs no copy.)"""
    compiled, params, _ = two_kind_programs
    weights = {name: int(np.prod(a.shape[1:])) * 2
               for name, a in params["blocks"].items()}
    large = {size for size in weights.values() if size >= 64 << 20}
    assert large == {weights["q_w"], weights["experts_w1"]} \
        and weights["q_w"] == 4096 * 16384 * 2
    for kernel, program in compiled.items():
        assert not _writes_of_size(program.as_text(), large), kernel


@pytest.mark.parametrize("index", ["static", "traced"])
@pytest.mark.parametrize("rows,t", [(24, 1), (4, 128)],
                         ids=["decode", "prefill-chunk"])
def test_a_head_split_moves_the_projections_product_not_its_weight(
        rows, t, index, one_chip):
    """ISSUE 50, in isolation: ``(x @ w[j])`` followed by the head split
    ``[B, T, H hd] -> [B, H, T, hd]``, compiled for a described v5e at
    Command A+'s ``q_w``.  Left free, XLA writes ``w[j]`` out transposed
    (a slice fusion + a ``copy``, 134 MB each) whether ``j`` is a constant
    or a traced scalar — slicing a stacked parameter is not what costs; behind
    ``optimization_barrier`` on the product nothing of the weight's size is
    written.  (If the first half ever fails, the compiler stopped folding
    and the barrier of ``_attend_cached`` can go.)"""
    d, h, hd = 4096, 128, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def written(barrier):
        def fn(x, w, j):
            y = x @ jax.lax.dynamic_index_in_dim(
                w, j if index == "traced" else 2, 0, keepdims=False)
            if barrier:
                y = jax.lax.optimization_barrier(y)
            return y.reshape(rows, t, h, hd).transpose(0, 2, 1, 3)

        text = jax.jit(fn).lower(
            sds((rows, t, d), jnp.bfloat16), sds((4, d, h * hd), jnp.bfloat16),
            sds((), jnp.int32)).compile().as_text()
        return _writes_of_size(text, {d * h * hd * 2})

    free = written(barrier=False)
    assert sorted(opcode for opcode, _ in free) == ["copy", "fusion"], free
    assert not written(barrier=True)


#: the long-decode cell (``mistral4-longdecode-closed``): Mistral Small 4 at
#: its published widths — 32 heads over a latent of 256 + a rope key of 64
#: in 384 lanes — 64 slots x 16,384 at the cell's block of 512
LATENT = dict(slots=64, ctx=16384, heads=32, width=384, rank=256, block=512,
              layers=6)
#: the latent layers of the state-decode cell
#: (``kimilinear-statedecode-closed``): Kimi Linear's 32 heads over a latent
#: of 512 + a key of 64 in 640 lanes, 192 slots x 4,352 at blocks of 256
LATENT_KIMI = dict(slots=192, ctx=4352, heads=32, width=640, rank=512,
                   block=256, layers=2)
#: blocks a loop iteration at the cells' blocks: (decode, verify T = 4, a
#: prefill step) and (decode, a prefill step)
LATENT_TILES, LATENT_KIMI_TILES = (4, 4, 2), (4, 2)


def _latent_walk_compiles(c, rows, t, block, one_chip):
    """Mosaic's own compile of the latent kernel for ``rows`` rows of ``t``
    positions at the widths ``c`` and ``block`` tokens a block; the tile
    the rule gives it is the landing buffer it is launched with, and two
    slots of it plus one update's scores lie inside the budget the rule
    names.  -> the tile (blocks)."""
    nbper = -(-c["ctx"] // block)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((c["layers"], 1 + c["slots"] * nbper, 1, block, c["width"]),
               jnp.bfloat16)
    fn = jax.jit(lambda q, p, bt, pos, valid:
                 da.paged_latent_attention_pallas(
                     q, p, bt, pos, rank=c["rank"], layer=0, valid=valid,
                     interpret=False))
    args = (sds((rows, c["heads"], t, c["width"]), jnp.bfloat16), pool,
            sds((rows, nbper), jnp.int32), sds((rows,), jnp.int32),
            sds((rows,), jnp.int32))
    lowered = fn.lower(*args)
    text = lowered.as_text()
    assert "tpu_custom_call" in text and da.latent_kernel_name(t) in text
    assert lowered.compile().memory_analysis().temp_size_in_bytes < 1 << 20
    tq, nt = da.latent_walk_shape(c["heads"], t, block, c["width"], 2, nbper)
    kernel, = (eqn.params["jaxpr"] for eqn in fn.trace(*args).jaxpr.eqns
               if eqn.primitive.name == "pallas_call")
    assert (2, nt, block, c["width"]) in [
        tuple(v.aval.shape) for v in kernel.invars]
    landing = 2 * nt * block * c["width"] * 2
    scores = tq * c["heads"] * nt * block * (4 + 2)
    assert landing + scores <= da._LATENT_VMEM_BUDGET
    return nt


@pytest.mark.parametrize("block", [32, 256, 512])
@pytest.mark.parametrize("rows,t", [(64, 1), (4, 128), (64, 4), (1, 512)],
                         ids=["decode", "prefill-chunk", "verify",
                              "prefill-1x512"])
def test_latent_walks_compile_at_the_long_decode_cells_shapes(rows, t, block,
                                                              one_chip):
    """ISSUE 39: Mosaic's own compile, for a described v5e, of the latent
    kernel as the decode step, the verify window and the ``[4, 128]`` /
    ``[1, 512]`` prefill calls launch it (``paged_latent_attn`` / ``_verify``
    / ``_prefill``), at the default block and at the cell's: ``[block,
    384]`` tiles copied out of the whole stack where it lies, no temporary.
    ISSUE 58: a TILE of them a loop iteration, many for the 32 query rows of
    a decode step and few for the 512 of a prefill step."""
    nt = _latent_walk_compiles(LATENT, rows, t, block, one_chip)
    if block == 512:
        assert nt == {1: LATENT_TILES[0], 4: LATENT_TILES[1]}.get(
            t, LATENT_TILES[2])


@pytest.mark.parametrize("rows,t", [(192, 1), (4, 128), (1, 512)],
                         ids=["decode", "prefill-chunk", "prefill-1x512"])
def test_latent_walks_compile_at_the_state_decode_cells_shapes(rows, t,
                                                               one_chip):
    """ISSUE 58: the same compile at Kimi Linear's widths (``[256, 640]``
    blocks, rank 512) as the state-decode cell's decode step and its two
    prefill rungs launch it: the tile follows the shapes."""
    nt = _latent_walk_compiles(LATENT_KIMI, rows, t, 256, one_chip)
    assert nt == (LATENT_KIMI_TILES[0] if t == 1 else LATENT_KIMI_TILES[1])


def test_compiled_latent_serving_programs_fit_and_alias_the_pool(
        as_on_tpu, one_chip, monkeypatch):
    """The long-decode cell's decode and prefill programs (Mistral Small 4
    at its published widths, this chip's share: 6 layers, 16 held of 128
    experts, 16,384 vocabulary rows) compile for a described v5e, alias the
    ONE pool leaf and hold temporaries under a gigabyte beside 5.75 GB of
    weights and 4.83 GB of pool."""
    import json
    import os

    from chipbench.families import mistral4
    from deepspeed_tpu.moe import grouped_matmul
    from deepspeed_tpu.ops import paged_kv

    monkeypatch.setattr(grouped_matmul, "interpret_kernels", lambda: False)
    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    with open(os.path.join(root, "chipbench", "configs",
                           "mistral-small-4-119b-2603.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    spec = mistral4.build(config)
    fwd = spec.decode_hooks["forward_cached"]
    c = LATENT
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
        lambda: paged_kv.pack_pool(spec.decode_hooks["init_cache"](
            1 + c["slots"] * nbper, c["block"], jnp.bfloat16))))
    assert set(pool) == {"latent"}
    assert pool["latent"].shape == (6, 2049, 1, 512, 384)

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
        "paged_latent_attn": (decode_step, (
            params, pool, i32(slots), i32(slots), i32(slots, nbper))),
        "paged_latent_prefill": (prefill, (
            params, pool, i32(4, 128), i32(4, nbper), i32(4), i32(4))),
        "paged_latent_prefill 1x512": (prefill, (
            params, pool, i32(1, 512), i32(1, nbper), i32(1), i32(1)))}
    pool_bytes = int(np.prod(pool["latent"].shape)) * 2
    for kernel, (fn, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        text = compiled.as_text()
        assert kernel.split()[0] in text and "moe_gmm" in text
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 1 << 30, (kernel, mem)
        assert mem.alias_size_in_bytes >= pool_bytes, (kernel, mem)


# ------------------------------------------------- the state kind (PR 51)
#: the state-decode cell (kimilinear-statedecode-closed): 192 slots x 4,352
#: positions, 6 KDA layers of 32 heads x 128 x 128 beside 2 latent layers
STATE = dict(slots=192, ctx=4352, layers=6, heads=32, width=128)


def test_kda_kernels_compile_at_the_state_cells_shapes(one_chip):
    """Mosaic's own compile, for a described v5e, of ``kda_step`` on the
    whole ``[6, 192, 32, 128, 128]`` float32 state leaf — aliased in and out,
    no temporary beside it — and of ``kda_chunk_state`` on a ``[4, 128]``
    prefill call's rows."""
    from deepspeed_tpu.ops import delta_rule as dr

    c = STATE
    h, w, rows = c["heads"], c["width"], c["slots"]

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaf = sds(c["layers"], rows, h, w, w)
    step = jax.jit(
        lambda q, k, v, g, b, leaf, l: dr.step(q, k, v, g, b, leaf, l,
                                               kernel=True, interpret=False),
        donate_argnums=(5,)).lower(
            sds(rows, h, w), sds(rows, h, w), sds(rows, h, w),
            sds(rows, h, w), sds(rows, h), leaf, sds(dtype=jnp.int32))
    assert 'kernel_name = "kda_step"' in step.as_text()
    mem = step.compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * int(np.prod(leaf.shape))
    assert mem.temp_size_in_bytes < 64 << 20, mem
    chunk = jax.jit(lambda *a: dr.chunked(*a, kernel=True, interpret=False)) \
        .lower(sds(4, h, 128, w), sds(4, h, 128, w), sds(4, h, 128, w),
               sds(4, h, 128, w), sds(4, h, 128), sds(4, h, w, w))
    assert 'kernel_name = "kda_chunk_state"' in chunk.as_text()
    assert chunk.compile().memory_analysis().temp_size_in_bytes < 256 << 20


def _programs_alias_the_whole_cache(programs, cache, also=()):
    """Compile ``programs`` (``{kernel names: (fn, args)}``, the cache tree
    argument 1, donated) for the described chip: every named kernel (and
    ``also``) is in the compiled text, the whole of ``cache`` is aliased in
    and out and the temporaries stay under 300 MB.  The cache's bytes."""
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in cache.values())
    for kernels, (fn, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        text = compiled.as_text()
        for kernel in kernels + tuple(also):
            assert kernel in text, kernel
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes, (kernels, mem)
        assert mem.temp_size_in_bytes < 300 << 20, (kernels, mem)
    return cache_bytes


@pytest.mark.limit(240)
def test_state_cells_programs_alias_the_whole_cache(as_on_tpu, one_chip,
                                                    monkeypatch):
    """The state-decode cell's decode and prefill programs (Kimi Linear at
    its published widths, this chip's share), compiled for a described v5e:
    the whole cache tree — latent pool, state and convolution tails, 4.64 GB
    — is aliased in and out, the three kernels of each program are there,
    and no temporary comes near a layer's slice of the state."""
    import json
    import os

    from chipbench.families import kimi_linear
    from deepspeed_tpu.moe import grouped_matmul
    from deepspeed_tpu.ops import delta_rule, paged_kv

    monkeypatch.setattr(grouped_matmul, "interpret_kernels", lambda: False)
    monkeypatch.setattr(delta_rule, "interpret_kernels", lambda: False)
    monkeypatch.setattr(delta_rule, "on_tpu", lambda: True)
    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    with open(os.path.join(root, "chipbench", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    spec = kimi_linear.build(config)
    fwd = spec.decode_hooks["forward_cached"]
    c = STATE
    slots = c["slots"]
    block = paged_kv.latent_block_tokens(576, 2, c["ctx"])
    nbper = paged_kv.blocks_for(c["ctx"], block)
    assert (block, nbper) == (256, 17)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return sds(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            spec.init_fn(jax.random.PRNGKey(0)))))
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: spec.decode_hooks["init_cache"](
            1 + slots * nbper, block, jnp.bfloat16, state_rows=slots)))
    assert {k: v.shape for k, v in cache.items()} == {
        "latent": (2, 3265, 1, 256, 640), "state": (6, 192, 32, 128, 128),
        "conv": (6, 192, 1, 3, 12288)}
    assert cache["state"].dtype == jnp.float32

    def decode_step(params, cache, tokens, lengths, bt):
        logits, cache, rec = fwd(params, tokens[:, None], cache, 0,
                                 lengths=lengths, block_tables={"full": bt},
                                 routing=True)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache, rec

    def prefill(params, cache, ids, bt, slot, base, valid):
        logits, cache, rec = fwd(
            params, ids, cache, base, lengths=valid,
            block_tables={"full": bt, "slot": slot}, routing=True)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache, rec

    programs = {
        ("kda_step", "paged_latent_attn"): (decode_step, (
            params, cache, i32(slots), i32(slots), i32(slots, nbper))),
        ("kda_chunk_state", "paged_latent_prefill"): (prefill, (
            params, cache, i32(4, 128), i32(4, nbper), i32(4), i32(4),
            i32(4))),
        # (the same kernels, the lone row's rung)
        ("paged_latent_prefill", "kda_chunk_state"): (prefill, (
            params, cache, i32(1, 512), i32(1, nbper), i32(1), i32(1),
            i32(1)))}
    # one layer's slice of the state is 403 MB
    _programs_alias_the_whole_cache(programs, cache, also=("moe_gmm",))


# ------------------------------------ the state kind beside K and V (PR 55)
#: the state chat cell (granite4h-chat-closed): 64 slots x 1,024 positions,
#: 36 state-space layers of 64 heads x 64 x 128 beside 4 attention layers
SSM = dict(slots=64, ctx=1024, block=32, layers=36, heads=64, width=64,
           state=128)


def test_ssd_kernels_compile_at_the_state_chat_cells_shapes(one_chip):
    """Mosaic's own compile, for a described v5e, of ``ssd_step`` on the
    whole ``[36, 64, 32, 128, 128]`` float32 state leaf (4.8 GB) — aliased in
    and out, no temporary beside it — and of ``ssd_chunk_state`` on every
    rung of the prefill ladder."""
    from deepspeed_tpu.ops import ssd

    c = SSM
    h, p, n, rows = c["heads"], c["width"], c["state"], c["slots"]

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaf = sds(c["layers"], rows, *ssd.packed_shape(h, p, n))
    assert leaf.shape == (36, 64, 32, 128, 128)
    step = jax.jit(
        lambda x, dt, a, b, cc, leaf, l: ssd.step(
            x, dt, a, b, cc, leaf, l, kernel=True, interpret=False),
        donate_argnums=(5,)).lower(
            sds(rows, h, p), sds(rows, h), sds(h), sds(rows, n), sds(rows, n),
            leaf, sds(dtype=jnp.int32))
    assert 'kernel_name = "ssd_step"' in step.as_text()
    mem = step.compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * int(np.prod(leaf.shape))
    assert mem.temp_size_in_bytes < 64 << 20, mem
    for b, t in ((4, 128), (2, 256), (1, 512)):
        chunk = jax.jit(
            lambda *a: ssd.chunked(*a, kernel=True, interpret=False)).lower(
                sds(b, t, h, p), sds(b, t, h), sds(h), sds(b, t, n),
                sds(b, t, n), sds(b, *ssd.packed_shape(h, p, n)))
        assert 'kernel_name = "ssd_chunk_state"' in chunk.as_text()
        assert chunk.compile().memory_analysis().temp_size_in_bytes \
            < 64 << 20


@pytest.mark.limit(240)
def test_state_chat_cells_programs_alias_the_whole_cache(as_on_tpu, one_chip,
                                                         monkeypatch):
    """The state chat cell's decode program and its widest prefill program
    (Granite 4.0-H Micro whole), compiled for a described v5e: the whole
    cache tree — K and V lane-packed, the state and the convolution tails,
    5.4 GB — is aliased in and out beside 6.4 GB of weights, both kinds'
    kernels are there, and no temporary comes near a layer's slice of the
    state (134 MB) times a few: no program holds a second copy of the
    leaf."""
    import json
    import os

    from chipbench.families import granite_hybrid
    from deepspeed_tpu.ops import paged_kv, ssd

    monkeypatch.setattr(ssd, "interpret_kernels", lambda: False)
    monkeypatch.setattr(ssd, "on_tpu", lambda: True)
    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    with open(os.path.join(root, "chipbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    spec = granite_hybrid.build(config)
    fwd = spec.decode_hooks["forward_cached"]
    c = SSM
    slots, nbper = c["slots"], paged_kv.blocks_for(c["ctx"], c["block"])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return sds(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            spec.init_fn(jax.random.PRNGKey(0)))))
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(lambda: {
        k: v if k in paged_kv.STATE_LEAVES else paged_kv.pack_pool(v)
        for k, v in spec.decode_hooks["init_cache"](
            1 + slots * nbper, c["block"], jnp.bfloat16,
            state_rows=slots).items()}))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (4, 2049, 8, 16, 128), "v": (4, 2049, 8, 16, 128),
        "state": (36, 64, 32, 128, 128), "conv": (36, 64, 1, 3, 4352)}

    def decode_step(params, cache, tokens, lengths, bt):
        logits, cache = fwd(params, tokens[:, None], cache, 0,
                            lengths=lengths, block_tables={"full": bt})
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def prefill(params, cache, ids, bt, slot, base, valid):
        logits, cache = fwd(params, ids, cache, base, lengths=valid,
                            block_tables={"full": bt, "slot": slot})
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    programs = {
        ("ssd_step", "paged_decode_attn"): (decode_step, (
            params, cache, i32(slots), i32(slots), i32(slots, nbper))),
        ("ssd_chunk_state", "paged_prefill_attn"): (prefill, (
            params, cache, i32(1, 512), i32(1, nbper), i32(1), i32(1),
            i32(1)))}
    assert round(_programs_alias_the_whole_cache(programs, cache) / 1e9,
                 2) == 5.43


@pytest.mark.limit(180)
def test_state_chat_cells_timed_programs_are_tied_to_the_float32_pass(
        as_on_tpu, one_chip, monkeypatch):
    """``serve_ssm.check_state_programs`` on a bf16 engine's OWN decode and
    prefill bodies, lowered for a described v5e at the rehearsal's widths:
    their state-kind layers call ``ssd_step`` / ``ssd_chunk_state`` on
    float32 operands and a float32 leaf whatever the activations' dtype, so
    the programs the window times are the ones the float32 limit is read
    off — and one bfloat16 operand in one of them is refused."""
    import json
    import os

    import deepspeed_tpu
    from chipbench.drivers import serve_ssm
    from chipbench.families import granite_hybrid
    from deepspeed_tpu.ops import ssd
    from deepspeed_tpu.telemetry.flops import ServingFlopsProfiler

    monkeypatch.setattr(ssd, "interpret_kernels", lambda: False)
    monkeypatch.setattr(ssd, "on_tpu", lambda: True)
    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    with open(os.path.join(root, "chipbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    config.update(config.pop("rehearse"))
    spec = granite_hybrid.build(config)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                    spec.init_fn(jax.random.PRNGKey(0)))
    srv = deepspeed_tpu.init_serving(
        spec, config={"dtype": "bf16"}, params=params, slots=4,
        max_seq_len=128, block_size=16, prefill_chunk=16)
    srv._get_decode_fn(), srv._get_prefill_fn()   # the bodies; no compile
    abstract, doctor = ServingFlopsProfiler._abstract_args, [lambda t: t]

    class Lowered:
        def __init__(self, lowered):
            self.lowered = lowered

        def as_text(self):
            return doctor[0](self.lowered.as_text())

    def lower(self, family, rung=None, sampling=False):
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            abstract(self, family, rung, sampling))
        with srv._tp_ctx():
            return Lowered(jax.jit(srv._program_bodies[family]).lower(*args))

    monkeypatch.setattr(ServingFlopsProfiler, "lower", lower)
    notes = []
    job = type("Job", (), {"note": staticmethod(notes.append)})
    profiler, programs = ServingFlopsProfiler(srv), {}
    for rung in (None, *srv._rungs):
        family = "decode" if rung is None else "prefill"
        text = profiler.lower(family, rung, sampling=True).as_text()
        programs[serve_ssm._program_name(rung)] = {
            "family": family, "rung": rung,
            "bodies": srv.stats()["kv_state"]["ssd"][family],
            "kernels": serve_ssm.state_kernels(text, "ssd")}
    assert set(programs) == {"decode", "prefill[4x16]", "prefill[1x64]"}
    step, = programs["decode"]["kernels"]
    assert step[0] == "ssd_step" and set(step[1]) == {"i32", "f32"} \
        and set(step[2]) == {"f32"}
    chunk, = programs["prefill[1x64]"]["kernels"]
    assert chunk[0] == "ssd_chunk_state" and set(chunk[1] + chunk[2]) \
        == {"f32"}
    # the float32 pass drives the narrow rung alone: the wide row's program
    # is held to its family's
    programs = {"served": programs, "float32": {
        k: v for k, v in programs.items() if k != "prefill[1x64]"}}
    got = serve_ssm.check_state_programs(job, srv, programs)
    assert got["ok"] and got["state_leaf"] == "float32", notes
    assert all(got[name]["held"] for name in programs["served"])

    def one_bf16_operand(text):
        lines = text.splitlines()
        for at, line in enumerate(lines):
            if 'kernel_name = "ssd_chunk_state"' in line:
                head, types = line.rsplit("} : ", 1)
                lines[at] = head + "} : " + types.replace("f32>", "bf16>", 1)
        return "\n".join(lines)

    doctor[0] = one_bf16_operand
    got = serve_ssm.check_state_programs(job, srv, programs)
    assert not got["ok"] and got["decode"]["held"]
    assert not got["prefill[1x64]"]["held"] and "REFUSED" in notes[-1]
    assert "bf16" in got["prefill[1x64]"]["kernels"][0][1]
    srv.close()


# ------------------------- power retention on the state kind, no pool (PR 57)
#: ``brumby-longdoc-closed``'s shapes: Brumby-14B-Base's published widths,
#: one pipeline stage of ten layers
POWER = dict(slots=12, layers=10, heads=40, kv_heads=8, width=128)


def test_power_kernels_compile_at_the_longdoc_cells_shapes(one_chip):
    """Mosaic's own compile, for a described v5e, of ``power_step`` on the
    whole ``[10, 12, 8, 65, 128, 128]`` float32 state leaf (4.1 GB) and its
    normaliser — both aliased in and out, no temporary beside them — and of
    ``power_chunk_state`` on every rung of the prefill ladder, the gathered
    rows' states advanced where they lie."""
    from deepspeed_tpu.ops import power_retention as pr

    c = POWER
    hq, h, n, rows = c["heads"], c["kv_heads"], c["width"], c["slots"]

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaf = sds(c["layers"], rows, *pr.stored_shape(h, n))
    zleaf = sds(*leaf.shape[:-2], n)
    assert leaf.shape == (10, 12, 8, 65, 128, 128)
    step = jax.jit(
        lambda q, k, v, lg, leaf, zleaf, l: pr.step(
            q, k, v, lg, leaf, zleaf, l, kernel=True, interpret=False),
        donate_argnums=(4, 5)).lower(
            sds(rows, hq, n), sds(rows, h, n), sds(rows, h, n), sds(rows, h),
            leaf, zleaf, sds(dtype=jnp.int32))
    assert 'kernel_name = "power_step"' in step.as_text()
    mem = step.compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * (
        int(np.prod(leaf.shape)) + int(np.prod(zleaf.shape)))
    assert mem.temp_size_in_bytes < 64 << 20, mem
    import contextlib

    for b, t in ((4, 128), (2, 256), (1, 512)):
        # (the comparison's float32 pass traces under full precision)
        with jax.default_matmul_precision("highest") if b == 1 \
                else contextlib.nullcontext():
            chunk = jax.jit(
                lambda *a: pr.chunked(*a, kernel=True, interpret=False),
                donate_argnums=(4, 5)).lower(
                    sds(b, t, hq, n), sds(b, t, h, n), sds(b, t, h, n),
                    sds(b, t, h), sds(b, *leaf.shape[2:]),
                    sds(b, *zleaf.shape[2:]))
        assert 'kernel_name = "power_chunk_state"' in chunk.as_text()
        mem = chunk.compile().memory_analysis()
        assert mem.alias_size_in_bytes >= 4 * b * int(
            np.prod(leaf.shape[2:]))
        assert mem.temp_size_in_bytes < 64 << 20, mem


@pytest.mark.limit(300)
def test_longdoc_cells_programs_alias_the_whole_cache(as_on_tpu, one_chip,
                                                      monkeypatch):
    """The long-document cell's decode program and its three prefill
    programs (Brumby-14B-Base at its published widths, ten layers), compiled
    for a described v5e: the whole cache tree — NO paged leaf: the state and
    its normaliser, 4.12 GB — is aliased in and out beside 9.7 GB of
    weights, the kernels are there, and no temporary comes near the leaf
    (a row's state a layer is 34 MB, gathered and scattered a call's rows at
    a time): no program holds a second copy of it, and a window at base 0
    resets its rows inside the program."""
    import json
    import os

    from chipbench.families import brumby
    from deepspeed_tpu.ops import paged_kv, power_retention as pr

    monkeypatch.setattr(pr, "interpret_kernels", lambda: False)
    monkeypatch.setattr(pr, "on_tpu", lambda: True)
    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    with open(os.path.join(root, "chipbench", "configs",
                           "Brumby-14B-Base.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    spec = brumby.build(config)
    fwd = spec.decode_hooks["forward_cached"]
    slots = POWER["slots"]

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return sds(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            spec.init_fn(jax.random.PRNGKey(0)))))
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: spec.decode_hooks["init_cache"](
            0, 32, jnp.bfloat16, state_rows=slots)))
    assert set(cache) <= set(paged_kv.STATE_LEAVES)
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "state": ((10, 12, 8, 65, 128, 128), "float32"),
        "z": ((10, 12, 8, 65, 128), "float32")}

    def decode_step(params, cache, tokens, lengths, slot):
        logits, cache = fwd(params, tokens[:, None], cache, 0,
                            lengths=lengths, block_tables={"slot": slot})
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def prefill(params, cache, ids, slot, base, valid):
        logits, cache = fwd(params, ids, cache, base, lengths=valid,
                            block_tables={"slot": slot})
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    programs = {"decode": ("power_step", decode_step, (
        params, cache, i32(slots), i32(slots), i32(slots)))}
    for rows, width in ((4, 128), (2, 256), (1, 512)):
        programs[f"prefill[{rows}x{width}]"] = (
            "power_chunk_state", prefill, (
                params, cache, i32(rows, width), i32(rows), i32(rows),
                i32(rows)))
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in cache.values())
    assert round(cache_bytes / 1e9, 2) == 4.12
    temps = {}
    for name, (kernel, fn, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        assert kernel in compiled.as_text(), name
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes, (name, mem)
        temps[name] = mem.temp_size_in_bytes
    # a call's rows' states gathered, reset and advanced (34 MB a row a
    # layer, three times over at most): 16 GB less 9.72 GB of weights and
    # the 4.12 GB leaf leave 2 GB
    assert max(temps.values()) < 640 << 20, temps
    print("temporaries by program, MB:",
          {k: round(v / 2 ** 20) for k, v in temps.items()})


def test_pipelined_zero3_step_has_no_activation_exchange_in_its_loops(
        v5e_2x2):
    """ISSUE 60: a small OPT's ZeRO-3 gradient step compiled for the four
    described chips, its layer loop plain and pipelined
    (``liveness.scan_layers_prefetched``), read the ``engine.collectives``
    way.  Pipelined, the two loops gather a layer's four weight matrices
    each (vectors are gathered whole, outside), most of them in asynchronous
    chains, and move no activation (no ``all-to-all``); left to the
    partitioner, more gathers sit plain in the loops.  (How many a compiler
    leaves plain is its scheduler's to say — at the four-chip cell's size,
    memory: PERF.md section 6, PR 60 — so the bound here is the plain
    scan's own count.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.models import opt
    from deepspeed_tpu.parallel.topology import DATA_AXES, MeshTopology
    from deepspeed_tpu.runtime.zero import collectives, liveness
    from deepspeed_tpu.runtime.zero.sharding import ZeroShardingPlan

    mesh = MeshTopology(devices=list(v5e_2x2.devices)).mesh
    rep = NamedSharding(mesh, P())
    found = {}
    for pipelined in (False, True):
        cfg = opt.OPTConfig(vocab_size=512, max_seq_len=256, num_layers=4,
                            num_heads=8, hidden_size=512, ffn_size=2048,
                            remat=True, use_flash=False)
        spec = opt.build(cfg)
        shapes = jax.eval_shape(lambda: spec.init_fn(jax.random.PRNGKey(0)))
        shardings = ZeroShardingPlan(3, mesh).param_shardings(shapes)
        if pipelined:
            cfg.scan_prefetch = liveness.LayerShardings(
                sharded=shardings["blocks"],
                gathered=jax.tree_util.tree_map(lambda _: rep,
                                                shardings["blocks"]))
        params = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            shapes, shardings)
        batch = {"input_ids": jax.ShapeDtypeStruct(
            (8, 257), jnp.int32, sharding=NamedSharding(mesh, P(DATA_AXES)))}

        def grads(p, b):
            return jax.value_and_grad(lambda p: spec.loss_fn(
                jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p),
                b, None, True))(p)

        with mesh:
            text = jax.jit(grads, out_shardings=(rep, shardings)).lower(
                params, batch).compile().as_text()
        found[pipelined] = collectives.count(text)
        print("pipelined" if pipelined else "plain scan",
              collectives.line(found[pipelined]))
    plain, piped = found[False], found[True]
    assert piped["all-to-all"]["in_loop"] == 0
    gathers = piped["all-gather"]
    assert gathers["in_loop"] == 8            # 4 matrices x 2 loops
    assert gathers["fused"] >= gathers["plain"]
    assert gathers["plain"] <= plain["all-gather"]["plain"]
