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
import pytest

from deepspeed_tpu.ops import decode_attention as da
from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.ops import quantized_matmul as qmm

#: (name, query heads == KV heads, head_dim, context): MHA families
ATTN_SHAPES = [("opt-1.3b", 32, 64, 2048), ("opt-6.7b", 32, 128, 2048),
               ("gpt2-125m", 12, 64, 1024)]
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


@pytest.mark.parametrize("name,h,hd,ctx", ATTN_SHAPES)
def test_contiguous_decode_lowers(name, h, hd, ctx):
    q = _sds((SLOTS, h, 1, hd), jnp.bfloat16)
    cache = _sds((SLOTS, h, ctx, hd), jnp.bfloat16)
    _lower_tpu(lambda q, k, v, pos: da.decode_attention_pallas(
        q, k, v, pos, interpret=False), q, cache, cache,
        _sds((SLOTS,), jnp.int32))


def test_flash_train_step_kernels_lower():
    """bench.py / chip_smoke.py training config: flash v2, 1024x1024
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
