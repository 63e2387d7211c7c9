"""Fused INT8-weight matmul in Pallas — dequantization INSIDE the kernel.

Reference parity: the INT8 inference GEMMs of DS-Inference
(``csrc/transformer/inference/csrc/gelu.cu`` dequant epilogues and the
``GroupQuantizer`` weight path of ``module_inject/replace_module.py:152``):
the reference never materializes an fp16 copy of an int8 weight in HBM —
dequant happens in the GEMM's shared-memory staging.

TPU design: XLA cannot fuse an elementwise producer into a ``dot`` operand,
so the point-of-use ``dequantize() @`` pattern (models/cached.py
``_maybe_dequant``) round-trips a full bf16 copy of every weight through HBM
each decode step: int8 read + bf16 write + bf16 read = ~5 bytes/param where
the int8 payload is 1.  At bs=1 decode — pure HBM-bandwidth-bound matvecs —
that is the whole latency.  This kernel streams int8 blocks into VMEM,
expands ``q * scale`` on the VPU, and feeds the MXU directly: HBM traffic is
the int8 payload + scales (~1.06 bytes/param), a ~5x cut.

Weight record format is :mod:`deepspeed_tpu.ops.quantization`:
``{"q": int8 [K, N], "scale": f32 [K, N/G]}`` (groups along the LAST axis).
The operands are restructured to ``q [K, N/G, G]`` / ``scale [K, N/G, 1]``
outside the kernel so every in-kernel op is lane-legal; that requires
``G % 128 == 0`` (the serving default, inference/config.py).  Symmetric
records only; asymmetric ("zero"), non-tiling, or off-lane-group records
fall back to dequant+matmul.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.platform import interpret_kernels



#: Trace-time gates set by the engine that owns the current trace (same
#: single-active-engine contract as model-config knobs, see
#: runtime/zero/liveness.py).  A pallas_call is opaque to the GSPMD
#: partitioner, so under tensor parallelism the WEIGHT-ONLY fused kernel is
#: disabled (``kernel_ok=False`` — its dequant+matmul fallback shards fine).
#: The W8A8 kernel instead goes through :func:`_w8a8_tp_call`, a
#: ``custom_partitioning`` wrapper that teaches the partitioner the two TP
#: layouts (``w8a8_tp=True``): column-parallel (N sharded — every shard runs
#: the s8 kernel on its weight slice, no communication) and row-parallel
#: (K sharded — local partial on the s8 kernel, one psum after).
#: decode-shaped row cap shared by w8a8_matmul / w8a8_matmul_stacked and
#: the models' indexed-decode gate (models/cached.py use_indexed_decode)
W8A8_MAX_ROWS = 8


_KERNEL_OK = True
_W8A8_TP = False


def configure(kernel_ok: bool, w8a8_tp: bool = False) -> None:
    global _KERNEL_OK, _W8A8_TP
    _KERNEL_OK = bool(kernel_ok)
    _W8A8_TP = bool(w8a8_tp)


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk, gpb, g = q_ref.shape
    # Decode is per-grid-STEP-overhead-bound, so steps must be large; VMEM
    # is bounded by the dequant intermediates, not the int8 block.  Hence:
    # big fetch blocks (bk x bn int8, the pipelining unit), dequantized in
    # small static sub-tiles via REF slicing (loading q_ref[...] whole
    # would put the full block in vector registers).  bf16 dequant: int8
    # values are exact in bf16 and the scale rounding (2^-8 rel) sits
    # below the int8 quantization error itself.  The [sub, gpb, g] ->
    # [sub, bn] merge is the lane-aligned reshape Mosaic supports (a
    # g < 128 split is an unsupported relayout; hence the G % 128
    # eligibility rule and the host-side 3D restructuring).
    _, b, sub = x_ref.shape                        # x_ref: [bk//sub, B, sub]

    def tile(t, _):
        qt = q_ref[pl.ds(t * sub, sub)]            # [sub, gpb, g] int8
        st = s_ref[pl.ds(t * sub, sub)]            # [sub, gpb, 1] f32
        w = (qt.astype(jnp.bfloat16) *
             st.astype(jnp.bfloat16)).reshape(sub, gpb * g)
        xt = x_ref[pl.ds(t, 1)].reshape(b, sub)    # major-dim slice of x
        acc_ref[...] += jax.lax.dot(xt.astype(jnp.bfloat16), w,
                                    preferred_element_type=jnp.float32)
        return _

    # rolled (static-trip) loop: one set of dequant intermediates is
    # reused across sub-tiles — unrolling kept them all live and blew the
    # scoped-VMEM stack (40MB at bk=2048); jax.lax.dynamic_slice on a
    # loaded VALUE is unimplemented in Mosaic, hence the [bk//sub, B, sub]
    # x staging that makes every sub-tile a major-dim REF slice
    jax.lax.fori_loop(0, bk // sub, tile, None)

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick_block(dim: int, group: int, cap: int, quantum: int) -> int:
    """Largest divisor of ``dim`` that is <= cap, a multiple of ``quantum``
    and of ``group`` (so blocks span whole quant groups)."""
    step = quantum
    while step % group:
        step += quantum
    best = 0
    b = step
    while b <= min(dim, cap):
        if dim % b == 0:
            best = b
        b += step
    return best


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_k",
                                             "block_n", "interpret"))
def _qmm_call(x2d, q3, scale3, out_dtype, block_k, block_n, interpret):
    b, k_dim = x2d.shape
    _, n_groups, g = q3.shape
    n_dim = n_groups * g
    gpb = block_n // g
    # sub-tile rows capped so the bf16 cast + product intermediates stay
    # ~4MB: sub * bn <= 1M elements
    sub = _pick_block(block_k, 1, max(8, (2 ** 20) // block_n), 8) or block_k
    grid = (n_dim // block_n, k_dim // block_k)
    x3 = x2d.reshape(b, k_dim // sub, sub).swapaxes(0, 1)
    return pl.pallas_call(
        functools.partial(_kernel, nk=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k // sub, b, sub),
                         lambda n, ki: (ki, 0, 0)),
            pl.BlockSpec((block_k, gpb, g), lambda n, ki: (ki, n, 0)),
            pl.BlockSpec((block_k, gpb, 1), lambda n, ki: (ki, n, 0)),
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda n, ki: (0, n)),
        out_shape=jax.ShapeDtypeStruct((b, n_dim), out_dtype),
        scratch_shapes=[pltpu.VMEM((b, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="qmm_weight_only",
    )(x3, q3, scale3)


def quantized_matmul(x, rec: dict, out_dtype=None, *, block_k: int = None,
                     block_n: int = None, max_rows: int = 256):
    """``x @ dequant(rec)`` without materializing the dequantized weight.

    x: [..., K] float; rec: symmetric int8 record (see module docstring).

    OFF BY DEFAULT (``DS_QMM=1`` opts in): measured end-to-end on the v5e
    the fused path LOSES to XLA's dequantize+matmul at every model size
    (OPT-1.3B 68.5 vs 82.2 tok/s; OPT-6.7B 10.1 vs 12.1) — the in-kernel
    int8->bf16 convert is a cross-tiling relayout costing ~7us/MB, 6x the
    fetch+VPU theory, and XLA runs its 5-byte/param materializing pipeline
    at full HBM bandwidth (round-4 builder measurement).  The kernel is
    kept as the scaffold for a true s8-MXU (W8A8) path, which avoids the
    relayout entirely.  Also falls back when the shapes don't tile, the
    record is asymmetric, or the row count exceeds the accumulator budget
    (long prefills are compute-bound and amortize the dequant copy).
    """
    from . import quantization as quant

    q, scale = rec["q"], rec["scale"]
    k_dim, n_dim = q.shape[-2], q.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    g = n_dim // scale.shape[-1]
    # FULL-row n blocks (bn = N): a single output block avoids the output
    # double-buffering that put mid-size bn configs over the scoped-VMEM
    # limit (bk=512/bn=4096 fails at 20.1M where bk=512/bn=8192 compiles),
    # and decode is per-grid-step-overhead-bound so steps should be as
    # large as VMEM allows anyway.  k blocks are sized by a per-step byte
    # budget (the double-buffered int8 fetch granularity).
    if block_n is None and "DS_QMM_BN" in os.environ:
        block_n = int(os.environ["DS_QMM_BN"])
    bn = n_dim if block_n is None else _pick_block(n_dim, g, block_n, 128)
    if block_k is None:
        step_bytes = int(float(os.environ.get("DS_QMM_STEP_MB", 1)) * 2**20)
        cap_k = max(1, step_bytes // max(bn, 1))
        block_k = cap_k
    bk = _pick_block(k_dim, 1, block_k, 256) or \
        _pick_block(k_dim, 1, block_k, 8)
    # the f32 accumulator + output block are [rows, bn]-sized: with
    # full-row bn the row budget shrinks accordingly (128 rows x 16384
    # lanes is an 8MB accumulator — prefill-sized inputs take the
    # dequant path, which amortizes its bf16 materialization over the
    # row count anyway)
    row_cap = min(max_rows, max(8, (2 ** 19) // max(bn, 1)))
    eligible = (
        _KERNEL_OK
        and os.environ.get("DS_QMM", "0") == "1"
        and q.ndim == 2
        and "zero" not in rec
        and rows <= row_cap
        and g % 128 == 0          # lane-aligned groups (see _kernel)
        and bk > 0 and bn >= g
    )
    if not eligible:
        return x @ quant.dequantize(rec, x.dtype)
    out_dtype = out_dtype or x.dtype
    x2d = x.reshape(rows, k_dim)
    out = _qmm_call(x2d, q.reshape(k_dim, n_dim // g, g),
                    scale.reshape(k_dim, n_dim // g, 1),
                    out_dtype, bk, bn, interpret_kernels())
    return out.reshape(lead + (n_dim,))


# ------------------------------------------------------------------ W8A8 path
# True s8-MXU serving matmul: activations quantize per k-chunk IN-KERNEL,
# the dot runs int8 x int8 -> int32 natively, and (activation_scale x
# K-grouped weight scale) applies to the partial AFTER the dot — no
# int8->bf16 weight relayout anywhere (the cost that sank the weight-only
# fused kernel, see module docstring).  Records come from
# quantization.quantize_k_grouped; accuracy trades ~0.5-1% activation
# rounding for the bandwidth floor (reference analog: MoQ weight+activation
# INT8, deepspeed/compression/basic_layer.py QuantAct).


def _w8a8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nk: int,
                 k_group: int, stacked: bool = False):
    # ``stacked``: q_ref/s_ref carry a leading unit layer dim (the stacked
    # entry's scalar-prefetch index maps picked the layer; the body is
    # otherwise identical, so both paths share this one implementation).
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk = q_ref.shape[1] if stacked else q_ref.shape[0]
    _, b, sub = x_ref.shape                       # sub == k_group

    def tile(t, _):
        xt = x_ref[pl.ds(t, 1)].reshape(b, sub).astype(jnp.float32)
        ax = jnp.max(jnp.abs(xt), axis=1, keepdims=True)      # [B, 1]
        ax = jnp.where(ax == 0, 1.0, ax)
        xq = jnp.clip(jnp.round(xt * (127.0 / ax)),
                      -127, 127).astype(jnp.int8)
        if stacked:
            qt = q_ref[0, pl.ds(t * sub, sub)]                # [sub, bn] s8
            st = s_ref[0, pl.ds(t, 1)].reshape(1, -1)         # [1, bn] f32
        else:
            qt = q_ref[pl.ds(t * sub, sub)]                   # [sub, bn] s8
            st = s_ref[pl.ds(t, 1)].reshape(1, -1)            # [1, bn] f32
        part = jax.lax.dot(xq, qt,
                           preferred_element_type=jnp.int32)  # s8 MXU
        acc_ref[...] += part.astype(jnp.float32) * (ax / 127.0) * st
        return _

    jax.lax.fori_loop(0, bk // sub, tile, None)

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _qmm_vmem_limit():
    """DS_QMM_VMEM_MB raises the w8a8 kernel's per-kernel scoped-vmem
    budget so larger DS_QMM_STEP_MB fetch blocks (2x double-buffered in
    VMEM) can compile for bandwidth experiments.  Resolved OUTSIDE the
    jitted call and passed as a static arg so it keys the jit cache —
    sweep scripts that change it mid-process get fresh compiles."""
    v = os.environ.get("DS_QMM_VMEM_MB")
    return int(float(v) * 2**20) if v else None


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_k",
                                             "interpret", "vmem_limit"))
def _w8a8_call(x2d, qk, kscale, out_dtype, block_k, interpret,
               vmem_limit=None):
    b, k_dim = x2d.shape
    n_dim = qk.shape[1]
    k_group = k_dim // kscale.shape[0]
    grid = (1, k_dim // block_k)
    x3 = x2d.reshape(b, k_dim // k_group, k_group).swapaxes(0, 1)
    return pl.pallas_call(
        functools.partial(_w8a8_kernel, nk=grid[1], k_group=k_group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k // k_group, b, k_group),
                         lambda n, ki: (ki, 0, 0)),
            pl.BlockSpec((block_k, n_dim), lambda n, ki: (ki, 0)),
            pl.BlockSpec((block_k // k_group, 1, n_dim),
                         lambda n, ki: (ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b, n_dim), lambda n, ki: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_dim), out_dtype),
        scratch_shapes=[pltpu.VMEM((b, n_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="qmm_w8a8_matmul",
    )(x3, qk, kscale)


def _w8a8_pick_bk(k_dim, kg_blocks, n_dim, block_k):
    """Shared W8A8 block sizing: returns ``(bk, k_group)`` with ``bk == 0``
    when the shape cannot tile (the per-layer and stacked entries must stay
    eligible under IDENTICAL conditions)."""
    k_group = k_dim // kg_blocks if kg_blocks else 0
    if not (k_group and k_dim % kg_blocks == 0):
        return 0, k_group
    if block_k is None:
        step_bytes = int(float(os.environ.get("DS_QMM_STEP_MB", 4)) * 2**20)
        block_k = max(1, step_bytes // max(n_dim, 1))
    return _pick_block(k_dim, k_group, block_k, k_group), k_group


def _w8a8_local(x2d, qk, kscale3, block_k=None, out_dtype=None):
    """One shard's worth of the W8A8 matmul: the s8-MXU kernel when the
    LOCAL shapes tile (lane-aligned N, whole k-groups), exact dequant+matmul
    otherwise.  Correct for any shapes, so the custom_partitioning lowering
    below can call it on whatever slice the partitioner hands each device.
    ``out_dtype`` keeps row-parallel partials in f32 so the cross-shard psum
    adds no rounding the unsharded kernel doesn't have."""
    from . import quantization as quant

    out_dtype = out_dtype or x2d.dtype
    k_dim, n_dim = qk.shape
    bk, _ = _w8a8_pick_bk(k_dim, kscale3.shape[0], n_dim, block_k)
    if (bk > 0 and n_dim % 128 == 0
            and os.environ.get("DS_W8A8", "1") != "0"):
        return _w8a8_call(x2d, qk, kscale3, out_dtype, bk, interpret_kernels(),
                          vmem_limit=_qmm_vmem_limit())
    deq = quant.dequantize_k({"qk": qk, "kscale": kscale3}, x2d.dtype)
    return jax.lax.dot(x2d, deq, preferred_element_type=out_dtype)


from ..utils.sharding import axis_size  # noqa: E402  (shared helper)


def _qk_spec(arg_shapes):
    spec = getattr(arg_shapes[1].sharding, "spec", None)
    spec = tuple(spec) if spec is not None else ()
    return (spec + (None, None))[:2]


def _b_spec(arg_shapes, *exclude):
    """Batch-dim sharding of the x operand, dropped when it collides with
    an axis the weight layout already uses."""
    spec = getattr(arg_shapes[0].sharding, "spec", None)
    b_s = tuple(spec)[0] if spec else None
    return None if b_s in exclude else b_s


def _w8a8_infer_sharding(mesh, arg_shapes, result_shape):
    from jax.sharding import NamedSharding, PartitionSpec as P

    _, n_s = _qk_spec(arg_shapes)
    return NamedSharding(mesh, P(_b_spec(arg_shapes, n_s), n_s))


def _w8a8_partition(mesh, arg_shapes, result_shape):
    from jax.sharding import NamedSharding, PartitionSpec as P

    k_s, n_s = _qk_spec(arg_shapes)
    k_dim, n_dim = arg_shapes[1].shape
    kg_blocks = arg_shapes[2].shape[0]
    rep = NamedSharding(mesh, P())
    # both lowerings return f32: the single value-rounding cast to the
    # caller's out_dtype happens OUTSIDE the custom_partitioning call
    # (w8a8_matmul), so tp=N matches the unsharded kernel's
    # accumulate-in-f32-round-once numerics for every out_dtype.  The
    # batch dim keeps the x operand's sharding (dp serving: each replica
    # computes only its batch slice).
    # Column shards never split quant groups (scales are per-column), so any
    # even N split is exact — shards whose local N is off-lane just run the
    # sharded dequant+dot inside _w8a8_local; K shards must keep whole
    # k-groups or the record's chunking misaligns (gather + warn below)
    if n_s is not None and n_dim % axis_size(mesh, n_s) == 0:
        b_s = _b_spec(arg_shapes, n_s)
        arg_sh = (NamedSharding(mesh, P(b_s, None)),
                  NamedSharding(mesh, P(None, n_s)),
                  NamedSharding(mesh, P(None, None, n_s)))
        return (mesh, _w8a8_tp_body,
                NamedSharding(mesh, P(b_s, n_s)), arg_sh)
    if k_s is not None and kg_blocks % axis_size(mesh, k_s) == 0:
        b_s = _b_spec(arg_shapes, k_s)
        arg_sh = (NamedSharding(mesh, P(b_s, k_s)),
                  NamedSharding(mesh, P(k_s, None)),
                  NamedSharding(mesh, P(k_s, None, None)))

        def lower(x2d, qk, kscale3):
            # f32 partials: each shard rounds once AFTER the full local K
            # reduction, and the psum itself runs in f32 — matching the
            # unsharded kernel's single-rounding accumulation
            part = _w8a8_local(x2d, qk, kscale3, out_dtype=jnp.float32)
            return jax.lax.psum(part, k_s)

        return mesh, lower, NamedSharding(mesh, P(b_s, None)), arg_sh
    if k_s is not None or n_s is not None:
        # an aligned sharding was suggested but the shard slices would split
        # k-groups: correctness demands a gathered lowering.  This defeats
        # the TP memory goal for THIS weight, so say so (once per shape —
        # the partition callback refires on every retrace) instead of
        # silently eating the gather.
        from ..utils.logging import warning_once

        warning_once(
            f"w8a8 weight [{k_dim}, {n_dim}] (K/G={kg_blocks}) cannot "
            f"shard over spec ({k_s}, {n_s}) without splitting quant "
            f"groups — this matmul runs GATHERED on every device; pick a "
            f"k_group-aligned tp degree to keep it sharded")
    b_s = _b_spec(arg_shapes, k_s, n_s)
    return (mesh, _w8a8_tp_body, NamedSharding(mesh, P(b_s, None)),
            (NamedSharding(mesh, P(b_s, None)), rep, rep))


from jax.experimental.custom_partitioning import custom_partitioning  # noqa: E402

def _w8a8_tp_body(x2d, qk, kscale3):
    # 3-arg body for custom_partitioning: the wrapper derives its operand
    # arity from the signature, so _w8a8_local's block_k/out_dtype knobs
    # must not leak into it.  Always f32 out — every lowering (replicated,
    # column, row+psum) then carries the full accumulator precision and
    # w8a8_matmul's single outer cast supplies the caller's out_dtype,
    # keeping tp=N bit-compatible with the unsharded kernel's
    # round-once-from-f32 result for out_dtypes wider than x.dtype.
    return _w8a8_local(x2d, qk, kscale3, out_dtype=jnp.float32)


#: GSPMD/shardy-aware entry: same math as :func:`_w8a8_local`, but the
#: partitioner is told how to run it sharded instead of gathering the weight
#: (which would defeat TP serving).  The k factor is declared a reduction so
#: shardy's propagation knows a K-sharded weight still yields a full [B, N]
#: result; the partition lowering owns the actual psum.
_w8a8_tp_call = custom_partitioning(_w8a8_tp_body)
_w8a8_tp_call.def_partition(
    partition=_w8a8_partition,
    infer_sharding_from_operands=_w8a8_infer_sharding,
    propagate_user_sharding=lambda mesh, user_shape: user_shape.sharding,
    sharding_rule="b k, k n, s u n -> b n",
    reduction_factors=("k", "s"),
    need_replication_factors=("u",),
)


def w8a8_matmul(x, rec: dict, out_dtype=None, *, block_k: int = None,
                max_rows: int = W8A8_MAX_ROWS):
    """``x @ dequant_k(rec)`` on the s8 MXU with in-kernel activation
    quantization.  Decode-shaped inputs only (``rows <= max_rows``); other
    shapes — and ``DS_W8A8=0`` — fall back to dequantize+matmul (prefill
    is compute-bound and amortizes the bf16 copy; its activations stay
    unquantized there, which is also the more accurate choice for the
    prompt pass)."""
    from . import quantization as quant

    qk, kscale = rec["qk"], rec["kscale"]
    k_dim, n_dim = qk.shape[-2], qk.shape[-1]
    k_group = k_dim // kscale.shape[-3]
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    if (qk.ndim == 2 and rows <= max_rows
            and os.environ.get("DS_W8A8", "1") != "0"):
        x2d = x.reshape(rows, k_dim)
        kscale3 = kscale.reshape(k_dim // k_group, 1, n_dim)
        if _W8A8_TP:
            # tensor-parallel serving: the custom_partitioning wrapper
            # keeps the weight sharded (column: per-shard s8 kernel, no
            # comm; row: f32 local partial + psum); per-shard kernel
            # ineligibility degrades to a SHARDED dequant+matmul.  Only a
            # sharding that would split quant groups forces a gathered
            # lowering (warned in _w8a8_partition).  Block sizing is
            # per-shard inside the lowering; ``block_k`` is not threaded.
            out = _w8a8_tp_call(x2d, qk, kscale3)
        elif _KERNEL_OK:
            out = _w8a8_local(x2d, qk, kscale3, block_k=block_k,
                              out_dtype=out_dtype)
        else:
            return x @ quant.dequantize_k(rec, x.dtype)
        return out.astype(out_dtype or x.dtype).reshape(lead + (n_dim,))
    return x @ quant.dequantize_k(rec, x.dtype)


# ------------------------------------------------- stacked (indexed) W8A8
# A scan/fori_loop over stacked per-layer weights hands the kernel a
# dynamic-slice of the [L, K, N] stack; XLA cannot fuse that slice into a
# custom call, so it materializes a per-layer int8 COPY in HBM every decode
# step — read + write + read where the payload is one read.  The stacked
# kernel instead takes the WHOLE stack plus the layer index as a
# scalar-prefetch operand: the BlockSpec index maps add the layer offset
# and every weight block is DMA'd straight from the resident stack.


def _w8a8_stacked_kernel(idx_ref, x_ref, q_ref, s_ref, o_ref, acc_ref, *,
                         nk: int, k_group: int):
    del idx_ref  # consumed by the index maps; kernel body sees the blocks
    _w8a8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, nk=nk,
                 k_group=k_group, stacked=True)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_k",
                                             "interpret", "vmem_limit"))
def _w8a8_stacked_call(idx, x2d, qks, kscales, out_dtype, block_k,
                       interpret, vmem_limit=None):
    b, k_dim = x2d.shape
    n_layers, _, n_dim = qks.shape
    k_group = k_dim // kscales.shape[1]
    grid = (1, k_dim // block_k)
    x3 = x2d.reshape(b, k_dim // k_group, k_group).swapaxes(0, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k // k_group, b, k_group),
                         lambda n, ki, idx_ref: (ki, 0, 0)),
            pl.BlockSpec((1, block_k, n_dim),
                         lambda n, ki, idx_ref: (idx_ref[0], ki, 0)),
            pl.BlockSpec((1, block_k // k_group, 1, n_dim),
                         lambda n, ki, idx_ref: (idx_ref[0], ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b, n_dim), lambda n, ki, idx_ref: (0, 0)),
        scratch_shapes=[pltpu.VMEM((b, n_dim), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_w8a8_stacked_kernel, nk=grid[1], k_group=k_group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_dim), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="qmm_w8a8_stacked",
    )(jnp.asarray(idx, jnp.int32).reshape(1), x3, qks, kscales)


def stacked_kernel_enabled() -> bool:
    """True when :func:`w8a8_matmul_stacked` would actually select the layer
    in-kernel.  Models gate their layer-indexed decode loop on this: under
    TP, or with the kernel disabled, the indexed loop would pay the
    per-layer dynamic-slice cost the scan path pays WITHOUT the stacked
    kernel's benefit (plus extra KV-stack slice/update traffic)."""
    return (_KERNEL_OK and not _W8A8_TP
            and os.environ.get("DS_W8A8", "1") != "0")


def w8a8_matmul_stacked(x, rec: dict, layer_idx, out_dtype=None, *,
                        block_k: int = None, max_rows: int = W8A8_MAX_ROWS):
    """``x @ dequant_k(rec[layer_idx])`` on the s8 MXU, selecting the layer
    INSIDE the kernel (scalar-prefetch index) so no per-layer weight copy
    is ever materialized.  ``rec`` holds stacked records (``qk [L, K, N]``,
    ``kscale [L, K/G, 1, N]``); ``layer_idx`` may be a traced scalar (a
    ``fori_loop`` induction variable).  Shapes the kernel cannot tile fall
    back to dequantizing the sliced layer — the same cost the scan path
    always pays."""
    from . import quantization as quant

    qk, kscale = rec["qk"], rec["kscale"]
    assert qk.ndim == 3, "stacked records only; use w8a8_matmul for 2D"
    k_dim, n_dim = qk.shape[-2], qk.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    bk, _ = _w8a8_pick_bk(k_dim, kscale.shape[-3], n_dim, block_k)
    eligible = (rows <= max_rows and bk > 0 and n_dim % 128 == 0
                and stacked_kernel_enabled())
    if eligible:
        x2d = x.reshape(rows, k_dim)
        out = _w8a8_stacked_call(layer_idx, x2d, qk, kscale,
                                 out_dtype or x.dtype, bk,
                                 interpret_kernels(),
                                 vmem_limit=_qmm_vmem_limit())
        return out.reshape(lead + (n_dim,))
    layer = {
        "qk": jax.lax.dynamic_index_in_dim(qk, layer_idx, keepdims=False),
        "kscale": jax.lax.dynamic_index_in_dim(kscale, layer_idx,
                                               keepdims=False),
    }
    return w8a8_matmul(x, layer, out_dtype=out_dtype, block_k=block_k,
                       max_rows=max_rows)
