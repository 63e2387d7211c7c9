"""Device-side ops for the block-paged KV cache (vLLM PagedAttention
layout, JAX/TPU edition).

Layout contract (per layer slice of the stacked pool):

 - pool leaf: ``[NB, HKV, block_size, hd]`` — the batch dim of the
   contiguous layout becomes the physical-block dim and the length dim
   becomes the in-block offset, so the models' ``init_cache(num_blocks,
   block_size, dtype)`` hook builds a pool unchanged.
 - block table: ``int32 [B, NBPER]`` — each row maps a sequence's logical
   block index (``position // block_size``) to a physical block.  Entry 0
   is the reserved scratch block (``inference/paged.py``), which doubles as
   the "unset" marker: reads of unset blocks are masked by position, writes
   of invalid tokens are routed there explicitly.

Speculative-decoding windows lean on two properties of this contract:

 - **Scratch routing is the write-side safety net**: a T = K+1 verify
   window may reach positions past a row's allocated table entries (the
   tail of a draft that cannot fit the request's remaining budget) — those
   writes land in scratch block 0 and are never read back unmasked, so the
   verify program keeps one fixed shape for every row regardless of how
   much budget each row has left.
 - **Rollback is free**: rejected draft tokens leave stale KV at positions
   ``committed_len .. committed_len + K``.  Nothing is copied or zeroed —
   the scheduler just keeps its host-side length at the committed value;
   position-based causal masking hides the stale tail from every read, and
   the next committed write at a position deterministically overwrites it
   (``pos // block_size`` / ``pos % block_size`` addressing — same block,
   same offset).  Refcounts never move on rollback.

**Tensor parallelism** (Megatron-style ``tp`` mesh axis): the serving
engine commits the pool sharded over the KV-HEAD dim
(``NamedSharding(mesh, P(None, None, "tp"))`` on the stacked ``[L, NB,
HKV, bs, hd]`` buffer) and installs its mesh here via :func:`configure`.
Every paged op then runs inside ``shard_map`` — each chip scatters/
gathers/attends over only its own ``HKV/tp`` head shard of the pool, with
ZERO per-step KV collectives (the head dim is fully data-parallel across
chips; the one all-reduce of tensor-parallel attention happens after the
output projection, outside these ops, exactly like the matmul path).
Block ids, tables, and positions are head-invariant, so they replicate
into every shard unchanged.  Pools whose head count does not divide the
axis (GQA with HKV < tp) simply skip the wrapping — ``head_shards``
returns 1 and the op runs replicated, bit-identical to tp=1.

**Quantized pool records (int8 KV, PR 7)**: a pool leaf may be a dict
``{"qp": int8 [NB, HKV, bs, hd], "ps": bf16 [NB, HKV, bs]}`` instead of a
float array — int8 codes plus a per-block scale table whose rows live and
die with the blocks (one ``[HKV, bs]`` scale row per block per K/V per
layer; within the row each (head, slot) token vector carries its own
scale).  The granularity is chosen by two constraints:

 - *append-only writes*: a token is quantized once, at scatter time, from
   its own ``hd`` values (``ops/quantization.quantize_kv``).  A scalar
   per-block scale would force a read-modify-write requantization of the
   whole block whenever a later token raised the block absmax; per-token
   scales make the write side exactly the int8 payload + one scale.
 - *head-locality under tp*: scales sit under the pool's own head dim, so
   the sharded scatter computes them from the chip's local head shard —
   no cross-chip absmax, zero per-step collectives, and codes/scales are
   bit-identical to the replicated layout (the tp parity argument of
   PR 5 carries over unchanged).

Scatter quantizes on write, gather (and the paged Pallas kernels in
``ops/decode_attention.py``) dequantizes on read, so HBM only ever moves
int8 codes + scales.  Scale rows of freed blocks hold stale values by
design — reads are position-masked until the next owner rewrites them —
and the serving engine's host-side ledger + ``analysis/invariants.py``
``scale-lockstep`` audit enforce that no live read can reach one.
Rollback of rejected speculative tokens stays free: re-quantizing the
same deterministic values yields the same codes and scales.

Everything here is pure XLA (scatter / gather), shared by prefill and the
CPU/correctness decode path; the TPU kernels that walk the block table
in-kernel live in ``ops/decode_attention.py``
(``paged_decode_attention_pallas`` / ``paged_verify_attention_pallas``)
and shard through the same context.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ------------------------------------------------------------- tp context
#: Mesh the paged ops shard over (``None`` = replicated pools, plain XLA
#: ops, the tp=1 behavior).  Installed by ``ServingEngine`` when it commits
#: a head-sharded pool; module-level — like ``ops/quantized_matmul
#: .configure`` — so the model families' ``forward_cached`` stay
#: mesh-agnostic.
_TP_MESH = None
_TP_AXIS = "tp"


def configure(mesh=None, axis: str = "tp") -> None:
    """Install (mesh + axis name) or clear (``None``) the tensor-parallel
    context for the paged device ops.  With a mesh installed, every paged
    op whose head dims divide the axis runs inside ``shard_map`` on its own
    KV-head shard."""
    global _TP_MESH, _TP_AXIS
    _TP_MESH = mesh
    _TP_AXIS = axis


@contextlib.contextmanager
def tp_context(mesh, axis: str = "tp"):
    """Scoped :func:`configure`: install the tp context for the duration of
    a block and restore whatever was there before.  The serving engine
    wraps every device invocation in this — tracing happens inside the
    call, so each engine's programs bake in ITS mesh (or none) even when
    engines of different tp degrees coexist in one process."""
    prev = (_TP_MESH, _TP_AXIS)
    configure(mesh, axis)
    try:
        yield
    finally:
        configure(*prev)


def tp_mesh():
    return _TP_MESH


def tp_axis() -> str:
    return _TP_AXIS


def head_shards(*head_counts: int) -> int:
    """Shard count the configured tp context puts on the given head dims:
    the mesh's tp-axis size when EVERY count divides it, else 1 — the
    replicated fallback for GQA pools with fewer KV heads than chips (head
    groups are shared) and for odd head counts."""
    if _TP_MESH is None:
        return 1
    n = int(dict(_TP_MESH.shape).get(_TP_AXIS, 1))
    if n <= 1:
        return 1
    return n if all(int(h) % n == 0 for h in head_counts) else 1


def head_shard_map(fn, in_specs, out_specs):
    """``shard_map`` over the configured mesh.  Callers place
    :func:`tp_axis` on HEAD dims only, so the body is embarrassingly
    parallel across chips — no collective ever appears inside
    (``check_vma=False``: outputs are sharded, not replicated)."""
    return jax.shard_map(fn, mesh=_TP_MESH, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ------------------------------------------------------------- dp context
#: Data-parallel grouping for ``engine_mode="dp_tp"`` serving
#: (``inference/serving.py``): the batch/slot dim AND the physical-block
#: dim additionally shard over the mesh ``dp`` axis — each dp shard owns a
#: contiguous span of rows and of pool blocks (group-scoped allocation,
#: ``inference/paged.GroupedBlockAllocator``), so every shard's gathers
#: and scatters are self-contained after localizing the global block ids
#: into its own chunk (subtract the group base, clamp to the local
#: scratch).  ``_DP_GROUPS == 1`` (the default) is the untouched tp-only
#: behavior.
_DP_AXIS = "dp"
_DP_MESH = None
_DP_GROUPS = 1
_DP_GSIZE = 0


def configure_dp(mesh=None, groups: int = 1, group_size: int = 0,
                 axis: str = "dp") -> None:
    """Install (mesh + group count + per-group block span) or clear
    (``mesh=None``) the data-parallel context for the paged device ops."""
    global _DP_MESH, _DP_GROUPS, _DP_GSIZE, _DP_AXIS
    _DP_MESH = mesh
    _DP_GROUPS = int(groups) if mesh is not None else 1
    _DP_GSIZE = int(group_size) if mesh is not None else 0
    _DP_AXIS = axis


@contextlib.contextmanager
def dp_context(mesh, groups: int, group_size: int, axis: str = "dp"):
    """Scoped :func:`configure_dp` — the serving engine wraps dp_tp-mode
    program invocations in this (nested inside :func:`tp_context`), so
    only programs traced for THAT engine bake in the dp sharding."""
    prev = (_DP_MESH, _DP_GROUPS, _DP_GSIZE, _DP_AXIS)
    configure_dp(mesh, groups, group_size, axis)
    try:
        yield
    finally:
        configure_dp(*prev)


def dp_groups() -> int:
    return _DP_GROUPS


def dp_axis() -> str:
    return _DP_AXIS


def dp_state():
    """(mesh, groups, group_size) of the installed dp context."""
    return _DP_MESH, _DP_GROUPS, _DP_GSIZE


def localize_block_tables(block_tables, group_size):
    """Map GLOBAL physical block ids into the calling dp shard's local
    chunk: subtract the shard's group base and clamp into
    ``[0, group_size)``.  Group-scoped allocation guarantees a row's real
    entries live in its own group's span, so the subtraction is exact for
    them; the global "unset" sentinel 0 (and any position past the span)
    clamps to local block 0 — the shard's own scratch — preserving the
    scratch-routing write contract shard-locally.  Must be called inside
    ``shard_map`` over the dp axis."""
    g = jax.lax.axis_index(_DP_AXIS)
    return jnp.clip(block_tables.astype(jnp.int32) - g * group_size,
                    0, group_size - 1)


def blocks_for(num_tokens: int, block_size: int) -> int:
    """Blocks needed to cover ``num_tokens`` positions (ceil division) —
    the one accounting formula the allocator, scheduler, and speculative
    budget caps must all agree on."""
    return -(-int(num_tokens) // int(block_size))


# -------------------------------------------------- quantized pool records
#: scale-table dtype (module docstring: range-safe, 2^-9 rounding below
#: the int8 error); exported so tests and stats agree on the layout
SCALE_DTYPE = jnp.bfloat16

_PQ_KEYS = frozenset({"qp", "ps"})


def is_quantized_pool(leaf) -> bool:
    """True for an int8 pool record ``{"qp": codes, "ps": scales}``
    (module docstring has the layout contract)."""
    return isinstance(leaf, dict) and set(leaf) == _PQ_KEYS


def pool_payload(leaf):
    """The code/payload array of a pool leaf: ``qp`` for quantized
    records, the leaf itself otherwise — shape/dtype probes go here."""
    return leaf["qp"] if is_quantized_pool(leaf) else leaf


def quantize_pool(pool, scale_dtype=None):
    """Convert a freshly built float pool (``init_cache`` output, any
    nesting) into int8 records: zero codes plus a zero scale table shaped
    ``payload.shape[:-1]`` (one scale per token vector, rows indexed by
    block — the per-block scale table).  Zero scales dequantize unwritten
    slots to exactly 0.0, matching the float pool's zero init."""
    scale_dtype = scale_dtype or SCALE_DTYPE

    def one(leaf):
        return {"qp": jnp.zeros(leaf.shape, jnp.int8),
                "ps": jnp.zeros(leaf.shape[:-1], scale_dtype)}

    return jax.tree_util.tree_map(one, pool)


def _scatter_one(pool, win, phys, off):
    """Scatter a [B, HKV, T, ...] window into one pool leaf at the [B, T]
    (physical block, in-block offset) targets — quantizing on write when
    the leaf is an int8 record.  Advanced indices at dims 0 and 2 around
    the ':' slice put the [B, T] index shape in front: value layout is
    [B, T, HKV, ...].  Duplicate targets only ever occur on the scratch
    block (any write order is fine — scratch is never read unmasked)."""
    if not is_quantized_pool(pool):
        return pool.at[phys, :, off].set(
            win.transpose(0, 2, 1, 3).astype(pool.dtype))
    from . import quantization as quant

    codes, scale = quant.quantize_kv(win, pool["ps"].dtype)
    return {"qp": pool["qp"].at[phys, :, off].set(
                codes.transpose(0, 2, 1, 3)),
            "ps": pool["ps"].at[phys, :, off].set(
                scale.transpose(0, 2, 1))}


def _paged_cache_update(ck, cv, k, v, pos, block_tables, valid=None):
    """Single-shard scatter body of :func:`paged_cache_update` — also the
    whole op when the pool is replicated (tp=1 / GQA fallback)."""
    b, hkv, t, hd = k.shape
    bs = pool_payload(ck).shape[2]
    nbper = block_tables.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    p = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]      # [B, T]
    ok = (jnp.arange(t, dtype=jnp.int32)[None, :] <
          jnp.asarray(valid, jnp.int32)[:, None]) if valid is not None \
        else jnp.ones((b, t), bool)
    li = p // bs
    ok = ok & (li >= 0) & (li < nbper)
    phys = jnp.take_along_axis(block_tables.astype(jnp.int32),
                               jnp.clip(li, 0, nbper - 1), axis=1)
    phys = jnp.where(ok, jnp.maximum(phys, 0), 0)                   # [B, T]
    off = jnp.where(ok, p % bs, 0)                                  # [B, T]
    ck = _scatter_one(ck, k, phys, off)
    cv = _scatter_one(cv, v, phys, off)
    return ck, cv


def paged_cache_update(ck, cv, k, v, pos, block_tables, valid=None):
    """Scatter a window of new keys/values into the paged pool.

    ck/cv:         [NB, HKV, block_size, hd] pool (one layer)
    k/v:           [B, HKV, T, hd] — T new tokens per row
    pos:           int32 scalar or [B] — global position of ``k[:, :, 0]``
                   per row (T == 1 decode: each row's own position; T > 1
                   chunked prefill: each row's chunk base)
    block_tables:  int32 [B, NBPER]
    valid:         optional int32 [B] — tokens of the T-window that are
                   real (default all T).  Invalid tokens, and positions
                   past the table's reach, write to scratch block 0.

    Under a configured tp context (module docstring) the scatter runs in
    ``shard_map``: each chip writes its own head shard of the pool (the
    k/v window arrives already head-sharded from the column-parallel kv
    projections); positions/tables replicate.
    """
    b = k.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    n = head_shards(pool_payload(ck).shape[1], k.shape[1])
    if _DP_GROUPS > 1:
        # dp_tp serving: rows + physical blocks shard over dp (heads over
        # tp when divisible); each shard scatters into its own pool chunk
        # through localized tables — no cross-shard traffic
        hp = P(_DP_AXIS, _TP_AXIS) if n > 1 else P(_DP_AXIS)
        dpsp = P(_DP_AXIS)
        gsize = _DP_GSIZE
        valid = jnp.full((b,), k.shape[2], jnp.int32) if valid is None \
            else jnp.asarray(valid, jnp.int32)

        def body(ck, cv, k, v, pos, bt, valid):
            bt = localize_block_tables(bt, gsize)
            return _paged_cache_update(ck, cv, k, v, pos, bt, valid)

        return jax.shard_map(
            body, mesh=_DP_MESH,
            in_specs=(hp, hp, hp, hp, dpsp, dpsp, dpsp),
            out_specs=(hp, hp), check_vma=False)(
                ck, cv, k, v, pos,
                jnp.asarray(block_tables, jnp.int32), valid)
    if n <= 1:
        return _paged_cache_update(ck, cv, k, v, pos, block_tables, valid)
    # P(None, tp) is a valid spec for every record leaf too: qp
    # [NB, HKV, bs, hd] and ps [NB, HKV, bs] both carry the head dim at
    # index 1, and shard_map broadcasts a PartitionSpec leaf over the
    # record's pytree prefix
    hs = P(None, _TP_AXIS)
    valid = jnp.full((b,), k.shape[2], jnp.int32) if valid is None \
        else jnp.asarray(valid, jnp.int32)
    return head_shard_map(
        _paged_cache_update, (hs, hs, hs, hs, P(), P(), P()), (hs, hs))(
            ck, cv, k, v, pos, jnp.asarray(block_tables, jnp.int32), valid)


def _paged_gather(pool_leaf, block_tables, out_dtype=None):
    """Single-shard gather body of :func:`paged_gather` — called directly
    by the in-``shard_map`` attention bodies (``ops/decode_attention.py``)
    so sharded callers never re-enter the wrapper.  Quantized records
    gather codes + scales and dequantize (f32 expand, one cast to
    ``out_dtype`` — pass the query/compute dtype so bf16 models keep a
    bf16 residual stream, exactly like a float pool of that dtype); HBM
    moves int8 + scales, the expansion happens on-chip.  ``out_dtype``
    never touches a float pool (bit-identical reads)."""
    if is_quantized_pool(pool_leaf):
        from . import quantization as quant

        codes = _paged_gather(pool_leaf["qp"], block_tables)  # [B,HKV,S,hd]
        b, nbper = block_tables.shape
        hkv, bs = pool_leaf["ps"].shape[1], pool_leaf["ps"].shape[2]
        s = pool_leaf["ps"][jnp.maximum(block_tables, 0)]  # [B,NBPER,HKV,bs]
        s = s.transpose(0, 2, 1, 3).reshape(b, hkv, nbper * bs)
        return quant.dequantize_kv(codes, s, out_dtype or jnp.float32)
    nb, hkv, bs, hd = pool_leaf.shape
    b, nbper = block_tables.shape
    g = pool_leaf[jnp.maximum(block_tables, 0)]     # [B, NBPER, HKV, bs, hd]
    return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nbper * bs, hd)


def _block_gather_one(leaf, ids):
    """Single-shard body of :func:`paged_block_gather` for one pool leaf:
    ``[L, NB, ...] -> [L, M, ...]`` along the physical-block dim."""
    return leaf[:, ids]


def paged_block_gather(pool, ids):
    """Gather whole physical blocks out of a (stacked, possibly multi-leaf)
    pool for host demotion: every leaf ``[L, NB, *rest]`` yields
    ``[L, M, *rest]`` at the ``int32 [M]`` block ids (``M`` is the engine's
    fixed ``swap_batch`` — pad with scratch block 0, whose gathered bytes
    the caller discards).  Quantized records travel whole: the ``qp`` codes
    AND their ``ps`` scale rows are ordinary leaves of the tree, so a
    demoted int8 block carries its scales with it.

    This is the device half of the tiered-KV demotion path
    (``inference/serving.py``): ONE fixed-shape compiled program per
    engine, one ``jax.device_get`` of its output per demotion batch.
    Under a configured tp context each chip gathers only its own head
    shard (dims: block at 1, head at 2 on every leaf — the pool layout
    contract), and the host-side ``device_get`` then assembles the full
    blocks from the addressable shards; ids replicate.
    """
    ids = jnp.asarray(ids, jnp.int32)
    leaves = jax.tree_util.tree_leaves(pool)
    n = head_shards(*[l.shape[2] for l in leaves])
    if n <= 1:
        return jax.tree_util.tree_map(
            lambda l: _block_gather_one(l, ids), pool)
    hs = P(None, None, _TP_AXIS)
    return head_shard_map(
        lambda p, i: jax.tree_util.tree_map(
            lambda l: _block_gather_one(l, i), p),
        (hs, P()), hs)(pool, ids)


def paged_block_scatter(pool, staged, ids):
    """Scatter host-promoted blocks back into the pool: every staged leaf
    ``[L, M, *rest]`` lands at the ``int32 [M]`` block ids of the matching
    pool leaf — the inverse of :func:`paged_block_gather`, so a
    demote → promote round trip is bit-identical (int8 codes and scale
    rows included).  Pad columns target scratch block 0 (duplicate
    scratch writes are fine — scratch is never read unmasked).

    Device half of tiered-KV promotion: the engine ``jax.device_put``\\ s
    the staged blocks ahead of admission (overlapping H2D with the decode
    step) and this ONE fixed-shape program commits them.  Under a
    configured tp context each chip scatters its own head shard (the
    staged array arrives already head-sharded from the engine's
    sharding-annotated ``device_put``); ids replicate.
    """
    ids = jnp.asarray(ids, jnp.int32)
    leaves = jax.tree_util.tree_leaves(pool)
    n = head_shards(*[l.shape[2] for l in leaves])

    def scatter(p, s, i):
        return jax.tree_util.tree_map(
            lambda pl, sl: pl.at[:, i].set(sl.astype(pl.dtype)), p, s)

    if n <= 1:
        return scatter(pool, staged, ids)
    hs = P(None, None, _TP_AXIS)
    return head_shard_map(scatter, (hs, hs, P()), hs)(pool, staged, ids)


def paged_gather(pool_leaf, block_tables, out_dtype=None):
    """Materialize each row's logical cache view from the pool:
    ``[NB, HKV, bs, hd]`` through ``int32 [B, NBPER]`` tables ->
    ``[B, HKV, NBPER*bs, hd]`` (int8 records dequantize — to ``out_dtype``
    when given, f32 otherwise; float pools ignore ``out_dtype``).  Unset
    (scratch) entries gather garbage that sits past every row's valid
    length — callers mask by position.  Under a configured tp context each
    chip gathers only its own head shard (output sharded
    ``[B, HKV/tp, S, hd]`` per chip)."""
    import functools

    n = head_shards(pool_payload(pool_leaf).shape[1])
    if n <= 1:
        return _paged_gather(pool_leaf, block_tables, out_dtype)
    hs = P(None, _TP_AXIS)
    return head_shard_map(
        functools.partial(_paged_gather, out_dtype=out_dtype),
        (hs, P()), hs)(pool_leaf, jnp.asarray(block_tables, jnp.int32))
