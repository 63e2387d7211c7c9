"""Device-side ops for the block-paged KV cache (vLLM PagedAttention
layout, JAX/TPU edition).

Layout contract — the WHOLE stacked pool, addressed in place:

 - pool leaves: each ``[L, NB, heads, block_size, width]`` — the models'
   ``init_cache(num_blocks, block_size, dtype)`` hook builds them unchanged
   (the batch dim of the contiguous layout becomes the physical-block dim
   and the length dim the in-block offset).  K and V are ``[L, NB, HKV,
   block_size, hd]``; a family with learned sparse attention
   (``models/mixtral.py`` with an indexer) adds a third kind of per-token
   state under the SAME block ids, the indexer's key ``[L, NB, 1,
   block_size, DI]`` (``DI`` = 64: lane-packed ``g = 2``, 4 KB a block in
   bf16), written by :func:`paged_window_update` at the ``(layer, block,
   offset)`` K and V take.  The serving engine treats the pool BY TREE —
   packing, donation, sharding, swap, prefix sharing and eviction move a
   block id's slice of every leaf together and name none — so a block is
   allocated, shared, evicted and swapped with all its leaves.  Every op
   here takes a whole leaf plus a ``layer`` index and touches ``(layer, physical block, head,
   offset)`` in place; nothing slices a layer out of the pool, re-stacks it
   or changes its layout.  The models' layer loops therefore CARRY the pool
   (``models/cached.py:scan_layers_cached``) and a program that donates it
   gets it back in the same buffer.  A 4-D ``[NB, HKV, bs, hd]`` pool with
   ``layer=None`` is the same code on a one-layer view (:func:`whole_pool`).
 - block table: ``int32 [B, NBPER]`` — each row maps a sequence's logical
   block index (``position // block_size``) to a physical block.  Entry 0
   is the reserved scratch block (``inference/paged.py``), which doubles as
   the "unset" marker: reads of unset blocks are masked by position, and a
   window that reaches past a row's allocated entries lands there.
 - **Layer kinds.**  One table serves every layer of a model whose layers
   are all of one kind.  A model that mixes full-attention and
   sliding-window layers (``LlamaConfig.layer_kinds``) holds one set of
   leaves PER KIND, each with its own block-id space, its own table and
   its own layer axis: the full kind ``k`` / ``v`` ``[L_full, NB_full,
   ...]`` under a table of ``ceil(max_seq_len / block_size)`` entries, the
   window kind ``kw`` / ``vw`` ``[L_win, NB_win, ...]`` under a RING of
   ``ceil((window + prefill_chunk) / block_size) + 1`` entries: logical
   block ``i`` of a row sits at entry ``i % width``, and the scheduler
   releases a block once every position of it is behind ``position -
   window`` (``inference/serving.py``).  The ops take ``ring=True`` (the
   write) or ``window=W`` (the reads, ``ops/decode_attention.py``) for a
   window layer and are today's programs without; which table and which
   leaves a layer addresses is static in the model's layer loop
   (``models/cached.py:scan_periods_cached``).  A kind's leaves need not be
   K and V (``models/cached.py KIND_LEAVES``): a model whose window layers
   are LATENT attention keeps ONE leaf of its own lane width under the ring
   (``latw``, written by :func:`paged_window_update` with ``ring=True``,
   read by ``paged_latent_attention(window=)``), in blocks of ITS bytes
   (:func:`latent_block_tokens` of its width: the engine reads the window
   kind's block off the cache tree), beside a full kind whose leaves are a
   latent and an indexer's key (``models/dots3.py``).

 - **The latent kind.**  A model with latent attention (MLA,
   ``LlamaConfig.kv_lora_rank > 0``) caches no key and no value a head: a
   token's whole state in a layer is the normed latent ``c`` and the ONE
   rotated key ``k_r`` every head shares, ``[c | k_r]`` — 256 + 64 = 320
   values, 640 B in bf16, where the same widths expanded to 32 heads' K
   and V would be 16,384 B.  Its pool is ONE leaf and no ``k`` / ``v``:
   ``latent [L, NB, 1, block_size, W]``, written by
   :func:`paged_window_update` and read by
   ``ops/decode_attention.paged_latent_attention``, under one block-id
   space and one table (every layer is of the one kind).  The unit axis
   where the others have their KV heads is what keeps the contract above
   — block at dim 1, heads at dim 2 of every leaf — so that packing,
   donation, swap, prefix sharing and eviction move it by tree as they
   move any pool; there is nothing on it for ``tp`` to split.  ``W`` is
   :func:`latent_pool_width`: the token's 320 values zero-padded to 384,
   three whole lane rows.  A ``[.., bs, 320]`` array is not smaller: XLA's
   tiled layout pads its minor dim to 384 in HBM all the same, and Mosaic
   copies out of an HBM operand in whole 128-lane tiles only, so the pad
   is what both would do, said once.  (Two leaves, ``c`` of 256 and
   ``k_r`` of 64 lane-packed two tokens a row, would hold the 640 B
   exactly — at two copies a block and a second, packed tile in the
   kernel; not built.)  The score reads all 384 lanes of a tile (the pad
   meets zeros in the query), the value its first 256: one copy a block
   serves both.  A 768-byte token makes a 32-token block 24 KB, a fifth of
   the 128 KB the walk was tuned at, and a block visit costs ~0.4 us
   whatever it moves: a serving engine that is given no ``block_size``
   takes :func:`latent_block_tokens` for this kind, 512 tokens of 768 B
   (PERF.md section 6, PR 39, has the table).  A latent leaf may have the
   indexer's key leaf beside it under the same table (a latent layer under
   a learned selection: ``ops/sparse_index_attention.
   paged_sparse_latent_attention`` reads the latents of the blocks that
   hold a chosen key), and a pool may hold TWO latent leaves of different
   widths, each in blocks of its own bytes, the second under the window
   kind's ring ("Layer kinds" above).

 - **The state kind.**  A model with recurrent layers keeps, for each such
   layer, no token at all: a row's whole past is a float32 matrix a head,
   ``state [L, rows, ...]`` (2 MiB a row a layer in the two families that
   ride it beside a paged pool, 34 MB in the one that has no pool, whatever
   the row's length), and what the FAMILY names beside it
   (:data:`STATE_COMPANIONS`): the last ``K - 1`` inputs of its short
   convolutions, ``conv [L, rows, 1, K - 1, channels]``, or the running sum
   that normalises its read, ``z [L, rows, heads, ...]``.  What is the
   KIND's: these
   leaves ride in the SAME cache tree as the paged leaves — donated, carried
   through the layer loop and handed back in the same buffers — but are
   indexed by ROW (the serving engine's slot), not by block: no block ids,
   no table, no allocator, nothing to share, swap or evict.  "Block at dim
   1, heads at dim 2" holds in form (rows at dim 1, heads — a unit axis for
   ``conv`` — at dim 2) and :func:`pack_pool` is NOT applied to them (there
   is no block to lane-pack; the engine skips, resets and counts them by the
   kind, :data:`STATE_LEAVES`).  A cache tree may hold the state kind's
   leaves and NOTHING else: such a model has no pool, no block and no table
   at all, and a row of it needs nothing as it grows.  A decode step's row
   ``b`` is row
   ``b`` of the leaves and the kernel updates the matrices in place
   (``input_output_aliases``); a prefill call names its rows'
   slots (``block_tables["slot"]``: gathered, advanced by the chunked form,
   scattered back at ``[layer, slot]``, a pad row's slot out of range and
   dropped; where there is no paged table to tell an idle row by, a decode
   step carries ``slot`` too, an idle row's out of range).  What is a
   FAMILY's: the shapes behind the rows, the
   recurrence and its kernels — a gated delta rule with a decay a key
   channel (``models/kimi_linear.py``, ``ops/delta_rule.py``: ``state [L_kda,
   rows, H, dk, dv]``, ``kda_step`` / ``kda_chunk_state``), a state-space scan
   with a scalar decay a head (``models/granite_hybrid.py``, ``ops/ssd.py``:
   ``state [L_ssm, rows, H / g, N, g P]``, head-packed so that its minor dim
   fills the lanes; ``ssd_step`` / ``ssd_chunk_state``), a gated sum of the
   key's degree-2 monomials by the value, read by a group of query heads
   and divided by its normaliser (``models/brumby.py``,
   ``ops/power_retention.py``: ``state [L, rows, HKV, hd / 2 + 1, hd, hd]``,
   ``power_step`` / ``power_chunk_state``).

 - **Tails.**  Row-indexed leaves may ride beside paged leaves WITHOUT a
   ``state`` leaf (:data:`TAIL_LEAVES`).  A model whose keys are made by
   short causal convolutions over the projections (``models/zaya.py``)
   caches a finished key and value a token in the ``full`` kind like any
   K/V — but the WRITER of token ``t`` needs what the convolutions read of
   token ``t - 1``, a row and a layer: ``conv [L, rows, 1, taps, channels]``
   (the convolutions' inputs a token back) and ``shift [L, rows, 1, 1,
   channels]`` (the half of a value that is the projection of the token
   before).  They are the state kind's contract with nothing recurrent in
   it: in the cache tree, donated and carried with the pool, indexed by
   ROW, no block ids, no table, no allocator, not lane-packed; a decode
   step's row ``b`` is row ``b``, a prefill call names its rows' slots
   (``block_tables["slot"]``), a window at base 0 starts from zero tails
   inside the program, a pad or an idle row moves none.  Every layer of
   such a model is paged AND has tails.  :data:`ROW_LEAVES` is the one
   table of what is indexed by row, of either kind.

**Layout** (what "in place" takes on a TPU).  A Mosaic kernel reads its
operand row-major — ``[L][NB][HKV][...]``, a block's tiles contiguous —
and tiles the last two dims (16 x 128 for bf16).  XLA:TPU's own layout for
the hook's ``[..., bs=32, hd=64]`` array is block-id-MINOR (``{1,4,3,2,0}``:
a 64-wide minor dim would leave half of the 128 lanes empty, so it puts the
769 blocks there instead, padded to 896), and from there every consumer
got a layout of its own: the six 100 MB copies per layer of PR 24/25
(slice -> the scatter's layout -> the kernel's, hd padded to 128 lanes ->
the re-stack's).  So a serving engine holds its pool LANE-PACKED
(:func:`pack_pool`): the same bytes viewed as ``[L, NB, HKV, bs/g, g*hd]``
with ``g = 128 // hd`` (2 for hd=64; 1, i.e. no change, from hd=128 up), a
block's g consecutive ``bs/g``-token spans side by side in the lanes.  That
view's minor dim is 128, so XLA's layout for it IS the row-major one, with
no padding (4.84 GB for the chat cell's 769 blocks, where XLA's layout of
the unpacked array took 5.64 GB and the kernel's 9.68 GB), the kernels read
it as it lies (a span is a lane slice of the tile:
``ops/decode_attention.py:_attend_chunk``), and XLA has no reason left to
move it.  It is also what makes a block ONE copy: in this layout all KV
heads of a (layer, block) are contiguous — ``[HKV, bs/g, 128]``, 128 KB in
bf16 for OPT-1.3B's 32 x 64 and OLMoE's 16 x 128 alike — so the paged
attention kernels (``_paged_walk_kernel``) leave the pool in HBM and, row
by row, fetch just the blocks the row's positions reach: ``pool.at[layer,
bt[b, i]]`` for ``i < cdiv(pos + 1, block_size)``, one DMA a block and
side, the next block in flight while this one is attended — across rows
too: a row's last tile starts the next row's first.  A table entry
past a row's valid prefix is never read, and a row costs its own length,
not ``max_seq_len``.  Two more things keep it so: the write reads, merges and
scatters back WHOLE blocks (:func:`_write_blocks`), in the stored view:
token offset o of a block is row ``o % (bs/g)``, lane group ``o // (bs/g)``,
and the window's tokens are laid into those lanes under a mask, so a
gathered block is never brought to token order (PR 41: un-packing and
re-packing the touched blocks was ten half-lane passes a layer, 3.85 ms of
a 24-row decode step at hd 64 where the merge in place takes 1.05) — index
dims (layer, block), the pool's two major dims, so row-major is the layout
that scatter wants too, where a scatter of token vectors at ``[layer, phys,
:, off]`` also indexes the in-block offset and has XLA re-lay-out the pool
around it — and nothing but that write, the gathers and the kernels ever
takes the pool as an operand.  (Row-granular writes were tried again in
PR 41 and refused: a decode row touches ONE stored 128-lane row a head, 1/16
of the block, but ``leaf.at[layer, phys, :, r].set(..)`` compiles, for a
described v5e at the chat cell's shapes, to four pool-sized copies a layer
and 2.4 GB of temporaries, and ran 29.9 ms where whole blocks run 1.05.  The
whole-block write is three passes over the touched blocks — gather, merge,
scatter — ~1.0 ms against the 0.36 ms its bytes take; under that lies only
writing the row from inside the walk kernel, the pool aliased in and out.)
Every op reads the packing off the shapes (pool minor
dim over the model's head dim), so a pool exactly as ``init_cache`` built
it — the benchmark's teacher-forced comparison passes one — goes through
the same code with ``g = 1`` (and, on a TPU, through whatever copies XLA's
layout of that array costs, plus the kernels' own copy of the layer's
blocks into whole lane rows — Mosaic copies out of HBM in whole 128-lane
tiles only, ``decode_attention._lane_rows``: fine for a comparison, not
for serving).  An int8 record packs its codes the same way; its scale table
``[L, NB, HKV, bs]`` is small (1/64 of the codes) and keeps the hook's
shape: the kernels take a layer's rows of it lane-padded, a copy of 1/L of
the table a call.

Speculative-decoding windows lean on two properties of this contract:

 - **Scratch routing is the write-side safety net**: a T = K+1 verify
   window may reach positions past a row's allocated table entries (the
   tail of a draft that cannot fit the request's remaining budget) — those
   writes land in scratch block 0 (entry 0) and are never read back
   unmasked; invalid tokens of a window are simply not written, so the
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
``{"qp": int8 [L, NB, HKV, bs, hd], "ps": bf16 [L, NB, HKV, bs]}`` instead
of a float array — int8 codes plus a per-block scale table whose rows live and
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

Everything here is pure XLA (block read-modify-write / gather): the write
every program shares, and the gather that is the read of the CPU/correctness
path, of an int8 record under a prefill chunk and of the resident-window
mask.  On a TPU every read — a decode token, a verify window, a prefill
chunk — goes through the kernels that walk a row's valid blocks of the
(layer, block table) index in-kernel; they live in
``ops/decode_attention.py`` (``paged_decode_attention_pallas`` /
``paged_verify_attention_pallas`` / ``paged_prefill_attention_pallas``)
and shard through the same context.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
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


def whole_pool(pool, layer):
    """``(stacked pool, int32 layer index)`` for a pool operand of the
    paged ops.  With ``layer`` given, ``pool`` already is the stacked
    ``[L, NB, ...]`` pool.  ``layer=None`` is the one-layer entry point
    (kernel tests, ad-hoc callers): a ``[NB, ...]`` pool is viewed as the
    stack ``[1, NB, ...]`` at layer 0 — a reshape, no second code path."""
    if layer is not None:
        return pool, jnp.asarray(layer, jnp.int32)
    return (jax.tree_util.tree_map(lambda a: a[None], pool),
            jnp.zeros((), jnp.int32))


#: lanes of a TPU vector register: the minor dim a pool's blocks are packed to
LANES = 128
#: the state kind's layer kinds (``models/cached.py KIND_LEAVES``) and the
#: leaf each family keeps BESIDE the matrix a head, ``state`` (module
#: docstring): a convolution's tail, a normaliser
STATE_COMPANIONS = {"kda": "conv", "ssm": "conv", "power": "z"}
#: the cache leaves of the state kind: indexed by ROW — a serving slot —
#: never by block
STATE_LEAVES = ("state",) + tuple(dict.fromkeys(STATE_COMPANIONS.values()))
#: the tails of a model whose K/V writer reads the token before (module
#: docstring "Tails"): row-indexed leaves beside paged ones, no ``state``
TAIL_LEAVES = ("conv", "shift")
#: every cache leaf that is indexed by ROW and not by block: what
#: :func:`pack_pool` is not applied to and no allocator knows
ROW_LEAVES = tuple(dict.fromkeys(STATE_LEAVES + TAIL_LEAVES))


def latent_pool_width(width: int) -> int:
    """Lanes a token of the latent kind takes in the pool: its ``width``
    values (``kv_lora_rank + qk_rope_dim``) zero-padded to whole 128-lane
    rows (module docstring "The latent kind")."""
    return -(-int(width) // LANES) * LANES


#: tokens of the default block of a pool with K and V a head, and the bytes
#: a layer's block of the latent kind may come to by default: the walk
#: spends ~0.4 us a block visit whatever the block moves and 0.48 us on
#: 384 KB of it at 819 GB/s — 43.0 / 12.5 / 7.7 / 5.4 ms a decode execution
#: at 32 / 128 / 256 / 512 tokens a block (PERF.md section 6, PR 39)
DEFAULT_BLOCK_TOKENS = 32
LATENT_BLOCK_BYTES = 1 << 19


def latent_block_tokens(width: int, itemsize: int, max_seq_len: int) -> int:
    """The block a latent pool gets where the caller names none: the
    largest power of two of tokens whose block stays within
    ``LATENT_BLOCK_BYTES`` a layer and an eighth of ``max_seq_len`` (a row's
    last block is half empty in the mean: 256 of ~8,000 keys at 512), and
    never under the other kinds' default."""
    fit = min(LATENT_BLOCK_BYTES // (latent_pool_width(width) * int(itemsize)),
              int(max_seq_len) // 8)
    return max(DEFAULT_BLOCK_TOKENS, 1 << max(fit, 1).bit_length() - 1)


def lane_pack(block_size: int, head_dim: int) -> int:
    """How many ``head_dim``-wide token rows share one 128-lane row of a
    lane-packed block: ``128 // head_dim`` when that divides both, else 1
    (head dims of 128 and up are lane-dense as they are)."""
    hd = int(head_dim)
    g = LANES // hd if 0 < hd < LANES and LANES % hd == 0 else 1
    return g if int(block_size) % g == 0 else 1


def pack_pool(pool):
    """The lane-packed view of a pool built by ``init_cache``: every payload
    leaf ``[L, NB, HKV, bs, hd]`` becomes ``[L, NB, HKV, bs/g, g*hd]``
    (``g = lane_pack(bs, hd)``; the scale table of an int8 record stays as
    it is).  Row ``r`` of a packed block holds tokens ``r, r + bs/g, ...``
    side by side — the block's g consecutive ``bs/g``-token spans, one per
    ``hd``-wide lane group — so a span is a contiguous lane slice of the
    block and of its scale row alike.  Same bytes; module docstring
    ("Layout") says why a serving engine holds its pool this way.  The ops
    below read the packing off the shapes (:func:`_unpack_block`), so they
    take either view."""
    def one(leaf):
        if is_quantized_pool(leaf):
            return {"qp": one(leaf["qp"]), "ps": leaf["ps"]}
        *lead, bs, hd = leaf.shape
        g = lane_pack(bs, hd)
        return leaf.reshape(*lead, g, bs // g, hd).swapaxes(-3, -2) \
            .reshape(*lead, bs // g, g * hd)

    return jax.tree_util.tree_map(one, pool, is_leaf=is_quantized_pool)


def _unpack_block(blocks, head_dim: int):
    """``[..., bs/g, g*hd] -> [..., bs, hd]`` (token order) for blocks read
    off a pool in either view; the packing ``g`` is read off the shapes."""
    *lead, rows, width = blocks.shape
    g = width // head_dim
    if g == 1:
        return blocks
    return blocks.reshape(*lead, rows, g, head_dim).swapaxes(-3, -2) \
        .reshape(*lead, rows * g, head_dim)


def _write_blocks(leaf, win, layer, phys, start, nvalid):
    """Write a [B, HKV, T, ...] window into one stacked pool array
    ``[L, NB, HKV, ...]`` (payload in either view, or a scale table) by
    whole physical blocks: ``phys`` int32 [B, J] names the J blocks row b's
    window reaches and ``start`` [B, J] the window token that lands at
    offset 0 of each (may be negative); tokens outside ``[0, nvalid[b])``
    keep what the block held.  Three ops: gather the ``[B, J]`` touched
    blocks at ``[layer, phys]``, merge the rows' tokens in, scatter the
    blocks back.  The merge happens in the view the pool STORES — a block
    ``[R, W]`` of ``g = W // hd`` lane groups holds token offset ``o`` at
    row ``o % R``, lane group ``o // R`` — so the gathered blocks are never
    brought to token order: the window is tiled over the lanes once and
    lane group ``q`` takes tokens ``start + q * R + r`` under its lanes'
    mask (``g = 1``: one group, no mask; a one-token window needs no gather
    of its own, its token is broadcast over the block under the mask).
    The scatter's index dims are the pool's two MAJOR dims
    (layer, block) and its window a whole block, so the pool's row-major
    layout is the one it wants and a loop-carried pool is updated in place
    — where a scatter of token vectors at ``[layer, phys, :, off]`` indexes
    the in-block offset too and has XLA re-lay-out the whole pool around
    it.  A real block is written by one row only (shared prefix blocks are
    full, hence never written); rows meet in the scratch block alone, where
    any order will do."""
    t = win.shape[2]
    tok = win.shape[3:]                       # (hd,) payload, () scales
    rows = leaf.shape[3]                      # R: bs // g
    g = leaf.shape[4] // tok[0] if tok else 1
    if g > 1:
        win = jnp.tile(win, g)                # a copy of the token a group
        group = jnp.arange(leaf.shape[4], dtype=jnp.int32) // tok[0]

    def tokens(q):
        """(take [B, J, R], new [B, J | 1, HKV, R | 1, ...]) of lane group q"""
        ti = start[:, :, None] + jnp.arange(q * rows, (q + 1) * rows,
                                            dtype=jnp.int32)
        take = (ti >= 0) & (ti < nvalid[:, None, None])
        if t == 1:
            return take, win[:, None].astype(leaf.dtype)
        new = win[jnp.arange(win.shape[0])[:, None, None], :,
                  jnp.clip(ti, 0, t - 1)]              # [B, J, R, HKV, ...]
        return take, jnp.moveaxis(new, 2, 3).astype(leaf.dtype)

    # the window's contribution first, the blocks after: the order g = 1
    # has always traced in (its lowered text is pinned, test_chip_lowering)
    groups = [tokens(q) for q in range(g)]
    blk = leaf[layer, phys]                   # [B, J, HKV, R, W] as stored
    for q, (take, new) in enumerate(groups):
        take = take.reshape(take.shape[:2] + (1, rows) + (1,) * len(tok))
        if g > 1:
            take = take & (group == q)
        blk = jnp.where(take, new, blk)
    return leaf.at[layer, phys].set(blk)


def _write_one(pool, win, layer, phys, start, nvalid):
    """:func:`_write_blocks` for one pool leaf — quantizing on write when
    the leaf is an int8 record (codes and their scale rows take the same
    walk)."""
    if not is_quantized_pool(pool):
        return _write_blocks(pool, win, layer, phys, start, nvalid)
    from . import quantization as quant

    codes, scale = quant.quantize_kv(win, pool["ps"].dtype)
    return {"qp": _write_blocks(pool["qp"], codes, layer, phys, start,
                                nvalid),
            "ps": _write_blocks(pool["ps"], scale, layer, phys, start,
                                nvalid)}


def _window_blocks(bs: int, b: int, t: int, pos, block_tables, valid,
                   ring: bool = False):
    """Where a ``[B, *, T, ...]`` window starting at ``pos`` lands:
    ``(phys, start, nvalid)`` as :func:`_write_blocks` takes them.  ``ring``:
    the table is a RING of its width (a window layer's, module docstring
    "Layer kinds"): logical block ``i`` sits at entry ``i % width``."""
    nbper = block_tables.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    nvalid = jnp.full((b,), t, jnp.int32) if valid is None \
        else jnp.clip(jnp.asarray(valid, jnp.int32), 0, t)
    # a T-token window starting anywhere reaches at most this many blocks
    nj = 1 if t == 1 else (t + bs - 2) // bs + 1
    li = pos[:, None] // bs + jnp.arange(nj, dtype=jnp.int32)[None, :]
    if ring:
        ok, at = li >= 0, li % nbper
    else:
        ok, at = (li >= 0) & (li < nbper), jnp.clip(li, 0, nbper - 1)
    phys = jnp.take_along_axis(block_tables.astype(jnp.int32), at, axis=1)
    phys = jnp.where(ok, jnp.maximum(phys, 0), 0)
    # blocks past the table's reach take no token: start past the window
    start = jnp.where(ok, li * bs - pos[:, None], t)
    return phys, start, nvalid


def _paged_cache_update(ck, cv, k, v, pos, block_tables, valid, layer,
                        ring: bool = False):
    """Single-shard body of :func:`paged_cache_update` on the stacked
    pool — also the whole op when the pool is replicated (tp=1 / GQA
    fallback)."""
    b, hkv, t, hd = k.shape
    bs = int(np.prod(pool_payload(ck).shape[3:])) // hd
    where = _window_blocks(bs, b, t, pos, block_tables, valid, ring)
    ck = _write_one(ck, k, layer, *where)
    cv = _write_one(cv, v, layer, *where)
    return ck, cv


@jax.named_scope("layer/attn/kv_write")
def paged_window_update(leaf, win, pos, block_tables, valid=None,
                        layer=None, ring: bool = False):
    """One more per-token pool leaf written as :func:`paged_cache_update`
    writes K and V: the ``[B, H, T, width]`` window ``win`` lands at the same
    ``(layer, block, offset)`` of the stacked float leaf ``[L, NB, H,
    block_size, width]`` (either view).  A learned-sparse-attention model
    keeps its indexer's keys this way (``ops/sparse_index_attention.py``), a
    latent model its latents; ``ring``: the table is a window layer's ring
    (:func:`paged_cache_update`).  One shard only: a leaf whose head dim is 1
    has nothing to split over ``tp``."""
    if _DP_GROUPS > 1 or is_quantized_pool(leaf):
        raise NotImplementedError(
            "paged_window_update takes a float leaf outside a dp context")
    b, _, t, width = win.shape
    bs = int(np.prod(leaf.shape[3:])) // width
    where = _window_blocks(bs, b, t, pos, jnp.asarray(block_tables,
                                                      jnp.int32), valid, ring)
    return _write_blocks(leaf, win, jnp.asarray(layer, jnp.int32), *where)


@jax.named_scope("layer/attn/kv_write")
def paged_cache_update(ck, cv, k, v, pos, block_tables, valid=None,
                       layer=None, ring: bool = False):
    """Scatter a window of new keys/values into the paged pool, in place.

    ck/cv:         the stacked pool [L, NB, HKV, block_size, hd] with
                   ``layer`` the (traced) int32 index of the layer written
                   — or one layer's [NB, HKV, block_size, hd] with
                   ``layer=None`` (:func:`whole_pool`)
    k/v:           [B, HKV, T, hd] — T new tokens per row
    pos:           int32 scalar or [B] — global position of ``k[:, :, 0]``
                   per row (T == 1 decode: each row's own position; T > 1
                   chunked prefill: each row's chunk base)
    block_tables:  int32 [B, NBPER]
    valid:         optional int32 [B] — tokens of the T-window that are
                   real (default all T).  Invalid tokens, and positions
                   past the table's reach, write to scratch block 0.
    ring:          the table is a window layer's ring (module docstring
                   "Layer kinds"): logical block ``i`` is entry ``i %
                   width``, and no position is past its reach.

    Returns the pool in the shape it came in.  Under a configured tp
    context (module docstring) the scatter runs in ``shard_map``: each chip
    writes its own head shard of the pool (the k/v window arrives already
    head-sharded from the column-parallel kv projections);
    positions/tables/layer replicate.
    """
    one_layer = layer is None
    (ck, layer), (cv, _) = whole_pool(ck, layer), whole_pool(cv, layer)
    b = k.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    bt = jnp.asarray(block_tables, jnp.int32)
    n = head_shards(pool_payload(ck).shape[2], k.shape[1])
    sharded = _DP_GROUPS > 1 or n > 1
    if ring and sharded:
        raise NotImplementedError(
            "a ring table (window layers) is written on one shard")
    if sharded and valid is None:
        valid = jnp.full((b,), k.shape[2], jnp.int32)
    if _DP_GROUPS > 1:
        # dp_tp serving: rows + physical blocks shard over dp (heads over
        # tp when divisible); each shard scatters into its own pool chunk
        # through localized tables — no cross-shard traffic
        tp = _TP_AXIS if n > 1 else None
        ps, hp, dpsp = P(None, _DP_AXIS, tp), P(_DP_AXIS, tp), P(_DP_AXIS)
        gsize = _DP_GSIZE

        def body(ck, cv, k, v, pos, bt, valid, layer):
            bt = localize_block_tables(bt, gsize)
            return _paged_cache_update(ck, cv, k, v, pos, bt, valid, layer)

        ck, cv = jax.shard_map(
            body, mesh=_DP_MESH,
            in_specs=(ps, ps, hp, hp, dpsp, dpsp, dpsp, P()),
            out_specs=(ps, ps), check_vma=False)(
                ck, cv, k, v, pos, bt, jnp.asarray(valid, jnp.int32), layer)
    elif n > 1:
        # P(None, None, tp) is a valid spec for every record leaf too: qp
        # [L, NB, HKV, bs, hd] and ps [L, NB, HKV, bs] both carry the head
        # dim at index 2, and shard_map broadcasts a PartitionSpec leaf
        # over the record's pytree prefix
        ps, hs = P(None, None, _TP_AXIS), P(None, _TP_AXIS)
        ck, cv = head_shard_map(
            _paged_cache_update,
            (ps, ps, hs, hs, P(), P(), P(), P()), (ps, ps))(
                ck, cv, k, v, pos, bt, jnp.asarray(valid, jnp.int32), layer)
    else:
        ck, cv = _paged_cache_update(ck, cv, k, v, pos, bt, valid, layer,
                                     ring)
    if one_layer:
        ck, cv = jax.tree_util.tree_map(lambda a: a[0], (ck, cv))
    return ck, cv


def _paged_gather(pool_leaf, block_tables, layer, head_dim,
                  out_dtype=None):
    """Single-shard gather body of :func:`paged_gather` on the stacked
    pool (either view; ``head_dim`` tells them apart):
    ``pool[layer, block_tables]`` — called directly by the
    in-``shard_map`` attention bodies (``ops/decode_attention.py``) so
    sharded callers never re-enter the wrapper.  Only the rows' own blocks
    are read; no layer slice of the pool is materialized.  Quantized
    records gather codes + scales and dequantize (f32 expand, one cast to
    ``out_dtype`` — pass the query/compute dtype so bf16 models keep a
    bf16 residual stream, exactly like a float pool of that dtype); HBM
    moves int8 + scales, the expansion happens on-chip.  ``out_dtype``
    never touches a float pool (bit-identical reads)."""
    bt = jnp.maximum(block_tables, 0)
    b, nbper = bt.shape
    if is_quantized_pool(pool_leaf):
        from . import quantization as quant

        codes = _paged_gather(pool_leaf["qp"], bt, layer, head_dim)
        s = pool_leaf["ps"][layer, bt]                  # [B,NBPER,HKV,bs]
        hkv, bs = s.shape[2], s.shape[3]
        s = s.transpose(0, 2, 1, 3).reshape(b, hkv, nbper * bs)
        return quant.dequantize_kv(codes, s, out_dtype or jnp.float32)
    g = _unpack_block(pool_leaf[layer, bt], head_dim)  # [B,NBPER,HKV,bs,hd]
    _, _, hkv, bs, hd = g.shape
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

    def put(leaf, staged_leaf, i):
        # one in-place dynamic_update_slice per staged block column:
        # indifferent to the pool's layout, where a scatter over the
        # block dim alone (layers as window) could have XLA copy the pool
        for m in range(staged_leaf.shape[1]):
            leaf = jax.lax.dynamic_update_slice(
                leaf, staged_leaf[:, m:m + 1].astype(leaf.dtype),
                (0, i[m]) + (0,) * (leaf.ndim - 2))
        return leaf

    def scatter(p, s, i):
        return jax.tree_util.tree_map(lambda pl, sl: put(pl, sl, i), p, s)

    if n <= 1:
        return scatter(pool, staged, ids)
    hs = P(None, None, _TP_AXIS)
    return head_shard_map(scatter, (hs, hs, P()), hs)(pool, staged, ids)


def paged_gather(pool_leaf, block_tables, out_dtype=None, layer=None,
                 head_dim=None):
    """Materialize each row's logical cache view from the pool: the
    stacked ``[L, NB, HKV, bs, hd]`` pool at ``layer`` (or one layer's
    ``[NB, HKV, bs, hd]`` with ``layer=None``) through ``int32 [B, NBPER]``
    tables -> ``[B, HKV, NBPER*bs, hd]`` (int8 records dequantize — to
    ``out_dtype`` when given, f32 otherwise; float pools ignore
    ``out_dtype``).  ``head_dim`` (the model's) is needed to read a
    lane-packed pool (:func:`pack_pool`); without it the pool is taken as
    ``init_cache`` built it.  Unset (scratch) entries gather garbage that
    sits past every row's valid length — callers mask by position.  Under
    a configured tp context each chip gathers only its own head shard
    (output sharded ``[B, HKV/tp, S, hd]`` per chip)."""
    import functools

    pool_leaf, layer = whole_pool(pool_leaf, layer)
    if head_dim is None:
        head_dim = pool_payload(pool_leaf).shape[-1]
    bt = jnp.asarray(block_tables, jnp.int32)
    n = head_shards(pool_payload(pool_leaf).shape[2])
    gather = functools.partial(_paged_gather, head_dim=head_dim,
                               out_dtype=out_dtype)
    if n <= 1:
        return gather(pool_leaf, bt, layer)
    return head_shard_map(
        gather, (P(None, None, _TP_AXIS), P(), P()), P(None, _TP_AXIS))(
            pool_leaf, bt, layer)
