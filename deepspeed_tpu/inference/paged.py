"""Host-side management for the block-paged KV cache: free-list block
allocator + prefix cache (token-trie over full blocks).

The serving engine's KV pool is a single ``[L, num_blocks, HKV, block_size,
hd]`` buffer; each sequence owns an ``int32`` *block table* mapping its
logical block index (position // block_size) to a physical block.  This
module owns the host-side bookkeeping:

 - :class:`BlockAllocator` — fixed pool of refcounted blocks with a FIFO
   free list.  Physical block 0 is RESERVED as scratch: pad rows, inactive
   slots, and masked-out prefill tokens write their (discarded) KV there, so
   every device program keeps a fixed shape without a dedicated pad slot.
 - :class:`PrefixCache` — vLLM automatic-prefix-caching / SGLang
   RadixAttention at block granularity.  Keys are ``(parent entry id, block
   token tuple)`` chains, so a lookup walks the trie block by block: a new
   request whose prompt shares a block-aligned prefix with any previously
   prefilled sequence reuses those physical blocks with zero recompute.
   The cache holds one reference on each registered block; when the
   allocator runs dry the engine evicts least-recently-used leaf entries
   whose block nobody else holds (``evict_one``).

Copy-on-write is never needed: lookups are capped below the full prompt
(at least one tail token is always recomputed) and reuse is full-block
only, so a sequence's next write position always lands in a privately
owned block — shared blocks are read-only by construction.

Speculative decoding adds no allocator state: the scheduler allocates a
slot's table entries up to ``min(committed + K + 1, pos_cap)`` before each
verify round — ``pos_cap`` (prompt + completion budget) bounds demand, and
window positions past it scatter to the scratch block instead of
allocating.  Rolling back rejected draft tokens therefore never increfs,
decrefs, or frees anything; the scheduler's host-side length simply stays
at the committed value and the same blocks are rewritten in place next
round.  Preempting mid-window releases the slot's blocks exactly like the
non-speculative path (refcounts make prefix-shared blocks survive).

All of this is plain Python/numpy on the host; the device-side scatter /
gather twins live in ``ops/paged_kv.py`` and ``ops/decode_attention.py``.

**Tiered KV (host-DRAM block tier)**: :class:`HostBlockStore` is the tier
below the device pool — a host numpy arena (the pinned-staging analog of
``runtime/zero/offload.py``'s moment buffers) sized in whole KV blocks,
with its own free list and LRU entry table.  Entries are
content-addressed by :func:`chain_key` — a fixed-width rolling digest
over ALL tokens from position 0 through the end of the block — so the
same key that
names a block span in the prefix trie names its host copy, and a chain
demoted block-by-block is re-discoverable block-by-block (each key
stands alone; no host-side parent pointers).  Residency is exclusive by
construction: demotion MOVES a block's bytes device→host (the device
block frees), promotion moves them back (the host slot frees), and a
``staged`` entry (``in_flight``) is a promotion whose ``device_put`` has
been issued but whose pool scatter has not landed — the
``residency-conservation`` audit in ``analysis/invariants.py`` checks
that every arena slot is exactly one of free / resident / in-flight and
that in-flight flags stay in lockstep with the engine's staged-prefetch
records.  Every entry additionally carries a :func:`block_checksum`
integrity record computed when the bytes enter the tier and re-verified
whenever they leave it (promotion staging, cross-replica export/import)
— a host-DRAM bit flip is detected at the exit point, the corrupt entry
is dropped, and the chain recomputes from tokens instead of serving
corrupt KV (docs/reliability.md).

**NVMe third tier** (ZeRO-Infinity's HBM↔DRAM↔NVMe ladder, serving
edition): :class:`NvmeBlockStore` is a fixed-slot spill FILE below the
host arena, driven by ``ops/aio.py`` (``async_pwrite`` spill /
``async_pread`` load, batched per chain with per-op status).  A
:class:`HostBlockStore` built with an attached NVMe store spills its LRU
tail past a high watermark instead of discarding it, adding a FOURTH
residency state — *spilled*: the entry's ``chain_key`` + ``checksum``
stay in the host-side table but its bytes live only in the spill file.
Spilled entries still answer :meth:`HostBlockStore.probe_run` (a
returning session's prefix survives arbitrary idle), and
:meth:`HostBlockStore.promote_spilled` moves them back into arena slots
before staging — verifying the checksum at the NVMe exit exactly like
every arena exit, so an NVMe bit flip (or a failed read, surfaced per-op
by ``AsyncIOHandle.wait_statuses``) drops the entry and recomputes
instead of serving stale or corrupt staging bytes.  Residency stays
exclusive across all three tiers: a key is device-resident, arena-
resident/in-flight, or spilled — never two at once (the
``residency-conservation`` audit covers the ladder end to end).

**Tensor parallelism**: everything in this module is per-host and
head-sharding-invariant.  Block ids, refcounts, and trie keys index
PHYSICAL BLOCKS (position spans), never attention heads — when the
serving engine shards the device pool over the KV-head dim
(``NamedSharding(mesh, P(None, None, "tp"))``), every chip holds the same
``num_blocks`` blocks, just a head slice of each, and the SAME block
table drives every shard's scatter/gather.  Allocator and trie state
therefore needs no replication, synchronization, or tp-aware branching:
one host-side instance is correct at any tp degree, and scheduling
decisions (admission, eviction, preemption) are bit-identical across
topologies.
"""

from __future__ import annotations

import dataclasses
import hashlib
import zlib
from collections import OrderedDict, deque
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..ops.aio import AsyncIOHandle, swap_chain_read, swap_chain_write

#: physical block 0 is never allocated; discarded writes are routed there
SCRATCH_BLOCK = 0


class TransportError(RuntimeError):
    """A KV transport operation (demote / promote / cross-replica
    export / import) failed.  ``transient=True`` means the caller may
    retry with backoff; ``transient=False`` is a permanent fault — the
    caller must fall back to local recompute (the contents are always
    recomputable from tokens, just not for free).  Raised by the
    fault-injection harness (``serving/faults.py``) in CPU-sim and by a
    real RPC/RDMA fabric in a multi-host deployment."""

    def __init__(self, op: str, transient: bool = True,
                 detail: str = ""):
        super().__init__(
            f"KV transport '{op}' failed "
            f"({'transient' if transient else 'permanent'})"
            + (f": {detail}" if detail else ""))
        self.op = op
        self.transient = bool(transient)


def block_checksum(block_arrays: Sequence[np.ndarray]) -> int:
    """Integrity checksum of one KV block's per-leaf byte content
    (crc32 chained across leaves — xxhash-style speed from zlib's C
    loop, no new dependency).  Computed when bytes enter the host tier
    and re-verified whenever they leave it (promotion staging,
    cross-replica export/import), so a bit flip in host DRAM is caught
    BEFORE the corrupt KV can reach a device pool — corrupt chains are
    dropped and recomputed, never served (docs/reliability.md)."""
    c = 0
    for a in block_arrays:
        c = zlib.crc32(np.ascontiguousarray(a).tobytes(), c)
    return c & 0xFFFFFFFF


#: chain keys are fixed-width blake2b digests; 16 bytes keeps the alias
#: probability below 2^-64 even across billions of cached blocks
CHAIN_KEY_BYTES = 16

#: seed digest for block 0 of every chain (the "empty prefix" state)
_CHAIN_SEED = b"\x00" * CHAIN_KEY_BYTES


def chain_key(tokens, block_index: int, block_size: int) -> bytes:
    """Content address of the ``block_index``-th KV block of a sequence:
    a rolling blake2b digest chained over every token from position 0
    through the end of that block.  Cumulative on purpose — KV at a
    position attends over the whole prefix, so two blocks hold identical
    KV iff their full leading token chains match, and each key stands
    alone (a host-resident run is probed block-by-block with no parent
    pointers).

    Keys are a FIXED :data:`CHAIN_KEY_BYTES` bytes regardless of chain
    depth.  Earlier builds used the raw int32 byte string of the whole
    leading chain, which grew without bound (block ``i``'s key was
    ``4 * block_size * (i + 1)`` bytes — quadratic total at 128k-token
    contexts) and made key handling depend on chain position; the rolling
    digest keeps the prefix-dependence property (``h_i = H(h_{i-1} ||
    tokens of block i)``) with O(1) keys.  MIGRATION: host/NVMe stores
    persisted by a pre-digest build hold raw-chain keys that will never
    match — drop such stores (entries are caches; chains recompute from
    tokens) rather than carrying them across the format change."""
    return chain_keys(tokens, int(block_index) + 1, block_size)[-1]


def chain_keys(tokens, n_blocks: int, block_size: int) -> List[bytes]:
    """:func:`chain_key` for blocks ``0..n_blocks-1`` in one pass:
    serialize the tokens once and roll the digest forward block by block
    — O(len) total.  Byte-for-byte equal to per-block :func:`chain_key`
    calls (pinned by a tier-1 test)."""
    bs = int(block_size)
    n = int(n_blocks) * bs
    buf = np.ascontiguousarray(np.asarray(tokens[:n], np.int32)).tobytes()
    keys: List[bytes] = []
    h = _CHAIN_SEED
    for i in range(int(n_blocks)):
        h = hashlib.blake2b(h + buf[4 * bs * i:4 * bs * (i + 1)],
                            digest_size=CHAIN_KEY_BYTES).digest()
        keys.append(h)
    return keys


class BlockAllocator:
    """Refcounted free-list allocator over ``num_blocks`` KV blocks.

    Block ids are ``1 .. num_blocks-1`` (:data:`SCRATCH_BLOCK` is reserved).
    ``alloc`` hands out a block with refcount 1; sharing (prefix reuse, the
    prefix cache's own hold) goes through ``incref``/``decref``; a block
    returns to the free list when its count reaches zero.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (1 scratch + 1 usable), got "
                f"{num_blocks}")
        self.num_blocks = int(num_blocks)
        self._free = deque(range(1, num_blocks))
        self._ref = [0] * num_blocks
        #: bumped on every alloc/incref/decref — anything derived from
        #: refcounts (free counts, prefix-cache evictability) is stale iff
        #: this moved, which lets the scheduler memoize its admission gate
        #: while the queue head is blocked
        self.version = 0

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        """Blocks currently held by at least one owner (excludes scratch)."""
        return self.num_blocks - 1 - len(self._free)

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def snapshot(self):
        """(refcounts copy, free-list copy) for the invariant checker
        (``analysis/invariants.py``) — read-only view of allocator state."""
        return list(self._ref), list(self._free)

    def alloc(self) -> Optional[int]:
        """A fresh block with refcount 1, or ``None`` when the pool is dry
        (the caller then evicts from the prefix cache / preempts)."""
        if not self._free:
            return None
        b = self._free.popleft()
        assert self._ref[b] == 0, f"block {b} on free list with refs"
        self._ref[b] = 1
        self.version += 1
        return b

    def incref(self, block: int) -> None:
        assert self._ref[block] > 0, f"incref on unowned block {block}"
        self._ref[block] += 1
        self.version += 1

    def decref(self, block: int) -> None:
        assert self._ref[block] > 0, f"decref on unowned block {block}"
        self._ref[block] -= 1
        self.version += 1
        if self._ref[block] == 0:
            self._free.append(block)


class WindowRing:
    """The sliding-window layer kind's blocks (``ops/paged_kv.py`` "Layer
    kinds"): an allocator over a block-id space of its own and one RING
    table a row, logical block ``i`` at entry ``i % width``.  ``width`` is
    ``ceil((window + chunk) / block_size) + 1`` — the window behind a
    dispatch's first query plus the dispatch's own positions, one block
    more for windows that start mid-block — and the pool holds a whole ring
    for every row at once, so this kind never runs dry.  A row's live
    logical blocks are ``[lo[row], hi[row])``."""

    def __init__(self, rows: int, window: int, chunk: int, block_size: int):
        self.window, self.block_size = int(window), int(block_size)
        self.width = -(-(self.window + int(chunk)) // self.block_size) + 1
        self.alloc = BlockAllocator(1 + rows * self.width)
        self.tables = np.zeros((rows, self.width), np.int32)
        self.lo = np.zeros(rows, np.int64)
        self.hi = np.zeros(rows, np.int64)
        self.peak = 0          # most blocks in use after any advance
        self.released = 0      # blocks released behind a row's window

    def advance(self, row: int, first_query: int, upto: int) -> None:
        """For a dispatch whose first query sits at ``first_query`` and
        whose last written position is ``upto - 1``: release the row's
        blocks wholly behind ``first_query - window`` (no query from there
        on keeps a key of them; their entries go back to scratch) — in the
        dispatch that passes them — then allocate up to ``upto``."""
        bs, width, table = self.block_size, self.width, self.tables[row]
        lo = max(first_query - self.window + 1, 0) // bs
        for li in range(int(self.lo[row]), min(lo, int(self.hi[row]))):
            self.alloc.decref(int(table[li % width]))
            table[li % width] = 0
            self.released += 1
        self.lo[row] = max(lo, self.lo[row])
        hi = -(-upto // bs)
        if hi - self.lo[row] > width:
            raise RuntimeError(
                f"row {row}: positions {first_query}..{upto} reach over "
                f"{hi - self.lo[row]} window blocks, the ring holds {width}")
        for li in range(max(int(self.hi[row]), int(self.lo[row])), hi):
            table[li % width] = self.alloc.alloc()
        self.hi[row] = max(hi, self.hi[row])
        self.peak = max(self.peak, self.alloc.blocks_in_use)

    def release(self, row: int) -> None:
        """Free everything the row holds (finished, preempted)."""
        for b in self.tables[row][self.tables[row] != 0]:
            self.alloc.decref(int(b))
        self.tables[row] = 0
        self.lo[row] = self.hi[row] = 0


class NoBlocks:
    """The allocator's face of a cache tree with NO paged leaf (a model
    whose every layer keeps a recurrent state a slot: ``ops/paged_kv.py``
    "The state kind"): there is no block, so none is free, none in use and
    none can be asked for (it has no ``alloc``) — the scheduler admits such
    a model's requests by a free slot and never comes here for one."""

    num_blocks = free_blocks = blocks_in_use = version = 0


class GroupedBlockAllocator:
    """:class:`BlockAllocator` partitioned into ``groups`` contiguous
    spans of ``num_blocks // groups`` physical blocks — one span per dp
    shard of a ``dp_tp``-mode pool (``inference/serving.py``).

    Global block ids stay the currency everywhere (tables, audits,
    telemetry); internally each group runs its own refcounted free list
    over local ids and allocation is group-scoped, so a sequence's blocks
    all land inside its dp shard's pool chunk.  Each group's local block 0
    (global ``g * group_size``) is that group's scratch and is never
    handed out; global block 0 doubles as the table-wide "unset" sentinel,
    exactly as in the flat allocator.
    """

    def __init__(self, num_blocks: int, groups: int):
        if groups < 1:
            raise ValueError(f"groups must be >= 1, got {groups}")
        if num_blocks % groups:
            raise ValueError(
                f"num_blocks ({num_blocks}) must divide evenly over "
                f"{groups} groups")
        self.num_blocks = int(num_blocks)
        self.groups = int(groups)
        self.group_size = self.num_blocks // self.groups
        if self.group_size < 2:
            raise ValueError(
                f"{num_blocks} blocks over {groups} groups leaves "
                f"{self.group_size} per group — need >= 2 (scratch + 1)")
        self._groups = [BlockAllocator(self.group_size)
                        for _ in range(self.groups)]

    @property
    def version(self) -> int:
        return sum(g.version for g in self._groups)

    @property
    def free_blocks(self) -> int:
        return sum(g.free_blocks for g in self._groups)

    @property
    def blocks_in_use(self) -> int:
        """Blocks held by at least one owner (excludes every group's
        scratch)."""
        return self.num_blocks - self.groups - self.free_blocks

    def group_of(self, block: int) -> int:
        return int(block) // self.group_size

    def group_free(self, group: int) -> int:
        """Free blocks remaining in ``group`` (admission placement)."""
        return self._groups[group].free_blocks

    def refcount(self, block: int) -> int:
        g, l = divmod(int(block), self.group_size)
        return self._groups[g].refcount(l)

    def snapshot(self):
        """Merged global-id view, same shape as
        :meth:`BlockAllocator.snapshot`: (refcounts list indexed by global
        id, free list of global ids)."""
        refs: List[int] = []
        free: List[int] = []
        for g, alloc in enumerate(self._groups):
            r, f = alloc.snapshot()
            refs.extend(r)
            free.extend(g * self.group_size + l for l in f)
        return refs, free

    def alloc(self, group: int = 0) -> Optional[int]:
        """A fresh block from ``group`` (global id), or ``None`` when that
        group's span is dry — capacity pressure is per-group by design."""
        local = self._groups[group].alloc()
        return None if local is None else group * self.group_size + local

    def incref(self, block: int) -> None:
        g, l = divmod(int(block), self.group_size)
        self._groups[g].incref(l)

    def decref(self, block: int) -> None:
        g, l = divmod(int(block), self.group_size)
        self._groups[g].decref(l)


@dataclasses.dataclass
class _PrefixEntry:
    uid: int                    # stable id for child keys (never reused)
    key: tuple                  # (parent uid | 0, token tuple)
    block: int                  # physical block holding this token span's KV
    parent: Optional["_PrefixEntry"]
    children: int = 0


class PrefixCache:
    """Token-trie over FULL KV blocks: chained ``(parent, tokens)`` keys.

    ``lookup`` walks a prompt block by block and claims (increfs) the
    longest cached block-aligned prefix; ``register`` inserts a freshly
    prefilled prompt's full blocks, with the cache itself holding one
    reference so the blocks outlive the sequence.  ``evict_one`` releases
    the least-recently-used *leaf* entry whose block only the cache still
    holds — parents are only evictable once all their children are gone, so
    every cached chain stays walkable from the root.
    """

    def __init__(self, block_size: int):
        self.block_size = int(block_size)
        self._entries: "OrderedDict[tuple, _PrefixEntry]" = OrderedDict()
        self._next_uid = 1
        # counters for ServingEngine.stats()
        self.lookups = 0
        self.hit_blocks = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def entries(self):
        """The live trie entries (insertion/LRU order) — read-only view for
        the invariant checker (``analysis/invariants.py``)."""
        return list(self._entries.values())

    def probe(self, tokens: Sequence[int], max_tokens: int) -> int:
        """Number of leading full blocks of ``tokens[:max_tokens]`` present
        in the trie — no refcounts touched (admission-gate peek).
        Exactly ``len(chain_blocks(...))`` so the admission gate and the
        router/export peek can never walk the trie differently."""
        return len(self.chain_blocks(tokens, max_tokens))

    def chain_blocks(self, tokens: Sequence[int],
                     max_tokens: int) -> List[int]:
        """Physical block ids of the cached leading chain of
        ``tokens[:max_tokens]`` — :meth:`probe`'s block-id twin: no
        refcounts touched and no LRU recency (a router/export peek must
        not perturb eviction order)."""
        bs = self.block_size
        parent_uid, out = 0, []
        for i in range(min(len(tokens), int(max_tokens)) // bs):
            e = self._entries.get(
                (parent_uid, tuple(int(t) for t in
                                   tokens[i * bs:(i + 1) * bs])))
            if e is None:
                break
            parent_uid = e.uid
            out.append(int(e.block))
        return out

    def lookup(self, tokens: Sequence[int], max_tokens: int,
               allocator: BlockAllocator) -> List[int]:
        """Claim the longest cached block-aligned prefix of
        ``tokens[:max_tokens]``: increfs and returns the physical block ids
        (the caller owns one reference per returned block)."""
        bs = self.block_size
        blocks: List[int] = []
        parent_uid = 0
        self.lookups += 1
        for i in range(min(len(tokens), max_tokens) // bs):
            key = (parent_uid,
                   tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))
            e = self._entries.get(key)
            if e is None:
                break
            self._entries.move_to_end(key)      # LRU touch
            allocator.incref(e.block)
            blocks.append(e.block)
            parent_uid = e.uid
        self.hit_blocks += len(blocks)
        return blocks

    def register(self, tokens: Sequence[int], blocks: Sequence[int],
                 allocator: BlockAllocator, start: int = 0) -> None:
        """Insert the chain ``tokens[(start+i)*bs:(start+i+1)*bs] ->
        blocks[i]``.  Existing entries win (the first prefill of a shared
        prompt is the canonical copy; a duplicate block simply isn't cached
        and frees with its sequence) — the chain continues through them
        either way.  ``start > 0`` (tiered-KV promotion) grafts the chain
        onto the entries for blocks ``0..start-1``, which must already be
        live — the caller just claimed them via :meth:`lookup`."""
        bs = self.block_size
        parent: Optional[_PrefixEntry] = None
        for i in range(start):
            parent = self._entries[
                ((parent.uid if parent else 0),
                 tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))]
        for i, b in enumerate(blocks, start=start):
            key = ((parent.uid if parent else 0),
                   tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))
            e = self._entries.get(key)
            if e is None:
                e = _PrefixEntry(uid=self._next_uid, key=key, block=int(b),
                                 parent=parent)
                self._next_uid += 1
                allocator.incref(int(b))
                if parent is not None:
                    parent.children += 1
                self._entries[key] = e
            self._entries.move_to_end(key)
            parent = e

    def evictable(self, allocator: BlockAllocator) -> int:
        """Blocks reclaimable by repeated :meth:`evict_one` calls.  A block
        whose refcount is exactly 1 is held only by the cache; any live
        sequence using a child of an entry also holds the parent's block
        (prefix chains are claimed whole), so refcount-1 entries always
        drain leaf-first."""
        return sum(1 for e in self._entries.values()
                   if allocator.refcount(e.block) == 1)

    def evict_one(self, allocator: BlockAllocator) -> Optional[int]:
        """Release the LRU leaf entry only the cache still holds; returns
        the freed block id (truthy — block 0 is scratch and never cached)
        or ``None``.  The id lets the caller retire per-block side state in
        lockstep with the free (the serving engine's int8-KV scale ledger,
        ``serving.py``)."""
        for e in self.evictable_leaves(allocator, 1):
            self.evict_entry(e, allocator)
            return int(e.block)
        return None

    def evictable_leaves(self, allocator: BlockAllocator,
                         limit: int) -> List[_PrefixEntry]:
        """Up to ``limit`` LRU-first leaf entries whose block only the
        cache still holds — the next eviction victims, exposed as a batch
        so the tiered-KV engine can demote their contents to host DRAM in
        ONE device round trip before releasing them."""
        out: List[_PrefixEntry] = []
        for e in self._entries.values():        # oldest first
            if e.children == 0 and allocator.refcount(e.block) == 1:
                out.append(e)
                if len(out) >= limit:
                    break
        return out

    def evict_entry(self, entry: _PrefixEntry,
                    allocator: BlockAllocator) -> None:
        """Release one specific (evictable-leaf) entry — the targeted twin
        of :meth:`evict_one` for batch demotion."""
        assert entry.children == 0 and \
            allocator.refcount(entry.block) == 1, \
            f"evict_entry on a non-evictable entry uid={entry.uid}"
        del self._entries[entry.key]
        if entry.parent is not None:
            entry.parent.children -= 1
        allocator.decref(entry.block)
        self.evictions += 1

    def chain_tokens(self, entry: _PrefixEntry) -> Tuple[int, ...]:
        """The FULL leading token chain of an entry (root span through the
        entry's own span) — exactly the tokens :func:`chain_key` hashes,
        recovered by walking the parent links."""
        spans = []
        e: Optional[_PrefixEntry] = entry
        while e is not None:
            spans.append(e.key[1])
            e = e.parent
        out: List[int] = []
        for span in reversed(spans):
            out.extend(span)
        return tuple(out)


@dataclasses.dataclass
class _NvmeEntry:
    key: bytes                  # chain_key of the block's content
    slot: int                   # file slot (byte offset = slot * nbytes)
    checksum: int = 0           # block_checksum of the spilled bytes


class NvmeBlockStore:
    """NVMe spill file below the host arena — the third rung of the
    serving KV ladder (module docstring "NVMe third tier").

    A fixed-slot file of ``num_blocks`` whole-KV-block records (slot
    ``i`` at byte offset ``i * block_nbytes``) driven by
    :class:`~deepspeed_tpu.ops.aio.AsyncIOHandle` — batched chain writes
    on spill, batched chain reads on load, each op's success surfaced
    individually (``wait_statuses``) so one failed read drops exactly
    one entry.  Entries keep the block's :func:`chain_key` and
    :func:`block_checksum`; :meth:`swap_in` re-hashes the bytes read
    back and refuses a mismatch — the NVMe exit is gated exactly like
    every arena exit.  LRU within the tier: spilling onto a full store
    discards the oldest spilled entry (the coldest bytes in the whole
    ladder — recomputable from tokens, just not for free)."""

    def __init__(self, num_blocks: int,
                 block_specs: Sequence[Tuple[tuple, object]],
                 path: str, *, io_threads: int = 4):
        if num_blocks < 1:
            raise ValueError(
                f"nvme tier num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self.path = str(path)
        self._specs: List[Tuple[tuple, np.dtype]] = [
            (tuple(shape), np.dtype(dtype)) for shape, dtype in block_specs]
        self._leaf_nbytes = [
            int(np.prod(shape, dtype=np.int64)) * dt.itemsize
            for shape, dt in self._specs]
        self.block_nbytes = int(sum(self._leaf_nbytes))
        self._io = AsyncIOHandle(num_threads=int(io_threads))
        self._free = deque(range(self.num_blocks))
        self._entries: "OrderedDict[bytes, _NvmeEntry]" = OrderedDict()
        # counters (the engine folds these into stats()/metrics)
        self.spills = 0
        self.loads = 0
        self.evictions = 0
        self.write_failures = 0
        self.read_failures = 0
        self.checksum_rejects = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - len(self._free)

    def has(self, key: bytes) -> bool:
        return key in self._entries

    def touch(self, key: bytes) -> None:
        self._entries.move_to_end(key)

    def checksum_of(self, key: bytes) -> int:
        return self._entries[key].checksum

    def pop(self, key: bytes) -> None:
        """Release an entry (its bytes now live in a tier above, or are
        being discarded): the file slot frees for reuse."""
        e = self._entries.pop(key)
        self._free.append(e.slot)

    def nvme_snapshot(self):
        """(free-list copy, ``{key: slot}``) for the residency audit."""
        return list(self._free), {
            k: e.slot for k, e in self._entries.items()}

    # ------------------------------------------------------- serialization
    def _flatten(self, block_arrays: Sequence[np.ndarray]) -> np.ndarray:
        buf = np.empty(self.block_nbytes, np.uint8)
        off = 0
        for (shape, dt), arr, n in zip(self._specs, block_arrays,
                                       self._leaf_nbytes):
            flat = np.ascontiguousarray(arr, dtype=dt).reshape(-1)
            buf[off:off + n] = flat.view(np.uint8)
            off += n
        return buf

    def _unflatten(self, buf: np.ndarray) -> List[np.ndarray]:
        out: List[np.ndarray] = []
        off = 0
        for (shape, dt), n in zip(self._specs, self._leaf_nbytes):
            out.append(buf[off:off + n].view(dt).reshape(shape))
            off += n
        return out

    # ---------------------------------------------------------- transfers
    def swap_out(self, key: bytes, block_arrays: Sequence[np.ndarray],
                 checksum: int) -> bool:
        """Spill one block's bytes to the file under ``key``; ``False``
        when the write failed (the caller discards the block instead —
        never trust a slot whose write may not have landed).  A duplicate
        key keeps the existing record (content-addressed: same key, same
        bytes) and refreshes recency."""
        if key in self._entries:
            self._entries.move_to_end(key)
            return True
        if not self._free:
            old_key, old = next(iter(self._entries.items()))
            del self._entries[old_key]
            self._free.append(old.slot)
            self.evictions += 1
        slot = self._free.popleft()
        ok = swap_chain_write(self._io, self.path, [self._flatten(
            block_arrays)], [slot * self.block_nbytes])[0]
        if not ok:
            self._free.append(slot)
            self.write_failures += 1
            return False
        self._entries[key] = _NvmeEntry(key=key, slot=slot,
                                        checksum=int(checksum))
        self.spills += 1
        return True

    def swap_in(self, key: bytes) -> Optional[List[np.ndarray]]:
        """Read one spilled block's bytes back; verifies the per-op aio
        status AND the stored checksum at this NVMe exit.  On success the
        per-leaf arrays return and the entry REMAINS (the caller pops it
        once the bytes land in the tier above); on a failed read or a
        checksum mismatch the entry is dropped — the chain truncates
        there and recomputes from tokens."""
        e = self._entries[key]
        buf = np.empty(self.block_nbytes, np.uint8)
        ok = swap_chain_read(self._io, self.path, [buf],
                             [e.slot * self.block_nbytes])[0]
        if not ok:
            self.read_failures += 1
            self.pop(key)
            return None
        arrays = self._unflatten(buf)
        if block_checksum(arrays) != e.checksum:
            self.checksum_rejects += 1
            self.pop(key)
            return None
        self.loads += 1
        return arrays

    def close(self) -> None:
        self._io.close()


@dataclasses.dataclass
class _HostEntry:
    key: bytes                  # chain_key of the block's content
    slot: int                   # arena slot holding the block's bytes
    in_flight: bool = False     # promotion staged (device_put issued)
    checksum: int = 0           # block_checksum of the stored bytes


class HostBlockStore:
    """Host-DRAM tier below the device block pool (tiered KV).

    A numpy arena of ``num_blocks`` whole-KV-block slots per pool leaf
    (module docstring "Tiered KV") with a free list and an LRU entry
    table keyed by :func:`chain_key`.  The serving engine demotes cold
    blocks here instead of discarding their contents (prefix-cache
    eviction, preemption) and promotes them back when an admitted
    sequence's chain probes resident — the transfer machinery itself
    (fixed-shape gather/scatter programs, ``device_get``/``device_put``)
    lives in ``ops/paged_kv.py`` / ``serving.py``; this class is pure
    host bookkeeping plus the arena bytes.

    ``block_specs`` gives one ``(per_block_shape, dtype)`` per flattened
    pool leaf — a quantized pool's codes and scale rows are separate
    leaves, so they demote/promote together by construction.

    Entry states: *resident* (bytes live in the arena, slot owned),
    *in-flight* (a staged promotion — the engine has issued the H2D
    ``device_put`` but not yet scattered into the pool), or — with an
    attached :class:`NvmeBlockStore` — *spilled* (bytes live only in the
    spill file; the key + checksum stay discoverable).  In-flight
    entries are never LRU-evicted (the staged transfer would read freed
    bytes) and are released either by :meth:`pop` (promotion landed) or
    :meth:`mark_in_flight(key, False)`` (stale prefetch discarded).

    ``nvme`` + ``nvme_watermark``: past the watermark (a fraction of
    ``num_blocks``), :meth:`put` spills the arena's LRU tail to the NVMe
    store instead of discarding it — demotion past a FULL arena likewise
    spills the LRU victim.  :meth:`promote_spilled` is the way back up.
    """

    def __init__(self, num_blocks: int,
                 block_specs: Sequence[Tuple[tuple, object]],
                 *, nvme: Optional[NvmeBlockStore] = None,
                 nvme_watermark: float = 1.0):
        if num_blocks < 1:
            raise ValueError(
                f"host tier num_blocks must be >= 1, got {num_blocks}")
        if not (0.0 < float(nvme_watermark) <= 1.0):
            raise ValueError(
                f"nvme_watermark must be in (0, 1], got {nvme_watermark}")
        self._nvme = nvme
        #: arena occupancy above which put() spills LRU entries down
        self._hi_blocks = max(1, int(float(nvme_watermark)
                                     * int(num_blocks)))
        self.num_blocks = int(num_blocks)
        self.arenas: List[np.ndarray] = [
            np.zeros((self.num_blocks,) + tuple(shape), dtype)
            for shape, dtype in block_specs]
        self.block_nbytes = int(sum(a[0].nbytes for a in self.arenas))
        self._free = deque(range(self.num_blocks))
        self._entries: "OrderedDict[bytes, _HostEntry]" = OrderedDict()
        # counters for ServingEngine.stats()
        self.evictions = 0
        #: blocks refused by :meth:`import_chain`'s checksum gate (the
        #: engine folds deltas into serving_checksum_failures_total)
        self.checksum_rejects = 0
        #: bumped whenever the resident KEY SET changes (put/pop/LRU
        #: eviction) — probe results are stale iff this moved, which lets
        #: the engine memoize empty prefetch probes across idle
        #: iterations (same trick as BlockAllocator.version)
        self.version = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - len(self._free)

    @property
    def arena_bytes(self) -> int:
        return int(sum(a.nbytes for a in self.arenas))

    def snapshot(self):
        """(free-list copy, ``{key: (slot, in_flight)}``) for the
        residency-conservation audit (``analysis/invariants.py``)."""
        return list(self._free), {
            k: (e.slot, e.in_flight) for k, e in self._entries.items()}

    def has(self, key: bytes) -> bool:
        """Tier membership: arena-resident, in-flight, OR spilled — the
        key's bytes are somewhere in the host/NVMe ladder and a demotion
        of the same content would be redundant."""
        return key in self._entries or (
            self._nvme is not None and self._nvme.has(key))

    def is_spilled(self, key: bytes) -> bool:
        """True when the key's bytes live only in the NVMe spill file
        (they must :meth:`promote_spilled` before staging can read
        them)."""
        return key not in self._entries and \
            self._nvme is not None and self._nvme.has(key)

    def promote_spilled(self, keys: Sequence[bytes]) -> int:
        """Load the spilled entries of a probed run back into arena
        slots (NVMe → arena, the ladder's way up); returns the length of
        the leading run that is arena-resident afterwards.  Each load is
        verified at the NVMe exit (per-op aio status + checksum —
        :meth:`NvmeBlockStore.swap_in`); a failed or corrupt load drops
        that entry and truncates the run there, exactly like a failed
        arena-exit verify.  Loading may itself spill OTHER cold arena
        entries past the watermark (``put`` path) — never the run being
        promoted, whose entries are the arena's newest: the promoted
        count is capped at the watermark budget, because one more load
        would start re-spilling this very run's head (thrash).  Longer
        chains promote incrementally — the engine pops staged entries to
        the device as it goes, freeing budget for the tail.

        In-flight entries are subtracted from the budget: they occupy
        arena slots but can never spill, so ``_spill_to_watermark``
        skips them and would otherwise re-spill this run's own head the
        moment promoted-count + pinned-count crossed the watermark."""
        n = 0
        if self._nvme is not None:
            pinned = sum(1 for e in self._entries.values()
                         if e.in_flight)
            budget = max(0, self._hi_blocks - pinned)
        else:
            budget = self.num_blocks
        for key in keys:
            if n >= budget:
                break
            if key in self._entries:
                self._entries.move_to_end(key)
                n += 1
                continue
            if self._nvme is None or not self._nvme.has(key):
                break
            checksum = self._nvme.checksum_of(key)
            arrays = self._nvme.swap_in(key)
            if arrays is None:
                # dropped at the NVMe exit gate — probe results naming
                # this key are stale now
                self.version += 1
                break
            if self.put(key, arrays, checksum=checksum) is None:
                break       # arena saturated in-flight; entry stays spilled
            n += 1
        return n

    # ------------------------------------------------- nvme tier surface
    @property
    def nvme_blocks(self) -> int:
        return 0 if self._nvme is None else self._nvme.num_blocks

    @property
    def nvme_blocks_in_use(self) -> int:
        return 0 if self._nvme is None else self._nvme.blocks_in_use

    @property
    def nvme_spills(self) -> int:
        return 0 if self._nvme is None else self._nvme.spills

    @property
    def nvme_loads(self) -> int:
        return 0 if self._nvme is None else self._nvme.loads

    @property
    def nvme_evictions(self) -> int:
        return 0 if self._nvme is None else self._nvme.evictions

    @property
    def nvme_read_failures(self) -> int:
        return 0 if self._nvme is None else self._nvme.read_failures

    @property
    def nvme_write_failures(self) -> int:
        return 0 if self._nvme is None else self._nvme.write_failures

    @property
    def nvme_checksum_rejects(self) -> int:
        return 0 if self._nvme is None else self._nvme.checksum_rejects

    def nvme_snapshot(self):
        """(file free-list copy, ``{key: slot}``) — empty without an
        attached NVMe store; the residency audit checks spilled/resident
        exclusivity and file-slot conservation against it."""
        if self._nvme is None:
            return [], {}
        return self._nvme.nvme_snapshot()

    def put(self, key: bytes,
            block_arrays: Sequence[np.ndarray],
            checksum: Optional[int] = None) -> Optional[int]:
        """Store one demoted block's per-leaf arrays under ``key``;
        returns the arena slot, or ``None`` when every slot is pinned by
        in-flight entries (the caller then simply drops the demotion —
        the block's contents are recomputable, just not for free).  A
        duplicate key keeps the existing copy (first-writer-wins, same
        dedup rule as the trie) and refreshes its recency.  ``checksum``
        pins the entry's integrity record (cross-replica import reuses
        the exporter's sum); ``None`` computes it from the bytes."""
        if key in self._entries:
            self._entries.move_to_end(key)
            if self._nvme is not None and self._nvme.has(key):
                # exclusive residency: the arena copy wins (same bytes —
                # content-addressed), the file slot frees
                self._nvme.pop(key)
            return self._entries[key].slot
        if not self._free:
            if not self._evict_oldest(spill=self._nvme is not None):
                return None
        slot = self._free.popleft()
        for arena, arr in zip(self.arenas, block_arrays):
            arena[slot] = arr
        self._entries[key] = _HostEntry(
            key=key, slot=slot,
            checksum=(block_checksum(block_arrays)
                      if checksum is None else int(checksum)))
        if self._nvme is not None and self._nvme.has(key):
            self._nvme.pop(key)
        self.version += 1
        self._spill_to_watermark()
        return slot

    def _evict_oldest(self, spill: bool) -> bool:
        """LRU-evict the oldest non-in-flight entry; with ``spill`` its
        bytes move DOWN the ladder (``NvmeBlockStore.swap_out`` keeps
        key + checksum) instead of being discarded.  ``False`` when every
        entry is pinned in-flight."""
        for k, e in self._entries.items():  # oldest first
            if e.in_flight:
                continue
            if spill and self._nvme is not None:
                # a failed write counts on the nvme store and the block
                # simply discards — recomputable, never trusted half-spilled
                self._nvme.swap_out(
                    k, [arena[e.slot] for arena in self.arenas],
                    e.checksum)
            del self._entries[k]
            self._free.append(e.slot)
            self.evictions += 1
            self.version += 1
            return True
        return False

    def _spill_to_watermark(self) -> None:
        """Demote the LRU tail to NVMe until arena occupancy is back at
        the high watermark (no-op without an attached NVMe store)."""
        if self._nvme is None:
            return
        while self.blocks_in_use > self._hi_blocks:
            if not self._evict_oldest(spill=True):
                break

    def read(self, key: bytes) -> List[np.ndarray]:
        """Per-leaf views of a resident block's bytes (no copy)."""
        e = self._entries[key]
        return [arena[e.slot] for arena in self.arenas]

    def pop(self, key: bytes) -> None:
        """Release a block (promotion landed on device): the slot frees,
        the entry dies — residency moves back to the device tier."""
        e = self._entries.pop(key)
        self._free.append(e.slot)
        self.version += 1

    def mark_in_flight(self, key: bytes, flag: bool = True) -> None:
        self._entries[key].in_flight = bool(flag)

    def checksum_of(self, key: bytes) -> int:
        """The integrity record stored when the block entered the tier."""
        return self._entries[key].checksum

    def verify(self, key: bytes) -> bool:
        """Recompute the resident bytes' checksum against the stored
        record — ``False`` means the arena bytes were corrupted after the
        store (host-DRAM bit flip).  O(block bytes); called at the points
        bytes LEAVE the arena (promotion staging, export), never on the
        per-iteration probe path."""
        e = self._entries[key]
        return block_checksum([arena[e.slot] for arena in self.arenas]) \
            == e.checksum

    def drop_corrupt(self, key: bytes) -> None:
        """Discard an entry whose bytes failed :meth:`verify`: the slot
        frees and the chain truncates here — the contents recompute from
        tokens on the next admission (corrupt KV is never served)."""
        e = self._entries.pop(key)
        self._free.append(e.slot)
        self.version += 1

    def export_chain(self, keys: Sequence[bytes]) -> List[List[np.ndarray]]:
        """Per-block, per-leaf byte COPIES of resident blocks — the
        cross-replica KV-pull wire format: a snapshot, so later LRU
        eviction or promotion on THIS store cannot tear the exported
        bytes mid-transfer.  Quantized pools' int8 codes and scale rows
        are separate leaves of the same block, so they export together
        by construction."""
        return [[np.array(a) for a in self.read(k)] for k in keys]

    def export_checksums(self, keys: Sequence[bytes]) -> List[int]:
        """The stored integrity records for an exported chain — travels
        beside :meth:`export_chain`'s bytes so the importer can verify
        the transfer end-to-end (``import_chain``)."""
        return [self.checksum_of(k) for k in keys]

    def import_chain(self, keys: Sequence[bytes],
                     blocks: Sequence[Sequence[np.ndarray]],
                     checksums: Optional[Sequence[int]] = None) -> int:
        """Store an exported chain (same order as :meth:`export_chain`);
        stops at the first refused ``put`` (arena saturated with
        in-flight entries) so the imported run stays contiguous — a
        holed chain would be unreachable past the hole anyway
        (``probe_run`` walks contiguously).  With ``checksums`` (the
        exporter's :meth:`export_checksums`), every block's bytes are
        re-hashed on arrival and a mismatch STOPS the import there —
        bytes corrupted in the exporter's arena or in transit never
        enter this tier (``checksum_rejects`` counts them; the engine
        surfaces the total as ``serving_checksum_failures_total``).
        Returns blocks stored."""
        n = 0
        sums = list(checksums) if checksums is not None else None
        for i, (key, arrs) in enumerate(zip(keys, blocks)):
            want = sums[i] if sums is not None else None
            if want is not None and block_checksum(arrs) != int(want):
                self.checksum_rejects += 1
                break
            if self.put(key, arrs, checksum=want) is None:
                break
            n += 1
        return n

    def probe_run(self, tokens, start_block: int, max_tokens: int,
                  block_size: int) -> List[bytes]:
        """Keys of the longest host-resident run of full blocks
        ``start_block, start_block+1, ...`` of ``tokens[:max_tokens]`` —
        the continuation probe admission uses after the device trie's own
        hits end.  Spilled entries count as resident — their bytes are
        still in the ladder (``promote_spilled`` brings them up before
        staging).  No state is touched beyond LRU recency."""
        keys: List[bytes] = []
        n = min(len(tokens), int(max_tokens)) // int(block_size)
        if n <= int(start_block):
            return keys
        run = chain_keys(tokens, n, block_size)
        for i in range(int(start_block), n):
            key = run[i]
            if key in self._entries:
                self._entries.move_to_end(key)
            elif self._nvme is not None and self._nvme.has(key):
                self._nvme.touch(key)
            else:
                break
            keys.append(key)
        return keys
