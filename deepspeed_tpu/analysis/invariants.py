"""Paged-state invariant checker: an O(blocks) audit of the serving
engine's host-side bookkeeping after every scheduler round.

``inference/paged.py`` documents the invariants the block allocator, the
prefix trie, and the scheduler's block tables maintain *by convention* —
refcounts mirror owners, the free list never aliases live blocks, scratch
block 0 is never owned, trie chains stay walkable.  Every ROADMAP
direction that touches the pool (quantized KV, tiered offload,
multi-replica routing) mutates exactly this state, and a single leaked
refcount surfaces as an un-debuggable OOM (pool "full" of unowned
blocks) or — worse — two sequences silently sharing a writable block.
This module turns the prose into a checked contract.

Named invariants (the :class:`PagedStateError` ``invariant`` field, also
the fault-injection test matrix in ``tests/unit/test_analysis.py``):

``refcount-conservation``
    Every block's refcount equals the number of holders that can ever
    decref it: slot ``held`` lists + prefix-trie entries.  A higher count
    is a leak (the block can never return to the free list); a lower one
    is a double-free in waiting.
``free-list-disjoint``
    The free list is duplicate-free, contains only refcount-0 blocks,
    never the scratch block, and shares no block with any holder; and
    every refcount-0 non-scratch block IS on the free list (nothing
    leaks out of the pool entirely).
``scratch-aliasing``
    Scratch block 0 is never held, never cached in the trie, and never
    addressed by the *allocated* span of a live table (table entry 0
    doubles as the "unset" marker, so an unset entry inside a span the
    sequence needs means its KV is silently landing in — and reading
    garbage from — the scratch block).
``trie-parent-child``
    Chains stay walkable (every entry's parent is a live entry) and
    ``children`` counters match the live child count — the two facts
    ``evict_one``'s leaf-first drain depends on.  Note the *naive*
    strengthening "parent block refcount >= child block refcount" is NOT
    an invariant: ``register``'s first-writer-wins dedup means a request
    that independently prefilled duplicate content holds its own copy of
    the parent span while the trie caches the child span's fresh block —
    a legal state where the child's block out-refs the parent's (pinned
    by a tier-1 eos-parity trace).  Trie-claimed references do chain
    whole, but refcounts cannot isolate them from duplicate holders.
``length-occupancy``
    Per active slot: the table's nonzero entries form one contiguous
    leading span, that span matches the slot's ``held`` blocks exactly
    (no divergence between the device-visible table and the host's
    ownership record), no physical block appears twice in a slot, and
    the span covers every token the slot has committed (``lengths`` /
    prefill base).  Inactive slots are fully zeroed.
``scale-lockstep``
    int8-KV engines only (``quantize="kv8"``): the per-block scale table
    is allocated and retired in lockstep with the blocks.  The engine's
    host ledger of live-scale blocks must cover every owner-held block
    (a held block outside the ledger means its reads would dequantize a
    previous owner's stale scales), contain only blocks with a nonzero
    refcount (a ledger entry surviving the free is a stale scale row
    waiting to be trusted), and never the scratch block.
``router-request-uniqueness``
    multi-replica router (``deepspeed_tpu/serving/``): every live
    request is queued or active on EXACTLY ONE replica — a request on
    two replicas would decode twice and race its own handle; a handle
    the router maps to replica R whose request actually lives on S is a
    lost cancel (``cancel`` would land on the wrong engine).
``router-drain-quiesced``
    a drained replica holds no pending or active requests — drain hands
    everything off by contract, so anything left behind is a request no
    worker thread will ever step again.
``router-failure-state``
    crash recovery (``ReplicaRouter.fail``): a crash-failed replica
    owns ZERO uids — ``fail`` must salvage and scrub the dead replica's
    host-side bookkeeping, so anything left behind was never re-homed
    and will never be stepped — and no live (not-done) handle maps to a
    failed replica: every live handle's owner is a live survivor, or
    the handle was resolved loudly (``RequestFailedError``) when the
    re-home budget ran out.
``residency-conservation``
    tiered-KV engines only (``host_blocks > 0``): every host-arena slot
    is exactly one of free / resident (owned by exactly one entry) /
    in-flight (a staged promotion), and the in-flight flags stay in
    lockstep with the engine's staged-prefetch records — an in-flight
    entry no staged record references is a LEAKED in-flight block (its
    arena slot can never free: ``put`` refuses to LRU-evict in-flight
    entries), and a record referencing a resident-but-unflagged entry is
    a staging buffer whose bytes the LRU can free mid-transfer.

The audit reads pure host state (numpy + lists) — no device sync — and
runs in O(num_blocks + trie entries).  ``ServingEngine`` calls it after
every scheduler iteration when ``debug_checks`` is on, and tier-1 serving
tests run with it unconditionally; with the flag off the cost is one
branch per iteration.
"""

from __future__ import annotations

from typing import Dict, Optional

#: mirror of ``inference.paged.SCRATCH_BLOCK`` — importing it would cycle
#: (serving imports this module; the inference package imports serving);
#: pinned by a tier-1 test instead
SCRATCH_BLOCK = 0


class PagedStateError(RuntimeError):
    """A paged-KV bookkeeping invariant does not hold; ``invariant`` names
    which one (see module docstring)."""

    def __init__(self, invariant: str, detail: str):
        super().__init__(
            f"paged-state invariant '{invariant}' violated: {detail}")
        self.invariant = invariant
        self.detail = detail


def _blocks_for(num_tokens: int, block_size: int) -> int:
    return -(-int(num_tokens) // int(block_size))


def audit_paged_state(allocator, tables, held, *,
                      prefix=None,
                      active_needs: Optional[Dict[int, int]] = None,
                      block_size: int = 1,
                      scale_live=None,
                      scratch_blocks=None,
                      window_frontiers: Optional[Dict[int, int]] = None,
                      landmark_blocks: int = 0) -> None:
    """Verify every invariant over one engine's host state; raises
    :class:`PagedStateError` naming the first violated invariant.

    allocator:     :class:`~deepspeed_tpu.inference.paged.BlockAllocator`.
    tables:        int array ``[slots, nbper]`` of physical block ids
                   (entry 0 = scratch doubles as "unset").
    held:          per-slot list of owned block ids (the host ownership
                   record the release path decrefs).
    prefix:        optional :class:`PrefixCache` (``None`` with
                   ``prefix_caching=False``).
    active_needs:  ``slot -> committed token count`` for live slots; slots
                   absent from the map must be fully released.
    block_size:    tokens per block (converts needs to table spans).
    scale_live:    optional set of block ids whose int8-KV scale rows are
                   live (``quantize="kv8"`` engines); ``None`` skips the
                   ``scale-lockstep`` check entirely.
    scratch_blocks: the set of reserved scratch block ids — default
                   ``{SCRATCH_BLOCK}``; a dp_tp engine passes every dp
                   group's base block (``inference/serving.py``).  Block
                   id 0 stays the table-wide "unset" sentinel either way;
                   a NONZERO scratch id appearing in a table span is an
                   error in its own right.
    window_frontiers: resident-window serving
                   (``ServingEngine(resident_window_blocks=N)``): ``slot
                   -> first device-resident non-landmark block index``.
                   A slot whose frontier exceeds ``landmark_blocks`` is
                   audited with the WINDOW occupancy shape instead of the
                   contiguous one: entries ``[0, landmark_blocks)`` set
                   (pinned landmarks), ``[landmark_blocks, frontier)``
                   unset (demoted to the host tier — the slide must zero
                   exactly what it demotes), ``[frontier, span)`` set
                   contiguously, and ``owned == mapped`` over the two
                   resident runs.
    landmark_blocks: leading blocks pinned on-device per windowed slot.
    """
    ref, free = allocator.snapshot()
    num_blocks = allocator.num_blocks
    entries = prefix.entries() if prefix is not None else []
    active_needs = active_needs or {}
    scratch = frozenset(int(b) for b in scratch_blocks) \
        if scratch_blocks is not None else frozenset({SCRATCH_BLOCK})

    # ---- refcount-conservation: owners (held lists + trie) == refcounts
    expected = [0] * num_blocks
    for slot, blocks in enumerate(held):
        for b in blocks:
            if not (0 <= int(b) < num_blocks):
                raise PagedStateError(
                    "refcount-conservation",
                    f"slot {slot} holds out-of-range block {b} "
                    f"(pool has {num_blocks})")
            expected[int(b)] += 1
    for e in entries:
        if not (0 <= int(e.block) < num_blocks):
            raise PagedStateError(
                "refcount-conservation",
                f"trie entry uid={e.uid} caches out-of-range block "
                f"{e.block} (pool has {num_blocks})")
        expected[int(e.block)] += 1
    for b in range(num_blocks):
        if b in scratch:
            continue
        if ref[b] != expected[b]:
            kind = "leaked (unreclaimable)" if ref[b] > expected[b] \
                else "under-counted (double-free in waiting)"
            raise PagedStateError(
                "refcount-conservation",
                f"block {b}: refcount {ref[b]} != {expected[b]} owners "
                f"(held lists + trie entries) — {kind}")
    for sb in sorted(scratch):
        if ref[sb] != 0 or expected[sb] != 0:
            raise PagedStateError(
                "scratch-aliasing",
                f"scratch block {sb} is owned (refcount "
                f"{ref[sb]}, {expected[sb]} holders) — "
                "it must stay unallocated")

    # ---- free-list-disjoint
    free_set = set(int(b) for b in free)
    if len(free_set) != len(free):
        raise PagedStateError("free-list-disjoint",
                              "free list contains duplicate block ids")
    if free_set & scratch:
        raise PagedStateError(
            "free-list-disjoint",
            f"scratch block(s) {sorted(free_set & scratch)} on the free "
            "list")
    for b in free_set:
        if ref[b] != 0:
            raise PagedStateError(
                "free-list-disjoint",
                f"block {b} is on the free list with refcount {ref[b]}")
        if expected[b] != 0:
            raise PagedStateError(
                "free-list-disjoint",
                f"block {b} is on the free list but has {expected[b]} "
                "live holder(s)")
    for b in range(num_blocks):
        if b in scratch:
            continue
        if ref[b] == 0 and b not in free_set:
            raise PagedStateError(
                "free-list-disjoint",
                f"block {b} has refcount 0 but is not on the free list "
                "(leaked out of the pool)")

    # ---- trie-parent-child
    live = set(id(e) for e in entries)
    child_count: Dict[int, int] = {}
    for e in entries:
        if int(e.block) in scratch:
            raise PagedStateError(
                "scratch-aliasing",
                f"trie entry uid={e.uid} caches a scratch block "
                f"({e.block})")
        if e.parent is not None:
            if id(e.parent) not in live:
                raise PagedStateError(
                    "trie-parent-child",
                    f"trie entry uid={e.uid} has an evicted parent "
                    f"(uid={e.parent.uid}) — chain no longer walkable")
            child_count[id(e.parent)] = child_count.get(id(e.parent), 0) + 1
    for e in entries:
        actual = child_count.get(id(e), 0)
        if e.children != actual:
            raise PagedStateError(
                "trie-parent-child",
                f"trie entry uid={e.uid}: children counter {e.children} "
                f"!= {actual} live children")
        # a parent with live children must keep its own cache hold (its
        # refcount can never drop below the 1 the conservation pass
        # attributes to the entry itself) — the weakest sound form of
        # "no child outlives its parent"; see module docstring for why
        # "parent refs >= child refs" is NOT sound
        if actual and ref[int(e.block)] < 1:
            raise PagedStateError(
                "trie-parent-child",
                f"trie entry uid={e.uid} has {actual} live children but "
                f"its block {e.block} is unreferenced")

    # ---- scale-lockstep (int8 KV only): scale rows live <=> block owned
    if scale_live is not None:
        if scratch & set(int(b) for b in scale_live):
            raise PagedStateError(
                "scale-lockstep",
                "a scratch block is in the live-scale ledger — scratch "
                "is never owned, its scale row is write-only garbage")
        for b in scale_live:
            if not (0 <= int(b) < num_blocks) or ref[int(b)] == 0:
                raise PagedStateError(
                    "scale-lockstep",
                    f"block {b} is in the live-scale ledger but has no "
                    "owner (refcount 0) — a stale scale row survived the "
                    "block free")
        for b in range(num_blocks):
            if b in scratch:
                continue
            if (ref[b] > 0 or expected[b] > 0) and b not in scale_live:
                raise PagedStateError(
                    "scale-lockstep",
                    f"block {b} is owned (refcount {ref[b]}) but missing "
                    "from the live-scale ledger — its reads would "
                    "dequantize stale scales")

    # ---- length-occupancy + scratch-aliasing over the tables
    nslots = len(tables)
    window_frontiers = window_frontiers or {}
    for slot in range(nslots):
        row = tables[slot]
        frontier = int(window_frontiers.get(slot, 0))
        lm = min(int(landmark_blocks), frontier)
        if frontier > lm:
            # resident-window shape: landmarks set, demoted middle unset,
            # then one contiguous resident run from the frontier
            for li in range(lm):
                if int(row[li]) == SCRATCH_BLOCK:
                    raise PagedStateError(
                        "length-occupancy",
                        f"slot {slot}: landmark entry {li} unset below "
                        f"the window frontier {frontier}")
            for li in range(lm, frontier):
                if int(row[li]) != SCRATCH_BLOCK:
                    raise PagedStateError(
                        "length-occupancy",
                        f"slot {slot}: entry {li} still set inside the "
                        f"demoted window region [{lm}, {frontier}) — the "
                        "slide must zero exactly what it demotes")
            span = frontier
            resident = list(range(lm))
        else:
            span = 0
            resident = []
        run_start = span
        while span < len(row) and int(row[span]) != SCRATCH_BLOCK:
            span += 1
        for li in range(span, len(row)):
            if int(row[li]) != SCRATCH_BLOCK:
                raise PagedStateError(
                    "length-occupancy",
                    f"slot {slot}: table entry {li} set after an unset "
                    f"entry at {span} — allocated span must be contiguous")
        resident.extend(range(run_start, span))
        owned = sorted(int(b) for b in held[slot])
        mapped = sorted(int(row[li]) for li in resident)
        hit = scratch.intersection(mapped)
        if hit:
            raise PagedStateError(
                "scratch-aliasing",
                f"slot {slot}: table span maps scratch block(s) "
                f"{sorted(hit)} — sequence KV would alias scratch garbage")
        if len(set(mapped)) != len(mapped):
            raise PagedStateError(
                "length-occupancy",
                f"slot {slot}: a physical block appears twice in its "
                f"table span {mapped}")
        if owned != mapped:
            raise PagedStateError(
                "length-occupancy",
                f"slot {slot}: table span blocks {mapped} diverge from "
                f"the held record {owned}")
        if slot in active_needs:
            need_span = _blocks_for(active_needs[slot], block_size)
            if span < need_span:
                raise PagedStateError(
                    "scratch-aliasing",
                    f"slot {slot}: {active_needs[slot]} committed tokens "
                    f"need {need_span} table entries but only {span} are "
                    "set — writes past the span land in the scratch block")
        elif span or held[slot]:
            raise PagedStateError(
                "length-occupancy",
                f"slot {slot} is inactive but still maps {span} table "
                f"entr(ies) / holds {len(held[slot])} block(s)")


def _fmt_key(key) -> str:
    """Render a chain key for an error message without dumping the whole
    token byte string."""
    h = key.hex() if isinstance(key, (bytes, bytearray)) else str(key)
    return h[:16] + ("…" if len(h) > 16 else "")


def audit_host_store(store, staged_keys) -> None:
    """Verify the ``residency-conservation`` invariant over a tiered-KV
    engine's :class:`~deepspeed_tpu.inference.paged.HostBlockStore`
    (module docstring); raises :class:`PagedStateError`.

    store:        the engine's host tier (``srv._host``).
    staged_keys:  the set of chain keys referenced by the engine's live
                  staged-prefetch records (``srv._staged``) — the other
                  half of the in-flight lockstep.
    """
    free, entries = store.snapshot()
    nb = store.num_blocks
    staged_keys = set(staged_keys or ())

    free_set = set(int(s) for s in free)
    if len(free_set) != len(free):
        raise PagedStateError(
            "residency-conservation",
            "host free list contains duplicate arena slots")
    owned = {}
    for key, (slot, in_flight) in entries.items():
        if not (0 <= int(slot) < nb):
            raise PagedStateError(
                "residency-conservation",
                f"host entry {_fmt_key(key)} maps out-of-range arena slot "
                f"{slot} (arena has {nb})")
        if slot in owned:
            raise PagedStateError(
                "residency-conservation",
                f"arena slot {slot} owned by two entries "
                f"({_fmt_key(owned[slot])} and {_fmt_key(key)})")
        if slot in free_set:
            raise PagedStateError(
                "residency-conservation",
                f"arena slot {slot} is on the free list but owned by "
                f"entry {_fmt_key(key)}")
        owned[int(slot)] = key
        if in_flight and key not in staged_keys:
            raise PagedStateError(
                "residency-conservation",
                f"leaked in-flight block: host entry {_fmt_key(key)} "
                f"(arena slot {slot}) is flagged in-flight but no staged "
                "promotion references it — its slot can never free")
    for slot in range(nb):
        if slot not in free_set and slot not in owned:
            raise PagedStateError(
                "residency-conservation",
                f"arena slot {slot} is neither free nor owned — leaked "
                "out of the host tier entirely")
    for key in staged_keys:
        if key in entries and not entries[key][1]:
            raise PagedStateError(
                "residency-conservation",
                f"staged promotion references resident entry "
                f"{_fmt_key(key)} that is NOT flagged in-flight — the "
                "LRU could free its bytes mid-transfer")

    # NVMe third tier (when attached): the *spilled* residency state must
    # stay exclusive with arena residency (content-addressed bytes live in
    # exactly one of the two host-side tiers), and the spill file's slot
    # accounting must conserve exactly like the arena's.
    nvme_snap = getattr(store, "nvme_snapshot", None)
    if nvme_snap is None:
        return
    nfree, nentries = nvme_snap()
    if not nentries and not nfree:
        return
    nnb = store.nvme_blocks
    nfree_set = set(int(s) for s in nfree)
    if len(nfree_set) != len(nfree):
        raise PagedStateError(
            "residency-conservation",
            "NVMe free list contains duplicate file slots")
    nowned = {}
    for key, slot in nentries.items():
        if key in entries:
            raise PagedStateError(
                "residency-conservation",
                f"chain key {_fmt_key(key)} is resident in BOTH the host "
                "arena and the NVMe spill file — tier residency must be "
                "exclusive (the dedup rule frees the file slot when the "
                "arena copy lands)")
        if not (0 <= int(slot) < nnb):
            raise PagedStateError(
                "residency-conservation",
                f"NVMe entry {_fmt_key(key)} maps out-of-range file slot "
                f"{slot} (spill file has {nnb})")
        if slot in nowned:
            raise PagedStateError(
                "residency-conservation",
                f"NVMe file slot {slot} owned by two entries "
                f"({_fmt_key(nowned[slot])} and {_fmt_key(key)})")
        if slot in nfree_set:
            raise PagedStateError(
                "residency-conservation",
                f"NVMe file slot {slot} is on the free list but owned "
                f"by entry {_fmt_key(key)}")
        nowned[int(slot)] = key
    for slot in range(nnb):
        if slot not in nfree_set and slot not in nowned:
            raise PagedStateError(
                "residency-conservation",
                f"NVMe file slot {slot} is neither free nor owned — "
                "leaked out of the spill file entirely")


def audit_router(router) -> None:
    """Verify the router-level invariants (module docstring:
    ``router-request-uniqueness`` / ``router-drain-quiesced`` /
    ``router-failure-state``) over a
    :class:`~deepspeed_tpu.serving.ReplicaRouter`; raises
    :class:`PagedStateError`.  Pure host state — runs after every
    ``router.step()`` under ``debug_checks``; each engine's own paged
    audit rides its engine-level flag."""
    failed = set(getattr(router, "_failed", ()))
    where = {}
    for rid, rep in enumerate(router.replicas):
        for item in rep._pending:
            uid = item.req.uid
            if uid in where:
                raise PagedStateError(
                    "router-request-uniqueness",
                    f"request {uid!r} queued on replica {rid} but "
                    f"already {where[uid][1]} on replica {where[uid][0]}")
            where[uid] = (rid, "queued")
        for st in rep._active.values():
            uid = st.req.uid
            if uid in where:
                raise PagedStateError(
                    "router-request-uniqueness",
                    f"request {uid!r} active on replica {rid} but "
                    f"already {where[uid][1]} on replica {where[uid][0]}")
            where[uid] = (rid, "active")
        if (rep._pending or rep._active) and rid in failed:
            # fail(rid) salvages + scrubs the dead engine's host-side
            # bookkeeping — anything still here was never re-homed and
            # nothing will ever step it
            raise PagedStateError(
                "router-failure-state",
                f"crash-failed replica {rid} still owns "
                f"{len(rep._pending)} queued / {len(rep._active)} active "
                "request(s) — salvage must leave a dead replica with "
                "zero uids")
        if rid in router._drained and rid not in failed and \
                (rep._pending or rep._active):
            raise PagedStateError(
                "router-drain-quiesced",
                f"replica {rid} is drained but still holds "
                f"{len(rep._pending)} queued / {len(rep._active)} active "
                "request(s) — nothing will ever step them")
    for uid, (handle, rid) in router._handles.items():
        if handle.done:
            if uid in where:
                raise PagedStateError(
                    "router-request-uniqueness",
                    f"request {uid!r} handle says {handle.status} but it "
                    f"is still {where[uid][1]} on replica {where[uid][0]}")
        else:
            if uid not in where:
                raise PagedStateError(
                    "router-request-uniqueness",
                    f"request {uid!r} handle says {handle.status} but no "
                    "replica holds it — the request was lost")
            if rid in failed:
                raise PagedStateError(
                    "router-failure-state",
                    f"live request {uid!r} is mapped to crash-failed "
                    f"replica {rid} — it must re-home to a survivor or "
                    "fail loudly (RequestFailedError), never wait on a "
                    "dead engine")
            if where[uid][0] != rid:
                raise PagedStateError(
                    "router-request-uniqueness",
                    f"request {uid!r} is mapped to replica {rid} but "
                    f"lives on replica {where[uid][0]} — cancel would "
                    "land on the wrong engine")


def audit_window_ring(ring, active_rows) -> None:
    """The window kind's table (``inference/paged.py WindowRing``): a row's
    live logical blocks ``[lo, hi)`` fit its ring and each sits at entry ``i
    % width`` under an id of its own (refcount 1, never scratch); every
    other entry is scratch; the allocator holds exactly those ids; a row
    that is not active holds none.  Raises :class:`PagedStateError`."""
    seen = set()
    for row in range(ring.tables.shape[0]):
        lo, hi = int(ring.lo[row]), int(ring.hi[row])
        live = {li % ring.width for li in range(lo, hi)}
        if hi - lo > ring.width or (row not in active_rows and hi > lo):
            raise PagedStateError(
                "window-ring-span",
                f"row {row} holds window blocks [{lo}, {hi}) in a ring of "
                f"{ring.width}" + ("" if row in active_rows
                                   else " but is not active"))
        for entry, block in enumerate(map(int, ring.tables[row])):
            if (block != 0) != (entry in live):
                raise PagedStateError(
                    "window-ring-entry",
                    f"row {row} ring entry {entry} holds block {block}; "
                    f"its live logical blocks are [{lo}, {hi})")
            if block and (block in seen or ring.alloc.refcount(block) != 1):
                raise PagedStateError(
                    "window-ring-ownership",
                    f"window block {block} (row {row}, entry {entry}) is "
                    f"shared or has refcount {ring.alloc.refcount(block)}")
            seen.add(block)
    seen.discard(0)
    if len(seen) != ring.alloc.blocks_in_use:
        raise PagedStateError(
            "window-ring-leak",
            f"the window allocator has {ring.alloc.blocks_in_use} blocks in "
            f"use, the rings hold {len(seen)}")


def audit_serving_engine(srv, active) -> None:
    """Engine-facing wrapper: pulls the :class:`ServingEngine` fields and
    derives each active slot's committed-token count (decode: host
    ``lengths``; prefill: the chunk base already written).

    When the engine carries a trace timeline (``telemetry/trace.py``),
    the audit records itself there — a green ``invariant_audit`` instant
    per run, or an ``invariant_violation`` naming the broken invariant
    *before* the raise, so a fatal audit is visible in the exported trace
    right next to the scheduler events that corrupted the state."""
    needs = {slot: max(int(srv._lengths[slot]), st.base)
             for slot, st in active.items()}
    frontiers = {slot: st.window_blk for slot, st in active.items()
                 if getattr(st, "window_blk", 0)} \
        if getattr(srv, "resident_window_blocks", 0) else None
    timeline = getattr(srv, "timeline", None)
    try:
        # (a cache tree with no paged leaf has no block, table or refcount
        # to audit: ``inference/paged.py NoBlocks``)
        if getattr(srv, "_paged", True):
            audit_paged_state(srv._alloc, srv._tables, srv._held,
                              prefix=srv._prefix, active_needs=needs,
                              block_size=srv.block_size,
                              scale_live=(srv._kv_scale_live
                                          if getattr(srv, "kv_quant", False)
                                          else None),
                              scratch_blocks=getattr(
                                  srv, "_scratch_blocks", None),
                              window_frontiers=frontiers,
                              landmark_blocks=getattr(
                                  srv, "_landmark_blocks", 0))
        if getattr(srv, "_windows", None):
            audit_window_ring(srv._ring, set(active))
        if getattr(srv, "_host", None) is not None:
            audit_host_store(
                srv._host,
                {k for rec in srv._staged.values() for k in rec["keys"]})
    except PagedStateError as e:
        if timeline is not None:
            timeline.instant("invariant_violation", invariant=e.invariant,
                             detail=e.detail)
        raise
    if timeline is not None:
        timeline.instant("invariant_audit", slots_active=len(needs),
                         blocks_in_use=srv._alloc.blocks_in_use)


def audit_incident_bundle(path) -> None:
    """Internal-consistency audit of a flight-recorder incident bundle
    (``telemetry/incident.py``): the manifest's file list matches the
    directory exactly, the trigger kind is in the pinned vocabulary,
    every progress entry carries a legal handle status, and a bundle
    claiming ``replayable`` actually ships its replay inputs.  Raises
    :class:`PagedStateError` naming the broken invariant —
    ``bin/graft-replay --validate`` and the incident tests run this
    before trusting a bundle's contents."""
    import json
    import os

    from ..telemetry.incident import (MANIFEST_KEYS, TRIGGER_KINDS,
                                      is_bundle)

    if not is_bundle(path):
        raise PagedStateError(
            "bundle-complete",
            f"{path!r} has no parseable manifest.json with the "
            "graft-incident format marker — a partial dump (the hidden "
            ".tmp dir) or not a bundle at all")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    if set(manifest) != MANIFEST_KEYS:
        raise PagedStateError(
            "bundle-manifest-schema",
            f"manifest keys {sorted(set(manifest) ^ MANIFEST_KEYS)} "
            "differ from the pinned set")
    listed = set(manifest["files"])
    on_disk = {f for f in os.listdir(path)
               if os.path.isfile(os.path.join(path, f))}
    if listed != on_disk:
        raise PagedStateError(
            "bundle-file-list",
            f"manifest lists {sorted(listed - on_disk)} missing from "
            f"disk / disk holds {sorted(on_disk - listed)} unlisted — "
            "the dump was tampered with or truncated")
    trig = manifest["trigger"]
    if trig["kind"] not in TRIGGER_KINDS:
        raise PagedStateError(
            "bundle-trigger-kind",
            f"unknown trigger kind {trig['kind']!r} (expected one of "
            f"{TRIGGER_KINDS})")
    prog_path = os.path.join(path, "progress.json")
    if os.path.isfile(prog_path):
        with open(prog_path) as f:
            progress = json.load(f)
        legal = {"queued", "active", "finished", "cancelled", "failed"}
        for uid, entry in progress.items():
            if entry.get("status") not in legal:
                raise PagedStateError(
                    "bundle-progress-status",
                    f"uid {uid!r} carries illegal status "
                    f"{entry.get('status')!r}")
    if manifest["replayable"]:
        for needed in ("request_trace.json", "replica_configs.json",
                       "progress.json"):
            if needed not in listed:
                raise PagedStateError(
                    "bundle-replay-inputs",
                    f"manifest claims replayable but {needed} is "
                    "missing")
    if manifest["trigger"]["kind"] == "watchdog_stall" and \
            "threads.txt" not in listed:
        raise PagedStateError(
            "bundle-stall-evidence",
            "a watchdog_stall bundle must carry threads.txt — the "
            "thread stacks ARE the stall evidence")
