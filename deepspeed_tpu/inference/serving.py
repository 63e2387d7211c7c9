"""Continuous-batching serving: block-paged KV cache, prefix reuse, and
chunked prefill over an iteration-level scheduler.

A slot pool that reserves one contiguous ``max_seq_len`` KV region per slot
and prefills every admitted prompt in full pays worst-case memory per
sequence and recomputes shared prompt prefixes (system prompts, few-shot
headers) on every request.  This engine layers the two highest-leverage
serving optimisations on top of continuous batching:

 - **Block-paged KV pool** (vLLM PagedAttention): one statically-shaped
   ``[L, num_blocks, HKV, block_size, hd]`` cache plus per-slot ``int32``
   block tables mapping each sequence's logical block index (``position //
   block_size``) to a physical block.  Blocks are handed out by a
   refcounted free-list allocator (``inference/paged.py``); physical block
   0 is reserved scratch — pad rows and inactive slots write their
   discarded KV there, so every device program keeps a fixed shape.
   Attention reaches the pool through the table: a gather-based XLA path
   for prefill/CPU and a Pallas kernel that walks the table in-kernel via
   scalar prefetch for TPU decode (``ops/decode_attention.py``).  The
   engine holds the pool lane-packed (``paged_kv.pack_pool``: the same
   bytes, ``[L, NB, HKV, bs/g, g*hd]``) and every program carries it whole
   through its layer loop, reading and writing ``(layer, block)`` in place
   (``ops/paged_kv.py`` has the contract and why).
 - **Prefix cache** (SGLang RadixAttention at block granularity): a token
   trie over *full* blocks.  A new request whose prompt shares a
   block-aligned prefix with any previously prefilled sequence reuses
   those physical blocks with zero recompute — only the tail is prefilled.
   Reuse is capped below the full prompt (>= 1 tail token always runs) and
   is full-block only, so a sequence's next write always lands in a
   privately owned block: shared blocks are read-only, no copy-on-write.
   When the allocator runs dry, least-recently-used cache entries are
   evicted first; if that is not enough, the *latest-admitted* sequence is
   preempted — its blocks are freed and it re-enters the queue front with
   its generated tokens folded into the prompt (greedy decoding makes the
   recompute token-exact, and its re-prefill usually hits its own cached
   blocks).
 - **Chunked prefill**: prompts advance through the cache in calls of a
   fixed BUDGET of tokens (``prefill_batch * prefill_chunk``) interleaved
   with decode steps.  A call is ``[prefill_batch, prefill_chunk]`` or
   ``[1, prefill_batch * prefill_chunk]`` (:func:`prefill_ladder`),
   whichever carries more REAL prompt tokens of its group's ready rows: a
   row alone gets the pad rows' tokens, two or three long rows take turns
   at the wide row (``ServingEngine._rung_for``; a row waits at most
   ``prefill_batch - 1`` calls), so the weights are read once per budget
   of real prompt tokens.  The whole serving loop compiles exactly **1
   decode program + 1 prefill program a rung** (two rungs), all built and
   run once (on pad rows) at the first prefill call, regardless of trace
   shape.

Scheduling is iteration-level and strict-FIFO as before: every iteration
admits waiting requests into free slots (gated on block availability —
the queue head blocks admission, no starvation), makes one prefill call
for every group of ``prefill_batch`` prefilling slots, then runs one
single-token decode step over all slots with per-sequence positions
(``lengths: int32[B]``).
``compile_count`` / ``compiled_programs`` remain the compile probe;
``stats()`` adds prefix-hit, block-occupancy, and preemption counters.

**Speculative decoding** (``spec_tokens=K > 0``) replaces the single-token
decode step with a draft–verify round (``inference/spec.py``): a proposer guesses K tokens per decode slot — a
small same-family draft model running K greedy steps in ONE compiled
program over its own paged pool (sharing the target's block tables, so
allocation/preemption/prefix-reuse bookkeeping is written once), or the
model-free n-gram prompt-lookup fallback (zero programs) — and the target
scores the K+1-token window in one fixed-shape paged forward through the
chunked-prefill T>1 path (``all_positions`` verify head).  Greedy
verification commits the longest target-matching draft prefix plus the
target's correction token, so outputs stay token-exact with plain greedy
decode; rejected tokens roll back for free (host lengths stay at the
committed value — stale KV is position-masked and overwritten in place,
blocks stay allocated, refcounts never move).  Block demand past a
request's remaining completion budget is never allocated: those window
positions scatter to the scratch block instead.  The whole trace compiles
at most **3 programs** — prefill (fused target+draft in draft mode), the
draft K-step rollout, and the verify pass.  ``stats()`` adds drafted/
accepted counters and acceptance rate, plus per-request TTFT/TPOT
percentiles (recorded for plain serving too).

**A third proposer: the model's own module** (``draft="self"`` beside
``spec_tokens=K``, ``inference/options.py SELF_DRAFT``; decode hook
``self_draft``: ``{"depth", "layers", "cache", "forward"}`` — a
multi-token-prediction module that is one more block of the served model,
fed by the trunk's hidden state and the next token; the engine asks the hook
and names no family).
(a) Its cache rows are more layers of the target's OWN leaves under the
target's block tables (the hook's ``cache`` keywords to ``init_cache``): no
second pool, and they roll back for free like every other entry.  The prefill
programs fill them — entry ``t`` is made from the hidden state at ``t`` AND
token ``t + 1``, so a chunk's last entry takes the prompt's next token, the
prompt's last entry the row's own first token — and leave each row's first
draft.  (b) A round (:meth:`ServingEngine._get_round_fn`) is two device
programs handed over back to back and ONE harvest: ``verify`` scores the
window ``[pending, d_1..d_K]`` taken where the device left it
(``_devtok`` / ``_devdraft``), takes the rejection sampler's verdict and
WALKS it on the device; ``draft`` runs the module over the 1..K+1 positions
the walk committed and leaves the next draft beside the next pending token.
The host gets ``[slots, K + 1]`` emitted ids and a count a row: no round-trip
between a draft and its verify, one ``spec_round`` span a round.  The count
stays on the device too (``_devcount``, beside the token and the draft):
round n + 1 is enqueued BEFORE round n's ids and counts are copied to the
host — the one call of lookahead every plain program has
(:meth:`ServingEngine._launch`) — and takes a row's base and sampler count
as the host's committed values plus what the device holds; the host plans
that round on bounds (:meth:`ServingEngine._run_self_round`).  (c) The
commit (:meth:`ServingEngine._commit_self_round`) takes 1..K+1 tokens a row,
cut at the row's ``eos`` or where its budget ends; a row that ended there
has ridden the round in flight, which drops it.  (d) The draft is the
module's argmax, verified by the delta-form rejection sampler: sampled rows
are distribution-exact, greedy rows token-exact with speculation off.  (e)
**The trie beside it**: the module's entry at a shared prefix's last position
belongs to whoever registered it (it was made from THAT request's next
token), and the first round needs the hidden state of the last cached
position — so a hit ends one block early (:meth:`ServingEngine._admit`);
blocks past a prompt's full blocks, where an uncommitted draft's entries may
lie, are never registered.  Not served with it: ``logit_masks``,
``host_blocks`` (``options.EXCLUDES``, group ``self_draft``).

**Tensor parallelism** (``shard_kv``, default auto): when the engine's
mesh carries a ``tp`` axis and the model's KV head count divides it, the
paged pool (and the draft pool) is committed sharded over the KV-HEAD dim
— ``NamedSharding(mesh, P(None, None, "tp"))`` on the stacked ``[L, NB,
HKV, bs, hd]`` buffer — so each chip stores ``HKV/tp`` heads (pool
capacity and decode memory bandwidth scale with the tp degree instead of
being replicated).  Every device call runs under ``ops/paged_kv
.tp_context``: the paged scatter/gather/attention ops trace inside
``shard_map`` on their head shard with ZERO per-step KV collectives (the
one tensor-parallel all-reduce stays after the output projection, exactly
like the Megatron matmul path).  Block tables, the allocator, the prefix
trie, and all scheduler state are host-side and head-sharding-invariant —
they index blocks, never heads — so scheduling is bit-identical at any tp
degree and the compile contract is unchanged.  GQA pools whose ``HKV``
does not divide tp fall back to the replicated tp=1 layout (head groups
are shared across chips); ``shard_kv=True`` then raises instead of
silently replicating.

**Quantized serving** (``quantize=``, default off): decode is memory-
bandwidth-bound, and the KV pool plus the weights are the traffic.
``"kv8"`` stores the paged pool (and the speculative draft pool) as int8
with a per-block scale table (``ops/paged_kv.py`` quantized pool
records): the scatter quantizes on write, the gather and all three paged
Pallas kernels dequantize on read, so HBM moves codes + scales — ~2x
servable blocks per chip and ~2x decode KV bandwidth, multiplicative
with the tp head-shard.  ``"w8a8"`` requires the wrapped engine to carry
K-grouped int8 weights (``init_serving(quantize=...)`` wires the config;
``quant: {enabled, type: "w8a8"}``) — decode matmuls then run the s8-MXU
stacked kernels (``ops/quantized_matmul``), while prefill/verify rows
fall back to the exact dequant path.  ``"w8a8+kv8"`` composes both.  The
host side is quant-invariant: allocator, trie, tables, and scheduling
are byte-identical to the float pool (scales ride under block ids), the
≤2/≤3-program compile contracts hold unchanged, and ``quantize=None``
traces the exact pre-quantization programs bit-for-bit.  Greedy parity
becomes a *bounded-divergence* contract on quantized lanes (int8
rounding can flip near-tie argmaxes): ``tests/unit/test_quant_serving.py``
pins token-match-rate and logit-RMSE bounds instead of bit equality.
A host-side ledger tracks which blocks own live scale rows; the
``debug_checks`` audit enforces it (``scale-lockstep`` invariant,
``analysis/invariants.py``).

**Tiered KV cache** (``host_blocks=N``, default off): under block
pressure the engine above threw computed state away — LRU prefix-cache
eviction, then preemption with full greedy recompute.  The tier adds a
host-DRAM arena below the device pool (:class:`~deepspeed_tpu.inference
.paged.HostBlockStore`): eviction and preemption **demote** cold blocks —
one fixed-shape block gather (``ops/paged_kv.paged_block_gather``) plus
ONE ``jax.device_get`` per ``swap_batch``-sized batch — instead of
freeing their contents, and admission of a sequence whose prefix (or
preempted state — generated tokens fold into the resume prompt, so the
same content-addressed chain keys cover both) is host-resident
**promotes** the run back: an async ``jax.device_put`` issued at least
one scheduler iteration ahead (double-buffered prefetch over the pending
queue head, exactly the ``param_stream.py`` overlap trick) so the H2D
transfer hides behind the decode step, then one fixed-shape scatter
(``paged_block_scatter``) commits the staged blocks and the chain
re-registers in the trie.  Promoted bytes are bit-identical to what was
demoted (under ``kv8`` the int8 codes and their scale rows travel as
separate leaves of the same swap tree; with a draft model the draft pool
demotes/promotes alongside the target's so speculative acceptance
survives a swap), so parity contracts are unchanged.  The two swap
programs are fixed-shape and sentry-registered — the compile budget
grows by exactly 2 and transfers can never introduce further programs.
Scheduling stays host-side and sharding/quant-invariant: under tp the
``device_get``/``device_put`` travel per addressable shard of the
head-sharded pool.  The residency state machine (device-free /
device-held / host-resident / in-flight) is audited by
``analysis/invariants.py`` (``residency-conservation``) under
``debug_checks``.

**Telemetry** (``telemetry/``, always on — the registry IS the stats
store): every scheduler counter and the TTFT/TPOT latency distributions
live in a :class:`~deepspeed_tpu.telemetry.MetricsRegistry`
(``engine.metrics`` — Prometheus text + JSON snapshot for scrapes and
bench artifacts), and ``stats()`` is a backward-compatible view over it.
Latencies are fixed-bucket streaming histograms — bounded memory for a
serve session of any length, replacing the old unbounded raw-sample
lists — with a small ``deque`` of recent per-request records kept for
debugging.  A bounded ring of scheduler events (admit, prefill chunk,
decode step, spec propose/verify/accept, prefix hit, block eviction,
preemption, finish, plus the sentry's trace/retrace and the invariant-
audit events from ``analysis/``) records a per-request timeline
(``trace_capacity=``, 0 = off) exportable as Chrome ``trace_event`` JSON
via ``dump_trace(path)`` — open it in Perfetto to see exactly where a
slow request spent its time.  Each iteration is one ``step`` span tiled
by four host phases (:meth:`ServingEngine.step`), every span doubling as
a ``ds.serve.*`` profiler annotation (ring on or off), so
``serve(profile_dir=...)`` — which brackets the first ``profile_iters``
scheduler iterations with a ``jax.profiler`` trace window — shows the
scheduler's phases on the device's clock
(``python -m deepspeed_tpu.telemetry.idle_gaps``).  PR 12 adds
the per-``slo_class`` attainment accounting behind ``slo_report()``
(``telemetry/slo.py``; ``slo_targets=``), the FLOPs/MFU profiler behind
``flops_report()`` (``telemetry/flops.py``; raw program bodies lowered
for ``cost_analysis`` — zero new compiled programs), and the router's
cross-ring flow linkage (``note_flow`` → admission emits the Chrome
flow finish).  Disabled, every hook is one ``is None`` predicate; the
cost when enabled has not been measured on a chip.

**Incremental serving API** (PR 11): the scheduler state (pending queue,
active slots) lives on the engine, not inside one ``serve()`` call.
``submit(request, priority=, slo_class=)`` enqueues a request and returns
a :class:`RequestHandle` with per-token streaming (``next_token``,
``tokens``), blocking ``result()``, and ``cancel()``; ``step()`` runs ONE
scheduler iteration (admit → prefill → decode → prefetch → audit) and
returns whether work remains; ``cancel(uid)`` drops a queued request
immediately and releases an active slot (blocks decref'd, ``cancelled``
timeline event) at the next iteration boundary — the only point the
paged-state invariants are guaranteed to hold.  ``drain()`` quiesces the
engine for a replica handoff: every active slot preempts (with the host
tier, committed blocks demote first), the remaining prefix-cache content
demotes, and the whole pending queue is handed back for re-submission
elsewhere (``deepspeed_tpu/serving/`` routes it).  From PR 44 the
scheduler keeps ONE call of lookahead (:meth:`ServingEngine._launch`): the
tokens of call n reach their handles during ``step()`` n+1, behind the
enqueue of call n+1, and ``step()`` says ``False`` only once nothing is in
flight; ``drain()`` / ``close()`` / a cancel / a preemption take the
results first, ``salvage()`` leaves them (they were never streamed).  A
self-drafting engine's rounds (``draft="self"``) ride it like a decode step
— what round n + 1 needs of round n (a token, a draft and a count a row)
stays on the device —; a draft model's and the n-gram proposer's rounds keep
their own fence (``EARLY_SETTLE_CAUSES``: ``"speculative"``).  The batch
``serve(list)`` entry point survives as a thin wrapper — submit all,
loop ``step()``, gather results — with byte-identical scheduling, and
tolerates an empty request list without tracing anything.  Admission
stays head-of-queue-gated (no starvation) but the queue is now
priority-ordered: higher ``priority`` (or an ``slo_class`` mapped
through ``SLO_PRIORITY``) admits first; preemption resumes still jump
to the very front regardless of class (they hold admission recency).

**Layer kinds** (PR 34): a model that mixes sliding-window and full
attention layers (decode hook ``window_layers``) is served on a pool with
leaves BY LAYER KIND (``ops/paged_kv.py`` "Layer kinds"), each kind with
its own block ids, allocator and table.  The full kind is every model's
pool: ``_alloc`` / ``_tables`` / ``_held``, admission and preemption as
they are.  The window kind (``_ring``: ``inference/paged.py WindowRing``)
is a RING of ``ceil((window + T) / block_size) + 1`` entries a slot (``T``
the widest row of a prefill call, ``prefill_batch * prefill_chunk`` where
the wide rung is built), sized for every slot at once, so it never runs
dry and never preempts: ``WindowRing.advance``, called under ``_ensure_blocks`` before
every dispatch, releases the blocks wholly behind ``position - window`` —
in the step that passes them — and allocates up to the dispatch's last
position; a released, preempted or finished slot frees both kinds.  The
programs take one table per kind (:meth:`ServingEngine._bt`).  What such a
model is NOT served with is refused by name at construction: the prefix
trie (``prefix_caching=True``; the default ``None`` turns it off for such
a model — a shared prefix lacks the window layers' last ``window`` keys),
the host / NVMe tiers, ``quantize="kv8"``, ``resident_window_blocks``,
``spec_tokens`` (a window past a row's budget writes through an unset
table entry into scratch; a ring entry is never unset), a draft model, and
tp / dp / sp meshes (``inference/options.py KIND_REFUSES["window"]``: the
one statement of it, as of every kind's list below).  ``stats()["kv_kinds"]`` has
both pools, the releases, the reach the scheduler reckons from its rows'
lengths (``kv_valid`` / ``kv_visible``, :meth:`ServingEngine._kv_reach`)
and the refusals.

**The latent kind** (PR 39): a model with latent attention (decode hook
``latent_attention``; ``models/llama.py`` ``kv_lora_rank > 0``) is served
on a pool of ONE leaf, ``latent [L, NB, 1, block_size, W]`` — a token's
normed latent and the one rotated key all heads share, 320 values padded to
384 lanes, and no K / V (``ops/paged_kv.py`` "The latent kind").  A latent
block is a block: one block-id space, one table, ``_alloc`` / ``_tables`` /
``_held``, admission, preemption, eviction, the prefix trie and the host
tier go by tree as for any model, and a verify window (``spec_tokens``) is
the same kernel at ``T = K + 1``.  Where the caller names no ``block_size``
the block is ``paged_kv.latent_block_tokens``'s, 512 tokens at 768 B (a
32-token block would be 24 KB, and a block visit costs ~0.4 us whatever it
moves).  Refused by name at construction, each with its
reason: ``quantize`` (kv8, w8a8), a tp mesh, ``engine_mode="dp_tp"``, ``sp
> 1``, a draft model, ``resident_window_blocks``.
``stats()["kv_latent"]`` names the kind, the token's width and bytes, the
block, the read each program was traced with and the refusals; the
``decode`` / ``prefill`` / ``spec_verify`` spans carry ``kv_valid`` (valid
keys x layers), ``kv_blocks``, ``kv_pairs`` (query-key pairs x layers),
``latent_bytes``, and of ONE layer's walk ``kv_tiles`` (its loop iterations,
``stats()["kv_latent"]["tile_blocks"]`` blocks each) and
``kv_first_tiles_ahead`` (the grid steps whose first tile the step before
theirs starts) (:meth:`ServingEngine._kv_reach`).

**The state kind** (PR 51, PR 55, PR 57): a model with recurrent layers
(decode hook ``state_layers``: gated delta-rule layers,
``models/kimi_linear.py``, beside a latent pool; state-space layers,
``models/granite_hybrid.py``, beside the ``full`` kind's K and V;
power-retention layers, ``models/brumby.py``, beside NOTHING) keeps, for
each such layer, a recurrent state a SLOT and nothing a token: ``state [L,
slots, ...]`` float32 (the trailing shape is the family's) and what the
family names beside it (``paged_kv.STATE_COMPANIONS``: the short
convolutions' tails ``conv [L, slots, 1, taps, channels]``, the normaliser
``z [L, slots, heads, ...]``) ride in the cache tree (``ops/paged_kv.py``
"The state kind") — donated and carried with the paged pool where there is
one, not lane-packed — with no block ids, no table and no allocator: a
slot's rows are its own for as long as it holds a request.  The state
follows the slot: a prefill window at base 0
starts from a zero state inside the program (a request ENTERING a slot needs
no reset call; the span counts it, ``state_resets``), each chunk of a prompt
carries it on (the pads of a ``[rows, chunk]`` call leave it untouched), a
decode step advances the live rows once and no idle row's lane, the one
call of lookahead keeps it (it is part of the donated cache), and
``_release_slot`` simply forgets it; ``_preempt``'s recompute re-prefills
from base 0 and is exact.  The programs take ``block_tables = {"full": ...}``
(decode: row b is slot b) or ``{"full": ..., "slot": int32 [rows]}``
(prefill; a pad row's slot is out of range).  Refused by name at
construction, each with its reason (``options.KIND_REFUSES["state"]``):
the prefix trie, the host / NVMe tiers, ``spec_tokens`` and a draft model,
``quantize`` (kv8, w8a8), ``resident_window_blocks`` and tp / dp / sp
meshes.  ``stats()["kv_state"]`` has the leaves, their
bytes (whatever the rows' lengths), the resets, which body each program's
recurrence lowered to — under the hook's ``bodies`` (``"kda"``: ``kda_step``
/ ``kda_chunk_state``; ``"ssd"``: ``ssd_step`` / ``ssd_chunk_state``;
``"power"``: ``power_step`` / ``power_chunk_state``) — and
the refusals; ``stats()["kv_kinds"]`` names the state kind beside the paged
one.  The engine names no family: what it knows of one is the hook.

**Tails** (PR 66): row-indexed leaves beside a pool whose EVERY layer is
paged, with no ``state`` leaf (decode hook ``tail_layers``:
``models/zaya.py``, whose keys are made by two causal convolutions of two
taps over the projections and whose values are half the projection of the
token before).  The finished K and V are the ``full`` kind's, a token a
block offset like any model's; what the WRITER of a row's next token needs
of the token before — ``conv [L, slots, 1, taps, channels]``, ``shift [L,
slots, 1, 1, channels]`` (``ops/paged_kv.py`` "Tails") — follows the slot
exactly as a recurrent state does, by the same code (``_rowed``: the state
kind's tree is this tree plus ``state``): the cache is built for ``slots``
rows, the programs take ``block_tables = {"full": ...}`` (decode) or
``{"full": ..., "slot": int32 [rows]}`` (prefill), a window at base 0
starts from zero tails inside the program (the span counts it,
``tail_resets``), a chunk carries them across its boundary, pads and idle
rows move none, the one call of lookahead keeps them (part of the donated
cache), ``_release_slot`` forgets them and ``_preempt``'s recompute from
base 0 is exact.  Refused by name, each with its reason
(``options.KIND_REFUSES["tails"]``): the prefix trie (the first token after
a shared block needs the tails at that block's end, and no block keeps
them), ``spec_tokens`` and a draft model, the host / NVMe tiers,
``quantize``, ``resident_window_blocks``, tp / dp / sp meshes.
``stats()["kv_tails"]`` has the leaves, their bytes, the taps, the resets,
the bytes the calls moved and the refusals; ``stats()["kv_kinds"]`` names
the kind beside ``full``; the ``decode`` / ``prefill`` spans carry
``tail_bytes`` / ``tail_resets``.

**No paged leaf at all** (PR 57): where the hook's cache tree holds the
state kind's leaves and nothing else (``_paged`` false) there is no pool:
none is committed (the start-up ring's ``pool`` span carries the state's
bytes, ``blocks`` 0), the allocator is ``inference/paged.py NoBlocks`` —
it has no block and gives none — the block table has no column, a row
of any length holds no block (:meth:`ServingEngine._blocks_for`), and a
request is ADMITTED BY A FREE SLOT
alone, needs nothing as it grows, is never preempted for room and is bounded
by ``max_seq_len`` only through the positions its rotary takes.  ``slots``
is what sizes the cache (a slot's state is the same bytes whatever its
length); ``num_blocks`` / ``block_size`` size nothing.  Both programs take
``block_tables = {"slot": int32 [rows]}`` — with no table to tell an idle
row by, a decode step names its live rows too (:meth:`_live_rows`), an idle
one's slot out of range.  ``stats()`` reports ``num_blocks`` /
``blocks_in_use`` / ``free_blocks`` 0, ``kv_pool_bytes`` 0 and
``kv_pool_shape`` ``[]`` (the state's bytes are ``kv_state``'s), ``kv_kinds``
the state kind alone, and the ``prefill`` spans ``kv_blocks`` 0.  Every one
of the refusals above holds as it stands.

Greedy decoding only: per-request outputs are token-identical to
sequential ``generate`` (pinned in ``tests/unit/test_serving.py``,
``tests/unit/test_paged_serving.py``, ``tests/unit/test_spec_decode.py``,
and — across tp degrees — ``tests/unit/test_tp_serving.py``); quantized
lanes are bounded-divergence instead (above).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
import os
import statistics
import tempfile
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis.concurrency import (LockSanitizer, caller_site,
                                    ordered_condition)
from ..analysis.invariants import audit_serving_engine
from ..moe import routed
from ..analysis.sentry import RecompileSentry, backend_compiles
from ..ops import (decode_attention, paged_kv, sp_attention,
                   sparse_index_attention)
from ..ops import sampling as sampling_ops
from ..ops.paged_kv import blocks_for
from ..parallel.topology import DP_AXIS, SP_AXIS, TP_AXIS
from ..telemetry import MetricsRegistry, ProfilerWindow, TraceTimeline
from ..telemetry.metrics import process_registry
from ..telemetry import trace as trace_mod
from ..telemetry.programs import Programs
from ..telemetry.slo import SLOTracker
from ..utils.logging import log_dist, logger
from ..utils.platform import on_tpu
from . import options
from .operands import OperandLayout
from .paged import (SCRATCH_BLOCK, BlockAllocator, GroupedBlockAllocator,
                    HostBlockStore, NoBlocks, NvmeBlockStore, PrefixCache,
                    TransportError, WindowRing, chain_key, chain_keys)
from .spec import NGramProposer, greedy_accept, rejection_accept


class RequestFailedError(RuntimeError):
    """A request was permanently failed by the serving fleet: its
    replica crashed and the re-home retry budget was exhausted, or no
    live replica remained to take it (``ReplicaRouter.fail``).  Raised
    by :meth:`RequestHandle.result`; tokens streamed before the failure
    stay readable via :meth:`RequestHandle.tokens`."""

    def __init__(self, uid, reason: str):
        super().__init__(f"request {uid!r} failed: {reason}")
        self.uid = uid
        self.reason = reason


#: the pieces of a step the segments time (``TraceTimeline.segment``):
#: ``plan`` / ``upload`` / ``commit`` on the ``step.prefill`` and
#: ``step.decode`` phases, ``enqueue`` / ``wait`` on the in-flight spans
SEGMENTS = ("plan", "upload", "enqueue", "wait", "commit")
#: a step is a STALL when it lasts over ``STALL_FACTOR`` x the median of
#: the last ``STALL_HISTORY`` steps of its shape (with / without a prefill
#: group) — the factor ``chipbench``'s ``step_stall_share`` puts on a
#: window's own median; a shape's median is taken every ``STALL_REFRESH``
#: steps of it (so none is judged before that many), and at most one
#: warning is logged per ``STALL_WARN_EVERY_S``
STALL_FACTOR = 3.0
STALL_HISTORY = 256
STALL_REFRESH = 32
STALL_WARN_EVERY_S = 10.0
#: what explains a stall, the first that covers over half its excess:
#: the scheduler's thread off the CPU outside the in-flight spans (blocked
#: in a runtime call such as an upload, or descheduled), the collector,
#: the wait for a program's results, else the host's own code
STALL_CAUSES = ("offcpu", "gc", "device_wait", "host")
#: what a stalled step is compared in (``ServingEngine._stall``)
_STALL_FIELDS = ("wall",) + SEGMENTS + ("offcpu", "gc")
#: why a call's results were taken before the next call was on the device's
#: queue (:meth:`ServingEngine._settle`): what the engine is (its audits, a
#: runner or a tier with a fence of its own — ``"speculative"``: a draft
#: model's rollout and the n-gram lookup start from a round's tokens; NOT a
#: self-drafting engine, whose round leaves its successor's inputs on the
#: device —, a replica that hands its rows on), what its rows need (a mask
#: made on the host from their tokens), or what is about to be done to a row
#: that is in flight
EARLY_SETTLE_CAUSES = ("debug_checks", "speculative", "kv_tier", "handoff",
                       "mask_builder", "preempt", "cancel", "drain", "close")
#: the cache leaves of the state kind: indexed by SLOT, never by block
ROW_LEAVES = paged_kv.ROW_LEAVES
#: a decode row's entry in the call's token operand when its input token is
#: the one the call before made and the host has not seen: the program
#: takes the row's entry of the engine's device-resident token vector
TOKEN_ON_DEVICE = -1

def _validate_decode_hooks(module, *, speculative: bool = False,
                           kv_quant: bool = False, sampling: bool = False,
                           role: str = "model"):
    """Fail fast at engine construction, naming the exact missing hook,
    instead of a TypeError deep inside the first prefill call.  Checks the
    hook dict AND the ``forward_cached`` signature (a family can carry a
    stale flag without the matching kwarg)."""
    hooks = getattr(module, "decode_hooks", None)
    name = getattr(module, "name", "<model>")
    if not hooks:
        raise ValueError(
            f"continuous batching needs decode_hooks; {role} {name} has "
            "none")
    for key in ("init_cache", "forward_cached"):
        if key not in hooks:
            raise ValueError(
                f"{role} {name}: decode_hooks is missing the '{key}' hook")
    if not hooks.get("supports_lengths"):
        raise ValueError(
            f"{role} {name}'s decode hooks predate per-sequence lengths "
            "(supports_lengths) — update its forward_cached to the lengths "
            "contract first")
    if not hooks.get("supports_paged"):
        raise ValueError(
            f"{role} {name}'s decode hooks predate the block-paged cache "
            "(supports_paged) — thread block_tables through its "
            "forward_cached first")
    if speculative and not hooks.get("supports_verify"):
        raise ValueError(
            f"{role} {name}'s decode hooks lack the speculative verify "
            "head (supports_verify) — add all-position logits "
            "(all_positions=True) to its forward_cached first")
    if sampling and not hooks.get("supports_sampling"):
        raise ValueError(
            f"{role} {name}'s decode hooks do not declare sampling "
            "support (supports_sampling) — a family qualifies when its "
            "forward_cached returns full-vocab logits the on-device "
            "sampler can filter; set the flag after verifying that, or "
            "build the engine with sampling=False (greedy-only)")
    if kv_quant and not hooks.get("supports_kv_quant"):
        raise ValueError(
            f"{role} {name}'s decode hooks do not declare int8-KV support "
            "(supports_kv_quant) — a family qualifies when every pool "
            "read/write goes through ops/paged_kv (record-aware); set the "
            "flag after verifying that, or drop quantize='kv8'")
    try:
        sig = inspect.signature(hooks["forward_cached"])
    except (TypeError, ValueError):  # graft: noqa(GL013) predicate: builtins / C callables have no signature — trust flags
        sig = None
    if sig is not None:
        need = ["lengths", "block_tables"] + \
            (["all_positions"] if speculative else [])
        missing = [kw for kw in need if kw not in sig.parameters]
        if missing:
            raise ValueError(
                f"{role} {name}: forward_cached does not accept the "
                f"{missing} keyword(s) its hook flags promise "
                f"(signature: forward_cached{sig})")
    return hooks


@dataclasses.dataclass
class Request:
    """One serving request: prompt token ids + a completion budget, plus
    the per-request sampling contract (PR 20).  ``temperature=0`` (the
    default) is greedy — bit-identical to every prior PR — and rides the
    SAME compiled programs as sampled traffic; ``seed`` keys the
    counter-based PRNG (``ops/sampling.py``), so a request's sampled
    stream is a pure function of ``(prompt, params, seed)`` — replayable
    across crash re-homes and preemptions.
    ``mask_builder`` (a :class:`~deepspeed_tpu.inference.constrain
    .LogitMaskBuilder`) opens the constrained-decoding lane; it needs an
    engine built with ``logit_masks=True``."""
    uid: Any
    prompt: np.ndarray                      # int32 [prompt_len]
    max_new_tokens: int = 32
    temperature: float = 0.0                # 0 = greedy (the default row)
    top_k: int = 0                          # 0 = off
    top_p: float = 1.0                      # 1 = off
    seed: int = 0                           # counter-based PRNG root
    mask_builder: Optional[Any] = None      # constrained-decoding hook

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError(f"request {self.uid!r}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"request {self.uid!r}: max_new_tokens must "
                             "be >= 1")
        if self.temperature < 0:
            raise ValueError(f"request {self.uid!r}: temperature must be "
                             f">= 0 (0 = greedy), got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"request {self.uid!r}: top_k must be >= 0 "
                             f"(0 = off), got {self.top_k}")
        if not 0 < self.top_p <= 1:
            raise ValueError(f"request {self.uid!r}: top_p must be in "
                             f"(0, 1] (1 = off), got {self.top_p}")
        # the PRNG root is materialized as np.uint32 at admission —
        # validate here so a bad seed is refused at submit() rather than
        # exploding the scheduler loop mid-trace (NumPy >= 2 raises
        # OverflowError on out-of-range uint32 casts)
        if not 0 <= self.seed < 2 ** 32:
            raise ValueError(f"request {self.uid!r}: seed must be in "
                             f"[0, 2**32), got {self.seed}")

    @property
    def sampled(self) -> bool:
        """True when this request draws from the sampler (any nonzero
        temperature); greedy requests never touch the PRNG streams."""
        return self.temperature > 0


#: ``slo_class`` -> default admission priority (``submit``): an SLO class
#: is a coarse priority band with a stable name — explicit ``priority=``
#: (nonzero) always wins over the class default
SLO_PRIORITY = {"realtime": 2, "interactive": 1, "standard": 0, "batch": -1,
                "giant_context": 0}

#: resident-window serving: leading blocks that stay device-resident and
#: attention-visible forever (attention-sink landmarks — the softmax needs
#: the early positions to stay numerically sane once the middle of the
#: context is masked out).  One block is enough for the sink effect; the
#: knob is a module constant rather than a ctor parameter to keep the
#: config surface at a single ``resident_window_blocks`` dial.
_LANDMARK_BLOCKS = 1


def prefill_ladder(batch: int, chunk: int, refuses):
    """The shapes ``(rows, width)`` a prefill call's budget of ``batch *
    chunk`` tokens is cut into: ``(batch, chunk)`` and, for a row that runs
    alone, ``(1, batch * chunk)`` — unless ``refuses(width)`` names a
    reason.  -> ``(rungs, why)``: ``why`` is what refused the wide row
    (None: it is there, or ``batch`` is 1).  The rungs between the two
    (``(batch / 2, 2 * chunk)``, ..) are not built: every rung is a program
    of its own, traced and lowered at set-up, and that is seconds of every
    engine's start; a short group's rows take turns at the wide row
    instead (``ServingEngine._rung_for``; docs/inference.md "Chunked
    prefill")."""
    rungs, why = [(batch, chunk)], None
    if batch > 1:
        why = refuses(batch * chunk)
        if why is None:
            rungs.append((1, batch * chunk))
    return rungs, why


class RequestHandle:
    """Live view of one submitted request (``ServingEngine.submit`` /
    ``ReplicaRouter.submit``): per-token streaming, completion, and
    cancellation.

    The engine side appends committed tokens as the scheduler emits them
    (prefill first token, decode steps, speculative accepts); the caller
    side reads them — ``tokens()`` for everything so far, ``next_token``
    for a streaming cursor (blocking when a worker thread drives the
    engine, ``timeout=0`` when the caller drives ``step()`` itself), and
    ``result()`` for the final padded ``[prompt + completion]`` array
    (``None`` if the request was cancelled).  A preemption keeps the
    handle: already-streamed tokens stand (greedy resume recomputes the
    identical sequence), and fresh tokens continue on the same handle —
    including across a replica drain handoff.  All state transitions run
    under one condition variable, so the handle is safe to read from a
    different thread than the scheduler's.

    Under ``debug_checks`` the engine passes its lock sanitizer
    (``analysis/concurrency.py``) and the condition becomes an
    instrumented ``ordered_condition``: the handle participates in the
    declared fleet lock order (fleet -> replica -> handle), and the
    blocking accessors (``result`` / blocking ``next_token``) raise
    :class:`~deepspeed_tpu.analysis.concurrency.BlockingUnderLockError`
    when entered while the calling thread holds any sanitized lock —
    waiting on a handle under the fleet or a replica lock is a deadlock
    (the scheduler that would finish the request can never run)."""

    def __init__(self, request: Request, *, priority: int = 0,
                 slo_class: Optional[str] = None, canceller=None,
                 lock_sanitizer: Optional[LockSanitizer] = None):
        self.request = request
        self.uid = request.uid
        self.priority = int(priority)
        self.slo_class = slo_class
        # "queued" -> "active" -> "finished" | "cancelled" | "failed"
        # (failed = crash re-homing exhausted; result() raises the
        # recorded RequestFailedError instead of returning)
        self.status = "queued"
        self._tokens: List[int] = []
        self._result: Optional[np.ndarray] = None
        self._error: Optional[BaseException] = None
        self._sanitizer = lock_sanitizer
        self._cond = ordered_condition("serving.handle", lock_sanitizer) \
            if lock_sanitizer is not None else threading.Condition()
        self._cursor = 0
        self._canceller = canceller

    # ---- engine-side transitions (scheduler thread)
    def _on_active(self) -> None:
        with self._cond:
            if self.status == "queued":
                self.status = "active"
            self._cond.notify_all()

    def _on_tokens(self, toks) -> None:
        with self._cond:
            self._tokens.extend(int(t) for t in toks)
            self._cond.notify_all()

    def _on_finish(self, result: np.ndarray) -> None:
        with self._cond:
            self._result = result
            self.status = "finished"
            self._cond.notify_all()

    def _on_cancel(self) -> None:
        with self._cond:
            self.status = "cancelled"
            self._cond.notify_all()

    def _on_fail(self, exc: BaseException) -> None:
        """Resolve the handle as permanently failed (crash re-homing
        exhausted) — never downgrades an already-finished request."""
        with self._cond:
            if self.status not in ("finished", "cancelled"):
                self._error = exc
                self.status = "failed"
            self._cond.notify_all()

    def set_canceller(self, canceller) -> None:
        """Rebind the cancel route (router submit / drain handoff) —
        under the handle condition, because a worker may already be
        streaming transitions into this handle when the router rebinds
        it (a bare attribute store is exactly the unguarded-shared-state
        hazard graft-race GL010 flags)."""
        with self._cond:
            self._canceller = canceller

    # ---- caller side
    @property
    def done(self) -> bool:
        return self.status in ("finished", "cancelled", "failed")

    def tokens(self) -> List[int]:
        """Every token committed so far (a copy)."""
        with self._cond:
            return list(self._tokens)

    def cancel(self) -> bool:
        """Cancel via whoever owns the request now (engine or router);
        ``False`` if it already finished."""
        return bool(self._canceller and self._canceller(self.uid))

    def next_token(self, timeout: Optional[float] = None) -> Optional[int]:
        """Streaming cursor: the next committed token, or ``None`` once
        the request is finished/cancelled/failed.  ``timeout=0`` is the
        non-blocking poll for callers driving ``step()`` themselves
        (``None`` then also means "nothing new yet"); a positive
        ``timeout`` that expires with the request still live raises
        ``TimeoutError`` — a lost replica surfaces as a loud, typed
        error at the caller instead of an indefinite hang
        (docs/reliability.md).  ``timeout=None`` blocks until a token
        arrives or the request resolves."""
        if self._sanitizer is not None and timeout != 0:
            self._sanitizer.check_wait(
                f"RequestHandle.next_token(uid={self.uid!r})",
                site=caller_site(2))
        with self._cond:
            self._cond.wait_for(
                lambda: self._cursor < len(self._tokens) or self.done,
                timeout)
            if self._cursor < len(self._tokens):
                tok = self._tokens[self._cursor]
                self._cursor += 1
                return tok
            if self.done or not timeout:   # resolved, poll, or blocking
                return None
            raise TimeoutError(
                f"request {self.uid!r} streamed nothing new within "
                f"{timeout}s (status {self.status})")

    def result(self, timeout: Optional[float] = None) -> Optional[np.ndarray]:
        """Block until completion; the padded ``[prompt + completion]``
        array (``serve`` semantics), or ``None`` if cancelled.  Raises
        ``TimeoutError`` if ``timeout`` expires first, and
        :class:`RequestFailedError` when the fleet permanently failed
        the request (crash re-homing exhausted — the tokens streamed
        before the failure stay readable via :meth:`tokens`)."""
        if self._sanitizer is not None and timeout != 0:
            self._sanitizer.check_wait(
                f"RequestHandle.result(uid={self.uid!r})",
                site=caller_site(2))
        with self._cond:
            if not self._cond.wait_for(lambda: self.done, timeout):
                raise TimeoutError(
                    f"request {self.uid!r} still {self.status} after "
                    f"{timeout}s")
            if self.status == "failed":
                raise self._error
            return self._result


@dataclasses.dataclass
class _PendingItem:
    """One queued request plus its resume/streaming context — what the
    pending queue holds and what ``drain()`` hands a router."""
    req: Request
    prior: List[int]               # tokens generated before a preemption
    priority: int = 0
    slo_class: Optional[str] = None
    eos: Optional[int] = None
    handle: Optional[RequestHandle] = None
    _order: tuple = (0, 0)         # (-priority, seq) — queue sort key


class _PendingQueue:
    """Priority-then-FIFO admission queue.

    Items sort by ``(-priority, submit seq)`` — higher priority first,
    FIFO within a class — except preemption resumes (``push_front``),
    which jump ahead of EVERYTHING: the resumed sequence holds admission
    recency and the scheduler's no-starvation gate reasons about the
    literal queue head."""

    def __init__(self):
        self._items: List[_PendingItem] = []
        self._seq = 0
        self._front = -1

    def push(self, item: _PendingItem) -> None:
        item._order = (-int(item.priority), self._seq)
        self._seq += 1
        i = len(self._items)
        while i > 0 and self._items[i - 1]._order > item._order:
            i -= 1
        self._items.insert(i, item)

    def push_front(self, item: _PendingItem) -> None:
        item._order = (-(1 << 30), self._front)
        self._front -= 1
        self._items.insert(0, item)

    def popleft(self) -> _PendingItem:
        return self._items.pop(0)

    def remove(self, uid) -> Optional[_PendingItem]:
        for i, item in enumerate(self._items):
            if item.req.uid == uid:
                return self._items.pop(i)
        return None

    def drain(self) -> List[_PendingItem]:
        items, self._items = self._items, []
        return items

    def __len__(self) -> int:
        return len(self._items)

    def __bool__(self) -> bool:
        return bool(self._items)

    def __iter__(self):
        return iter(self._items)

    def __getitem__(self, i) -> _PendingItem:
        return self._items[i]


@dataclasses.dataclass
class _SlotState:
    req: Request
    admit_seq: int                 # admission recency (preemption victim order)
    prompt_eff: np.ndarray         # prompt (+ pre-preemption tokens on resume)
    prior: List[int]               # tokens generated before a preemption
    out: List[int] = dataclasses.field(default_factory=list)
    base: int = 0                  # tokens already in the paged cache
    phase: str = "prefill"         # "prefill" -> "decode"
    eos: Optional[int] = None      # per-request eos (submit-time)
    priority: int = 0
    slo_class: Optional[str] = None
    handle: Optional[RequestHandle] = None
    #: resident-window serving: first block index of the device-resident
    #: window (blocks in [landmark, window_blk) are demoted + masked);
    #: stays 0 when resident_window_blocks == 0 or nothing has slid yet
    window_blk: int = 0
    #: tokens of this request a call on the device has made and the host
    #: has not harvested (``ServingEngine._settle``): they are in no list
    #: yet, but the cache length, the sampler's count and the budget of the
    #: next call are planned as if they were
    ahead: int = 0
    #: self-drafting rounds of this row the device has been handed whose
    #: counts the host has not harvested (0 or 1,
    #: ``ServingEngine._run_self_round``): each made 1..K+1 tokens, how many
    #: is on the device only (``_devcount``), so the row's length and its
    #: sampler's count are the host's plus that, and the host plans on
    #: bounds
    rounds: int = 0
    #: prefill calls of this row's group in a row that it was ready for and
    #: did not run in (``ServingEngine._rung_for``): its place in the
    #: group's turn order, at most ``prefill_batch - 1``
    waited: int = 0

    @property
    def plen_eff(self) -> int:
        return int(self.prompt_eff.size)

    @property
    def gen_count(self) -> int:
        return len(self.prior) + len(self.out)

    @property
    def pos_cap(self) -> int:
        """One past the highest cache position this request can ever
        commit: original prompt + completion budget.  Speculative windows
        reaching past the cap scatter to the scratch block instead of
        allocating blocks the request can never use (rollback-aware
        accounting — see ops/paged_kv.py)."""
        return self.plen_eff - len(self.prior) + self.req.max_new_tokens


class _Flight:
    """A call the device has been handed whose results the host has not
    taken: the scheduler's ONE call of lookahead
    (:meth:`ServingEngine._launch` / :meth:`ServingEngine._settle`).
    ``args`` is the argument dict of its in-flight span (``decode`` /
    ``prefill``), ``out`` the flat tokens-and-record array it returns,
    ``shape`` that of the tokens in it, ``commit`` what the harvest hands
    the tokens to, ``held`` the call's operands, kept until then,
    ``phase`` the host phase its ``upload`` / ``commit`` segments go under
    (its own name; ``decode`` for a self-drafting round, ``spec_round``)."""

    __slots__ = ("name", "args", "out", "shape", "commit", "held", "phase")

    def __init__(self, name, args, shape, commit, phase=None):
        self.name, self.args, self.shape = name, args, shape
        self.commit, self.out, self.held = commit, None, None
        self.phase = phase or name


class ServingEngine:
    """Iteration-level (continuous-batching) scheduler over an
    :class:`~deepspeed_tpu.inference.engine.InferenceEngine`'s KV-decode
    path, with a block-paged cache (module docstring has the design).

    Parameters (after ``engine``, keyword options: their names, defaults
    and ranges, and what does not combine, are ``inference/options.py``'s —
    ``inspect.signature`` shows them from there)
    ----------
    engine:         an ``init_inference`` engine whose model carries
                    ``decode_hooks`` with ``supports_lengths`` and
                    ``supports_paged`` (gpt2 / llama / opt / mixtral).
    slots:          max concurrently-active sequences.
    max_seq_len:    per-sequence budget (prompt + completion), clamped to
                    the model context length.
    block_size:     tokens per KV block (paging granularity — also the
                    prefix-reuse granularity).  Default ``None`` = 32, and
                    for a model with latent attention what
                    ``paged_kv.latent_block_tokens`` makes of the leaf's
                    bytes a token (512 at 768 B).
    num_blocks:     physical pool size incl. the scratch block.  Default
                    ``1 + slots * ceil(max_seq_len/block_size)`` (no
                    oversubscription); smaller pools oversubscribe and rely
                    on prefix eviction + preemption.
    prefill_chunk:  the NARROWEST row of a prefill call, in tokens: what a
                    prompt advances by in a call that holds
                    ``prefill_batch`` of them.
    prefill_batch:  the MOST sequences a prefill call holds.  The product
                    ``prefill_batch * prefill_chunk`` is a call's budget of
                    tokens: a row that is alone in its group, or whose
                    turn it is among two or three long ones, runs ``[1,
                    prefill_batch * prefill_chunk]`` and advances by what
                    the pad rows of ``[prefill_batch, prefill_chunk]``
                    would have wasted.  The wide rung exists only where
                    the shapes say it is sound (a row within the cache,
                    the prefill kernel's query rows a KV head, no resident
                    window); the constructor's log line and
                    ``stats()["prefill_shapes"]`` name the rungs built.
    prefix_caching: enable the block trie.  Default ``None`` = on, unless
                    the model mixes sliding-window and full layers (decode
                    hook ``window_layers``): a shared prefix is of no use
                    there without the window layers' last ``window`` keys,
                    which the ring has dropped; ``True`` then raises.
    spec_tokens:    speculative draft length K (0 = off).  Each decode
                    iteration proposes K tokens per slot and verifies
                    them in one K+1-token target pass (with
                    ``draft="self"`` at most the module's depth).
    shard_kv:       shard the paged pool over the mesh's ``tp`` axis
                    (KV-head dim — module docstring).  Default ``None`` =
                    auto: shard iff tp > 1 and the KV head count divides
                    it.  ``True`` additionally raises when the head count
                    does not divide (instead of silently replicating);
                    ``False`` forces the replicated tp=1 layout.
    quantize:       serving-path quantization (module docstring): ``None``
                    (default, bit-identical to pre-quantization behavior),
                    ``"kv8"`` (int8 paged KV pool + per-block scale
                    table), ``"w8a8"`` (requires an engine whose params
                    carry K-grouped int8 records — ``init_serving`` wires
                    the config), or ``"w8a8+kv8"``.  Quantized lanes trade
                    exact greedy parity for a bounded token-divergence /
                    logit-error contract.
    host_blocks:    host-DRAM tier size in KV blocks (module docstring
                    "Tiered KV cache"); ``0`` (default) disables tiering
                    — behavior, programs, and scheduling are then
                    byte-identical to the pre-tiering engine.  Requires
                    ``prefix_caching`` on (the
                    trie is the content-address space promotions graft
                    back into).  Size it at the session working set you
                    want to survive eviction — e.g. the full trace
                    footprint for a multi-turn chat tier.
    swap_batch:     blocks per demotion/promotion device round trip (the
                    fixed shape of the two swap programs; default 8).
                    Larger batches amortize transfer latency, smaller
                    ones waste less padding on short chains.
    engine_mode:    ``"replicas"`` (default) or ``"dp_tp"``.  dp_tp runs
                    ONE engine over a 2-D ``("dp", "tp")`` mesh: the
                    slot/batch axis and the physical-block dim shard
                    over ``dp`` (each dp group owns a contiguous span of
                    slots AND of pool blocks, with its own scratch
                    block), KV heads stay tp-sharded via the existing
                    ``tp_context`` — one compiled decode program serves
                    what otherwise takes dp router-fronted replicas,
                    and the router demotes to front-end admission.
                    v1 restrictions (ctor-validated): chunked prefill;
                    no speculative decoding, host tier, prefix caching
                    or quantization; ``slots`` and ``num_blocks``
                    divisible by the mesh dp degree.
    draft:          draft proposer model — an ``init_inference`` engine or
                    a bare ModelSpec (wrapped with the target's inference
                    config) of a small same-family/same-tokenizer model.
                    ``None`` selects the model-free n-gram prompt-lookup
                    proposer (zero extra compiled programs).  ``"self"``
                    (``options.SELF_DRAFT``): the served model's OWN
                    drafting module (decode hook ``self_draft``: a
                    multi-token-prediction block) — its rows in the
                    target's pool, its draft on the device, one harvest a
                    round (module docstring "A third proposer").
    ngram_max/min:  n-gram match lengths for the lookup proposer (longest
                    match first, most recent occurrence wins).
    debug_checks:   turn the documented contracts into enforced ones
                    (``analysis/``): the recompile sentry RAISES at trace
                    time on any retrace past the engine's compile budget
                    (with an abstract-signature diff), and the paged-state
                    invariant audit (refcounts, free list, scratch, trie,
                    table spans) runs after every scheduler iteration.
                    Off (default): the sentry still counts traces (zero
                    runtime cost — the wrapped body only executes while
                    tracing) and ``stats()['retraces_observed']`` reports
                    drift; the audit is one skipped branch per iteration.
    trace_capacity: per-request trace timeline ring size (module
                    docstring "Telemetry"): scheduler events are recorded
                    into a bounded host-side ring and exported as Chrome
                    ``trace_event`` JSON by :meth:`dump_trace`.  ``0``
                    disables event recording entirely (one predicate per
                    would-be event); the metrics registry backing
                    ``stats()`` and the spans' profiler annotations are
                    always on.  The default
                    (``telemetry/trace.py DEFAULT_CAPACITY``) holds two
                    minutes of the densest serving cell at twice its
                    step rate; the step's thread-CPU / collector
                    accounting and the ``stall`` events ride the ring's
                    clock and go off with it.
    slo_targets:    per-``slo_class`` latency targets + attainment
                    objective overrides, merged over
                    ``telemetry/slo.py DEFAULT_SLO_TARGETS`` — every
                    finished request lands in its class's TTFT/TPOT
                    histograms and attainment counters; ``slo_report()``
                    is the per-class view.
    peak_flops:     the MFU denominator (per-chip peak FLOPs × chips)
                    for :meth:`flops_report`; ``None`` leaves the MFU
                    gauge unset unless the report call supplies one.
    """

    def __init__(self, engine, **given):
        o = options.bind(given, "ServingEngine")
        options.check_ranges(vars(o))
        num_blocks, draft, shard_kv = o.num_blocks, o.draft, o.shard_kv
        self.spec_tokens = o.spec_tokens
        # ----- on-device sampling stack (PR 20)
        self.sampling, self.logit_masks = o.sampling, o.logit_masks
        self.quantize, self.kv_quant, _ = options.parse_quantize(o.quantize)
        qcfg = engine._config.quant
        self.weight_quant = qcfg.type if qcfg.enabled else None
        hooks = getattr(engine.module, "decode_hooks", None) or {}
        #: a recurrent state a row (decode hook ``state_layers``:
        #: ``{"layers", "heads", "key_dim", "value_dim", "bodies"}`` and
        #: what else the family says of itself): leaves indexed by SLOT,
        #: beside the paged pool where there is one, with no block ids, no
        #: table and no allocator (module docstring "The state kind");
        #: None otherwise
        self._state = hooks.get("state_layers")
        self._state_totals = {"resets": 0, "state_rows": 0}
        #: tails a row (decode hook ``tail_layers``: ``{"layers", "taps":
        #: {leaf: tokens}}``): what the writer of
        #: a token's key and value needs of the token BEFORE, leaves
        #: indexed by SLOT beside a pool whose every layer is paged (module
        #: docstring "Tails"); None otherwise
        self._tails = hooks.get("tail_layers")
        self._tail_totals = {"resets": 0, "tail_bytes": 0}
        #: row-indexed leaves of either kind (``paged_kv.ROW_LEAVES``): the
        #: cache is built for ``slots`` rows of them and a prefill call
        #: names its rows' slots
        self._rowed = self._state or self._tails
        #: learned sparse attention (decode hook ``sparse_attention``:
        #: ``{"topk"}``): the pool has a third leaf (the indexer's keys) and
        #: a row past ``topk`` keys attends ``topk`` of them; None otherwise
        self._sparse = hooks.get("sparse_attention")
        self._sparse_totals = dict.fromkeys(sparse_index_attention.COUNTS, 0)
        #: layers of two kinds (decode hook ``window_layers``: ``{"window",
        #: "layers": {"full", "sliding"}}``): the pool holds
        #: leaves BY KIND, each kind with its own block ids, allocator and
        #: table — the full kind as every model's, the window kind a ring
        #: (module docstring "Layer kinds"); None otherwise
        self._windows = hooks.get("window_layers")
        #: latent attention (decode hook ``latent_attention``: ``{"rank",
        #: "rope", "width"}``): the pool is ONE leaf holding ``width``
        #: values a token a layer and no K / V (module docstring "The
        #: latent kind"); None otherwise
        self._latent = hooks.get("latent_attention")
        #: the model's OWN drafting module (``draft="self"``,
        #: ``options.SELF_DRAFT``; decode hook ``self_draft``: ``{"depth":
        #: tokens ahead it guesses, "layers": the layers its rows add to the
        #: target's leaves, "cache": the keywords ``init_cache`` takes for
        #: them, "forward": ``(params, hidden, next ids, cache, pos,
        #: lengths=, block_tables=, all_positions=, at=, routing=) ->
        #: (logits, cache[, record])``}``): the proposer of a round with ONE
        #: harvest (:meth:`_get_round_fn`); None otherwise
        self._self_draft = None
        if draft == options.SELF_DRAFT:
            self._self_draft, draft = hooks.get("self_draft"), None
            name = getattr(engine.module, "name", "<model>")
            if self._self_draft is None:
                raise ValueError(
                    f"draft='self': {name} has no drafting module of its "
                    "own (decode hook self_draft) — pass a draft MODEL, or "
                    "no draft (the n-gram proposer)")
            if self.spec_tokens > int(self._self_draft["depth"]):
                raise ValueError(
                    f"draft='self': {name}'s module drafts "
                    f"{self._self_draft['depth']} token(s) a round, "
                    f"spec_tokens={self.spec_tokens} asks for more")
        self._latent_totals = dict.fromkeys(
            ("kv_valid", "kv_blocks", "kv_pairs", "latent_bytes", "kv_tiles",
             "kv_first_tiles_ahead"), 0)
        #: :meth:`_kv_reach`'s span args, summed
        self._window_totals = {"kv_valid": 0, "kv_visible": 0}
        self._full_peak = 0        # most full-kind blocks in use after a step

        # ----- what the options size: the context, the block, a call
        max_ctx = hooks.get("max_seq_len")
        max_seq_len = o.max_seq_len
        if max_seq_len is None:
            max_seq_len = max_ctx or 512
        if max_ctx is not None and max_seq_len > max_ctx:
            raise ValueError(
                f"max_seq_len {max_seq_len} exceeds the model context "
                f"length {max_ctx}")
        self.max_seq_len = int(max_seq_len)
        self.slots = o.slots
        block_size = o.block_size
        if block_size is None:
            block_size = paged_kv.latent_block_tokens(
                self._latent["width"],
                jnp.dtype(engine._config.jnp_dtype).itemsize,
                self.max_seq_len) if self._latent \
                else paged_kv.DEFAULT_BLOCK_TOKENS
        self.block_size = int(block_size)
        # logical per-sequence capacity, rounded up to whole blocks
        self._cache_len = blocks_for(self.max_seq_len, block_size) \
            * block_size
        # floor of 2: forward_cached dispatches per-row DECODE on T == 1,
        # so a width-1 prefill window would be misread as a decode step
        # (1-token prompts prefill fine in a width-2 window — the pad
        # column writes to scratch)
        self.prefill_chunk = max(2, min(o.prefill_chunk, self._cache_len))
        self.prefill_batch = o.prefill_batch

        # ----- the mesh's degrees, and what these options may not be
        # built as on them for a model with these kinds of cache: ONE
        # statement, ``inference/options.py``
        mesh = dict(engine.mesh.shape)
        #: ``"dp_tp"``: ONE engine over the 2-D mesh, slots and blocks in
        #: dp groups
        self.engine_mode = o.engine_mode
        self.tp_degree = int(mesh.get(TP_AXIS, 1))
        self.dp_degree = int(mesh.get(DP_AXIS, 1)) \
            if self.engine_mode == "dp_tp" else 1
        #: sequence-parallel (Ulysses) prefill over the mesh sp axis
        self.sp_degree = o.sp
        kinds = [kind for kind, hook in (
            ("state", self._state), ("tails", self._tails),
            ("window", self._windows),
            ("indexer", self._sparse), ("latent", self._latent)) if hook]
        prefix_caching = o.prefix_caching
        if prefix_caching is None:
            # on, unless a kind of this model's cache refuses it
            prefix_caching = not any(
                "prefix_caching" in options.KIND_REFUSES[k] for k in kinds)
        #: ``{kind: what it is not served with}``: ``stats()``'s
        #: ``["kv_kinds" | "kv_latent" | "kv_state"]["refused"]``
        self._refusals = options.check(
            {**vars(o), "block_size": self.block_size,
             "prefill_chunk": self.prefill_chunk,
             "prefix_caching": prefix_caching},
            {"tp": self.tp_degree, "dp": self.dp_degree,
             "mesh_sp": int(mesh.get(SP_AXIS, 1)),
             "weights": self.weight_quant},
            kinds, getattr(engine.module, "name", "<model>"))
        hooks = _validate_decode_hooks(engine.module,
                                       speculative=bool(self.spec_tokens),
                                       kv_quant=self.kv_quant,
                                       sampling=self.sampling)
        self.engine = engine
        self._fwd = hooks["forward_cached"]
        #: an expert family's cached forward also returns its per-layer
        #: routing record (``moe/routed.py RECORD``) when asked
        self._routing = bool(hooks.get("routing_record"))
        #: width of a layer's routing record (``moe/routed.py``): a model
        #: that holds a share of its experts also counts the absent pairs
        self._rec_width = len(routed.RECORD_HELD) \
            if hooks.get("experts_held") else len(routed.RECORD)
        self._rows_absent = 0
        self._init_cache = hooks["init_cache"]
        #: whether the cache tree has a paged leaf at all: a model whose
        #: EVERY layer is of the state kind has no pool, no block and no
        #: table — a request is admitted by a free slot alone, needs nothing
        #: as it grows and is bounded by ``max_seq_len`` only
        self._paged = not self._state or bool(self._paged_leaves(
            jax.eval_shape(lambda: self._init_cache(
                2, self.block_size, engine._config.jnp_dtype,
                state_rows=1))))
        # block-table width (a model with no paged leaf: no column)
        self._nbper = self._cache_len // block_size if self._paged else 0

        #: resident-window context paging for 100k+-token prompts: blocks
        #: of a row's sliding window that stay on the device (0: all)
        self.resident_window_blocks = o.resident_window_blocks
        #: leading blocks pinned device-resident + attention-visible
        self._landmark_blocks = _LANDMARK_BLOCKS \
            if self.resident_window_blocks else 0

        # ----- the prefill call is a BUDGET of prefill_batch x prefill_chunk
        # tokens, at the rung that carries most of a group's ready rows
        # (:meth:`_rung_for`): every rung of the ladder is a program
        #: ``(rows, width)`` of each prefill program, ``(prefill_batch,
        #: prefill_chunk)`` first, the wide row last; and what refused
        #: the wide row (None: it is there, or the batch is 1)
        self._rungs, self._ladder_stop = prefill_ladder(
            self.prefill_batch, self.prefill_chunk,
            functools.partial(self._refuse_rung, engine))
        #: the widest row of any prefill call: what the window kind's ring
        #: and a row's blocks ahead of a call are sized by
        self._prefill_width = self._rungs[-1][1]
        if num_blocks is None:
            num_blocks = self.dp_degree + self.slots * self._nbper
        if not self._paged:
            num_blocks = 0          # no pool is committed: nothing to count
            self._alloc = NoBlocks()
            self._scratch_blocks = None
        elif self.dp_degree > 1:
            # one allocation group per dp shard: each group owns a
            # contiguous span of physical blocks (its local block 0 is that
            # group's scratch), so every dp shard's gathers and scatters
            # stay within its own pool chunk
            if num_blocks % self.dp_degree:
                raise ValueError(
                    f"engine_mode='dp_tp': num_blocks ({num_blocks}) must "
                    f"divide evenly over the mesh dp axis "
                    f"({self.dp_degree})")
            if num_blocks // self.dp_degree < 1 + self._nbper:
                raise ValueError(
                    f"num_blocks {num_blocks} over {self.dp_degree} dp "
                    f"groups cannot hold one full sequence per group "
                    f"({self._nbper} blocks + 1 scratch each)")
            self._alloc = GroupedBlockAllocator(num_blocks, self.dp_degree)
            self._scratch_blocks = frozenset(
                g * (num_blocks // self.dp_degree)
                for g in range(self.dp_degree))
        else:
            # resident-window serving exists precisely so the device pool
            # can be SMALLER than one full logical sequence: only the
            # landmark prefix + sliding window (+ the chunk being
            # prefilled) must fit at once
            min_need = 1 + self._nbper
            if self.resident_window_blocks:
                min_need = min(
                    min_need,
                    1 + self._landmark_blocks + self.resident_window_blocks
                    + blocks_for(self._prefill_width, self.block_size))
            if num_blocks < min_need:
                raise ValueError(
                    f"num_blocks {num_blocks} cannot hold one full sequence "
                    f"({self._nbper} blocks + 1 scratch)"
                    if not self.resident_window_blocks else
                    f"num_blocks {num_blocks} cannot hold one resident "
                    f"window ({self._landmark_blocks} landmark + "
                    f"{self.resident_window_blocks} window + chunk span "
                    f"+ 1 scratch = {min_need})")
            self._alloc = BlockAllocator(num_blocks)
            self._scratch_blocks = None
        #: whether the block trie is on
        self.prefix_caching = bool(prefix_caching)
        self._prefix = PrefixCache(self.block_size) \
            if prefix_caching else None
        # ----- the host-DRAM tier, a replica's role in a disaggregated
        # fleet, the NVMe tier below the host's
        self.host_blocks, self.swap_batch = o.host_blocks, o.swap_batch
        self.role = o.role
        self.nvme_blocks = o.nvme_blocks
        self.nvme_high_watermark = o.nvme_high_watermark
        self._nvme_path_arg = o.nvme_path

        # ----- tensor parallelism: one pool, committed on the engine mesh so
        # the very first step sees the same placement as every later one —
        # sharded over the KV-HEAD dim (``P(None, None, "tp")`` on the
        # stacked [L, NB, HKV, bs, hd] buffer) when the mesh carries a tp
        # axis the head count divides, else replicated (module docstring)
        if self.kv_quant:
            # int8 pool records {qp, ps} (ops/paged_kv): codes + per-block
            # scale table, built from the float pool's ABSTRACT shapes
            # (eval_shape) — materializing the bf16 pool first would cost
            # a transient bf16+int8 double footprint at exactly the
            # near-full-HBM block counts kv8 exists to serve.  Host
            # bookkeeping below is layout-invariant — scales ride under
            # block ids — but the engine keeps a ledger of which blocks
            # own LIVE scale rows so the debug audit can prove scale
            # allocation stays in lockstep with blocks (scale-lockstep
            # invariant, analysis/invariants.py)
            mk_pool = lambda: paged_kv.quantize_pool(jax.eval_shape(
                lambda: self._init_cache(num_blocks, self.block_size,
                                         engine._config.jnp_dtype)))
            self._kv_dtype = "int8"
        else:
            kinds = {}
            if self._windows:
                # the window kind's allocator and ring tables, a whole ring
                # a slot (``inference/paged.py WindowRing``)
                self._ring = WindowRing(
                    self.slots, self._windows["window"], self._prefill_width,
                    self._window_block(engine._config.jnp_dtype))
                kinds["window_blocks"] = self._ring.alloc.num_blocks
            if self._rowed:
                kinds["state_rows"] = self.slots
            if self._self_draft:
                # the module's rows: more layers of the target's own leaves
                kinds.update(self._self_draft["cache"])
            mk_pool = lambda: self._init_cache(
                num_blocks, self.block_size, engine._config.jnp_dtype,
                **kinds)
            # (no paged leaf: the state kind's own dtype)
            shapes = jax.eval_shape(mk_pool)
            self._kv_dtype = jnp.dtype(jax.tree_util.tree_leaves(
                self._paged_leaves(shapes) or shapes)[0].dtype).name
        # the hook's (logical) shape [L, NB, HKV, bs, hd]; the pool itself
        # is held lane-packed (:meth:`_commit_pool`).  () where no leaf is
        # paged
        self._pool_shape = tuple(self._widest_leaf(mk_pool).shape) \
            if self._paged else ()
        #: the tile of the decode / verify walk at this pool's stored
        #: shapes: blocks a loop iteration, score columns a softmax update
        #: (``ops/decode_attention.py`` ``walk_tile_blocks``; None for a
        #: latent pool, which is read by a walk of its own, and where
        #: there is no pool to walk)
        self._decode_attn = None
        if self._paged and not self._latent:
            bs, hd = int(self._pool_shape[3]), int(self._pool_shape[4])
            rows = bs // paged_kv.lane_pack(bs, hd)
            tile = decode_attention.walk_tile_blocks(rows, self._nbper)
            self._decode_attn = {
                "tile_blocks": tile, "cols": tile * rows,
                "rows_ahead": decode_attention.WALK_ROWS_AHEAD}
        self._kv_scale_live: set = set()
        #: bytes an absorbed read needs of one key in one layer
        self._latent_token_bytes = self._latent["width"] \
            * jnp.dtype(self._kv_dtype).itemsize if self._latent else 0
        hkv = int(self._pool_shape[2]) if self._paged else 1
        divisible = self.tp_degree > 1 and hkv % self.tp_degree == 0
        if shard_kv and self.tp_degree > 1 and not divisible:
            raise ValueError(
                f"shard_kv=True but the model's KV head count ({hkv}) does "
                f"not divide the mesh tp axis ({self.tp_degree}) — GQA "
                "pools with HKV < tp serve replicated (head groups are "
                "shared across chips); drop shard_kv or lower tp_size")
        self.kv_sharded = divisible if shard_kv is None else \
            (bool(shard_kv) and divisible)
        if self.sp_degree > 1:
            # mirror ops/sp_attention.sp_shards so a config that would
            # silently fall back to single-rank prefill fails loud here
            nh = getattr(engine.module.model_config, "num_heads", None)
            sp_tp = self.tp_degree if self.kv_sharded else 1
            if nh is not None and (
                    int(nh) % hkv or int(nh) % sp_tp
                    or (int(nh) // sp_tp) % self.sp_degree):
                raise ValueError(
                    f"sp={o.sp}: the {nh} query heads must shard evenly "
                    f"over tp={sp_tp} then sp={o.sp} (and divide the "
                    f"{hkv} KV heads) for the Ulysses all-to-all — "
                    "lower sp or pick a head-count-compatible mesh")
        rep = NamedSharding(engine.mesh, P())
        if self.dp_degree > 1:
            # dp_tp: the physical-block dim shards over dp (each group owns
            # a contiguous span, matching GroupedBlockAllocator's layout)
            # and heads over tp when divisible — ``P(None, "dp", "tp")`` on
            # the stacked [L, NB, HKV, bs, hd] buffer
            pool_sharding = NamedSharding(
                engine.mesh,
                P(None, DP_AXIS, TP_AXIS) if self.kv_sharded
                else P(None, DP_AXIS))
        else:
            pool_sharding = NamedSharding(
                engine.mesh, P(None, None, TP_AXIS)) \
                if self.kv_sharded else rep
        self._pool_sharding = pool_sharding
        self._cache = self._commit_pool(mk_pool, pool_sharding,
                                        pool="target", blocks=num_blocks)
        # host-side block tables; entry 0 = scratch doubles as "unset"
        self._tables = np.zeros((self.slots, self._nbper), np.int32)
        self._held: List[List[int]] = [[] for _ in range(self.slots)]
        self._tokens = np.zeros(self.slots, np.int32)
        self._lengths = np.zeros(self.slots, np.int32)
        #: every slot's newest token where the device made it: the decode
        #: program returns it, the prefill program writes its rows' first
        #: tokens into it, and the decode program reads a row's input from
        #: it where the host has not harvested that token yet
        #: (``TOKEN_ON_DEVICE``) — ONE ``[slots]`` int32 vector whatever
        #: program made it, committed like the pool
        self._devtok = jax.device_put(np.zeros(self.slots, np.int32), rep)
        #: beside it, a self-drafting engine's NEXT DRAFT a slot, ``[slots,
        #: spec_tokens]``: the prefill program and the round leave it there
        #: and the next round reads it there — a draft never visits the host
        self._devdraft = jax.device_put(
            np.zeros((self.slots, self.spec_tokens), np.int32), rep) \
            if self._self_draft else None
        #: and the third vector, ``[slots]``: how many tokens the round just
        #: run committed for each row (1..K+1; 0 for a row that was not in
        #: it).  The NEXT round, enqueued before this one's counts reach the
        #: host, adds a row's entry to the base and the sampler's count the
        #: host gave it where the host says the row is a round behind
        #: (``behind``); the prefill programs neither read nor write it — a
        #: row that comes from one is not behind
        self._devcount = jax.device_put(
            np.zeros(self.slots, np.int32), rep) if self._self_draft else None
        #: slots whose pending token the HOST names in the next round (a
        #: sampled resume backs up one position); every other row's is the
        #: device's (``TOKEN_ON_DEVICE``)
        self._host_pending: set = set()
        #: resident-window serving: per-slot first attention-visible token
        #: past the landmark prefix (== landmark span while nothing has
        #: been demoted; rows of idle slots stay 0 and are never read by a
        #: windowed program because their batch rows are masked inactive)
        self._window_start = np.zeros(self.slots, np.int32)
        #: per-slot sampling state (PR 20): fixed-shape device operands —
        #: knob changes are operand VALUE changes, never recompiles.
        #: Rows of idle slots keep greedy defaults (temp 0), so inactive
        #: batch rows always take the bit-exact argmax lane.
        self._temps = np.zeros(self.slots, np.float32)
        self._topks = np.zeros(self.slots, np.int32)
        self._topps = np.ones(self.slots, np.float32)
        self._seeds = np.zeros(self.slots, np.uint32)
        self._vocab = int(getattr(engine.module.model_config, "vocab_size",
                                  0) or 0)
        #: constrained decoding: per-slot bool logit mask (True = allowed);
        #: only materialized when the engine opts into the mask operand
        self._masks = np.ones((self.slots, self._vocab), bool) \
            if self.logit_masks else None
        if self.logit_masks and not self._vocab:
            raise ValueError(
                "logit_masks=True needs the model config to expose "
                "vocab_size — the [slots, vocab] mask operand is sized "
                "from it")

        # compiled programs, built on first use
        #: the prefill program of each rung of the ladder, all built with
        #: the first (:meth:`_get_prefill_fn`)
        self._prefill_fns: Dict[Tuple[int, int], Any] = {}
        #: whether each has run once, on pad rows (:meth:`_warm_prefill`)
        self._prefill_warm = False
        self._decode_fn = None
        self._verify_fn = None
        self._draft_fn = None
        self._round_fn = None
        #: compile probe — one entry per traced program: 1 prefill + 1
        #: decode for an entire trace (speculative: 1 prefill + 1 verify
        #: [+ 1 draft rollout] — never more than 3)
        self.compiled_programs: List[Any] = []

        # ----- correctness tooling (analysis/): the recompile sentry wraps
        # every jitted body below so trace counts are enforced against the
        # declared budget — 2 (1 prefill + 1 decode; the n-gram speculative
        # verify replaces decode), 3 with a draft model (fused prefill +
        # rollout + verify).  debug_checks additionally raises at trace
        # time and audits the paged host state every scheduler iteration.
        self.debug_checks = o.debug_checks
        self.compile_budget = 3 if self.spec_tokens and (
            draft is not None or self._self_draft) else 2
        # one prefill program a rung of the ladder, all built (and run once
        # on pad rows) with the first of them
        self.compile_budget += len(self._rungs) - 1
        if self.host_blocks:
            # the tiered KV swap pair: kv_demote (block gather) +
            # kv_promote (block scatter), both fixed-shape at swap_batch —
            # H2D/D2H traffic itself never compiles anything further
            self.compile_budget += 2
        # long-context amendments are ZERO by construction and the sentry
        # enforces it: windowed programs REPLACE the plain decode/prefill
        # bodies one-for-one (the window mask is traced into the same
        # sentry entries, window_start rides as a [slots] int32 operand),
        # and sp prefill reshapes the SAME prefill program through
        # shard_map — neither adds a program
        self.sentry = RecompileSentry(name="serving",
                                      strict=self.debug_checks,
                                      total_budget=self.compile_budget)
        # lock sanitizer for the handle Conditions this engine mints
        # (analysis/concurrency.py): a router embedding this replica
        # overrides it with the fleet-shared one so replica-lock ->
        # handle-cond acquisition edges are order-checked; None when
        # debug_checks is off (handles fall back to plain Conditions —
        # zero overhead, same contract as the sentry)
        self._lock_sanitizer = LockSanitizer() if self.debug_checks \
            else None
        # (the process-wide jax.monitoring listener that corroborates the
        # sentry — it also sees programs built OUTSIDE registered entry
        # points, stats()["backend_compiles"] — came with the wrapped
        # engine: inference/engine.py installs it, unconditionally)

        # ----- speculative decoding state
        self._draft = None                 # draft InferenceEngine
        self._dcache = None                # draft paged pool (shares tables)
        self._dcache_sharded = False
        self._proposer = None              # host-side n-gram fallback
        self.ngram_max, self.ngram_min = o.ngram_max, o.ngram_min
        if self.spec_tokens:
            if draft is not None:
                from .engine import InferenceEngine

                if not isinstance(draft, InferenceEngine):
                    draft = InferenceEngine(
                        draft, engine._config,
                        device_group=engine.device_group)
                elif set(draft.mesh.devices.flat) != \
                        set(engine.mesh.devices.flat):
                    raise ValueError(
                        "the draft engine sits on other devices than the "
                        "target — build it with the target's device_group "
                        "(or pass the draft as a ModelSpec)")
                _validate_decode_hooks(draft.module, role="draft model",
                                       kv_quant=self.kv_quant,
                                       sampling=self.sampling)
                tv = getattr(engine.module.model_config, "vocab_size", None)
                dv = getattr(draft.module.model_config, "vocab_size", None)
                if tv is not None and dv is not None and tv != dv:
                    raise ValueError(
                        f"draft model vocab size {dv} != target vocab size "
                        f"{tv} — speculative decoding needs a shared "
                        "tokenizer")
                self._draft = draft
                mk_dpool = lambda: draft.module.decode_hooks["init_cache"](
                    num_blocks, self.block_size, draft._config.jnp_dtype)
                if self.kv_quant:
                    # the draft pool shares the target's block tables AND
                    # its quantization story — rollout reads/writes move
                    # int8 + scales too (abstract build, same double-
                    # footprint argument as the target pool above)
                    mk_float = mk_dpool
                    mk_dpool = lambda: paged_kv.quantize_pool(
                        jax.eval_shape(mk_float))
                dhkv = int(paged_kv.pool_payload(
                    jax.tree_util.tree_leaves(
                        jax.eval_shape(mk_dpool),
                        is_leaf=paged_kv.is_quantized_pool)[0]).shape[2])
                d_div = dhkv % self.tp_degree == 0
                if self.kv_sharded and not d_div:
                    if shard_kv:
                        raise ValueError(
                            f"shard_kv=True but the draft model's KV head "
                            f"count ({dhkv}) does not divide the mesh tp "
                            f"axis ({self.tp_degree}) — pick a draft whose "
                            "heads divide tp, or drop shard_kv")
                    log_dist(
                        f"ServingEngine: draft KV head count {dhkv} does "
                        f"not divide tp={self.tp_degree}; draft pool stays "
                        "replicated (target pool is sharded)", ranks=[0])
                # the draft pool rides the target's sharding story: same
                # head-dim spec when its HKV divides tp, else replicated
                # (the paged ops fall back per-shape — ops/paged_kv.py)
                self._dcache_sharded = self.kv_sharded and d_div
                dsharding = pool_sharding if self._dcache_sharded else rep
                self._dcache = self._commit_pool(
                    mk_dpool, dsharding, pool="draft", blocks=num_blocks)
            elif not self._self_draft:
                self._proposer = NGramProposer(self.spec_tokens,
                                               max_n=self.ngram_max,
                                               min_n=self.ngram_min)

        # ----- tiered KV: the host-DRAM arena below the device pool
        # (module docstring).  Built from the live swap tree's per-block
        # leaf shapes — the target pool, plus the draft pool when one
        # exists (drafts read their own KV through the shared tables, so
        # a promoted block must restore BOTH pools' bytes or speculative
        # acceptance would collapse to zero after every swap).  Quantized
        # records contribute their codes and scale rows as separate
        # leaves, so they demote/promote in lockstep by construction.
        self._host: Optional[HostBlockStore] = None
        self._demote_fn = None
        self._promote_fn = None
        self._staged: Dict[Any, Dict[str, Any]] = {}
        self._prefetch_gate: Dict[Any, tuple] = {}
        # prefill-role replicas park finished-prefill requests here (KV
        # demoted, slot released) until the router pumps take_handoffs()
        self._handoff_ready: List[_PendingItem] = []
        self._staging_shardings = None
        self._nvme: Optional[NvmeBlockStore] = None
        self.nvme_path: Optional[str] = None
        self._nvme_owns_path = False
        self._nvme_spills_seen = 0       # store-counter deltas already
        self._nvme_loads_seen = 0        # mirrored into the registry
        self._nvme_rejects_seen = 0
        if self.host_blocks:
            specs = [(tuple(l.shape[:1]) + tuple(l.shape[2:]), l.dtype)
                     for l in jax.tree_util.tree_leaves(self._swap_pools())]
            if self.nvme_blocks:
                # NVMe third tier below the arena: spill file at nvme_path
                # (auto-minted tempfile when unset — the engine owns and
                # unlinks it at close); entries keep chain_key + checksum
                # and every NVMe exit re-verifies before bytes re-enter
                # the arena (paged.py NvmeBlockStore)
                path = self._nvme_path_arg
                if path is None:
                    fd, path = tempfile.mkstemp(
                        prefix="ds_kv_spill_", suffix=".bin")
                    os.close(fd)
                    self._nvme_owns_path = True
                self.nvme_path = str(path)
                self._nvme = NvmeBlockStore(
                    self.nvme_blocks, specs, self.nvme_path)
                self._host = HostBlockStore(
                    self.host_blocks, specs, nvme=self._nvme,
                    nvme_watermark=self.nvme_high_watermark)
            else:
                self._host = HostBlockStore(self.host_blocks, specs)
            # per-leaf device_put specs are fixed for the engine's life
            self._staging_shardings = self._swap_leaf_shardings()

        # ----- telemetry (telemetry/): scheduler counters and latency
        # distributions live in the metrics registry — stats() is a view
        # over it (Prometheus text / JSON snapshot come for free), and the
        # legacy counter attributes below are read-only properties.  The
        # TTFT/TPOT histograms are fixed-bucket streaming: bounded memory
        # for a serve session of any length (the old raw-sample lists grew
        # forever and re-sorted on every stats() call); _latencies keeps a
        # small deque of recent per-request records as a debug view.
        m = self.metrics = MetricsRegistry()
        # what the process builds and its compile cache answers belongs to
        # no engine: the process's registry rides in this one's exposition
        m.include(process_registry())
        self._c_iterations = m.counter(
            "serving_iterations_total", "scheduler iterations run")
        self._c_decode_steps = m.counter(
            "serving_decode_steps_total", "single-token decode steps")
        self._c_prefill_calls = m.counter(
            "serving_prefill_calls_total", "prefill program invocations")
        self._c_prefill_shapes = {
            rung: m.counter(
                "serving_prefill_calls_by_shape_total",
                "prefill program invocations, by the call's rows x width",
                shape=self._rung_name(rung))
            for rung in self._rungs}
        self._c_prefill_turns = m.counter(
            "serving_prefill_turns_total",
            "prefill calls whose group had more ready rows than the call's "
            "shape holds: the others waited their turn")
        self._c_prefill_tokens = m.counter(
            "serving_prefill_call_tokens_total",
            "real prompt tokens the prefill calls advanced their rows by")
        self._c_prefill_budget = m.counter(
            "serving_prefill_call_budget_tokens_total",
            "tokens the prefill calls had room for (rows x width, summed)")
        self._c_moe_rows = m.counter(
            "serving_moe_expert_rows_total",
            "(token, expert) rows routed by decode and prefill programs, "
            "summed over layers (0 for a dense model)")
        self._c_moe_touched = m.counter(
            "serving_moe_experts_touched_total",
            "experts that received at least one row, summed over layers "
            "and program calls (0 for a dense model)")
        self._c_admitted = m.counter(
            "serving_requests_admitted_total", "requests admitted to slots")
        self._c_preempted = m.counter(
            "serving_requests_preempted_total",
            "sequences preempted under block pressure")
        self._c_prompt_tokens = m.counter(
            "serving_prompt_tokens_total", "prompt tokens admitted")
        self._c_prefix_hit_tokens = m.counter(
            "serving_prefix_hit_tokens_total",
            "prompt tokens served from the prefix cache")
        self._c_spec_rounds = m.counter(
            "serving_spec_rounds_total", "speculative draft-verify rounds")
        self._c_drafted = m.counter(
            "serving_spec_drafted_tokens_total", "draft tokens proposed")
        self._c_accepted = m.counter(
            "serving_spec_accepted_tokens_total", "draft tokens accepted")
        self._c_spec_rejected = m.counter(
            "serving_spec_draft_rejected_total",
            "draft tokens the verifier rejected (rejection sampler or "
            "greedy mismatch)")
        self._c_round_rows = m.counter(
            "serving_spec_round_rows_total",
            "decoding rows of self-drafting rounds")
        self._c_round_tokens = m.counter(
            "serving_spec_round_tokens_total",
            "tokens self-drafting rounds committed")
        self._c_sampled = {
            mode: m.counter("serving_sampled_requests_total",
                            "submitted requests by sampling mode",
                            mode=mode)
            for mode in ("greedy", "sampled", "constrained")}
        self._h_accept_ratio = m.histogram(
            "serving_spec_accept_ratio",
            buckets=(0.0, 0.25, 0.5, 0.75, 1.0),
            help="per-round fraction of drafted tokens accepted")
        self._c_finished = m.counter(
            "serving_requests_finished_total", "requests run to completion")
        self._c_cancelled = m.counter(
            "serving_requests_cancelled_total",
            "requests cancelled before completion (queued or active)")
        self._c_gen_tokens = m.counter(
            "serving_generated_tokens_total",
            "tokens committed across all requests (prefill first tokens, "
            "decode steps, accepted speculative drafts)")
        self._g_queue_depth = m.gauge(
            "serving_queue_depth", "requests waiting for a slot")
        self._c_invariant_checks = m.counter(
            "serving_invariant_checks_total",
            "paged-state audits run (analysis/invariants.py)")
        self._c_step_stalls = {
            cause: m.counter(
                "serving_step_stalls_total",
                "scheduler steps over 3x the running median of their "
                "shape, by what explains most of the excess",
                cause=cause)
            for cause in STALL_CAUSES}
        self._c_calls = m.counter(
            "serving_calls_total",
            "decode and prefill calls put on the device's queue")
        self._c_calls_ahead = m.counter(
            "serving_calls_ahead_total",
            "of them, put there while the call before was still in flight")
        self._c_early_settles = {
            cause: m.counter(
                "serving_early_settles_total",
                "calls whose results were taken before the next call was "
                "enqueued, by what needed them",
                cause=cause)
            for cause in EARLY_SETTLE_CAUSES}
        # tiered-KV swap traffic (zero-valued, never incremented when the
        # tier is off — the cells exist so dashboards see a stable schema)
        self._c_swap_out = m.counter(
            "serving_kv_swaps_total",
            "KV blocks swapped between the device pool and the host tier",
            direction="out", tier="host")
        self._c_swap_in = m.counter(
            "serving_kv_swaps_total",
            "KV blocks swapped between the device pool and the host tier",
            direction="in", tier="host")
        self._c_swap_bytes = m.counter(
            "serving_swap_bytes_total",
            "bytes moved over the device<->host KV tier (both directions)",
            tier="host")
        # NVMe third-tier traffic (tier="nvme"): host-arena spills past the
        # watermark and verified promotions back — synced by delta from the
        # NvmeBlockStore counters at every swap commit point
        self._c_nvme_out = m.counter(
            "serving_kv_swaps_total",
            "KV blocks swapped between the device pool and the host tier",
            direction="out", tier="nvme")
        self._c_nvme_in = m.counter(
            "serving_kv_swaps_total",
            "KV blocks swapped between the device pool and the host tier",
            direction="in", tier="nvme")
        self._c_nvme_bytes = m.counter(
            "serving_swap_bytes_total",
            "bytes moved over the device<->host KV tier (both directions)",
            tier="nvme")
        self._g_nvme_in_use = m.gauge(
            "serving_nvme_blocks_in_use",
            "KV blocks currently resident in the NVMe spill file")
        self._c_handoffs = m.counter(
            "serving_handoffs_total",
            "prefill->decode handoffs extracted from a prefill-role "
            "replica's scheduler")
        self._c_prefetch_miss = m.counter(
            "serving_prefetch_misses_total",
            "promotions that had to stage synchronously at admission "
            "(no prefetch was in flight for the chain)")
        self._c_resume_recompute = m.counter(
            "serving_resume_recompute_tokens_total",
            "prompt tokens re-prefilled when admitting a preemption resume "
            "(near zero with the host tier: demoted state promotes back)")
        self._c_checksum_fail = m.counter(
            "serving_checksum_failures_total",
            "host-tier KV blocks rejected by the integrity checksum "
            "(corrupt bytes dropped and recomputed, never served)")
        self._h_prefetch_wait = m.histogram(
            "serving_prefetch_wait_seconds",
            help="time admission blocked on an in-flight promotion "
                 "(0-bucket = the H2D transfer fully overlapped decode)")
        self._g_host_blocks_in_use = m.gauge(
            "serving_host_blocks_in_use",
            "host-tier arena slots holding demoted KV blocks")
        # long-context lane (zero-valued when sp == 1 and
        # resident_window_blocks == 0 — stable dashboard schema)
        self._c_window_slides = m.counter(
            "serving_context_window_slides_total",
            "resident-window slides: cold context block runs demoted to "
            "the host tier and masked out of decode attention")
        self._c_sp_a2a_bytes = m.counter(
            "serving_sp_alltoall_bytes_total",
            "cross-rank bytes moved by the Ulysses all-to-all pair "
            "during sequence-parallel prefill (analytic, host-computed)")
        self._h_ttft = m.histogram(
            "serving_ttft_seconds", help="per-request time to first token")
        self._h_tpot = m.histogram(
            "serving_tpot_seconds",
            help="per-request time per output token (decode cadence)")
        self._g_blocks_in_use = m.gauge(
            "serving_blocks_in_use", "physical KV blocks referenced")
        self._g_free_blocks = m.gauge(
            "serving_free_blocks", "physical KV blocks on the free list")
        # SLO attainment accounting (telemetry/slo.py): every finished
        # request lands in its class's TTFT/TPOT histograms + attainment
        # counters on THIS registry; slo_report() is the per-class view
        self._slo = SLOTracker(m, o.slo_targets)
        self.peak_flops = o.peak_flops
        self._flops_profiler = None        # built lazily by flops_report()
        #: raw (un-sentry-wrapped) program bodies + shape meta, captured
        #: at build time for the FLOPs profiler — lowering a RAW body for
        #: cost_analysis never ticks the sentry and never compiles
        #: (telemetry/flops.py).
        self._program_bodies: Dict[str, Any] = {}
        self._program_meta: Dict[str, Any] = {}
        #: per program, where its small per-call operands lie in the ONE
        #: host buffer a call carries (inference/operands.py) — made with
        #: the program, never inside a step
        self._layouts: Dict[str, OperandLayout] = {}
        #: router-noted flow ids (uid -> Chrome flow id): admission emits
        #: the matching flow-finish so the merged fleet trace draws the
        #: route -> admit arrow (telemetry/trace.py flow events)
        self._flow_ids: Dict[Any, int] = {}
        self.timeline = TraceTimeline(capacity=o.trace_capacity)
        # readable after this engine is gone (telemetry/trace.py kept())
        trace_mod.keep("serve", self.timeline)
        #: what finds this engine's compiled programs again
        #: (telemetry/programs.py): their scope tables, on demand
        self.programs = Programs()
        trace_mod.keep("programs", self.programs)
        #: the argument dict of the ``step`` span being recorded: what
        #: accrues per step (``kv_s``, ``flight_s``, ``flight_cpu_s``)
        #: lands here; outside a step (and with the ring off), on a dict
        #: nobody reads
        self._no_step = {"kv_s": 0.0, "flight_s": 0.0, "flight_cpu_s": 0.0}
        self._step_args: Dict[str, Any] = self._no_step
        #: the argument dict of the host phase (``step.prefill`` /
        #: ``step.decode``) being recorded: where the runners' ``plan`` /
        #: ``upload`` / ``commit`` segments land
        self._phase: Dict[str, Any] = {}
        #: argument dicts of this step's phase and in-flight spans, for
        #: the stall check's per-segment sums
        self._seg_args: List[Dict[str, Any]] = []
        #: the call on the device's queue whose results the host has not
        #: taken (:meth:`_launch` / :meth:`_settle`): at most ONE
        self._flight: Optional[_Flight] = None
        #: while the host is inside the runtime for a call (handing one
        #: over, waiting for one's results): what :meth:`_enter_runtime`
        #: read as it went in
        self._runtime = None
        #: whether this step has put a call on the device's queue
        self._launched = False
        #: per step shape (with / without a prefill group): the last
        #: ``STALL_HISTORY`` steps (``_note_step``'s rows) and the duration
        #: over which a step of that shape is a stall (None: too few yet)
        self._prefilled = False
        self._step_history = {shape: deque(maxlen=STALL_HISTORY)
                              for shape in (False, True)}
        self._stall_over: Dict[bool, Optional[float]] = {False: None,
                                                         True: None}
        self._stall_seen = {False: 0, True: 0}
        self._stall_warned = float("-inf")
        #: blocks evicted from the prefix trie since the last
        #: ``evict_block`` instant (one a host phase that evicted)
        self._evicted_blocks = 0
        #: collector pauses inside this engine's steps (``step.gc_s``)
        self._gc = trace_mod.GcWatch(self.timeline)
        self._closed = False
        if self.timeline.enabled:
            self._gc.install()
            # bounded lane table: one span lane per SLOT (a request's span
            # lands on the slot that finished it) — lane count never grows
            # with traffic, unlike per-uid lanes
            for s in range(self.slots):
                self.timeline.thread(f"slot {s}")
        self.sentry.on_trace = self._emit_trace_event
        self._latencies = deque(maxlen=256)  # recent finished requests
        self._trace_times: Dict[Any, Dict[str, Any]] = {}
        self._admit_seq = 0
        self._blocked_gate = None          # (head id, resume len, version)
        # ----- incremental scheduler state (module docstring "Incremental
        # serving API"): the pending queue and active slot map live on the
        # engine so submit()/step()/cancel()/drain() can drive the same
        # scheduler serve() wraps
        self._pending = _PendingQueue()
        self._active: Dict[int, _SlotState] = {}
        self._live_uids: set = set()       # pending + active uids, O(1)
        self._cancel_flags: set = set()    # active-slot cancels, applied at
        self._admission_log = None         # the next iteration boundary
        self._step_log = None
        #: trace-capture hook (autotuning/trace.py TraceRecorder): called
        #: once per successful submit() with the request and its
        #: submit-time knobs, BEFORE any slo_class -> priority mapping
        self._submit_observer = None
        #: fault-injection hook (serving/faults.py): a bound replica view
        #: armed by arm_faults(); None = zero cost (one predicate at each
        #: injection point, nothing else changes)
        self._fault_injector = None
        #: bounded deterministic retry/backoff for the engine-internal
        #: swap transport (demote/promote) under an armed fault plan —
        #: attributes, not ctor knobs, so resolved_config() stays stable
        self._transport_retries = 2
        self._transport_backoff_s = 0.0
        log_dist(
            f"ServingEngine: slots={self.slots}, cache_len="
            f"{self._cache_len}, block_size={self.block_size}, "
            f"num_blocks={num_blocks}, "
            f"chunked prefill (chunk={self.prefill_chunk}, prefix_cache="
            f"{self._prefix is not None})"
            + f", prefill_batch={self.prefill_batch}, prefill calls "
            + " / ".join(map(self._rung_name, self._rungs))
            + (f" (no wider rows: {self._ladder_stop})"
               if self._ladder_stop else "")
            + (f", speculative K={self.spec_tokens} "
               f"({'draft ' + self._draft.module.name if self._draft else 'its own module' if self._self_draft else 'n-gram'})"
               if self.spec_tokens else "")
            + (f", engine_mode=dp_tp (dp={self.dp_degree} groups)"
               if self.engine_mode == "dp_tp" else "")
            + (f", kv sharded over tp={self.tp_degree} "
               f"({hkv // self.tp_degree} heads/chip)" if self.kv_sharded
               else (f", kv replicated (tp={self.tp_degree})"
                     if self.tp_degree > 1 else ""))
            + (f", quantize={self.quantize}" if self.quantize else "")
            + (f", tiered KV (host_blocks={self.host_blocks}, "
               f"{self._host.arena_bytes / 1e6:.1f}MB host arena, "
               f"swap_batch={self.swap_batch})" if self._host else "")
            + (f", nvme tier (nvme_blocks={self.nvme_blocks}, watermark="
               f"{self.nvme_high_watermark}, {self.nvme_path})"
               if self._nvme is not None else "")
            + (f", role={self.role}" if self.role != "both" else "")
            + (f", sp={self.sp_degree} (Ulysses prefill)"
               if self.sp_degree > 1 else "")
            + (f", resident window={self.resident_window_blocks} blocks "
               f"(+{self._landmark_blocks} landmark)"
               if self.resident_window_blocks else ""),
            ranks=[0])

    def close(self) -> None:
        """Release host-side tier resources: join the NVMe aio handle and
        unlink an auto-minted spill file (a caller-provided ``nvme_path``
        is the caller's to keep).  Idempotent; the device pool and
        compiled programs are garbage-collected as usual."""
        self._settle("close")
        self._gc.remove()
        tl = self.timeline
        if tl.dropped and not self._closed:
            span_s = max(tl.now_us() * 1e-6, 1e-9)
            logger.warning(
                f"ServingEngine: the trace ring (trace_capacity="
                f"{tl.capacity}) wrapped: {tl.dropped} of {tl.emitted} "
                f"events dropped at {tl.emitted / span_s:.0f} events/s "
                f"over {span_s:.0f} s — raise trace_capacity to hold a "
                "longer window")
        self._closed = True
        nvme, self._nvme = self._nvme, None
        if nvme is not None:
            nvme.close()
            if self._nvme_owns_path and self.nvme_path:
                try:
                    os.unlink(self.nvme_path)
                except OSError:  # graft: noqa(GL013) best-effort cleanup of our own temp spill file
                    pass

    def __del__(self):
        try:
            self.close()
        except Exception:  # graft: noqa(GL013) __del__ during interpreter teardown — nothing left to tell
            pass

    def _tp_ctx(self):
        """Context every compiled-fn invocation runs under: tracing happens
        inside the call, so the paged device ops (``ops/paged_kv.py``,
        ``ops/decode_attention.py``) bake THIS engine's mesh — or none —
        into the program, even when engines of different tp degrees coexist
        in one process."""
        return paged_kv.tp_context(
            self.engine.mesh if self.kv_sharded else None)

    def _sp_ctx(self):
        """Sequence-parallel tracing context (``ops/sp_attention``):
        prefill invocations — and ONLY prefill invocations — enter it, so
        the T > 1 paged-attention dispatch sees the sp mesh and reshapes
        through the Ulysses all-to-all, while decode/verify programs
        (T <= VERIFY_T_MAX windows with nothing to shard) trace with the
        hook dormant."""
        if self.sp_degree > 1:
            return sp_attention.sp_context(self.engine.mesh)
        return contextlib.nullcontext()

    def _prefill_ctx(self):
        """:meth:`_tp_ctx` plus, for a prefill that is not fused with a
        draft's, :meth:`_sp_ctx`."""
        stack = contextlib.ExitStack()
        stack.enter_context(self._tp_ctx())
        if self._draft is None:
            stack.enter_context(self._sp_ctx())
        return stack

    def _decode_ctx(self):
        """:meth:`_tp_ctx` plus the dp grouping for ``engine_mode='dp_tp'``:
        the paged ops additionally shard batch rows and the physical-block
        dim over the mesh ``dp`` axis, localizing each shard's block-table
        reads into its own contiguous pool chunk (``ops/paged_kv.py
        dp_context``)."""
        if self.dp_degree > 1:
            stack = contextlib.ExitStack()
            stack.enter_context(self._tp_ctx())
            stack.enter_context(paged_kv.dp_context(
                self.engine.mesh, self.dp_degree,
                self._alloc.num_blocks // self.dp_degree))
            return stack
        return self._tp_ctx()

    # -------------------------------------------------------------- telemetry
    # Legacy counter attributes are read-only views over the registry cells
    # (internal code increments the cells; tests and callers keep reading
    # srv.iterations / srv.preempted / ... unchanged).
    @property
    def iterations(self) -> int:
        return int(self._c_iterations.value)

    @property
    def decode_steps(self) -> int:
        """Plain single-token decode calls committed: each emits at most
        ONE token a slot, so "tokens emitted over ``decode_steps x slots``"
        (a decode batch's occupancy, as the benchmark's ``decode_occupancy``
        reads it) is a share of 100 %.  A speculative engine makes none:
        its rounds count in :attr:`spec_rounds`, and a round emits 1 ..
        ``spec_tokens + 1`` tokens a row — over ``rounds x slots`` that
        passes 100 % once a draft is accepted, so the per-round measure is
        ``stats()["tokens_per_round"]`` (tokens, not a share)."""
        return int(self._c_decode_steps.value)

    @property
    def prefill_calls(self) -> int:
        return int(self._c_prefill_calls.value)

    @property
    def admitted(self) -> int:
        return int(self._c_admitted.value)

    @property
    def preempted(self) -> int:
        return int(self._c_preempted.value)

    @property
    def prompt_tokens(self) -> int:
        return int(self._c_prompt_tokens.value)

    @property
    def prefix_hit_tokens(self) -> int:
        return int(self._c_prefix_hit_tokens.value)

    @property
    def spec_rounds(self) -> int:
        return int(self._c_spec_rounds.value)

    @property
    def drafted_tokens(self) -> int:
        return int(self._c_drafted.value)

    @property
    def accepted_tokens(self) -> int:
        return int(self._c_accepted.value)

    @property
    def invariant_checks_run(self) -> int:
        return int(self._c_invariant_checks.value)

    def _emit_trace_event(self, entry) -> None:
        """Sentry trace callback (``analysis/sentry.py``): every (re)trace
        of a registered jitted body lands on the timeline — a ``retrace``
        event is contract drift made visible next to the scheduler events
        that triggered it."""
        over = entry.budget is not None and entry.traces > entry.budget
        self.timeline.instant("retrace" if over else "jit_trace",
                              entry=entry.name, traces=entry.traces)

    def dump_trace(self, path: str) -> str:
        """Write the per-request trace timeline as Chrome ``trace_event``
        JSON (open at https://ui.perfetto.dev); returns ``path``.  The
        ring holds the most recent ``trace_capacity`` events —
        ``stats()['trace_events_dropped']`` says how much history fell
        off."""
        return self.timeline.dump(
            path, process_name=f"serving:{self.engine.module.name}")

    # ------------------------------------------------------------ compiled fns
    def _first_call(self, fn, program: str, holder, key, **sizes):
        """Jitted ``fn`` under the name the sentry registered, its FIRST
        call a ``build`` span of the start-up ring with ``sizes``
        (``telemetry/trace.py FirstCall``).  That call over, the bare
        function takes the wrapper's place, ``holder[key]``, and no later
        call passes through it; the call that builds the LAST of a plain
        or speculative engine's programs (``decode`` / ``verify``) also
        logs the start-up line."""
        def built(bare):
            holder[key] = bare
            if program in ("decode", "draft" if self._self_draft
                           else "verify"):
                log_dist(trace_mod.setup_line(), ranks=[0])

        return trace_mod.FirstCall(fn, program, built,
                                   programs=self.programs, **sizes)

    def program_table(self, name: str) -> Dict[str, Any]:
        """The scope table of the compiled program ``name`` (``decode``,
        ``prefill[4x128]``, ``verify``, ``draft``: a key of
        ``self.programs.records``, there from the program's first call) —
        per instruction of its schedule and per scope and pass: bytes,
        matmul flops, kernels, trips (``telemetry/hlo_text.py
        scope_table``).  Built at the first demand from the executable that
        is running: no trace, no compile (``backend_compiles`` 0)."""
        ctx = self._decode_ctx if name == "decode" else \
            self._prefill_ctx if name.startswith("prefill") else self._tp_ctx
        with ctx():
            return self.programs.table(name)

    def note_flow(self, uid, flow_id: int) -> None:
        """Register a Chrome flow id for a routed request: admission will
        emit the matching flow-finish (``f``) event, linking the router's
        ``route`` flow-start to this replica's admission in the merged
        fleet trace (``telemetry/aggregate.merge_chrome_traces``).  The
        caller (the :class:`~deepspeed_tpu.serving.ReplicaRouter`) owns
        flow-id uniqueness across every ring that will be merged."""
        self._flow_ids[uid] = int(flow_id)

    def slo_report(self) -> Dict[str, Dict[str, Any]]:
        """Per-``slo_class`` attainment report (``telemetry/slo.py``):
        requests, TTFT/TPOT attainment against the configured targets,
        merged percentiles, and error-budget burn rates."""
        return self._slo.report()

    def flops_report(self, peak_flops: Optional[float] = None,
                     window_s: Optional[float] = None) -> Dict[str, Any]:
        """FLOPs/MFU snapshot (``telemetry/flops.py``): per-program FLOPs
        from XLA cost analysis (analytic fallback), cumulative
        ``serving_model_flops_total``, the MFU gauge against
        ``peak_flops`` (defaults to the constructor's), and the
        prefill/decode/swap/idle busy-fraction breakdown from the
        timeline.  Profiling lowers raw program bodies only — it never
        compiles and never ticks the recompile sentry."""
        if self._flops_profiler is None:
            from ..telemetry.flops import ServingFlopsProfiler

            self._flops_profiler = ServingFlopsProfiler(
                self, peak_flops=self.peak_flops)
        return self._flops_profiler.report(peak_flops=peak_flops,
                                           window_s=window_s)

    @property
    def compile_count(self) -> int:
        return len(self.compiled_programs)

    @staticmethod
    def _rung_name(rung) -> str:
        """``"<rows>x<width>"``: a prefill program's shape as the sentry,
        the ``prefill`` spans and ``stats()["prefill_shapes"]`` name it."""
        return f"{rung[0]}x{rung[1]}"

    def _refuse_rung(self, engine, width: int) -> Optional[str]:
        """Why no prefill program of this engine has rows ``width`` wide
        (None: one has), from what the constructor can see: the shapes,
        never a family's name."""
        if width > self._cache_len:
            return f"a row of {width} tokens passes the cache " \
                f"({self._cache_len})"
        if self.resident_window_blocks:
            return "a resident window slides once a call, so a call's " \
                "width is part of what its queries see"
        if self._latent or not self._paged:
            # the latent kernel tiles its own queries; a chunked recurrence
            # takes any whole number of its chunks
            return None
        dtype = engine._config.jnp_dtype
        kinds = {"window_blocks": 2} if self._windows else \
            {"state_rows": 1} if self._rowed else {}
        leaf = self._widest_leaf(lambda: self._init_cache(
            2, self.block_size, dtype, **kinds))
        hkv, bs, hd = map(int, leaf.shape[2:])
        heads = int(getattr(engine.module.model_config, "num_heads", hkv))
        if not decode_attention.prefill_row_fits(
                heads, hkv, bs, hd, leaf.dtype.itemsize, width, self._nbper):
            return f"the prefill kernel's plan takes no {heads // hkv} x " \
                f"{width} query rows a KV head"
        return None

    def _window_block(self, dtype) -> int:
        """Tokens a block of the window kind's leaves holds: the full
        kind's, unless the hook names that kind's leaves (``"leaves"``: a
        kind whose token is of another width has blocks of its own bytes) —
        then what the cache tree's own shapes say."""
        names = self._windows.get("leaves")
        if not names:
            return self.block_size
        shapes = jax.eval_shape(lambda: self._init_cache(
            2, self.block_size, dtype, window_blocks=2))
        return int(shapes[names[0]].shape[3])

    @classmethod
    def _widest_leaf(cls, mk_pool):
        """The hook's (logical) K or V leaf ``[L, NB, HKV, bs, hd]`` of the
        pool ``mk_pool`` would build, as a shape: the widest payload (a
        sparse-attention family's third leaf, one narrow head of indexer
        keys, is smaller)."""
        return max((paged_kv.pool_payload(leaf)
                    for leaf in jax.tree_util.tree_leaves(
                        cls._paged_leaves(jax.eval_shape(mk_pool)),
                        is_leaf=paged_kv.is_quantized_pool)),
                   key=lambda leaf: leaf.size)

    @staticmethod
    def _paged_leaves(cache):
        """``cache`` without the state kind's leaves: what has blocks."""
        if not isinstance(cache, dict):
            return cache
        return {k: v for k, v in cache.items() if k not in ROW_LEAVES}

    def _donate(self):
        # donating the pool avoids a full cache copy per step; XLA:CPU
        # ignores donation with a warning, so only ask for it on TPU
        return (1,) if on_tpu() else ()

    @staticmethod
    def _commit_pool(mk_pool, sharding, **sizes):
        """Build a pool on its sharding, lane-packed (``ops/paged_kv.py``
        "Layout": the same bytes as the hook's ``[L, NB, HKV, bs, hd]``,
        in the view whose TPU layout the paged kernels read) — in one
        jitted program, so neither an unpacked nor an unsharded copy of it
        ever exists.  A ``pool`` span of the start-up ring, until the pool
        is there: ``sizes`` (``pool``, ``blocks``) and its bytes, in all
        and by kind where it has kinds."""
        def packed():
            cache = mk_pool()
            if not isinstance(cache, dict):
                return paged_kv.pack_pool(cache)
            # (a row-indexed leaf has no block to pack)
            return {k: v if k in ROW_LEAVES else paged_kv.pack_pool(v)
                    for k, v in cache.items()}

        with trace_mod.setup_timeline().span("pool", **sizes) as made:
            pool = jax.block_until_ready(
                jax.jit(packed, out_shardings=sharding)())

            def nbytes(tree):
                return sum(int(x.nbytes)
                           for x in jax.tree_util.tree_leaves(tree))

            made["bytes"] = nbytes(pool)
            if isinstance(pool, dict):
                made["kinds"] = {k: nbytes(v) for k, v in pool.items()}
        return pool

    def _constrain_pool(self, cache):
        """dp_tp only: pin the cache OUTPUT of every decode/prefill program
        to the committed pool sharding.  Input shardings are part of the
        jit cache key — without this, a prefill that resharded the pool
        would hand the next decode a differently-placed argument and force
        a silent retrace the sentry would (rightly) flag."""
        if self.dp_degree <= 1:
            return cache
        sharding = self._pool_sharding
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, sharding), cache)

    def _pin_tokens(self, tokens):
        """Traced, on a mesh: the device-resident token vector leaves every
        program as the engine committed it (replicated) — an input's
        sharding is part of the jit cache key, as for the pool
        (:meth:`_constrain_pool`)."""
        if self.engine.mesh.size == 1:
            return tokens
        return jax.lax.with_sharding_constraint(
            tokens, NamedSharding(self.engine.mesh, P()))

    def _forward(self, *args, **kwargs):
        """Traced: the model's cached forward as ``(logits, cache,
        record)`` — ``record`` the int32 ``[L, 3]`` routing record of an
        expert family (decode hook ``routing_record``; with learned sparse
        attention the pair of that and the selections' int32 counts), else
        None."""
        if self._routing:
            return self._fwd(*args, routing=True, **kwargs)
        return (*self._fwd(*args, **kwargs), None)

    def _forward_hidden(self, *args, **kwargs):
        """Traced: :meth:`_forward` that also hands back the final norm's
        output at every window position (``hidden=True``): ``(logits,
        cache, record, hidden)``."""
        if self._routing:
            return self._fwd(*args, routing=True, hidden=True, **kwargs)
        logits, cache, hidden = self._fwd(*args, hidden=True, **kwargs)
        return logits, cache, None, hidden

    def _draft_forward(self, record, *args, **kwargs):
        """Traced: the model's own drafting module (hook ``self_draft``)
        as ``(logits, cache, record)`` — ``record`` the trunk's
        (:meth:`_forward`) with the module's behind it: one more layer's
        row of the routing record, its selection's counts added."""
        fwd = self._self_draft["forward"]
        if record is None:
            return (*fwd(*args, **kwargs), None)
        logits, cache, more = fwd(*args, routing=True, **kwargs)
        if self._sparse:
            record = (jnp.concatenate([record[0], more[0][None]]),
                      record[1] + more[1])
        else:
            record = jnp.concatenate([record, more[0][None]])
        return logits, cache, record

    def _keep_device(self, out) -> None:
        """What a serving program leaves on the device, from its results
        ``(flat, cache[, draft cache], token vector[, draft vector[, count
        vector]])`` — the last a self-drafting round's alone."""
        self._cache = out[1]
        if self._self_draft:
            self._devtok, self._devdraft = out[2], out[3]
            if len(out) == 5:
                self._devcount = out[4]
            return
        self._devtok = out[-1]
        if len(out) == 4:                  # the prefill fused with a draft's
            self._dcache = out[2]

    @staticmethod
    def _with_record(tokens, record):
        """Traced: the routing record (and the selections' counts) ride
        behind the tokens in the ONE int32 array a step copies back — no
        second transfer."""
        if record is None:
            return tokens
        return jnp.concatenate(
            [tokens.reshape(-1)] + [r.reshape(-1).astype(tokens.dtype)
                                    for r in jax.tree_util.tree_leaves(
                                        record)])

    def _split_record(self, flat, shape, span_args):
        """Host: undo :meth:`_with_record` on the copied-back array; the
        step's routing goes on its in-flight span (``experts_touched`` and
        ``expert_rows`` summed over layers, ``expert_rows_max`` the largest
        group of any layer, ``expert_rows_max_sum`` the layers' largest
        groups summed: what their grouped matmuls wait for) and into the
        totals, and so do the counts of a
        learned sparse attention, as the DEVICE made them
        (``ops/sparse_index_attention.COUNTS``: ``index_keys`` scored,
        ``kv_selected`` attended, ``kv_valid`` a dense read attends,
        ``sparse_rows`` past ``topk``, ``kv_read`` K/V rows fetched; one
        layer's worth — the program's sum over its layers, divided)."""
        if not self._routing:
            return flat
        n = int(np.prod(shape))
        tail = flat[n:]
        if self._sparse:
            COUNTS = sparse_index_attention.COUNTS
            layers = int(self._pool_shape[0])
            counts = {k: int(v) // layers
                      for k, v in zip(COUNTS, tail[-len(COUNTS):])}
            tail = tail[:-len(COUNTS)]
            span_args.update(counts)
            for key, v in counts.items():
                self._sparse_totals[key] += v
        rec = tail.reshape(-1, self._rec_width)
        touched, rows = int(rec[:, 0].sum()), int(rec[:, 1].sum())
        span_args.update(experts_touched=touched, expert_rows=rows,
                         expert_rows_max=int(rec[:, 2].max()),
                         expert_rows_max_sum=int(rec[:, 2].sum()))
        if self._rec_width > 3:
            span_args["expert_rows_absent"] = int(rec[:, 3].sum())
            self._rows_absent += span_args["expert_rows_absent"]
        self._c_moe_touched.inc(touched)
        self._c_moe_rows.inc(rows)
        return flat[:n].reshape(shape)

    def _note_sparse(self, program: str) -> None:
        """Trace time: which selection ``program``'s learned sparse
        attention was built with (``stats()["sparse_attn"]``)."""
        if self._sparse:
            self._program_meta.setdefault("sparse_attn", {})[program] = \
                sparse_index_attention.took()

    def _note_latent(self, program: str, paths) -> None:
        """Trace time: which read ``program``'s latent attention was built
        with (``stats()["kv_latent"]["latent_attn"]``): a
        ``paged_latent_*`` kernel on a TPU, ``"latent_gather"`` on a CPU."""
        if self._latent:
            self._program_meta.setdefault("latent_attn", {})[program] = \
                "+".join(sorted(p for p in paths if not self._state_body(p)))
        self._note_state(program, paths)

    def _state_body(self, path: str) -> bool:
        """Whether ``path`` (an ``ops/decode_attention.dispatch_log`` name)
        is a body of this model's state kind: the hook's ``bodies`` is the
        prefix its family's recurrence goes by."""
        return bool(self._state) \
            and path.startswith(self._state["bodies"] + "_")

    def _note_state(self, program: str, paths) -> None:
        """Trace time: which body ``program``'s state-kind layers lowered to
        (``stats()["kv_state"][<bodies>]``, the hook's ``bodies``: ``"kda"``
        a gated delta rule, ``"ssd"`` a state-space scan): the Pallas kernels
        ``<bodies>_step`` / ``<bodies>_chunk_state`` on a TPU,
        ``<bodies>_*_plain`` on a CPU."""
        if self._state:
            self._program_meta.setdefault("state_bodies", {})[program] = \
                "+".join(sorted(p for p in paths if self._state_body(p)))

    def _note_sampler(self, program: str, samp, logits) -> None:
        """Trace time: how ``program`` picks its tokens from ``logits``
        (``stats()["sampler"]``) — ``"argmax"`` for a greedy-only engine,
        else how ``ops/sampling.py`` finds the filter's thresholds at that
        vocabulary width (``"bitwise_search"``, or
        ``"bitwise_search_tiled"`` from ``sampling.TILED_FROM`` entries)."""
        self._program_meta.setdefault("sampler", {})[program] = \
            "argmax" if samp is None \
            else sampling_ops.thresholds(logits.shape[-1])

    def _sampler_rows(self, slots) -> Dict[str, int]:
        """Span counters of a dispatch over ``slots``: ``sampled_rows``
        (temperature > 0) and, of those, ``filtered_rows`` (``top_k > 0``
        or ``top_p < 1``: the rows the sampler's threshold searches run
        for), from the knob vectors the dispatch uploads."""
        sampled = self._temps[slots] > 0
        filtered = sampled & ((self._topks[slots] > 0)
                              | (self._topps[slots] < 1))
        return {"sampled_rows": int(sampled.sum()),
                "filtered_rows": int(filtered.sum())}

    def _next_tokens(self, logits, samp):
        """The per-row token rule shared by every program body: argmax for
        a greedy-only engine; otherwise a per-row ``where(temp > 0)``
        select between the on-device sampler (``ops/sampling.py``,
        counter-keyed by the row's seed + emitted count) and the SAME
        masked argmax — so one traced program serves mixed
        greedy+sampled+constrained batches with zero recompiles and the
        temp=0 rows stay bit-identical to the legacy greedy path."""
        with jax.named_scope("sample"):
            if samp is None:
                with jax.named_scope("sample/argmax"):
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            temps, topks, topps, seeds, counts, masks = samp
            greedy, lp = sampling_ops.filtered_logprobs(
                logits, temps, topks, topps, masks)
            keys = sampling_ops.slot_keys(seeds, counts,
                                          sampling_ops.SALT_TOKEN)
            return jnp.where(temps > 0,
                             sampling_ops.sample_tokens(lp, keys), greedy)

    @staticmethod
    def _pack_samp(tail):
        """Normalize a program's ``*samp`` operand tail: ``()`` (greedy
        engine) -> None, a 5-tuple (no mask operand) -> 6-tuple with
        ``masks=None``."""
        if not tail:
            return None
        t = tuple(tail)
        return t + (None,) if len(t) == 5 else t

    def _samp_args(self, counts):
        """The per-dispatch sampling operand tail (slot-indexed), on the
        host: the per-slot knob vectors + this dispatch's emitted-count
        vector (+ the mask matrix when the engine carries the mask
        operand).  Empty for sampling=False engines — callers splat it, so
        greedy engines keep the exact legacy call signature."""
        if not self.sampling:
            return ()
        args = (self._temps, self._topks, self._topps, self._seeds,
                np.asarray(counts, np.int32))
        if self.logit_masks:
            # a snapshot, as of the call's one buffer (``OperandLayout.fill``
            # has why): the matrix is written again (a released row) while
            # the call is still in flight
            args += (self._masks.copy(),)
        return args

    def _decode_counts(self):
        """[slots] emitted counts for a decode-phase dispatch (idle rows
        0 — their greedy lane never touches the PRNG)."""
        counts = np.zeros(self.slots, np.int32)
        for slot, st in self._active.items():
            if st.phase == "decode":
                counts[slot] = st.gen_count + st.ahead
        return counts

    def _samp_args_rows(self, group, rows):
        """The ROW-gathered sampling operand tail for a prefill dispatch
        (prefill batches arbitrary slots into ``rows`` rows; pad rows
        keep the greedy/unmasked defaults, so their discarded lane never
        touches the PRNG)."""
        if not self.sampling:
            return ()
        temps = np.zeros(rows, np.float32)
        topks = np.zeros(rows, np.int32)
        topps = np.ones(rows, np.float32)
        seeds = np.zeros(rows, np.uint32)
        counts = np.zeros(rows, np.int32)
        for row, slot in enumerate(group):
            temps[row] = self._temps[slot]
            topks[row] = self._topks[slot]
            topps[row] = self._topps[slot]
            seeds[row] = self._seeds[slot]
            st = self._active[slot]
            counts[row] = st.gen_count + st.ahead
        args = (temps, topks, topps, seeds, counts)
        if self.logit_masks:
            masks = np.ones((rows, self._vocab), bool)
            for row, slot in enumerate(group):
                masks[row] = self._masks[slot]
            args += (masks,)
        return args

    # -------------------------------------------------- one buffer a call
    def _operand_spec(self, rows, head, tail=()):
        """A program's packed operands in its body's positional order, for
        its :class:`OperandLayout`: ``head`` (name -> width of an int32
        operand: None a ``[rows]`` vector, else ``[rows, width]`` ids), the
        block tables — one ``[rows, nbper]`` table, or one a layer kind —
        ``tail`` (names of more int32 ``[rows]`` operands) and, on a
        sampling engine, the five sampling vectors.  The mask matrix of a
        ``logit_masks`` engine is no part of it: it is large, changes
        rarely and stays an operand of its own."""
        def sds(width=None, dtype=np.int32):
            return jax.ShapeDtypeStruct(
                (rows,) if width is None else (rows, width), dtype)

        spec = {name: sds(width) for name, width in head.items()}
        spec["block_tables"] = sds(self._nbper) if not self._windows else {
            "full": sds(self._nbper), "window": sds(self._ring.width)}
        if self._rowed:
            # the row-indexed leaves' "table": the slot of each row of a
            # prefill
            # call (a decode step's row b is slot b; where no table tells
            # an idle row, the decode step carries it too)
            spec["block_tables"] = {"full": sds(self._nbper)} \
                if self._paged else {}
            if "ids" in head or not self._paged:
                spec["block_tables"]["slot"] = sds()
        spec.update((name, sds()) for name in tail)
        if self.sampling:
            spec.update(temps=sds(dtype=np.float32), topks=sds(),
                        topps=sds(dtype=np.float32),
                        seeds=sds(dtype=np.uint32), counts=sds())
        return spec

    def _packed(self, program, body, spec, device_operands=2):
        """``body`` as its jitted call takes it: after the
        ``device_operands`` that live on the device (params, the pools),
        ONE int32 buffer holding the operands of ``spec``, unpacked at the
        head of the program, then whatever rides beside it (the mask
        matrix).  ``body`` itself stays the program below the unpacking
        (``_program_bodies``: what the FLOPs profiler and the lowering
        tests read).  The layout is made here, once a program."""
        layout = self._layouts[program] = OperandLayout(spec)
        n = device_operands

        @functools.wraps(body)
        def packed(*args):
            return body(*args[:n], *layout.unpack(args[n]), *args[n + 1:])

        return packed

    def _host_operands(self, program, *operands):
        """One call's host arrays: ``operands`` (numpy, the body's order)
        written into the program's buffer, and beside it what the layout
        does not hold (the mask matrix).  Also the ``puts`` /
        ``operand_bytes`` arguments of the call's in-flight span."""
        layout = self._layouts[program]
        n = len(layout.names)
        host = (layout.fill(*operands[:n]), *operands[n:])
        return host, {"puts": len(host),
                      "operand_bytes": sum(a.nbytes for a in host)}

    def _refresh_masks(self) -> None:
        """Rebuild every constrained slot's ``[vocab]`` mask row from its
        host-side builder (``inference/constrain.py`` protocol: bool
        allow-vector over the generated-so-far tokens + remaining
        budget).  Runs once per scheduler iteration BEFORE the dispatches
        — tokens only commit at iteration boundaries, so one refresh
        covers prefill-emit, decode, and verify alike.  Unconstrained
        rows were reset to all-True at admit/release and stay that way."""
        if self._masks is None:
            return
        for slot, st in self._active.items():
            mb = st.req.mask_builder
            if mb is None:
                continue
            row = np.asarray(
                mb.allowed(st.prior + st.out,
                           st.req.max_new_tokens - st.gen_count),
                dtype=bool)
            if row.shape != (self._vocab,):
                raise ValueError(
                    f"request {st.req.uid!r}: mask_builder.allowed() "
                    f"returned shape {row.shape}, expected "
                    f"({self._vocab},) (the model's vocab_size)")
            self._masks[slot, :] = row

    def _get_decode_fn(self):
        if self._decode_fn is None:
            fwd, prepare = self._forward, self.engine._prepare
            constrain = self._constrain_pool
            next_tokens, with_record = self._next_tokens, self._with_record

            def step_core(params, cache, tokens, lengths, block_tables,
                          samp):
                with decode_attention.dispatch_log() as paths:
                    logits, cache, rec = fwd(prepare(params),
                                             tokens[:, None], cache, 0,
                                             lengths=lengths,
                                             block_tables=block_tables)
                self._note_latent("decode", paths)
                self._note_sparse("decode")
                self._note_sampler("decode", samp, logits)
                return with_record(next_tokens(logits, samp), rec), \
                    constrain(cache)

            # *samp is the engine's sampling operand tail — () for
            # sampling=False (the exact legacy programs, bit-path
            # identical), (temps, topks, topps, seeds, counts[, masks])
            # otherwise; _samp_args builds it to match per dispatch, and
            # _packed hands it over out of the call's one buffer
            pack = self._pack_samp

            def decode_step(params, cache, tokens, lengths, block_tables,
                            *samp):
                return step_core(params, cache, tokens, lengths,
                                 block_tables, pack(samp))

            body_fn = decode_step
            if self.resident_window_blocks:
                # the windowed program also REPLACES plain decode (same
                # sentry entry, +0 budget): window_start rides as a
                # [slots] int32 operand and the mask is traced into the
                # body via the ops-module window context (entered HERE,
                # inside the traced python, so only this program bakes it)
                lm_tokens = self._landmark_blocks * self.block_size

                def decode_windowed(params, cache, tokens, lengths,
                                    block_tables, window_start, *samp):
                    # *samp is the engine's sampling operand tail (empty
                    # for sampling=False) — the window wrapper stays
                    # agnostic to it
                    with decode_attention.window_context(
                            window_start, lm_tokens):
                        return decode_step(params, cache, tokens,
                                           lengths, block_tables, *samp)

                body_fn = decode_windowed
            self._program_bodies["decode"] = body_fn
            spec = self._operand_spec(
                self.slots, {"tokens": None, "lengths": None},
                ("window_start",) if self.resident_window_blocks else ())
            slots, pin = self.slots, self._pin_tokens

            @functools.wraps(body_fn)      # the program keeps its name
            def decode_ahead(params, cache, devtok, tokens, *rest):
                """``body_fn`` on the device-resident token vector: a
                row whose entry of ``tokens`` says ``TOKEN_ON_DEVICE``
                is fed its entry of ``devtok``, the token the call
                before made; the tokens this call makes are the new
                vector, beside the flat array the host copies back."""
                flat, cache = body_fn(
                    params, cache,
                    jnp.where(tokens == TOKEN_ON_DEVICE, devtok, tokens),
                    *rest)
                return flat, cache, pin(flat[:slots])

            self._decode_fn = self._first_call(jax.jit(
                self.sentry.wrap(
                    self._packed("decode", decode_ahead, spec,
                                 device_operands=3), "decode"),
                donate_argnums=self._donate()),
                "decode", vars(self), "_decode_fn", slots=self.slots)
            self.compiled_programs.append(("decode", self.slots))
        return self._decode_fn

    def _get_prefill_fn(self, rung=None):
        """The prefill program of ``rung`` (``(rows, width)``; default
        ``(prefill_batch, prefill_chunk)``): ONE body at every shape of the
        ladder (``_rungs``), each a program of its own under the name
        ``prefill``, all built the first time any is asked for — a rung
        first met late would otherwise compile in the middle of a run.
        With a draft model, the draft's prefill is FUSED into the same
        program (both caches advance through the identical window/table
        contract), so speculative prefill still costs one program a
        rung."""
        rung = self._rungs[0] if rung is None else rung
        if self._prefill_fns:
            return self._prefill_fns[rung]
        fwd, prepare = self._forward, self.engine._prepare
        draft = self._draft
        constrain = self._constrain_pool

        next_tokens, pack = self._next_tokens, self._pack_samp
        with_record, meta = self._with_record, self._program_meta

        def prefill(params, cache, ids, block_tables, base, valid, *samp):
            """ids [J, width] right-padded; base int32 [J] per-row chunk
            start (reused-prefix length for fresh slots); valid int32
            [J] real tokens per row (pads write to scratch block 0).
            *samp is the ROW-gathered sampling tail (empty for greedy
            engines): the first emitted token of a sampled request
            draws with the SAME counter key (seed, emitted count) the
            decode path would use — that is what makes preempt/crash
            resumes, which re-emit through prefill, token-exact."""
            with decode_attention.dispatch_log() as paths:
                logits, cache, rec = fwd(prepare(params), ids, cache, base,
                                         lengths=valid,
                                         block_tables=block_tables)
            # which read the program was built with, noted as it is traced
            meta["prefill_attn"] = "+".join(sorted(paths))
            self._note_latent("prefill", paths)
            self._note_sparse("prefill")
            samp_t = pack(samp)
            self._note_sampler("prefill", samp_t, logits)
            return with_record(next_tokens(logits, samp_t), rec), \
                constrain(cache)

        body, donate = prefill, self._donate()
        if self.resident_window_blocks:
            # windowed prefill REPLACES the plain program (+0 budget):
            # later chunks of a giant prompt must not attend into the
            # demoted middle (those table entries now point at
            # scratch), so the same window mask gates the T > 1 path
            lm_tokens = self._landmark_blocks * self.block_size

            def prefill_windowed(params, cache, ids, block_tables,
                                 base, valid, window_start, *samp):
                with decode_attention.window_context(
                        window_start, lm_tokens):
                    return prefill(params, cache, ids, block_tables,
                                   base, valid, *samp)

            body = prefill_windowed
        elif draft is not None:
            dfwd = draft.module.decode_hooks["forward_cached"]
            dprepare = draft._prepare

            def prefill_fused(params, dparams, cache, dcache, ids,
                              block_tables, base, valid, *samp):
                first, cache = prefill(params, cache, ids, block_tables,
                                       base, valid, *samp)
                _, dcache = dfwd(dprepare(dparams), ids, dcache, base,
                                 lengths=valid, block_tables=block_tables)
                return first, cache, dcache

            body, donate = prefill_fused, (2, 3) if donate else ()
            self._program_meta["prefill_fused"] = True
        elif self._self_draft is not None:
            def prefill_self(params, cache, ids, block_tables, base, valid,
                             nxt, draft_at, *samp):
                """``prefill`` that also fills the model's own drafting
                module's rows and leaves each row's next draft: entry ``t``
                of the module is made from the hidden state at ``t`` AND
                token ``t + 1`` — the window's ids shifted by one, the last
                real position taking ``nxt`` (the prompt's next token, -1:
                none) or the row's own first token — and the draft is the
                module's argmax at window offset ``draft_at`` (the last real
                one; the one before for a row that backs up,
                :meth:`_advance_prefill_rows`).  -> ``(first tokens and
                records, cache, drafts [J])``."""
                p = prepare(params)
                with decode_attention.dispatch_log() as paths:
                    logits, cache, rec, hidden = self._forward_hidden(
                        p, ids, cache, base, lengths=valid,
                        block_tables=block_tables)
                meta["prefill_attn"] = "+".join(sorted(paths))
                self._note_latent("prefill", paths)
                self._note_sparse("prefill")
                samp_t = pack(samp)
                self._note_sampler("prefill", samp_t, logits)
                first = next_tokens(logits, samp_t)
                col = jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
                after = jnp.where(
                    col == valid[:, None] - 1,
                    jnp.where(nxt >= 0, nxt, first)[:, None],
                    jnp.roll(ids, -1, axis=1))
                with decode_attention.dispatch_log():
                    guess, cache, rec = self._draft_forward(
                        rec, p, hidden, after, cache, base, lengths=valid,
                        block_tables=block_tables, at=draft_at)
                return with_record(first, rec), constrain(cache), \
                    jnp.argmax(guess, axis=-1).astype(jnp.int32)

            body = prefill_self
        self._program_bodies["prefill"] = body
        n_dev = 2 if draft is None else 4
        # the vectors behind the pools: the tokens, a self-drafting
        # engine's drafts
        n_vec = 2 if self._self_draft else 1
        pin = self._pin_tokens
        for j, width in self._rungs:
            spec = self._operand_spec(
                j, {"ids": width},
                ("base", "valid") + (("window_start",)
                                     if self.resident_window_blocks else ())
                + (("nxt", "draft_at") if self._self_draft else ())
                + ("slot",))
            at = list(spec).index("slot")

            @functools.wraps(body)         # the program keeps its name
            def prefill_ahead(*args, j=j, at=at):
                """``body`` writing its rows' tokens into the
                device-resident token vector (the operand behind the pools)
                at ``slot``, each row's slot — a pad row's is out of range
                and dropped; a row with prompt left writes a token nobody
                reads — and a self-drafting engine's drafts into the vector
                beside it.  The vectors are returned last."""
                vecs, rest = args[n_dev:n_dev + n_vec], args[n_dev + n_vec:]
                out = body(*args[:n_dev], *rest[:at], *rest[at + 1:])
                made = (out[0][:j],)
                if n_vec == 2:
                    *out, drafts = out
                    made += (jnp.broadcast_to(drafts[:, None],
                                              (j, vecs[1].shape[1])),)
                return (*out, *(pin(vec.at[rest[at]].set(new, mode="drop"))
                                for vec, new in zip(vecs, made)))

            self._prefill_fns[j, width] = self._first_call(jax.jit(
                self.sentry.wrap(
                    self._packed(self._prefill_program((j, width)),
                                 prefill_ahead, spec,
                                 device_operands=n_dev + n_vec),
                    f"prefill[{self._rung_name((j, width))}]"),
                donate_argnums=donate),
                f"prefill[{self._rung_name((j, width))}]",
                self._prefill_fns, (j, width),
                shape=self._rung_name((j, width)))
            self.compiled_programs.append(("prefill", width, j))
        return self._prefill_fns[rung]

    def _prefill_program(self, rung) -> str:
        """The name a rung's operand layout is kept under
        (``_layouts``, ``stats()["operands"]``): ``"prefill"`` for
        ``(prefill_batch, prefill_chunk)``, ``"prefill[<rows>x<width>]"``
        for the wider rows."""
        return "prefill" if rung == self._rungs[0] \
            else f"prefill[{self._rung_name(rung)}]"

    def _warm_prefill(self, params) -> None:
        """Every rung's program once on pad rows (which write to scratch
        and whose tokens land nowhere), before the first real prefill call:
        from here on no prefill call of any shape compiles."""
        self._prefill_warm = True
        for rung in self._rungs:
            fn = self._get_prefill_fn(rung)
            j, width = rung
            operands = [np.zeros((j, width), np.int32),
                        self._bt(np.zeros((j, self._nbper), np.int32),
                                 [-1] * j),
                        np.zeros(j, np.int32), np.zeros(j, np.int32)]
            if self.resident_window_blocks:
                operands.append(np.zeros(j, np.int32))
            if self._self_draft:
                operands += [np.full(j, -1, np.int32), np.zeros(j, np.int32)]
            host, _ = self._host_operands(
                self._prefill_program(rung), *operands,
                np.full(j, self.slots, np.int32),
                *self._samp_args_rows((), j))
            args = self._prefill_args(params, host)
            with self._prefill_ctx():
                out = fn(*args)
            del args
            self._keep_device(out)

    def _prefill_args(self, params, host):
        """A prefill call's operands: the weights and pools (a draft
        model's beside the target's), the device-resident vectors, then the
        call's host buffer."""
        if self._draft is not None:
            return (params, self._draft.params, self._cache, self._dcache,
                    self._devtok, *host)
        if self._self_draft:
            return (params, self._cache, self._devtok, self._devdraft, *host)
        return (params, self._cache, self._devtok, *host)

    def _get_verify_fn(self):
        """The speculative K+1 verify program: one fixed-shape paged
        forward through the chunked-prefill T>1 path, scoring EVERY
        window position (the ``all_positions`` verify head) — this
        replaces the single-token decode program entirely in speculative
        mode.  On a sampling engine the same forward feeds the
        distribution-exact rejection sampler (delta-proposal form of
        Leviathan/Chen: the proposer — draft model OR n-gram — is treated
        as a point mass at its proposed token ``d``, so accept w.p.
        ``p_target(d)`` and resample the zeroed-``d`` residual on
        reject); ``temperature == 0`` rows degenerate bit-exactly to the
        greedy prefix-match, so one program serves mixed traces."""
        if self._verify_fn is None:
            fwd, prepare = self._fwd, self.engine._prepare
            k, pack = self.spec_tokens, self._pack_samp

            def verify(params, cache, ids, block_tables, base, valid,
                       *samp):
                """ids [slots, K+1] = [pending, d_1..d_K] per row; base
                int32 [slots] committed lengths; valid int32 [slots] real
                window tokens (0 for non-decode rows — all writes land in
                scratch).  Greedy engines return ``(scored, cache)``;
                sampling engines return ``(scored, accept, plain, resid,
                cache)`` where ``accept[s, i]`` is the rejection verdict
                for draft ``d_{i+1}`` and the two tail lanes are the
                draws the host walker picks between BY STOP REASON:
                ``resid[s, i]`` (residual draw) when the walk stopped
                because ``accept[s, i]`` is False, ``plain[s, i]``
                (unconditional target draw) when it stopped at the
                accept cap or the all-accepted bonus position — a cap
                stop never consumed the verdict, so blending on it
                would bias the emission (see docs/inference.md)."""
                # a learned sparse attention's counts ride behind the
                # scored tokens, as behind a decode step's
                with decode_attention.dispatch_log() as paths:
                    logits, cache, *rec = (self._forward if self._sparse
                                           else fwd)(
                        prepare(params), ids, cache, base, lengths=valid,
                        block_tables=block_tables, all_positions=True)
                rec = rec[0] if rec else None
                self._note_latent("verify", paths)
                self._note_sparse("verify")
                samp_t = pack(samp)
                self._note_sampler("verify", samp_t, logits)
                if samp_t is None:
                    return self._with_record(
                        jnp.argmax(logits, -1).astype(jnp.int32), rec), cache
                scored, accept, plain, resid = self._verify_lanes(
                    logits, ids, samp_t)
                return self._with_record(scored, rec), accept, plain, \
                    resid, cache

            self._program_bodies["verify"] = verify
            spec = self._operand_spec(self.slots, {"ids": k + 1},
                                      ("base", "valid"))
            self._verify_fn = self._first_call(jax.jit(
                self.sentry.wrap(self._packed("verify", verify, spec),
                                 "verify"),
                donate_argnums=self._donate()),
                "verify", vars(self), "_verify_fn",
                slots=self.slots, window=k + 1)
            self.compiled_programs.append(
                ("verify", self.slots, self.spec_tokens + 1))
        return self._verify_fn

    def _verify_lanes(self, logits, ids, samp_t):
        """Traced: the delta-form rejection sampler over a verify window
        (``logits [slots, K + 1, V]`` of the window ``ids``): ``(scored,
        accept, plain, resid)`` as :meth:`_get_verify_fn` documents them —
        the verify program's and the self-drafting round's alike."""
        k = ids.shape[1] - 1
        temps, topks, topps, seeds, counts, masks = samp_t
        slots, width = ids.shape          # width == K + 1
        flat = logits.reshape((-1, logits.shape[-1]))
        rep = lambda x: jnp.repeat(x, width)  # noqa: E731
        mrep = None if masks is None else \
            jnp.repeat(masks, width, axis=0)
        greedy, lp = sampling_ops.filtered_logprobs(
            flat, rep(temps), rep(topks), rep(topps), mrep)
        scored = greedy.reshape(slots, width)
        lp = lp.reshape(slots, width, -1)
        # accept test: position i decides emission counts + i
        pos = lp[:, :-1].reshape((-1, lp.shape[-1]))  # [S*K, V]
        drafts = ids[:, 1:].reshape(-1)
        p_d = sampling_ops.token_probs(pos, drafts) \
            .reshape(slots, k)
        u = sampling_ops.accept_uniforms(sampling_ops.grid_keys(
            seeds, counts, sampling_ops.SALT_ACCEPT, k))
        accept = u < p_d
        # tail lanes: the plain draw (accept-cap / bonus stop)
        # and the residual draw (rejection stop) share the
        # RESIDUAL-salt key at their emission index — the host
        # walker consumes exactly ONE of them per round (at the
        # single stop position), and the accept uniforms live on
        # their own salt, so the consumed stream stays i.i.d.
        # They are returned SEPARATELY: only the walker knows the
        # stop reason, and a cap stop (draft-model K-1 cap,
        # constrained cap 0) leaves accept[a] unconsumed — a
        # device-side where(accept, plain, resid) blend there
        # would emit marginal p(x)(1 + q) / q^2 instead of p.
        fkeys = sampling_ops.grid_keys(
            seeds, counts, sampling_ops.SALT_RESIDUAL, width)
        fkeys = fkeys.reshape((-1,) + fkeys.shape[2:])
        plain = sampling_ops.sample_tokens(
            lp.reshape((-1, lp.shape[-1])), fkeys) \
            .reshape(slots, width)
        rkeys = sampling_ops.grid_keys(
            seeds, counts, sampling_ops.SALT_RESIDUAL, k)
        resid = sampling_ops.sample_tokens(
            sampling_ops.residual_logits(pos, drafts),
            rkeys.reshape((-1,) + rkeys.shape[2:])) \
            .reshape(slots, k)
        # temp == 0 rows: bit-exact greedy (already implied by the
        # one-hot algebra; the select makes it unconditional)
        plain = jnp.where(temps[:, None] > 0, plain, scored)
        resid = jnp.where(temps[:, None] > 0, resid,
                          scored[:, :k])
        return scored, accept, plain, resid

    def _get_round_fn(self):
        """The self-drafting round (``draft="self"``): TWO programs handed
        over back to back, nothing of either visiting the host between them.
        ``verify`` scores the window ``[pending, d_1..d_K]`` — both taken
        where the device left them (:attr:`_devtok`, :attr:`_devdraft`) —,
        takes the rejection sampler's verdict AND walks it on the device (no
        cap: a row accepts drafts until its first rejection); ``draft`` runs
        the model's own module over the 1..K+1 positions the walk committed
        (hidden states of that same forward; each position's NEXT token is
        what the walk emitted) and leaves the next draft beside the next
        pending token.  The host gets the emitted ids ``[slots, K + 1]`` and
        a count a row, the routing record and the selections' counts of both
        behind them: one harvest a round, of the second program's one array.
        The pair replaces the decode program as verify + draft rollout do
        (+1 on the budget).  The module is a program of its own so that a
        device trace tells its time (it launches the trunk's kernels under
        the trunk's names); fused into ``verify`` it would save one dispatch
        a round.  A rejected draft's cache entries (the trunk's at ``base +
        accepted + 1 ..``, the module's likewise) stay position-masked and
        are overwritten by the next round.

        **The third vector** (:attr:`_devcount`, int32 ``[slots]``): the
        counts ``verify`` walked stay on the device beside the token and the
        draft, because the NEXT round is enqueued before they reach the host
        (:meth:`_run_self_round`).  ``verify`` reads it: for a row the host
        marks ``behind`` (its last round is still in flight) the window's
        base and the sampler's emission count are the host's values PLUS the
        row's entry — the length and the count the commit of that round
        will give it if the row goes on; for any other row (its last round
        is settled, or it comes from a prefill call, which does not touch
        the vector) they are the host's own.  ``draft`` takes the same base
        from ``verify``.  A row whose request ended in the round in flight
        (its ``eos``, or a budget the walk overran) rides the next round
        all the same, at a base the device advanced by the whole walk: what
        it writes lands in blocks its slot held or in scratch (a base is
        held under ``cache_len``, so the window reaches no further past the
        table than a last round's does), behind nothing a later row reads,
        and :meth:`_commit_self_round` drops what it made."""
        if self._round_fn is None:
            prepare = self.engine._prepare
            k, pack = self.spec_tokens, self._pack_samp
            constrain, pin = self._constrain_pool, self._pin_tokens
            leaves = jax.tree_util.tree_leaves
            cache_len = self._cache_len

            def verify(params, cache, devtok, devdraft, devcount, tokens,
                       block_tables, base, valid, behind, *samp):
                """``tokens`` int32 [slots]: a row's pending token, or
                ``TOKEN_ON_DEVICE``; ``base`` the lengths the host has
                committed, ``valid`` K + 1 for a decoding row and 0 for any
                other (all its writes land in scratch), ``behind`` 1 for a
                row whose last round the host has not harvested: its base
                and its sampler's count are the host's plus its entry of
                ``devcount``.  -> ``(cache, pending tokens, what the
                module's program takes: the emitted ids and counts flat, the
                hidden states, the ids, the counts — the next round's
                ``devcount`` —, the tables, the bases, the records)``."""
                pend = jnp.where(tokens == TOKEN_ON_DEVICE, devtok, tokens)
                ids = jnp.concatenate([pend[:, None], devdraft], axis=1)
                lag = jnp.where(behind > 0, devcount, 0)
                # (held inside the cache: a no-op for a row that goes on)
                base = jnp.minimum(base + lag, cache_len - 1)
                if samp:
                    samp = samp[:4] + (samp[4] + lag,) + samp[5:]
                with decode_attention.dispatch_log() as paths:
                    logits, cache, rec, hidden = self._forward_hidden(
                        prepare(params), ids, cache, base, lengths=valid,
                        block_tables=block_tables, all_positions=True)
                self._note_latent("verify", paths)
                self._note_sparse("verify")
                samp_t = pack(samp)
                self._note_sampler("verify", samp_t, logits)
                with jax.named_scope("verdict"):
                    if samp_t is None:
                        plain = jnp.argmax(logits, -1).astype(jnp.int32)
                        accept, resid = ids[:, 1:] == plain[:, :k], plain
                    else:
                        _, accept, plain, resid = self._verify_lanes(
                            logits, ids, samp_t)
                    # the walk: drafts accepted up to the first rejection;
                    # then the residual draw at the rejection, or — all
                    # accepted — the plain draw at the bonus position
                    a = jnp.cumprod(accept.astype(jnp.int32),
                                    axis=1).sum(axis=1)
                    tail = jnp.where(
                        a < k, jnp.take_along_axis(
                            resid, jnp.minimum(a, k - 1)[:, None],
                            axis=1)[:, 0], plain[:, k])
                    col = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
                    emitted = jnp.where(
                        col < a[:, None], jnp.roll(ids, -1, axis=1),
                        jnp.where(col == a[:, None], tail[:, None], 0))
                    count = pin(jnp.where(valid > 0, a + 1, 0))
                flat = jnp.concatenate([emitted.reshape(-1), count])
                return constrain(cache), pin(tail), (
                    flat, hidden, emitted, count, block_tables, base,
                    tuple(leaves(rec)))

            def draft(params, cache, devdraft, carry):
                """The module over the positions ``verify`` committed
                (``carry``: what it handed on) -> ``(emitted ids, counts and
                both programs' records flat, cache, next drafts)``."""
                flat, hidden, emitted, count, block_tables, base, rec = carry
                rec = None if not rec else rec[0] if len(rec) == 1 else rec
                with decode_attention.dispatch_log():
                    guess, cache, rec = self._draft_forward(
                        rec, prepare(params), hidden, emitted, cache, base,
                        lengths=count, block_tables=block_tables)
                return self._with_record(flat, rec), constrain(cache), pin(
                    jnp.broadcast_to(
                        jnp.argmax(guess, axis=-1).astype(jnp.int32)[:, None],
                        devdraft.shape))

            self._program_bodies["verify"] = verify
            self._program_bodies["draft"] = draft
            spec = self._operand_spec(self.slots, {"tokens": None},
                                      ("base", "valid", "behind"))
            donate = (1,) if self._donate() else ()
            self._verify_fn = self._first_call(jax.jit(
                self.sentry.wrap(
                    self._packed("verify", verify, spec, device_operands=5),
                    "verify"), donate_argnums=donate),
                "verify", vars(self), "_verify_fn",
                slots=self.slots, window=k + 1)
            self._draft_fn = self._first_call(jax.jit(
                self.sentry.wrap(draft, "draft"), donate_argnums=donate),
                "draft", vars(self), "_draft_fn", slots=self.slots, tokens=k)
            self.compiled_programs += [("verify", self.slots, k + 1),
                                       ("draft", self.slots, k)]

            def spec_round(params, cache, devtok, devdraft, devcount, *host):
                cache, tail, carry = self._verify_fn(
                    params, cache, devtok, devdraft, devcount, *host)
                flat, cache, drafts = self._draft_fn(params, cache, devdraft,
                                                     carry)
                return flat, cache, tail, drafts, carry[3]

            self._round_fn = spec_round
        return self._round_fn

    def _get_draft_fn(self):
        """The draft rollout program: K single-token steps of the draft
        model inside ONE ``lax.scan`` — the whole proposal costs one
        compiled program per trace, and the draft pool advances through the
        target's own block tables.  Sampling engines draw each step from
        the draft's own filtered distribution (DRAFT-salt counter keys, so
        proposals are deterministic from the committed prefix — what makes
        re-homed replay round-identical); the delta-form rejection
        verifier needs no draft probabilities back, so the output shape is
        unchanged.  Drafts never see logit masks: constrained slots run
        with ``max_accept = 0`` host-side."""
        if self._draft_fn is None:
            draft = self._draft
            dfwd = draft.module.decode_hooks["forward_cached"]
            dprepare = draft._prepare
            k, pack = self.spec_tokens, self._pack_samp

            def propose(dparams, dcache, tokens, lengths, block_tables,
                        *samp):
                dp = dprepare(dparams)
                samp_t = pack(samp)

                def rollout_step(carry, i):
                    tok, lens, cache = carry
                    logits, cache = dfwd(dp, tok[:, None], cache, 0,
                                         lengths=lens,
                                         block_tables=block_tables)
                    self._note_sampler("draft", samp_t, logits)
                    if samp_t is None:
                        nxt = jnp.argmax(logits, axis=-1) \
                            .astype(jnp.int32)
                    else:
                        temps, topks, topps, seeds, counts, _ = samp_t
                        greedy, lp = sampling_ops.filtered_logprobs(
                            logits, temps, topks, topps, None)
                        keys = sampling_ops.slot_keys(
                            seeds, counts + i, sampling_ops.SALT_DRAFT)
                        nxt = jnp.where(
                            temps > 0,
                            sampling_ops.sample_tokens(lp, keys), greedy)
                    return (nxt, lens + 1, cache), nxt

                (_, _, dcache), drafts = jax.lax.scan(
                    rollout_step, (tokens, lengths, dcache),
                    jnp.arange(k, dtype=jnp.int32))
                return drafts.T, dcache            # [slots, K]

            self._program_bodies["draft"] = propose
            spec = self._operand_spec(
                self.slots, {"tokens": None, "lengths": None})
            self._draft_fn = self._first_call(jax.jit(
                self.sentry.wrap(self._packed("draft", propose, spec),
                                 "draft"),
                donate_argnums=(1,) if self._donate() else ()),
                "draft", vars(self), "_draft_fn", slots=self.slots, tokens=k)
            self.compiled_programs.append(("draft", self.slots, k))
        return self._draft_fn

    # ------------------------------------------------------------- tiered KV
    def _swap_pools(self):
        """The tree the host tier mirrors: the target pool, plus the draft
        pool when speculative decoding carries one (they share block
        tables, so block residency is joint)."""
        return (self._cache, self._dcache) if self._dcache is not None \
            else self._cache

    def _set_swap_pools(self, pools) -> None:
        if self._dcache is not None:
            self._cache, self._dcache = pools
        else:
            self._cache = pools

    def _swap_leaf_shardings(self):
        """One sharding per flattened swap-tree leaf for the staging
        ``device_put``: target-pool leaves follow ``kv_sharded``, draft
        leaves follow ``_dcache_sharded`` — the two can differ (a GQA
        draft whose head count does not divide tp stays replicated while
        the target pool shards), and staging a replicated leaf with the
        head-sharded spec would raise at ``device_put``."""
        rep = NamedSharding(self.engine.mesh, P())
        hs = NamedSharding(self.engine.mesh, P(None, None, TP_AXIS))
        tgt = jax.tree_util.tree_map(
            lambda _: hs if self.kv_sharded else rep, self._cache)
        if self._dcache is None:
            return jax.tree_util.tree_leaves(tgt)
        drf = jax.tree_util.tree_map(
            lambda _: hs if self._dcache_sharded else rep, self._dcache)
        return jax.tree_util.tree_leaves((tgt, drf))

    def _get_demote_fn(self):
        """The fixed-shape block-gather program (``swap_batch`` ids, pad =
        scratch): its output is what one ``jax.device_get`` pulls per
        demotion batch."""
        if self._demote_fn is None:
            def kv_demote(cache, ids):
                return paged_kv.paged_block_gather(cache, ids)

            self._demote_fn = self._first_call(jax.jit(
                self.sentry.wrap(kv_demote, "kv_demote"),
                donate_argnums=()),       # the pool lives on
                "kv_demote", vars(self), "_demote_fn",
                blocks=self.swap_batch)
            self.compiled_programs.append(("kv_demote", self.swap_batch))
        return self._demote_fn

    def _get_promote_fn(self):
        """The fixed-shape block-scatter program committing staged
        (device_put-ahead) host blocks into the pool."""
        if self._promote_fn is None:
            def kv_promote(cache, staged, ids):
                return paged_kv.paged_block_scatter(cache, staged, ids)

            self._promote_fn = self._first_call(jax.jit(
                self.sentry.wrap(kv_promote, "kv_promote"),
                donate_argnums=(0,) if self._donate() else ()),
                "kv_promote", vars(self), "_promote_fn",
                blocks=self.swap_batch)
            self.compiled_programs.append(("kv_promote", self.swap_batch))
        return self._promote_fn

    def warm_swap_programs(self) -> None:
        """Compile the fixed-shape ``kv_demote``/``kv_promote`` pair ahead
        of traffic with a no-op round trip through the scratch block
        (gather scratch, scatter it back onto itself — byte-neutral by
        construction).  Without this, the first real demotion or
        promotion pays the compile inside a latency-sensitive admission —
        the router calls it on drain targets before migrated sessions
        land.  No-op when the tier is off; the programs are the same two
        sentry-registered entries either way (budget unchanged)."""
        if self._host is None:
            return
        if self._demote_fn is not None and self._promote_fn is not None:
            return                          # both already compiled
        ids = jnp.zeros(self.swap_batch, jnp.int32)
        with self._tp_ctx():
            staged = self._get_demote_fn()(self._swap_pools(), ids)
            self._set_swap_pools(
                self._get_promote_fn()(self._swap_pools(), staged, ids))

    # ------------------------------------------------------- fault injection
    def arm_faults(self, injector) -> None:
        """Arm (or, with ``None``, disarm) a fault-injection view on this
        engine (``serving/faults.py`` — a :class:`FaultInjector` bound to
        this replica's id).  The armed view is consulted at every
        scheduler iteration (crash/stall/corruption events) and at every
        swap-transport operation (transient/permanent transport faults);
        unarmed, each injection point is a single ``is None`` predicate."""
        self._fault_injector = injector

    def _swap_transport_ok(self, op: str) -> bool:
        """Gate one engine-internal swap-transport operation (demote /
        promote) through the armed fault plan with bounded deterministic
        retry: transient faults back off exponentially
        (``_transport_backoff_s * 2^attempt``) and retry up to
        ``_transport_retries`` times; a permanent fault (or an exhausted
        budget) returns ``False`` — the caller falls back to dropping
        the demotion or recomputing the chain (docs/reliability.md).
        Always ``True`` when no plan is armed."""
        inj = self._fault_injector
        if inj is None:
            return True
        for attempt in range(self._transport_retries + 1):
            try:
                inj.on_transport(op)
                return True
            except TransportError as e:
                # TransportError ONLY: anything else out of the
                # injector is a bug and must propagate, not masquerade
                # as a quiet permanent transport fault
                self.timeline.instant("transport_fault", op=op,
                                      attempt=attempt,
                                      transient=e.transient)
                if not e.transient:
                    break
                if self._transport_backoff_s:
                    time.sleep(self._transport_backoff_s * (2 ** attempt))
        return False

    def _verified_keys(self, keys: List[bytes]) -> List[bytes]:
        """Integrity gate at the points host-tier bytes LEAVE the arena
        (promotion staging, prefetch staging, cross-replica export):
        verify EVERY entry in the probed run against its stored
        checksum, drop every corrupt one, and truncate the usable run
        at the first failure (the chain is only walkable contiguously)
        — so each corrupt block in a probed run is detected exactly
        once, counted, and recomputed from tokens; corrupt KV is never
        staged, exported, or served.  An entry a live staged record
        still pins keeps its slot (the staged device copy predates the
        corruption and is clean); it drops on the next unpinned pass."""
        cut = len(keys)
        for i, key in enumerate(keys):
            if self._host.is_spilled(key):
                # no arena bytes to check yet: a spilled entry verifies at
                # the NVMe exit instead (promote_spilled — per-op aio
                # status + checksum re-hash), before staging can read it
                continue
            if self._host.verify(key):
                continue
            cut = min(cut, i)
            if any(key in rec["keys"] for rec in self._staged.values()):
                # pinned by a live staged record: truncate (never serve
                # it) but COUNT only on the pass that actually drops it
                # — otherwise every re-probe of the same pinned entry
                # would re-count one corruption
                continue
            self._c_checksum_fail.inc()
            self.timeline.instant("checksum_fail", key=key.hex()[:16],
                                  block_index=i)
            self._host.drop_corrupt(key)
        return keys[:cut]

    def scrub_host_tier(self) -> int:
        """Patrol scrub (the background-scrubber primitive real storage
        tiers run): verify EVERY resident host-tier entry against its
        stored checksum and drop the corrupt ones — entries shadowed
        behind an earlier corrupt block in their chain would otherwise
        sit undetected until (if ever) probed.  In-flight entries are
        skipped (their staged device copies predate the corruption and
        are clean; they drop on the next pass).  Counted into
        ``serving_checksum_failures_total``; returns entries dropped.
        O(arena bytes) — run it between traffic, not per iteration."""
        if self._host is None:
            return 0
        dropped = 0
        for key, e in list(self._host._entries.items()):
            if e.in_flight or self._host.verify(key):
                continue
            self._c_checksum_fail.inc()
            self.timeline.instant("checksum_fail", key=key.hex()[:16],
                                  scrub=True)
            self._host.drop_corrupt(key)
            dropped += 1
        if dropped:
            self.timeline.instant("host_scrub", dropped=dropped,
                                  resident=len(self._host))
        return dropped

    def _sync_nvme_metrics(self) -> None:
        """Mirror :class:`NvmeBlockStore` counter deltas into the metrics
        registry at the swap commit points (sanctioned sync helper, lint
        GL007 naming): ``tier="nvme"`` swap/byte counters, the spill-file
        occupancy gauge, ``nvme_spill``/``nvme_load`` timeline instants,
        and NVMe-exit checksum rejects folded into
        ``serving_checksum_failures_total`` (one integrity ledger across
        every tier boundary)."""
        if self._nvme is None:
            return
        h = self._host
        d_out = h.nvme_spills - self._nvme_spills_seen
        d_in = h.nvme_loads - self._nvme_loads_seen
        d_rej = h.nvme_checksum_rejects - self._nvme_rejects_seen
        if d_out:
            self._nvme_spills_seen = h.nvme_spills
            self._c_nvme_out.inc(d_out)
            self._c_nvme_bytes.inc(d_out * h.block_nbytes)
            self.timeline.instant("nvme_spill", blocks=d_out,
                                  bytes=d_out * h.block_nbytes)
        if d_in:
            self._nvme_loads_seen = h.nvme_loads
            self._c_nvme_in.inc(d_in)
            self._c_nvme_bytes.inc(d_in * h.block_nbytes)
            self.timeline.instant("nvme_load", blocks=d_in,
                                  bytes=d_in * h.block_nbytes)
        if d_rej:
            self._nvme_rejects_seen = h.nvme_checksum_rejects
            self._c_checksum_fail.inc(d_rej)
            self.timeline.instant("checksum_fail", op="nvme",
                                  blocks=d_rej)
        self._g_nvme_in_use.set(h.nvme_blocks_in_use)

    def _demote_blocks(self, blocks: List[int], keys: List[bytes]) -> int:
        """Copy the given device blocks into the host arena under their
        chain keys — the sanctioned blocking demotion helper (lint GL007):
        one gather program call + ONE ``jax.device_get`` per ``swap_batch``
        batch, host-arena writes, no per-block syncs.  Returns the blocks
        actually stored (the arena can refuse when it is full of in-flight
        entries — the demotion is then simply dropped; contents stay
        recomputable)."""
        if blocks and not self._swap_transport_ok("demote"):
            return 0                      # dropped: contents recomputable
        m = self.swap_batch
        stored = 0
        for i in range(0, len(blocks), m):
            chunk_b = blocks[i:i + m]
            chunk_k = keys[i:i + m]
            ids = np.zeros(m, np.int32)
            ids[:len(chunk_b)] = chunk_b
            ids_dev = jnp.asarray(ids)
            # each batch's round trip (gather program + D2H) as an X span:
            # the FLOPs profiler's busy-fraction breakdown reads "swap"
            with self._in_flight("swap", direction="out",
                                 blocks=len(chunk_b)):
                with self._tp_ctx():
                    staged = self._get_demote_fn()(self._swap_pools(),
                                                   ids_dev)
                host = jax.device_get(staged)      # one D2H per batch
            leaves = jax.tree_util.tree_leaves(host)
            for j, key in enumerate(chunk_k):
                if self._host.put(key, [lf[:, j] for lf in leaves]) \
                        is not None:
                    stored += 1
        if stored:
            self._c_swap_out.inc(stored)
            self._c_swap_bytes.inc(stored * self._host.block_nbytes)
            self.timeline.instant(
                "demote", blocks=stored,
                bytes=stored * self._host.block_nbytes)
        self._sync_nvme_metrics()   # arena stores may have spilled LRU tail
        return stored

    def _demote_evict_batch(self) -> int:
        """Tiered replacement for per-block ``evict_one`` under pool
        pressure: demote up to ``swap_batch`` LRU evictable prefix-cache
        leaves to the host tier in one device round trip, then release
        them — the freed blocks land on the free list with their contents
        preserved below.  Returns the number of blocks freed."""
        entries = self._prefix.evictable_leaves(self._alloc, self.swap_batch)
        if not entries:
            return 0
        blocks, keys, ekeys = [], [], []
        for e in entries:
            chain = self._prefix.chain_tokens(e)
            # chain is exactly (depth+1)*block_size tokens, so the block
            # index falls out of its length — no second parent walk
            key = chain_key(chain, len(chain) // self.block_size - 1,
                            self.block_size)
            ekeys.append(key)
            if not self._host.has(key):
                blocks.append(int(e.block))
                keys.append(key)
        if blocks:
            self._demote_blocks(blocks, keys)
        for e in entries:
            self._prefix.evict_entry(e, self._alloc)
            self._kv_scale_live.discard(int(e.block))
        # one event a batch.  demoted counts the blocks whose bytes the
        # tier really holds now (a saturated arena can refuse the store —
        # then the eviction discarded contents, like the untiered path)
        self.timeline.instant(
            "evict_block", blocks=len(entries),
            demoted=sum(self._host.has(key) for key in ekeys))
        return len(entries)

    def _demote_slot_blocks(self, slot: int, st: "_SlotState") -> None:
        """Preemption demotion: move the victim's exclusively-owned full
        blocks (committed content only) to the host tier before its slot
        releases — on resume, the same chain keys (generated tokens fold
        into the resume prompt) promote them back, so the recompute that
        used to re-run the whole prefix shrinks to the unfinished tail."""
        committed = max(int(self._lengths[slot]), st.base)
        seq = np.concatenate([st.prompt_eff, np.asarray(st.out, np.int32)])
        full = min(committed, seq.size) // self.block_size
        run = chain_keys(seq, full, self.block_size)
        blocks, keys = [], []
        for i in range(full):
            b = int(self._tables[slot, i])
            if b == 0 or self._alloc.refcount(b) != 1:
                continue       # shared (trie / other slot): stays on device
            if not self._host.has(run[i]):
                blocks.append(b)
                keys.append(run[i])
        if blocks:
            self._demote_blocks(blocks, keys)

    def _slide_windows(self) -> None:
        """Resident-window maintenance (one pass per scheduler iteration):
        for every active slot whose committed span has outgrown
        ``resident_window_blocks``, demote the oldest non-landmark block
        run to the host tier under its chain keys, zero the table entries
        (the windowed programs' attention mask already hides those
        positions — zeroed entries read scratch garbage that never
        reaches the softmax), release the blocks, and advance the slot's
        window start.  The demoted middle stays recoverable through the
        ordinary host/NVMe promotion path (e.g. for a later re-prefill at
        full attention); NOTHING downstream of the table sees a special
        case — the hole is just more scratch entries."""
        if not self.resident_window_blocks:
            return
        W, lm = self.resident_window_blocks, self._landmark_blocks
        for slot in sorted(self._active):
            st = self._active[slot]
            committed = max(int(self._lengths[slot]), st.base)
            nfull = committed // self.block_size
            f = max(st.window_blk, lm)
            cut = nfull - W
            if cut <= f:
                continue
            seq = np.concatenate([st.prompt_eff,
                                  np.asarray(st.out, np.int32)])
            run = chain_keys(seq, min(cut, seq.size // self.block_size),
                             self.block_size)
            demote_b, demote_k = [], []
            for li in range(f, cut):
                b = int(self._tables[slot, li])
                if b == 0 or li >= len(run):
                    continue
                if self._alloc.refcount(b) == 1 \
                        and not self._host.has(run[li]):
                    demote_b.append(b)
                    demote_k.append(run[li])
            if demote_b:
                self._demote_blocks(demote_b, demote_k)
            freed = 0
            for li in range(f, cut):
                b = int(self._tables[slot, li])
                if b == 0:
                    continue
                # drop THIS slot's mapping + reference; a trie-shared
                # block stays alive under the trie's refs and frees when
                # the LRU eviction path gets to it
                self._decref(b)
                self._held[slot].remove(b)
                self._tables[slot, li] = 0
                freed += 1
            st.window_blk = cut
            self._window_start[slot] = cut * self.block_size
            self._c_window_slides.inc()
            self.timeline.instant(
                "window_slide", slot=slot, uid=str(st.req.uid),
                window_start=cut * self.block_size, blocks_freed=freed,
                demoted=len(demote_b))

    def _stage_chunks(self, keys: List[bytes]):
        """Assemble host-resident blocks into ``swap_batch``-shaped staging
        buffers and issue their H2D ``jax.device_put`` (async — dispatch
        returns immediately, the copy overlaps whatever the device is
        running).  Marks every key in-flight; returns
        ``[(keys_chunk, staged_tree), ...]``."""
        m = self.swap_batch
        chunks = []
        shardings = self._staging_shardings
        template = jax.tree_util.tree_structure(self._swap_pools())
        for i in range(0, len(keys), m):
            chunk = keys[i:i + m]
            short = False
            if self._nvme is not None:
                # NVMe promotion staged through this same double-buffered
                # path: load the chunk's spilled entries back into arena
                # slots (verified at the NVMe exit) just before the read —
                # a shortfall (failed load, or the watermark budget is
                # holding earlier still-in-flight chunks) truncates the
                # stageable run here AND drops every later chunk (the
                # chain is only walkable contiguously); the tail stays
                # spilled and promotes on a later pass once the engine
                # pops the staged prefix
                n_ok = self._host.promote_spilled(chunk)
                self._sync_nvme_metrics()
                if n_ok < len(chunk):
                    chunk, short = chunk[:n_ok], True
                    if not chunk:
                        break
            per_leaf = None
            for j, key in enumerate(chunk):
                arrs = self._host.read(key)
                if per_leaf is None:
                    per_leaf = [
                        np.zeros((a.shape[0], m) + a.shape[1:], a.dtype)
                        for a in arrs]
                for buf, a in zip(per_leaf, arrs):
                    buf[:, j] = a
                self._host.mark_in_flight(key)
            staged = jax.tree_util.tree_unflatten(
                template, [jax.device_put(buf, sh)
                           for buf, sh in zip(per_leaf, shardings)])
            chunks.append((chunk, staged))
            if short:
                break
        return chunks

    def _issue_prefetch(self, pending) -> None:
        """End-of-iteration prefetch: probe the pending queue's first two
        requests for host-resident chains and stage their promotions NOW,
        one-plus scheduler iterations before admission can consume them —
        the double-buffered H2D overlap (``runtime/zero/param_stream.py``
        does the same for ZeRO-3 parameters)."""
        n = 0
        for item in pending:
            req, prior = item.req, item.prior
            if n >= 2 or len(self._staged) >= 2:   # double buffer
                break
            n += 1
            if req.uid in self._staged:
                continue
            # empty-probe memo: while neither the trie's refcount state
            # nor the host key set moved, re-probing the same request is
            # the same O(prompt) walk for the same empty answer — skip it
            # every idle iteration (the _blocked_gate trick, again)
            gate = (id(req), len(prior), self._alloc.version,
                    self._host.version)
            if self._prefetch_gate.get(req.uid) == gate:
                continue
            prompt_eff = np.concatenate(
                [req.prompt, np.asarray(prior, np.int32)]) \
                if prior else req.prompt
            plen = int(prompt_eff.size)
            n_dev = self._prefix.probe(prompt_eff, plen - 1)
            keys = self._host.probe_run(prompt_eff, n_dev, plen - 1,
                                        self.block_size)
            if keys:
                # never stage corrupt arena bytes toward the device
                # (integrity gate — the truncated tail recomputes)
                keys = self._verified_keys(keys)
            if not keys:
                self._prefetch_gate[req.uid] = gate
                continue
            # cap the staged DEVICE footprint at two swap batches per
            # request (a true chunk-level double buffer): a long session
            # chain would otherwise pin chain-length worth of staging
            # buffers next to a deliberately small pool for as long as
            # the queue head stays blocked — admission consumes the
            # staged prefix and stages the remainder there
            keys = keys[:2 * self.swap_batch]
            chunks = self._stage_chunks(keys)
            # NVMe shortfalls truncate inside _stage_chunks — the record
            # must name exactly the keys that really staged (and are
            # pinned in-flight), or a later discard would try to unflag
            # entries that never left the spill file
            keys = [k for ck, _ in chunks for k in ck]
            if not keys:
                self._prefetch_gate[req.uid] = gate
                continue
            self._staged[req.uid] = {"keys": keys, "chunks": chunks}
            self.timeline.instant("prefetch_issue", uid=str(req.uid),
                                  blocks=len(keys))

    def _unflag_keys(self, keys) -> None:
        """Roll staged keys back to plain host residency — UNLESS another
        live staged record still references them (two pending requests
        sharing a session prefix both stage the same chain; the in-flight
        pin must outlive either single record)."""
        still = {k for rec in self._staged.values() for k in rec["keys"]}
        for key in keys:
            if key not in still and self._host.has(key) \
                    and not self._host.is_spilled(key):
                # a spilled key has no arena entry to unflag (it re-spilled
                # or never promoted) — nothing to roll back
                self._host.mark_in_flight(key, False)

    def _discard_all_staged(self) -> None:
        recs = list(self._staged.values())
        self._staged.clear()
        for rec in recs:
            self._unflag_keys(rec["keys"])

    def _take_staged(self, uid, keys: List[bytes]):
        """Consume the prefetched staging for ``uid`` iff it covers a
        leading PREFIX of the chain admission resolved (prefetch caps its
        staged footprint, so a long chain's tail stages at admission); a
        mismatch discards it (and counts as a prefetch miss for the sync
        path)."""
        rec = self._staged.pop(uid, None)
        if rec is None:
            return None
        rk = rec["keys"]
        if len(rk) <= len(keys) and rk == keys[:len(rk)]:
            return rec["chunks"]
        self._unflag_keys(rk)
        return None

    def _promote_wait(self, staged) -> float:
        """Join an in-flight staging buffer (sanctioned blocking helper,
        lint GL007) and return how long admission actually stalled — 0 ≈
        the prefetch fully hid the transfer behind decode."""
        t0 = time.perf_counter()
        for leaf in jax.tree_util.tree_leaves(staged):
            leaf.block_until_ready()
        return time.perf_counter() - t0

    def _promote_chain(self, prompt_eff, plen: int, n_dev: int,
                       req) -> List[int]:
        """Promote the host-resident continuation of an admitted prompt's
        chain back into the device pool: consume the prefetched staging
        (or stage synchronously on a miss), allocate device blocks —
        reclaiming via batch demotion, never preemption; the admission
        gate already proved free + evictable covers the need — scatter the
        staged bytes in, and re-register the chain in the prefix trie from
        block ``n_dev`` on.  Returns the promoted physical blocks, claimed
        for the caller (one reference each, like ``PrefixCache.lookup``).
        Partial promotion (pool pressure mid-run) keeps the unpromoted
        tail host-resident."""
        keys = self._host.probe_run(prompt_eff, n_dev, plen - 1,
                                    self.block_size)
        # integrity gate: a corrupt entry truncates the promotable run
        # (dropped + counted; the tail recomputes), and a transport
        # fault that survives the bounded retry abandons the promotion
        # entirely — both fall back to the ordinary prefill recompute
        if keys:
            keys = self._verified_keys(keys)
        if keys and not self._swap_transport_ok("promote"):
            keys = []
        if not keys:
            # nothing host-resident to promote — but a prefetch staged for
            # this request may still exist (a sharing request promoted the
            # chain first, the trie drifted, or the run was just dropped
            # by the integrity/transport gate): it dies WITH the
            # admission, or its record would pin in-flight entries and
            # occupy the double buffer for the rest of the trace
            rec = self._staged.pop(req.uid, None)
            if rec is not None:
                self._unflag_keys(rec["keys"])
            return []
        chunks = self._take_staged(req.uid, keys)
        miss = chunks is None
        if miss:
            self._c_prefetch_miss.inc()
            self.timeline.instant("prefetch_miss", uid=str(req.uid),
                                  blocks=len(keys))
            chunks = self._stage_chunks(keys)
        else:
            staged_n = sum(len(ck) for ck, _ in chunks)
            if staged_n < len(keys):
                # the prefetch staged only the capped prefix — the tail
                # stages now; its device_put overlaps the prefix chunks'
                # scatter work
                chunks = chunks + self._stage_chunks(keys[staged_n:])
        promoted: List[int] = []
        wait_s = 0.0
        for ci, (chunk_keys, staged) in enumerate(chunks):
            ids = np.zeros(self.swap_batch, np.int32)
            got: List[int] = []
            for key in chunk_keys:
                b = self._alloc.alloc()
                if b is None:
                    if not self._demote_evict_batch():
                        break
                    b = self._alloc.alloc()
                    if b is None:
                        break
                if self.kv_quant:
                    self._kv_scale_live.add(b)
                got.append(b)
            if got:
                ids[:len(got)] = got
                ids_dev = jnp.asarray(ids)
                # waiting on the staged H2D copy, then the scatter's
                # dispatch (asynchronous: nothing reads it back here)
                with self._in_flight("swap", direction="in",
                                     blocks=len(got)):
                    wait_s += self._promote_wait(staged)
                    with self._tp_ctx():
                        self._set_swap_pools(self._get_promote_fn()(
                            self._swap_pools(), staged, ids_dev))
                for key in chunk_keys[:len(got)]:
                    self._host.pop(key)     # residency moved to device
                promoted.extend(got)
            if len(got) < len(chunk_keys):
                # pool dry mid-run: everything unscattered — this chunk's
                # tail and every later chunk — rolls back to plain host
                # residency (NEVER left dangling in-flight; keys a sharing
                # request still has staged keep their pin)
                self._unflag_keys(chunk_keys[len(got):])
                for later_keys, _ in chunks[ci + 1:]:
                    self._unflag_keys(later_keys)
                break
        if promoted:
            # a sharing pending request may have the just-popped keys
            # staged too: drop those records NOW — their staging is stale
            # (the sharer's own admission would probe the chain on device
            # and discard anyway), and a dangling record would both hold
            # the double buffer and flag a false residency violation if
            # the chain is later re-demoted un-flagged
            popped = set(k for ck, _ in chunks for k in ck
                         if not self._host.has(k))
            stale = [uid for uid, rec in self._staged.items()
                     if popped.intersection(rec["keys"])]
            for uid in stale:
                rec = self._staged.pop(uid)
                self._unflag_keys(rec["keys"])
            # graft the chain onto the trie (the cache takes its own hold,
            # exactly like a freshly prefilled prompt's registration)
            self._prefix.register(prompt_eff, promoted, self._alloc,
                                  start=n_dev)
            self._c_swap_in.inc(len(promoted))
            self._c_swap_bytes.inc(len(promoted) * self._host.block_nbytes)
            self._h_prefetch_wait.observe(wait_s)
            self.timeline.instant(
                "promote", uid=str(req.uid), blocks=len(promoted),
                bytes=len(promoted) * self._host.block_nbytes,
                prefetch="miss" if miss else "hit",
                wait_s=round(wait_s, 6))
        return promoted

    # ----------------------------------------------------------- block plumbing
    def _kv(self, fn, *args, **kwargs):
        """Call into the KV manager (allocator, prefix trie) on the
        scheduler's behalf, its seconds added to this step's ``kv_s``.
        Only the scheduler's outermost entry points go through here
        (``_ensure_blocks`` with the evictions and preemptions under it,
        the trie's probe/lookup/register, a finished slot's release), so
        no second is counted twice."""
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            # the segments' accumulator, by hand: this runs once a slot
            self._step_args["kv_s"] += time.perf_counter() - t0

    def _decref(self, b: int) -> None:
        """Release one reference; when the block actually frees, retire
        its scale-ledger entry in the same step (kv8 — the device scale
        row is stale from here until the next owner's first write)."""
        self._alloc.decref(b)
        if self._alloc.refcount(b) == 0:
            self._kv_scale_live.discard(b)

    def _release_slot(self, slot: int) -> None:
        for b in self._held[slot]:
            self._decref(b)
        self._held[slot] = []
        self._tables[slot] = 0
        if self._windows:
            self._ring.release(slot)
        self._tokens[slot] = 0
        self._lengths[slot] = 0
        self._window_start[slot] = 0
        # idle rows revert to the greedy/unmasked defaults — their lanes
        # still run in every dispatch (outputs discarded), so they must
        # stay deterministic and NaN-free
        self._temps[slot] = 0.0
        self._topks[slot] = 0
        self._topps[slot] = 1.0
        self._seeds[slot] = 0
        if self._masks is not None:
            self._masks[slot, :] = True

    def _preempt(self, slot: int) -> None:
        """Evict a sequence under block pressure: free its blocks and
        re-queue it at the FRONT with generated tokens folded into the
        prompt (greedy => recompute is token-exact).  With the host tier
        the victim's committed full blocks demote first, so the resume's
        "recompute" promotes them back instead of re-running prefill."""
        st = self._active.pop(slot)
        nblocks = len(self._held[slot])
        if self._host is not None:
            self._demote_slot_blocks(slot, st)
        self._release_slot(slot)
        self._pending.push_front(_PendingItem(
            req=st.req, prior=st.prior + st.out, priority=st.priority,
            slo_class=st.slo_class, eos=st.eos, handle=st.handle))
        self._c_preempted.inc()
        self.timeline.instant("preempt", uid=str(st.req.uid), slot=slot,
                              blocks_freed=nblocks)

    def _slot_group(self, slot: int) -> int:
        """dp group owning ``slot`` (always 0 outside dp_tp mode): slot
        spans are contiguous, matching the shard_map ``P("dp")`` row
        chunking of the decode program."""
        return slot // (self.slots // self.dp_degree) \
            if self.dp_degree > 1 else 0

    def _alloc_block(self, requester: int) -> Optional[int]:
        """One fresh block, reclaiming in order: free list -> LRU prefix-
        cache eviction -> preempting the latest-admitted sequence.  Returns
        ``None`` iff the requester itself was preempted.  In dp_tp mode
        both the free list and the preemption victim pool are scoped to
        the requester's dp group — blocks never cross pool shards."""
        grp = self._slot_group(requester)
        while True:
            b = self._alloc.alloc(grp) if self.dp_degree > 1 \
                else self._alloc.alloc()
            if b is not None:
                if self.kv_quant:
                    self._kv_scale_live.add(b)
                return b
            if self._prefix is not None:
                if self._host is not None:
                    # tiered: demote a batch of LRU leaves to host DRAM,
                    # then free them — contents survive below
                    if self._demote_evict_batch():
                        continue
                else:
                    evicted = self._prefix.evict_one(self._alloc)
                    if evicted:
                        self._kv_scale_live.discard(evicted)
                        self._evicted_blocks += 1   # the phase's event
                        continue
            if self._flight is not None:
                # a victim is chosen among committed rows: the call in
                # flight may finish some (their blocks come free) and its
                # rows' tokens fold into a victim's resume prompt
                self._settle("preempt")
                if requester not in self._active:
                    return None
                continue
            cands = self._active if self.dp_degree == 1 else \
                {s: st for s, st in self._active.items()
                 if self._slot_group(s) == grp}
            victim = max(cands, key=lambda s: cands[s].admit_seq)
            if victim == requester and len(cands) == 1:
                # cannot happen when num_blocks >= nbper+1 per group
                # (ctor check)
                raise RuntimeError(
                    "paged KV pool too small for a single sequence")
            self._preempt(victim)
            if victim == requester:
                return None

    def _blocks_for(self, tokens):
        """Blocks a row of ``tokens`` positions (an int, or an array of
        them) holds: none where no leaf is paged — a row of states holds no
        block, whatever its length."""
        return -(-tokens // self.block_size) if self._paged else tokens * 0

    def _ensure_blocks(self, slot: int, upto: int) -> bool:
        """Make the slot's table cover positions ``[0, upto)``; may preempt
        other slots (or the slot itself — returns False).  Resident-window
        serving: the demoted region ``[landmark, window_blk)`` is a
        DELIBERATE scratch hole — its entries stay 0 (attention masks them
        out) and must never be re-allocated here."""
        st = self._active.get(slot)
        skip_hi = getattr(st, "window_blk", 0) \
            if self.resident_window_blocks and st is not None else 0
        for li in range(self._blocks_for(upto)):
            if self._landmark_blocks <= li < skip_hi:
                continue
            if slot not in self._active:
                return False
            if self._tables[slot, li] == 0:
                b = self._alloc_block(requester=slot)
                if b is None:
                    return False
                self._tables[slot, li] = b
                self._held[slot].append(b)
        if self._windows and slot in self._active:
            # the window kind's side: the dispatch's first query sits at
            # the slot's committed length, its last written position is
            # ``upto - 1``; every slot owns a whole ring, so this never
            # evicts or preempts
            self._ring.advance(
                slot, st.base if st.phase == "prefill"
                else int(self._lengths[slot]), upto)
        return slot in self._active

    def _kv_walk(self, rows) -> Dict[str, int]:
        """Span args of a decode dispatch, from its live ``rows`` (slots)
        and their lengths: ``kv_blocks``, the blocks the rows' reads walk
        (``cdiv(valid, block_size)`` each), ``kv_tiles``, the loop
        iterations the walk makes of them (``cdiv(blocks, tile)``): their
        quotient says how full the tiles run — and ``kv_first_tiles_ahead``,
        the rows whose first tile the grid step before theirs started
        (``_paged_walk_kernel``): every live row but row 0 of a call (a dp
        group's rows are a call of their own)."""
        if self._decode_attn is None:
            return {}
        blocks = -(-(self._lengths[rows].astype(np.int64) + 1)
                   // self.block_size)
        tile = self._decode_attn["tile_blocks"]
        call = self.slots // self.dp_degree
        return {"kv_blocks": int(blocks.sum()),
                "kv_tiles": int((-(-blocks // tile)).sum()),
                "kv_first_tiles_ahead": int(
                    np.count_nonzero(np.asarray(rows) % call))}

    def _latent_walk(self, t: int) -> Tuple[int, int]:
        """The latent walk's shape for a window of ``t`` positions at this
        pool's stored shapes: ``(query positions a grid step, blocks a loop
        iteration)`` (``ops/decode_attention.latent_walk_shape``)."""
        return decode_attention.latent_walk_shape(
            int(self.engine.module.model_config.num_heads), t,
            int(self._pool_shape[3]), int(self._pool_shape[4]),
            jnp.dtype(self._kv_dtype).itemsize, self._nbper)

    def _kv_reach(self, valid, queries=None, t: int = 1,
                  at=None) -> Dict[str, int]:
        """Span args of a dispatch of a model with window layers, from the
        scheduler's own bookkeeping: ``valid`` holds, for each live row,
        the keys valid for its last query (its position + 1).  ``kv_valid``
        is their sum times the layers, ``kv_visible`` those of them inside
        each layer's reach — all in a full layer, at most ``window`` in a
        sliding one.  ARITHMETIC on lengths and the configuration's window:
        what the traffic lets a windowed read skip, not a reading of what
        the kernels fetched (the comparison with the plain reference and
        the kernels' device time are what hold them to the window).  A
        latent model's rows carry ``queries`` real positions each of a
        window of ``t``, and sit at ``at`` in their call (default: in the
        order given)."""
        if self._latent:
            # a latent model: the valid keys x layers, the blocks those
            # rows hold, and the bytes an absorbed read NEEDS of them
            # (``width`` values a key a layer in the pool's dtype)
            # and ``kv_pairs``, the (query, key) pairs x layers its scores
            # cover: a row's ``queries`` newest positions (default 1, a
            # decode step) see ``valid``, ``valid - 1``, .. keys
            valid = np.asarray(valid, np.int64)
            q = np.ones_like(valid) if queries is None \
                else np.asarray(queries, np.int64)
            layers = int(self._pool_shape[0])
            args = {"kv_valid": int(valid.sum()) * layers,
                    "kv_blocks": int((-(-valid // self.block_size)).sum()),
                    "kv_pairs": int((q * valid - q * (q - 1) // 2).sum())
                    * layers}
            args["latent_bytes"] = args["kv_valid"] * self._latent_token_bytes
            # the walks of ONE layer's call of ``t`` window positions
            # (``_paged_latent_kernel``): a grid step is ``tq`` positions of
            # a row and walks the blocks up to its last real query, ``nt`` a
            # loop iteration.  ``kv_tiles``: those iterations;
            # ``kv_first_tiles_ahead``: the steps whose first tile the step
            # before theirs starts — every step that holds one but the
            # call's first (``at``: each row's place in its call)
            tq, nt = self._latent_walk(t)
            first = np.arange(0, t, tq)
            reach = np.minimum(first + tq, q[:, None])
            blocks = -(-((valid - q)[:, None] + reach) // self.block_size)
            tiles = np.where(reach > first, -(-blocks // nt), 0)
            at = np.arange(len(valid)) if at is None else np.asarray(at)
            args["kv_tiles"] = int(tiles.sum())
            args["kv_first_tiles_ahead"] = int(np.count_nonzero(tiles)) \
                - int(np.any((at == 0) & (tiles[:, 0] > 0)))
            for key, v in args.items():
                self._latent_totals[key] += v
            if self._windows:
                # latent layers under a window beside them, on the ring:
                # ``kv_window``, the keys the rows' last queries keep x the
                # sliding layers, ``kv_window_blocks``, the ring blocks of
                # ONE layer those keys lie in
                window, bs = self._windows["window"], self._ring.block_size
                reach = {
                    "kv_window": int(np.minimum(valid, window).sum())
                    * self._windows["layers"]["sliding"],
                    "kv_window_blocks": int(
                        (-(-valid // bs)
                         - np.maximum(valid - window, 0) // bs).sum())}
                for key, v in reach.items():
                    self._window_totals[key] = \
                        self._window_totals.get(key, 0) + v
                args.update(reach)
            return args
        if not self._windows:
            return {}
        valid = np.asarray(valid, np.int64)
        layers = self._windows["layers"]
        args = {
            "kv_valid": int(valid.sum()) * sum(layers.values()),
            "kv_visible": int(
                layers["full"] * valid.sum() + layers["sliding"]
                * np.minimum(valid, self._windows["window"]).sum())}
        for key, v in args.items():
            self._window_totals[key] += v
        return args

    def _state_args(self, rows: int, resets: int,
                    tokens: int) -> Dict[str, int]:
        """Span args of a dispatch of a model with a recurrent state:
        ``state_rows``, the rows whose state the call advances,
        ``state_resets``, those of them that start from a zero state (a
        prefill window at base 0: a sequence entering its slot), and
        ``state_tokens``, the real tokens it advances them by."""
        if not self._state:
            return {}
        self._state_totals["state_rows"] += rows
        self._state_totals["resets"] += resets
        return {"state_rows": rows, "state_resets": resets,
                "state_tokens": tokens}

    def _tail_args(self, rows: int, resets: int) -> Dict[str, int]:
        """Span args of a dispatch of a model with tails: ``tail_bytes``,
        the tails its ``rows`` live rows read and write back, all layers,
        and ``tail_resets``, those of them that enter at base 0 (zero
        tails inside the program)."""
        if not self._tails:
            return {}
        nbytes = 2 * rows * self._state_bytes() // self.slots
        self._tail_totals["tail_bytes"] += nbytes
        self._tail_totals["resets"] += resets
        return {"tail_bytes": nbytes, "tail_resets": resets}

    def _bt(self, tables, rows=None):
        """The block-table operand of a dispatch, on the host: the full
        kind's ``tables`` (already masked to the dispatch's rows) — for a
        model with window layers, the table of each kind, the window kind's
        rings gathered for the same rows (``rows``: slot of each row, -1 a
        pad row; None: row i is slot i, rows whose table is all scratch
        are idle; a model with no paged leaf has no table and names its
        live rows in both programs, :meth:`_live_rows`)."""
        if self._rowed:
            if rows is None:
                return {"full": tables}
            slot = np.asarray([slot if slot >= 0 else self.slots
                               for slot in rows], np.int32)
            return {"full": tables, "slot": slot} if self._paged \
                else {"slot": slot}
        if not self._windows:
            return tables
        if rows is None:
            ring = np.where(tables[:, :1] != 0, self._ring.tables, 0)
        else:
            ring = np.zeros((len(rows), self._ring.width), np.int32)
            for row, slot in enumerate(rows):
                if slot >= 0:
                    ring[row] = self._ring.tables[slot]
        return {"full": tables, "window": ring}

    def _live_rows(self, dec) -> Optional[List[int]]:
        """``rows`` of :meth:`_bt` for a decode step over the slots ``dec``:
        None where a paged table tells the idle rows (all scratch), else
        every row's slot, -1 an idle one."""
        if self._paged:
            return None
        live = set(dec)
        return [s if s in live else -1 for s in range(self.slots)]

    # --------------------------------------------------------------- schedule
    def _admit(self):
        """Head-of-queue-gated admission into free slots (priority order,
        module docstring), gated on block availability (free + prefix-
        evictable) so an admitted sequence can always prefill its prompt;
        the queue head blocks admission when it doesn't fit — no
        starvation within a priority class."""
        pending, active = self._pending, self._active
        free = [s for s in range(self.slots) if s not in active]
        reserved = 0                       # blocks promised to this call's
        reserved_g: Dict[int, int] = {}    # ... per dp group, in dp_tp mode
        while pending and free:            # earlier joiners, not yet alloc'd
            item = pending[0]
            req, prior = item.req, item.prior
            if self.dp_degree > 1:
                # placement: the free slot whose dp group has the most
                # unpromised blocks — admission gates on THAT group's span
                slot_pick = max(
                    free,
                    key=lambda s: (self._alloc.group_free(
                        self._slot_group(s))
                        - reserved_g.get(self._slot_group(s), 0), -s))
                grp = self._slot_group(slot_pick)
            else:
                slot_pick, grp = free[0], None
            # blocked-head memo: while nothing refcount-related moved, the
            # gate's probe/evictable answer cannot change — skip the
            # O(prompt + trie) host walk every idle iteration
            gate_key = (id(req), len(prior), self._alloc.version)
            if gate_key == self._blocked_gate:
                break
            prompt_eff = np.concatenate(
                [req.prompt, np.asarray(prior, np.int32)]) \
                if prior else req.prompt
            plen = int(prompt_eff.size)
            # gate on a non-mutating probe first: while the queue head is
            # blocked, iterations must not churn refcounts / LRU recency
            # (no paged leaf: none, a free slot is all a request needs)
            total_need = self._blocks_for(plen + 1)
            if self.resident_window_blocks:
                # resident-window serving admits on the RESIDENT footprint
                # only — landmark + window + the chunk being prefilled —
                # because everything older demotes as the window slides;
                # gating on the full logical span would block every giant
                # prompt the window exists to serve
                total_need = min(
                    total_need,
                    self._landmark_blocks + self.resident_window_blocks
                    + blocks_for(self._prefill_width, self.block_size))
            n_hit = self._kv(self._prefix.probe, prompt_eff, plen - 1) \
                if self._prefix is not None else 0
            if self._self_draft:
                n_hit = max(n_hit - 1, 0)      # (the lookup below has why)

            def _avail():
                if grp is not None:
                    return self._alloc.group_free(grp) - \
                        reserved_g.get(grp, 0)
                return self._alloc.free_blocks - reserved + \
                    (self._kv(self._prefix.evictable, self._alloc)
                     if self._prefix is not None else 0)

            if total_need - n_hit > _avail():
                self._blocked_gate = gate_key
                break                      # strict FIFO: head blocks the rest
            hits: List[int] = []
            if self._prefix is not None:
                # cap below the full prompt: >= 1 tail token must prefill
                hits = self._kv(self._prefix.lookup, prompt_eff, plen - 1,
                                self._alloc)
                if self._self_draft and hits:
                    # the module's entry at a position is made from the
                    # NEXT token, so the last position of the last matched
                    # block belongs to whoever registered it — the one
                    # after it is where the two prompts may part; and the
                    # first round needs the hidden state of the last cached
                    # position.  The hit ends a block early (vLLM drops the
                    # last matched block for its EAGLE / MTP heads likewise)
                    self._decref(hits.pop())
            # re-check post-claim: hit blocks that were evictable no longer
            # count toward avail, so the probe gate can be optimistic by
            # up to n_hit blocks
            need = total_need - len(hits)
            if need > _avail():
                for b in hits:             # unclaim and wait for pressure
                    self._decref(b)        # to drain
                self._blocked_gate = (id(req), len(prior),
                                      self._alloc.version)
                break
            if self._host is not None and not self.resident_window_blocks:
                # tiered KV: the chain's continuation may live in host
                # DRAM (earlier eviction or this request's own preempted
                # state) — promote it back and extend the claimed prefix;
                # the gate above just proved the device blocks this costs
                # are coverable, so promotion never preempts anyone.
                # Resident-window mode skips this: demoted middle blocks
                # belong OUT of the device pool (the window mask hides
                # them) — promoting a 100k-token chain would flood the
                # deliberately small pool at admission
                hits.extend(self._promote_chain(prompt_eff, plen,
                                                len(hits), req))
                need = total_need - len(hits)
            reserved += max(need, 0)
            if grp is not None:
                reserved_g[grp] = reserved_g.get(grp, 0) + max(need, 0)
            pending.popleft()
            slot = slot_pick
            free.remove(slot)
            # latency probes: admit stamped once per request per trace (a
            # preemption resume keeps the original admission time, so its
            # TTFT/TPOT and its timeline span cover the whole wait)
            self._trace_times.setdefault(
                req.uid, {"admit": time.perf_counter(), "first": None,
                          "admit_us": self.timeline.now_us()})
            self._tables[slot, :len(hits)] = hits
            self._held[slot] = list(hits)
            st = _SlotState(req=req, admit_seq=self._admit_seq,
                            prompt_eff=prompt_eff, prior=list(prior),
                            base=len(hits) * self.block_size,
                            eos=item.eos, priority=item.priority,
                            slo_class=item.slo_class, handle=item.handle)
            self._admit_seq += 1
            active[slot] = st
            if self.sampling:
                self._temps[slot] = np.float32(req.temperature)
                self._topks[slot] = np.int32(req.top_k)
                self._topps[slot] = np.float32(req.top_p)
                self._seeds[slot] = np.uint32(req.seed)
                if self._masks is not None:
                    self._masks[slot, :] = True
            if self.resident_window_blocks:
                # fresh slot: full attention until the first slide
                self._window_start[slot] = 0
            if st.handle is not None:
                st.handle._on_active()
            if self._admission_log is not None:
                self._admission_log.append((req.uid, slot))
            self._c_admitted.inc()
            self._c_prompt_tokens.inc(plen)
            self._c_prefix_hit_tokens.inc(st.base)
            if prior:
                # tokens a preemption resume actually re-prefills — with
                # the host tier this stays near zero (promoted chains
                # cover all but the unfinished tail)
                self._c_resume_recompute.inc(plen - st.base)
            # prefix_hit_tokens == 0 is the cache-miss record
            self.timeline.instant("admit", uid=str(req.uid), slot=slot,
                                  prompt_tokens=plen,
                                  prefix_hit_tokens=st.base,
                                  resumed=bool(prior))
            fid = self._flow_ids.pop(req.uid, None)
            if fid is not None:
                # close the router's route flow on this admission — the
                # merged fleet trace draws the router -> replica arrow
                self.timeline.flow_end("route", fid, uid=str(req.uid),
                                       slot=slot)

    # --------------------------------------------------- incremental serving
    def _validate_request(self, r: Request) -> None:
        total = len(r.prompt) + r.max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(
                f"request {r.uid!r}: prompt ({len(r.prompt)}) + "
                f"max_new_tokens ({r.max_new_tokens}) = {total} exceeds "
                f"max_seq_len {self.max_seq_len}")
        if r.sampled and not self.sampling:
            raise ValueError(
                f"request {r.uid!r} asks for temperature="
                f"{r.temperature} but this engine was built with "
                "sampling=False (greedy-only programs) — rebuild with "
                "sampling=True to serve sampled traffic")
        if r.mask_builder is not None and not self.logit_masks:
            raise ValueError(
                f"request {r.uid!r} carries a mask_builder but this "
                "engine was built without the constrained-decoding lane "
                "— pass logit_masks=True (the [slots, vocab] mask "
                "operand is only threaded through the programs then)")

    def _session_boundary_reset(self) -> None:
        """First submit into an idle engine: object ids and prefetch gates
        from the previous trace are stale (the per-call reset ``serve``
        used to do)."""
        if self._pending or self._active:
            return
        self._blocked_gate = None
        if self._host is not None:
            self._discard_all_staged()
            self._prefetch_gate.clear()

    def submit(self, request: Request, *, priority: int = 0,
               slo_class: Optional[str] = None,
               eos_token_id: Optional[int] = None) -> RequestHandle:
        """Enqueue one request into the live scheduler and return its
        :class:`RequestHandle` (per-token streaming, ``result()``,
        ``cancel()``).  Admission happens on subsequent ``step()`` calls
        (``serve()`` and the replica router drive them).  ``priority``
        orders the pending queue (higher admits first; FIFO within a
        class); ``slo_class`` maps to a default priority through
        :data:`SLO_PRIORITY` when ``priority`` is 0."""
        self._validate_request(request)
        if request.uid in self._live_uids:
            raise ValueError(
                f"request uid {request.uid!r} is already in flight")
        self._session_boundary_reset()
        if self._submit_observer is not None:
            # record the CALLER's knobs (pre-SLO-mapping priority) so a
            # replay resubmits through the same mapping
            self._submit_observer(request, priority=priority,
                                  slo_class=slo_class,
                                  eos_token_id=eos_token_id)
        if priority == 0 and slo_class is not None:
            priority = SLO_PRIORITY.get(str(slo_class), 0)
        handle = RequestHandle(request, priority=priority,
                               slo_class=slo_class, canceller=self.cancel,
                               lock_sanitizer=self._lock_sanitizer)
        self._pending.push(_PendingItem(
            req=request, prior=[], priority=priority, slo_class=slo_class,
            eos=eos_token_id, handle=handle))
        self._c_sampled["constrained" if request.mask_builder is not None
                        else "sampled" if request.sampled
                        else "greedy"].inc()
        self._live_uids.add(request.uid)
        self._g_queue_depth.set(len(self._pending))
        self.timeline.instant("submit", uid=str(request.uid),
                              priority=int(priority),
                              slo=str(slo_class) if slo_class else "")
        return handle

    def _submit_item(self, item: _PendingItem,
                     canceller=None) -> None:
        """Router handoff entry: enqueue a fully-formed pending item (an
        in-flight request drained off another replica), keeping its
        handle, prior tokens, priority, and eos — token streaming
        continues on the same handle.  ``canceller`` is the cancel
        route to rebind (the router passes its own ``cancel`` so the
        handle never — even transiently — routes around the fleet
        locks straight into this engine); defaults to this engine's."""
        self._validate_request(item.req)
        if item.req.uid in self._live_uids:
            raise ValueError(
                f"request uid {item.req.uid!r} is already in flight")
        self._session_boundary_reset()
        if item.handle is not None:
            # under the handle condition (set_canceller) — the stream
            # may still be read concurrently during a drain handoff
            item.handle.set_canceller(canceller or self.cancel)
        self._pending.push(item)
        self._live_uids.add(item.req.uid)
        self._g_queue_depth.set(len(self._pending))
        self.timeline.instant("submit", uid=str(item.req.uid),
                              priority=int(item.priority),
                              resumed=bool(item.prior))

    def _cancel_pending(self, uid) -> bool:
        item = self._pending.remove(uid)
        if item is None:
            return False
        self._live_uids.discard(uid)
        if self._host is not None:
            rec = self._staged.pop(uid, None)
            if rec is not None:
                self._unflag_keys(rec["keys"])
        self._prefetch_gate.pop(uid, None)
        self._blocked_gate = None          # the head may have been this item
        self._trace_times.pop(uid, None)
        fid = self._flow_ids.pop(uid, None)
        if fid is not None:
            # never admitted — close the router's route flow here so the
            # merged trace carries no dangling flow start
            self.timeline.flow_end("route", fid, uid=str(uid),
                                   cancelled=True)
        self._c_cancelled.inc()
        self._g_queue_depth.set(len(self._pending))
        self.timeline.instant("cancelled", uid=str(uid), queued=True)
        if item.handle is not None:
            item.handle._on_cancel()
        return True

    def cancel(self, uid) -> bool:
        """Cancel a live request.  Queued: dropped immediately (its staged
        prefetch, if any, rolls back).  Active: the slot and its blocks
        release at the NEXT scheduler-iteration boundary — the only point
        the paged-state invariants are guaranteed to hold — with a
        ``cancelled`` timeline event; already-streamed tokens stand.
        Returns ``False`` when the uid is unknown or already finished."""
        if self._cancel_pending(uid):
            return True
        if any(st.req.uid == uid for st in self._active.values()):
            self._cancel_flags.add(uid)
            return True
        return False

    def _process_cancellations(self) -> None:
        """Apply deferred active-slot cancels at the iteration boundary:
        pop the slot, decref its blocks (prefix-shared blocks survive in
        the trie), and emit the audited ``cancelled`` event."""
        if not self._cancel_flags:
            return
        self._settle("cancel")             # a cancelled row may be in flight
        flags, self._cancel_flags = self._cancel_flags, set()
        for uid in flags:
            slot = next((s for s, st in self._active.items()
                         if st.req.uid == uid), None)
            if slot is None:
                # finished before the boundary, or preempted back to the
                # queue — the pending path handles the latter
                self._cancel_pending(uid)
                continue
            st = self._active.pop(slot)
            nblocks = len(self._held[slot])
            self._kv(self._release_slot, slot)
            self._live_uids.discard(uid)
            self._trace_times.pop(uid, None)
            self._c_cancelled.inc()
            self.timeline.instant("cancelled", uid=str(uid), slot=slot,
                                  blocks_freed=nblocks)
            if st.handle is not None:
                st.handle._on_cancel()

    def step(self) -> bool:
        """ONE scheduler iteration over the live queue/slots: process
        cancellations, admit, advance prefills, run the decode (or
        draft–verify) round, stage prefetches, audit.  Returns whether
        work remains — drive it in a loop (``serve``), from a replica
        worker thread (``deepspeed_tpu/serving/``), or by hand.

        ONE call of lookahead (:meth:`_launch`): a decode or prefill call
        is put on the device's queue BEFORE the results of the call before
        it are taken, so ``step()`` returns with its last call in flight
        and that call's tokens reach their handles early in the NEXT
        ``step()``, once the next call is enqueued; a step with nothing to
        enqueue takes them itself, so ``False`` is only ever said by a
        settled engine.  What a plan needs of committed state settles the
        call at once instead (``EARLY_SETTLE_CAUSES``) — today's order,
        chosen from what the engine can see, by no option.

        On the timeline (and, while a profile is being taken, on the
        profiler's clock as ``ds.serve.*``) an iteration is one ``step``
        span tiled by four host phases — ``step.admit``, ``step.prefill``,
        ``step.decode``, ``step.post`` — inside which the in-flight spans
        (``prefill``, ``decode``, ``spec_*``, ``swap``) mark the host's
        stays in the runtime, handing a program over and waiting for one's
        results: a phase's time outside them is the host's own work, which
        the device sits idle for only when no call is in flight behind
        it (``ahead`` on the span, ``stats()["lookahead"]``)."""
        if self._fault_injector is not None:
            # chaos harness (serving/faults.py): may raise SimulatedCrash
            # (the router/worker converts it into fail-and-re-home),
            # stall this replica, or flip bits in the host arena — all on
            # the armed plan's deterministic schedule
            self._fault_injector.on_step(self)
        if not (self._cancel_flags or self._pending or self._active):
            return self._step_idle()
        tl = self.timeline
        if not tl.enabled:
            with tl.span("step") as step_args:
                return self._step_phases(step_args)
        # ring on: the step's own cost beside its wall clock — thread-CPU
        # seconds (and, from _in_flight, the part of them and of the wall
        # clock inside in-flight spans), the collector's runs
        watch = self._gc
        watch.thread = threading.get_ident()
        gc_s0, gc_n0 = watch.seconds, watch.runs
        t0, cpu0 = time.perf_counter(), time.thread_time()
        try:
            with tl.span("step", kv_s=0.0, flight_s=0.0,
                         flight_cpu_s=0.0) as step_args:
                self._step_args = step_args
                self._seg_args = []
                try:
                    more = self._step_phases(step_args)
                finally:
                    step_args.update(cpu_s=time.thread_time() - cpu0,
                                     gc_s=watch.seconds - gc_s0,
                                     gc_n=watch.runs - gc_n0)
        finally:
            watch.thread = None
            self._step_args = self._no_step
        self._note_step(step_args, time.perf_counter() - t0)
        return more

    def _step_idle(self) -> bool:
        if self._host is not None:
            self._discard_all_staged()  # no queue left to consume them
        self._g_queue_depth.set(0)
        return False

    def _step_phases(self, step_args: Dict[str, Any]) -> bool:
        """The body of :meth:`step` under its ``step`` span; fills the
        span's arguments (``step_args``) at the end of the iteration."""
        span, seg = self.timeline.span, self.timeline.segment
        with span("step.admit"):
            self._process_cancellations()
            if not self._pending and not self._active:
                return self._step_idle()   # the cancellations emptied it
            params = self.engine.params
            self._launched = False
            self._c_iterations.inc()
            self.timeline.step = self.iterations
            admitted0, preempted0 = self.admitted, self.preempted
            released0 = self._ring.released if self._windows else 0
            self._admit()
            self._refresh_masks()
        with span("step.prefill") as phase:
            self._phase = phase
            self._seg_args.append(phase)
            phase["groups"] = self._run_prefill(params)
            if self._evicted_blocks:
                self._note_evictions()
            if self.role == "prefill":
                # disaggregated mode: prefill-complete slots leave the
                # decode rotation NOW — the decode dispatch below only ever
                # advances prefilling/empty slots on this replica
                self._extract_handoffs()
        # one decode step over every slot (per-sequence positions);
        # prefilling/empty slots point at the scratch block.  In
        # speculative mode the single-token step is replaced by a
        # draft–verify round committing up to K+1 tokens per slot.
        self._prefilled = bool(phase["groups"])
        with span("step.decode") as phase:
            self._phase = phase
            self._seg_args.append(phase)
            # a slot that finished prefill above decodes in this SAME
            # iteration — its first generated token must gate the second,
            # so constrained rows rebuild between the two dispatches
            with seg("step.decode.plan", phase):
                self._refresh_masks()
            if self.spec_tokens:
                phase["slots"] = self._run_spec_decode(params)
            else:
                phase["slots"] = self._run_plain_decode(params)
            if self._evicted_blocks:
                self._note_evictions()
        with span("step.post"):
            # resident-window maintenance AFTER both phases committed this
            # iteration's tokens: mid-prefill giant prompts slide too (the
            # next chunk's program then masks the demoted middle)
            if not self._launched or not self._active:
                # nothing to hand the device: the rows wait for the tokens
                # of the call that is in flight (budgets, finishes) — or
                # every row of it has finished (it carries a row whose eos
                # was seen behind its enqueue): nothing is left to plan
                self._settle()
            self._slide_windows()
            if self._host is not None:
                # stage next iteration's promotions NOW: the H2D copies
                # run while the next decode step computes (module
                # docstring "Tiered KV cache" — the param_stream overlap)
                self._issue_prefetch(self._pending)
            self._g_queue_depth.set(len(self._pending))
            step_args.update(
                iteration=self.iterations,
                admitted=self.admitted - admitted0,
                evicted=self.preempted - preempted0,
                blocks_in_use=self._alloc.blocks_in_use,
                active=len(self._active), pending=len(self._pending))
            if self._windows or self._rowed or self._self_draft:
                self._full_peak = max(self._full_peak,
                                      self._alloc.blocks_in_use)
            if self._windows:
                step_args.update(
                    window_num_blocks=self._ring.alloc.num_blocks,
                    window_blocks_in_use=self._ring.alloc.blocks_in_use,
                    window_blocks_released=self._ring.released - released0)
            if self._step_log is not None:
                self._step_log.append({k: step_args[k] for k in (
                    "iteration", "admitted", "evicted", "blocks_in_use")})
            if self.debug_checks:
                # O(blocks) host-state audit between scheduler rounds —
                # the scheduler's state is only guaranteed consistent at
                # iteration boundaries (analysis/invariants.py; the audit
                # drops its own event on the timeline)
                audit_serving_engine(self, self._active)
                self._c_invariant_checks.inc()
        return bool(self._pending or self._active)

    def _note_evictions(self) -> None:
        """One ``evict_block`` instant for every trie block the host phase
        that just ran evicted to reserve its rows' blocks — not one a
        block: an admission into a full pool evicts about as many blocks
        as it allocates (the tiered path's batches say so themselves,
        ``_demote_evict_batch``)."""
        self.timeline.instant("evict_block", blocks=self._evicted_blocks,
                              demoted=0)
        self._evicted_blocks = 0

    def _enter_runtime(self, name: str) -> None:
        """The host goes into the runtime on a call's behalf — to hand one
        to the device, to wait for one's results — under the annotation
        ``ds.serve.<name>``; :meth:`_leave_runtime` ends the stay.  Ring
        on, a stay's wall and thread-CPU seconds are added to the step's
        ``flight_s`` / ``flight_cpu_s``: the step's duration and ``cpu_s``
        less these are the host's OWN time and the CPU it got for it."""
        tl = self.timeline
        start = tl.now_us() if tl.enabled else 0.0
        note = trace_mod.annotation(f"ds.{tl.role}.{name}")
        note.__enter__()
        t0 = cpu0 = 0.0
        if tl.enabled:
            tl.lap()                       # its first segment begins now
            t0, cpu0 = time.perf_counter(), time.thread_time()
        self._runtime = (start, note, t0, cpu0)

    def _leave_runtime(self, name: str, args: Dict[str, Any]) -> None:
        """The stay ends, with the results of call ``name`` on the host:
        it is that call's in-flight span on the ring (``args``)."""
        (start, note, t0, cpu0), self._runtime = self._runtime, None
        tl = self.timeline
        if tl.enabled:
            step = self._step_args
            step["flight_cpu_s"] += time.thread_time() - cpu0
            step["flight_s"] += time.perf_counter() - t0
        note.__exit__(None, None, None)
        if tl.enabled:
            end = tl.now_us()
            tl.complete(name, start, end_us=end, **args)
            tl.lap(end)                    # the commit begins at its end

    @contextlib.contextmanager
    def _in_flight(self, name: str, **args):
        """An in-flight span (:meth:`step`) around a call that is made and
        harvested in one stay in the runtime — the speculative runner,
        which keeps its own fence; yields the span's argument
        dict.  The runner makes it inside its ``upload`` segment and
        enters it next, so nothing but two clock reads lies between."""
        self._enter_runtime(name)
        if self.timeline.enabled:
            self._seg_args.append(args)
        try:
            yield args
        finally:
            self._leave_runtime(name, args)

    def _settle_now(self) -> Optional[str]:
        """Why the call just enqueued cannot stay in flight while the next
        one is planned (``EARLY_SETTLE_CAUSES``), from what the engine is
        and what its rows carry — or None: the plan of the next call needs
        nothing of this one that the host does not know, or nothing that the
        device does not hold for it (a decode row's token; a self-drafting
        round's token, draft and count — its base and sampler count are
        settled on the device, :meth:`_get_round_fn`)."""
        if self.debug_checks:
            return "debug_checks"          # the audit reads committed state
        if self.spec_tokens and not self._self_draft:
            # a draft model's or the n-gram proposer's round plans on its
            # tokens (the lookup is host work on them).  A self-drafting
            # round leaves what its successor needs on the device
            # (``_devtok`` / ``_devdraft`` / ``_devcount``)
            return "speculative"
        if self._host is not None or self.resident_window_blocks:
            return "kv_tier"               # demotions key blocks by tokens
        if self.role == "prefill":
            return "handoff"               # a finished prompt leaves now
        if self._masks is not None and any(
                st.req.mask_builder is not None
                for st in self._active.values()):
            return "mask_builder"          # a host function of the tokens
        return None

    def _launch(self, flight: _Flight, fn, ctx) -> None:
        """Hand ``flight``'s call (``fn`` on ``flight.held``, the runner's
        frame keeping none of it) to the device, THEN take the results of
        the call before it (:meth:`_harvest`): the device goes from one
        program into the next while the host harvests, commits and plans.
        The caller has advanced what is certain of the call's outcome
        (lengths, bases, phases, ``ahead``; of a self-drafting round only
        that it is in flight, ``rounds``); what is not — the tokens, such a
        round's counts — stays on the device (``_devtok``, ``_devcount``)
        until this call is settled in its turn, at once if
        :meth:`_settle_now` names a cause.

        On the ring a call's ``decode`` / ``prefill`` span is the stay in
        the runtime that ENDED with its results on the host: from the
        enqueue that preceded its harvest — the next call's when it was
        ``ahead`` of this harvest, this call's own when it was settled at
        once — to the end of the harvest; the host's own work between two
        stays (plan, upload, commit) lies outside every such span, as it
        always did.  ``enqueue_s`` is the call's own hand-over, ``wait_s``
        how long its harvest blocked."""
        tl = self.timeline
        before, self._flight = self._flight, flight
        cause = self._settle_now()
        # a harvest follows the enqueue, of the call before or of this one:
        # one stay in the runtime.  Else (the first call of an idle engine)
        # the call is handed over with the device idle, as its operands
        # were packed: on the phase's ``upload`` segment
        stay = before is not None or cause is not None
        if stay:
            self._enter_runtime((before or flight).name)
        # the step that made the call, not the one that harvests it
        flight.args.update(ahead=int(before is not None),
                           step=self.iterations)
        self._c_calls.inc()
        self._c_calls_ahead.inc(flight.args["ahead"])
        self._launched = True
        enqueued: Dict[str, float] = {}
        with contextlib.nullcontext() if stay else tl.segment(
                f"step.{flight.phase}.upload", self._phase), \
                tl.segment(f"{flight.name}.enqueue", enqueued), ctx:
            out = fn(*flight.held)
        flight.out = out[0]
        self._keep_device(out)
        del out
        if tl.enabled:
            flight.args["enqueue_s"] = enqueued["enqueue_s"]
            if stay:
                self._seg_args.append(enqueued)
        if before is not None:
            self._harvest(before)
        if cause is not None:
            self._settle(cause)

    def _settle(self, cause: Optional[str] = None) -> None:
        """Take the results of the call in flight, if one is, and commit
        them.  ``cause`` says what needed them before the next call was
        enqueued (``EARLY_SETTLE_CAUSES``) and is counted; without one
        this is a step that has nothing to enqueue."""
        flight, self._flight = self._flight, None
        if flight is None:
            return
        if cause is not None:
            self._c_early_settles[cause].inc()
        self._harvest(flight)

    def _harvest(self, flight: _Flight) -> None:
        """``flight``'s tokens onto the host, and their commit: the plain
        path's ONE fence (:meth:`_launch` behind the next call's enqueue,
        :meth:`_settle` wherever else)."""
        tl = self.timeline
        if self._runtime is None:
            self._enter_runtime(flight.name)
        waited: Dict[str, float] = {}
        with tl.segment(f"{flight.name}.wait", waited):
            out = self._split_record(np.asarray(flight.out), flight.shape,
                                     flight.args)
            if flight.name == "spec_round":
                # as the DEVICE walked them: tokens the rows' windows
                # yielded, drafts accepted among them (a row's eos or
                # budget may cut its share at the commit)
                count = out[-self.slots:]
                flight.args.update(
                    emitted=int(count.sum()),
                    accepted=int((count - (count > 0)).sum()))
        if tl.enabled:
            flight.args["wait_s"] = waited["wait_s"]
            self._seg_args.append(waited)
        self._leave_runtime(flight.name, flight.args)
        with tl.segment(f"step.{flight.phase}.commit", self._phase):
            # the call's operands and results are released here, on the
            # commit's account
            flight.held = flight.out = None
            flight.commit(out)

    def _note_step(self, step_args: Dict[str, Any], wall_s: float) -> None:
        """Ring on, after every step: file it under its shape — duration,
        seconds off the CPU outside the in-flight spans, collector seconds
        and the argument dicts its segments are on — and, if it lasted over
        ``STALL_FACTOR`` x that shape's running median, name the stall
        (:meth:`_stall`)."""
        if "iteration" not in step_args:
            return                          # the cancellations emptied it
        own_s = wall_s - step_args["flight_s"]
        own_cpu_s = step_args["cpu_s"] - step_args["flight_cpu_s"]
        row = (wall_s, max(own_s - own_cpu_s, 0.0), step_args["gc_s"],
               self._seg_args)
        shape = self._prefilled
        history = self._step_history[shape]
        over = self._stall_over[shape]
        if over is not None and wall_s > over:
            self._stall(step_args["iteration"], row, history)
        history.append(row)
        seen = self._stall_seen[shape] = self._stall_seen[shape] + 1
        if seen % STALL_REFRESH == 0:
            self._stall_over[shape] = STALL_FACTOR * statistics.median(
                r[0] for r in history)

    def _stall(self, iteration: int, row, history) -> None:
        """A step that stalled: a ``stall`` instant on the ring saying how
        long against what median, which segment grew most against its own
        median, and the cause (``STALL_CAUSES``); a tick of
        ``serving_step_stalls_total{cause}``; a rate-limited warning.  The
        segments are summed here, for the stalled step and the history it
        is held against: a stall is rare and has lost milliseconds."""
        def fields(r):
            wall_s, offcpu_s, gc_s, seg_args = r
            return (wall_s, *(sum(a.get(k + "_s", 0.0) for a in seg_args)
                              for k in SEGMENTS), offcpu_s, gc_s)

        now = dict(zip(_STALL_FIELDS, fields(row)))
        median = dict(zip(_STALL_FIELDS, (
            statistics.median(col) for col in zip(*map(fields, history)))))
        grew = {f: now[f] - median[f] for f in _STALL_FIELDS}
        cause = next(
            (c for c, f in (("offcpu", "offcpu"), ("gc", "gc"),
                            ("device_wait", "wait"))
             if grew[f] > grew["wall"] / 2), "host")
        event = dict(
            iteration=iteration, cause=cause,
            wall_ms=round(now["wall"] * 1e3, 3),
            median_ms=round(median["wall"] * 1e3, 3),
            segment=max(SEGMENTS, key=grew.get),
            offcpu_ms=round(now["offcpu"] * 1e3, 3),
            gc_ms=round(now["gc"] * 1e3, 3),
            wait_ms=round(now["wait"] * 1e3, 3))
        self.timeline.instant("stall", **event)
        self._c_step_stalls[cause].inc()
        t = time.perf_counter()
        if t - self._stall_warned >= STALL_WARN_EVERY_S:
            self._stall_warned = t
            logger.warning(f"ServingEngine: stalled step {event}")

    def drain(self) -> List[_PendingItem]:
        """Quiesce this engine for a replica handoff (router drain
        protocol): preempt every active slot — with the host tier, each
        victim's committed full blocks demote first and its generated
        tokens fold into the resume prompt — then demote the remaining
        prefix-cache content to the host tier, and hand back the whole
        pending queue for re-submission elsewhere
        (``ReplicaRouter._submit_item`` on another replica).  After a
        drain the device pool is fully free; the host tier is the
        replica's exportable session store (``host_chain_export``)."""
        self._settle("drain")
        self._process_cancellations()
        for slot in sorted(self._active,
                           key=lambda s: -self._active[s].admit_seq):
            self._preempt(slot)
        if self._host is not None and self._prefix is not None:
            while self._demote_evict_batch():
                pass
            self._discard_all_staged()
            self._prefetch_gate.clear()
        # parked prefill-complete handoffs leave with the queue (their
        # per-item trace cleanup already ran at extraction)
        items = self.take_handoffs() + self._pending.drain()
        self._blocked_gate = None
        for item in items:
            # the latency span can only finish on the engine that admits
            # the resume; this engine's stamp would dangle forever.  A
            # still-noted flow id (queued, never admitted here) closes
            # NOW — the router starts a fresh flow to the new replica
            self._trace_times.pop(item.req.uid, None)
            fid = self._flow_ids.pop(item.req.uid, None)
            if fid is not None:
                self.timeline.flow_end("route", fid,
                                       uid=str(item.req.uid),
                                       handoff=True)
            self._live_uids.discard(item.req.uid)
        self._g_queue_depth.set(0)
        self.timeline.instant("drain", handoff=len(items),
                              host_blocks_in_use=(
                                  self._host.blocks_in_use
                                  if self._host is not None else 0))
        return items

    def salvage(self) -> List[_PendingItem]:
        """Crash salvage (:meth:`ReplicaRouter.fail` — the hard twin of
        :meth:`drain`): extract every live request's resume context using
        HOST-SIDE bookkeeping only.  No device program runs and nothing
        demotes — the engine is presumed crashed, so its device pool is
        not to be trusted and its host tier is not exportable (survivors'
        tiers are the KV-salvage source; the router pulls from them).
        Active slots fold their already-streamed tokens into the resume
        prompt (the preemption trick, so greedy resume on a survivor is
        token-exact) and release their blocks in the host ownership
        records, leaving the allocator/trie consistent for a later
        restart + readmit.  Items return actives-first in admission
        order, then the pending queue — the same hand-off order
        :meth:`drain` produces.  Deferred cancel flags are honored: a
        cancelled request resolves here instead of re-homing."""
        cancels, self._cancel_flags = self._cancel_flags, set()
        # a call in flight is left where it is: the device is not to be
        # trusted, and its tokens were never streamed — the survivor makes
        # them again (greedy, or the same (seed, count) key)
        self._flight = None
        items: List[_PendingItem] = []
        for slot in sorted(self._active,
                           key=lambda s: self._active[s].admit_seq):
            st = self._active[slot]
            items.append(_PendingItem(
                req=st.req, prior=st.prior + st.out, priority=st.priority,
                slo_class=st.slo_class, eos=st.eos, handle=st.handle))
        self._active.clear()
        for slot in range(self.slots):
            self._release_slot(slot)
        items.extend(self.take_handoffs())
        items.extend(self._pending.drain())
        if self._host is not None:
            self._discard_all_staged()
            self._prefetch_gate.clear()
        self._blocked_gate = None
        out: List[_PendingItem] = []
        for item in items:
            uid = item.req.uid
            self._live_uids.discard(uid)
            self._trace_times.pop(uid, None)
            fid = self._flow_ids.pop(uid, None)
            if fid is not None:
                self.timeline.flow_end("route", fid, uid=str(uid),
                                       salvaged=True)
            if uid in cancels:
                self._c_cancelled.inc()
                self.timeline.instant("cancelled", uid=str(uid),
                                      salvaged=True)
                if item.handle is not None:
                    item.handle._on_cancel()
                continue
            out.append(item)
        self._g_queue_depth.set(0)
        self.timeline.instant("salvage", items=len(out))
        return out

    # ------------------------------------------------ prefill-role handoffs
    def _extract_handoffs(self) -> None:
        """Prefill-role scheduling (``role="prefill"``): pull every slot
        whose prefill just completed OUT of the decode rotation — its
        first token is already streamed (TTFT is this replica's job), its
        committed chain demotes to the host tier, and the request parks
        in ``_handoff_ready`` for the router to re-route to a decode
        worker as an ordinary integrity-checked KV pull.  Runs between
        the prefill and decode dispatches of :meth:`step`, so a prefill
        worker's decode program only ever sees empty/prefilling slots and
        a long-prompt burst never time-shares a decode replica's TPOT.
        The generated-so-far tokens fold into the resume prompt exactly
        like a preemption, so the decode worker's greedy continuation is
        token-exact."""
        done = [s for s, st in self._active.items()
                if st.phase == "decode"]
        for slot in sorted(done, key=lambda s: self._active[s].admit_seq):
            st = self._active.pop(slot)
            nblocks = len(self._held[slot])
            if self._host is not None:
                self._demote_slot_blocks(slot, st)
            self._release_slot(slot)
            self._handoff_ready.append(_PendingItem(
                req=st.req, prior=st.prior + st.out, priority=st.priority,
                slo_class=st.slo_class, eos=st.eos, handle=st.handle))
            uid = st.req.uid
            # the TTFT span closed with the first token; the remaining
            # latency accrues on the decode worker that admits the resume
            # — this replica's stamp and route flow close now, exactly
            # like drain()'s per-item cleanup
            self._trace_times.pop(uid, None)
            fid = self._flow_ids.pop(uid, None)
            if fid is not None:
                self.timeline.flow_end("route", fid, uid=str(uid),
                                       handoff=True)
            self._live_uids.discard(uid)
            self._c_handoffs.inc()
            self.timeline.instant("handoff", uid=str(uid), slot=slot,
                                  blocks=nblocks,
                                  tokens=len(st.prior) + len(st.out))

    def take_handoffs(self) -> List[_PendingItem]:
        """Hand the parked prefill-complete requests to the caller (the
        router's per-step pump) and clear the parking list.  Empty on
        ``role="both"``/``"decode"`` replicas — the list only ever fills
        from :meth:`_extract_handoffs`."""
        items, self._handoff_ready = self._handoff_ready, []
        return items

    # ---------------------------------------------------- router probes/pull
    def affinity_probe(self, tokens) -> Dict[str, int]:
        """Routing probe (read-mostly, O(prompt)): leading full-block
        depth of ``tokens`` resident on this replica — device trie plus
        host-tier continuation — and the load signals the router balances
        on.  Capped at ``len(tokens) - 1`` exactly like admission's own
        lookup, so the reported depth is what an admitted request would
        actually reuse."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        plen = int(tokens.size)
        n_dev = self._prefix.probe(tokens, plen - 1) \
            if self._prefix is not None else 0
        n_host = len(self._host.probe_run(tokens, n_dev, plen - 1,
                                          self.block_size)) \
            if self._host is not None else 0
        return {"device_blocks": int(n_dev), "host_blocks": int(n_host),
                "blocks_in_use": int(self._alloc.blocks_in_use),
                "queue_depth": len(self._pending),
                "active": len(self._active)}

    def demote_chain(self, tokens, max_tokens: Optional[int] = None,
                     start_block: int = 0) -> int:
        """Snapshot the device-trie-resident chain of ``tokens`` into the
        host tier (cross-replica export): the trie keeps its entries and
        blocks — this copies bytes DOWN so ``host_chain_export`` can read
        them (the dedup-by-chain-key rule makes the device/host copy pair
        safe: a later eviction sees the key resident and just frees the
        device block).  ``start_block`` skips blocks the importer already
        holds — a pull for the chain's suffix must not D2H-copy (and
        LRU-churn the arena with) the prefix nobody will read.  Returns
        blocks newly stored."""
        if self._host is None or self._prefix is None:
            return 0
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        mt = int(tokens.size) if max_tokens is None else int(max_tokens)
        blocks = self._prefix.chain_blocks(tokens, mt)
        if len(blocks) <= int(start_block):
            return 0
        keys = chain_keys(tokens, len(blocks), self.block_size)
        pairs = [(b, k) for b, k in
                 list(zip(blocks, keys))[int(start_block):]
                 if not self._host.has(k)]
        if not pairs:
            return 0
        return self._demote_blocks([b for b, _ in pairs],
                                   [k for _, k in pairs])

    def host_chain_export(self, tokens, start_block: int = 0,
                          max_tokens: Optional[int] = None):
        """``(keys, per-block per-leaf byte COPIES, checksums)`` of the
        host-resident run of ``tokens`` from ``start_block`` on — the
        cross-replica KV-pull wire format (``HostBlockStore
        .export_chain`` + ``export_checksums``): the same
        content-addressed chain keys name the blocks on every replica,
        quantized ``{qp, ps}`` records travel as ordinary leaves so int8
        codes and scale rows move together bit-identically, and the
        per-block integrity checksums ride beside the bytes so the
        importer verifies the transfer end-to-end.  Corrupt entries are
        dropped before export (never exported); an armed fault plan may
        raise :class:`~deepspeed_tpu.inference.paged.TransportError`
        here — the router's pull retries with backoff."""
        if self._host is None:
            return [], [], []
        if self._fault_injector is not None:
            self._fault_injector.on_transport("export")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        mt = int(tokens.size) if max_tokens is None else int(max_tokens)
        keys = self._host.probe_run(tokens, start_block, mt,
                                    self.block_size)
        if keys:
            keys = self._verified_keys(keys)
        if keys and self._nvme is not None:
            # spilled entries must climb back into the arena before their
            # bytes can be copied out (verified at the NVMe exit); the
            # watermark budget may truncate a very long run — the importer
            # gets the leading prefix, its tail recomputes or re-pulls
            n_ok = self._host.promote_spilled(keys)
            self._sync_nvme_metrics()
            keys = keys[:n_ok]
        return keys, self._host.export_chain(keys), \
            self._host.export_checksums(keys)

    def host_chain_import(self, keys, blocks, checksums=None) -> int:
        """Store a pulled chain into this replica's host tier (admission
        then promotes it on-device through the ordinary fixed-shape
        scatter path).  With ``checksums`` every arriving block re-hashes
        against the exporter's record and a mismatch stops the import —
        ticked into ``serving_checksum_failures_total``, the chain tail
        recomputes locally.  Returns blocks stored."""
        if self._host is None or not keys:
            return 0
        if self._fault_injector is not None:
            self._fault_injector.on_transport("import")
        before = self._host.checksum_rejects
        n = self._host.import_chain(keys, blocks, checksums=checksums)
        rejects = self._host.checksum_rejects - before
        if rejects:
            self._c_checksum_fail.inc(rejects)
            self.timeline.instant("checksum_fail", op="import",
                                  blocks=rejects)
        self._sync_nvme_metrics()   # imports may push the LRU tail to NVMe
        return n

    # ----------------------------------------------------------- batch serve
    def serve(self, requests: Sequence[Request],
              eos_token_id: Optional[int] = None,
              admission_log: Optional[list] = None,
              step_log: Optional[list] = None,
              debug_checks: Optional[bool] = None,
              profile_dir: Optional[str] = None,
              profile_iters: Optional[int] = None) -> Dict[Any, np.ndarray]:
        """Run a request trace to completion; returns ``uid -> [prompt +
        completion]`` int32 arrays, padded to ``prompt + max_new_tokens``
        with eos back-fill (HF semantics, same as ``generate``).  A thin
        wrapper over the incremental API — ``submit`` everything, loop
        ``step()``, gather handle results — with identical scheduling;
        an empty request list returns ``{}`` without tracing anything.

        ``admission_log``, when given, collects ``(uid, slot)`` in admission
        order — the scheduler-determinism tests read it.  ``step_log``
        collects one dict per iteration (admitted / evicted / blocks_in_use
        per step) for observability.  ``debug_checks`` overrides the
        engine-level flag from here on (ctor docstring): per-iteration
        paged-state audits + strict recompile-sentry enforcement.

        ``profile_dir`` opens a ``jax.profiler`` trace window over this
        call's first ``profile_iters`` scheduler iterations (``None`` =
        the whole call) — the device-level deep dive behind the host-side
        timeline (``dump_trace``).  The window start/stop are themselves
        timeline events, so the two traces line up."""
        if debug_checks is not None:
            self.debug_checks = bool(debug_checks)
            self.sentry.strict = self.debug_checks
        requests = list(requests)
        if not requests:
            return {}
        if self.role != "both":
            raise RuntimeError(
                f"serve() on a role={self.role!r} replica — a "
                "disaggregated worker only runs its half of the pipeline "
                "(handoffs would never complete here); drive it behind a "
                "ReplicaRouter, or build with role='both'")
        if self._pending or self._active:
            raise RuntimeError(
                "serve() on a busy engine — requests are already in "
                "flight; drive submit()/step() instead")
        for r in requests:
            self._validate_request(r)
        uids = [r.uid for r in requests]
        if len(set(uids)) != len(uids):
            raise ValueError("duplicate request uids")
        handles = [self.submit(r, eos_token_id=eos_token_id)
                   for r in requests]
        self._admission_log = admission_log
        self._step_log = step_log
        window = None
        if profile_dir is not None:
            window = ProfilerWindow(profile_dir)
            if window.start():
                self.timeline.instant("profiler_start",
                                      profile_dir=str(profile_dir))
        iter0 = self.iterations
        try:
            while self.step():
                if window is not None and window.active and \
                        profile_iters is not None and \
                        self.iterations - iter0 >= profile_iters:
                    window.stop()
                    self.timeline.instant("profiler_stop")
        finally:
            self._admission_log = None
            self._step_log = None
            if window is not None and window.active:
                window.stop()
                self.timeline.instant("profiler_stop")
        return {h.uid: h.result(timeout=0) for h in handles}

    # ----------------------------------------------------------------- decode
    def _mark_first(self, st: _SlotState) -> None:
        tm = self._trace_times.get(st.req.uid)
        if tm is not None and tm["first"] is None:
            tm["first"] = time.perf_counter()

    def _emit_tokens(self, st: _SlotState, toks) -> None:
        """Per-token streaming + the committed-token counter: every token
        the scheduler commits flows through here exactly once."""
        self._c_gen_tokens.inc(len(toks))
        if st.handle is not None:
            st.handle._on_tokens(toks)

    def _finish_slot(self, slot: int) -> None:
        """Complete a request: build the padded ``[prompt + completion]``
        result (eos back-fill, HF semantics), record latencies and the
        per-request span, release the slot, resolve the handle."""
        st = self._active.pop(slot)
        req = st.req
        gen = np.asarray(st.prior + st.out, np.int32)
        eos_hit = st.eos is not None and gen.size and gen[-1] == st.eos
        out = np.zeros(req.max_new_tokens, np.int32)
        out[:gen.size] = gen
        if eos_hit:
            out[gen.size:] = st.eos        # back-fill (HF semantics)
        result = np.concatenate([req.prompt, out])
        tm = self._trace_times.pop(req.uid, None)
        if tm is not None and tm["first"] is not None:
            done = time.perf_counter()
            ttft = tm["first"] - tm["admit"]
            tpot = ((done - tm["first"]) / (gen.size - 1)) \
                if gen.size > 1 else 0.0
            self._c_finished.inc()
            self._h_ttft.observe(ttft)
            self._h_tpot.observe(tpot)
            # SLO accounting: unclassified traffic lands in "standard"
            # so fleet attainment is never flattered by omission
            self._slo.observe(st.slo_class, ttft, tpot)
            self._latencies.append({
                "uid": req.uid,
                "new_tokens": int(gen.size),
                "ttft_s": ttft,
                "tpot_s": tpot,
            })
            # per-request span on the finishing slot's lane: admission
            # (original — a preemption resume keeps it) to completion
            self.timeline.complete(
                f"req {req.uid}", tm["admit_us"], tid=slot + 1,
                uid=str(req.uid), new_tokens=int(gen.size),
                eos=bool(eos_hit), ttft_s=ttft)
        self._kv(self._release_slot, slot)
        self._live_uids.discard(req.uid)
        if st.handle is not None:
            st.handle._on_finish(result)

    def _run_plain_decode(self, params) -> int:
        """One single-token decode step over every decode-phase slot;
        returns how many slots it advanced (the decode runners all do).
        Every runner times its pieces as segments (``SEGMENTS``): ``plan``
        / ``upload`` / ``commit`` onto ``self._phase``, the argument dict
        of the host phase it runs in, ``enqueue`` / ``wait`` onto its
        in-flight span.

        The call is planned while the one before it may still be in flight
        (:meth:`_launch`): a row whose newest token is on the device only
        (``ahead``) is fed from there (``TOKEN_ON_DEVICE``), at the length
        and the sampler's count that token gives it, and left out if that
        token spends its budget.  Whether it is its ``eos`` the host
        cannot know: such a row rides once more, into a block its slot
        holds, and :meth:`_commit_decode` drops what it made."""
        active = self._active
        seg, phase = self.timeline.segment, self._phase

        def rows():
            return [s for s, st in active.items() if st.phase == "decode"
                    and st.gen_count + st.ahead < st.req.max_new_tokens]

        with seg("step.decode.plan", phase):
            for slot in sorted(rows(), key=lambda s: active[s].admit_seq):
                if slot in active:
                    self._kv(self._ensure_blocks, slot,
                             int(self._lengths[slot]) + 1)
            dec = sorted(rows())
            if not dec:
                return 0
            bt = np.zeros_like(self._tables)
            bt[dec] = self._tables[dec]
            tokens = self._tokens.copy()
            tokens[[s for s in dec if active[s].ahead]] = TOKEN_ON_DEVICE
            counts = self._decode_counts()
            decode_fn = self._get_decode_fn()
            span_kw = {**self._sampler_rows(dec),
                       **self._kv_walk(dec),
                       **self._kv_reach(self._lengths[dec] + 1, at=dec),
                       **self._state_args(len(dec), 0, len(dec)),
                       **self._tail_args(len(dec), 0)}
        with seg("step.decode.upload", phase):
            host, puts = self._host_operands(
                "decode", tokens, self._lengths,
                self._bt(bt, self._live_rows(dec)),
                *((self._window_start,) if self.resident_window_blocks
                  else ()), *self._samp_args(counts))
            fed = [(slot, active[slot]) for slot in dec]
            flight = _Flight("decode", dict(slots=len(dec), **puts,
                                            **span_kw), (self.slots,),
                             functools.partial(self._commit_decode, fed))
            for slot, st in fed:
                self._lengths[slot] += 1   # the fed token will be cached
                st.ahead += 1
            flight.held = (params, self._cache, self._devtok, *host)
            del host
            ctx = self._decode_ctx()
        self._launch(flight, decode_fn, ctx)
        return len(dec)

    def _commit_decode(self, rows, nxt) -> None:
        """The commit loop of :meth:`_run_plain_decode`, when the call's
        tokens are on the host.  A row whose request finished while this
        call was in flight (the token before was its ``eos``) is dropped:
        nothing of it is emitted or counted."""
        active = self._active
        self._c_decode_steps.inc()
        for slot, st in rows:
            st.ahead -= 1
            if active.get(slot) is not st:
                continue
            tok = int(nxt[slot])
            st.out.append(tok)
            self._emit_tokens(st, (tok,))
            self._mark_first(st)
            if (st.eos is not None and tok == st.eos) \
                    or st.gen_count >= st.req.max_new_tokens:
                self._finish_slot(slot)
            else:
                self._tokens[slot] = tok

    def _run_spec_decode(self, params):
        """One speculative draft–verify round over every decode-phase slot.

        Propose K tokens per row (the draft model's one-program K-step
        rollout, or the host-side n-gram lookup), scatter+score the
        K+1-token window ``[pending, d_1..d_K]`` in ONE verify pass at each
        row's own base position, then commit the longest target-matching
        draft prefix plus the target's correction token
        (``spec.greedy_accept`` — token-exact with plain greedy decode).
        Rollback of rejected tokens is just *not advancing* the host
        lengths past the committed value: stale tail KV stays position-
        masked and is overwritten in place by the next round (blocks stay
        allocated, refcounts untouched).  Block demand is capped at each
        request's remaining completion budget (``pos_cap``) — window
        positions past the cap scatter to scratch instead of allocating.
        The model's own module as the proposer: :meth:`_run_self_round`,
        the one of the three whose round rides the call of lookahead.
        """
        if self._self_draft:
            return self._run_self_round(params)
        k = self.spec_tokens
        active = self._active
        seg, phase = self.timeline.segment, self._phase
        with seg("step.decode.plan", phase):
            dec = sorted(
                (s for s, st in active.items() if st.phase == "decode"),
                key=lambda s: active[s].admit_seq)
            for slot in dec:
                if slot in active and active[slot].phase == "decode":
                    st = active[slot]
                    ln = int(self._lengths[slot])
                    cap = max(st.pos_cap, ln + 1)
                    self._kv(self._ensure_blocks, slot,
                             min(ln + k + 1, cap, self._cache_len))
            dec = sorted(s for s, st in active.items()
                         if st.phase == "decode")
            if not dec:
                return 0
            bt = np.zeros_like(self._tables)
            bt[dec] = self._tables[dec]
            counts = self._decode_counts()
            samp = self._samp_args(counts)
        if self._draft is not None:
            draft_fn = self._get_draft_fn()
            with seg("step.decode.upload", phase):
                # the draft never sees the mask matrix (constrained slots
                # accept no draft), so only the five vectors ride
                host, puts = self._host_operands(
                    "draft", self._tokens, self._lengths, bt, *samp[:5])
                args = (self._draft.params, self._dcache, *host)
                flight = self._in_flight("spec_propose", slots=len(dec),
                                         mode="draft", **puts)
            with flight as span_args:
                with seg("spec_propose.enqueue", span_args), self._tp_ctx():
                    drafts, self._dcache = draft_fn(*args)
                with seg("spec_propose.wait", span_args):
                    drafts = np.asarray(drafts)
        else:
            # the n-gram proposer is host work under the documented span
            # name: no program is in flight (nothing is handed over), the
            # device idles through it — planning, by the segments' account
            with self.timeline.span("spec_propose", slots=len(dec),
                                    mode="ngram", puts=0,
                                    operand_bytes=0), \
                    seg("step.decode.plan", phase):
                drafts = np.zeros((self.slots, k), np.int32)
                for slot in dec:
                    st = active[slot]
                    drafts[slot] = self._proposer.propose(
                        np.concatenate([st.prompt_eff,
                                        np.asarray(st.out, np.int32)]))
        with seg("step.decode.plan", phase):
            ids = np.zeros((self.slots, k + 1), np.int32)
            valid = np.zeros(self.slots, np.int32)
            ids[dec, 0] = self._tokens[dec]
            ids[dec, 1:] = drafts[dec]
            valid[dec] = k + 1
            verify_fn = self._get_verify_fn()
        with seg("step.decode.upload", phase):
            host, puts = self._host_operands(
                "verify", ids, bt, self._lengths, valid, *samp)
            args = (params, self._cache, *host)
            flight = self._in_flight(
                "spec_verify", slots=len(dec), window=k + 1, **puts,
                **(self._kv_reach(self._lengths[dec] + k + 1,
                                  np.full(len(dec), k + 1), t=k + 1, at=dec)
                   if self._latent else {}))
        with flight as span_args:
            with seg("spec_verify.enqueue", span_args), self._tp_ctx():
                out = verify_fn(*args)
            with seg("spec_verify.wait", span_args):
                if self.sampling:
                    scored, accept, plain, resid, self._cache = out
                    accept = np.asarray(accept)
                    plain = np.asarray(plain)
                    resid = np.asarray(resid)
                else:
                    scored, self._cache = out
                    accept = plain = resid = None
                scored = np.asarray(scored)
                if self._sparse:
                    scored = self._split_record(
                        scored, (self.slots, k + 1), span_args)
        with seg("step.decode.commit", phase):
            del args, host, out            # on the commit's account
            return self._commit_spec_round(dec, ids, scored, accept, plain,
                                           resid)

    def _run_self_round(self, params) -> int:
        """:meth:`_run_spec_decode`'s round where the proposer is the
        model's own module (:meth:`_get_round_fn`): ONE call, handed over
        and harvested through :meth:`_launch` like a decode step, under ONE
        ``spec_round`` span — and, like a decode step, planned while the
        round before it may still be in flight.

        What the host does not know of such a row (``rounds``) is how many
        tokens, 1..K+1, the round in flight commits for it.  The DEVICE
        knows (:attr:`_devcount`): the row is marked ``behind`` and the
        program adds its count to the base and the sampler's count the host
        gives — the committed ones.  The host plans on BOUNDS.  Blocks are
        reserved for the longest the row can be, ``committed + K + 1``,
        plus the window, under ``pos_cap`` and the cache as ever (a block
        too many for a round, at most).  A row whose budget the calls in
        flight spend FOR CERTAIN — each makes at least one token — sits the
        round out and finishes at their commit; one whose budget they MAY
        spend (``max_new_tokens - gen_count <= K + 1``) rides, as does one
        whose ``eos`` may be among them, and :meth:`_commit_self_round`
        drops what this round made of a row that had ended: the tokens a
        request receives are those of rounds settled one by one.  The
        span's reach (:meth:`_kv_reach`: ``kv_valid`` / ``kv_blocks`` /
        ``kv_pairs`` / ``latent_bytes`` / ``kv_tiles``) is taken at the
        COMMITTED length — up to K + 1 keys a row short of what a behind
        row's window reads, of thousands —; what the selection read
        (``index_keys`` / ``kv_selected`` / ``kv_read``) is counted on the
        device and exact."""
        k = self.spec_tokens
        active = self._active
        seg, phase = self.timeline.segment, self._phase

        def rows():
            return [s for s, st in active.items() if st.phase == "decode"
                    and st.gen_count + st.ahead + st.rounds
                    < st.req.max_new_tokens]

        with seg("step.decode.plan", phase):
            for slot in sorted(rows(), key=lambda s: active[s].admit_seq):
                st = active.get(slot)
                if st is not None:
                    # (read here: an allocation that finds no block settles
                    # the round in flight before it picks a victim)
                    ln = int(self._lengths[slot])
                    cap = max(st.pos_cap, ln + 1)
                    self._kv(self._ensure_blocks, slot,
                             min(ln + (st.rounds + 1) * (k + 1), cap,
                                 self._cache_len))
            dec = sorted(rows())
            if not dec:
                return 0
            bt = np.zeros_like(self._tables)
            bt[dec] = self._tables[dec]
            samp = self._samp_args(self._decode_counts())
            tokens = np.full(self.slots, TOKEN_ON_DEVICE, np.int32)
            named = [s for s in dec if s in self._host_pending]
            tokens[named] = self._tokens[named]
            self._host_pending.difference_update(dec)
            valid = np.zeros(self.slots, np.int32)
            valid[dec] = k + 1
            behind = np.zeros(self.slots, np.int32)
            behind[dec] = [active[s].rounds for s in dec]
            round_fn = self._get_round_fn()
            span_kw = {**self._sampler_rows(dec),
                       **self._kv_reach(self._lengths[dec] + k + 1,
                                        np.full(len(dec), k + 1), t=k + 1,
                                        at=dec)}
        with seg("step.decode.upload", phase):
            host, puts = self._host_operands(
                "verify", tokens, bt, self._lengths, valid, behind, *samp)
            fed = [(slot, active[slot]) for slot in dec]
            flight = _Flight(
                "spec_round", dict(slots=len(dec), window=k + 1,
                                   drafted=k * len(dec), **puts, **span_kw),
                (self.slots * (k + 2),),
                functools.partial(self._commit_self_round, fed),
                phase="decode")
            for _, st in fed:
                st.rounds += 1
            flight.held = (params, self._cache, self._devtok,
                           self._devdraft, self._devcount, *host)
            del host
            ctx = self._decode_ctx()
        self._launch(flight, round_fn, ctx)
        return len(dec)

    def _commit_self_round(self, rows, flat) -> None:
        """The commit of :meth:`_run_self_round`, when the round's emitted
        ids and counts are on the host — as a rule behind the enqueue of
        the NEXT round, which these rows ride already: a row takes its
        1..K+1 tokens, cut at its ``eos`` or where its budget ends (it is
        finished then: what the device left for its next round is never
        read, and what the round already in flight makes of it is dropped
        at that round's commit); a row that goes on advances by the whole
        count — what the device has added to its base already — and has its
        pending token, its draft and that count on the device.  A row whose
        request ended while the round was in flight (the round before cut
        it, a prefill call's first token was its ``eos``) is dropped:
        nothing of it is emitted or counted (the span's ``emitted`` /
        ``accepted`` are the device's walk and include it)."""
        k = self.spec_tokens
        active = self._active
        emitted = flat[:self.slots * (k + 1)].reshape(self.slots, k + 1)
        count = flat[self.slots * (k + 1):]
        self._c_spec_rounds.inc()
        accept_lens, total, drafted = [], 0, 0
        for slot, st in rows:
            st.rounds -= 1
            if active.get(slot) is not st:
                continue
            n = int(count[slot])
            budget = st.req.max_new_tokens - st.gen_count
            # drafts whose verdicts are real: not past the budget
            # (:meth:`_commit_spec_round` has why); accepted of them
            eligible = min(k, budget)
            raw = min(n - 1, eligible)
            drafted += eligible
            self._c_drafted.inc(eligible)
            self._c_accepted.inc(raw)
            self._c_spec_rejected.inc(eligible - raw)
            if eligible:
                self._h_accept_ratio.observe(raw / eligible)
            toks = [int(t) for t in emitted[slot, :min(n, budget)]]
            if st.eos is not None and st.eos in toks:
                toks = toks[:toks.index(st.eos) + 1]
            accept_lens.append(min(n - 1, len(toks)))
            total += len(toks)
            st.out.extend(toks)
            self._emit_tokens(st, toks)
            self._mark_first(st)
            if len(toks) < n or st.gen_count >= st.req.max_new_tokens \
                    or (st.eos is not None and toks[-1] == st.eos):
                self._finish_slot(slot)
            else:
                self._lengths[slot] += n
                self._tokens[slot] = toks[-1]
        self._c_round_rows.inc(len(accept_lens))
        self._c_round_tokens.inc(total)
        self.timeline.instant("spec_accept", accept_lens=accept_lens,
                              drafted=drafted)

    def _commit_spec_round(self, dec, ids, scored, accept, plain,
                           resid) -> int:
        """The commit loop of :meth:`_run_spec_decode`: per slot, the
        longest accepted draft prefix plus the correction token."""
        k = self.spec_tokens
        active = self._active
        self._c_spec_rounds.inc()
        # a draft-model proposer caps acceptance at K-1: the K-th draft's
        # KV was never written to the draft pool, so accepting it would
        # desync the draft's next feed position (n-gram has no such state)
        max_accept = k - 1 if self._draft is not None else k
        accept_lens, drafted = [], 0
        for slot in dec:
            st = active[slot]
            cap = max_accept
            if self._masks is not None and st.req.mask_builder is not None:
                # constrained slots accept 0 drafts per round: the mask
                # row is host-built per emitted token, so only the first
                # window position's (masked) distribution is valid.  The
                # cap-0 stop emits plain[0] — an unconditional draw from
                # the masked target distribution, exact by construction
                # (the unconsumed accept verdict never enters)
                cap = 0
            budget = st.req.max_new_tokens - st.gen_count
            if self.sampling:
                emitted, accepted, finished = rejection_accept(
                    ids[slot].tolist(), accept[slot].tolist(),
                    plain[slot].tolist(), resid[slot].tolist(), cap,
                    st.eos, budget)
                verdict = lambda i: bool(accept[slot][i])  # noqa: E731
            else:
                emitted, accepted, finished = greedy_accept(
                    ids[slot].tolist(), scored[slot].tolist(), cap,
                    st.eos, budget)
                verdict = lambda i: int(ids[slot][i + 1]) == \
                    int(scored[slot][i])                   # noqa: E731
            # acceptance telemetry counts PRE-truncation verdicts over
            # the drafts whose verdicts are real: cap-ineligible drafts
            # (the draft-model K-th, the whole window on constrained
            # slots) and positions past the completion budget (scratch-
            # routed, garbage logits) are excluded rather than counted
            # as rejections — eos/budget-truncated rounds would
            # otherwise read artificially rejection-heavy
            eligible = min(k, cap, budget)
            raw = 0
            while raw < eligible and verdict(raw):
                raw += 1
            self._c_drafted.inc(eligible)
            self._c_accepted.inc(raw)
            self._c_spec_rejected.inc(eligible - raw)
            if eligible:
                self._h_accept_ratio.observe(raw / eligible)
            accept_lens.append(accepted)
            drafted += eligible
            st.out.extend(emitted)
            self._emit_tokens(st, emitted)
            self._mark_first(st)
            if finished:
                self._finish_slot(slot)
            else:
                # commit = pending + accepted drafts now durable in-cache;
                # the correction token becomes the new pending feed
                self._lengths[slot] += accepted + 1
                self._tokens[slot] = emitted[-1]
        # (``drafted``: the ELIGIBLE drafts, as ``stats()`` counts them)
        self.timeline.instant("spec_accept", accept_lens=accept_lens,
                              drafted=drafted)
        return len(dec)

    # ---------------------------------------------------------------- prefill
    def _run_prefill(self, params) -> int:
        """Advance prefilling slots, in admission order ``prefill_batch``
        rows a group and one call a group; a call is a BUDGET of
        ``prefill_batch * prefill_chunk`` tokens at the rung of the ladder
        that carries the most REAL tokens (:meth:`_rung_for`), and the rows
        of the group a narrower rung cannot hold wait their turn; pad rows
        write to scratch.  Returns the number of prefill calls made."""
        active = self._active
        with self.timeline.segment("step.prefill.plan", self._phase):
            pre = [s for s, st in sorted(active.items(),
                                         key=lambda kv: kv[1].admit_seq)
                   if st.phase == "prefill"]
            if not pre:
                return 0
            groups = []
            ready = []
            for slot in pre:
                if slot not in active:
                    continue               # preempted by an earlier alloc
                st = active[slot]
                v = min(self.prefill_chunk, st.plen_eff - st.base)
                if self._kv(self._ensure_blocks, slot, st.base + v):
                    ready.append(slot)
            for i in range(0, len(ready), self.prefill_batch):
                group = [s for s in ready[i:i + self.prefill_batch]
                         if s in active]
                if group:
                    groups.append(group)

        calls = 0
        for group in groups:
            group = [s for s in group if s in active]
            if group and self._run_prefill_group(group, params):
                calls += 1
        return calls

    def _rung_for(self, group):
        """The shape of ``group``'s call and who runs in it: ``(rung,
        rows)``.  The rows take TURNS — whoever has been passed over most
        calls in a row first (``waited``), admission order between equals —
        and every rung ``(j, w)`` is reckoned by what its call would carry,
        ``min(w, prompt left)`` summed over the first ``j`` rows in turn:
        the rung that carries most runs, on a tie the one with more rows.
        A group of two or three long rows so gives one of them the whole
        budget (``[1, prefill_batch * prefill_chunk]``) call after call, a
        group that is full, or nearly done, shares ``[prefill_batch,
        prefill_chunk]``, and a ladder of one rung has nothing to choose.

        Waiting is bounded by construction: a row that is passed over moves
        ahead of every row that ran, so a group of ``n`` passes a row over
        at most ``n - 1`` calls in a row; and because a group's members
        change as rows finish around it, a rung is not eligible unless it
        runs every row that has waited ``prefill_batch - 1`` calls (the
        first rung holds any group): no ready row is passed over more than
        ``prefill_batch - 1`` calls in a row.  ``group`` is, and ``rows``
        come back, in admission order."""
        active = self._active
        turn = sorted(group, key=lambda s: -active[s].waited)
        left = [active[s].plen_eff - active[s].base for s in turn]
        must = sum(active[s].waited >= self.prefill_batch - 1 for s in turn)
        rung = max((r for r in self._rungs if r[0] >= must),
                   key=lambda r: (sum(min(r[1], n) for n in left[:r[0]]),
                                  r[0]))
        runs = set(turn[:rung[0]])
        return rung, [s for s in group if s in runs]

    def _run_prefill_group(self, group, params) -> bool:
        """One prefill call for ``group``'s ready rows, at the shape
        ``[rows, width]`` and for the rows :meth:`_rung_for` names: each
        advances its slot by ``min(width, remaining prompt)`` tokens from
        its own base; the others keep their blocks and their phase and wait
        (``waited``).  Rows whose window reaches the last prompt token
        yield that slot's first generated token (logits are gathered per
        row at ``valid - 1``).  False if no row was left to run (each lost
        its blocks to an earlier one)."""
        active = self._active
        seg, phase = self.timeline.segment, self._phase
        with seg("step.prefill.plan", phase):
            ready = group
            rung, group = self._rung_for(ready)
            j, width = rung
            if width > self.prefill_chunk:
                # the blocks of the wider rows (:meth:`_run_prefill` made
                # sure of a ``prefill_chunk`` each); a later row may lose
                # its own to an earlier one's
                for slot in group:
                    st = active.get(slot)
                    if st is not None:
                        self._kv(self._ensure_blocks, slot, st.base + min(
                            width, st.plen_eff - st.base))
                group = [s for s in group if s in active]
                if not group:
                    return False
            for slot in ready:
                if slot in active:
                    st = active[slot]
                    st.waited = 0 if slot in group else st.waited + 1
            ids = np.zeros((j, width), np.int32)
            bt = np.zeros((j, self._nbper), np.int32)
            base = np.zeros(j, np.int32)
            valid = np.zeros(j, np.int32)
            rows = []
            for row, slot in enumerate(group):
                st = active[slot]
                v = min(width, st.plen_eff - st.base)
                ids[row, :v] = st.prompt_eff[st.base:st.base + v]
                bt[row] = self._tables[slot]
                base[row] = st.base
                valid[row] = v
                rows.append((slot, v))
            prefill_fn = self._get_prefill_fn(rung)
            span_kw = {
                "width": width, "rows": len(group), "ready": len(ready),
                "shape": self._rung_name(rung), "tokens": int(valid.sum()),
                "slots": list(map(int, group)),
                # blocks the rows' reads walk: cdiv(base + valid, bs) each
                "kv_blocks": int(self._blocks_for(base + valid).sum()),
                **self._sampler_rows(group),
                **self._kv_reach((base + valid)[:len(group)],
                                 valid[:len(group)], t=width),
                **self._state_args(
                    len(group), int((base[:len(group)] == 0).sum()),
                    int(valid.sum())),
                **self._tail_args(
                    len(group), int((base[:len(group)] == 0).sum()))}
        if not self._prefill_warm:
            self._warm_prefill(params)
        with seg("step.prefill.upload", phase):
            operands = [ids,
                        self._bt(bt, list(group) + [-1] * (j - len(group))),
                        base, valid]
            if self.resident_window_blocks:
                # per-ROW window starts (prefill batches rows from
                # arbitrary slots); pad rows stay 0 = fully visible
                ws = np.zeros(j, np.int32)
                ws[:len(group)] = self._window_start[list(group)]
                operands.append(ws)
            # where each row's token goes in the device-resident vector;
            # a pad row's slot is out of range
            at = np.full(j, self.slots, np.int32)
            at[:len(group)] = group
            if self._self_draft:
                # the token after each row's window (-1: the prompt ends in
                # it) and where in it the row's draft is read: its last
                # real position — the one before, for a sampled resume,
                # which backs up one position (:meth:`_advance_prefill_rows`)
                nxt = np.full(j, -1, np.int32)
                draft_at = np.maximum(valid - 1, 0)
                for row, (slot, v) in enumerate(rows):
                    st = active[slot]
                    if st.base + v < st.plen_eff:
                        nxt[row] = st.prompt_eff[st.base + v]
                    elif self.sampling and st.prior and st.req.sampled:
                        draft_at[row] = max(v - 2, 0)
                operands += [nxt, draft_at]
            host, puts = self._host_operands(
                self._prefill_program(rung), *operands, at,
                *self._samp_args_rows(group, j))
            args = self._prefill_args(params, host)
            del host
            flight = _Flight(
                "prefill", dict(**puts, **span_kw), (j,),
                functools.partial(self._commit_prefill_group, rung,
                                  len(group), int(valid.sum()),
                                  int(len(ready) > j),
                                  self._advance_prefill_rows(rows)))
            flight.held = args
            del args
            ctx = self._prefill_ctx()
        self._launch(flight, prefill_fn, ctx)
        return True

    def _advance_prefill_rows(self, rows):
        """What is certain of a prefill call as it is enqueued: each row's
        slot has its chunk cached; a row that reached its last prompt token
        is a decode row from here, its first token ``ahead``.  Returns
        those rows as ``(row, slot, state, emits)``, for the commit."""
        done = []
        for row, (slot, v) in enumerate(rows):
            st = self._active[slot]
            st.base += v
            if st.base < st.plen_eff:
                continue                   # more chunks to go
            st.phase = "decode"
            emits = not (self.spec_tokens and self.sampling and st.prior
                         and st.req.sampled)
            if emits:
                self._lengths[slot] = st.plen_eff
                st.ahead += 1
            else:
                # spec-sampled RESUME: the original stream's token at
                # emission index len(prior) came out of a verify round
                # (accept/residual salts), not the prefill TOKEN salt —
                # so don't emit here.  Back up one position instead: feed
                # the last resumed token as the pending window head with
                # lengths = plen_eff - 1 (the verify scatter rewrites
                # that position's KV with identical values), and the next
                # round starts at count len(prior) — the exact boundary
                # the original round structure had, so replay is
                # round-identical and token-exact
                self._tokens[slot] = int(st.prompt_eff[-1])
                self._lengths[slot] = st.plen_eff - 1
                self._host_pending.add(slot)
            done.append((row, slot, st, emits))
        return done

    def _commit_prefill_group(self, rung, nrows, tokens, turn, done,
                              first) -> None:
        """The commit loop of :meth:`_run_prefill_group`, when the call's
        tokens are on the host: a row that reached its last prompt token
        (``done``, :meth:`_advance_prefill_rows`) registers its full
        blocks with the trie and emits its first token.  ``rung`` is the
        call's shape, ``tokens`` the real tokens of its ``nrows`` rows,
        ``turn`` whether ready rows of its group waited."""
        active = self._active
        width = rung[1]
        self._c_prefill_shapes[rung].inc()
        self._c_prefill_turns.inc(turn)
        self._c_prefill_tokens.inc(tokens)
        self._c_prefill_budget.inc(rung[0] * width)
        if self.sp_degree > 1:
            nbytes = sp_attention.alltoall_bytes(
                int(self._pool_shape[0]), nrows, width,
                getattr(self.engine.module.model_config, "num_heads",
                        int(self._pool_shape[2])),
                int(self._pool_shape[4]),
                jnp.dtype(self.engine._config.jnp_dtype).itemsize,
                self.sp_degree)
            self._c_sp_a2a_bytes.inc(nbytes)
            self.timeline.instant("sp_prefill", width=width,
                                  rows=nrows, bytes=nbytes,
                                  sp=self.sp_degree)
        self._c_prefill_calls.inc()
        for row, slot, st, emits in done:
            st.ahead -= emits
            if active.get(slot) is not st:
                continue
            if self._prefix is not None:
                # cache the prompt's FULL blocks (the trailing partial block
                # will also hold generated tokens — never shared)
                nfull = st.plen_eff // self.block_size
                if self.resident_window_blocks:
                    # window slides during prefill punch a scratch hole in
                    # the table — only the leading still-resident run is a
                    # valid trie chain (the demoted middle lives host-side
                    # under its chain keys, not in the trie)
                    run = 0
                    trow = self._tables[slot]
                    while run < nfull and int(trow[run]) != SCRATCH_BLOCK:
                        run += 1
                    nfull = run
                if nfull:
                    self._kv(self._prefix.register, st.prompt_eff,
                             self._tables[slot, :nfull], self._alloc)
            if not emits:
                continue
            tok = int(first[row])
            st.out.append(tok)
            self._emit_tokens(st, (tok,))
            self._mark_first(st)
            self._tokens[slot] = tok
            if (st.eos is not None and tok == st.eos) \
                    or st.gen_count >= st.req.max_new_tokens:
                self._finish_slot(slot)

    # ------------------------------------------------------------------ stats
    def resolved_config(self) -> Dict[str, Any]:
        """The engine's resolved serving knobs as a **round-trippable**,
        JSON-able ``init_serving`` kwargs dict: ``init_serving(model,
        **srv.resolved_config())`` rebuilds a behaviorally identical
        engine (auto knobs — ``shard_kv``, ``num_blocks``, SLO targets —
        come back resolved, so the rebuilt engine does not depend on the
        defaults in force when this one was built).  This is what
        autotuner trials and ``best_config.json`` persist so a winning
        config reproduces from artifacts alone.

        Not captured: the wrapped ``init_inference`` engine itself (model,
        params, dtype, quant group sizes) and a ``draft`` model object —
        a draft-model speculative engine round-trips to the n-gram
        proposer at the same ``spec_tokens`` (``draft="self"``, the model's
        own module, is a word and comes back).
        """
        return {**options.resolved(self),
                **({"draft": options.SELF_DRAFT} if self._self_draft
                   else {}), "topology": self.tp_degree}

    def _kv_footprint(self) -> Dict[str, Any]:
        """KV memory accounting: pool shape, total logical bytes (quant-
        adjusted — int8 codes + the scale table when ``kv8``), and each
        chip's share — ``total / tp`` when the pool is head-sharded, the
        whole pool when replicated (the pool replicates across every other
        mesh axis, so tp is the only divisor; the scale table carries the
        head dim too, so it shards with the codes)."""
        def _bytes(tree):
            return int(sum(x.size * x.dtype.itemsize
                           for x in jax.tree_util.tree_leaves(tree)))

        # (a cache tree with no paged leaf has no pool: its bytes are
        # ``stats()["kv_state"]``'s)
        total = _bytes(self._cache) if self._paged else 0
        scale_bytes = int(sum(
            leaf["ps"].size * leaf["ps"].dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(
                self._cache, is_leaf=paged_kv.is_quantized_pool)
            if paged_kv.is_quantized_pool(leaf)))
        out = {
            "tp_degree": self.tp_degree,
            "kv_sharded": self.kv_sharded,
            "quantize": self.quantize,
            "kv_dtype": self._kv_dtype,
            "weight_quant": self.weight_quant,
            "kv_scale_bytes": scale_bytes,
            "kv_pool_shape": list(self._pool_shape),
            "kv_pool_bytes": total,
            # dp_tp: the block dim shards over dp too, so each chip holds
            # total / (dp * tp) — the same per-chip bytes as one tp-only
            # replica serving 1/dp of the load
            "kv_pool_bytes_per_chip": total //
            (self.dp_degree * (self.tp_degree if self.kv_sharded else 1)),
        }
        if self._dcache is not None:
            dtotal = _bytes(self._dcache)
            out["draft_pool_bytes"] = dtotal
            out["draft_pool_bytes_per_chip"] = dtotal // \
                (self.tp_degree if self._dcache_sharded else 1)
        return out

    def _kv_kinds(self) -> Dict[str, Any]:
        """``stats()["kv_kinds"]`` (a model with window layers)."""
        def kind(alloc, layers, table_width, peak):
            return {"layers": layers, "num_blocks": alloc.num_blocks,
                    "blocks_in_use": alloc.blocks_in_use,
                    "peak_blocks_in_use": peak, "table_width": table_width}

        if self._self_draft and not (self._rowed or self._windows):
            # ONE kind of block: the model's layers and, behind them in the
            # same leaves and under the same table, its drafting module's
            draft = int(self._self_draft["layers"])
            return {"full": kind(self._alloc,
                                 int(self._pool_shape[0]) - draft,
                                 self._nbper, self._full_peak),
                    "draft": {"layers": draft, "table": "full",
                              "depth": int(self._self_draft["depth"])},
                    "expert_rows_absent": self._rows_absent}
        if self._rowed:
            # the paged kind beside the row-indexed one (which has no blocks)
            paged = "latent" if self._latent else "full"
            rowed = "state" if self._state else "tails"
            return {**({paged: kind(self._alloc, int(self._pool_shape[0]),
                                    self._nbper, self._full_peak)}
                       if self._paged else {}),
                    rowed: {"layers": self._rowed["layers"],
                            "slots": self.slots,
                            "bytes": self._state_bytes()},
                    "expert_rows_absent": self._rows_absent,
                    "refused": list(self._refusals[rowed])}
        layers = self._windows["layers"]
        own = {}
        if "token_width" in self._windows:
            # a window kind whose token is of its own width, in blocks of
            # its own bytes (``_window_block``)
            item = jnp.dtype(self._kv_dtype).itemsize
            own = {"block_size": self._ring.block_size,
                   "token_bytes": self._windows["token_width"] * item}
        return {
            "window": self._windows["window"],
            "full": kind(self._alloc, layers["full"], self._nbper,
                         self._full_peak),
            "sliding": {**kind(self._ring.alloc, layers["sliding"],
                               self._ring.width, self._ring.peak),
                        "released": self._ring.released, **own},
            **self._window_totals,
            "expert_rows_absent": self._rows_absent,
            "refused": list(self._refusals["window"])}

    def _state_bytes(self) -> int:
        """Bytes of the row-indexed leaves (a state and its companions, or
        tails), all slots."""
        return int(sum(self._cache[k].size * self._cache[k].dtype.itemsize
                       for k in ROW_LEAVES if k in self._cache))

    def _kv_state(self) -> Dict[str, Any]:
        """``stats()["kv_state"]`` (a model with a recurrent state a row)."""
        nbytes = self._state_bytes()
        return {"kind": "state", "layers": self._state["layers"],
                "slots": self.slots, "bytes": nbytes,
                "bytes_per_slot": nbytes // self.slots,
                "leaves": {k: list(self._cache[k].shape)
                           for k in ROW_LEAVES if k in self._cache},
                self._state["bodies"]: dict(
                    self._program_meta.get("state_bodies", {})),
                **self._state_totals,
                "refused": list(self._refusals["state"])}

    def _kv_tails(self) -> Dict[str, Any]:
        """``stats()["kv_tails"]`` (a model whose K/V writer reads the
        token before: tails a row beside the paged pool)."""
        nbytes = self._state_bytes()
        return {"kind": "tails", "layers": self._tails["layers"],
                "slots": self.slots, "bytes": nbytes,
                "bytes_per_slot": nbytes // self.slots,
                "leaves": {k: list(self._cache[k].shape)
                           for k in self._tails["taps"]},
                "taps": dict(self._tails["taps"]),
                **self._tail_totals,
                "refused": list(self._refusals["tails"])}

    def _kv_latent(self) -> Dict[str, Any]:
        """``stats()["kv_latent"]`` (a model with latent attention)."""
        lanes = int(self._pool_shape[4])
        item = self._latent_token_bytes // self._latent["width"]
        return {
            "kind": "latent", "layers": int(self._pool_shape[0]),
            # values a token a layer, and the lanes the pool gives them
            "token_width": self._latent["width"], "pool_width": lanes,
            "token_bytes": self._latent_token_bytes,
            "block_size": self.block_size,
            "block_bytes": self.block_size * lanes * item,
            "latent_attn": dict(self._program_meta.get("latent_attn", {})),
            # blocks a loop iteration of each program's walk lands and
            # attends (its spans carry ``kv_blocks`` / ``kv_tiles`` /
            # ``kv_first_tiles_ahead``)
            "tile_blocks": {
                "decode": self._latent_walk(1)[1],
                **({"verify": self._latent_walk(self.spec_tokens + 1)[1]}
                   if self.spec_tokens else {}),
                "prefill": {self._rung_name(rung): self._latent_walk(
                    rung[1])[1] for rung in self._rungs}},
            **self._latent_totals,
            "refused": list(self._refusals["latent"])}

    def _latency_stats(self) -> Dict[str, Any]:
        """TTFT/TPOT percentiles over every finished request (cumulative
        across serve calls, like the other counters) — read from the
        registry's streaming histograms: bounded memory, no re-sort, and
        still ``None`` before the first finished request."""
        out: Dict[str, Any] = {
            "requests_finished": int(self._c_finished.value)}
        for key, hist in (("ttft", self._h_ttft), ("tpot", self._h_tpot)):
            for q in (50, 95):
                out[f"{key}_p{q}_s"] = hist.quantile(q / 100.0)
        return out

    def stats(self) -> Dict[str, Any]:
        """Serving-loop observability: compile probe, prefix-cache hit
        rate, block occupancy, admission/eviction counters, per-request
        latency percentiles, the KV memory footprint (pool shape, total
        bytes, bytes per chip under tp sharding), and — in speculative
        mode — draft/accept counters and the acceptance rate.

        Every counter/latency value is a view over ``self.metrics``
        (``telemetry/``) — ``metrics.prometheus_text()`` and
        ``metrics.snapshot()`` expose the same data for scrapes and
        bench artifacts; the key set here is stable across PRs."""
        self._g_blocks_in_use.set(self._alloc.blocks_in_use)
        self._g_free_blocks.set(self._alloc.free_blocks)
        if self._host is not None:
            self._g_host_blocks_in_use.set(self._host.blocks_in_use)
        if self._nvme is not None:
            self._g_nvme_in_use.set(self._host.nvme_blocks_in_use)
        st = {
            "compile_count": self.compile_count,
            "compile_budget": self.compile_budget,
            "debug_checks": self.debug_checks,
            "invariant_checks_run": self.invariant_checks_run,
            "retraces_observed": self.sentry.retraces_observed,
            # process-wide, cumulative since the listener was installed
            # (the wrapped engine's construction installs it)
            "backend_compiles": backend_compiles(),
            # the process's start-up ring in numbers: seconds by phase and
            # by program, what else JAX built, what the compile cache
            # answered (telemetry/trace.py setup_summary)
            "setup": trace_mod.setup_summary(),
            "iterations": self.iterations,
            "decode_steps": self.decode_steps,
            "moe_expert_rows": int(self._c_moe_rows.value),
            "moe_experts_touched": int(self._c_moe_touched.value),
            "engine_mode": self.engine_mode,
            "prefill_calls": self.prefill_calls,
            # the ladder (every rung is a built program): calls by their
            # rows x width, and the real prompt tokens of all calls over
            # the tokens they had room for (None before the first)
            "prefill_shapes": {self._rung_name(rung): int(c.value)
                               for rung, c in self._c_prefill_shapes.items()},
            "prefill_fill": self._c_prefill_tokens.value
            / self._c_prefill_budget.value
            if self._c_prefill_budget.value else None,
            # calls in which ready rows of the group waited their turn: the
            # rung that carried most held fewer rows than the group
            "prefill_turns": int(self._c_prefill_turns.value),
            # the read the prefill program was traced with (None before its
            # first call): "paged_prefill_attn" on a TPU, "gather" on a CPU
            "prefill_attn": self._program_meta.get("prefill_attn"),
            # the tile of the decode / verify walk at this pool's shapes and
            # the grid steps its copies run ahead (None for a latent pool);
            # the ``decode`` spans carry ``kv_blocks``, ``kv_tiles`` and
            # ``kv_first_tiles_ahead``
            "decode_attn": self._decode_attn and dict(self._decode_attn),
            # how each built program picks its tokens: "argmax" (greedy-only
            # engine) or how ops/sampling.py finds the filter's thresholds
            "sampler": dict(self._program_meta.get("sampler", {})),
            # per built program, the ONE host buffer a call carries: its
            # fields, its bytes, and the host arrays a call hands over (the
            # buffer, and the mask matrix beside it where one rides)
            "operands": {
                name: {"fields": len(lay.fields), "bytes": lay.nbytes,
                       "puts": 2 if self.logit_masks and name != "draft"
                       else 1}
                for name, lay in self._layouts.items()},
            # the one call of lookahead (:meth:`_launch`): decode and
            # prefill calls enqueued, of them while the call before was
            # still in flight, and the calls settled early by what needed
            # their results (``EARLY_SETTLE_CAUSES``)
            "lookahead": {
                "calls": int(self._c_calls.value),
                "ahead": int(self._c_calls_ahead.value),
                "early": {cause: int(c.value) for cause, c
                          in self._c_early_settles.items() if c.value}},
            # a learned-sparse-attention model: what each program's
            # selection was traced with, and the totals of the spans'
            # counters (:meth:`_split_record`); None for any other model
            "sparse_attn": {**self._program_meta.get("sparse_attn", {}),
                            **self._sparse_totals} if self._sparse else None,
            # a model that mixes sliding-window and full layers: both pools
            # by kind, the window blocks released behind the rows, the
            # spans' reach counters summed, and what such a model is
            # refused; None for any other model
            "kv_kinds": self._kv_kinds()
            if self._windows or self._rowed or self._self_draft else None,
            # a model with a recurrent state a row: its leaves, their bytes
            # (whatever the rows' lengths), the resets, which body each
            # program's recurrence lowered to and what such a model is
            # refused; None for any other model
            "kv_state": self._kv_state() if self._state else None,
            # a model with tails a row beside its paged pool: the leaves,
            # their bytes, the taps, the resets, the bytes the calls moved
            # and what such a model is refused; None for any other model
            "kv_tails": self._kv_tails() if self._tails else None,
            # a model with latent attention: the pool's kind, a token's
            # width and bytes, the block, what each program's read was
            # traced with, the spans' counters summed, and what such a
            # model is refused; None for any other model
            "kv_latent": self._kv_latent() if self._latent else None,
            "admitted": self.admitted,
            "evicted": self.preempted,
            "cancelled": int(self._c_cancelled.value),
            "queue_depth": len(self._pending),
            "generated_tokens": int(self._c_gen_tokens.value),
            "prompt_tokens": self.prompt_tokens,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            # the prompt tokens the trie was asked about: every admission's
            "prefix_query_tokens": self.prompt_tokens
            if self._prefix is not None else 0,
            "prefix_cache_hit_rate": (
                self.prefix_hit_tokens / self.prompt_tokens
                if self.prompt_tokens else 0.0),
            "prefix_cache_entries": len(self._prefix)
            if self._prefix is not None else 0,
            "prefix_cache_evictions": self._prefix.evictions
            if self._prefix is not None else 0,
            "blocks_in_use": self._alloc.blocks_in_use,
            "free_blocks": self._alloc.free_blocks,
            "num_blocks": self._alloc.num_blocks,
            "block_size": self.block_size,
            "speculative": (
                None if not self.spec_tokens else
                f"draft:{self._draft.module.name}" if self._draft
                else "self" if self._self_draft else "ngram"),
            "spec_tokens": self.spec_tokens,
            "spec_rounds": self.spec_rounds,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "acceptance_rate": (self.accepted_tokens / self.drafted_tokens
                                if self.drafted_tokens else 0.0),
            # a self-drafting engine: tokens a decoding row commits a
            # round (1 .. spec_tokens + 1); 0.0 before the first
            "tokens_per_round": (
                self._c_round_tokens.value / self._c_round_rows.value
                if self._c_round_rows.value else 0.0),
            # sampling stack (sampling=False: flags off, zeros — schema
            # stays stable)
            "sampling": self.sampling,
            "logit_masks": self.logit_masks,
            "sampled_requests": int(
                self._c_sampled["sampled"].value
                + self._c_sampled["constrained"].value),
            "spec_draft_rejected": int(self._c_spec_rejected.value),
            # long-context lane (sp=1 / window off: 1-and-zeros — schema
            # stays stable)
            "sp": self.sp_degree,
            "resident_window_blocks": self.resident_window_blocks,
            "context_window_slides": int(self._c_window_slides.value),
            "sp_alltoall_bytes": int(self._c_sp_a2a_bytes.value),
            # tiered KV (host_blocks=0: zeros — schema stays stable)
            "host_blocks": self.host_blocks,
            "host_blocks_in_use": self._host.blocks_in_use
            if self._host is not None else 0,
            "host_pool_bytes": self._host.arena_bytes
            if self._host is not None else 0,
            "swap_in": int(self._c_swap_in.value),
            "swap_out": int(self._c_swap_out.value),
            "swap_bytes": int(self._c_swap_bytes.value),
            "prefetch_misses": int(self._c_prefetch_miss.value),
            "prefetch_wait_p50_s": self._h_prefetch_wait.quantile(0.50),
            "prefetch_wait_p95_s": self._h_prefetch_wait.quantile(0.95),
            "resume_recompute_tokens": int(self._c_resume_recompute.value),
            # disaggregated serving + NVMe third tier (role="both" /
            # nvme_blocks=0: "both" and zeros — schema stays stable)
            "role": self.role,
            "handoffs": int(self._c_handoffs.value),
            "nvme_blocks": self.nvme_blocks,
            "nvme_blocks_in_use": self._host.nvme_blocks_in_use
            if self._host is not None else 0,
            "nvme_spills": self._host.nvme_spills
            if self._host is not None else 0,
            "nvme_loads": self._host.nvme_loads
            if self._host is not None else 0,
            # timeline ring health (telemetry/trace.py): dropped > 0 means
            # the ring wrapped — raise trace_capacity for longer history
            "trace_capacity": self.timeline.capacity,
            "trace_events": len(self.timeline),
            "trace_events_dropped": self.timeline.dropped,
            # round-trippable init_serving kwargs (autotuner trials and
            # bench JSONs reproduce the engine from artifacts alone)
            "config": self.resolved_config(),
        }
        st.update(self._kv_footprint())
        st.update(self._latency_stats())
        return st


ServingEngine.__init__.__signature__ = options.signature(
    inspect.Parameter("self", inspect.Parameter.POSITIONAL_OR_KEYWORD),
    inspect.Parameter("engine", inspect.Parameter.POSITIONAL_OR_KEYWORD))
