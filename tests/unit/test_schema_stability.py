"""Telemetry schema stability: the observable dict surfaces —
``ServingEngine.stats()``, ``ReplicaRouter.stats()`` (+ per-replica
rows), and ``slo_report()`` — are PINNED key-for-key.

Dashboards, the bench JSON artifacts, and every PR 2–11 test read these
dicts by key; a silently dropped or renamed key is a breaking API change
nothing else would catch until a dashboard 404s.  The frozen sets below
are the contract: every pre-existing key must stay byte-identical
(the PR 12 acceptance criterion), and a NEW key is added here
deliberately, in the same PR that introduces it.
"""

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.serving import ReplicaRouter
import pytest


@pytest.fixture(scope="module")
def served():
    cfg = gpt2.GPT2Config.tiny(max_seq_len=128)
    deepspeed_tpu.comm.reset_topology()
    engine = deepspeed_tpu.init_inference(
        gpt2.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}})
    srv = ServingEngine(engine, slots=2, max_seq_len=64, block_size=8,
                        prefill_chunk=16)
    router = ReplicaRouter([srv])
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 9 + i),
                    max_new_tokens=3) for i in range(3)]
    router.serve(reqs)
    return srv, router


#: ServingEngine.stats() — the PR 2–11 key set, frozen byte-identical,
#: + PR 13's "config" (the round-trippable init_serving kwargs sub-dict
#: autotuner trials and bench JSONs reproduce engines from)
ENGINE_STATS_KEYS = frozenset({
    "acceptance_rate", "accepted_tokens", "admitted", "backend_compiles",
    "block_size", "blocks_in_use", "cancelled", "compile_budget",
    "compile_count", "config", "debug_checks", "decode_steps",
    "drafted_tokens", "engine_mode",
    "evicted", "free_blocks", "generated_tokens",
    "host_blocks",
    "host_blocks_in_use", "host_pool_bytes",
    "invariant_checks_run",
    "handoffs",
    "iterations", "kv_dtype", "kv_pool_bytes", "kv_pool_bytes_per_chip",
    "kv_pool_shape", "kv_scale_bytes", "kv_sharded",
    # PR 31: which read the prefill program was traced with
    "prefill_attn",
    # PR 45: the tile of the decode / verify walk at the pool's shapes
    "decode_attn",
    # PR 33: how each built program picks its tokens
    "sampler",
    # PR 38: per built program, the one host buffer a call carries
    "operands", "lookahead",
    # PR 32: a learned-sparse-attention model's selection paths + counters
    # (None for any other model)
    "sparse_attn",
    # PR 34: a model that mixes sliding-window and full layers: its two
    # pools by kind, the reach counters, the refusals (None otherwise)
    "kv_kinds",
    # PR 39: a model with latent attention: the pool's kind, a token's
    # width and bytes, the block, the reads' paths, the counters, the
    # refusals (None otherwise)
    "kv_latent",
    # PR 51: a model with a recurrent state a slot: its leaves, their bytes,
    # the resets, which body each program's delta rule lowered to, the
    # refusals (None otherwise)
    "kv_state",
    # PR 66: a model with tails a slot beside its paged pool: the leaves,
    # their bytes, the taps, the resets, the refusals (None otherwise)
    "kv_tails",
    # PR 53: the process's start-up ring in numbers
    # (telemetry/trace.py setup_summary)
    "setup",
    # PR 28: routed (token, expert) rows and experts touched, summed over
    # layers and program calls; 0 for a dense model
    "moe_expert_rows", "moe_experts_touched",
    "num_blocks", "nvme_blocks", "nvme_blocks_in_use", "nvme_loads",
    "nvme_spills", "prefetch_misses", "prefetch_wait_p50_s",
    "prefetch_wait_p95_s", "prefill_calls", "prefill_fill", "prefill_shapes",
    "prefill_turns",
    "prefix_cache_entries",
    "prefix_cache_evictions", "prefix_cache_hit_rate",
    "prefix_hit_tokens", "prompt_tokens", "quantize", "queue_depth",
    # PR 64: the prompt tokens the trie was asked about; tokens a decoding
    # row commits a self-drafting round
    "prefix_query_tokens", "tokens_per_round",
    "requests_finished", "resume_recompute_tokens", "retraces_observed",
    "role",
    "sampling", "logit_masks", "sampled_requests",
    "spec_draft_rejected",
    "sp", "resident_window_blocks", "context_window_slides",
    "sp_alltoall_bytes",
    "spec_rounds", "spec_tokens", "speculative", "swap_bytes", "swap_in",
    "swap_out", "tp_degree", "tpot_p50_s", "tpot_p95_s",
    "trace_capacity", "trace_events", "trace_events_dropped",
    "ttft_p50_s", "ttft_p95_s", "weight_quant",
})

#: stats()["config"] / resolved_config() — the ``init_serving`` kwargs
#: dict pinned key-for-key: bench JSONs, ``best_config.json``, and the
#: autotuner's trial records must stay mutually loadable across PRs
CONFIG_KEYS = frozenset({
    "block_size", "debug_checks",
    "engine_mode", "host_blocks",
    "max_seq_len", "ngram_max", "ngram_min", "num_blocks",
    "nvme_blocks", "nvme_high_watermark", "nvme_path", "peak_flops",
    "prefill_batch", "prefill_chunk", "prefix_caching",
    "quantize", "resident_window_blocks", "role", "sampling", "shard_kv",
    "slo_targets", "slots", "sp", "spec_tokens", "logit_masks",
    "swap_batch", "topology", "trace_capacity",
})

#: ReplicaRouter.stats() — PR 11 keys + PR 12's "metrics_endpoint" +
#: PR 14's lock-sanitizer counters (0 when debug_checks is off) +
#: PR 15's failure/recovery surface ("failed" replica list, crash and
#: re-home counters, typed-failure count, pull retries, per-class sheds)
ROUTER_STATS_KEYS = frozenset({
    "busy_s", "drained", "drains", "failed", "generated_tokens",
    "giant_context", "handoffs",
    "kv_pull", "kv_pull_blocks", "kv_pull_bytes", "kv_pull_retries",
    "kv_pulls", "lock_order_checks",
    "lock_violations", "metrics_endpoint",
    "per_replica", "policy", "prefix_cache_hit_rate", "prompt_tokens",
    "readmits", "replica_failures", "replicas", "requests_failed",
    "requests_rehomed", "requests_shed", "routed_affinity",
    "routed_balance",
})

PER_REPLICA_KEYS = frozenset({
    "active", "admitted", "blocks_in_use", "busy_s", "compile_budget",
    "compile_count", "config", "drained", "generated_tokens",
    "prefix_cache_hit_rate", "queue_depth", "replica", "role",
})

#: slo_report() — one entry per class, each with this exact shape
SLO_CLASSES = frozenset({"realtime", "interactive", "standard", "batch",
                         "giant_context"})
SLO_CLASS_KEYS = frozenset({
    "objective", "requests",
    "ttft_attained", "ttft_attainment", "ttft_burn_rate",
    "ttft_p50_s", "ttft_p95_s", "ttft_target_s",
    "tpot_attained", "tpot_attainment", "tpot_burn_rate",
    "tpot_p50_s", "tpot_p95_s", "tpot_target_s",
})

#: windowed_burn() — PR 18's incident-trigger signal, per class
SLO_WINDOW_KEYS = frozenset({
    "objective", "requests", "window_s",
    "ttft_attainment", "ttft_burn_rate",
    "tpot_attainment", "tpot_burn_rate",
})

#: ReplicaRouter.resolved_config() — PR 18: incident bundles persist
#: this dict and ``graft-replay`` rebuilds the fleet by splatting it
#: back into the constructor, so its key set is a compatibility surface
#: between bundles dumped by one build and replayed by another
ROUTER_CONFIG_KEYS = frozenset({
    "policy", "kv_pull", "threaded", "debug_checks", "trace_capacity",
    "max_queue_depth", "shed_classes", "burn_threshold", "pull_retries",
    "pull_backoff_s", "pull_timeout_s", "max_rehomes",
    "giant_context_tokens",
})

#: incident bundle manifest.json — PR 18: the on-disk contract between
#: the flight recorder and ``graft-replay``/postmortem tooling; bundles
#: outlive the process that dumped them, so a key change here needs a
#: BUNDLE_SCHEMA_VERSION bump, not a silent rename
MANIFEST_KEYS = frozenset({
    "schema_version", "bundle_format", "trigger", "wall_time_s",
    "wall_time_iso", "step_clocks", "seeds", "git_describe", "files",
    "replicas", "model", "router_config", "replayable", "gather_errors",
})


def test_engine_stats_keys_pinned(served):
    srv, _ = served
    assert set(srv.stats().keys()) == ENGINE_STATS_KEYS


def test_decode_attn_stats_keys_pinned(served):
    """``stats()["decode_attn"]``: the decode walk's tile at the pool's
    shapes (PR 45) and the grid steps its copies run ahead (PR 56: one)."""
    srv, _ = served
    walk = srv.stats()["decode_attn"]
    assert set(walk) == {"tile_blocks", "cols", "rows_ahead"}
    assert walk["rows_ahead"] == 1 and all(
        isinstance(v, int) for v in walk.values())


def test_latent_kind_stats_and_span_keys_pinned():
    """A model with latent attention: ``stats()["kv_latent"]`` and what its
    ``decode`` / ``prefill`` spans carry of the latent walk, key for key —
    PR 39's, and PR 58's ``tile_blocks`` / ``kv_tiles`` /
    ``kv_first_tiles_ahead`` (the walk's tile of each program, the loop
    iterations of one layer's call and the grid steps whose first tile the
    step before starts)."""
    from deepspeed_tpu.models import llama

    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        llama.build(llama.LlamaConfig(
            vocab_size=64, max_seq_len=64, num_layers=1, num_heads=2,
            num_kv_heads=2, head_width=16, hidden_size=32, ffn_size=32,
            q_lora_rank=16, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8,
            v_head_dim=8, remat=False)),
        config={"dtype": "fp32"}, slots=2, max_seq_len=64, block_size=8,
        prefill_chunk=16)
    srv.serve([Request(uid=0, prompt=np.arange(20) % 64, max_new_tokens=3)])
    walk = {"kv_valid", "kv_blocks", "kv_pairs", "latent_bytes", "kv_tiles",
            "kv_first_tiles_ahead"}
    lat = srv.stats()["kv_latent"]
    assert set(lat) == walk | {
        "kind", "layers", "token_width", "pool_width", "token_bytes",
        "block_size", "block_bytes", "latent_attn", "tile_blocks", "refused"}
    assert set(lat["tile_blocks"]) == {"decode", "prefill"}
    assert set(lat["tile_blocks"]["prefill"]) == set(
        srv.stats()["prefill_shapes"])
    for name in ("decode", "prefill"):
        spans = [e["args"] for e in srv.timeline.events()
                 if e["ph"] == "X" and e["name"] == name]
        assert spans and all(
            walk <= set(a) and all(isinstance(a[k], int) for k in walk)
            for a in spans), name
    assert srv.stats()["decode_attn"] is None
    srv.close()


def test_two_latent_kinds_stats_and_span_keys_pinned():
    """A model whose layers are latent attention under a learned selection
    and under a window (PR 61): ``stats()`` has all three kinds' entries —
    ``kv_latent`` as any latent model's, ``sparse_attn`` as any indexer's,
    ``kv_kinds`` as any window model's plus the window kind's own block and
    token bytes and the ``kv_window`` totals — and its ``decode`` /
    ``prefill`` spans carry the three kinds' counters side by side."""
    from deepspeed_tpu.models import dots3
    from deepspeed_tpu.ops import sparse_index_attention

    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        dots3.build(dots3.Dots3Config(
            vocab_size=64, max_seq_len=64, hidden_size=32,
            layer_types=("full_attention", "sliding_attention"),
            num_heads=2, num_kv_heads=2, head_width=16, q_lora_rank=16,
            kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8,
            index_heads=2, index_head_dim=16, index_topk=8, sliding_window=5,
            swa_num_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=24,
            swa_qk_nope_dim=8, swa_qk_rope_dim=8, swa_v_head_dim=8,
            ffn_size=16, dense_ffn_size=32, num_experts=4, top_k=2,
            router_score="sigmoid", router_bias=True, shared_experts=1,
            capacity_factor=None)),
        config={"dtype": "fp32"}, slots=2, max_seq_len=64, block_size=8,
        prefill_chunk=16)
    srv.serve([Request(uid=0, prompt=np.arange(20) % 64, max_new_tokens=3)])
    st = srv.stats()
    walk = {"kv_valid", "kv_blocks", "kv_pairs", "latent_bytes", "kv_tiles",
            "kv_first_tiles_ahead"}
    assert set(st["kv_latent"]) == walk | {
        "kind", "layers", "token_width", "pool_width", "token_bytes",
        "block_size", "block_bytes", "latent_attn", "tile_blocks", "refused"}
    assert set(st["sparse_attn"]) == {"decode", "prefill"} | set(
        sparse_index_attention.COUNTS)
    kind = {"layers", "num_blocks", "blocks_in_use", "peak_blocks_in_use",
            "table_width"}
    assert set(st["kv_kinds"]) == {
        "window", "full", "sliding", "kv_valid", "kv_visible", "kv_window",
        "kv_window_blocks", "expert_rows_absent", "refused"}
    assert set(st["kv_kinds"]["full"]) == kind
    assert set(st["kv_kinds"]["sliding"]) == kind | {
        "released", "block_size", "token_bytes"}
    reach = {"kv_window", "kv_window_blocks"}
    for name in ("decode", "prefill"):
        spans = [e["args"] for e in srv.timeline.events()
                 if e["ph"] == "X" and e["name"] == name]
        assert spans and all(
            (walk | reach | set(sparse_index_attention.COUNTS)) <= set(a)
            for a in spans), name
    assert st["decode_attn"] is None and st["kv_state"] is None \
        and st["kv_tails"] is None
    srv.close()


def test_engine_stats_keys_pinned_with_draft_pool_extras(served):
    """The only engine stats() extension point: a draft pool adds its
    two byte-accounting keys (PR 5 behavior, unchanged)."""
    srv, _ = served
    st = set(srv.stats().keys())
    assert "draft_pool_bytes" not in st       # no draft on this engine


def test_stats_config_keys_pinned_and_roundtrippable(served):
    """The config sub-dict is pinned key-for-key, JSON-able, and a
    fixpoint of ``init_serving``: rebuilding from it resolves to the
    identical dict (trials/benches reproduce engines from artifacts
    alone)."""
    import json

    srv, router = served
    cfg = srv.stats()["config"]
    assert set(cfg.keys()) == CONFIG_KEYS
    assert cfg == srv.resolved_config()
    json.dumps(cfg)
    assert router.stats()["per_replica"][0]["config"] == cfg
    deepspeed_tpu.comm.reset_topology()
    rebuilt = deepspeed_tpu.init_serving(
        gpt2.build(gpt2.GPT2Config.tiny(max_seq_len=128)),
        config={"dtype": "fp32"}, **cfg)
    assert rebuilt.resolved_config() == cfg


def test_router_stats_keys_pinned(served):
    _, router = served
    st = router.stats()
    assert set(st.keys()) == ROUTER_STATS_KEYS
    assert set(st["per_replica"][0].keys()) == PER_REPLICA_KEYS


def test_lock_metric_schema_pinned(served):
    """PR 14: the instrumented-lock telemetry surface — a debug_checks
    router registers ``serving_lock_wait_seconds{lock=fleet|replica}``
    and ``serving_lock_order_checks_total`` (GL008-compliant names),
    and ``stats()`` carries integer ``lock_order_checks`` /
    ``lock_violations``; with debug off the families are absent and the
    stats keys read 0."""
    srv, router = served
    st = router.stats()
    assert st["lock_order_checks"] == 0 and st["lock_violations"] == 0
    snap = router.metrics.snapshot()
    assert "serving_lock_wait_seconds" not in snap      # off: no family

    dbg = ReplicaRouter([ServingEngine(
        srv.engine, slots=2, max_seq_len=64, block_size=8,
        prefill_chunk=16, debug_checks=True)], debug_checks=True)
    snap = dbg.metrics.snapshot()
    fam = snap["serving_lock_wait_seconds"]
    assert fam["type"] == "histogram"
    assert sorted(s["labels"]["lock"] for s in fam["series"]) == \
        ["fleet", "replica"]
    assert snap["serving_lock_order_checks_total"]["type"] == "counter"
    st = dbg.stats()
    assert isinstance(st["lock_order_checks"], int)
    assert isinstance(st["lock_violations"], int)
    assert set(st.keys()) == ROUTER_STATS_KEYS


def test_slo_report_schema_pinned(served):
    srv, router = served
    for rep in (srv.slo_report(), router.slo_report()):
        assert set(rep.keys()) == SLO_CLASSES
        for cls, entry in rep.items():
            assert set(entry.keys()) == SLO_CLASS_KEYS, cls


def test_windowed_burn_schema_pinned(served):
    srv, _ = served
    win = srv._slo.windowed_burn()
    assert set(win.keys()) == SLO_CLASSES
    for cls, entry in win.items():
        assert set(entry.keys()) == SLO_WINDOW_KEYS, cls


def test_router_resolved_config_keys_pinned(served):
    _, router = served
    cfg = router.resolved_config()
    assert set(cfg.keys()) == ROUTER_CONFIG_KEYS
    import json

    json.dumps(cfg)
    srv = served[0]
    rebuilt = ReplicaRouter([ServingEngine(
        srv.engine, slots=2, max_seq_len=64, block_size=8,
        prefill_chunk=16)], **cfg)
    assert rebuilt.resolved_config() == cfg


def test_incident_manifest_keys_pinned():
    from deepspeed_tpu.telemetry import incident

    assert incident.MANIFEST_KEYS == MANIFEST_KEYS
    assert incident.BUNDLE_SCHEMA_VERSION == 2
    assert incident.TRIGGER_KINDS == (
        "replica_fail", "invariant_violation", "retrace",
        "checksum_burst", "burn_rate_breach", "watchdog_stall")


def test_flops_report_schema_pinned(served):
    srv, _ = served
    rep = srv.flops_report()
    assert set(rep.keys()) == {
        "programs", "program_calls", "model_flops_total",
        "flops_per_generated_token", "generated_tokens", "window_s",
        "peak_flops", "mfu", "busy_fractions"}
    for prog in rep["programs"].values():
        assert set(prog.keys()) == {
            "rows", "width", "flops_analytic", "flops_cost_analysis",
            "flops_per_call", "tokens_per_call", "source", "priced"}
