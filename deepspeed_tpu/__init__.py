"""deepspeed_tpu — a TPU-native distributed training & inference framework.

Public API mirrors the reference DeepSpeed surface (``deepspeed/__init__.py``):
``initialize`` (:52), ``init_inference`` (:233), ``add_config_arguments`` (:210),
``comm``, ``zero`` — implemented TPU-first on JAX/XLA/pjit/Pallas.
"""

from __future__ import annotations

import functools as _functools
import time as _time

_T_IMPORT = _time.perf_counter()    # the start-up ring's ``import`` span

from typing import Optional, Union  # noqa: E402

from . import comm  # noqa: E402
from . import models  # noqa: E402
from . import module_inject  # noqa: E402
from . import ops  # noqa: E402
from . import zero  # noqa: E402
from .runtime import lr_schedules  # noqa: E402
from .runtime.config import DeepSpeedConfig  # noqa: E402
from .runtime.engine import DeepSpeedEngine  # noqa: E402
from .runtime.model import ModelSpec, OnDevice, from_flax, from_functions  # noqa: E402
from .parallel.topology import (  # noqa: E402
    MeshTopology, PipeModelDataParallelTopology, ProcessTopology,
    topology_from_config)
from .telemetry import trace as _trace  # noqa: E402
from .utils.logging import log_dist, logger  # noqa: E402

__version__ = "0.1.0"
__git_hash__ = None
__git_branch__ = None

# the package's own import, top to bottom, is the first span of the
# process's start-up ring (telemetry/trace.py setup_timeline): what of a
# process's start is ``import deepspeed_tpu`` and what came before it
# (``import jax``, the backend's start) can be told apart
_setup = _trace.setup_timeline(epoch_s=_T_IMPORT)
_setup.complete("import", (_T_IMPORT - _setup.epoch_s) * 1e6,
                package=__name__)


def _on_setup_ring(fn):
    """``fn``, whole, as a span of the start-up ring under its own name
    (the engines' constructors put their phases inside it)."""
    @_functools.wraps(fn)
    def spanned(*args, **kwargs):
        with _trace.setup_timeline().span(fn.__name__):
            return fn(*args, **kwargs)
    return spanned


@_on_setup_ring
def initialize(args=None,
               model: Optional[ModelSpec] = None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required: Optional[bool] = None,
               collate_fn=None,
               config: Optional[Union[str, dict]] = None,
               config_params=None):
    """Initialize the engine (reference ``deepspeed.initialize``, __init__.py:52).

    Returns the same 4-tuple: ``(engine, optimizer, training_dataloader,
    lr_scheduler)``.  ``model`` is a :class:`ModelSpec` (pure init/loss functions
    over a param pytree) rather than an ``nn.Module``; ``optimizer`` (optional) is
    an optax ``GradientTransformation``; everything else is config-driven.
    """
    log_dist(f"deepspeed_tpu info: version={__version__}", ranks=[0])
    config = config if config is not None else config_params
    if args is not None and hasattr(args, "deepspeed_config") and \
            args.deepspeed_config is not None:
        assert config is None, \
            "Not sure how to proceed, we were given both a deepspeed_config and config"
        config = args.deepspeed_config

    # pp > 1 selects the pipeline engine (reference picks PipelineEngine when
    # the model is a PipelineModule, __init__.py:125)
    cfg_dict = config
    if isinstance(cfg_dict, str):
        import json

        with open(cfg_dict) as f:
            cfg_dict = json.load(f)
    from .parallel.topology import normalize_mesh_config

    mesh_norm = normalize_mesh_config((cfg_dict or {}).get("mesh"))
    engine_cls = DeepSpeedEngine
    if int(mesh_norm.get("pp", 1)) > 1:
        from .runtime.pipe.engine import PipelineEngine

        engine_cls = PipelineEngine
    engine = engine_cls(args=args,
                        model=model,
                        optimizer=optimizer,
                        model_parameters=model_parameters,
                        training_data=training_data,
                        lr_scheduler=lr_scheduler,
                        mpu=mpu,
                        dist_init_required=dist_init_required,
                        collate_fn=collate_fn,
                        config=cfg_dict)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def add_config_arguments(parser):
    """Argparse plumbing (reference __init__.py:210)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag for user code, no "
                       "impact on DeepSpeed backend)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed json configuration file.")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated enable DeepSpeed (helper flag for user "
                       "code, no impact on DeepSpeed backend)")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated DeepSpeed json configuration file.")
    return parser


def add_tuning_arguments(parser):
    return lr_schedules.add_tuning_arguments(parser)


def init_inference(model=None, config=None, params=None, *,
                   device_group=None, **kwargs):
    """Inference engine entry (reference __init__.py:233).

    ``model`` may be a :class:`ModelSpec`, a HuggingFace torch model (its
    architecture is matched to an injection policy and the weights converted —
    the ``replace_transformer_layer`` analog), or a path to an HF checkpoint
    directory.  ``params``: trained parameter pytree; without it the engine
    serves the converted HF weights, or freshly-initialized ones.
    ``device_group``: see :class:`~deepspeed_tpu.inference.engine
    .InferenceEngine` (``None`` = span every device).
    """
    from .inference.engine import InferenceEngine
    from .inference.config import DeepSpeedInferenceConfig

    if isinstance(config, dict):
        config = DeepSpeedInferenceConfig(**config)
    elif config is None:
        config = DeepSpeedInferenceConfig(**kwargs)
    if model is not None and not isinstance(model, ModelSpec):
        from .runtime.state_dict_factory import load_hf_weights

        model, converted = load_hf_weights(model)
        if params is None:
            params = converted
    return InferenceEngine(model, config, params=params,
                           device_group=device_group)


def init_router(model=None, config=None, params=None, *, replicas=2,
                policy="affinity", kv_pull=True, threaded=False,
                router_trace_capacity=4096, metrics_port=None,
                metrics_host="127.0.0.1", max_queue_depth=None,
                shed_classes=("batch",), burn_threshold=None,
                pull_retries=2, pull_backoff_s=0.0, pull_timeout_s=None,
                max_rehomes=3, prefill_workers=None,
                giant_context_tokens=0, **serving_kwargs):
    """Multi-replica serving entry (ROADMAP item 1): ``replicas`` ×
    ``init_serving`` engines — all serving ONE set of weights (the first
    replica's initialized/loaded params are handed to the others, so every
    replica is token-identical by construction; each replica places them
    on its own ``tp x sp`` device group, sharing buffers only when groups
    coincide) — behind a
    :class:`~deepspeed_tpu.serving.ReplicaRouter`.

    The router fronts the fleet with an incremental async API:
    ``submit(request, priority=, slo_class=)`` returns a streaming
    :class:`~deepspeed_tpu.inference.serving.RequestHandle`
    (``next_token`` / ``result()`` / ``cancel()``); ``serve(list)``
    remains the batch convenience.  Routing is prefix-affinity first
    (device trie + host tier probed by content-addressed chain key,
    backed by a queued-prefix hint table), balanced by blocks-in-use;
    with ``kv_pull`` (and ``host_blocks > 0`` in ``serving_kwargs``) a
    request landing on a cold replica pulls its prefix blocks from
    another replica's host tier instead of recomputing — and
    ``router.drain(rid)`` / ``readmit(rid)`` migrate a whole replica's
    sessions the same way without dropping requests
    (``deepspeed_tpu/serving/``; docs/inference.md "Multi-replica
    serving").

    ``threaded=True`` + ``router.start()`` runs one worker thread per
    replica; default off, the caller (or ``router.serve``) drives
    ``step()`` deterministically.  All remaining keyword arguments go to
    ``init_serving`` per replica — ``quantize=``, ``host_blocks=``,
    ``spec_tokens=``, ``topology=`` (dp×tp: N replicas each tp-sharded),
    ``slo_targets=`` compose unchanged, and each replica keeps its own
    sentry-enforced compile budget (the router itself never traces a
    program).

    ``metrics_port=N`` starts the fleet's live exposition server
    (``telemetry/server.py``; 0 = ephemeral port, ``router.
    metrics_server.port`` reports it): ``/metrics`` serves the federated
    Prometheus text over the router + every replica registry, ``/stats``
    the JSON fleet snapshot (router stats + per-class SLO report +
    registry snapshot), ``/trace`` the merged multi-replica Chrome
    trace.  ``router.stop()`` shuts it down.  See
    ``docs/observability.md`` "Fleet observability".

    Fault tolerance (docs/reliability.md): a crashed replica is failed
    out of rotation (``router.fail(rid)`` — supervisor hard-probe
    detection, worker-death handling, or the ``serving/faults.py``
    chaos harness) and its live requests re-home onto survivors with
    token-exact greedy resume, streaming on the same handles;
    cross-replica KV pulls retry transient faults (``pull_retries`` /
    ``pull_backoff_s`` / ``pull_timeout_s``) with checksum-verified
    bytes, and ``max_queue_depth`` / ``burn_threshold`` bound admission
    by shedding ``shed_classes`` work with typed ``RequestRejected``
    results under overload.

    ``prefill_workers=N`` disaggregates the fleet (docs/inference.md
    "Disaggregated serving"): the first N replicas build with
    ``role="prefill"`` (admission + chunked prefill only — they emit the
    first token, demote the prompt chain to their host tier, and hand
    the session off), the rest with ``role="decode"`` (steady-state
    token generation over pulled KV).  Requires ``kv_pull=True`` and
    ``host_blocks > 0`` in ``serving_kwargs`` (the handoff travels as a
    host-tier chain export/import).  Default ``None`` keeps every
    replica ``role="both"`` — bit-identical to the colocated fleet."""
    from .serving import ReplicaRouter, plan_roles

    if prefill_workers and "role" in serving_kwargs:
        raise ValueError(
            "pass prefill_workers= OR a per-fleet role=, not both — "
            "prefill_workers already assigns each replica's role")
    roles = plan_roles(int(replicas), prefill_workers)
    reps = []
    for i, role in enumerate(roles):
        # one tp x sp device group per replica (wrapping around when the
        # host has fewer groups than replicas); dp_tp engines span all
        per = dict(serving_kwargs)
        if per.get("engine_mode", "replicas") == "replicas":
            per.setdefault("device_group", i)
        if prefill_workers:
            per["role"] = role
        srv = init_serving(model, config, params, **per)
        if params is None:
            params = srv.engine.params
        reps.append(srv)
    router = ReplicaRouter(
        reps, policy=policy, kv_pull=kv_pull, threaded=threaded,
        debug_checks=bool(serving_kwargs.get("debug_checks", False)),
        trace_capacity=router_trace_capacity,
        max_queue_depth=max_queue_depth, shed_classes=shed_classes,
        burn_threshold=burn_threshold, pull_retries=pull_retries,
        pull_backoff_s=pull_backoff_s, pull_timeout_s=pull_timeout_s,
        max_rehomes=max_rehomes,
        giant_context_tokens=giant_context_tokens)
    if metrics_port is not None:
        router.start_metrics_server(port=metrics_port, host=metrics_host)
    return router


@_on_setup_ring
def init_serving(model=None, config=None, params=None, *, topology=None,
                 device_group=None, **kwargs):
    """Continuous-batching serving entry: an ``init_inference`` engine
    wrapped in the block-paged scheduler (``inference/serving.py``).
    Mixed-length request traces run at iteration-level granularity over a
    paged KV pool — finished sequences free their blocks immediately,
    shared block-aligned prompt prefixes are reused from the prefix cache
    with zero recompute, and prompts prefill in fixed chunks (one compiled
    prefill program) — instead of ``generate``'s run-to-longest static
    batches.

    The keyword options are the serving engine's
    (:class:`~deepspeed_tpu.inference.serving.ServingEngine`; their names,
    defaults and ranges, and what does not combine, are stated once, in
    ``inference/options.py`` — ``inspect.signature(init_serving)`` shows
    them), plus ``topology`` and ``device_group``; any other keyword must be
    a field of the ``init_inference`` config (``dtype=``, ``quant=``, ...)
    and anything else is a ``TypeError`` that names it.

    ``engine_mode="dp_tp"`` runs ONE engine over the 2-D ``("dp","tp")``
    mesh (slots + KV blocks dp-sharded, KV heads tp-sharded): one
    compiled decode program serves what otherwise takes dp router-fronted
    replicas.  See docs/inference.md "dp×tp engine mode".

    ``spec_tokens=K`` turns on speculative decoding: each decode iteration
    drafts K tokens per slot — with a small same-tokenizer ``draft`` model
    (ModelSpec or ``init_inference`` engine), or the model-free n-gram
    prompt-lookup proposer — and
    verifies the K+1 window in one batched target pass, committing the
    longest target-matching prefix.  Outputs stay token-exact with plain
    greedy decode at any acceptance rate.  ``draft="self"`` makes the
    served model's OWN multi-token-prediction module the proposer (a model
    whose decode hooks carry ``self_draft``: GLM-5's): its cache rows are
    more layers of the target's pool, its draft stays on the device, and a
    round is one ``spec_round`` span with one harvest
    (``inference/options.py SELF_DRAFT``; docs/inference.md "The
    self-drafting proposer").

    **Multi-chip serving**: ``topology=N`` (or ``{"tp": N}``) is shorthand
    for ``config={"tensor_parallel": {"tp_size": N}}`` (overriding any
    ``tensor_parallel`` already present) — the engine shards
    weights Megatron-style over the ``tp`` mesh axis, and the serving
    engine shards the paged KV pool over the KV-head dim so each chip
    stores ``HKV/N`` heads (N× the servable blocks/context).  ``shard_kv``
    (default auto) controls the pool sharding — see
    :class:`~deepspeed_tpu.inference.serving.ServingEngine`.  One engine
    occupies exactly one ``tp x sp`` group of chips — group
    ``device_group`` (default 0; :func:`init_router` gives replica ``i``
    group ``i``, wrapping around when there are fewer groups than
    replicas) — except in ``engine_mode="dp_tp"``, which spans them all.

    **Quantized serving**: ``quantize="kv8"`` stores the paged KV pool
    (and the speculative draft pool) as int8 with a per-block scale table
    — ~2x servable blocks per chip and ~2x decode KV bandwidth, composing
    with the tp head-shard.  ``quantize="w8a8"`` additionally rebuilds the
    engine config with ``quant: {enabled, type: "w8a8"}`` so decode
    matmuls run the s8-MXU stacked kernels; ``"w8a8+kv8"`` composes both.
    Quantized lanes trade exact greedy parity for a bounded
    token-divergence / logit-error contract (README "Quantized serving");
    ``quantize=None`` (default) is bit-identical to prior behavior.

    **Tiered KV cache**: ``host_blocks=N`` adds a host-DRAM tier of N KV
    blocks below the device pool — under block pressure cold blocks
    demote to host instead of being discarded (prefix-cache eviction AND
    preemption), and admission promotes host-resident chains back with a
    double-buffered prefetch that overlaps the H2D copy with the decode
    step (``swap_batch`` sizes the two fixed-shape swap programs).  The
    prefix trie becomes a session cache bounded by host DRAM rather than
    HBM: returning conversations re-admit at full prefix-hit speed, and
    preemption's recompute shrinks to the unfinished tail — with zero
    parity loss (promoted bytes are bit-identical to what was demoted).
    ``host_blocks=0`` (default) is byte-identical to prior behavior.
    See docs/inference.md "Tiered KV".

    ``nvme_blocks=N`` adds an NVMe spill file of N blocks BELOW the host
    arena (``nvme_path=`` names the file; default mints a tempfile the
    engine deletes on close): past ``nvme_high_watermark`` of the arena
    the LRU tail spills to disk via ``ops/aio.py``, and promotion stages
    spilled blocks back through the same double-buffered prefetch path —
    every NVMe exit re-verified against the stored checksum.
    ``role="prefill"|"decode"`` dedicates the engine to one phase of a
    disaggregated fleet behind :func:`init_router` (``role="both"``, the
    default, is bit-identical to prior behavior); see docs/inference.md
    "Disaggregated serving".

    **Long-context serving**: ``sp=N`` adds a sequence-parallel
    (Ulysses-style) ``sp`` mesh axis — prefill shards the prompt chunk
    over N ranks, converting heads<->sequence around attention with a
    pair of ``lax.all_to_all`` collectives (``ops/sp_attention``) and
    committing KV into the SAME paged pool, so everything downstream
    (prefix trie, tiers, kv8, tp, router pulls) is untouched; ``sp=1``
    (default) is bit-identical to prior behavior.  Composes with
    ``topology=`` tp on an ``sp×tp`` mesh.  ``resident_window_blocks=W``
    turns on resident-window decode for 100k+-token contexts: only a
    sliding W-block window plus pinned landmark (attention-sink) blocks
    stay device-resident — older KV demotes to the host/NVMe tiers under
    its chain keys and is masked out of attention — so the device pool
    can be far smaller than one logical context (requires
    ``host_blocks``).  See docs/inference.md "Long-context serving".

    **Sampling** (default on): per-request ``temperature`` / ``top_k`` /
    ``top_p`` / ``seed`` (``Request`` fields) run ON DEVICE as per-slot
    operand vectors inside the same compiled programs — greedy requests
    are the ``temperature=0`` rows, so mixed traces keep the compile
    contract with zero recompiles, and speculative decoding verifies
    sampled streams with the distribution-exact rejection sampler.
    ``logit_masks=True`` adds the
    constrained-decoding lane: requests carrying a ``mask_builder``
    (``inference/constrain.py``) sample under a host-built
    ``[slots, vocab]`` allow-mask (e.g. guaranteed-valid JSON).
    ``sampling=False`` strips the sampling operands for a byte-identical
    legacy greedy engine.  See docs/inference.md "Sampled decoding".

    ``debug_checks=True`` turns on the correctness tooling
    (``deepspeed_tpu/analysis/``): the recompile sentry raises on any
    trace past the engine's compile budget (with an abstract-signature
    diff of the retrace), and the paged-state invariant audit runs after
    every scheduler iteration; off, both are free and ``stats()`` still
    reports ``retraces_observed``.

    **Telemetry** (``deepspeed_tpu/telemetry/``): ``stats()`` is a view
    over the engine's metrics registry (``srv.metrics`` — Prometheus
    text / JSON snapshot), and a bounded ring of scheduler events
    (``trace_capacity=``, 0 = off) records a per-request timeline
    exportable as Chrome ``trace_event`` JSON via
    ``srv.dump_trace(path)``; ``serve(profile_dir=...)`` brackets
    scheduler iterations with a ``jax.profiler`` window.
    ``slo_targets=`` overrides the per-``slo_class`` TTFT/TPOT targets
    behind ``srv.slo_report()``; ``peak_flops=`` sets the MFU
    denominator for ``srv.flops_report()`` (the cost_analysis-backed
    FLOPs/MFU profiler, ``telemetry/flops.py``).  See
    ``docs/observability.md``."""
    from .inference import options
    from .inference.config import DeepSpeedInferenceConfig
    from .inference.serving import ServingEngine

    # the serving options (by attribute, at their defaults where not given);
    # what is left in kwargs must be the engine config's
    o = options.bind({k: kwargs.pop(k) for k in list(kwargs)
                      if k in options.OPTIONS}, "init_serving")
    fields = DeepSpeedInferenceConfig.model_fields
    for name in set(kwargs) - set(fields) - {
            f.alias for f in fields.values()}:
        raise TypeError(
            f"init_serving() got an unexpected keyword argument {name!r}")
    if topology is not None:
        tp = int(topology) if not isinstance(topology, dict) else \
            int(topology.get("tp", topology.get("tp_size", 1)))
        # topology= wins over any tensor_parallel already in config/kwargs,
        # and never mutates a caller-owned config object
        if isinstance(config, dict):
            config = {**config, "tensor_parallel": {"tp_size": tp}}
        elif config is None:
            kwargs["tensor_parallel"] = {"tp_size": tp}
        else:
            config = config.model_copy(deep=True)
            config.tensor_parallel.tp_size = tp
    if o.sp > 1:
        # sp= injects sequence_parallel the same way topology= injects
        # tensor_parallel: the engine builds the (dp, sp, tp) mesh, the
        # serving ctor validates the axis matches
        if isinstance(config, dict):
            config = {**config, "sequence_parallel": o.sp}
        elif config is None:
            kwargs["sequence_parallel"] = o.sp
        else:
            config = config.model_copy(deep=True)
            config.sequence_parallel = o.sp
    if o.quantize and "w8a8" in str(o.quantize):
        # route the engine's weights through the K-grouped int8 records the
        # w8a8 serving kernels consume.  An EXPLICIT quant block in config
        # wins when enabled (the caller may be pinning group_size /
        # shard_multiple; ServingEngine validates the type); an explicit
        # quant block that DISABLES quantization contradicts the knob and
        # raises — identically for dict and pydantic configs — instead of
        # being silently overridden.
        w8a8 = {"enabled": True, "type": "w8a8"}

        def _conflict():
            raise ValueError(
                "quantize includes 'w8a8' but config carries an explicit "
                "quant block with enabled=False — drop one of the two")

        if isinstance(config, dict):
            if "quant" not in config:
                config = {**config, "quant": w8a8}
            elif not config["quant"].get("enabled", False):
                _conflict()
        elif config is None:
            kwargs.setdefault("quant", w8a8)
        elif not config.quant.enabled:
            if "quant" in config.model_fields_set:
                _conflict()
            config = config.model_copy(deep=True)
            config.quant.enabled = True
            config.quant.type = "w8a8"
    if o.engine_mode == "replicas":
        device_group = device_group or 0
    elif device_group is not None:
        raise ValueError("engine_mode='dp_tp' spans every device — "
                         "device_group does not apply")
    engine = init_inference(model, config, params,
                            device_group=device_group, **kwargs)
    return ServingEngine(engine, **vars(o))


def _serving_signature():
    """``init_serving`` as ``inspect.signature`` shows it: its own
    parameters with every serving option (``inference/options.py``)
    keyword-only at its default."""
    import inspect

    from .inference import options

    own = list(inspect.signature(
        init_serving.__wrapped__).parameters.values())
    return options.signature(*own[:-1], var_keyword=own[-1].name)


init_serving.__signature__ = _serving_signature()
