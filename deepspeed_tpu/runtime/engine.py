"""The training engine.

TPU-native analog of reference ``deepspeed/runtime/engine.py`` (``DeepSpeedEngine``
:189).  The user contract is preserved — ``initialize(model, config) -> engine``,
then either the reference-style micro-step loop::

    loss = engine(batch)        # forward (engine.py:1766)
    engine.backward(loss)       # engine.py:1915
    engine.step()               # engine.py:2126

or the fused TPU-native path, one compiled XLA program per *global* step::

    state, metrics = engine.train_batch(batch)   # fwd+bwd+GAS+update, one jit

Where the reference orchestrates fwd/bwd/allreduce/step imperatively with hooks
and NCCL calls, here the whole training step — gradient accumulation loop
(lax.scan), mixed-precision casting, loss scaling, ZeRO-sharded gradient
reduction, clipping, optimizer update — is a single jitted function whose
communication schedule is derived by XLA SPMD from the sharding specs in
``runtime/zero/sharding.py``.  Grad allreduce (engine.py:1895), ZeRO
reduce-scatter (stage_1_and_2.py:952) and post-step allgather
(stage_1_and_2.py:1772) all fall out of those specs.

Master weights are always fp32 (the engine casts to the compute dtype inside the
loss closure), which subsumes the reference's separate FP16_Optimizer /
BF16_Optimizer / fused-master-weight machinery (fp16/fused_optimizer.py:20,
bf16_optimizer.py:38).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..analysis.sentry import RecompileSentry, install_compile_listener
from ..ops.optimizers import get_optimizer
from ..parallel.topology import (DATA_AXES, SP_AXIS, MeshTopology,
                                 topology_from_config)
from ..telemetry import MetricsRegistry
from ..telemetry import trace as trace_mod
from ..telemetry.programs import Programs
from ..telemetry.metrics import process_registry
from ..telemetry.trace import annotation
from ..utils.logging import log_dist, logger
from ..utils.platform import host_cpu_device
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER,
                           STEP_GLOBAL_TIMER, TRAIN_BATCH_TIMER,
                           SynchronizedWallClockTimer, ThroughputTimer)
from .checkpointing import CheckpointManager
from .config import DeepSpeedConfig
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .fp16.loss_scaler import LossScaleState, has_overflow, update_scale
from .lr_schedules import LRScheduler, get_lr_schedule
from .model import ModelSpec
from .zero.sharding import ZeroShardingPlan, constrain

PyTree = Any

MEMORY_OPT_ALLREDUCE_SIZE = 500000000


#: ``train_batch``'s metrics hold, under this key, what a model's ``loss_fn``
#: returned beside its loss (``(loss, record)``: a pytree of float32 scalars,
#: summed over the step's micro-batches); absent for a scalar loss
MODEL_RECORD = "model"


class _LazyMetrics:
    """Mapping over device-side metrics that defers the host transfer until
    first read.  Keeps the train loop free of per-step device_get round
    trips (which serialize the pipeline; very costly on remote backends)."""

    __slots__ = ("_dev", "_host")

    def __init__(self, device_metrics):
        self._dev = dict(device_metrics)
        self._host = None

    def _force(self):
        if self._host is None:
            got = jax.device_get({k: v for k, v in self._dev.items()
                                  if k != MODEL_RECORD})
            self._host = {k: np.asarray(v).item() for k, v in got.items()}
        return self._host

    def __getitem__(self, k):
        if k == MODEL_RECORD:
            # the model's own record (device arrays): fetched by who reads it
            return self._dev[k]
        return self._force()[k]

    def get(self, k, default=None):
        if k not in self._dev:       # don't force a transfer for a miss
            return default
        return self._force().get(k, default)

    def __contains__(self, k):
        return k in self._dev

    def __iter__(self):
        return iter(self._dev)

    def __len__(self):
        return len(self._dev)

    def keys(self):
        return self._dev.keys()

    def items(self):
        return self._force().items()

    def values(self):
        return self._force().values()

    def __repr__(self):
        return repr(self._force())


def _cast_floating(tree: PyTree, dtype) -> PyTree:
    def cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(cast, tree)


class DeepSpeedEngine:
    """Holds the sharded train state and the compiled step functions."""

    def __init__(self,
                 args=None,
                 model: Optional[ModelSpec] = None,
                 optimizer: Optional[Union[optax.GradientTransformation,
                                           Callable]] = None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required: Optional[bool] = None,
                 collate_fn=None,
                 config: Optional[Union[str, dict]] = None,
                 config_class: Optional[DeepSpeedConfig] = None,
                 dont_change_device: bool = False):
        assert model is not None, "deepspeed_tpu.initialize requires a model"
        assert isinstance(model, ModelSpec), (
            "model must be a deepspeed_tpu ModelSpec (see runtime/model.py); "
            "wrap flax modules with deepspeed_tpu.runtime.model.from_flax")
        # the start-up ring (telemetry/trace.py setup_timeline): this
        # constructor's phases as spans — inside ``initialize``, when that
        # is what called it — and, from the listener, every function JAX
        # builds in them
        setup = trace_mod.setup_timeline()
        install_compile_listener()
        with setup.span("configure"):
            self._configure(model, optimizer, lr_scheduler, training_data,
                            collate_fn, config, config_class)

        # sharded state
        self._init_rng = jax.random.PRNGKey(self._config.seed or 42)
        self._dropout_rng = jax.random.PRNGKey((self._config.seed or 42) + 1)
        with setup.span("build_state") as built:
            self._build_state()
            jax.block_until_ready(self.state)
            built.update(
                n_params=sum(int(x.size) for x in jax.tree_util.tree_leaves(
                    self.state["params"])),
                **{f"{k}_bytes": sum(
                    int(x.nbytes) for x in jax.tree_util.tree_leaves(
                        self.state[k])) for k in ("params", "opt_state")})
        with setup.span("build_step_fns"):
            #: what collectives each compiled step has, by program
            #: (``_read_collectives``, at the program's first call)
            self.collectives: Dict[str, Dict[str, Dict[str, int]]] = {}
            #: what finds the compiled steps again (telemetry/programs.py):
            #: their scope tables, on demand
            self.programs = Programs()
            trace_mod.keep("programs", self.programs)
            self._configure_stage3_liveness()
            self._build_step_fns()

        # data
        self.training_dataloader = self.deepspeed_io(training_data) \
            if training_data is not None else None
        self._data_iterator: Optional[Iterator] = None

        # timers/monitor/telemetry: one metrics registry backs the wall-
        # clock timer histograms, the train loss/lr/throughput gauges, and
        # the MonitorMaster event routing (_finalize_metrics writes the
        # registry snapshot through the CSV/TensorBoard/W&B backends on
        # report steps — telemetry/, docs/observability.md)
        self.metrics = MetricsRegistry()
        # what the process builds and its compile cache answers belongs to
        # no engine: the process's registry rides in this one's exposition
        self.metrics.include(process_registry())
        self.timers = SynchronizedWallClockTimer(registry=self.metrics)
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=self._config.steps_per_print or 10)
        self.wall_clock_breakdown_enabled = self._config.wall_clock_breakdown
        self._g_train_loss = self.metrics.gauge(
            "train_loss", "last reported global-step loss",
            monitor_name="Train/Samples/train_loss")
        self._g_train_lr = self.metrics.gauge(
            "train_lr", "last reported learning rate",
            monitor_name="Train/Samples/lr")
        # fp16-only: an unconditional family would emit a dead-constant
        # loss_scale series (and CSV file) for every full-precision run
        self._g_loss_scale = self.metrics.gauge(
            "train_loss_scale", "fp16 dynamic loss scale",
            monitor_name="Train/Samples/loss_scale") \
            if self.fp16_enabled else None
        self._g_samples_per_sec = self.metrics.gauge(
            "train_samples_per_sec",
            "running-average training throughput (ThroughputTimer)",
            monitor_name="Train/Samples/throughput")
        self._g_global_steps = self.metrics.gauge(
            "train_global_steps", "optimizer steps completed")
        from ..monitor.monitor import MonitorMaster

        self.monitor = MonitorMaster(self._config.monitor_config)
        self._metrics_server = None

        # (the storage backend, and the checkpoint library's import with
        # it, wait for the first save or load)
        self.checkpoint_manager = CheckpointManager(self)

        # micro-step accumulation buffers (forward/backward/step shim path)
        self._accum_grads: Optional[PyTree] = None
        self._accum_losses = []
        self._pending_batch = None

        log_dist(
            f"DeepSpeedEngine: mesh={self.topology}, zero_stage={self.zero_stage}, "
            f"dtype={self._config.precision_dtype}, "
            f"micro_bs/chip={self.train_micro_batch_size_per_gpu()}, "
            f"gas={self.gradient_accumulation_steps()}, "
            f"global_bs={self.train_batch_size()}", ranks=[0])

    def _configure(self, model, optimizer, lr_scheduler, training_data,
                   collate_fn, config, config_class) -> None:
        """The constructor's first phase, before anything is on a device:
        the configuration, the mesh, what the precision, ZeRO, offload and
        data-routing options ask for, the schedule and the optimizer."""
        dist.init_distributed()

        raw_config = config if config is not None else {}
        if isinstance(raw_config, str):
            import json

            with open(raw_config) as f:
                raw_dict = json.load(f)
        else:
            raw_dict = dict(raw_config)
        self.topology: MeshTopology = topology_from_config(raw_dict.get("mesh"))
        dist.configure(topology=self.topology)
        self.mesh = self.topology.mesh

        self._config = config_class or DeepSpeedConfig(
            raw_dict, mesh_topology=self.topology)
        dist.comms_logger.configure(self._config.comms_config)

        self.module = model  # reference name for the wrapped model
        self.model_spec = model
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn

        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._cached_metrics: Dict[str, Any] = {}

        # precision
        self.fp16_enabled = self._config.fp16_enabled
        self.bfloat16_enabled = self._config.bfloat16_enabled
        self.compute_dtype = {
            "float16": jnp.float16,
            "bfloat16": jnp.bfloat16,
            "float32": jnp.float32,
        }[self._config.precision_dtype]
        self.dynamic_loss_scale = self.fp16_enabled and self._config.loss_scale == 0

        # ZeRO plan
        self.zero_stage = self._config.zero_optimization_stage
        self.zero_plan = ZeroShardingPlan(
            self.zero_stage, self.mesh,
            param_persistence_threshold=(
                self._config.zero_config.param_persistence_threshold
                if self.zero_stage >= 3 else 0))

        # ZeRO-Offload / ZeRO-Infinity: optimizer state lives on host
        # (DRAM or NVMe) and steps through the C++ CPU optimizer
        off = self._config.zero_config.offload_optimizer
        self.offload_enabled = bool(off is not None and
                                    off.device.value != "none")
        self._offload_opt = None
        if self.offload_enabled and optimizer is not None:
            raise ValueError(
                "offload_optimizer requires a config-specified optimizer "
                "(adam/adamw/adagrad) — client optax transformations cannot "
                "run on host (reference: offload needs DeepSpeedCPUAdam)")

        # ZeRO-Infinity parameter streaming: block params stay host-resident
        # and stream through io_callback per scan step (zero/param_stream.py)
        offp = self._config.zero_config.offload_param
        self.param_stream_enabled = bool(offp is not None and
                                         offp.device.value != "none")
        self._param_store = None
        self._block_opt = None
        if self.param_stream_enabled:
            if not self.offload_enabled:
                raise ValueError(
                    "offload_param requires offload_optimizer too: streamed "
                    "block gradients are accumulated on host and must be "
                    "stepped by the host optimizer (reference ZeRO-Infinity "
                    "couples param+optimizer NVMe tiers, zero/stage3.py:486)")
            if model.pipeline_hooks is None:
                raise ValueError(
                    "offload_param needs a block-structured model "
                    "(ModelSpec.pipeline_hooks) so layers can stream "
                    "one scan step at a time")
            # multi-controller validated (round 3): callbacks pin to the
            # GLOBAL first device, so process 0's store serves loads and
            # receives the full psum'd grad push; _host_apply's
            # host_all_reduce_sum distributes it.  2-process x 2-device
            # loss parity vs the single-process run is asserted by
            # tests/unit/test_multiprocess.py::test_two_process_param_streaming_matches_single_process.
            if self.topology.pipe_parallel_size > 1:
                raise ValueError(
                    "offload_param with pp>1 is unsupported: the pipeline "
                    "engine shards the block params the streaming tier "
                    "removes from device state")

        # curriculum learning (reference engine consumes curriculum seqlen
        # at :1806-1812)
        self.curriculum_scheduler = None
        if self._config.curriculum_enabled:
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(
                self._config.curriculum_params)

        # activation_checkpointing config -> model remat selection
        # (reference activation_checkpointing/checkpointing.py:749) — must
        # run before _build_state so the first trace sees the new knobs
        from .remat import apply_config_to_model as _apply_ac

        _apply_ac(self._config.activation_checkpointing_config,
                  self.model_spec,
                  log=lambda m: log_dist(m, ranks=[0]),
                  n_devices=self.mesh.size if self.mesh is not None else 1)

        # sparse_gradients -> row-sparse embedding-grad exchange (reference
        # engine sparse allreduce, runtime/engine.py:2461-2476)
        if self._config.sparse_gradients_enabled:
            mc = getattr(self.model_spec, "model_config", None)
            if mc is not None and hasattr(mc, "sparse_embedding_grad"):
                mc.sparse_embedding_grad = True
                log_dist("sparse_gradients: embedding grads exchange "
                         "row-sparse over the data axes "
                         "(runtime/sparse_tensor.py)", ranks=[0])
            else:
                logger.warning(
                    "sparse_gradients: true, but the model does not expose "
                    "a sparse_embedding_grad knob; exchange stays dense")

        # random-LTD: scheduler drives the per-layer kept-token count; the
        # count is a trace-time constant, so crossing a schedule value
        # rebuilds the step fns (same retrace pattern as compression
        # schedule_offsets).  Reference data_routing/basic_layer.py:13.
        self.random_ltd_scheduler = None
        self._ltd_keep = None
        self._ltd_saturated = False
        if self._config.random_ltd_enabled:
            from .data_pipeline.random_ltd import RandomLTDScheduler

            mc = getattr(self.model_spec, "model_config", None)
            if mc is None or not hasattr(mc, "random_ltd_keep"):
                raise ValueError(
                    "data_routing.random_ltd requires a model that exposes "
                    "a random_ltd_keep knob (ModelSpec.model_config, e.g. "
                    "models/gpt2.GPT2Config)")
            self.random_ltd_scheduler = RandomLTDScheduler(
                self._config.random_ltd_params)
            # reference layer-range keys (random_ltd_layer_id_start /
            # random_ltd_layer_num) narrow WHICH layers drop tokens
            p = self._config.random_ltd_params
            if "random_ltd_layer_id_start" in p and \
                    hasattr(mc, "random_ltd_layer_start"):
                mc.random_ltd_layer_start = int(p["random_ltd_layer_id_start"])
            if "random_ltd_layer_num" in p and \
                    hasattr(mc, "random_ltd_layer_num"):
                mc.random_ltd_layer_num = int(p["random_ltd_layer_num"])

        # schedules and optimizer
        self._configure_lr_schedule()
        self._configure_optimizer()

        # 1-bit optimizers: past freeze_step the DP gradient exchange runs
        # through the error-compensated compressed all-reduce
        # (runtime/comm/compressed.py; reference runtime/comm/nccl.py:52).
        # Warmup stays dense, as the reference does.
        onebit_names = ("onebitadam", "onebitlamb", "zerooneadam")
        self.onebit_comm_enabled = bool(
            self._config.optimizer_name in onebit_names
            and self.topology.data_parallel_size > 1
            and self.topology.expert_parallel_size == 1
            and self.topology.model_parallel_size == 1
            and self.topology.pipe_parallel_size == 1
            and self.topology.sequence_parallel_size == 1
            # stage 1 composes: the exchange returns full mean grads and
            # the partitioned optimizer update slices them per dp shard
            # (the reference runs its 1-bit optimizers under ZeRO-1,
            # fp16/onebit/adam.py:11); stages 2/3 shard the grads
            # themselves and have no dense-exchange seam to compress
            and self.zero_stage in (0, 1)
            and not self.offload_enabled
            and not self.param_stream_enabled
            # sparse_embedding_lookup's backward opens its own shard_map;
            # nesting it inside the onebit step's shard_map is rejected by
            # jax (and the flattened compressed exchange covers the
            # embedding grads anyway)
            and not self._config.sparse_gradients_enabled)
        self._onebit_compressed = False
        self._onebit_freeze = int(
            (self._config.optimizer_params or {}).get("freeze_step", 100))
        if self._config.optimizer_name in onebit_names and \
                not self.onebit_comm_enabled and \
                self.topology.data_parallel_size > 1:
            if self.zero_stage >= 2:
                why = f"zero_optimization.stage={self.zero_stage} (needs <=1)"
            elif self.offload_enabled or self.param_stream_enabled:
                why = "offload/param streaming"
            elif self._config.sparse_gradients_enabled:
                why = ("sparse_gradients (its backward opens its own "
                       "shard_map; nesting inside the onebit step is "
                       "rejected by jax)")
            else:
                why = "a non-pure-dp mesh (tp/pp/ep/sp axes present)"
            msg = (
                "1-bit optimizer: the compressed gradient exchange does not "
                f"support {why}; the exchange would silently stay dense — a "
                "convergence-relevant behavior change vs the reference "
                "semantics. Remove the conflicting feature, or set "
                '"strict": false to accept the dense exchange.')
            if self._config.strict:
                raise ValueError(msg)
            logger.warning(msg + " (strict=false: keeping the dense "
                           "exchange; the optimizer's frozen-variance "
                           "semantics still apply)")

    # ------------------------------------------------------------------ config
    def train_batch_size(self) -> int:
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self._config.gradient_accumulation_steps

    def micro_batch_global(self) -> int:
        """Micro-batch across the whole data-parallel world (one scan step)."""
        return (self.train_micro_batch_size_per_gpu() *
                self.topology.data_parallel_size)

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def gradient_clipping(self) -> float:
        return self._config.gradient_clipping

    def steps_per_print(self) -> int:
        return self._config.steps_per_print

    def loss_scale(self) -> float:
        if not self.fp16_enabled:
            return 1.0
        return float(jax.device_get(self.state["scaler"].cur_scale))

    def get_lr(self):
        if self.offload_enabled and self._offload_opt is not None:
            return [self._host_lr()]
        # index by *applied* steps so the reported lr matches the in-graph
        # optax schedule count, which does not advance on overflow-skipped
        # steps (nor does the reference scheduler)
        step = max(self.global_steps - self.skipped_steps, 0)
        if self.lr_schedule is not None:
            return [float(self.lr_schedule(step))]
        return [self._base_lr]

    def get_global_grad_norm(self) -> Optional[float]:
        gn = self._cached_metrics.get("grad_norm")
        return float(gn) if gn is not None else None

    # --------------------------------------------------------------- optimizer
    def _configure_lr_schedule(self) -> None:
        self._base_lr = (self._config.optimizer_params or {}).get("lr", 1e-3)
        if callable(self.client_lr_scheduler):
            self.lr_schedule = self.client_lr_scheduler
        elif self._config.scheduler_name:
            self.lr_schedule = get_lr_schedule(self._config.scheduler_name,
                                               self._config.scheduler_params or {})
        else:
            self.lr_schedule = None
        self.lr_scheduler = (LRScheduler(self.lr_schedule)
                             if self.lr_schedule is not None else None)

    def _configure_optimizer(self) -> None:
        if self.client_optimizer is not None:
            base_tx = self.client_optimizer
            log_dist("Using client optimizer (optax transformation)", ranks=[0])
        elif self._config.optimizer_name:
            base_tx = get_optimizer(self._config.optimizer_name,
                                    self._config.optimizer_params or {},
                                    lr_schedule=self.lr_schedule)
            log_dist(f"Using config optimizer = {self._config.optimizer_name}",
                     ranks=[0])
        else:
            base_tx = get_optimizer("adam", {"lr": self._base_lr},
                                    lr_schedule=self.lr_schedule)
        chain = []
        if self._config.gradient_clipping:
            chain.append(optax.clip_by_global_norm(self._config.gradient_clipping))
        chain.append(base_tx)
        self.tx = optax.chain(*chain) if len(chain) > 1 else base_tx
        self.optimizer = self.tx  # reference-compat alias in the return tuple

    # ------------------------------------------------------------------- state
    def _scaler_init(self) -> LossScaleState:
        if self.fp16_enabled and not self.dynamic_loss_scale:
            return LossScaleState.create(init_scale=self._config.loss_scale)
        return LossScaleState.create(
            init_scale=self._config.dynamic_loss_scale_args["init_scale"],
            delayed_shift=self._config.dynamic_loss_scale_args["delayed_shift"])

    def _build_state(self) -> None:
        if self.param_stream_enabled:
            self._build_state_streamed()
            with trace_mod.setup_timeline().span("optimizer_state",
                                                 where="host"):
                self._init_offload_optimizer()
            return

        def onebit_errors(params):
            """Per-worker/server error-feedback buffers for the compressed
            exchange, [dp, ...]-stacked so they shard over dp.  Created at
            init (zeros are a no-op through the dense warmup) so the state
            pytree is stable across the freeze_step transition."""
            if not self.onebit_comm_enabled:
                return ()
            from .comm.compressed import error_shapes

            n = self.topology.data_parallel_size
            total = sum(int(np.prod(x.shape))
                        for x in jax.tree_util.tree_leaves(params))
            we_s, se_s = error_shapes((total,), n)
            return {"we": jnp.zeros((n,) + we_s, jnp.float32),
                    "se": jnp.zeros((n,) + se_s, jnp.float32)}

        def init_state(rng):
            # init_fn directly: a user-side OnDevice("meta") context must
            # not turn the ENGINE's init into abstract params (the engine
            # already materializes sharded-at-birth under jit)
            params = self.model_spec.init_fn(rng)
            params = _cast_floating(params, jnp.float32)  # fp32 master weights
            # offload: optimizer state is host-side (HostOffloadOptimizer)
            opt_state = () if self.offload_enabled else self.tx.init(params)
            return {
                "step": jnp.zeros((), jnp.int32),
                "params": params,
                "opt_state": opt_state,
                "scaler": self._scaler_init(),
                "onebit": onebit_errors(params),
            }

        abstract = jax.eval_shape(init_state, self._init_rng)
        self._abstract_params = abstract["params"]
        self.tp_specs = (self.model_spec.tp_rules(self._abstract_params)
                         if self.model_spec.tp_rules else None)
        rep = NamedSharding(self.mesh, P())
        from ..parallel.topology import DP_AXIS as _DP

        self.state_shardings = {
            "step": rep,
            "params": self.zero_plan.param_shardings(self._abstract_params,
                                                     self.tp_specs),
            "opt_state": self.zero_plan.opt_shardings_like(
                self._abstract_params, abstract["opt_state"], self.tp_specs),
            "scaler": jax.tree_util.tree_map(lambda _: rep, abstract["scaler"]),
            "onebit": jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P(_DP)), abstract["onebit"]),
        }
        self.grad_shardings = self.zero_plan.grad_shardings(
            self._abstract_params, self.tp_specs)
        with self.mesh:
            self.state = jax.jit(
                init_state, out_shardings=self.state_shardings)(self._init_rng)
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(self.state["params"]))
        log_dist(f"initialized {n_params/1e6:.2f}M parameters", ranks=[0])

        if self.offload_enabled:
            with trace_mod.setup_timeline().span("optimizer_state",
                                                 where="host"):
                self._init_offload_optimizer()

    def _configure_stage3_liveness(self) -> None:
        """Map ``stage3_prefetch_bucket_size`` / ``stage3_max_live_parameters``
        (reference ``zero/config.py:79``, coordinator
        ``partitioned_param_coordinator.py:239``) onto the scan granularity:
        the model gathers ``scan_group_size`` layers per scan step, so the
        prefetch bucket sets the gather size and the live cap bounds the
        resident gathered weights (current + prefetched group)."""
        mc = getattr(self.model_spec, "model_config", None)
        if mc is not None and hasattr(mc, "scan_group_size"):
            mc.scan_group_size = 1  # clear a stale G from a reused config
        if mc is not None and hasattr(mc, "scan_prefetch"):
            mc.scan_prefetch = None
        if self.zero_stage != 3 or self.param_stream_enabled:
            return
        hooks = getattr(self.model_spec, "pipeline_hooks", None) or {}
        key = hooks.get("blocks_key")
        if mc is None or key is None or not getattr(mc, "scan_layers", True) \
                or not hasattr(mc, "scan_group_size"):
            return  # model doesn't implement grouped gathers
        from .zero.liveness import blocks_param_count, stage3_group_size

        node = self._abstract_params
        try:
            for k in self._pp_blocks_path():
                node = node[k]
        except (KeyError, TypeError):
            return
        num_layers, per_layer = blocks_param_count(node)
        g = stage3_group_size(self._config.zero_config, per_layer, num_layers)
        mc.scan_group_size = g
        # overlap_comm (the reference's key; true by default at stage 3):
        # the layer loop is software-pipelined (liveness.
        # scan_layers_prefetched) — the model is handed where its blocks
        # live and what they are gathered to.  A ZeRO world of one device
        # has nothing to gather: the plain scan.
        if (self._config.zero_config.overlap_comm
                and hasattr(mc, "scan_prefetch")
                and self.topology.data_parallel_size > 1):
            mc.scan_prefetch = self._blocks_shardings(self._pp_blocks_path())
        if g > 1:
            log_dist(
                f"ZeRO-3 liveness: gathering {g} layers/scan step "
                f"({g * per_layer / 1e6:.1f}M params/bucket, "
                f"prefetch_bucket_size="
                f"{self._config.zero_config.prefetch_bucket_size:.0e}, "
                f"max_live_parameters="
                f"{self._config.zero_config.max_live_parameters:.0e})",
                ranks=[0])

    def _blocks_shardings(self, path):
        """``liveness.LayerShardings`` of the stacked blocks at ``path``:
        the ZeRO-3 sharding the state holds each leaf in, and the same
        without the ZeRO axes (its ``tp`` spec) — what a layer computes
        on."""
        from .zero.liveness import LayerShardings

        def at(tree):
            for k in path:
                tree = tree[k]
            return tree

        sharded = at(self.state_shardings["params"])
        tp = at(self.tp_specs) if self.tp_specs is not None else \
            jax.tree_util.tree_map(lambda _: None, sharded)
        gathered = jax.tree_util.tree_map(
            lambda _, spec: NamedSharding(
                self.mesh, spec if spec is not None else P()),
            sharded, tp, is_leaf=lambda x: x is None)
        return LayerShardings(sharded=sharded, gathered=gathered)

    # ------------------------------------------------- ZeRO-Infinity streaming
    def _pp_blocks_path(self) -> tuple:
        key = self.model_spec.pipeline_hooks["blocks_key"]
        return (key,) if isinstance(key, str) else tuple(key)

    def _build_state_streamed(self) -> None:
        """offload_param: init params on HOST, keep the stacked blocks in a
        StreamedParamStore (device HBM never holds more than one layer of
        them), device state holds only the small resident params — the
        persistence-threshold analog (``parameter_offload.py:316``)."""
        from .zero.param_stream import StreamedParamStore

        path = self._pp_blocks_path()
        # this process's own CPU device: under multi-controller,
        # jax.devices()[0] can be another process's device — device_get of
        # the init would fail there
        with jax.default_device(host_cpu_device()):
            params_full = jax.jit(
                lambda r: _cast_floating(self.model_spec.init_fn(r),
                                         jnp.float32))(self._init_rng)
        params_full = jax.device_get(params_full)
        node = params_full
        for k in path[:-1]:
            node = node[k]
        blocks = node[path[-1]]
        self._param_store = StreamedParamStore(blocks, self.compute_dtype)
        node[path[-1]] = {}  # resident tree: blocks live host-side only
        resident = params_full

        if self.model_spec.tp_rules is not None:
            log_dist("offload_param: ignoring tp_rules — streamed blocks are "
                     "replicated (TP over streamed layers is future work)",
                     ranks=[0])
        self.tp_specs = None
        abstract = jax.eval_shape(lambda: resident)
        self._abstract_params = abstract
        rep = NamedSharding(self.mesh, P())
        self.state_shardings = {
            "step": rep,
            "params": self.zero_plan.param_shardings(abstract, None),
            "opt_state": (),
            "scaler": jax.tree_util.tree_map(
                lambda _: rep, jax.eval_shape(self._scaler_init)),
            "onebit": (),
        }
        self.grad_shardings = self.zero_plan.grad_shardings(abstract, None)
        with self.mesh:
            state_host = {
                "step": jnp.zeros((), jnp.int32),
                "params": resident,
                "opt_state": (),
                "scaler": self._scaler_init(),
                "onebit": (),
            }
            self.state = jax.device_put(state_host, self.state_shardings)
        n_res = sum(x.size for x in
                    jax.tree_util.tree_leaves(self.state["params"]))
        n_blk = sum(m.size for m in self._param_store.master)
        log_dist(
            f"param streaming: {n_blk/1e6:.2f}M block params host-resident, "
            f"{n_res/1e6:.2f}M resident on device", ranks=[0])

        # block-master optimizer on host; adopt its flat buffer as the store's
        # master so updates land in place
        from .zero.offload import HostOffloadOptimizer

        off = self._config.zero_config.offload_optimizer
        self._block_opt = HostOffloadOptimizer(
            self._param_store.master,
            self._config.optimizer_name or "adam",
            self._config.optimizer_params or {},
            device=off.device.value,
            nvme_path=off.nvme_path,
            sub_group_size=self._config.zero_config.sub_group_size)
        self._param_store.master = self._block_opt.param_leaves()
        self._param_store.refresh_compute()

    def _streamed_loss_fn(self):
        """Loss over streamed blocks: embed/head use resident params; the
        scan body fetches one layer from host per step and is checkpointed so
        the backward re-fetches instead of saving L layers of weights."""
        import inspect

        hooks = self.model_spec.pipeline_hooks
        embed_fn, block_fn = hooks["embed_fn"], hooks["block_fn"]
        head_loss_fn = hooks["head_loss_fn"]
        dropout = float(hooks.get("dropout", 0.0) or 0.0)
        if dropout > 0.0:
            raise ValueError(
                "offload_param does not support dropout yet (the streamed "
                "block vjp would need the rng threaded through its "
                "residuals); set dropout=0")
        store = self._param_store
        L = store.num_layers
        if len(inspect.signature(block_fn).parameters) >= 3:
            call_block = lambda layer, x: block_fn(layer, x, None)
        else:
            call_block = block_fn
        apply_streamed = store.streamed_block(call_block)

        def loss_fn(params, batch, rng, train):
            if isinstance(batch, dict) and batch.get("labels") is not None:
                inputs, targets = batch["input_ids"], batch["labels"]
            else:
                ids = batch["input_ids"] if isinstance(batch, dict) else batch
                inputs, targets = ids[:, :-1], ids[:, 1:]
            x = embed_fn(params, inputs)

            def body(x, i):
                return apply_streamed(i, x), None

            x, _ = jax.lax.scan(body, x, jnp.arange(L))
            return head_loss_fn(params, x, targets)

        return loss_fn

    # ------------------------------------------------- partitioned host offload
    def to_grad_layout(self, params):
        """Reshard a params-shaped pytree into the grad (ZeRO partition)
        layout — one cached jitted identity, shared by offload init,
        checkpoint resync, and tests."""
        if not hasattr(self, "_to_grad_layout_fn"):
            self._to_grad_layout_fn = jax.jit(
                lambda p: p, out_shardings=self.grad_shardings)
        with self.mesh:
            return self._to_grad_layout_fn(params)

    @staticmethod
    def _piece_key(index) -> tuple:
        """Hashable key for a shard's index tuple (slices)."""
        return tuple((s.start or 0, s.stop) for s in index)

    def _local_pieces(self, arr) -> list:
        """Unique (key, np.ndarray) pieces of this process's shards, sorted.

        Replicated leaves dedupe to one piece; ZeRO-sharded leaves yield this
        process's partitions — the host-side analog of the reference's
        per-rank flat partition (``stage_1_and_2.py:102``)."""
        seen = {}
        for sh in arr.addressable_shards:
            key = self._piece_key(sh.index)
            if key not in seen:
                seen[key] = np.asarray(sh.data)
        return sorted(seen.items())

    def _init_offload_optimizer(self) -> None:
        """Partitioned host offload: every process owns the master/moments of
        its ZeRO partition (the grad sharding), updates it with the C++ CPU
        optimizer, and the updated partitions reshard back to the param layout
        through a jitted identity — XLA emits the all-gather the reference
        issues by hand after the offloaded step (``stage_1_and_2.py:1772``).
        Works multi-process: no ``process_count == 1`` restriction."""
        from .zero.offload import HostOffloadOptimizer

        off = self._config.zero_config.offload_optimizer
        # reshard the fp32 params into the grad (ZeRO partition) layout once;
        # each process then extracts its local pieces
        partitioned = self.to_grad_layout(self.state["params"])
        flat_parts, _ = jax.tree_util.tree_flatten(partitioned)
        self._offload_piece_keys = []
        init_pieces = []
        for leaf in flat_parts:
            items = self._local_pieces(leaf)
            self._offload_piece_keys.append([k for k, _ in items])
            init_pieces.extend(v for _, v in items)
        self._offload_opt = HostOffloadOptimizer(
            init_pieces,
            self._config.optimizer_name or "adam",
            self._config.optimizer_params or {},
            device=off.device.value,
            nvme_path=off.nvme_path,
            sub_group_size=self._config.zero_config.sub_group_size)
        # updated partitions -> param layout (replicates/allgathers as needed)
        self._offload_gather_fn = jax.jit(
            lambda p: p, out_shardings=self.state_shardings["params"],
            donate_argnums=(0,))
        log_dist(
            f"optimizer offload -> {off.device.value} "
            f"({self._offload_opt.total/1e6:.2f}M local elements, "
            f"native={self._offload_opt.opt.__class__.__name__}, "
            f"process {jax.process_index()}/{jax.process_count()})",
            ranks=[0])

    def _offload_pieces_of(self, tree) -> list:
        """Flatten a (grad-sharded) pytree into this process's pieces, in the
        same order as the host optimizer's layout."""
        pieces = []
        for leaf, keys in zip(jax.tree_util.tree_leaves(tree),
                              self._offload_piece_keys):
            items = dict(self._local_pieces(leaf))
            pieces.extend(items[k] for k in keys)
        return pieces

    def _offload_rebuild_params(self, new_pieces: list):
        """Reassemble updated partitions into sharded jax arrays (grad layout)
        then reshard to the param layout on device."""
        flat_abs, treedef = jax.tree_util.tree_flatten(self._abstract_params)
        flat_specs = jax.tree_util.tree_leaves(
            self.grad_shardings, is_leaf=lambda x: hasattr(x, "spec"))
        arrays = []
        i = 0
        for leaf_abs, spec, keys in zip(flat_abs, flat_specs,
                                        self._offload_piece_keys):
            by_key = {k: np.asarray(new_pieces[i + j], np.float32)
                      for j, k in enumerate(keys)}
            i += len(keys)
            dev_map = spec.addressable_devices_indices_map(leaf_abs.shape)
            bufs = [jax.device_put(by_key[self._piece_key(idx)], d)
                    for d, idx in dev_map.items()]
            arrays.append(jax.make_array_from_single_device_arrays(
                leaf_abs.shape, spec, bufs))
        partitioned = jax.tree_util.tree_unflatten(treedef, arrays)
        with self.mesh:
            return self._offload_gather_fn(partitioned)

    # --------------------------------------------------------------- step fns
    def _micro_loss_closure(self):
        loss_fn = (self._streamed_loss_fn() if self.param_stream_enabled
                   else self.model_spec.loss_fn)
        self._loss_impl = loss_fn  # eval shares it (streamed blocks strip
        # params["blocks"], so model_spec.loss_fn would not trace there)
        compute_dtype = self.compute_dtype
        cast = self.fp16_enabled or self.bfloat16_enabled

        def micro_loss(params, micro, rng, scale):
            """-> (scaled loss, (loss, record)); ``record`` is what the
            model returned beside its loss, ``None`` for a scalar loss."""
            with jax.named_scope("optim/cast"):
                p = _cast_floating(params, compute_dtype) if cast else params
            out = loss_fn(p, micro, rng, True)
            loss, record = out if isinstance(out, tuple) else (out, None)
            with jax.named_scope("loss"):
                return (loss.astype(jnp.float32) * scale), (loss, record)

        return micro_loss

    def _scaler_bookkeeping(self):
        """Shared fp16 scaler-advance + metrics builders (one source of truth
        for the in-jit update path and the host-offload path)."""
        fp16 = self.fp16_enabled
        dynamic = self.dynamic_loss_scale
        scaler_args = self._config.dynamic_loss_scale_args

        def next_scaler(scaler, overflow):
            if not fp16:
                return scaler
            return update_scale(
                scaler, overflow,
                scale_window=scaler_args["scale_window"],
                min_scale=scaler_args["min_scale"],
                delayed_shift=scaler_args["delayed_shift"],
                dynamic=dynamic)

        def make_metrics(mean_loss, grad_norm, overflow, new_scaler):
            return {
                "loss": mean_loss,
                "grad_norm": grad_norm,
                "overflow": overflow,
                "loss_scale": new_scaler.cur_scale,
                "skipped": new_scaler.skipped,
            }

        return next_scaler, make_metrics

    def _make_apply_update(self):
        """Build the shared optimizer-apply closure (overflow skip, scaler
        update, metrics) — used by both the DP and pipeline step functions."""
        fp16 = self.fp16_enabled
        tx = self.tx
        next_scaler, make_metrics = self._scaler_bookkeeping()

        @jax.named_scope("optim/update")
        def apply_update(state, grads, mean_loss):
            """grads: fp32, already averaged over the global batch & unscaled."""
            params, opt_state, scaler = (state["params"], state["opt_state"],
                                         state["scaler"])
            with jax.named_scope("grad/merge"):
                grad_norm = optax.global_norm(grads)
            overflow = has_overflow(grads) if fp16 else jnp.asarray(False)

            def do_update(_):
                updates, new_opt = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                return new_params, new_opt

            def skip_update(_):
                return params, opt_state

            if fp16:
                new_params, new_opt = jax.lax.cond(overflow, skip_update,
                                                   do_update, None)
            else:
                new_params, new_opt = do_update(None)
            new_scaler = next_scaler(scaler, overflow)
            new_state = {
                **state,  # pass through aux entries (e.g. onebit errors)
                "step": state["step"] + 1,
                "params": new_params,
                "opt_state": new_opt,
                "scaler": new_scaler,
            }
            metrics = make_metrics(mean_loss, grad_norm, overflow, new_scaler)
            return new_state, metrics

        return apply_update

    def _metrics_shardings(self):
        """One replicated sharding for every metric of a step (a pytree
        prefix: the model's record, where there is one, included)."""
        return NamedSharding(self.mesh, P())

    def _step_entry(self, fn, name: str, budget: Optional[int] = 1):
        """``sentry.wrap`` of a step body that also records, each time the
        body is traced, which flash kernels at which blocks the program
        got: ``flash_attention`` resolves its blocks at trace time
        (``ops/flash_attention.choices``), so what this trace added to that
        table is what the compiled step runs.  One log line a distinct
        resolution — blocks, strip, visible against computed pairs
        (``fa.computed_pairs``) — and the ``train_flash_block_q/k``,
        ``train_flash_strip`` and ``train_flash_computed_share`` gauges;
        ``flash_choices[name]`` keeps them whole for a reader without the
        log.  Beside it, what each checkpointed block of the program KEEPS
        for its backward (``runtime/remat.py``: ``remat_kept[name]``, one
        log line, the ``train_remat_kept_bytes`` gauge): ``input`` alone is
        a step that re-runs its attention in the backward,
        ``input+flash_lse+flash_out`` one whose flash forward runs once."""
        from ..ops import flash_attention as fa
        from . import remat

        @functools.wraps(fn)
        def traced(*args):
            before = fa.choices()
            with remat.listen() as blocks:
                out = fn(*args)
            ran = fa.choices(since=before)
            self.flash_choices[name] = ran
            self.remat_kept[name] = kept = remat.kept(blocks)
            for k, n in kept.items():
                log_dist(
                    f"{name}: a checkpointed block ({k.block}) keeps "
                    + " + ".join(f"{what} {b:,} B" for what, b in
                                 (("input", k.input), *k.named,
                                  ("other", k.other)) if b)
                    + f" = {k.bytes:,} B a micro-batch: {n} call(s) traced",
                    ranks=[0])
                self.metrics.gauge(
                    "train_remat_kept_bytes",
                    "bytes one checkpointed block call keeps for its "
                    "backward, a micro-batch (phase: the program; mode: "
                    "what is kept)",
                    phase=name, mode=k.what).set(k.bytes)
            for c, n in ran.items():
                visible, computed = fa.computed_pairs(c)
                share = 100.0 * visible / max(computed, 1)
                log_dist(
                    f"{name}: flash attention {c.generation} "
                    f"({' + '.join(fa.KERNELS[c.generation])}) at blocks "
                    f"{c.block_q} x {c.block_k} ({c.how}) for q {c.q_len} x "
                    f"kv {c.kv_len}, hd {c.d}"
                    + (f", window {c.window}" if c.window else "")
                    + (f", edge tiles in strips of {c.strip} rows"
                       if c.strip else ", whole tiles")
                    + f": {visible:,} visible of {computed:,} computed pairs "
                    f"a head ({share:.1f} %): {n} call(s) traced",
                    ranks=[0])
                mode = f"{c.generation}_{c.how}"
                for gauge, value, what in (
                        ("block_q", c.block_q, "flash attention block"),
                        ("block_k", c.block_k, "flash attention block"),
                        ("strip", c.strip, "rows of a strip inside a flash "
                         "tile an edge crosses (0: whole tiles)"),
                        ("computed_share", share, "visible pairs over the "
                         "pairs the flash kernels compute, %")):
                    self.metrics.gauge(
                        f"train_flash_{gauge}",
                        f"{what} of a compiled step (phase: the program; "
                        "mode: kernel generation, chosen|given)",
                        phase=name, mode=mode).set(value)
            return out

        return self.sentry.wrap(traced, name, budget)

    def _first_call(self, fn, program: str, attr: str):
        """Jitted step ``fn`` under the name the sentry registered, its
        FIRST call a ``build`` span of the start-up ring
        (``telemetry/trace.py FirstCall``: trace, lowering, compile, load
        and the first step, until the new state is there).  That call
        over, the bare function takes the wrapper's place (``attr``), so
        no later step passes through it, and the engine logs the start-up
        line."""
        def built(bare):
            setattr(self, attr, bare)
            log_dist(trace_mod.setup_line(), ranks=[0])

        return trace_mod.FirstCall(
            fn, program, built,
            before=functools.partial(self._read_collectives, program),
            programs=self.programs, gas=self.gradient_accumulation_steps(),
            micro_batch=self.train_micro_batch_size_per_gpu())

    def program_table(self, name: str) -> Dict[str, Any]:
        """The scope table of the compiled step ``name`` (``train_step``,
        ``train_multi``: a key of ``self.programs.records``, there from the
        step's first call) — per instruction of its schedule and per scope
        and pass: bytes, matmul flops, kernels, trips
        (``telemetry/hlo_text.py scope_table``).  Built at the first demand
        from the executable that is running: no trace, no compile."""
        return self.programs.table(name)

    def _read_collectives(self, program: str, fn, *args) -> None:
        """``collectives[program]``: what collectives the compiled step has
        and how many of them sit plain — synchronous, holding the core —
        inside a loop (``runtime/zero/collectives.py``, from the scheduled
        text of the step compiled for these very arguments; the call that
        follows finds the executable in JAX's caches, so the program is
        still compiled once).  One log line; the
        ``train_collectives_in_loop_plain`` gauge on report steps.  A step
        on one device has none and is not read."""
        from .zero import collectives

        if self.mesh.size == 1:
            self.collectives[program] = collectives.count("")
            return
        found = collectives.count(fn.lower(*args).compile().as_text())
        self.collectives[program] = found
        log_dist(f"{program}: collectives {collectives.line(found)}",
                 ranks=[0])
        for kind, row in found.items():
            self.metrics.gauge(
                "train_collectives_in_loop_plain",
                "collectives of a compiled step left synchronous inside a "
                "loop (phase: the program; mode: the kind)",
                phase=program, mode=kind).set(row["plain"])

    def setup_report(self) -> Optional[Dict[str, Any]]:
        """The process's start-up ring in numbers — seconds by phase of
        ``initialize`` and by program, what else JAX built and where, what
        the compile cache answered (``telemetry/trace.py
        setup_summary``)."""
        return trace_mod.setup_summary()

    def _build_step_fns(self) -> None:
        # recompile sentry (analysis/sentry.py): the config pins batch
        # shapes, so the fused train step compiles exactly once (budget 1)
        # and any retrace is contract drift — visible in sentry.report() /
        # retraces_observed.  multi-step/eval legitimately specialize per
        # shape (scan length = leading batch dim): budget None, count only.
        self.sentry = RecompileSentry(name="training")
        self.flash_choices: Dict[str, Dict[Any, int]] = {}
        self.remat_kept: Dict[str, Dict[Any, int]] = {}
        gas = self.gradient_accumulation_steps()
        fp16 = self.fp16_enabled
        micro_loss = self._micro_loss_closure()
        grad_shardings = self.grad_shardings
        apply_update = self._make_apply_update()

        def grads_of_micro(params, micro, rng, scale):
            (scaled_loss, (loss, record)), grads = jax.value_and_grad(
                micro_loss, has_aux=True)(params, micro, rng, scale)
            del scaled_loss
            return loss, grads, record

        @jax.named_scope("grad/merge")
        def accumulate(state, batch, base_rng):
            """Scan the GAS microbatches; returns (unscaled fp32 grads,
            loss, the model's record summed over them or None).  What the
            model's own scopes do not name inside it — the accumulation,
            the scan's stacked-leaf merge — is ``grad/merge``'s."""
            params, scaler = state["params"], state["scaler"]
            scale = scaler.cur_scale if fp16 else jnp.asarray(1.0, jnp.float32)
            step_rng = jax.random.fold_in(base_rng, state["step"])

            if gas == 1:
                # fast path: no accumulator (saves a zero-init + add pass
                # over a full fp32 grad buffer per step)
                micro = jax.tree_util.tree_map(lambda x: x[0], batch)
                loss, grads, record = grads_of_micro(
                    params, micro, jax.random.fold_in(step_rng, 0), scale)
                inv = 1.0 / scale
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32) * inv, grads)
                grads = constrain(grads, grad_shardings)
                return grads, loss.astype(jnp.float32), record

            zero_grads = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            zero_grads = constrain(zero_grads, grad_shardings)

            def body(carry, xs):
                acc, loss_sum = carry
                micro, idx = xs
                rng = jax.random.fold_in(step_rng, idx)
                loss, grads, record = grads_of_micro(params, micro, rng,
                                                     scale)
                acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), acc, grads)
                acc = constrain(acc, grad_shardings)
                return (acc, loss_sum + loss.astype(jnp.float32)), record

            (grads, loss_sum), records = jax.lax.scan(
                body, (zero_grads, jnp.zeros((), jnp.float32)),
                (batch, jnp.arange(gas)))
            inv = 1.0 / (gas * scale)
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            grads = constrain(grads, grad_shardings)
            mean_loss = loss_sum / gas
            record = jax.tree_util.tree_map(lambda r: r.sum(0), records)
            return grads, mean_loss, record

        def train_step(state, batch, base_rng):
            """batch: pytree with leading dims [gas, micro_global, ...]."""
            grads, mean_loss, record = accumulate(state, batch, base_rng)
            new_state, metrics = apply_update(state, grads, mean_loss)
            if record is not None:
                metrics = {**metrics, MODEL_RECORD: record}
            return new_state, metrics

        clip = self._config.gradient_clipping
        next_scaler, make_metrics = self._scaler_bookkeeping()
        self._next_scaler = next_scaler  # host-side reuse (param streaming)

        def offload_finish(state, grads, mean_loss):
            """Clip + overflow + scaler bookkeeping for grads headed to the
            host optimizer (grads already unscaled/averaged).  Under param
            streaming, clipping moves to the host where the streamed block
            grads can contribute to the global norm."""
            grad_norm = optax.global_norm(grads)
            if clip and not self.param_stream_enabled:
                factor = jnp.minimum(1.0, clip / (grad_norm + 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
            overflow = has_overflow(grads) if fp16 else jnp.asarray(False)
            new_scaler = next_scaler(state["scaler"], overflow)
            metrics = make_metrics(mean_loss, grad_norm, overflow, new_scaler)
            partial = {"step": state["step"] + 1, "scaler": new_scaler}
            return grads, partial, metrics

        def offload_grads_step(state, batch, base_rng):
            """Device half of the offload step: grads + clip + scaler
            bookkeeping in-graph; the optimizer apply happens on host."""
            grads, mean_loss, _ = accumulate(state, batch, base_rng)
            return offload_finish(state, grads, mean_loss)

        def micro_grads(params, scaler, batch, base_rng, idx):
            """One microbatch fwd+bwd for the forward/backward shim path."""
            scale = scaler.cur_scale if fp16 else jnp.asarray(1.0, jnp.float32)
            rng = jax.random.fold_in(base_rng, idx)
            loss, grads, _ = grads_of_micro(params, batch, rng, scale)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) / (gas * scale), grads)
            grads = constrain(grads, grad_shardings)
            return loss, grads

        def eval_step(params, batch, base_rng):
            p = (_cast_floating(params, self.compute_dtype)
                 if (self.fp16_enabled or self.bfloat16_enabled) else params)
            out = self._loss_impl(p, batch, base_rng, False)
            return out[0] if isinstance(out, tuple) else out

        rep = NamedSharding(self.mesh, P())
        metrics_shardings = self._metrics_shardings()
        def multi_step(state, batches, base_rng):
            """k train_steps under one jit; scan length = leading batch dim
            (jit specializes per shape, so one callable serves every k)."""
            def body(st, b):
                return train_step(st, b, base_rng)

            return jax.lax.scan(body, state, batches)

        self._train_multi_fn = self._first_call(jax.jit(
            self._step_entry(multi_step, "train_multi", budget=None),
            out_shardings=(self.state_shardings, metrics_shardings),
            donate_argnums=(0,)), "train_multi", "_train_multi_fn")
        self._train_step_fn = self._first_call(jax.jit(
            self._step_entry(train_step, "train_step"),
            out_shardings=(self.state_shardings, metrics_shardings),
            donate_argnums=(0,)), "train_step", "_train_step_fn")
        if self.onebit_comm_enabled and self._onebit_compressed:
            self._install_onebit_step(metrics_shardings)
        if self.offload_enabled:
            scaler_rep = jax.tree_util.tree_map(
                lambda _: rep, self.state_shardings["scaler"])
            offload_out = (self.grad_shardings,
                           {"step": rep, "scaler": scaler_rep},
                           metrics_shardings)
            # donate_argnums=() is deliberate: these return grads + a
            # partial {step, scaler} — state itself outlives the call (the
            # host optimizer applies the update and params are rebuilt)
            self._offload_grads_fn = jax.jit(offload_grads_step,
                                             donate_argnums=(),
                                             out_shardings=offload_out)
            self._offload_finish_fn = jax.jit(offload_finish,
                                              donate_argnums=(),
                                              out_shardings=offload_out)
        self._micro_grads_fn = jax.jit(
            micro_grads, out_shardings=(rep, self.grad_shardings),
            static_argnums=())
        self._apply_update_fn = jax.jit(
            apply_update,
            out_shardings=(self.state_shardings, metrics_shardings),
            donate_argnums=(0,))
        self._eval_step_fn = jax.jit(
            self._step_entry(eval_step, "eval_step", budget=None))
        self._tree_add_fn = jax.jit(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
            donate_argnums=(0,))

    def _install_onebit_step(self, metrics_shardings) -> None:
        """Replace the train step with one whose DP gradient exchange runs
        through the 1-bit compressed all-reduce (reference
        ``runtime/comm/nccl.py:52``).

        The default step computes grads under global-jit semantics, where
        XLA inserts the dense psum implicitly — there is no seam to
        compress.  This variant runs the whole fwd/bwd inside ``shard_map``
        over dp, so each device holds its LOCAL gas-accumulated gradient,
        flattens it, and exchanges PACKED sign bits (8/byte) + per-chunk
        scales (~32x wire reduction) with persistent worker/server error feedback
        carried in ``state["onebit"]``.  Installed only past freeze_step;
        warmup uses the dense path (``_advance_onebit`` retraces at the
        boundary, the same pattern as compression schedule_offsets).
        """
        from jax.flatten_util import ravel_pytree

        from ..parallel.topology import DP_AXIS
        from .comm.compressed import compressed_allreduce

        gas = self.gradient_accumulation_steps()
        fp16 = self.fp16_enabled
        micro_loss = self._micro_loss_closure()
        apply_update = self._make_apply_update()
        mesh = self.mesh

        leaves, treedef = jax.tree_util.tree_flatten(self._abstract_params)
        sizes = [int(np.prod(x.shape)) for x in leaves]
        offsets = np.concatenate([[0], np.cumsum(sizes)])

        def unflatten(flat):
            parts = [flat[int(o):int(o) + s].reshape(l.shape)
                     for o, s, l in zip(offsets, sizes, leaves)]
            return jax.tree_util.tree_unflatten(treedef, parts)

        def local_grads(params, scaler, step, batch, base_rng, we, se):
            """Runs per-device inside shard_map: batch is the LOCAL
            [gas, micro_local, ...] shard; we/se lose their stacking dim."""
            we, se = we[0], se[0]
            scale = (scaler.cur_scale if fp16
                     else jnp.asarray(1.0, jnp.float32))
            step_rng = jax.random.fold_in(
                jax.random.fold_in(base_rng, step),
                jax.lax.axis_index(DP_AXIS))

            def body(carry, xs):
                acc, loss_sum = carry
                micro, idx = xs
                rng = jax.random.fold_in(step_rng, idx)
                (_, (loss, _record)), grads = jax.value_and_grad(
                    micro_loss, has_aux=True)(params, micro, rng, scale)
                acc = acc + ravel_pytree(grads)[0].astype(jnp.float32)
                return (acc, loss_sum + loss.astype(jnp.float32)), None

            total = int(offsets[-1])
            (flat, loss_sum), _ = jax.lax.scan(
                body, (jnp.zeros((total,), jnp.float32),
                       jnp.zeros((), jnp.float32)),
                (batch, jnp.arange(gas)))
            flat = flat / (gas * scale)
            mean_flat, nwe, nse = compressed_allreduce(flat, we, se, DP_AXIS)
            loss = jax.lax.pmean(loss_sum / gas, DP_AXIS)
            return mean_flat, loss, nwe[None], nse[None]

        P_ = P
        sm = jax.shard_map(
            local_grads, mesh=mesh,
            in_specs=(P_(), P_(), P_(), P_(None, DP_AXIS), P_(),
                      P_(DP_AXIS), P_(DP_AXIS)),
            out_specs=(P_(), P_(), P_(DP_AXIS), P_(DP_AXIS)),
            check_vma=False)

        def train_step(state, batch, base_rng):
            mean_flat, mean_loss, nwe, nse = sm(
                state["params"], state["scaler"], state["step"], batch,
                base_rng, state["onebit"]["we"], state["onebit"]["se"])
            grads = unflatten(mean_flat)
            new_state, metrics = apply_update(state, grads, mean_loss)
            # fp16 overflow: an inf gradient turns the compression scales
            # inf and the residuals NaN — the param update is skipped by
            # apply_update, and the error buffers must roll back with it or
            # every later step inherits the NaN
            overflow = metrics["overflow"]
            new_state = {**new_state, "onebit": jax.tree_util.tree_map(
                lambda old, new: jnp.where(overflow, old, new),
                state["onebit"], {"we": nwe, "se": nse})}
            return new_state, metrics

        def multi_step(state, batches, base_rng):
            return jax.lax.scan(
                lambda st, b: train_step(st, b, base_rng), state, batches)

        self._train_step_fn = self._first_call(jax.jit(
            self._step_entry(train_step, "train_step_onebit"),
            out_shardings=(self.state_shardings, metrics_shardings),
            donate_argnums=(0,)), "train_step_onebit", "_train_step_fn")
        self._train_multi_fn = self._first_call(jax.jit(
            self._step_entry(multi_step, "train_multi_onebit", budget=None),
            out_shardings=(self.state_shardings, metrics_shardings),
            donate_argnums=(0,)), "train_multi_onebit", "_train_multi_fn")

    # ---------------------------------------------------------------- batching
    def _batch_sharding(self, leading_gas_dim, x=None):
        """Batch dim over (dp, ep); if sp>1, the sequence dim over sp too
        (when it divides — SP attention reshards internally otherwise).

        ``leading_gas_dim`` counts unsharded leading dims before the batch
        dim (bool for the historical [gas, micro] case; 2 for the
        multi-step [steps, gas, micro] layout of ``train_batches``)."""
        dims = [None] * int(leading_gas_dim) + [DATA_AXES]
        if x is not None:
            seq_dim = len(dims)
            sp = self.topology.sequence_parallel_size
            x_shape = getattr(x, "shape", ())
            # multi-process: the dataloader shards only the batch dim per
            # process, so seq-dim process-sharding would mis-assemble the
            # global array; SP attention reshards in-graph instead
            if (sp > 1 and jax.process_count() == 1
                    and len(x_shape) > seq_dim
                    and x_shape[seq_dim] % sp == 0):
                dims.append(SP_AXIS)
        return NamedSharding(self.mesh, P(*dims))

    def _shard_batch(self, batch, leading_gas_dim: bool = False):
        if jax.process_count() > 1:
            # each controller holds only its slice of the global batch (see
            # DeepSpeedDataLoader process_shard); assemble the global array
            return jax.tree_util.tree_map(
                lambda x: jax.make_array_from_process_local_data(
                    self._batch_sharding(leading_gas_dim, x), np.asarray(x)),
                batch)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(
                jnp.asarray(x), self._batch_sharding(leading_gas_dim, x)),
            batch)

    def _stack_micros(self, micros) -> PyTree:
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *micros)

    def _reshape_global_batch(self, batch) -> PyTree:
        gas = self.gradient_accumulation_steps()
        mb = self.micro_batch_global()

        def reshape(x):
            x = np.asarray(x)
            assert x.shape[0] == gas * mb, (
                f"train_batch expects global batch {gas * mb}, got {x.shape[0]}")
            return x.reshape((gas, mb) + x.shape[1:])

        return jax.tree_util.tree_map(reshape, batch)

    def _apply_curriculum(self, batch):
        """Truncate token batches to the curriculum difficulty (reference
        ``engine.py:1806-1812`` curriculum seqlen).  Difficulty is quantized
        by the schedule so the set of compiled shapes stays small."""
        if self.curriculum_scheduler is None or \
                self.curriculum_scheduler.curriculum_type != "seqlen":
            return batch
        diff = self.curriculum_scheduler.update_difficulty(
            self.global_steps + 1)

        def trunc(x):
            x = np.asarray(x)
            if x.ndim >= 1 and np.issubdtype(x.dtype, np.integer) and \
                    x.shape[-1] > diff + 1:
                return x[..., : diff + 1]  # +1: targets shift by one
            return x

        return jax.tree_util.tree_map(trunc, batch)

    # ------------------------------------------------------------------- train
    def _advance_random_ltd(self, batch) -> None:
        """Move the model's kept-token count to this step's schedule value;
        retrace when it changes (the count is a static shape).  The count is
        clamped to the batch sequence length so a schedule whose max_value
        exceeds the trained sequence cannot trigger rebuilds of semantically
        identical dense programs; once the clamped value can no longer
        change, the scheduler is marked saturated (train_batches then takes
        the fused multi-step dispatch again)."""
        if self._ltd_saturated:
            return
        # trained sequence length: input_ids minus the shift-by-one ONLY
        # when targets come from shifting (no explicit labels)
        if isinstance(batch, dict) and "input_ids" in batch:
            seq = batch["input_ids"].shape[-1]
            if batch.get("labels") is None:
                seq -= 1
        elif isinstance(batch, (tuple, list)) and len(batch) >= 2:
            # (input_ids, labels): explicit labels, no shift-by-one
            # (models/gpt2.py loss convention)
            seq = jax.tree_util.tree_leaves(batch[0])[0].shape[-1]
        else:
            seq = jax.tree_util.tree_leaves(batch)[0].shape[-1] - 1
        keep = self.random_ltd_scheduler.get_keep_count(
            self.global_steps, seq)
        if keep != self._ltd_keep:
            self._ltd_keep = keep
            self.model_spec.model_config.random_ltd_keep = keep
            log_dist(f"random-LTD: kept-token count -> {keep} at step "
                     f"{self.global_steps}", ranks=[0])
            self._build_step_fns()
        # latch ONLY when the schedule is fully ramped AND the model holds
        # the unclamped endpoint: a seq-clamped value must keep following
        # the batch (curriculum seqlen can grow later)
        if keep >= self.random_ltd_scheduler.max_value and \
                self.random_ltd_scheduler.get_keep_count(
                    self.global_steps, 1 << 30) >= \
                self.random_ltd_scheduler.max_value:
            self._ltd_saturated = True
            log_dist(f"random-LTD: schedule saturated at {keep} kept tokens; "
                     "no further retraces", ranks=[0])

    def train_batch(self, batch=None, data_iter=None) -> Tuple[Any, Dict]:
        """Run one full global step (all GAS microbatches + update) in one jit.

        ``batch`` leading dim may be ``train_batch_size`` (reshaped to
        [gas, micro]) or already [gas, micro_global, ...].  Alternatively pass
        ``data_iter`` yielding micro-global batches (reference
        ``PipelineEngine.train_batch`` signature).
        """
        # host spans on the profiler's clock while a profile is being
        # taken (telemetry/trace.py annotation: a flag test otherwise);
        # batch_prep ends with the host-to-device copy of the batch
        with annotation("ds.train.batch_prep"):
            if batch is None:
                it = data_iter or self._ensure_data_iterator()
                micros = [next(it) for _ in
                          range(self.gradient_accumulation_steps())]
                batch = self._stack_micros(micros)
            else:
                first = jax.tree_util.tree_leaves(batch)[0]
                if first.shape[0] == self.train_batch_size() and \
                        self.gradient_accumulation_steps() * \
                        self.micro_batch_global() == self.train_batch_size():
                    batch = self._reshape_global_batch(batch)
            batch = self._apply_curriculum(batch)
            batch = self._shard_batch(batch, leading_gas_dim=True)

        # compression schedule_offsets: advance the trace-time step marker
        # when a mechanism's offset is crossed and retrace (reference applies
        # each mechanism from its own schedule_offset onward)
        toggle = getattr(self.model_spec, "_compression_toggle", None)
        if toggle is not None:
            completed = self.global_steps  # steps finished before this one
            crossed = [off for off in self.model_spec._compression_offsets
                       if toggle.step < off <= completed]
            if crossed:
                toggle.step = completed
                log_dist(
                    f"compression: mechanisms with schedule_offset in "
                    f"{crossed} activate after {completed} steps", ranks=[0])
                self._build_step_fns()

        if self.random_ltd_scheduler is not None:
            self._advance_random_ltd(batch)

        # 1-bit: dense warmup until freeze_step, compressed exchange after
        # (reference keeps the variance-adaptation phase uncompressed)
        if self.onebit_comm_enabled and not self._onebit_compressed and \
                self.global_steps >= self._onebit_freeze:
            self._onebit_compressed = True
            log_dist(
                f"1-bit: freeze_step {self._onebit_freeze} reached — "
                "gradient exchange switches to the compressed all-reduce "
                "(packed sign bits + per-chunk scales, ~32x wire "
                "reduction)",
                ranks=[0])
            self._build_step_fns()

        fp = self._config.flops_profiler_config
        profiling_now = fp.enabled and \
            self.global_steps + 1 == fp.profile_step
        t0 = time.perf_counter() if profiling_now else None

        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        # StepTraceAnnotation: profile viewers group the trace by step
        with jax.profiler.StepTraceAnnotation(
                "ds.train.step", step_num=self.global_steps), \
                annotation("ds.train.dispatch"):
            if self.offload_enabled:
                self.state, metrics = self._train_step_offload(self.state,
                                                               batch)
            else:
                self.state, metrics = self._train_step_fn(
                    self.state, batch, self._dropout_rng)
        self.global_steps += 1
        self.micro_steps += self.gradient_accumulation_steps()
        self.global_samples += self.train_batch_size()
        # sync only on report steps: a per-step block would serialize the
        # dispatch pipeline (expensive host round-trip on remote backends)
        sync = metrics["loss"] if (profiling_now or self.global_steps %
                                   max(self.steps_per_print(), 1) == 0) \
            else None
        self.tput_timer.stop(global_step=True, sync_arrays=sync)
        # same sync decision: un-synced steps record dispatch latency, the
        # periodic synced step bounds the true step time (timer docstring)
        self.timers(TRAIN_BATCH_TIMER).stop(sync_arrays=sync)
        self._finalize_metrics(metrics)

        if profiling_now:
            # reference hooks the profiler at profile_step
            # (``runtime/engine.py:315,1796``); cost-analyze the compiled
            # step and reuse THIS step's measured wall clock — the profiler
            # observes training, it does not run extra updates
            from ..profiling.flops_profiler import FlopsProfiler

            latency = time.perf_counter() - t0
            self.flops_profiler = FlopsProfiler(engine=self)
            self.flops_profiler.profile_engine_step(batch, latency=latency)
            self.flops_profiler.print_profile(fp.output_file)
        return self.state, self._cached_metrics

    def train_batches(self, batches) -> Tuple[Any, Dict]:
        """Run several consecutive global steps in ONE device dispatch.

        ``batches``: a list of global batches (each as accepted by
        ``train_batch``) or a pytree already stacked on a leading steps dim
        ``[k, gas, micro_global, ...]``.  Semantically identical to ``k``
        ``train_batch`` calls — the update happens every ``gas``
        microbatches, RNG folds per step — but the k steps execute as one
        ``lax.scan``, so per-step host dispatch latency (the problem the
        reference solves with CUDA-graph capture,
        ``inference/engine.py:479``) is paid once per k.

        Falls back to per-step ``train_batch`` when a host-side feature
        needs to observe every step (offload optimizer, compression
        schedule offsets, curriculum seqlen, flops profiling).
        """
        if isinstance(batches, (list, tuple)):
            k = len(batches)
            stacked = None
        else:
            leaves = jax.tree_util.tree_leaves(batches)
            k = leaves[0].shape[0] if leaves else 0
            stacked = batches
        if k < 1:
            raise ValueError(
                "train_batches requires at least one batch (got an empty "
                f"{'list' if stacked is None else 'stacked pytree'})")
        fp = self._config.flops_profiler_config
        host_side_feature = (
            self.offload_enabled
            or getattr(self.model_spec, "_compression_toggle", None) is not None
            or (self.random_ltd_scheduler is not None
                and not self._ltd_saturated)
            or (self.onebit_comm_enabled and not self._onebit_compressed)
            or (self.curriculum_scheduler is not None
                and self.curriculum_scheduler.curriculum_type == "seqlen")
            or (fp.enabled
                and self.global_steps < fp.profile_step <= self.global_steps + k))
        if host_side_feature or k == 1:
            if stacked is not None:
                batches = [jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
                           for i in range(k)]
            for b in batches:
                out = self.train_batch(b)
            return out

        if stacked is None:
            reshaped = []
            for b in batches:
                first = jax.tree_util.tree_leaves(b)[0]
                if first.shape[0] == self.train_batch_size() and \
                        self.gradient_accumulation_steps() * \
                        self.micro_batch_global() == self.train_batch_size():
                    b = self._reshape_global_batch(b)
                reshaped.append(b)
            stacked = jax.tree_util.tree_map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]), *reshaped)
        dev = self._shard_batch(stacked, leading_gas_dim=2)

        self.tput_timer.start()
        self.state, mstack = self._train_multi_fn(
            self.state, dev, self._dropout_rng)
        self.global_steps += k
        self.micro_steps += k * self.gradient_accumulation_steps()
        self.global_samples += k * self.train_batch_size()
        metrics = jax.tree_util.tree_map(lambda a: a[-1], mstack)
        # mirror the timer's multi-step report condition (% < k, not == 0):
        # a report without a sync would print dispatch-only throughput
        sync = metrics["loss"] if (self.global_steps %
                                   max(self.steps_per_print(), 1) < k) \
            else None
        self.tput_timer.stop(global_step=True, sync_arrays=sync, steps=k)
        self._finalize_metrics(metrics, steps=k)
        return self.state, self._cached_metrics

    def _train_step_offload(self, state, batch):
        """Offload step: device grads -> host C++ optimizer -> device params.

        The transfer/step/transfer is the TPU analog of the reference's
        PCIe grad-offload + CPU-Adam + param copy-back cycle
        (``stage_1_and_2.py:1096``, ``csrc/adam/cpu_adam.cpp``).
        """
        grads, partial, metrics = self._offload_grads_fn(state, batch,
                                                         self._dropout_rng)
        return self._host_apply(state, grads, partial, metrics)

    def _host_lr(self) -> float:
        """LR for the host optimizer: indexed by *applied* steps so overflow-
        skipped steps don't advance the schedule (matches the in-graph optax
        scale_by_schedule count and the reference's scheduler semantics)."""
        if self.lr_schedule is not None:
            return float(self.lr_schedule(self._offload_opt.applied_steps))
        return self._base_lr

    def _host_apply(self, state, grads, partial, metrics):
        new_params = state["params"]
        overflow = self.fp16_enabled and bool(
            jax.device_get(metrics["overflow"]))
        if self.param_stream_enabled:
            # join in-flight grad-push io_callbacks before reading the host
            # accumulator — array readiness does not imply callback completion
            jax.effects_barrier()
            # scale/average the host-accumulated block grads exactly like the
            # in-graph path did for the resident grads
            scale = (float(jax.device_get(state["scaler"].cur_scale))
                     if self.fp16_enabled else 1.0)
            factor = 1.0 / (self.gradient_accumulation_steps() * scale)
            block_grads = self._param_store.pop_grads()
            for g in block_grads:
                g *= factor
            if jax.process_count() > 1:
                # combine per-process contributions (each process's grad-push
                # callbacks saw only its addressable devices' cotangents)
                block_grads = dist.host_all_reduce_sum(block_grads)
            if self.fp16_enabled and not overflow:
                block_overflow = not all(np.isfinite(g).all()
                                         for g in block_grads)
                if block_overflow:
                    # the in-graph scaler bookkeeping saw only resident grads
                    # and advanced as a successful step; redo it with
                    # overflow=True so the scale backs off (no livelock)
                    overflow = True
                    new_scaler = self._next_scaler(state["scaler"],
                                                   jnp.asarray(True))
                    partial = dict(partial)
                    partial["scaler"] = new_scaler
                    metrics = dict(metrics)
                    metrics["overflow"] = np.asarray(True)
                    metrics["loss_scale"] = new_scaler.cur_scale
                    metrics["skipped"] = new_scaler.skipped
        if not overflow:
            grad_pieces = [np.array(p, np.float32) for p in
                           self._offload_pieces_of(grads)]
            if self.param_stream_enabled:
                # host-side global clip across resident + streamed grads
                clip = self._config.gradient_clipping
                sq = sum(float(np.vdot(p, p)) for p in grad_pieces) + \
                    sum(float(np.vdot(g, g)) for g in block_grads)
                total_norm = float(np.sqrt(sq))
                if clip:
                    c = min(1.0, clip / (total_norm + 1e-6))
                    for p in grad_pieces:
                        p *= c
                    for g in block_grads:
                        g *= c
                self._block_opt.step(block_grads, lr=self._host_lr())
                self._param_store.refresh_compute()
            new_pieces = self._offload_opt.step(grad_pieces,
                                                lr=self._host_lr())
            new_params = self._offload_rebuild_params(new_pieces)
        new_state = {
            **state,  # pass through aux entries (e.g. onebit errors)
            "step": partial["step"],
            "params": new_params,
            "opt_state": state["opt_state"],
            "scaler": partial["scaler"],
        }
        return new_state, metrics

    def _ensure_data_iterator(self):
        if self._data_iterator is None:
            assert self.training_dataloader is not None, (
                "no training_data was passed to initialize() and no batch/"
                "data_iter given")
            self._data_iterator = iter(RepeatingLoader(self.training_dataloader))
        return self._data_iterator

    @property
    def skipped_steps(self) -> int:
        """Cumulative overflow-skipped steps; forces a metrics sync only when
        read (the scaler carries the cumulative count in-graph)."""
        m = getattr(self, "_cached_metrics", None)
        if m is not None and "skipped" in m:
            return int(m.get("skipped", self._skipped_steps_base))
        return self._skipped_steps_base

    @skipped_steps.setter
    def skipped_steps(self, v: int) -> None:
        self._skipped_steps_base = int(v)
        # drop stale metrics so a checkpoint restore's value takes effect
        # (the getter prefers the live metrics' cumulative counter)
        self._cached_metrics = {}

    def _finalize_metrics(self, metrics, steps: int = 1) -> None:
        # Lazy: metrics stay device-side until someone reads them.  A
        # device_get here would force a host round-trip EVERY step,
        # serializing the pipeline; the log/monitor branches below force
        # them only every steps_per_print.
        # ``steps``: report-window width for multi-step intervals (a k-step
        # train_batches can jump over the == 0 boundary).
        self._cached_metrics = _LazyMetrics(metrics)
        report = self.global_steps % max(self.steps_per_print(), 1) < steps
        if self.lr_scheduler is not None:
            self.lr_scheduler.step(self.global_steps)
        if report:
            # registry gauges are refreshed on report steps only — the same
            # cadence the metrics sync runs at, so this forces no extra
            # device round-trip — then the whole registry snapshot routes
            # through the MonitorMaster backends: loss/lr/loss-scale under
            # their historical event names (monitor_name), plus throughput
            # and the wall-clock timer histograms (telemetry/metrics.py
            # to_events)
            self._g_train_loss.set(float(self._cached_metrics["loss"]))
            if MODEL_RECORD in self._cached_metrics:
                # the model's own record, one gauge a field (the last step
                # of a multi-step call)
                record = jax.device_get(self._cached_metrics[MODEL_RECORD])
                for field, value in record.items():
                    self.metrics.gauge(
                        f"train_model_{field}",
                        "a field of the record the model's loss_fn returned "
                        "beside its loss, summed over the step's "
                        "micro-batches").set(float(np.ravel(value)[-1]))
            self._g_train_lr.set(float(self.get_lr()[0]))
            self._g_global_steps.set(self.global_steps)
            if self._g_loss_scale is not None:
                self._g_loss_scale.set(
                    float(self._cached_metrics["loss_scale"]))
            sps = self.tput_timer.avg_samples_per_sec()
            if sps == sps:                 # NaN until start_step is passed
                self._g_samples_per_sec.set(sps)
            if self.monitor.enabled:
                self.monitor.write_registry(self.metrics,
                                            self.global_samples)
        if report:
            log_dist(
                f"step={self.global_steps} loss={self._cached_metrics['loss']:.4f} "
                f"lr={self.get_lr()[0]:.3e} "
                f"grad_norm={self._cached_metrics['grad_norm']:.3f}", ranks=[0])

    def start_metrics_server(self, port: int = 0,
                             host: str = "127.0.0.1"):
        """Serve this engine's registry live (``telemetry/server.py``):
        ``/metrics`` = Prometheus text, ``/stats`` = JSON snapshot — the
        training registry joins the same exposition layer the serving
        fleet scrapes (and federates with it:
        ``telemetry.federate({"train": engine.metrics, ...})``).
        ``port=0`` binds an ephemeral port; idempotent; the returned
        server's ``stop()`` shuts it down."""
        from ..telemetry.server import MetricsServer

        if self._metrics_server is None:
            self._metrics_server = MetricsServer(
                metrics_text=self.metrics.prometheus_text,
                stats=self.metrics.snapshot,
                host=host, port=port).start()
        return self._metrics_server

    # -------------------------------------------- reference micro-step shims
    def forward(self, batch) -> jnp.ndarray:
        """Compute the microbatch loss+grads; loss returned, grads cached for
        ``backward``. (JAX has no separate autograd pass — fwd+bwd fuse.)"""
        if self.wall_clock_breakdown_enabled:
            self.timers(FORWARD_GLOBAL_TIMER).start()
        batch = self._shard_batch(batch, leading_gas_dim=False)
        loss, grads = self._micro_grads_fn(
            self.state["params"], self.state["scaler"], batch,
            self._dropout_rng,
            jnp.asarray(self.micro_steps, jnp.int32))
        self._pending = (loss, grads)
        if self.wall_clock_breakdown_enabled:
            self.timers(FORWARD_GLOBAL_TIMER).stop(sync_arrays=loss)
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients: bool = True):
        """Accumulate the cached microbatch grads (already averaged by 1/GAS)."""
        if self.wall_clock_breakdown_enabled:
            self.timers(BACKWARD_GLOBAL_TIMER).start()
        assert getattr(self, "_pending", None) is not None, \
            "backward() called without a preceding forward()"
        loss_val, grads = self._pending
        self._pending = None
        if self._accum_grads is None:
            self._accum_grads = grads
            self._accum_losses = [loss_val]
        else:
            self._accum_grads = self._tree_add_fn(self._accum_grads, grads)
            self._accum_losses.append(loss_val)
        self.micro_steps += 1
        if self.wall_clock_breakdown_enabled:
            self.timers(BACKWARD_GLOBAL_TIMER).stop(sync_arrays=loss_val)
        return loss_val

    def is_gradient_accumulation_boundary(self) -> bool:
        return self.micro_steps % self.gradient_accumulation_steps() == 0

    def step(self):
        """Apply the accumulated update at a GAS boundary (reference :2126)."""
        if not self.is_gradient_accumulation_boundary():
            return
        if self.wall_clock_breakdown_enabled:
            self.timers(STEP_GLOBAL_TIMER).start()
        assert self._accum_grads is not None, "step() without accumulated grads"
        mean_loss = (jnp.stack([jnp.asarray(l, jnp.float32)
                                for l in self._accum_losses]).mean()
                     if self._accum_losses else jnp.asarray(0.0, jnp.float32))
        if self.offload_enabled:
            grads, partial, metrics = self._offload_finish_fn(
                self.state, self._accum_grads, mean_loss)
            self.state, metrics = self._host_apply(self.state, grads, partial,
                                                   metrics)
        else:
            self.state, metrics = self._apply_update_fn(
                self.state, self._accum_grads, mean_loss)
        self._accum_grads = None
        self._accum_losses = []
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._finalize_metrics(metrics)
        if self.wall_clock_breakdown_enabled:
            self.timers(STEP_GLOBAL_TIMER).stop(
                sync_arrays=self.state["scaler"].cur_scale)

    # -------------------------------------------------------------------- eval
    def eval_batch(self, batch, rng=None):
        batch = self._shard_batch(batch, leading_gas_dim=False)
        rng = rng if rng is not None else self._dropout_rng
        return self._eval_step_fn(self.state["params"], batch, rng)

    # -------------------------------------------------------------------- data
    def deepspeed_io(self, dataset, batch_size: Optional[int] = None,
                     route=None, pin_memory: bool = True, data_sampler=None,
                     collate_fn=None, num_local_io_workers=None
                     ) -> DeepSpeedDataLoader:
        """Reference ``engine.py:318 deepspeed_io``: build the framework loader."""
        return DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size or self.micro_batch_global(),
            collate_fn=collate_fn or self.collate_fn,
            seed=self._config.seed or 0,
            drop_last=self._config.dataloader_drop_last,
            data_sampler=data_sampler,
            process_rank=dist.get_process_rank(),
            process_count=dist.get_process_world_size())

    # ------------------------------------------------------------- checkpoints
    def save_checkpoint(self, save_dir=None, tag=None, client_state=None,
                        save_latest=True):
        return self.checkpoint_manager.save(save_dir, tag=tag,
                                            client_state=client_state or {},
                                            save_latest=save_latest)

    def load_checkpoint(self, load_dir=None, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False):
        return self.checkpoint_manager.load(
            load_dir, tag=tag, load_optimizer_states=load_optimizer_states,
            load_module_only=load_module_only)

    # -------------------------------------------------------------------- misc
    @property
    def params(self):
        return self.state["params"]

    def get_fp32_params(self):
        return self.state["params"]

    def module_state_dict(self):
        return self.state["params"]

    def train(self, mode: bool = True):
        self._train_mode = mode
        return self

    def eval(self):
        return self.train(False)
