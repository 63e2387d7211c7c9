"""Inference engine.

Analog of reference ``deepspeed/inference/engine.py:35`` (``InferenceEngine``).
Wraps a :class:`ModelSpec`, shards its params over a ``tp`` mesh axis (the
auto-TP analog: our models carry Megatron-style PartitionSpecs in ``tp_rules``,
so "injection" is a sharding annotation instead of a module swap), casts to the
inference dtype, and compiles the forward.  ``jit`` replaces CUDA-graph
capture/replay (reference :479/:498).

Decode runs over a static KV cache when the model carries ``decode_hooks``
(prefill + single-token steps through the Pallas decode-attention kernel,
``ops/decode_attention.py`` — the reference ``softmax_context`` analog); models
without hooks fall back to full-recompute generation.  Both loops early-exit via
``lax.while_loop`` once every sequence has emitted ``eos_token_id``.  Compiled
generate programs are cached per shape with true LRU eviction.  For mixed-length
request traffic, the continuous-batching scheduler in ``inference/serving.py``
replaces these one-shot static batches with a slot-based KV pool and
iteration-level scheduling.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..analysis.sentry import RecompileSentry, install_compile_listener
from ..parallel.topology import MeshTopology
from ..runtime.model import ModelSpec
from ..telemetry import trace as trace_mod
from ..utils.logging import log_dist
from ..utils.lru import LRUCache
from ..utils.platform import host_cpu_device, on_tpu
from .config import DeepSpeedInferenceConfig


def _auto_seed(obj, seed):
    """Fresh draws per call (HF generate uses a stateful RNG); pass an
    explicit seed for reproducibility.  Shared by the resident and
    streamed (zero_inference) generate paths."""
    if seed is not None:
        return seed
    obj._sample_calls = getattr(obj, "_sample_calls", -1) + 1
    return obj._sample_calls


def _fill_after_eos(out, prompt_len, eos_token_id):
    """Back-fill everything after the first eos with eos (HF padding
    semantics).  Shared by the resident and streamed generate paths.

    Vectorized: a cumulative "eos seen" mask over the generated region,
    shifted right one column, marks every position strictly after each
    row's first eos (the eos itself stays; rows without eos are untouched;
    eos inside the prompt is ignored)."""
    if eos_token_id is not None and out.shape[1] > prompt_len:
        gen = out[:, prompt_len:]          # view — writes land in ``out``
        seen = np.cumsum(gen == eos_token_id, axis=1) > 0
        after = np.concatenate(
            [np.zeros((out.shape[0], 1), bool), seen[:, :-1]], axis=1)
        gen[after] = eos_token_id
    return out


def _cast_floating_skip_records(tree, dtype):
    """Cast float leaves to the serving dtype, leaving quantization
    records intact: PRE-QUANTIZED param trees (a quantized checkpoint, or
    a host tree quantized offline at 30B scale) must keep int8 payloads
    and f32 scales — the w8a8 kernel consumes f32 scales, and casting
    them to bf16 would silently degrade every matmul."""
    from ..ops import quantization as quant

    def cast(x):
        if quant.is_record(x):
            return x
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(cast, tree, is_leaf=quant.is_record)


class InferenceEngine:

    def __init__(self, model: ModelSpec, config: DeepSpeedInferenceConfig,
                 params=None, device_group: Optional[int] = None):
        """``device_group``: ``None`` spans every device (``dp`` absorbs
        what ``tp x sp`` leaves — data-parallel ``forward`` and the
        ``dp_tp`` serving mode want that).  An int makes the engine occupy
        exactly ONE ``tp x sp`` group of ``jax.devices()`` — group
        ``device_group`` modulo the number of groups — which is what a
        serving replica is: a ``tp=1`` engine left on a ``dp=n`` mesh
        replicates weights, pool and compute on all n chips, and on a TPU
        its bare Pallas kernels are refused outright ("Mosaic kernels
        cannot be automatically partitioned")."""
        assert isinstance(model, ModelSpec), (
            "init_inference expects a deepspeed_tpu ModelSpec")
        assert model.apply_fn is not None, "ModelSpec.apply_fn required for inference"
        self.module = model
        self._config = config
        self.device_group = device_group
        # Engines own trace-time model-config state (same contract as the
        # training engine's remat/liveness wiring): serving always scans one
        # layer per step — clear a ZeRO-3 G left by a training engine that
        # shared this model object.
        mc = getattr(model, "model_config", None)
        if mc is not None and hasattr(mc, "scan_group_size"):
            mc.scan_group_size = 1
        if mc is not None and hasattr(mc, "scan_prefetch"):
            mc.scan_prefetch = None

        tp = config.tensor_parallel.tp_size if config.tensor_parallel.enabled else 1
        sp = int(getattr(config, "sequence_parallel", 1) or 1)
        dist.init_distributed()
        devices = jax.devices()
        n = len(devices)
        group = max(tp, 1) * max(sp, 1)
        assert n % max(tp, 1) == 0, f"tp_size {tp} does not divide {n} devices"
        if n % group != 0:
            raise ValueError(
                f"sequence_parallel={sp} x tp_size={tp} does not divide "
                f"{n} devices")
        if device_group is not None:
            g = int(device_group) % (n // group)
            devices = devices[g * group:(g + 1) * group]
        self.topology = MeshTopology(tp=tp, sp=sp, dp=len(devices) // group,
                                     devices=devices)
        dist.configure(topology=self.topology)
        self.mesh = self.topology.mesh

        # the start-up ring (telemetry/trace.py setup_timeline): what the
        # cast and the placement take, and every function JAX builds here
        setup = trace_mod.setup_timeline()
        install_compile_listener()
        with setup.span("params_cast", dtype=str(config.dtype),
                        given=params is not None):
            if params is None:
                # init_fn: immune to a user-held OnDevice('meta') context
                params = model.init_fn(jax.random.PRNGKey(0))
            params = _cast_floating_skip_records(params, config.jnp_dtype)
        tp_specs = model.tp_rules(jax.eval_shape(lambda: params)) \
            if model.tp_rules else None
        rep = NamedSharding(self.mesh, P())
        if tp_specs is not None:
            shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec), tp_specs,
                is_leaf=lambda x: isinstance(x, P))
        else:
            shardings = jax.tree_util.tree_map(lambda _: rep, params)

        # INT8 weight-only storage (reference GroupQuantizer /
        # ZeRO-Inference): weights live in HBM as int8 + per-group scales.
        # quant-aware models (ModelSpec.quant_aware) dequantize lazily at
        # point of use — per-LAYER peak memory; for others the engine
        # dequantizes the whole tree at jit entry (int8 halves RESTING
        # weight memory but the forward's peak holds a full-precision copy)
        self._quantized = config.quant.enabled
        if self._quantized:
            from ..ops import quantization as quant
            from ..ops import quantized_matmul as qmm

            # the weight-only fused kernel needs an unsharded weight path
            # (pallas_calls are opaque to the GSPMD partitioner); the w8a8
            # kernel instead runs TP-sharded through a custom_partitioning
            # wrapper — column shards run the s8 kernel locally, row shards
            # psum a local partial (ops/quantized_matmul._w8a8_tp_call)
            qmm.configure(kernel_ok=(tp <= 1), w8a8_tp=(tp > 1))
            if tp > 1 and config.quant.type == "w8a8" and on_tpu():
                # found on a four-chip v5e host (jax 0.9.0, libtpu 0.0.34):
                # the decode program's layer loop fails to compile with
                # "INVALID_ARGUMENT: Custom emitter for
                # CustomSPMDPartitioning not found" — XLA:TPU never calls
                # the partition rule; the CPU-sim mesh does
                raise NotImplementedError(
                    "quant.type 'w8a8' under tensor parallelism does not "
                    "compile on a TPU yet (its custom_partitioning call is "
                    "rejected by XLA:TPU) — serve w8a8 at tp=1 (one replica "
                    "per chip behind init_router), or bf16/kv8 at tp>1")
            if tp > 1 and config.quant.type == "w8a8":
                log_dist(
                    "quant: w8a8 under tensor parallelism — decode matmuls "
                    "run sharded via custom_partitioning (s8 kernel per "
                    "shard; row-parallel adds one psum); weights whose "
                    "quant groups don't divide tp gather instead (warned "
                    "per shape at compile)", ranks=[0])

            # Quantize on the HOST: jnp ops on uncommitted (numpy) inputs
            # follow default_device, so stacked multi-billion-param leaves
            # never materialize an f32 copy in HBM (OPT-6.7B's stacked fc_w
            # alone is 8.6GB f32 — quantizing on-device OOMed a 16GB chip).
            # Only the int8 payload + scales reach the device, via the
            # sharded device_put below.
            #
            # Quant-aware models quantize the TRANSFORMER BLOCKS only (the
            # reference GroupQuantizer likewise targets layer weights, not
            # embeddings): a quantized embedding would be re-dequantized by
            # every decode step inside the token loop — measured ~6ms/token
            # on OPT-1.3B — for a memory saving that is <5% of the model.
            params = jax.device_get(params)
            hooks = getattr(model, "pipeline_hooks", None) or {}
            bkey = (getattr(model, "blocks_key", None)
                    or hooks.get("blocks_key")) if model.quant_aware else None
            w8a8 = config.quant.type == "w8a8"
            if w8a8 and bkey is None:
                raise ValueError(
                    f"quant.type 'w8a8' needs a quant-aware model with "
                    f"stacked blocks; {model.name} is not — use the "
                    f"default weight-only type")
            # blocks subtrees are STACKED [L, ...]: min_ndim=3 keeps
            # per-layer 1D params (e.g. [L, 3d] qkv_b) dense — at L >= 64
            # they'd otherwise pass the weight-matrix shape tests
            if w8a8:
                kg = max(128, int(config.quant.group_size))
                # group sizes refine so row-parallel K shards never split a
                # quant group — otherwise _w8a8_partition must gather the
                # weight (e.g. OPT-2.7B K=2560 at tp=8: g=128 -> 20 groups,
                # 20 % 8 != 0 -> gathered; g=80 -> 32 groups, sharded).
                # With the degree DERIVED from tp, only K-sharded leaves
                # refine (spec-aware: finer groups cost scale storage +
                # kernel trip count, pointless on column shards); an
                # EXPLICIT quant.shard_multiple refines uniformly so the
                # records stay bit-identical across tp degrees.
                sm = config.quant.shard_multiple or tp
                spec_aware = config.quant.shard_multiple is None and tp > 1

                def _quantize(tree, min_ndim, specs=None):
                    return quant.quantize_pytree_k_grouped(
                        tree, k_group=kg, min_ndim=min_ndim,
                        shard_multiple=sm,
                        spec_tree=specs if spec_aware else None)
            else:
                def _quantize(tree, min_ndim, specs=None):
                    return quant.quantize_pytree(
                        tree, num_bits=config.quant.num_bits,
                        group_size=config.quant.group_size,
                        min_ndim=min_ndim)
            prequantized = any(
                quant.is_record(leaf) for leaf in jax.tree_util.tree_leaves(
                    params, is_leaf=quant.is_record))
            if prequantized:
                # pre-quantized tree (quantized checkpoint / offline host
                # quantization): records pass through untouched — the kind
                # must match the configured quant type
                kinds = {
                    "w8a8" if quant.is_k_quantized(leaf) else "weight"
                    for leaf in jax.tree_util.tree_leaves(
                        params, is_leaf=quant.is_record)
                    if quant.is_record(leaf)}
                if kinds != {config.quant.type}:
                    raise ValueError(
                        f"pre-quantized params carry {sorted(kinds)} records "
                        f"but quant.type is {config.quant.type!r}")
                log_dist("quant: params arrived pre-quantized — skipping "
                         "host-side quantization", ranks=[0])
            with jax.default_device(host_cpu_device()):
                if prequantized:
                    pass
                elif bkey is not None:
                    path = (bkey,) if isinstance(bkey, str) else tuple(bkey)
                    node, snode = params, shardings
                    for k in path[:-1]:
                        node, snode = node[k], snode[k]
                    node[path[-1]] = _quantize(node[path[-1]], min_ndim=3,
                                               specs=snode[path[-1]])
                else:
                    params = _quantize(params, min_ndim=2, specs=shardings)
            params = jax.device_get(params)

            def _rec_shardings(x, s):
                if not quant.is_record(x):
                    return s
                out = {}
                for k in x:
                    if k in ("q", "qk"):
                        out[k] = s
                    elif k == "kscale" and getattr(s, "spec", None) is not None:
                        # kscale [..., K/G, 1, N] follows the weight's
                        # [..., K, N] spec (K-dim sharding lands on the K/G
                        # dim) so TP decode never re-slices a replicated
                        # scale tree; dims the axis doesn't divide (e.g.
                        # K/G=1 at hidden==k_group) stay replicated
                        spec = tuple(s.spec)
                        spec = spec + (None,) * (x[k].ndim - 1 - len(spec))
                        kspec = spec[:-2] + (spec[-2], None, spec[-1])
                        kspec = tuple(
                            ax if x[k].shape[i] %
                            qmm.axis_size(self.mesh, ax) == 0 else None
                            for i, ax in enumerate(kspec))
                        out[k] = NamedSharding(self.mesh, P(*kspec))
                    else:
                        out[k] = rep
                return out

            shardings = jax.tree_util.tree_map(
                _rec_shardings, params, shardings, is_leaf=quant.is_record)
            if model.quant_aware:
                self._prepare = lambda p: p
            else:
                log_dist(
                    f"quant: model {model.name} is not quant_aware — "
                    "dequantizing the full tree at jit entry (peak memory "
                    "includes a full-precision copy)", ranks=[0])
                self._prepare = lambda p: quant.dequantize_pytree(
                    p, config.jnp_dtype)
        else:
            self._prepare = lambda p: p
        self._streamed = None
        if config.zero_inference.enabled:
            params, shardings = self._init_zero_inference(params, shardings)
        with setup.span("params_place") as placed:
            self.params = jax.block_until_ready(jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s), params, shardings))
            leaves = jax.tree_util.tree_leaves(self.params)
            placed.update(bytes=sum(int(x.nbytes) for x in leaves),
                          leaves=len(leaves))

        prepare = self._prepare
        # recompile sentry (analysis/sentry.py): forward legitimately
        # specializes per batch shape (budget=None, count only); each
        # shape-keyed generate program below declares budget 1 — a retrace
        # of an already-built program is always contract drift
        self.sentry = RecompileSentry(name=f"inference:{model.name}")
        # telemetry registry (telemetry/): profile_model_time observes
        # per-forward wall clock into it, and wrappers (ServingEngine has
        # its own) can hang engine-level metrics here
        from ..telemetry import MetricsRegistry

        self.metrics = MetricsRegistry()
        self._forward_fn = jax.jit(self.sentry.wrap(
            lambda p, batch: model.apply_fn(prepare(p), batch, None),
            "forward", budget=None))
        # bounded per-shape jit cache; hot shapes survive eviction pressure
        # (utils/lru.py — same policy as ServingEngine's prefill-fn cache)
        self._generate_fns = LRUCache(capacity=32)
        if self._streamed is not None:
            self._streamed.resident = self.params
        log_dist(f"InferenceEngine: mesh={self.topology}, dtype={config.dtype}",
                 ranks=[0])

    def _init_zero_inference(self, params, shardings):
        """ZeRO-Inference mode (inference/zero_inference.py): pull the
        stacked blocks OUT of the device tree — they stay host-resident
        (int8 records when quantized) and stream per layer during
        generate.  Returns the resident (params, shardings) to place."""
        from .zero_inference import StreamedGenerator

        model, config = self.module, self._config
        zi = config.zero_inference
        if model.stream_hooks is None or model.decode_hooks is None:
            raise ValueError(
                f"zero_inference needs a model with stream_hooks + "
                f"decode_hooks; {model.name} has neither — serve it "
                "resident or add the per-layer hooks")
        if self.topology.tensor_parallel_size > 1:
            raise ValueError(
                "zero_inference streams layers through ONE device's HBM; "
                "combine with tp later or drop tensor_parallel")
        hooks = getattr(model, "pipeline_hooks", None) or {}
        bkey = hooks.get("blocks_key")
        if bkey is None:
            raise ValueError(
                f"zero_inference needs pipeline_hooks.blocks_key on "
                f"{model.name} to locate the stacked blocks")
        path = (bkey,) if isinstance(bkey, str) else tuple(bkey)
        params = jax.device_get(params)
        node, snode = params, shardings
        for k in path[:-1]:
            node, snode = node[k], snode[k]
        host_blocks = node.pop(path[-1])
        snode.pop(path[-1])
        num_layers = jax.tree_util.tree_leaves(host_blocks)[0].shape[0]
        self._streamed = StreamedGenerator(
            resident_params=None,  # set after device_put of the residents
            host_blocks=host_blocks, num_layers=num_layers,
            stream_hooks=model.stream_hooks,
            init_cache=model.decode_hooks["init_cache"],
            cache_dtype=config.jnp_dtype,
            pin_layers=zi.pin_layers, prefetch=zi.prefetch,
            sync_every=zi.sync_every,
            picker_factory=_make_token_picker)
        return params, shardings

    # ------------------------------------------------------------------ forward
    def forward(self, batch):
        """Logits for a batch (reference ``inference/engine.py:541``)."""
        if self._streamed is not None:
            raise NotImplementedError(
                "zero_inference serves via generate(); whole-batch logits "
                "would stream every layer for one forward — run a resident "
                "engine (or generate with max_new_tokens=1) instead")
        batch = self._put_batch(batch)
        return self._forward_fn(self.params, batch)

    __call__ = forward

    def _put_batch(self, batch):
        dp_total = self.topology.data_parallel_size

        def put(x):
            x = jnp.asarray(x)
            spec = P(("dp", "ep")) if (x.ndim > 0 and x.shape[0] % dp_total == 0) \
                else P()  # small batches replicate rather than fail
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map(put, batch)

    # ----------------------------------------------------------------- generate
    def generate(self, input_ids, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 seed: Optional[int] = None):
        """Decode with static shapes (reference ``_generate`` :571; beam
        search is likewise rejected there).  ``do_sample=True`` enables
        temperature / top-k / top-p sampling in-graph; default is greedy."""
        input_ids = np.asarray(input_ids)
        b, prompt_len = input_ids.shape
        total = prompt_len + max_new_tokens
        hooks = self.module.decode_hooks
        max_ctx = (hooks or {}).get("max_seq_len")
        if max_ctx is not None and total > max_ctx:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
                f"= {total} exceeds the model context length {max_ctx}")
        if self._streamed is not None:
            return self._streamed.generate(
                input_ids, max_new_tokens=max_new_tokens,
                eos_token_id=eos_token_id, do_sample=do_sample,
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=seed)
        sample_cfg = (do_sample, float(temperature), int(top_k),
                      float(top_p)) if do_sample else None
        # eos is part of the compiled program (early-exit while_loop)
        key = (b, prompt_len, max_new_tokens, sample_cfg, eos_token_id)

        def build():
            if self.module.decode_hooks is not None:
                return self._build_kv_cache_gen(
                    b, prompt_len, total, sample_cfg, eos_token_id)
            return self._build_recompute_gen(
                b, prompt_len, total, sample_cfg, eos_token_id)

        gen_fn = self._generate_fns.get_or_build(key, build)
        rng = jax.random.PRNGKey(_auto_seed(self, seed))
        out = gen_fn(self.params, jnp.asarray(input_ids), rng)
        out = np.array(out)  # writable host copy (np.asarray view is read-only)
        return _fill_after_eos(out, prompt_len, eos_token_id)

    @staticmethod
    def _gen_name(kind, b, prompt_len, total, sample_cfg, eos_token_id):
        """Sentry entry name for a shape-keyed generate program: each key
        compiles once (budget 1) — same identity as ``_generate_fns``'
        cache key, so an LRU-evicted rebuild is visible as a recount."""
        return (f"generate/{kind}[b={b},plen={prompt_len},total={total},"
                f"sample={sample_cfg},eos={eos_token_id}]")

    def _build_recompute_gen(self, b, prompt_len, total, sample_cfg=None,
                             eos_token_id=None):
        """Full-recompute fallback for models without decode hooks.  With an
        ``eos_token_id`` the token loop is a ``lax.while_loop`` that stops
        stepping once every sequence has emitted eos (positions past a
        row's eos stay 0 in-graph; ``_fill_after_eos`` back-fills them)."""
        apply_fn = self.module.apply_fn
        pick = _make_token_picker(sample_cfg)
        prepare = self._prepare

        def gen(params, ids, rng):
            params = prepare(params)
            buf = jnp.zeros((b, total), jnp.int32)
            buf = buf.at[:, :prompt_len].set(ids)

            def step(i, buf):
                logits = apply_fn(params, {"input_ids": buf}, None)
                next_tok = pick(logits[:, i - 1, :],
                                jax.random.fold_in(rng, i))
                return buf.at[:, i].set(next_tok), next_tok

            if eos_token_id is None:
                return jax.lax.fori_loop(
                    prompt_len, total,
                    lambda i, buf: step(i, buf)[0], buf)

            def cond(carry):
                _, i, done = carry
                return (i < total) & ~jnp.all(done)

            def body(carry):
                buf, i, done = carry
                buf, next_tok = step(i, buf)
                return buf, i + 1, done | (next_tok == eos_token_id)

            buf, _, _ = jax.lax.while_loop(
                cond, body,
                (buf, jnp.int32(prompt_len), jnp.zeros((b,), bool)))
            return buf

        return jax.jit(self.sentry.wrap(gen, self._gen_name(
            "recompute", b, prompt_len, total, sample_cfg, eos_token_id)))

    def _build_kv_cache_gen(self, b, prompt_len, total, sample_cfg=None,
                            eos_token_id=None):
        """Prefill + single-token decode loop over a static KV cache
        (reference ``softmax_context`` path; workspace sized like
        ``inference_context.h`` by the token budget).  With an
        ``eos_token_id`` the loop is a ``lax.while_loop`` that stops once
        every sequence has emitted eos — mixed-length batches stop at the
        LAST-finishing row instead of always burning the full budget."""
        hooks = self.module.decode_hooks
        init_cache, forward_cached = hooks["init_cache"], hooks["forward_cached"]
        # round the workspace up so the Pallas kernel's block_k divides it
        cache_len = -(-total // 128) * 128
        cache_dtype = self._config.jnp_dtype
        pick = _make_token_picker(sample_cfg)
        prepare = self._prepare

        def gen(params, ids, rng):
            params = prepare(params)
            cache = init_cache(b, cache_len, cache_dtype)
            buf = jnp.zeros((b, total), jnp.int32)
            buf = buf.at[:, :prompt_len].set(ids)
            logits, cache = forward_cached(params, ids, cache, 0)   # prefill
            first = pick(logits, jax.random.fold_in(rng, prompt_len))
            buf = buf.at[:, prompt_len].set(first)

            def step(pos, buf, cache):
                tok = jax.lax.dynamic_slice(buf, (0, pos), (b, 1))
                logits, cache = forward_cached(params, tok, cache, pos)
                nxt = pick(logits, jax.random.fold_in(rng, pos + 1))
                buf = jax.lax.dynamic_update_slice(buf, nxt[:, None],
                                                   (0, pos + 1))
                return buf, cache, nxt

            if eos_token_id is None:
                def body(pos, carry):
                    buf, cache = carry
                    buf, cache, _ = step(pos, buf, cache)
                    return buf, cache

                buf, _ = jax.lax.fori_loop(prompt_len, total - 1, body,
                                           (buf, cache))
                return buf

            def cond(carry):
                _, _, pos, done = carry
                return (pos < total - 1) & ~jnp.all(done)

            def body(carry):
                buf, cache, pos, done = carry
                buf, cache, nxt = step(pos, buf, cache)
                return buf, cache, pos + 1, done | (nxt == eos_token_id)

            buf, _, _, _ = jax.lax.while_loop(
                cond, body, (buf, cache, jnp.int32(prompt_len),
                             first == eos_token_id))
            return buf

        return jax.jit(self.sentry.wrap(gen, self._gen_name(
            "kv", b, prompt_len, total, sample_cfg, eos_token_id)))

    def profile_model_time(self, use_cuda_events: bool = True):
        """Enable per-forward wall-clock capture (reference
        ``inference/engine.py:163``); retrieve with :meth:`model_times`.
        Idempotent — repeated calls do not stack timers.  Every sample
        also lands in the engine registry's ``inference_forward_seconds``
        histogram (``self.metrics``) — the bounded-memory distribution
        survives the :meth:`model_times` drain."""
        if getattr(self, "_profiling", False):
            return
        self._profiling = True
        self._model_times = []
        hist = self.metrics.histogram(
            "inference_forward_seconds",
            help="profiled forward-pass wall clock (profile_model_time)")
        orig = self._forward_fn

        def timed(p, batch):
            import time
            t0 = time.perf_counter()
            out = jax.block_until_ready(orig(p, batch))
            dt = time.perf_counter() - t0
            self._model_times.append(dt)
            hist.observe(dt)
            return out

        self._forward_fn = timed

    def model_times(self):
        times = list(getattr(self, "_model_times", []))
        self._model_times = []
        return times


def _make_token_picker(sample_cfg):
    """Greedy argmax, or temperature/top-k/top-p sampling (in-graph).

    The reference's sampling lives in HF ``generate``; here it is part of the
    jitted decode loop.  logits: [B, V] -> int32 [B].
    """
    if sample_cfg is None:
        return lambda logits, rng: jnp.argmax(logits, axis=-1).astype(jnp.int32)
    _, temperature, top_k, top_p = sample_cfg

    def pick(logits, rng):
        logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
        v = logits.shape[-1]
        if top_k and top_k < v:
            kth = jnp.sort(logits, axis=-1)[:, v - top_k][:, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p < 1.0:
            sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep the smallest prefix with cumulative prob >= top_p
            cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
            cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)

    return pick
