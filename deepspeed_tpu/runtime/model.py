"""Model contract between user code and the engine.

The reference engine wraps a ``torch.nn.Module`` whose ``forward`` returns the
loss (``runtime/engine.py:189,206``).  The TPU-native equivalent of a module is a
pair of pure functions over a param pytree; :class:`ModelSpec` is that contract:

 - ``init_fn(rng)``                       -> params pytree
 - ``loss_fn(params, batch, rng, train)`` -> scalar loss (mean over the batch dim),
   or ``(loss, record)``: ``record`` a pytree of float32 scalars the engine sums
   over a step's micro-batches into ``train_batch``'s ``metrics["model"]``
 - ``apply_fn(params, batch, rng)``       -> model outputs (logits), for eval/inference
 - ``tp_rules(abstract_params)``          -> pytree of ``PartitionSpec`` carrying
   model-parallel (tp/ep/sp) placement, or None for replicated.  ZeRO sharding is
   layered on top by the engine (``runtime/zero/sharding.py``).

Anything exposing these four attributes works — our ``models/`` package, a wrapped
flax module (:func:`from_flax`), or hand-written functions.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

PyTree = Any


@dataclasses.dataclass
class ModelSpec:
    init_fn: Callable[..., PyTree]
    loss_fn: Callable[..., Any]
    apply_fn: Optional[Callable[..., Any]] = None
    tp_rules: Optional[Callable[[PyTree], PyTree]] = None
    #: optional: flops per token (fwd) for MFU reporting
    flops_per_token: Optional[float] = None
    name: str = "model"
    #: Optional pipeline decomposition for pp>1 (see runtime/pipe/engine.py):
    #:   blocks_key: tuple path of the [L, ...]-stacked block params
    #:   embed_fn(params, input_ids) -> activations [B, S, D]
    #:   block_fn(layer_params, x)   -> x  (one transformer block)
    #:   head_loss_fn(params, x, targets) -> scalar mean loss
    pipeline_hooks: Optional[dict] = None
    #: Optional KV-cache decode path (see inference/engine.py generate):
    #:   init_cache(batch_size, max_len, dtype) -> cache pytree
    #:       (leaves [L, B, ..., S, hd]: batch dim 1, length dim -2)
    #:   forward_cached(params, input_ids, cache, pos, lengths=None) ->
    #:       (last-position logits [B, V], updated cache)
    #: ``pos`` is the (traced) global position of input_ids[:, 0]; the same
    #: function serves prefill (T=prompt) and decode (T=1).  ``lengths``
    #: (traced int32 [B]; hooks that accept it set ``supports_lengths``) is
    #: the per-sequence position vector for continuous-batching slots
    #: (inference/serving.py): T == 1 decodes row ``b`` at its own position
    #: ``lengths[b]``; T > 1 is ragged right-padded prefill whose logits
    #: gather at each row's ``lengths[b] - 1``.
    decode_hooks: Optional[dict] = None
    #: The builder's config object (e.g. GPT2Config).  The engine mutates its
    #: remat knobs when the json config carries an ``activation_checkpointing``
    #: block (runtime/remat.py) — builders close over the config, so changes
    #: made before the first jit trace take effect.
    model_config: Any = None
    #: True = the model's forwards dequantize INT8 weight records
    #: (ops/quantization) lazily at point of use, so the inference engine
    #: passes the quantized pytree straight through — per-layer peak memory
    #: instead of a whole-tree dequantized copy.
    quant_aware: bool = False
    #: Tuple path of the [L, ...]-stacked block params for consumers
    #: outside pipeline parallelism (block-only quantization in the
    #: inference engine).  Falls back to pipeline_hooks["blocks_key"]
    #: when unset, so models with pipeline hooks declare it once.
    blocks_key: Optional[tuple] = None
    #: Optional per-layer decode decomposition for ZeRO-Inference-style
    #: weight streaming (inference/zero_inference.py) — serving models
    #: whose weights exceed device HBM by keeping the stacked blocks
    #: host-resident and streaming one layer at a time through the
    #: KV-cache decode step (reference: ZeRO-Inference, zero stage-3
    #: param offload driving inference-only forwards):
    #:   embed(params, input_ids, pos)       -> activations [B, T, D]
    #:   block(layer, x, ck, cv, pos)        -> (x, ck, cv)  (one layer,
    #:       per-LAYER cache slices [B, H, S, hd])
    #:   head(params, x_last)                -> last-position logits [B, V]
    #: ``params`` is the RESIDENT tree (everything but the blocks).
    stream_hooks: Optional[dict] = None

    def init(self, rng) -> PyTree:
        if _ON_DEVICE_STACK:
            ctx = _ON_DEVICE_STACK[-1]
            if ctx.device == "meta":
                import jax

                abstract = jax.eval_shape(self.init_fn, rng)
                if ctx.dtype is not None:
                    abstract = jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape,
                            ctx.dtype if jax.numpy.issubdtype(
                                x.dtype, jax.numpy.floating) else x.dtype),
                        abstract)
                return abstract
        return self.init_fn(rng)

    def loss(self, params, batch, rng=None, train: bool = True):
        return self.loss_fn(params, batch, rng, train)


#: active OnDevice contexts (innermost last)
_ON_DEVICE_STACK: list = []


class OnDevice:
    """Reference ``deepspeed.OnDevice`` (utils/init_on_device.py:10): build
    a model without allocating its weights.

    ``device="meta"`` makes :meth:`ModelSpec.init` return ABSTRACT params
    (``jax.eval_shape`` — shapes/dtypes only, no memory), optionally with
    float leaves recast to ``dtype``.  The engine's own init path is
    unaffected: it already materializes params sharded-at-birth under jit
    with ``out_shardings`` (the zero.Init analog), so this context exists
    for user-side model inspection and memory planning at 70B scale.
    """

    def __init__(self, dtype=None, device: str = "meta", enabled: bool = True):
        if enabled and device != "meta":
            raise ValueError(
                f"OnDevice(device={device!r}): only 'meta' is supported on "
                "TPU — materialized init is already placed/sharded by the "
                "engine; for a specific dtype, cast after init")
        self.dtype = dtype
        self.device = device if enabled else "none"

    def __enter__(self):
        _ON_DEVICE_STACK.append(self)
        return self

    def __exit__(self, *exc):
        _ON_DEVICE_STACK.pop()
        return False


def from_functions(init_fn, loss_fn, apply_fn=None, tp_rules=None,
                   name="model") -> ModelSpec:
    return ModelSpec(init_fn=init_fn, loss_fn=loss_fn, apply_fn=apply_fn,
                     tp_rules=tp_rules, name=name)


def from_flax(module, loss_from_logits: Callable, sample_batch,
              batch_to_inputs: Optional[Callable] = None,
              name: str = "flax_model") -> ModelSpec:
    """Adapt a ``flax.linen`` module.

    ``batch_to_inputs(batch) -> (args, kwargs)`` extracts module inputs from a
    batch; ``loss_from_logits(logits, batch) -> scalar``.
    """
    import jax

    if batch_to_inputs is None:
        batch_to_inputs = lambda batch: ((batch,), {})

    def init_fn(rng):
        args, kwargs = batch_to_inputs(sample_batch)
        return module.init(rng, *args, **kwargs)

    def apply_fn(params, batch, rng=None):
        args, kwargs = batch_to_inputs(batch)
        rngs = {"dropout": rng} if rng is not None else None
        return module.apply(params, *args, rngs=rngs, **kwargs)

    def loss_fn(params, batch, rng=None, train=True):
        logits = apply_fn(params, batch, rng if train else None)
        return loss_from_logits(logits, batch)

    return ModelSpec(init_fn=init_fn, loss_fn=loss_fn, apply_fn=apply_fn, name=name)
