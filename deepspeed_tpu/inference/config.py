"""Inference config (reference ``deepspeed/inference/config.py``).

Same user-facing keys; TPU notes:
 - ``enable_cuda_graph`` is accepted and ignored — ``jit`` *is* the graph capture
   (reference captures CUDA graphs at ``inference/engine.py:479``).
 - ``tensor_parallel.tp_size`` maps to the ``tp`` mesh axis.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from pydantic import Field

from ..runtime.config_utils import DeepSpeedConfigModel


class DeepSpeedTPConfig(DeepSpeedConfigModel):
    """Reference ``inference/config.py:44``."""
    enabled: bool = True
    tp_size: int = 1
    mpu: Optional[Any] = None
    tp_group: Optional[Any] = None


class DeepSpeedMoEConfig(DeepSpeedConfigModel):
    """Reference ``inference/config.py:62``."""
    enabled: bool = True
    ep_size: int = 1
    moe_experts: list = Field(default_factory=lambda: [1])
    type: str = "standard"


class QuantizationConfig(DeepSpeedConfigModel):
    enabled: bool = False
    # 128 = the TPU lane width: groups at lane multiples let the fused
    # dequant-matmul kernel (ops/quantized_matmul) serve the weights
    # straight from int8; other sizes (the reference GroupQuantizer
    # default is 64) are honored via the dequant+matmul path
    group_size: int = 128
    num_bits: int = 8
    # "weight": int8 weight-only storage, bf16 math (default).
    # "w8a8": K-grouped weights + in-kernel activation quantization on the
    # s8 MXU for decode (reference analog: MoQ weight+activation INT8);
    # requires a quant-aware model with stacked blocks.
    type: str = "weight"
    # w8a8 group-size alignment target: quant groups are refined so a
    # row-parallel K shard over this many devices never splits a group
    # (ops/quantization.pick_k_group).  None = the engine's tp degree.
    # Pin it (e.g. to the largest tp you'll serve) to get bit-identical
    # weight records across tp degrees.
    shard_multiple: Optional[int] = None


class ZeroInferenceConfig(DeepSpeedConfigModel):
    """ZeRO-Inference analog (reference: zero stage-3 ``offload_param`` to
    CPU driving inference-only forwards — the reference's
    OPT-30B-on-one-GPU configuration): the stacked transformer blocks stay
    HOST-resident and stream through HBM one layer at a time during
    prefill/decode, so the servable model size is bounded by host DRAM,
    not device HBM.  Large batches amortize the per-step weight traffic
    (the reference's throughput recipe).  See
    inference/zero_inference.py."""
    enabled: bool = False
    #: first N layers stay device-resident (use spare HBM to cut traffic)
    pin_layers: int = 0
    #: host->device transfers issued ahead of compute (double buffering)
    prefetch: int = 1
    #: dispatch-throttle period, in layers: every N layers the host waits
    #: on a 1-element activation fetch so in-flight transfers stay
    #: bounded instead of racing the whole model into HBM
    sync_every: int = 1


class InferenceCheckpointConfig(DeepSpeedConfigModel):
    checkpoint_dir: Optional[str] = None
    save_mp_checkpoint_path: Optional[str] = None
    base_dir: Optional[str] = None


class DeepSpeedInferenceConfig(DeepSpeedConfigModel):
    """Reference ``inference/config.py:123`` key set."""
    kernel_inject: bool = Field(False, alias="replace_with_kernel_inject")
    dtype: str = "bfloat16"
    tensor_parallel: DeepSpeedTPConfig = Field(
        default_factory=DeepSpeedTPConfig, alias="tp")
    enable_cuda_graph: bool = False
    zero: Dict[str, Any] = Field(default_factory=dict)
    triangular_masking: bool = True
    moe: DeepSpeedMoEConfig = Field(default_factory=DeepSpeedMoEConfig)
    quant: QuantizationConfig = Field(default_factory=QuantizationConfig)
    zero_inference: ZeroInferenceConfig = Field(
        default_factory=ZeroInferenceConfig)
    checkpoint: Optional[Any] = None
    base_dir: str = ""
    max_tokens: int = Field(1024, alias="max_out_tokens")
    min_out_tokens: int = Field(1, alias="min_tokens")
    replace_method: str = "auto"
    injection_policy: Optional[Dict] = Field(None, alias="injection_dict")
    return_tuple: bool = True
    training_mp_size: int = 1
    max_batch_size: int = Field(1, alias="max_out_batch")
    # Ulysses-style sequence parallelism: size of the mesh ``sp`` axis.
    # Prefill shards the prompt over it (ops/sp_attention); the reference
    # has no analog (pre-Ulysses) — TPU-native extension.
    sequence_parallel: int = Field(1, alias="sp")

    @property
    def jnp_dtype(self):
        import jax.numpy as jnp

        return {
            "float16": jnp.float16, "fp16": jnp.float16, "half": jnp.float16,
            "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
            "float32": jnp.float32, "fp32": jnp.float32, "float": jnp.float32,
            "int8": jnp.int8,
        }[str(self.dtype).replace("torch.", "")]
