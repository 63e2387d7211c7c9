"""Autotuning config (reference ``autotuning/config.py``; same key names
under the ``autotuning`` block)."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from ..runtime.config_utils import DeepSpeedConfigModel


class AutotuningConfig(DeepSpeedConfigModel):
    enabled: bool = False
    fast: bool = True                       # tune zero stage + micro batch
    results_dir: str = "autotuning_results"
    exps_dir: str = "autotuning_exps"
    overwrite: bool = True
    metric: str = "throughput"              # throughput | latency
    start_profile_step: int = 3             # warmup steps before measuring
    end_profile_step: int = 5
    max_train_batch_size: Optional[int] = None
    min_train_batch_size: int = 1
    max_train_micro_batch_size_per_gpu: int = 1024
    min_train_micro_batch_size_per_gpu: int = 1
    num_tuning_micro_batch_sizes: int = 3
    tuner_type: str = "staged"              # staged | gridsearch | model_based
    tuner_early_stopping: int = 5
    tuner_num_trials: int = 50
    arg_mappings: Dict[str, Any] = {}
    zero_stages: Optional[List[int]] = None  # restrict the searched stages
    #: staged mode: which knob groups to tune, in order.  "batch" = zero
    #: stage x micro batch; "remat" = remat_policy x scan_layers; "gas" =
    #: gradient accumulation; "flash" = flash kernel block sizes.  These are
    #: the knobs that actually set TPU throughput — the
    #: reference's fast mode only covers the first group.
    stages: List[str] = ["batch", "remat", "gas", "flash"]
    gas_candidates: List[int] = [1, 2, 4, 8, 16]
    remat_policies: List[str] = ["full", "dots", "dots_flash"]
    flash_blocks: List[List[int]] = [[256, 1024], [512, 1024],
                                     [1024, 1024], [512, 512]]
