"""Wall-clock and throughput timers.

TPU-native analog of the reference's ``deepspeed/utils/timer.py``
(``SynchronizedWallClockTimer`` :33, ``ThroughputTimer`` :137).  The reference
synchronizes with CUDA events; on TPU the only sound synchronization point is
blocking on device arrays, so ``Timer.stop(sync_arrays=...)`` optionally calls
``jax.block_until_ready`` on the arrays produced by the timed region.  Timers are
host-side: they time dispatched steps, which under ``jit`` includes compile time on
the first call — callers should warm up before trusting numbers (same caveat as
CUDA-graph capture in the reference).

Timers may be backed by the telemetry layer: construct
:class:`SynchronizedWallClockTimer` with a
:class:`~deepspeed_tpu.telemetry.MetricsRegistry` and every ``stop()``
also lands the elapsed milliseconds in a per-timer-labeled streaming
histogram (``train_wall_clock_ms{timer=...}``) — the training engine
wires its registry through here so fwd/bwd/step breakdowns reach the
``MonitorMaster`` backends and Prometheus exposition alongside
loss/lr/throughput (``docs/observability.md``).  Host timers NEVER
belong inside jit/shard_map bodies — they would time dispatch, not
device execution (lint rule GL006 enforces this).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterable, List, Optional

from .logging import log_dist

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"


def _sync(arrays) -> None:
    """Wait until the device has produced ``arrays``: JAX dispatch returns
    at enqueue time, so a timer that does not block measures dispatch only.
    A failure here is a failed computation and propagates."""
    if arrays is None:
        return
    import jax

    jax.block_until_ready(arrays)


class Timer:
    """A single named stopwatch accumulating elapsed milliseconds.

    ``histogram`` (optional): a telemetry ``Histogram`` each ``stop()``'s
    elapsed milliseconds is also observed into — bounded-memory
    distribution of every interval this timer ever measured, independent
    of the reset/elapsed cycle the log path runs."""

    def __init__(self, name: str, histogram: Any = None):
        self.name_ = name
        self.started_ = False
        self.start_time = 0.0
        self.elapsed_ms = 0.0
        self.count = 0
        self._histogram = histogram
        # segment carry for elapsed()-on-a-running-timer probes: the
        # internal stop/restart must not split one logical interval into
        # two histogram samples (count inflation, p50 dragged down)
        self._hist_carry_ms = 0.0

    def start(self) -> None:
        assert not self.started_, f"{self.name_} timer has already been started"
        self.start_time = time.perf_counter()
        self.started_ = True

    def stop(self, reset: bool = False, sync_arrays: Any = None,
             record: bool = True) -> None:
        assert self.started_, f"{self.name_} timer is not started"
        _sync(sync_arrays)
        elapsed = (time.perf_counter() - self.start_time) * 1000.0
        if self._histogram is not None:
            if record:
                self._histogram.observe(elapsed + self._hist_carry_ms)
                self._hist_carry_ms = 0.0
            else:
                self._hist_carry_ms += elapsed
        if reset:
            self.elapsed_ms = elapsed
            self.count = 1
        else:
            self.elapsed_ms += elapsed
            self.count += 1
        self.started_ = False

    def reset(self) -> None:
        self.started_ = False
        self.elapsed_ms = 0.0
        self.count = 0
        self._hist_carry_ms = 0.0

    def elapsed(self, reset: bool = True) -> float:
        """Return accumulated elapsed time in ms (stops/restarts a running
        timer; the probe's internal stop carries — not records — its
        segment, so the eventual real ``stop`` observes ONE histogram
        sample for the whole interval)."""
        started = self.started_
        if started:
            self.stop(record=False)
        total = self.elapsed_ms
        if reset:
            self.reset()
        if started:
            self.start()
        return total

    def mean(self) -> float:
        return self.elapsed_ms / max(self.count, 1)


#: bucket edges for millisecond-denominated timer histograms: 10us..5min
#: (a cold-compile first step lands in the tail instead of overflowing)
TIMER_MS_BUCKETS = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
    100.0, 250.0, 500.0, 1e3, 2.5e3, 5e3, 1e4, 3e4, 6e4, 3e5)


class SynchronizedWallClockTimer:
    """Group of named timers. ``.log(names)`` prints a one-line breakdown.

    ``registry``: optional telemetry ``MetricsRegistry`` — each named
    timer then observes every measured interval into the
    ``train_wall_clock_ms{timer=<name>}`` histogram family (module
    docstring)."""

    def __init__(self, registry: Any = None):
        self.timers: Dict[str, Timer] = {}
        self._registry = registry

    def __call__(self, name: str) -> Timer:
        if name not in self.timers:
            hist = None
            if self._registry is not None:
                hist = self._registry.histogram(
                    "train_wall_clock_ms", buckets=TIMER_MS_BUCKETS,
                    help="engine wall-clock breakdown (ms per interval)",
                    timer=name)
            self.timers[name] = Timer(name, histogram=hist)
        return self.timers[name]

    def has_timer(self, name: str) -> bool:
        return name in self.timers

    @staticmethod
    def memory_usage() -> str:
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            in_use = stats.get("bytes_in_use", 0) / (1024**3)
            peak = stats.get("peak_bytes_in_use", 0) / (1024**3)
            return f"DeviceMem: in-use {in_use:.2f} GB | peak {peak:.2f} GB"
        except Exception:
            return "DeviceMem: unavailable"

    def log(self, names: Iterable[str], normalizer: float = 1.0, reset: bool = True,
            memory_breakdown: bool = False, ranks: Optional[List[int]] = None) -> None:
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) / normalizer
                string += f" | {name}: {elapsed_time:.2f}"
        if memory_breakdown:
            string += " | " + self.memory_usage()
        log_dist(string, ranks=ranks or [0])

    def get_mean(self, names: Iterable[str], normalizer: float = 1.0) -> Dict[str, float]:
        assert normalizer > 0.0
        return {
            name: self.timers[name].mean() / normalizer
            for name in names if name in self.timers
        }


class ThroughputTimer:
    """Samples/sec + optional TFLOPs reporting across train batches."""

    def __init__(self, batch_size: int, start_step: int = 2, steps_per_output: int = 50,
                 monitor_memory: bool = False, logging_fn=None):
        self.start_time = 0.0
        self.end_time = 0.0
        self.started = False
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self.step_elapsed_time = 0.0
        self.steps_per_output = steps_per_output
        self._steps_since_report = 0
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or (lambda msg: log_dist(msg, ranks=[0]))
        self.initialized = False

    def update_epoch_count(self) -> None:
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self) -> None:
        self.initialized = True

    def start(self) -> None:
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step:
            self.start_time = time.perf_counter()

    def stop(self, global_step: bool = False, report_speed: bool = True,
             sync_arrays: Any = None, steps: int = 1) -> None:
        """``steps``: number of global steps covered by this start/stop
        interval (>1 for the engine's multi-step ``train_batches`` path)."""
        if not self.started:
            return
        self.started = False
        self.micro_step_count += steps
        if global_step:
            self.global_step_count += steps
        if self.start_time > 0:
            _sync(sync_arrays)
            self.end_time = time.perf_counter()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            self.step_elapsed_time += duration
            self.start_time = 0.0
            if global_step and report_speed and \
                    self.global_step_count % self.steps_per_output < steps:
                # steps since the last report (multi-step intervals may not
                # divide steps_per_output; scale by what was actually timed)
                covered = self._steps_since_report + steps
                self.logging(
                    f"epoch={self.epoch_count}/micro_step={self.micro_step_count}/"
                    f"global_step={self.global_step_count}, "
                    f"RunningAvgSamplesPerSec={self.avg_samples_per_sec():.2f}, "
                    f"CurrSamplesPerSec={self.batch_size / self.step_elapsed_time * covered:.2f}")
                self.step_elapsed_time = 0.0
                self._steps_since_report = 0
            elif global_step:
                self._steps_since_report += steps

    def avg_samples_per_sec(self) -> float:
        if self.global_step_count > self.start_step and self.total_elapsed_time > 0:
            samples = self.batch_size * (self.global_step_count - self.start_step)
            return samples / self.total_elapsed_time
        return float("nan")


def trainable_parameters_size(params) -> int:
    import jax

    return sum(x.size for x in jax.tree_util.tree_leaves(params))
