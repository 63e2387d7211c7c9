"""Published peaks of the chips this benchmark runs on, keyed by
``device_kind``.  A device that is not in the table is an error, not a
default: a roofline share against a guessed peak is a guess.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s inter-chip interconnect.
(``bench.py``'s ``PEAK_BF16_FLOPS`` has the same bf16 figure; this copy is
the benchmark's own so a PR that claims a gain cannot move it.)
"""

from __future__ import annotations

from typing import Any, Dict

_V5E = {
    "bf16_flops": 197e12,
    "int8_ops": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bits_per_s": 1600e9,
}

#: substrings of ``device_kind`` (lower-cased) -> peaks
PEAKS: Dict[str, Dict[str, float]] = {
    "v5 lite": _V5E,
    "v5e": _V5E,
    "v5litepod": _V5E,
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    kind = device_kind.lower()
    for key, peaks in PEAKS.items():
        if key in kind:
            return peaks
    raise ValueError(
        f"no peaks on record for device_kind {device_kind!r}: add it to "
        "chipbench/peaks.py with its source instead of guessing")


def device_info(devices) -> Dict[str, Any]:
    """What JAX reports about the devices a run used."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
