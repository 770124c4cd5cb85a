"""Published peaks of the devices the benchmark knows, keyed by the
`device_kind` JAX reports.  A device that is not here is an error, never a
default: a share of a wrong peak is a wrong number under a right name."""

from __future__ import annotations

from typing import Dict

# Source: Google Cloud documentation, "TPU v5e" system architecture page:
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no published {what} for device kind {device_kind!r} in "
            f"benchmark/peaks.py (known: {sorted(PEAKS)}); add it with its "
            "source before reporting a share of it") from None
