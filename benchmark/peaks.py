"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Copied from ``multidisttorch_tpu/telemetry/device.py`` (the row the
cells run on). Source: Google Cloud TPU documentation, "TPU v5e": 197
TFLOP/s in bf16, 16 GB of HBM at 819 GB/s per chip. A kind that is not
in the table is an error, never a neighbour's numbers.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} has no {what} in benchmark/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with their "
            "source before reporting a utilization on it"
        ) from None
