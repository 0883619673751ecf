"""Model FLOP/s utilization: the analytic FLOPs of forward and backward
per token (``flops.py``, recomputation not counted) times the cell's
rate per chip, over the chip's published bf16 peak (``peaks.py``). An
end-to-end utilization, not a kernel's roofline share."""

from benchmark import peaks, readings

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    per_s = readings.rate(
        record["units_per_reading_per_chip"], record["stamps"], record["min_readings"]
    )
    peak = peaks.peak(record["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * per_s * record["flops_per_unit"] / peak
