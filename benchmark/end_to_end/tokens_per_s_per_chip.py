"""Trained tokens per second per chip: the tokens one chip trains in a
reading, over the median reading of the window (``readings.py``)."""

from benchmark import readings

UNIT = "tokens/s/chip"


def read(record: dict):
    return readings.rate(
        record["units_per_reading_per_chip"], record["stamps"], record["min_readings"]
    )
