"""The median reading: one optimizer step of every trial of the cell."""

from benchmark import readings

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return 1e3 * readings.summarize(record["stamps"], record["min_readings"])["median_s"]
