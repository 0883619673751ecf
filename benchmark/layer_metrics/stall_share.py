"""The part of the window lost to readings slower than the median:
``1 - median reading x number of readings / (last stamp - first
stamp)``. What the median hides from the rate, under a name of its
own."""

from benchmark import readings

LAYER = "runners"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return 100.0 * readings.summarize(record["stamps"], record["min_readings"])["stall_share"]
