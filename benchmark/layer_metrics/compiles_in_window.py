"""Trips to the compiler between the first and the last stamp, cache
hits and misses alike. Must read 0: a run in which it does not is not
``correct``."""

LAYER = "compile"
UNIT = "count"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return record["compiles_in_window"]
