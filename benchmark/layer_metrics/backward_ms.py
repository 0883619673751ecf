"""Device time of one optimizer step in the backward pass (``transpose(``
and not recomputed), every part; forward is the rest bar the
optimizer, and the progress line prints it (``scope_reduce.py``)."""

from benchmark import scope_reduce

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return scope_reduce.ms_per_step(record, passes=("backward",))
