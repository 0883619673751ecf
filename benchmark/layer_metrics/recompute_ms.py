"""Device time of one optimizer step in the recomputed forward
(``rematted_computation``), every part: what ``remat=True`` costs
(``scope_reduce.py``)."""

from benchmark import scope_reduce

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return scope_reduce.ms_per_step(record, passes=("recompute",))
