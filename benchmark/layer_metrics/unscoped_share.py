"""Share of the traced window's device busy time whose operation maps
to no part of the model (``scope_reduce.classify`` gives ``unscoped``).
The coverage guard of the split by scope: a refactor that drops a
scope, or a cached executable built before the scopes, shows here
first."""

from benchmark import scope_reduce

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    unscoped = scope_reduce.ms_per_step(record, parts=("unscoped",))
    return None if unscoped is None else 100.0 * unscoped / scope_reduce.ms_per_step(record)
