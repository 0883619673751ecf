"""Device time of one optimizer step under the ``loop_exit`` scope,
every pass: the looped model's exit gate after every loop, the exit
distribution and its entropy (``loop_scopes.py``). Part of what
``scope_reduce`` charges to ``unscoped``."""

from benchmark import loop_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return loop_scopes.exit_ms_per_step(record)
