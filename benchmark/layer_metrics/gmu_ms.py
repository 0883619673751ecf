"""Device time of one optimizer step under the ``gmu`` scope, every pass:
all of a gated memory unit's mixer, ``W_out(m * silu(W_in u))`` on
another layer's memory ``m`` (``ssm_scopes.py``). Part of what
``scope_reduce`` charges to ``block_other``."""

from benchmark import ssm_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return ssm_scopes.ms_per_step(record, "gmu")
