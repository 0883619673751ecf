"""Device time of one optimizer step under the ``ssm_proj`` scope, every
pass: a Mamba layer's four projections (``in_proj`` d -> 2E, ``x_proj``
E -> R + 2N, ``dt_proj`` R -> E, ``out_proj`` E -> d) and its gate
(``ssm_scopes.py``). Part of what ``scope_reduce`` charges to
``block_other``."""

from benchmark import ssm_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return ssm_scopes.ms_per_step(record, "ssm_proj")
