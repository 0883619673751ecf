"""Device time of one optimizer step after the last block: ``ln_out``, the
vocabulary ``head`` and the ``loss`` scope, every pass
(``scope_reduce.py``)."""

from benchmark import scope_reduce

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return scope_reduce.ms_per_step(record, parts=("head", "loss"))
