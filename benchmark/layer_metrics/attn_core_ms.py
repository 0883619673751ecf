"""Device time of one optimizer step under the ``attn_core`` scope, every
pass: whatever attention is injected (dense scores and softmax today)
(``scope_reduce.py``)."""

from benchmark import scope_reduce

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return scope_reduce.ms_per_step(record, parts=("attn_core",))
