"""Device time of one optimizer step under the ``attn_window`` scope,
every pass: the attention core of the layers held to a sliding window
(``swa_scopes.py``). Part of what ``attn_core_ms`` reads."""

from benchmark import swa_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return swa_scopes.ms_per_step(record, "attn_window")
