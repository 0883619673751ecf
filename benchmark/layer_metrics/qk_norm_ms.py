"""Device time of one optimizer step under the ``qk_norm`` scope, every
pass: an attention layer's per-head norms of q and k and their rotation, between the projections and the core
(``conv_scopes.py``). Part of what ``scope_reduce`` charges to
``block_other``."""

from benchmark import conv_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return conv_scopes.ms_per_step(record, "qk_norm")
