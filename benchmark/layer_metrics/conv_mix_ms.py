"""Device time of one optimizer step under the ``conv_mix`` scope, every
pass: a conv operator's two gates and its causal depthwise taps, elementwise passes over ``(B, T, d)`` arrays
(``conv_scopes.py``). Part of what ``scope_reduce`` charges to
``block_other``."""

from benchmark import conv_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return conv_scopes.ms_per_step(record, "conv_mix")
