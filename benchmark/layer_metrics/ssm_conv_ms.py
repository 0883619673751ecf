"""Device time of one optimizer step under the ``ssm_conv`` scope, every
pass: a Mamba layer's causal depthwise convolution, four shifted
multiply-adds of ``(B, T, E)``, and the ``silu`` after it
(``ssm_scopes.py``). Part of what ``scope_reduce`` charges to
``block_other``."""

from benchmark import ssm_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return ssm_scopes.ms_per_step(record, "ssm_conv")
