"""Device time of one optimizer step under the ``attn_cross`` scope,
every pass: the attention core of the layers that attend with a q of
their own over another layer's k and v (``ssm_scopes.py``). Part of what
``attn_core_ms`` reads."""

from benchmark import ssm_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return ssm_scopes.ms_per_step(record, "attn_cross")
