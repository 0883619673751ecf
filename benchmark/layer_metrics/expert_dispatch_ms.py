"""Device time of one optimizer step inside the expert layers under the
``expert_dispatch`` scope, every pass: the exchange between token order and expert order: sorting the assignments, gathering the tokens into the buffer, masking, and the weighted sum back
(``moe_scopes.py``). Part of what ``mlp_ms`` reads as a whole."""

from benchmark import moe_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return moe_scopes.ms_per_step(record, "expert_dispatch")
