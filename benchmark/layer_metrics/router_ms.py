"""Device time of one optimizer step inside the expert layers under the
``router`` scope, every pass: the router: scores over all the experts, the top-k, the weights
(``moe_scopes.py``). Part of what ``mlp_ms`` reads as a whole."""

from benchmark import moe_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return moe_scopes.ms_per_step(record, "router")
