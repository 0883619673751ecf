"""Device time of one optimizer step under the ``hc_maps`` scope, every
pass: making each sublayer's three hyper-connection maps (the norm over
all the streams, the projections, the sigmoids, the Sinkhorn iterations)
and taking the gradient back through them (``hc_scopes.py``). Part of
what ``scope_reduce`` charges to ``block_other``."""

from benchmark import hc_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return hc_scopes.ms_per_step(record, "hc_maps")
