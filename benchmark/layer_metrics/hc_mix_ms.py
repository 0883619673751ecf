"""Device time of one optimizer step under the ``hc_mix`` scope, every
pass: the mixes of the residual streams (what a sublayer reads, ``Hpre
X``; what is written back, ``Hres X + Hpost^T y``; the sum of the
streams before the last norm) and their backward (``hc_scopes.py``).
Part of what ``scope_reduce`` charges to ``block_other``."""

from benchmark import hc_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return hc_scopes.ms_per_step(record, "hc_mix")
