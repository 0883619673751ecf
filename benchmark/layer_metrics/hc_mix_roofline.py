"""The hyper-connections' mixes against the chip's HBM bandwidth: the
bytes any implementation has to move for them in a step (per sublayer
and token ``(2n + 2) C`` elements forward and ``(3n + 2) C`` backward,
``hc_scopes.mix_bytes_per_step``: useful bytes only, so the recomputed
forward and every stream read more than once lower the share) over the
device time under the ``hc_mix`` scope, every pass. Bound by memory: a
few multiply-adds a byte."""

from benchmark import hc_scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return hc_scopes.mix_roofline_share(record)
