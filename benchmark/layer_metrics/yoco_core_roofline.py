"""The attention core of the window, full and cross layers against the
chip's bf16 peak: 2 x 40 x 128 FLOPs a kept (query, key) pair forward
and 3 x that trained (``flops_phi4flash.py``: ``T (T + 1) / 2`` pairs a
full or cross layer, ``W (W + 1) / 2 + (T - W) W`` a window layer) over
the device time under the ``attn_core`` scope, every pass. Heads 64
wide fill half of a 128-deep contraction
(``ssm_scopes.core_roofline_share``)."""

from benchmark import ssm_scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return ssm_scopes.core_roofline_share(record)
