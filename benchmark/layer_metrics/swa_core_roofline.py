"""The grouped-head attention core of full and window layers against
the chip's bf16 peak: the ``q k^T`` and ``p v`` FLOPs of forward and
backward of every layer over exactly the (query, key) pairs the layer's
mask keeps (``flops_swa.py``: ``T (T + 1) / 2`` a full layer, ``W (W +
1) / 2 + (T - W) W`` a window layer; useful work only, so the masked
part of a diagonal or edge tile lowers the share and a skipped block
cannot raise it) over the device time under the ``attn_core`` scope,
every pass. Bound by compute at T = 16,384."""

from benchmark import swa_scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return swa_scopes.core_roofline_share(record)
