"""The experts held against the chip's bf16 peak: 6 x 3 x hidden x
width FLOPs (three matrices, forward and backward) for every
assignment the step's counter saw (``flops_joyai.py``: useful work
only, so rows of padding, half-empty tiles and the recomputed forward
lower the share) over the device time under the ``experts`` scope,
every pass. Bound by compute from about 240 tokens an expert on: each
expert's three matrices are read once a pass."""

from benchmark import moe_scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return moe_scopes.roofline_share(
        record,
        moe_scopes.experts_flops_per_step(record),
        moe_scopes.ms_per_step(record, "experts"),
    )
