"""Latent attention's core against the chip's bf16 peak: the causal
``q k^T`` (192 wide) and ``p v`` (128 wide) FLOPs of forward and
backward of every layer (``flops_joyai.py``: useful work only, so
padding, the masked half of a diagonal block and the recomputed forward
lower the share and can never raise it) over the device time under the
``attn_core`` scope, every pass. Bound by compute at T = 4,096: the
kernels read each of q, k and v once a pass."""

from benchmark import moe_scopes, scope_reduce

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return moe_scopes.roofline_share(
        record,
        moe_scopes.mla_core_flops_per_step(record),
        scope_reduce.ms_per_step(record, parts=("attn_core",)),
    )
