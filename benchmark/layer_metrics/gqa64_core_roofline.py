"""The attention core of heads 64 wide over grouped KV heads against
the chip's bf16 peak: 2 x heads x 128 FLOPs a kept (query, key) pair
forward, three times that trained
(``flops_lfm2.attention_core_train_flops``), over the device time under
the ``attn_core`` scope, every pass. A 64-deep contraction fills half
of the MXU, so a kernel that wastes nothing else stands near 50%
(``conv_scopes.core_roofline_share``)."""

from benchmark import conv_scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return conv_scopes.core_roofline_share(record)
