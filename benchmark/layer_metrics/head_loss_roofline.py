"""The vocabulary head and the loss against the chip's bf16 peak: 6 x d
x V FLOPs a position and loop (``flops_ouro.head_train_flops``), over
the device time under the ``head`` and ``loss`` scopes, every pass
(``loop_scopes.head_loss_roofline_share``)."""

from benchmark import loop_scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return loop_scopes.head_loss_roofline_share(record)
