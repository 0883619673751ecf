"""The gated short convolutions against the chip's HBM bandwidth: the
bytes any implementation has to move in a step (forward B, C and u read
and the result written once; backward those and the cotangent read and
the three gradients and the taps' written once, at the compute dtype:
``flops_lfm2.conv_mix_train_bytes``) over the device time under the
``conv_mix`` scope, every pass, whatever implements it (XLA's fusions
or a kernel). Useful bytes only: the recomputed forward and every
intermediate written and read again lower it
(``conv_scopes.mix_roofline_share``)."""

from benchmark import conv_scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return conv_scopes.mix_roofline_share(record)
