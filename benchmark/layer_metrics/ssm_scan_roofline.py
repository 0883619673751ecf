"""The selective scans against the chip's HBM bandwidth: the bytes any
scan has to move in a step (forward x, delta, B and C read and y
written once; backward those and y's cotangent read and the four
gradients written once, at the compute dtype:
``flops_phi4flash.scan_train_bytes``) over the device time under the
``ssm_scan`` scope, every pass, whatever implements the scan. Useful
bytes only, so it cannot pass 100%. **What a scan can reach is nearer
12% than 100%**: it is bound by the vector unit (16 multiply-adds and
an ``exp`` a state element and step: the kernel pair alone, 24.7 of the
27.3 ms under the scope, stands at 13%), and the scope also times XLA's
128-fold repeats of B and C along the lanes and the sums of
``scan_bwd``'s partial gradients, which move bytes this count leaves
out (``ssm_scopes.scan_roofline_share``; PERF.md section 5). Read it as
how far the arithmetic keeps the scan from the memory's limit, not as a
share still to be won."""

from benchmark import ssm_scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return ssm_scopes.scan_roofline_share(record)
