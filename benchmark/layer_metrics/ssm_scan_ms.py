"""Device time of one optimizer step under the ``ssm_scan`` scope, every
pass: the Mamba layers' selective scans, forward and backward, whatever
implements them (``ops/selective_scan.py``: the kernel pair ``scan_fwd``
and ``scan_bwd`` on one TPU chip), with the repeats of B and C along the
lanes and the sums of the partial gradients (``ssm_scopes.py``). Part of
what ``scope_reduce`` charges to ``block_other``."""

from benchmark import ssm_scopes

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"


def read(record: dict):
    return ssm_scopes.ms_per_step(record, "ssm_scan")
