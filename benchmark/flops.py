"""Analytic FLOPs of one optimizer step.

Copied from ``bench.py::_lm_train_flops_per_token`` (checked against
the ledger: at GPT-2 medium's sizes and T=256 it gives 2.159 GFLOP a
token, which times PR 22's 43,730 tokens/s over 197 TFLOP/s is the
47.9% that line reports). The copy lives here so that no later PR can
change the yardstick; the original is listed in PERF.md for deletion.
"""

from __future__ import annotations


def lm_train_flops_per_token(d: int, layers: int, t: int, vocab: int) -> float:
    """Matmul FLOPs of forward and backward, per trained token.

    Forward per token: 24*d^2 per layer (q, k, v and output projections
    8*d^2, the 4x MLP 16*d^2) + causal attention 2*T*d (QK^T and AV at
    4*T*d, halved by the mask) + the d*vocab head (2*d*V). Backward is
    twice the forward for a dense stack, so train = 3 x forward.
    Embedding lookups are gathers, not FLOPs; recomputation is not
    counted.
    """
    fwd = layers * (24.0 * d * d + 2.0 * t * d) + 2.0 * d * vocab
    return 3.0 * fwd
