"""Analytic FLOPs of a hyper-connected latent-attention, routed-expert
configuration (``xing4.0-29b-a4b``), one chip's share of it: what the
cell's ``mfu`` divides by.

``flops_joyai.train_flops_per_token`` on the configuration's keys (they
are the same keys: latent attention, the dense layers, the router, the
shared expert, the assignments counted, the head) plus the products
that make the hyper-connections' maps: for each of a layer's two
sublayers the ``n d``-long state of a token times ``2n + n^2`` columns.
Matrix products only, forward and backward (train = 3 x forward). The
mixes of the streams are multiply-adds by a number a token, not matrix
products, and like norms, rotations and Sinkhorn's divisions they are
not counted: what they cost shows in ``hc_mix_ms`` and, against the
bytes they have to move, in ``hc_mix_roofline``.
"""

from __future__ import annotations

from benchmark import flops_joyai

SUBLAYERS = 2  # attention and the FFN, each behind its own connection


def maps_forward_per_token(config: dict) -> float:
    """The three projections of every connection of every layer."""
    n = config["hc_mult"]
    per_connection = 2.0 * n * config["hidden_size"] * (2 * n + n * n)
    return config["num_hidden_layers"] * SUBLAYERS * per_connection


def train_flops_per_token(config: dict, t: int, assignments_per_token_per_layer: float) -> float:
    return flops_joyai.train_flops_per_token(
        config, t, assignments_per_token_per_layer
    ) + 3.0 * maps_forward_per_token(config)
