"""Analytic FLOPs of a grouped-head, window/full, routed-expert
configuration (``smallthinker-21b-a3b``), one chip's share of it: what
the cell's ``mfu`` and ``swa_core_roofline`` divide by.

Matrix products only, forward and backward (backward is twice the
forward for every product here, so train = 3 x forward), from the keys
of the configuration's file; the conventions are ``flops_joyai.py``'s:
gathers, norms, rotations, the softmax and the router's top-k are not
FLOPs, and recomputation, masked halves of a tile and whatever a kernel
wastes are not counted. The attention core is counted over exactly the
(query, key) pairs the layer's mask keeps, so a kernel that skips what
lies outside the window cannot read over 100% for it.

The routed experts' share depends on the routing, so it is counted per
assignment and multiplied by the assignments the step's own counter
saw.
"""

from __future__ import annotations


def kept_pairs(t: int, window: int | None) -> int:
    """(query, key) pairs of one sequence of ``t`` with key <= query
    and, given a window, query - key < window."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def layer_windows(config: dict) -> list:
    """Each layer's window, ``None`` for a full layer."""
    return [config["sliding_window_size"] if windowed else None
            for windowed in config["sliding_window_layout"]]


def attention_core_forward_per_pair(config: dict) -> float:
    """``q k^T`` and ``p v`` of every query head for one kept pair."""
    return 2.0 * config["num_attention_heads"] * 2 * config["head_dim"]


def attention_core_train_flops(config: dict, t: int, tokens: int) -> float:
    """Forward and backward of the attention core of every layer for
    ``tokens`` tokens in sequences of ``t``."""
    pairs = sum(kept_pairs(t, w) for w in layer_windows(config))
    return 3.0 * (tokens / t) * pairs * attention_core_forward_per_pair(config)


def expert_train_flops_per_assignment(config: dict) -> float:
    """One token through one routed expert, forward and backward: the
    three matrices of a ReGLU, 2 FLOPs a weight, times 3."""
    return 3.0 * 2.0 * 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def train_flops_per_token(config: dict, t: int, assignments_per_token_per_layer: float) -> float:
    """Forward and backward per trained token on this chip;
    ``assignments_per_token_per_layer`` is the mean number of a token's
    choices that land on an expert held here, as counted."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    layers = config["num_hidden_layers"]
    projections = 2.0 * (d * h * hd + 2 * d * hkv * hd + h * hd * d)
    core = sum(kept_pairs(t, w) for w in layer_windows(config)) / t \
        * attention_core_forward_per_pair(config)
    expert_layer = (
        2.0 * d * config["router_width"]
        + 2.0 * 3 * d * config["moe_ffn_hidden_size"] * assignments_per_token_per_layer
    )
    forward = layers * (projections + expert_layer) + core + 2.0 * d * config["vocab_size"]
    return 3.0 * forward
