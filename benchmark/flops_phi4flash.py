"""Analytic FLOPs and the scan's bytes of the decoder-hybrid-decoder
configuration (``phi-4-mini-flash``), one chip's share of it: what the
cell's ``mfu``, ``yoco_core_roofline`` and ``ssm_scan_roofline`` divide
by.

Matrix products only, forward and backward (backward is twice the
forward for every product here, so train = 3 x forward), from the keys
of the configuration's file; the conventions are ``flops_swa.py``'s:
gathers, norms, the softmax, the convolution and the selective scan
(0.1% of the step, on the vector unit) are not FLOPs, and
recomputation, masked halves of a tile and whatever a kernel wastes are
not counted. The attention core is counted over exactly the (query,
key) pairs a layer's mask keeps.

The scan is bound by bandwidth, so it is held to bytes: what any
implementation has to move, once, at the compute dtype.
"""

from __future__ import annotations

from benchmark.flops_swa import kept_pairs  # (query, key) pairs a causal mask and a window keep

ATTENTION_KINDS = {"window": True, "full_kv": False, "cross": False}  # kind: windowed
MAMBA_KINDS = ("mamba", "mamba_memory")


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def core_pairs(config: dict, t: int) -> int:
    """The kept pairs of the window, full and cross layers together."""
    return sum(
        kept_pairs(t, config["sliding_window"] if ATTENTION_KINDS[kind] else None)
        for kind in config["layer_kinds"] if kind in ATTENTION_KINDS
    )


def attention_core_forward_per_pair(config: dict) -> float:
    """``q k^T`` and ``p v`` of every query head for one kept pair: 2 x
    40 x 128 at heads of 64."""
    return 2.0 * config["num_attention_heads"] * 2 * head_dim(config)


def attention_core_train_flops(config: dict, t: int, tokens: int) -> float:
    """Forward and backward of the attention core of every attention
    layer for ``tokens`` tokens in sequences of ``t``."""
    return 3.0 * (tokens / t) * core_pairs(config, t) * attention_core_forward_per_pair(config)


def mixer_weights(config: dict, kind: str) -> int:
    """The weights of a layer's mixer that a token is multiplied by."""
    d, hd = config["hidden_size"], head_dim(config)
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    assumed = config["assumed"]
    e = assumed["expand"] * d
    if kind in MAMBA_KINDS:
        r, n = assumed["dt_rank"], assumed["d_state"]
        return d * 2 * e + e * (r + 2 * n) + r * e + e * d
    if kind == "gmu":
        return 2 * d * e
    if kind == "cross":
        return 2 * d * h * hd
    return d * (h + 2 * hkv) * hd + h * hd * d


def train_flops_per_token(config: dict, t: int) -> float:
    """Forward and backward per trained token on this chip."""
    d = config["hidden_size"]
    mlp = 3 * d * config["intermediate_size"]
    weights = sum(mixer_weights(config, kind) + mlp for kind in config["layer_kinds"])
    core = core_pairs(config, t) / t * attention_core_forward_per_pair(config)
    forward = 2.0 * weights + core + 2.0 * d * config["vocab_size"]  # the tied head
    return 3.0 * forward


def scan_train_bytes(config: dict, tokens: int, itemsize: int = 2) -> float:
    """The bytes every Mamba layer's selective scan has to move for
    ``tokens`` tokens, forward and backward once: forward x, delta, B
    and C read and y written; backward those four and y's cotangent
    read and the gradients of x, delta, B and C written; at the compute
    dtype (``itemsize``). The weights (A, D, the bias) and the chunk
    states are not counted."""
    e = config["assumed"]["expand"] * config["hidden_size"]
    n = config["assumed"]["d_state"]
    forward = 2 * e + 2 * n + e
    backward = (2 * e + 2 * n) + e + (2 * e + 2 * n)
    layers = sum(kind in MAMBA_KINDS for kind in config["layer_kinds"])
    return float(layers * tokens * (forward + backward) * itemsize)
