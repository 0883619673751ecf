"""Analytic FLOPs and bytes of a gated short-convolution / attention
expert configuration (``lfm2-24b-a2b``), one chip's share of it: what
the cell's ``mfu``, ``gqa64_core_roofline`` and ``conv_mix_roofline``
divide by.

Matrix products only, forward and backward (backward is twice the
forward for every product here, so train = 3 x forward), from the keys
of the configuration's file; the conventions are ``flops_joyai.py``'s:
gathers, norms, rotations, the softmax, the router's top-k and the
convolution's gates and taps (5 multiply-adds an element) are not
FLOPs, and recomputation, masked halves of a tile and whatever a kernel
wastes are not counted. The attention core is counted over exactly the
(query, key) pairs the causal mask keeps.

The routed experts' share depends on the routing, so it is counted per
assignment and multiplied by the assignments the step's own counter
saw (``flops_joyai.expert_train_flops_per_assignment`` reads this
file's ``hidden_size`` and ``moe_intermediate_size`` too).
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def kept_pairs(t: int) -> int:
    """(query, key) pairs of one sequence of ``t`` with key <= query."""
    return t * (t + 1) // 2


def layers_of(config: dict, kind: str) -> int:
    return sum(k == kind for k in config["layer_types"])


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def attention_core_forward_per_pair(config: dict) -> float:
    """``q k^T`` and ``p v`` of every query head for one kept pair."""
    return 2.0 * config["num_attention_heads"] * 2 * head_dim(config)


def attention_core_train_flops(config: dict, t: int, tokens: int) -> float:
    """Forward and backward of the attention core of every attention
    layer for ``tokens`` tokens in sequences of ``t``."""
    pairs = layers_of(config, "full_attention") * kept_pairs(t)
    return 3.0 * (tokens / t) * pairs * attention_core_forward_per_pair(config)


def conv_mix_train_bytes(config: dict, tokens: int) -> float:
    """The bytes any implementation of the gates and the taps has to
    move in a step, every conv layer: forward ``B``, ``C`` and ``u``
    read and the result written once (4 arrays of ``tokens x d``);
    backward those three and the cotangent read and the three gradients
    written once (7), and the taps' gradient (float32); at the compute
    dtype. The recomputed forward is not counted."""
    d = config["hidden_size"]
    itemsize = DTYPE_BYTES[config["assumed"]["compute_dtype"]]
    a_layer = 11.0 * tokens * d * itemsize + config["conv_L_cache"] * d * 4
    return layers_of(config, "conv") * a_layer


def forward_flops_by_part(config: dict, t: int, assignments_per_token_per_layer: float) -> dict:
    """Forward FLOPs a token on this chip, by part."""
    d, hd = config["hidden_size"], head_dim(config)
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dense_layers = config["num_dense_layers"]
    expert_layers = config["num_hidden_layers"] - dense_layers
    return {
        "conv_proj": layers_of(config, "conv") * 2.0 * (d * 3 * d + d * d),
        "attn_proj": layers_of(config, "full_attention")
        * 2.0 * (d * h * hd + 2 * d * hkv * hd + h * hd * d),
        "attn_core": layers_of(config, "full_attention") * kept_pairs(t) / t
        * attention_core_forward_per_pair(config),
        "dense_mlp": dense_layers * 2.0 * 3 * d * config["intermediate_size"],
        "router": expert_layers * 2.0 * d * config["router_width"],
        "experts": expert_layers * 2.0 * 3 * d * config["moe_intermediate_size"]
        * assignments_per_token_per_layer,
        "head": 2.0 * d * config["vocab_size"],
    }


def train_flops_per_token(config: dict, t: int, assignments_per_token_per_layer: float) -> float:
    """Forward and backward per trained token on this chip;
    ``assignments_per_token_per_layer`` is the mean number of a token's
    choices that land on an expert held here, as counted."""
    return 3.0 * sum(forward_flops_by_part(config, t, assignments_per_token_per_layer).values())
