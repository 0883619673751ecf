"""Analytic FLOPs of the ``joyai-llm-flash`` configuration, one chip's
share of it: what ``mfu``, ``mla_core_roofline`` and
``experts_roofline`` divide by.

Matrix products only, forward and backward (backward is twice the
forward for every product here, so train = 3 x forward), from the keys
of the configuration's file. Gathers (the embedding, the dispatch of
tokens to experts), norms, rotations, the softmax and the router's
top-k are not FLOPs; recomputation, padding and whatever a kernel
wastes are not counted: a number here is the useful work, whatever
implements it.

The routed experts' share depends on the routing, so it is counted per
assignment (one token through one expert held here) and multiplied by
the assignments the step's own counter saw.
"""

from __future__ import annotations


def attention_core_forward_per_token(config: dict, t: int) -> float:
    """Causal ``q k^T`` and ``p v`` per token and layer: a token at
    position ``i`` meets ``i + 1`` keys, ``(t + 1) / 2`` on average."""
    h = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return 2.0 * h * (qk + config["v_head_dim"]) * (t + 1) / 2


def attention_core_train_flops(config: dict, t: int, tokens: int) -> float:
    """Forward and backward of the attention core of every layer, for
    ``tokens`` tokens in sequences of ``t``."""
    return 3.0 * config["num_hidden_layers"] * tokens * attention_core_forward_per_token(config, t)


def expert_train_flops_per_assignment(config: dict) -> float:
    """One token through one routed expert, forward and backward: the
    three matrices of a SwiGLU, 2 FLOPs a weight, times 3."""
    return 3.0 * 2.0 * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def train_flops_per_token(config: dict, t: int, assignments_per_token_per_layer: float) -> float:
    """Forward and backward per trained token on this chip;
    ``assignments_per_token_per_layer`` is the mean number of a token's
    choices that land on an expert held here, as counted."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    mla = 2.0 * (
        d * q_rank + q_rank * h * (nope + rope)  # q: down, up
        + d * (kv_rank + rope) + kv_rank * h * (nope + dv)  # k, v: down, up
        + h * dv * d  # output
    ) + attention_core_forward_per_token(config, t)
    dense_layers = config["first_k_dense_replace"]
    expert_layers = config["num_hidden_layers"] - dense_layers
    dense_mlp = 2.0 * 3 * d * config["intermediate_size"]
    expert_width = config["moe_intermediate_size"]
    expert_layer = (
        2.0 * d * config["router_width"]
        + 2.0 * 3 * d * expert_width * config["n_shared_experts"]
        + 2.0 * 3 * d * expert_width * assignments_per_token_per_layer
    )
    forward = (
        config["num_hidden_layers"] * mla
        + dense_layers * dense_mlp
        + expert_layers * expert_layer
        + 2.0 * d * config["vocab_size"]
    )
    return 3.0 * forward
