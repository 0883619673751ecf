"""Analytic FLOPs of a looped decoder configuration (``ouro-2.6b``), one
chip's share of it: what the cell's ``mfu``, ``mha128_core_roofline``
and ``head_loss_roofline`` divide by.

Matrix products only, forward and backward (backward is twice the
forward for every product here, so train = 3 x forward), from the keys
of the configuration's file; the conventions are ``flops_lfm2.py``'s:
norms, rotations, the softmax, the exit distribution and the loss's
elementwise passes are not FLOPs, and recomputation and whatever a
kernel wastes are not counted. The attention core is counted over
exactly the (query, key) pairs the causal mask keeps. Every count is of
all ``total_ut_steps`` loops: a layer's weights run once a loop.
"""

from __future__ import annotations

from benchmark.flops_lfm2 import kept_pairs


def attention_core_forward_per_pair(config: dict) -> float:
    """``q k^T`` and ``p v`` of every query head for one kept pair."""
    return 2.0 * config["num_attention_heads"] * 2 * config["head_dim"]


def attention_core_train_flops(config: dict, t: int, tokens: int) -> float:
    """Forward and backward of the attention core of every layer in
    every loop for ``tokens`` tokens in sequences of ``t``."""
    applications = config["num_hidden_layers"] * config["total_ut_steps"]
    return 3.0 * (tokens / t) * applications * kept_pairs(t) * attention_core_forward_per_pair(config)


def head_train_flops(config: dict, tokens: int) -> float:
    """The vocabulary head's three products (the logits, ``d hidden``,
    ``d head weights``) for every loop of ``tokens`` tokens: ``6 d V`` a
    position and loop."""
    return 6.0 * config["hidden_size"] * config["vocab_size"] * config["total_ut_steps"] * tokens


def forward_flops_by_part(config: dict, t: int) -> dict:
    """Forward FLOPs a token on this chip, by part, all loops."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    applications = config["num_hidden_layers"] * config["total_ut_steps"]
    loops = config["total_ut_steps"]
    return {
        "attn_proj": applications * 2.0 * (d * h * hd + 2 * d * hkv * hd + h * hd * d),
        "attn_core": applications * kept_pairs(t) / t * attention_core_forward_per_pair(config),
        "mlp": applications * 2.0 * 3 * d * config["intermediate_size"],
        "exit_gate": loops * 2.0 * d,
        "head": loops * 2.0 * d * config["vocab_size"],
    }


def train_flops_per_token(config: dict, t: int) -> float:
    """Forward and backward per trained token on this chip."""
    return 3.0 * sum(forward_flops_by_part(config, t).values())
