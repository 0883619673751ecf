"""JoyAI-LLM-Flash's decoder in plain ``jax.numpy``, float32.

The plain reference of the ``joyai-llm-flash`` configuration: forward
pass, next-token loss and gradients, written from the model's public
``config.json`` (``joyai-llm-flash.json`` beside this file has its keys)
and importing nothing of the program under test. No kernels, no cache,
no mixed precision, no sorting or grouping of tokens: every matrix
product runs at ``default_matmul_precision("highest")``, attention
builds its scores, and the expert layer is a loop over the experts held
with a mask.

Per layer, with ``d`` = ``hidden_size`` and ``H`` heads::

    x1 = x + MLA(rms(x)),  x2 = x1 + FFN(rms(x1));  final rms, untied head, no biases
    MLA:  c_q = rms(y W_qa);  q = c_q W_qb  as (H, nope + rope)
          [c_kv | k_r] = y W_kva;  c_kv = rms(c_kv);  [k_nope | v] per head = c_kv W_kvb
          q_rope and k_r rotated: pairs (2i, 2i+1), angle pos * theta**(-2i/rope)
          k = [k_nope | k_r, the same for every head]
          causal softmax(q k^T / sqrt(nope + rope)) v, flattened, then W_o
    FFN of the first ``first_k_dense_replace`` layers: W_down(silu(y W_gate) * (y W_up))
    FFN of the rest: s = sigmoid(y W_r) over all ``router_width`` experts; the
          ``num_experts_per_tok`` largest of s + b are chosen (b: the selection
          bias, ``e_score_correction_bias``; one group); g = s at the chosen /
          (their sum + 1e-20) * ``routed_scaling_factor``;
          sum over the chosen experts e of g_e E_e(y)  +  E_shared(y),
          each expert W_down(silu(y W_gate) * (y W_up)).

**The chip's share.** ``experts_held = [first, count]``: of the routed
sum only the terms of experts ``first .. first + count - 1`` are added
(their weights are the only ones given); the router, the choice and the
normalisation are over all ``router_width`` experts. What the absent
experts would add is left out, as in the program. The vocabulary is
whatever ``wte`` and ``head`` hold.

Not here, as not in the program (``departures`` in the configuration's
file): the next-token-plus-one module and the rule that moves ``b``.

Three things are about fitting the chip machine at 4,096 tokens and
change no operation: attention runs one block of ``ATTENTION_BLOCK``
queries at a time against all the keys (the ``(H, T, T)`` scores of a
sequence are 2.1 GB in float32); each layer, and each such block, is
wrapped in ``jax.checkpoint`` so that the backward pass recomputes it
instead of keeping every layer's scores alive; and the experts of a
layer run as one ``lax.scan`` over their stacked weights (unrolled, the
float32 program took five minutes to compile into 1.4 GB of code; the
layers stay unrolled, because stacking their weights would copy them).

Weights come in as a dict: ``wte (V, d)``, ``blocks``: a list of dicts
with ``ln1 w_qa q_norm w_qb w_kva kv_norm w_kvb wo ln2`` and either
``w_gate w_up w_down`` (a dense layer) or ``router (d, E) score_bias
(E,) e_gate e_up (count, d, h) e_down (count, h, d) s_gate s_up
s_down`` (an expert layer); then ``lnf`` and ``head (d, V)``. Matrices
are stored ``(in, out)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ATTENTION_BLOCK = 512  # queries a block; a T it does not divide runs whole


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotate_pairs(x, theta):
    """``x``: ``(B, T, H, rope)``. Pair ``i`` is elements ``(2i, 2i+1)``."""
    t, width = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # (T, rope/2)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (width // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def attention(q, k, v):
    """Causal softmax attention; q, k ``(B, T, H, Dq)``, v ``(B, T, H, Dv)``."""
    b, t, h, dq = q.shape
    block = ATTENTION_BLOCK if t % ATTENTION_BLOCK == 0 else t

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(dq)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))  # (blocks, B, block, H, Dv)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, v.shape[-1])


def mla(y, w, config):
    b, t, _ = y.shape
    h = config["num_attention_heads"]
    nope, rope, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    rank, eps, theta = config["kv_lora_rank"], config["rms_norm_eps"], config["rope_theta"]
    q = (rms(y @ w["w_qa"], w["q_norm"], eps) @ w["w_qb"]).reshape(b, t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta)], axis=-1)
    latent = y @ w["w_kva"]
    k_r = rotate_pairs(latent[:, :, None, rank:], theta)  # (B, T, 1, rope)
    kv = (rms(latent[..., :rank], w["kv_norm"], eps) @ w["w_kvb"]).reshape(b, t, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rope))], axis=-1)
    return attention(q, k, kv[..., nope:]).reshape(b, t, h * dv) @ w["wo"]


def swiglu(y, gate, up, down):
    return (silu(y @ gate) * (y @ up)) @ down


def route(y, w, config):
    """``(chosen (N, k) int32, weights (N, k))`` over all the router's experts."""
    scores = 1.0 / (1.0 + jnp.exp(-(y @ w["router"])))
    _, chosen = jax.lax.top_k(scores + w["score_bias"], config["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * config["routed_scaling_factor"]


def experts(y, w, config):
    """``(output, chosen, assignments per expert held)`` for ``y`` ``(N, d)``."""
    first, count = config["experts_held"]
    chosen, weights = route(y, w, config)

    def add_expert(out, expert):
        e, gate, up, down = expert
        here = chosen == first + e  # (N, k); an expert is chosen at most once a token
        g = jnp.sum(jnp.where(here, weights, 0.0), axis=-1, keepdims=True)
        return out + g * swiglu(y, gate, up, down), jnp.sum(here)

    out, counts = jax.lax.scan(
        add_expert,
        swiglu(y, w["s_gate"], w["s_up"], w["s_down"]),
        (jnp.arange(count), w["e_gate"], w["e_up"], w["e_down"]),
    )
    return out, chosen, counts


def block(x, w, config):
    eps = config["rms_norm_eps"]
    x = x + mla(rms(x, w["ln1"], eps), w, config)
    y = rms(x, w["ln2"], eps)
    if "router" not in w:
        none = jnp.zeros((0,), jnp.int32)
        return x + swiglu(y, w["w_gate"], w["w_up"], w["w_down"]), (none, none)
    b, t, d = y.shape
    out, chosen, counts = experts(y.reshape(b * t, d), w, config)
    return x + out.reshape(b, t, d), (chosen, counts)


def forward(weights, tokens, config):
    """``(B, T) int32 -> ((B, T, V) float32 logits, per expert layer the
    experts chosen (N, k) and the assignments to each expert held)``."""
    x = weights["wte"][tokens]
    routing = []
    for w in weights["blocks"]:
        x, picked = jax.checkpoint(lambda x, w: block(x, w, config))(x, w)
        if "router" in w:
            routing.append(picked)
    logits = rms(x, weights["lnf"], config["rms_norm_eps"]) @ weights["head"]
    chosen, counts = zip(*routing)
    return logits, {"chosen": jnp.stack(chosen), "expert_counts": jnp.stack(counts)}


def next_token_loss(logits, tokens):
    """Mean cross-entropy of position ``i`` predicting token ``i+1``,
    over the ``T-1`` positions that have a next token and over the
    batch."""
    logits, targets = logits[:, :-1], tokens[:, 1:]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def logits_loss_grads(weights, tokens, config):
    """Everything the comparison needs, in one traced function:
    ``(logits, loss, gradients, routing)``."""
    with jax.default_matmul_precision("highest"):
        weights = jax.tree.map(lambda a: a.astype(jnp.float32), weights)

        def loss_of(w):
            logits, routing = forward(w, tokens, config)
            return next_token_loss(logits, tokens), (logits, routing)

        (loss, (logits, routing)), grads = jax.value_and_grad(loss_of, has_aux=True)(weights)
    return logits, loss, grads, routing
