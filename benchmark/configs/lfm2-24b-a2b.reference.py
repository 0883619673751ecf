"""LFM2-24B-A2B's decoder in plain ``jax.numpy``, float32.

The plain reference of the ``lfm2-24b-a2b`` configuration: forward
pass, next-token loss and gradients, written from the model's public
``config.json`` (``lfm2-24b-a2b.json`` beside this file has its keys)
and importing nothing of the program under test. No kernels, no skipped
blocks, no grouping of heads or of tokens: every matrix product runs at
``default_matmul_precision("highest")``, the convolution is a sum of
three shifted copies of its gated input, the KV heads are repeated to
one a query head, attention is a full score matrix under a mask made
from positions, and the expert layer is a loop over the experts held
with a mask.

Per layer ``l``, with ``d`` = ``hidden_size``::

    y = rms(x)
    layer_types[l] = "conv":
        B, C, u = the three thirds of y W_in (d -> 3d)
        g = B * u;  z_t = w_0 g_{t-2} + w_1 g_{t-1} + w_2 g_t   (conv_L_cache = 3 taps, g_t = 0 for t < 0)
        x1 = x + (C * z) W_out
    layer_types[l] = "full_attention", H heads over Hkv KV heads of hd = d / H:
        q = y W_q as (H, hd);  k = y W_k, v = y W_v as (Hkv, hd), each repeated H / Hkv times
        q, k = rms over each head's hd elements (scale q_norm, k_norm of hd), then rotated: element i
            with i + hd/2, angle pos * theta**(-2i/hd)
        s = q k^T / sqrt(hd), kept where key <= query
        x1 = x + softmax(s) v W_o
    z = rms(x1)
    l < num_dense_layers:  x2 = x1 + (silu(z W_gate) * (z W_up)) W_down
    else:  s = sigmoid(z W_r);  chosen = the num_experts_per_tok largest of s + b (use_expert_bias)
           g = s at the chosen / their sum (norm_topk_prob), times routed_scaling_factor
           x2 = x1 + sum over the chosen experts e of g_e (silu(z W_gate,e) * (z W_up,e)) W_down,e

then the final rms and the head, the embedding's transpose; no biases.

**The chip's share.** ``experts_held = [first, count]``: of the routed
sum only the terms of experts ``first .. first + count - 1`` are added
(their weights are the only ones given); the router, the choice and the
normalisation are over all ``router_width`` experts. What the absent
experts would add is left out, as in the program. Where the file's
``assumed.absent_share_grad`` is false, the share ``S`` of a token's
weight that its experts held have is a constant to the backward pass
(``g = stop_gradient(S) * (g / S)`` on the experts held, the same
numbers forward). The vocabulary is whatever ``wte`` holds.

Four things are about fitting the chip machine at 4 x 8,192 tokens and
change no operation: attention runs one block of ``ATTENTION_BLOCK``
queries at a time against all the keys; each layer, each such block and
each expert is wrapped in ``jax.checkpoint``; the experts of a layer
run as one ``lax.scan`` over their stacked weights; and under the
gradient the head and the loss run ``LOSS_BLOCK`` positions at a time,
the logits that are handed back being made once, outside it, from the
same last hidden state (``logits_of``).

Weights come in as a dict: ``wte (V, d)``, ``blocks``: a list of dicts
with ``ln1``, ``ln2``, the operator's ``w_in (d, 3d) conv_w (3, d)
w_out (d, d)`` or ``wq wk wv wo q_norm k_norm``, and the feed-forward's
``w_gate w_up w_down`` or ``router (d, E) router_bias (E,) e_gate e_up
(count, d, h) e_down (count, h, d)``; then ``lnf``. Matrices are stored
``(in, out)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ATTENTION_BLOCK = 256  # queries a block; a T it does not divide runs whole
LOSS_BLOCK = 2048  # positions a block of the head and the loss; likewise


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def short_conv(y, w):
    """The gated short convolution of ``y`` ``(B, T, d)``."""
    t = y.shape[1]
    b_gate, c_gate, u = jnp.split(y @ w["w_in"], 3, axis=-1)
    g = b_gate * u
    taps = w["conv_w"].shape[0]
    z = jnp.zeros_like(g)
    for j in range(taps):  # tap j reads the position taps - 1 - j back
        back = taps - 1 - j
        shifted = jnp.pad(g, ((0, 0), (back, 0), (0, 0)))[:, :t]
        z = z + w["conv_w"][j] * shifted
    return (c_gate * z) @ w["w_out"]


def rotate_halves(x, theta):
    """``x``: ``(B, T, H, width)``. Element ``i`` pairs with ``i + width/2``."""
    t, width = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # (T, width/2)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : width // 2], x[..., width // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v):
    """Causal softmax attention; q, k, v ``(B, T, H, D)``."""
    b, t, h, d = q.shape
    block = ATTENTION_BLOCK if t % ATTENTION_BLOCK == 0 else t

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]  # key <= query
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))  # (blocks, B, block, H, D)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, d)


def full_attention(y, w, config):
    b, t, d = y.shape
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = d // h, config["norm_eps"]
    theta = config["rope_parameters"]["rope_theta"]
    q = rms((y @ w["wq"]).reshape(b, t, h, hd), w["q_norm"], eps)
    k = rms((y @ w["wk"]).reshape(b, t, hkv, hd), w["k_norm"], eps)
    v = (y @ w["wv"]).reshape(b, t, hkv, hd)
    q, k = rotate_halves(q, theta), rotate_halves(k, theta)
    k, v = jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2)
    return attention(q, k, v).reshape(b, t, h * hd) @ w["wo"]


def swiglu(z, gate, up, down):
    return (silu(z @ gate) * (z @ up)) @ down


def route(z, w, config):
    """``(chosen (N, k) int32, weights (N, k))`` over all the router's experts."""
    scores = 1.0 / (1.0 + jnp.exp(-(z @ w["router"])))
    bias = w["router_bias"] if config["use_expert_bias"] else 0.0
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), config["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, picked * config["routed_scaling_factor"]


def experts(z, chosen, weights, w, config):
    """``(output, assignments per expert held)`` for ``z`` ``(N, d)``."""
    first, count = config["experts_held"]
    if not config.get("assumed", {}).get("absent_share_grad", True):
        here = jnp.where((chosen >= first) & (chosen < first + count), weights, 0.0)
        share = jnp.sum(here, axis=-1, keepdims=True)
        weights = jax.lax.stop_gradient(share) * (here / jnp.maximum(share, 1e-20))

    @jax.checkpoint
    def add_expert(out, expert):
        e, gate, up, down = expert
        here = chosen == first + e  # (N, k); an expert is chosen at most once a token
        g = jnp.sum(jnp.where(here, weights, 0.0), axis=-1, keepdims=True)
        return out + g * swiglu(z, gate, up, down), jnp.sum(here)

    return jax.lax.scan(
        add_expert, jnp.zeros_like(z), (jnp.arange(count), w["e_gate"], w["e_up"], w["e_down"])
    )


def block(x, w, config, layer):
    """``(x, (chosen, counts))``, the second ``None`` for a dense layer."""
    eps = config["norm_eps"]
    b, t, d = x.shape
    y = rms(x, w["ln1"], eps)
    if config["layer_types"][layer] == "conv":
        x = x + short_conv(y, w)
    else:
        x = x + full_attention(y, w, config)
    z = rms(x, w["ln2"], eps)
    if layer < config["num_dense_layers"]:
        return x + swiglu(z, w["w_gate"], w["w_up"], w["w_down"]), None
    z = z.reshape(b * t, d)
    chosen, weights = route(z, w, config)
    out, counts = experts(z, chosen, weights, w, config)
    return x + out.reshape(b, t, d), (chosen, counts)


def hidden(weights, tokens, config):
    """``(B, T) int32 -> ((B, T, d) the last layer's output, per expert
    layer the experts chosen (N, k) and the assignments to each expert
    held)``."""
    x = weights["wte"][tokens]
    routing = []
    for layer, w in enumerate(weights["blocks"]):
        x, picked = jax.checkpoint(lambda x, w, layer=layer: block(x, w, config, layer))(x, w)
        if picked is not None:
            routing.append(picked)
    chosen, counts = zip(*routing)
    return x, {"chosen": jnp.stack(chosen), "expert_counts": jnp.stack(counts)}


def head(x, weights, config):
    return rms(x, weights["lnf"], config["norm_eps"]) @ weights["wte"].T


def forward(weights, tokens, config):
    """``(B, T) int32 -> ((B, T, V) float32 logits, the routing)``."""
    x, routing = hidden(weights, tokens, config)
    return head(x, weights, config), routing


def next_token_loss(logits, tokens):
    """Mean cross-entropy of position ``i`` predicting token ``i+1``,
    over the ``T-1`` positions that have a next token and over the
    batch."""
    logits, targets = logits[:, :-1], tokens[:, 1:]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def next_token_loss_by_blocks(x, weights, tokens, config):
    """:func:`next_token_loss` of ``head(x)``, the head and the
    log-softmax made ``LOSS_BLOCK`` positions at a time."""
    b, t, _ = x.shape
    block = LOSS_BLOCK if t % LOSS_BLOCK == 0 else t
    targets = jnp.roll(tokens, -1, axis=1)  # the last position has no next token

    @jax.checkpoint
    def one_block(start):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, axis=1)
        logits = head(cut(x), weights, config)
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
        picked = jnp.take_along_axis(logp, cut(targets)[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(start + jnp.arange(block) < t - 1, picked, 0.0))

    return jnp.sum(jax.lax.map(one_block, jnp.arange(0, t, block))) / (b * (t - 1))


def hidden_loss_grads(weights, tokens, config):
    """``(last hidden state, loss, gradients, routing)``: everything the
    comparison needs but the logits, which :func:`logits_of` makes from
    the hidden state (a caller short of memory makes them once the
    gradients are out of the way: 1.07 GB at 4 x 8,192 tokens)."""
    with jax.default_matmul_precision("highest"):
        weights = jax.tree.map(lambda a: a.astype(jnp.float32), weights)

        def loss_of(w):
            x, routing = hidden(w, tokens, config)
            return next_token_loss_by_blocks(x, w, tokens, config), (x, routing)

        (loss, (x, routing)), grads = jax.value_and_grad(loss_of, has_aux=True)(weights)
    return x, loss, grads, routing


def logits_of(x, weights, config):
    with jax.default_matmul_precision("highest"):
        return head(x, jax.tree.map(lambda a: a.astype(jnp.float32), weights), config)


def logits_loss_grads(weights, tokens, config):
    """``(logits, loss, gradients, routing)`` in one traced function."""
    x, loss, grads, routing = hidden_loss_grads(weights, tokens, config)
    return logits_of(x, weights, config), loss, grads, routing


ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8  # ``assumed.optimizer``


def adam_first_step(grads, learning_rate):
    """What Adam's first step, from moments of zero, adds to each
    parameter: the moments of one gradient, each corrected for its
    start, ``- lr m / (sqrt(v) + eps)``."""

    def change(g):
        m, v = (1 - ADAM_B1) * g, (1 - ADAM_B2) * g * g
        m, v = m / (1 - ADAM_B1), v / (1 - ADAM_B2)
        return -learning_rate * m / (jnp.sqrt(v) + ADAM_EPS)

    return jax.tree.map(change, grads)
