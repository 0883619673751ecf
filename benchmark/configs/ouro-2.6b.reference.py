"""Ouro-2.6B's looped decoder in plain ``jax.numpy``, float32.

The plain reference of the ``ouro-2.6b`` configuration: forward pass,
exit distribution, training objective and gradients, written from the
model's public ``config.json`` (``ouro-2.6b.json`` beside this file has
its keys and, under ``assumed``, what the config does not say) and
importing nothing of the program under test. No kernels, no grouping of
heads: every matrix product runs at ``default_matmul_precision("highest")``,
attention is a full score matrix under a mask made from positions, and
the exit distribution is written as the products it is.

With ``d`` = ``hidden_size``, ``U`` = ``total_ut_steps``::

    h_0 = wte[tokens]
    loop u = 1..U:  x = h_{u-1};  for each block:  x = block(x);  h_u = rms(x, lnf)
        logits_u = h_u head;  g_u = h_u gate_w + gate_b
    block(x):
        y = rms(x, ln1)
        q = y wq, k = y wk, v = y wv as (H, hd), hd = head_dim; q and k rotated: element i with
            i + hd/2, angle pos * theta**(-2i/hd), pos the same in every loop
        a = x + rms(softmax(q k^T / sqrt(hd), key <= query) v wo, ln1_post)
        out = a + rms((silu(z w_gate) * (z w_up)) w_down, ln2_post),  z = rms(a, ln2)
    lambda_u = sigmoid(g_u);  p_u = lambda_u prod_{j<u} (1 - lambda_j) for u < U;  p_U = prod_{j<U} (1 - lambda_j)
    loss = mean over the positions with a next token of  sum_u p_u CE(logits_u)
           + beta sum_u p_u log p_u,  beta = assumed.exit_entropy_weight

Three things are about fitting the chip machine at 2 x 4,096 tokens and
change no operation: attention runs one block of ``ATTENTION_BLOCK``
queries at a time against all the keys; each loop, each block
application inside it and each such query block is wrapped in
``jax.checkpoint`` (the loops as a ``lax.scan`` plan 6.8 GiB, not 14.4,
but double the compiled program, 71 MB against 37, and the compile
cache's 192 MiB has to hold the cell's step beside it); and the head and
the cross-entropy run ``LOSS_BLOCK`` positions of a loop at a time under
``jax.checkpoint``, the logits that are handed back being made loop by
loop, outside it, from the same states (``logits_of``).

Weights come in as a dict: ``wte (V, d)``, ``blocks``: a list of dicts
with ``ln1 wq wk wv wo ln1_post ln2 w_gate w_up w_down ln2_post``; then
``lnf``, ``gate_w (d, 1)``, ``gate_b (1,)``, ``head (d, V)``. Matrices
are stored ``(in, out)``.
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


def block(x, w, config):
    b, t, d = x.shape
    h, hkv, hd = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    y = rms(x, w["ln1"], eps)
    q = rotate_halves((y @ w["wq"]).reshape(b, t, h, hd), theta)
    k = rotate_halves((y @ w["wk"]).reshape(b, t, hkv, hd), theta)
    v = (y @ w["wv"]).reshape(b, t, hkv, hd)
    k, v = jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2)
    a = x + rms(attention(q, k, v).reshape(b, t, h * hd) @ w["wo"], w["ln1_post"], eps)
    z = rms(a, w["ln2"], eps)
    mlp = (silu(z @ w["w_gate"]) * (z @ w["w_up"])) @ w["w_down"]
    return a + rms(mlp, w["ln2_post"], eps)


def loops(weights, tokens, config):
    """``(states (U, B, T, d), gate logits (U, B, T))``: the final
    norm's output after every loop, and the exit gate's logit on it."""
    x = weights["wte"][tokens]

    def one_loop(x, blocks, lnf):
        for w in blocks:
            x = jax.checkpoint(lambda x, w: block(x, w, config))(x, w)
        return rms(x, lnf, config["rms_norm_eps"])

    states, gates = [], []
    for _ in range(config["total_ut_steps"]):
        x = jax.checkpoint(one_loop)(x, weights["blocks"], weights["lnf"])
        states.append(x)
        gates.append((x @ weights["gate_w"])[..., 0] + weights["gate_b"][0])
    return jnp.stack(states), jnp.stack(gates)


def exit_distribution(gate_logits):
    """``p`` ``(U, B, T)``: the chance of leaving after each loop."""
    lam = 1.0 / (1.0 + jnp.exp(-gate_logits))
    p, left = [], jnp.ones_like(lam[0])
    for u in range(lam.shape[0] - 1):
        p.append(lam[u] * left)
        left = left * (1.0 - lam[u])
    return jnp.stack(p + [left])


def cross_entropies(state, weights, tokens):
    """``(B, T)`` cross-entropy of position ``i`` predicting token
    ``i+1`` from one loop's state, 0 at the last position; the head and
    the log-softmax ``LOSS_BLOCK`` positions at a time."""
    b, t, _ = state.shape
    block = LOSS_BLOCK if t % LOSS_BLOCK == 0 else t
    targets = jnp.roll(tokens, -1, axis=1)  # the last position has no next token

    @jax.checkpoint
    def one_block(start):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, axis=1)
        logits = cut(state) @ weights["head"]
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
        picked = jnp.take_along_axis(logp, cut(targets)[..., None], axis=-1)[..., 0]
        return jnp.where(start + jnp.arange(block) < t - 1, -picked, 0.0)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))  # (blocks, B, block)
    return out.transpose(1, 0, 2).reshape(b, t)


def objective(weights, tokens, config):
    """``(loss, (states, counters))``: the entropy-regularised expected
    loss over the exit distribution, and per loop the mean ``p_u`` and
    the mean cross-entropy over the positions with a next token."""
    states, gates = loops(weights, tokens, config)
    b, t = tokens.shape
    positions = b * (t - 1)
    has_next = (jnp.arange(t) < t - 1).astype(jnp.float32)
    p = exit_distribution(gates)
    ce = jnp.stack([cross_entropies(s, weights, tokens) for s in states])
    beta = config["assumed"]["exit_entropy_weight"]
    loss = (jnp.sum(p * ce) + beta * jnp.sum(p * jnp.log(p) * has_next)) / positions
    counters = {
        "exit_p": jnp.sum(p * has_next, axis=(1, 2)) / positions,
        "loop_loss": jnp.sum(ce, axis=(1, 2)) / positions,
    }
    return loss, (states, counters)


def hidden_loss_grads(weights, tokens, config):
    """``(states, loss, gradients, counters)``: everything the
    comparison needs but the logits, which :func:`logits_of` makes from
    a loop's state (a caller short of memory makes them loop by loop
    once the gradients are out of the way: 1.5 GiB a loop at 2 x 4,096
    tokens)."""
    with jax.default_matmul_precision("highest"):
        weights = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
        (loss, (states, counters)), grads = jax.value_and_grad(objective, has_aux=True)(
            weights, tokens, config
        )
    return states, loss, grads, counters


def logits_of(state, weights):
    """One loop's logits ``(B, T, V)`` from its state."""
    with jax.default_matmul_precision("highest"):
        return state.astype(jnp.float32) @ weights["head"].astype(jnp.float32)


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
