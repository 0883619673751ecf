"""SmallThinker-21BA3B's decoder in plain ``jax.numpy``, float32.

The plain reference of the ``smallthinker-21b-a3b`` configuration:
forward pass, next-token loss and gradients, written from the model's
public ``config.json`` (``smallthinker-21b-a3b.json`` beside this file
has its keys) and importing nothing of the program under test. No
kernels, no skipped blocks, no grouping of heads or of tokens: every
matrix product runs at ``default_matmul_precision("highest")``, the KV
heads are repeated to one a query head, a window layer is a full score
matrix under a mask, and the expert layer is a loop over the experts
held with a mask.

Per layer ``l``, with ``d`` = ``hidden_size``, ``H`` query heads over
``Hkv`` KV heads of ``head_dim``::

    y = rms(x)
    p = softmax(y W_r) over all ``router_width`` experts          # the router reads y, before attention
    chosen = the ``moe_num_active_primary_experts`` largest of p;  g = p at the chosen / their sum
    q = y W_q as (H, head_dim);  k = y W_k, v = y W_v as (Hkv, head_dim), each repeated H / Hkv times
    rope_layout[l] = 1: q and k rotated, element i with i + head_dim/2, angle pos * theta**(-2i/head_dim)
    s = q k^T / sqrt(head_dim), kept where key <= query and, sliding_window_layout[l] = 1,
        query - key < sliding_window_size
    x1 = x + softmax(s) v W_o
    z = rms(x1)
    x2 = x1 + sum over the chosen experts e of g_e W_down,e (relu(z W_gate,e) * (z W_up,e))

then the final rms and the untied head; no biases.

**The chip's share.** ``experts_held = [first, count]``: of the routed
sum only the terms of experts ``first .. first + count - 1`` are added
(their weights are the only ones given); the router, the choice and the
normalisation are over all ``router_width`` experts. What the absent
experts would add is left out, as in the program. Where the file's
``assumed.absent_share_grad`` is false, the share ``S`` of a token's
weight that its experts held have is a constant to the backward pass
(``g = stop_gradient(S) * (g / S)`` on the experts held, the same
numbers forward): the gradient then takes the absent experts' answers
to be as useful to the token, weight for weight, as the held ones',
where the cut alone would tell the router that only the experts held
ever answer. The vocabulary is whatever ``wte`` and ``head`` hold.

Four things are about fitting the chip machine at 16,384 tokens and
change no operation: attention runs one block of ``ATTENTION_BLOCK``
queries at a time against all the keys (the ``(H, T, T)`` scores of a
sequence are 30 GB in float32); each layer, each such block and each
expert is wrapped in ``jax.checkpoint``; the experts of a layer run as
one ``lax.scan`` over their stacked weights; and under the gradient the
head and the loss run ``LOSS_BLOCK`` positions at a time (the ``(T, V)``
logits are 1.2 GB in float32, and their log-softmax and its gradient as
much again), the logits that are handed back being made once, outside
it, from the same last hidden state (``logits_of``).

Weights come in as a dict: ``wte (V, d)``, ``blocks``: a list of dicts
with ``ln1 wq wk wv wo ln2 router (d, E) e_gate e_up (count, d, h)
e_down (count, h, d)``; then ``lnf`` and ``head (d, V)``. Matrices are
stored ``(in, out)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ATTENTION_BLOCK = 256  # queries a block; a T it does not divide runs whole
LOSS_BLOCK = 2048  # positions a block of the head and the loss; likewise


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotate_halves(x, theta):
    """``x``: ``(B, T, H, width)``. Element ``i`` pairs with ``i + width/2``."""
    t, width = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # (T, width/2)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : width // 2], x[..., width // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window):
    """Causal softmax attention, ``window`` keys wide where given; q, k,
    v ``(B, T, H, D)``."""
    b, t, h, d = q.shape
    block = ATTENTION_BLOCK if t % ATTENTION_BLOCK == 0 else t

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        ahead = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]  # query - key
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))  # (blocks, B, block, H, D)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, d)


def grouped_attention(y, w, config, layer):
    b, t, _ = y.shape
    h, hkv, hd = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    q = (y @ w["wq"]).reshape(b, t, h, hd)
    k = jnp.repeat((y @ w["wk"]).reshape(b, t, hkv, hd), h // hkv, axis=2)
    v = jnp.repeat((y @ w["wv"]).reshape(b, t, hkv, hd), h // hkv, axis=2)
    if config["rope_layout"][layer]:
        q, k = rotate_halves(q, config["rope_theta"]), rotate_halves(k, config["rope_theta"])
    window = config["sliding_window_size"] if config["sliding_window_layout"][layer] else None
    return attention(q, k, v, window).reshape(b, t, h * hd) @ w["wo"]


def reglu(z, gate, up, down):
    return (jnp.maximum(z @ gate, 0.0) * (z @ up)) @ down


def route(y, w, config):
    """``(chosen (N, k) int32, weights (N, k))`` over all the router's experts."""
    logits = y @ w["router"]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    picked, chosen = jax.lax.top_k(p, config["moe_num_active_primary_experts"])
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


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
        return out + g * reglu(z, gate, up, down), jnp.sum(here)

    return jax.lax.scan(
        add_expert, jnp.zeros_like(z), (jnp.arange(count), w["e_gate"], w["e_up"], w["e_down"])
    )


def block(x, w, config, layer):
    eps = config["rms_norm_eps"]
    b, t, d = x.shape
    y = rms(x, w["ln1"], eps)
    chosen, weights = route(y.reshape(b * t, d), w, config)
    x = x + grouped_attention(y, w, config, layer)
    z = rms(x, w["ln2"], eps)
    out, counts = experts(z.reshape(b * t, d), chosen, weights, w, config)
    return x + out.reshape(b, t, d), (chosen, counts)


def hidden(weights, tokens, config):
    """``(B, T) int32 -> ((B, T, d) the last layer's output, per layer
    the experts chosen (N, k) and the assignments to each expert
    held)``."""
    x = weights["wte"][tokens]
    routing = []
    for layer, w in enumerate(weights["blocks"]):
        x, picked = jax.checkpoint(lambda x, w, layer=layer: block(x, w, config, layer))(x, w)
        routing.append(picked)
    chosen, counts = zip(*routing)
    return x, {"chosen": jnp.stack(chosen), "expert_counts": jnp.stack(counts)}


def head(x, weights, config):
    return rms(x, weights["lnf"], config["rms_norm_eps"]) @ weights["head"]


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
    gradients are out of the way: 1.2 GB at 16,384 tokens)."""
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
