"""GPT-2 (Radford et al. 2019) in plain ``jax.numpy``, float32.

The plain reference of the ``gpt2-medium`` configuration: forward pass,
next-token loss and gradients, written from the published description
and importing nothing of the program under test. No kernels, no cache,
no mixed precision: every matrix product runs at
``default_matmul_precision("highest")`` (on a TPU a float32 product is
otherwise taken in bf16 passes).

Block, as published: ``x + proj(attn(ln(x)))`` then
``x + down(gelu_new(up(ln(x))))``, pre-LayerNorm, learned positions,
causal softmax attention scaled by ``1/sqrt(head size)``, a final
LayerNorm, logits over the vocabulary. Departures, all of them the
program's and listed in ``gpt2-medium.json``: the head is a matrix of
its own with a bias (not the transposed embedding); q, k and v are
three matrices (the same mathematics as one fused one); no dropout.

Two things here are about fitting the chip machine and change no
operation: the blocks run as one ``lax.scan`` over their stacked
weights, and the scanned block is wrapped in ``jax.checkpoint``.
Unrolled, the float32 backward pass of 24 layers compiled to a 168 MB
executable (the machine's persistent cache holds 192 MiB) and kept
every layer's attention scores alive beside the training state.

Weights come in as a dict of arrays: ``wte (V, d)``, ``wpe (P, d)``,
``blocks``: a list of dicts with ``ln1_g ln1_b wq bq wk bk wv bv wo bo
ln2_g ln2_b w_up b_up w_down b_down`` (matrices stored ``(in, out)``),
then ``lnf_g lnf_b head_w (d, V) head_b``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def attention(q, k, v):
    """Causal softmax attention, ``(B, T, H, Dh)`` each."""
    t = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def block(x, w, n_head, eps):
    b, t, d = x.shape
    y = layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
    split = lambda a: a.reshape(b, t, n_head, d // n_head)
    q = split(y @ w["wq"] + w["bq"])
    k = split(y @ w["wk"] + w["bk"])
    v = split(y @ w["wv"] + w["bv"])
    x = x + attention(q, k, v).reshape(b, t, d) @ w["wo"] + w["bo"]
    y = layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    return x + gelu_new(y @ w["w_up"] + w["b_up"]) @ w["w_down"] + w["b_down"]


def forward(weights, tokens, config):
    """``(B, T) int32 -> (B, T, V) float32`` logits."""
    eps, n_head = config["layer_norm_epsilon"], config["n_head"]
    t = tokens.shape[1]
    x = weights["wte"][tokens] + weights["wpe"][jnp.arange(t)][None]
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *weights["blocks"])
    one_block = jax.checkpoint(lambda x, w: block(x, w, n_head, eps))
    x, _ = jax.lax.scan(lambda x, w: (one_block(x, w), None), x, stacked)
    x = layer_norm(x, weights["lnf_g"], weights["lnf_b"], eps)
    return x @ weights["head_w"] + weights["head_b"]


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
    """Everything the comparison needs, in one traced function."""
    with jax.default_matmul_precision("highest"):
        weights = jax.tree.map(lambda a: a.astype(jnp.float32), weights)

        def loss_of(w):
            logits = forward(w, tokens, config)
            return next_token_loss(logits, tokens), logits

        (loss, logits), grads = jax.value_and_grad(loss_of, has_aux=True)(weights)
    return logits, loss, grads
