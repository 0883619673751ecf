"""The vocabulary head and the next-token loss as one walk over blocks
of positions.

A language model's training step ends in three products over the
vocabulary (hidden state x head weights, and the two products of that
one's backward pass) around a softmax over ``(B x T, V)`` float32
logits. Differentiated as written (``train/lm.py::lm_loss_mean``
through a float32 head), the logits and their gradient are two float32
arrays of that size, the largest the step holds, and each backward
product reads a float32 operand that the TPU's matrix unit rounds to
the compute type anyway. :func:`lm_head_loss` makes the same numbers
with neither array: it walks the positions in equal blocks, and for a
block makes the logits, the logsumexp and the target's logit (float32),
the block's share of the loss, the logits' gradient (float32, rounded
once to the products' operand type) and from that one array the block's
``d hidden`` and its addition to ``d head weights`` and ``d bias``. All
of it happens in the forward rule of a ``jax.custom_vjp``: the
gradients are the rule's residuals and the backward rule scales them by
the loss's cotangent. A rule that made the logits again in the backward
pass would run four products for three. :func:`lm_head_loss_weighted`
is the same walk with each position's cross-entropy weighed (a looped
model's passes by their exit probability, ``train/lm.py::_looped_loss``).

``lm_loss_mean`` stays the definition: ``tests/test_head_loss.py``
holds the walk to it, differentiated through a plain float32 head.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from multidisttorch_tpu.utils.profiling import SCOPE_HEAD, SCOPE_LOSS

# The most float32 logits one block of the walk may hold, in bytes. A
# step whose whole ``(B x T, V)`` array is under it takes one block (no
# loop); a larger one takes the fewest equal blocks that fit. Raced on
# the chip at 1, 2, 4 and 8 blocks (PERF.md section 6, PR 38): of 16,384
# positions a 50,257-row head runs fastest whole and 0.7% slower in 2
# or 8 blocks, a 25,008-row tied one 3.8% faster in 2 or 4 than whole;
# half a GiB gives them 8 and 4, and the step's plan the most room.
LOGITS_BLOCK_BYTES = 1 << 29


def num_blocks(rows: int, vocab: int) -> int:
    """The fewest equal blocks of ``rows`` positions whose float32
    logits each stay under :data:`LOGITS_BLOCK_BYTES`: a divisor of
    ``rows``, from the shapes alone."""
    least = -(-rows * vocab * 4 // LOGITS_BLOCK_BYTES)
    return next(k for k in range(max(least, 1), rows + 1) if rows % k == 0)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def lm_head_loss(
    hidden: jax.Array,
    weights: jax.Array,
    bias: Optional[jax.Array],
    tokens: jax.Array,
    dtype: Any,
    tied: bool,
) -> jax.Array:
    """Mean next-token cross-entropy of ``hidden @ weights + bias``
    against ``tokens`` rolled left by one, the last position of each
    sequence masked: ``lm_loss_mean(logits, tokens)`` without the
    logits.

    ``hidden`` is ``(B, T, d)``, the model's state after its last norm;
    ``weights`` ``(d, V)``, or ``(V, d)`` with ``tied`` (the embedding
    table, read as it lies); ``bias`` ``(V,)`` or ``None``; ``tokens``
    ``(B, T)`` integers. The three products read ``hidden``,
    ``weights`` and the logits' gradient at ``dtype`` (the model's
    compute type; float32 operands run at the default precision, as a
    float32 ``nn.Dense`` does) and accumulate in float32; everything
    between them is float32."""
    return _walk(hidden, weights, bias, tokens, dtype, tied)[0]


def lm_head_loss_weighted(
    hidden: jax.Array,
    weights: jax.Array,
    bias: Optional[jax.Array],
    tokens: jax.Array,
    dtype: Any,
    tied: bool,
    position_weights: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """:func:`lm_head_loss` with each position's cross-entropy weighed
    by ``position_weights`` ``(B, T)``: ``(loss, CE)``, the loss
    ``sum(w * CE) / positions`` (``positions`` the ``B x (T - 1)`` that
    have a next token), differentiable in ``w`` too (its gradient ``CE /
    positions``), and ``CE`` the masked ``(B, T)`` cross-entropies
    themselves, 0 at each sequence's last position. ``CE`` is a reading,
    for a caller to count: its gradient is stopped, and the loss is what
    trains."""
    loss, ce = _weighted(hidden, weights, bias, tokens, position_weights, dtype, tied)
    return loss, jax.lax.stop_gradient(ce)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _weighted(hidden, weights, bias, tokens, position_weights, dtype, tied):
    return _weighted_forward(hidden, weights, bias, tokens, position_weights, dtype, tied)[0]


def _walk(hidden, weights, bias, tokens, dtype, tied, position_weights=None):
    """``(loss, (d hidden, d weights, d bias), CE)``, the gradients those
    of a loss cotangent of 1 and ``CE`` each position's cross-entropy
    ``(B x T,)``, unmasked, where ``position_weights`` is given (else
    ``None``)."""
    b, t, d = hidden.shape
    rows, vocab = b * t, weights.shape[0 if tied else 1]
    blocks = num_blocks(rows, vocab)
    by_block = (rows,) if blocks == 1 else (blocks, rows // blocks)
    weighted = position_weights is not None
    with jax.named_scope(SCOPE_HEAD):
        x = hidden.reshape(*by_block, d).astype(dtype)
        w = weights.astype(dtype)
    with jax.named_scope(SCOPE_LOSS):
        targets = jnp.roll(tokens, -1, axis=1).reshape(by_block)
        # lm_loss_mean's weights with its denominator folded in
        per_position = (jnp.arange(t) < t - 1).astype(jnp.float32) / ((t - 1) * b)
        scale = jnp.broadcast_to(per_position, (b, t))
        if weighted:
            scale = scale * position_weights.astype(jnp.float32)
        scale = scale.reshape(by_block)
    # contracting dimensions of rows x weights, gradient x weights, rows x gradient
    out_dims, in_dims = (((1,), (1,)), ((1,), (0,))) if tied else (((1,), (0,)), ((1,), (1,)))
    product = partial(jax.lax.dot_general, preferred_element_type=jnp.float32)

    def block(sums, operands):
        loss, d_weights, d_bias = sums
        xb, target, weight = operands
        with jax.named_scope(SCOPE_HEAD):
            logits = product(xb, w, (out_dims, ((), ())))
            if bias is not None:
                logits = logits + bias.astype(jnp.float32)
        with jax.named_scope(SCOPE_LOSS):
            hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) == target[:, None]
            top = jnp.max(logits, axis=-1, keepdims=True)
            lse = top + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1, keepdims=True))
            at_target = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
            per_token = lse[:, 0] - at_target
            loss = loss + jnp.sum(per_token * weight)
            grad = ((jnp.exp(logits - lse) - hit) * weight[:, None]).astype(dtype)
        with jax.named_scope(SCOPE_HEAD):
            d_x = product(grad, w, (in_dims, ((), ()))).astype(hidden.dtype)
            pair = (grad, xb) if tied else (xb, grad)
            d_weights = d_weights + product(*pair, (((0,), (0,)), ((), ())))
            if bias is not None:
                d_bias = d_bias + jnp.sum(grad, axis=0, dtype=jnp.float32)
        return (loss, d_weights, d_bias), (d_x, per_token if weighted else None)

    with jax.named_scope(SCOPE_HEAD):
        zeros = (
            jnp.zeros((), jnp.float32),
            jnp.zeros(weights.shape, jnp.float32),
            None if bias is None else jnp.zeros(bias.shape, jnp.float32),
        )
    walk = block if blocks == 1 else partial(jax.lax.scan, block)
    (loss, d_weights, d_bias), (d_x, ce) = walk(zeros, (x, targets, scale))
    with jax.named_scope(SCOPE_HEAD):
        as_given = lambda g, like: None if like is None else g.astype(like.dtype)
        gradients = d_x.reshape(b, t, d), as_given(d_weights, weights), as_given(d_bias, bias)
    return loss, gradients, ce


def _forward(hidden, weights, bias, tokens, dtype, tied):
    loss, gradients, _ = _walk(hidden, weights, bias, tokens, dtype, tied)
    return loss, gradients


def _backward(dtype, tied, gradients, cotangent):
    with jax.named_scope(SCOPE_HEAD):
        d_hidden, d_weights, d_bias = jax.tree.map(
            lambda g: (cotangent * g).astype(g.dtype), gradients
        )
    return d_hidden, d_weights, d_bias, None


def _weighted_forward(hidden, weights, bias, tokens, position_weights, dtype, tied):
    loss, gradients, ce = _walk(hidden, weights, bias, tokens, dtype, tied, position_weights)
    b, t = tokens.shape
    with jax.named_scope(SCOPE_LOSS):
        ce = ce.reshape(b, t) * (jnp.arange(t) < t - 1)
        d_position_weights = (ce / ((t - 1) * b)).astype(position_weights.dtype)
    return (loss, ce), (gradients, d_position_weights)


def _weighted_backward(dtype, tied, residuals, cotangents):
    gradients, d_position_weights = residuals
    cotangent, _ = cotangents  # CE's: zero, its gradient is stopped where it is handed out
    d_hidden, d_weights, d_bias, d_tokens = _backward(dtype, tied, gradients, cotangent)
    with jax.named_scope(SCOPE_HEAD):
        d_position_weights = (cotangent * d_position_weights).astype(d_position_weights.dtype)
    return d_hidden, d_weights, d_bias, d_tokens, d_position_weights


lm_head_loss.defvjp(_forward, _backward)
_weighted.defvjp(_weighted_forward, _weighted_backward)
