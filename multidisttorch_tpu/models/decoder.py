"""What the decoder LMs of ``models/`` share, and nothing of any one of
them: the rule that keeps values across rematerialization and its names,
the shell around a stack of blocks (length check, embedding, final norm,
head, and ``head_weights`` for ``train/lm.py``'s head walk), and the
pieces more than one block is made of (the bias-free projection and
RMSNorm, the gated MLP, the dense-or-expert feed-forward, rotary angles
and the halves rotation, the causal depthwise convolution).

Every model imports this module and no other model's: a block's reasons
for what it keeps, and what keeping it measured, sit beside the block's
own ``checkpoint_name`` calls. Which kernel runs under a block is asked
of the op (``ops/attention.py``, ``ops/moe.py::RoutedExperts``,
``ops/selective_scan.py``), from the placement ``parallel/mesh.py``
reads.

Parameter names are the same in every model: ``tok_embed`` (and
``pos_embed`` where positions are learned), ``block_<i>``, ``ln_out``,
``head`` (absent where the head is the embedding's transpose).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from multidisttorch_tpu.ops.hyper_connection import SAVED_MAPS, SAVED_Y
from multidisttorch_tpu.ops.moe import SAVED_ROUTING, RoutedExperts
from multidisttorch_tpu.ops.pallas_attention import SAVED_LSE, SAVED_OUT
from multidisttorch_tpu.ops.selective_scan import SAVED_SCAN_OUT, SAVED_SCAN_STATES
from multidisttorch_tpu.utils.profiling import SCOPE_HEAD, SCOPE_MLP

# The names a block gives, besides those the ops give themselves. Defined
# here and not beside the kernels: a line moved in
# ``ops/pallas_attention.py`` or ``ops/moe.py`` changes every kernel's
# serialized module.
SAVED_RESIDUAL = "residual_after_attention"
SAVED_QKV = "attention_operands"
SAVED_MLP_HIDDEN = "mlp_hidden"

# One policy object for every block: jaxprs and jit's caches compare it
# by identity.
_KEEP_ACROSS_REMAT = jax.checkpoint_policies.save_only_these_names(
    SAVED_OUT, SAVED_LSE, SAVED_MAPS, SAVED_Y, SAVED_RESIDUAL, SAVED_ROUTING, SAVED_QKV,
    SAVED_SCAN_OUT, SAVED_SCAN_STATES, SAVED_MLP_HIDDEN,
)


def remat_block(block_cls):
    """``block_cls`` under per-block rematerialization, the one rule of
    every model here that has a ``remat`` field: the backward pass
    recomputes a block from its input, and of what the block made it
    keeps, by name, the values below; a block keeps those its trace
    holds, and where it holds none (a ``TransformerLM`` on the CPU or
    over several chips) nothing but the input is saved. Who gives a name
    and why is said where it is given.

    - ``SAVED_OUT``, ``SAVED_LSE`` (``ops/pallas_attention.py``): an
      attention kernel's output and logsumexp, ``(B, T, H*Dv)`` at the
      compute dtype and float32 ``(B, H, T)``;
    - ``SAVED_RESIDUAL``: the residual stream after the token mixer,
      ``x + proj(o)``, ``(B, T, d)`` at the compute dtype;
    - ``SAVED_ROUTING`` (``ops/moe.py::RoutedExperts``): the router's
      float32 logits ``(N, E)``, with sigmoid scoring its choices and
      their scores ``(N, k)``, the order into expert order ``(N*k,)``
      and the counts;
    - ``SAVED_MAPS``, ``SAVED_Y`` (``ops/hyper_connection.py``): a
      connection's projections and norm factor, and its sublayer's
      output;
    - ``SAVED_QKV``: q, k and v as the attention reads them, flat ``(B,
      T, (H + 2 Hkv) * head_dim)`` at the compute dtype;
    - ``SAVED_SCAN_OUT``, ``SAVED_SCAN_STATES`` (``ops/selective_scan.py``):
      the selective scan's output ``(B, T, E)`` at the compute dtype and
      its float32 state at each chunk's end ``(B, T / chunk, N, E)``;
    - ``SAVED_MLP_HIDDEN``: an MLP's first product, ``(B, T, width)`` at
      the compute dtype, before its activation."""
    return nn.remat(block_cls, policy=_KEEP_ACROSS_REMAT)


def block_class(mod, block_cls):
    """``block_cls``, under :func:`remat_block` where ``mod.remat``."""
    return remat_block(block_cls) if mod.remat else block_cls


def embed_tokens(mod, tokens, *, stddev=None, positions=False):
    """``(x, table)``: ``mod``'s embedding ``tok_embed`` of ``tokens``
    ``(B, T)`` at ``mod.dtype``, plus a learned ``pos_embed`` where
    ``positions``, and the float32 table ``(V, d)`` a tied head reads.
    ``stddev`` ``None`` draws the rows as ``nn.Embed`` does. Raises while
    tracing where T passes ``mod.max_len``: an out-of-range ``nn.Embed``
    gather would clip or fill silently."""
    _, t = tokens.shape
    if t > mod.max_len:
        raise ValueError(f"sequence length {t} exceeds max_len={mod.max_len}")
    drawn = {} if stddev is None else {"embedding_init": nn.initializers.normal(stddev)}
    embed = nn.Embed(
        mod.vocab_size, mod.d_model, dtype=mod.dtype, param_dtype=jnp.float32,
        name="tok_embed", **drawn,
    )
    x = embed(tokens)
    if positions:
        x = x + nn.Embed(
            mod.max_len, mod.d_model, dtype=mod.dtype, param_dtype=jnp.float32,
            name="pos_embed",
        )(jnp.arange(t)[None, :])
    return x, embed.embedding


def norm_and_head(mod, x, head=True, *, norm=nn.RMSNorm, eps=1e-6, table=None, bias=False):
    """The final ``norm`` ``ln_out`` and the float32 vocabulary head:
    ``table``'s transpose where given (under the scope ``head``, as the
    modules' own), else ``nn.Dense`` ``head``, with a bias where
    ``bias``. With ``head`` false the normed state itself, which
    :func:`head_weights` names the weights for."""
    x = norm(epsilon=eps, dtype=mod.dtype, param_dtype=jnp.float32, name="ln_out")(x)
    if not head:
        return x
    if table is not None:
        with jax.named_scope(SCOPE_HEAD):
            return jnp.einsum("btd,vd->btv", x.astype(jnp.float32), table.astype(jnp.float32))
    return nn.Dense(
        mod.vocab_size, use_bias=bias, dtype=jnp.float32, param_dtype=jnp.float32, name="head"
    )(x)


def head_weights(params, tied=False):
    """``(weights, bias, tied)`` of an LM's vocabulary head as its
    parameter tree holds them: ``head/kernel`` ``(d, V)`` and
    ``head/bias`` (``None`` where the head has none), or with ``tied``
    the embedding table ``tok_embed/embedding`` ``(V, d)``, which the
    head reads transposed. Every LM here answers ``head_weights(params)``
    with this and takes ``head=False`` in its call to hand back the
    state after ``ln_out`` where the logits would be: the two halves of
    what ``train/lm.py``'s step asks of a model to run the head and the
    loss as one walk (``ops/head_loss.py``) and never hold the logits.
    A model without the method is asked for its logits, as ever."""
    if tied:
        return params["tok_embed"]["embedding"], None, True
    return params["head"]["kernel"], params["head"].get("bias"), False


def dense(mod, features: int, name: str):
    """``nn.Dense`` ``name`` without a bias, computing at ``mod.dtype``
    on float32 parameters: every projection of the expert and hybrid
    models."""
    return nn.Dense(features, use_bias=False, dtype=mod.dtype, param_dtype=jnp.float32, name=name)


def rms_norm(mod, name: str, dtype=None):
    """``nn.RMSNorm`` ``name`` at ``mod.eps``, computing at ``dtype``
    (``None``: ``mod.dtype``) on a float32 scale."""
    return nn.RMSNorm(epsilon=mod.eps, dtype=dtype or mod.dtype, param_dtype=jnp.float32, name=name)


def gated_mlp(mod, y, width: int):
    """``down(silu(gate(y)) * up(y))``, ``width`` wide, under the scope
    ``mlp``: the dense feed-forward of the expert models."""
    with jax.named_scope(SCOPE_MLP):
        return dense(mod, y.shape[-1], "down")(
            nn.silu(dense(mod, width, "gate")(y)) * dense(mod, width, "up")(y)
        )


def feed_forward(mod, y, **experts):
    """``(out, counts)``: the feed-forward of an expert model's block on
    ``y`` ``(B, T, d)``, :func:`gated_mlp` ``mod.hidden_dim`` wide where
    ``mod.num_experts`` is 0 (``counts`` empty), else the expert layer
    ``moe`` (``ops.moe.RoutedExperts``) over its tokens, from ``mod``'s
    expert fields and the layer's fields in ``experts``."""
    b, t, d = y.shape
    if not mod.num_experts:
        return gated_mlp(mod, y, mod.hidden_dim), jnp.zeros((0,), jnp.int32)
    out, counts = RoutedExperts(
        num_experts=mod.num_experts, experts_held=mod.experts_held, top_k=mod.top_k,
        hidden_dim=mod.hidden_dim, routed_scaling=mod.routed_scaling, dtype=mod.dtype,
        name="moe", **experts,
    )(y.reshape(b * t, d))
    return out.reshape(b, t, d), counts


def rope_angles(positions, theta: float, width: int, scaling=None):
    """``positions * theta**(-2i/width)`` for the pairs ``i`` of a
    ``width``-wide rotary part, or ``positions`` times ``scaling``'s
    blended frequencies (``latent_moe.YarnScaling``): ``(T, width/2)``
    float32."""
    if scaling is None:
        inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    else:
        inv_freq = jnp.asarray(scaling.inv_freq(theta, width), jnp.float32)
    return positions.astype(jnp.float32)[:, None] * inv_freq[None, :]


def rope_halves(x, cos, sin):
    """``x`` ``(B, T, H, width)`` rotated in the halves convention:
    element ``i`` with ``i + width/2`` by the angle ``i`` of every
    position, ``cos``, ``sin`` ``(T, width/2)``; the arithmetic
    float32."""
    half = x.shape[-1] // 2
    x32, c, s = x.astype(jnp.float32), cos[:, None, :], sin[:, None, :]
    lo, hi = x32[..., :half], x32[..., half:]
    return jnp.concatenate([lo * c - hi * s, hi * c + lo * s], axis=-1).astype(x.dtype)


def causal_conv(x, w, bias=None):
    """Depthwise causal convolution of ``x`` ``(B, T, E)`` with ``w``
    ``(taps, E)``, the last tap on the position itself: ``taps``
    shifted multiply-adds, no padded copy through a convolution.
    ``bias`` ``(E,)`` where the convolution has one."""
    t, taps = x.shape[1], w.shape[0]
    ahead = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(ahead[:, j:j + t] * w[j].astype(x.dtype) for j in range(taps))
    return out if bias is None else out + bias.astype(x.dtype)
