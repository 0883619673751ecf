"""Causal LM of latent attention and fine-grained routed experts.

The block today's open expert models are made of, which
``models/transformer.py``'s GPT-2 block cannot express: RMSNorm, no
biases, rotary positions on a part of each head, low-rank query and
key-value paths with a norm in the middle (multi-head latent
attention, MLA), a gated (SwiGLU) MLP, and after ``dense_layers``
leading dense layers an expert layer in every block
(``ops.moe.RoutedExperts``: sigmoid scores, ``top_k`` of
``num_experts``, a shared expert, no token dropped). A trial gets it
exactly as it gets ``TransformerLM``: plain fields, a state from
``create_lm_state``, a step from ``make_lm_train_step``.

Per layer, with ``y = RMSNorm(x)``::

    c_q = RMSNorm(y W_qa)                 q = c_q W_qb  as (H, nope + rope)
    [c_kv | k_r] = y W_kva                c_kv = RMSNorm(c_kv)
    [k_nope | v] per head = c_kv W_kvb    k = [k_nope | k_r for every head]
    q_rope, k_r rotated (pairs (2i, 2i+1) of the rope part, angle pos * inv_freq_i)
    x1 = x + W_o attention(q, k, v)       causal, scores * s / sqrt(nope + rope)
    x2 = x1 + FFN(RMSNorm(x1))

**Rotary scaling.** With ``rope_scaling`` left ``None``, ``inv_freq_i =
theta^(-2i/rope)`` and ``s = 1``. Given a :class:`YarnScaling` (a
configuration's ``rope_scaling`` of type ``yarn``), with ``f_i =
theta^(-2i/rope)`` and ``L`` the original context::

    turns(beta) = rope ln(L / (2 pi beta)) / (2 ln theta)
    low = floor(turns(beta_fast)), high = ceil(turns(beta_slow)), inside [0, rope - 1]
    m_i = 1 - clip((i - low) / (high - low), 0, 1)
    inv_freq_i = (1 - m_i) f_i / factor + m_i f_i
    mscale(m) = 0.1 m ln(factor) + 1
    cos, sin times mscale(mscale) / mscale(mscale_all_dim);   s = mscale(mscale_all_dim)^2

Only the angles and one number change: the kernels take ``(cos, sin)``
and a ``scale`` they multiply the scores by anyway; on the assembled
path q is multiplied by ``s``.

**Residual streams.** With ``hc_mult`` = n > 1 the residual state of a
token is ``X`` in ``R^{n x d}`` and each of the two sublayers ``F``
(latent attention with ``ln_attn`` and ``proj`` inside; the FFN with
``ln_mlp`` inside) sits behind a manifold-constrained hyper-connection
(``ops/hyper_connection.py``, where the layout and what ``nn.remat``
keeps are argued), parameters ``hc_attn`` and ``hc_mlp`` of a block::

    x~ = RMSNorm(vec(X))                               # over all n d entries, with a scale
    H~pre = a_pre (x~ phi_pre) + b_pre      H~post = a_post (x~ phi_post) + b_post
    H~res = a_res mat(x~ phi_res) + b_res              # (n, n)
    Hpre = sigmoid(H~pre)     Hpost = 2 sigmoid(H~post)
    M = exp(clamp(H~res));  20 times: M <- M / (colsum(M) + eps), M <- M / (rowsum(M) + eps)
    u = Hpre X  (d)        y = F(u)  (d)        X' = M X + Hpost^T y   (n x d)

The streams are a tuple of n ``(B, T, d)`` arrays: the embedding n
times, summed before ``ln_out``. With ``hc_mult`` 1 (the default) none
of this is traced: the model, its parameter tree and its lowered step
are the plain residual ones, character for character (test).

q and k are ``nope + rope`` wide and v ``v_head_dim``; the attention is
injected as in ``TransformerLM``. **Which path runs where.** Given no
attention, on one TPU chip, at a length and at widths
``ops.pallas_attention.latent_takes_kernel`` takes (128 + 64 beside
128: ``joyai-llm-flash``), the block never assembles q or k: the
columns of ``W_qb`` and ``W_kvb`` are applied part by part, each part
leaves its matmul as a flat ``(B, T, H * width)`` array, the one rotary
key stays ``(B, T, rope)``, and ``ops.pallas_attention.latent_attention``
takes the five operands (it rotates q's rotary part itself). Everywhere
else (an injected ``attention=``, the CPU, several chips, a T or widths
the rule refuses, the toy widths of tests and examples) q and k are
assembled as above and go to the ``(q, k, v)`` callable: the injected
one, or ``ops/attention.py::causal`` (the blockwise kernel at the
padded width 256 where ``default_takes_kernel`` says so, else dense).
``ops/attention.py::latent_on_parts`` decides while tracing, from the
operands alone; the parameters are the same tree, names and initial
values on both paths.

**One chip's share.** ``experts_held = (first, count)`` names the
experts of every expert layer whose weights live here; the router keeps
its ``num_experts`` outputs and its ``top_k`` (``ops/moe.py``). A
sliced vocabulary is simply a smaller ``vocab_size``.

The model returns ``(logits, {"expert_counts": (expert layers, count)
int32})``: the assignments each expert held received, which
``make_lm_train_step`` hands out with the loss; with residual streams
also ``"hc_marginal_err"``, the largest distance of a row or column
sum of any ``M`` of the step from 1 (20 iterations need not have
converged: a projection that stopped converging shows here).

Names: a trace is split by the scope path of each operation
(``benchmark/scope_reduce.py``), so the pieces of the two low-rank
paths run under ``jax.named_scope``s ``q``, ``k``, ``v`` (the names a
plain block's projections carry), the output projection is ``proj``,
the norms ``ln_attn``, ``ln_mlp``, the dense MLP runs under ``mlp`` and
the expert layer is the module ``moe``. The residual path runs under
two scopes of its own, opened outside all of these: ``hc_maps`` (the
norm over the streams, the projections, sigmoids and Sinkhorn, inside
the modules ``hc_attn`` and ``hc_mlp``) and ``hc_mix`` (``Hpre X``,
``M X + Hpost^T y``, the sum before ``ln_out``, and their backward).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.ops import attention as default_attention
from multidisttorch_tpu.ops import hyper_connection
from multidisttorch_tpu.ops.pallas_attention import latent_attention
from multidisttorch_tpu.utils.profiling import (
    SCOPE_ATTN_CORE,
    SCOPE_K,
    SCOPE_Q,
    SCOPE_V,
)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A configuration's ``rope_scaling`` of type ``yarn`` (YaRN, arXiv
    2309.00071, as the latent-attention family's public modelling code
    applies it). A pair whose wavelength is short against the
    ``original_max_position`` the model was trained at keeps its
    frequency, one that is long has it divided by ``factor``, and those
    between ``beta_fast`` and ``beta_slow`` turns over that length are
    blended; the scores are multiplied by ``mscale(mscale_all_dim)^2``
    and cos and sin by ``mscale(mscale) / mscale(mscale_all_dim)``,
    ``mscale(m) = 0.1 m ln(factor) + 1``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def inv_freq(self, theta: float, width: int) -> np.ndarray:
        """The blended frequency of each pair of a ``width``-wide
        rotary part, float64."""
        pairs = np.arange(width // 2)
        freq = theta ** (-2.0 * pairs / width)
        turns_at = lambda beta: (
            width * math.log(self.original_max_position / (beta * 2 * math.pi))
            / (2 * math.log(theta))
        )
        low = max(math.floor(turns_at(self.beta_fast)), 0)
        high = min(math.ceil(turns_at(self.beta_slow)), width - 1)
        keep = 1.0 - np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
        return (1.0 - keep) * freq / self.factor + keep * freq

    def _mscale(self, m: float) -> float:
        return 0.1 * m * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0

    @property
    def score_scale(self) -> float:
        """What multiplies ``1 / sqrt(nope + rope)``."""
        return self._mscale(self.mscale_all_dim) ** 2

    @property
    def rotation_scale(self) -> float:
        """What multiplies cos and sin."""
        return self._mscale(self.mscale) / self._mscale(self.mscale_all_dim)


def _rotation_scaled(cos, sin, scaling: Optional[YarnScaling]):
    """``cos`` and ``sin`` times ``scaling``'s ``rotation_scale``, where
    that is not 1."""
    if scaling is None or scaling.rotation_scale == 1.0:
        return cos, sin
    return cos * scaling.rotation_scale, sin * scaling.rotation_scale


def rope_interleaved(x, positions, theta: float, scaling: Optional[YarnScaling] = None):
    """Rotate the pairs ``(2i, 2i+1)`` of ``x``'s last axis by
    ``positions * theta**(-2i/width)`` (``scaling``: by its blended
    frequencies); ``x`` is ``(..., T, H, width)``,
    the arithmetic float32. Written with lane rolls rather than a
    ``(width/2, 2)`` reshape, which the TPU would have to relayout."""
    width = x.shape[-1]
    angle = decoder.rope_angles(positions, theta, width, scaling)
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)[:, None, :]
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)[:, None, :]
    cos, sin = _rotation_scaled(cos, sin, scaling)
    x32 = x.astype(jnp.float32)
    even = jnp.arange(width) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x32, -1, axis=-1), jnp.roll(x32, 1, axis=-1))
    return (x32 * cos + partner * sin).astype(x.dtype)


class _DenseByParts(nn.Module):
    """``nn.Dense`` without a bias (its parameter, name and initial
    values) for a kernel whose columns are ``heads`` groups of
    ``sum(widths)``: part ``i`` of every head, ``widths[i]`` columns of
    each group, applied as a product of its own, so that it leaves its
    matmul as a flat ``(..., heads * widths[i])`` array and no array of
    whole groups is made to cut it from."""

    heads: int
    widths: tuple[int, ...]
    dtype: Any

    @nn.compact
    def __call__(self, x, part: int):
        group, at, width = sum(self.widths), sum(self.widths[:part]), self.widths[part]
        kernel = self.param(
            "kernel", nn.linear.default_kernel_init, (x.shape[-1], self.heads * group),
            jnp.float32,
        )
        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        columns = kernel.reshape(-1, self.heads, group)[..., at:at + width]
        return x @ columns.reshape(-1, self.heads * width)


class LatentMoEBlock(nn.Module):
    """One pre-norm block: latent attention, then a dense SwiGLU MLP
    (``num_experts`` 0) or an expert layer. Returns ``(x, counts)``,
    ``counts`` ``(count,)`` int32 and empty for a dense block. With
    ``hc_mult`` > 1 it takes and returns the ``hc_mult`` streams (a
    tuple of ``(B, T, d)`` arrays), each sublayer behind its
    hyper-connection, and returns ``(streams, counts, marginal_err)``.

    The attention is one of two paths, chosen while tracing (the
    module's docstring says where each runs): :meth:`_kernel_on_parts`,
    the kernel on q's and k's parts as ``q_b`` and ``kv_b`` make them,
    or :meth:`_assembled`, q and k at ``nope + rope`` a head for a
    ``(q, k, v)`` callable. Both read the same parameters."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    rope_theta: float
    hidden_dim: int  # the dense MLP's width, or one expert's
    # (q, k, v) -> out; q, k (B, T, H, nope + rope), v, out (B, T, H, v). None: the default
    attention: Optional[Callable] = None
    num_experts: int = 0
    experts_held: tuple[int, int] = (0, 0)
    top_k: int = 0
    shared_experts: int = 0
    routed_scaling: float = 1.0
    eps: float = 1e-6
    dtype: Any = jnp.float32
    rope_scaling: Optional[YarnScaling] = None
    hc_mult: int = 1  # residual streams; 1: the plain residual add
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple[float, float] = (-30.0, 30.0)

    @nn.compact
    def __call__(self, x):
        if self.hc_mult == 1:
            # kept across remat: proj's backward reads its input and weights,
            # never its output, so the recomputed block does not multiply by
            # proj again (4,096 wide in joyai-llm-flash; PERF.md section 6,
            # PR 34). With streams SAVED_Y does that around the connection.
            x = checkpoint_name(x + self._attention(x), decoder.SAVED_RESIDUAL)
            y, counts = self._ffn(x)
            return x + y, counts
        connection = lambda name: hyper_connection.HyperConnection(
            sinkhorn_iters=self.hc_sinkhorn_iters, eps=self.hc_eps, clamp=self.hc_clamp,
            norm_eps=self.eps, name=name,
        )
        streams = x
        around_attn = connection("hc_attn")(streams)
        y = self._attention(hyper_connection.read(around_attn, streams))
        streams = hyper_connection.write(around_attn, streams, y)
        around_ffn = connection("hc_mlp")(streams)
        y, counts = self._ffn(hyper_connection.read(around_ffn, streams))
        streams = hyper_connection.write(around_ffn, streams, y)
        return streams, counts, jnp.maximum(around_attn.marginal_err, around_ffn.marginal_err)

    @nn.nowrap
    def _attention(self, x):
        """Latent attention of ``ln_attn(x)``, through ``proj``."""
        dense, norm = partial(decoder.dense, self), partial(decoder.rms_norm, self)
        b, t, d = x.shape
        h, nope, rope, dv = self.num_heads, self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim
        positions = jnp.arange(t)

        y = norm("ln_attn")(x)
        by_parts = self.attention is None and default_attention.latent_on_parts(
            x, h, nope, rope, dv
        )
        with jax.named_scope(SCOPE_Q):
            c_q = norm("q_norm")(dense(self.q_lora_rank, "q_a")(y))
        with jax.named_scope(SCOPE_K):
            latent = dense(self.kv_lora_rank + rope, "kv_a")(y)
            c_kv = norm("kv_norm")(latent[..., : self.kv_lora_rank])
            k_rope = rope_interleaved(
                latent[..., None, self.kv_lora_rank:], positions, self.rope_theta,
                self.rope_scaling,
            )  # (B, T, 1, rope): one rope key for all heads
        attend = self._kernel_on_parts if by_parts else self._assembled
        attn = attend(c_q, c_kv, k_rope, positions)
        return dense(d, "proj")(attn.reshape(b, t, h * dv))

    @nn.nowrap
    def _ffn(self, x):
        """``(y, counts)``: the dense MLP or the expert layer of
        ``ln_mlp(x)``."""
        y = decoder.rms_norm(self, "ln_mlp")(x)
        shared = self.shared_experts * self.hidden_dim
        return decoder.feed_forward(self, y, shared_hidden_dim=shared)

    @nn.nowrap
    def _assembled(self, c_q, c_kv, k_rope, positions):
        """q and k assembled at ``nope + rope`` a head, the rotary key
        copied to every head, for an attention of the ``(q, k, v)``
        kind."""
        b, t, _ = c_q.shape
        h, nope, rope, dv = self.num_heads, self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim
        with jax.named_scope(SCOPE_Q):
            q = decoder.dense(self, h * (nope + rope), "q_b")(c_q).reshape(b, t, h, nope + rope)
            q = jnp.concatenate(
                [
                    q[..., :nope],
                    rope_interleaved(q[..., nope:], positions, self.rope_theta, self.rope_scaling),
                ],
                axis=-1,
            )
            if self.rope_scaling is not None and self.rope_scaling.score_scale != 1.0:
                q = q * self.rope_scaling.score_scale  # the callable divides by sqrt(width)
        with jax.named_scope(SCOPE_V):
            kv = decoder.dense(self, h * (nope + dv), "kv_b")(c_kv).reshape(b, t, h, nope + dv)
            v = kv[..., nope:]
        with jax.named_scope(SCOPE_K):
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, h, rope))], axis=-1
            )
        with jax.named_scope(SCOPE_ATTN_CORE):
            return (self.attention or default_attention.causal)(q, k, v)

    @nn.nowrap
    def _kernel_on_parts(self, c_q, c_kv, k_rope, positions):
        """The same attention with nothing assembled: ``q_b``'s and
        ``kv_b``'s columns applied part by part, the one key as it is,
        and ``ops.pallas_attention.latent_attention`` on the five
        operands and the angles of q's rotation, which it makes on the
        flat ``(B, T, H * rope)`` array a block at a time."""
        b, t, _ = c_q.shape
        h, nope, rope, dv = self.num_heads, self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim
        q_b = _DenseByParts(h, (nope, rope), self.dtype, name="q_b")
        kv_b = _DenseByParts(h, (nope, dv), self.dtype, name="kv_b")
        heads = lambda x: x.reshape(b, t, h, -1)  # free: the kernels read the flat array
        with jax.named_scope(SCOPE_Q):
            q_nope, q_rope = q_b(c_q, 0), q_b(c_q, 1)
            angle = decoder.rope_angles(positions, self.rope_theta, rope, self.rope_scaling)
            # of q_rope: the kernels make it
            rotation = _rotation_scaled(jnp.cos(angle), jnp.sin(angle), self.rope_scaling)
        with jax.named_scope(SCOPE_K):
            k_nope = kv_b(c_kv, 0)
        with jax.named_scope(SCOPE_V):
            v = kv_b(c_kv, 1)
        with jax.named_scope(SCOPE_ATTN_CORE):
            return latent_attention(
                heads(q_nope), heads(q_rope), heads(k_nope), k_rope[:, :, 0], heads(v),
                q_rotation=rotation, causal=True,
                scale=None if self.rope_scaling is None
                else self.rope_scaling.score_scale / math.sqrt(nope + rope),
            )


class LatentMoELM(nn.Module):
    """Decoder-only LM: ``(B, T) int32 -> ((B, T, vocab) float32 logits,
    {"expert_counts": (num_layers - dense_layers, count) int32})``.

    ``experts_held`` ``None`` holds every expert. The defaults are a
    toy for tests and examples; a configuration's file gives the
    published sizes (``benchmark/configs/``)."""

    vocab_size: int
    d_model: int = 64
    num_heads: int = 4
    num_layers: int = 3
    dense_layers: int = 1
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    dense_hidden_dim: int = 128
    num_experts: int = 8
    experts_held: Optional[tuple[int, int]] = None
    top_k: int = 2
    expert_hidden_dim: int = 32
    shared_experts: int = 1
    routed_scaling: float = 1.0
    eps: float = 1e-6
    max_len: int = 256
    attention: Optional[Callable] = None
    dtype: Any = jnp.float32
    remat: bool = False  # per-block checkpointing (decoder.remat_block)
    rope_scaling: Optional[YarnScaling] = None
    # residual streams mixed by hyper-connections (ops/hyper_connection.py); 1: plain residuals
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple[float, float] = (-30.0, 30.0)

    @nn.compact
    def __call__(self, tokens, head=True):
        if not 0 <= self.dense_layers < self.num_layers:
            raise ValueError(
                f"dense_layers={self.dense_layers} leaves no expert layer of {self.num_layers}"
            )
        x, _ = decoder.embed_tokens(self, tokens)
        block_cls = decoder.block_class(self, LatentMoEBlock)
        shared = dict(
            num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank, qk_nope_dim=self.qk_nope_dim,
            qk_rope_dim=self.qk_rope_dim, v_head_dim=self.v_head_dim,
            rope_theta=self.rope_theta, attention=self.attention,
            eps=self.eps, dtype=self.dtype, rope_scaling=self.rope_scaling,
            hc_mult=self.hc_mult, hc_sinkhorn_iters=self.hc_sinkhorn_iters,
            hc_eps=self.hc_eps, hc_clamp=self.hc_clamp,
        )
        if self.hc_mult != 1:
            # the embedding copied into the streams (the hyper-connections
            # paper's rule for the first layer): the same array n times
            x = (x,) * self.hc_mult
        routed = dict(
            hidden_dim=self.expert_hidden_dim, num_experts=self.num_experts,
            experts_held=self.experts_held or (0, self.num_experts),
            top_k=self.top_k, shared_experts=self.shared_experts,
            routed_scaling=self.routed_scaling,
        )
        counts, errs = [], []
        for i in range(self.num_layers):
            ffn = dict(hidden_dim=self.dense_hidden_dim) if i < self.dense_layers else routed
            x, c, *err = block_cls(**shared, **ffn, name=f"block_{i}")(x)
            counts.append(c)
            errs += err
        if self.hc_mult != 1:
            x = hyper_connection.merge(x)
        logits = decoder.norm_and_head(self, x, head, eps=self.eps)
        counters = {"expert_counts": jnp.stack(counts[self.dense_layers:])}
        if errs:
            counters["hc_marginal_err"] = jnp.max(jnp.stack(errs))
        return logits, counters

    def head_weights(self, params):
        return decoder.head_weights(params)
