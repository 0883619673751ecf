"""Causal LM whose token mixer is a gated short convolution in most
layers and grouped-head attention in the rest, over dense and routed
feed-forwards.

The block of the convolution/attention expert models, which none of
``models/transformer.py``, ``models/latent_moe.py``,
``models/grouped_window_moe.py`` and ``models/ssm_hybrid.py`` expresses:
a layer's operator is one of two by a layout field, and neither reads a
state: a doubly gated depthwise convolution of a few taps, or full
causal attention over fewer KV heads than query heads whose q and k are
normed head by head before they are rotated. The leading layers' feed-
forward is a dense SwiGLU MLP, the others' an expert layer
(``ops.moe.RoutedExperts``: sigmoid scores, a selection bias, SwiGLU
experts, no shared expert); the head is the embedding's transpose. A
trial gets it as it gets the other LMs: plain fields, a state from
``create_lm_state``, a step from ``make_lm_train_step``.

Per layer ``i``, with ``d`` the model's width::

    y = RMSNorm(x)
    layer_types[i] = "conv":
        B, C, u = split(y W_in)                          # d -> 3d, no bias
        z_t = sum_j w_j * (B * u)_{t - (taps - 1) + j}   # causal, depthwise, no bias, no activation
        x1 = x + (C * z) W_out
    layer_types[i] = "full_attention", H query heads over Hkv KV heads of head_dim:
        q = y W_q as (H, head_dim);  k = y W_k, v = y W_v as (Hkv, head_dim)
        q, k = RMSNorm over each head's head_dim elements (one scale for q, one for k), then rotated
            over the whole head, element i with i + head_dim/2, angle pos * theta**(-2i/head_dim)
        s_ij = q_i . k_j / sqrt(head_dim), kept where j <= i;  head h reads KV head h // (H / Hkv)
        x1 = x + softmax(s) v W_o
    z = RMSNorm(x1)
    i < num_dense_layers:  x2 = x1 + W_down(silu(W_gate z) * (W_up z))
    else:  s = sigmoid(z W_r) (float32);  chosen = top_k(s + b)
           w = s[chosen] / sum(s[chosen]) * routed_scaling
           x2 = x1 + sum over e in chosen, held here:  w_e W_down,e (silu(W_gate,e z) * (W_up,e z))

then the final RMSNorm and the head, the embedding's transpose under
``tie_embeddings`` and a matrix of its own otherwise; no biases.

**Which attention runs where.** The block norms and rotates q and k
itself (the norm sits between the projection and the rotation, so no
kernel's ``q_rotation`` can carry the latter) and hands the core plain
operands: ``ops.pallas_attention.grouped_attention`` where
``ops/attention.py::grouped_kernel`` says so (one TPU chip, heads 128
wide or 64 wide over an even number of KV heads), else
``blocked_window_attention``, XLA's masked softmax in query blocks. An
injected ``attention`` has ``grouped_attention``'s signature.

**One chip's share**, the embedding's deviation and
``absent_share_grad`` are ``GroupedWindowMoELM``'s, which says why a
share trained alone wants the last ``False`` and what seeded weights
ask of the second.

The model returns ``(logits, {"expert_counts": (expert layers, count)
int32})``.

Names: ``ln_attn`` (the operator's norm, a convolution's too, so that a
reader of the other models' names finds it), ``in_proj``, ``out_proj``,
``q``, ``k``, ``v``, ``q_norm``, ``k_norm``, ``proj``, ``ln_mlp``,
``gate``, ``up``, ``down`` and ``moe`` are flax modules and ``conv_w``
the taps. ``W_in`` and ``W_out`` run under the scope ``conv_proj``, the
gates and the taps under ``conv_mix``, the head norms and the rotation
under ``qk_norm``, the core under ``attn_core`` and inside it
``attn_full``, a dense MLP under ``mlp``, a tied head under ``head``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.ops import attention as default_attention
from multidisttorch_tpu.ops.pallas_attention import blocked_window_attention
from multidisttorch_tpu.utils.profiling import (
    SCOPE_ATTN_CORE,
    SCOPE_ATTN_FULL,
    SCOPE_CONV_MIX,
    SCOPE_CONV_PROJ,
    SCOPE_K,
    SCOPE_Q,
    SCOPE_QK_NORM,
    SCOPE_V,
)

LAYER_TYPES = ("conv", "full_attention")


def gated_short_conv(bcu, taps):
    """``C * conv(B * u)`` of ``bcu`` ``(B, T, 3d)``, ``W_in``'s output
    in the order B, C, u, with ``taps`` ``(taps, d)`` of a causal
    depthwise convolution (the last tap on the position itself): the
    two gates are the operator's only nonlinearity."""
    b_gate, c_gate, u = jnp.split(bcu, 3, axis=-1)
    return c_gate * decoder.causal_conv(b_gate * u, taps)


class ShortConvMoEBlock(nn.Module):
    """One pre-norm block: the operator ``kind`` names, then a dense
    MLP (``num_experts`` 0) or the expert layer, ``hidden_dim`` wide
    either way. Returns ``(x, counts)``, ``counts`` ``(count,)`` int32
    and empty for a dense block. Under ``decoder.remat_block`` it
    keeps the router's results, and an attention layer also the stream
    after attention, q, k and v as the projections leave them and the
    core's output and logsumexp: the recomputed block holds the norms
    (the head norms and the rotation too), the feed-forward's first
    half and the exchange's gathers, and no product of the
    attention's. A conv layer keeps nothing of its operator: all of it
    is made again, ``W_out`` too (the stream after it is 128 MiB a
    layer at 4 x 8,192 tokens, and with the four kept the cell's step
    plans 14.83 GiB of the chip's 15.75 against 14.45: PERF.md
    section 6, PR 39)."""

    kind: str
    hidden_dim: int
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 10000.0
    conv_taps: int = 3
    num_experts: int = 0
    experts_held: tuple[int, int] = (0, 0)
    top_k: int = 0
    routed_scaling: float = 1.0
    absent_share_grad: bool = True  # as RoutedExperts'
    # (q, k, v, *, window, q_rotation) -> out, as ops.pallas_attention.grouped_attention. None: the default
    attention: Optional[Callable] = None
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        if self.kind not in LAYER_TYPES:
            raise ValueError(f"ShortConvMoEBlock: kind {self.kind!r} is none of {LAYER_TYPES}")
        y = decoder.rms_norm(self, "ln_attn")(x)
        if self.kind == "conv":
            x = x + self._conv(y)
        else:
            x = checkpoint_name(x + self._attention(y), decoder.SAVED_RESIDUAL)
        out, counts = decoder.feed_forward(
            self, decoder.rms_norm(self, "ln_mlp")(x), absent_share_grad=self.absent_share_grad
        )
        return x + out, counts

    @nn.nowrap
    def _conv(self, y):
        d = y.shape[-1]
        with jax.named_scope(SCOPE_CONV_PROJ):
            bcu = decoder.dense(self, 3 * d, "in_proj")(y)
        with jax.named_scope(SCOPE_CONV_MIX):
            taps = self.param(
                "conv_w", nn.initializers.lecun_normal(), (self.conv_taps, d), jnp.float32
            )
            mixed = gated_short_conv(bcu, taps)
        with jax.named_scope(SCOPE_CONV_PROJ):
            return decoder.dense(self, d, "out_proj")(mixed)

    @nn.nowrap
    def _attention(self, y):
        b, t, d = y.shape
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        # q, k and v stay flat but where something reads heads, as
        # GroupedWindowMoEBlock keeps them and for its reason. Kept across
        # remat as the projections leave them: the head norm's backward reads
        # its input, so keeping q and k normed and rotated would have the
        # recomputed block multiply for them again; it norms and rotates again.
        def projected(n, name, scope):
            with jax.named_scope(scope):
                return checkpoint_name(decoder.dense(self, n * hd, name)(y), decoder.SAVED_QKV)

        q, k = projected(h, "q", SCOPE_Q), projected(hkv, "k", SCOPE_K)
        v = projected(hkv, "v", SCOPE_V)
        heads = lambda a: a.reshape(b, t, -1, hd)
        with jax.named_scope(SCOPE_QK_NORM):
            angle = decoder.rope_angles(jnp.arange(t), self.rope_theta, hd)
            cos, sin = jnp.cos(angle), jnp.sin(angle)

            def normed_rotated(a, name):  # float32 from the norm's statistics to the rotation's end
                a = decoder.rms_norm(self, name, jnp.float32)(heads(a))
                a = decoder.rope_halves(a, cos, sin)
                return a.astype(self.dtype).reshape(b, t, -1)

            q, k = normed_rotated(q, "q_norm"), normed_rotated(k, "k_norm")
        attend = self.attention or default_attention.grouped_kernel(y, h, hkv, hd)
        with jax.named_scope(SCOPE_ATTN_CORE), jax.named_scope(SCOPE_ATTN_FULL):
            if attend is None:
                attn = blocked_window_attention(heads(q), heads(k), heads(v), window=None)
            else:
                attn = attend(heads(q), heads(k), heads(v), window=None, q_rotation=None)
        return decoder.dense(self, d, "proj")(attn.reshape(b, t, h * hd))


class ShortConvMoELM(nn.Module):
    """Decoder-only LM: ``(B, T) int32 -> ((B, T, vocab) float32 logits,
    {"expert_counts": (len(layer_types) - num_dense_layers, count)
    int32})``.

    ``layer_types`` names every layer's operator, ``"conv"`` or
    ``"full_attention"`` (a chip's share of the stack is whatever
    layers it names); the first ``num_dense_layers`` of them carry a
    dense MLP ``dense_hidden_dim`` wide, the others ``num_experts``
    experts ``hidden_dim`` wide each, of which ``experts_held``
    ``(first, count)`` live here (``None``: all of them). The defaults
    are a toy for tests and examples; a configuration's file gives the
    published sizes (``benchmark/configs/``)."""

    vocab_size: int
    d_model: int = 64
    layer_types: tuple[str, ...] = ("conv", "conv", "full_attention", "conv")
    num_dense_layers: int = 1
    dense_hidden_dim: int = 128
    conv_taps: int = 3
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 10000.0
    hidden_dim: int = 32  # one expert's width
    num_experts: int = 8
    experts_held: Optional[tuple[int, int]] = None
    top_k: int = 2
    routed_scaling: float = 1.0
    absent_share_grad: bool = True  # as RoutedExperts'; False for a chip's share trained alone
    eps: float = 1e-5
    max_len: int = 256
    tie_embeddings: bool = True
    embed_stddev: Optional[float] = None  # None: nn.Embed's own 1 / sqrt(d_model)
    attention: Optional[Callable] = None
    dtype: Any = jnp.float32
    remat: bool = False  # per-block checkpointing (decoder.remat_block)

    @nn.compact
    def __call__(self, tokens, head=True):
        if not 0 <= self.num_dense_layers < len(self.layer_types):
            raise ValueError(
                f"num_dense_layers={self.num_dense_layers} leaves no expert layer of "
                f"{len(self.layer_types)}"
            )
        x, table = decoder.embed_tokens(self, tokens, stddev=self.embed_stddev)
        block_cls = decoder.block_class(self, ShortConvMoEBlock)
        shared = dict(
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            rope_theta=self.rope_theta, conv_taps=self.conv_taps, attention=self.attention,
            eps=self.eps, dtype=self.dtype,
        )
        routed = dict(
            hidden_dim=self.hidden_dim, num_experts=self.num_experts,
            experts_held=self.experts_held or (0, self.num_experts), top_k=self.top_k,
            routed_scaling=self.routed_scaling, absent_share_grad=self.absent_share_grad,
        )
        counts = []
        for i, kind in enumerate(self.layer_types):
            ffn = dict(hidden_dim=self.dense_hidden_dim) if i < self.num_dense_layers else routed
            x, c = block_cls(kind=kind, **shared, **ffn, name=f"block_{i}")(x)
            counts.append(c)
        logits = decoder.norm_and_head(
            self, x, head, eps=self.eps, table=table if self.tie_embeddings else None
        )
        return logits, {"expert_counts": jnp.stack(counts[self.num_dense_layers:])}

    def head_weights(self, params):
        return decoder.head_weights(params, tied=self.tie_embeddings)
