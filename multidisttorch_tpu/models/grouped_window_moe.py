"""Causal LM of grouped-head attention in a layer pattern and routed
experts whose router reads the block's input.

The block of the window/full hybrid expert models, which neither
``models/transformer.py`` nor ``models/latent_moe.py`` expresses: fewer
KV heads than query heads, a pattern by layer index of which layers see
the whole past and which a sliding window, and of which layers carry
rotary positions and which none at all, and in every layer an expert
layer whose router scores what attention reads, before attention
(``ops.moe.RoutedExperts`` with ``scoring="softmax"``, ReGLU experts,
no shared expert). A trial gets it as it gets ``LatentMoELM``: plain
fields, a state from ``create_lm_state``, a step from
``make_lm_train_step``.

Per layer, with ``H`` query heads over ``Hkv`` KV heads of width
``head_dim``::

    y = RMSNorm(x)
    r = y W_r  (float32);  chosen = top_k(r);  w = softmax(r[chosen])
    q = y W_q as (H, head_dim);  k = y W_k, v = y W_v as (Hkv, head_dim)   # head h reads KV head h // (H / Hkv)
    rope_layout[layer] = 1: q and k rotated over the whole head, element i with i + head_dim/2,
        angle pos * theta**(-2i/head_dim);  0: not rotated, no positions at all
    s_ij = q_i . k_j / sqrt(head_dim), kept where j <= i, and where window_layout[layer] = 1 also i - j < window
    x1 = x + softmax(s) v W_o
    z = RMSNorm(x1)
    x2 = x1 + sum over e in chosen, held here:  w_e W_down,e (relu(W_gate,e z) * (W_up,e z))

then the final RMSNorm and an untied head; no biases.

**Which attention runs where.** Where ``ops/attention.py::grouped_kernel``
says so (one TPU chip, heads 128 wide, or 64 wide in a layer without
positions: the kernels at 64 rotate nothing) the core is
``ops.pallas_attention.grouped_attention``, whose kernels read q, k and
v flat as the projections leave them and rotate q as they load it; k is
rotated here (``Hkv`` heads). Everywhere else (the CPU, several chips,
toy widths) q is rotated here too and the core is
``blocked_window_attention``, XLA's masked softmax in query blocks. An
injected ``attention`` has ``grouped_attention``'s signature.

**One chip's share.** ``experts_held = (first, count)`` as in
``LatentMoELM``; a sliced vocabulary is a smaller ``vocab_size``. A
share that is trained alone, with no shared expert to answer the tokens
whose experts are all elsewhere, teaches its routers that only the
experts held answer, and within tens of steps every token chooses them;
``absent_share_grad=False`` (``RoutedExperts``' field) keeps that from
the backward pass and changes nothing forward.

**The embedding's deviation.** ``embed_stddev`` ``None`` draws the rows
as ``nn.Embed`` does (``1 / sqrt(d_model)``). At random weights every
sublayer's output has entries of unit size (lecun-normal matrices after
a norm), so rows that small drown in the first of them, and from the
third layer on every token reads alike to the routers: a caller that
wants seeded weights to route as a trained model's do (tokens told
apart, an even load) gives a deviation of its own, as a benchmark
configuration's file does.

The model returns ``(logits, {"expert_counts": (layers, count)
int32})``.

Names: ``ln_attn``, ``q``, ``k``, ``v``, ``proj``, ``ln_mlp`` and
``moe`` are flax modules (the rotations run under the scopes ``q`` and
``k``); the core runs under ``attn_core`` and inside it under
``attn_full`` or ``attn_window``; the router's operations are
``moe/router`` like the rest of the expert layer's, wherever XLA
schedules them.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.ops import attention as default_attention
from multidisttorch_tpu.ops.moe import RoutedExperts
from multidisttorch_tpu.ops.pallas_attention import blocked_window_attention
from multidisttorch_tpu.utils.profiling import (
    SCOPE_ATTN_CORE,
    SCOPE_ATTN_FULL,
    SCOPE_ATTN_WINDOW,
    SCOPE_K,
    SCOPE_Q,
    SCOPE_V,
)


class GroupedWindowMoEBlock(nn.Module):
    """One pre-norm block: grouped-head attention (``window`` ``None``:
    the whole past; ``rotary`` ``False``: no positions), then the
    expert layer, routed from the block's normed input. Returns ``(x,
    counts)``. Under ``decoder.remat_block`` it keeps, beside the
    core's output and logsumexp and the router's results, the stream
    after attention and q, k and v as the core reads them: the
    recomputed block holds the two norms, the experts' first halves and
    the exchange's gathers."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]
    rotary: bool
    rope_theta: float
    hidden_dim: int  # one expert's width
    num_experts: int
    experts_held: tuple[int, int]
    top_k: int
    absent_share_grad: bool = True  # as RoutedExperts'
    # (q, k, v, *, window, q_rotation) -> out, as ops.pallas_attention.grouped_attention. None: the default
    attention: Optional[Callable] = None
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dense, norm = partial(decoder.dense, self), partial(decoder.rms_norm, self)
        y = norm("ln_attn")(x)
        # q, k and v stay flat, as the projections write them and the kernels
        # read them, and are heads only where something reads heads: the TPU
        # compiler lays a kept (B, T, H, 128) array out with T innermost and
        # copies it back for the kernels in both passes (PERF.md section 6,
        # PR 36).
        q, k, v = dense(h * hd, "q")(y), dense(hkv * hd, "k")(y), dense(hkv * hd, "v")(y)
        heads = lambda a: a.reshape(b, t, -1, hd)
        rotation = None
        if self.rotary:
            angle = decoder.rope_angles(jnp.arange(t), self.rope_theta, hd)
            rotation = jnp.cos(angle), jnp.sin(angle)
            with jax.named_scope(SCOPE_K):
                k = decoder.rope_halves(heads(k), *rotation).reshape(k.shape)
        attend = self.attention or default_attention.grouped_kernel(
            x, h, hkv, hd, rotates_q=self.rotary
        )
        if attend is None and self.rotary:  # the plain path takes q as it is multiplied
            with jax.named_scope(SCOPE_Q):
                q = decoder.rope_halves(heads(q), *rotation).reshape(q.shape)
        # What the attention reads, kept across remat by name: the call and
        # so the kernels' backward take the named copies, and the recomputed
        # block makes none of the three products and no rotation again (9.2
        # KB a token and layer in smallthinker-21b-a3b: 28 + 4 + 4 heads of
        # 128 in bf16; PERF.md section 6, PR 36).
        def kept(a, scope):  # jax rounds a kept float where it is named: the projection's work
            with jax.named_scope(scope):
                return checkpoint_name(a, decoder.SAVED_QKV)

        q, k, v = heads(kept(q, SCOPE_Q)), heads(kept(k, SCOPE_K)), heads(kept(v, SCOPE_V))
        kind = SCOPE_ATTN_FULL if self.window is None else SCOPE_ATTN_WINDOW
        with jax.named_scope(SCOPE_ATTN_CORE), jax.named_scope(kind):
            if attend is None:
                attn = blocked_window_attention(q, k, v, window=self.window)
            else:
                attn = attend(q, k, v, window=self.window, q_rotation=rotation)
        # kept across remat, as LatentMoEBlock keeps it: proj (3,584 wide in
        # smallthinker-21b-a3b) is not multiplied again
        x = checkpoint_name(
            x + dense(d, "proj")(attn.reshape(b, t, h * hd)), decoder.SAVED_RESIDUAL
        )

        z = norm("ln_mlp")(x)
        out, counts = RoutedExperts(
            num_experts=self.num_experts,
            experts_held=self.experts_held,
            top_k=self.top_k,
            hidden_dim=self.hidden_dim,
            dtype=self.dtype,
            scoring="softmax",
            activation="relu",
            absent_share_grad=self.absent_share_grad,
            name="moe",
        )(z.reshape(b * t, d), router_input=y.reshape(b * t, d))
        return x + out.reshape(b, t, d), counts


class GroupedWindowMoELM(nn.Module):
    """Decoder-only LM: ``(B, T) int32 -> ((B, T, vocab) float32 logits,
    {"expert_counts": (num_layers, count) int32})``.

    ``window_layout`` and ``rope_layout`` say by layer index (1 or 0)
    whether the layer's attention is held to ``window`` keys and whether
    its q and k are rotated. ``experts_held`` ``None`` holds every
    expert. The defaults are a toy for tests and examples; a
    configuration's file gives the published sizes
    (``benchmark/configs/``)."""

    vocab_size: int
    d_model: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    num_layers: int = 4
    window_layout: tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: tuple[int, ...] = (0, 1, 1, 1)
    window: int = 8
    rope_theta: float = 10000.0
    num_experts: int = 8
    experts_held: Optional[tuple[int, int]] = None
    top_k: int = 2
    expert_hidden_dim: int = 32
    eps: float = 1e-6
    max_len: int = 256
    embed_stddev: Optional[float] = None  # None: nn.Embed's own 1 / sqrt(d_model)
    absent_share_grad: bool = True  # as RoutedExperts'; False for a chip's share trained alone
    attention: Optional[Callable] = None
    dtype: Any = jnp.float32
    remat: bool = False  # per-block checkpointing (decoder.remat_block)

    @nn.compact
    def __call__(self, tokens, head=True):
        if not len(self.window_layout) == len(self.rope_layout) == self.num_layers:
            raise ValueError(
                f"window_layout and rope_layout name {len(self.window_layout)} and "
                f"{len(self.rope_layout)} layers of {self.num_layers}"
            )
        x, _ = decoder.embed_tokens(self, tokens, stddev=self.embed_stddev)
        block_cls = decoder.block_class(self, GroupedWindowMoEBlock)
        counts = []
        for i, (windowed, rotary) in enumerate(zip(self.window_layout, self.rope_layout)):
            x, c = block_cls(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                window=self.window if windowed else None, rotary=bool(rotary),
                rope_theta=self.rope_theta, hidden_dim=self.expert_hidden_dim,
                num_experts=self.num_experts,
                experts_held=self.experts_held or (0, self.num_experts),
                top_k=self.top_k, absent_share_grad=self.absent_share_grad,
                attention=self.attention, eps=self.eps, dtype=self.dtype,
                name=f"block_{i}",
            )(x)
            counts.append(c)
        logits = decoder.norm_and_head(self, x, head, eps=self.eps)
        return logits, {"expert_counts": jnp.stack(counts)}

    def head_weights(self, params):
        return decoder.head_weights(params)
