"""Causal LM of Mamba layers, window and full attention, gated memory
units and cross-attention: the decoder-hybrid-decoder (SambaY, arXiv
2507.06607; Phi-4-mini-flash-reasoning's ``config.json``).

The first half of the stack is a hybrid decoder (Mamba and window
attention alternating); its last Mamba layer hands on its scan's output
as the **memory**, and one full-attention layer after it hands on its
**k and v**. The second half reads them and makes neither: a gated
memory unit gates the memory with a projection of its own input, and a
cross-attention layer attends with a q of its own over the one shared
k and v. No model here read another block's intermediate result before
this one, carried a recurrent state, or tied its head to its embedding.

Every layer ``i`` is ``x += Mixer_i(LN(x)); x += W_down(silu(W_gate h) *
(W_up h)), h = LN'(x)``, ``nn.LayerNorm`` with scale and bias. The
mixer by the layer's kind (``layer_kinds``; :func:`default_layer_kinds`
is the published rule), with ``E = expand * d``, a state of ``N =
d_state`` a channel, ``R = dt_rank``::

    mamba, mamba_memory:
        x, z = split(W_in u)                                   # d -> 2E
        x = silu(conv(x))            # causal, depthwise, d_conv taps and a bias: d_conv shifted multiply-adds
        dt, B, C = split(W_x x)                                # E -> R + 2N
        D_t = softplus(W_dt dt + b_dt)                         # R -> E
        h_t = exp(D_t A) * h_{t-1} + (D_t x_t) B_t^T,  A = -exp(A_log);   y_t = h_t C_t + D x_t
        out = W_out(y * silu(z))                               # E -> d;  mamba_memory hands on y, the memory m
    window, full_kv:
        q, k, v = split(W_qkv u)     # d -> (H + 2 Hkv) head_dim; query head h reads KV head h // (H / Hkv)
        s_ij = q_i . k_j / sqrt(head_dim), kept where j <= i, and in a window layer where i - j < window
        out = W_o softmax(s) v       # no positions at all;  full_kv hands on k and v
    gmu:    out = W_out(m * silu(W_in u))                      # d -> E -> d, m at the same position
    cross:  q = W_q u;  out = W_o softmax(q k^T / sqrt(head_dim)) v    # the shared k, v; causal, no window

then the final LayerNorm and the head: the embedding's transpose where
``tie_embeddings``, else a matrix of its own; no bias anywhere but the
convolution's, ``dt``'s and the norms'.

**What runs where**, decided while tracing from the operands alone: on
one TPU chip the scan is ``ops.selective_scan``'s kernel pair
(``scan_takes_kernel``, which the scan asks itself) and the three kinds
of attention ``ops.pallas_attention.grouped_attention`` at head width 64
(``ops/attention.py::grouped_kernel``: two KV heads and their four query
heads a grid step, the cross layer's call given the full layer's k and
v); everywhere else the ``jax.lax`` scan and
``blocked_window_attention``.

**Starts.** ``A_log`` starts at ``log(1..N)`` a channel and ``dt``'s
bias so that ``softplus`` gives steps log-uniform in ``[DT_MIN,
DT_MAX]`` = [0.001, 0.1] (Mamba's own: with lecun-normal weights alone the decay is 0
or 1 and the scan tests nothing); ``D`` at 1.

Under ``remat`` (``decoder.remat_block``) a block keeps, beside its
inputs, the scan's output and chunk states or the attention's output
and logsumexp, the stream after the mixer and, on one TPU chip, the
MLP's ``gate`` output before ``silu`` (``SAVED_MLP_HIDDEN``: the
recomputed block makes ``up`` alone again); the memory and the
shared k, v are outputs of the blocks that make them and inputs of the
blocks that read them, and their gradients sum over the readers.

The model returns ``(logits, {"ssm_state_rms": (Mamba layers,)
float32})``: the root mean square of each Mamba layer's last state
``h_T``.

Names: ``ln_attn``, ``ln_mlp``, ``proj``, ``gate``, ``up``, ``down``,
``tok_embed``, ``ln_out`` are flax modules; scopes ``ssm_proj``
(``in_proj``, ``x_proj``, ``dt_proj``, ``out_proj`` and the gate),
``ssm_conv``, ``ssm_scan``, ``gmu``, ``q``, ``k``, ``v`` (the one
product ``qkv`` runs under ``q``), ``attn_core`` and inside it
``attn_window``, ``attn_full`` or ``attn_cross``, ``mlp``, ``head``.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.ops import attention as default_attention
from multidisttorch_tpu.ops.pallas_attention import blocked_window_attention
from multidisttorch_tpu.ops.selective_scan import selective_scan
from multidisttorch_tpu.parallel import mesh
from multidisttorch_tpu.utils.profiling import (
    SCOPE_ATTN_CORE,
    SCOPE_ATTN_CROSS,
    SCOPE_ATTN_FULL,
    SCOPE_ATTN_WINDOW,
    SCOPE_GMU,
    SCOPE_MLP,
    SCOPE_Q,
    SCOPE_SSM_CONV,
    SCOPE_SSM_PROJ,
    SCOPE_SSM_SCAN,
)

KINDS = ("mamba", "window", "mamba_memory", "full_kv", "gmu", "cross")


def default_layer_kinds(num_layers: int, mb_per_layer: int = 2) -> tuple[str, ...]:
    """The published layout: in the first half, layer ``i`` is Mamba
    where ``mb_per_layer`` divides ``i`` and window attention
    elsewhere; layer ``num_layers / 2`` is the Mamba layer that makes
    the memory and the one after it the full-attention layer that makes
    k and v; from there on a gated memory unit where ``mb_per_layer``
    divides ``i`` and cross-attention elsewhere. 32 layers: 9 + 8 + 1 +
    7 + 7."""
    half = num_layers // 2
    if num_layers % 4 or half % mb_per_layer:
        raise ValueError(
            f"default_layer_kinds: {num_layers} layers at mb_per_layer {mb_per_layer}: the "
            "layout takes a multiple of 4 whose half mb_per_layer divides"
        )

    def kind(i: int) -> str:
        if i == half:
            return "mamba_memory"
        if i == half + 1:
            return "full_kv"
        state = i % mb_per_layer == 0
        if i < half:
            return "mamba" if state else "window"
        return "gmu" if state else "cross"

    return tuple(kind(i) for i in range(num_layers))


DT_MIN, DT_MAX = 1e-3, 1e-1  # a Mamba layer's steps at the start (Mamba's own)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``b`` with ``softplus(b)`` log-uniform in ``[DT_MIN, DT_MAX]``."""
    dt = jnp.exp(
        jax.random.uniform(key, shape, jnp.float32) * (math.log(DT_MAX) - math.log(DT_MIN))
        + math.log(DT_MIN)
    )
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus's inverse


def _a_log_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.log(jnp.broadcast_to(jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape)).astype(
        dtype
    )


class SambaYBlock(nn.Module):
    """One layer of ``kind``. ``__call__(x, memory, kv) -> (x, handed,
    state_rms)``: ``memory`` is read by ``gmu`` and ``kv`` (a pair) by
    ``cross``; ``handed`` is the memory from ``mamba_memory``, ``(k,
    v)`` from ``full_kv`` and ``None`` from the rest; ``state_rms`` the
    root mean square of a Mamba layer's last state, ``None`` from the
    rest."""

    kind: str
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    mlp_width: int
    d_state: int
    d_conv: int
    expand: int
    dt_rank: int
    # (q, k, v, *, window) -> out, heads apart, as blocked_window_attention. None: the default
    attention: Optional[Callable] = None
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, memory=None, kv=None):
        if self.kind not in KINDS:
            raise ValueError(f"SambaYBlock: kind {self.kind!r} is none of {KINDS}")
        norm = lambda name: nn.LayerNorm(
            epsilon=self.eps, dtype=self.dtype, param_dtype=jnp.float32, name=name
        )
        u = norm("ln_attn")(x)
        handed = state_rms = None
        if self.kind in ("mamba", "mamba_memory"):
            out, y, state_rms = self._mamba(u)
            handed = y if self.kind == "mamba_memory" else None
        elif self.kind == "gmu":
            with jax.named_scope(SCOPE_GMU):
                out = decoder.dense(self, x.shape[-1], "out_proj")(
                    memory * nn.silu(decoder.dense(self, memory.shape[-1], "in_proj")(u))
                )
        else:
            out, made = self._attention(u, kv)
            handed = made if self.kind == "full_kv" else None
        x = checkpoint_name(x + out, decoder.SAVED_RESIDUAL)
        # gate's output is kept across remat on one TPU chip, where the
        # room for it was measured: 20 KB a token and layer in
        # phi-4-mini-flash (bf16, width 10,240), and the recomputed block
        # makes up alone again (28.9 ms of ssm-yoco-t16384's 57.9 of MLP
        # recomputation). Kept instead, up's output spared as much and
        # slowed the forward by 8.3 ms; both plan 15.14 GiB, more than any
        # accepted cell runs (PERF.md section 6, PR 41).
        h = norm("ln_mlp")(x)
        with jax.named_scope(SCOPE_MLP):
            gate = decoder.dense(self, self.mlp_width, "gate")(h)
            if mesh.on_one_tpu_chip(x):  # flat, as the Dense writes it
                gate = checkpoint_name(gate, decoder.SAVED_MLP_HIDDEN)
            h = decoder.dense(self, x.shape[-1], "down")(
                nn.silu(gate) * decoder.dense(self, self.mlp_width, "up")(h)
            )
        return x + h, handed, state_rms

    @nn.nowrap
    def _mamba(self, u):
        """``(out (B, T, d), y (B, T, E), rms of the last state)``."""
        d = u.shape[-1]
        e, n, r = self.expand * d, self.d_state, self.dt_rank
        conv_w = self.param("conv_w", nn.initializers.lecun_normal(), (self.d_conv, e), jnp.float32)
        conv_b = self.param("conv_b", nn.initializers.zeros, (e,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (e, n), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (e,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (e,), jnp.float32)
        with jax.named_scope(SCOPE_SSM_PROJ):
            xz = decoder.dense(self, 2 * e, "in_proj")(u)
            x, z = xz[..., :e], xz[..., e:]
        with jax.named_scope(SCOPE_SSM_CONV):
            x = nn.silu(decoder.causal_conv(x, conv_w, conv_b))
        with jax.named_scope(SCOPE_SSM_PROJ):
            dbc = decoder.dense(self, r + 2 * n, "x_proj")(x)
            dt = decoder.dense(self, e, "dt_proj")(dbc[..., :r])
        # the kernel pair or the plain form, by the scan's own rule; its
        # output and chunk states are kept across remat (10.3 and 1.3 KB a
        # token and layer in phi-4-mini-flash, E = 5,120, N = 16)
        with jax.named_scope(SCOPE_SSM_SCAN):
            y, last = selective_scan(
                x, dt, -jnp.exp(a_log), dbc[..., r:r + n], dbc[..., r + n:], skip, dt_bias,
                return_last_state=True,
            )
            state_rms = jnp.sqrt(jnp.mean(jnp.square(jax.lax.stop_gradient(last))))
        with jax.named_scope(SCOPE_SSM_PROJ):
            out = decoder.dense(self, d, "out_proj")(y * nn.silu(z))
        return out, y, state_rms

    @nn.nowrap
    def _attention(self, u, kv):
        """``(out (B, T, d), (k, v) heads apart)``: q of this layer
        over its own k and v, or (``cross``) over the ``kv`` given."""
        b, t, d = u.shape
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        heads = lambda a: a.reshape(b, t, -1, hd)
        if self.kind == "cross":
            with jax.named_scope(SCOPE_Q):
                q = heads(decoder.dense(self, h * hd, "q")(u))
            k, v = kv
        else:
            with jax.named_scope(SCOPE_Q):  # one product; k and v are its columns
                qkv = decoder.dense(self, (h + 2 * hkv) * hd, "qkv")(u)
            q, k, v = (heads(a) for a in jnp.split(qkv, [h * hd, (h + hkv) * hd], axis=-1))
        window = self.window if self.kind == "window" else None
        attend = (
            self.attention or default_attention.grouped_kernel(u, h, hkv, hd)
            or blocked_window_attention
        )
        scope = {"window": SCOPE_ATTN_WINDOW, "full_kv": SCOPE_ATTN_FULL,
                 "cross": SCOPE_ATTN_CROSS}[self.kind]
        with jax.named_scope(SCOPE_ATTN_CORE), jax.named_scope(scope):
            attn = attend(q, k, v, window=window)
        return decoder.dense(self, d, "proj")(attn.reshape(b, t, h * hd)), (k, v)


class SambaYLM(nn.Module):
    """Decoder-only LM: ``(B, T) int32 -> ((B, T, vocab) float32 logits,
    {"ssm_state_rms": (Mamba layers,) float32})``.

    ``layer_kinds`` ``None`` is :func:`default_layer_kinds` of
    ``num_layers`` and ``mb_per_layer``; given, it names every layer
    (a chip's share of the stack need not be contiguous: a
    ``mamba_memory`` has to come before the first ``gmu`` and a
    ``full_kv`` before the first ``cross``). ``dt_rank`` ``None`` is
    ``ceil(d_model / 16)``. The defaults are a toy for tests and
    examples; a configuration's file gives the published sizes
    (``benchmark/configs/``)."""

    vocab_size: int
    d_model: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    num_layers: int = 8
    mb_per_layer: int = 2
    layer_kinds: Optional[tuple[str, ...]] = None
    window: int = 8
    mlp_width: int = 128
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None
    eps: float = 1e-5
    max_len: int = 256
    tie_embeddings: bool = True
    attention: Optional[Callable] = None
    dtype: Any = jnp.float32
    remat: bool = False  # per-block checkpointing (decoder.remat_block)

    def kinds(self) -> tuple[str, ...]:
        if self.layer_kinds is None:
            return default_layer_kinds(self.num_layers, self.mb_per_layer)
        return tuple(self.layer_kinds)

    @nn.compact
    def __call__(self, tokens, head=True):
        x, table = decoder.embed_tokens(self, tokens)
        block_cls = decoder.block_class(self, SambaYBlock)
        memory = kv = None
        state_rms = []
        for i, kind in enumerate(self.kinds()):
            if (kind == "gmu" and memory is None) or (kind == "cross" and kv is None):
                raise ValueError(f"layer {i} ({kind}) comes before the layer that makes what it reads")
            x, handed, rms = block_cls(
                kind=kind, num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, window=self.window, mlp_width=self.mlp_width,
                d_state=self.d_state, d_conv=self.d_conv, expand=self.expand,
                dt_rank=self.dt_rank or -(-self.d_model // 16),
                attention=self.attention, eps=self.eps, dtype=self.dtype, name=f"block_{i}",
            )(x, memory if kind == "gmu" else None, kv if kind == "cross" else None)
            if kind == "mamba_memory":
                memory = handed
            elif kind == "full_kv":
                kv = handed
            if rms is not None:
                state_rms.append(rms)
        logits = decoder.norm_and_head(
            self, x, head, norm=nn.LayerNorm, eps=self.eps,
            table=table if self.tie_embeddings else None,
        )
        return logits, {"ssm_state_rms": jnp.stack(state_rms)}

    def head_weights(self, params):
        return decoder.head_weights(params, tied=self.tie_embeddings)
