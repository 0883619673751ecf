"""Causal LM whose stack of blocks runs several times a token, with an
exit gate after every pass (a looped language model).

The L blocks are built once and called U times: pass ``t`` reads what
pass ``t - 1`` left after the final norm, with the same weights, so the
parameter count does not depend on U. After every pass the final norm's
output is what the head reads and what a gate of one logit a token
reads; the U gates give the exit distribution (:func:`exit_log_probs`)
that ``train/lm.py::_looped_loss`` weighs the U passes' losses by. A trial
gets it as it gets the other LMs: plain fields, a state from
``create_lm_state``, a step from ``make_lm_train_step``.

With ``d`` the model's width, ``H`` query heads over ``Hkv`` KV heads of
``head_dim``::

    h_0 = Embed(tokens)
    pass t = 1..U:  x = h_{t-1};  for each block i:  x = B_i(x);  h_t = RMSNorm_f(x)
        gate logit g_t = w_g . h_t + b_g (float32);  per loop the head h_t W_head (float32)
    B_i(x):
        y = RMSNorm_a1(x)
        q = y W_q as (H, head_dim);  k = y W_k, v = y W_v as (Hkv, head_dim)
        q, k rotated over the whole head, element i with i + head_dim/2, angle pos * theta**(-2i/head_dim),
            the same positions in every pass
        s_ij = q_i . k_j / sqrt(head_dim), kept where j <= i;  head h reads KV head h // (H / Hkv)
        a = x + RMSNorm_a2(softmax(s) v W_o)
        out = a + RMSNorm_m2(W_down(silu(W_gate z) * (W_up z))),  z = RMSNorm_m1(a)

every norm an RMSNorm with a scale and no bias (four a block: the
"sandwich"); no biases but the gate's.

**Which attention runs where**, as in ``models/grouped_window_moe.py``:
where ``ops/attention.py::grouped_kernel`` takes the heads (one TPU
chip, heads 128 wide) the core is ``ops.pallas_attention.grouped_attention``,
which rotates q as it loads it (k is rotated here); everywhere else q is
rotated here too and the core is ``blocked_window_attention``.

The model returns ``(out, gate_logits)``: ``out`` the U passes' logits
``(U, B, T, vocab)`` float32, or with ``head`` false their states after
the final norm ``(U, B, T, d)``; ``gate_logits`` ``(U, B, T)`` float32.

Names: ``tok_embed``, ``block_<i>``, ``ln_out``, ``head`` and
``exit_gate`` are flax modules; in a block ``ln_attn``, ``q``, ``k``,
``v``, ``proj``, ``ln_attn_out``, ``ln_mlp``, ``gate``, ``up``,
``down`` and ``ln_mlp_out``. Pass ``t`` runs under the scope
``loop_<t>``, the gate under ``loop_exit``; inside a block the
rotations run under ``q`` and ``k``, the core under ``attn_core``, the
MLP under ``mlp``, and the two norms after the sublayers under
``ln_attn`` and ``ln_mlp``, the names the trace's readers give to norms.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.ops import attention as default_attention
from multidisttorch_tpu.ops.pallas_attention import blocked_window_attention
from multidisttorch_tpu.utils.profiling import (
    SCOPE_ATTN_CORE,
    SCOPE_K,
    SCOPE_LOOP,
    SCOPE_LOOP_EXIT,
    SCOPE_Q,
    SCOPE_V,
)


class LoopedBlock(nn.Module):
    """One sandwich-norm block: a norm before and after each of the
    attention and the MLP. Under ``decoder.remat_block`` it keeps the
    core's output and logsumexp and q, k and v as the core reads them:
    the recomputed block holds the norms, ``proj`` and the MLP's first
    half."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    hidden_dim: int
    rope_theta: float
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dense, norm = partial(decoder.dense, self), partial(decoder.rms_norm, self)
        y = norm("ln_attn")(x)
        # flat, as the projections write them and the kernels read them
        q, k, v = dense(h * hd, "q")(y), dense(hkv * hd, "k")(y), dense(hkv * hd, "v")(y)
        heads = lambda a: a.reshape(b, t, -1, hd)
        angle = decoder.rope_angles(jnp.arange(t), self.rope_theta, hd)
        rotation = jnp.cos(angle), jnp.sin(angle)
        with jax.named_scope(SCOPE_K):
            k = decoder.rope_halves(heads(k), *rotation).reshape(k.shape)
        attend = default_attention.grouped_kernel(x, h, hkv, hd, rotates_q=True)
        if attend is None:  # the plain path takes q as it is multiplied
            with jax.named_scope(SCOPE_Q):
                q = decoder.rope_halves(heads(q), *rotation).reshape(q.shape)

        # Kept across remat by name: of what fits a step under 14.5 GiB at 2 x
        # 4,096 tokens, q, k and v spare the most (779 ms a step against 794
        # with the MLP's gate output kept instead, 824 with neither; both
        # plan 14.95 GiB: PERF.md section 6)
        def kept(a, scope):  # jax rounds a kept float where it is named: the projection's work
            with jax.named_scope(scope):
                return checkpoint_name(a, decoder.SAVED_QKV)

        q, k, v = heads(kept(q, SCOPE_Q)), heads(kept(k, SCOPE_K)), heads(kept(v, SCOPE_V))
        with jax.named_scope(SCOPE_ATTN_CORE):
            if attend is None:
                attn = blocked_window_attention(q, k, v, window=None)
            else:
                attn = attend(q, k, v, window=None, q_rotation=rotation)
        attn = dense(d, "proj")(attn.reshape(b, t, h * hd))
        # the norms after the sublayers run under the names of those before
        # them, which the trace's readers count as norms
        with jax.named_scope("ln_attn"):
            x = x + norm("ln_attn_out")(attn)
        mlp = decoder.gated_mlp(self, norm("ln_mlp")(x), self.hidden_dim)
        with jax.named_scope("ln_mlp"):
            return x + norm("ln_mlp_out")(mlp)


class LoopedLM(nn.Module):
    """Decoder-only LM whose ``num_layers`` blocks run ``loops`` times a
    token: ``(B, T) int32 -> ((loops, B, T, vocab) float32 logits,
    (loops, B, T) float32 exit-gate logits)``, the first the states after
    the final norm with ``head`` false.

    ``exit_entropy_weight`` is the weight of the exit distribution's
    entropy in the training objective (``train/lm.py`` trains any model
    that offers ``exit_log_probs`` on it). The defaults are a toy for tests and
    examples; a configuration's file gives the published sizes
    (``benchmark/configs/``)."""

    vocab_size: int
    d_model: int = 64
    num_layers: int = 2
    loops: int = 4
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 16
    hidden_dim: int = 128
    rope_theta: float = 10000.0
    exit_entropy_weight: float = 0.1
    eps: float = 1e-6
    max_len: int = 256
    dtype: Any = jnp.float32
    remat: bool = False  # per-block checkpointing (decoder.remat_block)

    @nn.compact
    def __call__(self, tokens, head=True):
        x, _ = decoder.embed_tokens(self, tokens)
        block_cls = decoder.block_class(self, LoopedBlock)
        blocks = [
            block_cls(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                hidden_dim=self.hidden_dim, rope_theta=self.rope_theta, eps=self.eps,
                dtype=self.dtype, name=f"block_{i}",
            )
            for i in range(self.num_layers)
        ]
        final_norm = decoder.rms_norm(self, "ln_out")
        gate = nn.Dense(1, dtype=jnp.float32, param_dtype=jnp.float32, name="exit_gate")
        vocab_head = nn.Dense(
            self.vocab_size, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
            name="head",
        )
        outs, gates = [], []
        for t in range(self.loops):
            with jax.named_scope(SCOPE_LOOP.format(t)):
                for block in blocks:
                    x = block(x)
                x = final_norm(x)
                with jax.named_scope(SCOPE_LOOP_EXIT):
                    gates.append(gate(x)[..., 0])
                outs.append(vocab_head(x) if head else x)
        return jnp.stack(outs), jnp.stack(gates)

    def head_weights(self, params):
        return decoder.head_weights(params)

    def exit_log_probs(self, gate_logits):
        return exit_log_probs(gate_logits)


def exit_log_probs(gate_logits: jax.Array) -> jax.Array:
    """``log p_t`` of the exit distribution, from the exit gates' logits
    ``g`` ``(U, ...)``: with ``lambda_t = sigmoid(g_t)``, ``p_t =
    lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < U`` and ``p_U =
    prod_{j<U} (1 - lambda_j)``, so that the U values sum to 1 at every
    position (``p_1 = 1`` where U is 1); float32 and in log space (``log
    sigmoid``), so that no product underflows. The last loop's gate is
    not read."""
    g = gate_logits.astype(jnp.float32)
    stay = jax.nn.log_sigmoid(-g[:-1])  # log(1 - lambda_t), t < U
    before = jnp.cumsum(stay, axis=0) - stay  # sum_{j<t} log(1 - lambda_j)
    last = jnp.sum(stay, axis=0, keepdims=True)
    return jnp.concatenate([jax.nn.log_sigmoid(g[:-1]) + before, last], axis=0)
