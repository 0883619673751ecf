"""Causal transformer LM with pluggable (ring-parallel) attention.

The reference has no attention anywhere (SURVEY.md §5: "long-context /
sequence parallelism: absent — the model is an MLP VAE"), but
long-context is first-class here, and an op is only first-class when a
trainable model uses it. This is that model: a standard pre-LN decoder
stack whose attention implementation is injected — pass
``ops.ring_attention.make_ring_attention(trial, causal=True)`` and the
sequence dimension shards across the trial's device axis (context
length scales with devices, each chip holding ``T/N`` of the sequence);
pass nothing and it runs single-chip attention, the blockwise kernel
on one TPU chip and the dense path elsewhere. Same params either way,
so ring-vs-dense is directly comparable (tested).

TPU-first details: pre-LN (stable without warmup games), learned
positional embeddings (static shapes), GELU MLP at 4x width (MXU-sized
matmuls), float32 params with a ``dtype`` knob for bf16 compute — the
same conventions as the rest of ``models/``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from multidisttorch_tpu.models import decoder
from multidisttorch_tpu.ops import attention as default_attention
from multidisttorch_tpu.parallel import mesh
from multidisttorch_tpu.utils.profiling import SCOPE_ATTN_CORE, SCOPE_MLP


def _layer_ctors(mod):
    """The dense/layernorm constructors every block variant shares
    (compute at ``mod.dtype``, params f32)."""
    dense = lambda feats, name: nn.Dense(
        feats, dtype=mod.dtype, param_dtype=jnp.float32, name=name
    )
    ln = lambda name: nn.LayerNorm(
        dtype=mod.dtype, param_dtype=jnp.float32, name=name
    )
    return dense, ln


def _attention_residual(mod, x, dense, ln, keep=False):
    """The attention half shared by :class:`Block` and
    :class:`MoEBlock` (one copy — the two must never drift); with
    ``keep`` q, k and v are named ``SAVED_QKV`` as the projections
    write them, before the reshape to heads.

    Separate q/k/v projections (not one fused 3d dense): each output's
    flat feature dim factors as [head, head_dim], so a tensor-parallel
    column sharding of the kernel IS a head sharding after the reshape
    — no resharding at the reshape, which the fused layout (proj-major
    [3, head, dh]) can't offer.
    """
    b, t, d = x.shape
    h = mod.num_heads
    y = ln("ln_attn")(x)

    def operand(name):
        a = dense(d, name)(y)
        if keep:  # jax rounds a kept float where it is named: the projection's work
            with jax.named_scope(name):
                a = checkpoint_name(a, decoder.SAVED_QKV)
        return a.reshape(b, t, h, d // h)

    q, k, v = operand("q"), operand("k"), operand("v")
    with jax.named_scope(SCOPE_ATTN_CORE):
        attn = mod.attention(q, k, v)
    attn = attn.reshape(b, t, d)
    return x + dense(d, "proj")(attn)


class Block(nn.Module):
    """Pre-LN decoder block: attention + 4x GELU MLP, both residual."""

    d_model: int
    num_heads: int
    attention: Callable  # (q, k, v) -> out, all (B, T, H, Dh); causal
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense, ln = _layer_ctors(self)
        # q, k, v (decoder.SAVED_QKV, 6 KB a token and layer in
        # gpt2-medium) and up's output (SAVED_MLP_HIDDEN, 8 KB) are kept
        # across remat on one TPU chip, where the room for them was
        # measured beside the head and loss that train/lm.py walks in
        # blocks: the recomputed block makes proj alone again (14.3 and
        # 18.8 ms of lm-dense's step; PERF.md section 6, PR 40). A trial
        # over several chips still holds its logits. The stream after
        # attention (SAVED_RESIDUAL) is not kept: 1,024 wide, it spares
        # less than it displaced (-1.5% on the chip, PR 34).
        keep = mesh.on_one_tpu_chip(x)
        x = _attention_residual(self, x, dense, ln, keep)
        d = x.shape[-1]
        y = ln("ln_mlp")(x)
        with jax.named_scope(SCOPE_MLP):
            y = dense(4 * d, "up")(y)
            if keep:
                y = checkpoint_name(y, decoder.SAVED_MLP_HIDDEN)
            y = nn.gelu(y)
            y = dense(d, "down")(y)
        return x + y


def _lm_param_shapes(trial, model):
    """Abstract param shapes for a sharding builder. The dummy length
    must divide the trial's data-axis extent or a ring-attention
    model's shard_map fails inside eval_shape (same constraint
    create_lm_state solves the same way)."""
    dummy_len = min(8 * trial.data_size, model.max_len)
    return jax.eval_shape(
        model.init,
        {"params": jax.random.key(0)},
        jnp.zeros((1, dummy_len), jnp.int32),
    )["params"]


class TransformerLM(nn.Module):
    """Decoder-only LM: ``(B, T) int32 tokens -> (B, T, vocab) logits``.

    ``attention`` must be causal. ``None`` is exact causal attention
    local to each head, by the path the operands allow
    (``ops/attention.py::causal``): the blockwise Pallas kernel on one
    TPU chip at the lengths and head widths it tiles, XLA's dense path
    everywhere else, several chips included (so ``None`` stays
    shardable over heads and batch). For sequence parallelism pass
    ``make_ring_attention(trial, causal=True)`` and shard the token
    batch's T dimension over the trial's data axis.
    """

    vocab_size: int
    d_model: int = 64
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 256
    attention: Optional[Callable] = None
    dtype: Any = jnp.float32
    # Per-BLOCK rematerialization (decoder.remat_block): the block
    # boundaries' residual streams are saved, and with them the kernel's
    # output and logsumexp where the kernel runs and, on one TPU chip,
    # q, k, v and the MLP's pre-activation; each block's other
    # activations (proj's sum, the norms, gelu, dense attention's
    # probs) are recomputed in the backward pass. This is the
    # placement that actually cuts peak HBM for a deep stack —
    # checkpointing the whole forward would leave every layer's
    # activations live during the backward and save nothing.
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, head=True):
        x, _ = decoder.embed_tokens(self, tokens, positions=True)
        attn = self.attention or default_attention.causal
        block_cls = decoder.block_class(self, Block)
        for i in range(self.num_layers):
            x = block_cls(
                d_model=self.d_model,
                num_heads=self.num_heads,
                attention=attn,
                dtype=self.dtype,
                name=f"block_{i}",
            )(x)
        return decoder.norm_and_head(self, x, head, norm=nn.LayerNorm, bias=True)

    def head_weights(self, params):
        return decoder.head_weights(params)


def transformer_tp_shardings(
    trial, model: TransformerLM, *, shard_attention: bool | str = "auto"
):
    """Megatron-style tensor-parallel shardings for the LM's blocks.

    Two column/row pairs per block, exactly Megatron's decomposition:

    - MLP: ``up`` column-parallel (output features sharded over the
      ``model`` axis), ``down`` row-parallel (input features sharded;
      GSPMD closes the pair with one psum) — 2/3 of a block's params.
    - Attention (``shard_attention``): ``q``/``k``/``v``
      column-parallel — their flat feature dim factors as
      ``[head, head_dim]``, so the column shard IS a head shard after
      the reshape — and ``proj`` row-parallel closing with a psum.
      Heads must divide the model axis; attention itself must be
      per-head local. ``"auto"`` shards heads for the dense default
      AND for ring/ring-flash callables built with head sharding
      (``shard_heads="auto"`` on a 2-D mesh sets ``fn.head_sharded``);
      a replicated-head ring keeps the attention projections
      replicated.

    Embeddings, norms, and the vocab head stay replicated. Requires
    ``4*d_model`` divisible by the model-axis extent.
    """
    from multidisttorch_tpu.parallel.mesh import MODEL_AXIS

    m = trial.model_size
    if (4 * model.d_model) % m:
        raise ValueError(
            f"4*d_model={4 * model.d_model} not divisible by the model "
            f"axis ({m})"
        )
    if shard_attention == "auto":
        # per-head-local attention paths: the default (dense wherever
        # the operands span several chips, ops/attention.py), or a ring
        # built with head sharding (its shard_map splits heads over the
        # model axis itself — fn.head_sharded marks it). A plain flash
        # callable sets head_sharded=False explicitly: its single
        # unsharded pallas_call can't be split by GSPMD, so replicated
        # projections are the deliberate choice, not a fallthrough
        # (see make_flash_attention's docstring for the TP-capable
        # ring-flash alternative).
        per_head_local = model.attention is None or getattr(
            model.attention, "head_sharded", False
        )
        shard_attention = per_head_local and model.num_heads % m == 0
    if shard_attention and model.num_heads % m:
        raise ValueError(
            f"num_heads={model.num_heads} not divisible by the model "
            f"axis ({m}); head sharding needs whole heads per device"
        )
    col = {
        "kernel": trial.sharding(None, MODEL_AXIS),
        "bias": trial.sharding(MODEL_AXIS),
    }
    row = {
        "kernel": trial.sharding(MODEL_AXIS, None),
        "bias": trial.sharding(),
    }
    repl = trial.sharding()
    shapes = _lm_param_shapes(trial, model)

    col_names = {"up"} | ({"q", "k", "v"} if shard_attention else set())
    row_names = {"down"} | ({"proj"} if shard_attention else set())

    def rule(path, _leaf):
        keys = [p.key for p in path if hasattr(p, "key")]
        if keys and keys[0].startswith("block_"):
            if keys[1] in col_names:
                return col["kernel"] if keys[-1] == "kernel" else col["bias"]
            if keys[1] in row_names:
                return row["kernel"] if keys[-1] == "kernel" else row["bias"]
        return repl

    return jax.tree_util.tree_map_with_path(rule, shapes)


class MoEBlock(nn.Module):
    """Pre-LN decoder block whose MLP is a top-1-routed expert mixture.

    Same attention half as :class:`Block`; the 4x GELU MLP is replaced
    by :class:`ops.moe.MoEMLP` (GShard static dispatch — SURVEY.md §2c
    has no MoE anywhere in the reference). Returns ``(x, aux)`` so the
    Switch load-balancing loss can reach the objective.
    """

    d_model: int
    num_heads: int
    attention: Callable
    num_experts: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        from multidisttorch_tpu.ops.moe import MoEMLP

        dense, ln = _layer_ctors(self)
        x = _attention_residual(self, x, dense, ln)
        b, t, d = x.shape
        y = ln("ln_mlp")(x)
        # MoEMLP routes per token: flatten (B, T, d) -> (B*T, d)
        y2, aux = MoEMLP(
            num_experts=self.num_experts,
            hidden_dim=4 * d,
            out_dim=d,
            capacity_factor=self.capacity_factor,
            dtype=self.dtype,
            name="moe",
        )(y.reshape(b * t, d))
        return x + y2.reshape(b, t, d), aux


class MoETransformerLM(nn.Module):
    """Decoder-only LM with expert-parallel MoE MLPs in every block.

    ``(B, T) int32 tokens -> ((B, T, vocab) logits, aux)`` where
    ``aux`` is the mean Switch load-balancing loss over blocks. Expert
    parallelism is a sharding: place params with
    :func:`moe_lm_ep_shardings` and each device of the trial's model
    axis runs only its experts.
    """

    vocab_size: int
    d_model: int = 64
    num_heads: int = 4
    num_layers: int = 2
    num_experts: int = 4
    capacity_factor: float = 1.25
    max_len: int = 256
    attention: Optional[Callable] = None
    dtype: Any = jnp.float32
    remat: bool = False  # per-block checkpointing (decoder.remat_block)

    @nn.compact
    def __call__(self, tokens, head=True):
        x, _ = decoder.embed_tokens(self, tokens, positions=True)
        attn = self.attention or default_attention.causal
        block_cls = decoder.block_class(self, MoEBlock)
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(self.num_layers):
            x, aux = block_cls(
                d_model=self.d_model,
                num_heads=self.num_heads,
                attention=attn,
                num_experts=self.num_experts,
                capacity_factor=self.capacity_factor,
                dtype=self.dtype,
                name=f"block_{i}",
            )(x)
            aux_total = aux_total + aux
        logits = decoder.norm_and_head(self, x, head, norm=nn.LayerNorm, bias=True)
        return logits, aux_total / self.num_layers

    def head_weights(self, params):
        return decoder.head_weights(params)


def moe_lm_ep_shardings(trial, model: MoETransformerLM):
    """Expert-parallel shardings for the MoE LM: every expert-indexed
    leaf (the blocks' ``moe/w1|b1|w2|b2``) splits over the trial's
    ``model`` axis via the one shared rule
    (:func:`ops.moe.moe_ep_shardings`); attention projections, router,
    embeddings, norms, and the head stay replicated."""
    from multidisttorch_tpu.ops.moe import moe_ep_shardings

    return moe_ep_shardings(trial, _lm_param_shapes(trial, model))
