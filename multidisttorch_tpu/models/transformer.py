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

from multidisttorch_tpu.ops.hyper_connection import SAVED_MAPS, SAVED_Y
from multidisttorch_tpu.ops.moe import SAVED_ROUTING
from multidisttorch_tpu.ops.pallas_attention import (
    SAVED_LSE,
    SAVED_OUT,
    default_takes_kernel,
    flash_attention,
)
from multidisttorch_tpu.ops.ring_attention import dense_attention_reference
from multidisttorch_tpu.ops.selective_scan import SAVED_SCAN_OUT, SAVED_SCAN_STATES
from multidisttorch_tpu.utils.profiling import SCOPE_ATTN_CORE, SCOPE_MLP

# The residual stream after a block's attention, ``x + proj(o)``, by the
# name :func:`remat_block` keeps: ``proj``'s backward reads ``o`` and the
# weights, never its output, so with the sum kept the recomputed block
# does not multiply by ``proj`` again. What that is worth a byte kept
# grows with the width ``proj`` reads: the blocks of
# ``models/latent_moe.py`` and ``models/grouped_window_moe.py`` (4,096
# and 3,584 wide in their cells) give the name; :class:`Block` (1,024 in
# its cells) does not, where the same name made the step slower on the
# chip than the product it spared (PERF.md section 6, PR 34). It keeps
# q, k, v and ``up``'s output instead, each of which spares three to
# four times ``proj``'s milliseconds, and on the chip displaced nothing
# (PR 40); ``ln_mlp`` still reads the sum, so ``proj`` is the one
# product its recomputed block makes again.
SAVED_RESIDUAL = "residual_after_attention"

# The operands an attention reads, q, k and v as its call receives them
# (k rotated already; flat, as the projections write them and the
# kernels read them), by the name :func:`remat_block` keeps: the
# kernels' backward reads them, so with the three kept the recomputed
# block multiplies its normed input by none of the three matrices and
# rotates nothing. ``GroupedWindowMoEBlock``, ``ShortConvMoEBlock``'s
# attention layers and, on one TPU chip, :class:`Block` give the name;
# ``LatentMoEBlock`` does not (its five operands, 2.6 GiB in its cell,
# do not fit under the step's plan; PERF.md section 7). Named flat: a kept array has no reader that fixes
# its layout, and a 4-D name cost copies in both passes (PERF.md
# section 6, PR 36). Defined here and not beside the kernels: a line
# moved in ``ops/pallas_attention.py`` or ``ops/moe.py`` changes every
# kernel's serialized module.
SAVED_QKV = "attention_operands"

# :class:`Block`'s MLP pre-activation, ``up``'s output before ``gelu``,
# ``(B, T, 4d)`` at the compute dtype, by the name :func:`remat_block`
# keeps: ``gelu``'s backward and ``down``'s read it, so with it kept the
# recomputed block makes no ``up`` product, only the elementwise
# ``gelu`` again. ``models/ssm_hybrid.py::SambaYBlock`` gives the name,
# on one TPU chip, to its gated MLP's ``gate`` output before ``silu``
# (flat, ``(B, T, mlp_width)``): 20 KB a token and layer in
# ``phi-4-mini-flash`` (bf16, width 10,240), which spares one of the
# two products a recomputed block made again (0.86 TFLOP a layer at
# 16,384 tokens: 28.9 ms of recomputation and 8.5 of the backward in
# ``ssm-yoco-t16384``). Kept instead, ``up``'s output spared as much
# recomputation and slowed the forward by 8.3 ms; both, 40 KB, plan
# 15.14 GiB, more than any accepted cell runs (PERF.md section 6, PR 41).
SAVED_MLP_HIDDEN = "mlp_hidden"


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
                a = checkpoint_name(a, SAVED_QKV)
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
        # q, k, v and up's output are kept across remat on one TPU chip,
        # where the room for them was measured beside the head and loss
        # that train/lm.py walks in blocks (PERF.md section 6, PR 40); a
        # trial over several chips still holds its logits.
        keep = _on_one_tpu_chip(x)
        x = _attention_residual(self, x, dense, ln, keep)
        d = x.shape[-1]
        y = ln("ln_mlp")(x)
        with jax.named_scope(SCOPE_MLP):
            y = dense(4 * d, "up")(y)
            if keep:
                y = checkpoint_name(y, SAVED_MLP_HIDDEN)
            y = nn.gelu(y)
            y = dense(d, "down")(y)
        return x + y


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
    keeps, by name, what costs most to remake a byte. Ten names, each
    given where the value is made, and a block keeps those its trace
    holds:

    - ``SAVED_OUT``, ``SAVED_LSE`` (``ops/pallas_attention.py``): an
      attention kernel's output and logsumexp, bf16 ``(B, T, H*Dv)``
      and f32 ``(B, H, T)``, so the recomputed forward holds no kernel;
    - ``SAVED_RESIDUAL`` (this file; given by ``LatentMoEBlock``
      without streams, ``GroupedWindowMoEBlock``, ``SambaYBlock`` and
      ``ShortConvMoEBlock``'s attention layers, on every attention
      path, the dense one too): the residual stream after
      the token mixer, ``x + proj(o)``, one ``(B, T, d)`` array at the
      compute dtype, so ``proj`` is not multiplied again;
    - ``SAVED_ROUTING`` (``ops/moe.py::RoutedExperts``): the router's
      float32 logits ``(N, E)``, with sigmoid scoring its choices and
      their scores ``(N, k)``, the order into expert order ``(N*k,)``
      and the counts, so neither the float32 product nor the gathers
      and sorts after it run again;
    - ``SAVED_MAPS``, ``SAVED_Y`` (``ops/hyper_connection.py``): a
      connection's projections and norm factor, and its sublayer's
      output, which does there what ``SAVED_RESIDUAL`` does around a
      plain residual add;
    - ``SAVED_QKV`` (this file; given by ``GroupedWindowMoEBlock`` and
      ``ShortConvMoEBlock``'s attention layers on either attention
      path, and by :class:`Block` on one TPU chip): q, k and v flat,
      ``(B, T, (H + 2 Hkv) * head_dim)`` at the compute dtype, k
      rotated where the block rotates it before the call, so the three
      products and that rotation are not made again: 9.2 KB a token
      and layer in ``smallthinker-21b-a3b`` (bf16, 28 + 4 + 4 heads of
      128), 6 KB in ``gpt2-medium`` (3 x 1,024), for 14.3 ms of its
      step's 37.6 ms of recomputation in the 24 layers of ``lm-dense``;
    - ``SAVED_SCAN_OUT``, ``SAVED_SCAN_STATES``
      (``ops/selective_scan.py``; a Mamba layer of
      ``models/ssm_hybrid.py``): the selective scan's output ``(B, T,
      E)`` at the compute dtype and the state at each chunk's end,
      float32 ``(B, T / 256, N, E)``, which the scan's backward walks
      from, so the recomputed forward holds no scan: 10.3 KB and 1.3 KB
      a token and layer in ``phi-4-mini-flash`` (E = 5,120, N = 16);
    - ``SAVED_MLP_HIDDEN`` (this file; given by :class:`Block` and by
      ``SambaYBlock``, each on one TPU chip): the MLP's pre-activation
      ``(B, T, 4d)`` at the compute dtype, 8 KB a token and layer in
      ``gpt2-medium``, so the recomputed block makes no ``up`` product
      (18.8 ms in ``lm-dense``), only ``gelu`` again; in
      ``SambaYBlock`` the gated MLP's ``gate`` output ``(B, T,
      mlp_width)``, 20 KB a token and layer in ``phi-4-mini-flash``
      (40 KB with ``up``'s, which does not fit), so the recomputed
      block makes ``up`` alone, and ``silu`` and the multiply again
      (28.9 ms of ``ssm-yoco-t16384``'s 57.9 of MLP recomputation).

    Everything else (``LatentMoEBlock``'s q, k and v, the experts' and
    the other MLPs' hidden activations, ``SambaYBlock``'s ``up``,
    :class:`Block`'s ``proj``, the norms) is made again from the block's
    input; where the trace holds none of the names (a ``TransformerLM``
    on the CPU or over several chips, on the dense path) nothing but the
    input is saved."""
    return nn.remat(block_cls, policy=_KEEP_ACROSS_REMAT)


def _placement(x):
    """``(device_kind, num_devices)`` of the mesh the computation's
    operands were placed on, as tracing sees it: a jitted function's
    values carry the abstract mesh of its committed ``NamedSharding``
    arguments (every state ``create_lm_state`` makes and every batch a
    ``TrialMesh`` places). ``None`` where there is none to see: an
    uncommitted or single-device array, shapes alone."""
    mesh = jax.typeof(x).sharding.mesh
    return None if mesh.empty else (mesh.abstract_device.device_kind, mesh.size)


def _on_one_tpu_chip(x) -> bool:
    """Whether ``x`` lies on one TPU device, as :func:`_placement` sees
    it: where :class:`Block` and ``SambaYBlock`` measured the room to
    keep more across remat than their names elsewhere."""
    placed = _placement(x)
    return bool(placed) and placed[0].startswith("TPU") and placed[1] == 1


def _default_causal(attn):
    """The attention a model runs when none was injected: the blockwise
    kernel (``ops.pallas_attention.flash_attention``, the code
    ``make_flash_attention`` hands out) where
    ``ops.pallas_attention.default_takes_kernel`` says it applies — a
    TPU, operands on one device, a sequence length and heads the
    kernel tiles — and the dense path everywhere else: the CPU, a
    placement tracing cannot see, a data-parallel batch or
    tensor-parallel heads over several chips (a bare ``pallas_call``
    has no partitioning rule; GSPMD would gather its operands), a
    pipeline stage under ``shard_map``, a T of 200. Decided while
    tracing, from the operands alone; there is no switch."""
    if attn is not None:
        return attn

    def causal(q, k, v):
        placed = _placement(q)
        if placed and default_takes_kernel(*placed, *q.shape[1:], v.shape[-1]):
            return flash_attention(q, k, v, causal=True)
        return dense_attention_reference(q, k, v, causal=True)

    return causal


def _lm_embed(mod, tokens):
    """Token + learned positional embeddings, shared by both LM
    variants — includes the trace-time length check (out-of-range
    nn.Embed gathers would silently clip/fill, not raise)."""
    _, t = tokens.shape
    if t > mod.max_len:
        raise ValueError(f"sequence length {t} exceeds max_len={mod.max_len}")
    x = nn.Embed(
        mod.vocab_size, mod.d_model, dtype=mod.dtype,
        param_dtype=jnp.float32, name="tok_embed",
    )(tokens)
    pos = nn.Embed(
        mod.max_len, mod.d_model, dtype=mod.dtype,
        param_dtype=jnp.float32, name="pos_embed",
    )(jnp.arange(t)[None, :])
    return x + pos


def _lm_head(mod, x, head=True):
    """Final norm + f32 vocab head, shared by both LM variants; with
    ``head`` false the normed state itself (:func:`head_weights`)."""
    x = nn.LayerNorm(
        dtype=mod.dtype, param_dtype=jnp.float32, name="ln_out"
    )(x)
    if not head:
        return x
    return nn.Dense(
        mod.vocab_size, dtype=jnp.float32, param_dtype=jnp.float32,
        name="head",
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
    (:func:`_default_causal`): the blockwise Pallas kernel on a single
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
    # Per-BLOCK rematerialization (remat_block): the block boundaries'
    # residual streams are saved, and with them the attention kernel's
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
        x = _lm_embed(self, tokens)
        attn = _default_causal(self.attention)
        block_cls = remat_block(Block) if self.remat else Block
        for i in range(self.num_layers):
            x = block_cls(
                d_model=self.d_model,
                num_heads=self.num_heads,
                attention=attn,
                dtype=self.dtype,
                name=f"block_{i}",
            )(x)
        return _lm_head(self, x, head)

    def head_weights(self, params):
        return head_weights(params)


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
        # the operands span several chips, _default_causal), or a ring
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
    remat: bool = False  # per-block checkpointing (remat_block)

    @nn.compact
    def __call__(self, tokens, head=True):
        x = _lm_embed(self, tokens)
        attn = _default_causal(self.attention)
        block_cls = remat_block(MoEBlock) if self.remat else MoEBlock
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
        logits = _lm_head(self, x, head)
        return logits, aux_total / self.num_layers

    def head_weights(self, params):
        return head_weights(params)


def moe_lm_ep_shardings(trial, model: MoETransformerLM):
    """Expert-parallel shardings for the MoE LM: every expert-indexed
    leaf (the blocks' ``moe/w1|b1|w2|b2``) splits over the trial's
    ``model`` axis via the one shared rule
    (:func:`ops.moe.moe_ep_shardings`); attention projections, router,
    embeddings, norms, and the head stay replicated."""
    from multidisttorch_tpu.ops.moe import moe_ep_shardings

    return moe_ep_shardings(trial, _lm_param_shapes(trial, model))
