"""Blockwise (flash) causal attention as Pallas TPU kernels.

The reference framework has no attention at all (SURVEY.md §5); this
repo's long-context story is ring attention across chips
(``ops/ring_attention.py``) — but *within* one chip the attention block
still materializes the full ``(B, H, Tq, Tk)`` score matrix in HBM,
which caps single-chip context length and wastes bandwidth on the
framework's own TransformerLM. This module is the single-chip half of
the long-context design: an exact, online-softmax attention that tiles
Q/K/V into VMEM blocks, keeps the running max/sum in VMEM scratch, and
never writes scores to HBM. Forward and backward are both Pallas
kernels wired through ``jax.custom_vjp`` (the backward recomputes
probabilities from the saved per-row logsumexp — the standard
flash-attention memory trade).

Layout contract matches ``make_ring_attention``: ``(batch, seq, heads,
head_dim)``; bf16 or f32 in, accumulation always f32. The kernels
compile through Mosaic; the CPU test suite runs them in interpreter
mode by asking for it (``ops/pallas_mode.py``). Sequence lengths
divisible by 128 tile at the MXU edge; other lengths run as one
whole-sequence block (see :func:`flash_attention`).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multidisttorch_tpu.ops.pallas_mode import pallas_interpret


# Q/K tile edge: 128 matches the MXU systolic array; shorter sequences
# use the whole sequence as one block.
_BLOCK = 128
_NEG_INF = -1e30  # finite sentinel: -inf rows poison exp() on the VPU

# Largest non-128-divisible T allowed to run as one whole-sequence
# block. The whole-block path keeps the (T, T) f32 score tile plus
# three (T, d) operand tiles resident in VMEM — ~4.5 MB at T=1024,
# d=64, comfortably inside a v5e core's budget; at T=8256 the score
# tile alone is 272 MB and the kernel fails at Mosaic compile time.
# Above this, causal inputs are padded to the tile edge (exact — see
# flash_attention) and non-causal inputs get a clear error instead of
# a compile-time blowup (ADVICE r4).
_MAX_WHOLE_BLOCK = 1024


def _blocks(t: int) -> int:
    return _BLOCK if t % _BLOCK == 0 else t


def _out_struct(shape, dtype, like):
    """``ShapeDtypeStruct`` carrying the operands' varying-mesh-axes
    type. Under a ``check_vma=True`` ``shard_map`` (e.g. the pipeline's
    staged forward, parallel/pipeline.py) a pallas_call must declare
    its outputs' VMA explicitly or tracing rejects it; propagating the
    input's vma makes the kernels VMA-transparent (outside shard_map
    ``typeof(x).vma`` is empty and this is a no-op)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _row_spec(bq, index_map):
    """Block over a per-row statistic (logsumexp, delta) stored as
    ``(BH, 1, T)``: the TPU lowering wants a block's last two dims to be
    multiples of (8, 128) or the whole array dim, which a ``(1, bq)``
    block of a ``(BH, T)`` array is not once BH > 1. The unit middle
    dim is the whole dim, and T rides the lanes."""
    return pl.BlockSpec((1, 1, bq), index_map, memory_space=pltpu.VMEM)


# ---------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc,
                *, scale, causal, block_q, block_k):
    """Grid (BH, nq, nk), nk innermost ("arbitrary"): one Q block's
    online-softmax accumulation across K blocks, carried in VMEM
    scratch; outputs written on the last K step."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    # Causal: K blocks strictly above the diagonal contribute nothing.
    # (`causal` is static; the block comparison is traced — they can't
    # share one boolean expression.)
    q_start = iq * block_q
    k_start = ik * block_k

    def _block():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_prev = m_sc[:]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # rows at _NEG_INF underflow to 0 exactly
        corr = jnp.exp(m_prev - m_new)
        l_sc[:] = l_sc[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_sc[:] = m_new
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_block)
    else:
        _block()

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_sc[:]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc[:] / denom).astype(o_ref.dtype)
        # logsumexp per row — the one residual the backward needs to
        # rebuild p without the (Tq, Tk) matrix.
        lse_ref[0, 0] = (m_sc[:] + jnp.log(denom))[:, 0]


def _fwd_call(q, k, v, scale, causal):
    bh, t, d = q.shape
    bq, bk = _blocks(t), _blocks(t)
    kernel = partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk
    )
    grid = (bh, t // bq, t // bk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            _row_spec(bq, lambda b, i, j: (b, 0, i)),
        ),
        out_shape=(
            _out_struct((bh, t, d), q.dtype, q),
            _out_struct((bh, 1, t), jnp.float32, q),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=pallas_interpret(),
    )(q, k, v)
    return o, lse[:, 0]


# ---------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k):
    """Grid (BH, nq, nk): dQ for one Q block, accumulated across K."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = iq * block_q
    k_start = ik * block_k

    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(cols <= rows, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # exact probs via saved lse
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_block)
    else:
        _block()

    @pl.when(ik == nk - 1)
    def _emit():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_k):
    """Grid (BH, nk, nq): dK/dV for one K block, accumulated across Q."""
    ik, iq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = ik * block_k

    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(cols <= rows, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # (block_q, block_k)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_block)
    else:
        _block()

    @pl.when(iq == nq - 1)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, scale, causal, g_lse=None):
    bh, t, d = q.shape
    bq, bk = _blocks(t), _blocks(t)
    # delta_i = rowsum(dO ⊙ O): tiny elementwise reduce; XLA fuses it.
    # An lse cotangent folds in here with no kernel change: the shared
    # score gradient is ds = p·(dp − delta + g_lse), and the kernels
    # compute ds = p·(dp − delta'), so delta' = delta − g_lse.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (bh, t)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)

    wide = lambda blk: pl.BlockSpec(
        (1, blk, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM
    )
    row = _row_spec(bq, lambda b, i, j: (b, 0, i))
    other = lambda blk: pl.BlockSpec(
        (1, blk, d), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM
    )
    other_row = _row_spec(bq, lambda b, i, j: (b, 0, j))
    lse, delta = lse[:, None], delta[:, None]  # (bh, 1, t) row layout

    dq = pl.pallas_call(
        partial(_bwd_dq_kernel, scale=scale, causal=causal,
                block_q=bq, block_k=bk),
        grid=(bh, t // bq, t // bk),
        in_specs=[wide(bq), other(bk), other(bk), wide(bq), row, row],
        out_specs=wide(bq),
        out_shape=_out_struct(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=pallas_interpret(),
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                block_q=bq, block_k=bk),
        grid=(bh, t // bk, t // bq),
        in_specs=[other(bq), wide(bk), wide(bk), other(bq),
                  other_row, other_row],
        out_specs=(wide(bk), wide(bk)),
        out_shape=(
            _out_struct(k.shape, k.dtype, k),
            _out_struct(v.shape, v.dtype, v),
        ),
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=pallas_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------
# public entry (custom_vjp over the (BH, T, D)-flattened layout)
# ---------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_flat_lse(q, k, v, scale, causal):
    """``(o, lse)`` over the flattened ``(BH, T, D)`` layout.

    Exposing lse (per-row logsumexp of the scores) with a real VJP is
    what lets :func:`make_ring_flash_attention` combine per-hop partial
    attentions differentiably — the hop weights are ``exp(lse_h − m)``,
    so gradients flow into lse, not just into ``o``.
    """
    return _fwd_call(q, k, v, scale, causal)


def _flash_flat_fwd(q, k, v, scale, causal):
    o, lse = _fwd_call(q, k, v, scale, causal)
    return (o, lse), (q, k, v, o, lse)


def _flash_flat_bwd(scale, causal, res, g):
    q, k, v, o, lse = res
    g_o, g_lse = g
    dq, dk, dv = _bwd_call(
        q, k, v, o, lse, g_o, scale, causal, g_lse=g_lse
    )
    return dq, dk, dv


_flash_flat_lse.defvjp(_flash_flat_fwd, _flash_flat_bwd)


def flash_attention(q, k, v, *, causal: bool = False):
    """Exact blockwise attention; drop-in for
    :func:`ops.ring_attention.dense_attention_reference`.

    ``q, k, v``: ``(batch, seq, heads, head_dim)``, bf16 or f32. Scores
    and the softmax never touch HBM; memory is O(T·D) instead of O(T²).
    Sequences that are a multiple of 128 tile at the MXU edge; shorter
    non-divisible sequences (≤ ``_MAX_WHOLE_BLOCK``) run as one
    whole-sequence block. A LARGE non-divisible T is handled per the
    mask structure: causal inputs are zero-padded up to the tile edge
    and the output sliced back — exact, because the causal mask keeps
    every real query from seeing the appended keys, and the sliced
    rows carry zero cotangent so padded queries contribute nothing to
    dK/dV — while non-causal inputs (where appended keys WOULD be
    attended) raise instead of blowing VMEM at Mosaic compile time.
    """
    b, t, h, d = q.shape
    if t % _BLOCK and t > _MAX_WHOLE_BLOCK:
        if not causal:
            raise ValueError(
                f"flash_attention: non-causal seq_len {t} is neither a "
                f"multiple of {_BLOCK} nor small enough "
                f"(<= {_MAX_WHOLE_BLOCK}) for the whole-sequence block "
                f"path; pad the sequence to a multiple of {_BLOCK} and "
                "mask in the caller"
            )
        pad = -t % _BLOCK
        spec = ((0, 0), (0, pad), (0, 0), (0, 0))
        return flash_attention(
            jnp.pad(q, spec), jnp.pad(k, spec), jnp.pad(v, spec),
            causal=True,
        )[:, :t]
    scale = 1.0 / (d**0.5)
    # (B, T, H, D) -> (B*H, T, D): each (batch, head) pair is an
    # independent attention problem and a grid row.
    to_flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    o, _ = _flash_flat_lse(to_flat(q), to_flat(k), to_flat(v), scale, causal)
    return o.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def make_flash_attention(*, causal: bool = True):
    """An ``attention=`` callable for :class:`models.transformer
    .TransformerLM` using the Pallas kernel on the chip-local sequence.

    TP note (ADVICE r4): the math is per-head-local, but the callable
    runs as one ``pallas_call`` under ``jit`` with no partitioning
    spec, so GSPMD cannot split it over a model axis —
    ``transformer_tp_shardings(..., "auto")`` therefore keeps the
    attention projections replicated when this callable is installed.
    That decision is signaled explicitly via ``head_sharded = False``
    (the same introspection attribute the ring factories set) rather
    than falling out of a missing attribute. For head-parallel TP with
    flash semantics, use :func:`make_ring_flash_attention` with
    ``shard_heads="auto"`` — its ``shard_map`` places one flash kernel
    per model-axis shard.
    """

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=causal)

    attn.head_sharded = False  # single unsharded pallas_call: auto TP
    # must keep q/k/v/proj replicated for this callable
    attn.carries_collectives = False  # safe inside a pipeline stage
    return attn


# ---------------------------------------------------------------------
# ring-flash: sequence parallelism across chips, flash within each hop
# ---------------------------------------------------------------------


def _ring_flash_local(q, k, v, *, axis_name, num_devices, causal, scale):
    """Per-device body under shard_map: the full ring-flash composition.

    Local Q stays put; K/V blocks rotate around the ring
    (``ops/ring_attention.py``'s topology), but each hop's block pair
    is computed by the Pallas flash kernel instead of a materialized
    einsum — so the per-hop ``(T/N, T/N)`` scores live only in VMEM.
    Hops combine through their logsumexps in an online-softmax carry
    (plain jnp, so the whole thing reverse-differentiates: each hop's
    cotangents re-enter the kernel's custom VJP, including the lse
    term).

    Causal structure per hop: a block strictly left of the diagonal is
    plain full attention, the diagonal block is locally-causal (equal
    global offsets make local masking exact), and blocks right of the
    diagonal contribute nothing (lse = -inf sentinel → zero weight).
    """
    b, t_loc, h, d = q.shape
    my = jax.lax.axis_index(axis_name)
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t_loc, d)
    qf = flat(q)

    from multidisttorch_tpu.parallel.collectives import pvary

    m0 = pvary(jnp.full((b * h, t_loc), _NEG_INF, jnp.float32), axis_name)
    l0 = pvary(jnp.zeros((b * h, t_loc), jnp.float32), axis_name)
    acc0 = pvary(
        jnp.zeros((b * h, t_loc, d), jnp.float32), axis_name
    )
    perm = [(i, (i + 1) % num_devices) for i in range(num_devices)]

    def body(carry, step):
        kf, vf, m, l, acc = carry

        def full():
            return _flash_flat_lse(qf, kf, vf, scale, False)

        def diag():
            return _flash_flat_lse(qf, kf, vf, scale, True)

        def skip():
            return (
                jnp.zeros_like(qf),
                jnp.full((b * h, t_loc), _NEG_INF, jnp.float32),
            )

        if causal:
            src = (my - step) % num_devices
            mode = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            o_h, lse_h = jax.lax.switch(mode, [full, diag, skip])
        else:
            o_h, lse_h = full()

        m_new = jnp.maximum(m, lse_h)
        c = jnp.exp(m - m_new)
        w = jnp.exp(lse_h - m_new)
        l_new = l * c + w
        acc_new = acc * c[..., None] + w[..., None] * o_h.astype(jnp.float32)
        kf_next = jax.lax.ppermute(kf, axis_name, perm)
        vf_next = jax.lax.ppermute(vf, axis_name, perm)
        return (kf_next, vf_next, m_new, l_new, acc_new), None

    (_, _, _, l, acc), _ = jax.lax.scan(
        body, (flat(k), flat(v), m0, l0, acc0), jnp.arange(num_devices)
    )
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return (
        out.reshape(b, h, t_loc, d).transpose(0, 2, 1, 3).astype(q.dtype)
    )


@lru_cache(maxsize=None)
def _make_ring_flash_cached(mesh, causal: bool, head_axis=None):
    from jax.sharding import PartitionSpec as P

    from multidisttorch_tpu.parallel.mesh import DATA_AXIS

    num_devices = int(mesh.shape[DATA_AXIS])
    spec = P(None, DATA_AXIS, head_axis, None)

    def fn(q, k, v):
        scale = 1.0 / (q.shape[-1] ** 0.5)
        return jax.shard_map(
            partial(
                _ring_flash_local,
                axis_name=DATA_AXIS,
                num_devices=num_devices,
                causal=causal,
                scale=scale,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            # pallas_call's out_shape carries no VMA annotation, so the
            # varying-axis checker can't type the per-hop kernel
            # results (same constraint as the fused ELBO loss under
            # shard_map — train/steps.py).
            check_vma=False,
        )(q, k, v)

    return jax.jit(fn)


def make_ring_flash_attention(trial, *, causal: bool = False,
                              shard_heads="auto"):
    """Sequence-parallel exact attention with flash-kernel hops.

    Same contract and sharding as
    :func:`ops.ring_attention.make_ring_attention` — ``(batch, seq,
    heads, head_dim)`` with ``seq`` sharded over the trial's data axis,
    and on a 2-D ``(data x model)`` mesh heads additionally sharded
    over the model axis (``shard_heads="auto"``) — but the per-hop
    block computation is the Pallas kernel, so no device ever
    materializes even a ``(T/N, T/N)`` score block in HBM. This is the
    composition the long-context design is built around: ICI ring for
    the cross-chip half, VMEM blocking for the within-chip half.
    Compiled functions are memoized per ``(mesh, causal, head_axis)``
    like :func:`make_ring_attention`. The returned callable exposes
    ``.head_sharded``.
    """
    from multidisttorch_tpu.ops.ring_attention import (
        _resolve_head_axis,
        _wrap_head_check,
    )
    from multidisttorch_tpu.parallel.mesh import TrialMesh

    mesh = trial.mesh if isinstance(trial, TrialMesh) else trial
    head_axis = _resolve_head_axis(mesh, shard_heads)
    return _wrap_head_check(
        _make_ring_flash_cached(mesh, causal, head_axis), mesh, head_axis
    )
