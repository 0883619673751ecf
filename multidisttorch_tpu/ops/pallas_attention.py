"""Blockwise (flash) attention as one pair of Pallas TPU kernels.

Within one chip a dense attention writes the full ``(B, H, Tq, Tk)``
score matrix to HBM and reads it back several times a pass; at
16 x 16 heads x 1,024^2 that was 45% of GPT-2 medium's step. This module
is the exact online-softmax attention that never does: a forward kernel
and ONE fused backward kernel (dQ, dK and dV from a single recomputation
of the probabilities out of the saved per-row logsumexp), wired through
``jax.custom_vjp``. It is what a single-chip ``TransformerLM``,
``LatentMoELM`` or ``GroupedWindowMoELM`` runs when no attention is
injected (``models/transformer.py::_default_causal`` and the two rules
beside ``default_takes_kernel`` say when), what
``make_flash_attention`` hands out, and the hop of
``make_ring_flash_attention``.

What runs where:

- **Layout.** ``(batch, seq, heads, head_dim)`` in and out, as
  ``make_ring_attention``. Head widths of 32, 64 or a multiple of 128
  are read straight from the projections' ``(B, T, H*D)`` array, 128
  lanes (two heads of 64) a block, so no transpose to ``(B, H, T, D)``
  is ever made and every load and store is lane-dense. A head inside a
  block is picked by zeroing the other heads' lanes of q (and dO): a
  128-deep contraction costs the MXU what a 64-deep one does. Other
  widths (the tests' 8, 16, 20) run the same kernels over a flattened
  ``(B*H, T, D)`` copy.
- **Two widths.** q and k share one width and v, the output and its
  cotangent another (latent attention: 128 + 64 beside 128). Through
  :func:`flash_attention` every array is read at its own width, one
  head a lane block; a q/k width that is not whole lanes (192) is
  padded with zero lanes to the next 128 (256), which changes no
  score, and the scores are divided by the root of the width q came
  with. Since PR 30 no benchmark cell runs that path: it is what an
  injected ``(q, k, v)`` attention and the tests get. A one-chip
  ``LatentMoELM`` given no attention calls :func:`latent_attention`,
  a pair of kernels of its own (``latent_fwd``, ``latent_bwd``) over
  the same helpers, with q and k as the two parts their projections
  make: the 128-wide parts a head a lane block, q's rotary part two
  heads a lane block and rotated as a block is loaded, the one rotary
  key read once for all heads, a score the sum of two products. There
  nothing 192 or 256 wide exists in HBM, in any pass
  (``models/latent_moe.py::LatentMoEBlock`` says when).
- **Blocks.** In the ``(q, k, v)`` and the latent kernels one grid step
  is one query block against the whole K/V sequence, which stays in
  VMEM (256 KB each at T=1,024; so they stop near T = 4,096); a loop
  inside the kernel walks the K/V blocks below the diagonal unmasked
  and the diagonal block masked, so nothing above the diagonal is
  fetched or computed. The block edge follows T: the largest of
  ``_BLOCKS`` that divides it, else the whole sequence.
- **Grouped KV heads, a window, K and V by the block.** A third pair,
  ``grouped_fwd`` and ``grouped_bwd`` (:func:`grouped_attention`; a
  one-chip ``GroupedWindowMoELM``'s, :func:`grouped_takes_kernel` says
  when), takes fewer KV heads than query heads at head width 128, ``(B,
  T, H*128)`` and ``(B, T, Hkv*128)`` flat from the projections, and an
  optional sliding window. A grid step is one (query block, K/V block)
  tile for all the query heads of a group against the group's one K/V
  block, fetched once for them; the tiles are listed at trace time
  (:func:`_visits`): only those that hold a pair with ``j <= i`` and
  ``i - j < window``, nothing outside fetched or multiplied, in the
  one fused backward kernel as well. A tile wholly inside runs whole
  and unmasked; the diagonal, and the far edge of a window of whole
  blocks, run in sub-steps of queries, each against only the keys its
  queries keep (:func:`_sub_steps`: the diagonal as the ``(q, k, v)``
  kernels walk theirs, the far edge its mirror), so the triangle each
  keeps is all that is multiplied; the far edge of any other window
  runs whole under the mask. K and V come
  by the block, so T = 16,384 compiles (the forward's VMEM does not
  grow with T; the backward keeps one KV head's float32 dK and dV, 1.5
  KB a token with their outgoing copies). q of a window layer is
  rotated as a block is loaded (the halves convention), as the latent
  kernels rotate theirs.
- **Dtypes.** Operands go into the MXU as they come (bf16 stays bf16),
  every matmul accumulates in f32, the softmax statistics and the
  logsumexp are f32, and probabilities and score gradients are cast to
  the operand dtype for the second matmuls, as XLA's dense path does.
- **Under rematerialization.** The backward kernel needs q, k, v, the
  forward's output and its logsumexp. A block under ``nn.remat``
  rebuilds q, k and v from its input; the output (bf16 ``(B, T,
  H*Dv)``) and the logsumexp (f32 ``(B, H, T)``) carry the names
  ``SAVED_OUT`` and ``SAVED_LSE``, and the models' remat rule
  (``models/transformer.py::remat_block``) saves what is so named, so
  the recomputed forward of a block holds no kernel: one forward and
  one backward call a layer and step. The names are given in the
  ``custom_vjp``'s forward rule, to the residuals themselves:
  differentiation replaces the call by that rule before a
  checkpoint's policy sorts the block's values, so the policy meets
  them there. Names on the call's outputs at the call site would be
  copies of what the backward rule holds, and the call would be run
  again for the residuals (the race is in CHANGES.md, PR 28).
  Without ``jax.checkpoint``, or under one whose policy does not know
  the names, they are inert.

The kernels compile through Mosaic; the CPU test suite runs them in
interpreter mode by asking for it (``ops/pallas_mode.py``).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multidisttorch_tpu.ops.pallas_mode import pallas_interpret


# Query/key block edges, best first: the edge is the largest that
# divides T; a T that none divides runs as one whole-sequence block.
# Under the causal mask a query block meets its own K/V block in steps
# of some queries, each step against the keys up to its own: half a
# block a step forward (wide matmuls, 3/4 of the square), 256 queries
# backward (5/8 of it). Both from the chip race at 16 x 1,024 and 64 x
# 256, bf16, head width 64 (PERF.md section 6): smaller blocks lose to
# the per-block work, one step a block to the triangle it wastes.
_BLOCKS = (1024, 512, 256, 128)
_LANES = 128  # the MXU's and a vreg's width
_NEG_INF = -1e30  # finite sentinel: -inf rows poison exp() on the VPU

# Largest T no block edge divides that may run as one whole-sequence
# block. The (T, T) f32 score tile is 4 MB at T=1024; at T=8256 it alone
# is 272 MB and Mosaic refuses the kernel. Above this, causal inputs are
# padded to the tile edge (exact, see flash_attention) and non-causal
# inputs get a clear error.
_MAX_WHOLE_BLOCK = 1024


def _block_for(t: int) -> int:
    return next((b for b in _BLOCKS if t % b == 0), t)


def _fwd_step(blk: int) -> int:
    return blk // 2 if blk % 256 == 0 else blk


def _bwd_step(blk: int) -> int:
    return 256 if blk % 256 == 0 else blk


def default_takes_kernel(
    device_kind: str, num_devices: int, seq_len: int, num_heads: int,
    head_dim: int, v_head_dim: int | None = None,
) -> bool:
    """Whether a model that was given no attention runs the ``(q, k,
    v)`` kernel (``models/transformer.py::_default_causal`` asks, with
    what tracing shows of the operands' placement) or XLA's dense path.
    :func:`latent_takes_kernel` and :func:`grouped_takes_kernel` ask
    the same of latent attention's parts and of grouped KV heads, each
    with its own widths on top: what runs where after them is

    - ``TransformerLM``: ``flash_fwd``, ``flash_bwd``; a sequence's
      whole K and V in VMEM;
    - ``LatentMoELM``: ``latent_fwd``, ``latent_bwd``; likewise;
    - ``GroupedWindowMoELM``: ``grouped_fwd``, ``grouped_bwd``; one
      K/V block a grid step, the blocks outside a window skipped;

    and everywhere else (the CPU, several chips, a T or widths a rule
    refuses) XLA's dense path, for grouped heads in query blocks
    (:func:`blocked_window_attention`). The rule of this function:

    - a TPU: Mosaic compiles for nothing else, and the CPU suite's
      interpreter is for tests that inject the kernel;
    - operands on one device: a bare ``pallas_call`` has no
      partitioning rule, so over a data- or model-parallel mesh GSPMD
      would gather q, k and v onto every chip;
    - a T that 128 divides, from 256 on: the shortest length raced
      against dense on the chip (64 x 256: 1.8 against 2.7 ms a layer;
      16 x 1,024: 2.3 against 10.6; PERF.md section 6);
    - heads read straight from the projections' array, at the widths
      run on the chip: 128, or 64 in pairs (25 heads of 64 would take
      the flattened layout, which was not raced), or latent
      attention's 192 for q and k beside 128 for v, which
      :func:`flash_attention` pads to 256 and 128 lanes a head and
      :func:`latent_attention` takes as the parts 128 + 64
      (:func:`latent_takes_kernel`).
    """
    widths = (head_dim, head_dim if v_head_dim is None else v_head_dim)
    return (
        device_kind.startswith("TPU")
        and num_devices == 1
        and seq_len >= 256
        and seq_len % _BLOCKS[-1] == 0
        and widths in ((64, 64), (128, 128), (192, 128))
        and (widths[0] != 64 or _heads_per_block(num_heads, 64) is not None)
    )


def _heads_per_block(h: int, d: int) -> int | None:
    """How many heads share one lane block of the ``(B, T, H*D)`` array;
    ``None`` when the width does not pack and the flattened
    ``(B*H, T, D)`` layout is used."""
    if d % _LANES == 0:
        return 1
    g = _LANES // d
    return g if d in (32, 64) and h % g == 0 else None


def _out_struct(shape, dtype, like):
    """``ShapeDtypeStruct`` carrying the operands' varying-mesh-axes
    type. Under a ``check_vma=True`` ``shard_map`` (e.g. the pipeline's
    staged forward, parallel/pipeline.py) a pallas_call must declare
    its outputs' VMA explicitly or tracing rejects it; propagating the
    input's vma makes the kernels VMA-transparent (outside shard_map
    ``typeof(x).vma`` is empty and this is a no-op)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


# ---------------------------------------------------------------------
# the kernels. Arrays are (N, T, C*W): C lane blocks of W = g*d lanes,
# g heads of width d each; q and k are dk wide and v, the output and its
# cotangent dv (one head a block where the two differ). Per-row
# statistics (logsumexp, delta) are
# (N, C, g, T): T rides the lanes and the (g, block) tile's first dim is
# the whole array dim, which is what the TPU's 8x128 block rule wants.
# ---------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _dot(a, b, dims=_NN, *, interpret=False):
    """f32-accumulated matmul of the operands as they come. A caller's
    ``default_matmul_precision`` (``"highest"`` in the f32 parity
    checks) speaks of f32 operands and is left to them; bf16 operands
    have the MXU's one precision, and Mosaic refuses a request for more
    ("Bad lhs type"). The interpreter runs on XLA:CPU, which lacks a
    bf16 x bf16 = f32 dot for some of the shapes met here, so there the
    operands are widened first: the same numbers, since a product of
    two bf16 values is exact in f32 and both ways accumulate in f32."""
    if interpret:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=jnp.float32
    )


def _head_lanes(g: int, d: int, rows: int):
    """One boolean ``(rows, g*d)`` lane mask per head of the block, or
    ``[None]`` when the block is a single head."""
    if g == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, g * d), 1)
    return [(lane >= h * d) & (lane < (h + 1) * d) for h in range(g)]


def _only(x, lanes):
    """``x`` with every other head's lanes zeroed: contracting it over
    the whole block is the one head's product."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _merge(per_head, lanes):
    """Each head's own lanes out of its ``(rows, W)`` array."""
    out = per_head[0]
    for x, m in zip(per_head[1:], lanes[1:]):
        out = jnp.where(m, x, out)
    return out


def _transposed(x):
    """``x.T`` through f32: Mosaic transposes 32-bit tiles."""
    return x.astype(jnp.float32).T.astype(x.dtype)


def _walk(tile, i, nk, blk, sub, causal):
    """Run ``tile(j, at, n, nkeys, own)`` — the block's queries
    ``at..at+n`` against the first ``nkeys`` keys of K/V block ``j`` —
    over everything query block ``i`` sees: all ``nk`` blocks whole,
    or under the causal mask blocks ``0..i-1`` whole and block ``i``
    in steps of ``sub`` queries, each against the keys up to its own
    (``own``: the last ``n`` keys are the queries themselves and get
    the triangle). Nothing above the diagonal is touched."""

    def body(j, carry):
        tile(j, 0, blk, blk, False)
        return carry

    jax.lax.fori_loop(0, i if causal else nk, body, 0)
    if causal:
        for at in range(0, blk, sub):
            tile(i, at, sub, at + sub, True)


def _triangle(s, n, keys_axis):
    """Mask the queries' own ``n`` keys, the last ``n`` along
    ``keys_axis`` of ``s``, to key <= query."""
    key = jax.lax.broadcasted_iota(jnp.int32, (n, n), keys_axis)
    query = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1 - keys_axis)
    if s.shape[keys_axis] == n:
        return jnp.where(key <= query, s, _NEG_INF)
    seen, own = jnp.split(s, [s.shape[keys_axis] - n], axis=keys_axis)
    own = jnp.where(key <= query, own, _NEG_INF)
    return jnp.concatenate([seen, own], axis=keys_axis)


def _one_branch(body):
    """A kernel whose whole ``body(i, *refs)`` (``i``: the query block)
    sits in a branch that is always taken. Under a ``check_vma``
    ``shard_map`` (a pipeline stage) the Pallas interpreter binds a
    kernel's top-level reads and writes again and rejects a varying
    block indexed by plain constants; what is inside a branch it leaves
    as traced, and there ``program_id`` is not resolved any more, hence
    ``i`` from out here. Mosaic pays one predicated region."""

    def kernel(*refs, **statics):
        i = pl.program_id(2)
        pl.when(i >= 0)(lambda: body(i, *refs, **statics))

    return kernel


def _start_softmax(i, v_ref, v_t, acc_t, m_sc, l_sc, blk, nk):
    """What a forward grid step begins with: V transposed into ``v_t``
    on a sequence's first query block, and the running maximum, sum and
    output of this block's online softmax at their starts."""

    @pl.when(i == 0)
    def _v_transposed():
        for j in range(nk):
            v_t[j] = _transposed(v_ref[0, j * blk:(j + 1) * blk, :])

    m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_t[...] = jnp.zeros_like(acc_t)


def _softmax_step(s, v_h, h, cols, acc_t, m_sc, l_sc, dot):
    """One tile of head ``h``'s online softmax: the scores ``s``, keys x
    queries ``cols`` and masked already, and the keys' values ``v_h``
    ``(dv, keys)`` into the running maximum, sum and output."""
    m_prev = m_sc[h, :, cols]  # (1, n)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)  # masked entries underflow to 0 exactly
    corr = jnp.exp(m_prev - m_new)
    l_sc[h, :, cols] = l_sc[h, :, cols] * corr + jnp.sum(p, axis=0, keepdims=True)
    acc_t[h, :, cols] = acc_t[h, :, cols] * corr + dot(v_h, p.astype(v_h.dtype))
    m_sc[h, :, cols] = m_new


def _finish_softmax(g, acc_t, m_sc, l_sc, o_ref, lse_ref):
    """The block's output, heads side by side, and its logsumexp."""
    out_t = []
    for h in range(g):
        l = l_sc[h]
        l = jnp.where(l > 0, l, 1.0)
        out_t.append(acc_t[h] / l)
        # logsumexp per row: the one residual the backward needs to
        # rebuild p without the (Tq, Tk) matrix.
        lse_ref[0, 0, h] = (m_sc[h] + jnp.log(l))[0]
    o_ref[0] = jnp.concatenate(out_t, axis=0).T.astype(o_ref.dtype)


@_one_branch
def _fwd_kernel(i, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_t, m_sc, l_sc, v_t,
                *, scale, fold, causal, g, dk, dv, blk, sub, nk, interpret):
    """Grid (N, C, nq), nq sequential: one query block's online softmax
    over the K/V blocks it sees, each head of the lane block in turn.

    Everything is kept keys x queries: the scores are ``k @ q.T``, so
    the softmax's max and sum run down the sublanes (plain VPU work; a
    reduction along the lanes goes through the XLU and was most of the
    forward's time), the statistics are lane-major rows that the
    logsumexp is stored from as they are, and the output accumulates as
    ``(d, blk)`` a head from ``v.T @ p.T``. q is transposed once a grid
    step, V once a sequence, the output once on its way out."""

    dot = partial(_dot, interpret=interpret)

    _start_softmax(i, v_ref, v_t, acc_t, m_sc, l_sc, blk, nk)
    q = q_ref[0]  # (blk, W)
    if fold:  # a power of two: exact in any float dtype
        q = q * scale
    q_t = [_transposed(_only(q, m)) for m in _head_lanes(g, dk, blk)]

    def tile(j, at, n, nkeys, own):
        k = k_ref[0, pl.ds(pl.multiple_of(j * blk, blk), nkeys), :]
        cols = slice(at, at + n)
        for h in range(g):
            q_th = q_t[h][:, cols]
            s = dot(k, q_th)  # (keys, queries) f32
            if not fold:
                s = s * scale
            if own:
                s = _triangle(s, n, 0)
            v_h = v_t[j, h * dv:(h + 1) * dv, :nkeys]  # (dv, keys)
            _softmax_step(s, v_h, h, cols, acc_t, m_sc, l_sc, dot)

    _walk(tile, i, nk, blk, sub, causal)
    _finish_softmax(g, acc_t, m_sc, l_sc, o_ref, lse_ref)


@_one_branch
def _bwd_kernel(i, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, g_lse_ref,
                dq_ref, dk_ref, dv_ref, dk_t, dv_t, dq_acc,
                *, scale, fold, causal, g, dk, dv, blk, sub, nk, interpret):
    """Grid (N, C, nq), nq sequential: one query block against the K/V
    blocks it sees, queries x keys. Scores, probabilities and their
    gradients are made once a tile and feed all three products. dQ
    leaves with its block; dK and dV accumulate transposed, ``(W,
    blk)`` a K/V block, across the query blocks and are written on the
    last one: with q and dO transposed once a grid step, every matmul
    of a tile is a plain ``a @ b`` or ``a @ b.T``."""

    dot = partial(_dot, interpret=interpret)

    @pl.when(i == 0)
    def _init():
        dk_t[...] = jnp.zeros_like(dk_t)
        dv_t[...] = jnp.zeros_like(dv_t)

    q, do = q_ref[0], do_ref[0]  # (blk, W)
    if fold:
        q = q * scale
    lanes = _head_lanes(g, dk, blk)
    q_h = [_only(q, m) for m in lanes]
    do_h = [_only(do, m) for m in _head_lanes(g, dv, blk)]
    q_t = _transposed(q)  # (W, blk): head h is rows h*d..
    do_t = do.astype(jnp.float32).T
    # delta = rowsum(dO * O) a head, made here from the transposed
    # blocks, where a head is a run of sublanes: no pass of XLA's over
    # an f32 product in HBM. A cotangent of the logsumexp (ring-flash's
    # hop weights) folds in at no cost: the score gradient is
    # ds = p * (dp - delta + g_lse).
    d_o = do_t * o_ref[0].astype(jnp.float32).T
    do_t = do_t.astype(do.dtype)
    lse, delta = [], []
    for h in range(g):
        row = jnp.sum(d_o[h * dv:(h + 1) * dv], axis=0) - g_lse_ref[0, 0, h]
        delta.append(row[:, None])  # (blk, 1)
        lse.append(lse_ref[0, 0, h][:, None])
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(j, at, n, nkeys, own):
        keys = pl.ds(pl.multiple_of(j * blk, blk), nkeys)
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]
        rows = slice(at, at + n)
        for h in range(g):
            head_k = slice(h * dk, (h + 1) * dk)
            head_v = slice(h * dv, (h + 1) * dv)
            s = dot(q_h[h][rows], k, _NT)  # (queries, keys) f32
            if not fold:
                s = s * scale
            if own:
                s = _triangle(s, n, 1)
            p = jnp.exp(s - lse[h][rows])  # exact probabilities via saved lse
            dp = dot(do_h[h][rows], v, _NT)
            ds = (p * (dp - delta[h][rows])).astype(k.dtype)
            dv_t[j, head_v, :nkeys] = dv_t[j, head_v, :nkeys] + dot(
                do_t[head_v, rows], p.astype(v.dtype)
            )
            dk_t[j, head_k, :nkeys] = dk_t[j, head_k, :nkeys] + dot(
                q_t[head_k, rows], ds
            )
            dq_acc[h, rows, :] = dq_acc[h, rows, :] + dot(ds, k)

    _walk(tile, i, nk, blk, sub, causal)

    dq = _merge([dq_acc[h] for h in range(g)], lanes) * scale
    dq_ref[0] = dq.astype(dq_ref.dtype)

    @pl.when(i == nk - 1)
    def _emit():
        for j in range(nk):
            rows = slice(j * blk, (j + 1) * blk)
            dk = dk_t[j].T  # (blk, W); q carried the scale if folded
            if not fold:
                dk = dk * scale
            dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_t[j].T.astype(dv_ref.dtype)


def _specs(t, g, blk):
    """``block(w)`` and ``whole(w)``: one query block, and the whole
    sequence, of an array whose lane blocks are ``w`` wide; ``stat``:
    a query block of the per-row statistics."""
    block = lambda w: pl.BlockSpec((1, blk, w), lambda n, c, i: (n, i, c),
                                   memory_space=pltpu.VMEM)
    whole = lambda w: pl.BlockSpec((1, t, w), lambda n, c, i: (n, 0, c),
                                   memory_space=pltpu.VMEM)
    stat = pl.BlockSpec((1, 1, g, blk), lambda n, c, i: (n, c, 0, i),
                        memory_space=pltpu.VMEM)
    return block, whole, stat


def _params(t, w, blk, itemsize, lane_blocks="parallel"):
    """Double-buffered operands and results, the f32 accumulators and a
    handful of (blk, blk) f32 tiles, with room to spare: Mosaic's own
    default (16 MiB) is too small from T=4,096 on. ``resident`` grows
    with T for the ``(q, k, v)`` and the latent kernels, which keep a
    sequence's whole K and V (and dK, dV) in VMEM and so stop where the
    100 MiB cap does, a little past T = 4,096 at these widths; the
    grouped kernels fetch K and V by the block and size their own
    (``_grouped_params``)."""
    resident = 8 * t * w * itemsize + 2 * t * w * 4
    tiles = 8 * blk * blk * 4 + 16 * blk * w * 4
    return pltpu.CompilerParams(
        # what a sequence's first query block sets up, the later use
        dimension_semantics=("parallel", lane_blocks, "arbitrary"),
        vmem_limit_bytes=int(
            min(max(32 << 20, 2 * (resident + tiles)), 100 << 20)
        ),
    )


def _statics(scale, causal, g, dk, dv, blk, t, sub, interpret):
    # a power-of-two scale (head widths 16, 64, 256) is folded into q
    return dict(
        scale=scale, fold=math.frexp(scale)[0] == 0.5, causal=causal,
        g=g, dk=dk, dv=dv, blk=blk, sub=sub, nk=t // blk, interpret=interpret,
    )


# One traced and lowered function for every layer of a model: the inner
# jit makes the 24 blocks of a step share it instead of tracing and
# lowering some hundred kernel bodies one by one.
@partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fwd_call(q, k, v, scale, causal, g, dk, dv, blk, interpret):
    n, t, cw = q.shape
    wk, wv, c = g * dk, g * dv, cw // (g * dk)
    block, whole, stat = _specs(t, g, blk)
    return pl.pallas_call(
        partial(
            _fwd_kernel,
            **_statics(scale, causal, g, dk, dv, blk, t, _fwd_step(blk), interpret),
        ),
        grid=(n, c, t // blk),
        in_specs=[block(wk), whole(wk), whole(wv)],
        out_specs=(block(wv), stat),
        out_shape=(
            _out_struct(v.shape, q.dtype, q),
            _out_struct((n, c, g, t), jnp.float32, q),
        ),
        scratch_shapes=[
            pltpu.VMEM((g, dv, blk), jnp.float32),  # output, transposed
            pltpu.VMEM((g, 1, blk), jnp.float32),  # running max
            pltpu.VMEM((g, 1, blk), jnp.float32),  # running sum
            pltpu.VMEM((t // blk, wv, blk), v.dtype),  # V, transposed
        ],
        compiler_params=_params(t, wk, blk, q.dtype.itemsize),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


@partial(jax.jit, static_argnums=(7, 8, 9, 10, 11, 12, 13))
def _bwd_call(q, k, v, o, lse, do, g_lse, scale, causal, g, dk, dv, blk, interpret):
    n, t, cw = q.shape
    wk, wv, c = g * dk, g * dv, cw // (g * dk)
    block, whole, stat = _specs(t, g, blk)
    nk = t // blk
    return pl.pallas_call(
        partial(
            _bwd_kernel,
            **_statics(scale, causal, g, dk, dv, blk, t, _bwd_step(blk), interpret),
        ),
        grid=(n, c, nk),
        in_specs=[block(wk), whole(wk), whole(wv), block(wv), block(wv), stat, stat],
        out_specs=(block(wk), whole(wk), whole(wv)),
        out_shape=tuple(_out_struct(x.shape, x.dtype, x) for x in (q, k, v)),
        scratch_shapes=[
            pltpu.VMEM((nk, wk, blk), jnp.float32),  # dK, transposed
            pltpu.VMEM((nk, wv, blk), jnp.float32),  # dV, transposed
            pltpu.VMEM((g, blk, wk), jnp.float32),  # dQ, all lanes a head
        ],
        compiler_params=_params(t, wk, blk, q.dtype.itemsize),
        interpret=interpret,
        name="flash_bwd",
    )(q, k, v, o, do, lse, g_lse.astype(jnp.float32))


# ---------------------------------------------------------------------
# custom_vjp over the kernels' (N, T, C*W) layout
# ---------------------------------------------------------------------


# What the forward kernel produced, by name: the output and the per-row
# logsumexp are all the backward kernel needs beside q, k and v, so a
# rematerialised block that saves these two
# (``models/transformer.py::remat_block``) does not run the forward
# kernel again.
SAVED_OUT = "flash_attention_out"
SAVED_LSE = "flash_attention_lse"


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, scale, causal, g, dk, dv, blk):
    """``(o, lse)``: ``o`` as ``v``, ``lse`` ``(N, C, g, T)``.

    Exposing lse (per-row logsumexp of the scores) with a real VJP is
    what lets :func:`make_ring_flash_attention` combine per-hop partial
    attentions differentiably — the hop weights are ``exp(lse_h − m)``,
    so gradients flow into lse, not just into ``o``.
    """
    return _fwd_call(q, k, v, scale, causal, g, dk, dv, blk, pallas_interpret())


def _flash_lse_fwd(q, k, v, scale, causal, g, dk, dv, blk):
    o, lse = _flash_lse(q, k, v, scale, causal, g, dk, dv, blk)
    o, lse = checkpoint_name(o, SAVED_OUT), checkpoint_name(lse, SAVED_LSE)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(scale, causal, g, dk, dv, blk, res, cot):
    q, k, v, o, lse = res
    g_o, g_lse = cot
    return _bwd_call(
        q, k, v, o, lse, g_o, g_lse, scale, causal, g, dk, dv, blk,
        pallas_interpret(),
    )


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _attend(q, k, v, *, causal: bool, block: int | None = None,
            scale: float | None = None):
    """``(o, lse)`` for ``(B, T, H, D)`` operands: ``o`` as ``v``,
    ``lse`` ``(B, H, T)`` f32. Picks the layout the head widths allow
    (q and k of one width, v of its own) and the block edge T allows;
    ``scale`` multiplies the scores, ``1/sqrt(D)`` of q left out."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    blk = _block_for(t) if block is None else block
    if t % blk:
        raise ValueError(f"block {blk} does not divide seq_len {t}")
    if scale is None:
        scale = 1.0 / (dk**0.5)
    if dk == dv:
        g = _heads_per_block(h, dk)
    else:  # one head a lane block, each array at its own width
        g = 1 if dk % _LANES == 0 and dv % _LANES == 0 else None
    if g is not None:  # the projections' own layout, a free reshape
        flat = lambda x: x.reshape(b, t, h * x.shape[-1])
        o, lse = _flash_lse(flat(q), flat(k), flat(v), scale, causal, g, dk, dv, blk)
        return o.reshape(b, t, h, dv), lse.reshape(b, h, t)
    # (B, T, H, D) -> (B*H, T, D): each (batch, head) pair is a grid row
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])
    o, lse = _flash_lse(flat(q), flat(k), flat(v), scale, causal, 1, dk, dv, blk)
    return o.reshape(b, h, t, dv).transpose(0, 2, 1, 3), lse.reshape(b, h, t)


def flash_attention(q, k, v, *, causal: bool = False, block: int | None = None):
    """Exact blockwise attention; drop-in for
    :func:`ops.ring_attention.dense_attention_reference`.

    ``q, k, v``: ``(batch, seq, heads, head_dim)``, bf16 or f32. Scores
    and the softmax never touch HBM; memory is O(T·D) instead of O(T²).
    q and k may be wider than v (latent attention's 192 beside 128):
    they are then padded with zero lanes to a multiple of 128, which
    leaves every score as it was, the scores are still divided by the
    root of the width they came with, and the kernels read q and k at
    the padded width and v, the output and its cotangent at v's.
    ``block`` is the query/key block edge; left out, it is the largest
    of ``_BLOCKS`` that divides T. A T that 128 does not divide
    is handled per the mask structure: causal inputs are zero-padded up
    to the tile edge and the output sliced back — exact, because the
    causal mask keeps every real query from seeing the appended keys,
    and the sliced rows carry zero cotangent so padded queries
    contribute nothing to dK/dV — while non-causal inputs (where
    appended keys WOULD be attended) run as one whole-sequence block up
    to ``_MAX_WHOLE_BLOCK`` and raise beyond it instead of blowing VMEM
    at Mosaic compile time.
    """
    t, dk = q.shape[1], q.shape[-1]
    if dk != v.shape[-1] and dk % _LANES:
        lanes = ((0, 0), (0, 0), (0, 0), (0, -dk % _LANES))
        return _attend(
            jnp.pad(q, lanes), jnp.pad(k, lanes), v, causal=causal,
            block=block, scale=1.0 / (dk**0.5),
        )[0]
    if block is None and t % _BLOCKS[-1]:
        if causal and t > _MAX_WHOLE_BLOCK:
            pad = -t % _BLOCKS[-1]
            spec = ((0, 0), (0, pad), (0, 0), (0, 0))
            return flash_attention(
                jnp.pad(q, spec), jnp.pad(k, spec), jnp.pad(v, spec),
                causal=True,
            )[:, :t]
        if t > _MAX_WHOLE_BLOCK:
            raise ValueError(
                f"flash_attention: non-causal seq_len {t} is neither a "
                f"multiple of {_BLOCKS[-1]} nor small enough "
                f"(<= {_MAX_WHOLE_BLOCK}) for the whole-sequence block "
                f"path; pad the sequence to a multiple of {_BLOCKS[-1]} "
                "and mask in the caller"
            )
    return _attend(q, k, v, causal=causal, block=block)[0]


def make_flash_attention(*, causal: bool = True):
    """An ``attention=`` callable for :class:`models.transformer
    .TransformerLM`: :func:`flash_attention` on the chip-local
    sequence, whatever its length. It is the code a single-chip model
    runs by default on the TPU (``_default_causal``); injecting it asks
    for the kernel where the default would fall back to dense (a T that
    128 does not divide, the CPU interpreter).

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
# latent attention: q and k as the two parts their projections make
# ---------------------------------------------------------------------

_ROPE = 64  # the rotary part's width: two heads a lane block


def latent_takes_kernel(
    device_kind: str, num_devices: int, seq_len: int, num_heads: int,
    nope_dim: int, rope_dim: int, v_head_dim: int,
) -> bool:
    """Whether a latent-attention block that was given no attention
    hands :func:`latent_attention` the parts of q and k as its
    projections make them (``models/latent_moe.py::LatentMoEBlock``
    asks while tracing): where :func:`default_takes_kernel` takes the
    assembled widths, and the parts are the ones the kernels tile."""
    return default_takes_kernel(
        device_kind, num_devices, seq_len, num_heads, nope_dim + rope_dim, v_head_dim
    ) and _latent_widths_tile(num_heads, nope_dim, rope_dim, v_head_dim)


def _latent_widths_tile(h: int, dn: int, dr: int, dv: int) -> bool:
    """One head a lane block of the 128-wide parts, the heads of the
    rotary part in pairs."""
    return dr == _ROPE and h % (_LANES // _ROPE) == 0 and dn % _LANES == 0 and dv % _LANES == 0


# The kernels. A grid step is the ``g = 2`` heads of one lane block of
# q's rotary part: ``(N, T, H*dn)`` arrays are read ``g*dn`` lanes a
# step, a head a whole lane block of them, q's rotary part ``(N, T,
# H*64)`` 128 lanes a step as ``_fwd_kernel`` reads two 64-wide heads,
# and the one rotary key, side by side ``g`` times ``(N, T, 128)``, is
# the same block for every pair of heads: copy ``h`` of it is head
# ``h``'s key. A score is the sum of two products, each accumulated in
# f32; everything after it is the kernels' above. The heads of a step
# are a loop, not a copy of the code a head (a head's lanes are cut
# from the refs at a traced multiple of 128): the compiled kernels are
# the size of one head's, and a step program holds a pair a layer.


def _head_at(h, width):
    """Head ``h``'s ``width`` lanes or sublanes of a ref, ``h`` traced."""
    return pl.ds(pl.multiple_of(h * width, width), width)


def _rope_of(qr, h):
    """``qr``, q's rotary block, with every head's lanes but ``h``'s
    zeroed."""
    lane = jax.lax.broadcasted_iota(jnp.int32, qr.shape, 1)
    return _only(qr[...], lane // _ROPE == h)


def _swap_pairs(x):
    """Lanes ``2i`` and ``2i + 1`` of a ``(rows, 128)`` block exchanged:
    two rotations of the lanes on the XLU. The exchange is its own
    transpose."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane % 2 == 0, pltpu.roll(x, _LANES - 1, 1), pltpu.roll(x, 1, 1))


def _rotated(x, cos_ref, sin_ref):
    """The pairs ``(2i, 2i + 1)`` of ``x`` rotated by the block's
    angles, float32: ``cos_ref`` holds a pair's cosine at both lanes,
    ``sin_ref`` its sine negated at the even one."""
    x = x.astype(jnp.float32)
    return x * cos_ref[...] + _swap_pairs(x) * sin_ref[...]


def _rotated_back(g, cos_ref, sin_ref):
    """The transpose of :func:`_rotated`: the gradient of the rotated
    block taken to the block as it came."""
    return g * cos_ref[...] + _swap_pairs(g * sin_ref[...])


def _latent_fwd_kernel(qn_ref, qr_ref, cos_ref, sin_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                       acc_t, m_sc, l_sc, v_t, qr,
                       *, scale, causal, g, dn, dv, blk, sub, nk, interpret):
    """Grid (N, H/g, nq), nq sequential; keys x queries as
    ``_fwd_kernel``."""
    i = pl.program_id(2)
    dot = partial(_dot, interpret=interpret)
    _start_softmax(i, v_ref, v_t, acc_t, m_sc, l_sc, blk, nk)
    qr[...] = _rotated(qr_ref[0], cos_ref, sin_ref).astype(qr.dtype)

    def head(h, carry):
        nope = _head_at(h, dn)
        qn_t = _transposed(qn_ref[0, :, nope])  # (dn, blk)
        qr_t = _transposed(_rope_of(qr, h))  # (128, blk), the other head's rows zero

        def tile(j, at, n, nkeys, own):
            keys = pl.ds(pl.multiple_of(j * blk, blk), nkeys)
            cols = slice(at, at + n)
            s = dot(kn_ref[0, keys, nope], qn_t[:, cols]) + dot(kr_ref[0, keys, :], qr_t[:, cols])
            s = s * scale
            if own:
                s = _triangle(s, n, 0)
            v_h = v_t[j, _head_at(h, dv), :nkeys]  # (dv, keys)
            _softmax_step(s, v_h, h, cols, acc_t, m_sc, l_sc, dot)

        _walk(tile, i, nk, blk, sub, causal)
        return carry

    jax.lax.fori_loop(0, g, head, 0)
    _finish_softmax(g, acc_t, m_sc, l_sc, o_ref, lse_ref)


def _latent_bwd_kernel(qn_ref, qr_ref, cos_ref, sin_ref, kn_ref, kr_ref, v_ref, o_ref, do_ref,
                       lse_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                       dkn_t, dkr_t, dv_t, dqn_acc, dqr_acc, qr, qr_t,
                       *, scale, causal, g, dn, dv, blk, sub, nk, interpret):
    """Grid (N, H/g, nq), both inner axes sequential; queries x keys as
    ``_bwd_kernel``. The rotary key's gradient accumulates over the
    query blocks and over the pairs of heads of a sequence, head ``h``
    of every pair into rows ``h*64..`` (the gradient of its copy of the
    key), and is written on the sequence's last grid step."""
    c, i = pl.program_id(1), pl.program_id(2)
    dot = partial(_dot, interpret=interpret)

    @pl.when(i == 0)
    def _init():
        dkn_t[...] = jnp.zeros_like(dkn_t)
        dv_t[...] = jnp.zeros_like(dv_t)

    @pl.when((i == 0) & (c == 0))
    def _init_shared():
        dkr_t[...] = jnp.zeros_like(dkr_t)

    qr[...] = _rotated(qr_ref[0], cos_ref, sin_ref).astype(qr.dtype)
    qr_t[...] = _transposed(qr[...])  # (128, blk): head h is rows h*64..
    dqn_acc[...] = jnp.zeros_like(dqn_acc)
    dqr_acc[...] = jnp.zeros_like(dqr_acc)

    def head(h, carry):
        nope, rope, vals = _head_at(h, dn), _head_at(h, _ROPE), _head_at(h, dv)
        qn, do = qn_ref[0, :, nope], do_ref[0, :, vals]  # (blk, dn), (blk, dv)
        qr_h = _rope_of(qr, h)
        qn_t = _transposed(qn)
        do_t = do.astype(jnp.float32).T
        # delta = rowsum(dO * O), from the transposed blocks as ``_bwd_kernel``'s
        delta = jnp.sum(do_t * o_ref[0, :, vals].astype(jnp.float32).T, axis=0)[:, None]
        do_t = do_t.astype(do.dtype)
        lse = lse_ref[0, 0, h][:, None]  # (blk, 1)

        def tile(j, at, n, nkeys, own):
            keys = pl.ds(pl.multiple_of(j * blk, blk), nkeys)
            kn, kr, v = kn_ref[0, keys, nope], kr_ref[0, keys, :], v_ref[0, keys, vals]
            rows = slice(at, at + n)
            s = (dot(qn[rows], kn, _NT) + dot(qr_h[rows], kr, _NT)) * scale  # (queries, keys) f32
            if own:
                s = _triangle(s, n, 1)
            p = jnp.exp(s - lse[rows])  # exact probabilities via saved lse
            dp = dot(do[rows], v, _NT)
            ds = (p * (dp - delta[rows])).astype(kn.dtype)
            dv_t[j, vals, :nkeys] = dv_t[j, vals, :nkeys] + dot(do_t[:, rows], p.astype(v.dtype))
            dkn_t[j, nope, :nkeys] = dkn_t[j, nope, :nkeys] + dot(qn_t[:, rows], ds)
            dkr_t[j, rope, :nkeys] = dkr_t[j, rope, :nkeys] + dot(qr_t[rope, rows], ds)
            dqn_acc[rows, nope] = dqn_acc[rows, nope] + dot(ds, kn)
            dqr_acc[h, rows, :] = dqr_acc[h, rows, :] + dot(ds, kr)

        _walk(tile, i, nk, blk, sub, causal)
        return carry

    jax.lax.fori_loop(0, g, head, 0)

    dqn_ref[0] = (dqn_acc[...] * scale).astype(dqn_ref.dtype)
    dqr = _merge([dqr_acc[h] for h in range(g)], _head_lanes(g, _ROPE, blk)) * scale
    dqr_ref[0] = _rotated_back(dqr, cos_ref, sin_ref).astype(dqr_ref.dtype)

    @pl.when(i == nk - 1)
    def _emit():
        for j in range(nk):
            rows = slice(j * blk, (j + 1) * blk)
            dkn_ref[0, rows, :] = (dkn_t[j].T * scale).astype(dkn_ref.dtype)
            dv_ref[0, rows, :] = dv_t[j].T.astype(dv_ref.dtype)

    @pl.when((i == nk - 1) & (c == pl.num_programs(1) - 1))
    def _emit_shared():
        for j in range(nk):
            rows = slice(j * blk, (j + 1) * blk)
            dkr_ref[0, rows, :] = (dkr_t[j].T * scale).astype(dkr_ref.dtype)


def _latent_specs(t, g, blk):
    """``_specs``; the rotary key's: the whole sequence, the same block
    for every pair of heads; and a query block of the rotation's
    ``(T, 128)`` tables."""
    shared = pl.BlockSpec((1, t, _LANES), lambda n, c, i: (n, 0, 0),
                          memory_space=pltpu.VMEM)
    angles = pl.BlockSpec((blk, _LANES), lambda n, c, i: (i, 0), memory_space=pltpu.VMEM)
    return *_specs(t, g, blk), shared, angles


def _latent_statics(qn, qr, v, scale, causal, blk, sub, interpret):
    """The kernels' static arguments, and what a grid step reads of the
    128-wide parts and of v: ``g`` heads' lanes."""
    g, h = _LANES // _ROPE, qr.shape[-1] // _ROPE
    dn, dv = qn.shape[-1] // h, v.shape[-1] // h
    statics = dict(
        scale=scale, causal=causal, g=g, dn=dn, dv=dv, blk=blk, sub=sub,
        nk=qn.shape[1] // blk, interpret=interpret,
    )
    return statics, g, g * dn, g * dv


@partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _latent_fwd_call(qn, qr, cos, sin, kn, kr, v, scale, causal, blk, interpret):
    n, t, _ = qn.shape
    st, g, wn, wv = _latent_statics(qn, qr, v, scale, causal, blk, _fwd_step(blk), interpret)
    c = qn.shape[-1] // wn
    block, whole, stat, shared, angles = _latent_specs(t, g, blk)
    return pl.pallas_call(
        partial(_latent_fwd_kernel, **st),
        grid=(n, c, t // blk),
        in_specs=[block(wn), block(_LANES), angles, angles, whole(wn), shared, whole(wv)],
        out_specs=(block(wv), stat),
        out_shape=(
            _out_struct(v.shape, qn.dtype, qn),
            _out_struct((n, c, g, t), jnp.float32, qn),
        ),
        scratch_shapes=[
            pltpu.VMEM((g, wv // g, blk), jnp.float32),  # output, transposed
            pltpu.VMEM((g, 1, blk), jnp.float32),  # running max
            pltpu.VMEM((g, 1, blk), jnp.float32),  # running sum
            pltpu.VMEM((t // blk, wv, blk), v.dtype),  # V, transposed
            pltpu.VMEM((blk, _LANES), qr.dtype),  # q's rotary part, rotated
        ],
        compiler_params=_params(t, wn, blk, qn.dtype.itemsize),
        interpret=interpret,
        name="latent_fwd",
    )(qn, qr, cos, sin, kn, kr, v)


@partial(jax.jit, static_argnums=(10, 11, 12, 13))
def _latent_bwd_call(qn, qr, cos, sin, kn, kr, v, o, lse, do, scale, causal, blk, interpret):
    n, t, _ = qn.shape
    st, g, wn, wv = _latent_statics(qn, qr, v, scale, causal, blk, _bwd_step(blk), interpret)
    nk = t // blk
    block, whole, stat, shared, angles = _latent_specs(t, g, blk)
    return pl.pallas_call(
        partial(_latent_bwd_kernel, **st),
        grid=(n, qn.shape[-1] // wn, nk),
        in_specs=[block(wn), block(_LANES), angles, angles, whole(wn), shared, whole(wv),
                  block(wv), block(wv), stat],
        out_specs=(block(wn), block(_LANES), whole(wn), shared, whole(wv)),
        out_shape=tuple(_out_struct(x.shape, x.dtype, x) for x in (qn, qr, kn, kr, v)),
        scratch_shapes=[
            pltpu.VMEM((nk, wn, blk), jnp.float32),  # dK's 128-wide part, transposed
            pltpu.VMEM((nk, _LANES, blk), jnp.float32),  # the rotary key's, transposed
            pltpu.VMEM((nk, wv, blk), jnp.float32),  # dV, transposed
            pltpu.VMEM((blk, wn), jnp.float32),  # dQ's 128-wide part
            pltpu.VMEM((g, blk, _LANES), jnp.float32),  # its rotary part, all lanes a head
            pltpu.VMEM((blk, _LANES), qr.dtype),  # q's rotary part, rotated
            pltpu.VMEM((_LANES, blk), qr.dtype),  # and transposed
        ],
        # the rotary key's gradient sums over the pairs of heads
        compiler_params=_params(t, wn, blk, qn.dtype.itemsize, lane_blocks="arbitrary"),
        interpret=interpret,
        name="latent_bwd",
    )(qn, qr, cos, sin, kn, kr, v, o, do, lse)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _latent(qn, qr, cos, sin, kn, kr, v, scale, causal, blk):
    """The output, as ``v``, for the kernels' flat operands."""
    return _latent_fwd_call(qn, qr, cos, sin, kn, kr, v, scale, causal, blk, pallas_interpret())[0]


def _latent_fwd(qn, qr, cos, sin, kn, kr, v, scale, causal, blk):
    o, lse = _latent_fwd_call(qn, qr, cos, sin, kn, kr, v, scale, causal, blk, pallas_interpret())
    o, lse = checkpoint_name(o, SAVED_OUT), checkpoint_name(lse, SAVED_LSE)
    return o, (qn, qr, cos, sin, kn, kr, v, o, lse)


def _latent_bwd(scale, causal, blk, res, g_o):
    dqn, dqr, dkn, dkr, dv = _latent_bwd_call(*res, g_o, scale, causal, blk, pallas_interpret())
    cos, sin = res[2:4]  # the angles are the positions': nobody reads their gradient
    return dqn, dqr, jnp.zeros_like(cos), jnp.zeros_like(sin), dkn, dkr, dv


_latent.defvjp(_latent_fwd, _latent_bwd)


def latent_attention(q_nope, q_rope, k_nope, k_rope, v, *, q_rotation=None,
                     causal: bool = False, block: int | None = None,
                     scale: float | None = None):
    """:func:`flash_attention` for latent attention's operands as the
    projections make them: ``q_nope, k_nope (B, T, H, 128)``, ``q_rope
    (B, T, H, 64)``, ``k_rope (B, T, 64)`` the one rotary key of all
    heads, rotated already, ``v (B, T, H, 128)``. The scores are
    ``(q_nope . k_nope + q_rope . k_rope) * scale``, ``scale`` ``1 /
    sqrt(128 + 64)`` unless given (a configuration whose rotary scaling
    changes it hands it in: the kernels multiply the scores by it
    anyway, so it costs no pass over q), what the
    assembled ``[q_nope | q_rope]`` and ``[k_nope | k_rope for every
    head]`` give; no such array is made, here or in the backward pass,
    which returns the gradient of each operand (the key's summed over
    the heads).

    ``q_rotation = (cos, sin)``, ``(T, 32)`` float32 each: ``q_rope``
    comes unrotated, and the kernels rotate its pairs ``(2i, 2i + 1)``
    by the angles ``i`` of every position as they load a block
    (float32, then the operands' dtype: the arithmetic of
    ``models/latent_moe.py::rope_interleaved``) and take the gradient
    back through the rotation as they store it. In XLA the rotation of
    the 32 heads' flat array cost more than the key's matmuls (PERF.md,
    PR 30); left out, ``q_rope`` is used as it comes.

    Every array is read and written in the projections' flat layout
    (the reshapes are free): the 128-wide parts one head a lane block,
    q's rotary part two heads a lane block, the key once for all heads
    (side by side twice, 128 lanes, so that each head of a pair meets a
    copy at its own lanes). Widths: a rotary part of 64, an even number
    of heads, the other parts multiples of 128; a block edge as
    :func:`flash_attention`'s, for a T that 128 divides or one of at
    most ``_MAX_WHOLE_BLOCK``."""
    b, t, h, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], v.shape[-1]
    if not _latent_widths_tile(h, dn, dr, dv):
        raise ValueError(
            f"latent_attention: {h} heads of {dn} + {dr} beside {dv} do not tile: the "
            f"rotary part is {_ROPE} wide, the heads even, the other parts multiples of {_LANES}"
        )
    blk = _block_for(t) if block is None else block
    if t % blk or (block is None and blk > _MAX_WHOLE_BLOCK):
        raise ValueError(f"latent_attention: no block edge for seq_len {t} (asked: {block})")
    pair = _LANES // _ROPE  # heads a lane block of the rotary parts
    if q_rotation is None:
        cos, sin = jnp.ones((t, _LANES), jnp.float32), jnp.zeros((t, _LANES), jnp.float32)
    else:  # a pair's angle at both its lanes, every head of a lane block alike
        lanes = lambda x: jnp.tile(jnp.repeat(x.astype(jnp.float32), 2, axis=-1), (1, pair))
        cos, sin = lanes(q_rotation[0]), lanes(q_rotation[1])
        sin = jnp.where(jnp.arange(_LANES) % 2 == 0, -sin, sin)
    flat = lambda x: x.reshape(b, t, h * x.shape[-1])
    o = _latent(
        flat(q_nope), flat(q_rope), cos, sin, flat(k_nope),
        jnp.concatenate([k_rope] * pair, axis=-1), flat(v),
        1.0 / ((dn + dr) ** 0.5) if scale is None else scale, causal, blk,
    )
    return o.reshape(b, t, h, dv)


# ---------------------------------------------------------------------
# grouped KV heads, an optional window, K and V by the block
# ---------------------------------------------------------------------

# Block edges of the grouped kernels, best first (the chip race at 1 x
# 16,384, 28 heads over 4, forward + backward: a full layer 46.2 ms at
# 1,024, 49.2 at 512, 88.3 at 256; a window layer of 4,096 25.4, 25.6,
# 43.4: PERF.md section 6, PR 33).
_GROUPED_BLOCKS = (1024, 512, 256, 128)
_FIRST, _LAST, _MASKED = 1, 2, 4  # what a visit is to its query block, and its tile's kind


def grouped_takes_kernel(
    device_kind: str, num_devices: int, seq_len: int, num_heads: int,
    num_kv_heads: int, head_dim: int, rotates_q: bool = False,
) -> bool:
    """Whether a block of grouped-head attention that was given no
    attention runs :func:`grouped_attention`'s kernels (the models ask
    while tracing, with what tracing shows of the operands' placement)
    or the plain masked path in query blocks
    (:func:`blocked_window_attention`): where
    :func:`default_takes_kernel` takes the head width (a TPU, operands
    on one device, a T from 256 that 128 divides), at 128, or at 64 with
    the KV heads in pairs, for whole groups of query heads a KV head.
    ``rotates_q``: the caller will hand the kernels a ``q_rotation``,
    which only those at 128 apply; a rotary layer of heads 64 wide
    keeps the plain path, which takes q rotated. No upper bound on T:
    K and V come by the block."""
    paired = head_dim == 64 and num_kv_heads % 2 == 0 and not rotates_q
    return (
        default_takes_kernel(device_kind, num_devices, seq_len, num_heads, head_dim)
        and (head_dim == _LANES or paired)
        and num_heads % num_kv_heads == 0
    )


def _visits(t: int, blk: int, window: int | None):
    """The (query block, K/V block) tiles that hold a pair the mask
    keeps (``j <= i``, and ``i - j < window``), as three int32 tables a
    grid step reads: query block by query block, each from its diagonal
    tile down to the farthest it reaches (the diagonal first: there
    every query sees a key, its own, so the running maximum is a score
    from then on and a masked score's ``exp`` is 0 exactly), with a
    flag word: first and last visit of the query block, and whether the
    tile needs the mask (the diagonal, and the window's far edge).
    Static: numpy at trace time. Nothing outside these tiles is fetched
    or multiplied, in either pass. A masked visit the 128-wide pair
    runs in sub-steps of queries that multiply only the part of the
    tile holding kept pairs (:func:`_sub_steps`; on the diagonal each
    sub-step's keys run up to its queries' own, so the order above
    holds a sub-step at a time); the 64-wide pair multiplies it whole
    under :func:`_kept`."""
    q_of, k_of, flags = [], [], []
    for i in range(t // blk):
        lo = 0 if window is None else max(0, (i * blk - window + 1) // blk)
        for j in range(i, lo - 1, -1):
            edge = window is not None and (i + 1) * blk - 1 - j * blk >= window
            q_of.append(i)
            k_of.append(j)
            flags.append(_FIRST * (j == i) | _LAST * (j == lo) | _MASKED * (j == i or edge))
    return tuple(np.asarray(x, np.int32) for x in (q_of, k_of, flags))


def _kept(i, j, blk: int, window: int | None, keys_axis: int):
    """The ``(blk, blk)`` mask of query block ``i`` against K/V block
    ``j``: key <= query, and query - key < ``window``."""
    query = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1 - keys_axis)
    key = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), keys_axis)
    ahead = (i - j) * blk + query - key
    return ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)


def _grouped_sub(blk: int) -> int:
    """Queries a sub-step of a masked tile of the 128-wide grouped pair
    takes, from the block edge: a quarter of it, 128 at the least
    (raced on a TPU v5e at block 1,024, forward + backward, ms a layer:
    a window layer of 4,096 at T 16,384, 28 heads over 4, 26.48 whole,
    25.75 in steps of 512, 23.19 in steps of 256; a full layer 49.11,
    45.79, 45.36; PERF.md section 6)."""
    return max(blk // 4, _LANES)


def _far_edge(s, n, keys_axis):
    """Mask the queries' own ``n`` keys one window back, the first ``n``
    along ``keys_axis`` of ``s``, to key > query (the mirror of
    :func:`_triangle`)."""
    key = jax.lax.broadcasted_iota(jnp.int32, (n, n), keys_axis)
    query = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1 - keys_axis)
    if s.shape[keys_axis] == n:
        return jnp.where(key > query, s, _NEG_INF)
    own, beyond = jnp.split(s, [n], axis=keys_axis)
    return jnp.concatenate([jnp.where(key > query, own, _NEG_INF), beyond], axis=keys_axis)


def _sub_steps(blk: int, window: int | None, diagonal: bool):
    """How the 128-wide pair runs a masked visit of :func:`_visits`:
    ``(steps, own)``, the sub-steps ``(at, n, lo, hi)`` (the tile's
    queries ``at..at+n`` against its keys ``lo..hi``) and what masks the
    ``n`` keys that are the queries' own. On the diagonal each step of
    ``_grouped_sub(blk)`` queries meets the keys up to its own, the last
    ``n`` under :func:`_triangle`, as ``_walk`` steps the flash pair's;
    at the far edge of a window of whole blocks, which keeps key >
    query, the mirror: the keys from its own on, the first ``n`` under
    :func:`_far_edge`. Nothing a step leaves out holds a kept pair.
    ``None`` where the mask is not one triangle (a window shorter than a
    block, or not whole blocks, at its edge): the whole tile under
    :func:`_kept`."""
    sub = _grouped_sub(blk)
    if diagonal and (window is None or window >= blk):
        return [(at, sub, 0, at + sub) for at in range(0, blk, sub)], _triangle
    if not diagonal and window is not None and window % blk == 0:
        return [(at, sub, at, blk) for at in range(0, blk, sub)], _far_edge
    return None


def _tile_work(t: int, blk: int, window: int | None) -> float:
    """The (query, key) products the 128-wide pair computes for one
    query head, in whole tiles: what :func:`_visits` lists, each masked
    visit as :func:`_sub_steps` runs it."""
    q_of, k_of, flags = _visits(t, blk, window)
    work = 0.0
    for i, j, f in zip(q_of, k_of, flags):
        plan = _sub_steps(blk, window, i == j) if f & _MASKED else None
        work += 1.0 if plan is None else sum(n * (hi - lo) for _, n, lo, hi in plan[0]) / blk**2
    return work


def _grouped_plan(i, j, blk: int, window: int | None, masked: bool, diagonal: bool,
                  keys_axis: int):
    """A visit of the 128-wide pair as ``(steps, own)`` (:func:`_sub_steps`):
    an unmasked tile one step whole and no mask; a masked one in its
    sub-steps, or whole under :func:`_kept`."""
    whole = [(0, blk, 0, blk)]
    if not masked:
        return whole, None
    plan = _sub_steps(blk, window, diagonal)
    if plan is not None:
        return plan
    keep = _kept(i, j, blk, window, keys_axis)
    return whole, lambda s, n, axis: jnp.where(keep, s, _NEG_INF)


def _grouped_visit(tile, i, j, flags, window: int | None):
    """Run ``tile(masked, diagonal)`` for the visit's kind: an unmasked
    tile, the diagonal (always a query block's first visit) or, under a
    window, its far edge."""
    masked = (flags & _MASKED) != 0
    pl.when(jnp.logical_not(masked))(lambda: tile(False, False))
    pl.when(masked & (i == j))(lambda: tile(True, True))
    if window is not None:
        pl.when(masked & (i != j))(lambda: tile(True, False))


def _rotated_halves(x, cos_ref, sin_ref):
    """A head's ``(rows, 128)`` block rotated by the block's angles in
    the halves convention (element ``i`` with ``i + 64``), float32:
    ``cos_ref`` holds an angle's cosine at both lanes, ``sin_ref`` its
    sine, negated at the first half's."""
    x = x.astype(jnp.float32)
    return x * cos_ref[...] + pltpu.roll(x, _LANES // 2, 1) * sin_ref[...]


def _rotated_halves_back(g, cos_ref, sin_ref):
    """The transpose of :func:`_rotated_halves` (the exchange of the
    halves is its own)."""
    return g * cos_ref[...] + pltpu.roll(g * sin_ref[...], _LANES // 2, 1)


def _grouped_fwd_kernel(q_of, k_of, flags_of, *refs, scale, window, g, blk, rotate, interpret):
    """Grid (N, KV heads, visits), the visits sequential: one tile of
    one query block's online softmax, the ``g`` query heads of the
    group in turn against the one K/V block fetched for them all; keys
    x queries as ``_fwd_kernel``. q is rotated (window layers) and
    transposed once a query block, V transposed once a visit."""
    q_ref, *refs = refs
    if rotate:
        cos_ref, sin_ref, *refs = refs
    k_ref, v_ref, o_ref, lse_ref, acc_t, m_sc, l_sc, q_t = refs
    visit = pl.program_id(2)
    i, j, flags = q_of[visit], k_of[visit], flags_of[visit]
    dot = partial(_dot, interpret=interpret)

    @pl.when((flags & _FIRST) != 0)
    def _start():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_t[...] = jnp.zeros_like(acc_t)

        def head(h, carry):
            q = q_ref[0, :, _head_at(h, _LANES)]
            if rotate:
                q = _rotated_halves(q, cos_ref, sin_ref).astype(q_ref.dtype)
            q_t[h] = _transposed(q)  # (128, blk)
            return carry

        jax.lax.fori_loop(0, g, head, 0)

    def tile(masked: bool, diagonal: bool):
        k, v_t = k_ref[0], _transposed(v_ref[0])  # (blk, 128), (128, blk)
        steps, own = _grouped_plan(i, j, blk, window, masked, diagonal, 0)

        def head(h, carry):
            for at, n, lo, hi in steps:
                cols = slice(at, at + n)
                s = dot(k[lo:hi], q_t[h, :, cols]) * scale  # (keys, queries) f32
                if own is not None:
                    s = own(s, n, 0)
                _softmax_step(s, v_t[:, lo:hi], h, cols, acc_t, m_sc, l_sc, dot)
            return carry

        jax.lax.fori_loop(0, g, head, 0)

    _grouped_visit(tile, i, j, flags, window)
    pl.when((flags & _LAST) != 0)(lambda: _finish_softmax(g, acc_t, m_sc, l_sc, o_ref, lse_ref))


def _grouped_bwd_kernel(q_of, k_of, flags_of, *refs, scale, window, g, blk, rotate, interpret):
    """Grid (N, KV heads, visits), the visits sequential and in the
    forward's order; queries x keys as ``_bwd_kernel``. dQ of a query
    block accumulates over its visits and leaves with the last (rotated
    back where q came unrotated); dK and dV of the group's one KV head
    accumulate transposed, ``(128, blk)`` a K/V block, over the query
    blocks within reach and the ``g`` heads, and are written on the KV
    head's last visit. What is made once a query block (q rotated, q
    and dO transposed, delta, the logsumexp as a column) waits in VMEM
    for the block's other visits."""
    q_ref, *refs = refs
    if rotate:
        cos_ref, sin_ref, *refs = refs
    (k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
     dk_t, dv_t, dq_acc, q_sc, q_t, do_t, lse_sc, delta_sc) = refs
    visit, last_visit = pl.program_id(2), pl.num_programs(2) - 1
    i, j, flags = q_of[visit], k_of[visit], flags_of[visit]
    dot = partial(_dot, interpret=interpret)

    @pl.when(visit == 0)
    def _init():
        dk_t[...] = jnp.zeros_like(dk_t)
        dv_t[...] = jnp.zeros_like(dv_t)

    @pl.when((flags & _FIRST) != 0)
    def _start():
        dq_acc[...] = jnp.zeros_like(dq_acc)

        def head(h, carry):
            lanes = _head_at(h, _LANES)
            q, do = q_ref[0, :, lanes], do_ref[0, :, lanes]
            if rotate:
                q = _rotated_halves(q, cos_ref, sin_ref).astype(q_ref.dtype)
            q_sc[:, lanes] = q
            q_t[h] = _transposed(q)
            do_t[h] = _transposed(do)
            # delta = rowsum(dO * O), the one use of the forward's output
            delta_sc[h] = jnp.sum(
                do.astype(jnp.float32) * o_ref[0, :, lanes].astype(jnp.float32),
                axis=1, keepdims=True,
            )
            lse_sc[h] = lse_ref[0, 0, h][:, None]
            return carry

        jax.lax.fori_loop(0, g, head, 0)

    def tile(masked: bool, diagonal: bool):
        k_all, v_all = k_ref[0], v_ref[0]  # (blk, 128)
        steps, own = _grouped_plan(i, j, blk, window, masked, diagonal, 1)

        def head(h, carry):
            lanes = _head_at(h, _LANES)
            for at, n, lo, hi in steps:
                rows, keys = slice(at, at + n), slice(lo, hi)
                k, v = k_all[keys], v_all[keys]
                s = dot(q_sc[rows, lanes], k, _NT) * scale  # (queries, keys) f32
                if own is not None:
                    s = own(s, n, 1)
                p = jnp.exp(s - lse_sc[h, rows, :])  # exact probabilities via saved lse
                dp = dot(do_ref[0, rows, lanes], v, _NT)
                ds = (p * (dp - delta_sc[h, rows, :])).astype(k.dtype)
                dv_t[j, :, keys] = dv_t[j, :, keys] + dot(do_t[h, :, rows], p.astype(v.dtype))
                dk_t[j, :, keys] = dk_t[j, :, keys] + dot(q_t[h, :, rows], ds)
                dq_acc[rows, lanes] = dq_acc[rows, lanes] + dot(ds, k)
            return carry

        jax.lax.fori_loop(0, g, head, 0)

    _grouped_visit(tile, i, j, flags, window)

    @pl.when((flags & _LAST) != 0)
    def _emit_dq():
        def head(h, carry):
            lanes = _head_at(h, _LANES)
            dq = dq_acc[:, lanes] * scale
            if rotate:
                dq = _rotated_halves_back(dq, cos_ref, sin_ref)
            dq_ref[0, :, lanes] = dq.astype(dq_ref.dtype)
            return carry

        jax.lax.fori_loop(0, g, head, 0)

    @pl.when(visit == last_visit)
    def _emit_dkv():
        def block(n, carry):
            rows = pl.ds(pl.multiple_of(n * blk, blk), blk)
            dk_ref[0, rows, :] = (dk_t[n].T * scale).astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_t[n].T.astype(dv_ref.dtype)
            return carry

        jax.lax.fori_loop(0, dk_t.shape[0], block, 0)


def _grouped_layout(q, k, rotation, window, blk: int):
    """What both calls are laid out by: the visits' three tables, the
    grid ``(N, KV heads, visits)``, ``g`` query heads a KV head, and
    the block specs under the prefetched tables: a query block of a
    group's heads, the visit's K/V block of the group's one KV head, a
    query block of the statistics, and of the rotation's two tables
    where q comes unrotated."""
    n, t, _ = q.shape
    g = q.shape[-1] // k.shape[-1]
    tables = _visits(t, blk, window)
    group = pl.BlockSpec((1, blk, g * _LANES), lambda n, c, v, q_of, k_of, f: (n, q_of[v], c))
    keys = pl.BlockSpec((1, blk, _LANES), lambda n, c, v, q_of, k_of, f: (n, k_of[v], c))
    stat = pl.BlockSpec((1, 1, g, blk), lambda n, c, v, q_of, k_of, f: (n, c, 0, q_of[v]))
    angles = pl.BlockSpec((blk, _LANES), lambda n, c, v, q_of, k_of, f: (q_of[v], 0))
    grid = (n, k.shape[-1] // _LANES, len(tables[0]))
    return tables, grid, g, group, keys, stat, [angles, angles] if rotation else []


def _grouped_params(resident: int, g: int, blk: int):
    """As ``_params``: ``resident`` is what stays in VMEM beside a
    visit's blocks. The forward has none; the backward keeps a KV
    head's dK and dV, float32 and as they leave, 1.5 KB a token."""
    blocks = 16 * blk * g * _LANES * 4 + 8 * blk * blk * 4
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(min(max(32 << 20, 2 * (resident + blocks)), 100 << 20)),
    )


@partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _grouped_fwd_call(q, k, v, rotation, scale, window, blk, interpret):
    tables, grid, g, group, keys, stat, angles = _grouped_layout(q, k, rotation, window, blk)
    return pl.pallas_call(
        partial(_grouped_fwd_kernel, scale=scale, window=window, g=g, blk=blk,
                rotate=bool(rotation), interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[group, *angles, keys, keys],
            out_specs=(group, stat),
            scratch_shapes=[
                pltpu.VMEM((g, _LANES, blk), jnp.float32),  # output, transposed
                pltpu.VMEM((g, 1, blk), jnp.float32),  # running max
                pltpu.VMEM((g, 1, blk), jnp.float32),  # running sum
                pltpu.VMEM((g, _LANES, blk), q.dtype),  # q, rotated and transposed
            ],
        ),
        out_shape=(
            _out_struct(q.shape, q.dtype, q),
            _out_struct((*grid[:2], g, q.shape[1]), jnp.float32, q),
        ),
        compiler_params=_grouped_params(0, g, blk),
        interpret=interpret,
        name="grouped_fwd",
    )(*tables, q, *(rotation or ()), k, v)


@partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _grouped_bwd_call(q, k, v, rotation, o, lse, do, scale, window, blk, interpret):
    t = q.shape[1]
    tables, grid, g, group, keys, stat, angles = _grouped_layout(q, k, rotation, window, blk)
    whole = pl.BlockSpec((1, t, _LANES), lambda n, c, v, q_of, k_of, f: (n, 0, c))
    column = pltpu.VMEM((g, blk, 1), jnp.float32)
    return pl.pallas_call(
        partial(_grouped_bwd_kernel, scale=scale, window=window, g=g, blk=blk,
                rotate=bool(rotation), interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[group, *angles, keys, keys, group, group, stat],
            out_specs=(group, whole, whole),
            scratch_shapes=[
                pltpu.VMEM((t // blk, _LANES, blk), jnp.float32),  # dK, transposed
                pltpu.VMEM((t // blk, _LANES, blk), jnp.float32),  # dV, transposed
                pltpu.VMEM((blk, g * _LANES), jnp.float32),  # dQ
                pltpu.VMEM((blk, g * _LANES), q.dtype),  # q, rotated
                pltpu.VMEM((g, _LANES, blk), q.dtype),  # and transposed
                pltpu.VMEM((g, _LANES, blk), q.dtype),  # dO, transposed
                column, column,  # the logsumexp and delta of a head's queries
            ],
        ),
        out_shape=tuple(_out_struct(x.shape, x.dtype, x) for x in (q, k, v)),
        compiler_params=_grouped_params(
            2 * t * _LANES * (4 + 2 * k.dtype.itemsize), g, blk
        ),
        interpret=interpret,
        name="grouped_bwd",
    )(*tables, q, *(rotation or ()), k, v, o, do, lse)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _grouped(q, k, v, rotation, scale, window, blk):
    """The output, as ``q``, for the kernels' flat operands."""
    return _grouped_fwd_call(q, k, v, rotation, scale, window, blk, pallas_interpret())[0]


def _grouped_fwd(q, k, v, rotation, scale, window, blk):
    o, lse = _grouped_fwd_call(q, k, v, rotation, scale, window, blk, pallas_interpret())
    o, lse = checkpoint_name(o, SAVED_OUT), checkpoint_name(lse, SAVED_LSE)
    return o, (q, k, v, rotation, o, lse)


def _grouped_bwd(scale, window, blk, res, g_o):
    *operands, o, lse = res
    dq, dk, dv = _grouped_bwd_call(*operands, o, lse, g_o, scale, window, blk, pallas_interpret())
    # the angles are the positions': nobody reads their gradient
    return dq, dk, dv, jax.tree.map(jnp.zeros_like, operands[3])


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def _halves_tables(cos, sin):
    """``(T, 64)`` cosines and sines of the halves convention as the
    ``(T, 128)`` float32 tables the grouped kernels multiply a head by:
    an angle's cosine at both its lanes, its sine negated at the first
    half's."""
    cos, sin = cos.astype(jnp.float32), sin.astype(jnp.float32)
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)


def grouped_attention(q, k, v, *, window: int | None = None, q_rotation=None,
                      block: int | None = None):
    """Causal attention over grouped KV heads with an optional window:
    ``q (B, T, H, 128)``, ``k, v (B, T, Hkv, 128)``, query head ``h``
    reading KV head ``h // (H / Hkv)``; query ``i`` sees the keys ``j
    <= i`` and, given a ``window``, only those with ``i - j < window``.
    Exact, as :func:`flash_attention`; scores are divided by
    ``sqrt(128)``.

    Every array is read and written in the projections' flat layout.
    A grid step is one (query block, K/V block) tile for the ``H /
    Hkv`` query heads of a group: their ``(block, H / Hkv * 128)``
    lanes of q against the group's one ``(block, 128)`` K and V block,
    fetched once for all of them. Only tiles that hold a kept pair are
    visited (:func:`_visits`): the ones wholly inside unmasked, the
    diagonal and the window's far edge in sub-steps of queries that
    multiply only what the mask keeps (:func:`_sub_steps`), the same
    list in the one fused backward kernel, where dK and dV of a K/V
    block gather from the query blocks within reach. K and V come by
    the block, so the forward's VMEM does not grow with T; the backward
    keeps a KV head's float32 dK and dV and their outgoing copies, 1.5
    KB a token (24 MiB at T = 16,384).

    ``q_rotation = (cos, sin)``, ``(T, 64)`` float32 each: q comes
    unrotated and the kernels rotate each head's block as they load it,
    element ``i`` with ``i + 64`` by the angle ``i`` of every position
    (float32, then the operands' dtype), and take the gradient back
    through the rotation; k comes rotated (it is ``Hkv`` heads, an
    eighth of q's bytes or less). Left out, q is used as it comes.

    **Heads 64 wide** (``q (B, T, H, 64)``, ``k, v (B, T, Hkv, 64)``,
    ``Hkv`` even; scores divided by 8) run a pair of kernels of their
    own over the same tiles, ``grouped64_fwd`` and ``grouped64_bwd`` at
    the end of this file: a grid step is two KV heads' 128 lanes of K
    and V against their ``2 H / Hkv`` query heads' lanes of q. They take
    no ``q_rotation``. k and v are arguments in both widths, so a layer
    may attend with its own q over another layer's k and v, and the
    cotangents add up where the arrays are made."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    paired = d == 64 and hkv % 2 == 0  # two KV heads a lane block: the kernels at the file's end
    if (d != _LANES and not paired) or v.shape[-1] != d or h % hkv:
        raise ValueError(
            f"grouped_attention: {h} query heads over {hkv} KV heads of width {d}: the "
            f"kernels take whole groups of heads {_LANES} wide, or 64 wide over KV heads in pairs"
        )
    if block is None:  # the largest edge that divides T; heads of 64 by their own race (below)
        edges = _GROUPED_BLOCKS if d == _LANES else _grouped64_blocks(t, window)
        blk = next((x for x in edges if t % x == 0), None)
    else:
        blk = block
    if blk is None or t % blk or blk % _LANES:
        raise ValueError(f"grouped_attention: no block edge for seq_len {t} (asked: {block})")
    if window is not None and window >= t:
        window = None  # every key a query may see is inside: plain causal
    flat = lambda x: x.reshape(b, t, -1)
    if paired:
        if q_rotation is not None:
            raise ValueError("grouped_attention: heads 64 wide come rotated, or with no positions")
        o = _grouped64(flat(q), flat(k), flat(v), 1.0 / math.sqrt(d), window, blk)
        return o.reshape(b, t, h, d)
    rotation = None if q_rotation is None else _halves_tables(*q_rotation)
    o = _grouped(flat(q), flat(k), flat(v), rotation, 1.0 / math.sqrt(d), window, blk)
    return o.reshape(b, t, h, d)


def blocked_window_attention(q, k, v, *, window: int | None = None, block: int = 512):
    """The plain form of :func:`grouped_attention` (q and k as they are
    to be multiplied: rotated already), what runs off one TPU chip:
    XLA's masked softmax, one block of ``block`` queries at a time
    against the keys the block can see, each block recomputed in the
    backward pass: all the keys without a window (the ``(H, block, T)``
    scores are all that is ever alive), and under a window the ``block
    + window - 1`` keys a block's window reaches, sliced from K and V
    (``(H, block, block + window)`` scores, and a window layer does not
    do a full layer's work); a T that ``block`` does not divide runs
    whole. The KV heads are not repeated: a group's query heads meet
    their one KV head in the product."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    block = block if t % block == 0 else t
    q = q.reshape(b, t, hkv, g, d)
    # the keys a query block reads: the block's own and the window's reach before it
    span = t if window is None else min(t, block + window - 1)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        first = jnp.clip(start + block - span, 0, t - span)  # of the keys read
        kb, vb = (jax.lax.dynamic_slice_in_dim(a, first, span, axis=1) for a in (k, v))
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb, preferred_element_type=jnp.float32)
        ahead = (start + jnp.arange(block))[:, None] - (first + jnp.arange(span))[None, :]
        keep = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
        p = jax.nn.softmax(jnp.where(keep, s / math.sqrt(d), -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), vb)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))  # (blocks, B, block, Hkv, g, d)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, h, d)


# ---------------------------------------------------------------------
# ring-flash: sequence parallelism across chips, flash within each hop
# ---------------------------------------------------------------------


def _ring_flash_local(q, k, v, *, axis_name, num_devices, causal):
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

    from multidisttorch_tpu.parallel.collectives import pvary

    m0 = pvary(jnp.full((b, h, t_loc), _NEG_INF, jnp.float32), axis_name)
    l0 = pvary(jnp.zeros((b, h, t_loc), jnp.float32), axis_name)
    acc0 = pvary(jnp.zeros((b, t_loc, h, d), jnp.float32), axis_name)
    perm = [(i, (i + 1) % num_devices) for i in range(num_devices)]
    per_row = lambda x: x.transpose(0, 2, 1)[..., None]  # (B, H, T) on acc

    def body(carry, step):
        k_blk, v_blk, m, l, acc = carry

        def full():
            return _attend(q, k_blk, v_blk, causal=False)

        def diag():
            return _attend(q, k_blk, v_blk, causal=True)

        def skip():
            return (
                jnp.zeros_like(q),
                jnp.full((b, h, t_loc), _NEG_INF, jnp.float32),
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
        acc_new = acc * per_row(c) + per_row(w) * o_h.astype(jnp.float32)
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, m_new, l_new, acc_new), None

    (_, _, _, l, acc), _ = jax.lax.scan(
        body, (k, v, m0, l0, acc0), jnp.arange(num_devices)
    )
    return (acc / per_row(jnp.where(l > 0, l, 1.0))).astype(q.dtype)


@lru_cache(maxsize=None)
def _make_ring_flash_cached(mesh, causal: bool, head_axis=None):
    from jax.sharding import PartitionSpec as P

    from multidisttorch_tpu.parallel.mesh import DATA_AXIS

    num_devices = int(mesh.shape[DATA_AXIS])
    spec = P(None, DATA_AXIS, head_axis, None)

    def fn(q, k, v):
        return jax.shard_map(
            partial(
                _ring_flash_local,
                axis_name=DATA_AXIS,
                num_devices=num_devices,
                causal=causal,
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


# ---------------------------------------------------------------------
# grouped KV heads 64 wide: two KV heads a 128-lane K/V block
# (at the end of the file: a line moved above changes the serialized
# module of every kernel there, and with it every cell's cache key)
# ---------------------------------------------------------------------
#
# ``grouped64_fwd`` and ``grouped64_bwd`` are the grouped kernels'
# walks (the same ``_visits``, ``_kept``, flags and softmax steps) over
# heads of 64. A grid step is one tile of two KV heads' 128 lanes of K
# and V against their ``2 r`` query heads' ``r * 128`` lanes of q (``r``
# = query heads a KV head; 2 in ``phi-4-mini-flash``: 256 lanes). Once a
# query block, each query head is laid out over its KV head's 64 lanes
# of a 128-lane block, zeros in the other 64, so that every product
# against the K or V block is the one head's: a 128-deep contraction
# costs the MXU what a 64-deep one does. p v is made for the KV head's
# 64 rows alone; dQ leaves through the inverse of the layout.


# Block edges at head width 64, best first (the chip race at 1 x 16,384,
# 40 heads over 20, forward + backward: a full layer 62.0 ms at 512 and
# 88.0 at 1,024, whose backward holds four heads' 1,024 x 1,024 tiles at
# once; a window layer of 512 10.5 at 512, 11.9 at 256, 21.9 at 1,024,
# which multiplies twice the tiles the window needs: PERF.md section 6,
# PR 37).
_GROUPED64_BLOCKS = (512, 256, 128)


def _grouped64_blocks(t: int, window: int | None):
    """The edges a layer of heads 64 wide may take: none beyond its
    window's reach."""
    reach = t if window is None else max(window, _GROUPED64_BLOCKS[-1])
    return tuple(x for x in _GROUPED64_BLOCKS if x <= reach)


def _over_its_kv_head(x, half: int, at: int):
    """``x`` ``(rows, 128)`` float32, two query heads side by side: the
    head in lanes ``half * 64 ..`` moved to lanes ``at * 64 ..``, zeros
    in the other 64."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(lane // 64 == half, x, 0.0)
    return x if half == at else pltpu.roll(x, 64, 1)


def _grouped64_fwd_kernel(q_of, k_of, flags_of, q_ref, k_ref, v_ref, o_ref, lse_ref,
                          acc_t, m_sc, l_sc, q_t, *, scale, window, r, blk, interpret):
    """Grid (N, pairs of KV heads, visits), as ``_grouped_fwd_kernel``;
    keys x queries. ``q_t[p]`` is query head ``p`` of the group,
    transposed, in the rows of its KV head ``p // r`` of the pair."""
    visit = pl.program_id(2)
    i, j, flags = q_of[visit], k_of[visit], flags_of[visit]
    dot = partial(_dot, interpret=interpret)
    heads = range(2 * r)

    @pl.when((flags & _FIRST) != 0)
    def _start():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_t[...] = jnp.zeros_like(acc_t)
        for p in heads:
            two = q_ref[0, :, (p // 2) * _LANES:(p // 2 + 1) * _LANES].astype(jnp.float32)
            q_t[p] = _over_its_kv_head(two, p % 2, p // r).T.astype(q_t.dtype)  # (128, blk)

    def tile(masked: bool):
        k, v_t = k_ref[0], _transposed(v_ref[0])  # (blk, 128), (128, blk)
        keep = _kept(i, j, blk, window, 0) if masked else None
        for p in heads:
            s = dot(k, q_t[p]) * scale  # (keys, queries) f32
            if masked:
                s = jnp.where(keep, s, _NEG_INF)
            rows = slice((p // r) * 64, (p // r + 1) * 64)
            _softmax_step(s, v_t[rows], p, slice(None), acc_t, m_sc, l_sc, dot)

    pl.when((flags & _MASKED) != 0)(lambda: tile(True))
    pl.when((flags & _MASKED) == 0)(lambda: tile(False))
    pl.when((flags & _LAST) != 0)(
        lambda: _finish_softmax(2 * r, acc_t, m_sc, l_sc, o_ref, lse_ref)
    )


def _grouped64_bwd_kernel(q_of, k_of, flags_of, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          dq_ref, dk_ref, dv_ref, dk_t, dv_t, dq_acc, q_sc, do_sc, q_t, do_t,
                          lse_sc, delta_sc, *, scale, window, r, blk, interpret):
    """As ``_grouped_bwd_kernel``, queries x keys, on q and dO laid out
    a query head a 128-lane block over its KV head's lanes (``q_sc``,
    ``do_sc`` and transposed ``q_t``, ``do_t``): dK and dV of the pair
    gather in their own rows, and dQ's 64 lanes a head go back where q
    came from."""
    visit, last_visit = pl.program_id(2), pl.num_programs(2) - 1
    i, j, flags = q_of[visit], k_of[visit], flags_of[visit]
    dot = partial(_dot, interpret=interpret)
    heads = range(2 * r)
    block_of = lambda n: slice(n * _LANES, (n + 1) * _LANES)

    @pl.when(visit == 0)
    def _init():
        dk_t[...] = jnp.zeros_like(dk_t)
        dv_t[...] = jnp.zeros_like(dv_t)

    @pl.when((flags & _FIRST) != 0)
    def _start():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        for p in heads:
            two = block_of(p // 2)
            q = _over_its_kv_head(q_ref[0, :, two].astype(jnp.float32), p % 2, p // r)
            do = do_ref[0, :, two].astype(jnp.float32)
            lane = jax.lax.broadcasted_iota(jnp.int32, do.shape, 1)
            # delta = rowsum(dO * O) over the head's own lanes
            delta_sc[p] = jnp.sum(
                jnp.where(lane // 64 == p % 2, do * o_ref[0, :, two].astype(jnp.float32), 0.0),
                axis=1, keepdims=True,
            )
            do = _over_its_kv_head(do, p % 2, p // r)
            q_sc[:, block_of(p)] = q.astype(q_sc.dtype)
            do_sc[:, block_of(p)] = do.astype(do_sc.dtype)
            q_t[p] = q.T.astype(q_t.dtype)
            do_t[p] = do.T.astype(do_t.dtype)
            lse_sc[p] = lse_ref[0, 0, p][:, None]

    def tile(masked: bool):
        k, v = k_ref[0], v_ref[0]  # (blk, 128)
        keep = _kept(i, j, blk, window, 1) if masked else None
        for p in heads:
            s = dot(q_sc[:, block_of(p)], k, _NT) * scale  # (queries, keys) f32
            if masked:
                s = jnp.where(keep, s, _NEG_INF)
            prob = jnp.exp(s - lse_sc[p])
            dp = dot(do_sc[:, block_of(p)], v, _NT)
            ds = (prob * (dp - delta_sc[p])).astype(k.dtype)
            dv_t[j] = dv_t[j] + dot(do_t[p], prob.astype(v.dtype))
            dk_t[j] = dk_t[j] + dot(q_t[p], ds)
            dq_acc[:, block_of(p)] = dq_acc[:, block_of(p)] + dot(ds, k)

    pl.when((flags & _MASKED) != 0)(lambda: tile(True))
    pl.when((flags & _MASKED) == 0)(lambda: tile(False))

    @pl.when((flags & _LAST) != 0)
    def _emit_dq():
        for n in range(r):  # two query heads a 128-lane block of dQ
            lo, hi = 2 * n, 2 * n + 1
            a, b = dq_acc[:, block_of(lo)], dq_acc[:, block_of(hi)]
            a = a if lo // r == 0 else pltpu.roll(a, 64, 1)  # from its KV head's lanes to lanes 0..63
            b = b if hi // r == 1 else pltpu.roll(b, 64, 1)  # to lanes 64..127
            lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
            dq_ref[0, :, block_of(n)] = (jnp.where(lane < 64, a, b) * scale).astype(dq_ref.dtype)

    @pl.when(visit == last_visit)
    def _emit_dkv():
        def block(n, carry):
            rows = pl.ds(pl.multiple_of(n * blk, blk), blk)
            dk_ref[0, rows, :] = (dk_t[n].T * scale).astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_t[n].T.astype(dv_ref.dtype)
            return carry

        jax.lax.fori_loop(0, dk_t.shape[0], block, 0)


def _grouped64_layout(q, k, window, blk: int):
    """``_grouped_layout``'s tables, grid and specs (``r`` query heads
    a KV head: ``r * 128`` lanes of q a pair of KV heads), with a
    statistic a query head."""
    tables, grid, r, group, keys, _, _ = _grouped_layout(q, k, None, window, blk)
    stat = pl.BlockSpec((1, 1, 2 * r, blk), lambda n, c, v, q_of, k_of, f: (n, c, 0, q_of[v]))
    return tables, grid, r, group, keys, stat


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _grouped64_fwd_call(q, k, v, scale, window, blk, interpret):
    tables, grid, r, group, keys, stat = _grouped64_layout(q, k, window, blk)
    return pl.pallas_call(
        partial(_grouped64_fwd_kernel, scale=scale, window=window, r=r, blk=blk,
                interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[group, keys, keys],
            out_specs=(group, stat),
            scratch_shapes=[
                pltpu.VMEM((2 * r, 64, blk), jnp.float32),  # output, transposed
                pltpu.VMEM((2 * r, 1, blk), jnp.float32),  # running max
                pltpu.VMEM((2 * r, 1, blk), jnp.float32),  # running sum
                pltpu.VMEM((2 * r, _LANES, blk), q.dtype),  # q over its KV head, transposed
            ],
        ),
        out_shape=(
            _out_struct(q.shape, q.dtype, q),
            _out_struct((*grid[:2], 2 * r, q.shape[1]), jnp.float32, q),
        ),
        compiler_params=_grouped_params(0, 2 * r, blk),
        interpret=interpret,
        name="grouped64_fwd",
    )(*tables, q, k, v)


@partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _grouped64_bwd_call(q, k, v, o, lse, do, scale, window, blk, interpret):
    t = q.shape[1]
    tables, grid, r, group, keys, stat = _grouped64_layout(q, k, window, blk)
    whole = pl.BlockSpec((1, t, _LANES), lambda n, c, v, q_of, k_of, f: (n, 0, c))
    column = pltpu.VMEM((2 * r, blk, 1), jnp.float32)
    laid_out = pltpu.VMEM((blk, 2 * r * _LANES), q.dtype)
    transposed = pltpu.VMEM((2 * r, _LANES, blk), q.dtype)
    return pl.pallas_call(
        partial(_grouped64_bwd_kernel, scale=scale, window=window, r=r, blk=blk,
                interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[group, keys, keys, group, group, stat],
            out_specs=(group, whole, whole),
            scratch_shapes=[
                pltpu.VMEM((t // blk, _LANES, blk), jnp.float32),  # dK, transposed
                pltpu.VMEM((t // blk, _LANES, blk), jnp.float32),  # dV, transposed
                pltpu.VMEM((blk, 2 * r * _LANES), jnp.float32),  # dQ, a head a lane block
                laid_out, laid_out, transposed, transposed,  # q and dO, and transposed
                column, column,  # the logsumexp and delta of a head's queries
            ],
        ),
        out_shape=tuple(_out_struct(x.shape, x.dtype, x) for x in (q, k, v)),
        compiler_params=_grouped_params(
            2 * t * _LANES * (4 + 2 * k.dtype.itemsize), 2 * r, blk
        ),
        interpret=interpret,
        name="grouped64_bwd",
    )(*tables, q, k, v, o, do, lse)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped64(q, k, v, scale, window, blk):
    """The output, as ``q``, for the kernels' flat operands."""
    return _grouped64_fwd_call(q, k, v, scale, window, blk, pallas_interpret())[0]


def _grouped64_fwd(q, k, v, scale, window, blk):
    o, lse = _grouped64_fwd_call(q, k, v, scale, window, blk, pallas_interpret())
    o, lse = checkpoint_name(o, SAVED_OUT), checkpoint_name(lse, SAVED_LSE)
    return o, (q, k, v, o, lse)


def _grouped64_bwd(scale, window, blk, res, g_o):
    return _grouped64_bwd_call(*res, g_o, scale, window, blk, pallas_interpret())


_grouped64.defvjp(_grouped64_fwd, _grouped64_bwd)
