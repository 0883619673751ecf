"""Fused negative-ELBO Pallas TPU kernel (forward + backward).

The ELBO (``ops/losses.py``, mirroring /root/reference/vae-hpo.py:49-58)
is a pure bandwidth-bound reduction over four arrays (logits, targets,
mu, logvar). XLA already fuses most of it; this kernel makes the fusion
explicit and total — one VMEM pass producing the scalar loss, and one
pass producing all three gradients — and serves as the repo's reference
pattern for Pallas TPU kernels (per /opt/skills/guides/pallas_guide.md).

Differentiable via ``jax.custom_vjp``: the backward kernel computes
  d/dlogits  = sigmoid(logits) - x          (BCE-from-logits)
  d/dmu      = beta * mu                    (KL)
  d/dlogvar  = beta * 0.5 * (exp(logvar) - 1)
all scaled by the upstream cotangent.

Compiles through Mosaic; the CPU tests run it in interpreter mode by
asking for it (``ops/pallas_mode.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multidisttorch_tpu.ops.pallas_mode import pallas_interpret


# VMEM working-set budget for one grid step (both passes keep ≤4 operand
# blocks + ≤3 output blocks resident; v5e VMEM is 128MB/core but small
# blocks pipeline better and leave room for XLA's own buffers). Module
# constant so tests can shrink it to force multi-block grids.
_VMEM_BUDGET_BYTES = 4 * 2**20


def _block_rows(logits, x, mu, logvar) -> int:
    """Rows per grid step: the largest divisor of ``batch`` that is a
    whole number of sublane tiles and whose 7-buffer working set fits
    the VMEM budget (whole rows only: the feature dims stay unsplit, so
    the reduction needs no cross-column accumulator). Sized from the
    actual operand dtypes — bf16 blocks are half the bytes of f32, so
    the bf16 train path gets twice the rows per grid step. The TPU
    lowering takes a row block only in multiples of the sublane tile
    (8 rows of f32, 16 of bf16) or as the whole batch, so a batch with
    no such divisor runs as one block."""
    batch, d = logits.shape
    latent = mu.shape[1]
    # Worst-case resident set (the bwd pass): logits, x, dlogits wide;
    # mu, logvar, dmu, dlogvar narrow — outputs at their primal's dtype.
    per_row = d * (2 * logits.dtype.itemsize + x.dtype.itemsize) + latent * 2 * (
        mu.dtype.itemsize + logvar.dtype.itemsize
    )
    tile = 32 // min(a.dtype.itemsize for a in (logits, x, mu, logvar))
    target = max(tile, _VMEM_BUDGET_BYTES // per_row)
    if batch <= target:
        return batch
    for bb in range(target - target % tile, 0, -tile):
        if batch % bb == 0:
            return bb
    return batch


def _fwd_kernel(logits_ref, x_ref, mu_ref, logvar_ref, out_ref, *, beta):
    # Blocks stream in at their storage dtype (bf16 on the TPU train
    # path — half the HBM bytes of f32); the reduction itself is f32.
    l = logits_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    # stable BCE from logits: max(l,0) - l*x + log1p(exp(-|l|))
    bce = jnp.sum(
        jnp.maximum(l, 0.0) - l * x + jnp.log1p(jnp.exp(-jnp.abs(l)))
    )
    mu = mu_ref[:].astype(jnp.float32)
    logvar = logvar_ref[:].astype(jnp.float32)
    kl = -0.5 * jnp.sum(1.0 + logvar - mu * mu - jnp.exp(logvar))
    part = bce + beta * kl

    # Scalar accumulation across the (sequential) batch-block grid: the
    # SMEM output block is the same (0,0) cell every step. Every store
    # casts to the REF's dtype explicitly: Mosaic rejects a swap whose
    # value dtype strays from the ref (the round-4 hardware failure —
    # "Invalid dtype for swap: Ref float32 vs value bfloat16" — when
    # bf16 operands reached this accumulator; interpret mode casts
    # silently, so only the explicit cast keeps both worlds identical).
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[0, 0] = part.astype(out_ref.dtype)

    @pl.when(pl.program_id(0) > 0)
    def _acc():
        out_ref[0, 0] = (
            out_ref[0, 0].astype(jnp.float32) + part
        ).astype(out_ref.dtype)


def _bwd_kernel(logits_ref, x_ref, mu_ref, logvar_ref,
                dlogits_ref, dmu_ref, dlogvar_ref, *, beta):
    # f32 math, outputs stored back at each cotangent's own dtype
    # (= its primal's dtype, per custom_vjp's contract).
    l = logits_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    dlogits_ref[:] = (jax.nn.sigmoid(l) - x).astype(dlogits_ref.dtype)
    dmu_ref[:] = (beta * mu_ref[:].astype(jnp.float32)).astype(dmu_ref.dtype)
    dlogvar_ref[:] = (
        beta * 0.5 * (jnp.exp(logvar_ref[:].astype(jnp.float32)) - 1.0)
    ).astype(dlogvar_ref.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_elbo_loss_sum(logits, x, mu, logvar, beta=1.0):
    """Summed negative ELBO, fused in a single Pallas kernel.

    Drop-in for :func:`ops.losses.elbo_loss_sum` (same semantics as the
    reference loss at beta=1). Arrays are 2-D ``(batch, D)`` /
    ``(batch, latent)`` in any float dtype (mixed ok — the TPU train
    path feeds bf16 activations with f32 targets); reduction math is
    always f32, gradients come back in each primal's own dtype.
    """
    return _fwd(logits, x, mu, logvar, beta)[0]


def _fwd(logits, x, mu, logvar, beta):
    b, d = logits.shape
    lat = mu.shape[1]
    bb = _block_rows(logits, x, mu, logvar)
    out = pl.pallas_call(
        partial(_fwd_kernel, beta=beta),
        grid=(b // bb,),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        in_specs=[
            pl.BlockSpec((bb, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, lat), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, lat), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM
        ),
        interpret=pallas_interpret(),
    )(logits, x, mu, logvar)
    return out[0, 0], (logits, x, mu, logvar)


def _bwd(beta, residuals, g):
    logits, x, mu, logvar = residuals
    b, d = logits.shape
    lat = mu.shape[1]
    bb = _block_rows(logits, x, mu, logvar)
    wide = lambda: pl.BlockSpec(
        (bb, d), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    narrow = lambda: pl.BlockSpec(
        (bb, lat), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    dlogits, dmu, dlogvar = pl.pallas_call(
        partial(_bwd_kernel, beta=beta),
        grid=(b // bb,),
        out_shape=(
            jax.ShapeDtypeStruct(logits.shape, logits.dtype),
            jax.ShapeDtypeStruct(mu.shape, mu.dtype),
            jax.ShapeDtypeStruct(logvar.shape, logvar.dtype),
        ),
        in_specs=[wide(), wide(), narrow(), narrow()],
        out_specs=(wide(), narrow(), narrow()),
        interpret=pallas_interpret(),
    )(logits, x, mu, logvar)
    # x is data: propagate its true cotangent (-logits * g) for
    # completeness even though training never differentiates w.r.t. it.
    # Cotangent dtypes must equal primal dtypes (custom_vjp contract).
    return (
        (g * dlogits).astype(logits.dtype),
        (g * (-logits)).astype(x.dtype),
        (g * dmu).astype(mu.dtype),
        (g * dlogvar).astype(logvar.dtype),
    )


fused_elbo_loss_sum.defvjp(_fwd, _bwd)

