"""Which attention a model runs where it was given none, asked while
tracing of the operands' shapes and placement
(``parallel/mesh.py::placement``): a kernel where its rule beside it in
``ops/pallas_attention.py`` takes them (a TPU, operands on one device,
lengths and heads it tiles), the plain form everywhere else (the CPU, a
placement tracing cannot see, a batch or heads over several chips,
where GSPMD would gather a bare ``pallas_call``'s operands). There is
no switch; a model's injected ``attention=`` wins wherever it is given.
"""

from __future__ import annotations

from multidisttorch_tpu.ops.pallas_attention import (
    default_takes_kernel,
    flash_attention,
    grouped_attention,
    grouped_takes_kernel,
    latent_takes_kernel,
)
from multidisttorch_tpu.ops.ring_attention import dense_attention_reference
from multidisttorch_tpu.parallel import mesh


def causal(q, k, v):
    """Exact causal attention of ``(q, k, v)``, ``(B, T, H, Dh)`` each
    (v's width may differ): the blockwise kernel
    (``ops.pallas_attention.flash_attention``, the code
    ``make_flash_attention`` hands out) where ``default_takes_kernel``
    says so, else XLA's dense path, which GSPMD partitions over batch and
    heads."""
    placed = mesh.placement(q)
    if placed and default_takes_kernel(*placed, *q.shape[1:], v.shape[-1]):
        return flash_attention(q, k, v, causal=True)
    return dense_attention_reference(q, k, v, causal=True)


def latent_on_parts(x, num_heads: int, nope: int, rope: int, v_width: int) -> bool:
    """Whether the latent attention of a block whose input is ``x`` ``(B,
    T, d)`` runs ``ops.pallas_attention.latent_attention`` on the parts
    of q and k as their projections make them (``latent_takes_kernel``),
    or assembles q and k for a ``(q, k, v)`` attention. Asked of the
    block's input, before any projection: the answer decides how q and k
    are made."""
    placed = mesh.placement(x)
    return bool(placed and latent_takes_kernel(*placed, x.shape[1], num_heads, nope, rope, v_width))


def grouped_kernel(x, num_heads: int, num_kv_heads: int, head_dim: int, *, rotates_q=False):
    """``ops.pallas_attention.grouped_attention`` where
    ``grouped_takes_kernel`` takes the grouped-head attention of a block
    whose input is ``x`` ``(B, T, d)``, else ``None``: the block then runs
    ``blocked_window_attention``, which takes q rotated. ``rotates_q``:
    the block would hand the kernels a ``q_rotation``; a block that
    rotates q asks before it does, and rotates it itself only on ``None``."""
    placed = mesh.placement(x)
    if placed and grouped_takes_kernel(
        *placed, x.shape[1], num_heads, num_kv_heads, head_dim, rotates_q=rotates_q
    ):
        return grouped_attention
    return None
