"""The selective scan of a Mamba layer, chunked, forward and backward.

Per sequence, with ``E`` channels and a state of ``N`` numbers a
channel (``x``, ``delta`` ``(B, T, E)``; ``A`` ``(E, N)``, negative;
``B``, ``C`` ``(B, T, N)``; ``D`` ``(E,)``)::

    d_t = softplus(delta_t + delta_bias)            # dt's bias and softplus are the scan's: one pass
    h_t = exp(d_t A) * h_{t-1} + (d_t x_t) B_t^T    # (E, N), h_{-1} = 0
    y_t = h_t C_t + D x_t

The ``(B, T, E, N)`` states are never alive (5.4 GB a layer in float32
at T = 16,384, E = 5,120). :func:`selective_scan` is a
``jax.custom_vjp``: the forward walks the sequence in chunks and keeps
the state at each chunk's end, ``(B, T / chunk, N, E)`` float32 (64
chunks of 256: 21 MB), and nothing else of that size; the backward
walks the chunks in reverse, remakes a chunk's states from the end of
the chunk before it, and returns the gradients of ``x``, ``delta``,
``A``, ``B``, ``C``, ``D`` and ``delta_bias``. The state, ``exp``,
``softplus`` and every sum are float32 whatever the operands' dtype;
``y`` and the gradients of ``x``, ``delta``, ``B`` and ``C`` leave at
their operand's dtype.

Two forms of the same chunked walk, and one rule which runs
(:func:`scan_takes_kernel`, which :func:`selective_scan` asks while
tracing, with what tracing shows of ``x``'s placement):

- **On one TPU chip** a Pallas kernel pair, ``scan_fwd`` and
  ``scan_bwd``. A grid step is a chunk of the sequence by a block of
  ``_LANES_A_STEP`` channels; the state rides ``(N, channels)``, N on
  the sublanes and the channels on the lanes, carried in registers
  through the chunk's steps and in VMEM across the grid's sequence
  axis. ``B_t`` and ``C_t`` come as ``(N, 128)`` tiles, a value
  repeated along the lanes (made by XLA, 8 KB a step and fetched once
  a chunk for all the channel blocks). The backward kernel remakes the
  chunk's states into VMEM, then walks it in reverse; the sums over
  the channels that ``dB`` and ``dC`` are leave as ``(T, N, 128)``
  partial sums, the sums over time that ``dA``, ``dD`` and the bias's
  gradient are as one partial a chunk, and XLA adds them up.
- **Everywhere else** (the CPU, several devices, shapes the kernels do
  not tile) the same walk in ``jax.lax``: a ``scan`` over the chunks
  around a ``scan`` over a chunk's steps, the backward a ``jax.vjp`` of
  one chunk at a time. A ``T`` the chunk does not divide is padded with
  steps of ``d_t = 0``, which leave the state as it is.

Under ``models/decoder.py::remat_block`` the forward's ``y`` and
chunk states carry the names ``SAVED_SCAN_OUT`` and
``SAVED_SCAN_STATES``, given in the ``custom_vjp``'s forward rule as
the attention kernels give theirs, so a recomputed block holds no scan.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multidisttorch_tpu.ops.pallas_mode import pallas_interpret
from multidisttorch_tpu.parallel import mesh

SAVED_SCAN_OUT = "selective_scan_out"
SAVED_SCAN_STATES = "selective_scan_states"

CHUNK = 256  # steps between two kept states
_LANES = 128
_LANES_A_STEP = 512  # channels a grid step: the state is 8 vregs at N = 16
_GROUP = 8  # steps unrolled: one sublane tile of a (T, E) operand


def scan_takes_kernel(
    device_kind: str, num_devices: int, seq_len: int, channels: int, d_state: int,
    chunk: int = CHUNK,
) -> bool:
    """Whether :func:`selective_scan` runs the kernel pair or the
    ``jax.lax`` form: a TPU, operands on one device (a bare
    ``pallas_call`` has no partitioning rule), a T that whole chunks
    make up, chunks of whole sublane tiles of steps, channels in blocks
    of 512 and a state of whole sublane tiles. On the chip the pair takes a Mamba layer's scan at 1 x
    16,384 x 5,120 (bf16 operands) 4.5 ms forward and 14.4 with the
    backward, the ``lax`` form of the same walk 15.3 and 98.9, and
    ``associative_scan`` over the whole sequence does not fit the chip
    (PERF.md section 6, PR 37)."""
    return (
        device_kind.startswith("TPU")
        and num_devices == 1
        and seq_len % chunk == 0
        and chunk % _GROUP == 0
        and channels % _LANES_A_STEP == 0
        and d_state % 8 == 0
    )


def _steps(delta, bias):
    """``d_t`` in float32 from ``delta`` as it comes."""
    d = delta.astype(jnp.float32) + bias.astype(jnp.float32)
    # softplus and, below, its slope, written out: the same operations in
    # the kernels and in the plain form
    return jnp.maximum(d, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(d)))


def _slope(delta, bias):
    """``d softplus / d delta`` at ``delta + bias``."""
    return 1.0 / (1.0 + jnp.exp(-(delta + bias)))


# ---------------------------------------------------------------------
# the plain form
# ---------------------------------------------------------------------


def _chunk_walk(h, a, d, chunk_operands):
    """One chunk from the state ``h`` ``(B, E, N)``: ``(h at its end,
    y (L, B, E))``. ``chunk_operands``: x, d ``(L, B, E)``, b, c ``(L,
    B, N)``, time in front; all float32."""

    def step(h, at):
        x_t, d_t, b_t, c_t = at
        h = jnp.exp(d_t[..., None] * a) * h + (d_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("ben,bn->be", h, c_t) + d * x_t

    return jax.lax.scan(step, h, chunk_operands)


def _in_chunks(a, chunk: int):
    """``(B, T, W) -> (chunks, chunk, B, W)``, float32, ``T`` padded to
    whole chunks with zeros: a step of ``d_t = 0`` changes nothing."""
    b, t, w = a.shape
    a = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, (-t) % chunk), (0, 0)))
    return a.reshape(b, -1, chunk, w).transpose(1, 2, 0, 3)


def _from_chunks(a):
    n, l, b, w = a.shape
    return a.transpose(2, 0, 1, 3).reshape(b, n * l, w)


def _plain_fwd(x, delta, a, b, c, d, bias, chunk: int):
    """``(y (B, T, E) float32, ends (B, chunks, N, E))``."""
    a, d = a.astype(jnp.float32), d.astype(jnp.float32)

    def one(h, operands):
        h, y = _chunk_walk(h, a, d, operands)
        return h, (y, h)

    h0 = jnp.zeros((x.shape[0], *a.shape), jnp.float32)
    operands = tuple(_in_chunks(z, chunk) for z in (x, _steps(delta, bias), b, c))
    _, (y, ends) = jax.lax.scan(one, h0, operands)
    return _from_chunks(y)[:, : x.shape[1]], ends.transpose(1, 0, 3, 2)


def _plain_bwd(x, delta, a, b, c, d, bias, chunk: int, ends, g_y, g_last):
    """The gradients of the seven, float32, the chunks in reverse, each
    remade from the end of the one before it."""
    t = x.shape[1]
    a32, d32 = a.astype(jnp.float32), d.astype(jnp.float32)
    steps, pull_steps = jax.vjp(_steps, delta, bias)
    operands = tuple(_in_chunks(z, chunk) for z in (x, steps, b, c))
    starts = jnp.concatenate(
        [jnp.zeros_like(ends[:, :1]), ends[:, :-1]], axis=1
    ).transpose(1, 0, 3, 2)  # (chunks, B, E, N)

    def one(carry, at):
        g_h, g_a, g_d = carry
        start, chunk_operands, g_chunk = at
        _, pull = jax.vjp(_chunk_walk, start, a32, d32, chunk_operands)
        g_h, da, dd, g_operands = pull((g_h, g_chunk))
        return (g_h, g_a + da, g_d + dd), g_operands

    zero = (g_last.astype(jnp.float32), jnp.zeros_like(a32), jnp.zeros_like(d32))
    (_, g_a, g_d), g_operands = jax.lax.scan(
        one, zero, (starts, operands, _in_chunks(g_y, chunk)), reverse=True
    )
    g_x, g_steps, g_b, g_c = (_from_chunks(z)[:, :t] for z in g_operands)
    g_delta, g_bias = pull_steps(g_steps)
    return g_x, g_delta, g_a, g_b, g_c, g_d, g_bias


# ---------------------------------------------------------------------
# the kernels. The state of a block of channels is (N, lanes) float32,
# kept as one (N, 128) array a lane tile; a step's d_t, x_t and dy_t are
# rows of (8, lanes) tiles, repeated down the N sublanes; B_t and C_t
# come repeated along the lanes already.
# ---------------------------------------------------------------------


def _tiles(width: int):
    return [slice(k * _LANES, (k + 1) * _LANES) for k in range(width // _LANES)]


def _rows_of(ref, group):
    return ref[0, pl.ds(pl.multiple_of(group * _GROUP, _GROUP), _GROUP), :].astype(jnp.float32)


def _down(row, n: int):
    """A ``(1, 128)`` row repeated down ``n`` sublanes."""
    return jnp.broadcast_to(row, (n, _LANES))


def _placed(tile, row, i):
    """``tile`` ``(8, 128)`` with ``row`` ``(1, 128)`` as its row
    ``i``."""
    at = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, _LANES), 0)
    return jnp.where(at == i, jnp.broadcast_to(row, (_GROUP, _LANES)), tile)


def _advanced(h, a, d8, u8, i: int, b_t, lanes, n: int):
    """A lane tile of the state after step ``i`` of a group: ``exp(d_t
    A) * h + (d_t x_t) B_t``."""
    return (jnp.exp(_down(d8[i:i + 1, lanes], n) * a) * h + _down(u8[i:i + 1, lanes], n) * b_t)


def _scan_fwd_kernel(x_ref, dt_ref, bias_ref, a_ref, b_ref, c_ref, d_ref, y_ref, ends_ref, h_sc,
                     *, chunk):
    """Grid (B, chunks, channel blocks), the last two sequential: one
    chunk of one block of channels, from the state the chunk before
    left in ``h_sc``."""
    at_chunk, block = pl.program_id(1), pl.program_id(2)
    n, width = a_ref.shape
    tiles = _tiles(width)

    @pl.when(at_chunk == 0)
    def _start():
        h_sc[block] = jnp.zeros((n, width), jnp.float32)

    a = [a_ref[:, lanes] for lanes in tiles]

    def group(gi, h):
        x8 = _rows_of(x_ref, gi)
        d8 = _steps(_rows_of(dt_ref, gi), bias_ref[...])
        u8 = d8 * x8
        h, y8 = list(h), [jnp.zeros((_GROUP, _LANES), jnp.float32) for _ in tiles]
        for i in range(_GROUP):
            b_t = b_ref[0, gi * _GROUP + i].astype(jnp.float32)  # (N, 128)
            c_t = c_ref[0, gi * _GROUP + i].astype(jnp.float32)
            for k, lanes in enumerate(tiles):
                h[k] = _advanced(h[k], a[k], d8, u8, i, b_t, lanes, n)
                y8[k] = _placed(y8[k], jnp.sum(h[k] * c_t, axis=0, keepdims=True), i)
        y = jnp.concatenate(y8, axis=1) + d_ref[...] * x8
        y_ref[0, pl.ds(pl.multiple_of(gi * _GROUP, _GROUP), _GROUP), :] = y.astype(y_ref.dtype)
        return tuple(h)

    h = jax.lax.fori_loop(0, chunk // _GROUP, group, tuple(h_sc[block, :, lanes] for lanes in tiles))
    end = jnp.concatenate(h, axis=1)
    h_sc[block] = end
    ends_ref[0, 0] = end


def _scan_bwd_kernel(x_ref, dt_ref, bias_ref, a_ref, b_ref, c_ref, d_ref, start_ref, gy_ref,
                     glast_ref, gx_ref, gdt_ref, gb_ref, gc_ref, ga_ref, gv_ref, g_sc, hs_sc,
                     *, chunk):
    """Grid as the forward's, the chunks last to first (the index maps
    turn them). ``hs_sc[t + 1]`` is the state after the chunk's step
    ``t`` and ``hs_sc[0]`` the one it started from; ``g_sc`` carries the
    state's cotangent to the chunk before. ``gb_ref`` and ``gc_ref``
    gather over the channel blocks; ``gv_ref`` holds the chunk's part of
    ``dD`` (rows 0 to 7, to be summed) and of the bias's gradient (rows
    8 to 15)."""
    at_chunk, last_chunk, block = pl.program_id(1), pl.num_programs(1) - 1, pl.program_id(2)
    n, width = a_ref.shape
    tiles = _tiles(width)
    groups = chunk // _GROUP

    @pl.when(at_chunk == 0)
    def _start():
        g_sc[block] = glast_ref[0]

    @pl.when(block == 0)
    def _first_block():
        gb_ref[...] = jnp.zeros_like(gb_ref)
        gc_ref[...] = jnp.zeros_like(gc_ref)

    a = [a_ref[:, lanes] for lanes in tiles]
    # the sequence's first chunk (the last one walked) starts from nothing
    hs_sc[0] = jnp.where(at_chunk == last_chunk, 0.0, start_ref[0, 0])

    def remake(gi, h):
        x8 = _rows_of(x_ref, gi)
        d8 = _steps(_rows_of(dt_ref, gi), bias_ref[...])
        u8 = d8 * x8
        h = list(h)
        for i in range(_GROUP):
            b_t = b_ref[0, gi * _GROUP + i].astype(jnp.float32)
            for k, lanes in enumerate(tiles):
                h[k] = _advanced(h[k], a[k], d8, u8, i, b_t, lanes, n)
                hs_sc[gi * _GROUP + i + 1, :, lanes] = h[k]
        return tuple(h)

    jax.lax.fori_loop(0, groups, remake, tuple(hs_sc[0, :, lanes] for lanes in tiles))
    gv_ref[...] = jnp.zeros_like(gv_ref)

    def back(gj, carry):
        gi = groups - 1 - gj
        g, g_a = list(carry[0]), list(carry[1])
        rows = pl.ds(pl.multiple_of(gi * _GROUP, _GROUP), _GROUP)
        x8, gy8 = _rows_of(x_ref, gi), _rows_of(gy_ref, gi)
        raw8 = _rows_of(dt_ref, gi)
        d8 = _steps(raw8, bias_ref[...])
        u8 = d8 * x8
        zero = lambda: [jnp.zeros((_GROUP, _LANES), jnp.float32) for _ in tiles]
        r8, q8 = zero(), zero()  # sum_n g B, and sum_n g a h A, by step and channel
        for i in reversed(range(_GROUP)):
            t = gi * _GROUP + i
            b_t = b_ref[0, t].astype(jnp.float32)
            c_t = c_ref[0, t].astype(jnp.float32)
            g_b, g_c = jnp.zeros((n, _LANES), jnp.float32), jnp.zeros((n, _LANES), jnp.float32)
            for k, lanes in enumerate(tiles):
                gy_t, d_t = _down(gy8[i:i + 1, lanes], n), _down(d8[i:i + 1, lanes], n)
                g_t = g[k] + gy_t * c_t
                g_c = g_c + gy_t * hs_sc[t + 1, :, lanes]
                decay = jnp.exp(d_t * a[k])
                w = g_t * decay * hs_sc[t, :, lanes]
                g_a[k] = g_a[k] + w * d_t
                r8[k] = _placed(r8[k], jnp.sum(g_t * b_t, axis=0, keepdims=True), i)
                q8[k] = _placed(q8[k], jnp.sum(w * a[k], axis=0, keepdims=True), i)
                g_b = g_b + g_t * _down(u8[i:i + 1, lanes], n)
                g[k] = decay * g_t
            gb_ref[0, t] = gb_ref[0, t] + g_b
            gc_ref[0, t] = gc_ref[0, t] + g_c
        r8, q8 = jnp.concatenate(r8, axis=1), jnp.concatenate(q8, axis=1)
        g_d8 = (q8 + x8 * r8) * _slope(raw8, bias_ref[...])
        gx_ref[0, rows, :] = (d_ref[...] * gy8 + d8 * r8).astype(gx_ref.dtype)
        gdt_ref[0, rows, :] = g_d8.astype(gdt_ref.dtype)
        gv_ref[0, 0, :_GROUP, :] = gv_ref[0, 0, :_GROUP, :] + gy8 * x8
        gv_ref[0, 0, _GROUP:, :] = gv_ref[0, 0, _GROUP:, :] + g_d8
        return tuple(g), tuple(g_a)

    g, g_a = jax.lax.fori_loop(
        0, groups, back,
        (tuple(g_sc[block, :, lanes] for lanes in tiles),
         tuple(jnp.zeros((n, _LANES), jnp.float32) for _ in tiles)),
    )
    g_sc[block] = jnp.concatenate(g, axis=1)
    ga_ref[0, 0] = jnp.concatenate(g_a, axis=1)


def _along_lanes(z):
    """``(B, T, N) -> (B, T, N, 128)``, a value repeated along the
    lanes: what a step multiplies an ``(N, 128)`` tile of the state
    by."""
    return jnp.broadcast_to(z[..., None], (*z.shape, _LANES))


def _kernel_grid(x, chunk: int):
    """(B, chunks, channel blocks): the last two sequential."""
    bsz, t, e = x.shape
    return bsz, t // chunk, e // _LANES_A_STEP


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=64 << 20
)


@partial(jax.jit, static_argnums=(7, 8))
def _kernel_fwd(x, delta, a, b, c, d, bias, chunk, interpret):
    grid, n, width = _kernel_grid(x, chunk), a.shape[1], _LANES_A_STEP
    bsz, t, e = x.shape
    rows = pl.BlockSpec((1, chunk, width), lambda i, j, k: (i, j, k))
    channel = pl.BlockSpec((1, width), lambda i, j, k: (0, k))
    decay = pl.BlockSpec((n, width), lambda i, j, k: (0, k))
    inputs = pl.BlockSpec((1, chunk, n, _LANES), lambda i, j, k: (i, j, 0, 0))
    return pl.pallas_call(
        partial(_scan_fwd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[rows, rows, channel, decay, inputs, inputs, channel],
        out_specs=(rows, pl.BlockSpec((1, 1, n, width), lambda i, j, k: (i, j, 0, k))),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz, grid[1], n, e), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((grid[2], n, width), jnp.float32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
        name="scan_fwd",
    )(x, delta, bias.astype(jnp.float32)[None], a.astype(jnp.float32).T,
      _along_lanes(b), _along_lanes(c), d.astype(jnp.float32)[None])


@partial(jax.jit, static_argnums=(10, 11))
def _kernel_bwd(x, delta, a, b, c, d, bias, ends, g_y, g_last, chunk, interpret):
    """The seven gradients, the sums float32; ``g_last`` ``(B, N, E)``."""
    grid, n, width = _kernel_grid(x, chunk), a.shape[1], _LANES_A_STEP
    bsz, t, e = x.shape
    last = grid[1] - 1
    rows = pl.BlockSpec((1, chunk, width), lambda i, j, k: (i, last - j, k))
    channel = pl.BlockSpec((1, width), lambda i, j, k: (0, k))
    decay = pl.BlockSpec((n, width), lambda i, j, k: (0, k))
    inputs = pl.BlockSpec((1, chunk, n, _LANES), lambda i, j, k: (i, last - j, 0, 0))
    before = pl.BlockSpec(  # the end of the chunk before: where this one started
        (1, 1, n, width), lambda i, j, k: (i, jnp.maximum(last - j - 1, 0), 0, k)
    )
    state = pl.BlockSpec((1, n, width), lambda i, j, k: (i, 0, k))
    of_chunk = lambda rows_: pl.BlockSpec((1, 1, rows_, width), lambda i, j, k: (i, last - j, 0, k))
    per_chunk = lambda rows_: jax.ShapeDtypeStruct((bsz, grid[1], rows_, e), jnp.float32)
    partial_sums = jax.ShapeDtypeStruct((bsz, t, n, _LANES), jnp.float32)
    g_x, g_delta, g_b, g_c, g_a, g_v = pl.pallas_call(
        partial(_scan_bwd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[rows, rows, channel, decay, inputs, inputs, channel, before, rows, state],
        out_specs=(rows, rows, inputs, inputs, of_chunk(n), of_chunk(2 * _GROUP)),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(delta.shape, delta.dtype),
            partial_sums, partial_sums, per_chunk(n), per_chunk(2 * _GROUP),
        ),
        scratch_shapes=[
            pltpu.VMEM((grid[2], n, width), jnp.float32),
            pltpu.VMEM((chunk + 1, n, width), jnp.float32),
        ],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
        name="scan_bwd",
    )(x, delta, bias.astype(jnp.float32)[None], a.astype(jnp.float32).T,
      _along_lanes(b), _along_lanes(c), d.astype(jnp.float32)[None], ends, g_y, g_last)
    return (
        g_x, g_delta, jnp.sum(g_a, axis=(0, 1)).T,
        jnp.sum(g_b, axis=-1), jnp.sum(g_c, axis=-1),
        jnp.sum(g_v[:, :, :_GROUP], axis=(0, 1, 2)), jnp.sum(g_v[:, :, _GROUP:], axis=(0, 1, 2)),
    )


# ---------------------------------------------------------------------
# the call
# ---------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan(x, delta, a, b, c, d, bias, chunk, kernel):
    """``(y as x, the last state (B, N, E) float32)``."""
    y, ends = _forward(x, delta, a, b, c, d, bias, chunk, kernel)
    return y, ends[:, -1]


def _forward(x, delta, a, b, c, d, bias, chunk, kernel):
    if kernel:
        return _kernel_fwd(x, delta, a, b, c, d, bias, chunk, pallas_interpret())
    y, ends = _plain_fwd(x, delta, a, b, c, d, bias, chunk)
    return y.astype(x.dtype), ends


def _scan_fwd(x, delta, a, b, c, d, bias, chunk, kernel):
    y, ends = _forward(x, delta, a, b, c, d, bias, chunk, kernel)
    y, ends = checkpoint_name(y, SAVED_SCAN_OUT), checkpoint_name(ends, SAVED_SCAN_STATES)
    return (y, ends[:, -1]), (x, delta, a, b, c, d, bias, ends)


def _scan_bwd(chunk, kernel, res, cotangents):
    x, delta, a, b, c, d, bias, ends = res
    g_y, g_last = cotangents
    if kernel:
        grads = _kernel_bwd(x, delta, a, b, c, d, bias, ends, g_y, g_last.astype(jnp.float32),
                            chunk, pallas_interpret())
    else:
        grads = _plain_bwd(x, delta, a, b, c, d, bias, chunk, ends, g_y,
                           g_last.transpose(0, 2, 1))
    return tuple(g.astype(z.dtype) for g, z in zip(grads, (x, delta, a, b, c, d, bias)))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, delta, A, B, C, D, delta_bias, *, chunk: int = CHUNK,
                   return_last_state: bool = False):
    """``y (B, T, E)`` as ``x``, and asked the last state ``(B, E, N)``
    float32, of the recurrence in the module's docstring; differentiable
    in all of ``x``, ``delta``, ``A``, ``B``, ``C``, ``D``,
    ``delta_bias`` (and through the last state). The Pallas pair where
    :func:`scan_takes_kernel` says so of these operands as ``x`` is
    placed, else the ``jax.lax`` form. ``chunk``: steps between two
    states kept for the backward pass."""
    placed = mesh.placement(x)
    kernel = bool(placed) and scan_takes_kernel(*placed, x.shape[1], *A.shape, chunk)
    y, last = _scan(x, delta, A, B, C, D, delta_bias, chunk, kernel)
    return (y, last.transpose(0, 2, 1)) if return_last_state else y
