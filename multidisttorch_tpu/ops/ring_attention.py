"""Ring attention: sequence-parallel exact attention over a device axis.

The reference framework has no attention or sequence dimension at all
(SURVEY.md §5 — its model is an MLP VAE), but a framework claiming its
scale on TPU must handle long-context models whose sequences exceed one
chip's HBM. This op shards the sequence across a (sub)mesh axis and
computes **exact** softmax attention by rotating K/V blocks around the
ring with ``jax.lax.ppermute`` (ICI neighbor exchanges — the topology
ring attention was designed for), carrying the online-softmax running
max/sum so no device ever materializes the full (T, T) score matrix.

Memory per device: O(T/n · T/n) scores instead of O(T²); communication:
n-1 neighbor hops of the local K/V block, overlapped by XLA with the
per-block compute. Composes with the framework's trial parallelism: the
ring axis is any ``TrialMesh``'s data axis, so one trial can run
sequence-parallel attention while others train unrelated models.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from multidisttorch_tpu.parallel.mesh import DATA_AXIS, TrialMesh


def _attention_block(q, k, v, q_pos, k_pos, m, l, acc, *, causal, scale):
    """One online-softmax update of local Q against one K/V block.

    q: (B, Tq, H, D); k, v: (B, Tk, H, D); m, l: (B, H, Tq);
    acc: (B, Tq, H, D). Standard flash-attention running update.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # (B, H, Tq, Tk)
    if causal:
        mask = k_pos[None, None, None, :] <= q_pos[None, None, :, None]
        s = jnp.where(mask, s, -jnp.inf)
    blk_max = jnp.max(s, axis=-1)  # (B, H, Tq)
    m_new = jnp.maximum(m, blk_max)
    # guard fully-masked rows (m_new == -inf): keep them at zero weight
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])  # (B, H, Tq, Tk)
    if causal:
        p = jnp.where(jnp.isfinite(s), p, 0.0)
    correction = jnp.where(
        jnp.isfinite(m), jnp.exp(m - safe_m), jnp.zeros_like(m)
    )
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v
    )
    return m_new, l_new, acc_new


def _ring_attention_local(
    q, k, v, *, axis_name, num_devices, causal, scale, vary_axes=None
):
    """Per-device body under shard_map: local Q stays put, K/V rotate.

    ``vary_axes`` lists every mesh axis the operands vary over — just
    the ring axis in 1-D mode, plus the model axis when heads are
    sharded (2-D sequence x head parallelism). The body itself is
    oblivious to the head count: attention is per-head local math.
    """
    my_idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    q_pos = my_idx * t_local + jnp.arange(t_local)

    b, _, h, d = q.shape
    # The carry starts as constants but becomes device-varying through
    # the loop body; shard_map's VMA typing requires the initial carry
    # to carry the axis annotation already.
    from multidisttorch_tpu.parallel.collectives import pvary

    axes = vary_axes if vary_axes is not None else (axis_name,)
    m0 = pvary(jnp.full((b, h, t_local), -jnp.inf, jnp.float32), axes)
    l0 = pvary(jnp.zeros((b, h, t_local), jnp.float32), axes)
    # v may be narrower than q and k (latent attention); the output is v's
    acc0 = pvary(jnp.zeros((b, t_local, h, v.shape[-1]), jnp.float32), axes)

    def body(step, carry):
        k_blk, v_blk, m, l, acc = carry
        src_idx = (my_idx - step) % num_devices
        k_pos = src_idx * t_local + jnp.arange(t_local)
        m, l, acc = _attention_block(
            q.astype(jnp.float32),
            k_blk.astype(jnp.float32),
            v_blk.astype(jnp.float32),
            q_pos,
            k_pos,
            m,
            l,
            acc,
            causal=causal,
            scale=scale,
        )
        # rotate K/V one hop around the ring (device i -> i+1), so next
        # step this device holds the block of (my_idx - step - 1) % n
        perm = [(i, (i + 1) % num_devices) for i in range(num_devices)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, acc

    _, _, m, l, acc = jax.lax.fori_loop(0, num_devices, body, (k, v, m0, l0, acc0))
    # normalize; fully-masked rows (l == 0) return zeros
    denom = jnp.where(l > 0, l, 1.0).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


@lru_cache(maxsize=None)
def _make_ring_attention_cached(
    mesh: Mesh, axis_name: str, causal: bool, head_axis: str | None = None
):
    num_devices = int(mesh.shape[axis_name])
    # sequence sharded over the ring axis; heads over the model axis
    # when 2-D (sequence x head) parallelism is on
    spec = P(None, axis_name, head_axis, None)
    vary_axes = (axis_name,) + ((head_axis,) if head_axis else ())

    def fn(q, k, v):
        scale = 1.0 / (q.shape[-1] ** 0.5)
        return jax.shard_map(
            partial(
                _ring_attention_local,
                axis_name=axis_name,
                num_devices=num_devices,
                causal=causal,
                scale=scale,
                vary_axes=vary_axes,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )(q, k, v)

    return jax.jit(fn)


def _resolve_head_axis(mesh: Mesh, shard_heads) -> str | None:
    """Shared by ring and ring-flash: which mesh axis (if any) shards
    the head dimension. ``"auto"`` shards whenever the trial actually
    has a model axis — the 2-D (sequence x head) configuration."""
    from multidisttorch_tpu.parallel.mesh import MODEL_AXIS

    m = int(dict(mesh.shape).get(MODEL_AXIS, 1))
    if shard_heads == "auto":
        return MODEL_AXIS if m > 1 else None
    if shard_heads:
        if m <= 1:
            raise ValueError(
                "shard_heads=True needs a model axis on the trial mesh "
                "(setup_groups(model_parallel=...))"
            )
        return MODEL_AXIS
    return None


def _wrap_head_check(inner, mesh: Mesh, head_axis: str | None):
    """Shared by ring and ring-flash entry points: validate head
    divisibility at call time and expose ``.head_sharded``."""
    m = int(mesh.shape[head_axis]) if head_axis else 1

    def fn(q, k, v):
        if head_axis and q.shape[2] % m:
            raise ValueError(
                f"heads={q.shape[2]} not divisible by the model axis "
                f"({m}); pass shard_heads=False or adjust the model"
            )
        return inner(q, k, v)

    fn.head_sharded = head_axis is not None
    # Ring callables always run a shard_map with ppermute hops — the
    # marker pipeline staging checks (a collective cannot execute
    # inside a lax.switch branch only some devices take).
    fn.carries_collectives = True
    return fn


def make_ring_attention(
    trial: TrialMesh | Mesh, *, causal: bool = False, shard_heads="auto"
):
    """Compiled sequence-parallel attention over a trial's device axis.

    Returns ``fn(q, k, v) -> out`` for arrays of shape ``(batch, seq,
    heads, head_dim)`` with ``seq`` divisible by the data-axis extent;
    the sequence dimension is sharded across the ring, and the result
    is numerically exact attention (fp32 accumulation). On a 2-D
    ``(data x model)`` trial mesh, heads additionally shard over the
    model axis (``shard_heads="auto"``; heads must divide it) — the
    sequence x head parallel configuration that composes with
    ``transformer_tp_shardings``'s attention-column shards. The
    returned callable exposes ``.head_sharded`` for introspection.
    """
    mesh = trial.mesh if isinstance(trial, TrialMesh) else trial
    head_axis = _resolve_head_axis(mesh, shard_heads)
    inner = _make_ring_attention_cached(mesh, DATA_AXIS, causal, head_axis)
    return _wrap_head_check(inner, mesh, head_axis)


def dense_attention_reference(q, k, v, *, causal: bool = False):
    """Plain O(T²) attention: scores, mask, softmax, a second matmul,
    with the ``(B, H, Tq, Tk)`` scores in memory. The reference every
    other attention of the package is tested against, and what a model
    given no attention falls back to wherever the blockwise kernel
    does not apply (``ops.pallas_attention.default_takes_kernel``):
    off the TPU, over several chips (XLA partitions it over batch and
    heads as it stands), at a length or head width the kernel does not
    tile."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
