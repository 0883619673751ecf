"""Manifold-constrained hyper-connections: ``n`` residual streams and
the per-token maps a sublayer reads from and writes to them through.

The residual state of a token is ``X`` in ``R^{n x C}`` (hyper-
connections, arXiv 2409.19606). A sublayer ``F`` no longer computes
``x + F(x)``: three small maps are made from the token's own state,
``F`` reads one mix of the streams and its output is written back to
all of them beside a mix of the streams among themselves, which is
projected onto the doubly stochastic matrices by Sinkhorn-Knopp
("mHC", arXiv 2512.24880)::

    x~ = RMSNorm(vec(X)) * g                              # over all nC entries
    H~pre = a_pre (x~ phi_pre) + b_pre       H~post = a_post (x~ phi_post) + b_post
    H~res = a_res mat(x~ phi_res) + b_res                 # (n, n)
    Hpre = sigmoid(H~pre)     Hpost = 2 sigmoid(H~post)
    M = exp(clamp(H~res));  iters times: M <- M / (colsum(M) + eps), M <- M / (rowsum(M) + eps)
    u = Hpre X  (C)        y = F(u)  (C)        X' = Hres X + Hpost^T y   (n x C)

with ``Hres`` the last ``M``. :class:`HyperConnection` holds one
sublayer's parameters and makes the maps; :func:`read` is ``Hpre X``
and :func:`write` is ``Hres X + Hpost^T y``. ``F`` runs between the
two, outside this module and outside its scopes.

**Laid out for the TPU.** The streams are a tuple of ``n`` arrays
``(B, T, C)``, not one ``(B, T, n, C)`` array: a 4-row minor-but-one
axis would pad to a whole sublane tile, a stream is then cut out and
put back by every mix, and as ``n`` operands of one elementwise fusion
each stream is read once a mix. Copying the embedding into the streams
costs nothing (the model passes the same array ``n`` times). Everything small keeps the
tokens on the lanes: the pre-activations are ``(2n + n^2, N)``, and
the Sinkhorn iterations run on ``(n, n, N / 128, 128)``, whole
``(8, 128)`` tiles with the matrix's two axes in front, so that a row
or column sum is an add of tiles and no ``(N, 4, 4)`` array (64 times
its size in HBM) is ever made. The iterations are one ``fori_loop``
(a ``scan`` under differentiation): one loop body in the program
however many iterations.

Float32 from ``x~`` to ``Hres`` (the products to every bit of a
float32 product at ``Precision.HIGHEST``, which is what the expert
layer's router asks for: the TPU would otherwise take one bf16 pass;
:func:`project_bf16` says how they get there in one pass); the mixes
accumulate in float32 and round once, to the streams' dtype. The norm's factor is a number a token, so the projection runs on
the streams as they are and is scaled after: ``x~ phi = r (X (g phi))``.

**Under ``nn.remat``** (``models/transformer.py::remat_block`` keeps
seven names: the kernels' ``SAVED_OUT`` and ``SAVED_LSE``, an expert
block's ``SAVED_RESIDUAL`` and ``SAVED_QKV``, the router's ``SAVED_ROUTING``
and the two given here): the projections' products and the norm's factor,
25 float32 a token and sublayer (:data:`SAVED_MAPS`), so the recomputed
forward reads the streams for the mixes alone, and the sublayer's
output (:data:`SAVED_Y`), which the backward of ``Hpost^T y`` needs
where a plain residual add needed nothing: without it the recomputed
block would run ``F`` to its end. It is also what spares the
recomputed block ``proj``, as the stream after attention
(``SAVED_RESIDUAL``) does around a plain residual add, which is why a
block behind connections does not name that stream. The 20 iterations
run again in the backward pass, for their own residuals.

Scopes (``utils/profiling.py``): ``hc_maps`` (norm, projections,
sigmoids, Sinkhorn, the counter) and ``hc_mix`` (the three mixes).
"""

from __future__ import annotations

from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from multidisttorch_tpu.utils.profiling import SCOPE_HC_MAPS, SCOPE_HC_MIX

SAVED_MAPS = "hyper_connection_maps"
SAVED_Y = "hyper_connection_y"
_LANES = 128


class Maps(NamedTuple):
    """One sublayer's maps, float32, tokens as the streams have them:
    ``pre``, ``post`` ``(n, B, T)``, ``res`` ``(n, n, B, T)`` with
    ``res[i, j]`` the weight of stream ``j`` in the new stream ``i``;
    ``marginal_err`` the largest distance of a row or column sum of any
    token's ``res`` from 1."""

    pre: jax.Array
    post: jax.Array
    res: jax.Array
    marginal_err: jax.Array


def bf16_parts(a, count: int = 3):
    """``a`` (float32) as ``count`` bf16 arrays that sum to it, each
    holding the next 8 bits of the mantissa: three make a float32."""
    parts = []
    for _ in range(count):
        parts.append(a.astype(jnp.bfloat16))
        a = a - parts[-1].astype(jnp.float32)
    return parts


@jax.custom_vjp
def project_bf16(x, w):
    """``x @ w`` in float32 for ``x`` ``(N, K)`` bf16 and ``w`` ``(K,
    M)`` float32, ``M`` small, each pass one bf16 product on the MXU.
    Asked for as a float32 product at ``Precision.HIGHEST`` it is six
    passes a product, forward and both ways back, each at ``M`` of the
    MXU's 128 columns; but a bf16 ``x`` is its own first part, so ``w``'s
    three bf16 parts side by side (``3M`` columns, one pass) give every
    bit HIGHEST would. Backward: ``w``'s gradient likewise, from the
    three parts of the float32 cotangent; ``x``'s from the three largest
    of the nine part products, in one pass over ``3M`` rows: 2^-16 of its
    size is left out and it is rounded to bf16 next."""
    return _project_fwd(x, w)[0]


def _project_fwd(x, w):
    m = w.shape[-1]
    wide = jnp.dot(x, jnp.concatenate(bf16_parts(w), axis=-1),
                   preferred_element_type=jnp.float32)  # (N, 3M)
    return wide[:, :m] + wide[:, m:2 * m] + wide[:, 2 * m:], (x, w)


def _project_bwd(res, g):
    x, w = res
    m = w.shape[-1]
    g0, g1, g2 = bf16_parts(g)
    w0, w1, _ = bf16_parts(w)
    wide = jnp.dot(x.T, jnp.concatenate([g0, g1, g2], axis=-1),
                   preferred_element_type=jnp.float32)  # (K, 3M)
    dw = wide[:, :m] + wide[:, m:2 * m] + wide[:, 2 * m:]
    dx = jnp.dot(jnp.concatenate([g0, g0, g1], axis=-1),
                 jnp.concatenate([w0, w1, w0], axis=-1).T,
                 preferred_element_type=jnp.float32)  # g0 w0 + g0 w1 + g1 w0
    return dx.astype(x.dtype), dw


project_bf16.defvjp(_project_fwd, _project_bwd)


def _project(x, w):
    """``x @ w`` in float32 whatever ``x`` is: :func:`project_bf16` for
    the bf16 streams of a trial, XLA's own six passes for float32 ones
    (tests, the CPU)."""
    if x.dtype == jnp.bfloat16:
        return project_bf16(x, w)
    return jnp.dot(x.astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST)


def sinkhorn(logits, iters: int, eps: float, clamp: tuple[float, float]):
    """``exp(clamp(logits))`` normalised ``iters`` times, columns then
    rows; ``logits`` is ``(n, n, ...)`` with the matrix's rows and
    columns in front. Rows sum to 1 after the last step, columns as
    nearly as ``iters`` steps bring them."""
    m = jnp.exp(jnp.clip(logits, *clamp))

    def step(_, m):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)  # a column: over the rows i
        return m / (jnp.sum(m, axis=1, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, step, m)


class HyperConnection(nn.Module):
    """The maps of one sublayer from the streams it is about to read:
    ``streams`` (a tuple of ``n`` arrays ``(B, T, C)``) ``->``
    :class:`Maps`. Parameters, all float32: ``norm (nC,)``, ``phi_pre``,
    ``phi_post`` ``(nC, n)``, ``phi_res (nC, n*n)`` (row-major: column
    ``i*n + j`` is ``Hres[i, j]``), ``b_pre``, ``b_post`` ``(n,)``,
    ``b_res (n, n)`` and the gates ``a_pre``, ``a_post``, ``a_res``
    ``()``. A projection's product and a bias are each drawn with
    deviation one half (the gates start at 1): large enough that the
    maps differ from token to token from the first step, small enough
    that 20 iterations bring every token's column sums within 1e-4 of 1
    (at deviation 1 each the worst of 80,000 matrices is 2e-2 away)."""

    sinkhorn_iters: int = 20
    eps: float = 1e-6  # in the Sinkhorn denominators
    clamp: tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, streams) -> Maps:
        n = len(streams)
        b, t, c = streams[0].shape
        tokens = b * t
        fan_in = nn.initializers.variance_scaling(0.25, "fan_in", "normal")
        bias, one = nn.initializers.normal(0.5), nn.initializers.ones
        g = self.param("norm", one, (n * c,), jnp.float32)
        phi = [
            self.param(f"phi_{name}", fan_in, (n * c, width), jnp.float32)
            for name, width in (("pre", n), ("post", n), ("res", n * n))
        ]
        biases = [
            self.param(f"b_{name}", bias, shape, jnp.float32)
            for name, shape in (("pre", (n,)), ("post", (n,)), ("res", (n, n)))
        ]
        gates = [self.param(f"a_{name}", one, (), jnp.float32) for name in ("pre", "post", "res")]

        with jax.named_scope(SCOPE_HC_MAPS):
            weights = (g[:, None] * jnp.concatenate(phi, axis=-1)).reshape(n, c, -1)
            xs = [x.reshape(tokens, c) for x in streams]
            r = jax.lax.rsqrt(
                sum(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1) for x in xs) / (n * c)
                + self.norm_eps
            )  # (N,)
            proj = sum(_project(x, w) for x, w in zip(xs, weights)).T  # (2n + n*n, N)
            proj, r = checkpoint_name(proj, SAVED_MAPS), checkpoint_name(r, SAVED_MAPS)
            gate = jnp.concatenate(
                [jnp.broadcast_to(a, (w.shape[-1],)) for a, w in zip(gates, phi)]
            )
            offset = jnp.concatenate([bb.reshape(-1) for bb in biases])
            pre_act = proj * r[None, :] * gate[:, None] + offset[:, None]

            # tokens as whole (8, 128) tiles behind the maps' own axes
            lanes = _LANES if tokens % _LANES == 0 else tokens
            tiled = pre_act.reshape(-1, tokens // lanes, lanes)
            pre = jax.nn.sigmoid(tiled[:n])
            post = 2.0 * jax.nn.sigmoid(tiled[n:2 * n])
            res = sinkhorn(
                tiled[2 * n:].reshape(n, n, -1, lanes), self.sinkhorn_iters, self.eps, self.clamp
            )
            err = jnp.maximum(
                jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0)),
                jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)),
            )
            return Maps(
                pre.reshape(n, b, t), post.reshape(n, b, t), res.reshape(n, n, b, t),
                jax.lax.stop_gradient(err),
            )


def _weighted(weights, streams):
    """``sum_j weights[j] * streams[j]`` in float32; a weight is ``(B,
    T)``, one number a token."""
    return sum(w[..., None] * x.astype(jnp.float32) for w, x in zip(weights, streams))


def _apart(mix):
    """``mix`` as device operations of its own, in every pass: behind
    ``optimization_barrier``s XLA fuses a mix neither into the norm or
    the projection that follows nor into the product before it (it did:
    most of the mixes' time ran under the neighbours' names, and the
    bytes they move were charged to nobody). What the barriers force
    into HBM lives there anyway: the streams and ``y`` are saved or have
    several readers, ``u`` is read twice by the norm."""

    def apart(*operands):
        return jax.lax.optimization_barrier(mix(*jax.lax.optimization_barrier(operands)))

    return apart


@_apart
def _read(pre, streams):
    return _weighted(pre, streams).astype(streams[0].dtype)


@_apart
def _write(res, post, streams, y):
    y = y.astype(jnp.float32)
    return tuple(
        (_weighted(row, streams) + p[..., None] * y).astype(streams[0].dtype)
        for row, p in zip(res, post)
    )


def read(maps: Maps, streams):
    """``u = Hpre X``: what the sublayer reads, ``(B, T, C)``."""
    with jax.named_scope(SCOPE_HC_MIX):
        return _read(maps.pre, streams)


def write(maps: Maps, streams, y):
    """``X' = Hres X + Hpost^T y``: the streams after the sublayer whose
    output is ``y``, a tuple as ``streams``."""
    with jax.named_scope(SCOPE_HC_MIX):
        return _write(maps.res, maps.post, streams, checkpoint_name(y, SAVED_Y))


def merge(streams):
    """The streams summed into one before the final norm (the same
    paper's rule for the last layer)."""
    with jax.named_scope(SCOPE_HC_MIX):
        return sum(x.astype(jnp.float32) for x in streams).astype(streams[0].dtype)
