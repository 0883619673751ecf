"""Mixture-of-experts MLPs: a capacity-limited one and a dropless one.

The reference has no MoE or expert parallelism (SURVEY.md §2c). Two
layers live here.

:class:`MoEMLP` (GShard/Switch style, what ``MoETransformerLM`` and
``moe_vae`` run): top-1 routing under a capacity limit, expressed as
STATIC one-hot dispatch/combine einsums (no gather/scatter, no dynamic
shapes), so the whole block jits into a handful of MXU-friendly
contractions. Each expert serves at most ``C = ceil(tokens/E *
capacity_factor)`` tokens per batch; overflow tokens pass through with
zero contribution. Expert parallelism is a sharding: every
expert-indexed parameter carries a leading ``(E, ...)`` axis annotated
over the submesh's ``model`` axis (:func:`moe_ep_shardings`), and GSPMD
partitions the einsums so each device runs only its experts, inserting
the all-to-all-equivalent collectives itself. The Switch auxiliary
load-balancing loss (eq. 4) is returned alongside the output.

:class:`RoutedExperts` (what today's fine-grained expert models run,
``models/latent_moe.py``): sigmoid scores over ALL the experts of the
layer, the ``top_k`` largest of score + selection bias a token, weights
normalised over the chosen, no capacity and no dropped token. The layer
is told which experts it holds (``experts_held``: one chip's share of
an expert-parallel group) and adds only their terms, plus a shared
expert computed whole; what the absent experts would add is left out,
and nothing stands in for the exchange that would fetch it. The
(token, expert) assignments that land here are sorted by expert, their
tokens gathered into one buffer, run through grouped matrix products
(``grouped_dot``: XLA's ``jax.lax.ragged_dot``, or on one TPU chip
jax's Pallas grouped-matmul kernel, chosen by the model from the
operands' placement) and summed back by weight. Shapes are static: the buffer holds twice the mean
load, and a step whose routing sends more than that here (up to every
token with all it can send) walks the expert order one buffer at a
time, chosen by ``lax.cond`` on the step's own count.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from multidisttorch_tpu.ops.pallas_mode import pallas_interpret
from multidisttorch_tpu.utils.profiling import (
    SCOPE_EXPERT_DISPATCH,
    SCOPE_EXPERTS,
    SCOPE_ROUTER,
    SCOPE_SHARED_EXPERT,
)


class MoEMLP(nn.Module):
    """Top-1-routed expert MLP: ``(B, d_in) -> (B, d_out)``.

    Parameters carry a leading expert axis — ``gate`` is a plain dense
    router, ``w1/b1/w2/b2`` are per-expert two-layer MLP weights.
    """

    num_experts: int
    hidden_dim: int
    out_dim: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        b, d = x.shape
        e, h, o = self.num_experts, self.hidden_dim, self.out_dim
        cap = max(1, math.ceil(b * self.capacity_factor / e))
        x = x.astype(self.dtype)

        init = nn.initializers.lecun_normal()
        w1 = self.param("w1", init, (e, d, h), jnp.float32).astype(self.dtype)
        b1 = self.param(
            "b1", nn.initializers.zeros, (e, h), jnp.float32
        ).astype(self.dtype)
        w2 = self.param("w2", init, (e, h, o), jnp.float32).astype(self.dtype)
        b2 = self.param(
            "b2", nn.initializers.zeros, (e, o), jnp.float32
        ).astype(self.dtype)

        gates = jax.nn.softmax(
            nn.Dense(e, dtype=jnp.float32, param_dtype=jnp.float32,
                     name="gate")(x.astype(jnp.float32)),
            axis=-1,
        )  # (B, E) — router math in f32 for stable argmax/softmax
        expert_idx = jnp.argmax(gates, axis=-1)  # (B,)
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (B, E)
        top_gate = jnp.sum(gates * onehot, axis=-1)  # (B,)

        # Queue position of each token within its chosen expert; tokens
        # past capacity are dropped (zero dispatch -> zero output).
        pos = jnp.cumsum(onehot, axis=0) * onehot  # (B, E), 1-based
        within = (pos > 0) & (pos <= cap)
        disp = jax.nn.one_hot(
            (pos - 1.0).astype(jnp.int32), cap, dtype=jnp.float32
        ) * within[..., None].astype(jnp.float32)  # (B, E, C)

        expert_in = jnp.einsum(
            "bec,bd->ecd", disp.astype(self.dtype), x
        )  # (E, C, d)
        hmid = jax.nn.relu(
            jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None, :]
        )
        out_e = jnp.einsum("ech,eho->eco", hmid, w2) + b2[:, None, :]

        combine = disp * top_gate[:, None, None]  # (B, E, C)
        y = jnp.einsum("bec,eco->bo", combine.astype(self.dtype), out_e)

        # Switch aux loss: E * sum_e (fraction routed to e) * (mean gate
        # prob of e) — minimized at uniform routing.
        frac = jnp.mean(onehot, axis=0)
        prob = jnp.mean(gates, axis=0)
        aux = e * jnp.sum(frac * prob)
        return y, aux.astype(jnp.float32)


def moe_ep_shardings(trial, params: Any) -> Any:
    """Expert-parallel shardings for a :class:`MoEMLP` param tree: every
    expert-indexed leaf (leading axis ``num_experts``) splits over the
    submesh's ``model`` axis; the router stays replicated. GSPMD then
    partitions the dispatch/compute/combine einsums per expert shard.

    Requires ``num_experts % trial.model_size == 0``.
    """
    from multidisttorch_tpu.parallel.mesh import MODEL_AXIS

    m = trial.model_size
    repl = trial.sharding()

    def rule(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("w1", "b1", "w2", "b2"):
            if leaf.shape[0] % m:
                raise ValueError(
                    f"num_experts={leaf.shape[0]} not divisible by the "
                    f"model axis ({m})"
                )
            return trial.sharding(MODEL_AXIS, *([None] * (leaf.ndim - 1)))
        return repl

    return jax.tree_util.tree_map_with_path(rule, params)


# ---------------------------------------------------------------------
# the dropless layer
# ---------------------------------------------------------------------


def _gather_tokens(x, tok):
    """Row ``r`` of the buffer is token ``tok[r]``: ``(N, d) -> (M, d)``."""
    return jnp.take(x, tok, axis=0)


def _sum_slots(ys, row, w):
    """``out[t] = sum_j w[t, j] * ys[row[t, j]]`` in float32: a token's
    ``k`` slots read back from the buffer. A slot of weight 0 adds
    exactly 0 whatever its row holds (rows past the step's count are
    never written)."""
    picked = jnp.take(ys, row, axis=0).astype(jnp.float32)  # (N, k, d)
    w = w[..., None]
    return jnp.sum(jnp.where(w != 0, picked * w, 0.0), axis=1).astype(ys.dtype)


# Both directions of the exchange between token order and expert order
# are gathers: row r holds one (token, slot) pair and each pair has one
# row, so the transpose of "gather the tokens" is "sum each token's
# slots" and the reverse. Autodiff would write both transposes as
# scatter-adds of (M, d) rows.


@jax.custom_vjp
def _dispatch(x, tok, row, held):
    return _gather_tokens(x, tok)


def _dispatch_fwd(x, tok, row, held):
    return _gather_tokens(x, tok), (tok, row, held)


def _dispatch_bwd(res, g):
    tok, row, held = res
    return _sum_slots(g, row, held), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, tok, row, w, w_of_row):
    return _sum_slots(ys, row, w)


def _combine_fwd(ys, tok, row, w, w_of_row):
    return _sum_slots(ys, row, w), (ys, tok, row, w, w_of_row)


def _combine_bwd(res, g):
    ys, tok, row, w, w_of_row = res
    g_ys = (_gather_tokens(g, tok).astype(jnp.float32) * w_of_row[:, None]).astype(ys.dtype)
    picked = jnp.take(ys, row, axis=0).astype(jnp.float32)
    g_w = jnp.einsum("nkd,nd->nk", picked, g.astype(jnp.float32))
    g_w = jnp.where(w != 0, g_w, 0.0)
    return g_ys, None, None, g_w, jnp.zeros_like(w_of_row)


_combine.defvjp(_combine_fwd, _combine_bwd)


# The experts' matrix products: rows ``sum(sizes[:g]) .. sum(sizes[:g+1])``
# of ``lhs`` times ``rhs[g]``, float32 accumulated and out; rows past
# ``sum(sizes)`` hold whatever (the layer masks them).

_TILE_ROWS = 512  # rows a tile of the kernel; mean load an expert in moe-mla-t4096


def ragged_grouped_dot(lhs, rhs, sizes):
    """XLA's own (``jax.lax.ragged_dot``): every backend, any shape."""
    return jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=jnp.float32)


def kernel_grouped_dot(lhs, rhs, sizes):
    """jax's Pallas grouped matmul for the TPU (megablox ``gmm``, with
    its own backward kernels): only the tiles that hold rows of a group
    are visited. XLA's ragged dot, expanded by the TPU compiler, ran
    the cell's experts no faster and its operations carry no scope
    path, so a trace could not say whose they were (PERF.md section 6)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    # the widest tile up to 1,024 that divides the width into whole tiles
    tile = lambda width: next(t for t in range(1024, 0, -128) if width % t == 0)
    return gmm(
        lhs, rhs, sizes, jnp.float32, (_TILE_ROWS, *map(tile, rhs.shape[1:])),
        interpret=pallas_interpret(),
    )


def grouped_dot_takes_kernel(
    device_kind: str, num_devices: int, rows: int, k: int, n: int
) -> bool:
    """Whether an expert layer that was given no ``grouped_dot`` runs
    the Pallas kernel (``models/latent_moe.py`` asks, with what tracing
    shows of the operands' placement, as ``default_takes_kernel`` is
    asked for the attention) or XLA's ragged dot: a TPU, operands on
    one device, whole tiles of rows and whole lanes of both widths."""
    return (
        device_kind.startswith("TPU")
        and num_devices == 1
        and rows % _TILE_ROWS == 0
        and k % 128 == 0
        and n % 128 == 0
    )


def _buffer_rows(n: int, k: int, count: int, e: int) -> tuple[int, int]:
    """``(usual, worst)`` rows of the buffer of assignments for ``n``
    tokens choosing ``k`` of ``e`` experts, ``count`` of them held
    here: every token can send ``min(k, count)`` assignments, on
    average ``n*k*count/e`` arrive, and the usual buffer holds twice
    that (a multiple of 8 rows)."""
    worst = n * min(k, count)
    return min(worst, -(-2 * n * k * count // e // 8) * 8), worst


class RoutedExperts(nn.Module):
    """Dropless sigmoid-routed experts, one chip's share of them:
    ``(N, d) -> ((N, d), (count,) int32)``, the second the assignments
    to each expert held.

    ``num_experts`` is the router's width, ``experts_held = (first,
    count)`` the experts whose weights live here. Parameters: ``router``
    ``(d, E)`` and the selection bias ``score_bias`` ``(E,)`` (it moves
    which experts are chosen and never their weights, so its gradient
    is zero), ``w_gate``, ``w_up`` ``(count, d, h)`` and ``w_down``
    ``(count, h, d)``, each expert a SwiGLU, and the shared expert's
    ``shared_gate``, ``shared_up``, ``shared_down`` when
    ``shared_hidden_dim`` is not 0.
    """

    num_experts: int
    experts_held: tuple[int, int]
    top_k: int
    hidden_dim: int
    shared_hidden_dim: int = 0
    routed_scaling: float = 1.0
    dtype: Any = jnp.float32
    grouped_dot: Callable = ragged_grouped_dot  # (lhs, rhs, sizes) -> float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        n, d = x.shape
        e, k, h = self.num_experts, self.top_k, self.hidden_dim
        first, count = self.experts_held
        if not (0 <= first and first + count <= e and 0 < count and k <= e):
            raise ValueError(
                f"experts_held={self.experts_held} top_k={k} do not fit {e} experts"
            )
        x = x.astype(self.dtype)
        per_expert = nn.initializers.lecun_normal(batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(), (d, e), jnp.float32)
        bias = self.param("score_bias", nn.initializers.normal(0.01), (e,), jnp.float32)
        w_gate = self.param("w_gate", per_expert, (count, d, h), jnp.float32)
        w_up = self.param("w_up", per_expert, (count, d, h), jnp.float32)
        w_down = self.param("w_down", per_expert, (count, h, d), jnp.float32)

        with jax.named_scope(SCOPE_ROUTER):
            # float32 in earnest: on the TPU a float32 product otherwise
            # runs as one bf16 pass, and a choice among 256 close scores
            # turns on less than that rounds away
            scores = jax.nn.sigmoid(
                jnp.dot(x.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST)
            )  # (N, E)
            _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), k)
            # for whoever asks (``mutable=["intermediates"]``): a test, the
            # benchmark's comparison of choices with its reference
            self.sow("intermediates", "chosen", chosen)
            picked = jnp.take_along_axis(scores, chosen, axis=-1)  # (N, k)
            weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
            weights = weights * self.routed_scaling

        with jax.named_scope(SCOPE_EXPERT_DISPATCH):
            local = chosen - first
            held = (local >= 0) & (local < count)
            # expert order, the assignments to absent experts last
            group = jnp.where(held, local, count).reshape(n * k)
            order = jnp.argsort(group, stable=True)  # row -> assignment
            row_of = jnp.argsort(order).reshape(n, k)  # assignment -> row
            counts = jnp.sum(
                group[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32
            )
            total = jnp.sum(counts)
            weights = jnp.where(held, weights, 0.0)

        with jax.named_scope(SCOPE_EXPERTS):
            w_gate_up = jnp.concatenate([w_gate, w_up], axis=-1).astype(self.dtype)

        def routed(start, rows: int, grouped_dot=self.grouped_dot):
            """The held experts' part of rows ``start .. start + rows``
            of the expert order, through a buffer of ``rows`` rows."""
            assignment = jax.lax.dynamic_slice_in_dim(order, start, rows)
            tok = assignment // k
            inside = (row_of >= start) & (row_of < start + rows)
            row = jnp.clip(row_of - start, 0, rows - 1)
            w = jnp.where(inside, weights, 0.0)  # weight 0 outside the buffer
            xs = _dispatch(x, tok, row, (held & inside).astype(jnp.float32))
            ends = jnp.cumsum(counts)
            in_buffer = lambda at: jnp.clip(at - start, 0, rows)
            sizes = in_buffer(ends) - in_buffer(ends - counts)
            with jax.named_scope(SCOPE_EXPERTS):
                # operands as they come (bf16), float32 accumulated and
                # out; gate and up as one product, so xs is read once
                gate_up = grouped_dot(xs, w_gate_up, sizes)
                act = (nn.silu(gate_up[:, :h]) * gate_up[:, h:]).astype(self.dtype)
                ys = grouped_dot(act, w_down.astype(self.dtype), sizes).astype(self.dtype)
            ys = jnp.where((start + jnp.arange(rows) < total)[:, None], ys, 0)
            w_of_row = jnp.take(w.reshape(n * k), assignment)
            return _combine(ys, tok, row, w, w_of_row)

        # A step that sends more than the usual buffer holds (nothing
        # is dropped) walks the expert order a buffer at a time, each
        # recomputed in the backward pass, so that the worst case
        # sizes no temporary. The walk multiplies with XLA's ragged
        # dot whatever the layer was given: a second set of kernels in
        # the seldom-taken branch would add 8 MB to a step's cached
        # executables, which are near the chip machine's cache limit
        # (PERF.md section 6). All of it is the exchange's scope but
        # the experts' products, which name their own inside it.
        usual, worst = _buffer_rows(n, k, count, e)
        with jax.named_scope(SCOPE_EXPERT_DISPATCH):
            if usual < worst:
                order = jnp.pad(order, (0, -worst % usual))

                def walk():
                    one = jax.checkpoint(
                        lambda at: routed(at, usual, ragged_grouped_dot).astype(jnp.float32)
                    )
                    out, _ = jax.lax.scan(
                        lambda acc, at: (acc + one(at), None),
                        jnp.zeros((n, d), jnp.float32),
                        jnp.arange(0, worst, usual),
                    )
                    return out.astype(self.dtype)

                y = jax.lax.cond(total <= usual, lambda: routed(0, usual), walk)
            else:
                y = routed(0, worst)

        if self.shared_hidden_dim:
            with jax.named_scope(SCOPE_SHARED_EXPERT):
                dense = lambda feats, name: nn.Dense(
                    feats, use_bias=False, dtype=self.dtype,
                    param_dtype=jnp.float32, name=name,
                )
                hs = self.shared_hidden_dim
                y = y + dense(d, "shared_down")(
                    nn.silu(dense(hs, "shared_gate")(x)) * dense(hs, "shared_up")(x)
                )
        return y, counts
