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
``models/latent_moe.py``, ``models/grouped_window_moe.py``): scores
over ALL the experts of the layer (sigmoid, the ``top_k`` largest of
score + selection bias a token, weights normalised over the chosen; or
the ``top_k`` largest logits and a softmax over them), SwiGLU or ReGLU
experts, no capacity and no dropped token. The layer
is told which experts it holds (``experts_held``: one chip's share of
an expert-parallel group) and adds only their terms, plus a shared
expert computed whole; what the absent experts would add is left out,
and nothing stands in for the exchange that would fetch it. The
(token, expert) assignments that land here are sorted by expert, their
tokens gathered into one buffer, run through grouped matrix products
and summed back by weight, token by token. Every gather and every sum
of that exchange, in the backward pass too, runs over the buffer's M
rows and never over the ``N * top_k`` slots, most of which went to
experts held elsewhere. Who makes the grouped products and the sums by
token is the layer's ``grouped_dot`` (a :class:`GroupedDot`): XLA
(``jax.lax.ragged_dot``, a scatter-add) or, on one TPU chip, kernels
(jax's Pallas grouped matmul; ``token_sums`` of this file, the sums as
0/1 matrices times the rows on the MXU), chosen by the layer from its
input's placement. Shapes are static: the buffer holds twice the mean
load, and a step whose routing sends more than that here (up to every
token with all it can send) walks the expert order one buffer at a
time, chosen by ``lax.cond`` on the step's own count.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from multidisttorch_tpu.ops.hyper_connection import bf16_parts
from multidisttorch_tpu.ops.pallas_mode import pallas_interpret
from multidisttorch_tpu.utils.profiling import (
    SCOPE_EXPERT_DISPATCH,
    SCOPE_EXPERTS,
    SCOPE_ROUTER,
    SCOPE_SHARED_EXPERT,
)

# What ``RoutedExperts``' router makes and the backward pass reads, by
# the name the models' remat rule (``models/decoder.py::remat_block``)
# keeps: the recomputed block then runs neither the float32 product nor
# the gathers and sorts after it, to remake arrays of a few MB.
SAVED_ROUTING = "router_results"


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


def _take(x, at):
    """``x[at]`` along the first axis for indices known to be in range
    (``jnp.take`` would clamp them and select the fill value)."""
    return x.at[at].get(mode="promise_in_bounds")


# The exchange between token order and expert order. Row r of the
# buffer holds one (token, slot) pair and each pair has one row, so the
# transpose of "gather the tokens" is "sum each token's rows" and the
# reverse. Every gather fetches the buffer's M rows and every sum runs
# over them (the layer's ``grouped_dot.token_sums``): of the N*k pairs
# only those that landed here have a row, one in sixteen in
# ``moe-mla-t4096``, and nothing is gathered to ``(N, k, d)``. ``tok``
# is a row's token and ``pair`` its pair as one index, ``token * k +
# slot``; a row past the step's count has the ``key`` N and the
# ``pair`` N*k, one past the last, and is in no sum. Left to autodiff
# both transposes would be XLA's scatter-add of (M, d) rows, slower on
# the TPU than the ``(N, k, d)`` sums were (PERF.md section 6, PR 32's
# race).


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(grouped_dot, x, tok, key):
    """Row ``r`` of the buffer is token ``tok[r]``: ``(N, d) -> (M, d)``."""
    return _take(x, tok)


def _dispatch_fwd(grouped_dot, x, tok, key):
    # the zero-size slice keeps N, the one static thing the rule needs
    return _take(x, tok), (key, x[:, :0])


def _dispatch_bwd(grouped_dot, res, g):
    key, x = res
    return grouped_dot.token_sums(g, None, key, x.shape[0]), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(grouped_dot, ys, tok, key, pair, w):
    """``out[t] = sum_j w[t, j] * ys[row of (t, j)]`` for ``w`` ``(N,
    k)``, as the sum over the rows, each by the weight of its pair."""
    return _combine_fwd(grouped_dot, ys, tok, key, pair, w)[0]


def _combine_fwd(grouped_dot, ys, tok, key, pair, w):
    w_of_row = w.reshape(-1).at[pair].get(mode="fill", fill_value=0)
    return grouped_dot.token_sums(ys, w_of_row, key, w.shape[0]), (ys, tok, pair, w_of_row, w)


def _combine_bwd(grouped_dot, res, g):
    ys, tok, pair, w_of_row, w = res
    g_rows = _take(g, tok).astype(jnp.float32)
    g_ys = (g_rows * w_of_row[:, None]).astype(ys.dtype)
    # a pair's weight gets <its row, its token's cotangent>: M inner
    # products, each put where its pair is; the pairs without a row get 0
    g_w_of_row = jnp.sum(ys.astype(jnp.float32) * g_rows, axis=-1)
    g_w = jnp.zeros((w.size,), w.dtype).at[pair].set(g_w_of_row, mode="drop", unique_indices=True)
    return g_ys, None, None, None, g_w.reshape(w.shape)


_combine.defvjp(_combine_fwd, _combine_bwd)


# The layer's two products over ragged groups of rows, as one backend
# makes them (``GroupedDot``).
#
# ``experts(lhs, rhs, sizes)``: rows ``sum(sizes[:g]) .. sum(sizes[:g+1])``
# of ``lhs`` ``(M, K)`` times ``rhs[g]``, accumulated in float32 and
# handed on in the operands' dtype (``jnp.dot``'s rule): bf16 by bf16
# gives bf16, rounded once from the float32 sum, and its cotangent
# comes back bf16, so the backward products (megablox's ``gmm`` and
# ``tgmm``, or XLA's ragged dots) run bf16 by bf16 too; rows past
# ``sum(sizes)`` hold whatever (the layer masks them).
#
# ``token_sums(rows, weight, key, n)``: ``out[t] = sum of weight[r] *
# rows[r] over the r with key[r] == t``, ``(M, d) -> (n, d)``; ``weight``
# ``None`` is 1. Each term and the sum are float32 and the sum is
# rounded to the rows' dtype once; a row of key ``n`` is in no sum,
# whatever it holds (NaN too).


class GroupedDot(NamedTuple):
    experts: Callable  # (lhs (M, K), rhs (G, K, N), sizes (G,)) -> (M, N), the operands' dtype
    token_sums: Callable  # (rows (M, d), weight (M,) or None, key (M,), n) -> (n, d)


_TILE_ROWS = 512  # rows a tile of the experts' kernel; mean load an expert in moe-mla-t4096
_TOKEN_BLOCK = 256  # tokens a block of the sums' kernel
_SUM_TILE_ROWS = 128  # rows a step of the sums' kernel; about what a block of tokens has


def _widest_tile(width: int, most: int = 1024) -> int:
    """The widest tile up to ``most`` that divides ``width`` into whole tiles."""
    return next(t for t in range(most, 0, -128) if width % t == 0)


# The backward's ``tgmm`` holds a ``(tile_k, tile_n)`` float32 sum and
# its outgoing copies beside the operands' tiles: 896 x 1,024
# (``xing4.0-29b-a4b``) fits the 16 MiB a kernel's stack may take, 1,024
# x 1,024 with a float32 cotangent passes it by 124 KB (the TPU
# compiler for a described v5e, PR 39).
_MAX_TILE_AREA = 896 * 1024


def _tiles(k: int, n: int) -> tuple[int, int]:
    """``(tile_k, tile_n)`` of the experts' kernel for a ``(k, n)``
    product: the widest of each, ``n``'s narrowed while the two pass
    ``_MAX_TILE_AREA`` together."""
    tile_k, tile_n = _widest_tile(k), _widest_tile(n)
    while tile_k * tile_n > _MAX_TILE_AREA:
        tile_n = _widest_tile(n, tile_n - 128)
    return tile_k, tile_n


def _visits(key, n: int, block: int, tile: int):
    """``(block_of, tile_of, count)``: the (token block, tile of rows)
    pairs the sums' kernel visits, in order: each block with every tile
    its rows reach into, an empty block once (it is written too), and
    then, up to the static ``m // tile + n // block``, the last pair
    again. ``count`` says how many of them are to be computed: none
    where no row has a key under ``n``."""
    blocks, tiles = n // block, key.shape[0] // tile
    firsts = jnp.arange(blocks + 1, dtype=jnp.int32) * block
    edge = jnp.sum(key[:, None] < firsts[None, :], axis=0, dtype=jnp.int32)  # rows before a block
    lo, hi = edge[:-1], edge[1:]
    first = jnp.minimum(lo // tile, tiles - 1)
    reach = jnp.where(hi > lo, (hi - 1) // tile - first + 1, 1)
    end = jnp.cumsum(reach)
    visit = jnp.arange(tiles + blocks, dtype=jnp.int32)
    block_of = jnp.minimum(
        jnp.sum(end[None, :] <= visit[:, None], axis=1, dtype=jnp.int32), blocks - 1
    )
    within = jnp.minimum(visit - (end - reach)[block_of], reach[block_of] - 1)
    return block_of, first[block_of] + within, jnp.where(edge[-1] > 0, end[-1], 0)[None]


def _token_sums_kernel(block_of, tile_of, count, key, *refs, block: int):
    *weight, rows, out, acc = refs  # the weights' column only where the rows have weights
    visit, last = pl.program_id(1), pl.num_programs(1) - 1
    here = block_of[visit]

    @pl.when((visit == 0) | (block_of[jnp.maximum(visit - 1, 0)] != here))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(visit < count[0])
    def _():
        # 1 where the row's token is that position of this block; the
        # rows of another block's tokens match none
        at = key[...] - here * block  # (1, tile)
        select = jax.lax.broadcasted_iota(jnp.int32, (block, at.shape[1]), 0) == at
        select = select.astype(jnp.bfloat16)
        # the matrix is exact in bf16, and so has to be each term: a
        # bf16 row of weight 1 is, a float32 term goes as the three
        # bf16 parts that add up to it
        terms = rows[...]
        if weight or terms.dtype != jnp.bfloat16:
            terms = terms.astype(jnp.float32)
            terms = bf16_parts(terms * weight[0][...] if weight else terms)
        else:
            terms = [terms]
        for part in terms:
            acc[...] += jnp.dot(select, part, preferred_element_type=jnp.float32)

    @pl.when((visit == last) | (block_of[jnp.minimum(visit + 1, last)] != here))
    def _():
        out[...] = acc[...].astype(out.dtype)


@functools.partial(jax.jit, static_argnames="n")  # one lowering of the kernel a shape, not one a call
def _kernel_token_sums(rows, weight, key, n: int):
    """The sums as products on the MXU. The rows are brought into token
    order (a sort of M keys, one M-row gather) and the tokens cut into
    blocks; a block's rows are then a contiguous, ragged range, and its
    sums are a 0/1 matrix ``(block, rows)`` times those rows, a tile of
    rows a step. The matrix is made from the keys and the terms from
    the rows in VMEM; neither is ever in HBM."""
    m, d = rows.shape
    block, tile, columns = math.gcd(n, _TOKEN_BLOCK), _SUM_TILE_ROWS, _widest_tile(d)
    by_token = jnp.argsort(key)
    key = _take(key, by_token)
    # a place past the rows that count fetches the first row: what the
    # kernel multiplies by 0 was then written by the step (unless no
    # row counts, and then it computes nothing)
    by_token = jnp.where(key < n, by_token, by_token[0])
    index = lambda j, v, block_of, tile_of, count: (tile_of[v], j)
    operands, specs = [_take(rows, by_token)], [pl.BlockSpec((tile, columns), index)]
    if weight is not None:  # a column, to scale the rows by
        operands.insert(0, _take(weight, by_token)[:, None])
        specs.insert(0, pl.BlockSpec((tile, 1), lambda j, v, *visits: index(0, v, *visits)))
    return pl.pallas_call(
        functools.partial(_token_sums_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(d // columns, m // tile + n // block),
            in_specs=[
                pl.BlockSpec((1, tile), lambda j, v, block_of, tile_of, count: (0, tile_of[v])),
                *specs,
            ],
            out_specs=pl.BlockSpec(
                (block, columns), lambda j, v, block_of, tile_of, count: (block_of[v], j)
            ),
            scratch_shapes=[pltpu.VMEM((block, columns), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), rows.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name="token_sums",
    )(*_visits(key, n, block, tile), key[None, :], *operands)


def _segment_token_sums(rows, weight, key, n: int):
    terms = rows.astype(jnp.float32)
    if weight is not None:
        terms = terms * weight[:, None]
    # a key of n is out of range, and what is out of range is dropped
    return jax.ops.segment_sum(terms, key, num_segments=n).astype(rows.dtype)


def _ragged_experts(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=jnp.result_type(lhs, rhs))


def _kernel_experts(lhs, rhs, sizes):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    # the kernel sums in a float32 tile and stores it in this dtype
    return gmm(
        lhs, rhs, sizes, jnp.result_type(lhs, rhs), (_TILE_ROWS, *_tiles(*rhs.shape[1:])),
        interpret=pallas_interpret(),
    )


# XLA's own (``jax.lax.ragged_dot``, and a scatter-add of the rows):
# every backend, any shape.
ragged_grouped_dot = GroupedDot(_ragged_experts, _segment_token_sums)

# The TPU's kernels: for the experts jax's Pallas grouped matmul
# (megablox ``gmm``, with its own backward kernels), which visits only
# the tiles that hold rows of a group (XLA's ragged dot, expanded by
# the TPU compiler, ran the cell's experts no faster and its operations
# carry no scope path, so a trace could not say whose they were:
# PERF.md section 6); for the sums a kernel of this file.
kernel_grouped_dot = GroupedDot(_kernel_experts, _kernel_token_sums)


def grouped_dot_takes_kernel(
    device_kind: str, num_devices: int, rows: int, k: int, n: int
) -> bool:
    """Whether an expert layer that was given no ``grouped_dot`` runs
    a product as the Pallas kernel (:func:`default_grouped_dot` asks,
    with what tracing shows of the layer input's placement) or as XLA's
    form: a TPU, operands on one device, whole tiles of rows and whole
    lanes of both widths. The experts' product is ``(rows, k)`` by
    ``(k, n)``; the sums by token take ``rows`` rows ``n`` wide to
    ``k`` tokens."""
    return (
        device_kind.startswith("TPU")
        and num_devices == 1
        and rows % _TILE_ROWS == 0
        and k % 128 == 0
        and n % 128 == 0
    )


def default_grouped_dot(x) -> GroupedDot:
    """The two grouped products of an expert layer whose input is ``x``
    (the experts' and the sums of the rows by token): each the kernel
    where :func:`grouped_dot_takes_kernel` says so and XLA's form
    everywhere else, decided while tracing. The placement is read off
    the layer's input, because a kernel's result no longer shows the
    mesh it was computed on."""
    # imported here: a line added above the kernels moves them in their serialized modules
    from multidisttorch_tpu.parallel import mesh

    placed = mesh.placement(x)

    def chosen(rows: int, k: int, n: int) -> GroupedDot:
        kernel = placed and grouped_dot_takes_kernel(*placed, rows, k, n)
        return kernel_grouped_dot if kernel else ragged_grouped_dot

    def experts(lhs, rhs, sizes):
        return chosen(*lhs.shape, rhs.shape[-1]).experts(lhs, rhs, sizes)

    def token_sums(rows, weight, key, n):
        return chosen(rows.shape[0], n, rows.shape[1]).token_sums(rows, weight, key, n)

    return GroupedDot(experts, token_sums)


def _buffer_rows(n: int, k: int, count: int, e: int) -> tuple[int, int]:
    """``(usual, worst)`` rows of the buffer of assignments for ``n``
    tokens choosing ``k`` of ``e`` experts, ``count`` of them held
    here: every token can send ``min(k, count)`` assignments, on
    average ``n*k*count/e`` arrive, and the usual buffer holds twice
    that (a multiple of 8 rows)."""
    worst = n * min(k, count)
    return min(worst, -(-2 * n * k * count // e // 8) * 8), worst


class RoutedExperts(nn.Module):
    """Dropless routed experts, one chip's share of them:
    ``(N, d) -> ((N, d), (count,) int32)``, the second the assignments
    to each expert held.

    ``num_experts`` is the router's width, ``experts_held = (first,
    count)`` the experts whose weights live here. Parameters: ``router``
    ``(d, E)`` and, under ``scoring="sigmoid"``, the selection bias
    ``score_bias`` ``(E,)`` (it moves
    which experts are chosen and never their weights, so its gradient
    is zero), ``w_gate``, ``w_up`` ``(count, d, h)`` and ``w_down``
    ``(count, h, d)``, each expert ``W_down(act(W_gate x) * (W_up x))``,
    and the shared expert's
    ``shared_gate``, ``shared_up``, ``shared_down`` when
    ``shared_hidden_dim`` is not 0.

    ``scoring``: ``"sigmoid"``: sigmoid scores, the ``top_k`` largest
    of score + bias chosen, the chosen scores normalised to sum 1;
    ``"softmax"``: the ``top_k`` largest logits chosen and a softmax
    over them (which is the softmax over all the experts, its top k,
    normalised), no bias parameter. ``activation``: ``"silu"`` (SwiGLU)
    or ``"relu"`` (ReGLU). ``router_input``, where given, is what the
    router reads in place of ``x``: a block whose router reads the
    block's input while the experts read what attention made of it
    hands in both.

    ``absent_share_grad`` ``False``
    keeps from the backward pass what the share of a token's weight that
    its experts held here have, ``S``, would tell it: the weights are
    ``stop_gradient(S) * (w / S)`` over the experts held, the same
    numbers forward. Run alone, a chip's share answers only for its own
    experts, so the router's gradient says that weight moved onto them
    always helps, and within tens of steps every token chooses them;
    with the share held still the gradient is the whole group's under
    the one assumption a chip alone can make, that the absent experts'
    answers are as useful to a token, weight for weight, as the held
    ones'.
    """

    num_experts: int
    experts_held: tuple[int, int]
    top_k: int
    hidden_dim: int
    shared_hidden_dim: int = 0
    routed_scaling: float = 1.0
    dtype: Any = jnp.float32
    grouped_dot: Optional[GroupedDot] = None  # the two products' maker; None: default_grouped_dot
    scoring: str = "sigmoid"
    activation: str = "silu"
    absent_share_grad: bool = True

    @nn.compact
    def __call__(self, x: jnp.ndarray, router_input=None) -> tuple[jnp.ndarray, jnp.ndarray]:
        n, d = x.shape
        e, k, h = self.num_experts, self.top_k, self.hidden_dim
        grouped_dot = default_grouped_dot(x) if self.grouped_dot is None else self.grouped_dot
        first, count = self.experts_held
        if not (0 <= first and first + count <= e and 0 < count and k <= e):
            raise ValueError(
                f"experts_held={self.experts_held} top_k={k} do not fit {e} experts"
            )
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring={self.scoring!r}: sigmoid or softmax")
        act_fn = {"silu": nn.silu, "relu": nn.relu}[self.activation]
        x = x.astype(self.dtype)
        routed_from = x if router_input is None else router_input
        per_expert = nn.initializers.lecun_normal(batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(), (d, e), jnp.float32)
        if self.scoring == "sigmoid":
            bias = self.param("score_bias", nn.initializers.normal(0.01), (e,), jnp.float32)
        w_gate = self.param("w_gate", per_expert, (count, d, h), jnp.float32)
        w_up = self.param("w_up", per_expert, (count, d, h), jnp.float32)
        w_down = self.param("w_down", per_expert, (count, h, d), jnp.float32)

        # Under ``nn.remat`` a name keeps a value only for those who read the
        # named copy, and a primitive's own derivative rule reads the
        # primitive's output: so the logits are named before the sigmoid
        # (which runs again, one elementwise pass) and the choices before
        # the gather, and everyone after takes the named value.
        with jax.named_scope(SCOPE_ROUTER):
            # float32 in earnest: on the TPU a float32 product otherwise
            # runs as one bf16 pass, and a choice among 256 close scores
            # turns on less than that rounds away
            scores = jnp.dot(
                routed_from.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST
            )  # (N, E)
            scores = checkpoint_name(scores, SAVED_ROUTING)
            if self.scoring == "sigmoid":
                scores = jax.nn.sigmoid(scores)
                _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), k)
                chosen = checkpoint_name(chosen, SAVED_ROUTING)
            else:
                # the chosen logits come with the choice: no (N, k) gather.
                # ``top_k``'s derivative rule gathers by its own indices, so
                # it runs again on the saved logits and a name would keep nothing
                picked, chosen = jax.lax.top_k(scores, k)
            # for whoever asks (``mutable=["intermediates"]``): a test, the
            # benchmark's comparison of choices with its reference
            self.sow("intermediates", "chosen", chosen)
            if self.scoring == "sigmoid":
                picked = jnp.take_along_axis(scores, chosen, axis=-1)  # (N, k)
                # the normalisation's backward reads them
                picked = checkpoint_name(picked, SAVED_ROUTING)
                weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
            else:
                weights = jax.nn.softmax(picked, axis=-1)
            weights = weights * self.routed_scaling

        with jax.named_scope(SCOPE_EXPERT_DISPATCH):
            local = chosen - first
            held = (local >= 0) & (local < count)
            if not self.absent_share_grad:
                here = jnp.where(held, weights, 0)
                share = jnp.sum(here, axis=-1, keepdims=True)
                weights = jax.lax.stop_gradient(share) * (here / jnp.maximum(share, 1e-20))
            # expert order, the assignments to absent experts last
            group = jnp.where(held, local, count).reshape(n * k)
            # row -> pair, as token * k + slot
            order = checkpoint_name(jnp.argsort(group, stable=True), SAVED_ROUTING)
            counts = jnp.sum(
                group[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32
            )
            counts = checkpoint_name(counts, SAVED_ROUTING)
            total = jnp.sum(counts)

        with jax.named_scope(SCOPE_EXPERTS):
            w_gate_up = jnp.concatenate([w_gate, w_up], axis=-1).astype(self.dtype)

        def routed(start, rows: int, grouped_dot=grouped_dot):
            """The held experts' part of rows ``start .. start + rows``
            of the expert order, through a buffer of ``rows`` rows."""
            pair = jax.lax.dynamic_slice_in_dim(order, start, rows)
            tok = pair // k
            valid = start + jnp.arange(rows) < total  # rows the step's count reaches
            pair = jnp.where(valid, pair, n * k)
            key = pair // k
            xs = _dispatch(grouped_dot, x, tok, key)
            ends = jnp.cumsum(counts)
            in_buffer = lambda at: jnp.clip(at - start, 0, rows)
            sizes = in_buffer(ends) - in_buffer(ends - counts)
            with jax.named_scope(SCOPE_EXPERTS):
                # operands and results in the layer's dtype, accumulated
                # in float32 (``GroupedDot``): at bf16 no float32 copy of
                # the buffer's rows is written, forward or backward; gate
                # and up as one product, so xs is read once; the
                # activation in float32 inside its fusion
                gate_up = grouped_dot.experts(xs, w_gate_up, sizes)
                f32 = lambda a: a.astype(jnp.float32)
                act = (act_fn(f32(gate_up[:, :h])) * f32(gate_up[:, h:])).astype(self.dtype)
                ys = grouped_dot.experts(act, w_down.astype(self.dtype), sizes)
            ys = jnp.where(valid[:, None], ys, 0)
            return _combine(grouped_dot, ys, tok, key, pair, weights)

        # A step that sends more than the usual buffer holds (nothing
        # is dropped) walks the expert order a buffer at a time, each
        # recomputed in the backward pass, so that the worst case
        # sizes no temporary. The walk multiplies with XLA's ragged
        # dot whatever the layer was given: a second set of those
        # kernels in the seldom-taken branch would add 8 MB to a
        # step's cached executables, which are near the chip machine's
        # cache limit (PERF.md section 6); the sums by token stay the
        # layer's, a tenth of that. All of it is the exchange's scope
        # but the experts' products, which name their own inside it.
        usual, worst = _buffer_rows(n, k, count, e)
        with jax.named_scope(SCOPE_EXPERT_DISPATCH):
            if usual < worst:
                order = jnp.pad(order, (0, -worst % usual))

                def walk():
                    dots = grouped_dot._replace(experts=ragged_grouped_dot.experts)
                    nothing = lambda: jnp.zeros((n, d), jnp.float32)
                    # a buffer past the step's count holds no row and is not run
                    one = jax.checkpoint(lambda at: jax.lax.cond(
                        at < total, lambda: routed(at, usual, dots).astype(jnp.float32), nothing
                    ))
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
                    act_fn(dense(hs, "shared_gate")(x)) * dense(hs, "shared_up")(x)
                )
        return y, counts
