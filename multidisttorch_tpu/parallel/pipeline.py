"""Pipeline parallelism: stage-sharded models via collective microbatching.

The reference framework is DP-only (SURVEY.md §2c — pipeline parallelism
is "absent from all 448 lines"), but a TPU framework at its scale must
let one trial's model exceed one chip. This module implements GPipe-style
pipeline parallelism the SPMD way: every device runs the *same* jitted
program under ``shard_map``; the stage dimension of the weights is
sharded over a ``pipe`` mesh axis, microbatches march through the stages
with non-cyclic ``jax.lax.ppermute`` neighbor hops (ICI-adjacent by
construction — see ``setup_groups(pipeline_parallel=...)``), and the
whole schedule is a single differentiable ``lax.scan``, so ``jax.grad``
of a loss on the pipeline output *is* the backward pipeline — no
hand-written backward schedule, no recompilation per stage.

Schedule: the classic GPipe fill/steady/drain loop — with M microbatches
and S stages, the scan runs ``M + S - 1`` ticks; stage 0 injects
microbatch ``t`` at tick ``t``, stage ``S-1`` emits microbatch
``t-(S-1)`` at tick ``t``. Bubble fraction ``(S-1)/(M+S-1)`` — pick
``num_microbatches >> num_stages`` to amortize, exactly as in the GPipe
paper. Composes with data parallelism: on a ``(data, pipe)`` submesh the
batch dimension is additionally sharded over ``data`` and XLA reduces
gradients over both axes.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multidisttorch_tpu.parallel.mesh import DATA_AXIS, PIPE_AXIS, TrialMesh


def _resolve_mesh(trial: TrialMesh | Mesh) -> Mesh:
    return trial.mesh if isinstance(trial, TrialMesh) else trial


def stage_params_sharding(trial: TrialMesh | Mesh) -> NamedSharding:
    """Sharding for stacked per-stage weights: leading (stage) axis split
    over the ``pipe`` mesh axis, so each device holds exactly its own
    stage's parameters."""
    mesh = _resolve_mesh(trial)
    return NamedSharding(mesh, P(PIPE_AXIS))


def _pipeline_local(
    stage_params,
    batch,
    *,
    stage_fn: Callable,
    num_stages: int,
    num_microbatches: int,
    pipe_axis: str,
    vary_axes: tuple[str, ...],
):
    """Per-device body under shard_map.

    ``stage_params`` leaves arrive with a leading stage axis of local
    extent 1 (their global leading axis is sharded over ``pipe``);
    ``batch`` is this device's data shard, replicated across the pipe
    axis (every stage sees it; only stage 0 reads it).
    """
    my_params = jax.tree.map(lambda x: x[0], stage_params)
    stage_id = jax.lax.axis_index(pipe_axis)
    is_first = stage_id == 0
    is_last = stage_id == num_stages - 1

    n = batch.shape[0]
    mb = n // num_microbatches
    micro = batch.reshape((num_microbatches, mb) + batch.shape[1:])

    # Probe the stage output shape once (abstractly — no FLOPs at runtime)
    # so the carry/output buffers can be allocated. Pipeline stages must
    # be shape-preserving in the activation (equal-width stages), the
    # standard GPipe restriction that makes the ppermute well-typed.
    out_aval = jax.eval_shape(stage_fn, my_params, micro[0])
    if out_aval.shape != micro[0].shape:
        raise ValueError(
            f"pipeline stages must preserve activation shape; stage maps "
            f"{micro[0].shape} -> {out_aval.shape}"
        )

    # Carries start as constants but become device-varying through the
    # loop (pipe via ppermute/axis_index, data via the batch shard —
    # but NOT model, over which stages are replicated); annotate up
    # front (shard_map VMA typing).
    from multidisttorch_tpu.parallel.collectives import pvary

    state0 = pvary(jnp.zeros(micro[0].shape, out_aval.dtype), vary_axes)
    out0 = pvary(jnp.zeros(micro.shape, out_aval.dtype), vary_axes)

    # Non-cyclic shift: stage i hands its activation to stage i+1; stage
    # S-1's send is dropped, stage 0 receives zeros (and ignores them).
    shift = [(i, i + 1) for i in range(num_stages - 1)]

    def tick(carry, t):
        state, outs = carry
        inj = micro[jnp.clip(t, 0, num_microbatches - 1)]
        x = jnp.where(is_first, inj.astype(state.dtype), state)
        y = stage_fn(my_params, x)
        out_idx = t - (num_stages - 1)
        valid = jnp.logical_and(is_last, out_idx >= 0)
        slot = jnp.clip(out_idx, 0, num_microbatches - 1)
        prev = jax.lax.dynamic_index_in_dim(outs, slot, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, y, prev), slot, axis=0
        )
        state = jax.lax.ppermute(y, pipe_axis, shift)
        return (state, outs), None

    ticks = jnp.arange(num_microbatches + num_stages - 1)
    (_, outs), _ = jax.lax.scan(tick, (state0, out0), ticks)

    # Only the last stage holds real outputs; psum over the pipe axis
    # broadcasts them (everyone else contributes zeros), making the
    # result pipe-invariant so it can leave the shard_map replicated.
    outs = jax.lax.psum(jnp.where(is_last, outs, jnp.zeros_like(outs)), pipe_axis)
    return outs.reshape((n,) + outs.shape[2:])


def pipeline_apply(
    trial: TrialMesh | Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    *,
    num_microbatches: int,
) -> Callable[[Any, jax.Array], jax.Array]:
    """Build a pipelined forward ``apply(stage_params, batch) -> out``.

    - ``stage_fn(params_one_stage, x) -> y`` is the per-stage compute; it
      must preserve the activation shape (equal-width stages).
    - ``stage_params`` is a pytree whose every leaf has leading axis
      ``num_stages``; place it with :func:`stage_params_sharding` so each
      pipe-axis device owns one stage.
    - ``batch`` has leading axis divisible by ``num_microbatches`` (per
      data shard, if the submesh also has a ``data`` axis).

    The returned function is pure and differentiable — wrap it in a loss
    and ``jax.grad``/``jax.jit`` exactly like any other forward. Under
    jit, GSPMD additionally reduces gradients over the ``data`` axis,
    giving DP x PP from one program.
    """
    mesh = _resolve_mesh(trial)
    if PIPE_AXIS not in mesh.shape:
        raise ValueError(
            f"mesh has no '{PIPE_AXIS}' axis (axes: {tuple(mesh.shape)}); "
            "carve one with setup_groups(..., pipeline_parallel=S)"
        )
    num_stages = int(mesh.shape[PIPE_AXIS])
    if num_microbatches < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {num_microbatches}")
    has_data = DATA_AXIS in mesh.shape
    data_size = int(mesh.shape[DATA_AXIS]) if has_data else 1
    batch_spec = P(DATA_AXIS) if has_data else P()

    def apply(stage_params, batch):
        n_leading = jax.tree.leaves(stage_params)[0].shape[0]
        if n_leading != num_stages:
            raise ValueError(
                f"stage_params leading axis {n_leading} != pipe axis "
                f"extent {num_stages}"
            )
        shard_n, rem = divmod(batch.shape[0], data_size)
        if rem or shard_n % num_microbatches:
            raise ValueError(
                f"batch leading axis {batch.shape[0]} must divide into "
                f"{data_size} data shard(s) x {num_microbatches} "
                "microbatches of equal size"
            )
        return jax.shard_map(
            partial(
                _pipeline_local,
                stage_fn=stage_fn,
                num_stages=num_stages,
                num_microbatches=num_microbatches,
                pipe_axis=PIPE_AXIS,
                vary_axes=(
                    ((DATA_AXIS,) if has_data else ()) + (PIPE_AXIS,)
                ),
            ),
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(PIPE_AXIS), stage_params), batch_spec),
            out_specs=batch_spec,
        )(stage_params, batch)

    return apply


def sequential_reference(stage_fn, stage_params, batch):
    """Single-device reference: run the stages back to back (for tests)."""
    x = batch
    num_stages = jax.tree.leaves(stage_params)[0].shape[0]
    for s in range(num_stages):
        x = stage_fn(jax.tree.map(lambda p: p[s], stage_params), x)
    return x


# --- shape-heterogeneous stages (real models) -------------------------------
#
# :func:`pipeline_apply` requires equal-width stages — fine for scan-over-
# layers transformer stacks, useless for the models this repo actually
# ships (a ResNet halves its spatial dims while doubling channels; a
# ConvVAE narrows to a latent bottleneck). The general SPMD form below
# lifts the restriction with two devices-run-one-program tricks:
#
# - **padded flat carry**: every activation travels between stages as a
#   ``(microbatch, A)`` float32 buffer, ``A`` = the widest per-sample
#   activation in the chain; each stage unpads/reshapes its true input
#   and re-pads its output. The ppermute stays well-typed because every
#   hop has the one static shape.
# - **lax.switch on the stage index**: stage bodies differ, but SPMD
#   needs one program — each device selects its own stage's branch with
#   its pipe-axis coordinate. Branch s statically unpacks stage s's
#   params from the packed row and runs its compute; control flow is a
#   device-local scalar conditional, so no collective may appear inside
#   a stage body (document-level contract, same as GPipe kernels).
# - **packed params**: per-stage param pytrees (different structures!)
#   flatten+concat+pad into one ``(S, Pmax)`` float32 array sharded over
#   ``pipe`` — each device physically holds only its own stage's row,
#   which is the memory point of pipeline parallelism. The optimizer
#   runs directly on the packed array (Adam is elementwise), so the
#   sharding survives training with zero extra machinery.


def pack_stage_params(stage_trees: Sequence[Any]) -> tuple[jax.Array, tuple]:
    """Pack per-stage param pytrees into one ``(S, Pmax)`` float32 array.

    Returns ``(packed, metas)``; place ``packed`` with
    :func:`stage_params_sharding` so each pipe device owns its row.
    ``metas`` is static unpack metadata for :func:`unpack_stage_params`
    and :func:`pipeline_apply_stages`.
    """
    metas, rows = [], []
    for tree in stage_trees:
        leaves, treedef = jax.tree.flatten(tree)
        for leaf in leaves:
            if leaf.dtype != jnp.float32:
                raise ValueError(
                    f"packed stage params must be float32, got {leaf.dtype} "
                    "(keep param_dtype=float32; compute dtype is the "
                    "stage_fn's business)"
                )
        metas.append((treedef, tuple(tuple(l.shape) for l in leaves)))
        rows.append(
            jnp.concatenate([jnp.ravel(l) for l in leaves])
            if leaves
            else jnp.zeros((0,), jnp.float32)
        )
    pmax = max((int(r.shape[0]) for r in rows), default=0)
    packed = jnp.stack([jnp.pad(r, (0, pmax - r.shape[0])) for r in rows])
    return packed, tuple(metas)


def unpack_stage_params(row: jax.Array, meta) -> Any:
    """Rebuild one stage's param pytree from its packed row (static
    slicing — safe inside a ``lax.switch`` branch)."""

    treedef, shapes = meta
    leaves, off = [], 0
    for shape in shapes:
        size = math.prod(shape)
        leaves.append(row[off : off + size].reshape(shape))
        off += size
    return jax.tree.unflatten(treedef, leaves)


def _pipeline_stages_local(
    packed_params,
    batch,
    *,
    stage_fns,
    metas,
    in_shapes,
    out_shape,
    width,
    num_stages,
    num_microbatches,
    pipe_axis,
    vary_axes,
):
    """Per-device body for heterogeneous stages (see module comment)."""
    from multidisttorch_tpu.parallel.collectives import pvary

    my_row = packed_params[0]  # this device's stage row, (Pmax,)
    stage_id = jax.lax.axis_index(pipe_axis)
    is_first = stage_id == 0
    is_last = stage_id == num_stages - 1

    n = batch.shape[0]
    mb = n // num_microbatches
    micro = batch.reshape((num_microbatches, mb) + batch.shape[1:])

    def flat_pad(x):
        f = x.reshape(x.shape[0], -1).astype(jnp.float32)
        return jnp.pad(f, ((0, 0), (0, width - f.shape[1])))

    def make_branch(s):

        in_size = math.prod(in_shapes[s])

        def branch(row, buf):
            p = unpack_stage_params(row, metas[s])
            a = buf[:, :in_size].reshape((mb,) + in_shapes[s])
            return flat_pad(stage_fns[s](p, a))

        return branch

    branches = [make_branch(s) for s in range(num_stages)]

    state0 = pvary(jnp.zeros((mb, width), jnp.float32), vary_axes)
    out0 = pvary(
        jnp.zeros((num_microbatches, mb, width), jnp.float32), vary_axes
    )
    shift = [(i, i + 1) for i in range(num_stages - 1)]

    def tick(carry, t):
        state, outs = carry
        inj = flat_pad(micro[jnp.clip(t, 0, num_microbatches - 1)])
        x = jnp.where(is_first, inj, state)
        y = jax.lax.switch(stage_id, branches, my_row, x)
        out_idx = t - (num_stages - 1)
        valid = jnp.logical_and(is_last, out_idx >= 0)
        slot = jnp.clip(out_idx, 0, num_microbatches - 1)
        prev = jax.lax.dynamic_index_in_dim(outs, slot, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, y, prev), slot, axis=0
        )
        state = jax.lax.ppermute(y, pipe_axis, shift)
        return (state, outs), None

    ticks = jnp.arange(num_microbatches + num_stages - 1)
    (_, outs), _ = jax.lax.scan(tick, (state0, out0), ticks)

    outs = jax.lax.psum(
        jnp.where(is_last, outs, jnp.zeros_like(outs)), pipe_axis
    )

    out_size = math.prod(out_shape)
    return outs[:, :, :out_size].reshape((n,) + out_shape)


def pipeline_apply_stages(
    trial: TrialMesh | Mesh,
    stage_fns: Sequence[Callable[[Any, jax.Array], jax.Array]],
    stage_params: Sequence[Any],
    *,
    num_microbatches: int,
) -> tuple[Callable[[Any, jax.Array], jax.Array], jax.Array]:
    """GPipe for **shape-heterogeneous** stages — real models.

    - ``stage_fns[s](params_s, x) -> y``: per-stage compute; input/output
      shapes may differ per stage (a conv stage may halve spatial dims,
      the last stage may emit class logits). Stage bodies must be
      collective-free (each device executes only its own branch).
    - ``stage_params[s]``: stage s's param pytree (float32 leaves;
      structures may differ per stage).

    Returns ``(apply, packed)``: place ``packed`` with
    :func:`stage_params_sharding`, then ``apply(packed, batch) -> out``
    is pure and differentiable — grad w.r.t. ``packed`` keeps the
    per-stage sharding, and an elementwise optimizer (Adam) applied to
    the packed array trains the pipeline directly. On a ``(data, pipe)``
    submesh GSPMD additionally reduces gradients over ``data``: DP x PP
    from one jitted program.
    """

    mesh = _resolve_mesh(trial)
    if PIPE_AXIS not in mesh.shape:
        raise ValueError(
            f"mesh has no '{PIPE_AXIS}' axis (axes: {tuple(mesh.shape)}); "
            "carve one with setup_groups(..., pipeline_parallel=S)"
        )
    num_stages = int(mesh.shape[PIPE_AXIS])
    if len(stage_fns) != num_stages or len(stage_params) != num_stages:
        raise ValueError(
            f"{len(stage_fns)} stage_fns / {len(stage_params)} stage_params "
            f"for a pipe axis of extent {num_stages}"
        )
    if num_microbatches < 1:
        raise ValueError(
            f"num_microbatches must be >= 1, got {num_microbatches}"
        )
    has_data = DATA_AXIS in mesh.shape
    data_size = int(mesh.shape[DATA_AXIS]) if has_data else 1
    batch_spec = P(DATA_AXIS) if has_data else P()

    packed, metas = pack_stage_params(stage_params)
    param_avals = [
        jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree
        )
        for tree in stage_params
    ]

    def apply(packed_arr, batch):
        shard_n, rem = divmod(batch.shape[0], data_size)
        if rem or shard_n % num_microbatches:
            raise ValueError(
                f"batch leading axis {batch.shape[0]} must divide into "
                f"{data_size} data shard(s) x {num_microbatches} "
                "microbatches of equal size"
            )
        mb = shard_n // num_microbatches
        # Probe the stage shape chain abstractly (no FLOPs): stage s's
        # output shape is stage s+1's input shape.
        in_shapes = [tuple(batch.shape[1:])]
        for s in range(num_stages):
            out_aval = jax.eval_shape(
                stage_fns[s],
                param_avals[s],
                jax.ShapeDtypeStruct((mb,) + in_shapes[s], jnp.float32),
            )
            in_shapes.append(tuple(out_aval.shape[1:]))
        width = max(math.prod(s) for s in in_shapes)

        return jax.shard_map(
            partial(
                _pipeline_stages_local,
                stage_fns=tuple(stage_fns),
                metas=metas,
                in_shapes=tuple(in_shapes[:num_stages]),
                out_shape=in_shapes[num_stages],
                width=width,
                num_stages=num_stages,
                num_microbatches=num_microbatches,
                pipe_axis=PIPE_AXIS,
                vary_axes=(
                    ((DATA_AXIS,) if has_data else ()) + (PIPE_AXIS,)
                ),
            ),
            mesh=mesh,
            in_specs=(P(PIPE_AXIS), batch_spec),
            out_specs=batch_spec,
        )(packed_arr, batch)

    return apply, packed


def sequential_stages_reference(stage_fns, stage_params, batch):
    """Single-device reference for heterogeneous stages (for tests)."""
    x = batch
    for fn, p in zip(stage_fns, stage_params):
        x = fn(p, x)
    return x


# --- cross-submesh MPMD pipeline parallelism ------------------------
#
# Everything above is SPMD pipelining: one mesh, one program, the stage
# dimension a mesh axis. The MPMD form (arXiv 2412.14374) drops both
# constraints: each stage owns its OWN submesh and runs its OWN
# compiled programs — a 2-stage trial is a *vector* of slice requests
# to the service scheduler (all-or-nothing multi-block placement,
# ``service/scheduler.py``), per-stage programs are first-class ``kind``s
# in the compile registry (``compile/programs.py``), and the host drives
# the classic GPipe fill/steady/drain schedule with explicit
# ``jax.device_put`` transfers carrying activations (forward) and
# cotangents (backward) between stage submeshes.
#
# Contract per stage:
#   - ``stage_fns[s](params_s, acts, rng) -> acts'`` for s < S-1, where
#     ``acts`` is a tuple of batch-major arrays (stage 0 receives
#     ``(batch,)``);
#   - ``last_fn(params_{S-1}, acts) -> loss`` (per-sample mean over the
#     microbatch) closes the chain.
# The backward pass is recompute-vjp per stage (GPipe's activation
# policy: only the stage INPUTS are stashed between phases; the vjp
# re-runs the stage forward), so per-stage programs are:
# fwd / last-forward (loss metric) / bwd (cotangent in, grads out) /
# last-bwd / update (per-stage Adam; optionally ZeRO-sharded over the
# stage submesh's data axis — ``parallel/fsdp.py``'s sharded-update
# composes per stage unchanged).
#
# Schedule: two phases of ``M + S - 1`` ticks each (forward fill/drain,
# then backward fill/drain), microbatch gradients accumulated in
# arrival order — the same ascending-microbatch summation as
# ``train.steps.accumulate_gradients``, which is what makes the
# single-mesh reference (:func:`make_mpmd_reference_step`) the parity
# anchor. Bubble fraction: each stage is busy 2M of the 2(M+S-1) ticks,
# so the schedule's idle fraction is (S-1)/(M+S-1) — the books record
# busy/idle per dispatch (a MEASURED schedule property, not the
# formula), and tests/test_pipeline_mpmd.py holds the two against each
# other.


def make_vae_stage_fns(model, beta: float):
    """The flagship VAE as a 2-stage MPMD chain.

    Stage 0 (encoder + reparameterization): ``(x,) -> (z, mu, logvar,
    x_flat)`` — mu/logvar and the flattened input ride the activation
    tuple because the ELBO at the far end needs them. Stage 1 (decoder
    + loss): logits from z, per-sample-mean negative ELBO.

    The reparameterization draws ``eps = normal(rng, ...)`` from the
    microbatch's explicit key rather than flax's ``make_rng`` fold, so
    the same math composes unchanged into the single-mesh reference
    step (:func:`make_mpmd_reference_step`) — the parity contract is
    between the pipelined and un-pipelined execution of THIS forward,
    with identical per-microbatch noise by construction.

    Returns ``(stage_fns, last_fn, stage_param_keys)`` where
    ``stage_param_keys`` names each stage's top-level param modules
    (:func:`split_stage_params`).
    """
    from multidisttorch_tpu.ops.losses import elbo_loss_sum

    def encode_stage(params, acts, rng):
        (x,) = acts
        mu, logvar = model.apply({"params": params}, x, method="encode")
        eps = jax.random.normal(rng, mu.shape, dtype=jnp.float32).astype(
            mu.dtype
        )
        z = mu + eps * jnp.exp(0.5 * logvar)
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        return (z, mu, logvar, flat)

    def decode_loss_stage(params, acts):
        z, mu, logvar, flat = acts
        logits = model.apply({"params": params}, z, method="decode")
        m = flat.shape[0]
        return elbo_loss_sum(logits, flat, mu, logvar, beta) / m

    return [encode_stage], decode_loss_stage, (
        ("fc1", "fc21", "fc22"),
        ("fc3", "fc4"),
    )


def make_vae_stage_eval_fns(model, beta: float):
    """Posterior-mean eval split along the same 2-stage boundary:
    ``enc_eval(params0, batch) -> (mu, logvar, flat)`` on stage 0,
    ``dec_eval(params1, acts, weights) -> weighted loss_sum`` on the
    last stage — the pipelined sibling of the driver's masked
    ``make_eval_step``."""
    from multidisttorch_tpu.ops.losses import elbo_loss_weighted_sum

    def enc_eval(params, batch):
        mu, logvar = model.apply({"params": params}, batch, method="encode")
        flat = batch.reshape(batch.shape[0], -1).astype(jnp.float32)
        return (mu, logvar, flat)

    def dec_eval(params, acts, weights):
        mu, logvar, flat = acts
        logits = model.apply({"params": params}, mu, method="decode")
        return elbo_loss_weighted_sum(
            logits, flat, mu, logvar, weights, beta
        ).astype(jnp.float32)

    return enc_eval, dec_eval


def split_stage_params(params, stage_param_keys) -> list:
    """Split a full param tree into per-stage trees by top-level module
    name. The split is exact and disjoint — training the stage trees
    with per-stage Adam is elementwise-identical to training the full
    tree (Adam has no cross-leaf coupling)."""
    seen = [k for keys in stage_param_keys for k in keys]
    if sorted(seen) != sorted(params):
        raise ValueError(
            f"stage split {stage_param_keys} does not partition the "
            f"param tree {sorted(params)}"
        )
    return [{k: params[k] for k in keys} for keys in stage_param_keys]


def merge_stage_params(stage_trees) -> dict:
    """Inverse of :func:`split_stage_params` (checkpoint export, PBT
    exchange across pipelined trials)."""
    out: dict = {}
    for tree in stage_trees:
        out.update(tree)
    return out


def analytic_bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """The GPipe schedule model: idle fraction (S-1)/(S-1+M)."""
    s, m = int(num_stages), int(num_microbatches)
    return (s - 1) / (s - 1 + m) if s > 1 else 0.0


def _tree_bytes(tree) -> int:
    return sum(
        int(leaf.size) * leaf.dtype.itemsize for leaf in jax.tree.leaves(tree)
    )


def _avals_of(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype), tree
    )


class MpmdPipeline:
    """One pipelined trial: S stages on S distinct submeshes.

    Owns per-stage :class:`~multidisttorch_tpu.train.steps.TrainState`s
    and compiled programs, and drives the GPipe microbatch schedule
    with ``device_put`` transfers between stage submeshes. Single
    controller (the service daemon's world); per-stage programs compile
    through the process-lifetime executable registry when
    ``registry_keys`` are supplied (retries and bucket-twin trials
    never recompile a stage).

    ``zero_update=True`` additionally places each stage's optimizer
    state ZeRO-sharded over that stage submesh's data axis
    (``parallel.fsdp.place_zero_state``) — pipeline parallelism across
    submeshes, data parallelism + sharded weight update within each.
    """

    def __init__(
        self,
        stages: Sequence,  # [TrialMesh, ...]
        stage_fns: Sequence[Callable],
        last_fn: Callable,
        stage_params: Sequence[Any],
        *,
        lr: float,
        microbatches: int,
        zero_update: bool = False,
        registry_keys: Optional[dict] = None,
        eval_fns: Optional[tuple] = None,
    ):
        import optax

        from multidisttorch_tpu.parallel.fsdp import place_zero_state
        from multidisttorch_tpu.train.steps import TrainState

        self.stages = list(stages)
        S = self.S = len(self.stages)
        if S < 2:
            raise ValueError(
                f"an MPMD pipeline needs >= 2 stages, got {S} (a 1-stage "
                "trial is a plain submesh trial)"
            )
        if len(stage_fns) != S - 1 or len(stage_params) != S:
            raise ValueError(
                f"{len(stage_fns)} stage_fns / {len(stage_params)} "
                f"stage_params for {S} stages (need S-1 fns + last_fn)"
            )
        self.M = int(microbatches)
        if self.M < 1:
            raise ValueError(f"microbatches must be >= 1, got {self.M}")
        self._stage_fns = list(stage_fns)
        self._last_fn = last_fn
        self._tx = optax.adam(float(lr))
        self.zero_update = bool(zero_update)

        # Per-stage states: split-tree Adam — elementwise-identical to
        # full-tree Adam on the merged params.
        self.states = []
        self.state_shardings = []
        for trial, p in zip(self.stages, stage_params):
            st = TrainState(
                params=p,
                opt_state=self._tx.init(p),
                step=jnp.zeros((), jnp.int32),
            )
            if self.zero_update and trial.data_size > 1:
                st, sh = place_zero_state(trial, st)
            else:
                st = trial.device_put(st)
                sh = jax.tree.map(lambda _: trial.replicated_sharding, st)
            self.states.append(st)
            self.state_shardings.append(sh)

        self._build_programs(registry_keys or {}, eval_fns)

        # Schedule books: busy/idle measured at dispatch time.
        self.books = {
            "steps": 0,
            "ticks": 0,
            "busy": 0,
            "stage_busy": [0] * S,
            "transfers": 0,
            "transfer_bytes": 0,
        }
        # First-step argument SHAPES per program — the device cost
        # books' input (telemetry/device.record_pipeline_cost); shapes
        # only, so donated buffers are never retained.
        self.cost_args: dict = {}

    # -- program construction ----------------------------------------

    def _registry_compile(self, key, jit_fn, avals):
        """Compile one stage program through the executable registry
        (one ``lower→compile`` per (kind, bucket, stage, submesh) ever;
        concurrent same-key callers coalesce). Falls back to the plain
        jit fn on any registry failure — MPMD execution must not hinge
        on the compile subsystem."""
        if key is None:
            return jit_fn
        try:
            from multidisttorch_tpu.compile.registry import (
                READY,
                SOURCE_INLINE,
                get_executable_registry,
            )

            reg = get_executable_registry()
            ex = reg.take(key)
            if ex is not None:
                return ex
            if reg.claim(key):
                e = reg.compile_now(
                    key, jit_fn, avals, source=SOURCE_INLINE
                )
                if e.status == READY:
                    ex = reg.take(key)
                    if ex is not None:
                        return ex
        except Exception:  # noqa: BLE001 — registry is an optimization
            pass
        return jit_fn

    def _build_programs(self, keys: dict, eval_fns) -> None:
        S, M = self.S, self.M
        self._fwd = [None] * S
        self._bwd = [None] * S
        self._update = [None] * S

        # Probe the activation shape chain abstractly: stage s's output
        # avals are stage s+1's input avals. Shapes are per-MICROBATCH.
        p_avals = [
            jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), st.params
            )
            for st in self.states
        ]
        rng_aval = jax.eval_shape(lambda: jax.random.key(0))
        self._acts_avals: list = [None] * S  # input acts per stage

        for s in range(S):
            trial = self.stages[s]
            repl = trial.replicated_sharding
            batch_sh = trial.batch_sharding
            if s < S - 1:
                fn = self._stage_fns[s]

                def fwd(params, acts, rng, _fn=fn):
                    return _fn(params, acts, rng)

                def bwd(params, acts, rng, cot, _fn=fn):
                    _, vjp = jax.vjp(
                        lambda p, a: _fn(p, a, rng), params, acts
                    )
                    gp, ga = vjp(cot)
                    return ga, gp

                self._fwd[s] = jax.jit(
                    fwd,
                    in_shardings=(
                        self.state_shardings[s].params, batch_sh, repl
                    ),
                    out_shardings=batch_sh,
                )
                self._bwd[s] = jax.jit(
                    bwd,
                    in_shardings=(
                        self.state_shardings[s].params, batch_sh, repl,
                        batch_sh,
                    ),
                    out_shardings=(batch_sh, repl),
                )
            else:
                last = self._last_fn

                def last_fwd(params, acts, _fn=last):
                    return _fn(params, acts)

                def last_bwd(params, acts, _fn=last):
                    gp, ga = jax.grad(_fn, argnums=(0, 1))(params, acts)
                    return ga, gp

                self._fwd[s] = jax.jit(
                    last_fwd,
                    in_shardings=(
                        self.state_shardings[s].params, batch_sh
                    ),
                    out_shardings=repl,
                )
                self._bwd[s] = jax.jit(
                    last_bwd,
                    in_shardings=(
                        self.state_shardings[s].params, batch_sh
                    ),
                    out_shardings=(batch_sh, repl),
                )

            def update(st, gsum, _tx=self._tx, _M=M):
                from multidisttorch_tpu.train.steps import TrainState

                grads = jax.tree.map(lambda g: g / _M, gsum)
                updates, new_opt = _tx.update(
                    grads, st.opt_state, st.params
                )
                import optax as _optax

                new_params = _optax.apply_updates(st.params, updates)
                return TrainState(
                    params=new_params, opt_state=new_opt, step=st.step + 1
                )

            self._update[s] = jax.jit(
                update,
                in_shardings=(self.state_shardings[s], repl),
                out_shardings=self.state_shardings[s],
                donate_argnums=(0,),
            )

        # Registry admission (timed, attributed, shared): needs concrete
        # avals, which depend on the microbatch shape — resolved on
        # first step via _admit_programs.
        self._keys = dict(keys)
        self._admitted = False
        self._p_avals = p_avals
        self._rng_aval = rng_aval

        # Eval programs (posterior-mean, masked): forward-only chain.
        self._eval_enc = self._eval_dec = None
        if eval_fns is not None:
            enc_eval, dec_eval = eval_fns
            first, last_m = self.stages[0], self.stages[-1]
            self._eval_enc = jax.jit(
                enc_eval,
                in_shardings=(
                    self.state_shardings[0].params, first.batch_sharding
                ),
                out_shardings=first.batch_sharding,
            )
            self._eval_dec = jax.jit(
                dec_eval,
                in_shardings=(
                    self.state_shardings[-1].params,
                    last_m.batch_sharding,
                    last_m.batch_sharding,
                ),
                out_shardings=last_m.replicated_sharding,
            )

    def _admit_programs(self, mb_shape, batch_dtype) -> None:
        """First-step registry admission: with the microbatch shape
        known, derive each stage program's avals and route the jit fns
        through the executable registry (one compile per program key
        ever — a retried/re-placed trial's stages come back as
        ``cache_hit``s)."""
        if self._admitted:
            return
        self._admitted = True
        S = self.S
        acts_aval = (jax.ShapeDtypeStruct(mb_shape, batch_dtype),)
        for s in range(S):
            self._acts_avals[s] = acts_aval
            if s < S - 1:
                out_aval = jax.eval_shape(
                    self._stage_fns[s],
                    self._p_avals[s],
                    acts_aval,
                    self._rng_aval,
                )
                self._fwd[s] = self._registry_compile(
                    self._keys.get(("fwd", s)),
                    self._fwd[s],
                    (self._p_avals[s], acts_aval, self._rng_aval),
                )
                self._bwd[s] = self._registry_compile(
                    self._keys.get(("bwd", s)),
                    self._bwd[s],
                    (
                        self._p_avals[s], acts_aval, self._rng_aval,
                        out_aval,
                    ),
                )
                acts_aval = out_aval
            else:
                self._fwd[s] = self._registry_compile(
                    self._keys.get(("fwd", s)),
                    self._fwd[s],
                    (self._p_avals[s], acts_aval),
                )
                self._bwd[s] = self._registry_compile(
                    self._keys.get(("bwd", s)),
                    self._bwd[s],
                    (self._p_avals[s], acts_aval),
                )
            state_aval = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                self.states[s],
            )
            gsum_aval = self._p_avals[s]
            self._update[s] = self._registry_compile(
                self._keys.get(("update", s)),
                self._update[s],
                (state_aval, gsum_aval),
            )

    # -- the schedule -------------------------------------------------

    def _transfer(self, tree, trial) -> Any:
        """One inter-stage hop: place the activation/cotangent tuple on
        the destination stage's submesh, batch-sharded over its data
        axis."""
        self.books["transfers"] += 1
        self.books["transfer_bytes"] += _tree_bytes(tree)
        return jax.device_put(tree, trial.batch_sharding)

    def step(self, batch, rng) -> dict:
        """One optimizer step: M microbatches through the two-phase
        GPipe schedule, per-stage gradient accumulation, per-stage
        update. ``batch`` lives on stage 0's submesh; ``rng`` is the
        step key (split into per-microbatch keys exactly like
        ``accumulate_gradients``'s caller). Returns
        ``{"loss_sum": <async device scalar on the last stage>}``."""
        M, S = self.M, self.S
        n = int(batch.shape[0])
        if n % M:
            raise ValueError(
                f"batch size {n} not divisible by microbatches={M}"
            )
        mb = n // M
        self._admit_programs((mb,) + tuple(batch.shape[1:]), batch.dtype)
        rngs = jax.random.split(rng, M)
        # Per-stage copies of the microbatch keys (the recompute-vjp
        # backward needs the stage's forward noise).
        stage_rngs = [
            [
                jax.device_put(rngs[m], self.stages[s].replicated_sharding)
                for m in range(M)
            ]
            for s in range(S - 1)
        ]
        stash: list = [[None] * M for _ in range(S)]
        cot: list = [[None] * M for _ in range(S)]
        gsum: list = [None] * S
        losses = []
        books = self.books
        ticks = M + S - 1

        # Forward phase: stage s runs microbatch t-s at tick t; output
        # transfers to stage s+1's submesh. Dispatches are async — the
        # host enqueues the whole tick and moves on; XLA's dependency
        # order IS the pipeline.
        for t in range(ticks):
            books["ticks"] += 1
            for s in range(S):
                m = t - s
                if not (0 <= m < M):
                    continue
                books["busy"] += 1
                books["stage_busy"][s] += 1
                if s == 0:
                    # Re-pin the slice's sharding: a sliced sharded
                    # array comes back with whatever layout XLA chose,
                    # and the stage program's in_shardings are exact.
                    acts = jax.device_put(
                        (batch[m * mb:(m + 1) * mb],),
                        self.stages[0].batch_sharding,
                    )
                else:
                    acts = stash[s][m]
                if s < S - 1:
                    args = (self.states[s].params, acts, stage_rngs[s][m])
                    out = self._fwd[s](*args)
                    stash[s][m] = acts
                    stash[s + 1][m] = self._transfer(
                        out, self.stages[s + 1]
                    )
                else:
                    args = (self.states[s].params, acts)
                    losses.append(self._fwd[s](*args))
                    stash[s][m] = acts
                if books["steps"] == 0 and m == 0:
                    self.cost_args[("fwd", s)] = _avals_of(args)

        # Backward phase: microbatch m starts at the LAST stage and
        # cotangents hop backward; per-stage grads accumulate in
        # ascending-m order (the accumulate_gradients order — parity).
        for t in range(ticks):
            books["ticks"] += 1
            for s in reversed(range(S)):
                m = t - (S - 1 - s)
                if not (0 <= m < M):
                    continue
                books["busy"] += 1
                books["stage_busy"][s] += 1
                if s == S - 1:
                    args = (self.states[s].params, stash[s][m])
                else:
                    args = (
                        self.states[s].params,
                        stash[s][m],
                        stage_rngs[s][m],
                        cot[s][m],
                    )
                if books["steps"] == 0 and m == 0:
                    self.cost_args[("bwd", s)] = _avals_of(args)
                cot_in, gp = self._bwd[s](*args)
                gsum[s] = (
                    gp
                    if gsum[s] is None
                    else jax.tree.map(jnp.add, gsum[s], gp)
                )
                if s > 0:
                    cot[s - 1][m] = self._transfer(
                        cot_in, self.stages[s - 1]
                    )
                stash[s][m] = None

        for s in range(S):
            if books["steps"] == 0:
                self.cost_args[("update", s)] = _avals_of(
                    (self.states[s], gsum[s])
                )
            self.states[s] = self._update[s](self.states[s], gsum[s])
        books["steps"] += 1

        loss_mean = losses[0]
        for extra in losses[1:]:
            loss_mean = loss_mean + extra
        loss_mean = loss_mean / M
        return {"loss_sum": (loss_mean * n).astype(jnp.float32)}

    def eval_batch(self, batch, weights):
        """Masked posterior-mean eval of one padded batch: encode on
        stage 0, one transfer, decode+loss on the last stage. Returns
        the weighted ``loss_sum`` (async device scalar)."""
        if self._eval_enc is None:
            raise ValueError("pipeline built without eval_fns")
        acts = self._eval_enc(self.states[0].params, batch)
        acts = self._transfer(acts, self.stages[-1])
        w = jax.device_put(weights, self.stages[-1].batch_sharding)
        return self._eval_dec(self.states[-1].params, acts, w)

    # -- books --------------------------------------------------------

    def measured_bubble(self) -> Optional[float]:
        """Idle fraction of the schedule actually driven: 1 −
        busy-dispatches / (S × ticks), counted per dispatch as the
        host loop runs. Gated against
        :func:`analytic_bubble_fraction` — and be precise about what
        that gate pins: a correctly-driven loop yields the analytic
        value BY CONSTRUCTION, so the gate is a schedule-STRUCTURE
        regression guard (wrong tick set, a skipped or double-driven
        stage, a mis-sized phase), not a device-overlap measurement.
        Wall-clock overlap across stages — the bubble a chip actually
        pays — needs real parallel hardware; the standing MFU caveat
        applies until open item 5's TPU run."""
        if self.books["ticks"] == 0:
            return None
        return 1.0 - self.books["busy"] / (self.S * self.books["ticks"])

    def schedule_books(self) -> dict:
        return {
            **{
                k: (list(v) if isinstance(v, list) else v)
                for k, v in self.books.items()
            },
            "stages": self.S,
            "microbatches": self.M,
            "measured_bubble": self.measured_bubble(),
            "analytic_bubble": analytic_bubble_fraction(self.S, self.M),
            "zero_update": self.zero_update,
        }

    def cost_parts(self) -> list:
        """The device cost books' input
        (``telemetry.device.record_pipeline_cost``): every per-stage
        program with its first-step arg shapes, stage devices, and
        per-optimizer-step multiplicity (forward/backward run once per
        microbatch, the update once). Empty before the first step."""
        fns = {"fwd": self._fwd, "bwd": self._bwd, "update": self._update}
        parts = []
        for s in range(self.S):
            for which, mult in (
                ("fwd", self.M), ("bwd", self.M), ("update", 1),
            ):
                args = self.cost_args.get((which, s))
                if args is None:
                    return []
                parts.append(
                    (fns[which][s], args, self.stages[s].devices, mult)
                )
        return parts

    def optimizer_state_bytes(self) -> dict:
        """Summed per-stage optimizer books (``parallel.fsdp``'s
        analytic accounting): what one device of each stage holds, and
        the replicated-equivalent total."""
        from multidisttorch_tpu.parallel.fsdp import optimizer_state_bytes

        per_dev = 0
        total = 0
        for st in self.states:
            b = optimizer_state_bytes(st)
            per_dev += b["per_device_bytes"]
            total += b["total_bytes"]
        return {"per_device_bytes": per_dev, "total_bytes": total}


def make_mpmd_reference_step(
    trial,
    stage_fns: Sequence[Callable],
    last_fn: Callable,
    tx,
    *,
    microbatches: int,
):
    """The single-mesh parity anchor for an MPMD pipeline: the SAME
    stage chain and the SAME per-microbatch keys, composed into one
    jitted step on one submesh with scan-based gradient accumulation
    (``train.steps.accumulate_gradients`` — ascending-microbatch
    summation, the pipeline's order). ``tests/test_pipeline_mpmd.py``
    holds the pipelined trial's losses against this step's.

    Returns ``step(state, batch, rng) -> (state, {"loss_sum"})`` with
    the driver's metric contract (summed loss over the batch).
    """
    import optax

    from multidisttorch_tpu.train.steps import (
        TrainState,
        accumulate_gradients,
    )

    M = int(microbatches)

    def micro_loss(params, mb_batch, mb_rng):
        acts = (mb_batch,)
        for fn in stage_fns:
            acts = fn(params, acts, mb_rng)
        return last_fn(params, acts)

    def step_fn(state: TrainState, batch, rng):
        n = batch.shape[0]
        if M == 1:
            loss, grads = jax.value_and_grad(micro_loss)(
                state.params, batch, rng
            )
        else:
            loss, _, grads = accumulate_gradients(
                trial,
                lambda p, mbb, r: (micro_loss(p, mbb, r), ()),
                state.params,
                (batch,),
                (jax.random.split(rng, M),),
                grad_accum=M,
            )
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=new_params, opt_state=new_opt, step=state.step + 1
        )
        return new_state, {"loss_sum": (loss * n).astype(jnp.float32)}

    repl = trial.replicated_sharding
    return jax.jit(
        step_fn,
        in_shardings=(repl, trial.batch_sharding, repl),
        out_shardings=(repl, repl),
        donate_argnums=(0,),
    )
