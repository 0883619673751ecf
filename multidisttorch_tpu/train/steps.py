"""Per-trial jit-compiled train/eval/sample steps.

This is the TPU-native replacement for the reference's DDP training
machinery (``/root/reference/vae-hpo.py:61-92,122-131``): where the
reference wraps the model in ``DistributedDataParallel(model,
process_group=group)`` and relies on backward-hook all-reduces scoped to
the subgroup, here the entire step is one jit-compiled program placed on
the trial's submesh — parameters and optimizer state replicated
(``TrialMesh.replicated_sharding``), the batch sharded over the
submesh's ``data`` axis (``TrialMesh.batch_sharding``) — and XLA inserts
the gradient reduction over ICI itself. One compilation per trial; every
subsequent step is a single async dispatch.

Gradient semantics: the loss is the per-sample mean, so gradients are
scale-invariant to batch/group size. The reference's effective gradient
(DDP average of per-rank *summed* losses, ``vae-hpo.py:49-58,130``) is
``local_batch_size``× larger; under Adam (the reference's optimizer,
``vae-hpo.py:131``) the difference is absorbed by the second-moment
normalization. Logged losses are *sums* so the reference's per-sample
logging arithmetic (``vae-hpo.py:83,89,118``) carries over unchanged.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from flax import struct

from multidisttorch_tpu.models.vae import VAE
from multidisttorch_tpu.ops.losses import elbo_loss_sum
from multidisttorch_tpu.parallel.mesh import DATA_AXIS, TrialMesh


@struct.dataclass
class TrainState:
    """Replicated per-trial training state (the analog of the reference's
    DDP-wrapped model + Adam optimizer, ``vae-hpo.py:129-131``).

    A plain pytree: serializable for checkpoint/resume and PBT
    weight-exchange across submeshes.
    """

    params: Any
    opt_state: Any
    step: jnp.ndarray  # int32 scalar


def build_train_state(
    model: VAE, tx: optax.GradientTransformation, rng: jax.Array
) -> TrainState:
    """Construct an un-placed :class:`TrainState` on the default device.

    The single source of the state pytree's structure: placement
    (:func:`create_train_state`) and the multi-host broadcast template
    (``hpo/pbt.py``) both derive from it, so the tree every process
    expects in a cross-process transfer can never drift from the tree
    members actually train.
    """
    params = model.init(
        {"params": rng, "reparam": rng},
        jnp.zeros((1, model.input_dim), jnp.float32),
    )["params"]
    return TrainState(
        params=params,
        opt_state=tx.init(params),
        step=jnp.zeros((), jnp.int32),
    )


def place_sharded_state(
    trial: TrialMesh,
    params: Any,
    tx: optax.GradientTransformation,
    param_shardings: Any,
) -> TrainState:
    """Place an initialized param tree as a weight-sharded TrainState.

    The one copy of the tensor-parallel placement recipe (shared by the
    VAE and classifier state creators): params placed per
    ``param_shardings``; the optimizer state initialized *eagerly* so
    computation-follows-data gives each Adam moment its weight's
    sharding — no hand-written moment shardings. (Do NOT jit the init:
    jit constant-folds the zeros and drops the sharding.) Scalar opt
    leaves with no input dependence (Adam's count) come back
    single-device — those are pinned replicated on the submesh.
    """
    from jax.sharding import NamedSharding

    params = jax.device_put(params, param_shardings)
    opt_state = jax.tree.map(
        lambda x: (
            x
            if isinstance(getattr(x, "sharding", None), NamedSharding)
            else trial.device_put(x)
        ),
        tx.init(params),
    )
    return TrainState(
        params=params,
        opt_state=opt_state,
        step=jax.device_put(
            jnp.zeros((), jnp.int32), trial.replicated_sharding
        ),
    )


def create_train_state(
    trial: TrialMesh,
    model: VAE,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    param_shardings: Any = None,
) -> TrainState:
    """Initialize params on host, place them on the trial submesh.

    The analog of ``VAE().to(device)`` + DDP's initial parameter
    broadcast (``vae-hpo.py:129-130``) — except there is no broadcast:
    placement with a sharding materializes the right shard/copy on every
    member device. Default is DDP-style full replication;
    ``param_shardings`` (a pytree of ``NamedSharding`` matching the
    param tree, e.g. ``models.vae.vae_tp_shardings``) instead shards
    weights over the submesh's model axis via
    :func:`place_sharded_state`.
    """
    if param_shardings is None:
        return trial.device_put(build_train_state(model, tx, rng))

    params = model.init(
        {"params": rng, "reparam": rng},
        jnp.zeros((1, model.input_dim), jnp.float32),
    )["params"]
    return place_sharded_state(trial, params, tx, param_shardings)


def state_shardings(state: TrainState) -> TrainState:
    """The concrete sharding of every leaf of a placed ``TrainState`` —
    pass to :func:`make_train_step` to pin a tensor-parallel state's
    layout across steps (no layout drift, no resharding)."""
    return jax.tree.map(lambda x: x.sharding, state)


def accumulate_gradients(
    trial: TrialMesh,
    fn: Callable,
    params: Any,
    batch_arrays: tuple,
    per_micro_args: tuple = (),
    *,
    grad_accum: int,
):
    """The ONE copy of the microbatch gradient-accumulation recipe.

    ``fn(params, *micro_batch_arrays, *micro_extra_args) -> (loss, aux)``
    is evaluated on ``grad_accum`` equal splits of each batch-major
    array (dim 0), with gradients, f32 losses, and aux values summed in
    a ``lax.scan`` carry; returns ``(loss_mean, aux_sum, grads_mean)``.
    ``per_micro_args`` are already microbatch-major ``(A, ...)`` (e.g.
    per-microbatch RNG keys). The reshape keeps batch rows sharded over
    the data axis WITHIN each microbatch — without the constraint GSPMD
    may shard the microbatch index instead, which parallelizes the scan
    away and gives up the activation-memory saving.
    """
    n = batch_arrays[0].shape[0]
    if n % grad_accum:
        raise ValueError(
            f"batch size {n} not divisible by grad_accum={grad_accum}"
        )
    mb = n // grad_accum

    def prep(a):
        m = a.reshape((grad_accum, mb) + a.shape[1:])
        return jax.lax.with_sharding_constraint(
            m, trial.sharding(None, DATA_AXIS, *([None] * (a.ndim - 1)))
        )

    micro = tuple(prep(a) for a in batch_arrays)

    def body(carry, xs):
        loss_acc, aux_acc, grad_acc = carry
        (l, aux), g = jax.value_and_grad(fn, has_aux=True)(params, *xs)
        return (
            loss_acc + l.astype(jnp.float32),
            jax.tree.map(jnp.add, aux_acc, aux),
            jax.tree.map(jnp.add, grad_acc, g),
        ), None

    # Abstract eval for the aux zero-carry (shapes/dtypes only, no FLOPs).
    aux_shape = jax.eval_shape(
        lambda p, *xs: fn(p, *xs)[1],
        params,
        *(m[0] for m in micro),
        *(x[0] for x in per_micro_args),
    )
    zeros = (
        jnp.zeros((), jnp.float32),
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), aux_shape),
        jax.tree.map(jnp.zeros_like, params),
    )
    (loss_sum, aux_sum, grad_sum), _ = jax.lax.scan(
        body, zeros, micro + per_micro_args
    )
    return (
        loss_sum / grad_accum,
        aux_sum,
        jax.tree.map(lambda g: g / grad_accum, grad_sum),
    )


def _validate_grad_accum(grad_accum: int) -> None:
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")


def _build_step_fn(
    trial: TrialMesh,
    model: VAE,
    tx: optax.GradientTransformation,
    beta: float,
    use_fused_loss: bool,
    remat: bool = False,
    grad_accum: int = 1,
) -> Callable[[TrainState, jax.Array, jax.Array], tuple[TrainState, dict]]:
    """The un-jitted train-step body shared by :func:`make_train_step`
    (one step per dispatch) and :func:`make_multi_step` (scan-fused).

    ``remat=True`` wraps the forward in ``jax.checkpoint``: activations
    are recomputed during the backward pass instead of stored — the
    standard HBM-for-FLOPs trade when a model (or a long scan of fused
    steps) outgrows device memory. Numerically identical training.

    ``grad_accum=A`` splits the batch into A equal microbatches and
    accumulates their gradients in a ``lax.scan`` before the single
    optimizer update — activation memory drops to one microbatch's
    worth, so the effective batch can exceed HBM. The per-sample-mean
    loss makes the accumulated gradient the mean of microbatch
    gradients, i.e. the same estimator as the full batch (each
    microbatch draws its own reparameterization noise, so values match
    the full-batch program in expectation, not bitwise).
    """
    loss_impl = elbo_loss_sum
    if use_fused_loss:
        from jax.sharding import PartitionSpec as _P

        from multidisttorch_tpu.ops.pallas_elbo import fused_elbo_loss_sum
        from multidisttorch_tpu.parallel.mesh import DATA_AXIS as _AXIS

        if trial.size == 1:
            loss_impl = fused_elbo_loss_sum
        else:
            # A bare Pallas custom call is opaque to the partitioner, so
            # on a multi-device submesh XLA would all-gather all four
            # operands onto every chip. Run the kernel per-shard under
            # shard_map and psum the partial sums instead — each chip
            # reduces only its own batch rows.
            def loss_impl(logits, x, mu, logvar, beta):
                return jax.shard_map(
                    lambda lo, xx, m, lv: jax.lax.psum(
                        fused_elbo_loss_sum(lo, xx, m, lv, beta), _AXIS
                    ),
                    mesh=trial.mesh,
                    in_specs=(_P(_AXIS), _P(_AXIS), _P(_AXIS), _P(_AXIS)),
                    out_specs=_P(),
                    # pallas_call's out_shape carries no VMA annotation,
                    # so the varying-axis checker can't type it; the
                    # trailing psum makes the result replicated anyway.
                    check_vma=False,
                )(logits, x, mu, logvar)

    def forward(params, batch, rng):
        return model.apply({"params": params}, batch, rngs={"reparam": rng})

    if remat:
        forward = jax.checkpoint(forward)

    def microbatch_loss(params, mb_batch, mb_rng):
        m = mb_batch.shape[0]
        recon_logits, mu, logvar = forward(params, mb_batch, mb_rng)
        total = loss_impl(
            recon_logits, mb_batch.reshape(m, -1), mu, logvar, beta
        )
        return total / m

    def step_fn(state: TrainState, batch: jax.Array, rng: jax.Array):
        n = batch.shape[0]

        if grad_accum == 1:
            loss, grads = jax.value_and_grad(microbatch_loss)(
                state.params, batch, rng
            )
        else:
            loss, _, grads = accumulate_gradients(
                trial,
                lambda p, mb, r: (microbatch_loss(p, mb, r), ()),
                state.params,
                (batch,),
                (jax.random.split(rng, grad_accum),),
                grad_accum=grad_accum,
            )

        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=new_params, opt_state=new_opt, step=state.step + 1
        )
        metrics = {"loss_sum": (loss * n).astype(jnp.float32)}
        return new_state, metrics

    return step_fn


def make_train_step(
    trial: TrialMesh,
    model: VAE,
    tx: optax.GradientTransformation,
    *,
    beta: float = 1.0,
    use_fused_loss: bool = False,
    shardings: Any = None,
    remat: bool = False,
    grad_accum: int = 1,
) -> Callable[[TrainState, jax.Array, jax.Array], tuple[TrainState, dict]]:
    """Build the compiled train step for one trial submesh.

    Returns ``step(state, batch, rng) -> (state, metrics)`` where
    ``batch`` is the trial-global batch (sharded over the submesh data
    axis on entry), and ``metrics['loss_sum']`` is the summed negative
    ELBO over the batch (reference logging contract, ``vae-hpo.py:73``).
    ``use_fused_loss`` swaps in the single-pass Pallas ELBO kernel
    (``ops/pallas_elbo.py``, forward + custom-VJP backward); default off
    because XLA's own fusion is already competitive and composes with
    the surrounding matmuls.

    ``shardings`` (from :func:`state_shardings` on a tensor-parallel
    state) pins the state layout in and out of the step, so a 2-D
    (data × model) trial runs Megatron-style: batch split over ``data``,
    weights split over ``model``, and GSPMD inserts the activation
    psums + gradient reductions over the right ICI axes.
    """
    repl = trial.replicated_sharding
    data = trial.batch_sharding
    _validate_grad_accum(grad_accum)
    step_fn = _build_step_fn(
        trial, model, tx, beta, use_fused_loss, remat, grad_accum
    )
    state_sh = repl if shardings is None else shardings
    return jax.jit(
        step_fn,
        in_shardings=(state_sh, data, repl),
        out_shardings=(state_sh, repl),
        donate_argnums=(0,),
    )


def make_multi_step(
    trial: TrialMesh,
    model: VAE,
    tx: optax.GradientTransformation,
    *,
    beta: float = 1.0,
    use_fused_loss: bool = False,
    shardings: Any = None,
    remat: bool = False,
    grad_accum: int = 1,
) -> Callable[[TrainState, jax.Array, jax.Array], tuple[TrainState, dict]]:
    """K chained train steps in ONE dispatch, via ``lax.scan``.

    At the reference's workload size (a 784-400-20 MLP VAE at batch 128,
    ``/root/reference/vae-hpo.py:19-45,183``) a single train step is a
    few microseconds of MXU time, so a per-step Python dispatch — the
    reference's loop shape (``vae-hpo.py:67-74``) and
    :func:`make_train_step`'s — is host-bound. The TPU-first fix is to
    keep the loop on device: scan the step body over a stacked batch so
    the chip runs K optimizer updates per host round-trip.

    Returns ``multi_step(state, batches, rng) -> (state, metrics)`` where
    ``batches`` has shape ``(K, batch, ...)`` — sharded over the submesh
    data axis on dim 1 — and ``metrics['loss_sum']`` has shape ``(K,)``
    (one summed negative ELBO per inner step, same logging contract as
    :func:`make_train_step`). ``rng`` is split into K per-step keys
    inside the compiled program.
    """
    _validate_grad_accum(grad_accum)
    step_fn = _build_step_fn(
        trial, model, tx, beta, use_fused_loss, remat, grad_accum
    )
    repl = trial.replicated_sharding
    batches_sh = trial.sharding(None, DATA_AXIS)
    state_sh = repl if shardings is None else shardings

    def multi_fn(state: TrainState, batches: jax.Array, rng: jax.Array):
        rngs = jax.random.split(rng, batches.shape[0])

        def body(s, xs):
            b, r = xs
            s, metrics = step_fn(s, b, r)
            return s, metrics["loss_sum"]

        state, losses = jax.lax.scan(body, state, (batches, rngs))
        return state, {"loss_sum": losses}

    return jax.jit(
        multi_fn,
        in_shardings=(state_sh, batches_sh, repl),
        out_shardings=(state_sh, repl),
        donate_argnums=(0,),
    )


# --- trial stacking: K same-shape trials through ONE compiled program ---
#
# At the flagship's size a whole train step is microseconds of MXU time,
# so a sweep of small trials is dispatch-bound no matter how its
# submeshes are carved (docs/DISPATCH.md; not measured in steady state
# on the chip, ROADMAP A10). Scan-fusion amortizes
# dispatch *in time* (more steps per call); stacking amortizes it *in
# trials*: bucket K configs that share every array shape (architecture,
# batch size) and differ only in scalar hypers (lr, beta, seed), stack
# their states along a leading trial axis, and vmap the step body over
# that axis — XLA fuses K trials' matmuls into batched ops inside one
# program, so one host dispatch advances K trials (the DrJAX
# mapped-workload construction, arXiv:2403.07128). Composes with
# lax.scan chunking: one dispatch = fused_steps x K optimizer updates.
#
# Per-trial hypers ride in as batched arrays (TrialHypers); the
# optimizer is rebuilt per-lane inside the vmap from the traced lr as
# chain(scale_by_adam, scale(-lr)) — the literal definition of
# optax.adam(lr), so state trees AND update math are bit-identical to
# the unstacked driver path (regression-tested in tests/test_stacking).
# `active` masks a lane's parameter updates (x1.0 live, x0.0 retired):
# a finished trial's lane keeps flowing through the same compiled
# program with frozen params until the driver refills the lane with the
# next queued config (`write_lane`) — retirement and refill never
# recompile.


@struct.dataclass
class TrialHypers:
    """Per-lane scalar hyperparameters of a stacked trial bucket, each
    shape ``(K,)``: the vmapped axis of everything that may differ
    between bucket members without changing the compiled program."""

    lr: jnp.ndarray
    beta: jnp.ndarray
    # 1.0 = lane training; 0.0 = lane retired (updates masked to zero,
    # params frozen at their final values until the lane is refilled).
    active: jnp.ndarray

    @staticmethod
    def stack(lrs, betas, active=None) -> "TrialHypers":
        lrs = jnp.asarray(lrs, jnp.float32)
        return TrialHypers(
            lr=lrs,
            beta=jnp.asarray(betas, jnp.float32),
            active=(
                jnp.ones_like(lrs)
                if active is None
                else jnp.asarray(active, jnp.float32)
            ),
        )


def build_lane_state(model: VAE, seed: int) -> TrainState:
    """One lane's fresh :class:`TrainState` (un-placed, no leading axis).

    Adam's init is learning-rate-independent (zero moments + count), so
    a single builder serves every lane regardless of its lr — the same
    tree :func:`build_train_state` produces for the unstacked driver
    path, which is what keeps stacked/unstacked checkpoints
    interchangeable."""
    return build_train_state(model, optax.adam(1.0), jax.random.key(seed))


def build_stacked_train_state(model: VAE, seeds: Sequence[int]) -> TrainState:
    """Stack K per-seed lane states along a new leading trial axis."""
    lanes = [build_lane_state(model, s) for s in seeds]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)


def create_stacked_train_state(
    trial: TrialMesh, model: VAE, seeds: Sequence[int]
) -> TrainState:
    """Build and place a stacked state: every leaf gains a leading
    ``K = len(seeds)`` axis, replicated over the submesh (the trial axis
    is the vmap axis, never a mesh axis — lanes are data-independent by
    construction, so there is nothing to communicate between them)."""
    return trial.device_put(build_stacked_train_state(model, seeds))


def _lane_fold_rngs(base_rngs: jax.Array, lane_steps: jnp.ndarray) -> jax.Array:
    """Per-lane step keys: ``fold_in(base_k, step_k)`` — the SAME stream
    as the unstacked per-step driver path (driver.py folds its trial key
    with the global optimizer-step count), which is what makes
    stacked-vs-unstacked bit-for-bit parity possible."""
    return jax.vmap(jax.random.fold_in)(base_rngs, lane_steps)


def _stacked_lane_body(
    trial: TrialMesh, model: VAE, remat: bool, grad_accum: int
):
    """The per-lane step body vmapped by both stacked step builders:
    ``(state, batch, rng, lr, beta, active) -> (state, loss_sum)`` with
    lr/beta as traced scalars (the batched-hypers contract) and the
    optimizer rebuilt from lr as optax.adam's own definition."""

    def forward(params, batch, rng):
        return model.apply({"params": params}, batch, rngs={"reparam": rng})

    if remat:
        forward = jax.checkpoint(forward)

    def microbatch_loss(params, mb_batch, mb_rng, beta):
        m = mb_batch.shape[0]
        recon_logits, mu, logvar = forward(params, mb_batch, mb_rng)
        total = elbo_loss_sum(
            recon_logits, mb_batch.reshape(m, -1), mu, logvar, beta
        )
        return total / m

    def lane_body(state, batch, rng, lr, beta, active):
        n = batch.shape[0]
        if grad_accum == 1:
            loss, grads = jax.value_and_grad(microbatch_loss)(
                state.params, batch, rng, beta
            )
        else:
            loss, _, grads = accumulate_gradients(
                trial,
                lambda p, mb, r: (microbatch_loss(p, mb, r, beta), ()),
                state.params,
                (batch,),
                (jax.random.split(rng, grad_accum),),
                grad_accum=grad_accum,
            )
        # optax.adam(lr) IS chain(scale_by_adam, scale(-lr)); building it
        # from the traced per-lane lr keeps state structure and update
        # arithmetic bit-identical to the unstacked path.
        tx = optax.chain(optax.scale_by_adam(), optax.scale(-lr))
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=new_params, opt_state=new_opt, step=state.step + 1
        )
        # Retirement mask as a SELECT, not a multiply: `active * update`
        # changes XLA's FMA contraction around the parameter add and
        # costs live lanes one ulp vs the unstacked program (measured);
        # where() picks whole computed values, so live lanes stay
        # bit-identical and retired lanes stay frozen exactly.
        new_state = jax.tree.map(
            lambda new, old: jnp.where(active > 0.5, new, old),
            new_state,
            state,
        )
        return new_state, (loss * n).astype(jnp.float32)

    return lane_body


def make_stacked_train_step(
    trial: TrialMesh,
    model: VAE,
    *,
    remat: bool = False,
    grad_accum: int = 1,
):
    """One vmapped optimizer step for K stacked trials in ONE dispatch.

    Returns ``step(state, hypers, batch, base_rngs, lane_steps) ->
    (state, metrics)`` where every ``state`` leaf and ``batch``
    (``(K, B, ...)``, dim 1 sharded over the submesh data axis) carry a
    leading trial axis, ``hypers`` is a :class:`TrialHypers` of ``(K,)``
    arrays, ``base_rngs`` is a ``(K,)`` key array (one per-trial stream,
    ``key(seed+1)`` in the driver), and ``lane_steps`` ``(K,)`` int32 is
    each lane's optimizer-step count — folded into its key exactly like
    the unstacked per-step path, so a stacked trial's RNG stream (and
    therefore its weights) match the unstacked trial bit-for-bit.
    ``metrics['loss_sum']`` is ``(K,)``, one summed negative ELBO per
    trial (the reference logging contract, per lane).

    The fused Pallas ELBO is deliberately NOT plumbed here: its kernel
    takes beta as a compile-time constant, and per-lane traced betas
    would force one kernel instance per lane — the XLA loss fuses fine
    under vmap and benches within noise of the kernel (BENCH r4).
    """
    _validate_grad_accum(grad_accum)
    lane_body = _stacked_lane_body(trial, model, remat, grad_accum)
    vstep = jax.vmap(lane_body, in_axes=(0, 0, 0, 0, 0, 0))
    repl = trial.replicated_sharding
    batch_sh = trial.sharding(None, DATA_AXIS)

    def step_fn(
        state: TrainState,
        hypers: TrialHypers,
        batch: jax.Array,
        base_rngs: jax.Array,
        lane_steps: jnp.ndarray,
    ):
        rngs = _lane_fold_rngs(base_rngs, lane_steps)
        state, loss_sums = vstep(
            state, batch, rngs, hypers.lr, hypers.beta, hypers.active
        )
        return state, {"loss_sum": loss_sums}

    return jax.jit(
        step_fn,
        in_shardings=(repl, repl, batch_sh, repl, repl),
        out_shardings=(repl, repl),
        donate_argnums=(0,),
    )


def make_stacked_multi_step(
    trial: TrialMesh,
    model: VAE,
    *,
    remat: bool = False,
    grad_accum: int = 1,
):
    """``S`` scan-chained vmapped steps: one dispatch = S x K optimizer
    updates (scan amortizes dispatch in time, the stacked axis amortizes
    it in trials — the two compose multiplicatively).

    Returns ``multi(state, hypers, batches, base_rngs, lane_steps) ->
    (state, metrics)`` with ``batches`` of shape ``(S, K, B, ...)``
    (dim 2 sharded over the submesh data axis) and
    ``metrics['loss_sum']`` of shape ``(S, K)``. Inner step ``s`` folds
    ``lane_steps + s`` into each lane's base key — the identical stream
    to :func:`make_stacked_train_step` called S times, so chunked and
    per-step stacked training produce bit-identical weights (unlike
    :func:`make_multi_step`, whose split-based stream is its own).
    """
    _validate_grad_accum(grad_accum)
    lane_body = _stacked_lane_body(trial, model, remat, grad_accum)
    vstep = jax.vmap(lane_body, in_axes=(0, 0, 0, 0, 0, 0))
    repl = trial.replicated_sharding
    batches_sh = trial.sharding(None, None, DATA_AXIS)

    def multi_fn(
        state: TrainState,
        hypers: TrialHypers,
        batches: jax.Array,
        base_rngs: jax.Array,
        lane_steps: jnp.ndarray,
    ):
        def body(s, xs):
            b, i = xs
            rngs = _lane_fold_rngs(base_rngs, lane_steps + i)
            s, loss_sums = vstep(
                s, b, rngs, hypers.lr, hypers.beta, hypers.active
            )
            return s, loss_sums

        state, losses = jax.lax.scan(
            body, state, (batches, jnp.arange(batches.shape[0], dtype=jnp.int32))
        )
        return state, {"loss_sum": losses}

    return jax.jit(
        multi_fn,
        in_shardings=(repl, repl, batches_sh, repl, repl),
        out_shardings=(repl, repl),
        donate_argnums=(0,),
    )


def _stacked_eval_lane(model: VAE):
    """The per-lane masked posterior-mean eval body shared by
    :func:`make_stacked_eval_step` and the fused PBT generation program
    (:func:`make_pbt_generation_step`) — one copy, so a lane's eval loss
    is bit-identical whether it is scored standalone or inside the
    fused generation dispatch."""
    from multidisttorch_tpu.ops.losses import elbo_loss_weighted_sum

    def lane_eval(params, beta, batch, weights):
        n = batch.shape[0]
        flat = batch.reshape(n, -1)
        mu, logvar = model.apply({"params": params}, batch, method="encode")
        recon_logits = model.apply({"params": params}, mu, method="decode")
        return elbo_loss_weighted_sum(
            recon_logits, flat, mu, logvar, weights, beta
        ).astype(jnp.float32)

    return lane_eval


def _scan_eval_sums(veval, params, betas, eval_batches, eval_weights):
    """Scan-accumulate the per-lane eval loss sums over ``(E, B, ...)``
    stacked eval batches from a zero f32 carry — the ONE copy of the
    eval reduction structure shared by :func:`make_stacked_eval_scan`
    and the fused PBT generation program. Sharing the structure is a
    bit-parity requirement, not a style choice: XLA fuses a scanned
    reduction differently from a per-batch one (last-ulp reassociation,
    measured on XLA:CPU at the flagship model size), so the per-submesh
    reference path and the fused path must BOTH reduce through this
    scan for their scores to stay bit-identical."""
    k_lanes = betas.shape[0]

    def ebody(acc, xs):
        b, w = xs
        return acc + veval(params, betas, b, w), None

    sums, _ = jax.lax.scan(
        ebody,
        jnp.zeros((k_lanes,), jnp.float32),
        (eval_batches, eval_weights),
    )
    return sums


def make_stacked_eval_scan(trial: TrialMesh, model: VAE):
    """Whole-eval-set masked eval for K stacked trials in ONE dispatch:
    ``eval_scan(state, hypers, eval_batches, eval_weights) ->
    {'loss_sum': (K,)}`` with ``eval_batches`` ``(E, B, ...)`` and
    ``eval_weights`` ``(E, B)`` (dim 1 data-sharded, shared across
    lanes) — the per-batch :func:`make_stacked_eval_step` folded over
    the eval set on device. This is the PBT reference path's scorer:
    structurally identical to the eval phase inside the fused
    generation program (see :func:`_scan_eval_sums`)."""
    repl = trial.replicated_sharding
    eval_sh = trial.sharding(None, DATA_AXIS)
    veval = jax.vmap(_stacked_eval_lane(model), in_axes=(0, 0, None, None))

    def eval_fn(
        state: TrainState, hypers: TrialHypers, eval_batches, eval_weights
    ):
        return {
            "loss_sum": _scan_eval_sums(
                veval, state.params, hypers.beta, eval_batches,
                eval_weights,
            )
        }

    return jax.jit(
        eval_fn,
        in_shardings=(repl, repl, eval_sh, eval_sh),
        out_shardings=repl,
    )


def make_stacked_eval_step(trial: TrialMesh, model: VAE):
    """Masked posterior-mean eval for K stacked trials in one dispatch:
    ``eval(state, hypers, batch, weights) -> {'loss_sum': (K,)}`` — the
    batch and its pad-mask weights are shared across lanes (every trial
    scores the same test rows, reference contract), only the state and
    beta are per-lane."""
    repl = trial.replicated_sharding
    data = trial.batch_sharding

    veval = jax.vmap(_stacked_eval_lane(model), in_axes=(0, 0, None, None))

    def eval_fn(state: TrainState, hypers: TrialHypers, batch, weights):
        return {"loss_sum": veval(state.params, hypers.beta, batch, weights)}

    return jax.jit(
        eval_fn,
        in_shardings=(repl, repl, data, data),
        out_shardings=repl,
    )


def make_lane_ops(trial: TrialMesh):
    """Compiled lane surgery for mask-and-refill: ``(read, write)``.

    ``read(state, k) -> TrainState`` slices lane ``k`` out of a stacked
    state (checkpoint/result capture at retirement); ``write(state,
    lane_state, k) -> state`` overwrites lane ``k`` with a freshly
    initialized lane (refill). ``k`` is a TRACED int32, so every lane
    index reuses one compiled program each way — a bucket churns through
    its whole queue with zero recompiles (asserted via ``_cache_size``
    in tests)."""
    repl = trial.replicated_sharding

    def read(state: TrainState, k) -> TrainState:
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, k, 0, keepdims=False),
            state,
        )

    def write(state: TrainState, lane: TrainState, k) -> TrainState:
        return jax.tree.map(
            lambda a, b: jax.lax.dynamic_update_index_in_dim(
                a, b.astype(a.dtype), k, 0
            ),
            state,
            lane,
        )

    read_j = jax.jit(read, in_shardings=(repl, None), out_shardings=repl)
    write_j = jax.jit(
        write,
        in_shardings=(repl, repl, None),
        out_shardings=repl,
        donate_argnums=(0,),
    )
    return read_j, write_j


# --- fused PBT: exploit/explore as collectives over the lane axis ---
#
# The stacked lane axis (above) already runs K trials as one vmapped
# program; population-based training adds one more per-generation op —
# the exploit/explore exchange — and the pre-stacking PBT ran it
# host-side: fetch every member's score, rank on the host, device_get/
# device_put each exploited member's whole state across submeshes. Over
# the lane axis the exchange is just lane-collectives (the DrJAX
# population-as-mapped-axis construction, arXiv:2403.07128): a stable
# argsort ranks lanes, a gather copies winners' params+opt-state into
# losers' lanes, and a where perturbs the batched per-lane lr — so a
# whole generation (train scan + eval scan + exchange) compiles into
# ONE program and dispatches once, with no host round-trip per
# exploited member. The explore perturbation is a PURE function of
# (explore_key, generation, target lane) — the seeding contract that
# lets the host-side reference path (hpo/pbt.py, fused=False) draw the
# identical factors and stay bit-identical to the in-program exchange
# (docs/PBT.md).

# Domain-separation tag folded into key(seed) for the explore stream:
# keeps perturbation draws disjoint from the param-init (key(seed+k))
# and per-step data (key(seed+k+1)) streams, which share the seed space.
PBT_EXPLORE_TAG = 0x9E3779B9


def pbt_explore_key(seed: int) -> jax.Array:
    """The population's explore stream root: every perturbation in a
    PBT run (fused or host-side reference) derives from this one key,
    so the two paths draw identical factors."""
    return jax.random.fold_in(jax.random.key(seed), PBT_EXPLORE_TAG)


def pbt_perturb_factor(
    explore_key: jax.Array, gen, lane, perturb_factors: tuple
) -> jnp.ndarray:
    """The explore draw for (generation, target lane): a pure function
    — ``fold_in(fold_in(explore_key, gen), lane)`` indexing the factor
    table — identical eager (host reference path) and traced (inside
    the fused generation program), which is the whole seeding contract.
    ``gen``/``lane`` may be Python ints or traced int32 scalars."""
    k = jax.random.fold_in(jax.random.fold_in(explore_key, gen), lane)
    idx = jax.random.randint(k, (), 0, len(perturb_factors))
    return jnp.asarray(perturb_factors, jnp.float32)[idx]


def pbt_exchange(
    state: TrainState,
    hypers: TrialHypers,
    eval_sums: jnp.ndarray,
    gen,
    explore_key: jax.Array,
    *,
    n_exploit: int,
    perturb_factors: tuple,
    lr_min: float,
    lr_max: float,
):
    """The in-program exploit/explore over the lane axis.

    ``eval_sums`` is the per-lane summed eval loss ``(K,)`` (f32; the
    monotone rank statistic — dividing by the shared row count changes
    no ordering). Ranking sanitizes NaN to ``+inf`` with a STABLE
    argsort, so a diverged lane ranks strictly last (never a source)
    and ties break by lane index — the same total order the host
    reference path computes with ``np.argsort(kind='stable')``.

    With ``n_exploit`` top/bottom slots (a static int, clamped by the
    caller to ``K // 2`` so the slices can never overlap), bottom slot
    ``i`` exploits top slot ``i`` iff its sanitized loss is strictly
    worse: the whole per-lane TrainState (params, optimizer moments,
    step) is GATHERED from the source lane, and the target lane's lr
    becomes ``clip(lr[src] * factor, lr_min, lr_max)`` with the factor
    drawn by :func:`pbt_perturb_factor`. Non-exploiting lanes pass
    through untouched (gather from self). ``n_exploit == 0`` (the K=1
    degenerate population) is the identity exchange.

    Returns ``(state, hypers, stats)`` where ``stats`` carries
    ``order`` (lanes best→worst), ``exploited`` (K,) bool, ``src``
    (K,) int32 (self where not exploited), and ``new_lr`` (K,) f32 —
    the host's books for telemetry and history, one fetch per
    generation.
    """
    k_lanes = hypers.lr.shape[0]
    sanitized = jnp.where(jnp.isnan(eval_sums), jnp.inf, eval_sums)
    order = jnp.argsort(sanitized, stable=True).astype(jnp.int32)
    lanes = jnp.arange(k_lanes, dtype=jnp.int32)
    if n_exploit == 0:
        stats = {
            "order": order,
            "exploited": jnp.zeros((k_lanes,), bool),
            "src": lanes,
            "new_lr": hypers.lr,
        }
        return state, hypers, stats
    top = order[:n_exploit]
    bottom = order[k_lanes - n_exploit:]
    cond = sanitized[bottom] > sanitized[top]
    src = lanes.at[bottom].set(jnp.where(cond, top, bottom))
    exploited = jnp.zeros((k_lanes,), bool).at[bottom].set(cond)
    factors = jax.vmap(
        lambda lane: pbt_perturb_factor(
            explore_key, gen, lane, perturb_factors
        )
    )(lanes)
    new_lr = jnp.where(
        exploited,
        jnp.clip(jnp.take(hypers.lr, src) * factors, lr_min, lr_max),
        hypers.lr,
    )
    new_state = jax.tree.map(lambda a: jnp.take(a, src, axis=0), state)
    new_hypers = TrialHypers(
        lr=new_lr, beta=hypers.beta, active=hypers.active
    )
    stats = {
        "order": order,
        "exploited": exploited,
        "src": src,
        "new_lr": new_lr,
    }
    return new_state, new_hypers, stats


def make_pbt_generation_step(
    trial: TrialMesh,
    model: VAE,
    *,
    n_exploit: int,
    perturb_factors: tuple,
    lr_min: float,
    lr_max: float,
):
    """ONE whole PBT generation as ONE compiled dispatch: an S-step
    train scan over K stacked lanes (the exact
    :func:`make_stacked_multi_step` body and RNG stream), an eval scan
    over E shared pad-and-mask batches (the exact
    :func:`make_stacked_eval_step` lane body), and the in-program
    :func:`pbt_exchange` — where the pre-stacking PBT paid K train
    dispatches + K·E eval dispatches + a host round-trip per exploited
    member per generation.

    Returns ``gen_step(state, hypers, batches, eval_batches,
    eval_weights, base_rngs, lane_steps, gen, explore_key) ->
    (state, hypers, stats)`` with ``batches`` of shape ``(S, K, B, ...)``
    (dim 2 data-sharded), ``eval_batches``/``eval_weights`` of shape
    ``(E, B, ...)``/``(E, B)`` shared across lanes, and ``gen`` a traced
    int32 scalar — so one executable serves every generation (the
    ``pbt_gen`` program kind, registered and AOT-compiled through
    ``compile/programs.py``). ``stats`` carries per-step train losses
    ``(S, K)``, per-lane eval loss sums ``(K,)``, and the exchange
    books (:func:`pbt_exchange`).
    """
    lane_body = _stacked_lane_body(trial, model, remat=False, grad_accum=1)
    vstep = jax.vmap(lane_body, in_axes=(0, 0, 0, 0, 0, 0))
    veval = jax.vmap(_stacked_eval_lane(model), in_axes=(0, 0, None, None))
    repl = trial.replicated_sharding
    batches_sh = trial.sharding(None, None, DATA_AXIS)
    eval_sh = trial.sharding(None, DATA_AXIS)

    def gen_fn(
        state: TrainState,
        hypers: TrialHypers,
        batches: jax.Array,
        eval_batches: jax.Array,
        eval_weights: jax.Array,
        base_rngs: jax.Array,
        lane_steps: jnp.ndarray,
        gen: jnp.ndarray,
        explore_key: jax.Array,
    ):
        def body(s, xs):
            b, i = xs
            rngs = _lane_fold_rngs(base_rngs, lane_steps + i)
            s, loss_sums = vstep(
                s, b, rngs, hypers.lr, hypers.beta, hypers.active
            )
            return s, loss_sums

        state, train_losses = jax.lax.scan(
            body,
            state,
            (batches, jnp.arange(batches.shape[0], dtype=jnp.int32)),
        )

        # Eval lane-SEQUENTIALLY at width 1 (lax.map over the lane
        # axis), not as one width-K vmap: XLA's batched eval reduction
        # at width K rounds the loss sum differently from the width-1
        # program the per-submesh reference members run (last-ulp,
        # measured at the flagship size on a sharded submesh), and the
        # fused-vs-reference bit-parity contract pins the reference's
        # arithmetic. Eval is a small fraction of a generation's FLOPs
        # (E forward passes vs S forward+backward+update), so the
        # sequential map costs little; the train scan stays width-K.
        def eval_one(args):
            p1, b1 = args
            return _scan_eval_sums(
                veval, p1, b1, eval_batches, eval_weights
            )[0]

        eval_sums = jax.lax.map(
            eval_one,
            (
                jax.tree.map(lambda x: x[:, None], state.params),
                hypers.beta[:, None],
            ),
        )

        state, hypers_out, stats = pbt_exchange(
            state,
            hypers,
            eval_sums,
            gen,
            explore_key,
            n_exploit=n_exploit,
            perturb_factors=perturb_factors,
            lr_min=lr_min,
            lr_max=lr_max,
        )
        stats["train_loss_sum"] = train_losses
        stats["eval_loss_sum"] = eval_sums
        return state, hypers_out, stats

    return jax.jit(
        gen_fn,
        in_shardings=(
            repl, repl, batches_sh, eval_sh, eval_sh, repl, repl, repl,
            repl,
        ),
        out_shardings=(repl, repl, repl),
        donate_argnums=(0, 1),
    )


def wrap_step_with_hooks(
    step_fn: Callable,
    *,
    before: Optional[Callable] = None,
    transform_batch: Optional[Callable] = None,
    batch_argnum: int = 1,
) -> Callable:
    """Host-side hook seam around a compiled step — the fault-injection
    thread-through point (``faults/inject.py`` via ``hpo/driver.py``),
    usable for any pre-dispatch instrumentation.

    ``before(batch)`` runs before the dispatch (it may raise — an
    injected crash/preemption — or stall — an injected straggler);
    ``transform_batch(batch) -> batch`` may replace the batch operand
    (NaN poisoning for divergence drills). Both see the positional
    argument at ``batch_argnum``. The compiled program itself is
    untouched: hooks never change shapes, so nothing recompiles, and a
    ``None``-hook wrap is exactly the bare step.
    """
    if before is None and transform_batch is None:
        return step_fn

    def hooked(*args, **kwargs):
        args = list(args)
        batch = args[batch_argnum]
        if before is not None:
            before(batch)
        if transform_batch is not None:
            args[batch_argnum] = transform_batch(batch)
        return step_fn(*args, **kwargs)

    # Keep the compiled function reachable through the wrapper: the
    # device cost books (telemetry/device.py) need ``.lower()`` on the
    # underlying jit fn to run XLA's cost analysis on the program that
    # actually dispatches.
    hooked.__wrapped__ = step_fn
    return hooked


def make_eval_step(
    trial: TrialMesh,
    model: VAE,
    *,
    beta: float = 1.0,
    with_recon: bool = True,
    masked: bool = False,
    sampled: bool = False,
    shardings: Any = None,
) -> Callable[..., dict]:
    """Compiled eval step: summed ELBO (+ reconstructions) for one batch.

    The analog of the reference's ``test`` inner loop
    (``vae-hpo.py:101-105``) minus the host-side PNG I/O; with
    ``with_recon=True`` reconstruction probabilities are returned so the
    caller can image them (``vae-hpo.py:106-116``). Loss-only callers
    (e.g. PBT scoring) pass ``with_recon=False`` to skip materializing
    the (N, input_dim) output.

    ``masked=True`` returns ``eval_fn(state, batch, weights)`` whose
    ``loss_sum`` is the weight-vector masked sum — the static-shape way
    to evaluate a test set that doesn't divide the batch size: the final
    partial batch arrives zero-padded with 0.0 weights
    (``data.sampler.EvalDataIterator``) and contributes exactly its real
    rows, so reported test losses cover every row, like the reference's.

    ``sampled=True`` appends an ``rng`` argument and evaluates the
    reference's exact semantics — the full sampled forward, z drawn from
    the posterior (``vae-hpo.py:101-105`` calls ``model(data)``, which
    reparameterizes, ``vae-hpo.py:42-45``) — for apples-to-apples test
    losses against the reference. Default stays the posterior mean:
    deterministic, and a strictly tighter bound.
    """
    from multidisttorch_tpu.ops.losses import elbo_loss_weighted_sum

    repl = trial.replicated_sharding
    data = trial.batch_sharding
    # ``shardings`` (a TrainState of NamedShardings) pins a
    # weight-sharded state's layout on entry, same as the train steps —
    # without it a TP/EP state would be gathered to replicated per call.
    state_sh = repl if shardings is None else shardings

    def eval_core(state: TrainState, batch: jax.Array, weights, rng=None):
        n = batch.shape[0]
        flat = batch.reshape(n, -1)
        if sampled:
            recon_logits, mu, logvar = model.apply(
                {"params": state.params}, batch, rngs={"reparam": rng}
            )
        else:
            mu, logvar = model.apply(
                {"params": state.params}, batch, method="encode"
            )
            recon_logits = model.apply(
                {"params": state.params}, mu, method="decode"
            )
        if weights is None:
            loss = elbo_loss_sum(recon_logits, flat, mu, logvar, beta)
        else:
            loss = elbo_loss_weighted_sum(
                recon_logits, flat, mu, logvar, weights, beta
            )
        out = {"loss_sum": loss.astype(jnp.float32)}
        if with_recon:
            out["recon"] = jax.nn.sigmoid(recon_logits.astype(jnp.float32))
        return out

    if masked and sampled:
        return jax.jit(
            eval_core,
            in_shardings=(state_sh, data, data, repl),
            out_shardings=repl,
        )
    if masked:
        def eval_masked(state: TrainState, batch: jax.Array, weights):
            return eval_core(state, batch, weights)

        return jax.jit(
            eval_masked,
            in_shardings=(state_sh, data, data),
            out_shardings=repl,
        )
    if sampled:
        def eval_sampled_fn(state: TrainState, batch: jax.Array, rng):
            return eval_core(state, batch, None, rng)

        return jax.jit(
            eval_sampled_fn,
            in_shardings=(state_sh, data, repl),
            out_shardings=repl,
        )

    def eval_fn(state: TrainState, batch: jax.Array):
        return eval_core(state, batch, None)

    return jax.jit(eval_fn, in_shardings=(state_sh, data), out_shardings=repl)


def make_sample_step(
    trial: TrialMesh,
    model: VAE,
    num_samples: int = 64,
    *,
    shardings: Any = None,
) -> Callable[[TrainState, jax.Array], jax.Array]:
    """Compiled prior-sampling step: ``randn(n, latent) → decode``.

    Mirrors the reference's per-epoch sample dump
    (``vae-hpo.py:163-170``), returning pixel probabilities for imaging.
    ``shardings`` pins a weight-sharded state's layout on entry.
    """
    repl = trial.replicated_sharding
    state_sh = repl if shardings is None else shardings

    def sample_fn(state: TrainState, rng: jax.Array):
        z = jax.random.normal(rng, (num_samples, model.latent_dim))
        probs = model.apply(
            {"params": state.params}, z, method="decode_probs"
        )
        return probs.astype(jnp.float32)

    return jax.jit(
        sample_fn, in_shardings=(state_sh, repl), out_shardings=repl
    )
