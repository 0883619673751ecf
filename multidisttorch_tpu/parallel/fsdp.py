"""ZeRO/FSDP-style parameter+optimizer sharding over the data axis.

The reference replicates the full model and optimizer on every rank
(plain DDP, ``/root/reference/vae-hpo.py:130-131`` — SURVEY.md §2c lists
ZeRO/FSDP as absent). On TPU the capability costs almost nothing to add
the XLA way: annotate each parameter leaf with a ``NamedSharding`` that
splits its largest divisible axis over the submesh's ``data`` axis, and
GSPMD inserts the all-gathers before use and reduce-scatters after the
gradient — the ZeRO-3 execution pattern — while the Adam moments
(eagerly initialized, computation-follows-data) inherit the same shards,
cutting state memory by the data-axis extent. No wrapper class, no
hooks: the sharding *is* the feature.

Composes with the rest of the framework unchanged: the sharded state
threads through ``make_train_step(..., shardings=state_shardings(state))``
exactly like a tensor-parallel state does.
"""

from __future__ import annotations

from typing import Any

import jax

from multidisttorch_tpu.parallel.mesh import DATA_AXIS, TrialMesh


def fsdp_param_shardings(
    trial: TrialMesh, params: Any, *, min_size: int = 1024
) -> Any:
    """Per-leaf shardings splitting each parameter over the data axis.

    For every leaf, shard the largest axis divisible by the submesh's
    data extent; leaves smaller than ``min_size`` elements (biases,
    norm scales — where a shard would be less than one lane tile and
    the gather latency outweighs the memory) stay replicated.

    Returns a pytree of ``NamedSharding`` matching ``params`` — pass to
    ``create_train_state(..., param_shardings=...)`` /
    ``create_classifier_state``.

    Implemented as the composition rule over an all-replicated base, so
    the 1-D and layered (ZeRO-over-TP) paths share ONE dim-selection
    rule and cannot drift.
    """
    repl = trial.sharding()
    return fsdp_compose_shardings(
        trial, params, jax.tree.map(lambda _: repl, params),
        min_size=min_size,
    )


def fsdp_compose_shardings(
    trial: TrialMesh, params: Any, base_shardings: Any, *,
    min_size: int = 1024,
) -> Any:
    """Layer ZeRO data-axis sharding on top of an existing sharding tree.

    The Megatron + ZeRO-3 composition: ``base_shardings`` (typically a
    tensor-parallel tree like ``vae_tp_shardings`` /
    ``transformer_tp_shardings``) says which dims ride the ``model``
    axis; this adds ``data``-axis sharding on the largest
    data-divisible dim each base spec leaves unsharded, so parameters
    and Adam moments split over BOTH axes of a 2-D submesh. Leaves the
    base untouched where it already covers every dim, where the leaf is
    small (< ``min_size`` elements), or where no free dim divides the
    data extent. GSPMD turns the annotations into the all-gather /
    reduce-scatter schedule exactly as in the 1-D case.
    """
    n = trial.data_size

    def rule(leaf, base):
        if leaf.size < min_size:
            return base
        spec = list(base.spec) + [None] * (leaf.ndim - len(base.spec))
        free = [
            (dim, i) for i, dim in enumerate(leaf.shape)
            if spec[i] is None and dim % n == 0
        ]
        if not free:
            return base
        _, axis = max(free)
        spec[axis] = DATA_AXIS
        return trial.sharding(*spec)

    return jax.tree.map(rule, params, base_shardings)


# --- ZeRO-style sharded weight update (optimizer-state sharding) ----
#
# The functions above shard the PARAMETERS (ZeRO-3: all-gather weights
# before use). The sharded-update mode below is the ZeRO-1/2 point in
# the trade space (arXiv 2004.13336): parameters stay replicated — the
# forward/backward is the plain DDP program, bit-compatible with the
# replicated reference — but the Adam moments are partitioned over the
# data axis, so each device updates only the shard of the state it
# owns. Under GSPMD the annotation IS the protocol: with moments
# pinned data-sharded and params pinned replicated in the step's
# out_shardings, XLA reduce-scatters the gradient into the moment
# update and all-gathers the fresh parameters after `apply_updates` —
# the canonical reduce-scatter → shard-update → all-gather schedule,
# with per-device optimizer memory cut to ~1/n_data of replicated.
# Selected per-TrialConfig (`zero_update=True`, hpo/driver.py); losses
# match the replicated reference within a pinned tolerance (the grad
# reduction reassociates across devices — tests/test_pipeline_mpmd.py).


def zero_update_shardings(
    trial: TrialMesh, state: Any, *, min_size: int = 1024
) -> Any:
    """Sharding tree for the sharded-update TrainState variant:
    ``params``/``step`` replicated, each ``opt_state`` leaf split over
    the data axis by :func:`fsdp_param_shardings`'s dim-selection rule
    — ONE rule for the parameter path (ZeRO-3 annotations) and the
    optimizer-state path, so the two cannot drift on which leaves
    shard (leaves smaller than ``min_size`` elements — Adam's count
    scalar, bias moments — stay replicated; the gather would cost more
    than the bytes).

    Returns a pytree of ``NamedSharding`` with ``state``'s structure —
    pass to ``make_train_step(..., shardings=...)`` to pin the layout
    across steps, and to checkpoint restore so a resumed state lands
    sharded."""
    repl = trial.sharding()
    return state.replace(
        params=jax.tree.map(lambda _: repl, state.params),
        opt_state=fsdp_param_shardings(
            trial, state.opt_state, min_size=min_size
        ),
        step=repl,
    )


def place_zero_state(
    trial: TrialMesh, state: Any, *, min_size: int = 1024
) -> tuple[Any, Any]:
    """Place a (host or replicated) TrainState in sharded-update form:
    ``(state, shardings)`` with the optimizer leaves physically split
    over the submesh's data axis. Multi-controller safe via
    ``TrialMesh.device_put`` (each process materializes only its
    addressable shards)."""
    sh = zero_update_shardings(trial, state, min_size=min_size)
    if jax.process_count() == 1:
        return jax.device_put(state, sh), sh
    return trial.device_put(state, sh), sh


def describe_shardings(shardings: Any) -> dict:
    """Flatten a shardings pytree into ``{leaf-key: spec-string}`` —
    the checkpoint manifest's layout record (docs/RESILIENCE.md
    "Checkpoint format v2"): the on-disk format names the
    ``NamedSharding`` layout the state trained under, so a reader (or
    a restore-parity check) can see which leaves the runtime sharded
    without reconstructing the mesh. The same flattening rule as the
    manifest builder's, so keys line up with manifest leaf keys."""
    from flax import serialization

    from multidisttorch_tpu.train.ckpt_store import _flatten_state_dict

    out: dict[str, str] = {}
    for key, sh in _flatten_state_dict(
        serialization.to_state_dict(shardings)
    ):
        spec = getattr(sh, "spec", None)
        if spec is not None:
            out[key] = str(spec)
    return out


def optimizer_state_bytes(state: Any) -> dict:
    """Analytic optimizer-memory book from a placed TrainState:
    ``per_device_bytes`` (what one chip actually holds, from each opt
    leaf's concrete sharding) and ``total_bytes`` (the replicated-
    equivalent footprint — what the same state costs per device with
    no sharding). The ratio is the ZeRO win the memory books surface
    (asserted in ``tests/test_pipeline_mpmd.py``); works on CPU where
    ``memory_stats()`` does not exist."""
    import math

    per_dev = 0
    total = 0
    for leaf in jax.tree.leaves(state.opt_state):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is None or dtype is None:
            continue
        nbytes = int(size) * dtype.itemsize
        total += nbytes
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            shard = sharding.shard_shape(tuple(leaf.shape))
            per_dev += int(math.prod(shard)) * dtype.itemsize
        else:
            per_dev += nbytes
    return {"per_device_bytes": per_dev, "total_bytes": total}
