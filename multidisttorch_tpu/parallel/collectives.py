"""Group-scoped collectives over trial submeshes.

The reference reaches collectives through torch.distributed with a
``group=`` handle: ``dist.all_gather(..., group=subgroup)``
(``/root/reference/example-subgroup.py:27,32``) and DDP's implicit
bucketed gradient all-reduce (``vae-hpo.py:130``). The TPU-native form:
``jax.shard_map`` over the submesh's ``data`` axis, with
``jax.lax.all_gather`` / ``psum`` / ``pmean`` compiled by XLA onto ICI.
Two groups' collectives touch disjoint devices, so they proceed
concurrently and independently — same contract as the reference's two
concurrent subgroup gathers, with no NCCL communicator setup.

In most training code you will not call these directly: replicate params
and shard the batch with ``TrialMesh.{replicated,batch}_sharding`` and
XLA inserts the gradient reduction itself (the pjit analog of DDP).
These wrappers exist for explicit collective programming and for parity
with the reference's demo (`example-subgroup.py`).

Compiled executables are cached per (mesh, op) so repeated calls on a
hot path (e.g. a per-step psum) trace and compile exactly once.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from multidisttorch_tpu.parallel.mesh import DATA_AXIS, TrialMesh


def pvary(x, axis_names):
    """Annotate ``x`` as device-varying over ``axis_names`` under
    ``shard_map``'s varying-axis (VMA) typing.

    Needed when a loop carry starts as a mesh-invariant constant but
    becomes device-varying through the body (ppermute, axis_index, shard
    data) — the initial carry must already hold the annotation.
    """
    return jax.lax.pcast(x, axis_names, to="varying")


@lru_cache(maxsize=None)
def _gather_fn(mesh: Mesh):
    # check_vma=False: the gathered result is device-invariant by
    # construction, but shard_map's varying-axis inference cannot prove
    # replication through all_gather.
    return jax.jit(
        jax.shard_map(
            lambda s: jax.lax.all_gather(s, DATA_AXIS, axis=0, tiled=True),
            mesh=mesh,
            in_specs=P(DATA_AXIS),
            out_specs=P(),
            check_vma=False,
        )
    )


@lru_cache(maxsize=None)
def _reduce_fn(mesh: Mesh, op: str):
    reducer = {"psum": jax.lax.psum, "pmean": jax.lax.pmean}[op]
    # Each member device contributes one row of x; squeeze the per-device
    # shard's leading dim so the reduced result has shape x.shape[1:].
    return jax.jit(
        jax.shard_map(
            lambda s: reducer(jnp.squeeze(s, axis=0), DATA_AXIS),
            mesh=mesh,
            in_specs=P(DATA_AXIS),
            out_specs=P(),
        )
    )


def group_all_gather(trial: TrialMesh, x):
    """All-gather per-device shards within one trial group.

    ``x`` has leading dim == group size (one row per member device, the
    analog of each rank contributing one tensor). Returns the gathered
    array, identical on (replicated across) every member device —
    matching ``dist.all_gather``'s every-rank-gets-all contract
    (``example-subgroup.py:25-33``).
    """
    return _gather_fn(trial.mesh)(x)


def group_psum(trial: TrialMesh, x):
    """Sum per-device shards (leading dim == group size) across the group.

    The explicit form of DDP's gradient all-reduce scoped to a subgroup
    (``vae-hpo.py:130``). Every member device holds the full sum.
    """
    return _reduce_fn(trial.mesh, "psum")(x)


def group_pmean(trial: TrialMesh, x):
    """Mean per-device shards across the group (DDP gradient averaging)."""
    return _reduce_fn(trial.mesh, "pmean")(x)


@lru_cache(maxsize=None)
def _sum_flags_fn(mesh: Mesh):
    from jax.sharding import NamedSharding

    return jax.jit(
        jnp.sum, out_shardings=NamedSharding(mesh, P())
    )


def group_all_ok(
    trial: TrialMesh,
    ok: bool,
    *,
    timeout_s: float | None = None,
    what: str = "group health agreement",
    error_cls: type | None = None,
) -> bool:
    """Cross-process health agreement scoped to ONE trial submesh.

    Returns True iff every process owning a device of this group called
    with ``ok=True``. The TPU-native failure-detection primitive: the
    health bit rides the same submesh the trial runs on — one tiny SPMD
    reduction over the group's devices, touching only the group's owner
    processes. No world-scoped barrier, so unrelated trials stay
    decoupled (quirk Q3 stays fixed; contrast the reference, where a
    failed rank simply hangs the world's collectives — SURVEY.md §5
    "failure detection").

    Collective contract: every owner process must call this at the same
    point in its dispatch sequence for this group (the HPO driver calls
    it at trial setup and at each epoch boundary — deterministic
    cadence).

    ``timeout_s`` bounds the wait on the reduction's result fetch: an
    owner process that died before contributing leaves the collective
    blocked forever — with a deadline it becomes a ``TimeoutError``
    naming ``what`` (``parallel.cluster.call_with_timeout`` semantics:
    the stuck collective is abandoned on a daemon thread; the caller
    should treat the group as lost and restart against the sweep
    ledger). ``None``/0 = unbounded, the pre-timeout behavior.
    ``error_cls`` names the raised type on expiry (default
    ``AgreementTimeout``; the HPO driver's device-sync points pass
    ``cluster.WedgedCollective`` for the exit-code contract).
    """
    import time

    import numpy as np

    from multidisttorch_tpu.parallel.cluster import (
        AgreementTimeout,
        call_with_timeout,
    )
    from multidisttorch_tpu.telemetry.events import get_bus

    if error_cls is None:
        error_cls = AgreementTimeout

    def agree() -> bool:
        n = trial.size
        # One element per member device, each process filling its
        # addressable shards with its own health bit.
        sharding = trial.sharding(tuple(trial.mesh.axis_names))
        local = np.zeros(1, np.float32) if ok else np.ones(1, np.float32)
        if jax.process_count() == 1:
            flags = jax.device_put(
                np.full(n, local[0], np.float32), sharding
            )
        else:
            flags = jax.make_array_from_callback(
                (n,), sharding, lambda idx: local
            )
        failed = _sum_flags_fn(trial.mesh)(flags)
        return float(failed) == 0.0

    bus = get_bus()
    if bus is None:
        return call_with_timeout(agree, timeout_s, what, error_cls=error_cls)
    # Telemetry seam: agreement latency is the sweep's cross-process
    # sync cost — a slow peer shows up here long before it times out.
    t0 = time.perf_counter()
    try:
        agreed = call_with_timeout(
            agree, timeout_s, what, error_cls=error_cls
        )
    except BaseException as e:
        bus.emit(
            "agreement",
            group_id=trial.group_id,
            what=what,
            outcome=f"error: {type(e).__name__}",
            wall_s=round(time.perf_counter() - t0, 6),
        )
        raise
    bus.emit(
        "agreement",
        group_id=trial.group_id,
        what=what,
        outcome="agreed" if agreed else "peer_failure",
        local_ok=ok,
        wall_s=round(time.perf_counter() - t0, 6),
    )
    return agreed


@lru_cache(maxsize=None)
def _min_flags_fn(mesh: Mesh):
    from jax.sharding import NamedSharding

    return jax.jit(jnp.min, out_shardings=NamedSharding(mesh, P()))


def group_min_scalar(
    trial: TrialMesh,
    value: int,
    *,
    timeout_s: float | None = None,
    what: str = "group min agreement",
    error_cls: type | None = None,
) -> int:
    """Agree on the MINIMUM of a per-process integer across one trial
    submesh's owner processes — the on-mesh sibling of
    :func:`group_all_ok` for value (not just health) agreement.

    Note the RECOVERY path deliberately does not use this: the
    cross-host restore agreement (``train.checkpoint.
    agreed_restore_step``) rides the coordination-service sideband
    (``cluster.agree_min_int``) instead, because it must work when the
    device world is the broken thing — and on backends without
    cross-process XLA computations. This on-mesh form is for healthy
    in-band coordination (e.g. agreeing a shared schedule knob on ICI
    without touching the coordinator).

    Same collective contract, timeout semantics, and ``error_cls``
    behavior as :func:`group_all_ok` (one tiny submesh-scoped
    reduction; no world barrier).
    """
    import numpy as np

    from multidisttorch_tpu.parallel.cluster import (
        AgreementTimeout,
        call_with_timeout,
    )

    if error_cls is None:
        error_cls = AgreementTimeout

    def agree() -> int:
        n = trial.size
        sharding = trial.sharding(tuple(trial.mesh.axis_names))
        local = np.full(1, int(value), np.int32)
        if jax.process_count() == 1:
            flags = jax.device_put(np.full(n, local[0], np.int32), sharding)
        else:
            flags = jax.make_array_from_callback(
                (n,), sharding, lambda idx: local
            )
        return int(_min_flags_fn(trial.mesh)(flags))

    return call_with_timeout(agree, timeout_s, what, error_cls=error_cls)
