"""Device-group carving: one global mesh → N disjoint trial submeshes.

This is the TPU-native rebuild of the reference's core capability,
``setup_ddp_groups`` (``/root/reference/utils.py:146-163``): partition
the world into N equal contiguous groups, each a first-class
communicator. In torch.distributed that requires a world-collective
``dist.new_group`` handshake per group, executed on *every* rank
(``utils.py:155-157``; the commented-out broken member-only variant at
``example-subgroup.py:10-19`` is the reference's own lesson). In JAX a
sub-communicator is pure host-side metadata: a ``jax.sharding.Mesh``
built over a slice of ``jax.devices()``. Creation involves no
cross-process event; XLA materializes the actual ICI/DCN collectives at
compile time from shardings referencing the submesh.

Deliberate fixes over the reference (SURVEY.md §2d):

- Q5: a world that doesn't divide evenly by ``num_groups`` raises
  immediately instead of silently orphaning trailing ranks (which hangs
  the reference's world-scoped barriers).
- Q2's API shape is preserved — every process gets handles to *all*
  groups and tests membership per group — but the collective-creation
  constraint disappears.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Axis name used for the data-parallel dimension of every trial submesh.
DATA_AXIS = "data"
# Axis name for the optional model/tensor-parallel dimension (2-D submeshes).
MODEL_AXIS = "model"
# Axis name for the optional pipeline-stage dimension (parallel/pipeline.py).
PIPE_AXIS = "pipe"


def device_world(devices: Optional[Sequence[jax.Device]] = None) -> tuple[int, int]:
    """``(num_devices, first_local_device_index)`` over the global device list.

    The reference's "world" is processes (one GPU per rank); the TPU
    analog of a rank is a device. Returns the global device count and the
    index of this process's first addressable device (0 in
    single-controller mode).
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    local = [i for i, d in enumerate(devs) if d.process_index == jax.process_index()]
    return len(devs), (local[0] if local else -1)


def global_mesh(
    devices: Optional[Sequence[jax.Device]] = None, axis: str = DATA_AXIS
) -> Mesh:
    """Build the 1-D global mesh over all devices (axis name ``axis``)."""
    devs = np.array(list(jax.devices()) if devices is None else list(devices))
    return Mesh(devs, (axis,))


def placement(x) -> Optional[tuple[str, int]]:
    """``(device_kind, num_devices)`` of the mesh ``x`` was placed on, as
    tracing sees it: a jitted function's values carry the abstract mesh
    of its committed ``NamedSharding`` arguments (every state
    ``create_lm_state`` makes and every batch a :class:`TrialMesh`
    places). ``None`` where there is none to see: an uncommitted or
    single-device array, shapes alone, an array made inside the trace
    or by a kernel. The one read of where a computation runs: every op
    that picks between a kernel and its plain form, the remat rules that
    keep more on one chip and the LM step's head walk ask it here, by
    the module attribute, so that a test can tell them all at once that
    the CPU devices are a TPU."""
    mesh = jax.typeof(x).sharding.mesh
    return None if mesh.empty else (mesh.abstract_device.device_kind, mesh.size)


def on_one_tpu_chip(x) -> bool:
    """Whether ``x`` lies on one TPU device, as :func:`placement` sees it."""
    placed = placement(x)
    return bool(placed) and placed[0].startswith("TPU") and placed[1] == 1


@dataclass(frozen=True)
class TrialMesh:
    """One carved device group — the analog of a torch process subgroup.

    Wraps a disjoint contiguous slice of the global device list as a 1-D
    ``Mesh`` with a ``data`` axis, plus the membership/rank queries the
    reference exposes on group handles (``dist.get_rank(group)``,
    ``utils.py:160``; ``dist.get_world_size(group)``, ``vae-hpo.py:126``).
    """

    group_id: int
    mesh: Mesh
    global_ranks: tuple[int, ...]  # indices into the global device list

    def __post_init__(self):
        from multidisttorch_tpu.utils.compile_cache import guard_submesh

        guard_submesh(self.devices)

    @property
    def devices(self) -> tuple[jax.Device, ...]:
        return tuple(self.mesh.devices.ravel().tolist())

    @property
    def size(self) -> int:
        """Device count in this group (``dist.get_world_size(group)``)."""
        return int(self.mesh.devices.size)

    @property
    def data_size(self) -> int:
        """Extent of the data-parallel axis (== ``size`` on 1-D groups)."""
        return int(self.mesh.shape[DATA_AXIS])

    @property
    def model_size(self) -> int:
        """Extent of the model-parallel axis (1 on 1-D groups)."""
        return int(dict(self.mesh.shape).get(MODEL_AXIS, 1))

    @property
    def pipe_size(self) -> int:
        """Extent of the pipeline-stage axis (1 unless carved with
        ``pipeline_parallel > 1``)."""
        return int(dict(self.mesh.shape).get(PIPE_AXIS, 1))

    @property
    def is_local_member(self) -> bool:
        """Whether this process owns any device of the group.

        The analog of the reference's membership test
        ``dist.get_rank(group) >= 0`` (``vae-hpo.py:201``): in
        multi-controller SPMD, a process participates in a trial iff it
        has addressable devices in the trial's submesh.
        """
        pid = jax.process_index()
        return any(d.process_index == pid for d in self.devices)

    @property
    def local_rank(self) -> int:
        """Group-rank of this process's first device in the group, or -1.

        Mirrors ``dist.get_rank(group)`` returning -1 for non-members.
        """
        pid = jax.process_index()
        for i, d in enumerate(self.devices):
            if d.process_index == pid:
                return i
        return -1

    def rank_of(self, device: jax.Device) -> int:
        """Group-rank of ``device``, or -1 if it is not a member."""
        for i, d in enumerate(self.devices):
            if d == device:
                return i
        return -1

    @property
    def owner_processes(self) -> frozenset[int]:
        """Process indices owning at least one device of this group —
        global device metadata, so every process computes the same set."""
        return frozenset(d.process_index for d in self.devices)

    @property
    def spans_processes(self) -> bool:
        """Whether this group's devices live on more than one process
        (when True, per-trial failure handling needs the cross-process
        agreement in ``collectives.group_all_ok``)."""
        return len(self.owner_processes) > 1

    # --- shardings: the pjit-native face of "this group's communicator" ---

    @property
    def batch_sharding(self) -> NamedSharding:
        """Shard dim 0 over the group's data axis (true within-trial DP —
        fixes quirk Q1, where the reference fed every rank of a group the
        identical shard, ``vae-hpo.py:146``)."""
        return NamedSharding(self.mesh, P(DATA_AXIS))

    @property
    def replicated_sharding(self) -> NamedSharding:
        """Replicate across the group (model/optimizer state, DDP-style)."""
        return NamedSharding(self.mesh, P())

    def sharding(self, *spec) -> NamedSharding:
        """Arbitrary ``PartitionSpec`` over this group's mesh axes —
        e.g. ``trial.sharding(None, MODEL_AXIS)`` for a column-sharded
        weight on a 2-D (data × model) submesh."""
        return NamedSharding(self.mesh, P(*spec))

    @property
    def is_writer_process(self) -> bool:
        """Whether this process is the group's designated artifact writer
        (the owner of the group's first device). Exactly one process per
        group: the multi-controller guard that keeps images, checkpoints,
        and metrics written once per trial instead of once per owner
        process (the reference's every-rank-writes behavior is quirk Q4's
        second half, ``vae-hpo.py:156-158``)."""
        return self.devices[0].process_index == jax.process_index()

    def device_put(self, tree, sharding: Optional[NamedSharding] = None):
        """Place a host pytree onto this group's devices (replicated by
        default).

        Multi-controller safe: when the submesh spans processes (or this
        process owns none of it), placement goes through
        ``make_array_from_callback`` so each process materializes only
        its addressable shards — every process must call this with the
        same values (host-side determinism), the same contract as the
        data path (``data/sampler.py``)."""
        sh = self.replicated_sharding if sharding is None else sharding
        if jax.process_count() == 1:
            return jax.device_put(tree, sh)

        def put_leaf(x, leaf_sh):
            dt = getattr(x, "dtype", None)
            if dt is not None and jax.dtypes.issubdtype(
                dt, jax.dtypes.prng_key
            ):
                # Typed PRNG keys (PBT base_rngs, the explore key)
                # cannot round-trip through np.asarray: place the raw
                # uint32 key data and rewrap. Keys only ever place
                # replicated here, and a replicated spec is
                # rank-agnostic, so the same sharding serves the key
                # data's extra trailing dim.
                impl = jax.random.key_impl(x)
                data = np.asarray(jax.random.key_data(x))
                placed = jax.make_array_from_callback(
                    data.shape, leaf_sh, lambda idx: data[idx]
                )
                return jax.random.wrap_key_data(placed, impl=impl)
            x = np.asarray(x)
            return jax.make_array_from_callback(
                x.shape, leaf_sh, lambda idx: x[idx]
            )

        if isinstance(sh, NamedSharding):
            return jax.tree.map(lambda x: put_leaf(x, sh), tree)
        return jax.tree.map(put_leaf, tree, sh)

    def __repr__(self) -> str:  # keep dataclass-frozen hash/eq, short repr
        return (
            f"TrialMesh(group_id={self.group_id}, size={self.size}, "
            f"global_ranks={self.global_ranks})"
        )


def setup_groups(
    num_groups: int,
    devices: Optional[Sequence[jax.Device]] = None,
    *,
    allow_uneven: bool = False,
    model_parallel: int = 1,
    pipeline_parallel: int = 1,
) -> list[TrialMesh]:
    """Carve the device world into ``num_groups`` contiguous disjoint groups.

    API mirror of ``setup_ddp_groups`` (``/root/reference/
    utils.py:146-163``): contiguous rank blocks ``[g*k .. g*k+k-1]``,
    every process receives handles to all groups. Differences:

    - creation is metadata-only (no world-collective ``new_group``
      handshake — quirk Q2 evaporates);
    - a non-divisible world raises ``ValueError`` unless
      ``allow_uneven=True`` explicitly opts into dropping the remainder
      devices (the reference silently orphans them and then hangs on its
      world barriers — quirk Q5);
    - ``model_parallel=m`` makes each group a 2-D ``(data, model)``
      submesh of shape ``(k/m, m)`` for within-trial tensor parallelism
      (beyond the reference, which is DP-only — SURVEY.md §2c). The
      model axis occupies the *fastest-varying* device positions so TP
      collectives ride adjacent ICI links.
    - ``pipeline_parallel=p`` adds a ``pipe`` axis (see
      ``parallel/pipeline.py``) between ``data`` and ``model``:
      each group becomes a ``(k/(p*m), p[, m])`` grid. Pipe-axis
      neighbors are ``m`` device positions apart — adjacent when
      ``m == 1`` — so GPipe's stage-to-stage ppermute hops stay on
      short ICI paths.

    Timed into the process's compile log as the span
    ``admit:setup_groups`` (``utils/profiling.span``); with
    ``devices=None`` in a process that has not asked for its devices
    yet, that is the backend's start too.
    """
    # utils/__init__ imports this module (through utils/logging).
    from multidisttorch_tpu.utils.profiling import SPAN_SETUP_GROUPS, span

    with span(SPAN_SETUP_GROUPS):
        return _carve_groups(
            num_groups,
            devices,
            allow_uneven=allow_uneven,
            model_parallel=model_parallel,
            pipeline_parallel=pipeline_parallel,
        )


def _carve_groups(
    num_groups, devices, *, allow_uneven, model_parallel, pipeline_parallel
) -> list[TrialMesh]:
    devs = list(jax.devices()) if devices is None else list(devices)
    world = len(devs)
    if num_groups < 1:
        raise ValueError(f"num_groups must be >= 1, got {num_groups}")
    if world < num_groups:
        raise ValueError(
            f"Number of groups {num_groups} requested exceeds number of "
            f"total devices {world} available"
        )
    per_group, remainder = divmod(world, num_groups)
    if remainder and not allow_uneven:
        raise ValueError(
            f"World of {world} devices does not divide into {num_groups} "
            f"groups ({remainder} devices would be orphaned, which in the "
            "reference design hangs the job — SURVEY.md Q5). Pass "
            "allow_uneven=True to deliberately drop the remainder."
        )

    if model_parallel < 1:
        raise ValueError(f"model_parallel must be >= 1, got {model_parallel}")
    if pipeline_parallel < 1:
        raise ValueError(
            f"pipeline_parallel must be >= 1, got {pipeline_parallel}"
        )
    inner = model_parallel * pipeline_parallel
    if per_group % inner:
        raise ValueError(
            f"group size {per_group} does not divide into pipeline_parallel="
            f"{pipeline_parallel} x model_parallel={model_parallel} (each "
            "group needs a full (data, pipe, model) grid)"
        )

    # Axis layout: model fastest-varying (adjacent ICI for TP
    # collectives), then pipe, then data. Size-1 pipe/model axes are
    # dropped so the default carve stays the 1-D (data,) mesh.
    dims = [
        (DATA_AXIS, per_group // inner),
        (PIPE_AXIS, pipeline_parallel),
        (MODEL_AXIS, model_parallel),
    ]
    kept = [(name, n) for name, n in dims if n > 1 or name == DATA_AXIS]

    groups = []
    for g in range(num_groups):
        ranks = tuple(range(g * per_group, (g + 1) * per_group))
        grid = np.array([devs[r] for r in ranks])
        submesh = Mesh(
            grid.reshape(tuple(n for _, n in kept)),
            tuple(name for name, _ in kept),
        )
        groups.append(TrialMesh(group_id=g, mesh=submesh, global_ranks=ranks))
    return groups
