"""multidisttorch_tpu — a TPU-native (JAX/XLA/pjit) framework with the
capabilities of ORNL/MultiDistTorch.

The reference framework (``/root/reference``) carves one
torch.distributed job into N process subgroups and runs an independent
DDP training trial in each (``utils.py:146-163``, ``vae-hpo.py:177-202``).
This package is the ground-up TPU rebuild: one global ``jax.sharding.Mesh``
is carved into N disjoint submeshes (pure metadata — no collective
handshake, no rendezvous server, no NIC pinning), each trial runs a
jit-compiled data-parallel train step on its own submesh, and a host-side
HPO driver dispatches trials concurrently with no cross-trial barriers.

Public API (mirrors the reference's ``from utils import *`` surface,
``utils.py:9-174``, re-designed for JAX):

- cluster/runtime bring-up: :func:`initialize_runtime`,
  :func:`detect_process_env`, :func:`parse_slurm_nodelist`,
  :func:`coordinator_address`, :func:`find_ifname`
- size/rank queries: :func:`process_world`, :func:`device_world`
- group carving: :func:`setup_groups`, :class:`TrialMesh`,
  :func:`global_mesh`
- group-scoped collectives: :func:`group_all_gather`, :func:`group_psum`,
  :func:`group_pmean`
- group-aware logging: :func:`log0`
"""

from multidisttorch_tpu.parallel.cluster import (
    ProcessEnv,
    coordinator_address,
    detect_process_env,
    find_ifname,
    initialize_runtime,
    parse_slurm_nodelist,
    process_world,
    sync_hosts,
)
from multidisttorch_tpu.parallel.mesh import (
    TrialMesh,
    device_world,
    global_mesh,
    setup_groups,
)
from multidisttorch_tpu.parallel.collectives import (
    group_all_gather,
    group_pmean,
    group_psum,
)
from multidisttorch_tpu.utils.logging import log0

__version__ = "0.1.0"

__all__ = [
    "ProcessEnv",
    "TrialMesh",
    "coordinator_address",
    "detect_process_env",
    "device_world",
    "find_ifname",
    "global_mesh",
    "group_all_gather",
    "group_pmean",
    "group_psum",
    "initialize_runtime",
    "log0",
    "parse_slurm_nodelist",
    "process_world",
    "setup_groups",
    "sync_hosts",
]
