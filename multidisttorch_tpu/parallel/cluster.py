"""Cluster-environment detection and distributed-runtime bring-up.

TPU-native replacement for the reference's launcher/rendezvous layer
(``/root/reference/utils.py:9-144``). The reference must (a) learn its
world size/rank from MPI or SLURM env vars, (b) elect a rendezvous master
host, (c) pin a NIC for the Gloo transport, and (d) run a TCP rendezvous
via ``dist.init_process_group``. On TPU none of that machinery survives:
devices are addressed through ``jax.devices()``, and multi-host jobs need
only ``jax.distributed.initialize`` (which itself autodetects TPU
metadata). What *does* carry over is the launcher-env detection contract —
the same jobs the reference runs under (mpirun/jsrun on Summit-likes,
srun on SLURM clusters) must be recognized here, so every env-var
priority chain from the reference is preserved, with honest error
handling instead of the reference's dead ``except KeyError`` fallback
(``utils.py:141-142``, quirk Q8 in SURVEY.md).
"""

from __future__ import annotations

import os
import re
import socket
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class ProcessEnv:
    """Launcher-provided process coordinates, before runtime init.

    Mirrors the return contract of ``init_comm_size_and_rank``
    (``/root/reference/utils.py:9-26``): ``(1, 0)`` when no launcher env
    is present (sequential mode). ``source`` records which detector won.
    """

    num_processes: int
    process_id: int
    source: str  # "openmpi" | "slurm" | "tpu" | "jax" | "local"


def detect_process_env(environ: Optional[dict] = None) -> ProcessEnv:
    """Detect world size / rank from the launcher environment.

    Priority chain extends the reference's (``utils.py:13-24``):
    OpenMPI (Summit-style ``OMPI_COMM_WORLD_*``) → SLURM
    (``SLURM_NPROCS``/``SLURM_PROCID``) → Cloud TPU multi-host env
    (``TPU_WORKER_ID`` + ``TPU_WORKER_HOSTNAMES``) → generic JAX
    coordinates (``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``) → local
    single-process fallback ``(1, 0)``.
    """
    env = os.environ if environ is None else environ

    if env.get("OMPI_COMM_WORLD_SIZE") and env.get("OMPI_COMM_WORLD_RANK"):
        return ProcessEnv(
            int(env["OMPI_COMM_WORLD_SIZE"]),
            int(env["OMPI_COMM_WORLD_RANK"]),
            "openmpi",
        )
    if env.get("SLURM_NPROCS") and env.get("SLURM_PROCID"):
        return ProcessEnv(
            int(env["SLURM_NPROCS"]), int(env["SLURM_PROCID"]), "slurm"
        )
    if env.get("TPU_WORKER_ID") and env.get("TPU_WORKER_HOSTNAMES"):
        hostnames = [h for h in env["TPU_WORKER_HOSTNAMES"].split(",") if h]
        return ProcessEnv(len(hostnames), int(env["TPU_WORKER_ID"]), "tpu")
    if env.get("JAX_NUM_PROCESSES") and env.get("JAX_PROCESS_ID"):
        return ProcessEnv(
            int(env["JAX_NUM_PROCESSES"]), int(env["JAX_PROCESS_ID"]), "jax"
        )
    return ProcessEnv(1, 0, "local")


# Matches one hostlist block: a prefix optionally followed by a bracketed
# index group, e.g. "or-condo-g[05,07-08,13]" or a bare "or-condo-g04".
_BLOCK_RE = re.compile(r"([\w-]+(?:\[[\d,\-]+\])?)")
_BRACKET_RE = re.compile(r"^(?P<prefix>[\w\-]+)\[(?P<indices>[\d,\-]+)\]$")
_RANGE_RE = re.compile(r"^(\d+)-(\d+)$")


def parse_slurm_nodelist(nodelist: str) -> list[str]:
    """Expand a SLURM compressed nodelist into an explicit host list.

    Behavioral parity with ``/root/reference/utils.py:59-90`` (same
    accepted grammar, same zero-padding preservation): e.g.
    ``"or-condo-g[05,07-08,13],or-condo-h[01,12]"`` expands to
    ``["or-condo-g05", "or-condo-g07", "or-condo-g08", "or-condo-g13",
    "or-condo-h01", "or-condo-h12"]``. The first element is used as the
    coordinator host (the reference used it as the rendezvous master,
    ``utils.py:117-119``).
    """
    hosts: list[str] = []
    for block in _BLOCK_RE.findall(nodelist):
        m = _BRACKET_RE.match(block)
        if m is None:
            hosts.append(block)
            continue
        prefix = m.group("prefix")
        for piece in m.group("indices").split(","):
            rng = _RANGE_RE.match(piece)
            if rng is None:
                hosts.append(prefix + piece)
            else:
                lo, hi = rng.groups()
                width = len(lo)
                hosts.extend(
                    f"{prefix}{i:0{width}d}" for i in range(int(lo), int(hi) + 1)
                )
    return hosts


def coordinator_address(environ: Optional[dict] = None, port: Optional[int] = None) -> str:
    """Elect the coordinator host:port for ``jax.distributed.initialize``.

    Preserves the reference's master-address priority chain
    (``/root/reference/utils.py:108-119``): ``LSB_HOSTS`` token [1]
    (Summit jsrun) → ``LSB_MCPU_HOSTS`` token [2] → first host of the
    expanded ``SLURM_NODELIST`` → ``MASTER_ADDR`` env → ``127.0.0.1``.
    Port comes from the ``port`` argument, then ``MASTER_PORT``, then the
    reference's default 8889 (``utils.py:109``).
    """
    env = os.environ if environ is None else environ

    if env.get("LSB_HOSTS") is not None:
        host = env["LSB_HOSTS"].split()[1]
    elif env.get("LSB_MCPU_HOSTS") is not None:
        host = env["LSB_MCPU_HOSTS"].split()[2]
    elif env.get("SLURM_NODELIST"):
        nodes = parse_slurm_nodelist(env["SLURM_NODELIST"])
        if not nodes:
            raise ValueError(
                f"SLURM_NODELIST={env['SLURM_NODELIST']!r} parsed to an "
                "empty host list"
            )
        host = nodes[0]
    else:
        host = env.get("MASTER_ADDR", "127.0.0.1")

    resolved_port = port if port is not None else int(env.get("MASTER_PORT", "8889"))
    return f"{host}:{resolved_port}"


def find_ifname(address: str) -> Optional[str]:
    """Resolve an IP/hostname to the local NIC name carrying it.

    Parity helper for ``/root/reference/utils.py:40-56``. The reference
    needs this to pin Gloo's TCP transport to the right NIC
    (``GLOO_SOCKET_IFNAME``, ``utils.py:128-131``); a TPU runtime has no
    transport to pin (ICI/DCN routing is XLA's job), so this survives
    only as a diagnostics helper for debugging DCN/host networking.
    Returns ``None`` if no local NIC owns the address or psutil is
    unavailable.
    """
    try:
        import psutil
    except ImportError:
        return None
    try:
        ipaddr = socket.gethostbyname(address)
    except socket.gaierror:
        return None
    for nic, addrs in psutil.net_if_addrs().items():
        for addr in addrs:
            if addr.address == ipaddr:
                return nic
    return None


def select_platform(
    environ: Optional[dict] = None, default: Optional[str] = None
) -> Optional[str]:
    """Honor the ``MDT_PLATFORM`` backend override; returns it (or None).

    The operator's escape hatch, mirroring the reference's
    ``DDP_BACKEND`` env override (``/root/reference/utils.py:96-97``)
    which forces a torch backend ahead of autodetection. Here the
    analogous knob forces the JAX platform (``cpu``/``tpu``/a plugin
    name) *before* backend initialization — e.g. ``MDT_PLATFORM=cpu``
    keeps a job off a wedged TPU host entirely. An empty/unset var
    means "no override" (falls back to ``default``, usually None).

    Must be called before anything touches a JAX backend: raises an
    honest error — *without* mutating global config — if the backend
    already initialized to a different platform (``jax.config.update``
    silently ignores late changes, so pretending would mask the no-op).
    """
    env = os.environ if environ is None else environ
    platform = env.get("MDT_PLATFORM") or default
    if not platform:
        return None
    import jax

    # jax has no public "is a backend up yet?" query; the private table
    # exists on the installed jax (0.9.0, pinned in pyproject.toml).
    from jax._src import xla_bridge

    already_initialized = bool(xla_bridge._backends)
    if already_initialized:
        if jax.default_backend() != platform.split(",")[0]:
            raise RuntimeError(
                f"MDT_PLATFORM={platform!r} requested but the JAX backend "
                f"already initialized as {jax.default_backend()!r}; set "
                "the override before first device use"
            )
        return platform  # already effective; nothing to change
    jax.config.update("jax_platforms", platform)
    return platform


_initialized_env: Optional[ProcessEnv] = None


def initialize_runtime(
    coordinator: Optional[str] = None,
    environ: Optional[dict] = None,
) -> tuple[int, int]:
    """Bring up the distributed runtime; returns ``(num_processes, process_id)``.

    TPU-native replacement for ``setup_ddp`` (``/root/reference/
    utils.py:93-144``). Differences by design:

    - No backend selection: there is no NCCL/Gloo choice to make — XLA
      emits ICI/DCN collectives directly. (Reference: ``utils.py:96-103``.)
    - No env-var exports, no rendezvous server, no NIC pinning
      (reference: ``utils.py:122-131``): single-process jobs need nothing
      at all, multi-process jobs need one ``jax.distributed.initialize``
      call with the coordinator elected by :func:`coordinator_address`.
    - Honest errors (fixes quirk Q8, ``utils.py:141-142``): failures from
      ``jax.distributed.initialize`` propagate instead of being silently
      downgraded to "sequential mode".

    Safe to call more than once; subsequent calls return the cached
    coordinates (mirroring the reference's ``is_initialized()`` guard,
    ``utils.py:138``).
    """
    global _initialized_env
    if _initialized_env is not None:
        return _initialized_env.num_processes, _initialized_env.process_id

    select_platform(environ)
    from multidisttorch_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    penv = detect_process_env(environ)
    if penv.num_processes > 1:
        import jax

        env = os.environ if environ is None else environ
        env_elects_master = any(
            env.get(k) is not None
            for k in ("LSB_HOSTS", "LSB_MCPU_HOSTS", "SLURM_NODELIST", "MASTER_ADDR")
        )
        if coordinator is None and penv.source == "tpu" and not env_elects_master:
            # Cloud TPU pods publish coordinator metadata JAX already
            # knows how to read; none of the reference's master-election
            # env vars (LSB_*/SLURM_*/MASTER_ADDR) exist there, so the
            # elected fallback would be 127.0.0.1 — wrong on every
            # non-zero worker. Let JAX autodetect instead.
            jax.distributed.initialize()
        elif coordinator is None and penv.source == "jax" and not env_elects_master:
            # Generic JAX coordinates: respect JAX_COORDINATOR_ADDRESS
            # (JAX reads it only when coordinator_address is None) rather
            # than electing a 127.0.0.1 fallback on every worker.
            jax.distributed.initialize(
                coordinator_address=None,
                num_processes=penv.num_processes,
                process_id=penv.process_id,
            )
        else:
            jax.distributed.initialize(
                coordinator_address=(
                    coordinator
                    if coordinator is not None
                    else coordinator_address(environ)
                ),
                num_processes=penv.num_processes,
                process_id=penv.process_id,
            )
    _initialized_env = penv
    return penv.num_processes, penv.process_id


class AgreementTimeout(TimeoutError):
    """A deadline-bounded cross-process coordination call expired.

    A dedicated subclass, NOT a bare ``TimeoutError``: on Python >= 3.10
    ``socket.timeout`` IS ``TimeoutError``, so supervision matching the
    builtin would misclassify any transient network/NFS timeout inside a
    trial as a lost peer and kill the whole sweep. Only THIS type means
    "the distributed state can no longer be trusted; restart against
    the ledger" (``hpo/supervision.py`` classifies it like preemption).
    """


class WedgedCollective(AgreementTimeout):
    """A device-sync point (host barrier, submesh agreement, epoch-loss
    fetch, completion ``block_until_ready``) wedged past its deadline.

    The watchdog's verdict on a stuck cross-host collective: a peer
    stopped dispatching (wedged, preempted, dead NIC) and this process
    is blocked on a result that will never arrive. Subclasses
    :class:`AgreementTimeout`, so supervision classifies it as
    preemption (die, restart against the ledger) — the extra type names
    *which* failure mode for the exit-code contract: a supervised
    worker catching this exits with :data:`PREEMPTION_EXIT_CODE` so an
    elastic supervisor (``tools/sweep_supervisor.py``) can tell
    "healthy host, lost world" from a genuine crash.
    """


# The exit-code contract (docs/RESILIENCE.md "Elastic multi-host"):
# a supervised worker that dies because the *world* failed around it —
# host preemption, a wedged collective, a graceful SIGTERM drain —
# exits with this code (BSD EX_TEMPFAIL: "try again"). The supervisor
# re-admits such hosts into the next, possibly smaller, world; any
# other non-zero exit marks the host itself as lost.
PREEMPTION_EXIT_CODE = 75


def call_with_timeout(
    fn,
    timeout_s: Optional[float],
    what: str,
    *,
    error_cls: type = AgreementTimeout,
):
    """Run ``fn()`` with a wall-clock deadline; raise a *diagnosable*
    :class:`AgreementTimeout` naming ``what`` instead of hanging
    forever.

    The failure mode this exists for: a dead/hung peer process leaves a
    cross-process collective (barrier, health reduction) blocked with no
    error — the reference's exact steady-state on a lost rank
    (SURVEY.md §5). A blocked C-level collective cannot be interrupted
    from Python, so the deadline runs ``fn`` on a watchdog thread and
    abandons it on expiry: the stuck thread leaks (daemon — it dies with
    the process), which is the honest trade for turning an indefinite
    hang into an actionable error. ``timeout_s=None`` or <= 0 means no
    deadline (direct call).

    ``error_cls`` selects the raised type (must accept one message
    argument): the driver's device-sync watchdogs pass
    :class:`WedgedCollective` so the failure names itself; the default
    stays :class:`AgreementTimeout` for generic coordination calls.

    The runner thread MUST be a daemon: on expiry the blocked ``fn`` is
    abandoned mid-call, and a non-daemon leak would make interpreter
    shutdown join a thread that never returns — the process would
    survive its own timeout just to hang at exit (regression-tested in
    tests/test_elastic.py).
    """
    if timeout_s is None or timeout_s <= 0:
        return fn()
    import threading

    box: dict = {}

    def runner():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=runner, daemon=True, name=f"watchdog:{what}")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise error_cls(
            f"{what} did not complete within {timeout_s:g}s — a "
            "participating process is likely dead, preempted, or hung. "
            "The blocked collective was abandoned on a daemon thread; "
            "treat this process's distributed state as unusable and "
            "restart the job (the sweep ledger makes the restart cheap)."
        )
    if "error" in box:
        raise box["error"]
    return box.get("value")


def _env_timeout(env_var: str, default: Optional[float]) -> Optional[float]:
    raw = os.environ.get(env_var)
    if raw is None or raw == "":
        return default
    return float(raw)


def coordination_client():
    """The distributed runtime's coordination-service client, or None
    (single-process).

    The sideband channel for cross-host agreement that must work even
    when the accelerator backend cannot (a wedged TPU host, or
    XLA:CPU's missing multiprocess computations): a host barrier and a
    key-value store served by the coordinator process, independent of
    any compiled collective."""
    from jax._src import distributed

    return distributed.global_state.client


_UNBOUNDED_MS = 2**31 - 1  # "no deadline" for coordination-service waits


def agree_min_int(
    name: str,
    value: int,
    participants,
    *,
    timeout_s: Optional[float],
    what: str,
    error_cls: type = None,
) -> int:
    """Agree on the MINIMUM of a per-process integer across
    ``participants`` (process indices) via the coordination-service
    key-value store — the **sideband agreement** primitive.

    Unlike an on-mesh reduction (``collectives.group_min_scalar``) this
    never touches a compiled collective, so it works during recovery —
    exactly when the device world may be the thing that is broken —
    and on backends without cross-process XLA computations (CPU). Keys
    are scoped by ``name``; callers make names unique per agreement
    instance (the driver uses ``trial:attempt``), and every world
    restart gets a fresh coordinator so stale keys cannot leak across
    worlds.

    A participant that never shows up turns into ``error_cls``
    (default :class:`WedgedCollective`) within ``timeout_s`` — the
    no-hang contract. Single-process (or a single participant) returns
    ``value`` unchanged.
    """
    if error_cls is None:
        error_cls = WedgedCollective
    participants = sorted(int(p) for p in participants)
    import jax

    if len(participants) <= 1 or jax.process_count() == 1:
        return int(value)
    client = coordination_client()
    if client is None:
        raise error_cls(
            f"{what}: no coordination-service client available for the "
            f"sideband agreement {name!r} (distributed runtime not "
            "initialized?)"
        )
    pid = jax.process_index()
    timeout_ms = (
        int(timeout_s * 1000)
        if timeout_s and timeout_s > 0
        else _UNBOUNDED_MS
    )
    try:
        client.key_value_set(f"{name}:p{pid}", str(int(value)))
        values = [
            int(client.blocking_key_value_get(f"{name}:p{q}", timeout_ms))
            for q in participants
        ]
    except Exception as e:
        raise error_cls(
            f"{what} did not complete within "
            f"{(timeout_ms / 1000.0):g}s — a participant of the sideband "
            f"agreement {name!r} (processes {participants}) is missing: "
            "likely dead, preempted, or wedged. Treat this process's "
            "distributed state as unusable and restart against the "
            "sweep ledger."
        ) from e
    return min(values)


import itertools as _itertools

# Barrier ids must be unique per invocation; processes call sync_hosts
# at the same points (the documented collective-cadence contract), so a
# per-process counter yields matching ids everywhere.
_sync_barrier_counter = _itertools.count()

# Backend-capability verdict, cached after the first probe: whether
# this process's backend can run cross-process XLA computations at all
# (XLA:CPU cannot). Constant per process — re-probing would pay a
# doomed collective compile + a leaked watchdog thread on EVERY CPU
# barrier.
_xla_sync_unsupported = False


def sync_hosts(name: str = "sync", *, timeout_s: Optional[float] = None) -> None:
    """Barrier across host processes (multi-controller only).

    The analog of the reference's ``dist.barrier()`` — but deliberately
    NOT used anywhere in the trial path (the reference's world-scoped
    barriers serialize the sweep, quirk Q3). Provided for host-side
    coordination such as "download data once before dispatch"
    (``vae-hpo.py:133-144``) and end-of-job collection. No-op
    single-controller.

    ``timeout_s`` (default: ``MDT_SYNC_TIMEOUT_S`` env var, else 1800)
    bounds the wait: a dead peer turns into a descriptive
    :class:`WedgedCollective` naming the barrier instead of an
    indefinite hang — the reference's unbounded ``dist.barrier()`` is
    exactly the failure this guards against. The default is deliberately
    generous (30 min): this barrier's documented use is "wait while one
    host downloads the dataset", which is legitimately slow; jobs whose
    barriers wait even longer pass ``timeout_s`` explicitly or ``0`` /
    ``MDT_SYNC_TIMEOUT_S=0`` for the old unbounded behavior.

    Backend-agnostic: ``sync_global_devices`` compiles a cross-process
    collective, which XLA:CPU does not implement ("Multiprocess
    computations aren't implemented") — there the barrier degrades to
    the coordination-service host barrier, same semantics for host-side
    coordination, natively deadline-bounded (no watchdog thread to
    leak). The elastic chaos drills exercise the wedge path through
    exactly this barrier.
    """
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        if timeout_s is None:
            timeout_s = _env_timeout("MDT_SYNC_TIMEOUT_S", 1800.0)
        global _xla_sync_unsupported
        what = (
            f"host barrier {name!r} over {jax.process_count()} processes"
        )
        if not _xla_sync_unsupported:
            try:
                call_with_timeout(
                    lambda: multihost_utils.sync_global_devices(name),
                    timeout_s,
                    what,
                    # A stuck barrier IS a wedged collective: name it so
                    # the exit-code contract (and the supervisor) react.
                    error_cls=WedgedCollective,
                )
                return
            except WedgedCollective:
                raise
            except Exception as e:  # noqa: BLE001 — capability probe
                if "Multiprocess computations" not in str(e):
                    raise
                # XLA:CPU: fall back to the coordination-service
                # barrier, and remember the verdict — it is constant
                # per process. Every process of a CPU world raises
                # identically, so all participants fall back together.
                _xla_sync_unsupported = True
        client = coordination_client()
        if client is None:
            raise RuntimeError(
                f"{what}: backend cannot run multiprocess computations "
                "and no coordination-service client is available"
            )
        bid = f"mdt:sync:{name}:{next(_sync_barrier_counter)}"
        timeout_ms = (
            int(timeout_s * 1000)
            if timeout_s and timeout_s > 0
            else _UNBOUNDED_MS
        )
        try:
            client.wait_at_barrier(bid, timeout_ms)
        except Exception as e:
            raise WedgedCollective(
                f"{what} did not complete within "
                f"{(timeout_ms / 1000.0):g}s — a participating process "
                "is likely dead, preempted, or wedged. Treat this "
                "process's distributed state as unusable and restart "
                "the job (the sweep ledger makes the restart cheap)."
            ) from e


def process_world() -> tuple[int, int]:
    """Process count and index, ``(size, rank)``.

    Analog of ``get_comm_size_and_rank`` (``/root/reference/
    utils.py:28-38``). Unlike torch's side-effect-free
    ``dist.is_initialized()`` probe, querying JAX's process coordinates
    initializes the XLA backend — which would poison a later
    ``jax.distributed.initialize``. So this calls
    :func:`initialize_runtime` first (idempotent), making it safe in any
    order, exactly like the reference's query.
    """
    import jax

    initialize_runtime()
    return jax.process_count(), jax.process_index()
