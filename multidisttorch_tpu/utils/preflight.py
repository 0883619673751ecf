"""Backend preflight diagnostics: classified verdicts, never hangs.

A standalone, out-of-process probe of an accelerator backend for the
tools that must decide BEFORE they start a world whether the backend
can be used at all: ``tools/preflight.py`` is the CLI, and the elastic
supervisor (``tools/sweep_supervisor.py --preflight``) runs it before
forming a world so an unusable backend becomes a *diagnosed, skippable*
condition instead of a hung launch. Nothing that trains calls it: a
training process initialises JAX once, itself — a chip belongs to one
process at a time, and a probe child would pay backend start-up twice.
For the same reason this process never imports jax: the probe children
run one after another, each releasing the device before the next.

The probe is structured as stages, each bounded and recorded:

1. **init** — out-of-process ``jax.devices()`` with a hard timeout.
2. **plugin_scan** (failure path only — a healthy probe never pays the
   /proc walk) — read-only /proc + /dev evidence: accel/vfio node
   holders and processes with the TPU runtime mapped (another process
   on this host holds the chip). Then one shorter init retry after
   ``retry_delay_s`` (a holder that has just exited) — skipped when the
   platform is simply absent, which must classify fast.
3. **canary** — in the SAME out-of-process shape: device enumeration,
   a tiny ``jit`` compile+execute with a value check (init succeeding
   while execution fails is a distinct failure mode), and
   ``memory_stats()`` where the backend keeps them.

Everything folds to ONE verdict from a closed taxonomy
(docs/OBSERVABILITY.md "Fleet"):

- ``healthy`` / ``transient_recovered`` — usable (the latter means the
  first init probe failed and the retry cleared; kept distinct because
  it is evidence, not a clean bill).
- ``wedged_leaked_plugin`` — a holder process on this host owns the
  accelerator; kill it and re-probe.
- ``wedged_init_timeout`` — init blocked past the deadline with no
  holder in evidence.
- ``backend_absent`` — the requested platform is not present at all
  (fast, classified — never a hang; CI asserts this).
- ``init_failed`` / ``canary_failed`` — non-timeout failures with the
  error recorded.

An optional **compile_cache** stage (``compile_cache=True`` /
``tools/preflight.py --compile-cache``) probes the quarantined
persistent executable cache the same bounded, out-of-process way
(docs/COMPILE.md): a CRC sidecar scan over the cache dir plus ONE
cold/warmup/warm canary protocol run in sacrificial children — both
read-only (rejects reported, nothing quarantined or evicted: a
diagnostic must not discard a production cache on a transient
failure). Its
verdict rides the report as ``compile_cache.verdict`` using the cache
layer's own closed taxonomy (``passed`` / ``canary_mismatch`` /
``canary_crashed`` / ``canary_timeout``) — cache state is orthogonal
to backend usability, so it refines the report without ever flipping
a healthy backend verdict (a cache nobody can trust just means cold
compiles, exactly as safe as the cache staying off).

Verdicts are emitted on the telemetry bus
(``preflight_start`` / ``preflight_stage`` / ``preflight_verdict``)
under the usual zero-cost-when-off contract.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

# -- verdict taxonomy -------------------------------------------------

HEALTHY = "healthy"
TRANSIENT_RECOVERED = "transient_recovered"
WEDGED_LEAKED_PLUGIN = "wedged_leaked_plugin"
WEDGED_INIT_TIMEOUT = "wedged_init_timeout"
BACKEND_ABSENT = "backend_absent"
INIT_FAILED = "init_failed"
CANARY_FAILED = "canary_failed"

VERDICTS = (
    HEALTHY,
    TRANSIENT_RECOVERED,
    WEDGED_LEAKED_PLUGIN,
    WEDGED_INIT_TIMEOUT,
    BACKEND_ABSENT,
    INIT_FAILED,
    CANARY_FAILED,
)
USABLE_VERDICTS = frozenset({HEALTHY, TRANSIENT_RECOVERED})

# Bounds (seconds). A healthy TPU init takes some tens of seconds; one
# that waits on a chip another process holds blocks for good — cap well
# past healthy-init time.
PREFLIGHT_TIMEOUT_S = int(os.environ.get("MDT_PREFLIGHT_TIMEOUT_S", "120"))
RETRY_DELAY_S = int(os.environ.get("MDT_BENCH_RETRY_DELAY_S", "30"))
RETRY_TIMEOUT_S = 60  # a retry still blocked this long is the same
# wedge, not a slow init
CANARY_TIMEOUT_S = int(os.environ.get("MDT_PREFLIGHT_CANARY_S", "120"))

# Fast-failure error shapes that mean "the platform is not here" (vs a
# backend that exists but broke) — matched lowercase against the
# probe's error + stderr tail. Deliberately NOT the generic "unable to
# initialize backend" wrapper: jax wraps BOTH absence ("...: Backend
# 'x' is not in the list of known backends") and a present-but-crashed
# plugin ("...: UNAVAILABLE ...") in that prefix, and only the former
# should skip the wedge retry.
_ABSENT_PATTERNS = (
    "unknown backend",
    "is not in the list of known backends",
    "no platforms that are instances",
    "is not a known platform",
    "no visible",
)


def _read_small(path: str, cap: int = 4096) -> str:
    try:
        with open(path, "rb") as f:
            return f.read(cap).decode(errors="replace")
    except OSError:
        return ""


def plugin_scan() -> dict:
    """Gather machine-readable evidence about WHY a TPU probe failed:
    is another process on this host holding the accelerator?

    1. device nodes — local-PCIe TPUs appear as /dev/accel* or
       /dev/vfio*.
    2. holder processes — every /proc/<pid> whose open fds reference an
       accel/vfio node, or whose mapped libraries include the TPU
       runtime (libtpu). A non-empty list = held by a process we can
       name.

    Everything is best-effort and silent on permission errors: the value
    of this function is the recorded artifact, never a new failure mode.
    """
    import glob
    import stat as stat_mod

    triage: dict = {}

    nodes = {}
    for pat in ("/dev/accel*", "/dev/vfio*"):
        for p in sorted(glob.glob(pat)):
            try:
                st = os.stat(p)
                nodes[p] = {
                    "mode": stat_mod.filemode(st.st_mode),
                    "uid": st.st_uid,
                }
            except OSError as e:
                nodes[p] = {"error": str(e)}
    triage["device_nodes"] = nodes or "absent"

    holders = []
    jax_procs = []
    my_pid = os.getpid()
    for pid_dir in glob.glob("/proc/[0-9]*"):
        pid = int(os.path.basename(pid_dir))
        if pid == my_pid:
            continue
        cmdline = _read_small(f"{pid_dir}/cmdline").replace("\0", " ").strip()
        if not cmdline:
            continue
        fd_targets = []
        try:
            for fd in os.listdir(f"{pid_dir}/fd"):
                try:
                    fd_targets.append(os.readlink(f"{pid_dir}/fd/{fd}"))
                except OSError:
                    pass
        except OSError:
            pass
        if any("accel" in t or "vfio" in t for t in fd_targets):
            holders.append({"pid": pid, "cmdline": cmdline[:200]})
            continue
        # Full maps read (several MB cap): shared-object mappings sit at
        # high addresses near the END of the address-ordered file, so a
        # small cap would always miss the runtime and wrongly clear a
        # leaked holder process.
        maps = _read_small(f"{pid_dir}/maps", cap=8 << 20)
        if "libtpu" in maps:
            jax_procs.append({"pid": pid, "cmdline": cmdline[:200]})
    triage["accel_node_holders"] = holders
    triage["pjrt_plugin_processes"] = jax_procs
    return triage


def _subprocess_env(platform: Optional[str]) -> dict:
    env = dict(os.environ)
    if platform:
        env["JAX_PLATFORMS"] = platform
    return env


def probe_init(timeout_s: int, platform: Optional[str] = None) -> dict:
    """One out-of-process ``jax.devices()`` probe with a hard timeout.

    ``jax.devices()`` on a TPU another process holds either crashes
    with UNAVAILABLE or blocks until something external kills the
    caller. Probing out-of-process turns both into a fast, attributable
    diagnostic; the calling process never touches the backend.
    ``timeout: true`` in the failure dict distinguishes a blocked init
    (the wedge class) from a fast error (the absent/broken class).
    """
    code = (
        "import jax\n"
        "d = jax.devices()\n"
        "print('PROBE|%s|%s|%d' % (d[0].platform, d[0].device_kind, len(d)))\n"
    )
    t0 = time.perf_counter()
    try:
        p = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=_subprocess_env(platform),
        )
    except subprocess.TimeoutExpired as e:
        tail = (
            (e.stderr or b"").decode(errors="replace")
            if isinstance(e.stderr, bytes)
            else (e.stderr or "")
        )[-400:]
        return {
            "ok": False,
            "timeout": True,
            "error": (
                f"backend init still blocked after {timeout_s}s "
                "(the chip is held or unreachable — see the triage)"
            ),
            "elapsed_s": round(time.perf_counter() - t0, 1),
            "stderr_tail": tail,
        }
    for line in p.stdout.splitlines():
        if line.startswith("PROBE|"):
            _, platform_got, kind, n = line.split("|")
            return {
                "ok": True,
                "platform": platform_got,
                "device_kind": kind,
                "n_devices": int(n),
                "elapsed_s": round(time.perf_counter() - t0, 1),
            }
    return {
        "ok": False,
        "timeout": False,
        "error": f"backend init failed (rc={p.returncode})",
        "elapsed_s": round(time.perf_counter() - t0, 1),
        "stderr_tail": p.stderr[-400:],
    }


def probe_canary(timeout_s: int, platform: Optional[str] = None) -> dict:
    """Out-of-process compile+execute canary: enumerate devices, run a
    tiny jitted matmul-sum with a value check, and collect
    ``memory_stats()`` where the backend keeps them. Catches the
    backend that *initializes* but cannot compile or execute."""
    code = (
        "import json\n"
        "import jax, jax.numpy as jnp\n"
        "ds = jax.devices()\n"
        "out = {'n_devices': len(ds), 'platform': ds[0].platform,\n"
        "       'device_kind': ds[0].device_kind}\n"
        "x = jnp.ones((8, 8), jnp.float32)\n"
        "y = float(jax.jit(lambda a: (a @ a).sum())(x))\n"
        "out['canary_value'] = y\n"
        "out['canary_ok'] = abs(y - 512.0) < 1e-3\n"
        "ms = None\n"
        "try:\n"
        "    ms = ds[0].memory_stats()\n"
        "except Exception:\n"
        "    pass\n"
        "out['memory_stats'] = (\n"
        "    {k: int(v) for k, v in ms.items()} if ms else None)\n"
        "print('CANARY|' + json.dumps(out))\n"
    )
    t0 = time.perf_counter()
    try:
        p = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=_subprocess_env(platform),
        )
    except subprocess.TimeoutExpired:
        return {
            "ok": False,
            "timeout": True,
            "error": (
                f"compile+execute canary still blocked after {timeout_s}s"
            ),
            "elapsed_s": round(time.perf_counter() - t0, 1),
        }
    for line in p.stdout.splitlines():
        if line.startswith("CANARY|"):
            try:
                out = json.loads(line[len("CANARY|"):])
            except json.JSONDecodeError:
                break
            out["ok"] = bool(out.get("canary_ok"))
            out["elapsed_s"] = round(time.perf_counter() - t0, 1)
            if not out["ok"]:
                out["error"] = (
                    f"canary executed but returned {out.get('canary_value')}"
                    " (expected 512.0)"
                )
            return out
    return {
        "ok": False,
        "timeout": False,
        "error": f"canary failed (rc={p.returncode})",
        "elapsed_s": round(time.perf_counter() - t0, 1),
        "stderr_tail": p.stderr[-400:],
    }


def _emit(kind: str, **data) -> None:
    from multidisttorch_tpu.telemetry.events import get_bus

    bus = get_bus()
    if bus is not None:
        bus.emit(kind, **data)


def _looks_absent(probe: dict) -> bool:
    text = (
        str(probe.get("error", "")) + " " + str(probe.get("stderr_tail", ""))
    ).lower()
    return any(pat in text for pat in _ABSENT_PATTERNS)


def run_preflight(
    platform: Optional[str] = None,
    *,
    init_timeout_s: int = PREFLIGHT_TIMEOUT_S,
    retry_timeout_s: int = RETRY_TIMEOUT_S,
    retry_delay_s: int = RETRY_DELAY_S,
    canary: bool = True,
    canary_timeout_s: int = CANARY_TIMEOUT_S,
    scan: bool = True,
    compile_cache: bool = False,
    compile_cache_dir: Optional[str] = None,
) -> dict:
    """The full structured probe: bounded init → (on failure: /proc
    evidence scan + one delayed retry) → enumeration → compile/execute
    canary (+ memory_stats) → ONE classified verdict. Total wall time
    is bounded by construction (every stage has a hard timeout;
    nothing in this process touches a jax backend). Emits
    ``preflight_*`` telemetry when a bus is live."""
    t0 = time.perf_counter()
    _emit("preflight_start", platform=platform or "default")
    stages: list[dict] = []

    def stage(name: str, result: dict) -> dict:
        rec = {"stage": name, **result}
        stages.append(rec)
        _emit(
            "preflight_stage",
            stage=name,
            ok=bool(result.get("ok", True)),
            elapsed_s=result.get("elapsed_s"),
        )
        return rec

    # The /proc evidence sweep is failure-path only: on a healthy
    # backend its fd-table/maps walk over
    # every process is seconds of discarded I/O — and the supervisor
    # runs this probe before every world.
    triage = None

    def run_scan() -> None:
        nonlocal triage
        if not scan or triage is not None:
            return
        t_scan = time.perf_counter()
        triage = plugin_scan()
        stage(
            "plugin_scan",
            {
                "ok": True,
                "elapsed_s": round(time.perf_counter() - t_scan, 2),
                "holders": len(triage["accel_node_holders"]),
                "plugin_processes": len(triage["pjrt_plugin_processes"]),
            },
        )

    first = probe_init(init_timeout_s, platform)
    stage("init", first)
    if not first["ok"]:
        run_scan()
    retried = None
    probe = first
    # Retry only wedge-shaped failures: an absent platform fails fast
    # and deterministically — sleeping 30s before re-asking the same
    # question would turn the one verdict that SHOULD be instant into
    # the slowest one.
    if not first["ok"] and not _looks_absent(first):
        time.sleep(retry_delay_s)
        retried = probe_init(retry_timeout_s, platform)
        stage("init_retry", retried)
        if retried["ok"]:
            probe = retried

    verdict: str
    reason: str
    device = None
    memory_stats = None
    if probe["ok"]:
        device = {
            "platform": probe["platform"],
            "device_kind": probe["device_kind"],
            "n_devices": probe["n_devices"],
        }
        stage("enumerate", {"ok": True, **device})
        can = None
        if canary:
            can = probe_canary(canary_timeout_s, platform)
            stage("canary", can)
            memory_stats = can.get("memory_stats")
        if can is not None and not can["ok"]:
            verdict = CANARY_FAILED
            reason = str(can.get("error", "canary failed"))
        elif retried is not None and retried["ok"]:
            verdict = TRANSIENT_RECOVERED
            reason = (
                "first init probe failed "
                f"({first.get('error', '?')}); retry after "
                f"{retry_delay_s}s succeeded"
            )
        else:
            verdict = HEALTHY
            reason = (
                f"{device['n_devices']} {device['platform']} device(s), "
                + ("canary compile+execute ok" if canary else "canary skipped")
            )
    else:
        failed = retried if retried is not None else first
        if first.get("timeout") or failed.get("timeout"):
            holders = (
                (triage or {}).get("accel_node_holders", [])
                or (triage or {}).get("pjrt_plugin_processes", [])
            )
            if holders:
                verdict = WEDGED_LEAKED_PLUGIN
                reason = (
                    "init blocked past deadline with a live accelerator "
                    f"holder on this host: {holders[:3]}"
                )
            else:
                verdict = WEDGED_INIT_TIMEOUT
                reason = str(failed.get("error", "init timeout"))
        elif _looks_absent(first) or _looks_absent(failed):
            verdict = BACKEND_ABSENT
            reason = (
                f"platform {platform or 'default'!r} is not present: "
                + str(failed.get("error", ""))
            )
        else:
            verdict = INIT_FAILED
            reason = str(failed.get("error", "init failed"))

    cache_report = None
    if compile_cache and probe["ok"]:
        # Only a usable backend can run the cache canary's sacrificial
        # children; on a wedged/absent backend the cache question is
        # moot (nothing will compile either way).
        from multidisttorch_tpu.compile.cache import cache_probe

        t_cache = time.perf_counter()
        cp = cache_probe(
            compile_cache_dir,
            platform=platform,
            canary=True,
        )
        can = cp.get("canary") or {}
        cache_report = {
            "cache_dir": cp["cache_dir"],
            "verdict": can.get("verdict", "scan_only"),
            "usable": bool(cp.get("usable")),
            "scan": cp.get("scan"),
            "evicted": can.get("evicted", 0),
        }
        stage(
            "compile_cache",
            {
                "ok": bool(cp.get("usable")),
                "elapsed_s": round(time.perf_counter() - t_cache, 2),
                "cache_verdict": cache_report["verdict"],
                "scanned": (cp.get("scan") or {}).get("checked"),
                "rejected": len((cp.get("scan") or {}).get("rejected") or []),
            },
        )

    elapsed = round(time.perf_counter() - t0, 2)
    usable = verdict in USABLE_VERDICTS
    _emit(
        "preflight_verdict",
        platform=platform or "default",
        verdict=verdict,
        reason=reason,
        usable=usable,
        elapsed_s=elapsed,
    )
    return {
        "protocol": "preflight_v1",
        "platform_requested": platform or "default",
        "verdict": verdict,
        "verdict_reason": reason,
        "usable": usable,
        "elapsed_s": elapsed,
        "stages": stages,
        "device": device,
        "memory_stats": memory_stats,
        "triage": triage,
        "compile_cache": cache_report,
    }
