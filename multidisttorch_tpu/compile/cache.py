"""Quarantined persistent executable cache: a CPU-world drill.

On the chip the compile cache is jax's plain persistent cache, turned
on by every entry point (``utils/compile_cache.enable_compile_cache``).
This module is the older, guarded way in, kept for the cold-start drill
(``compile/coldstart.py``) and the preflight's read-only cache probe
until ROADMAP C7 decides what of it the TPU path needs:

1. **Per-entry CRC32 sidecars** (the checkpoint layer's pattern,
   ``train/checkpoint.py``): :func:`seal_cache` records each entry's
   CRC32+length in a ``*.mdtcrc`` sidecar; :func:`scan_cache` verifies
   every entry on the way in and MOVES failures (bit-rot, torn writes,
   unsealed files of unknown provenance) to ``quarantine/`` — jax sees
   a miss and cold-compiles, never a garbled blob.
2. **Subprocess canary-execute quarantine** (:func:`canary_quarantine`):
   three sacrificial children prove the full deserialize-and-run path:
   a cold child (cache off) banks the reference output bits; a warmup
   child (cache on) guarantees the canary entry exists on disk; a warm
   child (cache on) necessarily deserializes it, runs the canary batch,
   and must **bit-match** the cold reference. A crash, hang, or
   mismatch in the warm child quarantines the entries.
3. **Policy gate** (:func:`cache_policy`): a passed canary enables the
   cache only in a process that declared itself sacrificial
   (``MDT_CACHE_SACRIFICIAL=1``, e.g. the coldstart drill's warm child,
   which is parity-gated against the cold child); any other process
   gets the verdict ``quarantined_only``.

:func:`enable_quarantined_cache` composes the three: scan → canary →
gate → enable (or a classified refusal). Its canary children each
initialise the backend, and a chip belongs to one process at a time, so
it refuses any platform but ``cpu``. The preflight engine
(``utils/preflight.py``), whose own process never touches jax, reuses
:func:`cache_probe` for its compile-cache stage.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
import zlib
from typing import Callable, Optional

from multidisttorch_tpu.telemetry.events import get_bus
from multidisttorch_tpu.utils.compile_cache import (
    default_cache_dir,
    enable_compile_cache,
)

SIDECAR_SUFFIX = ".mdtcrc"
QUARANTINE_DIR = "quarantine"

# Verdict taxonomy (closed): how an enable attempt resolved.
ENABLED = "enabled"
QUARANTINED_ONLY = "quarantined_only"  # canary passed; CPU policy says
# deserialized executables stay in sacrificial children
CANARY_MISMATCH = "canary_mismatch"
CANARY_CRASHED = "canary_crashed"
CANARY_TIMEOUT = "canary_timeout"
SCAN_ONLY = "scan_only"  # canary skipped; cache not enabled

CANARY_TIMEOUT_S = int(os.environ.get("MDT_CACHE_CANARY_TIMEOUT_S", "120"))


def _emit(kind: str, **data) -> None:
    bus = get_bus()
    if bus is not None:
        bus.emit(kind, **data)


# -- sidecars ---------------------------------------------------------


def _is_entry(name: str) -> bool:
    """Cache-entry files we seal: everything except our sidecars and
    jax's ``*-atime`` access markers (rewritten on every read — a CRC
    over them would churn without meaning)."""
    return not name.endswith(SIDECAR_SUFFIX) and not name.endswith("-atime")


def _entries(cache_dir: str) -> list[str]:
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return []
    return sorted(
        n
        for n in names
        if _is_entry(n) and os.path.isfile(os.path.join(cache_dir, n))
    )


def _crc_file(path: str) -> tuple[int, int]:
    """Chunked CRC32+length of a file — cache entries on the TPU path
    are serialized executables that can run to hundreds of MB, so the
    whole-blob read would spike RAM by the largest entry."""
    crc = 0
    n = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            crc = zlib.crc32(chunk, crc)
            n += len(chunk)
    return crc, n


def seal_cache(cache_dir: str, *, only: Optional[set] = None) -> dict:
    """Write/refresh a CRC32+length sidecar for every cache entry.

    Run after a writer process finishes compiling (the canary warmup
    child, the coldstart bench's seed child, a TPU sweep that just
    populated the cache): only sealed entries survive the next
    :func:`scan_cache` — an unsealed entry is an entry whose writer we
    cannot vouch for. ``only`` restricts sealing to the named entries:
    a caller that wrote SOME entries (the canary warmup) must not
    vouch for strangers that happen to share the dir."""
    sealed = refreshed = 0
    for name in _entries(cache_dir):
        if only is not None and name not in only:
            continue
        path = os.path.join(cache_dir, name)
        try:
            crc, n = _crc_file(path)
            rec = {"crc32": crc, "nbytes": n}
            side = path + SIDECAR_SUFFIX
            prev = None
            if os.path.exists(side):
                try:
                    with open(side, "r") as f:
                        prev = json.load(f)
                except (OSError, json.JSONDecodeError):
                    prev = None
            if prev == rec:
                continue
            tmp = side + ".tmp"
            with open(tmp, "w") as f:
                json.dump(rec, f)
            os.replace(tmp, side)
            if prev is None:
                sealed += 1
            else:
                refreshed += 1
        except OSError:
            continue
    return {"entries": len(_entries(cache_dir)), "sealed": sealed,
            "refreshed": refreshed}


def _quarantine(cache_dir: str, name: str) -> None:
    qdir = os.path.join(cache_dir, QUARANTINE_DIR)
    os.makedirs(qdir, exist_ok=True)
    src = os.path.join(cache_dir, name)
    shutil.move(src, os.path.join(qdir, name))
    side = src + SIDECAR_SUFFIX
    if os.path.exists(side):
        shutil.move(
            side, os.path.join(qdir, name + SIDECAR_SUFFIX)
        )


def scan_cache(cache_dir: str, *, quarantine: bool = True) -> dict:
    """Verify every entry against its sidecar; move failures aside.

    Rejection reasons (each a quarantined entry when ``quarantine``):
    ``unsealed`` (no sidecar — unknown provenance), ``sidecar_unreadable``,
    ``size_mismatch`` (torn write), ``crc_mismatch`` (bit rot /
    corruption). jax treats a moved entry as a plain cache miss, so a
    failed scan costs a cold compile, never a garbled executable."""
    checked = ok = 0
    rejected: list[dict] = []
    for name in _entries(cache_dir):
        path = os.path.join(cache_dir, name)
        checked += 1
        reason = None
        side = path + SIDECAR_SUFFIX
        if not os.path.exists(side):
            reason = "unsealed"
        else:
            # A sidecar that parses but is not {crc32: int, nbytes:
            # int} (bit rot can produce VALID JSON of the wrong shape)
            # is exactly as untrustworthy as one that doesn't parse —
            # classify, never crash: this scanner runs inside the
            # corruption-containment path itself.
            try:
                with open(side, "r") as f:
                    rec = json.load(f)
                want_crc = int(rec["crc32"])
                want_n = int(rec["nbytes"])
            except (OSError, json.JSONDecodeError, KeyError, TypeError,
                    ValueError):
                reason = "sidecar_unreadable"
            if reason is None:
                try:
                    crc, n = _crc_file(path)
                except OSError:
                    reason = "unreadable"
                if reason is None:
                    if n != want_n:
                        reason = "size_mismatch"
                    elif crc != want_crc:
                        reason = "crc_mismatch"
        if reason is None:
            ok += 1
            continue
        rejected.append({"entry": name, "reason": reason})
        if quarantine:
            try:
                _quarantine(cache_dir, name)
            except OSError:
                pass
    report = {"checked": checked, "ok": ok, "rejected": rejected,
              "quarantined": len(rejected) if quarantine else 0}
    _emit("cache_scan", dir=cache_dir, **{
        "checked": checked, "ok": ok, "quarantined": report["quarantined"],
    })
    return report


# -- subprocess canary ------------------------------------------------

_CANARY_CODE = """
import sys
cache_dir = sys.argv[1]
if cache_dir != "-":
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import jax, jax.numpy as jnp
import numpy as np

@jax.jit
def canary(x, k):
    y = jnp.tanh(x @ x.T)
    y = y + jax.random.normal(k, y.shape) * 1e-3
    return (y @ y).sum(axis=0)

x = jnp.linspace(0.0, 1.0, 32 * 16, dtype=jnp.float32).reshape(32, 16)
out = np.asarray(canary(x, jax.random.key(7)))
print("CANARYBITS|" + out.tobytes().hex())
"""


def _run_canary_child(
    mode: str,
    cache_dir: str,
    platform: Optional[str],
    timeout_s: float,
) -> dict:
    """One bounded sacrificial child: ``mode`` is ``cold`` (cache off —
    the trusted reference), ``warmup`` (cache on — guarantees the entry
    exists), or ``warm`` (cache on — necessarily deserializes). Shape
    mirrors ``utils/preflight.py``'s out-of-process probes: a wedged or
    crashing deserializer must never take the caller down."""
    env = dict(os.environ)
    # Each mode configures its cache via argv + jax.config ONLY — an
    # inherited JAX_COMPILATION_CACHE_DIR would point the COLD child at
    # the suspect cache, and a cold reference that deserialized the same
    # corrupt entry as the warm child bit-matches it: the gate this
    # protocol exists for would pass the failure it is there to catch.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if platform:
        env["JAX_PLATFORMS"] = platform
    arg = "-" if mode == "cold" else cache_dir
    t0 = time.perf_counter()
    try:
        p = subprocess.run(
            [sys.executable, "-c", _CANARY_CODE, arg],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
        )
    except subprocess.TimeoutExpired:
        return {
            "ok": False,
            "timeout": True,
            "error": f"canary {mode} child blocked past {timeout_s}s",
            "elapsed_s": round(time.perf_counter() - t0, 2),
        }
    bits = None
    for line in p.stdout.splitlines():
        if line.startswith("CANARYBITS|"):
            bits = line[len("CANARYBITS|"):].strip()
    if p.returncode != 0 or bits is None:
        return {
            "ok": False,
            "timeout": False,
            "rc": p.returncode,
            "error": (
                f"canary {mode} child died rc={p.returncode} "
                "(deserialized-executable crash class)"
            ),
            "stderr_tail": p.stderr[-400:],
            "elapsed_s": round(time.perf_counter() - t0, 2),
        }
    return {
        "ok": True,
        "bits": bits,
        "elapsed_s": round(time.perf_counter() - t0, 2),
    }


def canary_quarantine(
    cache_dir: str,
    *,
    platform: Optional[str] = None,
    timeout_s: float = CANARY_TIMEOUT_S,
    runner: Optional[Callable] = None,
    evict_on_failure: bool = True,
) -> dict:
    """The cold/warmup/warm bit-match protocol over ``cache_dir``.

    Returns ``{"passed": bool, "verdict": ..., "cold"/"warmup"/"warm":
    per-child records, "evicted": n}``. ``runner`` is injectable for
    tests (scripted children — a crash or mismatch can be drilled
    without a real broken jaxlib). On any warm-side failure the
    cache's entries are quarantined (``evict_on_failure``): a cache
    that cannot prove deserialize-and-run is a cache nobody loads.
    """
    run = runner or _run_canary_child
    out: dict = {"passed": False, "evicted": 0}
    cold = run("cold", cache_dir, platform, timeout_s)
    out["cold"] = cold
    if not cold.get("ok"):
        # Without a trusted reference there is no verdict to give —
        # classify on the cold child's own failure shape.
        out["verdict"] = (
            CANARY_TIMEOUT if cold.get("timeout") else CANARY_CRASHED
        )
        return out
    os.makedirs(cache_dir, exist_ok=True)
    before = set(_entries(cache_dir))
    warmup = run("warmup", cache_dir, platform, timeout_s)
    out["warmup"] = warmup
    # Seal ONLY the warmup child's own new entries: those are the ones
    # whose provenance this protocol just established. Pre-existing
    # unsealed strangers stay unsealed (the probe path scans without
    # quarantining, so they may still be present here).
    seal_cache(
        cache_dir, only={n for n in _entries(cache_dir) if n not in before}
    )
    if not warmup.get("ok"):
        out["verdict"] = (
            CANARY_TIMEOUT if warmup.get("timeout") else CANARY_CRASHED
        )
        if evict_on_failure:
            out["evicted"] = _evict_all(cache_dir)
        return out
    warm = run("warm", cache_dir, platform, timeout_s)
    out["warm"] = warm
    if not warm.get("ok"):
        out["verdict"] = (
            CANARY_TIMEOUT if warm.get("timeout") else CANARY_CRASHED
        )
        if evict_on_failure:
            out["evicted"] = _evict_all(cache_dir)
        return out
    if warm.get("bits") != cold.get("bits"):
        out["verdict"] = CANARY_MISMATCH
        if evict_on_failure:
            out["evicted"] = _evict_all(cache_dir)
        return out
    out["passed"] = True
    out["verdict"] = "passed"
    return out


def _evict_all(cache_dir: str) -> int:
    """Quarantine every entry: the deserializer itself failed the
    canary, so no entry in this dir may be loaded by anyone but a
    sacrificial child."""
    n = 0
    for name in _entries(cache_dir):
        try:
            _quarantine(cache_dir, name)
            n += 1
        except OSError:
            pass
    return n


# -- policy + the safe opt-in ----------------------------------------


def is_sacrificial_process() -> bool:
    """Whether this process has declared itself expendable — allowed to
    load deserialized executables on backends the policy otherwise
    quarantines (the coldstart bench's warm child sets this)."""
    return os.environ.get("MDT_CACHE_SACRIFICIAL") == "1"


def cache_policy(*, sacrificial: Optional[bool] = None) -> str:
    """Where a passed canary leads: ``enabled`` in a process that
    declared itself sacrificial, ``quarantined_only`` in any other —
    a passed canary only licenses sacrificial children."""
    if sacrificial if sacrificial is not None else is_sacrificial_process():
        return ENABLED
    return QUARANTINED_ONLY


def _enable(cache_dir: str) -> bool:
    import jax

    try:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        enable_compile_cache()  # thresholds; the directory set above stands
    except Exception:  # noqa: BLE001 — the cache is an optimization
        return False
    return True


def cache_probe(
    cache_dir: Optional[str] = None,
    *,
    platform: Optional[str] = None,
    canary: bool = True,
    timeout_s: float = CANARY_TIMEOUT_S,
    runner: Optional[Callable] = None,
) -> dict:
    """Read-side probe without enabling anything: sidecar scan report +
    (optionally) one canary protocol run. The preflight engine's
    compile-cache stage (``utils/preflight.py``) and ``tools/preflight
    --compile-cache`` both consume this.

    The probe is non-destructive by design: the scan REPORTS rejects
    without quarantining them and a failed canary does NOT evict — a
    transient child timeout on a loaded host must not throw away a
    production cache's accumulated compiles. Mutation (quarantine +
    evict-on-failure) belongs to :func:`enable_quarantined_cache`,
    the path that would actually load the entries."""
    cache_dir = cache_dir or default_cache_dir()
    out: dict = {"cache_dir": cache_dir}
    out["scan"] = scan_cache(cache_dir, quarantine=False)
    if canary:
        out["canary"] = canary_quarantine(
            cache_dir, platform=platform, timeout_s=timeout_s,
            runner=runner, evict_on_failure=False,
        )
        out["usable"] = bool(out["canary"]["passed"])
    else:
        out["canary"] = None
        out["usable"] = False
    return out


def enable_quarantined_cache(
    cache_dir: Optional[str] = None,
    *,
    platform: str = "cpu",
    scan: bool = True,
    canary: bool = True,
    sacrificial: Optional[bool] = None,
    timeout_s: float = CANARY_TIMEOUT_S,
    runner: Optional[Callable] = None,
) -> dict:
    """The safe opt-in: scan → canary → backend gate → enable.

    Returns a verdict dict — ``{"enabled": bool, "verdict": one of
    enabled/quarantined_only/canary_*/scan_only, "scan": ...,
    "canary": ..., "cache_dir": ...}``. The invariant callers rely on:
    **this process's jax config points at the cache only when the
    verdict is** ``enabled`` **— which requires a passed canary** (or
    an explicit ``canary=False``). Everything else leaves the config
    untouched.
    """
    cache_dir = cache_dir or default_cache_dir()
    out: dict = {"cache_dir": cache_dir, "enabled": False}
    if platform != "cpu":
        raise ValueError(
            "enable_quarantined_cache is a CPU-world drill: its canary "
            "starts three children that each initialise the backend, and "
            f"a chip belongs to one process at a time (platform={platform!r}"
            "). On the chip use utils.compile_cache.enable_compile_cache."
        )
    out["platform"] = platform
    if scan:
        out["scan"] = scan_cache(cache_dir)
    if not canary:
        out["verdict"] = SCAN_ONLY
        _emit("cache_quarantined", dir=cache_dir, reason=SCAN_ONLY)
        return out
    can = canary_quarantine(
        cache_dir, platform=platform, timeout_s=timeout_s, runner=runner,
    )
    out["canary"] = can
    _emit(
        "cache_canary",
        dir=cache_dir,
        verdict=can["verdict"],
        passed=can["passed"],
        evicted=can.get("evicted", 0),
    )
    if not can["passed"]:
        out["verdict"] = can["verdict"]
        _emit("cache_quarantined", dir=cache_dir, reason=can["verdict"])
        return out
    policy = cache_policy(sacrificial=sacrificial)
    if policy != ENABLED:
        out["verdict"] = policy
        _emit("cache_quarantined", dir=cache_dir, reason=policy)
        return out
    if _enable(cache_dir):
        out["enabled"] = True
        out["verdict"] = ENABLED
        _emit("cache_enabled", dir=cache_dir, platform=platform)
    else:
        out["verdict"] = SCAN_ONLY
        _emit("cache_quarantined", dir=cache_dir, reason="enable_failed")
    return out
