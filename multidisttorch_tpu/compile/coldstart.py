"""Cold-start benchmark: cold vs precompiled vs cache-warm admission.

A CPU-world drill: every mode runs in a fresh child process, and a chip
belongs to one process at a time, so the drill (``python -m
multidisttorch_tpu.compile.coldstart``) is for the ``cpu`` platform
only. Admission cost on the chip is ROADMAP A2.

One fixed multi-bucket sweep — ``len(COLDSTART_HIDDENS)`` shape buckets
(distinct hidden dims), one trial each, one submesh, so every admission
is serialized and visible — is run to completion in FRESH child
processes, one per mode (a child per mode is what makes "cold" honest:
jax's in-process caches cannot leak executables between modes):

- **cold** — no farm, no persistent cache: every admission pays the
  full inline ``lower→compile`` (the pre-PR baseline, now timed and
  attributed by the registry).
- **precompiled** — ``run_hpo(precompile=True)``: the farm compiles all
  four programs on worker threads at entry; the first admission waits
  cooperatively, the rest take finished executables.
- **seed** (measurement-free) — warms the persistent cache directory
  with the sweep's programs and seals the entries (CRC sidecars).
- **cache-warm** — the full subsystem, as a restarted service would
  run it: the quarantined cache path end-to-end (sidecar scan →
  subprocess canary bit-match gate → sacrificial enable — this IS the
  XLA:CPU policy: the warm child is expendable by construction and
  parity-gated below) PLUS the farm, whose workers now deserialize
  from disk instead of compiling — admission cost drops below the
  compile-from-scratch farm's.

Per-trial **admission latency** is ``first_dispatch − attempt_start``
off the child's telemetry stream (setup + compile — the cold-start cost
a sweep-as-a-service front door charges each trial). Gates:

- ``parity``: every trial's final train/test losses BIT-identical
  (float hex) across cold, precompiled, and cache-warm — an executable
  that arrived by farm thread or disk deserialization must be the same
  program, or the whole subsystem is disqualified.
- ``admission_blocked_on_compile`` (farm mode): no admission compiled
  inline on the host loop — every program arrived by registry hit or
  cooperative wait.
- ``speedup_cold_over_precompiled`` ≥ 2 and cache-warm mean below
  precompiled mean (the acceptance targets; recorded either way).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

# The fixed sweep: 4 shape buckets (hidden_dim), one trial per bucket,
# single submesh. Several epochs of training per trial so the farm's
# background compiles genuinely overlap foreground training (the
# service shape: admission cost amortizes against real work, and the
# worker stays ahead of the admission cadence).
COLDSTART_HIDDENS = (64, 96, 128, 160, 192, 224)
COLDSTART_ROWS = 2048
COLDSTART_BATCH = 64
COLDSTART_EPOCHS = 8
CHILD_TIMEOUT_S = int(os.environ.get("MDT_COLDSTART_CHILD_TIMEOUT_S", "600"))


def coldstart_configs():
    from multidisttorch_tpu.hpo.driver import TrialConfig

    return [
        TrialConfig(
            trial_id=i,
            epochs=COLDSTART_EPOCHS,
            batch_size=COLDSTART_BATCH,
            lr=1e-3,
            seed=7,
            hidden_dim=h,
            latent_dim=16,
        )
        for i, h in enumerate(COLDSTART_HIDDENS)
    ]


def _child_main(mode: str, out_dir: str, tel_dir: str, cache_dir: str) -> int:
    """One mode's sweep in THIS (child) process. Prints the result line
    the parent parses; telemetry lands under ``tel_dir``."""
    from multidisttorch_tpu import telemetry
    from multidisttorch_tpu.compile import cache as _cache
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.hpo.driver import run_hpo

    telemetry.configure(tel_dir)
    cache_rec = None
    if mode in ("cold", "farm"):
        # run_hpo turns jax's persistent cache on (utils/compile_cache);
        # these legs exist to measure compiling.
        import jax

        jax.config.update("jax_enable_compilation_cache", False)
    elif mode == "seed":
        # Cache writer: plain enable (this child is sacrificial by
        # role — it exists to populate the dir), then seal what landed.
        _cache._enable(cache_dir)
    elif mode == "warm":
        cache_rec = _cache.enable_quarantined_cache(
            cache_dir, sacrificial=True
        )
    train = synthetic_mnist(COLDSTART_ROWS)
    test = synthetic_mnist(256)
    t0 = time.perf_counter()
    results = run_hpo(
        coldstart_configs(),
        train,
        test,
        num_groups=1,
        out_dir=out_dir,
        save_images=False,
        verbose=False,
        precompile=(mode in ("farm", "warm")),
    )
    wall = time.perf_counter() - t0
    if mode == "seed":
        sealed = _cache.seal_cache(cache_dir)
    else:
        sealed = None
    out = {
        "mode": mode,
        "wall_s": round(wall, 3),
        "sealed": sealed,
        "cache": (
            {
                "enabled": cache_rec["enabled"],
                "verdict": cache_rec["verdict"],
                "scan": cache_rec.get("scan"),
                "canary_passed": bool(
                    (cache_rec.get("canary") or {}).get("passed")
                ),
            }
            if cache_rec is not None
            else None
        ),
        "trials": [
            {
                "trial_id": r.trial_id,
                "status": r.status,
                "steps": r.steps,
                "train_hex": float(r.final_train_loss).hex(),
                "test_hex": float(r.final_test_loss).hex(),
            }
            for r in results
        ],
    }
    print("COLDSTART|" + json.dumps(out))
    return 0


def _run_child(
    mode: str, work_dir: str, cache_dir: str, timeout_s: int
) -> dict:
    tel_dir = os.path.join(work_dir, f"tel_{mode}")
    out_dir = os.path.join(work_dir, f"out_{mode}")
    os.makedirs(tel_dir, exist_ok=True)
    env = dict(os.environ)
    # Each mode configures its own cache explicitly — an inherited
    # cache env would silently warm the cold leg and fake the whole
    # comparison.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if mode in ("farm", "warm"):
        # Pin the farm width for machine-comparable artifacts: two
        # workers overlap each item's init+train compiles, so even
        # trial 0's admission waits on ONE compile wall, not a serial
        # queue (default_workers() would give a 2-core CI box a single
        # worker).
        env.setdefault("MDT_PRECOMPILE_WORKERS", "2")
    if mode == "warm":
        # The cache-warm child is sacrificial BY DECLARATION — the
        # env mark is what licenses deserialized executables on the
        # XLA:CPU quarantined-only policy (compile/cache.py).
        env["MDT_CACHE_SACRIFICIAL"] = "1"
    t0 = time.perf_counter()
    try:
        p = subprocess.run(
            [
                sys.executable,
                "-m",
                "multidisttorch_tpu.compile.coldstart",
                "--child",
                mode,
                "--out",
                out_dir,
                "--tel",
                tel_dir,
                "--cache",
                cache_dir,
            ],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
        )
    except subprocess.TimeoutExpired:
        return {
            "mode": mode,
            "ok": False,
            "error": f"child timed out after {timeout_s}s",
            "tel_dir": tel_dir,
        }
    rec = None
    for line in p.stdout.splitlines():
        if line.startswith("COLDSTART|"):
            try:
                rec = json.loads(line[len("COLDSTART|"):])
            except json.JSONDecodeError:
                rec = None
    if p.returncode != 0 or rec is None:
        return {
            "mode": mode,
            "ok": False,
            "error": (
                f"child rc={p.returncode} "
                "(a crash here in the warm mode is the deserialized-"
                "executable corruption class — quarantine held)"
            ),
            "stderr_tail": p.stderr[-600:],
            "tel_dir": tel_dir,
        }
    rec["ok"] = True
    rec["child_wall_s"] = round(time.perf_counter() - t0, 3)
    rec["tel_dir"] = tel_dir
    return rec


def _fold_admissions(tel_dir: str) -> dict:
    """Per-trial admission latencies + compile books off a child's
    telemetry stream (the run-summary fold, post-hoc)."""
    from multidisttorch_tpu.telemetry.events import EVENTS_NAME, read_events
    from multidisttorch_tpu.telemetry.export import SweepFold

    fold = SweepFold()
    path = os.path.join(tel_dir, EVENTS_NAME)
    for ev in read_events(path):
        fold.feed(ev)
    lat = [
        a["admission_s"]
        for a in fold.admissions
        if a.get("admission_s") is not None
    ]
    return {
        "admissions": fold.admissions,
        "latencies_s": [round(v, 4) for v in lat],
        "mean_admission_s": (
            round(sum(lat) / len(lat), 4) if lat else None
        ),
        "max_admission_s": round(max(lat), 4) if lat else None,
        "compile_books": fold.compile_books,
        "compiles": fold.compiles,
        "compile_s_total": fold.compile_s_total,
        "cache_hits": fold.cache_hits,
        "precompile": fold.precompile,
    }


def run_coldstart_bench(
    work_dir: str, *, timeout_s: int = CHILD_TIMEOUT_S
) -> dict:
    """The full protocol: cold → farm → seed → warm children, folded
    into one artifact dict (see module docstring for the gates)."""
    os.makedirs(work_dir, exist_ok=True)
    cache_dir = os.path.join(work_dir, "xla_cache")
    out: dict = {
        "protocol": "coldstart_v1",
        "buckets": len(COLDSTART_HIDDENS),
        "hidden_dims": list(COLDSTART_HIDDENS),
        "epochs": COLDSTART_EPOCHS,
        "batch_size": COLDSTART_BATCH,
        "rows": COLDSTART_ROWS,
        "modes": {},
    }
    for mode in ("cold", "farm", "seed", "warm"):
        rec = _run_child(mode, work_dir, cache_dir, timeout_s)
        if rec.get("ok") and mode != "seed":
            rec["books"] = _fold_admissions(rec["tel_dir"])
        out["modes"][mode] = rec

    cold = out["modes"]["cold"]
    farm = out["modes"]["farm"]
    warm = out["modes"]["warm"]

    def trials_hex(rec) -> Optional[dict]:
        if not rec.get("ok"):
            return None
        return {
            t["trial_id"]: (t["train_hex"], t["test_hex"], t["status"])
            for t in rec["trials"]
        }

    ref = trials_hex(cold)
    parity = ref is not None
    mismatches = []
    for name, rec in (("farm", farm), ("warm", warm)):
        th = trials_hex(rec)
        if th is None or th != ref:
            parity = False
            mismatches.append(name)
    out["parity"] = parity
    out["parity_mismatches"] = mismatches

    def mean_of(rec) -> Optional[float]:
        return (rec.get("books") or {}).get("mean_admission_s")

    cold_mean, farm_mean, warm_mean = (
        mean_of(cold), mean_of(farm), mean_of(warm),
    )
    out["cold_mean_admission_s"] = cold_mean
    out["precompiled_mean_admission_s"] = farm_mean
    out["cache_warm_mean_admission_s"] = warm_mean
    out["speedup_cold_over_precompiled"] = (
        round(cold_mean / farm_mean, 3)
        if cold_mean and farm_mean
        else None
    )
    out["cache_warm_below_precompiled"] = (
        warm_mean < farm_mean
        if warm_mean is not None and farm_mean is not None
        else None
    )
    # "Admission blocked on XLA" = some trial's program was compiled
    # inline on the host loop (outcome inline, or jit fallback — the
    # implicit first-dispatch compile). With the farm on, every
    # program must arrive by registry hit or cooperative wait.
    farm_adm = (farm.get("books") or {}).get("admissions") or []
    out["admission_blocked_on_compile"] = (
        any(a.get("outcome") in ("inline", "jit") for a in farm_adm)
        if farm.get("ok")
        else None
    )
    out["cache_verdict"] = (warm.get("cache") or {}).get("verdict") if \
        warm.get("ok") else None
    out["passed"] = bool(
        parity
        and out["speedup_cold_over_precompiled"] is not None
        and out["speedup_cold_over_precompiled"] >= 2.0
        and out["admission_blocked_on_compile"] is False
        and out["cache_warm_below_precompiled"] is True
    )
    return out


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="coldstart drill: driver, or with --child one mode's child"
    )
    parser.add_argument("--child", default=None)
    parser.add_argument("--out", default=None)
    parser.add_argument("--tel", default=None)
    parser.add_argument("--cache", default=None)
    parser.add_argument("--work", default=None)
    args = parser.parse_args(argv)
    if args.child:
        return _child_main(args.child, args.out, args.tel, args.cache)
    import tempfile

    work = args.work or tempfile.mkdtemp(prefix="coldstart_")
    print(json.dumps(run_coldstart_bench(work), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
