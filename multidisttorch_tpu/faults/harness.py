"""The standard chaos protocol behind ``tools/chaos_run.py``.

Runs the SAME small sweep twice — once clean, once under
:meth:`FaultPlan.standard` with full supervision (retry + ledger +
scan-back restore + driver restart on preemption) — and reports:

- **recovery**: every infra fault in the plan fired and the sweep still
  settled every trial (completed, or diverged where the plan injected
  divergence);
- **goodput**: useful optimizer steps / executed optimizer steps across
  all attempts (fault-free ≡ 1.0). Step-based, not wall-clock-based, so
  the metric measures the *recovery machinery's* overhead — replayed
  epochs, from-scratch lane restarts — rather than CPU recompile noise
  that would swamp a tiny CI-sized model;
- **parity**: for every trial whose faults hit between checkpoints
  (everything except the injected divergence), the final train loss is
  bit-identical to the fault-free run — resume-and-replay is exact.
"""

from __future__ import annotations

import time
from dataclasses import asdict

from multidisttorch_tpu.faults.inject import FaultInjector, HostPreemption
from multidisttorch_tpu.faults.plan import DIVERGE, FaultPlan

MAX_RESTARTS = 8  # driver restarts on preemption; plan-bounded in practice


def standard_configs(trials: int = 6, epochs: int = 4) -> list:
    """The chaos sweep's trial set: tiny VAEs (CI-sized), distinct
    lr/seed per trial so results are distinguishable, quiet logging."""
    from multidisttorch_tpu.hpo.driver import TrialConfig

    return [
        TrialConfig(
            trial_id=i,
            epochs=epochs,
            batch_size=16,
            hidden_dim=32,
            latent_dim=8,
            lr=1e-3 + 1e-4 * i,
            seed=i,
            log_interval=10_000,
        )
        for i in range(trials)
    ]


def _sweep_kwargs(out_dir: str) -> dict:
    return dict(
        num_groups=2,
        out_dir=out_dir,
        verbose=False,
        save_images=False,
    )


def run_chaos_bench(
    work_dir: str,
    *,
    trials: int = 6,
    epochs: int = 4,
    seed: int = 0,
    include_preempt: bool = True,
    data_rows: int = 128,
    stacked: bool = False,
    plan: "FaultPlan | None" = None,
    telemetry_dir: "str | None" = None,
) -> dict:
    """Execute the standard fault schedule and return the report dict.

    ``stacked=True`` runs the chaos sweep in trial-stacking mode
    (lane-recovery drill: 2 groups, K lanes each) — preemption is
    excluded there (a stacked sweep cannot resume, so the restart
    protocol doesn't apply; the unstacked run is the restart drill).

    ``plan`` drills a custom :class:`FaultPlan` verbatim instead of the
    standard schedule (its ``trial_id``s must reference this sweep's
    trials, ``0..trials-1``); the report's recovery/parity/goodput math
    is identical, but the 0.8 goodput acceptance is the STANDARD
    schedule's contract — custom-plan callers decide their own bar.

    The chaos run (never the fault-free reference — its timings stay
    clean) executes under telemetry (docs/OBSERVABILITY.md): events
    stream to ``telemetry_dir`` (default ``{work_dir}/telemetry``), and
    the report's ``telemetry`` block carries the exported Perfetto
    trace/Prometheus/summary paths plus the cross-check that every
    fired fault, scheduled retry, and lane refill appears as a tagged
    event in the trace. The driver-restart loop lives INSIDE the
    telemetry scope, so one timeline spans every preemption restart.
    """
    import os
    import shutil

    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.hpo.driver import run_hpo
    from multidisttorch_tpu.hpo.ledger import SweepLedger
    from multidisttorch_tpu.hpo.supervision import RetryPolicy

    configs = standard_configs(trials, epochs)
    train = synthetic_mnist(data_rows, seed=0)
    steps_per_epoch = data_rows // configs[0].batch_size

    # --- fault-free reference ---------------------------------------
    # Fresh sweep dirs: a stale ledger/checkpoint set from a previous
    # bench invocation would contaminate the restart protocol.
    ff_dir = os.path.join(work_dir, "fault_free")
    for d in (ff_dir, os.path.join(work_dir, "chaos")):
        shutil.rmtree(d, ignore_errors=True)
    t0 = time.time()
    ff_results = run_hpo(
        configs, train, None, **_sweep_kwargs(ff_dir),
        ledger=False, stack_trials=stacked,
    )
    wall_ff = time.time() - t0
    ff_loss = {r.trial_id: r.final_train_loss for r in ff_results}

    # --- chaos run --------------------------------------------------
    custom_plan = plan is not None
    if plan is None:
        plan = FaultPlan.standard(
            [c.trial_id for c in configs],
            seed=seed,
            steps_per_epoch=steps_per_epoch,
            include_preempt=include_preempt and not stacked,
        )
    injector = FaultInjector(plan)
    chaos_dir = os.path.join(work_dir, "chaos")
    retry = RetryPolicy(max_retries=2, backoff_base_s=0.01)
    restarts = 0
    tel_dir = telemetry_dir or os.path.join(work_dir, "telemetry")
    from multidisttorch_tpu import telemetry

    # The chaos drill runs with the anomaly layer armed for capture:
    # the plan's SLOW fault (a 0.2s stall against ~ms steps) must both
    # fire a straggler anomaly AND open a bounded profiler window whose
    # trace lands under {tel_dir}/anomaly_traces — CI uploads it.
    # Thresholds are tightened for the CI-sized sweep (the standard
    # plan's stall lands as early as step ~7 of an 8-step epoch, so the
    # detector must be warm after a handful of marks).
    anomaly_cfg = telemetry.AnomalyConfig(
        window=16,
        min_samples=4,
        z_threshold=4.0,
        min_ratio=3.0,
        cooldown_marks=8,
        capture_steps=10,
        capture_cooldown_s=5.0,
    )

    t0 = time.time()
    with telemetry.telemetry_run(
        tel_dir,
        anomaly=anomaly_cfg,
        anomaly_capture_dir=os.path.join(tel_dir, "anomaly_traces"),
    ):
        while True:
            try:
                results = run_hpo(
                    configs, train, None, **_sweep_kwargs(chaos_dir),
                    resilient=True,
                    retry=retry,
                    fault_plan=injector,
                    resume=restarts > 0,
                    ckpt_keep_last=2,
                    stack_trials=stacked,
                )
                break
            except HostPreemption:
                # The simulated host died mid-sweep. A real deployment
                # restarts the driver process; here the restart reuses
                # the injector (fired faults stay fired) and the
                # on-disk ledger + checkpoints do the rest.
                restarts += 1
                if restarts > MAX_RESTARTS:
                    raise RuntimeError(
                        f"chaos harness: >{MAX_RESTARTS} preemption "
                        "restarts — the plan should bound preemptions; "
                        "supervision is not converging"
                    )
        # Wall clock closes BEFORE the export: the fault-free reference
        # pays no export cost, so wall_ratio must not charge it here.
        wall_chaos = time.time() - t0
        telemetry_report = _export_telemetry(tel_dir, injector)

    # --- accounting -------------------------------------------------
    by_id = {r.trial_id: r for r in results}
    diverge_targets = {
        s.trial_id for s in plan.specs if s.kind == DIVERGE
    }
    # Useful = work embodied in a SETTLED outcome (completed weights or
    # a terminal divergence verdict). A terminally-failed trial's steps
    # are executed-but-wasted: they appear in the denominator via its
    # ledger progress records, never in the numerator.
    useful_steps = sum(
        r.steps
        for r in results
        if r.status in ("completed", "resumed_complete", "diverged")
    )
    executed_steps = _executed_steps(SweepLedger(chaos_dir), useful=results)
    goodput = useful_steps / executed_steps if executed_steps else 0.0

    recovered, parity = [], []
    for cfg in configs:
        r = by_id[cfg.trial_id]
        if cfg.trial_id in diverge_targets:
            recovered.append(
                {"trial_id": cfg.trial_id, "expected": "diverged",
                 "status": r.status, "ok": r.status == "diverged"}
            )
            continue
        bit_identical = r.final_train_loss == ff_loss[cfg.trial_id]
        recovered.append(
            {"trial_id": cfg.trial_id, "expected": "completed",
             "status": r.status,
             "ok": r.status in ("completed", "resumed_complete")}
        )
        parity.append(
            {"trial_id": cfg.trial_id, "attempts": r.attempt,
             "chaos_loss": r.final_train_loss,
             "fault_free_loss": ff_loss[cfg.trial_id],
             "bit_identical": bit_identical}
        )

    all_recovered = all(x["ok"] for x in recovered)
    all_parity = all(x["bit_identical"] for x in parity)
    return {
        "protocol": (
            ("chaos_custom_plan_v1" if custom_plan else "chaos_standard_v1")
            + ("_stacked" if stacked else "")
        ),
        "custom_plan": custom_plan,
        "plan": {"seed": plan.seed, "specs": [asdict(s) for s in plan.specs]},
        "faults_fired": list(injector.fired),
        "restarts_after_preemption": restarts,
        "trials": trials,
        "epochs": epochs,
        "steps_per_epoch": steps_per_epoch,
        "useful_steps": useful_steps,
        "executed_steps": executed_steps,
        "goodput": round(goodput, 4),
        "wall_fault_free_s": round(wall_ff, 3),
        "wall_chaos_s": round(wall_chaos, 3),
        "wall_ratio": round(wall_ff / wall_chaos, 4) if wall_chaos else None,
        "recovered": recovered,
        "all_infra_faults_recovered": all_recovered,
        "final_metrics_bit_identical": all_parity,
        "parity": parity,
        "statuses": {r.trial_id: r.status for r in results},
        "telemetry": telemetry_report,
    }


def _export_telemetry(tel_dir: str, injector: FaultInjector) -> dict:
    """Export the chaos run's trace/metrics/summary and cross-check the
    event stream against the injector's ground truth: every fired fault
    must appear as a tagged ``fault_injected`` event, and the trace must
    carry the sweep's retries and lane refills. Called INSIDE the
    telemetry scope (the registry is still live for the Prometheus
    dump)."""
    import json
    import os

    from multidisttorch_tpu.telemetry import EVENTS_NAME, export, read_events

    events = read_events(os.path.join(tel_dir, EVENTS_NAME))
    paths = export.export_all(tel_dir, events)

    def count(kind: str, **match) -> int:
        n = 0
        for ev in events:
            if ev.get("kind") != kind:
                continue
            data = ev.get("data") or {}
            if all(data.get(k) == v or ev.get(k) == v
                   for k, v in match.items()):
                n += 1
        return n

    fired_traced = all(
        count(
            "fault_injected", fault_kind=rec["kind"],
            trial_id=rec["trial_id"],
        ) > 0
        for rec in injector.fired
    )
    with open(paths["trace"]) as f:
        trace = json.load(f)  # loads == Perfetto-parseable JSON
    # Monotonicity is checked on the RAW event stream (emission order),
    # not the trace — build_trace sorts its output, so checking the
    # trace would pass by construction.
    raw_ts = [float(e.get("ts", 0.0)) for e in events]
    # Device-books acceptance: the exported run summary must carry a
    # per-trial MFU verdict (a float, or an explicit null WITH a
    # reason) and a peak-memory field (null tolerated only where the
    # backend reports no memory stats AND live-buffer accounting
    # failed — 'graceful skip', never a missing key).
    with open(paths["summary"]) as f:
        summary = json.load(f)
    trials = summary.get("trials", {})
    device_books_ok = bool(trials) and all(
        "mfu" in t
        and ("peak_memory_bytes" in t)
        and (t["mfu"] is not None or t.get("mfu_reason"))
        for t in trials.values()
    )
    capture_dirs = [
        (ev.get("data") or {}).get("log_dir")
        for ev in events
        if ev.get("kind") == "profiler_capture_started"
    ]
    return {
        "dir": tel_dir,
        **paths,
        "events_recorded": len(events),
        "faults_fired": len(injector.fired),
        "faults_traced": count("fault_injected"),
        "all_faults_traced": fired_traced,
        "retries_traced": count("retry_scheduled")
        + count("lane_fault", retrying=True),
        "lane_refills_traced": count("lane_refill"),
        "trace_monotonic": raw_ts == sorted(raw_ts)
        and bool(trace.get("traceEvents")),
        "device_books_in_summary": device_books_ok,
        "anomalies_traced": sum(
            1 for ev in events
            if str(ev.get("kind", "")).startswith("anomaly_")
        ),
        "stragglers_traced": count("anomaly_step_straggler"),
        "profiler_captures": [
            d for d in capture_dirs if d and os.path.isdir(d)
        ],
    }


def run_chaos_mh_bench(
    work_dir: str,
    *,
    hosts: int = 3,
    devs_per_host: int = 2,
    trials: int = 6,
    epochs: int = 3,
    kind: str = "host_lost",
    victim: int = 1,
    fault_at_host_step: "int | None" = None,
    groups_mode: str = "per_host",
    data_rows: int = 128,
    heartbeat_deadline_s: float = 3.0,
    agree_timeout_s: float = 15.0,
    world_timeout_s: float = 420.0,
    boot_grace_s: float = 120.0,
) -> dict:
    """The elastic multi-host chaos drill behind
    ``tools/chaos_run.py --multihost`` (docs/RESILIENCE.md
    "Elastic multi-host").

    Kill-one-of-N on CPU: an :class:`~tools.sweep_supervisor.
    ElasticSupervisor` launches ``hosts`` worker processes (the
    framework's own OpenMPI-style detection, ``devs_per_host`` virtual
    CPU devices each, one submesh group per host), a host-scoped fault
    fires on host ``victim`` mid-sweep (``host_lost``: instant
    ``os._exit``, SIGKILL semantics; ``wedge``: the host stalls with
    its heartbeat suspended and the survivors' sync watchdogs must
    exit with a named ``WedgedCollective`` within the deadline), and
    the supervisor re-forms a ``hosts - 1`` world that finishes the
    sweep against the ledger.

    Reported acceptance inputs:

    - **completion**: every trial settles (the survivors absorb the
      victim's trials — ledger-driven migration);
    - **goodput**: useful/executed optimizer steps across all worlds
      and attempts (the single-host chaos bench's step-based metric);
    - **parity**: recovered trials' final losses are bit-identical to
      an in-process fault-free reference — legitimate here because the
      submesh SHAPE survives the shrink (every group is
      ``devs_per_host`` devices before and after), so per-trial math
      is invariant to which host runs it;
    - **watchdog**: for ``kind="wedge"``, at least one survivor exited
      with ``PREEMPTION_EXIT_CODE`` naming ``WedgedCollective``.
    """
    import json
    import os
    import shutil
    import sys

    from multidisttorch_tpu.faults.plan import FaultSpec, HOST_KINDS
    from multidisttorch_tpu.hpo.driver import run_hpo
    from multidisttorch_tpu.hpo.ledger import SweepLedger
    from multidisttorch_tpu.parallel.membership import world_history
    from multidisttorch_tpu.parallel.mesh import setup_groups

    if kind not in HOST_KINDS:
        raise ValueError(f"kind must be one of {sorted(HOST_KINDS)}")

    configs = standard_configs(trials, epochs)
    steps_per_epoch = data_rows // configs[0].batch_size
    if fault_at_host_step is None:
        # Mid-sweep on the victim's cumulative-step clock: past the
        # first epoch boundary (so a checkpoint exists to migrate
        # from), well before its share of the sweep completes.
        fault_at_host_step = steps_per_epoch + steps_per_epoch // 2

    run_dir = os.path.join(work_dir, "mh_chaos")
    ff_dir = os.path.join(work_dir, "mh_fault_free")
    for d in (run_dir, ff_dir):
        shutil.rmtree(d, ignore_errors=True)
    os.makedirs(run_dir, exist_ok=True)

    # --- fault-free reference (in-process, same submesh shape) ------
    # Bit-parity is the contract only where the submesh SHAPE survives
    # the shrink: per-host groups keep every group devs_per_host wide
    # in every world. A spanning-group drill (groups_mode="1", the
    # wedge-watchdog exercise) changes the group width on shrink, so
    # the reduction order — and hence the bits — legitimately differ;
    # parity is skipped there, completion + watchdog are the gates.
    wall_ff = 0.0
    ff_loss: dict = {}
    parity_applicable = groups_mode == "per_host"
    if parity_applicable:
        from multidisttorch_tpu.data.datasets import synthetic_mnist

        import jax

        n_dev = hosts * devs_per_host
        if len(jax.devices()) < n_dev:
            raise RuntimeError(
                f"chaos-mh reference needs {n_dev} local virtual devices, "
                f"found {len(jax.devices())} (set "
                "--xla_force_host_platform_device_count)"
            )
        train = synthetic_mnist(data_rows, seed=0)
        t0 = time.time()
        ff_results = run_hpo(
            configs,
            train,
            None,
            groups=setup_groups(hosts, devices=jax.devices()[:n_dev]),
            out_dir=ff_dir,
            verbose=False,
            save_images=False,
            save_checkpoints=False,
            ledger=False,
        )
        wall_ff = time.time() - t0
        ff_loss = {r.trial_id: r.final_train_loss for r in ff_results}

    # --- the drill --------------------------------------------------
    from multidisttorch_tpu.faults.plan import FaultPlan

    plan = FaultPlan(
        specs=(
            FaultSpec(
                kind,
                trial_id=-1,
                step=int(fault_at_host_step),
                host=int(victim),
                delay_s=600.0 if kind == "wedge" else 0.0,
            ),
        ),
        seed=0,
    )
    with open(os.path.join(run_dir, "fault_plan.json"), "w") as f:
        f.write(plan.to_json())

    # tools/ is not a package: resolve the supervisor/worker by path.
    tools_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        "tools",
    )
    sys.path.insert(0, tools_dir)
    try:
        from sweep_supervisor import ElasticSupervisor
    finally:
        sys.path.remove(tools_dir)

    from multidisttorch_tpu import telemetry

    t0 = time.time()
    with telemetry.telemetry_run(os.path.join(run_dir, "telemetry", "sup")):
        sup = ElasticSupervisor(
            [
                sys.executable,
                os.path.join(tools_dir, "elastic_worker.py"),
                "chaos_sweep",
                run_dir,
            ],
            run_dir,
            hosts,
            devs_per_host=devs_per_host,
            heartbeat_deadline_s=heartbeat_deadline_s,
            boot_grace_s=boot_grace_s,
            world_timeout_s=world_timeout_s,
            env_extra={
                "MDT_MH_TRIALS": str(trials),
                "MDT_MH_EPOCHS": str(epochs),
                "MDT_MH_DATA_ROWS": str(data_rows),
                "MDT_MH_GROUPS": groups_mode,
                "MDT_AGREE_TIMEOUT_S": str(agree_timeout_s),
                "MDT_SYNC_TIMEOUT_S": str(agree_timeout_s),
            },
        )
        sup_report = sup.run()
    wall_chaos = time.time() - t0

    # --- gather the final world's results ---------------------------
    final = sup_report["worlds"][-1]
    merged: dict[int, dict] = {}
    for slot in final["hosts"]:
        path = os.path.join(
            run_dir, f"results-h{slot}-w{final['epoch']}.json"
        )
        if not os.path.exists(path):
            continue
        with open(path) as f:
            rec = json.load(f)
        for tid_s, t in rec.get("trials", {}).items():
            tid = int(tid_s)
            cur = merged.get(tid)
            # Prefer the owner's live record over peers' ledger echoes.
            if cur is None or (
                t["status"] == "completed"
                and cur["status"] != "completed"
            ):
                merged[tid] = t

    settled = {"completed", "resumed_complete", "diverged"}
    useful_steps = sum(
        t["steps"] for t in merged.values() if t["status"] in settled
    )
    # Executed = every step embodied in a settled outcome (including
    # the checkpointed prefix a since-lost host executed — that work
    # happened exactly once, even when its records died with the host)
    # + the recorded progress of failed/preempted/retried attempts
    # beyond their own resume points — including wasted-step totals the
    # supervisor's between-worlds ledger compaction carried into its
    # `compacted` summary records. Work a hard-killed host did PAST its
    # last checkpoint is unobservable and uncounted, so goodput is an
    # upper bound — and <= 1 by construction (executed >= useful).
    from multidisttorch_tpu.hpo.ledger import wasted_steps

    executed_steps = useful_steps + sum(
        wasted_steps(ev) for ev in SweepLedger(run_dir).load()
    )
    goodput = useful_steps / executed_steps if executed_steps else 0.0

    parity = []
    for cfg in (configs if parity_applicable else []):
        t = merged.get(cfg.trial_id)
        if t is None or t["status"] not in ("completed", "resumed_complete"):
            continue
        parity.append(
            {
                "trial_id": cfg.trial_id,
                "chaos_loss": t["final_train_loss"],
                "fault_free_loss": ff_loss[cfg.trial_id],
                "bit_identical": (
                    t["final_train_loss"] == ff_loss[cfg.trial_id]
                ),
            }
        )

    # Watchdog evidence: survivors of a wedged world exit 75 printing
    # the named error; grep the world logs.
    wedged_exits = 0
    for w in sup_report["worlds"]:
        for slot, log in (w.get("logs") or {}).items():
            try:
                with open(log) as f:
                    text = f.read()
            except OSError:
                continue
            if "WedgedCollective" in text:
                wedged_exits += 1

    # Membership telemetry union: every world's worker sinks plus the
    # supervisor's, folded for the traced-events cross-check. The
    # supervisor already exported the merged fleet artifacts on its way
    # out (telemetry/fleet/) — that dir is the merge's OUTPUT, so it is
    # excluded here or every event would count twice.
    from multidisttorch_tpu.telemetry import fleet as fleet_mod
    from multidisttorch_tpu.telemetry.events import read_events

    tel_events = []
    for shard in fleet_mod.discover_shards(run_dir):
        tel_events.extend(read_events(shard))
    kinds = {}
    for ev in tel_events:
        k = str(ev.get("kind", ""))
        kinds[k] = kinds.get(k, 0) + 1

    # --- fleet artifact gates (ISSUE 6) -----------------------------
    # The drill's observability acceptance: ONE merged, skew-corrected
    # timeline spanning every host and world, with the injected fault,
    # the shrink, the migration lineage, and a non-null restart-tax
    # breakdown all present in fleet_summary.json. Re-export here only
    # if the supervisor's own export failed (it is best-effort there).
    fleet_paths = sup_report.get("fleet")
    if not fleet_paths or "error" in fleet_paths:
        fleet_paths = fleet_mod.export_fleet(run_dir)["paths"]
    with open(fleet_paths["summary"]) as f:
        fleet_summary = json.load(f)
    tax = fleet_summary.get("restart_tax") or []
    # Non-null breakdown: every transition carries its three live
    # phases; restore is evidence-joined from the worker streams and
    # must be present for at least one transition (the re-formed world
    # restores from checkpoint by construction of this drill).
    restart_tax_nonnull = bool(tax) and all(
        t.get("detect_s") is not None
        and t.get("drain_s") is not None
        and t.get("relaunch_s") is not None
        for t in tax
    ) and any(t.get("restore_s") is not None for t in tax)
    # fleet.migrated_trials is the one authority on what counts as a
    # migration; the summary carries its verdict
    migrated_in_lineage = len(fleet_summary.get("migrated_trials") or [])
    fleet_block = {
        "paths": fleet_paths,
        "all_hosts_traced": fleet_summary.get("all_hosts_traced"),
        "hosts_seen": fleet_summary.get("hosts_seen"),
        "worlds_in_timeline": len(fleet_summary.get("worlds") or []),
        "world_shrunk_traced": fleet_summary.get("world_shrunk_traced"),
        "all_faults_traced": (
            fleet_summary.get("faults", {}).get("all_faults_traced")
        ),
        "faults_fired": fleet_summary.get("faults", {}).get("fired"),
        "restart_tax": tax,
        "restart_tax_nonnull": restart_tax_nonnull,
        "migrated_trials_in_lineage": migrated_in_lineage,
        "torn_lines_total": fleet_summary.get("torn_lines_total"),
        "goodput": fleet_summary.get("goodput"),
        "skew": fleet_summary.get("skew"),
    }

    all_settled = all(
        merged.get(cfg.trial_id, {}).get("status") in settled
        for cfg in configs
    )
    return {
        "protocol": "chaos_mh_v1",
        "kind": kind,
        "hosts": hosts,
        "devs_per_host": devs_per_host,
        "victim": victim,
        "fault_at_host_step": int(fault_at_host_step),
        "trials": trials,
        "epochs": epochs,
        "steps_per_epoch": steps_per_epoch,
        "plan": json.loads(plan.to_json()),
        "worlds_formed": sup_report["worlds_formed"],
        "hosts_lost": sup_report["hosts_lost"],
        "hosts_final": sup_report["hosts_final"],
        "all_trials_settled": all_settled,
        "statuses": {
            tid: t["status"] for tid, t in sorted(merged.items())
        },
        "useful_steps": useful_steps,
        "executed_steps": executed_steps,
        "goodput": round(goodput, 4),
        "groups_mode": groups_mode,
        "parity_applicable": parity_applicable,
        "parity": parity,
        "recovered_bit_identical": (
            all(p["bit_identical"] for p in parity) and bool(parity)
            if parity_applicable
            else None
        ),
        "wedged_collective_exits": wedged_exits,
        "wall_fault_free_s": round(wall_ff, 3),
        "wall_chaos_s": round(wall_chaos, 3),
        "membership": {
            "worlds": world_history(run_dir),
            "events_traced": kinds,
            "host_lost_traced": kinds.get("host_lost", 0) > 0,
            "world_shrunk_traced": kinds.get("world_shrunk", 0) > 0,
            "trials_migrated_traced": kinds.get("trial_migrated", 0),
        },
        "fleet": fleet_block,
        "supervisor": sup_report,
        "run_dir": run_dir,
    }


def _executed_steps(ledger, useful) -> int:
    """Total optimizer steps executed across every attempt: each
    attempt's (end step − resume step), summed — settled final attempts
    from the results themselves, failed/interrupted attempts from their
    ledger progress records. Terminally-failed results are excluded from
    the result-side sum (their final attempt's work arrives via the
    'failed' event's progress summary; counting the result too would
    double-count it, and its steps are wasted work, not useful)."""
    from multidisttorch_tpu.hpo.ledger import wasted_steps

    total = sum(
        max(0, r.steps - r.resumed_from_step)
        for r in useful
        if r.status in ("completed", "resumed_complete", "diverged")
    )
    # wasted_steps also honors `compacted` summaries, so the accounting
    # survives a ledger compaction between restarts.
    return total + sum(wasted_steps(ev) for ev in ledger.load())
