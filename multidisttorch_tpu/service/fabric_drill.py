"""The service-fabric acceptance drills (docs/SERVICE.md "Service
fabric"; ``tools/chaos_run.py --fabric`` runs the chaos form).

Three phases, one report:

1. **Failover** — two REAL replica subprocesses
   (``tools/sweep_service.py --fabric``) over a 2-shard fabric, each
   owning its home shard. Replica 1 is ``SIGKILL``ed with work placed
   AND outstanding on its shard (a ``kill_exercised``-style gate — a
   run that finished early certifies nothing); replica 0 must observe
   the stale lease, claim the next fencing epoch, ADOPT the orphaned
   shard (journal replay), re-home its ever-placed trials through
   scan-back restore, and settle every submission. Gates: zero lost,
   adoption evidenced in the lease stream (two claimants, ascending
   epochs), and the re-homed trials' final losses BIT-IDENTICAL to an
   undisturbed single-service reference of the same configs.
2. **Deadline preemption** — an in-process service whose pool is full
   of best-effort work (durable checkpoints landed) receives a
   deadline-tagged trial that cannot fit: the best-effort lanes are
   checkpoint-drain PREEMPTED (ledger ``preempted``, requeued), the
   deadline trial places and completes before its deadline, the
   victims resume from checkpoint and still complete, and the
   eviction count respects the anti-thrash budget.
3. **Load generation** — ``service/loadgen.py`` replays N synthetic
   submissions (default 1M; CI runs 100k) against the pure scheduler
   core at simulation speed: p99 placement latency, fairness error vs
   weights <= 10%, deadline hit rate, preemption/defrag churn.

Everything is CPU-honest: the protocol, not the FLOPs, is the subject.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import time
from typing import Optional

from multidisttorch_tpu.service import fabric, queue as squeue

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# Tenants chosen so the 2-shard CRC routing puts them on DIFFERENT
# shards (asserted at drill start — the routing is deterministic, so
# this can never silently rot).
TENANT_SHARD0 = "alpha"
TENANT_SHARD1 = "beta"


def _read_jsonl(path: str) -> list[dict]:
    """Torn-tail-tolerant JSONL read — the queue layer's shared
    complete-lines reader, from offset 0."""
    return squeue.read_jsonl_from(path, 0)[0]


def _final_losses(service_dir: str) -> dict[tuple, float]:
    """(tenant, seed, hidden_dim) -> final_train_loss of the COMPLETED
    attempt, joined across the queue journal (identity) and the sweep
    ledger (losses) of one service/shard directory."""
    folded = squeue.fold_queue(squeue.load_queue(service_dir))
    by_tid = {
        rec["trial_id"]: rec
        for rec in folded.values()
        if rec.get("trial_id") is not None
    }
    out: dict[tuple, float] = {}
    for ev in _read_jsonl(os.path.join(service_dir, "sweep_ledger.jsonl")):
        if ev.get("event") != "attempt_end":
            continue
        if ev.get("status") != "completed":
            continue
        rec = by_tid.get(ev.get("trial_id"))
        if rec is None:
            continue
        cfg = rec.get("config") or {}
        s = ev.get("summary") or {}
        out[(rec["tenant"], cfg.get("seed"), cfg.get("hidden_dim"))] = (
            s.get("final_train_loss")
        )
    return out


def _spawn_replica(
    service_dir: str, replica: int, *, log_path: str, extra=()
):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        )
    env.pop("MDT_TELEMETRY", None)  # replicas configure their own
    env["MDT_HOST_SLOT"] = str(replica)  # per-replica telemetry shard
    argv = [
        sys.executable,
        os.path.join(REPO_ROOT, "tools", "sweep_service.py"),
        service_dir,
        "--fabric",
        "--replica", str(replica),
        "--n-shards", "2",
        "--slices", "2",
        "--max-lanes", "2",
        "--data-rows", "128",
        "--retry", "2",
        "--lease-deadline", "2.0",
        "--exit-when-drained",
        "--idle-grace", "2.0",
        *extra,
    ]
    log_f = open(log_path, "a")
    proc = subprocess.Popen(
        argv, env=env, stdout=log_f, stderr=subprocess.STDOUT, text=True
    )
    return proc, log_f


def run_failover_phase(work_dir: str) -> dict:
    assert fabric.shard_of(TENANT_SHARD0, 2) == 0
    assert fabric.shard_of(TENANT_SHARD1, 2) == 1
    service_dir = os.path.join(work_dir, "fabric_service")
    shutil.rmtree(service_dir, ignore_errors=True)
    os.makedirs(service_dir, exist_ok=True)
    fabric.ensure_fabric_config(service_dir, 2)

    base = dict(batch_size=32, latent_dim=4, log_interval=1000, epochs=3)
    shapes = (16, 24)
    client = fabric.FabricClient(service_dir, n_shards=2)
    subs: dict[str, list[str]] = {TENANT_SHARD0: [], TENANT_SHARD1: []}
    for i in range(6):
        subs[TENANT_SHARD0].append(
            client.submit(
                {**base, "hidden_dim": shapes[i % 2], "seed": i},
                tenant=TENANT_SHARD0,
            )
        )
    for i in range(6):
        subs[TENANT_SHARD1].append(
            client.submit(
                {**base, "hidden_dim": shapes[i % 2], "seed": 100 + i},
                tenant=TENANT_SHARD1,
            )
        )
    all_ids = subs[TENANT_SHARD0] + subs[TENANT_SHARD1]
    shard1_dir = fabric.shard_dir(service_dir, 1)

    log0 = os.path.join(work_dir, "replica0.log")
    log1 = os.path.join(work_dir, "replica1.log")
    p0, f0 = _spawn_replica(service_dir, 0, log_path=log0)
    p1, f1 = _spawn_replica(service_dir, 1, log_path=log1)

    # Kill replica 1 once its shard has BOTH settled work (progress
    # happened) and placed work outstanding (the crash has something
    # to orphan) — otherwise the failover gates certify nothing.
    kill_exercised = False
    killed_at: Optional[dict] = None
    t0 = time.time()
    try:
        while time.time() - t0 < 300:
            folded = squeue.fold_queue(squeue.load_queue(shard1_dir))
            states = [r["state"] for r in folded.values()]
            n_settled = states.count(squeue.SETTLED)
            n_placed = states.count(squeue.PLACED)
            owner = fabric.shard_owner(service_dir, 1)
            if (
                n_settled >= 1
                and n_placed >= 1
                and owner is not None
                and int(owner.get("replica", -1)) == 1
            ):
                killed_at = {"settled": n_settled, "placed": n_placed}
                break
            if p1.poll() is not None:
                break  # finished/died early — gated below
            time.sleep(0.2)
        if p1.poll() is None and killed_at is not None:
            p1.send_signal(signal.SIGKILL)
            kill_exercised = True
        p1.wait(timeout=60)
    finally:
        f1.close()
    kill_exercised = kill_exercised and p1.returncode == -signal.SIGKILL

    # Replica 0 adopts shard 1 (stale lease -> next epoch) and runs
    # everything to completion; --exit-when-drained idles it out only
    # once BOTH shards are quiescent.
    try:
        final = client.wait(all_ids, timeout_s=600.0)
        p0.wait(timeout=120)
    finally:
        try:
            if p0.poll() is None:
                p0.terminate()
                p0.wait(timeout=60)
        except (OSError, subprocess.TimeoutExpired):
            p0.kill()
        f0.close()

    states = {s: r.get("state") for s, r in final.items()}
    lost = sorted(
        s
        for s in all_ids
        if states.get(s) not in (squeue.SETTLED, squeue.REJECTED)
    )
    statuses = {s: r.get("status") for s, r in final.items()}

    # Adoption evidence: the shard-1 lease stream must show replica 1's
    # claim AND replica 0's higher-epoch takeover.
    lease = _read_jsonl(fabric.lease_file(service_dir, 1))
    claims = [
        (int(r.get("epoch", 0)), int(r.get("replica", -1)))
        for r in lease
        if r.get("status") == fabric.CLAIM
    ]
    claimants = {rep for _, rep in claims}
    epochs = [e for e, _ in claims]
    adopted = (
        {0, 1} <= claimants and len(epochs) >= 2
        and epochs == sorted(epochs)
    )

    # Re-homed trials: placed again after the kill (placements >= 2) or
    # journaled unplaced by the adopter's restart recovery.
    folded1 = squeue.fold_queue(squeue.load_queue(shard1_dir))
    rehomed = sorted(
        sid
        for sid, rec in folded1.items()
        if rec.get("placements", 0) >= 2
        or rec.get("unplaced_reason") == "daemon restart recovery"
    )

    # Bit-parity reference: the same configs, undisturbed, one plain
    # single-controller service per shard's tenant set.
    ref_dir = os.path.join(work_dir, "fabric_reference")
    shutil.rmtree(ref_dir, ignore_errors=True)
    ref_losses = _reference_losses(ref_dir, base, shapes)
    got = {}
    for k in range(2):
        got.update(_final_losses(fabric.shard_dir(service_dir, k)))
    compared = 0
    mismatched = []
    for key, ref in ref_losses.items():
        if key in got:
            compared += 1
            if got[key] != ref:
                mismatched.append(
                    {"key": list(key), "got": got[key], "ref": ref}
                )
    rehomed_keys = set()
    for sid in rehomed:
        rec = folded1.get(sid) or {}
        cfg = rec.get("config") or {}
        rehomed_keys.add(
            (rec.get("tenant"), cfg.get("seed"), cfg.get("hidden_dim"))
        )
    rehomed_compared = sum(1 for k in rehomed_keys if k in ref_losses)

    # The adoption story as the replicas told it (telemetry shards).
    events = []
    for p in sorted(
        glob.glob(
            os.path.join(service_dir, "telemetry", "**", "events*.jsonl"),
            recursive=True,
        )
    ):
        events.extend(_read_jsonl(p))
    shard_events = {
        k: sum(1 for e in events if e.get("kind") == k)
        for k in (
            "shard_claimed", "shard_adopted", "shard_fence_lost",
            "shard_released", "replica_start", "replica_end",
        )
    }

    # Trace-completeness drill (docs/OBSERVABILITY.md "Tracing &
    # SLOs"): every settled submission must reconstruct — offline,
    # from the durable shard journals/ledgers alone — as ONE
    # contiguous span tree with zero orphans, and the SIGKILLed
    # shard's re-homed submissions must span BOTH fence epochs.
    from multidisttorch_tpu.telemetry import trace as ttrace

    trace_export = ttrace.export_traces(
        service_dir, os.path.join(work_dir, "fabric_traces")
    )
    completeness = trace_export["completeness"]
    trace_block = {
        "completeness": completeness,
        "exported": {
            k: trace_export[k] for k in ("spans", "perfetto")
        },
        "rehomed_cross_epoch": bool(
            completeness["epoch_takeovers"] >= 1
            and completeness["multi_epoch_submissions"] >= 1
        ),
    }

    return {
        "submissions": len(all_ids),
        "kill_exercised": kill_exercised,
        "killed_at": killed_at,
        "replica_exits": [p0.returncode, p1.returncode],
        "lost_submissions": lost,
        "zero_lost": not lost,
        "statuses": dict(sorted(statuses.items())),
        "completed": sum(
            1 for v in statuses.values() if v == "completed"
        ),
        "shard1_lease_claims": claims,
        "adoption_evident": adopted,
        "rehomed_submissions": rehomed,
        "rehomed_count": len(rehomed),
        "parity": {
            "compared": compared,
            "rehomed_compared": rehomed_compared,
            "mismatched": mismatched,
            "bit_identical": compared > 0 and not mismatched,
        },
        "shard_events": shard_events,
        "trace": trace_block,
        "fabric_health": fabric.fabric_health(service_dir),
        "logs": [log0, log1],
    }


def _reference_losses(ref_dir: str, base: dict, shapes) -> dict:
    """Undisturbed single-service reference run of the SAME configs,
    in-process (CPU submeshes carved the same way — the losses are the
    bitwise anchor the failover run must reproduce)."""
    from multidisttorch_tpu.hpo.supervision import RetryPolicy
    from multidisttorch_tpu.service.runtime import SweepService

    os.makedirs(ref_dir, exist_ok=True)
    client = squeue.SweepClient(ref_dir)
    for tenant, seed0 in ((TENANT_SHARD0, 0), (TENANT_SHARD1, 100)):
        for i in range(6):
            client.submit(
                {**base, "hidden_dim": shapes[i % 2], "seed": seed0 + i},
                tenant=tenant,
            )
    svc = SweepService(
        ref_dir,
        n_slices=2,
        max_lanes=2,
        data_rows=128,
        retry=RetryPolicy(max_retries=2),
    )
    svc.serve(exit_when_drained=True, idle_grace_s=0.5, max_wall_s=600)
    return _final_losses(ref_dir)


def run_fabric_chaos(
    work_dir: str, *, victim: int = 1, step: int = 12, seed: int = 0
) -> dict:
    """The ``daemon_lost`` chaos drill (``tools/chaos_run.py
    --fabric``): same two-replica fabric as the failover phase, but the
    kill comes from INSIDE — a seeded :class:`FaultPlan` whose
    ``daemon_lost`` spec SIGKILLs the victim replica when its
    cumulative dispatch clock reaches ``step`` (the fired record lands
    fsync'd before the kill, so the drill can assert the fault
    actually fired). Both replicas are armed with the SAME plan; the
    spec's ``host`` field names the victim — the host-loss machinery's
    shape exactly."""
    from multidisttorch_tpu.faults.plan import DAEMON_LOST, FaultPlan, FaultSpec

    service_dir = os.path.join(work_dir, "fabric_chaos")
    shutil.rmtree(service_dir, ignore_errors=True)
    os.makedirs(service_dir, exist_ok=True)
    fabric.ensure_fabric_config(service_dir, 2)
    plan = FaultPlan(
        specs=(
            FaultSpec(
                DAEMON_LOST, trial_id=-1, step=int(step), host=int(victim)
            ),
        ),
        seed=seed,
    )
    plan_path = os.path.join(work_dir, "fabric_fault_plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())

    base = dict(batch_size=32, latent_dim=4, log_interval=1000, epochs=3)
    client = fabric.FabricClient(service_dir, n_shards=2)
    ids = []
    for i in range(5):
        ids.append(
            client.submit(
                {**base, "hidden_dim": 16, "seed": i},
                tenant=TENANT_SHARD0,
            )
        )
        ids.append(
            client.submit(
                {**base, "hidden_dim": 24, "seed": 100 + i},
                tenant=TENANT_SHARD1,
            )
        )
    procs = []
    logs = []
    for rep in (0, 1):
        log = os.path.join(work_dir, f"chaos_replica{rep}.log")
        logs.append(log)
        procs.append(
            _spawn_replica(
                service_dir,
                rep,
                log_path=log,
                extra=("--fault-plan", plan_path),
            )
        )
    (p0, f0), (p1, f1) = procs
    vproc = p1 if victim == 1 else p0
    try:
        final = client.wait(ids, timeout_s=600.0)
        vproc.wait(timeout=120)
        p0.wait(timeout=180)
        if p1.poll() is None:
            p1.wait(timeout=180)
    finally:
        for p, f in procs:
            try:
                if p.poll() is None:
                    p.terminate()
                    p.wait(timeout=60)
            except (OSError, subprocess.TimeoutExpired):
                p.kill()
            f.close()

    states = {s: r.get("state") for s, r in final.items()}
    lost = sorted(
        s
        for s in ids
        if states.get(s) not in (squeue.SETTLED, squeue.REJECTED)
    )
    fired = _read_jsonl(
        os.path.join(service_dir, "fabric", f"fired-{victim}.jsonl")
    )
    fired_daemon_lost = [
        r for r in fired if r.get("kind") == DAEMON_LOST
    ]
    lease = _read_jsonl(
        fabric.lease_file(service_dir, 1 if victim == 1 else 0)
    )
    claimants = {
        int(r.get("replica", -1))
        for r in lease
        if r.get("status") == fabric.CLAIM
    }
    survivor = 0 if victim == 1 else 1
    return {
        "plan": json.loads(plan.to_json()),
        "victim": victim,
        "victim_exit": vproc.returncode,
        "victim_sigkilled": vproc.returncode == -signal.SIGKILL,
        "fault_fired": len(fired_daemon_lost) >= 1,
        "fired_records": fired_daemon_lost,
        "lost_submissions": lost,
        "zero_lost": not lost,
        "completed": sum(
            1
            for r in final.values()
            if r.get("status") == "completed"
        ),
        "submissions": len(ids),
        "survivor_claimed_victims_shard": survivor in claimants
        and victim in claimants,
        "fabric_health": fabric.fabric_health(service_dir),
        "logs": logs,
        "ok": bool(
            vproc.returncode == -signal.SIGKILL
            and len(fired_daemon_lost) >= 1
            and not lost
            and survivor in claimants
        ),
    }


def run_deadline_phase(work_dir: str) -> dict:
    from multidisttorch_tpu import telemetry
    from multidisttorch_tpu.hpo.supervision import RetryPolicy
    from multidisttorch_tpu.service.runtime import SweepService
    from multidisttorch_tpu.service.scheduler import PreemptionPolicy

    service_dir = os.path.join(work_dir, "deadline")
    shutil.rmtree(service_dir, ignore_errors=True)
    os.makedirs(service_dir, exist_ok=True)
    tel_dir = os.path.join(service_dir, "telemetry")
    own_telemetry = not telemetry.enabled()
    if own_telemetry:
        telemetry.configure(tel_dir)
    bus = telemetry.get_bus()
    events_path = (
        bus.path
        if bus is not None and bus.path
        else os.path.join(tel_dir, "events.jsonl")
    )
    policy = PreemptionPolicy(
        max_preemptions_per_trial=1,
        trial_cooldown_s=5.0,
        global_cooldown_s=0.05,
    )
    client = squeue.SweepClient(service_dir, tenant="drill")
    base = dict(batch_size=32, latent_dim=4, log_interval=1000)
    svc = SweepService(
        service_dir,
        n_slices=2,
        max_lanes=1,
        data_rows=128,
        defrag_enabled=False,
        preempt=policy,
        retry=RetryPolicy(max_retries=2),
    )
    report: dict = {"ok": False}
    try:
        # Two best-effort whales fill the pool (distinct buckets: no
        # co-pack), then run until each has a DURABLE checkpoint — the
        # preemption primitive refuses to evict unflushed progress.
        be = [
            client.submit({**base, "epochs": 40, "hidden_dim": 16}),
            client.submit({**base, "epochs": 40, "hidden_dim": 24}),
        ]
        t0 = time.time()
        while time.time() - t0 < 120:
            svc.tick()
            if len(svc.active) == 2 and all(
                bool(ap.run.result.checkpoint)
                for ap in svc.active.values()
            ):
                break
        pool_full = svc.pool.free_total == 0

        # The deadline whale: size 2 = the WHOLE pool. It can only
        # place if both best-effort lanes are evicted.
        deadline_s = 120.0
        big = client.submit(
            {**base, "epochs": 1, "hidden_dim": 40, "seed": 9},
            size=2,
            deadline_s=deadline_s,
        )
        submit_ts = time.time()
        while time.time() - submit_ts < 150:
            svc.tick()
            if svc.settled.get(big):
                break
        big_status = svc.settled.get(big)
        big_settle_s = round(time.time() - submit_ts, 3)

        # Victims must come back: resume from their drained checkpoint
        # and complete.
        t0 = time.time()
        while len(svc.settled) < 3 and time.time() - t0 < 600:
            svc.tick()
        svc._drain(reason="drill end")
        books = svc.books()
    finally:
        events = telemetry.read_events(events_path)
        if own_telemetry:
            telemetry.disable()
    pre = [
        e
        for e in events
        if str(e.get("kind", "")).startswith("preempt")
    ]
    kinds = {
        k: sum(1 for e in pre if e["kind"] == k)
        for k in (
            "preempt_start", "preempt_victim", "preempt_end",
            "preempt_blocked",
        )
    }
    victims = [
        (e.get("data") or {})
        for e in pre
        if e["kind"] == "preempt_victim"
    ]
    hits = [e for e in events if e.get("kind") == "deadline_hit"]
    budget_ok = all(
        v.get("preempt_count", 99)
        <= policy.max_preemptions_per_trial
        for v in victims
    ) and len(victims) <= 2 * policy.max_preemptions_per_trial
    report.update(
        {
            "pool_full_before_deadline": pool_full,
            "deadline_submission": big,
            "deadline_s": deadline_s,
            "deadline_status": big_status,
            "settle_latency_s": big_settle_s,
            "completed_before_deadline": bool(
                big_status == "completed" and big_settle_s < deadline_s
            ),
            "preempt_events": kinds,
            "victims": victims,
            "victims_within_budget": budget_ok,
            "deadline_hit_traced": len(hits) >= 1,
            "victims_resumed_and_completed": all(
                s == "completed" for s in svc.settled.values()
            )
            and len(svc.settled) == 3,
            "deadline_books": books.get("deadline"),
            "preemption_books": books.get("preemption"),
            "ok": bool(
                pool_full
                and kinds["preempt_victim"] >= 1
                and big_status == "completed"
                and big_settle_s < deadline_s
                and budget_ok
                and len(hits) >= 1
                and len(svc.settled) == 3
                and all(
                    s == "completed" for s in svc.settled.values()
                )
            ),
        }
    )
    return report


def run_loadgen_phase(n_submissions: int, *, seed: int = 0) -> dict:
    from multidisttorch_tpu.service.loadgen import run_loadgen
    from multidisttorch_tpu.telemetry import ctlprof as _ctlprof

    # The replay runs under the control-plane profiler (armed for the
    # phase if nothing armed one already): the banked report carries
    # per-phase flight books alongside submissions/s, so the
    # ctlprof ledger's baseline rounds come from THIS path.
    own = _ctlprof.get_ctlprof() is None
    prof = _ctlprof.configure() if own else _ctlprof.get_ctlprof()
    try:
        report = run_loadgen(n_submissions=n_submissions, seed=seed)
        report["ctl"] = prof.books()
    finally:
        if own:
            _ctlprof.disable()
    report["gates"] = {
        "zero_lost": report["zero_lost"],
        "fairness_within_10pct": report["fairness"]["within_10pct"],
        "deadline_hit_rate_floor_0.9": (
            report["deadline"]["hit_rate"] is not None
            and report["deadline"]["hit_rate"] >= 0.9
        ),
        "p99_recorded": bool(
            report["placement_latency_s"].get("count")
        ),
        # Offline SLO verdict, exact off the banked full histogram —
        # the scalar-percentile gates above stay as cross-checks.
        "slo_met": report["slo"]["met"],
        "slo_exact": all(
            s.get("exact") for s in report["slo"]["slos"].values()
        ),
    }
    report["ok"] = all(report["gates"].values())
    return report


def _pick_split_tenants(
    parent: int, n_give: int, n_keep: int
) -> tuple[list[str], list[str]]:
    """Deterministic tenant names that route to ``parent`` under the
    2-shard base topology, partitioned by which HALF of the parent's
    hash range a first split would hand to the child — the drill must
    know, before any replica starts, which submissions the handoff
    will move."""
    from multidisttorch_tpu.service import topology as stopo

    topo = stopo.Topology(2)
    _keep, give = topo.split_halves(parent, topo.next_shard_id())
    gives: list[str] = []
    keeps: list[str] = []
    i = 0
    while len(gives) < n_give or len(keeps) < n_keep:
        t = f"split{i}"
        i += 1
        h = stopo.tenant_hash(t)
        if h % 2 != parent:
            continue
        (gives if give.matches(h, 2) else keeps).append(t)
    return gives[:n_give], keeps[:n_keep]


def run_split_chaos(
    work_dir: str, *, victim: int = 1, handoff_step: int = 2, seed: int = 0
) -> dict:
    """The kill-mid-split chaos drill (the PR 17 tentpole's proof): a
    seeded ``shard_split_lost`` fault SIGKILLs the SPLITTING replica
    on its split-handoff clock — strictly between two durable ``moved``
    records, with the topology's ``split_begin`` durable and its
    commit not — leaving the exact seam the protocol exists for: a
    pending split, a half-transferred queue, spool files already in
    the child's intake. The surviving replica must adopt the orphaned
    parent shard, find the evidence, COMPLETE the split (re-run the
    idempotent transfer, append ``split_commit``, birth the child) and
    settle every submission: zero lost, none double-owned, journals
    replaying cleanly across the seam."""
    from multidisttorch_tpu.faults.plan import (
        SHARD_SPLIT_LOST,
        FaultPlan,
        FaultSpec,
    )
    from multidisttorch_tpu.service import topology as stopo

    service_dir = os.path.join(work_dir, "fabric_split")
    shutil.rmtree(service_dir, ignore_errors=True)
    os.makedirs(service_dir, exist_ok=True)
    fabric.ensure_fabric_config(service_dir, 2)
    plan = FaultPlan(
        specs=(
            FaultSpec(
                SHARD_SPLIT_LOST,
                trial_id=-1,
                step=int(handoff_step),
                host=int(victim),
            ),
        ),
        seed=seed,
    )
    plan_path = os.path.join(work_dir, "split_fault_plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())

    # 6 give-half + 2 keep-half submissions on the victim's shard: at
    # kill time (after the 3rd handoff record) give-half work is BOTH
    # already-moved and still-unmoved — the seam has meat on each
    # side. Two more on the survivor's home shard keep it honest
    # about serving while it adopts.
    gives, keeps = _pick_split_tenants(victim, 6, 2)
    survivor = 1 - victim
    surv_tenant = TENANT_SHARD0 if survivor == 0 else TENANT_SHARD1
    base = dict(batch_size=32, latent_dim=4, log_interval=1000, epochs=2)
    client = fabric.FabricClient(service_dir, n_shards=2)
    ids = []
    for i, t in enumerate(gives + keeps):
        ids.append(
            client.submit(
                {**base, "hidden_dim": 16, "seed": i}, tenant=t
            )
        )
    for i in range(2):
        ids.append(
            client.submit(
                {**base, "hidden_dim": 24, "seed": 50 + i},
                tenant=surv_tenant,
            )
        )

    # Only the victim is armed to split (hair trigger: its 8-deep
    # backlog crosses depth 4 immediately); the survivor gets the
    # steal knob instead — once its own shard drains it may lift
    # queued work off the overloaded shard, and the drill's gates
    # must hold regardless of how that race lands.
    procs = []
    logs = []
    for rep in (0, 1):
        log = os.path.join(work_dir, f"split_replica{rep}.log")
        logs.append(log)
        extra = (
            (
                "--split-queue-depth", "4",
                "--split-min-interval", "0.25",
                "--fault-plan", plan_path,
            )
            if rep == victim
            else ("--steal-threshold", "6")
        )
        procs.append(
            _spawn_replica(
                service_dir,
                rep,
                log_path=log,
                extra=("--max-lanes", "1", *extra),
            )
        )
    (p0, f0), (p1, f1) = procs
    vproc = p1 if victim == 1 else p0
    try:
        final = client.wait(ids, timeout_s=600.0)
        vproc.wait(timeout=120)
        for p, _ in procs:
            if p.poll() is None:
                p.wait(timeout=180)
    finally:
        for p, f in procs:
            try:
                if p.poll() is None:
                    p.terminate()
                    p.wait(timeout=60)
            except (OSError, subprocess.TimeoutExpired):
                p.kill()
            f.close()

    states = {s: r.get("state") for s, r in final.items()}
    lost = sorted(
        s
        for s in ids
        if states.get(s) not in (squeue.SETTLED, squeue.REJECTED)
    )
    fired = _read_jsonl(
        os.path.join(service_dir, "fabric", f"fired-{victim}.jsonl")
    )
    fired_split = [r for r in fired if r.get("kind") == SHARD_SPLIT_LOST]

    # The topology log is the drill's flight recorder: the victim's
    # split_begin must be there, and the seam must have CLOSED — a
    # commit (or, if the kill somehow beat every handoff record, an
    # abort), with nothing pending in the folded state.
    events = stopo.load_topology_events(service_dir)
    by_kind = {}
    for ev in events:
        by_kind.setdefault(ev.get("event"), []).append(ev)
    topo = stopo.load_topology(service_dir, n_base=2)
    committed = bool(by_kind.get(stopo.SPLIT_COMMIT))
    live = topo.live_shards()

    # No-double-own, from the durable journals alone: fold EVERY live
    # shard's queue; each submission may have at most one
    # non-superseded record across the fabric (superseded = journaled
    # ``moved`` away, or rejected wrong-shard and retried elsewhere).
    owners: dict[str, list[int]] = {}
    moved_split = 0
    for k in set(live) | {0, 1}:
        sdir = fabric.shard_dir(service_dir, k)
        folded = squeue.fold_queue(squeue.load_queue(sdir))
        for sid, rec in folded.items():
            if (
                rec.get("state") == squeue.MOVED
                and rec.get("moved_kind") == fabric.MOVE_SPLIT
            ):
                moved_split += 1
            if not fabric.FabricClient._superseded(rec):
                owners.setdefault(sid, []).append(k)
    double_owned = sorted(
        sid for sid, ks in owners.items() if len(ks) > 1
    )
    unowned = sorted(s for s in ids if not owners.get(s))

    split_kill_exercised = bool(
        vproc.returncode == -signal.SIGKILL and len(fired_split) >= 1
    )
    report = {
        "plan": json.loads(plan.to_json()),
        "victim": victim,
        "victim_exit": vproc.returncode,
        "split_kill_exercised": split_kill_exercised,
        "fired_records": fired_split,
        "submissions": len(ids),
        "give_tenants": gives,
        "keep_tenants": keeps,
        "lost_submissions": lost,
        "zero_lost": not lost,
        "completed": sum(
            1 for r in final.values() if r.get("status") == "completed"
        ),
        "no_double_own": not double_owned and not unowned,
        "double_owned": double_owned,
        "unowned": unowned,
        "moved_split_records": moved_split,
        "topology": {
            "events": events,
            "log_path": stopo.topology_path(service_dir),
            "epoch": topo.epoch,
            "live_shards": live,
            "committed": committed,
            "aborted": bool(by_kind.get(stopo.SPLIT_ABORT)),
            "seam_closed": not topo.pending,
            "split_begun": bool(by_kind.get(stopo.SPLIT_BEGIN)),
        },
        "fabric_health": fabric.fabric_health(service_dir),
        "logs": logs,
    }
    report["ok"] = bool(
        split_kill_exercised
        and not lost
        and report["no_double_own"]
        and report["topology"]["split_begun"]
        and report["topology"]["seam_closed"]
        and moved_split >= 1
    )
    return report


def _run_movable_arm(
    service_dir: str, submissions: list[dict], *, evict: bool, svc_kw: dict
) -> dict:
    """One in-process service run of ``submissions``: if ``evict``,
    checkpoint-drain the placement mid-flight (the defrag/preemption
    planner's move primitive, called on a placement kind that used to
    be pinned) once it has durable progress, then run everything —
    including the requeued victims — to completion."""
    import contextlib

    from multidisttorch_tpu.hpo.supervision import RetryPolicy
    from multidisttorch_tpu.service.runtime import SweepService

    shutil.rmtree(service_dir, ignore_errors=True)
    os.makedirs(service_dir, exist_ok=True)
    client = squeue.SweepClient(service_dir, tenant="mv")
    for sub in submissions:
        client.submit(dict(sub))
    # The driver narrates retry resumes on stdout; a caller may keep
    # stdout for one JSON line — route the narration to stderr with
    # the rest of the drill diagnostics.
    with contextlib.redirect_stdout(sys.stderr):
        svc = SweepService(
            service_dir,
            data_rows=128,
            defrag_enabled=False,
            retry=RetryPolicy(max_retries=2),
            **svc_kw,
        )
        evicted = False
        requeued = 0
        t0 = time.time()
        while len(svc.settled) < len(submissions) and time.time() - t0 < 600:
            svc.tick()
            if evict and not evicted:
                for ap in list(svc.active.values()):
                    if ap.stacked:
                        ready = any(
                            lane["epochs_done"] >= 1
                            for lane in ap.run.lanes
                        )
                    else:
                        ready = bool(ap.run.result.checkpoint)
                    if ready and ap.movable(svc.snapshot_drain):
                        entries = svc._checkpoint_drain(
                            ap, reason="movable drill eviction"
                        )
                        requeued = len(entries)
                        evicted = True
                        break
        svc._drain(reason="movable drill end")
    statuses = dict(svc.settled)
    return {
        "evicted": evicted,
        "requeued": requeued,
        "statuses": statuses,
        "all_completed": len(statuses) == len(submissions)
        and all(s == "completed" for s in statuses.values()),
        "losses": {
            "|".join(map(str, k)): v
            for k, v in _final_losses(service_dir).items()
        },
    }


def run_movable_phase(work_dir: str) -> dict:
    """Movable stacked buckets and pipelined vectors (the planner's
    ``movable`` set now covers every placement kind): evict each
    mid-flight through the checkpoint-drain primitive — the stacked
    bucket snapshots ALL lanes together at a cooperative round
    boundary, the pipelined vector drains its stage blocks
    all-or-nothing — resume, run to completion, and demand the final
    losses be BIT-IDENTICAL to an undisturbed run of the same
    configs."""
    base = dict(batch_size=32, latent_dim=4, log_interval=1000, epochs=4)
    out: dict = {}
    arms = {
        # Two same-shape trials on a 1-slice pool with 2 lanes: they
        # co-pack into ONE stacked bucket (the only way both run).
        "stacked": (
            [
                {**base, "hidden_dim": 16, "seed": 0},
                {**base, "hidden_dim": 16, "seed": 1},
            ],
            dict(n_slices=1, max_lanes=2),
        ),
        # One 2-stage MPMD pipeline on a 2-slice pool: a vector
        # placement of two stage blocks.
        "pipelined": (
            [{**base, "hidden_dim": 16, "seed": 7, "pipeline_stages": 2}],
            dict(n_slices=2, max_lanes=1),
        ),
    }
    for name, (subs, svc_kw) in arms.items():
        disturbed = _run_movable_arm(
            os.path.join(work_dir, f"movable_{name}"),
            subs,
            evict=True,
            svc_kw=svc_kw,
        )
        reference = _run_movable_arm(
            os.path.join(work_dir, f"movable_{name}_ref"),
            subs,
            evict=False,
            svc_kw=svc_kw,
        )
        mismatched = sorted(
            k
            for k in set(disturbed["losses"]) | set(reference["losses"])
            if disturbed["losses"].get(k) != reference["losses"].get(k)
        )
        out[name] = {
            "evicted": disturbed["evicted"],
            "requeued": disturbed["requeued"],
            "all_completed": disturbed["all_completed"]
            and reference["all_completed"],
            "losses": disturbed["losses"],
            "reference_losses": reference["losses"],
            "mismatched": mismatched,
            "bit_identical": bool(
                disturbed["evicted"]
                and disturbed["all_completed"]
                and reference["all_completed"]
                and len(disturbed["losses"]) == len(subs)
                and not mismatched
            ),
        }
    out["ok"] = all(
        out[n]["bit_identical"] for n in ("stacked", "pipelined")
    )
    return out


def run_scenario_phase(
    n_submissions: Optional[int] = None, *, seed: int = 0
) -> dict:
    """The loadgen scenario zoo over the DYNAMIC topology: every named
    scenario replays twice — the elastic arm (splits + stealing,
    routing through the production topology trie) against the
    static-routing baseline on the identical seeded workload — gated
    on zero-lost / no-double-own and the elastic arm's p99 placement
    latency and deadline hit-rate staying within 10% of the static
    baseline."""
    from multidisttorch_tpu.service.loadgen import (
        FABRIC_SCENARIOS,
        run_fabric_scenario,
    )

    if n_submissions is None:
        n_submissions = int(
            os.environ.get("MDT_FABRIC_SCENARIO_N", "20000") or 20000
        )
    scenarios: dict[str, dict] = {}
    for name in sorted(FABRIC_SCENARIOS):
        rep = run_fabric_scenario(
            name, n_submissions=n_submissions, seed=seed
        )
        rep["ok"] = all(rep["gates"].values())
        scenarios[name] = rep
    return {
        "n_submissions": n_submissions,
        "scenarios": scenarios,
        "ok": all(r["ok"] for r in scenarios.values()),
    }


def run_fabric_bench(
    work_dir: str, *, loadgen_n: Optional[int] = None
) -> dict:
    os.makedirs(work_dir, exist_ok=True)
    if loadgen_n is None:
        loadgen_n = int(
            os.environ.get("MDT_FABRIC_LOADGEN_N", "1000000") or 1000000
        )
    t0 = time.time()
    failover = run_failover_phase(work_dir)
    split_chaos = run_split_chaos(work_dir)
    movable = run_movable_phase(work_dir)
    deadline = run_deadline_phase(work_dir)
    loadgen = run_loadgen_phase(loadgen_n)
    scenarios = run_scenario_phase()
    gates = {
        "kill_exercised": failover["kill_exercised"],
        "zero_lost_submissions": failover["zero_lost"],
        "shard_adopted_by_survivor": failover["adoption_evident"],
        "rehomed_trials_present": failover["rehomed_count"] >= 1,
        "rehomed_bit_identical": failover["parity"]["bit_identical"],
        # Trace completeness (ISSUE 14): every settled submission of
        # the SIGKILL drill reconstructs as one contiguous span tree
        # with zero orphan spans, spanning both fence epochs.
        "trace_complete": failover["trace"]["completeness"]["complete"],
        "trace_cross_epoch": failover["trace"]["rehomed_cross_epoch"],
        # Elastic topology (ISSUE 17): the replica SIGKILLed BETWEEN
        # split-handoff records, the seam closed by the adopter, zero
        # lost, none double-owned; stacked + pipelined placements each
        # evicted-and-resumed bit-identical; the scenario zoo's
        # elastic arm within 10% of static routing.
        "split_kill_exercised": split_chaos["split_kill_exercised"],
        "split_zero_lost": split_chaos["zero_lost"],
        "split_no_double_own": split_chaos["no_double_own"],
        "split_seam_closed": split_chaos["topology"]["seam_closed"],
        "stacked_evict_resume_bit_identical": movable["stacked"][
            "bit_identical"
        ],
        "pipelined_evict_resume_bit_identical": movable["pipelined"][
            "bit_identical"
        ],
        "scenario_gates": scenarios["ok"],
        "deadline_preemption_drill": deadline["ok"],
        "loadgen_gates": loadgen["ok"],
    }
    return {
        "protocol": "fabric_v2",
        "wall_s": round(time.time() - t0, 1),
        "failover": failover,
        "split_chaos": split_chaos,
        "movable": movable,
        "deadline": deadline,
        "loadgen": loadgen,
        "fabric_scenarios": scenarios,
        "gates": gates,
        "ok": all(gates.values()),
    }
