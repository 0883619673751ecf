#!/usr/bin/env python
"""Chaos drill CLI: run the standard fault schedule against the HPO
driver's supervision stack and report recovery + goodput.

    JAX_PLATFORMS=cpu python tools/chaos_run.py \
        --out results/chaos_cpu.json

Runs entirely on CPU (8 virtual devices) with a CI-sized sweep: every
infra fault in ``FaultPlan.standard`` must be recovered automatically
(retry-with-resume, lane refill, ledger restart after the simulated
preemption), the injected divergence must settle as a terminal
``diverged`` result, and goodput (useful/executed optimizer steps) is
the recovery-overhead headline. The protocol is
``multidisttorch_tpu/faults/harness.py``; ``tests/test_telemetry.py``
and ``tests/test_elastic.py`` assert it at a smaller size.

A custom plan can be drilled with ``--plan my_plan.json`` (the
``FaultPlan.to_json`` format) — see docs/RESILIENCE.md for how to write
one.
"""

import argparse
import json
import os
import sys
import tempfile

# Allow running straight from a checkout (tools/ is not a package).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser(
        description="deterministic fault-injection drill for run_hpo "
        "supervision (see docs/RESILIENCE.md)"
    )
    parser.add_argument(
        "--out", default=None,
        help="write the full JSON report here (default: stdout only)",
    )
    parser.add_argument(
        "--work-dir", default=None,
        help="sweep scratch dir (default: a fresh temp dir)",
    )
    parser.add_argument("--trials", type=int, default=6)
    parser.add_argument("--epochs", type=int, default=4)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--stacked", action="store_true",
        help="drill the trial-stacking path instead (lane fault -> "
        "mask-and-refill recovery; preemption excluded: stacked sweeps "
        "do not resume)",
    )
    parser.add_argument(
        "--no-preempt", action="store_true",
        help="skip the simulated host preemption + driver restart",
    )
    parser.add_argument(
        "--plan", default=None,
        help="drill a custom FaultPlan JSON file (FaultPlan.to_json "
        "format; trial_ids must be 0..trials-1) instead of the "
        "standard schedule. Report-only: the goodput >= 0.8 acceptance "
        "gate applies to the standard schedule only",
    )
    parser.add_argument(
        "--multihost", action="store_true",
        help="run the ELASTIC multi-host drill instead: N worker "
        "processes under tools/sweep_supervisor.py, a host_lost/wedge "
        "fault on one host mid-sweep, supervised world-shrink restart, "
        "ledger-driven trial migration (docs/RESILIENCE.md \"Elastic "
        "multi-host\")",
    )
    parser.add_argument("--mh-hosts", type=int, default=3)
    parser.add_argument("--mh-devs-per-host", type=int, default=2)
    parser.add_argument(
        "--mh-kind", choices=("host_lost", "wedge"), default="host_lost",
        help="the injected host fault: host_lost = instant os._exit "
        "(SIGKILL semantics); wedge = the host stalls with its "
        "heartbeat suspended and survivors must exit with a named "
        "WedgedCollective within the watchdog deadline",
    )
    parser.add_argument("--mh-victim", type=int, default=1)
    parser.add_argument(
        "--fabric", action="store_true",
        help="run the service-fabric failover drill instead: 2 fabric "
        "replica daemons armed with a seeded FaultPlan whose "
        "daemon_lost spec SIGKILLs the victim replica on its dispatch "
        "clock; the survivor must adopt the orphaned shard (lease-"
        "fenced epoch claim + journal replay) and settle every "
        "submission (docs/SERVICE.md \"Service fabric\")",
    )
    parser.add_argument(
        "--fabric-victim", type=int, default=1, choices=(0, 1),
        help="which of the two replicas the daemon_lost spec targets",
    )
    parser.add_argument(
        "--fabric-step", type=int, default=12,
        help="the victim's cumulative dispatch count at which "
        "daemon_lost fires",
    )
    parser.add_argument(
        "--mh-groups", default="per_host",
        help="submesh carve for the drill: 'per_host' (default; "
        "bit-parity applies, and the wedge surfaces at the bounded "
        "end-of-sweep sideband barrier) or an integer group count "
        "(e.g. 1 = one group spanning all hosts — needs a backend "
        "with cross-process XLA computations, i.e. NOT the CPU "
        "backend this tool forces)",
    )
    parser.add_argument(
        "--mh-agree-timeout", type=float, default=15.0,
        help="MDT_AGREE_TIMEOUT_S for the workers: the wedge-watchdog "
        "deadline the WedgedCollective exit is asserted against",
    )
    parser.add_argument(
        "--telemetry-dir", default=None,
        help="write the chaos run's telemetry (events.jsonl, Perfetto "
        "trace.json, metrics.prom, summary.json) here instead of "
        "{work_dir}/telemetry — what CI uploads as artifacts; open the "
        "trace at https://ui.perfetto.dev (docs/OBSERVABILITY.md)",
    )
    args = parser.parse_args()

    # 8 virtual CPU devices (the test harness topology) so 2 submesh
    # groups exist even on a laptop; must land before backend init.
    if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    work_dir = args.work_dir or tempfile.mkdtemp(prefix="chaos_run_")

    if args.fabric:
        from multidisttorch_tpu.service.fabric_drill import (
            run_fabric_chaos,
        )

        report = run_fabric_chaos(
            work_dir,
            victim=args.fabric_victim,
            step=args.fabric_step,
            seed=args.seed,
        )
        headline = {
            "metric": "fabric_chaos_zero_lost_after_daemon_lost",
            "value": 1.0 if report["zero_lost"] else 0.0,
            "unit": "all submissions settled across a SIGKILLed "
            "replica + shard adoption",
            "victim_sigkilled": report["victim_sigkilled"],
            "fault_fired": report["fault_fired"],
            "survivor_claimed_victims_shard": report[
                "survivor_claimed_victims_shard"
            ],
            "completed": report["completed"],
            "submissions": report["submissions"],
            "detail": report,
        }
        print(json.dumps(headline))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            tmp = args.out + ".tmp"
            with open(tmp, "w") as f:
                json.dump(headline, f, indent=2)
            os.replace(tmp, args.out)
            print(f"report written to {args.out}", file=sys.stderr)
        return 0 if report["ok"] else 1

    if args.multihost:
        from multidisttorch_tpu.faults.harness import run_chaos_mh_bench

        report = run_chaos_mh_bench(
            work_dir,
            hosts=args.mh_hosts,
            devs_per_host=args.mh_devs_per_host,
            trials=args.trials,
            epochs=args.epochs,
            kind=args.mh_kind,
            victim=args.mh_victim,
            groups_mode=args.mh_groups,
            agree_timeout_s=args.mh_agree_timeout,
            # Wedge: the survivors' bounded end-of-sweep barrier must
            # trip (the asserted WedgedCollective exit) BEFORE the
            # supervisor's staleness verdict — so the lease deadline is
            # deliberately lazy for that kind.
            heartbeat_deadline_s=45.0 if args.mh_kind == "wedge" else 3.0,
        )
        ok = (
            report["all_trials_settled"]
            and report["goodput"] >= 0.8
            and report["worlds_formed"] >= 2
            and report["hosts_lost"] == [args.mh_victim]
            and (
                report["recovered_bit_identical"] in (True, None)
            )
            # membership telemetry: the shrink is a traced, typed story
            and report["membership"]["host_lost_traced"]
            and report["membership"]["world_shrunk_traced"]
            # the watchdog acceptance: a wedge must surface as a NAMED
            # WedgedCollective exit, never a silent hang/timeout
            and (
                args.mh_kind != "wedge"
                or report["wedged_collective_exits"] >= 1
            )
            # fleet observability gates (ISSUE 6, docs/OBSERVABILITY.md
            # "Fleet"): the merged timeline spans every host, every
            # fired fault appears in it, and the world transition has a
            # non-null restart-tax breakdown. faults_fired >= 1 keeps
            # the cross-check honest: all_faults_traced over an empty
            # (missing/unreadable) fired-log is vacuously true.
            and report["fleet"]["all_hosts_traced"]
            and report["fleet"]["faults_fired"] >= 1
            and report["fleet"]["all_faults_traced"]
            and report["fleet"]["restart_tax_nonnull"]
        )
        headline = {
            "metric": "chaos_mh_goodput_useful_over_executed_steps",
            "value": report["goodput"],
            "unit": "fraction",
            "vs_baseline": round(report["goodput"] / 0.8, 3),
            "kind": args.mh_kind,
            "hosts": f"{args.mh_hosts}->{report['hosts_final']}",
            "all_trials_settled": report["all_trials_settled"],
            "recovered_bit_identical": report["recovered_bit_identical"],
            "wedged_collective_exits": report["wedged_collective_exits"],
            "all_hosts_traced": report["fleet"]["all_hosts_traced"],
            "all_faults_traced": report["fleet"]["all_faults_traced"],
            "restart_tax_nonnull": report["fleet"]["restart_tax_nonnull"],
            "detail": report,
        }
        print(json.dumps(headline))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            tmp = args.out + ".tmp"
            with open(tmp, "w") as f:
                json.dump(headline, f, indent=2)
            os.replace(tmp, args.out)
            print(f"report written to {args.out}", file=sys.stderr)
        return 0 if ok else 1

    from multidisttorch_tpu.faults.harness import run_chaos_bench

    plan = None
    if args.plan is not None:
        from multidisttorch_tpu.faults.plan import FaultPlan

        with open(args.plan) as f:
            plan = FaultPlan.from_json(f.read())
        bad_ids = {
            s.trial_id for s in plan.specs
        } - set(range(args.trials))
        if bad_ids:
            parser.error(
                f"--plan targets trial ids {sorted(bad_ids)} outside this "
                f"sweep's 0..{args.trials - 1} (adjust --trials or the plan)"
            )

    report = run_chaos_bench(
        work_dir,
        trials=args.trials,
        epochs=args.epochs,
        seed=args.seed,
        include_preempt=not args.no_preempt,
        stacked=args.stacked,
        plan=plan,
        telemetry_dir=args.telemetry_dir,
    )

    tel = report.get("telemetry") or {}
    ok = (
        report["all_infra_faults_recovered"]
        and report["final_metrics_bit_identical"]
        # the goodput bar is the STANDARD schedule's acceptance; a
        # custom plan is report-only there (its author owns the bar)
        and (plan is not None or report["goodput"] >= 0.8)
        # the observability acceptance: every fired fault appears as a
        # tagged event in a monotonic, Perfetto-loadable trace
        and tel.get("all_faults_traced", False)
        and tel.get("trace_monotonic", False)
        # the device-books acceptance (ISSUE 4): the exported summary
        # carries per-trial MFU (or explicit null-with-reason) and
        # peak-memory fields
        and tel.get("device_books_in_summary", False)
    )
    headline = {
        "metric": "chaos_goodput_useful_over_executed_steps",
        "value": report["goodput"],
        "unit": "fraction",
        "vs_baseline": round(report["goodput"] / 0.8, 3),
        "all_infra_faults_recovered": report["all_infra_faults_recovered"],
        "final_metrics_bit_identical": report["final_metrics_bit_identical"],
        "restarts_after_preemption": report["restarts_after_preemption"],
        "telemetry_trace": tel.get("trace"),
        "all_faults_traced": tel.get("all_faults_traced"),
        "device_books_in_summary": tel.get("device_books_in_summary"),
        "anomalies_traced": tel.get("anomalies_traced"),
        "profiler_captures": tel.get("profiler_captures"),
        "detail": report,
    }
    print(json.dumps(headline))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(headline, f, indent=2)
        os.replace(tmp, args.out)
        print(f"report written to {args.out}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
