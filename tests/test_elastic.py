"""Elastic multi-host survival layer: membership heartbeats, the wedge
watchdog's exit-code contract, supervised world-shrink restart, ledger
compaction, decorrelated retry jitter, and the SIGTERM graceful drain.

Fast tests run in-process (membership and supervisor logic are plain
files + subprocesses — no device runtime); the true multi-controller
drills (kill-one-of-N, wedge -> WedgedCollective, cross-host restore
agreement) are ``multihost``-marked subprocess worlds like
tests/test_multihost.py's.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO, "tools")


# --------------------------------------------------------------------
# membership: lease files, staleness, torn tails
# --------------------------------------------------------------------


def test_heartbeat_writes_and_stops_cleanly(tmp_path):
    from multidisttorch_tpu.parallel import membership as m

    hb = m.Heartbeat(str(tmp_path), 3, interval_s=0.02, world_epoch=1,
                     world_size=2).start()
    time.sleep(0.15)
    hb.stop()
    recs = m.read_lease(m.lease_path(str(tmp_path), 3))
    assert len(recs) >= 3  # immediate beat + interval beats + final
    assert recs[0]["status"] == "alive" and recs[-1]["status"] == "left"
    assert all(r["slot"] == 3 and r["world_epoch"] == 1 for r in recs)
    assert [r["seq"] for r in recs] == sorted(r["seq"] for r in recs)


def test_lease_read_tolerates_torn_tail(tmp_path):
    from multidisttorch_tpu.parallel import membership as m

    path = m.lease_path(str(tmp_path), 0)
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as f:
        f.write(json.dumps({"slot": 0, "ts": 1.0, "status": "alive"}) + "\n")
        f.write('{"slot": 0, "ts": 2.0, "stat')  # torn mid-append
    recs = m.read_lease(path)
    assert len(recs) == 1 and recs[0]["ts"] == 1.0


def test_lost_hosts_stale_vs_fresh_vs_left(tmp_path):
    from multidisttorch_tpu.parallel import membership as m

    now = time.time()

    def write(slot, ts, status="alive"):
        path = m.lease_path(str(tmp_path), slot)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(
                {"slot": slot, "ts": ts, "status": status}) + "\n")

    write(0, now)            # fresh: alive
    write(1, now - 10.0)     # stale: lost
    write(2, now - 10.0, status="left")  # clean departure: not lost
    view = m.MembershipView(str(tmp_path))
    assert view.lost_hosts(3.0, now=now) == [1]
    assert view.lost_hosts(3.0, now=now, among=[0, 2]) == []
    assert set(view.hosts()) == {0, 1, 2}


def test_heartbeat_suspend_goes_stale(tmp_path):
    from multidisttorch_tpu.parallel import membership as m

    hb = m.start_heartbeat(str(tmp_path), 0, interval_s=0.02)
    try:
        time.sleep(0.1)
        assert m.suspend_heartbeat()
        rec = m.latest_lease(m.lease_path(str(tmp_path), 0))
        time.sleep(0.15)
        rec2 = m.latest_lease(m.lease_path(str(tmp_path), 0))
        # suspended: no new beats; the lease ages toward lost
        assert rec2["seq"] == rec["seq"]
    finally:
        m.stop_heartbeat()


def test_world_history_roundtrip(tmp_path):
    from multidisttorch_tpu.parallel import membership as m

    m.record_world(str(tmp_path), epoch=0, hosts=[0, 1, 2])
    m.record_world(str(tmp_path), epoch=1, hosts=[0, 2], lost=[1],
                   reason="host_lost")
    hist = m.world_history(str(tmp_path))
    assert [w["epoch"] for w in hist] == [0, 1]
    assert hist[1]["lost"] == [1] and hist[1]["hosts"] == [0, 2]


# --------------------------------------------------------------------
# watchdog: WedgedCollective, exit codes, daemon regression
# --------------------------------------------------------------------


def test_wedged_collective_is_preemption_class():
    from multidisttorch_tpu.hpo.supervision import (
        PREEMPTION,
        classify_failure,
        exit_code_for,
    )
    from multidisttorch_tpu.parallel.cluster import (
        PREEMPTION_EXIT_CODE,
        AgreementTimeout,
        WedgedCollective,
    )

    exc = WedgedCollective("epoch sync wedged")
    assert isinstance(exc, AgreementTimeout)  # back-compat catch sites
    assert classify_failure(exc) == PREEMPTION
    assert exit_code_for(exc) == PREEMPTION_EXIT_CODE
    assert exit_code_for(RuntimeError("boom")) == 1


def test_call_with_timeout_error_cls_and_daemon_leak_regression():
    from multidisttorch_tpu.parallel.cluster import (
        AgreementTimeout,
        WedgedCollective,
        call_with_timeout,
    )

    release = threading.Event()

    def blocked():
        release.wait(30)

    before = set(threading.enumerate())
    with pytest.raises(WedgedCollective):
        call_with_timeout(
            blocked, 0.05, "test sync", error_cls=WedgedCollective
        )
    # The abandoned runner thread MUST be a daemon: a non-daemon leak
    # would make interpreter shutdown join a blocked thread forever.
    leaked = [
        t for t in set(threading.enumerate()) - before
        if t.name.startswith("watchdog:")
    ]
    assert leaked, "watchdog runner not found"
    assert all(t.daemon for t in leaked)
    # default error type unchanged
    with pytest.raises(AgreementTimeout):
        call_with_timeout(blocked, 0.05, "test sync")
    release.set()


def test_group_min_scalar_on_mesh_single_process():
    # The on-mesh value-agreement sibling of group_all_ok (the
    # recovery path uses the sideband agree_min_int instead).
    from multidisttorch_tpu.parallel.collectives import group_min_scalar
    from multidisttorch_tpu.parallel.mesh import setup_groups

    g0, _g1 = setup_groups(2)
    assert group_min_scalar(g0, 7) == 7
    assert group_min_scalar(g0, 0, what="zero") == 0


def test_agree_min_int_single_process_identity():
    from multidisttorch_tpu.parallel.cluster import agree_min_int

    assert agree_min_int(
        "t", 5, [0], timeout_s=1.0, what="solo"
    ) == 5


# --------------------------------------------------------------------
# decorrelated retry jitter
# --------------------------------------------------------------------


def test_backoff_without_jitter_is_bitwise_stable():
    from multidisttorch_tpu.hpo.supervision import RetryPolicy

    p = RetryPolicy(backoff_base_s=0.05, backoff_factor=2.0,
                    backoff_max_s=30.0)
    assert p.backoff_s(1) == 0.05
    assert p.backoff_s(2) == 0.1
    assert p.backoff_s(3, key=17) == 0.2  # key ignored when jitter off


def test_jitter_deterministic_decorrelated_bounded():
    from multidisttorch_tpu.hpo.supervision import RetryPolicy

    p = RetryPolicy(
        max_retries=5, backoff_base_s=0.05, backoff_max_s=2.0,
        jitter=True, jitter_seed=42,
    )
    # deterministic under (seed, key, retry_number)
    for k in (1, 2, 3):
        assert p.backoff_s(k, key=7) == p.backoff_s(k, key=7)
    # decorrelated across keys: N lanes felled together back off apart
    delays = {key: p.backoff_s(1, key=key) for key in range(8)}
    assert len(set(delays.values())) > 4
    # bounded: [base, max] always
    for key in range(8):
        for k in (1, 2, 3, 4, 5):
            d = p.backoff_s(k, key=key)
            assert p.backoff_base_s <= d <= p.backoff_max_s
    # a different seed reshuffles the schedule
    q = RetryPolicy(
        max_retries=5, backoff_base_s=0.05, backoff_max_s=2.0,
        jitter=True, jitter_seed=43,
    )
    assert any(
        p.backoff_s(1, key=key) != q.backoff_s(1, key=key)
        for key in range(8)
    )


# --------------------------------------------------------------------
# ledger compaction
# --------------------------------------------------------------------


def _storm_ledger(tmp_path, hashes=3, rounds=7):
    """Synthesize a restart storm: per config hash, `rounds` attempts
    of preempted/retrying churn, the first hash settling at the end."""
    from multidisttorch_tpu.hpo.ledger import SweepLedger

    led = SweepLedger(str(tmp_path))
    for h_i in range(hashes):
        h = f"hash-{h_i:02d}"
        for a in range(1, rounds + 1):
            led.attempt_start(h_i, h, a)
            status = "retrying" if a % 2 else "preempted"
            led.attempt_end(
                h_i, h, a, status, error="storm",
                summary={"steps_at_failure": 4 * a,
                         "resumed_from_step": 0},
            )
        if h_i == 0:
            led.attempt_start(h_i, h, rounds + 1)
            led.attempt_end(
                h_i, h, rounds + 1, "completed",
                summary={"steps": 40, "resumed_from_step": 0},
            )
    return led


def test_compact_preserves_restart_folds_and_shrinks(tmp_path):
    led = _storm_ledger(tmp_path)
    finished0 = {h: r["status"] for h, r in led.finished().items()}
    attempts0 = led.attempts()
    infra0 = led.infra_failures()
    before = len(led.load())
    stats = led.compact()
    assert stats["lines_before"] == before
    assert stats["lines_after"] < before  # the storm actually shrank
    assert {h: r["status"] for h, r in led.finished().items()} == finished0
    assert led.attempts() == attempts0
    assert led.infra_failures() == infra0
    # compaction is stable: a second pass changes nothing semantic
    led.compact()
    assert led.attempts() == attempts0
    assert led.infra_failures() == infra0


def test_compact_tolerates_torn_tail_and_is_atomic(tmp_path):
    led = _storm_ledger(tmp_path)
    with open(led.path, "a") as f:
        f.write('{"event": "attempt_start", "config')  # torn
    attempts0 = led.attempts()
    led.compact()
    assert led.attempts() == attempts0
    # no stray tmp file left behind
    assert not os.path.exists(led.path + ".tmp")


def test_compact_respects_write_gate(tmp_path):
    from multidisttorch_tpu.hpo.ledger import SweepLedger

    led = _storm_ledger(tmp_path)
    n = len(led.load())
    reader = SweepLedger(str(tmp_path), write=False)
    assert reader.compact() == {
        "lines_before": 0, "lines_after": 0, "hashes": 0,
    }
    assert len(led.load()) == n  # untouched


def test_resumed_sweep_skips_settled_after_compaction(tmp_path):
    # End-to-end: settle a sweep, compact, resume — the compacted
    # ledger must still drive the skip.
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.hpo.driver import TrialConfig, run_hpo
    from multidisttorch_tpu.hpo.ledger import SweepLedger

    train = synthetic_mnist(64, seed=0)
    cfgs = [
        TrialConfig(i, epochs=1, batch_size=16, hidden_dim=16,
                    latent_dim=4, seed=i)
        for i in range(2)
    ]
    kw = dict(num_groups=2, out_dir=str(tmp_path), verbose=False,
              save_images=False, save_checkpoints=False)
    rs = run_hpo(cfgs, train, None, **kw)
    assert all(r.status == "completed" for r in rs)
    SweepLedger(str(tmp_path)).compact()
    rs2 = run_hpo(cfgs, train, None, resume=True, **kw)
    assert all(r.status == "resumed_complete" for r in rs2)


def test_ledger_view_compact_cli(tmp_path):
    _storm_ledger(tmp_path)
    sys.path.insert(0, _TOOLS)
    try:
        import ledger_view
    finally:
        sys.path.remove(_TOOLS)
    assert ledger_view.main(["--compact", str(tmp_path)]) == 0
    assert ledger_view.main(["--json", str(tmp_path)]) == 0


# --------------------------------------------------------------------
# host-scoped fault kinds
# --------------------------------------------------------------------


def test_fault_spec_host_kinds_validation():
    from multidisttorch_tpu.faults.plan import (
        HOST_LOST,
        WEDGE,
        FaultPlan,
        FaultSpec,
    )

    spec = FaultSpec(HOST_LOST, trial_id=-1, step=12, host=1)
    assert spec.host == 1
    with pytest.raises(ValueError, match="host"):
        FaultSpec(WEDGE, trial_id=-1, step=3)  # host missing
    with pytest.raises(ValueError, match="step"):
        FaultSpec(HOST_LOST, trial_id=-1, host=1)  # step missing
    # JSON round-trip carries the host field
    plan = FaultPlan(specs=(spec,), seed=3)
    assert FaultPlan.from_json(plan.to_json()).specs[0].host == 1


def test_injector_host_lost_fires_on_cumulative_clock(monkeypatch):
    from multidisttorch_tpu.faults import inject
    from multidisttorch_tpu.faults.plan import HOST_LOST, FaultPlan, FaultSpec

    exits = []
    monkeypatch.setattr(inject.os, "_exit", lambda code: exits.append(code))
    plan = FaultPlan(
        specs=(FaultSpec(HOST_LOST, trial_id=-1, step=10, host=2),)
    )
    inj = inject.FaultInjector(plan, host_slot=2)
    # trial steps don't matter; the HOST clock does (any trial's hook)
    inj.step_hook(0, 0, 4)   # host steps 0..4
    inj.step_hook(1, 0, 4)   # 4..8
    assert not exits
    inj.step_hook(0, 4, 4)   # 8..12 covers step 10 -> fires
    assert exits == [inject.HOST_LOST_EXIT_CODE]
    # wrong slot never fires
    inj2 = inject.FaultInjector(plan, host_slot=0)
    inj2.step_hook(0, 0, 100)
    assert len(exits) == 1
    # no slot (single-controller) never fires
    inj3 = inject.FaultInjector(plan)
    inj3.step_hook(0, 0, 100)
    assert len(exits) == 1


def test_injector_wedge_suspends_heartbeat_then_preempts(tmp_path):
    from multidisttorch_tpu.faults import inject
    from multidisttorch_tpu.faults.plan import WEDGE, FaultPlan, FaultSpec
    from multidisttorch_tpu.parallel import membership as m

    hb = m.start_heartbeat(str(tmp_path), 1, interval_s=0.02)
    try:
        plan = FaultPlan(
            specs=(
                FaultSpec(WEDGE, trial_id=-1, step=0, host=1,
                          delay_s=0.05),
            )
        )
        inj = inject.FaultInjector(plan, host_slot=1)
        with pytest.raises(inject.HostPreemption, match="wedge"):
            inj.step_hook(0, 0, 1)
        assert hb._suspended.is_set()
        assert inj.fired and inj.fired[0]["kind"] == WEDGE
    finally:
        m.stop_heartbeat()


def test_injector_fired_log_survives_restart(tmp_path):
    from multidisttorch_tpu.faults import inject
    from multidisttorch_tpu.faults.plan import CRASH, FaultPlan, FaultSpec

    log = str(tmp_path / "fired.jsonl")
    plan = FaultPlan(specs=(FaultSpec(CRASH, trial_id=0, step=5),))
    inj = inject.FaultInjector(plan, fired_log=log)
    with pytest.raises(inject.InjectedCrash):
        inj.step_hook(0, 5, 1)
    # a "restarted host" builds a fresh injector over the same log:
    # the one-shot fault must stay fired
    inj2 = inject.FaultInjector(plan, fired_log=log)
    inj2.step_hook(0, 5, 1)  # no raise
    assert inj2.fired == []  # nothing new fired


# --------------------------------------------------------------------
# supervisor (fast: fake no-device workers)
# --------------------------------------------------------------------

_FAKE_WORKER = textwrap.dedent(
    """
    import os, signal, sys, time
    sys.path.insert(0, {repo!r})
    from multidisttorch_tpu.parallel import membership

    slot = int(os.environ["MDT_HOST_SLOT"])
    epoch = int(os.environ["MDT_WORLD_EPOCH"])
    run_dir = os.environ["MDT_ELASTIC_RUN_DIR"]
    membership.start_heartbeat(
        run_dir, slot, interval_s=0.05, world_epoch=epoch,
        world_size=int(os.environ["OMPI_COMM_WORLD_SIZE"]),
    )

    def on_term(sig, frame):
        membership.stop_heartbeat()
        sys.exit(75)  # the drain contract: healthy host, lost world

    signal.signal(signal.SIGTERM, on_term)

    if epoch == 0:
        if slot == 1:
            time.sleep(0.6)
            os._exit(86)  # hard host loss (SIGKILL semantics)
        while True:
            time.sleep(0.05)  # train forever; supervisor drains us
    else:
        time.sleep(0.4)  # the shrunken world finishes the sweep
        membership.stop_heartbeat()
        sys.exit(0)
    """
)


def test_supervisor_shrinks_world_on_hard_host_loss(tmp_path):
    sys.path.insert(0, _TOOLS)
    try:
        from sweep_supervisor import ElasticSupervisor
    finally:
        sys.path.remove(_TOOLS)

    worker = tmp_path / "fake_worker.py"
    worker.write_text(_FAKE_WORKER.format(repo=_REPO))
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    sup = ElasticSupervisor(
        [sys.executable, str(worker)],
        run_dir,
        3,
        heartbeat_deadline_s=1.0,
        poll_s=0.05,
        boot_grace_s=10.0,
        drain_grace_s=5.0,
        world_timeout_s=60.0,
        compact_ledger=False,  # no ledger in the fake sweep
    )
    report = sup.run()
    assert report["success"]
    assert report["worlds_formed"] == 2
    assert report["hosts_lost"] == [1]
    assert report["worlds"][0]["outcome"] == "host_lost"
    # The worlds are N processes on ONE host: a CPU-world drill. N
    # workers cannot share the host's chips, so a platform other than
    # cpu is refused up front instead of hanging them on the device.
    sup.env_extra = {"JAX_PLATFORMS": "tpu"}
    with pytest.raises(SystemExit, match="CPU-world drill"):
        sup._launch_world(0, [0, 1])
    sup._refuse_shared_chips(1)  # one worker may hold the chips
    assert report["worlds"][1]["outcome"] == "complete"
    assert report["worlds"][1]["hosts"] == [0, 2]
    # survivors were drained, not blamed: their exits are 75/terms
    w0 = report["worlds"][0]["exits"]
    assert w0[1] not in (0, 75)
    # the durable world history matches the report
    from multidisttorch_tpu.parallel.membership import world_history

    hist = world_history(run_dir)
    assert [w["epoch"] for w in hist] == [0, 1]
    assert hist[1]["lost"] == [1]


# --------------------------------------------------------------------
# SIGTERM graceful drain (subprocess; single-host, so tier-1-fast)
# --------------------------------------------------------------------

_DRAIN_WORKER = os.path.join(os.path.dirname(__file__), "drain_worker.py")


@pytest.mark.chaos
def test_sigterm_drain_preemption_exit_and_bounded_loss(tmp_path):
    from multidisttorch_tpu.hpo.ledger import SweepLedger
    from multidisttorch_tpu.parallel.cluster import PREEMPTION_EXIT_CODE

    out_dir = str(tmp_path / "sweep")
    env = dict(os.environ)
    p = subprocess.Popen(
        [sys.executable, _DRAIN_WORKER, out_dir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    # Wait for epoch >= 2 to be durably checkpointed, then SIGTERM.
    meta_path = os.path.join(out_dir, "trial-0", "state.msgpack.json")
    deadline = time.time() + 180
    killed = False
    while time.time() < deadline and p.poll() is None:
        try:
            with open(meta_path) as f:
                meta = json.load(f)
            if int(meta.get("completed_epochs", 0)) >= 2:
                p.send_signal(signal.SIGTERM)
                killed = True
                break
        except (OSError, ValueError):
            pass
        time.sleep(0.01)
    assert killed, "sweep finished before the drain could be exercised"
    out = p.communicate(timeout=120)[0]
    assert p.returncode == PREEMPTION_EXIT_CODE, out[-2000:]
    assert "HostPreemption" in out and "graceful drain" in out, out[-2000:]

    # The drain recorded the in-flight attempt (fsync'd ledger).
    led = SweepLedger(out_dir)
    pre = [
        ev for ev in led.load()
        if ev.get("event") == "attempt_end"
        and ev.get("status") == "preempted"
    ]
    assert pre and "graceful drain" in pre[-1]["error"]
    steps_at_kill = int(pre[-1]["summary"]["steps_at_failure"])

    # Resume: completes, and lost work <= one checkpoint cadence.
    p2 = subprocess.run(
        [sys.executable, _DRAIN_WORKER, out_dir, "resume"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=300,
    )
    assert p2.returncode == 0, p2.stdout[-2000:]
    line = [l for l in p2.stdout.splitlines() if l.startswith("RESULT ")]
    res = json.loads(line[-1][len("RESULT "):])
    assert res["status"] == "completed"
    steps_per_epoch = 1024 // 32
    assert res["resumed_from_step"] >= steps_per_epoch  # real resume
    assert steps_at_kill - res["resumed_from_step"] <= steps_per_epoch


# --------------------------------------------------------------------
# true multi-controller elastic drills (subprocess worlds; slow tier)
# --------------------------------------------------------------------


def _launch_mh(mode, tmp_path, *, nprocs=2, devs_per_proc=4, timeout=420,
               extra_env=None):
    import test_multihost  # same-directory import (pytest rootdir path)

    return test_multihost._launch(
        mode, tmp_path, nprocs=nprocs, devs_per_proc=devs_per_proc,
        timeout=timeout, extra_env=extra_env,
    )


@pytest.mark.multihost
def test_cross_host_restore_agreement_min_step(tmp_path):
    # A real 2-process world over a real keep-last checkpoint lineage
    # (steps 4 and 8). With process 1's VIEW of the newest candidate
    # torn, BOTH processes must agree on the earlier step 4 — without
    # the agreement, process 0 would restore step 8 and desync SPMD.
    # Healthy views agree on 8; a host seeing nothing valid degrades
    # both to scratch; and a participant that never joins produces a
    # NAMED WedgedCollective within the deadline (no hang).
    r0, r1 = _launch_mh("elastic_restore_agree", tmp_path)
    assert r0["torn_agreed"] == r1["torn_agreed"] == 4
    assert r0["healthy_agreed"] == r1["healthy_agreed"] == 8
    assert r0["none_agreed"] is None and r1["none_agreed"] is None
    assert r0["wedge"] == "WedgedCollective"
    assert r0["wedge_wait_s"] < 10  # bounded by the 2s deadline + slop


@pytest.mark.multihost
def test_elastic_drill_host_lost_three_hosts(tmp_path):
    # The kill-one-of-3 drill end-to-end through the real harness:
    # host 1 dies mid-sweep (os._exit, heartbeat and all), the
    # supervisor re-forms a 2-host world, the survivors finish every
    # trial, recovered results bit-match the fault-free reference.
    from multidisttorch_tpu.faults.harness import run_chaos_mh_bench

    report = run_chaos_mh_bench(
        str(tmp_path),
        hosts=3,
        devs_per_host=2,
        trials=4,
        epochs=2,
        kind="host_lost",
        victim=1,
        heartbeat_deadline_s=2.0,
        agree_timeout_s=10.0,
        boot_grace_s=90.0,
        world_timeout_s=300.0,
    )
    assert report["worlds_formed"] >= 2, report["supervisor"]
    assert report["hosts_lost"] == [1]
    assert report["hosts_final"] == 2
    assert report["all_trials_settled"], report["statuses"]
    assert report["recovered_bit_identical"], report["parity"]
    assert report["goodput"] > 0.5
    assert report["membership"]["host_lost_traced"]
    assert report["membership"]["world_shrunk_traced"]


@pytest.mark.multihost
def test_wedge_exits_with_named_wedged_collective(tmp_path):
    # A wedged host (stalled, heartbeat suspended) on a SPANNING group:
    # the survivor's sync watchdog must exit with a NAMED
    # WedgedCollective within the deadline (never a test timeout), the
    # supervisor must classify the wedged host as lost via its stale
    # lease, and the shrunken world must finish the sweep.
    from multidisttorch_tpu.faults.harness import run_chaos_mh_bench

    report = run_chaos_mh_bench(
        str(tmp_path),
        hosts=2,
        devs_per_host=2,
        trials=3,
        epochs=2,
        kind="wedge",
        victim=1,
        # The survivor must hit its bounded end-of-sweep barrier (8s)
        # BEFORE the supervisor's staleness verdict fires, so the
        # WedgedCollective exit path is what gets exercised — hence a
        # deliberately lazy heartbeat deadline.
        heartbeat_deadline_s=45.0,
        agree_timeout_s=8.0,
        boot_grace_s=90.0,
        world_timeout_s=300.0,
    )
    assert report["wedged_collective_exits"] >= 1, report["supervisor"]
    assert report["hosts_lost"] == [1]
    assert report["worlds_formed"] >= 2
    assert report["all_trials_settled"], report["statuses"]
    assert report["membership"]["host_lost_traced"]
