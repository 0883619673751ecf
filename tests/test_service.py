"""Sweep-as-a-service: queue durability, scheduler invariants, defrag
policy, and the daemon runtime (docs/SERVICE.md).

The property-style invariants (ISSUE 10's test satellite):

- fair share never starves a nonempty tenant;
- bin-packing never splits a shape bucket across submeshes mid-pass;
- defrag never migrates a trial with an unflushed checkpoint;
- the queue survives ``kill -9`` mid-append (real subprocess SIGKILL).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from multidisttorch_tpu.service.defrag import PlacedBlock, plan_defrag
from multidisttorch_tpu.service.queue import (
    ADMITTED,
    PENDING,
    SETTLED,
    QueueStats,
    Submission,
    SubmissionQueue,
    SweepClient,
    fold_queue,
    intake_dir,
    load_queue,
    queue_path,
)
from multidisttorch_tpu.service.scheduler import (
    ADMIT,
    FairShareScheduler,
    PendingTrial,
    REJECT_BACKPRESSURE,
    REJECT_QUOTA,
    SlicePool,
    TenantPolicy,
)

pytestmark = pytest.mark.service


def entry(
    sub_id,
    tenant="t",
    *,
    priority=1,
    bucket=("b",),
    size=1,
    cost=10.0,
    **kw,
):
    return PendingTrial(
        sub_id=sub_id,
        tenant=tenant,
        priority=priority,
        cfg=None,
        bucket=bucket,
        size=size,
        cost=cost,
        submit_ts=0.0,
        **kw,
    )


# --------------------------------------------------------------------
# durable queue
# --------------------------------------------------------------------


class TestQueue:
    def test_submit_drain_settle_roundtrip(self, tmp_path):
        d = str(tmp_path)
        c = SweepClient(d, tenant="alice")
        sid = c.submit({"epochs": 2}, priority=0, size=2, deadline_s=9.0)
        assert sid.startswith("alice-")
        # Committed before any daemon exists:
        assert c.status(sid)["state"] == PENDING
        q = SubmissionQueue(d)
        known = set()
        fresh = q.drain_intake(known_ids=known)
        assert [s.submission_id for s in fresh] == [sid]
        assert fresh[0].tenant == "alice"
        assert fresh[0].size == 2 and fresh[0].priority == 0
        assert fresh[0].deadline_s == 9.0
        # Spool file consumed; journal carries it now.
        assert not os.listdir(intake_dir(d))
        q.admitted(sid, trial_id=0, chash="h0", bucket="(b,)")
        q.placed(
            sid, trial_id=0, start=0, size=2, lanes=1,
            stacked=False, resumed=False,
        )
        q.settled(sid, trial_id=0, status="completed")
        rec = fold_queue(load_queue(d))[sid]
        assert rec["state"] == SETTLED
        assert rec["status"] == "completed"
        assert rec["trial_id"] == 0
        assert rec["placements"] == 1
        stats = QueueStats.of({sid: rec})
        assert stats.by_state == {SETTLED: 1}

    def test_unplaced_returns_to_admitted(self, tmp_path):
        d = str(tmp_path)
        c = SweepClient(d)
        sid = c.submit({})
        q = SubmissionQueue(d)
        q.drain_intake(known_ids=set())
        q.admitted(sid, trial_id=0, chash="h", bucket="b")
        q.placed(sid, trial_id=0, start=0, size=1, lanes=1,
                 stacked=False, resumed=False)
        q.unplaced(sid, trial_id=0, reason="drain")
        rec = fold_queue(load_queue(d))[sid]
        assert rec["state"] == ADMITTED
        assert rec["unplaced_reason"] == "drain"

    def test_torn_tail_costs_one_transition_not_the_submission(
        self, tmp_path
    ):
        d = str(tmp_path)
        c = SweepClient(d)
        sid = c.submit({})
        q = SubmissionQueue(d)
        q.drain_intake(known_ids=set())
        q.admitted(sid, trial_id=0, chash="h", bucket="b")
        # Crash mid-append: the settled record tears.
        with open(queue_path(d), "a") as f:
            f.write('{"event": "settled", "submission_id": "' + sid)
        rec = fold_queue(load_queue(d))[sid]
        assert rec["state"] == ADMITTED  # the torn line is skipped

    def test_duplicate_spool_replay_is_idempotent(self, tmp_path):
        # Crash between the durable `submitted` append and the spool
        # unlink: the file replays but must not journal twice.
        d = str(tmp_path)
        c = SweepClient(d)
        sid = c.submit({})
        q = SubmissionQueue(d)
        q.drain_intake(known_ids=set())
        # Resurrect the spool file (as if unlink never happened).
        c2 = SweepClient(d)
        path = os.path.join(intake_dir(d), sid + ".json")
        with open(path, "w") as f:
            json.dump(
                Submission(
                    submission_id=sid, tenant="default", config={}
                ).to_dict(),
                f,
            )
        known = set(fold_queue(load_queue(d)))
        fresh = q.drain_intake(known_ids=known)
        assert fresh == []  # deduped
        assert not os.path.exists(path)  # but still cleaned up
        events = load_queue(d)
        assert (
            sum(1 for e in events if e.get("event") == "submitted") == 1
        )
        del c2

    def test_torn_tmp_spool_file_ignored(self, tmp_path):
        d = str(tmp_path)
        os.makedirs(intake_dir(d), exist_ok=True)
        with open(os.path.join(intake_dir(d), "x.json.tmp"), "w") as f:
            f.write('{"submission_id": "x"')  # mid-write
        with open(os.path.join(intake_dir(d), "bad.json"), "w") as f:
            f.write("{garbled")  # renamed but undecodable (fs damage)
        q = SubmissionQueue(d)
        assert q.drain_intake(known_ids=set()) == []

    def test_queue_survives_kill9_mid_append(self, tmp_path):
        """A real SIGKILL against a child hammering submits + journal
        appends: afterwards the journal folds cleanly and every
        DURABLY-submitted id (client returned / journal holds it) is
        recoverable — the zero-lost-submissions contract."""
        d = str(tmp_path)
        code = (
            "import sys, os\n"
            "sys.path.insert(0, %r)\n"
            "from multidisttorch_tpu.service.queue import ("
            "SweepClient, SubmissionQueue)\n"
            "d = %r\n"
            "c = SweepClient(d, tenant='k9')\n"
            "q = SubmissionQueue(d)\n"
            "known = set()\n"
            "i = 0\n"
            "while True:\n"
            "    sid = c.submit({'seed': i})\n"
            "    print(sid, flush=True)\n"
            "    q.drain_intake(known_ids=known)\n"
            "    q.admitted(sid, trial_id=i, chash='h%%d' %% i, "
            "bucket='b')\n"
            "    i += 1\n"
        ) % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
             d)
        proc = subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            text=True,
        )
        # Let it commit a few, then kill -9 mid-flight.
        printed = []
        deadline = time.time() + 30
        while len(printed) < 5 and time.time() < deadline:
            line = proc.stdout.readline().strip()
            if line:
                printed.append(line)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        assert printed, "child never committed a submission"
        folded = fold_queue(load_queue(d))
        spooled = {
            n[: -len(".json")]
            for n in os.listdir(intake_dir(d))
            if n.endswith(".json")
        }
        for sid in printed:
            # Every id the client observed as committed is either
            # journaled or still sitting durably in the spool.
            assert sid in folded or sid in spooled, sid
        # The journal itself folds without error (torn tail skipped).
        for rec in folded.values():
            assert rec["state"] in (PENDING, ADMITTED)


# --------------------------------------------------------------------
# scheduler: admission, fair share, packing
# --------------------------------------------------------------------


class TestAdmission:
    def test_quota_and_backpressure_verdicts(self):
        s = FairShareScheduler(
            {"a": TenantPolicy(max_pending=2)},
            max_total_pending=3,
        )
        assert s.admit_verdict("a")[0] == ADMIT
        s.push(entry("a0", "a"))
        s.push(entry("a1", "a"))
        v, reason = s.admit_verdict("a")
        assert v == REJECT_QUOTA and "quota" in reason
        # Other tenants still fine until the global cap...
        assert s.admit_verdict("b")[0] == ADMIT
        s.push(entry("b0", "b"))
        v, _ = s.admit_verdict("b")
        assert v == REJECT_BACKPRESSURE


class TestFairShare:
    def _drain(self, s, pool, max_iters=500, max_lanes=1):
        order = []
        for _ in range(max_iters):
            ps = s.schedule(pool, max_lanes=max_lanes)
            for p in ps:
                order.extend(e.tenant for e in p.members)
                pool.free(p.start, p.size)
            if not s.pending_count():
                break
        return order

    @pytest.mark.parametrize("n_slices", [1, 4])
    def test_weighted_share_under_contention(self, n_slices):
        # 2:1 weights, 2:1 demand, equal cost -> contended service
        # lands within 10% of the weights in BOTH slot regimes.
        pool = SlicePool(n_slices)
        s = FairShareScheduler(
            {"a": TenantPolicy(weight=2.0), "b": TenantPolicy(weight=1.0)}
        )
        for i in range(24):
            s.push(entry(f"a{i}", "a", bucket=("x", i)))
        for i in range(12):
            s.push(entry(f"b{i}", "b", bucket=("y", i)))
        self._drain(s, pool)
        rep = s.fair_share_report()
        for t in ("a", "b"):
            assert abs(rep[t]["ratio_to_weight"] - 1.0) <= 0.10, rep

    def test_nonempty_tenant_never_starves(self):
        # Property: under an adversarial weight gap and a single slot,
        # the tiny-weight tenant is still served in bounded time.
        pool = SlicePool(1)
        s = FairShareScheduler(
            {
                "whale": TenantPolicy(weight=1000.0),
                "shrimp": TenantPolicy(weight=0.001),
            }
        )
        for i in range(200):
            s.push(entry(f"w{i}", "whale", bucket=("w", i)))
        s.push(entry("s0", "shrimp", bucket=("s",)))
        served_shrimp = False
        for _ in range(250):
            for p in s.schedule(pool, max_lanes=1):
                if any(e.tenant == "shrimp" for e in p.members):
                    served_shrimp = True
                pool.free(p.start, p.size)
            if served_shrimp:
                break
        assert served_shrimp

    def test_idle_tenant_banks_no_credit(self):
        # A tenant idle while another is served must not later burst
        # past its weight share (virtual-time activation rule).
        pool = SlicePool(1)
        s = FairShareScheduler(
            {"a": TenantPolicy(weight=1.0), "b": TenantPolicy(weight=1.0)}
        )
        for i in range(20):
            s.push(entry(f"a{i}", "a", bucket=("x", i)))
        # Serve a alone for 10 opportunities.
        for _ in range(10):
            for p in s.schedule(pool, max_lanes=1):
                pool.free(p.start, p.size)
        for i in range(20):
            s.push(entry(f"b{i}", "b", bucket=("y", i)))
        order = self._drain(s, pool)
        # From b's arrival, service alternates ~1:1 — b does NOT get a
        # 10-placement catch-up monopoly.
        first10 = order[:10]
        assert first10.count("b") <= 6, order[:12]

    def test_priority_lane_strictness(self):
        pool = SlicePool(1)
        s = FairShareScheduler()
        s.push(entry("lo", "t", priority=2, bucket=("l",)))
        s.push(entry("hi", "u", priority=0, bucket=("h",)))
        ps = s.schedule(pool, max_lanes=1)
        assert ps[0].members[0].sub_id == "hi"

    def test_backoff_veto_does_not_block_tenant(self):
        pool = SlicePool(2)
        s = FairShareScheduler()
        late = entry("late", "t", bucket=("l",))
        late.not_before = time.time() + 3600
        s.push(late)
        s.push(entry("now", "t", bucket=("n",)))
        now = time.time()
        ps = s.schedule(
            pool, max_lanes=1, can_start=lambda e: now >= e.not_before
        )
        assert [p.members[0].sub_id for p in ps] == ["now"]


class TestPacking:
    def test_same_bucket_copacks_across_tenants(self):
        pool = SlicePool(4)
        s = FairShareScheduler()
        s.push(entry("a0", "a", bucket=("same",)))
        s.push(entry("b0", "b", bucket=("same",)))
        ps = s.schedule(pool, max_lanes=4)
        assert len(ps) == 1 and ps[0].lanes == 2
        assert {e.tenant for e in ps[0].members} == {"a", "b"}

    def test_never_splits_a_bucket_across_submeshes(self):
        # Invariant: one pass opens ceil(n/max_lanes) placements per
        # (bucket, size) — never two partially-filled submeshes.
        pool = SlicePool(8)
        s = FairShareScheduler()
        for i in range(11):
            s.push(entry(f"x{i}", f"t{i % 3}", bucket=("B",)))
        ps = s.schedule(pool, max_lanes=4)
        same = [p for p in ps if p.bucket == ("B",)]
        lanes = sorted(p.lanes for p in same)
        assert sum(lanes) == 11
        assert lanes == [3, 4, 4]
        underfull = [p for p in same if p.lanes < 4]
        assert len(underfull) <= 1
        for p in same:
            assert all(e.bucket == ("B",) for e in p.members)

    def test_resume_scan_never_copacks(self):
        pool = SlicePool(4)
        s = FairShareScheduler()
        s.push(entry("fresh", "a", bucket=("B",)))
        s.push(entry("recovered", "a", bucket=("B",), resume_scan=True))
        ps = s.schedule(pool, max_lanes=4)
        assert len(ps) == 2  # the scan-resume trial runs classic

    def test_blocked_large_stamps_starvation_clock(self):
        s = FairShareScheduler()
        # occupy 0 and 2 so no 2-contiguous run exists
        pool2 = SlicePool(4)
        assert pool2.alloc_at(0, 1) and pool2.alloc_at(2, 1)
        big = entry("big", "t", bucket=("big",), size=2)
        s.push(big)
        t0 = 1000.0
        assert s.schedule(pool2, max_lanes=1, now=t0) == []
        assert big.blocked_since == t0
        starved = s.starved_entries(threshold_s=5.0, now=t0 + 6.0)
        assert [e.sub_id for e in starved] == ["big"]
        # Fragmentation gauge sees it too.
        assert pool2.fragmentation() == 0.5
        assert pool2.largest_free_run() == 1 and pool2.free_total == 2


class TestSlicePool:
    def test_alloc_contiguity_and_coalescing(self):
        p = SlicePool(6)
        a = p.alloc(2)
        b = p.alloc(3)
        assert (a, b) == (0, 2)
        p.free(a, 2)
        assert p.free_runs() == [(0, 2), (5, 1)]
        assert p.alloc(3) is None  # only 2+1 available
        p.free(b, 3)
        assert p.free_runs() == [(0, 6)]  # coalesced
        with pytest.raises(ValueError):
            p.free(0, 1)  # double free

    def test_alloc_at(self):
        p = SlicePool(4)
        assert p.alloc_at(2, 2)
        assert not p.alloc_at(1, 2)  # overlaps
        assert not p.alloc_at(3, 2)  # out of range
        assert p.alloc(2) == 0


# --------------------------------------------------------------------
# defrag planner
# --------------------------------------------------------------------


class TestDefragPlanner:
    def _pool(self, n, occupied):
        p = SlicePool(n)
        for start, size in occupied:
            assert p.alloc_at(start, size)
        return p

    def test_min_moves_window(self):
        # occupied: A@1(1), B@3(1); free {0,2}. Want 2: either window
        # works with ONE move; the plan picks the lowest feasible
        # window and re-homes the victim outside it.
        pool = self._pool(4, [(1, 1), (3, 1)])
        blocks = [
            PlacedBlock(0, 1, 1, True),
            PlacedBlock(1, 3, 1, True),
        ]
        plan = plan_defrag(pool, blocks, 2)
        assert plan is not None and len(plan.moves) == 1
        (pid, dst) = plan.moves[0]
        assert plan.window_start == 0 and pid == 0 and dst == 2

    def test_never_moves_unflushed_checkpoint(self):
        # The unflushed (movable=False) placement is never a victim —
        # even when that makes the plan infeasible.
        pool = self._pool(4, [(1, 1), (3, 1)])
        blocks = [
            PlacedBlock(0, 1, 1, False),  # unflushed
            PlacedBlock(1, 3, 1, False),
        ]
        assert plan_defrag(pool, blocks, 2) is None
        # movable_fn veto at PLAN time wins over a stale flag too.
        blocks = [
            PlacedBlock(0, 1, 1, True),
            PlacedBlock(1, 3, 1, True),
        ]
        assert (
            plan_defrag(pool, blocks, 2, movable_fn=lambda b: False)
            is None
        )
        plan = plan_defrag(
            pool, blocks, 2, movable_fn=lambda b: b.placement_id == 1
        )
        assert plan is not None
        assert [pid for pid, _ in plan.moves] == [1]

    def test_victims_rehome_outside_window(self):
        # 6 slices: occupied A@1(1), B@4(1); free {0,2,3,5}. Want 3:
        # cheapest window is {0,1,2} (one move), and A must re-home in
        # free space OUTSIDE that window ({3} first-fit).
        pool = self._pool(6, [(1, 1), (4, 1)])
        blocks = [
            PlacedBlock(0, 1, 1, True),
            PlacedBlock(1, 4, 1, True),
        ]
        plan = plan_defrag(pool, blocks, 3)
        assert plan is not None
        assert plan.window_start == 0 and plan.window_size == 3
        assert plan.moves == [(0, 3)]
        # And genuinely infeasible layouts return None: every window
        # holds work, and the one free slice cannot absorb a 2-wide
        # victim.
        pool2 = self._pool(6, [(0, 2), (3, 1), (5, 1)])
        blocks2 = [
            PlacedBlock(0, 0, 2, True),
            PlacedBlock(1, 3, 1, True),
            PlacedBlock(2, 5, 1, True),
        ]
        assert plan_defrag(pool2, blocks2, 3) is None

    def test_zero_move_plan_when_already_fits(self):
        pool = self._pool(4, [(0, 1)])
        plan = plan_defrag(pool, [PlacedBlock(0, 0, 1, True)], 2)
        assert plan is not None and plan.moves == []
        assert plan.window_start == 1

    def test_infeasible_capacity_returns_none(self):
        pool = self._pool(2, [(0, 2)])
        assert plan_defrag(
            pool, [PlacedBlock(0, 0, 2, True)], 2
        ) is None


# --------------------------------------------------------------------
# ledger satellites: tags + concurrent compaction
# --------------------------------------------------------------------


class TestLedgerSatellites:
    def test_tenant_tags_on_attempt_records(self, tmp_path):
        from multidisttorch_tpu.hpo.ledger import SweepLedger

        led = SweepLedger(str(tmp_path))
        led.attempt_start(
            0, "h0", 1, tenant="alice", priority=0, submit_ts=123.5
        )
        led.attempt_end(
            0, "h0", 1, "completed",
            summary={"steps": 4},
            tenant="alice", priority=0, submit_ts=123.5,
        )
        led.attempt_start(1, "h1", 1)  # untagged — old callers
        evs = led.load()
        assert evs[0]["tenant"] == "alice"
        assert evs[0]["priority"] == 0
        assert evs[0]["submit_ts"] == 123.5
        assert evs[1]["tenant"] == "alice"
        assert "tenant" not in evs[2]  # untagged stays byte-compatible
        # Old-style records (no tags) parse through every fold.
        assert led.attempts() == {"h0": 1, "h1": 1}
        assert set(led.finished()) == {"h0"}

    def test_tagged_events_feed_sweepfold_and_fleet(self, tmp_path):
        from multidisttorch_tpu import telemetry
        from multidisttorch_tpu.hpo.ledger import SweepLedger
        from multidisttorch_tpu.telemetry.export import (
            SweepFold,
            run_summary,
        )
        from multidisttorch_tpu.telemetry.fleet import per_tenant_books

        tel = str(tmp_path / "tel")
        with telemetry.telemetry_run(tel):
            led = SweepLedger(str(tmp_path))
            for tid, ten in ((0, "alice"), (1, "bob")):
                led.attempt_start(tid, f"h{tid}", 1, tenant=ten)
                led.attempt_end(
                    tid, f"h{tid}", 1, "completed",
                    summary={"steps": 8, "resumed_from_step": 0},
                    tenant=ten,
                )
            events = [
                e.to_dict()
                for e in telemetry.get_bus().recent()
            ]
            summary = run_summary(events)
        fold = SweepFold()
        for e in events:
            fold.feed(e)
        books = fold.tenant_books()
        assert books["alice"]["useful_steps"] == 8
        assert books["alice"]["goodput"] == 1.0
        assert books["bob"]["trials"] == 1
        assert summary["tenants"]["bob"]["settled"] == 1
        assert fold.trials[0]["tenant"] == "alice"
        fleet = per_tenant_books(events)
        assert fleet["alice"]["goodput"] == 1.0
        assert fleet["bob"]["trials"] == 1

    def test_untagged_stream_has_no_tenant_keys(self):
        from multidisttorch_tpu.telemetry.export import run_summary

        summary = run_summary(
            [
                {
                    "kind": "attempt_end",
                    "ts": 1.0,
                    "trial_id": 0,
                    "attempt": 1,
                    "data": {
                        "status": "completed",
                        "summary": {"steps": 2},
                    },
                }
            ]
        )
        assert "tenants" not in summary

    def test_compact_concurrent_with_appender_loses_nothing(
        self, tmp_path
    ):
        """The satellite bugfix: a compaction racing a live appender
        must not drop the appended record. Without the mutate lock the
        append lands between compact()'s load and its os.replace and
        vanishes; with it, every hash appended by the writer thread
        survives every concurrent compaction."""
        import threading

        from multidisttorch_tpu.hpo.ledger import SweepLedger

        led = SweepLedger(str(tmp_path))
        N = 120
        stop = threading.Event()

        def appender():
            for i in range(N):
                led.attempt_start(i, f"h{i}", 1)
                led.attempt_end(
                    i, f"h{i}", 1, "completed", summary={"steps": 1}
                )
            stop.set()

        def compactor():
            while not stop.is_set():
                led.compact()
            led.compact()

        ta = threading.Thread(target=appender)
        tc = threading.Thread(target=compactor)
        ta.start()
        tc.start()
        ta.join(timeout=120)
        tc.join(timeout=120)
        assert stop.is_set()
        finished = led.finished()
        assert len(finished) == N, (
            f"compaction dropped {N - len(finished)} settled records"
        )
        attempts = led.attempts()
        assert all(attempts[f"h{i}"] == 1 for i in range(N))


# --------------------------------------------------------------------
# runtime: end-to-end service drills (real training on virtual CPUs)
# --------------------------------------------------------------------


BASE = dict(batch_size=32, latent_dim=4, log_interval=1000)


def make_service(d, **kw):
    from multidisttorch_tpu.service.runtime import SweepService

    kw.setdefault("data_rows", 128)
    kw.setdefault("verbose", False)
    return SweepService(str(d), **kw)


def run_until(svc, cond, timeout_s=180.0):
    t0 = time.time()
    while time.time() - t0 < timeout_s:
        svc.tick()
        if cond():
            return True
    return False


class TestServiceRuntime:
    def test_multi_tenant_e2e_with_copack(self, tmp_path):
        d = str(tmp_path)
        ca = SweepClient(d, tenant="alice")
        cb = SweepClient(d, tenant="bob")
        ids = [
            ca.submit({**BASE, "epochs": 1, "hidden_dim": 16, "seed": i})
            for i in range(2)
        ]
        ids.append(
            cb.submit({**BASE, "epochs": 1, "hidden_dim": 16, "seed": 9})
        )
        svc = make_service(tmp_path, n_slices=2, max_lanes=4)
        rep = svc.serve(exit_when_drained=True, max_wall_s=300)
        assert rep["outcome"] == "idle"
        assert sorted(rep["settled"]) == sorted(ids)
        assert set(rep["settled"].values()) == {"completed"}
        # Same shape bucket from DIFFERENT tenants co-packed into one
        # stacked placement:
        folded = fold_queue(load_queue(d))
        lanes = {folded[s]["last_placement"]["lanes"] for s in ids}
        assert lanes == {3}
        assert all(folded[s]["last_placement"]["stacked"] for s in ids)
        books = rep["books"]
        assert books["tenants"]["alice"]["goodput"] == 1.0
        assert books["tenants"]["bob"]["settled"] == 1
        assert books["queue_wait"]["count"] == 3
        assert books["placement_latency"]["count"] >= 1

    def test_invalid_config_rejected_not_crashed(self, tmp_path):
        d = str(tmp_path)
        c = SweepClient(d)
        bad = c.submit({"no_such_field": 1})
        huge = c.submit({**BASE, "epochs": 1, "hidden_dim": 16}, size=99)
        ok = c.submit({**BASE, "epochs": 1, "hidden_dim": 16})
        svc = make_service(tmp_path, n_slices=2, max_lanes=2)
        rep = svc.serve(exit_when_drained=True, max_wall_s=300)
        assert rep["settled"][bad] == "rejected_invalid"
        assert rep["settled"][huge] == "rejected_invalid"
        assert rep["settled"][ok] == "completed"

    def test_quota_rejection_journaled(self, tmp_path):
        d = str(tmp_path)
        c = SweepClient(d, tenant="q")
        ids = [
            c.submit({**BASE, "epochs": 1, "hidden_dim": 16, "seed": i})
            for i in range(3)
        ]
        svc = make_service(
            tmp_path,
            n_slices=2,
            max_lanes=2,
            policies={"q": TenantPolicy(max_pending=2)},
        )
        rep = svc.serve(exit_when_drained=True, max_wall_s=300)
        statuses = sorted(rep["settled"][s] for s in ids)
        assert statuses == ["completed", "completed", "rejected_quota"]

    def test_divergent_trial_settles_diverged(self, tmp_path):
        d = str(tmp_path)
        c = SweepClient(d)
        sid = c.submit(
            {**BASE, "epochs": 1, "hidden_dim": 16, "lr": 1e18}
        )
        svc = make_service(tmp_path, n_slices=1, max_lanes=1)
        rep = svc.serve(exit_when_drained=True, max_wall_s=300)
        assert rep["settled"][sid] == "diverged"

    def test_restart_recovery_resumes_from_checkpoint(self, tmp_path):
        d = str(tmp_path)
        c = SweepClient(d)
        ids = [
            c.submit({**BASE, "epochs": 4, "hidden_dim": 16, "seed": i})
            for i in range(4)
        ]
        svc = make_service(tmp_path, n_slices=2, max_lanes=1)
        # "Crash" once a checkpoint exists: no drain, just abandon.
        assert run_until(
            svc,
            lambda: any(
                os.path.exists(
                    os.path.join(d, f"trial-{t}", "state.msgpack")
                )
                for t in range(4)
            ),
        )
        assert not svc.settled or len(svc.settled) < 4
        del svc
        svc2 = make_service(tmp_path, n_slices=2, max_lanes=1)
        assert len(svc2.entries) >= 1  # recovered live submissions
        rep = svc2.serve(exit_when_drained=True, max_wall_s=300)
        assert sorted(rep["settled"]) == sorted(ids)
        assert set(rep["settled"].values()) == {"completed"}
        folded = fold_queue(load_queue(d))
        # At least one trial re-placed with the scan-back resume flag.
        resumed = [
            s for s in ids
            if (folded[s].get("last_placement") or {}).get("resumed")
        ]
        assert resumed
        # Goodput stays honest: useful <= executed.
        tb = rep["books"]["tenants"]["default"]
        assert tb["useful_steps"] <= tb["executed_steps"]
        # ... and holds its floor across the crash: an abandoned
        # attempt left no record of its own, so the ledger charges it
        # the steps up to the resume point, once.
        assert tb["goodput"] >= 0.8

    def test_recovery_never_reuses_assigned_trial_ids(self, tmp_path):
        """Regression: a submission journaled `submitted` but killed
        before its `admitted` record goes through admission on
        restart — its fresh trial id must not collide with ids the
        previous incarnation already assigned."""
        d = str(tmp_path)
        c = SweepClient(d)
        q = SubmissionQueue(d)
        admitted_sid = c.submit({**BASE, "epochs": 1, "hidden_dim": 16})
        pending_sid = c.submit(
            {**BASE, "epochs": 1, "hidden_dim": 24, "seed": 7}
        )
        q.drain_intake(known_ids=set())
        # Previous incarnation admitted ONE (tid 3, a high id), then
        # died before admitting the other.
        q.admitted(admitted_sid, trial_id=3, chash="h3", bucket="b")
        svc = make_service(tmp_path, n_slices=2, max_lanes=1)
        folded = fold_queue(load_queue(d))
        tids = {
            folded[s]["trial_id"] for s in (admitted_sid, pending_sid)
        }
        assert folded[pending_sid]["trial_id"] not in (None, 3)
        assert len(tids) == 2  # no collision
        assert svc.next_trial_id > max(tids)
        rep = svc.serve(exit_when_drained=True, max_wall_s=300)
        assert set(rep["settled"].values()) == {"completed"}

    def test_drain_records_preempted_and_unplaced(self, tmp_path):
        d = str(tmp_path)
        c = SweepClient(d)
        sid = c.submit({**BASE, "epochs": 30, "hidden_dim": 16})
        svc = make_service(tmp_path, n_slices=1, max_lanes=1)
        assert run_until(svc, lambda: bool(svc.active))
        svc.stop()
        rep = svc.serve(exit_when_drained=True, max_wall_s=60)
        assert rep["outcome"] == "preempted"
        from multidisttorch_tpu.hpo.ledger import SweepLedger

        folded = fold_queue(load_queue(d))
        assert folded[sid]["state"] == ADMITTED  # unplaced, not lost
        led_events = [
            e
            for e in SweepLedger(d).load()
            if e.get("event") == "attempt_end"
        ]
        assert led_events and led_events[-1]["status"] == "preempted"
        assert led_events[-1]["tenant"] == "default"

    def test_defrag_unblocks_starved_large_trial(self, tmp_path):
        from multidisttorch_tpu import telemetry

        d = str(tmp_path)
        tel = os.path.join(d, "telemetry")
        c = SweepClient(d, tenant="t")
        with telemetry.telemetry_run(tel):
            svc = make_service(
                tmp_path,
                n_slices=4,
                max_lanes=1,
                starvation_s=0.3,
                defrag_cooldown_s=0.1,
            )
            # Pin the layout: short@0, long@1, short@2, long@3.
            for cfg in (
                {**BASE, "epochs": 1, "hidden_dim": 16},
                {**BASE, "epochs": 40, "hidden_dim": 24},
                {**BASE, "epochs": 1, "hidden_dim": 40},
                {**BASE, "epochs": 40, "hidden_dim": 56},
            ):
                c.submit(cfg)
                assert run_until(
                    svc, lambda: svc.sched.pending_count() == 0
                )
            # Shorts finish -> non-adjacent holes; big starves.
            assert run_until(
                svc,
                lambda: sum(
                    1 for s in svc.settled.values() if s == "completed"
                ) >= 2,
            )
            assert svc.pool.largest_free_run() < 2 <= svc.pool.free_total
            big = c.submit(
                {**BASE, "epochs": 1, "hidden_dim": 16, "seed": 9},
                size=2,
            )
            assert run_until(
                svc, lambda: svc.settled.get(big) == "completed"
            )
            # Migrated victims still finish (scan-back restore worked).
            assert run_until(svc, lambda: len(svc.settled) == 5, 300)
            assert set(svc.settled.values()) == {"completed"}
            svc._drain(reason="test end")
            events = telemetry.read_events(
                os.path.join(tel, "events.jsonl")
            )
        kinds = [e["kind"] for e in events]
        assert "defrag_start" in kinds
        assert "defrag_move" in kinds
        assert "defrag_end" in kinds
        assert "trial_migrated" in kinds
        end = next(e for e in events if e["kind"] == "defrag_end")
        assert end["data"]["freed_contiguous"] >= 2
        placed_big = [
            e
            for e in events
            if e["kind"] == "trial_placed"
            and (e.get("data") or {}).get("sub_id") == big
        ]
        # Order in the one process's append-only stream, not clocks.
        assert placed_big and events.index(placed_big[-1]) > events.index(end)

    def test_defrag_waits_for_unflushed_checkpoint(self, tmp_path):
        """Invariant at the RUNTIME level: a placement whose
        checkpoint write is in flight reports unmovable, so the
        planner cannot choose it."""
        import threading

        from multidisttorch_tpu.service.runtime import _Active

        class FakeRun:
            def __init__(self):
                self._ckpt_thread = threading.Thread(
                    target=time.sleep, args=(30,), daemon=True
                )
                self._step_no = 8

                class R:
                    checkpoint = "/some/ckpt"

                self.result = R()

        ap = _Active(
            placement_id=0, start=0, size=1, stacked=False,
            run=FakeRun(), gen=None, entries={}, place_ts=0.0,
            construct_s=0.0,
        )
        ap.run._ckpt_thread.start()
        assert not ap.movable()  # write in flight
        ap.run._ckpt_thread.join(timeout=0.01)
        ap.run._ckpt_thread = None
        assert ap.movable()  # flushed
        ap.run.result.checkpoint = ""
        assert not ap.movable()  # progress but nothing durable
        ap.run._step_no = 0
        assert ap.movable()  # nothing to lose
        # Stacked placements are movable now: the bucket drain
        # snapshots every live lane at its epoch boundary itself, so
        # only an in-flight lane persist defers them — and only under
        # the legacy join-drain (the snapshot drain adopts the write).
        ap.stacked = True
        assert ap.movable()
        ap.run._ckpt_thread = threading.Thread(
            target=time.sleep, args=(30,), daemon=True
        )
        ap.run._ckpt_thread.start()
        assert not ap.movable()  # legacy join-drain defers
        assert ap.movable(snapshot_drain=True)  # adopted in-flight write


# --------------------------------------------------------------------
# tools
# --------------------------------------------------------------------


class TestTools:
    def _seed_queue(self, d):
        c = SweepClient(str(d), tenant="alice")
        sid = c.submit({"epochs": 1, "hidden_dim": 16})
        q = SubmissionQueue(str(d))
        q.drain_intake(known_ids=set())
        q.admitted(sid, trial_id=0, chash="h", bucket="(32, 16)")
        return sid

    def test_ledger_view_queue_render_and_json(self, tmp_path, capsys):
        sys.path.insert(
            0,
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools",
            ),
        )
        import ledger_view

        sid = self._seed_queue(tmp_path)
        assert ledger_view.main([str(tmp_path), "--queue"]) == 0
        out = capsys.readouterr().out
        assert sid[:24] in out and "alice" in out and "admitted" in out
        assert ledger_view.main([str(tmp_path), "--queue", "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["by_submission"][sid]["state"] == "admitted"

    def test_sweep_top_service_panel(self, tmp_path, capsys):
        import importlib

        sys.path.insert(
            0,
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools",
            ),
        )
        sweep_top = importlib.import_module("sweep_top")
        sid = self._seed_queue(tmp_path)
        with open(tmp_path / "service_books.json", "w") as f:
            json.dump(
                {
                    "tenants": {
                        "alice": {"useful_steps": 4, "goodput": 1.0}
                    },
                    "fair_share": {
                        "alice": {
                            "weight": 2.0,
                            "contended_share": 0.5,
                            "ratio_to_weight": 1.0,
                        }
                    },
                    "queue_wait": {"count": 1, "p50_s": 0.5,
                                   "p99_s": 1.0, "max_s": 0.7},
                    "placement_latency": {"count": 1, "p50_s": 1.0,
                                          "p99_s": 2.0, "max_s": 1.5},
                    "fragmentation": {"now": 0.25, "max": 0.5,
                                      "free_slices": 2,
                                      "largest_free_run": 1},
                    "defrag": {"events": 1, "moved_slices": 1,
                               "unblocked": ["x"]},
                },
                f,
            )
        assert sweep_top.main([str(tmp_path), "--service"]) == 0
        out = capsys.readouterr().out
        assert "alice" in out and "queue-wait" in out
        assert "defrag" in out and "fragmentation" in out
        assert sweep_top.main([str(tmp_path), "--service", "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["queue"][sid]["tenant"] == "alice"
        assert payload["books"]["defrag"]["events"] == 1

    def test_sweep_submit_cli(self, tmp_path, capsys):
        sys.path.insert(
            0,
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools",
            ),
        )
        import sweep_submit

        rc = sweep_submit.main(
            [
                str(tmp_path), "--tenant", "cli", "--priority", "0",
                "--epochs", "2", "--hidden-dim", "32", "--count", "2",
                "--json",
            ]
        )
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        ids = payload["submitted"]
        assert len(ids) == 2 and all(s.startswith("cli-") for s in ids)
        q = SubmissionQueue(str(tmp_path))
        fresh = q.drain_intake(known_ids=set())
        assert len(fresh) == 2
        assert {s.config["seed"] for s in fresh} == {0, 1}
        assert all(s.priority == 0 for s in fresh)
        assert all(s.config["hidden_dim"] == 32 for s in fresh)
