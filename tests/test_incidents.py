"""Incident-plane drills: the root-cause detector's closed taxonomy,
correlation (dedup / flap-reopen / rank escalation), the torn-tail-
tolerant durable ledger, SIGKILL-mid-dump bundle quarantine, the
always-on flight ring's zero-cost-off contract, the offline causal
autopsy, and the console/CLI surfaces (docs/INCIDENTS.md)."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from multidisttorch_tpu import telemetry
from multidisttorch_tpu.faults import (
    CKPT_CORRUPT,
    CRASH,
    DIVERGE,
    PREEMPT,
    FaultPlan,
    FaultSpec,
    HostPreemption,
)
from multidisttorch_tpu.telemetry import incident as tincident
from multidisttorch_tpu.telemetry.events import get_bus
from multidisttorch_tpu.telemetry.incident import (
    BACKEND_WEDGED,
    CKPT_INTEGRITY,
    DIVERGENCE_STORM,
    FENCE_LOST,
    HOST_PREEMPTED,
    KINDS,
    REPLICA_LOST,
    SLO_BURN,
    SPLIT_TORN,
    STEAL_ANOMALY,
    WEDGED_COLLECTIVE,
    IncidentDetector,
    detect_incidents,
    fold_incidents,
    load_incidents,
    read_incident_records,
    sweep_partial_bundles,
)

pytestmark = pytest.mark.incidents


def _ev(kind, ts=1000.0, trial_id=None, **data):
    ev = {"kind": kind, "ts": ts}
    if trial_id is not None:
        ev["trial_id"] = trial_id
    if data:
        ev["data"] = data
    return ev


# -- taxonomy / classification rules ----------------------------------


def test_taxonomy_is_closed_and_complete():
    assert len(KINDS) == 10
    assert len(set(KINDS)) == 10


@pytest.mark.parametrize(
    "ev,kind,subject",
    [
        (
            _ev("shard_fence_lost", shard=2, replica=0, reason="outbid"),
            FENCE_LOST, "shard:2",
        ),
        (
            _ev("shard_adopted", shard=1, replica=3, epoch=2),
            REPLICA_LOST, "shard:1",
        ),
        (
            _ev("host_lost", slot=4, stale_s=2.5, world_epoch=1),
            REPLICA_LOST, "host:4",
        ),
        (
            _ev("shard_split_resolved", shard=0, child=2, replica=1,
                action="abort"),
            SPLIT_TORN, "shard:0",
        ),
        (
            _ev("failure_classified", trial_id=7,
                failure_class="preemption", exc_type="WedgedCollective",
                error="wedged"),
            WEDGED_COLLECTIVE, "trial:7",
        ),
        (
            _ev("failure_classified", trial_id=5,
                failure_class="preemption", exc_type="HostPreemption",
                error="preempted"),
            HOST_PREEMPTED, "trial:5",
        ),
        (
            _ev("preflight_verdict", platform="tpu",
                verdict="wedged_init_timeout", reason="deadline",
                usable=False, elapsed_s=30.0),
            BACKEND_WEDGED, "backend:tpu",
        ),
        (
            _ev("slo_alert", slo="queue_wait_p95_60s", label=None,
                state="firing", burn=4.0, compliance=0.5),
            SLO_BURN, "slo:queue_wait_p95_60s:None",
        ),
        (
            _ev("ckpt_scan_reject", path="/runs/t0/ckpt.msgpack",
                reason="crc mismatch"),
            CKPT_INTEGRITY, "ckpt:/runs/t0",
        ),
    ],
)
def test_single_event_rules(ev, kind, subject):
    folded = detect_incidents([ev])
    assert len(folded) == 1
    (inc,) = folded.values()
    assert inc["kind"] == kind
    assert inc["subject"] == subject


def test_first_claim_is_not_an_incident():
    folded = detect_incidents(
        [_ev("shard_adopted", shard=0, replica=0, epoch=1)]
    )
    assert folded == {}


def test_usable_preflight_is_not_an_incident():
    folded = detect_incidents(
        [
            _ev("preflight_verdict", platform="cpu", verdict="healthy",
                usable=True, elapsed_s=1.0)
        ]
    )
    assert folded == {}


def test_divergence_storm_needs_distinct_trials_in_window():
    def diverge(tid, ts):
        return _ev(
            "failure_classified", ts=ts, trial_id=tid,
            failure_class="divergence", exc_type="DivergenceError",
            error="nan",
        )

    # Same trial three times: attrition, not a storm.
    assert detect_incidents(
        [diverge(0, 1000.0 + i) for i in range(3)]
    ) == {}
    # Three distinct trials inside the window: one storm incident.
    folded = detect_incidents(
        [diverge(t, 1000.0 + t) for t in range(3)]
    )
    assert len(folded) == 1
    (inc,) = folded.values()
    assert inc["kind"] == DIVERGENCE_STORM
    assert inc["subject"] == "sweep"
    # Spread past the window: never accumulates.
    assert detect_incidents(
        [diverge(t, 1000.0 + 500.0 * t) for t in range(3)],
        storm_window_s=120.0,
    ) == {}


def test_steal_anomaly_duplicate_grant_and_ungranted_execute():
    dup = detect_incidents(
        [
            _ev("steal_grant", ts=1.0, victim_shard=0, thief_shard=1,
                seq=7, n=2),
            _ev("steal_grant", ts=2.0, victim_shard=0, thief_shard=1,
                seq=7, n=2),
        ]
    )
    assert [i["kind"] for i in dup.values()] == [STEAL_ANOMALY]
    (inc,) = dup.values()
    assert inc["detail"]["why"] == "duplicate_grant"

    ungranted = detect_incidents(
        [
            _ev("steal_executed", ts=1.0, victim_shard=3, thief_shard=4,
                sub_ids=["s-1"]),
        ]
    )
    (inc,) = ungranted.values()
    assert inc["kind"] == STEAL_ANOMALY
    assert inc["detail"]["why"] == "executed_without_grant"

    # The healthy protocol — grant then execute — is silent.
    assert detect_incidents(
        [
            _ev("steal_grant", ts=1.0, victim_shard=0, thief_shard=1,
                seq=1, n=1),
            _ev("steal_executed", ts=2.0, victim_shard=0, thief_shard=1,
                sub_ids=["s-1"]),
        ]
    ) == {}


# -- correlation: dedup, escalation, flap reopen ----------------------


def test_takeover_chain_is_one_incident(tmp_path):
    """The fence-loss + adoption echo of ONE takeover lands in one
    incident, and the torn-split resolution ESCALATES it in place."""
    det = IncidentDetector(str(tmp_path), emit_events=False)
    det.observe(_ev("shard_fence_lost", ts=1.0, shard=0, replica=0,
                    reason="lease expired"))
    det.observe(_ev("shard_adopted", ts=2.0, shard=0, replica=1,
                    epoch=2))
    det.observe(_ev("shard_split_resolved", ts=3.0, shard=0, child=2,
                    replica=1, action="abort"))
    assert det.opened == 1
    (inc,) = det.open_incidents()
    assert inc.kind == SPLIT_TORN  # escalated from fence_lost
    assert inc.count == 3
    # Durable history: open + escalate, folded back to the same state.
    folded = load_incidents(str(tmp_path))
    assert folded[inc.id]["kind"] == SPLIT_TORN
    assert folded[inc.id]["count"] == 3
    recs, torn = read_incident_records(
        os.path.join(str(tmp_path), tincident.INCIDENTS_NAME)
    )
    assert not torn
    assert [r["rec"] for r in recs] == ["open", "escalate"]


def test_lower_rank_absorbs_without_escalation(tmp_path):
    det = IncidentDetector(str(tmp_path), emit_events=False)
    det.observe(_ev("shard_fence_lost", ts=1.0, shard=0, replica=0,
                    reason="outbid"))
    det.observe(_ev("shard_adopted", ts=2.0, shard=0, replica=1,
                    epoch=2))
    (inc,) = det.open_incidents()
    assert inc.kind == FENCE_LOST  # replica_lost ranks below
    assert inc.count == 2


def test_flapping_lease_reopens_one_incident(tmp_path):
    """resolve -> re-fire inside flap_window_s reopens the SAME id
    (flaps++) instead of minting a ledger flood."""
    det = IncidentDetector(
        str(tmp_path), emit_events=False, flap_window_s=60.0
    )
    t = 1000.0
    first = det.observe(
        _ev("shard_fence_lost", ts=t, shard=0, replica=0, reason="flap")
    )
    for i in range(1, 4):
        det.resolve_subject("shard:0", ts=t + 10.0 * i,
                            reason="lease re-won")
        again = det.observe(
            _ev("shard_fence_lost", ts=t + 10.0 * i + 5.0, shard=0,
                replica=0, reason="flap")
        )
        assert again.id == first.id
        assert again.flaps == i
    assert det.opened == 1
    folded = load_incidents(str(tmp_path))
    assert list(folded) == [first.id]
    assert folded[first.id]["flaps"] == 3
    assert folded[first.id]["status"] == "open"
    # Past the flap window a fresh fire is a NEW incident.
    det.resolve_subject("shard:0", ts=t + 100.0, reason="stable")
    fresh = det.observe(
        _ev("shard_fence_lost", ts=t + 500.0, shard=0, replica=0,
            reason="new fault")
    )
    assert fresh.id != first.id


def test_slo_resolve_event_resolves_subject(tmp_path):
    det = IncidentDetector(str(tmp_path), emit_events=False)
    det.observe(_ev("slo_alert", ts=1.0, slo="q", label=None,
                    state="firing", burn=5.0))
    assert len(det.open_incidents()) == 1
    det.observe(_ev("slo_alert", ts=2.0, slo="q", label=None,
                    state="resolved", burn=0.1))
    assert det.open_incidents() == []
    folded = load_incidents(str(tmp_path))
    (inc,) = folded.values()
    assert inc["status"] == "resolved"


def test_quiet_resolve_auto_closes(tmp_path):
    det = IncidentDetector(
        str(tmp_path), emit_events=False, quiet_resolve_s=30.0
    )
    det.observe(_ev("shard_fence_lost", ts=1000.0, shard=0, replica=0,
                    reason="outbid"))
    # Any later observation past the quiet window sweeps the stale one.
    det.observe(_ev("epoch", ts=1100.0))
    assert det.open_incidents() == []


# -- durable ledger ---------------------------------------------------


def test_torn_tail_replay_and_heal(tmp_path):
    d = str(tmp_path)
    det = IncidentDetector(d, emit_events=False)
    det.observe(_ev("shard_fence_lost", ts=1.0, shard=0, replica=0,
                    reason="outbid"))
    det.observe(_ev("ckpt_scan_reject", ts=2.0, path="/r/t0/c.msgpack",
                    reason="crc"))
    path = os.path.join(d, tincident.INCIDENTS_NAME)
    with open(path, "a") as f:
        f.write('{"rec": "open", "id": "inc-9999", "kind": "tru')
    # Reader: torn tail detected, whole lines intact.
    recs, torn = read_incident_records(path)
    assert torn
    assert len(recs) == 2
    assert "inc-9999" not in fold_incidents(recs)
    # A new session over the torn ledger heals the tail, resumes the
    # id sequence past every banked id, and appends cleanly.
    det2 = IncidentDetector(d, emit_events=False)
    assert det2.tail_repaired
    inc = det2.observe(
        _ev("host_lost", ts=3.0, slot=1, stale_s=9.0, world_epoch=0)
    )
    assert int(inc.id.split("-")[1]) > 2
    # The repair newline-terminates the garbage (it stays countable as
    # exactly one torn line) so the new append is a FRESH whole line.
    recs2, torn2 = read_incident_records(path)
    assert torn2 == 1
    assert [r["rec"] for r in recs2] == ["open", "open", "open"]


def test_counts_flushed_on_resolve(tmp_path):
    """Absorbs are memory-only (per-absorb appends would defeat the
    flood protection); the resolve record flushes the final count."""
    d = str(tmp_path)
    det = IncidentDetector(d, emit_events=False)
    for i in range(5):
        det.observe(
            _ev("shard_fence_lost", ts=1.0 + i, shard=0, replica=0,
                reason="outbid")
        )
    assert load_incidents(d)[det.open_incidents()[0].id]["count"] == 1
    det.resolve_subject("shard:0", ts=10.0, reason="done")
    (inc,) = load_incidents(d).values()
    assert inc["count"] == 5
    assert inc["status"] == "resolved"


def test_id_sequence_never_recycled_across_sessions(tmp_path):
    d = str(tmp_path)
    det = IncidentDetector(d, emit_events=False)
    a = det.observe(_ev("shard_fence_lost", ts=1.0, shard=0, replica=0,
                        reason="x"))
    det2 = IncidentDetector(d, emit_events=False)
    b = det2.observe(_ev("shard_fence_lost", ts=2.0, shard=1, replica=0,
                         reason="x"))
    assert b.id != a.id
    assert int(b.id.split("-")[1]) == int(a.id.split("-")[1]) + 1


# -- bundles ----------------------------------------------------------


def test_bundle_published_atomically(tmp_path):
    d = str(tmp_path)
    ring = tincident.FlightRing(maxlen=8)
    for i in range(20):
        ring.note({"kind": "epoch", "ts": float(i)})
    det = IncidentDetector(d, emit_events=False, ring=ring)
    inc = det.observe(
        _ev("shard_fence_lost", ts=30.0, shard=0, replica=0,
            reason="outbid")
    )
    bdir = os.path.join(d, tincident.BUNDLE_DIRNAME, inc.id)
    assert os.path.isdir(bdir)
    assert not os.path.isdir(bdir + ".partial")
    with open(os.path.join(bdir, "flight_ring.json")) as f:
        dump = json.load(f)
    # Bounded black box: the ring held only the newest maxlen events
    # but counted everything it saw.
    assert len(dump["events"]) == 8
    assert dump["noted"] == 20
    with open(os.path.join(bdir, "trigger.json")) as f:
        trig = json.load(f)
    assert trig["incident"]["id"] == inc.id
    assert trig["trigger_event"]["kind"] == "shard_fence_lost"


def test_sigkill_mid_dump_leaves_valid_ledger_and_quarantines(tmp_path):
    """The black-box crash drill: a child stalls inside the bundle
    dump (MDT_INCIDENT_DUMP_STALL) and is SIGKILLed before the
    publish rename. The ledger must already hold the fsync'd open
    record; the bundle must be a ``.partial`` dir that the sweep
    renames to ``.quarantined`` — never a half-bundle that looks
    whole."""
    d = str(tmp_path / "scope")
    child = textwrap.dedent(
        f"""
        import sys
        sys.path.insert(0, {repr(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))})
        from multidisttorch_tpu.telemetry.incident import (
            FlightRing, IncidentDetector,
        )
        ring = FlightRing(maxlen=8)
        ring.note({{"kind": "epoch", "ts": 0.5}})
        det = IncidentDetector({d!r}, emit_events=False, ring=ring)
        det.observe({{"kind": "shard_fence_lost", "ts": 1.0,
                      "data": {{"shard": 0, "replica": 0,
                                "reason": "outbid"}}}})
        print("UNREACHABLE", flush=True)
        """
    )
    env = dict(os.environ, MDT_INCIDENT_DUMP_STALL="60")
    proc = subprocess.Popen(
        [sys.executable, "-c", child], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        bundles = os.path.join(d, tincident.BUNDLE_DIRNAME)
        deadline = time.monotonic() + 30.0
        part = None
        while time.monotonic() < deadline:
            if os.path.isdir(bundles):
                parts = [
                    n for n in os.listdir(bundles)
                    if n.endswith(".partial")
                ]
                if parts and os.path.exists(
                    os.path.join(bundles, parts[0], "flight_ring.json")
                ):
                    part = parts[0]
                    break
            time.sleep(0.02)
        assert part is not None, "child never reached the dump stall"
        proc.send_signal(signal.SIGKILL)
    finally:
        proc.kill()
        proc.wait(timeout=30)
    # Ledger: whole, already holding the open record.
    recs, torn = read_incident_records(
        os.path.join(d, tincident.INCIDENTS_NAME)
    )
    assert not torn
    assert [r["rec"] for r in recs] == ["open"]
    # Bundle: still partial; the sweep quarantines it.
    iid = part[: -len(".partial")]
    assert not os.path.isdir(os.path.join(bundles, iid))
    swept = sweep_partial_bundles(d)
    assert len(swept) == 1
    assert swept[0].endswith(".quarantined")
    assert not os.path.isdir(os.path.join(bundles, part))
    # Re-arming over the crash scene replays the incident as open.
    folded = load_incidents(d)
    assert folded[iid]["status"] == "open"


# -- flight ring + zero-cost-off --------------------------------------


def test_ring_is_bounded_and_counts_drops():
    ring = tincident.FlightRing(maxlen=4)
    for i in range(10):
        ring.note({"i": i})
    snap = ring.snapshot()
    assert len(snap) == 4
    assert [r["i"] for r in snap] == [6, 7, 8, 9]
    assert ring.noted == 10


def test_zero_cost_when_off(monkeypatch):
    """Telemetry OFF: no ring, no detector, and the incident module's
    clock is never read on any production seam."""
    assert not telemetry.enabled()
    assert telemetry.get_flight_ring() is None
    assert telemetry.get_incident_detector() is None

    def _boom():
        raise AssertionError("incident clock read while telemetry off")

    monkeypatch.setattr(tincident, "_clock", _boom)
    from multidisttorch_tpu.hpo.supervision import classify_failure
    from multidisttorch_tpu.train.guards import DivergenceError

    exc = DivergenceError("epoch_loss", float("nan"))
    assert classify_failure(exc) == "divergence"


def test_telemetry_scope_arms_and_disarms_incident_plane(tmp_path):
    d = str(tmp_path)
    with telemetry.telemetry_run(d):
        assert telemetry.get_flight_ring() is not None
        det = telemetry.get_incident_detector()
        assert det is not None
        bus = get_bus()
        bus.emit("shard_fence_lost", shard=0, replica=0, reason="outbid")
        # The tap fed the ring and the detector through the same emit.
        assert telemetry.get_flight_ring().noted >= 1
        assert len(det.open_incidents()) == 1
        # The detector's own incident event must not re-trigger it.
        kinds = [e.kind for e in bus.recent()]
        assert "incident" in kinds
        assert det.opened == 1
    assert telemetry.get_flight_ring() is None
    assert telemetry.get_incident_detector() is None
    assert os.path.exists(os.path.join(d, tincident.INCIDENTS_NAME))


def test_offline_replay_matches_live_fold(tmp_path):
    d = str(tmp_path)
    events = [
        _ev("shard_fence_lost", ts=1.0, shard=0, replica=0,
            reason="outbid"),
        _ev("shard_adopted", ts=2.0, shard=0, replica=1, epoch=2),
        _ev("ckpt_scan_reject", ts=3.0, path="/r/t1/c.msgpack",
            reason="crc"),
    ]
    det = IncidentDetector(d, emit_events=False)
    for ev in events:
        det.observe(ev)
    # Compare (kind, subject): counts differ by design — the live
    # ledger flushes absorbed-echo counts only on escalate/resolve.
    live = {
        (i["kind"], i["subject"]) for i in load_incidents(d).values()
    }
    offline = {
        (i["kind"], i["subject"])
        for i in detect_incidents(events).values()
    }
    assert live == offline


# -- the production seams, through a real sweep ------------------------


@pytest.mark.parametrize(
    "specs,trials,epochs,verdict",
    [
        ((), 2, 1, None),  # the soak: no fault, no incident
        (tuple(FaultSpec(DIVERGE, t, step=2) for t in range(3)), 3, 1,
         DIVERGENCE_STORM),
        # 8 steps an epoch: the only checkpoint rots, the crash's retry
        # scans it and rejects it.
        ((FaultSpec(CKPT_CORRUPT, 0, epoch=1), FaultSpec(CRASH, 0, step=11)),
         1, 2, CKPT_INTEGRITY),
        ((FaultSpec(PREEMPT, 0, step=2),), 1, 1, HOST_PREEMPTED),
    ],
    ids=["soak", "diverge_storm", "ckpt_corrupt", "preempt"],
)
def test_sweep_fault_opens_exactly_one_incident(
    specs, trials, epochs, verdict, tmp_path
):
    """A fault injected into a real ``run_hpo`` sweep reaches the
    detector through the production emits: exactly one incident of the
    expected kind, its black-box bundle published; none without a
    cause."""
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.hpo.driver import TrialConfig, run_hpo
    from multidisttorch_tpu.hpo.supervision import RetryPolicy

    d = str(tmp_path)
    cfgs = [
        TrialConfig(trial_id=t, epochs=epochs, batch_size=16, hidden_dim=32,
                    latent_dim=8, log_interval=10_000, seed=t)
        for t in range(trials)
    ]
    with telemetry.telemetry_run(d):
        try:
            run_hpo(
                cfgs, synthetic_mnist(128, seed=0), None, num_groups=1,
                out_dir=os.path.join(d, "sweep"), verbose=False,
                save_images=False, resilient=True,
                retry=RetryPolicy(max_retries=2, backoff_base_s=0.01),
                fault_plan=FaultPlan(specs=specs),
            )
        except HostPreemption:  # escapes even a resilient sweep
            assert verdict == HOST_PREEMPTED
    incs = list(load_incidents(d).values())
    assert [i["kind"] for i in incs] == ([verdict] if verdict else [])
    for inc in incs:
        bundle = os.path.join(d, tincident.BUNDLE_DIRNAME, inc["id"])
        for name in ("trigger.json", "flight_ring.json"):
            assert os.path.isfile(os.path.join(bundle, name))


# -- causal autopsy ---------------------------------------------------


def test_autopsy_report_and_exports(tmp_path):
    d = str(tmp_path)
    with telemetry.telemetry_run(d):
        bus = get_bus()
        bus.emit("shard_fence_lost", shard=0, replica=0,
                 reason="lease expired")
        bus.emit("shard_adopted", shard=0, replica=1, epoch=2,
                 replayed_submissions=3)
    folded = load_incidents(d)
    (iid,) = folded
    report = tincident.build_incident_report(d, iid)
    assert report["verdict"] == FENCE_LOST
    assert report["incident"]["id"] == iid
    # The event stream next to the ledger is a cited surface, and the
    # causal chain includes both halves of the takeover.
    assert "events" in report["corroborating_surfaces"]
    cited = [
        r["rec"].get("kind")
        for r in report["timeline"]
        if r["source"] == "events"
    ]
    assert "shard_fence_lost" in cited
    assert "shard_adopted" in cited
    out = report["bundle_dir"]
    for name in ("report.json", "perfetto.json", "affected_traces.json"):
        assert os.path.isfile(os.path.join(out, name))
    with open(os.path.join(out, "perfetto.json")) as f:
        perf = json.load(f)
    assert any(e.get("ph") == "X" for e in perf["traceEvents"])
    # Unknown id: loud, with the known ids in the message.
    with pytest.raises(KeyError):
        tincident.build_incident_report(d, "inc-nope")


# -- slo_alert exemplar satellite -------------------------------------


def test_slo_alert_exemplar_present_and_byte_compat(tmp_path):
    from multidisttorch_tpu.telemetry.metrics import Histogram
    from multidisttorch_tpu.telemetry.slo import LATENCY, SloEngine, SloSpec

    def spec():
        return SloSpec(
            name="q", kind=LATENCY, source="queue_wait",
            threshold_s=0.1, objective=0.9, windows=((5.0, 1.0),),
        )

    def burn(eng):
        t = 1000.0
        for i in range(20):
            eng.observe_latency("queue_wait", 3.0, ts=t + i * 0.1)
        eng.evaluate(now=t + 2.5)

    d0 = str(tmp_path / "bare")
    with telemetry.telemetry_run(d0):
        burn(SloEngine((spec(),)))
    d1 = str(tmp_path / "exemplar")
    with telemetry.telemetry_run(d1):
        eng = SloEngine((spec(),))
        hist = Histogram((0.1, 1.0, 10.0))
        for i in range(20):
            hist.observe(3.0, exemplar=f"sub-{i:03d}")
        eng.attach_exemplar("queue_wait", hist)
        burn(eng)

    def alert(d):
        evs = telemetry.read_events(os.path.join(d, "events.jsonl"))
        return next(e for e in evs if e["kind"] == "slo_alert")

    bare, rich = alert(d0), alert(d1)
    # Nothing attached => the field is NEVER serialized (byte-compat
    # with pre-exemplar streams).
    assert "exemplar" not in bare["data"]
    ex = rich["data"]["exemplar"]
    assert ex["id"].startswith("sub-")
    assert ex["value_s"] == pytest.approx(3.0)
    # And the incident carries the citation into its detail.
    (inc,) = load_incidents(d1).values()
    assert inc["kind"] == SLO_BURN
    assert inc["detail"]["exemplar"]["id"] == ex["id"]
    (inc0,) = load_incidents(d0).values()
    assert "exemplar" not in inc0["detail"]


# -- console + CLI ----------------------------------------------------


def _scripted_service_dir(tmp_path) -> str:
    d = str(tmp_path / "svc")
    with telemetry.telemetry_run(os.path.join(d, "telemetry")):
        bus = get_bus()
        bus.emit("shard_fence_lost", shard=0, replica=1, reason="outbid")
        bus.emit(
            "failure_classified", trial_id=4,
            failure_class="preemption", exc_type="HostPreemption",
            error="gone",
        )
    return d


def test_sweep_top_incidents_panel_and_json(tmp_path, capsys):
    import importlib

    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools",
        ),
    )
    sweep_top = importlib.import_module("sweep_top")

    d = _scripted_service_dir(tmp_path)
    follow = sweep_top.ServiceFollow(d)
    _q, _b, _s, incidents = follow.refresh()
    assert len(incidents) == 2
    panel = sweep_top.render_incidents_panel(incidents)
    assert "open 2" in panel
    assert "fence_lost" in panel and "host_preempted" in panel
    assert "trial:4" in panel

    # Incremental: an operator resolve appended after the first fold
    # lands on the next refresh without re-reading history.
    iid = next(
        i for i, v in incidents.items() if v["kind"] == FENCE_LOST
    )
    tincident._fsync_append(
        os.path.join(d, "telemetry", tincident.INCIDENTS_NAME),
        {"rec": "resolve", "id": iid, "ts": time.time(),
         "reason": "mitigated", "count": 1, "flaps": 0},
    )
    offset_before = follow.ioffset
    _q, _b, _s, incidents = follow.refresh()
    assert follow.ioffset > offset_before
    assert incidents[iid]["status"] == "resolved"
    assert "resolved 1" in sweep_top.render_incidents_panel(incidents)

    # --json --service carries the incidents block.
    rc = sweep_top.main([d, "--service", "--json"])
    assert rc == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["incidents"][iid]["status"] == "resolved"


def test_incident_cli_list_show_report_resolve_sweep(tmp_path, capsys):
    import importlib

    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools",
        ),
    )
    cli = importlib.import_module("incident")

    d = _scripted_service_dir(tmp_path)
    assert cli.main([d]) == 0
    out = capsys.readouterr().out
    assert "fence_lost" in out and "host_preempted" in out

    folded = load_incidents(d)
    iid = next(i for i, v in folded.items() if v["kind"] == FENCE_LOST)
    assert cli.main([d, "show", iid]) == 0
    assert iid in capsys.readouterr().out

    assert cli.main([d, "report", iid, "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["verdict"] == FENCE_LOST

    assert cli.main([d, "resolve", iid, "--reason", "fixed"]) == 0
    capsys.readouterr()
    assert load_incidents(d)[iid]["status"] == "resolved"
    # Resolving again is a polite no-op.
    assert cli.main([d, "resolve", iid]) == 0
    assert "already resolved" in capsys.readouterr().out

    # sweep quarantines a planted partial bundle.
    part = os.path.join(
        d, "telemetry", tincident.BUNDLE_DIRNAME, "inc-0042.partial"
    )
    os.makedirs(part)
    assert cli.main([d, "sweep"]) == 0
    assert "1 partial bundle(s) quarantined" in capsys.readouterr().out
    assert not os.path.isdir(part)

    with pytest.raises(SystemExit):
        cli.main([d, "show", "inc-nope"])
