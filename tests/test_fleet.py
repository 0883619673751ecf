"""Fleet observability plane (ISSUE 6, docs/OBSERVABILITY.md "Fleet"):
bus-level host/world identity, the cross-host shard merge with its
skew model, world/restart books, the fleet Perfetto trace, the
preflight verdict taxonomy, and the ``sweep_top --fleet`` console.

Everything here is plain files + fabricated streams — no device
runtime, no subprocess worlds (the live kill-one-of-3 drill that
exercises the same layer end-to-end is tests/test_elastic.py's
``multihost`` tier and the CI elastic job). The two exceptions are the
real-CPU preflight smokes, which spawn the probe's own bounded
subprocesses exactly as production does.
"""

import importlib.util
import json
import os
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------
# bus-level fleet identity (the satellite fix + its regression tests)
# --------------------------------------------------------------------


def test_bus_stamps_host_world_on_every_event(tmp_path):
    from multidisttorch_tpu.telemetry import events as E

    path = str(tmp_path / "events.jsonl")
    bus = E.Bus(path=path, host=3, world=1)
    bus.emit("epoch", trial_id=0, step=5)
    bus.emit("fault_injected", trial_id=-1, fault_kind="host_lost")
    bus.close()
    recs = E.read_events(path)
    assert [r["host"] for r in recs] == [3, 3]
    assert [r["world"] for r in recs] == [1, 1]


def test_untagged_single_host_stream_is_byte_stable(tmp_path):
    """The pre-fleet serialization contract, byte for byte: an untagged
    bus must never serialize host/world keys (or reorder the others) —
    a single-host trace written today is identical to one written
    before the fleet layer existed."""
    from multidisttorch_tpu.telemetry import events as E

    path = str(tmp_path / "events.jsonl")
    bus = E.Bus(path=path)
    ev = bus.emit("epoch", trial_id=1, step=2, loss=0.5)
    bus.close()
    line = open(path).read().splitlines()[0]
    expected = json.dumps(
        {
            "kind": "epoch",
            "ts": ev.ts,
            "trial_id": 1,
            "step": 2,
            "data": {"loss": 0.5},
        }
    )
    assert line == expected
    assert "host" not in line and "world" not in line


def test_configure_defaults_tags_from_supervisor_env(tmp_path, monkeypatch):
    from multidisttorch_tpu.telemetry import events as E

    monkeypatch.setenv("MDT_HOST_SLOT", "2")
    monkeypatch.setenv("MDT_WORLD_EPOCH", "1")
    bus = E.configure(path=None)
    try:
        assert bus.host == 2 and bus.world == 1
    finally:
        E.disable()
    # explicit wins over env; garbage env degrades to untagged
    bus = E.configure(path=None, host=7)
    try:
        assert bus.host == 7
    finally:
        E.disable()
    monkeypatch.setenv("MDT_HOST_SLOT", "not-a-slot")
    monkeypatch.delenv("MDT_WORLD_EPOCH")
    bus = E.configure(path=None)
    try:
        assert bus.host is None and bus.world is None
    finally:
        E.disable()


# --------------------------------------------------------------------
# fabricated fleet run dirs
# --------------------------------------------------------------------


def _ev(kind, ts, host=None, world=None, trial_id=None, attempt=None,
        step=None, **data):
    d = {"kind": kind, "ts": ts}
    if trial_id is not None:
        d["trial_id"] = trial_id
    if attempt is not None:
        d["attempt"] = attempt
    if step is not None:
        d["step"] = step
    if host is not None:
        d["host"] = host
    if world is not None:
        d["world"] = world
    if data:
        d["data"] = data
    return d


def _write_shard(run_dir, rel, events, torn_tail=False):
    path = os.path.join(run_dir, "telemetry", rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
        if torn_tail:
            f.write('{"kind": "epoch", "ts": 99.0, "tr')
    return path


def _attempt_pair(t0, host, world, trial_id, steps=10, status="completed"):
    return [
        _ev("attempt_start", t0, host=host, world=world,
            trial_id=trial_id, attempt=1),
        _ev("attempt_end", t0 + 1.0, host=host, world=world,
            trial_id=trial_id, attempt=1, status=status,
            summary={"steps": steps, "resumed_from_step": 0}),
    ]


def _fleet_run_dir(tmp_path, *, torn=False):
    """A 3-host, 2-world elastic run: host 1 dies after world 0, trial
    5 migrates host 1 -> host 0, the supervisor emits the restart-tax
    event, and world 1 restores + steps (the evidence the tax report
    joins)."""
    from multidisttorch_tpu.parallel import membership as m

    run_dir = str(tmp_path / "run")
    os.makedirs(m.membership_dir(run_dir))
    worlds_path = os.path.join(m.membership_dir(run_dir), m.WORLDS_NAME)
    with open(worlds_path, "w") as f:
        f.write(json.dumps({"epoch": 0, "hosts": [0, 1, 2], "lost": [],
                            "reason": "", "ts": 9.5}) + "\n")
        f.write(json.dumps({"epoch": 1, "hosts": [0, 2], "lost": [1],
                            "reason": "host_lost", "ts": 20.0}) + "\n")
    # mtime == newest record ts: a zero supervisor skew anchor, like a
    # live run where the fs stamps the append as it happens
    os.utime(worlds_path, (20.0, 20.0))

    # world 0: all three hosts work; trial 5 is host 1's
    w0 = []
    for h in range(3):
        evs = [_ev("sweep_start", 10.0 + h * 0.01, host=h, world=0,
                   configs=6)]
        tid = h  # trials 0..2 settle in world 0
        evs += _attempt_pair(11.0 + h * 0.01, h, 0, tid)
        if h == 1:
            evs.append(_ev("epoch", 12.0, host=1, world=0, trial_id=5,
                           step=8))
        _write_shard(run_dir, f"w0/events.p{h}.jsonl", evs,
                     torn_tail=torn and h == 1)
        w0.append(evs)

    # supervisor stream: untagged; restart_tax marks world 1's launch
    sup = [
        _ev("world_start", 10.0, epoch=0, hosts=[0, 1, 2]),
        _ev("host_lost", 19.5, slot=1, stale_s=3.2, world_epoch=0),
        _ev("world_end", 19.6, epoch=0, outcome="host_lost"),
        _ev("restart_tax", 20.0, world_epoch=1, trigger="host_lost",
            lost=[1], detect_s=3.2, drain_s=0.3, relaunch_s=0.5),
        _ev("world_start", 20.0, epoch=1, hosts=[0, 2]),
    ]
    _write_shard(run_dir, "sup/events.jsonl", sup)

    # world 1: survivors; trial 5 now owned by host 0 (migrated),
    # restores from checkpoint then steps
    w1_h0 = [
        _ev("trial_migrated", 20.5, host=0, world=1, trial_id=5,
            from_host=1, to_host=0),
        _ev("ckpt_restore", 22.0, host=0, world=1, trial_id=5, step=8),
        _ev("epoch", 25.0, host=0, world=1, trial_id=5, step=16),
    ]
    w1_h0 += _attempt_pair(26.0, 0, 1, 5, steps=20,
                           status="completed")
    w1_h2 = _attempt_pair(21.0, 2, 1, 4)
    _write_shard(run_dir, "w1/events.p0.jsonl", w1_h0)
    _write_shard(run_dir, "w1/events.p1.jsonl", w1_h2)
    return run_dir


# --------------------------------------------------------------------
# shard discovery + merge semantics
# --------------------------------------------------------------------


def test_merge_is_deterministic_and_complete(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    run_dir = _fleet_run_dir(tmp_path)
    a = fleet.merge_fleet(run_dir)
    b = fleet.merge_fleet(run_dir)
    assert json.dumps(a["events"]) == json.dumps(b["events"])
    ts = [e["ts"] for e in a["events"]]
    assert ts == sorted(ts)
    assert a["expected_hosts"] == [0, 1, 2]
    assert a["hosts_seen"] == [0, 1, 2]
    assert a["all_hosts_traced"] is True
    assert a["torn_lines_total"] == 0
    n_in = sum(s["events"] for s in a["shards"])
    assert len(a["events"]) == n_in


def test_merge_counts_torn_tail_per_shard(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    run_dir = _fleet_run_dir(tmp_path, torn=True)
    merged = fleet.merge_fleet(run_dir)
    assert merged["torn_lines_total"] == 1
    torn_shards = [s for s in merged["shards"] if s["torn_lines"]]
    assert len(torn_shards) == 1
    assert "w0" in torn_shards[0]["shard"]
    # the decodable prefix of the torn shard still merged
    assert any(
        e.get("kind") == "epoch" and e.get("host") == 1
        for e in merged["events"]
    )


def test_merge_world_falls_back_to_shard_directory(tmp_path):
    """A writer that lost its world tag (pre-fleet stream in a w{k}
    dir) is still attributed to the world its shard lives under."""
    from multidisttorch_tpu.telemetry import fleet

    run_dir = str(tmp_path / "run")
    _write_shard(run_dir, "w2/events.jsonl",
                 [_ev("epoch", 1.0, host=0, trial_id=0, step=1)])
    merged = fleet.merge_fleet(run_dir)
    assert merged["events"][0]["world"] == 2


def test_merge_excludes_its_own_previous_output(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    run_dir = _fleet_run_dir(tmp_path)
    first = fleet.export_fleet(run_dir)
    again = fleet.merge_fleet(run_dir)
    assert len(again["events"]) == first["summary"]["events"]
    assert not any("fleet" in s["shard"] for s in again["shards"])


def test_missing_host_shard_fails_the_traced_gate(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    run_dir = _fleet_run_dir(tmp_path)
    # host 1 wrote only the world-0 shard (it died in the shrink):
    # losing that file means the merged timeline no longer covers it
    os.remove(os.path.join(run_dir, "telemetry", "w0",
                           "events.p1.jsonl"))
    merged = fleet.merge_fleet(run_dir)
    assert merged["all_hosts_traced"] is False
    assert 1 not in merged["hosts_seen"]


# --------------------------------------------------------------------
# the skew model
# --------------------------------------------------------------------


def test_skew_from_anchors_clamps_noise_and_keeps_real_offsets():
    from multidisttorch_tpu.telemetry import fleet

    applied = fleet.skew_from_anchors(
        {0: 0.1, 1: -0.2, 2: 5.0, 3: -1.5, "sup": 0.01},
        min_skew_s=0.25,
    )
    assert applied == {0: 0.0, 1: 0.0, 2: 5.0, 3: -1.5, "sup": 0.0}
    # pure + deterministic: same anchors, same corrections
    assert applied == fleet.skew_from_anchors(
        {0: 0.1, 1: -0.2, 2: 5.0, 3: -1.5, "sup": 0.01},
        min_skew_s=0.25,
    )


def test_merge_applies_lease_anchored_skew_correction(tmp_path):
    """Host 0's wall clock runs 5 s behind the shared fs clock (its
    lease's newest ts is 5 s older than the file's mtime): its events
    must shift forward by 5 s onto the fleet clock, keeping the raw
    stamp in ts_raw; the in-sync host is untouched."""
    from multidisttorch_tpu.parallel import membership as m
    from multidisttorch_tpu.telemetry import fleet

    run_dir = str(tmp_path / "run")
    os.makedirs(m.membership_dir(run_dir))
    now = time.time()
    for slot, skew in ((0, -5.0), (1, 0.0)):
        path = m.lease_path(run_dir, slot)
        with open(path, "w") as f:
            for i in range(3):
                f.write(json.dumps({
                    "slot": slot, "ts": now + skew + i * 0.25,
                    "mono": 100.0 + i * 0.25, "status": "alive",
                }) + "\n")
        newest = now + skew + 2 * 0.25
        os.utime(path, (newest - skew, newest - skew))
    _write_shard(run_dir, "w0/events.p0.jsonl",
                 [_ev("epoch", now - 5.0, host=0, trial_id=0, step=1)])
    _write_shard(run_dir, "w0/events.p1.jsonl",
                 [_ev("epoch", now, host=1, trial_id=1, step=1)])

    merged = fleet.merge_fleet(run_dir)
    by_host = {e["host"]: e for e in merged["events"]}
    assert by_host[0]["ts"] == pytest.approx(now, abs=0.05)
    assert by_host[0]["ts_raw"] == pytest.approx(now - 5.0, abs=1e-9)
    assert "ts_raw" not in by_host[1]
    assert merged["skew"]["0"]["applied_offset_s"] == pytest.approx(
        5.0, abs=0.05
    )
    assert merged["skew"]["1"]["applied_offset_s"] == 0.0


def test_wall_clock_step_reported_not_folded():
    from multidisttorch_tpu.telemetry.fleet import _wall_step_diagnostics

    steady = [
        {"ts": 100.0 + i, "mono": 50.0 + i} for i in range(5)
    ]
    assert _wall_step_diagnostics(steady)["wall_clock_steps"] == 0
    jumped = list(steady)
    # NTP yanks the wall clock 30 s forward between beats 4 and 5
    jumped.append({"ts": 135.0, "mono": 55.0})
    diag = _wall_step_diagnostics(jumped)
    assert diag["wall_clock_steps"] == 1
    assert diag["max_wall_mono_drift_s"] == pytest.approx(30.0, abs=0.1)


# --------------------------------------------------------------------
# lineage, per-world books, restart tax
# --------------------------------------------------------------------


def test_trial_lineage_tracks_migration_across_worlds(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    merged = fleet.merge_fleet(_fleet_run_dir(tmp_path))
    lineage = fleet.trial_lineage(merged["events"])
    chain = lineage[5]
    assert [(c["world"], c["host"]) for c in chain] == [(0, 1), (1, 0)]
    assert chain[0]["last_ts"] <= chain[1]["first_ts"]


def test_per_world_books_fold_goodput_and_dedup_echoes(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    merged = fleet.merge_fleet(_fleet_run_dir(tmp_path))
    # a multi-controller echo of an already-counted attempt_end
    events = merged["events"] + [
        _ev("attempt_end", 26.9, host=2, world=1, trial_id=5, attempt=1,
            status="completed",
            summary={"steps": 20, "resumed_from_step": 0}),
    ]
    books = fleet.per_world_books(events)
    assert books["0"]["attempt_ends"] == 3
    assert books["1"]["attempt_ends"] == 2  # echo deduplicated
    assert books["1"]["useful_steps"] == 30
    assert books["0"]["goodput"] == 1.0
    assert books["1"]["hosts"] == [0, 2]


def test_restart_tax_joins_live_phases_with_worker_evidence(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    merged = fleet.merge_fleet(_fleet_run_dir(tmp_path))
    (tax,) = fleet.restart_tax_report(merged["events"])
    assert tax["world_epoch"] == 1
    assert tax["trigger"] == "host_lost" and tax["lost"] == [1]
    # live phases straight off the supervisor's event
    assert tax["detect_s"] == 3.2
    assert tax["drain_s"] == 0.3
    assert tax["relaunch_s"] == 0.5
    # joined phases: launch at ts=20, first restore at 22, first epoch
    # completion at 25
    assert tax["restore_s"] == pytest.approx(2.0)
    assert tax["first_useful_step_s"] == pytest.approx(5.0)
    assert tax["total_s"] == pytest.approx(3.2 + 0.3 + 0.5 + 2.0)


# --------------------------------------------------------------------
# the fleet trace
# --------------------------------------------------------------------


def test_fleet_trace_one_process_per_host_with_world_spans(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    merged = fleet.merge_fleet(_fleet_run_dir(tmp_path))
    trace = json.loads(json.dumps(fleet.build_fleet_trace(merged)))
    te = trace["traceEvents"]
    names = {
        e["pid"]: e["args"]["name"]
        for e in te
        if e.get("name") == "process_name"
    }
    assert names[1] == "supervisor"
    assert {names[fleet._host_pid(h)] for h in (0, 1, 2)} == {
        "host 0", "host 1", "host 2",
    }
    # world-epoch SPANS (ph X) on the supervisor track; the sup
    # stream's world_start/world_end instants share the category
    worlds = [e for e in te
              if e.get("cat") == "world" and e.get("ph") == "X"]
    assert [w["name"].split()[1] for w in worlds] == ["0", "1"]
    assert all(w["pid"] == 1 for w in worlds)
    assert worlds[0]["ts"] >= 0  # explicit t0 covers pre-event spans
    assert worlds[0]["dur"] > 0
    # the open final world runs to the last merged event
    assert worlds[1]["ts"] + worlds[1]["dur"] >= max(
        e["ts"] for e in te if "ts" in e
    ) - 1.0
    # non-negative, monotonically ordered timeline
    ts = [e["ts"] for e in te if "ts" in e]
    assert ts == sorted(ts) and ts[0] >= 0


def test_fleet_trace_draws_migration_flow_arrows(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    merged = fleet.merge_fleet(_fleet_run_dir(tmp_path))
    te = fleet.build_fleet_trace(merged)["traceEvents"]
    flows = [e for e in te if e.get("cat") == "migration"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    start = next(e for e in flows if e["ph"] == "s")
    finish = next(e for e in flows if e["ph"] == "f")
    assert start["pid"] == fleet._host_pid(1)  # from host 1...
    assert finish["pid"] == fleet._host_pid(0)  # ...to host 0
    assert start["id"] == finish["id"]
    assert start["ts"] <= finish["ts"]


# --------------------------------------------------------------------
# summary + export
# --------------------------------------------------------------------


def test_fleet_summary_books_and_gates(tmp_path):
    from multidisttorch_tpu.telemetry import fleet

    run_dir = _fleet_run_dir(tmp_path)
    s = fleet.fleet_summary(run_dir, now=lambda: 30.0)
    assert s["protocol"] == "fleet_v1"
    assert s["all_hosts_traced"] is True
    assert s["world_transitions"] == 1
    assert s["world_shrunk_traced"] is False  # fabricated sup stream
    assert set(s["hosts"]) == {"0", "1", "2"}
    assert s["hosts"]["1"]["worlds"] == [0]
    assert s["goodput"] == 1.0
    assert s["restart_tax"][0]["world_epoch"] == 1
    assert s["lineage"]["5"][-1]["host"] == 0
    assert s["migrations"][0]["trial_id"] == 5
    assert s["faults"] == {
        "fired": 0, "traced": 0, "all_faults_traced": True,
    }


def test_export_fleet_writes_all_three_artifacts(tmp_path):
    from multidisttorch_tpu.telemetry import fleet
    from multidisttorch_tpu.telemetry.events import read_events

    run_dir = _fleet_run_dir(tmp_path)
    out = fleet.export_fleet(run_dir)
    paths = out["paths"]
    merged_events = read_events(paths["events"])
    assert len(merged_events) == out["summary"]["events"]
    trace = json.load(open(paths["trace"]))
    assert trace["traceEvents"]
    summary = json.load(open(paths["summary"]))
    assert summary["all_hosts_traced"] is True
    assert summary["restart_tax"]


# --------------------------------------------------------------------
# preflight classification (fake backends — pure classification logic)
# --------------------------------------------------------------------

_OK_PROBE = {"ok": True, "platform": "cpu", "device_kind": "cpu",
             "n_devices": 2, "elapsed_s": 0.1}
_TIMEOUT_PROBE = {"ok": False, "timeout": True, "elapsed_s": 5.0,
                  "error": "backend init still blocked after 5s",
                  "stderr_tail": ""}
_ABSENT_PROBE = {"ok": False, "timeout": False, "elapsed_s": 0.2,
                 "error": "backend init failed (rc=1)",
                 "stderr_tail": "RuntimeError: Unknown backend tpu9"}
_BROKEN_PROBE = {"ok": False, "timeout": False, "elapsed_s": 0.2,
                 "error": "backend init failed (rc=1)",
                 "stderr_tail": "Aborted (core dumped)"}
# jax's generic wrapper around a PRESENT backend that crashed fast —
# must NOT classify as absent (the wrapper prefix alone is ambiguous;
# absence says "... is not in the list of known backends")
_CRASHED_PROBE = {"ok": False, "timeout": False, "elapsed_s": 0.3,
                 "error": "backend init failed (rc=1)",
                 "stderr_tail": "RuntimeError: Unable to initialize "
                 "backend 'tpu': UNAVAILABLE: connection failed"}
_OK_CANARY = {"ok": True, "canary_ok": True, "canary_value": 512.0,
              "n_devices": 2, "platform": "cpu", "device_kind": "cpu",
              "memory_stats": None, "elapsed_s": 0.2}
_BAD_CANARY = {"ok": False, "timeout": False, "elapsed_s": 0.2,
               "error": "canary failed (rc=1)", "stderr_tail": "boom"}


def _triage(holders=(), plugin_procs=()):
    return {
        "device_nodes": "absent",
        "accel_node_holders": list(holders),
        "pjrt_plugin_processes": list(plugin_procs),
    }


def _fake_preflight(monkeypatch, probes, canary=_OK_CANARY,
                    triage=None):
    """Drive run_preflight against a scripted backend: ``probes`` is
    consumed one init probe per call."""
    from multidisttorch_tpu.utils import preflight as pf

    seq = list(probes)
    monkeypatch.setattr(pf, "probe_init",
                        lambda t, platform=None: seq.pop(0))
    monkeypatch.setattr(pf, "probe_canary",
                        lambda t, platform=None: dict(canary))
    monkeypatch.setattr(pf, "plugin_scan",
                        lambda: triage or _triage())
    return pf


@pytest.mark.parametrize(
    "probes,canary,triage,verdict,usable",
    [
        ([_OK_PROBE], _OK_CANARY, None, "healthy", True),
        ([_TIMEOUT_PROBE, _OK_PROBE], _OK_CANARY, None,
         "transient_recovered", True),
        ([_TIMEOUT_PROBE, _TIMEOUT_PROBE], _OK_CANARY,
         _triage(holders=[{"pid": 1, "cmdline": "leaker"}]),
         "wedged_leaked_plugin", False),
        ([_TIMEOUT_PROBE, _TIMEOUT_PROBE], _OK_CANARY, _triage(),
         "wedged_init_timeout", False),
        ([_ABSENT_PROBE], _OK_CANARY, None, "backend_absent", False),
        ([_BROKEN_PROBE, _BROKEN_PROBE], _OK_CANARY, None,
         "init_failed", False),
        ([_CRASHED_PROBE, _CRASHED_PROBE], _OK_CANARY, None,
         "init_failed", False),
        ([_CRASHED_PROBE, _OK_PROBE], _OK_CANARY, None,
         "transient_recovered", True),
        ([_OK_PROBE], _BAD_CANARY, None, "canary_failed", False),
    ],
    ids=["healthy", "transient", "leaked",
         "init_timeout", "absent", "init_failed",
         "crashed_not_absent", "crashed_then_recovered",
         "canary_failed"],
)
def test_preflight_verdict_taxonomy(monkeypatch, probes, canary,
                                    triage, verdict, usable):
    pf = _fake_preflight(monkeypatch, probes, canary=canary,
                         triage=triage)
    report = pf.run_preflight(retry_delay_s=0)
    assert report["verdict"] == verdict
    assert report["usable"] is usable
    assert report["verdict_reason"]
    assert report["verdict"] in pf.VERDICTS
    assert (verdict in pf.USABLE_VERDICTS) == usable


def test_preflight_healthy_skips_the_proc_scan(monkeypatch):
    """The /proc evidence walk is failure-path only: a healthy probe
    (the supervisor's every-world case) must not pay it."""
    from multidisttorch_tpu.utils import preflight as pf

    monkeypatch.setattr(pf, "probe_init",
                        lambda t, platform=None: dict(_OK_PROBE))
    monkeypatch.setattr(pf, "probe_canary",
                        lambda t, platform=None: dict(_OK_CANARY))

    def boom():
        raise AssertionError("plugin_scan must not run on a healthy probe")

    monkeypatch.setattr(pf, "plugin_scan", boom)
    report = pf.run_preflight(retry_delay_s=0)
    assert report["verdict"] == "healthy"
    assert report["triage"] is None


def test_preflight_absent_platform_skips_the_retry_sleep(monkeypatch):
    """An absent platform fails fast and deterministically — the probe
    must classify it WITHOUT the 30 s wedge-retry pause (the CI smoke
    asserts the classified-not-hanging contract end to end)."""
    pf = _fake_preflight(monkeypatch, [_ABSENT_PROBE])
    t0 = time.perf_counter()
    report = pf.run_preflight(retry_delay_s=30)
    assert time.perf_counter() - t0 < 5.0
    assert report["verdict"] == "backend_absent"
    assert all(s["stage"] != "init_retry" for s in report["stages"])


def test_preflight_emits_classified_verdict_events(monkeypatch, tmp_path):
    from multidisttorch_tpu import telemetry
    from multidisttorch_tpu.telemetry.events import read_events

    pf = _fake_preflight(monkeypatch, [_OK_PROBE])
    with telemetry.telemetry_run(str(tmp_path)):
        pf.run_preflight(retry_delay_s=0)
    recs = read_events(str(tmp_path / "events.jsonl"))
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "preflight_start"
    assert "preflight_stage" in kinds
    verdict = next(r for r in recs if r["kind"] == "preflight_verdict")
    assert verdict["data"]["verdict"] == "healthy"
    assert verdict["data"]["usable"] is True


def test_preflight_real_cpu_smoke():
    """The out-of-process probe against the real CPU backend: healthy,
    canary executes, bounded wall time."""
    from multidisttorch_tpu.utils import preflight as pf

    report = pf.run_preflight(
        "cpu", init_timeout_s=120, canary_timeout_s=120,
        retry_delay_s=0, scan=False,
    )
    assert report["verdict"] == "healthy" and report["usable"]
    assert report["device"]["platform"] == "cpu"
    canary = next(s for s in report["stages"] if s["stage"] == "canary")
    assert canary["ok"] and canary["canary_value"] == 512.0


def test_supervisor_preflight_refuses_bad_backend(monkeypatch, tmp_path):
    """A non-usable verdict aborts the launch with the classified
    reason instead of wedging N workers into the boot grace."""
    from multidisttorch_tpu.utils import preflight as pf

    sweep_supervisor = _load_tool("sweep_supervisor")
    monkeypatch.setattr(
        pf, "run_preflight",
        lambda *a, **k: {
            "verdict": pf.WEDGED_INIT_TIMEOUT,
            "verdict_reason": "init blocked after 5s",
            "usable": False,
        },
    )
    sup = sweep_supervisor.ElasticSupervisor(
        ["true"], str(tmp_path), 2, preflight=True,
    )
    with pytest.raises(RuntimeError, match="wedged_init_timeout"):
        sup._run_preflight()
    assert sup.preflight_report["usable"] is False


def test_preflight_cli_classifies_cpu_and_writes_report(tmp_path, capsys):
    preflight_cli = _load_tool("preflight")
    out_path = str(tmp_path / "preflight.json")
    rc = preflight_cli.main([
        "--platform", "cpu", "--no-scan", "--retry-delay", "0",
        "--json", "--out", out_path,
    ])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["verdict"] == "healthy"
    assert json.load(open(out_path))["verdict"] == "healthy"


# --------------------------------------------------------------------
# the fleet console
# --------------------------------------------------------------------


def test_host_health_verdicts():
    from multidisttorch_tpu.telemetry.console import host_health

    assert host_health("alive", 0.5) == "up"
    assert host_health("alive", 10.0) == "STALE"
    assert host_health("left", 100.0) == "left"
    assert host_health("draining", 0.1) == "drain"
    assert host_health("alive", None) == "?"


def test_sweep_top_fleet_render(tmp_path, capsys):
    sweep_top = _load_tool("sweep_top")
    run_dir = _fleet_run_dir(tmp_path)
    assert sweep_top.main([run_dir, "--fleet"]) == 0
    out = capsys.readouterr().out
    assert "hosts" in out and "worlds" in out
    assert "restart tax" in out
    assert "trial 5: w0@h1 -> w1@h0" in out
    # world history rows with the shrink reason
    assert "host_lost" in out


def test_sweep_top_fleet_json_snapshot(tmp_path, capsys):
    sweep_top = _load_tool("sweep_top")
    run_dir = _fleet_run_dir(tmp_path)
    assert sweep_top.main([run_dir, "--fleet", "--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["all_hosts_traced"] is True
    assert snap["restart_tax"][0]["trigger"] == "host_lost"
    assert "5" in snap["lineage"]
    assert "trials" in snap and snap["trials"]


def test_sweep_top_fleet_rejects_non_directory(tmp_path, capsys):
    sweep_top = _load_tool("sweep_top")
    assert sweep_top.main([str(tmp_path / "nope"), "--fleet"]) == 1
