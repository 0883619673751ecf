"""Telemetry tests: event bus (bounded queue, torn-tail JSONL, ordering
across retry boundaries), exporters (Perfetto trace loads + monotonic,
Prometheus dump parses), stacked step-time attribution, and the
zero-cost-when-off contract (no event objects constructed on hot paths
with telemetry disabled — the CI tier-1 guard of ISSUE 3)."""

import importlib.util
import json
import logging
import os
import re

import numpy as np
import pytest

from multidisttorch_tpu import telemetry
from multidisttorch_tpu.data.datasets import synthetic_mnist
from multidisttorch_tpu.faults.plan import CRASH, FaultPlan, FaultSpec
from multidisttorch_tpu.hpo.driver import TrialConfig, run_hpo
from multidisttorch_tpu.hpo.supervision import RetryPolicy
from multidisttorch_tpu.telemetry import anomaly as tele_anomaly
from multidisttorch_tpu.telemetry import device as tele_device
from multidisttorch_tpu.telemetry import events as tele_events
from multidisttorch_tpu.telemetry import export as tele_export
from multidisttorch_tpu.telemetry import metrics as tele_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    """Every test leaves telemetry globally OFF (the default state the
    rest of the suite assumes)."""
    yield
    telemetry.disable()


def small_configs(n, epochs=1, **kw):
    return [
        TrialConfig(
            trial_id=i, epochs=epochs, batch_size=16, hidden_dim=16,
            latent_dim=4, seed=i, log_interval=10_000, **kw,
        )
        for i in range(n)
    ]


# -- event bus ---------------------------------------------------------


def test_bounded_queue_drops_oldest():
    bus = tele_events.Bus(queue_max=4)
    for i in range(10):
        bus.emit("tick", step=i)
    recent = bus.recent()
    assert len(recent) == 4
    assert [e.step for e in recent] == [6, 7, 8, 9]  # newest kept
    assert bus.dropped == 6
    assert bus.emitted == 10


def test_jsonl_sink_and_torn_tail(tmp_path):
    path = str(tmp_path / "events.jsonl")
    bus = tele_events.Bus(path=path)
    for i in range(3):
        bus.emit("tick", step=i, trial_id=1)
    bus.close()
    # A crash mid-append tears the final line; the reader must skip it
    # (same contract as the sweep ledger).
    with open(path, "a") as f:
        f.write('{"kind": "torn", "ts": 1.0, "da')
    got = tele_events.read_events(path)
    assert [e["step"] for e in got] == [0, 1, 2]
    assert all(e["kind"] == "tick" for e in got)
    # Event fields round-trip; identity tags ride at the top level.
    assert got[0]["trial_id"] == 1


def test_bus_survives_sink_failure(tmp_path):
    path = str(tmp_path / "events.jsonl")
    bus = tele_events.Bus(path=path)
    bus.emit("a")
    bus._sink.close()  # simulate the fd dying under the bus
    bus.emit("b")  # must not raise; degrades to in-memory only
    assert [e.kind for e in bus.recent()] == ["a", "b"]
    assert bus._sink is None


# -- event ordering across a retry boundary (driver integration) -------


def test_event_ordering_across_retry(tmp_path):
    tdir = str(tmp_path / "tele")
    cfgs = small_configs(2, epochs=2)
    data = synthetic_mnist(64, seed=0)
    plan = FaultPlan(specs=(FaultSpec(CRASH, 0, step=5),))
    with telemetry.telemetry_run(tdir):
        results = run_hpo(
            cfgs, data, None, num_groups=2,
            out_dir=str(tmp_path / "out"),
            save_images=False, verbose=False,
            resilient=True, retry=RetryPolicy(max_retries=2,
                                              backoff_base_s=0.01),
            fault_plan=plan,
        )
    assert all(
        r.status in ("completed", "resumed_complete") for r in results
    )
    events = tele_events.read_events(os.path.join(tdir, "events.jsonl"))
    # Timestamps are monotone non-decreasing in append order.
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)
    # Trial 0's lifecycle reads in causal order across the retry
    # boundary: start(1) .. fault .. end(retrying) .. start(2) ..
    # end(completed).
    seq = [
        (e["kind"], (e.get("data") or {}).get("status"))
        for e in events
        if e.get("trial_id") == 0
        and e["kind"] in ("attempt_start", "attempt_end",
                          "fault_injected", "retry_scheduled")
    ]
    kinds = [k for k, _ in seq]
    assert kinds.index("fault_injected") > kinds.index("attempt_start")
    assert ("attempt_end", "retrying") in seq
    assert ("attempt_end", "completed") in seq
    assert seq.index(("attempt_end", "retrying")) < seq.index(
        ("attempt_end", "completed")
    )
    # The second attempt_start lands after the retrying end.
    starts = [i for i, (k, _) in enumerate(seq) if k == "attempt_start"]
    assert len(starts) == 2
    assert starts[1] > seq.index(("attempt_end", "retrying"))
    # The scheduled retry itself is an event.
    assert "retry_scheduled" in kinds


def test_stacked_sweep_emits_bucket_and_lane_events(tmp_path):
    tdir = str(tmp_path / "tele")
    cfgs = small_configs(3, epochs=1)
    data = synthetic_mnist(64, seed=0)
    with telemetry.telemetry_run(tdir):
        results = run_hpo(
            cfgs, data, None, num_groups=1,
            out_dir=str(tmp_path / "out"),
            save_images=False, verbose=False,
            stack_trials=True, stack_max_lanes=2,
        )
    assert [r.status for r in results] == ["completed"] * 3
    events = tele_events.read_events(os.path.join(tdir, "events.jsonl"))
    kinds = [e["kind"] for e in events]
    assert "stack_bucket" in kinds
    # 3 trials over 2 lanes: every retirement frees a lane; one refill
    # (the queued third trial) and two terminal maskings.
    assert kinds.count("lane_retire") == 3
    assert kinds.count("lane_refill") == 1
    assert kinds.count("lane_masked") == 2
    # Stacked epochs are lane-tagged.
    lanes = {e.get("lane") for e in events if e["kind"] == "epoch"}
    assert lanes <= {0, 1} and lanes


# -- exporters ---------------------------------------------------------


def _demo_events(tmp_path):
    tdir = str(tmp_path / "tele")
    cfgs = small_configs(2, epochs=1)
    data = synthetic_mnist(64, seed=0)
    plan = FaultPlan(specs=(FaultSpec(CRASH, 0, step=1),))
    with telemetry.telemetry_run(tdir):
        run_hpo(
            cfgs, data, None, num_groups=2,
            out_dir=str(tmp_path / "out"),
            save_images=False, verbose=False,
            resilient=True,
            retry=RetryPolicy(max_retries=1, backoff_base_s=0.01),
            fault_plan=plan,
        )
        reg = telemetry.get_registry()
        paths = tele_export.export_all(tdir, registry=reg)
    return tdir, paths


def test_trace_export_loads_and_is_monotonic(tmp_path):
    _tdir, paths = _demo_events(tmp_path)
    with open(paths["trace"]) as f:
        trace = json.loads(f.read())  # loads == Perfetto-parseable JSON
    evs = trace["traceEvents"]
    assert evs, "trace must not be empty"
    ts = [e["ts"] for e in evs if "ts" in e]
    assert ts == sorted(ts), "trace timestamps must be monotonic"
    assert all(t >= 0 for t in ts)
    # One track per trial: thread_name metadata for both trials, and
    # the attempt spans ride their trial's tid.
    names = {
        e["args"]["name"] for e in evs if e.get("name") == "thread_name"
    }
    assert {"driver", "trial 0", "trial 1"} <= names
    spans = [e for e in evs if e.get("ph") == "X"]
    assert spans and all(e["dur"] >= 0 for e in spans)
    # The injected fault appears as a tagged instant on trial 0's track.
    faults = [e for e in evs if e.get("name") == "fault_injected"]
    assert faults and faults[0]["tid"] == 1  # tid = trial_id + 1
    assert faults[0]["args"]["fault_kind"] == "crash"


_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+informna]+$"
)
_PROM_TYPE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$"
)


def test_prometheus_dump_parses(tmp_path):
    _tdir, paths = _demo_events(tmp_path)
    with open(paths["prometheus"]) as f:
        text = f.read()
    assert text.strip(), "dump must not be empty"
    seen_samples = 0
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert _PROM_TYPE.match(line), f"bad TYPE line: {line!r}"
        else:
            assert _PROM_SAMPLE.match(line), f"bad sample line: {line!r}"
            seen_samples += 1
    assert seen_samples >= 3
    # Histogram buckets are cumulative (monotone in le order as dumped).
    for name in {
        line.split("{")[0]
        for line in text.splitlines()
        if "_bucket{" in line
    }:
        series = [
            float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith(name + "{")
        ]
        assert series == sorted(series)


def test_run_summary_accounting(tmp_path):
    tdir, paths = _demo_events(tmp_path)
    with open(paths["summary"]) as f:
        summary = json.load(f)
    assert summary["events"] == len(
        tele_events.read_events(os.path.join(tdir, "events.jsonl"))
    )
    # Trial 0 crashed once and retried: 2 attempts, 1 retry; goodput
    # counts its replayed work in the denominator only.
    t0 = summary["trials"]["0"]
    assert t0["attempts"] == 2
    assert t0["retries"] == 1
    assert t0["status"] == "completed"
    assert summary["executed_steps"] >= summary["useful_steps"] > 0
    assert 0 < summary["goodput"] <= 1.0
    assert "metrics" in summary  # registry snapshot embedded


# -- zero-cost-when-off (the CI tier-1 guard) --------------------------


class _Boom:
    def __init__(self, *a, **kw):
        raise AssertionError(
            "telemetry Event constructed with telemetry OFF — the "
            "zero-cost contract is broken"
        )


def _boom_fn(*a, **kw):
    raise AssertionError(
        "telemetry device/anomaly seam reached with telemetry OFF — the "
        "zero-cost contract is broken"
    )


def test_telemetry_off_constructs_no_events(tmp_path, monkeypatch):
    assert telemetry.get_bus() is None and telemetry.get_registry() is None
    assert telemetry.get_monitor() is None
    # Any Event construction anywhere in the sweep now explodes.
    monkeypatch.setattr(tele_events, "Event", _Boom)
    monkeypatch.setattr(
        tele_metrics, "StepSeries", _Boom
    )  # and no step series either
    # ...and no device-book or anomaly objects either (ISSUE 4): the
    # cost/memory/straggler seams must all sit behind the same guards.
    monkeypatch.setattr(tele_device, "record_step_cost", _boom_fn)
    monkeypatch.setattr(tele_device, "sample_memory", _boom_fn)
    monkeypatch.setattr(tele_device, "compiled_cost_analysis", _boom_fn)
    monkeypatch.setattr(tele_anomaly, "RollingRobustZ", _Boom)
    monkeypatch.setattr(tele_anomaly, "AnomalyMonitor", _Boom)
    cfgs = small_configs(2, epochs=1)
    data = synthetic_mnist(64, seed=0)
    results = run_hpo(
        cfgs, data, data, num_groups=2,
        out_dir=str(tmp_path / "out"),
        save_images=False, verbose=False,
    )
    assert [r.status for r in results] == ["completed"] * 2
    assert telemetry.get_bus() is None


# -- compile accounting (from the program's compile log) ---------------


def test_compile_count_is_programs_not_traces(tmp_path):
    """``compile_count`` counts programs compiled or loaded, one a
    ``backend`` entry of the compile log: a function with an inner
    ``jit`` is traced several times over and compiled once, and the
    cache's ``compile_time_saved_sec`` is no compile time."""
    import jax
    import jax.numpy as jnp

    with telemetry.telemetry_run(str(tmp_path / "tel")):
        reg = tele_metrics.get_registry()

        @jax.jit
        def inner(x):
            return jnp.sin(x) * 2.0

        def outer(x):
            return inner(x) + jnp.where(x > 0, x, 0.0)

        x = jnp.arange(5, dtype=jnp.float32) - 2.0  # made before counting
        traces = []

        def on_secs(event, secs, **kw):
            if event.endswith("jaxpr_trace_duration"):
                traces.append(kw["fun_name"])

        before = reg.counter("compile_count").value
        before_s = reg.counter("compile_seconds").value
        jax.monitoring.register_event_duration_secs_listener(on_secs)
        try:
            jax.jit(outer)(x).block_until_ready()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_secs)
        assert len(traces) > 1 and "inner" in traces  # outer, inner, sin, ...
        assert reg.counter("compile_count").value - before == 1
        assert reg.counter("compile_seconds").value > before_s
        jax.jit(outer)(x).block_until_ready()  # cached in memory: nothing
        assert reg.counter("compile_count").value - before == 1


# -- step-time semantics (StepSeries) ---------------------------------


def test_step_series_open_interval():
    """open_interval breaks the chain: the next mark opens instead of
    closing a boundary-spanning interval (epoch boundaries must not
    read as giant steps)."""
    s = tele_metrics.StepSeries(sample_every=0)
    s.mark()
    assert s.mark() is not None  # normal chained mark observes
    s.open_interval()
    assert s.mark() is None  # re-opened: nothing observed
    assert s.mark() is not None
    assert s.dispatches == 2


def test_step_series_synced_mark_returns_none():
    """A device-synced sample's interval includes the drained dispatch
    backlog — it must go to the device book but NOT be returned as a
    dispatch dt (the straggler detector would false-fire on it every
    sample_every marks and burn its capture budget)."""
    import jax.numpy as jnp

    v = jnp.zeros(())
    s = tele_metrics.StepSeries(sample_every=1)  # every mark syncs
    s.mark(v)  # opening
    assert s.mark(v) is None
    assert s.device.count == 1  # ...but the device book observed it
    s2 = tele_metrics.StepSeries(sample_every=0)  # never syncs
    s2.mark(v)
    assert s2.mark(v) is not None  # dispatch marks still feed the det.


def test_step_series_per_lane_rate():
    s = tele_metrics.StepSeries(sample_every=0)
    s.mark()  # opens the first interval
    for _ in range(5):
        s.mark(steps=2, lanes=3)  # fused-2 dispatches on a 3-lane bucket
    snap = s.snapshot()
    assert snap["dispatches"] == 5
    assert snap["steps"] == 10
    assert snap["lane_steps"] == 30
    assert snap["per_lane_steps_per_s"] == pytest.approx(
        3 * snap["steps_per_s"]
    )
    assert snap["dispatch"]["count"] == 5


def test_histogram_percentile_buckets():
    h = tele_metrics.Histogram(bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 4
    assert h.percentile(50) == 0.1  # bucket upper bound estimate
    assert h.percentile(100) == 10.0
    h.observe(100.0)  # +Inf bucket reports the max seen
    assert h.percentile(100) == 100.0


def test_registry_labels_and_snapshot():
    reg = tele_metrics.MetricsRegistry()
    reg.counter("retries", trial="3").inc()
    reg.counter("retries", trial="3").inc()
    reg.gauge("lanes", group="0").set(4)
    snap = reg.snapshot()
    assert snap["counters"]['retries{trial="3"}'] == 2.0
    assert snap["gauges"]['lanes{group="0"}'] == 4.0


# -- console tools -----------------------------------------------------


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sweep_top_renders_live_and_finished(tmp_path, capsys):
    tdir, _paths = _demo_events(tmp_path)
    sweep_top = _load_tool("sweep_top")
    assert sweep_top.main([tdir]) == 0
    out = capsys.readouterr().out
    assert "goodput" in out
    assert "sweep finished" in out
    assert re.search(r"^0\s+ok", out, re.M)  # trial 0 row, completed
    assert re.search(r"^0\s+ok\s+2", out, re.M)  # ...on attempt 2
    # Live tail: truncate the file mid-line; the renderer holds the
    # torn tail for the next poll instead of crashing.
    ev_path = os.path.join(tdir, "events.jsonl")
    blob = open(ev_path).read()
    open(ev_path, "w").write(blob[: len(blob) // 2])
    assert sweep_top.main([ev_path]) == 0


def test_ledger_view_settled_vs_in_flight(tmp_path, capsys):
    from multidisttorch_tpu.hpo.ledger import SweepLedger

    out_dir = str(tmp_path / "sweep")
    led = SweepLedger(out_dir)
    led.attempt_start(0, "aaaa", 1)
    led.attempt_end(0, "aaaa", 1, "completed", summary={"steps": 8})
    led.attempt_start(1, "bbbb", 1)
    led.attempt_end(1, "bbbb", 1, "retrying", error="boom")
    led.attempt_start(1, "bbbb", 2)  # in flight: no end record
    ledger_view = _load_tool("ledger_view")
    assert ledger_view.main([out_dir]) == 0
    out = capsys.readouterr().out
    assert "SETTLED" in out and "IN-FLIGHT" in out
    assert "#1:ok" in out
    assert "#1:retry -> #2:run" in out


def test_sweep_top_missing_file_errors(tmp_path, capsys):
    sweep_top = _load_tool("sweep_top")
    assert sweep_top.main([str(tmp_path / "nope")]) == 1


def test_sweep_top_json_snapshot(tmp_path, capsys):
    """--json: machine-readable one-shot of the same fold (ISSUE 4
    satellite) — CI consumes this instead of screen-scraping."""
    tdir, _paths = _demo_events(tmp_path)
    capsys.readouterr()  # drain the demo sweep's own log lines
    sweep_top = _load_tool("sweep_top")
    assert sweep_top.main([tdir, "--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["done"] is True
    assert snap["goodput"] is not None
    t0 = snap["trials"]["0"]
    assert t0["attempts"] == 2 and t0["status"] == "completed"
    # Device books folded off the event stream: cost record + memory
    # watermark per series key.
    assert snap["device_books"]
    book = next(iter(snap["device_books"].values()))
    assert book.get("flops_per_lane_step") or book.get("peak_bytes")


def test_ledger_view_json_snapshot(tmp_path, capsys):
    from multidisttorch_tpu.hpo.ledger import SweepLedger

    out_dir = str(tmp_path / "sweep")
    led = SweepLedger(out_dir)
    led.attempt_start(0, "aaaa", 1)
    led.attempt_end(0, "aaaa", 1, "completed", summary={"steps": 8})
    led.attempt_start(1, "bbbb", 1)
    ledger_view = _load_tool("ledger_view")
    assert ledger_view.main([out_dir, "--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["configs"] == 2
    assert snap["settled"] == 1 and snap["in_flight"] == 1
    assert snap["by_config"]["aaaa"]["attempts"][0]["status"] == "completed"


# -- chaos harness telemetry block (trace acceptance) ------------------


@pytest.mark.chaos
def test_chaos_harness_traces_every_fault(tmp_path):
    from multidisttorch_tpu.faults.harness import run_chaos_bench

    report = run_chaos_bench(
        str(tmp_path / "chaos"), trials=3, epochs=2, include_preempt=False
    )
    tel = report["telemetry"]
    assert tel["all_faults_traced"]
    assert tel["trace_monotonic"]
    assert tel["faults_fired"] > 0
    assert tel["events_recorded"] > 0
    assert os.path.exists(tel["trace"])
    # Telemetry is globally off again after the harness returns.
    assert telemetry.get_bus() is None
