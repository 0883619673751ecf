"""Sweep-wide telemetry: structured events, metrics, exporters.

The reference's only instrumentation is one group-aware print per trial
(``/root/reference/utils.py:165-174``); after trial stacking (PR 1) and
chaos supervision (PR 2) a sweep has rich internal dynamics — lane
retirements, backoff retries, checkpoint scan-backs, goodput — that were
invisible outside ad-hoc prints. This package makes them first-class:

- :mod:`~multidisttorch_tpu.telemetry.events` — a process-local typed
  **event bus** with a bounded in-memory queue and an append-only JSONL
  sink (torn-tail tolerant, like the sweep ledger). The driver,
  supervision, checkpoint, fault-injection, and collectives layers all
  emit through it — host-side seams only, never inside traced code.
- :mod:`~multidisttorch_tpu.telemetry.metrics` — counters, gauges,
  fixed-bucket histograms; per-trial/per-bucket step timing with sparse
  device-inclusive sampling; compile accounting.
- :mod:`~multidisttorch_tpu.telemetry.export` — Chrome/Perfetto trace
  JSON (one track per trial), a Prometheus-style text dump, and a
  run-summary JSON.
- ``tools/sweep_top.py`` — live console over the event JSONL.

**Zero-cost-when-off contract**: telemetry is DISABLED by default.
Every hot-path seam is written as ``bus = get_bus(); if bus is not
None: bus.emit(...)`` — with telemetry off, ``get_bus()`` returns
``None`` and *no event object is ever constructed* (regression-tested
in tests/test_telemetry.py). When on, the budget is <= 2% step-time
overhead: a budget, not yet a measurement on the chip (ROADMAP A13).

Enable programmatically::

    from multidisttorch_tpu import telemetry
    with telemetry.telemetry_run("out/telemetry"):
        run_hpo(...)

or by environment (picked up at sweep start): ``MDT_TELEMETRY=1``
[+ ``MDT_TELEMETRY_DIR=<dir>``].

See docs/OBSERVABILITY.md for the event taxonomy and metrics catalog.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

from multidisttorch_tpu.telemetry import anomaly as _anomaly
from multidisttorch_tpu.telemetry import ctlprof as _ctlprof
from multidisttorch_tpu.telemetry import events as _events
from multidisttorch_tpu.telemetry import incident as _incident
from multidisttorch_tpu.telemetry import metrics as _metrics

get_bus = _events.get_bus
get_registry = _metrics.get_registry
get_monitor = _anomaly.get_monitor
get_ctlprof = _ctlprof.get_ctlprof
get_flight_ring = _incident.get_flight_ring
get_incident_detector = _incident.get_detector
AnomalyConfig = _anomaly.AnomalyConfig
read_events = _events.read_events
EVENTS_NAME = _events.EVENTS_NAME
INCIDENTS_NAME = _incident.INCIDENTS_NAME


def enabled() -> bool:
    """Whether telemetry is currently on (bus exists)."""
    return _events.get_bus() is not None


def configure(
    out_dir: Optional[str] = None,
    *,
    queue_max: int = 4096,
    device_sample_every: int = 100,
    anomaly: Optional["AnomalyConfig"] = None,
    anomaly_capture_dir: Optional[str] = None,
    host: Optional[int] = None,
    world: Optional[int] = None,
) -> None:
    """Turn telemetry ON: create the event bus (JSONL sink under
    ``out_dir`` when given, in-memory only otherwise), the metrics
    registry, and the anomaly monitor (``anomaly=`` tunes thresholds;
    ``anomaly_capture_dir=`` additionally arms the bounded profiler
    capture — off by default, since only one profiler session can
    exist per process), and install the best-effort compile
    listener. ``host``/``world`` are the fleet identity tags stamped
    on every event (default from ``MDT_HOST_SLOT``/``MDT_WORLD_EPOCH``
    — see ``telemetry/fleet.py``; unset means an untagged single-host
    stream)."""
    path = None
    if out_dir is not None:
        os.makedirs(out_dir, exist_ok=True)
        name = _events.EVENTS_NAME
        # Multi-controller: every process emits (agreements, writer-
        # gated checkpoint saves, ...) and the dir is typically a
        # shared filesystem — independent handles on ONE file would
        # interleave and overwrite each other's bytes. One sink per
        # process; tools read the per-process streams individually.
        # Identity must never come from jax.process_count() — that
        # initializes the backend, and the elastic supervisor and the
        # preflight CLI configure telemetry precisely to diagnose a
        # backend that would wedge that call. Instead: an ALREADY
        # initialized jax.distributed (covers explicit
        # initialize(coordinator, num_processes=...) launches with no
        # launcher env — reading global_state initializes nothing),
        # else the launcher env (the same source jax.distributed
        # auto-initializes from).
        num_processes, process_id = 1, 0
        from jax._src import distributed as _jdist

        if _jdist.global_state.client is not None:
            num_processes = _jdist.global_state.num_processes or 1
            process_id = _jdist.global_state.process_id or 0
        if num_processes <= 1:
            from multidisttorch_tpu.parallel.cluster import (
                detect_process_env,
            )

            penv = detect_process_env()
            num_processes, process_id = penv.num_processes, penv.process_id
        if num_processes > 1:
            name = f"events.p{process_id}.jsonl"
        path = os.path.join(out_dir, name)
    bus = _events.configure(
        path=path, queue_max=queue_max, host=host, world=world
    )
    # Incident plane rides the same switch (ISSUE 19): the always-on
    # flight ring + root-cause detector tap every emit, the incident
    # ledger and bundles land next to the event stream, and the
    # standing <=2% A/B therefore measures the ON side with the ring
    # armed. The tap is installed AFTER the detector exists so no emit
    # ever sees a half-armed plane.
    bus.tap = _incident.configure(out_dir, host=bus.host)
    reg = _metrics.configure(device_sample_every=device_sample_every)
    # Control-plane flight books ride the same switch: the profiler's
    # wall histograms are registry series, so the A/B overhead bench's
    # ON side carries ctlprof and the Prometheus dump exports its
    # books for free. Flame file (when MDT_CTLPROF_SAMPLE_HZ is set)
    # lands next to the event stream.
    _ctlprof.configure(
        registry=reg,
        flame_path=(
            os.path.join(out_dir, "ctl_flame.txt")
            if out_dir is not None
            else None
        ),
    )
    if anomaly_capture_dir is not None:
        import dataclasses

        anomaly = dataclasses.replace(
            anomaly or _anomaly.AnomalyConfig(),
            capture_dir=anomaly_capture_dir,
        )
    _anomaly.configure(anomaly)
    _metrics.install_compile_listener()


def disable() -> None:
    """Turn telemetry OFF (close the sink, stop any profiler window,
    drop bus, registry, anomaly monitor, flight ring, and incident
    detector)."""
    _anomaly.disable()
    _events.disable()
    _incident.disable()
    _ctlprof.disable()
    _metrics.disable()


def configure_from_env() -> bool:
    """Enable telemetry when ``MDT_TELEMETRY`` is truthy (dir from
    ``MDT_TELEMETRY_DIR``, default ``telemetry/``). Called once at sweep
    start by the HPO driver; a no-op (cheap env read) otherwise.
    Already-configured telemetry is left alone — an explicit
    :func:`configure` wins over the env."""
    if enabled():
        return True
    flag = os.environ.get("MDT_TELEMETRY", "").strip().lower()
    if flag in ("", "0", "false", "off"):
        return False
    out_dir = os.environ.get("MDT_TELEMETRY_DIR", "telemetry")
    # MDT_TELEMETRY_CAPTURE=1 additionally arms anomaly-triggered
    # profiler capture windows (bounded/rate-limited; traces land under
    # {dir}/anomaly_traces). Off by default: jax allows one profiler
    # session per process and an explicit profile_dir= must win.
    cap = os.environ.get("MDT_TELEMETRY_CAPTURE", "").strip().lower()
    configure(
        out_dir,
        anomaly_capture_dir=(
            os.path.join(out_dir, "anomaly_traces")
            if cap not in ("", "0", "false", "off")
            else None
        ),
    )
    return True


@contextlib.contextmanager
def telemetry_run(out_dir: Optional[str] = None, **kwargs):
    """Scope telemetry to a block: configure on entry, disable on exit
    (restoring a previously-active configuration is deliberately not
    attempted — nesting telemetry runs is not a supported shape)."""
    configure(out_dir, **kwargs)
    try:
        yield _events.get_bus()
    finally:
        disable()


__all__ = [
    "EVENTS_NAME",
    "INCIDENTS_NAME",
    "AnomalyConfig",
    "configure",
    "configure_from_env",
    "disable",
    "enabled",
    "get_bus",
    "get_ctlprof",
    "get_flight_ring",
    "get_incident_detector",
    "get_monitor",
    "get_registry",
    "read_events",
    "telemetry_run",
]
