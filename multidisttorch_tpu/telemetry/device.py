"""Device-level performance books: XLA cost accounting, MFU, memory.

PR 3 made the *host-side* sweep dynamics first-class; the device stayed
a black box — MFU existed only as a hand-derived analytic
number, and nothing recorded what a compiled step actually costs or
what device memory a trial actually peaks at. This module keeps those
books, per trial / per stacked bucket, inside the PR 3 registry:

- **Cost books** (:func:`record_step_cost`): pull
  ``jit(...).lower(args).compile().cost_analysis()`` — post-optimization
  FLOPs and bytes-accessed straight from XLA — for a compiled train
  step, normalize to *per lane-step* (one optimizer update on one
  lane: a stacked ``fused=S, lanes=K`` dispatch is ``S*K`` lane-steps),
  and store gauges under the step series' key. Backend-safe: any
  backend that cannot analyze (or a program that cannot lower twice)
  degrades to a recorded *reason*, never an exception.
- **MFU + roofline** (:func:`device_books`): combine the cost gauges
  with the series' own step timings (``StepSeries`` — device-sampled
  books included) into live model-FLOPs-utilization against the chip
  generation's peak (:func:`peak_flops_per_chip`), plus a compute- vs bandwidth-bound roofline verdict from
  arithmetic intensity vs the ridge point.
- **Memory books** (:func:`sample_memory`): ``device.memory_stats()``
  watermarks where the backend keeps them (TPU), live-buffer accounting
  (``jax.live_arrays`` shard bytes) where it doesn't (CPU returns
  ``None``), folded into peak gauges and ``device_memory`` counter
  events (a Perfetto counter track in the trace export).

Zero-cost-when-off: every entry point returns immediately when the
metrics registry is ``None`` — no book object is ever constructed
(tier-1-enforced together with the event-bus contract). When on, cost
analysis runs ONCE per compiled program per trial/bucket (an AOT
re-lower+compile — compile-time cost only, never step-time), and
memory samples ride existing sync boundaries (epoch / checkpoint /
lane refill), never the dispatch hot loop.
"""

from __future__ import annotations

from typing import Optional, Sequence

from multidisttorch_tpu.telemetry.events import get_bus
from multidisttorch_tpu.telemetry.metrics import MetricsRegistry, get_registry

# Peak dense bf16 FLOP/s and HBM bytes/s per chip (Google Cloud TPU
# documentation), keyed by the exact ``device_kind`` string jax reports
# — the spellings are those of the installed jax's own table
# (jax/_src/pallas/mosaic/tpu_info.py); "TPU v5 lite" is what the v5e
# reports (chip_smoke.py prints it). The benchmark keeps a copy of its
# own (benchmark/peaks.py), which no program PR may edit.
PEAK_FLOPS_PER_CHIP = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}
PEAK_HBM_BYTES_PER_S = {
    "TPU v4": 1.23e12,
    "TPU v5 lite": 8.19e11,
    "TPU v5e": 8.19e11,
    "TPU v5": 2.765e12,
    "TPU v5p": 2.765e12,
    "TPU v6 lite": 1.64e12,
    "TPU v6e": 1.64e12,
}


def _lookup_by_kind(table: dict, device_kind: str) -> Optional[float]:
    kind = device_kind or ""
    if kind in table:
        return table[kind]
    if kind.startswith("TPU"):
        # A chip with no peak on record must not get a neighbour's: an
        # MFU against the wrong peak is a wrong number, not a missing one.
        raise ValueError(
            f"device_kind {kind!r} is not in the peak table "
            f"(telemetry/device.py knows {sorted(table)}); add its "
            "published peaks before reporting utilization on it"
        )
    return None


def peak_flops_per_chip(device_kind: str) -> Optional[float]:
    """Peak dense bf16 FLOP/s for a device kind. ``None`` off-TPU (CPU
    reports ``mfu: null`` with a reason); a TPU kind the table does not
    hold raises."""
    return _lookup_by_kind(PEAK_FLOPS_PER_CHIP, device_kind)


def peak_membw_per_chip(device_kind: str) -> Optional[float]:
    """Peak HBM bytes/s for a device kind; same contract as
    :func:`peak_flops_per_chip`."""
    return _lookup_by_kind(PEAK_HBM_BYTES_PER_S, device_kind)


def _aot_executable(fn):
    """An already-compiled executable reachable from ``fn``: ``fn``
    itself or its ``__wrapped__`` (hook wrappers tag it) when that is
    the ``jax.stages.Compiled`` the compile registry hands the
    driver."""
    from jax.stages import Compiled

    for cand in (fn, getattr(fn, "__wrapped__", None)):
        if isinstance(cand, Compiled):
            return cand
    return None


def compiled_cost_analysis(fn, args: tuple, kwargs: dict = None) -> dict:
    """XLA's post-optimization cost analysis of ``fn(*args)``.

    Returns ``{"flops": float|None, "bytes_accessed": float|None,
    "reason": str|None}`` — reason set exactly when flops is None.
    ``fn`` may be a jit function or a host wrapper exposing the
    underlying program via ``__wrapped__`` (``wrap_step_with_hooks``
    tags it). When the program is ALREADY an AOT executable (the
    compile registry's ``Compiled`` — docs/COMPILE.md), the analysis
    is read straight off it: zero re-lowering, zero re-compiling —
    the cost books and the compile farm share one executable. Only a
    plain jit fn pays the AOT lower+compile here (a one-time
    compile-cost, paid only with telemetry on, and itself served from
    jax's in-process caches when the registry compiled the same
    program already).

    Shapes are all that matter to the analysis, so calling this after
    the first real dispatch (with the *new*, post-donation state) is
    equivalent to analyzing the program that actually ran.
    """
    aot = _aot_executable(fn)
    if aot is not None:
        try:
            cost = aot.cost_analysis()
        except Exception as e:  # noqa: BLE001 — observability never
            # raises
            return {
                "flops": None,
                "bytes_accessed": None,
                "reason": (
                    f"cost_analysis failed: {type(e).__name__}: {e}"
                ),
            }
        return _fold_cost(cost)
    # Prefer the function's own .lower; only fall through __wrapped__
    # when the outer object has none (a host hook wrapper). jit
    # functions themselves carry a __wrapped__ (the raw Python body,
    # NOT lowerable), so the order matters.
    lower = getattr(fn, "lower", None)
    if lower is None:
        lower = getattr(getattr(fn, "__wrapped__", None), "lower", None)
    if lower is None:
        return {
            "flops": None,
            "bytes_accessed": None,
            "reason": f"not a lowerable function: {type(fn).__name__}",
        }
    try:
        cost = lower(*args, **(kwargs or {})).compile().cost_analysis()
    except Exception as e:  # noqa: BLE001 — observability never raises
        return {
            "flops": None,
            "bytes_accessed": None,
            "reason": f"cost_analysis failed: {type(e).__name__}: {e}",
        }
    return _fold_cost(cost)


def _fold_cost(cost) -> dict:
    if not isinstance(cost, dict):
        return {
            "flops": None,
            "bytes_accessed": None,
            "reason": (
                "backend returned no cost analysis "
                f"({type(cost).__name__})"
            ),
        }
    flops = cost.get("flops")
    if flops is None or flops < 0:
        return {
            "flops": None,
            "bytes_accessed": None,
            "reason": "backend cost analysis reports no flops",
        }
    b = cost.get("bytes accessed", cost.get("bytes_accessed"))
    return {
        "flops": float(flops),
        "bytes_accessed": float(b) if b is not None else None,
        "reason": None,
    }


# Cost-analysis results keyed by (caller program key, arg shapes):
# re-lowering + re-compiling an identical program once per same-shape
# trial (and again per retry attempt) would multiply a sweep's compile
# wall for numbers that cannot differ. Process-lifetime, bounded by
# the number of distinct compiled-program shapes.
_cost_cache: dict = {}


def _args_signature(args: tuple) -> tuple:
    import jax

    return tuple(
        (tuple(getattr(x, "shape", ())), str(getattr(x, "dtype", type(x))))
        for x in jax.tree.leaves(args)
    )


def record_step_cost(
    key: str,
    fn,
    args: tuple,
    *,
    steps: int = 1,
    lanes: int = 1,
    devices: Sequence = (),
    trial_id: Optional[int] = None,
    group_id: Optional[int] = None,
    cache_key=None,
) -> Optional[dict]:
    """Run cost analysis for the step series ``key``'s compiled program
    and store the per-lane-step cost books in the registry.

    ``steps`` is the dispatch's fused chunk length and ``lanes`` its
    compiled lane count (a stacked program computes every lane, masked
    or not, so the analysis covers all K); one dispatch = ``steps *
    lanes`` lane-steps. Gauges land under ``key`` so :func:`device_books`
    can join them with the same key's :class:`StepSeries`; a
    ``device_cost`` event carries the record (and the failure reason,
    when there is one) to the JSONL stream for the live console.

    FLOPs are stored as SUBMESH-GLOBAL per lane-step: XLA's
    ``cost_analysis`` describes the *partitioned per-device module*
    (measured: a batch-sharded matmul on 8 devices reports 1/8 of the
    global count), so the per-device figure is scaled by the submesh's
    device count. Replicated elementwise work (the optimizer update)
    is thereby counted once per device — negligible next to the
    matmuls, and the honest direction for an executed-FLOPs book.

    No-op (returns None) when telemetry is off. Call once per series —
    the driver guards with a per-run flag. ``cache_key`` (the driver
    passes its shape-bucket key) additionally memoizes the analysis
    across same-shape trials and retry attempts — combined with the
    arg-shape signature it identifies the compiled program up to
    scalar hypers (lr/beta), which don't change its cost.
    """
    reg = get_registry()
    if reg is None:
        return None
    ca = None
    full_key = None
    if cache_key is not None:
        full_key = (cache_key, _args_signature(args))
        ca = _cost_cache.get(full_key)
    if ca is None:
        ca = compiled_cost_analysis(fn, args)
        if full_key is not None:
            _cost_cache[full_key] = ca
    d0 = devices[0] if devices else None
    device_kind = getattr(d0, "device_kind", "") or ""
    platform = getattr(d0, "platform", "") or ""
    peak = peak_flops_per_chip(device_kind)
    peak_bw = peak_membw_per_chip(device_kind)
    n_dev = max(1, len(devices))
    lane_steps = max(1, int(steps) * int(lanes))
    rec = {
        "key": key,
        "steps": int(steps),
        "lanes": int(lanes),
        "devices": n_dev,
        "device_kind": device_kind,
        "platform": platform,
        "flops_per_lane_step": (
            ca["flops"] * n_dev / lane_steps
            if ca["flops"] is not None
            else None
        ),
        "bytes_per_lane_step": (
            ca["bytes_accessed"] * n_dev / lane_steps
            if ca["bytes_accessed"] is not None
            else None
        ),
        "peak_flops_per_chip": peak,
        "peak_membw_per_chip": peak_bw,
        "reason": ca["reason"],
    }
    reg.counter("device_cost_records").inc()
    reg.gauge("device_lanes", key=key).set(lanes)
    reg.gauge("device_mesh_devices", key=key).set(n_dev)
    if rec["flops_per_lane_step"] is not None:
        reg.gauge("device_flops_per_lane_step", key=key).set(
            rec["flops_per_lane_step"]
        )
    if rec["bytes_per_lane_step"] is not None:
        reg.gauge("device_bytes_per_lane_step", key=key).set(
            rec["bytes_per_lane_step"]
        )
    if peak is not None:
        reg.gauge("device_peak_flops_per_chip", key=key).set(peak)
    if peak_bw is not None:
        reg.gauge("device_peak_membw_per_chip", key=key).set(peak_bw)
    bus = get_bus()
    if bus is not None:
        bus.emit(
            "device_cost", trial_id=trial_id, group_id=group_id, **rec
        )
    return rec


def record_pipeline_cost(
    key: str,
    parts,
    *,
    devices: Sequence = (),
    trial_id: Optional[int] = None,
    group_id: Optional[int] = None,
) -> Optional[dict]:
    """Cost books for an MPMD pipelined trial: one optimizer step spans
    SEVERAL per-stage programs on DIFFERENT submeshes, so the per-step
    FLOPs book is the weighted sum over ``parts`` — each a ``(fn, args,
    stage_devices, per_step_multiplier)`` tuple (forward/backward run
    once per microbatch, the update once). Stored under ``key`` with
    the same gauge/event shape as :func:`record_step_cost` so
    :func:`device_books` joins it with the pipeline's step series
    unchanged: MFU on a backend with a peak table, explicit
    null-with-reason on CPU. Any stage whose analysis fails degrades
    the whole book to the recorded reason (a partial sum would be a
    made-up number)."""
    reg = get_registry()
    if reg is None:
        return None
    flops: Optional[float] = 0.0
    bytes_: Optional[float] = 0.0
    reason = None
    for fn, args, stage_devices, mult in parts:
        ca = compiled_cost_analysis(fn, args)
        if ca["flops"] is None:
            flops, bytes_, reason = None, None, ca["reason"]
            break
        nd = max(1, len(stage_devices))
        flops += ca["flops"] * nd * float(mult)
        if ca["bytes_accessed"] is None:
            # One stage without a bytes book voids the whole sum — a
            # partial total would read as the pipeline's bandwidth.
            bytes_ = None
        elif bytes_ is not None:
            bytes_ += ca["bytes_accessed"] * nd * float(mult)
    d0 = devices[0] if devices else None
    device_kind = getattr(d0, "device_kind", "") or ""
    platform = getattr(d0, "platform", "") or ""
    peak = peak_flops_per_chip(device_kind)
    peak_bw = peak_membw_per_chip(device_kind)
    n_dev = max(1, len(devices))
    rec = {
        "key": key,
        "steps": 1,
        "lanes": 1,
        "devices": n_dev,
        "device_kind": device_kind,
        "platform": platform,
        "flops_per_lane_step": flops,
        "bytes_per_lane_step": bytes_,
        "peak_flops_per_chip": peak,
        "peak_membw_per_chip": peak_bw,
        "reason": reason,
    }
    reg.counter("device_cost_records").inc()
    reg.gauge("device_lanes", key=key).set(1)
    reg.gauge("device_mesh_devices", key=key).set(n_dev)
    if flops is not None:
        reg.gauge("device_flops_per_lane_step", key=key).set(flops)
    if bytes_ is not None:
        reg.gauge("device_bytes_per_lane_step", key=key).set(bytes_)
    if peak is not None:
        reg.gauge("device_peak_flops_per_chip", key=key).set(peak)
    if peak_bw is not None:
        reg.gauge("device_peak_membw_per_chip", key=key).set(peak_bw)
    bus = get_bus()
    if bus is not None:
        bus.emit(
            "device_cost", trial_id=trial_id, group_id=group_id, **rec
        )
    return rec


def _live_buffer_bytes(devices: Sequence) -> Optional[int]:
    """Committed live-array bytes on ``devices`` — the CPU-grade stand-in
    for an allocator watermark: what the process is *holding*, summed
    over each array's shards actually resident on the sampled devices
    (so a replicated array on an 8-device submesh counts 8 shards on
    that submesh and none elsewhere)."""
    import jax

    devset = set(devices)
    total = 0
    try:
        arrays = jax.live_arrays()
    except Exception:  # noqa: BLE001 — accounting is best-effort
        return None
    for a in arrays:
        try:
            for sh in a.addressable_shards:
                if sh.device in devset:
                    total += int(sh.data.nbytes)
        except Exception:  # noqa: BLE001 — deleted/donated mid-walk
            continue
    return total


def sample_memory(
    key: str,
    devices: Sequence,
    *,
    where: str = "",
    trial_id: Optional[int] = None,
    group_id: Optional[int] = None,
) -> Optional[dict]:
    """Sample device memory for the series ``key`` and fold it into the
    peak-watermark gauges.

    Prefers the backend allocator's own books (``device.memory_stats()``
    — ``bytes_in_use`` / ``peak_bytes_in_use``, present on TPU); where
    the backend keeps none (CPU returns ``None``), falls back to
    live-buffer accounting over the sampled devices. Numbers are the
    MAX over the series' devices (SPMD replication makes per-device
    peaks near-identical; max is the one that OOMs first).

    Host-side only, and intended for boundaries the loop already
    synchronizes at (epoch, checkpoint, lane refill) — never per
    dispatch. No-op (returns None) when telemetry is off.
    """
    reg = get_registry()
    if reg is None:
        return None
    in_use = peak = None
    source = None
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without the API
            stats = None
        if not stats:
            continue
        source = "memory_stats"
        b = stats.get("bytes_in_use")
        p = stats.get("peak_bytes_in_use", b)
        if b is not None:
            in_use = max(in_use or 0, int(b))
        if p is not None:
            peak = max(peak or 0, int(p))
    if source is None:
        live = _live_buffer_bytes(devices)
        if live is not None:
            source = "live_buffers"
            in_use = live
            peak = live  # watermark semantics come from the max-gauge
    rec = {
        "key": key,
        "where": where,
        "bytes_in_use": in_use,
        "peak_bytes": peak,
        "source": source or "unavailable",
    }
    reg.counter("device_memory_samples", key=key).inc()
    if in_use is not None:
        reg.gauge("device_memory_bytes", key=key).set(in_use)
    if peak is not None:
        reg.gauge("device_peak_memory_bytes", key=key).set_max(peak)
    bus = get_bus()
    if bus is not None:
        bus.emit(
            "device_memory", trial_id=trial_id, group_id=group_id, **rec
        )
    return rec


COMPUTE_BOUND = "compute_bound"
BANDWIDTH_BOUND = "bandwidth_bound"


def roofline_class(
    flops: Optional[float],
    bytes_accessed: Optional[float],
    peak_flops: Optional[float],
    peak_bw: Optional[float],
) -> Optional[str]:
    """Roofline verdict: arithmetic intensity (FLOPs/byte) above the
    ridge point (peak FLOP/s over peak bytes/s) means the kernel runs
    out of math before memory — compute-bound; below, bandwidth-bound.
    None when any input is unknown (no peak tables off-TPU)."""
    if not flops or not bytes_accessed or not peak_flops or not peak_bw:
        return None
    intensity = flops / bytes_accessed
    ridge = peak_flops / peak_bw
    return COMPUTE_BOUND if intensity >= ridge else BANDWIDTH_BOUND


def _book_for(reg: MetricsRegistry, key: str, series_snap: dict) -> dict:
    def g(name):
        return reg.gauge_value(name, key=key)

    flops = g("device_flops_per_lane_step")
    bytes_ = g("device_bytes_per_lane_step")
    peak = g("device_peak_flops_per_chip")
    peak_bw = g("device_peak_membw_per_chip")
    n_dev = g("device_mesh_devices") or 1
    lane_steps = series_snap.get("lane_steps", 0)
    total_s = series_snap.get("total_s", 0.0)
    book = {
        "key": key,
        "flops_per_step": flops,
        "bytes_per_step": bytes_,
        "peak_flops_per_chip": peak,
        "devices": int(n_dev),
        "lane_steps": lane_steps,
        "total_s": round(total_s, 6),
        "mfu": None,
        "mfu_reason": None,
        "roofline": roofline_class(flops, bytes_, peak, peak_bw),
        "peak_memory_bytes": (
            int(v)
            if (v := reg.gauge_value("device_peak_memory_bytes", key=key))
            is not None
            else None
        ),
    }
    if flops is None:
        book["mfu_reason"] = (
            "no XLA cost analysis for this step (backend reported none "
            "or analysis failed — see the device_cost event)"
        )
    elif peak is None:
        book["mfu_reason"] = (
            "no known peak FLOP/s for this device kind (CPU or "
            "unrecognized generation) — analytic FLOPs are recorded, "
            "utilization is not defined"
        )
    elif lane_steps <= 0 or total_s <= 0:
        book["mfu_reason"] = "no step timings recorded for this series"
    else:
        # Sustained model FLOP/s over the series' active window vs the
        # submesh's aggregate peak. lane_steps/total_s is the honest
        # rate: it charges dispatch gaps and host stalls against the
        # device, exactly what MFU is supposed to expose.
        book["mfu"] = round(
            flops * lane_steps / total_s / (peak * n_dev), 6
        )
    return book


def device_books(
    registry: Optional[MetricsRegistry] = None,
) -> dict[str, dict]:
    """Join every step series with its cost/memory gauges into one
    MFU + roofline + watermark book per series key (``trial-{id}`` /
    ``bucket-g{group}``) — the run summary's ``device_books`` block.
    Empty dict when telemetry is off."""
    registry = registry or get_registry()
    if registry is None:
        return {}
    books = {}
    for key, snap in registry.step_series_snapshots().items():
        books[key] = _book_for(registry, key, snap)
    return books
