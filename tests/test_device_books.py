"""Device performance books (ISSUE 4 tentpole): XLA cost analysis
extraction, MFU/roofline math, memory watermarks (allocator stats on
TPU, live-buffer accounting on CPU), and the run-summary contract —
every trial carries ``mfu`` (float, or explicit null WITH a reason)
and ``peak_memory_bytes``."""

import json

import jax
import jax.numpy as jnp
import pytest

from multidisttorch_tpu import telemetry
from multidisttorch_tpu.data.datasets import synthetic_mnist
from multidisttorch_tpu.hpo.driver import TrialConfig, run_hpo
from multidisttorch_tpu.telemetry import device as tele_device
from multidisttorch_tpu.telemetry import export as tele_export
from multidisttorch_tpu.telemetry import metrics as tele_metrics


@pytest.fixture(autouse=True)
def _telemetry_off_after():
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


# -- cost analysis extraction ------------------------------------------


def test_compiled_cost_analysis_reports_flops_on_cpu():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    ca = tele_device.compiled_cost_analysis(f, (x,))
    assert ca["reason"] is None
    # 32x32 @ 32x32 is 2*32^3 = 65536 matmul FLOPs at minimum.
    assert ca["flops"] >= 2 * 32**3
    assert ca["bytes_accessed"] and ca["bytes_accessed"] > 0


def test_compiled_cost_analysis_unwraps_hook_wrappers():
    from multidisttorch_tpu.train.steps import wrap_step_with_hooks

    f = jax.jit(lambda s, x: s + x.sum())
    hooked = wrap_step_with_hooks(f, before=lambda b: None)
    ca = tele_device.compiled_cost_analysis(
        hooked, (jnp.float32(0.0), jnp.ones((8, 8)))
    )
    assert ca["flops"] is not None and ca["reason"] is None


def test_compiled_cost_analysis_graceful_on_non_lowerable():
    ca = tele_device.compiled_cost_analysis(lambda x: x, (1.0,))
    assert ca["flops"] is None
    assert "not a lowerable" in ca["reason"]


def test_peak_tables():
    # FLOP/s: the parametrised cases below; bytes/s: same lookup rule.
    assert tele_device.peak_membw_per_chip("TPU v4") == pytest.approx(
        1.23e12
    )
    assert tele_device.peak_membw_per_chip("cpu") is None


@pytest.mark.parametrize(
    "kind,expected",
    [
        ("TPU v4", 275e12),
        ("TPU v5 lite", 197e12),
        ("TPU v5e", 197e12),
        ("TPU v5p", 459e12),
        ("TPU v6e", 918e12),
        ("cpu", None),
    ],
)
def test_peak_flops_per_chip(kind, expected):
    assert tele_device.peak_flops_per_chip(kind) == expected


def test_peak_flops_unknown_tpu_kind_raises():
    # A chip with no peak on record gets no neighbour's peak: "v5" in
    # the kind must not make it a v5p, and no environment hint fills in.
    with pytest.raises(ValueError, match="not in the peak table"):
        tele_device.peak_flops_per_chip("TPU v5 weird")


def test_lm_flops_formula():
    # The copy `mfu` is computed from (benchmark/flops.py): tier-1
    # guards the yardstick against a hand count.
    from benchmark.flops import lm_train_flops_per_token

    f = lm_train_flops_per_token(d=64, layers=2, t=128, vocab=256)
    fwd = 2 * (24.0 * 64 * 64 + 2.0 * 128 * 64) + 2.0 * 64 * 256
    assert f == 3.0 * fwd


def test_roofline_classification():
    # intensity 1000 FLOPs/byte >> ridge 275/1.23 ~ 224 -> compute.
    assert tele_device.roofline_class(1e6, 1e3, 275e12, 1.23e12) == (
        tele_device.COMPUTE_BOUND
    )
    # intensity 1 << ridge -> bandwidth.
    assert tele_device.roofline_class(1e3, 1e3, 275e12, 1.23e12) == (
        tele_device.BANDWIDTH_BOUND
    )
    assert tele_device.roofline_class(None, 1e3, 275e12, 1.23e12) is None
    assert tele_device.roofline_class(1e3, 1e3, None, 1.23e12) is None


# -- MFU math over the registry ----------------------------------------


def test_mfu_math_with_known_peak():
    telemetry.configure(None)
    reg = telemetry.get_registry()
    s = reg.step_series("trial-0")
    # Hand-build the books: 100 lane-steps in 2s at 1e9 FLOPs/step on a
    # 4-chip submesh with 1e12 peak -> 50e9 FLOP/s vs 4e12 = 0.0125.
    s.lane_steps, s.steps, s.total_s, s.dispatches = 100, 100, 2.0, 100
    reg.gauge("device_flops_per_lane_step", key="trial-0").set(1e9)
    reg.gauge("device_peak_flops_per_chip", key="trial-0").set(1e12)
    reg.gauge("device_mesh_devices", key="trial-0").set(4)
    books = tele_device.device_books(reg)
    assert books["trial-0"]["mfu"] == pytest.approx(0.0125)
    assert books["trial-0"]["mfu_reason"] is None


def test_mfu_null_reasons():
    telemetry.configure(None)
    reg = telemetry.get_registry()
    s = reg.step_series("trial-1")
    s.lane_steps, s.total_s = 10, 1.0
    # flops but no peak (the CPU shape).
    reg.gauge("device_flops_per_lane_step", key="trial-1").set(1e6)
    books = tele_device.device_books(reg)
    assert books["trial-1"]["mfu"] is None
    assert "peak FLOP/s" in books["trial-1"]["mfu_reason"]
    # no flops at all.
    reg.step_series("trial-2").lane_steps = 5
    books = tele_device.device_books(reg)
    assert books["trial-2"]["mfu"] is None
    assert "cost analysis" in books["trial-2"]["mfu_reason"]


def test_record_step_cost_cache_skips_recompile(monkeypatch):
    """Same cache key + same arg shapes = one AOT analysis: a sweep of
    N same-shape trials (or a retried trial) must not pay N extra
    compiles for identical numbers."""
    telemetry.configure(None)
    calls = {"n": 0}
    real = tele_device.compiled_cost_analysis

    def counting(fn, args, kwargs=None):
        calls["n"] += 1
        return real(fn, args, kwargs)

    monkeypatch.setattr(tele_device, "compiled_cost_analysis", counting)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((16, 16))
    key = ("single", ("test-shape-bucket",))
    r1 = tele_device.record_step_cost("trial-0", f, (x,), cache_key=key)
    r2 = tele_device.record_step_cost("trial-1", f, (x,), cache_key=key)
    assert calls["n"] == 1  # second record served from the cache
    assert r1["flops_per_lane_step"] == r2["flops_per_lane_step"] > 0
    # A different arg shape is a different program: cache miss.
    tele_device.record_step_cost(
        "trial-2", f, (jnp.ones((32, 32)),), cache_key=key
    )
    assert calls["n"] == 2


def test_memory_watermark_gauge_keeps_max():
    g = tele_metrics.Gauge()
    g.set_max(100)
    g.set_max(50)
    assert g.value == 100
    g.set_max(200)
    assert g.value == 200


def test_sample_memory_live_buffer_fallback():
    """On CPU (memory_stats None) the live-buffer accounting must
    produce a real number covering resident arrays."""
    telemetry.configure(None)
    keep = jax.device_put(jnp.ones((256, 256), jnp.float32))  # 256 KiB
    rec = tele_device.sample_memory(
        "trial-9", [keep.devices().pop()], where="test"
    )
    assert rec["source"] in ("live_buffers", "memory_stats")
    assert rec["bytes_in_use"] >= keep.nbytes
    reg = telemetry.get_registry()
    assert (
        reg.gauge_value("device_peak_memory_bytes", key="trial-9")
        >= keep.nbytes
    )


# -- end-to-end: CPU smoke sweep run-summary contract ------------------


def _smoke_summary(tmp_path, **hpo_kw):
    tdir = str(tmp_path / "tele")
    data = synthetic_mnist(64, seed=0)
    with telemetry.telemetry_run(tdir):
        results = run_hpo(
            small_configs(hpo_kw.pop("n", 2), epochs=2),
            data, None,
            out_dir=str(tmp_path / "out"),
            save_images=False, verbose=False,
            **hpo_kw,
        )
        paths = tele_export.export_all(
            tdir, registry=telemetry.get_registry()
        )
    with open(paths["summary"]) as f:
        return results, json.load(f), paths


def test_run_summary_carries_per_trial_device_books(tmp_path):
    results, summary, paths = _smoke_summary(tmp_path, num_groups=2)
    assert all(r.status == "completed" for r in results)
    assert summary["device_books"]
    for tid in ("0", "1"):
        t = summary["trials"][tid]
        # The acceptance contract: mfu present — a float, or an
        # explicit null with a reason (CPU: no peak FLOP/s table).
        assert "mfu" in t
        if t["mfu"] is None:
            assert t["mfu_reason"]
        assert "peak_memory_bytes" in t
        # CPU live-buffer accounting yields a real watermark.
        assert t["peak_memory_bytes"] and t["peak_memory_bytes"] > 0
        book = summary["device_books"][t["device_series"]]
        # XLA's cost analysis ran on the compiled train step: a real
        # per-step FLOPs figure even on CPU — and a SUBMESH-GLOBAL one.
        # cost_analysis describes the per-device partitioned module
        # (1/n of global on this n-device submesh), so an unscaled
        # figure would fall BELOW the analytic matmul floor: fwd 2*MACs
        # over the 784-16-(4,4)-16-784 stack, train ~ 3x fwd, x batch.
        dims = [(784, 16), (16, 4), (16, 4), (4, 16), (16, 784)]
        floor = 3 * 2 * sum(a * b for a, b in dims) * 16
        assert book["flops_per_step"] and book["flops_per_step"] >= floor


def test_stacked_sweep_books_are_bucket_scoped(tmp_path):
    results, summary, _paths = _smoke_summary(
        tmp_path, n=3, num_groups=1, stack_trials=True, stack_max_lanes=2
    )
    assert [r.status for r in results] == ["completed"] * 3
    assert "bucket-g0" in summary["device_books"]
    book = summary["device_books"]["bucket-g0"]
    assert book["flops_per_step"] and book["flops_per_step"] > 0
    assert book["peak_memory_bytes"] and book["peak_memory_bytes"] > 0
    # Every stacked trial resolves its books through the bucket series.
    for tid in ("0", "1", "2"):
        t = summary["trials"][tid]
        assert t["device_series"] == "bucket-g0"
        assert "mfu" in t and "peak_memory_bytes" in t


def test_trace_has_memory_counter_track(tmp_path):
    _results, _summary, paths = _smoke_summary(tmp_path, num_groups=2)
    with open(paths["trace"]) as f:
        trace = json.load(f)
    counters = [
        e for e in trace["traceEvents"]
        if e.get("ph") == "C" and e["name"].startswith("device_memory[")
    ]
    assert counters, "memory samples must render as a counter track"
    assert all("bytes_in_use" in e["args"] for e in counters)


def test_device_cost_events_reach_the_stream(tmp_path):
    _results, summary, paths = _smoke_summary(tmp_path, num_groups=2)
    events = telemetry.read_events(paths["events"])
    costs = [e for e in events if e["kind"] == "device_cost"]
    assert costs, "each trial's compile site must emit a device_cost"
    d = costs[0]["data"]
    assert d["flops_per_lane_step"] > 0
    assert d["platform"] == "cpu"
