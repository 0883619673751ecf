"""The compile subsystem (multidisttorch_tpu/compile/): executable
registry coalescing, the background AOT precompile farm, the
quarantined persistent cache, and the driver's admission path.

The safety property under test everywhere: **no deserialized
executable ever executes in the trial process without a passed
canary** — a corrupt entry is quarantined by its sidecar, a failed
canary evicts the whole cache dir, and the process's jax config points
at the cache only on the one verdict (``enabled``) that requires a
passed canary. Scripted canary runners stand in for real broken
jaxlibs so every failure mode is drilled deterministically in-process
(the real subprocess protocol is exercised by the coldstart bench and
the CI canary job).
"""

import json
import os
import threading
import time
import zlib

import jax
import numpy as np
import pytest

from multidisttorch_tpu.compile import programs as cprog
from multidisttorch_tpu.compile.cache import (
    CANARY_CRASHED,
    CANARY_MISMATCH,
    QUARANTINE_DIR,
    SIDECAR_SUFFIX,
    cache_probe,
    canary_quarantine,
    enable_quarantined_cache,
    scan_cache,
    seal_cache,
)
from multidisttorch_tpu.compile.farm import PrecompilePool
from multidisttorch_tpu.compile.registry import (
    CLAIMED,
    COMPILING,
    FAILED,
    PENDING,
    READY,
    ExecutableRegistry,
    get_executable_registry,
)
from multidisttorch_tpu.hpo.driver import TrialConfig, stack_bucket_key
from multidisttorch_tpu.parallel.mesh import setup_groups


@pytest.fixture(autouse=True)
def _fresh_registry():
    # The registry is process-lifetime by design; tests must not leak
    # programs into (or depend on) each other's tables.
    get_executable_registry().reset()
    yield
    get_executable_registry().reset()


def _cfg(**kw):
    base = dict(
        trial_id=0, epochs=1, batch_size=16, lr=1e-3, seed=7,
        hidden_dim=16, latent_dim=4,
    )
    base.update(kw)
    return TrialConfig(**base)


# -- program vocabulary ----------------------------------------------


def test_single_keys_bake_hypers_but_init_does_not():
    g = setup_groups(1)[0]
    a, b = _cfg(lr=1e-3), _cfg(lr=2e-3)
    bucket = stack_bucket_key(a)
    assert stack_bucket_key(b) == bucket  # lr is not a shape
    # lr twins are DIFFERENT train programs (lr is an XLA constant)...
    assert cprog.single_train_key(g, a, bucket) != cprog.single_train_key(
        g, b, bucket
    )
    # ...but share ONE init program (init never reads the hypers).
    assert cprog.single_init_key(g, a, bucket) == cprog.single_init_key(
        g, b, bucket
    )
    for key in (
        cprog.single_train_key(g, a, bucket),
        cprog.single_init_key(g, a, bucket),
        cprog.stacked_train_key(g, bucket, 4),
    ):
        assert isinstance(cprog.program_label(key), str)


def test_mesh_fingerprint_distinguishes_groups():
    g0, g1 = setup_groups(2)[:2]
    cfg = _cfg()
    bucket = stack_bucket_key(cfg)
    # An executable is loaded onto concrete devices: bucket twins on
    # different submeshes must never share a registry slot.
    assert cprog.single_train_key(g0, cfg, bucket) != cprog.single_train_key(
        g1, cfg, bucket
    )
    # EXCEPT init: it is jitted with no device pinning (the driver
    # device_puts its output), so every group shares ONE compile —
    # N-group sweeps must not pay N bit-identical init lowerings.
    assert cprog.single_init_key(g0, cfg, bucket) == cprog.single_init_key(
        g1, cfg, bucket
    )
    assert cprog.program_label(
        cprog.single_init_key(g0, cfg, bucket)
    ).endswith("@shared")


def test_avals_match_guards_shape_drift():
    cfg = _cfg()
    avals = cprog.single_avals(cfg)
    state_aval = avals["train"][0]
    assert cprog.avals_match(state_aval, state_aval)
    other = cprog.single_avals(_cfg(hidden_dim=32))["train"][0]
    assert not cprog.avals_match(state_aval, other)
    assert not cprog.avals_match(state_aval, object())  # never raises


def test_registry_init_state_bit_identical_to_eager():
    import optax

    from multidisttorch_tpu.train.steps import build_train_state

    cfg = _cfg()
    model = cprog.default_model(cfg)
    eager = build_train_state(model, optax.adam(cfg.lr), jax.random.key(7))
    compiled = (
        cprog.build_init_fn(cfg, model)
        .lower(*cprog.init_avals())
        .compile()
    )(jax.random.key(7))
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(compiled)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# -- registry: coalescing, ownership, torn shutdown -------------------


def test_compile_now_coalesces_duplicate_signatures():
    reg = ExecutableRegistry()
    key = ("train", ("k",), (1e-3, 1.0), (0,))
    n_compiles = [0]
    gate = threading.Event()

    def fn_factory():
        def body(x):
            return x + 1
        return jax.jit(body)

    fn = fn_factory()
    aval = (jax.ShapeDtypeStruct((4,), np.float32),)

    class SlowFn:
        def lower(self, *avals):
            n_compiles[0] += 1
            gate.wait(timeout=5)
            return fn.lower(*avals)

    results = []

    def worker():
        results.append(reg.compile_now(key, SlowFn(), aval))

    threads = [threading.Thread(target=worker) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    gate.set()
    for t in threads:
        t.join(timeout=10)
    # Exactly ONE thread lowered; the other two coalesced onto the
    # same entry and saw it READY.
    assert n_compiles[0] == 1
    assert all(e.status == READY for e in results)
    assert len({id(e) for e in results}) == 1
    # A later taker gets the executable and hit accounting.
    assert reg.take(key) is not None
    assert reg.entry(key).hits == 1


def test_registry_failed_is_terminal_and_sticky():
    reg = ExecutableRegistry()
    key = ("train", ("bad",), (1e-3, 1.0), (0,))

    class Broken:
        def lower(self, *a):
            raise RuntimeError("no lowering for you")

    e = reg.compile_now(key, Broken(), ())
    assert e.status == FAILED and "no lowering" in e.error
    assert reg.take(key) is None
    # A retry does NOT re-attempt a known-bad lowering.
    e2 = reg.compile_now(key, Broken(), ())
    assert e2 is e and e2.status == FAILED


def test_claim_vs_farm_ownership():
    reg = ExecutableRegistry()
    key = ("train", ("x",), (1e-3, 1.0), (0,))
    assert reg.schedule(key) is True
    assert reg.schedule(key) is False  # one farm job per program
    assert reg.status(key) == PENDING
    assert reg.claim(key) is True  # driver takes the queued job
    assert reg.status(key) == CLAIMED
    # The farm worker's check: CLAIMED is not PENDING, so it skips.
    assert reg.status(key) != PENDING


def test_pool_torn_shutdown_releases_queued_jobs():
    reg = ExecutableRegistry()
    pool = PrecompilePool(registry=reg, workers=1)
    release = threading.Event()
    started = threading.Event()

    def slow_builder():
        started.set()
        release.wait(timeout=10)
        return jax.jit(lambda x: x * 2), (
            jax.ShapeDtypeStruct((2,), np.float32),
        )

    k_inflight = ("train", ("a",), (1e-3, 1.0), (0,))
    k_queued = ("train", ("b",), (1e-3, 1.0), (0,))
    assert pool.submit(k_inflight, slow_builder)
    assert pool.submit(
        k_queued,
        lambda: (jax.jit(lambda x: x), (
            jax.ShapeDtypeStruct((2,), np.float32),
        )),
    )
    assert started.wait(timeout=10)
    pool.shutdown()  # torn: one in flight, one still queued
    # The queued job's PENDING entry is RELEASED — the next admission
    # claims and compiles it inline instead of waiting forever on a
    # worker that will never come.
    assert reg.status(k_queued) is None
    assert reg.claim(k_queued) is True
    # The in-flight compile finishes into the registry harmlessly.
    release.set()
    deadline = time.monotonic() + 10
    while reg.status(k_inflight) not in (READY, FAILED):
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert reg.status(k_inflight) == READY
    # Post-shutdown submits are refused AND leave no orphan PENDING
    # entry behind — one would stall a later admission on this key for
    # the full cooperative wait.
    k_late = ("train", ("c",), (1e-3, 1.0), (0,))
    assert not pool.submit(
        k_late, lambda: (jax.jit(lambda x: x), ()),
    )
    assert reg.status(k_late) is None


def test_pool_plan_sweep_dedups_duplicate_signatures():
    reg = ExecutableRegistry()
    pool = PrecompilePool(registry=reg, workers=1)
    g = setup_groups(1)
    # Four trials, ONE program signature (same bucket, same lr): the
    # farm must submit one train job + one init job, not four.
    items = [("single", [(i, _cfg(trial_id=i))]) for i in range(4)]
    n = pool.plan_sweep(items, g)
    assert n == 2  # init + train
    assert pool.drain(timeout_s=120)
    pool.shutdown(wait=True)
    cfg = _cfg()
    bucket = stack_bucket_key(cfg)
    assert reg.status(cprog.single_train_key(g[0], cfg, bucket)) == READY
    assert reg.status(cprog.single_init_key(g[0], cfg, bucket)) == READY


def test_admission_waits_cooperatively_never_blocks():
    # While a farm worker is mid-compile, the driver's admission
    # generator must YIELD (other submeshes keep stepping), not block —
    # and take the executable when the worker lands it.
    from multidisttorch_tpu.hpo.driver import _aot_admit

    reg = get_executable_registry()
    g = setup_groups(1)[0]
    cfg = _cfg()
    bucket = stack_bucket_key(cfg)
    key = cprog.single_train_key(g, cfg, bucket)
    avals = cprog.single_avals(cfg)
    steps = cprog.build_single_steps(g, cfg)

    release = threading.Event()

    class GatedFn:
        def lower(self, *a):
            release.wait(timeout=30)
            return steps["train"].lower(*a)

    worker = threading.Thread(
        target=lambda: reg.compile_now(key, GatedFn(), avals["train"])
    )
    worker.start()
    deadline = time.monotonic() + 10
    while reg.status(key) != COMPILING:
        assert time.monotonic() < deadline
        time.sleep(0.005)

    state_aval = avals["train"][0]
    gen = _aot_admit(
        {"train": key}, {"train": steps["train"], "multi": None},
        lambda: avals, state_aval, "train",
    )
    yields = 0
    taken = admission = None
    t0 = time.monotonic()
    while True:
        try:
            next(gen)
            yields += 1
            if yields == 3:
                release.set()  # the farm finishes while we cooperate
        except StopIteration as stop:
            taken, admission = stop.value
            break
        assert time.monotonic() - t0 < 30
    assert yields >= 3  # it yielded instead of blocking the host loop
    assert "train" in taken
    assert admission["outcome"] == "wait"
    worker.join(timeout=10)


def test_admission_claims_pending_job_inline():
    from multidisttorch_tpu.hpo.driver import _aot_admit

    reg = get_executable_registry()
    pool = PrecompilePool(registry=reg, workers=1)
    g = setup_groups(1)[0]
    cfg = _cfg(hidden_dim=32)
    bucket = stack_bucket_key(cfg)
    key = cprog.single_train_key(g, cfg, bucket)
    avals = cprog.single_avals(cfg)
    steps = cprog.build_single_steps(g, cfg)
    # A torn farm left the building: entry released, program unknown.
    assert reg.schedule(key)
    pool.shutdown()
    reg.release(key)
    gen = _aot_admit(
        {"train": key}, {"train": steps["train"], "multi": None},
        lambda: avals, avals["train"][0], "train",
    )
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            taken, admission = stop.value
            break
    assert admission["outcome"] == "inline"
    assert "train" in taken
    assert reg.status(key) == READY


# -- sidecars + scan --------------------------------------------------


def _plant_entry(cache_dir, name, blob=b"x" * 64):
    os.makedirs(cache_dir, exist_ok=True)
    with open(os.path.join(cache_dir, name), "wb") as f:
        f.write(blob)


def test_scan_rejects_corrupt_truncated_and_unsealed(tmp_path):
    d = str(tmp_path / "cache")
    _plant_entry(d, "good", b"a" * 100)
    _plant_entry(d, "bitrot", b"b" * 100)
    _plant_entry(d, "torn", b"c" * 100)
    seal_cache(d)
    # bit rot: same length, different bytes -> crc_mismatch
    _plant_entry(d, "bitrot", b"B" + b"b" * 99)
    # torn write: truncated after sealing -> size_mismatch
    _plant_entry(d, "torn", b"c" * 10)
    # unknown provenance: never sealed -> unsealed
    _plant_entry(d, "stranger", b"s" * 20)
    report = scan_cache(d)
    reasons = {r["entry"]: r["reason"] for r in report["rejected"]}
    assert reasons == {
        "bitrot": "crc_mismatch",
        "torn": "size_mismatch",
        "stranger": "unsealed",
    }
    assert report["ok"] == 1 and report["quarantined"] == 3
    # Rejected entries MOVED aside: jax sees a miss, never a garbled
    # blob; the good entry stays.
    left = sorted(
        n for n in os.listdir(d)
        if not n.endswith(SIDECAR_SUFFIX) and n != QUARANTINE_DIR
    )
    assert left == ["good"]
    qdir = os.path.join(d, QUARANTINE_DIR)
    assert sorted(
        n for n in os.listdir(qdir) if not n.endswith(SIDECAR_SUFFIX)
    ) == ["bitrot", "stranger", "torn"]


def test_scan_classifies_malformed_but_parseable_sidecars(tmp_path):
    # Bit rot can produce a sidecar that parses as VALID JSON of the
    # wrong shape ([], 0, {"nbytes": null}) — the scanner must
    # classify it sidecar_unreadable and quarantine, never crash: it
    # runs inside the corruption-containment path itself.
    d = str(tmp_path / "cache")
    for name, side in (
        ("e_list", "[]"),
        ("e_zero", "0"),
        ("e_null", '{"crc32": 1, "nbytes": null}'),
        ("e_str", '{"crc32": "xx", "nbytes": 2}'),
    ):
        _plant_entry(d, name, b"xy")
        with open(os.path.join(d, name + SIDECAR_SUFFIX), "w") as f:
            f.write(side)
    report = scan_cache(d)
    assert report["ok"] == 0
    assert {r["reason"] for r in report["rejected"]} == {
        "sidecar_unreadable"
    }
    assert report["quarantined"] == 4


def test_seal_is_idempotent_and_refreshes(tmp_path):
    d = str(tmp_path / "cache")
    _plant_entry(d, "e1", b"v1")
    r1 = seal_cache(d)
    assert r1["sealed"] == 1
    assert seal_cache(d)["sealed"] == 0  # unchanged -> no churn
    _plant_entry(d, "e1", b"v2")  # legit rewrite by a writer
    r3 = seal_cache(d)
    assert r3["refreshed"] == 1
    assert scan_cache(d)["ok"] == 1


# -- the canary quarantine -------------------------------------------


def _scripted_runner(script):
    """A canary-child stand-in: script maps mode -> result dict."""
    calls = []

    def run(mode, cache_dir, platform, timeout_s):
        calls.append(mode)
        out = script[mode]
        return dict(out() if callable(out) else out)

    run.calls = calls
    return run


def test_canary_mismatch_evicts_and_leaves_cold_path(tmp_path):
    d = str(tmp_path / "cache")
    _plant_entry(d, "entry", b"deadbeef" * 8)
    seal_cache(d)
    runner = _scripted_runner({
        "cold": {"ok": True, "bits": "aa"},
        "warmup": {"ok": True, "bits": "aa"},
        "warm": {"ok": True, "bits": "bb"},  # deserialize drifted
    })
    out = canary_quarantine(d, runner=runner)
    assert out["verdict"] == CANARY_MISMATCH and not out["passed"]
    assert out["evicted"] >= 1
    # Every entry quarantined: nothing left for jax to load — the next
    # compile is COLD, which is the fallback the protocol promises.
    assert [
        n for n in os.listdir(d)
        if not n.endswith(SIDECAR_SUFFIX) and n != QUARANTINE_DIR
    ] == []


def test_heap_corrupting_entry_never_loads_in_trial_process(tmp_path):
    # THE acceptance property (ISSUE 7): plant a stand-in for a
    # heap-corrupting executable — an entry whose sidecar is VALID (the
    # scan alone cannot catch it: PR 1's corruption was bit-exact on
    # disk) and whose deserialize-and-run CRASHES the canary child. The
    # trial process must end with its jax config NOT pointing at the
    # cache, the entries evicted, and a classified verdict — the
    # corrupt executable never gets a chance to execute here.
    d = str(tmp_path / "cache")
    _plant_entry(d, "heapbomb", b"\x7fELF-corrupting-thunks" * 4)
    seal_cache(d)
    assert scan_cache(d, quarantine=False)["ok"] == 1  # scan trusts it

    runner = _scripted_runner({
        "cold": {"ok": True, "bits": "aa"},
        "warmup": {"ok": True, "bits": "aa"},
        "warm": {  # the sacrificial child dies the PR 1 death
            "ok": False, "timeout": False, "rc": -11,
            "error": "canary warm child died rc=-11 "
                     "(deserialized-executable crash class)",
        },
    })
    prev = jax.config.jax_compilation_cache_dir
    out = enable_quarantined_cache(d, platform="cpu", runner=runner)
    assert out["enabled"] is False
    assert out["verdict"] == CANARY_CRASHED
    assert jax.config.jax_compilation_cache_dir == prev  # untouched
    assert out["canary"]["evicted"] >= 1
    qdir = os.path.join(d, QUARANTINE_DIR)
    assert "heapbomb" in os.listdir(qdir)


def test_passed_canary_on_cpu_stays_quarantined_only(tmp_path, monkeypatch):
    # Even a PASSED canary licenses only sacrificial processes: any
    # other process keeps its cache configuration untouched.
    monkeypatch.delenv("MDT_CACHE_SACRIFICIAL", raising=False)
    d = str(tmp_path / "cache")
    ok_runner = _scripted_runner({
        "cold": {"ok": True, "bits": "aa"},
        "warmup": {"ok": True, "bits": "aa"},
        "warm": {"ok": True, "bits": "aa"},
    })
    prev = jax.config.jax_compilation_cache_dir
    out = enable_quarantined_cache(d, platform="cpu", runner=ok_runner)
    assert out["verdict"] == "quarantined_only" and not out["enabled"]
    assert jax.config.jax_compilation_cache_dir == prev


def test_passed_canary_enables_sacrificial_and_refuses_a_chip(tmp_path):
    ok_runner = _scripted_runner({
        "cold": {"ok": True, "bits": "aa"},
        "warmup": {"ok": True, "bits": "aa"},
        "warm": {"ok": True, "bits": "aa"},
    })
    prev = jax.config.jax_compilation_cache_dir
    # The canary's three children each need the backend; a chip belongs
    # to one process at a time, so a TPU platform is refused outright.
    with pytest.raises(ValueError, match="CPU-world drill"):
        enable_quarantined_cache(
            str(tmp_path / "tpu_cache"), platform="tpu", runner=ok_runner
        )
    assert jax.config.jax_compilation_cache_dir == prev
    try:
        d2 = str(tmp_path / "sac_cache")
        out = enable_quarantined_cache(
            d2, platform="cpu", runner=ok_runner, sacrificial=True
        )
        assert out["enabled"] and out["verdict"] == "enabled"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_probe_reports_scan_and_canary(tmp_path):
    d = str(tmp_path / "cache")
    _plant_entry(d, "sealed_ok", b"fine")
    seal_cache(d)
    _plant_entry(d, "stranger", b"who")
    out = cache_probe(d, runner=_scripted_runner({
        "cold": {"ok": True, "bits": "aa"},
        "warmup": {"ok": True, "bits": "aa"},
        "warm": {"ok": True, "bits": "aa"},
    }))
    # The probe REPORTS the unsealed stranger without quarantining it
    # (read-only contract: mutation belongs to the enable path).
    assert out["scan"]["quarantined"] == 0
    assert [r["reason"] for r in out["scan"]["rejected"]] == ["unsealed"]
    assert "stranger" in os.listdir(d)
    assert out["canary"]["passed"] and out["usable"]
    # And it did not vouch for the stranger: still no sidecar.
    assert not os.path.exists(
        os.path.join(d, "stranger" + SIDECAR_SUFFIX)
    )


def test_cache_probe_failure_is_nondestructive(tmp_path):
    # A transient canary failure (e.g. a loaded host timing out the
    # child) during a PROBE must not throw away the production cache:
    # entries stay in place, nothing is evicted.
    d = str(tmp_path / "cache")
    _plant_entry(d, "precious", b"hours of TPU compiles")
    seal_cache(d)
    out = cache_probe(d, runner=_scripted_runner({
        "cold": {"ok": True, "bits": "aa"},
        "warmup": {"ok": True, "bits": "aa"},
        "warm": {
            "ok": False, "timeout": True,
            "error": "canary warm child blocked past 120s",
        },
    }))
    assert not out["usable"]
    assert out["canary"]["verdict"] == "canary_timeout"
    assert out["canary"]["evicted"] == 0
    assert "precious" in os.listdir(d)
    assert not os.path.isdir(os.path.join(d, QUARANTINE_DIR)) or (
        os.listdir(os.path.join(d, QUARANTINE_DIR)) == []
    )


def test_canary_child_env_never_inherits_cache_dir(monkeypatch):
    # The cold reference child must compile with NO cache — an
    # inherited JAX_COMPILATION_CACHE_DIR would make it deserialize
    # the same suspect entry as the warm child and bit-match it.
    import subprocess as _sp

    from multidisttorch_tpu.compile.cache import _run_canary_child

    captured = {}

    class _P:
        returncode = 0
        stdout = "CANARYBITS|00\n"
        stderr = ""

    def fake_run(cmd, **kw):
        captured["env"] = kw["env"]
        return _P()

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/suspect")
    monkeypatch.setattr(_sp, "run", fake_run)
    for mode in ("cold", "warmup", "warm"):
        r = _run_canary_child(mode, "/tmp/x", None, 5.0)
        assert r["ok"]
        assert "JAX_COMPILATION_CACHE_DIR" not in captured["env"]


def test_registry_lru_bound_evicts_terminal_only():
    # The service-lifetime memory bound: terminal entries beyond
    # max_programs are dropped LRU-first; in-flight ownership states
    # always survive. An evicted program just recompiles next time.
    reg = ExecutableRegistry(max_programs=2)
    fn = jax.jit(lambda x: x + 1)
    aval = (jax.ShapeDtypeStruct((2,), np.float32),)

    def k(i):
        return ("train", (f"p{i}",), (1e-3 * (i + 1), 1.0), (0,))

    assert reg.compile_now(k(0), fn, aval).status == READY
    assert reg.compile_now(k(1), fn, aval).status == READY
    reg.take(k(0))  # k0 is now more recently used than k1
    assert reg.compile_now(k(2), fn, aval).status == READY
    # k1 (LRU terminal) was evicted to admit k2; k0 survived.
    assert reg.status(k(1)) is None
    assert reg.status(k(0)) == READY and reg.status(k(2)) == READY
    assert reg.evicted == 1
    # A PENDING farm job is never evicted, even under cap pressure.
    assert reg.schedule(k(3))
    assert reg.compile_now(k(4), fn, aval).status == READY
    assert reg.status(k(3)) == PENDING


# -- end-to-end: the farm under run_hpo + cold-start books ------------


@pytest.mark.slow
def test_precompiled_sweep_never_blocks_and_matches_jit(tmp_path):
    # The tentpole contract end-to-end on a real sweep: with the farm
    # on, every trial's program arrives by registry hit or cooperative
    # wait (never an inline/jit compile on the host loop), the books
    # record it, and results are bit-identical to the plain-jit sweep.
    from multidisttorch_tpu import telemetry
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.hpo.driver import run_hpo
    from multidisttorch_tpu.telemetry.events import EVENTS_NAME, read_events
    from multidisttorch_tpu.telemetry.export import SweepFold

    train, test = synthetic_mnist(256), synthetic_mnist(64)
    cfgs = [
        _cfg(trial_id=i, hidden_dim=16 + 8 * i, epochs=1)
        for i in range(3)
    ]
    tel = str(tmp_path / "tel")
    telemetry.configure(tel)
    try:
        r_farm = run_hpo(
            cfgs, train, test, num_groups=1,
            out_dir=str(tmp_path / "farm"), save_images=False,
            verbose=False, precompile=True,
        )
    finally:
        telemetry.disable()
    fold = SweepFold()
    for ev in read_events(os.path.join(tel, EVENTS_NAME)):
        fold.feed(ev)
    assert len(fold.admissions) == 3
    for a in fold.admissions:
        assert a["outcome"] in ("hit", "wait"), a
        assert a["admission_s"] is not None
    assert fold.precompile.get("plan") == 1
    assert fold.compiles >= 3 and fold.compile_s_total > 0
    # Parity: farm-admitted executables are the driver's programs.
    get_executable_registry().reset()
    os.environ["MDT_AOT_ADMISSION"] = "0"
    try:
        r_jit = run_hpo(
            cfgs, train, test, num_groups=1,
            out_dir=str(tmp_path / "jit"), save_images=False,
            verbose=False,
        )
    finally:
        del os.environ["MDT_AOT_ADMISSION"]
    for a, b in zip(r_farm, r_jit):
        assert float(a.final_train_loss).hex() == float(
            b.final_train_loss
        ).hex()
        assert float(a.final_test_loss).hex() == float(
            b.final_test_loss
        ).hex()


def test_sweepfold_compile_books_fold():
    from multidisttorch_tpu.telemetry.export import SweepFold

    fold = SweepFold()
    mk = lambda kind, **data: {  # noqa: E731
        "kind": kind, "ts": data.pop("ts", 1.0), "data": data,
        "trial_id": data.pop("trial_id", None),
    }
    fold.feed(mk("compile_end", program="p1", program_kind="train",
                 source="precompile", compile_s=1.5, ok=True))
    fold.feed(mk("compile_end", program="p2", program_kind="init",
                 source="inline", compile_s=0.5, ok=False, error="boom"))
    fold.feed(mk("cache_hit", program="p1", source="precompile"))
    fold.feed(mk("precompile_scheduled", program="p1"))
    ev_start = {"kind": "attempt_start", "ts": 10.0, "trial_id": 3,
                "attempt": 1, "data": {}}
    ev_disp = {"kind": "first_dispatch", "ts": 12.5, "trial_id": 3,
               "data": {"outcome": "hit", "wait_s": 0.0, "program": "p1"}}
    fold.feed(ev_start)
    fold.feed(ev_disp)
    assert fold.compile_books["p1"]["compile_s"] == 1.5
    assert fold.compile_books["p1"]["hits"] == 1
    assert fold.compile_books["p2"]["ok"] is False
    assert fold.compiles == 2 and fold.cache_hits == 1
    assert fold.precompile == {"scheduled": 1}
    (adm,) = fold.admissions
    assert adm["trial_id"] == 3 and adm["outcome"] == "hit"
    assert adm["admission_s"] == 2.5
    assert fold.trials[3]["compile_outcome"] == "hit"


def test_crc_sidecar_format_is_plain_json(tmp_path):
    # The sidecar is the checkpoint layer's pattern: inspectable JSON,
    # not a pickle — a corrupted sidecar must never execute anything.
    d = str(tmp_path / "cache")
    _plant_entry(d, "e", b"payload")
    seal_cache(d)
    with open(os.path.join(d, "e" + SIDECAR_SUFFIX)) as f:
        rec = json.load(f)
    assert rec == {"crc32": zlib.crc32(b"payload"), "nbytes": 7}
