"""Checkpoint data plane v2 (docs/RESILIENCE.md "Checkpoint format
v2"): content-addressed chunk store, incremental manifests, refcounted
GC + orphan sweep, chunk-complete verification/scan-back, the
cross-host restore agreement over chunked checkpoints, and the
snapshot-fast preemption drain (ledger honesty + RAM re-place)."""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from multidisttorch_tpu.models.vae import VAE
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train import checkpoint as ck
from multidisttorch_tpu.train import ckpt_store as cs
from multidisttorch_tpu.train.steps import build_train_state

pytestmark = pytest.mark.ckpt


def _state(step=0, seed=0, hidden=16):
    s = build_train_state(
        VAE(hidden_dim=hidden, latent_dim=4),
        optax.adam(1e-3),
        jax.random.key(seed),
    )
    return s.replace(step=jnp.asarray(step, jnp.int32))


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(jax.device_get(a)), jax.tree.leaves(
        jax.device_get(b)
    )
    return len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


def _save_v2(state, path, step, *, keep_last=1, chunk=4096, stats=None):
    return ck.save_state(
        state,
        path,
        metadata={"step": step, "completed_epochs": max(1, step // 8)},
        keep_last=keep_last,
        format="v2",
        chunk_bytes=chunk,
        stats_out=stats,
    )


# -- chunk store ------------------------------------------------------


def test_chunk_store_roundtrip_dedup_crc(tmp_path):
    store = cs.ChunkStore(str(tmp_path / "chunks"))
    blob = os.urandom(10_000)
    digest, written = store.put(blob)
    assert written == len(blob)
    # Content-addressed dedup: the second landing writes nothing.
    digest2, written2 = store.put(blob)
    assert digest2 == digest and written2 == 0
    assert store.read(digest) == blob
    ok, reason = store.verify(digest, nbytes=len(blob))
    assert ok, reason
    # Bit-rot: payload garbled under a valid sidecar.
    with open(store.chunk_path(digest), "r+b") as f:
        f.seek(100)
        f.write(b"\xff" * 8)
    ok, reason = store.verify(digest)
    assert not ok and "crc32 mismatch" in reason
    with pytest.raises(IOError):
        store.read(digest)


def test_v2_save_restore_bitwise_and_sidecar(tmp_path):
    path = str(tmp_path / "state.msgpack")
    s = _state(3, seed=1)
    stats = {}
    _save_v2(s, path, 3, stats=stats)
    assert stats["format"] == "v2" and stats["total_bytes"] > 0
    # The primary file is a tiny manifest, not the full state.
    assert os.path.getsize(path) < stats["total_bytes"] // 10
    assert cs.is_manifest_file(path)
    ok, meta, reason = ck.verify_checkpoint(path)
    assert ok, reason
    assert meta["_format"] == "v2"
    restored = ck.restore_state(_state(), path)
    assert _tree_equal(restored, s)


def test_incremental_resave_references_unchanged_chunks(tmp_path):
    path = str(tmp_path / "state.msgpack")
    s = _state(8, seed=2)
    _save_v2(s, path, 8)
    stats = {}
    _save_v2(s, path, 8, stats=stats)
    # Bit-identical state: every chunk referenced, none rewritten.
    assert stats["new_bytes"] == 0
    assert stats["reused_bytes"] == stats["total_bytes"]
    # Touch ONE leaf: only its chunks cost bytes.
    s2 = s.replace(
        params={
            **dict(s.params),
            "fc21": jax.tree.map(lambda x: x + 1, dict(s.params)["fc21"]),
        }
    )
    stats2 = {}
    _save_v2(s2, path, 9, stats=stats2)
    fc21_bytes = sum(
        np.asarray(x).nbytes
        for x in jax.tree.leaves(dict(jax.device_get(s2.params))["fc21"])
    )
    assert 0 < stats2["new_bytes"] <= fc21_bytes + 2 * 4096
    restored = ck.restore_state(_state(), path)
    assert _tree_equal(restored, s2)


def test_torn_manifest_scans_back(tmp_path):
    path = str(tmp_path / "state.msgpack")
    (g,) = setup_groups(1)
    s8, s16 = _state(8, seed=1), _state(16, seed=2)
    _save_v2(s8, path, 8, keep_last=2)
    _save_v2(s16, path, 16, keep_last=2)
    # Torn manifest: truncated mid-write.
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    ok, _, reason = ck.verify_checkpoint(path)
    assert not ok and "size mismatch" in reason
    got = ck.restore_latest_valid(_state(), path, g)
    assert got is not None
    restored, meta, used = got
    assert int(meta["step"]) == 16 and used.endswith(".v0000000016")
    assert _tree_equal(restored, s16)


def test_missing_chunk_scans_back_to_previous_step(tmp_path):
    path = str(tmp_path / "state.msgpack")
    (g,) = setup_groups(1)
    s8, s16 = _state(8, seed=1), _state(16, seed=2)
    _save_v2(s8, path, 8, keep_last=2)
    _save_v2(s16, path, 16, keep_last=2)
    store = cs.ChunkStore(cs.chunk_dir_for(path))
    newest = cs.read_manifest_file(path)
    prev = cs.read_manifest_file(path + ".v0000000008")
    unique = cs.manifest_digests(newest) - cs.manifest_digests(prev)
    assert unique  # different seeds -> different params
    os.remove(store.chunk_path(next(iter(unique))))
    ok, _, reason = ck.verify_checkpoint(path)
    assert not ok and "chunk-incomplete" in reason
    # The .v16 version references the SAME missing chunk — the scan
    # must fall all the way back to step 8, which is chunk-complete.
    got = ck.restore_latest_valid(_state(), path, g)
    assert got is not None
    restored, meta, used = got
    assert int(meta["step"]) == 8
    assert _tree_equal(restored, s8)


# -- retention + GC ---------------------------------------------------


def _stable_and_moving(step, seed_moving):
    """A state whose encoder subtree is bitwise-stable across saves
    while the decoder moves — the chunk-sharing fixture."""
    s = _state(step, seed=0)
    p = dict(jax.device_get(s.params))
    p["fc4"] = jax.tree.map(
        lambda x: np.asarray(x) + np.float32(seed_moving), p["fc4"]
    )
    return s.replace(params=p)


def test_retention_shares_chunks_and_never_drops_referenced(tmp_path):
    path = str(tmp_path / "state.msgpack")
    store = cs.ChunkStore(cs.chunk_dir_for(path))
    for i, step in enumerate((8, 16, 24)):
        _save_v2(_stable_and_moving(step, i), path, step, keep_last=2)
    # keep_last=2: step 8's version pruned; its UNIQUE chunks are gone,
    # the shared (stable-subtree) chunks survive for 16/24.
    assert not os.path.exists(path + ".v0000000008")
    m24 = cs.read_manifest_file(path)
    m16 = cs.read_manifest_file(path + ".v0000000016")
    shared = cs.manifest_digests(m24) & cs.manifest_digests(m16)
    assert shared  # the stable encoder dedups across versions
    # The eviction-never-drops-a-referenced-chunk regression: every
    # RETAINED manifest stays chunk-complete after pruning.
    for cand in ck.checkpoint_candidates(path):
        ok, _, reason = ck.verify_checkpoint(cand)
        assert ok, (cand, reason)
    # Refcounts: shared chunks counted once per referencing manifest.
    refs = store.refcounts()
    for d in shared:
        assert refs.get(d, 0) >= 2
    # Disk holds no chunk that zero retained manifests reference
    # (the primary-replace + prune decrements fired).
    live = cs.manifest_digests(m24) | cs.manifest_digests(m16)
    on_disk = set(store.all_chunks())
    assert on_disk == live


def test_gc_reconciles_and_sweeps_orphans(tmp_path):
    path = str(tmp_path / "state.msgpack")
    s = _state(8, seed=3)
    _save_v2(s, path, 8)
    store = cs.ChunkStore(cs.chunk_dir_for(path))
    # A crashed save's leak: chunks landed, no manifest references
    # them, refcounts never updated.
    orphan, _ = store.put(os.urandom(5000))
    # And a leaked COUNT: refs claim a manifest that does not exist.
    store.incr({orphan})
    rep = cs.sweep_ckpt_dir(str(tmp_path), grace_s=3600.0)
    assert rep["orphans_removed"] == 0 and rep["kept_in_grace"] == 1
    assert rep["leaked_refs_reconciled"] >= 1  # the bogus count dropped
    rep = cs.sweep_ckpt_dir(str(tmp_path), grace_s=0.0)
    assert rep["orphans_removed"] == 1
    assert not os.path.exists(store.chunk_path(orphan))
    # The referenced manifest stays restorable — even with refs.json
    # deleted entirely (the sweep rebuilds it from the manifests).
    os.remove(store.refs_path())
    rep = cs.sweep_ckpt_dir(str(tmp_path), grace_s=0.0)
    assert rep["orphans_removed"] == 0
    ok, _, reason = ck.verify_checkpoint(path)
    assert ok, reason
    assert _tree_equal(ck.restore_state(_state(), path), s)


def test_ckpt_gc_cli(tmp_path, capsys):
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import ckpt_gc

    d = tmp_path / "run" / "trial-0"
    d.mkdir(parents=True)
    path = str(d / "state.msgpack")
    _save_v2(_state(8), path, 8)
    store = cs.ChunkStore(cs.chunk_dir_for(path))
    orphan, _ = store.put(os.urandom(1000))
    # Dry run: reports, removes nothing.
    rc = ckpt_gc.main([str(tmp_path / "run"), "--dry-run", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["totals"]["dirs"] == 1
    assert out["reports"][0]["orphans_found"] == 1
    assert os.path.exists(store.chunk_path(orphan))
    # Real sweep.
    rc = ckpt_gc.main([str(tmp_path / "run"), "--grace", "0", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["totals"]["orphans_removed"] == 1
    assert not os.path.exists(store.chunk_path(orphan))
    ok, _, reason = ck.verify_checkpoint(path)
    assert ok, reason


_KILL_CHILD = r"""
import os, sys
sys.path.insert(0, sys.argv[2])
os.environ["MDT_CKPT_PERSIST_DELAY_S"] = "0.15"
import jax, optax
import jax.numpy as jnp
from multidisttorch_tpu.models.vae import VAE
from multidisttorch_tpu.train import checkpoint as ck
from multidisttorch_tpu.train.steps import build_train_state

s = build_train_state(VAE(hidden_dim=16, latent_dim=4),
                      optax.adam(1e-3), jax.random.key(0))
path = sys.argv[1]
step = 0
while True:
    step += 8
    ck.save_state(
        s.replace(step=jnp.asarray(step, jnp.int32)), path,
        metadata={"step": step, "completed_epochs": step // 8},
        keep_last=2, format="v2", chunk_bytes=2048,
    )
    print("SAVED %d" % step, flush=True)
"""


@pytest.mark.ckpt
def test_kill_mid_save_leaves_previous_step_restorable(tmp_path):
    """SIGKILL DURING a v2 persist (the delay env holds every save
    open for 150ms): the previous step stays restorable, the wreckage
    is leaked chunks at worst, and the orphan sweep reclaims them
    without touching the survivors."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    path = str(tmp_path / "state.msgpack")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [
            sys.executable,
            "-c",
            _KILL_CHILD,
            path,
            os.path.abspath(repo),
        ],
        stdout=subprocess.PIPE,
        text=True,
        env=env,
    )
    saved = 0
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("SAVED"):
                saved = int(line.split()[1])
                if saved >= 16:
                    break
        assert saved >= 16, "child never reached two durable saves"
        # Kill mid-save: the delay guarantees the NEXT save is open
        # for a long window; give it time to enter it.
        time.sleep(0.05)
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        proc.wait(timeout=30)
    (g,) = setup_groups(1)
    got = ck.restore_latest_valid(_state(), path, g)
    assert got is not None
    restored, meta, used = got
    # A step the child durably reported (or one the kill let finish).
    assert int(meta["step"]) >= saved - 8
    assert int(jax.device_get(restored.step)) == int(meta["step"])
    # Crash wreckage never corrupts: sweep reclaims leaks, survivors
    # stay chunk-complete and restorable.
    cs.sweep_ckpt_dir(str(tmp_path), grace_s=0.0)
    got2 = ck.restore_latest_valid(_state(), path, g)
    assert got2 is not None and int(got2[1]["step"]) == int(meta["step"])
    # And the directory keeps working: a fresh save on top is clean.
    _save_v2(_state(99), path, 99)
    ok, _, reason = ck.verify_checkpoint(path)
    assert ok, reason


# -- agreement / cache ------------------------------------------------


def test_agreed_restore_step_over_chunked_checkpoints(tmp_path):
    """The cross-host restore agreement's read side over v2: local
    candidate verification is chunk-complete, so a host whose newest
    manifest lost a chunk votes the previous step."""
    path = str(tmp_path / "state.msgpack")
    _save_v2(_state(8, seed=1), path, 8, keep_last=2)
    _save_v2(_state(16, seed=2), path, 16, keep_last=2)
    got = ck.agreed_restore_step(
        path, name="t0:a1", participants=[0], timeout_s=5.0
    )
    assert got is not None and got[0] == 16
    # Lose a chunk unique to step 16 on "this host": the vote drops.
    store = cs.ChunkStore(cs.chunk_dir_for(path))
    uniq = cs.manifest_digests(cs.read_manifest_file(path)) - (
        cs.manifest_digests(
            cs.read_manifest_file(path + ".v0000000008")
        )
    )
    os.remove(store.chunk_path(next(iter(uniq))))
    got = ck.agreed_restore_step(
        path, name="t0:a2", participants=[0], timeout_s=5.0
    )
    assert got is not None and got[0] == 8


def test_snapshot_cache_semantics():
    cache = ck._SnapshotCache(max_entries=2)
    cache.put("/a/t1/s.msgpack", {"x": 1}, {"step": 1})
    cache.put("/a/t2/s.msgpack", {"x": 2}, {"step": 2})
    got = cache.get("/a/t1/s.msgpack")
    assert got is not None and got[0] == {"x": 1}
    # LRU bound: t1 was just touched, so t2 evicts.
    cache.put("/a/t3/s.msgpack", {"x": 3}, {"step": 3})
    assert cache.get("/a/t2/s.msgpack") is None
    assert cache.get("/a/t1/s.msgpack") is not None
    # Ownership-change invalidation: everything under a dir drops.
    assert cache.drop_under("/a") == 2
    assert len(cache) == 0


def test_driver_v2_skips_gather_for_sharded_state():
    """The sharded-native save path: under v2 a single-controller
    ZeRO state checkpoints WITHOUT the gather-to-replicated dispatch;
    v1 keeps it (serialization needs one blob)."""
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.hpo.driver import TrialConfig, _TrialRun

    import tempfile

    (g,) = setup_groups(1)
    data = synthetic_mnist(64, seed=0)
    out = tempfile.mkdtemp()
    base = dict(
        epochs=1, batch_size=32, hidden_dim=16, latent_dim=4,
        zero_update=True,
    )
    run_v2 = _TrialRun(
        g, TrialConfig(trial_id=0, **base), data, None,
        out, save_images=False, verbose=False,
        ckpt_format="v2",
    )
    assert run_v2._gather_state is None
    run_v1 = _TrialRun(
        g, TrialConfig(trial_id=1, **base), data, None,
        out, save_images=False, verbose=False,
        ckpt_format="v1",
    )
    assert run_v1._gather_state is not None


def test_pipeline_stage_manifests_share_one_store(tmp_path):
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.hpo.driver import TrialConfig
    from multidisttorch_tpu.hpo.pipeline_run import run_pipeline_trial

    groups = setup_groups(2)
    cfg = TrialConfig(
        trial_id=0, epochs=1, batch_size=32, latent_dim=4,
        pipeline_stages=2, grad_accum=2,
    )
    os.environ["MDT_CKPT_FORMAT"] = "v2"
    try:
        run_pipeline_trial(
            cfg, synthetic_mnist(64, seed=0),
            stage_meshes=groups, out_dir=str(tmp_path),
        )
    finally:
        os.environ.pop("MDT_CKPT_FORMAT", None)
    d = tmp_path / "trial-0"
    stage_paths = [str(d / f"stage{s}.msgpack") for s in range(2)]
    for p in stage_paths:
        assert cs.is_manifest_file(p)
        ok, meta, reason = ck.verify_checkpoint(p)
        assert ok, reason
        assert meta["pipeline_stage"] is True
    # One chunk store per trial dir, shared by both stage families.
    assert cs.chunk_dir_for(stage_paths[0]) == cs.chunk_dir_for(
        stage_paths[1]
    )
    assert len(cs.live_manifest_files(str(d))) == 2


# -- v1 against v2, every trial flavor ---------------------------------


def _decode_on_disk(path):
    """(format, {leaf key: host array}) of one checkpoint file, read
    without a template: what the disk holds."""
    from flax import serialization

    with open(path, "rb") as f:
        blob = f.read()
    if cs.is_manifest_blob(blob):
        store = cs.ChunkStore(cs.chunk_dir_for(path))
        fmt, sd = "v2", cs.restore_arrays(cs.load_manifest(blob), store)
    else:
        fmt, sd = "v1", serialization.msgpack_restore(blob)
    return fmt, dict(cs._flatten_state_dict(sd))


@pytest.mark.parametrize("flavor", ["classic", "stacked", "zero", "pipelined"])
def test_v1_and_v2_checkpoints_hold_the_same_bits(flavor, tmp_path, monkeypatch):
    """Trained twice from one seed, once writing v1 and once v2: both
    files decode to the same leaves, dtypes and bits, and the ZeRO
    manifest names the moments' sharding (no gather was run)."""
    from multidisttorch_tpu.data.datasets import synthetic_mnist
    from multidisttorch_tpu.hpo.driver import TrialConfig, run_hpo
    from multidisttorch_tpu.hpo.pipeline_run import run_pipeline_trial

    train = synthetic_mnist(128, seed=0)
    base = dict(epochs=1, batch_size=32, hidden_dim=16, latent_dim=4,
                log_interval=1000)
    n = 2 if flavor == "stacked" else 1
    names = [f"trial-{i}/state.msgpack" for i in range(n)]
    if flavor == "pipelined":
        names = ["trial-0/stage0.msgpack", "trial-0/stage1.msgpack"]
    decoded = {}
    for fmt in ("v1", "v2"):
        monkeypatch.setenv("MDT_CKPT_FORMAT", fmt)
        out = str(tmp_path / fmt)
        if flavor == "pipelined":
            cfg = TrialConfig(trial_id=0, pipeline_stages=2, grad_accum=2, **base)
            run_pipeline_trial(cfg, train, stage_meshes=setup_groups(2),
                               out_dir=out, verbose=False)
        else:
            cfgs = [TrialConfig(trial_id=i, seed=i, zero_update=flavor == "zero",
                                **base) for i in range(n)]
            run_hpo(cfgs, train, num_groups=1, out_dir=out, save_images=False,
                    verbose=False, stack_trials=flavor == "stacked")
        decoded[fmt] = [_decode_on_disk(os.path.join(out, x)) for x in names]
    for (f1, a), (f2, b) in zip(decoded["v1"], decoded["v2"]):
        assert (f1, f2) == ("v1", "v2") and a and set(a) == set(b)
        for k in a:
            x, y = np.asarray(a[k]), np.asarray(b[k])
            assert x.dtype == y.dtype and np.array_equal(x, y), k
    if flavor == "zero":
        m = cs.read_manifest_file(str(tmp_path / "v2" / names[0]))
        assert any("data" in str(leaf.get("sharding")) for leaf in m["leaves"]
                   if leaf["key"].startswith("opt_state"))


# -- snapshot-fast drain (service) ------------------------------------


@pytest.mark.service
def test_snapshot_drain_honesty_and_ram_replace(tmp_path):
    """The drain contract end to end: slices free at the snapshot, the
    ledger records `preempted` only after the background persist lands,
    the victim re-places from the RAM snapshot, and the trace renders
    the snapshot/persist split inside the attempt."""
    from multidisttorch_tpu import telemetry
    from multidisttorch_tpu.service import queue as squeue
    from multidisttorch_tpu.service.runtime import SweepService
    from multidisttorch_tpu.telemetry import trace as ttrace

    d = str(tmp_path / "svc")
    os.makedirs(d)
    telemetry.configure(os.path.join(d, "telemetry"))
    os.environ[
        "MDT_CKPT_PERSIST_DELAY_S"
    ] = "0.4"
    ram0 = ck.ckpt_counters()["restores_ram"]
    try:
        client = squeue.SweepClient(d, tenant="t")
        sub = client.submit(
            {
                "epochs": 4,
                "batch_size": 32,
                "latent_dim": 4,
                "hidden_dim": 16,
                "log_interval": 1000,
            }
        )
        svc = SweepService(
            d, n_slices=1, max_lanes=1, data_rows=128,
            defrag_enabled=False, snapshot_drain=True, ckpt_format="v2",
        )
        t0 = time.time()
        ap = None
        while time.time() - t0 < 60:
            svc.tick()
            actives = list(svc.active.values())
            if actives and bool(
                actives[0].run.result.checkpoint
            ) and not actives[0].run._ckpt_idle():
                ap = actives[0]
                break
        assert ap is not None, "no in-flight checkpoint write observed"
        tid = next(iter(ap.entries)).__int__()

        svc._checkpoint_drain(ap, reason="test preemption")
        # Snapshot phase: slices free NOW, persist still in flight,
        # and the ledger does NOT say preempted yet.
        assert svc.pool.free_total == 1
        assert len(svc._pending_persists) == 1
        with open(svc.ledger.path) as f:
            assert '"preempted"' not in f.read()
        # Persist lands -> honest record + requeue.
        t0 = time.time()
        while svc._pending_persists and time.time() - t0 < 30:
            svc.tick()
        assert not svc._pending_persists
        with open(svc.ledger.path) as f:
            led = f.read()
        assert led.count('"preempted"') == 1
        # The victim re-places in THIS process: RAM-snapshot restore.
        t0 = time.time()
        while not svc.settled.get(sub) and time.time() - t0 < 120:
            svc.tick()
        assert svc.settled.get(sub) == "completed"
        assert ck.ckpt_counters()["restores_ram"] > ram0
        books = svc.books()
        ckb = books["checkpoint"]
        assert ckb["drain_snapshot"]["count"] == 1
        assert ckb["drain_persist"]["count"] == 1
        assert ckb["restores_ram"] >= 1
        svc._drain(reason="test end")
        svc.store.shutdown()
    finally:
        os.environ.pop("MDT_CKPT_PERSIST_DELAY_S", None)
        telemetry.disable()
    # The offline trace renders the split: a ckpt_persist SPAN (not
    # instant), closed, inside the submission's tree.
    traces = ttrace.build_submission_traces(d)
    tr = traces[sub]
    names = {
        s["name"]: s for s in tr["spans"]
    }
    assert "ckpt_persist" in names
    persist = names["ckpt_persist"]
    assert persist["kind"] == "span"
    assert persist["end"] is not None
    assert any(
        s["name"] == "ckpt_snapshot" for s in tr["spans"]
    )
    assert tid is not None  # silence unused warnings


@pytest.mark.service
def test_legacy_join_drain_mode_still_blocks(tmp_path):
    """MDT_SNAPSHOT_DRAIN=0 / snapshot_drain=False keeps the v1-era
    semantics: the drain joins the persist inline, records preempted
    immediately, and requeues before returning — the bench's
    comparison arm, and the conservative operator escape hatch."""
    from multidisttorch_tpu.service import queue as squeue
    from multidisttorch_tpu.service.runtime import SweepService

    d = str(tmp_path / "svc")
    os.makedirs(d)
    client = squeue.SweepClient(d, tenant="t")
    client.submit(
        {
            "epochs": 3,
            "batch_size": 32,
            "latent_dim": 4,
            "hidden_dim": 16,
            "log_interval": 1000,
        }
    )
    svc = SweepService(
        d, n_slices=1, max_lanes=1, data_rows=128,
        defrag_enabled=False, snapshot_drain=False, ckpt_format="v1",
    )
    t0 = time.time()
    ap = None
    while time.time() - t0 < 60:
        svc.tick()
        actives = list(svc.active.values())
        if actives and bool(actives[0].run.result.checkpoint):
            ap = actives[0]
            break
    assert ap is not None
    svc._checkpoint_drain(ap, reason="test preemption")
    # Everything happened inline: no pending persist, ledger already
    # has the record, pool already free.
    assert not svc._pending_persists
    assert svc.pool.free_total == 1
    with open(svc.ledger.path) as f:
        assert '"preempted"' in f.read()
    svc._drain(reason="test end")
    svc.store.shutdown()


@pytest.mark.service
def test_deadline_preemption_through_the_snapshot_drain(tmp_path, monkeypatch):
    """A deadline submission the size of the pool evicts both
    best-effort trials through the snapshot drain: while a victim's
    persist is in flight its `preempted` record is not in the ledger;
    afterwards both are, and the whale and both victims complete."""
    from multidisttorch_tpu.service import queue as squeue
    from multidisttorch_tpu.service.runtime import SweepService
    from multidisttorch_tpu.service.scheduler import PreemptionPolicy

    monkeypatch.setenv("MDT_CKPT_PERSIST_DELAY_S", "0.25")
    d = str(tmp_path)
    client = squeue.SweepClient(d, tenant="t")
    svc = SweepService(
        d, n_slices=2, max_lanes=1, data_rows=128, defrag_enabled=False,
        snapshot_drain=True, ckpt_format="v2",
        preempt=PreemptionPolicy(
            max_preemptions_per_trial=1, trial_cooldown_s=5.0,
            global_cooldown_s=0.05,
        ),
    )
    base = dict(batch_size=32, latent_dim=4, log_interval=1000)
    ram0 = ck.ckpt_counters()["restores_ram"]

    def tick_until(cond, timeout_s=300.0):
        t0 = time.time()
        while not cond() and time.time() - t0 < timeout_s:
            svc.tick()
        return cond()

    for hidden in (16, 24):  # two buckets: nothing co-packs
        client.submit({**base, "epochs": 12, "hidden_dim": hidden})
    assert tick_until(lambda: len(svc.active) == 2 and all(
        ap.run.result.checkpoint for ap in svc.active.values()))
    whale = client.submit({**base, "epochs": 1, "hidden_dim": 40, "seed": 9},
                          size=2, deadline_s=600.0)
    seen = {"pending": 0, "early": 0}

    def whale_settled():
        pending = {p.entry.trial_id for p in svc._pending_persists}
        if pending:
            seen["pending"] += 1
            with open(svc.ledger.path) as f:
                recs = [json.loads(line) for line in f if line.strip()]
            seen["early"] += sum(r.get("status") == "preempted"
                                 and r.get("trial_id") in pending for r in recs)
        return whale in svc.settled

    assert tick_until(whale_settled) and svc.settled[whale] == "completed"
    assert seen["pending"] and not seen["early"]
    assert tick_until(lambda: len(svc.settled) == 3)
    assert set(svc.settled.values()) == {"completed"}
    with open(svc.ledger.path) as f:
        assert f.read().count('"preempted"') == 2
    assert svc.books()["preemption"]["evictions"] == 2
    assert ck.ckpt_counters()["restores_ram"] > ram0
    svc._drain(reason="test end")
    svc.store.shutdown()


def test_sweep_top_renders_ckpt_books():
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import sweep_top

    from types import SimpleNamespace

    books = {
        "checkpoint": {
            "format": "v2",
            "snapshot_drain": True,
            "pending_persists": 1,
            "saves": 12,
            "bytes_total": 10_000_000,
            "bytes_written": 2_500_000,
            "bytes_reused": 7_500_000,
            "delta_ratio": 0.25,
            "restores": 3,
            "restores_ram": 2,
            "drain_snapshot": {
                "count": 2, "p50_s": 0.001, "p99_s": 0.002,
                "max_s": 0.002,
            },
            "drain_persist": {
                "count": 2, "p50_s": 0.3, "p99_s": 0.5, "max_s": 0.5,
            },
        },
    }
    out = sweep_top.render_service(
        {}, books, SimpleNamespace(trials={}), "/tmp/svc"
    )
    assert "ckpt" in out and "fmt v2" in out
    assert "delta 0.25" in out
    assert "ram-restores 2" in out
    assert "drain-snapshot" in out and "drain-persist" in out
