"""True multi-controller integration tests: N cooperating processes with
M virtual CPU devices each over the Gloo-backed JAX distributed runtime
(2x4 for the core cases, 4x2 for the >2-process agreement/writer-gating
and uneven-ownership cases).

The reference could only validate multi-node behavior by running on the
real clusters its env detection targets (SURVEY.md §4); these tests
exercise the same contracts — per-process trial membership, a submesh
spanning processes, cross-process PBT weight exchange — in plain pytest.

Subprocesses are required (jax.distributed is per-process global state),
so these tests bypass the in-process 8-fake-device conftest harness.
"""

import json
import os
import socket
import subprocess
import sys
import threading

import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "mh_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(
    mode: str,
    tmp_path,
    *,
    nprocs: int = 2,
    devs_per_proc: int = 4,
    timeout: int = 420,
    extra_env: dict | None = None,
) -> list[dict]:
    """Run ``nprocs`` worker ranks through the framework's own
    OpenMPI-style env detection; return every RESULT payload.

    The default 2x4 world matches the original harness; 4x2 exercises
    agreement/writer-gating at >2 processes (the reference's own demo is
    an 8-process world, example-subgroup.py:39)."""
    port = _free_port()
    procs = []
    for rank in range(nprocs):
        env = dict(os.environ)
        env.update(
            OMPI_COMM_WORLD_SIZE=str(nprocs),
            OMPI_COMM_WORLD_RANK=str(rank),
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(port),
            MH_DEVS_PER_PROC=str(devs_per_proc),
            **(extra_env or {}),
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, _WORKER, mode, str(tmp_path / "out")],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    # Drain all pipes concurrently: one rank dying mid-collective can
    # fill its pipe while its peers block in the collective — sequential
    # communicate() would deadlock the group. Kill whatever survives a
    # timeout so a hung rendezvous can't poison later tests.
    outs: list = [None] * nprocs

    def drain(i, p):
        try:
            outs[i] = p.communicate(timeout=timeout)[0]
        except subprocess.TimeoutExpired:
            pass

    try:
        threads = [
            threading.Thread(target=drain, args=(i, p))
            for i, p in enumerate(procs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout + 30)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert out is not None, f"rank {rank} timed out"
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-4000:]}"
    results = []
    for out in outs:
        lines = [l for l in out.splitlines() if l.startswith("RESULT ")]
        assert lines, f"no RESULT line in:\n{out[-4000:]}"
        results.append(json.loads(lines[-1][len("RESULT "):]))
    return sorted(results, key=lambda r: r["pid"])


@pytest.mark.multihost
def test_split_groups_each_process_runs_its_trial(tmp_path):
    r0, r1 = _launch("hpo_split", tmp_path)
    # Process g owns group g only -> runs only trial g (the reference's
    # membership contract, vae-hpo.py:200-202, without any collective).
    assert r0["local_trials"] == [0]
    assert r1["local_trials"] == [1]
    assert r0["steps"]["0"] == 8 and r1["steps"]["1"] == 8


@pytest.mark.multihost
def test_spanning_group_trains_identically_on_both_processes(tmp_path):
    r0, r1 = _launch("hpo_span", tmp_path)
    # SPMD: both processes executed the same trial over the shared
    # 8-device submesh and must agree bit-for-bit on the results.
    assert r0["final_train_loss"] == r1["final_train_loss"]
    assert r0["final_test_loss"] == r1["final_test_loss"]
    assert r0["steps"] == r1["steps"] == 16
    # Writer gating: artifacts exist, and only rank 0 (owner of the
    # group's first device) reports having written the checkpoint.
    assert r0["wrote_metrics"] and r1["wrote_metrics"]  # shared FS view
    assert r0["wrote_ckpt"] and not r1["wrote_ckpt"]


@pytest.mark.multihost
def test_resilient_split_groups_isolate_deterministic_failure(tmp_path):
    r0, r1 = _launch("resilient_split", tmp_path)
    # Trial 1 (group 1, wholly owned by process 1) fails
    # deterministically; the sweep completes everywhere, and group 0's
    # elastic queue still serves trial 2.
    assert r0["statuses"] == {"0": "completed", "2": "completed"}
    assert r1["statuses"] == {"1": "failed"}
    assert "injected deterministic failure" in r1["errors"]["1"]


@pytest.mark.multihost
def test_resilient_spanning_group_agrees_on_writer_only_failure(tmp_path):
    r0, r1 = _launch("resilient_span_io", tmp_path)
    # The image write failed on the WRITER process only; the
    # epoch-boundary health reduction must kill trial 0 on BOTH owner
    # processes (without it, rank 1 keeps stepping trial 0 while rank 0
    # has freed the submesh — desynchronized collectives / hang). Both
    # must then complete trial 1 on the freed submesh.
    for r in (r0, r1):
        assert r["statuses"] == {"0": "failed", "1": "completed"}, r
        assert r["trial1_steps"] == 16
    # Rank 0 carries the real error; rank 1 learned of it via agreement.
    assert "injected writer-only disk failure" in r0["errors"]["0"]
    assert "peer" in r1["errors"]["0"] or "injected" in r1["errors"]["0"]


@pytest.mark.multihost
def test_resilient_spanning_group_agrees_on_asymmetric_setup_failure(tmp_path):
    r0, r1 = _launch("resilient_span_setup", tmp_path)
    # Setup raised on process 1 only; the setup agreement keeps process
    # 0 from stepping a trial its peer never constructed.
    for r in (r0, r1):
        assert r["statuses"] == {"0": "failed", "1": "completed"}, r
    assert "injected one-process setup failure" in r1["errors"]["0"]
    assert "peer" in r0["errors"]["0"]


@pytest.mark.multihost
def test_spanning_group_trains_identically_on_four_processes(tmp_path):
    # VERDICT r3 item 7: the 2-process harness capped validation below
    # the reference's own 8-process demo (example-subgroup.py:39). Same
    # spanning-SPMD contract at 4 processes x 2 devices.
    rs = _launch("hpo_span", tmp_path, nprocs=4, devs_per_proc=2, timeout=600)
    assert len(rs) == 4
    assert len({r["final_train_loss"] for r in rs}) == 1
    assert len({r["final_test_loss"] for r in rs}) == 1
    assert all(r["steps"] == 16 for r in rs)
    # Writer gating at 4 processes: exactly one owner wrote the ckpt —
    # the owner of device 0 (process 0).
    assert [r["wrote_ckpt"] for r in rs] == [True, False, False, False]
    assert all(r["wrote_metrics"] for r in rs)  # shared-FS view


@pytest.mark.multihost
def test_resilient_spanning_agreement_at_four_processes(tmp_path):
    # Writer-only I/O failure agreed across FOUR owner processes: every
    # process must kill trial 0 identically and complete trial 1.
    rs = _launch(
        "resilient_span_io", tmp_path, nprocs=4, devs_per_proc=2,
        timeout=600,
    )
    assert len(rs) == 4
    for r in rs:
        assert r["statuses"] == {"0": "failed", "1": "completed"}, r
        assert r["trial1_steps"] == 16
    assert "injected writer-only disk failure" in rs[0]["errors"]["0"]
    for r in rs[1:]:
        assert "peer" in r["errors"]["0"] or "injected" in r["errors"]["0"]


@pytest.mark.multihost
def test_uneven_ownership_spanning_groups(tmp_path):
    # Two 3-device groups over a 4x2 world: owners hold UNEQUAL device
    # counts (2/1 and 1/2), and process 3 owns nothing. Membership,
    # bit-identical SPMD results across co-owners, writer gating, and a
    # clean no-op exit for the unowned process.
    rs = _launch("hpo_uneven", tmp_path, nprocs=4, devs_per_proc=2,
                 timeout=600)
    assert len(rs) == 4
    assert rs[0]["local_trials"] == [0]
    assert rs[1]["local_trials"] == [0, 1]
    assert rs[2]["local_trials"] == [1]
    assert rs[3]["local_trials"] == []
    # co-owners agree bit-for-bit per trial
    assert rs[0]["losses"]["0"] == rs[1]["losses"]["0"]
    assert rs[1]["losses"]["1"] == rs[2]["losses"]["1"]
    # writers: group 0's first device is on proc 0; group 1's on proc 1
    assert rs[0]["wrote_ckpt"]["0"] and not rs[1]["wrote_ckpt"]["0"]
    assert rs[1]["wrote_ckpt"]["1"] and not rs[2]["wrote_ckpt"]["1"]


@pytest.mark.multihost
def test_sequence_parallel_lm_spans_processes(tmp_path):
    # Long-context across HOSTS: one 64-token context sharded over 8
    # devices owned by 2 processes — ring attention's K/V rotation
    # crosses the process boundary. SPMD identity + learning.
    r0, r1 = _launch("lm_sp", tmp_path)
    assert r0["seq_shard_len"] == 8  # 64 tokens / 8 devices
    assert r0["first_loss"] == r1["first_loss"]
    assert r0["final_loss"] == r1["final_loss"]
    assert r0["first_loss"] > 1.5  # near-random at init (ln 16 ≈ 2.77)
    assert r0["final_loss"] < 0.8  # learned the periodic pattern


@pytest.mark.multihost
def test_moe_lm_ep_x_sp_spans_processes(tmp_path):
    # One (data=4 x model=2) trial spanning 2 processes: experts split
    # over the model axis, context ringing over the data axis — the
    # EP x SP composition under real multi-controller SPMD.
    r0, r1 = _launch("moe_lm_ep_sp", tmp_path)
    assert r0["expert_shard"] == 1  # 2 experts / 2-wide model axis
    assert r0["seq_shard_len"] == 8
    assert r0["first_loss"] == r1["first_loss"]
    assert r0["final_loss"] == r1["final_loss"]
    assert r0["final_loss"] < r0["first_loss"] * 0.5


@pytest.mark.multihost
def test_ring_flash_lm_spans_processes(tmp_path):
    # Same cross-process long-context world through the ring-flash path:
    # each hop's block pair runs the Pallas flash kernel while K/V
    # cross the process boundary on the ppermute ring.
    r0, r1 = _launch("lm_sp_flash", tmp_path)
    assert r0["seq_shard_len"] == 8
    assert r0["first_loss"] == r1["first_loss"]
    assert r0["final_loss"] == r1["final_loss"]
    assert r0["first_loss"] > 1.5
    assert r0["final_loss"] < 0.8


@pytest.mark.multihost
def test_spanning_tp_trial_checkpoints(tmp_path):
    # Weight-sharded (TP) trial spanning 2 processes with checkpointing
    # on: the epoch checkpoint must gather-to-replicated on all owners
    # so the writer can serialize — the sweep completes identically on
    # both processes and the checkpoint lands on disk.
    r0, r1 = _launch("hpo_span_tp", tmp_path)
    for r in (r0, r1):
        assert r["status"] == "completed", r
        assert r["steps"] == 16
        assert r["ckpt_exists"]
    assert r0["final_train_loss"] == r1["final_train_loss"]
    assert r0["wrote_ckpt"] and not r1["wrote_ckpt"]


@pytest.mark.multihost
def test_pbt_four_processes_population4_agrees(tmp_path):
    # PBT's global decisions (scores, ranking, exploits, perturbed lrs)
    # must agree across FOUR processes with a 4-member population (one
    # member per 2-device group, each wholly owned by one process), with
    # at least one exploit crossing a process boundary.
    rs = _launch(
        "pbt", tmp_path, nprocs=4, devs_per_proc=2, timeout=600,
        extra_env={"MH_PBT_POP": "4"},
    )
    assert len(rs) == 4
    for r in rs[1:]:
        assert r["best_member"] == rs[0]["best_member"]
        assert r["best_eval_loss"] == rs[0]["best_eval_loss"]
        assert r["final_lrs"] == rs[0]["final_lrs"]
        assert r["scores"] == rs[0]["scores"]
    assert rs[0]["n_exploits"] >= 1


@pytest.mark.multihost
def test_pbt_cross_process_exploit_agrees(tmp_path):
    r0, r1 = _launch("pbt", tmp_path)
    # Global decisions (scores, ranking, exploit targets, perturbed lrs)
    # must be identical on every process; at least one exploit crossed
    # the process boundary via broadcast_one_to_all.
    assert r0["best_member"] == r1["best_member"]
    assert r0["best_eval_loss"] == r1["best_eval_loss"]
    assert r0["final_lrs"] == r1["final_lrs"]
    assert r0["scores"] == r1["scores"]
    assert r0["n_exploits"] == r1["n_exploits"] >= 1
