"""CPU rehearsal of the entry at a tiny size: control flow, the record
every metric reader takes, and the keys of the last line. No number
from here is a device number."""

import json
import subprocess
import sys

import jax
import pytest

from benchmark import cells
from benchmark.compile_book import CompileBook

TINY_CONFIG = {
    "name": "tiny", "entry": "lm_trial",
    "reference": "benchmark/configs/gpt2-medium.reference.py",
    "vocab_size": 61, "n_positions": 32, "n_embd": 32, "n_head": 4, "n_layer": 2,
    "layer_norm_epsilon": 1e-6,
    "assumed": {"compute_dtype": "bfloat16", "remat": False},
    "compared": {"logits_rel_rms": 0.05, "loss_rel": 0.01, "grad_rel_l2": 0.2},
}


def tiny_traffic(trials: int) -> dict:
    return {
        "name": "tiny", "batch_sequences": 4, "sequence_length": 32,
        "learning_rates": [1e-2 * (i + 1) for i in range(trials)],
    }


@pytest.fixture(scope="module")
def book():
    return CompileBook()


def tiny_cell(trials: int) -> cells.Cell:
    real = cells.load_cell("lm-dense")
    return cells.Cell(
        name=real.name, chips=trials, config=TINY_CONFIG,
        traffic=tiny_traffic(trials),
        end_to_end=real.end_to_end, per_layer=real.per_layer,
    )


def finish(record: dict) -> dict:
    record["t_process_start"] = record["stamps"][0] - 1.0
    record["t_entry"] = record["stamps"][0] - 0.5
    record["device"] = {"kind": "TPU v5 lite", "count": 1}  # for the peak table only
    return record


def test_one_trial_untraced(book):
    cell = tiny_cell(1)
    record = finish(cell.entry().run(cell, jax.devices()[:1], 3, 1.0, None, book))
    assert record["checks"] == {
        "reference": True, "losses_finite": True, "losses_falling": True,
        "nothing_compiled_in_window": True,
    }, record["reference"]["notes"]
    assert record["correct"] and record["failed"] == 0
    assert record["attempted"] == len(record["stamps"]) - 1
    got = cells.read_metrics(cell.end_to_end, "end_to_end", record)
    assert set(got) == {"tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}


def test_fleet_traced(book, tmp_path):
    """Four trials round-robin on four virtual devices, then the traced
    part. The CPU trace holds no TPU plane, so the reduction has
    nothing to read: the rehearsal stops there."""
    cell = tiny_cell(4)
    entry = cell.entry()
    with pytest.raises(ValueError, match="no device operation"):
        entry.run(cell, jax.devices()[:4], 5, 0.5, str(tmp_path / "trace"), book)


def test_fleet_untraced_and_layer_readers(book):
    cell = tiny_cell(4)
    record = finish(cell.entry().run(cell, jax.devices()[:4], 5, 2.0, None, book))
    assert record["correct"], (record["checks"], record["reference"]["notes"])
    assert record["attempted"] == 4 * (len(record["stamps"]) - 1)
    record["trace"] = {"idle_share_worst": 0.25}
    got = cells.read_metrics(cell.per_layer, "layer_metrics", record)
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert got["device_idle_share"]["value"] == 25.0
    assert got["compiles_in_window"]["value"] == 0


def test_a_different_seed_gives_different_inputs(book):
    cell = tiny_cell(1)
    runs = [
        cell.entry().run(cell, jax.devices()[:1], seed, 1.0, None, book)["losses_first_last"]
        for seed in (3, 3, 4)
    ]
    assert runs[0][0][0] == runs[1][0][0] != runs[2][0][0]


def test_command_refuses_the_cpu():
    """The command itself: no TPU, no result line, exit code not 0."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "lm-dense", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_last_line_keys_are_the_contract(book, capsys, monkeypatch):
    """``run.main`` with the chip check stood down: the last line of
    standard output is one JSON object with the contract's keys."""
    from benchmark import run as command

    cell = tiny_cell(1)
    monkeypatch.setattr(cells, "load_cell", lambda name: cell)

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __init__(self, real):
            self.real = real

        def __getattr__(self, name):
            return getattr(self.real, name)

    real_devices = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu(real_devices[0])])
    entry = cell.entry()
    real_run = entry.run
    monkeypatch.setattr(
        cells.Cell, "entry",
        lambda self: type("E", (), {"run": staticmethod(
            lambda cell, devices, *rest: real_run(cell, real_devices[:1], *rest))}),
    )
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "lm-dense", "--seed", "1",
                                      "--seconds", "1", "--trace", "0"])
    assert command.main() == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(last["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    for metric in last["metrics"].values():
        assert set(metric) == {"value", "unit"}
