"""``chip_smoke.py``'s control flow, debugged on the CPU at a tiny size
before chip time is spent on it: phases 2 (classic + resume), 3
(stacked + classic twin) and 5 (service) on virtual CPU devices. The
script itself still refuses a CPU backend."""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from multidisttorch_tpu.data.datasets import synthetic_mnist
from multidisttorch_tpu.utils.compile_cache import (
    compile_log,
    enable_compile_cache,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = chip_smoke.Size(
    hidden_dim=16, latent_dim=4, batch_size=32, fused_steps=2,
    train_rows=128, test_rows=64, stacked_lanes=4, submissions=2,
    stacked_rel_tol=1e-6,
)


@pytest.fixture(scope="module")
def data():
    return synthetic_mnist(TINY.train_rows, seed=0), synthetic_mnist(
        TINY.test_rows, seed=1
    )


def test_phases_classic_stacked_service_tiny(tmp_path, data):
    train, test = data
    devices = jax.devices()[:2]
    enable_compile_cache()  # as main() does: installs the compile log
    before = compile_log().snapshot()
    with chip_smoke.phase("2 run_hpo classic", devices):
        first = chip_smoke.phase_classic(
            devices, str(tmp_path), TINY, train, test
        )
    assert [r.group_id for r in first] == [0, 1]
    with chip_smoke.phase("3 run_hpo stacked", devices):
        stacked = chip_smoke.phase_stacked(
            devices, str(tmp_path), TINY, train, test
        )
    assert len(stacked) == TINY.stacked_lanes
    with chip_smoke.phase("5 sweep service", devices):
        settled = chip_smoke.phase_service(
            devices, str(tmp_path), TINY, train, test
        )
    assert len(settled) == TINY.submissions
    after = compile_log().snapshot()
    # the persistent cache is on
    assert after["hits"] + after["misses"] > before["hits"] + before["misses"]
    assert after["backend_s"] > before["backend_s"]


def test_a_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="status='failed'"):
        from multidisttorch_tpu.hpo import TrialResult

        chip_smoke.check_results(
            [TrialResult(0, 0, TINY.config(0), status="failed", error="boom")],
            steps=4, label="classic",
        )


def test_script_refuses_a_cpu_backend():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode != 0
    assert p.stdout == ""  # no result line, nothing trained
    assert "needs a TPU" in p.stderr
