"""Where the persistent compile cache lives (utils/compile_cache.py):
placed from outside by ``JAX_COMPILATION_CACHE_DIR``, else at one fixed
path in the checkout — never in a directory that moves."""

import os
import re
import subprocess
import sys

import jax
import pytest

from multidisttorch_tpu.utils.compile_cache import (
    default_cache_dir,
    enable_compile_cache,
    guard_submesh,
    submesh_defeats_cache,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_default_dir_honors_env_override(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/shared/disk")
    assert default_cache_dir() == "/some/shared/disk"


def test_default_dir_anchors_at_checkout_root(monkeypatch):
    # cwd-independent: the fallback is .jax_cache NEXT TO the package,
    # so every entry point shares one cache no matter where it runs.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir("/tmp")
    assert default_cache_dir() == os.path.join(ROOT, ".jax_cache")


def test_unset_goes_to_the_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert enable_compile_cache() == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        ROOT, ".jax_cache"
    )
    # jax's default of 1 s of compile time would skip the VAE's programs
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_a_directory_placed_from_outside_stands(restore_cache_config):
    # jax reads JAX_COMPILATION_CACHE_DIR into its config at import; a
    # configured directory — that one, or a drill's own — is left alone.
    jax.config.update("jax_compilation_cache_dir", "/some/dir")
    assert enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == "/some/dir"
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_env_dir_reaches_a_fresh_process_and_is_written_there(tmp_path):
    # End to end in a process of its own, as the chip machine would run
    # it: the variable set before python starts, an entry point called,
    # one compile — the entry lands in that directory and nowhere else.
    code = (
        "import jax, jax.numpy as jnp\n"
        "import multidisttorch_tpu as mdt\n"
        "mdt.initialize_runtime()\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones((4,))).block_until_ready()\n"
        "print('DIR|' + jax.config.jax_compilation_cache_dir)\n"
    )
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert f"DIR|{tmp_path}" in p.stdout
    assert any(n.endswith("-cache") for n in os.listdir(tmp_path))


def test_no_entry_point_places_the_cache_in_a_moving_directory():
    # The directory is part of the cache key: a temp dir, a pid or a
    # timestamp in it means no run ever hits. Every code path that sets
    # the directory on the training path must go through the one rule.
    # (Where the checkout itself sits is not the program's choice, so
    # the rule is checked in the source, not against the resolved path.)
    # The module also keeps the compile log, which reads the clock; the
    # two functions that place the directory may not.
    import inspect

    for placer in (default_cache_dir, enable_compile_cache):
        assert not re.search(
            r"tempfile|getpid|time\.", inspect.getsource(placer)
        )
    sources = [
        os.path.join(ROOT, f) for f in os.listdir(ROOT) if f.endswith(".py")
    ]
    for top in ("multidisttorch_tpu", "tools", "examples"):
        for base, _, files in os.walk(os.path.join(ROOT, top)):
            sources += [os.path.join(base, f) for f in files if f.endswith(".py")]
    setters = []
    for path in sources:
        with open(path) as fh:
            if re.search(r"update\(\s*[\"']jax_compilation_cache_dir", fh.read()):
                setters.append(os.path.relpath(path, ROOT))
    # utils/compile_cache.py is the rule; compile/cache.py is the
    # CPU-world quarantine drill (refuses a chip — test_compile_farm).
    assert sorted(setters) == [
        "multidisttorch_tpu/compile/cache.py",
        "multidisttorch_tpu/utils/compile_cache.py",
    ]


def test_which_submeshes_defeat_the_cache():
    # Measured on a four-chip v5e (PR 21): a cached executable with
    # collectives over chips [2, 3] halts when the next process
    # deserializes it; [0, 1], all four, and single chips are fine. The
    # guard takes every multi-chip strict subset of a TPU world.
    from types import SimpleNamespace

    tpu = [SimpleNamespace(platform="tpu", id=i) for i in range(4)]
    assert submesh_defeats_cache(tpu[2:], 4)
    assert submesh_defeats_cache(tpu[:2], 4)
    assert not submesh_defeats_cache(tpu, 4)  # the whole world
    assert not submesh_defeats_cache(tpu[3:], 4)  # one chip
    cpu = [SimpleNamespace(platform="cpu", id=i) for i in range(8)]
    assert not submesh_defeats_cache(cpu[2:4], 8)


def test_guard_turns_the_cache_off_for_the_process(
    tmp_path, monkeypatch, restore_cache_config
):
    import jax.numpy as jnp

    from jax.experimental.compilation_cache import compilation_cache

    import multidisttorch_tpu.utils.compile_cache as rule
    from multidisttorch_tpu.parallel.mesh import setup_groups

    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()  # jax binds its directory once
    enable_compile_cache()
    # CPU submeshes never trip it: carving is free of side effects here.
    setup_groups(4)
    assert jax.config.jax_enable_compilation_cache
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,))).block_until_ready()
    written = len(os.listdir(tmp_path))
    assert written >= 1
    try:
        monkeypatch.setattr(rule, "submesh_defeats_cache", lambda d, w: True)
        with pytest.warns(RuntimeWarning, match="turned off"):
            setup_groups(4)  # every TrialMesh passes through the guard
        assert not jax.config.jax_enable_compilation_cache
        # from here on compiles are cold and write nothing
        jax.jit(lambda x: x * 5 + 2)(jnp.ones((5,))).block_until_ready()
        assert len(os.listdir(tmp_path)) == written
        guard_submesh(jax.devices()[:2])  # idempotent, no second warning
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
