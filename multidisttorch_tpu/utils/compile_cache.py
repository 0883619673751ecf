"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``initialize_runtime``, ``run_hpo``,
the sweep service, ``chip_smoke.py``): the cache is JAX's own
persistent compilation cache, placed from outside by
``JAX_COMPILATION_CACHE_DIR`` and otherwise kept at one fixed path,
``.jax_cache`` at the checkout root. The directory is part of the
cache key, so a directory that moves — a temp dir, a pid, a timestamp —
never hits; nothing on the training path may hold the cache in one.
"""

from __future__ import annotations

import os


def default_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache`` at the
    checkout root (the parent of the ``multidisttorch_tpu`` package) —
    one shared location regardless of the caller's cwd."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return the directory in effect.

    A directory already configured stands — jax reads
    ``JAX_COMPILATION_CACHE_DIR`` into its config at import, so where
    that is set no directory is set in code. Only when none is
    configured does the cache go to ``<checkout>/.jax_cache``. Every
    compile qualifies: jax's default thresholds (1 s of compile time)
    would skip the VAE's programs, which compile in less.
    """
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def submesh_defeats_cache(devices, world_size: int) -> bool:
    """Whether programs over ``devices`` cannot be trusted to the
    persistent cache: more than one TPU chip, fewer than the whole
    world. Measured on a four-chip v5e host (libtpu 0.0.34, PR 21): an
    executable with collectives over chips [2, 3], written to the cache
    by one process and deserialized by the next, dies at its first run
    with ``FAILED_PRECONDITION: The program continuator has halted
    unexpectedly`` — every time, whatever ran before it. The same
    program over [0, 1] or over all four chips, and single-chip programs
    on any chip, deserialize and run. The fault is below jax (it hands
    the deserializer the right devices), so the guard is conservative:
    any multi-chip strict subset."""
    return devices[0].platform == "tpu" and 1 < len(devices) < world_size


def guard_submesh(devices) -> None:
    """Called wherever a trial submesh is made (``TrialMesh``). If the
    submesh defeats the cache (:func:`submesh_defeats_cache`), turn the
    persistent cache off for the rest of this process: jax's switch is
    process-wide and programs compile lazily, so there is no narrower
    place to stand. Programs already compiled are unaffected; later ones
    compile cold and write nothing."""
    import warnings

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not jax.config.jax_enable_compilation_cache:
        return
    world = len(jax.devices(devices[0].platform))
    if not submesh_defeats_cache(devices, world):
        return
    jax.config.update("jax_enable_compilation_cache", False)
    # jax decides once per process whether the cache is in use; make it
    # decide again.
    compilation_cache.reset_cache()
    warnings.warn(
        f"persistent compile cache turned off for this process: a "
        f"{len(devices)}-chip submesh of a {world}-chip TPU world was "
        "carved, and cached executables with collectives over such a "
        "submesh fail when deserialized (utils/compile_cache.py)",
        RuntimeWarning,
        stacklevel=4,  # past TrialMesh's __post_init__ and __init__
    )
