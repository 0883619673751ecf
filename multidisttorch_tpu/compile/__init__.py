"""The compile subsystem: kill the compile tax.

Every new shape bucket pays a full ``lower→compile`` on first sight.
jax's plain persistent cache carries compiles from one process to the
next (``utils/compile_cache.enable_compile_cache``, on from every entry
point); the layers here work inside a process and around that cache:

- :mod:`~multidisttorch_tpu.compile.registry` +
  :mod:`~multidisttorch_tpu.compile.programs` — a process-lifetime
  **executable registry** keyed by the program vocabulary (shape
  bucket + baked scalar hypers + submesh devices). One compile per
  program, ever; coalesced; timed; shared with the cost books.
- :mod:`~multidisttorch_tpu.compile.farm` — the **background AOT
  precompile farm**: ``run_hpo(precompile=True)`` (or
  ``MDT_PRECOMPILE=1``) walks the sweep's pending configs at entry and
  compiles every bucket's programs on worker threads, so trial
  admission never blocks the host loop on XLA.
- :mod:`~multidisttorch_tpu.compile.cache` — the **quarantined
  persistent cache**, a CPU-world drill: CRC32 sidecars + a subprocess
  canary-execute protocol in front of jax's on-disk executable cache,
  enabling it only in processes that declared themselves sacrificial.
- :mod:`~multidisttorch_tpu.compile.coldstart` — the **cold-start
  books' drill** (``python -m multidisttorch_tpu.compile.coldstart``,
  CPU only): cold vs precompiled vs cache-warm admission with a
  bit-parity gate.

See docs/COMPILE.md for the safety model and protocols.
"""

from multidisttorch_tpu.compile import programs  # noqa: F401
from multidisttorch_tpu.compile.cache import (  # noqa: F401
    cache_probe,
    canary_quarantine,
    enable_quarantined_cache,
    scan_cache,
    seal_cache,
)
from multidisttorch_tpu.compile.farm import PrecompilePool  # noqa: F401
from multidisttorch_tpu.compile.registry import (  # noqa: F401
    ExecutableRegistry,
    get_executable_registry,
)
