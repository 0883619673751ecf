"""Whether this package's Pallas kernels compile or interpret."""

import os


def pallas_interpret() -> bool:
    """Kernels compile through Mosaic unless ``MDT_PALLAS_INTERPRET=1``
    asks for the Pallas interpreter. The CPU test suite asks
    (``tests/conftest.py``); nothing derives it from the backend, so a
    machine whose chip failed to come up gets the lowering's error
    instead of a silent interpreter run under a TPU metric's name."""
    return os.environ.get("MDT_PALLAS_INTERPRET") == "1"
