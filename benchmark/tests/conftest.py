"""``pytest benchmark/tests`` runs by hand, on the CPU: four virtual
devices for the fleet rehearsal, and the checkout root on the path.
Tier-1 collects ``tests/`` only and does not see this directory."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
