"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip shardings compile and
execute without TPU hardware (the same checks run on real chips as
``chip_smoke.py``'s mesh phase, via ``__graft_entry__.multichip_checks``).
The platform must be pinned before JAX initializes a backend.
"""

import os
import sys

# hard override: tests always run on the virtual CPU mesh, whatever the
# ambient environment selects (the chip is reached only through
# chip_smoke.py / the benches, one process per chip)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# repo root on sys.path so `import platform_aware_scheduling_tpu` works
# without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
