"""Test harness config: the CPU backend with 8 virtual devices.

Multi-chip sharding is validated on virtual CPU devices
(``--xla_force_host_platform_device_count``); the sharded code paths are
identical on a chip mesh.  Must run before jax initialises a backend.
The chip itself is exercised by ``chip_smoke.py`` through the chip tool,
and ``tests/test_tpu_compile.py`` compiles the kernels for a described
v5e.

The persistent compilation cache is off: a CPU test run writes nothing
into the checkout (entry points that call
``utils.backend.configure_compile_cache`` still only set its directory).
Child processes inherit all of this through the environment.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# An externally supplied device count (a wider-mesh run) wins over the
# 8-device default.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
