"""Device identity and the persistent compile cache.

One definition of "are we on a TPU": the platform of the default
device, as JAX reports it.  No backend name is special.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

# <repo root>/.jax_cache — a fixed path: the cache key includes the
# directory, so a name that moved between runs would never hit.
COMPILE_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def device_platform() -> str:
    """Platform of the default device: "tpu", "cpu", "gpu"."""
    import jax

    return jax.devices()[0].platform


def tpu_devices_present() -> bool:
    """True when the default device is a TPU chip."""
    return device_platform() == "tpu"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Called first by every entry point (``rs``, ``rs serve``, bench.py,
    chip_smoke.py).  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    uses it and nothing else is set here.  Otherwise the cache lives
    at :data:`COMPILE_CACHE_DIR` and every compile is kept (each chip
    call starts cold, so even sub-second compiles are worth a hit).
    Before jax is imported the setting goes through the environment
    JAX reads at import (so ``rs -h`` stays free of the jax import, and
    child processes share the directory); after, through jax.config.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if "jax" not in sys.modules:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        return COMPILE_CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return COMPILE_CACHE_DIR
