"""Platform selection shared by every entry point (benchmark, graft
hooks, examples, tests, chip_smoke).

Tests and the CPU children of the cohort launchers need N virtual CPU
devices whatever platform the machine exports; everything else runs on
the platform jax finds.  Both rules, and the compile-cache location,
live here and nowhere else.
"""

from __future__ import annotations

import os


def force_cpu(virtual_devices: int = 8) -> None:
    """Pin jax to the CPU backend with N virtual devices.  Safe to call
    before OR after jax import, but before any backend-touching call."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(f"--xla_force_host_platform_device_count={virtual_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache() -> str:
    """Persistent XLA compile cache — repeat runs skip big compiles.
    Returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads the
    directory from it and none is set here.  Otherwise the cache lives
    in ``<checkout>/.jax_cache``: the path is part of the cache key, so
    it is derived from this package's location and is the same on every
    call."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        cache_dir = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
