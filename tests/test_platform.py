"""utils/platform.enable_compile_cache: the one place a compile cache is
set, and the rule for where it lives."""

import os
import subprocess
import sys
import tempfile

import jax

from flink_tensorflow_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    """Run enable_compile_cache() against a recording jax.config.update
    (the suite's real config must not start caching mid-session)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    returned = platform.enable_compile_cache()
    return returned, dict(calls)


def test_env_var_set_means_no_directory_set_in_code(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    returned, updates = _recorded_updates(monkeypatch)
    assert returned == "/some/dir"
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 1.0


def test_env_var_unset_means_checkout_dot_jax_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, updates = _recorded_updates(monkeypatch)
    second, _ = _recorded_updates(monkeypatch)
    # Derived from the package's location: stable across calls and
    # processes (the path is part of the cache key), never a temp dir.
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == first
    assert not first.startswith(tempfile.gettempdir() + os.sep)


def test_env_var_directory_receives_the_entries(tmp_path):
    """End to end in a fresh process: with the variable exported, the
    entries land in that directory and the checkout's is not created
    for them."""
    code = (
        "from flink_tensorflow_tpu.utils.platform import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "import jax, jax.numpy as jnp\n"
        "assert jax.config.jax_compilation_cache_dir == %r\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((8, 8))).block_until_ready()\n"
    ) % str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert os.listdir(tmp_path), "no cache entry written under the variable's dir"
