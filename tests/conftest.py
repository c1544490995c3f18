"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's MiniCluster strategy (SURVEY.md §4): Flink projects
test "multi-node" in one JVM; we test multi-chip sharding on virtual CPU
devices.  Env vars must be set before jax initializes its backends, hence
at conftest import time.
"""

# Force CPU even when the environment preselects a TPU platform: tests
# need the virtual 8-device mesh, and must not hold (or depend on) the
# machine's chip.
from flink_tensorflow_tpu.utils.platform import force_cpu

# force_cpu REPLACES any preset device-count flag (a stray
# XLA_FLAGS=--xla_force_host_platform_device_count=4 in the environment
# would otherwise silently shrink the suite's required 8-device mesh and
# fail tests confusingly).
force_cpu(8)

import pytest  # noqa: E402


@pytest.fixture
def env():
    from flink_tensorflow_tpu import StreamExecutionEnvironment

    return StreamExecutionEnvironment(parallelism=2)
