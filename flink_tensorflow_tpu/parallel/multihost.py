"""Multi-host mesh formation — the JobManager/TaskManager cluster analogue.

The reference scales out via Flink's cluster (JobManager schedules subtasks
onto TaskManagers; TF ClusterSpec names workers for NCCL).  TPU-native
multi-host (SURVEY.md §7 step 8): every host runs the SAME job binary; the
JAX distributed runtime (coordinator + heartbeats) replaces the
JobManager's membership view, and the global mesh spans all hosts' chips —
collectives ride ICI within a slice and DCN across slices.

Caveat documented in SURVEY.md §5: XLA meshes cannot shrink live.  On
worker loss the supervisor restarts the cohort from the last snapshot and
re-forms the mesh (restart-from-checkpoint recovery, like Flink's region
failover, not live elasticity).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import typing

from flink_tensorflow_tpu.parallel.mesh import MeshSpec

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class HostTopology:
    """This host's view of the cohort after initialization."""

    process_id: int
    num_processes: int
    local_devices: int
    global_devices: int


def initialize(
    coordinator_address: typing.Optional[str] = None,
    num_processes: typing.Optional[int] = None,
    process_id: typing.Optional[int] = None,
) -> HostTopology:
    """Join the distributed cohort (idempotent; no-op for single host).

    Arguments default from the standard env vars the launcher sets
    (``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``);
    TPU pod slices auto-discover all three from the TPU metadata server.

    On the CPU backend (tests / MiniCluster-style local cohorts,
    SURVEY.md §4) cross-process collectives need an explicit transport:
    gloo is selected automatically **when the platform is pinned to CPU**
    (``JAX_PLATFORMS=cpu`` or ``jax.config.update("jax_platforms", "cpu")``
    — use ``utils.platform.force_cpu()``).  When jax is left to
    auto-detect, the backend cannot be known before ``jax.distributed``
    initializes, so no transport is forced — pin the platform explicitly
    for local cohorts.  TPU cohorts use ICI/DCN natively.
    """
    import jax

    coordinator_address = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    num_processes = num_processes or _env_int("JAX_NUM_PROCESSES")
    process_id = process_id if process_id is not None else _env_int("JAX_PROCESS_ID")

    if not jax.distributed.is_initialized() and (
            coordinator_address is not None or num_processes not in (None, 1)):
        # jax.config.jax_platforms reads JAX_PLATFORMS at import and is
        # what force_cpu() updates afterwards — the one place to look.
        platforms = jax.config.jax_platforms or ""
        if (num_processes or 1) > 1 and "cpu" in platforms.split(","):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        logger.info(
            "joined cohort: process %s/%s via %s",
            jax.process_index(), jax.process_count(), coordinator_address,
        )
    return HostTopology(
        process_id=jax.process_index(),
        num_processes=jax.process_count(),
        local_devices=len(jax.local_devices()),
        global_devices=len(jax.devices()),
    )


def _env_int(name: str) -> typing.Optional[int]:
    v = os.environ.get(name)
    return int(v) if v is not None else None


def hybrid_device_array(
    spec: MeshSpec, devices: typing.Sequence, *, dcn_axis: str = "pipe"
):
    """Physical device layout for :func:`global_mesh` — split out so the
    multi-slice branch is unit-testable with stub devices carrying
    ``slice_index``/``process_index`` (real multi-slice hardware is not
    available in CI).  Returns the ``[axis...]``-shaped device ndarray.
    """
    from jax.experimental import mesh_utils

    names = spec.axis_names
    shape = tuple(spec.axes[a] for a in names)
    if spec.num_devices != len(devices):
        raise ValueError(
            f"mesh {dict(spec.axes)} needs {spec.num_devices} devices, "
            f"cohort has {len(devices)}"
        )
    num_slices = max((getattr(d, "slice_index", 0) for d in devices), default=0) + 1
    if num_slices > 1:
        dcn = dcn_axis if dcn_axis in names else names[0]
        if spec.axes[dcn] % num_slices != 0:
            raise ValueError(
                f"DCN axis {dcn!r} has size {spec.axes[dcn]} which does not "
                f"divide over {num_slices} slices"
            )
        # The DCN axis spans the slices; any remaining extent of that
        # axis (size/num_slices) stays inside each slice over ICI.
        dcn_shape = tuple(num_slices if a == dcn else 1 for a in names)
        ici_shape = tuple(
            spec.axes[a] if a != dcn else spec.axes[a] // num_slices
            for a in names
        )
        return mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices
        )
    return mesh_utils.create_device_mesh(shape, devices=devices)


def global_mesh(axes: typing.Mapping[str, int], *, dcn_axis: str = "pipe"):
    """Build a mesh over ALL hosts' devices.

    When the cohort spans multiple slices (DCN between them), the
    ``dcn_axis`` (default ``pipe``, else the outermost declared axis) is
    laid across slices — the axes that tolerate lower bandwidth go over
    DCN, ICI-hungry axes stay inside a slice (scaling-book recipe;
    ``create_hybrid_device_mesh`` handles the physical layout).
    """
    import jax

    spec = MeshSpec(axes)
    dev_array = hybrid_device_array(spec, jax.devices(), dcn_axis=dcn_axis)
    return jax.sharding.Mesh(dev_array, spec.axis_names)
