"""Device meshes — the ClusterSpec replacement.

The reference's distributed story is TF ``ClusterSpec`` + NCCL allreduce
(BASELINE.json:5): explicit worker addresses, explicit ring collectives.
TPU-native, the whole thing collapses into a named :class:`jax.sharding.Mesh`
(SURVEY.md §2 "Distributed communication backend"): axes are declared, data
is annotated with `NamedSharding`, and XLA emits the collectives over ICI
(intra-slice) / DCN (across slices).  No communication code in user jobs.

Axis conventions (fixed names so operators, train steps, and kernels agree):

- ``data``  — data parallelism: batch sharded, params replicated (or FSDP).
- ``model`` — tensor parallelism: weight matrices sharded.
- ``seq``   — sequence/context parallelism: ring attention shards tokens.
- ``pipe``  — pipeline parallelism: layer stages.
- ``expert``— expert parallelism for MoE layers.

The reference only exercises ``data`` (SURVEY.md §2 parallelism table); the
other axes exist so the mesh API doesn't preclude them (SURVEY.md §5) and
are exercised by the long-context path (parallel/ring_attention.py).
"""

from __future__ import annotations

import dataclasses
import math
import typing
import weakref

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"
TP_AXIS = "tp"

#: Canonical axis order: DCN-adjacent parallelism first (pipe/data tolerate
#: lower bandwidth), ICI-hungry axes (model/seq/tp) innermost where the
#: device mesh puts physically-adjacent chips (scaling-book mesh recipe).
#: ``fsdp`` (param shards gathered per layer) and ``tp`` (within-layer
#: tensor parallel, the SpecLayout convention of the sharded-serving arc)
#: join the order for the zoo-scale layouts shardcheck analyzes.
AXIS_ORDER = (PIPE_AXIS, DATA_AXIS, FSDP_AXIS, EXPERT_AXIS, SEQ_AXIS,
              MODEL_AXIS, TP_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh: axis name -> size.  Size 1 axes are kept (they make
    shardings explicit and cost nothing)."""

    axes: typing.Mapping[str, int]

    def __post_init__(self):
        unknown = set(self.axes) - set(AXIS_ORDER)
        if unknown:
            raise ValueError(f"unknown mesh axes {unknown}; known: {AXIS_ORDER}")
        for name, size in self.axes.items():
            if size < 1:
                raise ValueError(f"axis {name} must be >=1, got {size}")
        object.__setattr__(self, "axes", dict(self.axes))

    @property
    def num_devices(self) -> int:
        return math.prod(self.axes.values())

    @property
    def axis_names(self) -> typing.Tuple[str, ...]:
        return tuple(a for a in AXIS_ORDER if a in self.axes)

    def build(self, devices: typing.Optional[typing.Sequence] = None):
        """Materialize a ``jax.sharding.Mesh`` over real (or given) devices.

        Device order comes from ``mesh_utils.create_device_mesh``, which
        lays physically-adjacent TPU chips along the innermost axes so
        ``model``/``seq`` collectives ride the shortest ICI hops.
        """
        import jax
        from jax.experimental import mesh_utils

        names = self.axis_names
        shape = tuple(self.axes[a] for a in names)
        if devices is None:
            devices = jax.devices()
        if len(devices) != self.num_devices:
            raise ValueError(
                f"mesh {dict(self.axes)} needs {self.num_devices} devices, "
                f"have {len(devices)}"
            )
        if devices and getattr(devices[0], "platform", None) == "tpu":
            # Physical-topology-aware layout; a failure here is a real
            # configuration error and must stay loud (a silent row-major
            # fallback would quietly cost ICI adjacency).
            dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
        else:
            # CPU/virtual platforms have no topology: row-major reshape.
            import numpy as np

            dev_array = np.asarray(list(devices)).reshape(shape)
        return jax.sharding.Mesh(dev_array, names)


def make_mesh(axes: typing.Mapping[str, int], devices=None):
    """``make_mesh({"data": 8})`` -> Mesh; the one-liner for jobs."""
    return MeshSpec(axes).build(devices)


def abstract_mesh(axes: typing.Mapping[str, int]):
    """A ``jax.sharding.AbstractMesh`` over the declared axes — a mesh
    with SHAPE but no devices, so a CPU-only dev box can declare (and
    statically analyze, via analysis/shardcheck.py) a v5e-8 layout it
    cannot materialize.  ``env.set_mesh(abstract_mesh({"data": 4,
    "model": 2}))`` is the plan-analysis posture; executing a job that
    actually needs devices on an abstract mesh fails at open().
    """
    spec = MeshSpec(axes)  # validates names/sizes against AXIS_ORDER
    from jax.sharding import AbstractMesh

    names = spec.axis_names
    return AbstractMesh(tuple(spec.axes[a] for a in names), names)


def is_abstract_mesh(mesh) -> bool:
    """True for AbstractMesh declarations (shape-only, no devices)."""
    from jax.sharding import AbstractMesh

    return isinstance(mesh, AbstractMesh)


# -- shardings --------------------------------------------------------------

def named_sharding(mesh, *spec):
    """``named_sharding(mesh, "data", None)`` -> NamedSharding(P("data", None))."""
    import jax

    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec))


def batch_sharding(mesh):
    """Shard dim 0 of every leaf across ``data`` (x ``seq`` if present for
    token streams handled elsewhere) — the canonical input-batch placement."""
    return named_sharding(mesh, DATA_AXIS)


def replicated(mesh):
    return named_sharding(mesh)


# Keyed on the mesh object itself via weakref — an id()-keyed dict went
# stale when a mesh was garbage-collected and a NEW mesh reused the same
# id, silently inheriting the old answer and sending shard_batch down
# the wrong single- vs multi-process path.  Entries die with their mesh.
_SPANS_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def spans_processes(mesh) -> bool:
    """True when the mesh's devices live in more than one process — the
    multi-host case where each process holds only its local batch shard.
    Cached per mesh: shard_batch calls this per micro-batch, and walking
    every device object each time is O(devices) hot-path Python work for
    an invariant."""
    try:
        hit = _SPANS_CACHE.get(mesh)
    except TypeError:  # unhashable/unweakrefable stand-in (test doubles)
        return len({d.process_index for d in mesh.devices.flat}) > 1
    if hit is None:
        hit = len({d.process_index for d in mesh.devices.flat}) > 1
        try:
            _SPANS_CACHE[mesh] = hit
        except TypeError:  # pragma: no cover - unweakrefable mesh
            pass
    return hit


def shard_batch(mesh, pytree):
    """Place a host batch pytree on the mesh, dim 0 split over ``data``.

    Single process: ``pytree`` is the global batch, one transfer.
    Multi-process mesh: ``pytree`` is THIS PROCESS's shard of the global
    batch (each host ingests its own stream partition — the reference's
    per-TaskManager ingestion, SURVEY.md §3.5); the global jax.Array is
    assembled from the process-local rows without any cross-host copy.
    """
    import jax

    sharding = batch_sharding(mesh)
    if spans_processes(mesh):
        import numpy as np

        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(sharding, np.asarray(x)),
            pytree,
        )
    return jax.device_put(pytree, sharding)


def replicate(mesh, pytree):
    """Replicate params/state across the whole mesh (pure-DP placement).

    Multi-process meshes assemble the global replicated array from each
    process's (identical) host copy; typed PRNG keys are unwrapped to
    their raw data for the placement and rewrapped after.
    """
    import jax

    sharding = replicated(mesh)
    if not spans_processes(mesh):
        return jax.device_put(pytree, sharding)
    import numpy as np

    def place(x):
        if hasattr(x, "dtype") and jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            data = jax.make_array_from_process_local_data(
                sharding, np.asarray(jax.random.key_data(x))
            )
            return jax.random.wrap_key_data(data, impl=jax.random.key_impl(x))
        return jax.make_array_from_process_local_data(sharding, np.asarray(x))

    return jax.tree.map(place, pytree)
