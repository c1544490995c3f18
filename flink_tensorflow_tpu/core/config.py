"""Typed job configuration — one validated dataclass per job.

The reference threads job settings through Flink's untyped
``Configuration``/``ParameterTool``/``GlobalJobParameters`` (SURVEY.md §5
"Config / flag system"); SURVEY prescribes the rebuild use "a single typed
config dataclass per job; no global flags".  ``JobConfig`` is that
dataclass: every framework knob (checkpointing, channels, source pacing,
device/mesh selection) lives here, is validated before the executor is
built, and is frozen so a running job's configuration cannot drift.

User-level parameters (the reference's ``GlobalJobParameters`` role —
model paths, thresholds, anything a user function reads at runtime) go in
``user_params``; the old untyped ``env.job_config`` dict is a deprecated
alias for it.
"""

from __future__ import annotations

import dataclasses
import typing

from flink_tensorflow_tpu.metrics.health import HealthConfig
from flink_tensorflow_tpu.metrics.reporters import MetricConfig


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Where and how often aligned snapshots persist."""

    #: Directory for persisted snapshots; None disables persistence.
    dir: typing.Optional[str] = None
    #: Periodic trigger interval; None means manual triggers only.
    interval_s: typing.Optional[float] = None
    #: Count-based triggers: each source injects barrier k after its
    #: k*N-th record — barrier positions become a deterministic function
    #: of the stream, the consistency contract multi-host cohorts need
    #: (every process cuts snapshots at identical stream positions).
    #: Mutually exclusive with interval_s; disables manual triggers.
    every_n_records: typing.Optional[int] = None
    #: Budget for one aligned checkpoint to drain.
    timeout_s: float = 60.0
    #: Keep only the newest N completed checkpoints on disk (Flink's
    #: retained-checkpoints policy); None keeps everything.  Pruning
    #: happens after a NEWER checkpoint is durable (and, on a
    #: DistributedExecutor cohort, after its GLOBAL 2PC commit fired,
    #: so every peer holds the retained ids too).  CAUTION for
    #: hand-rolled CohortSupervisor cohorts (independent per-worker
    #: executors, per-worker dirs, no global gate): each worker prunes
    #: alone, so size retain_last comfortably above the worst-case
    #: cross-worker checkpoint skew (>= 3 recommended) or the
    #: latest-COMMON-checkpoint restore point can be pruned away on the
    #: fastest worker.
    retain_last: typing.Optional[int] = None

    def validate(self) -> None:
        if self.interval_s is not None:
            if self.dir is None:
                raise ValueError("checkpoint.interval_s requires checkpoint.dir")
            if self.interval_s <= 0:
                raise ValueError(f"checkpoint.interval_s must be > 0, got {self.interval_s}")
        if self.every_n_records is not None:
            if self.dir is None:
                raise ValueError("checkpoint.every_n_records requires checkpoint.dir")
            if self.interval_s is not None:
                raise ValueError(
                    "checkpoint.every_n_records and interval_s are mutually "
                    "exclusive (count-based barriers must stay deterministic)"
                )
            if self.every_n_records < 1:
                raise ValueError(
                    f"checkpoint.every_n_records must be >= 1, got {self.every_n_records}"
                )
        if self.timeout_s <= 0:
            raise ValueError(f"checkpoint.timeout_s must be > 0, got {self.timeout_s}")
        if self.retain_last is not None:
            if self.dir is None:
                raise ValueError("checkpoint.retain_last requires checkpoint.dir")
            if self.retain_last < 1:
                raise ValueError(
                    f"checkpoint.retain_last must be >= 1, got {self.retain_last}"
                )


@dataclasses.dataclass(frozen=True)
class JobConfig:
    """All framework-level knobs for one job, validated at ``execute()``.

    Fields mirror what the environment's fluent setters configure; the
    setters are retained as conveniences that rebuild this config via
    ``dataclasses.replace``.
    """

    #: Default operator parallelism (Flink's env-level parallelism).
    parallelism: int = 1
    #: Key-group count (Flink's maxParallelism): the upper bound on keyed
    #: parallelism, fixed for the job's lifetime so keyed state can be
    #: redistributed when a restart changes parallelism.
    max_parallelism: int = 128
    #: Bounded capacity of inter-subtask channels (records).
    channel_capacity: int = 1024
    #: Operator chaining (analysis/chaining.py): fuse forward
    #: same-parallelism neighbors into one subtask thread so records
    #: pass by direct method call instead of a queue hop.  Off is the
    #: ``chaining=off`` comparison mode (one thread + channel per
    #: operator, the pre-chaining layout); per-operator opt-outs are
    #: ``stream.start_new_chain()`` / ``stream.disable_chaining()``.
    chaining: bool = True
    #: Debug-mode concurrency sanitizer (core.sanitizer_rt): instrument
    #: the runtime's locks/condvars (channels, source mailboxes, split
    #: and checkpoint coordinators), record a happens-before graph with
    #: lock-order-inversion + waits-for-deadlock detection, and assert
    #: the barrier protocol invariants (no record past a blocked channel
    #: during alignment, snapshot order == chain stream order, split
    #: assignment frozen during the enumerator-pool snapshot).  Off (the
    #: default) is a zero-cost no-op path — plain threading primitives.
    #: The FLINK_TPU_SANITIZE=1 env var force-enables it without config
    #: changes; FLINK_TPU_SANITIZE_STALL_S adds the stall watchdog.
    sanitize: bool = False
    #: Where the sanitizer's cross-process happens-before event log is
    #: dumped (the ``flink-tpu-sanitize --cohort`` input); a cohort
    #: process suffixes ``.proc<k>`` before the extension.  None keeps
    #: the ring in memory only.  FLINK_TPU_SANITIZE_LOG overrides.
    sanitize_log_path: typing.Optional[str] = None
    #: End-to-end span tracing (flink_tensorflow_tpu.tracing): thread a
    #: per-record/per-batch trace context from source admission through
    #: chains, channels, h2d/compute/d2h, checkpoint alignment, split
    #: lifecycle, and remote edges; spans land in per-thread ring
    #: buffers and export as Chrome Trace Event JSON (Perfetto).  Off
    #: (the default) is a zero-cost no-op path — one is-None test per
    #: hook site, zero per-record allocation.  FLINK_TPU_TRACE=1
    #: force-enables without config changes.
    trace: bool = False
    #: Where the Chrome trace JSON is written when the job finishes (or
    #: fails); None keeps spans in memory only (reachable through the
    #: executor's tracer — the flink-tpu-trace CLI path).  The
    #: FLINK_TPU_TRACE_PATH env var overrides.
    trace_path: typing.Optional[str] = None
    #: Head-based sampling: admit every round(1/rate)-th record per
    #: source subtask into the trace (deterministic given the metrics
    #: seed — see tracing.Tracer).  1.0 traces everything.
    trace_sample_rate: float = 1.0
    #: Flight recorder (tracing/flight.py): an always-on bounded ring of
    #: recent control-rate events (job/subtask lifecycle, barrier
    #: injections, snapshots, per-report metric deltas) — independent of
    #: ``trace`` — dumped to ``flight_path`` on crash, sanitizer
    #: violation, SIGTERM/SIGINT, or ``JobHandle.cancel`` and replayable
    #: via ``flink-tpu-trace --from-flight-dump``.  False is the
    #: zero-alloc off path (FLINK_TPU_FLIGHT overrides either way).
    flight_recorder: bool = True
    #: Where flight dumps land; None records in memory only (no disk
    #: write even on crash).  FLINK_TPU_FLIGHT_PATH overrides.
    flight_path: typing.Optional[str] = None
    #: Compact dtype for float tensors on the record plane's wire:
    #: "bf16"/"f16" halve the bytes of every f32 field in remote TCP
    #: frames and shuffle payloads (tensors/serde.py restores at decode);
    #: "int8" is absmax-quantized.  None/"f32" ships full width.  Model
    #: runners always ship their inputs in the declared dtype.
    #: FLINK_TPU_WIRE_DTYPE overrides.
    #: Accuracy caveats documented in tensors/serde.py.
    wire_dtype: typing.Optional[str] = None
    #: Frame coalescing on the remote record plane (core/shuffle.py,
    #: io/remote.py — Flink's network-buffer model): records buffer
    #: until this many estimated payload bytes, then flush as ONE
    #: multi-record frame.  0 disables coalescing (frame per record,
    #: the pre-coalescing wire).  FLINK_TPU_WIRE_FLUSH_BYTES overrides.
    wire_flush_bytes: int = 64 * 1024
    #: Flink-style buffer timeout: a partially filled buffer flushes
    #: this many milliseconds after its FIRST record, bounding the
    #: latency coalescing may add.  Barriers, watermarks and
    #: end-of-partition always force an immediate flush (alignment and
    #: exactly-once semantics never wait on the timeout).  0 flushes
    #: every record (Flink's bufferTimeout=0).  FLINK_TPU_WIRE_FLUSH_MS
    #: overrides.  Latency-sensitive open-loop jobs should keep this
    #: small — see the `remote-edge-buffer-timeout` lint.
    wire_flush_ms: float = 5.0
    #: Same-host shuffle edges ride a shared-memory ring
    #: (native/ring.ShmByteRing over tmpfs) instead of loopback TCP —
    #: the kernel network stack is skipped entirely; the TCP connection
    #: remains as handshake/wakeup/liveness channel.  Cross-host edges
    #: are unaffected.  FLINK_TPU_SHM=0/1 overrides.
    shm_channels: bool = True
    #: Credit-based flow control on the cross-process record plane
    #: (Flink's network-stack model): receivers grant per-edge credits
    #: (buffer quanta derived from ``channel_capacity``) in the shuffle
    #: handshake and replenish them as the downstream gate drains;
    #: senders spend one credit per flushed data frame and park when
    #: credit hits zero — a stalled consumer throttles the producer
    #: chain within one credit window instead of ballooning reactor
    #: send queues and kernel TCP buffers.  Barriers, watermarks,
    #: end-of-partition and 2PC/control announcements BYPASS credit so
    #: a zero-credit edge can never wedge checkpoint alignment (the
    #: checkpoint deadline-abort sweeper remains the backstop).
    #: FLINK_TPU_FLOW_CONTROL=0/1 overrides.  Disabling this on a
    #: checkpointed multi-process plan behind an open-loop source
    #: trips the `flow-control` lint.
    flow_control: bool = True
    #: Deterministic fault-injection plan (core.faults.FaultPlan, a spec
    #: string, or a sequence of FaultSpec/spec strings): scheduled
    #: kill/stall/sever/blackhole/delay/store_fail faults pinned to
    #: (restart epoch, stream position) — the chaos plane that exercises
    #: the restart/reconnect/abort machinery.  None (the default) keeps
    #: the production zero-cost path; FLINK_TPU_FAULTS overrides.
    faults: typing.Optional[typing.Any] = None
    #: Sleep between source emissions — test/backpressure pacing.
    source_throttle_s: float = 0.0
    checkpoint: CheckpointConfig = dataclasses.field(default_factory=CheckpointConfig)
    #: Assigns a jax device per (task_name, subtask_index) — operator DP.
    device_provider: typing.Optional[typing.Callable[[str, int], typing.Any]] = None
    #: Shared jax.sharding.Mesh for gang operators (DP/TP training), or a
    #: jax.sharding.AbstractMesh (parallel.mesh.abstract_mesh) declaring a
    #: target layout for PLAN-TIME analysis only: a CPU-only dev box can
    #: declare a v5e-8 mesh and run analysis/shardcheck.py against it,
    #: but a job whose operators need devices cannot open on one.
    mesh: typing.Optional[typing.Any] = None
    #: Per-device HBM ceiling (bytes) for the static memory budget
    #: (analysis/shardcheck.py): params + optimizer state + KV pool +
    #: peak activation liveness, summed per device under the declared
    #: mesh, must fit or the plan fails validation with ERROR
    #: provenance.  None disables the budget gate.  The admission gate
    #: of the paged-KV-economy arc: v5e = 16 GiB/chip, v5p = 95 GiB.
    hbm_budget_bytes: typing.Optional[int] = None
    #: User-level parameters readable from RuntimeContext (the reference's
    #: GlobalJobParameters role).  Not interpreted by the framework.
    user_params: typing.Mapping[str, typing.Any] = dataclasses.field(default_factory=dict)
    #: Cohort membership for the cross-process record plane (subtasks
    #: placed over processes, keyed/rebalance edges spanning them through
    #: the shuffle).  None = single-process execution.  See
    #: core.distributed.DistributedConfig.
    distributed: typing.Optional[typing.Any] = None
    #: Observability plane: reporter interval + sinks + registry seed
    #: (metrics.reporters.MetricConfig).  The default publishes nothing
    #: while the job runs — no reporter thread, metrics only in the
    #: JobResult.
    metrics: MetricConfig = dataclasses.field(default_factory=MetricConfig)
    #: Health evaluation plane (metrics.health.HealthConfig): SLO rules
    #: evaluated over the (merged cohort) metric snapshot each telemetry
    #: interval on process 0, published back as ``health.*`` gauges,
    #: flight events, and trace instants.  With
    #: ``health.autoscale`` (core.autoscale.AutoscaleConfig) a sustained
    #: BREACH of a scaling rule additionally drives the
    #: checkpoint->stop->respawn-at-new-parallelism->rescale-restore
    #: loop.  None (the default) starts no evaluator thread.
    health: typing.Optional[HealthConfig] = None
    #: Roofline attribution plane (metrics.roofline.RooflineConfig):
    #: declares the DeviceSpec peak and drift tolerances; the captured
    #: plan's CostTable (analysis.costmodel) is priced automatically at
    #: execute() when ``roofline.cost_table`` is None.  Runners join
    #: measured step times against it and publish per-operator
    #: ``roofline.*`` gauges + compile events.  None (the default) costs
    #: nothing at runtime.
    roofline: typing.Optional[typing.Any] = None

    def validate(self) -> "JobConfig":
        if self.parallelism < 1:
            raise ValueError(f"parallelism must be >= 1, got {self.parallelism}")
        if self.max_parallelism < 1:
            raise ValueError(
                f"max_parallelism must be >= 1, got {self.max_parallelism}"
            )
        if self.channel_capacity < 1:
            raise ValueError(
                f"channel_capacity must be >= 1, got {self.channel_capacity}"
            )
        if self.source_throttle_s < 0:
            raise ValueError(
                f"source_throttle_s must be >= 0, got {self.source_throttle_s}"
            )
        if self.wire_flush_bytes < 0:
            raise ValueError(
                f"wire_flush_bytes must be >= 0, got {self.wire_flush_bytes}"
            )
        if self.wire_flush_ms < 0:
            raise ValueError(
                f"wire_flush_ms must be >= 0, got {self.wire_flush_ms}"
            )
        if self.wire_dtype is not None:
            from flink_tensorflow_tpu.tensors.serde import WIRE_DTYPES

            if self.wire_dtype not in WIRE_DTYPES:
                raise ValueError(
                    f"wire_dtype must be one of {WIRE_DTYPES} or None, "
                    f"got {self.wire_dtype!r}"
                )
        if self.faults is not None:
            from flink_tensorflow_tpu.core.faults import FaultPlan

            FaultPlan.resolve(self.faults)  # raises on malformed specs
        if not (0.0 < self.trace_sample_rate <= 1.0):
            raise ValueError(
                f"trace_sample_rate must be in (0, 1], got {self.trace_sample_rate}"
            )
        if self.device_provider is not None and not callable(self.device_provider):
            raise ValueError("device_provider must be callable (task, idx) -> device")
        if self.mesh is not None:
            # NOTE: hasattr(AbstractMesh, "devices") RAISES (jax makes the
            # unimplemented property loud), so probe shape/axis_names —
            # present on both Mesh and AbstractMesh — instead.
            if not (hasattr(self.mesh, "shape")
                    and hasattr(self.mesh, "axis_names")):
                raise ValueError(
                    "mesh must be a jax.sharding.Mesh (or AbstractMesh for "
                    f"plan-time analysis), got {type(self.mesh).__name__}"
                )
        if self.hbm_budget_bytes is not None and self.hbm_budget_bytes < 1:
            raise ValueError(
                f"hbm_budget_bytes must be >= 1, got {self.hbm_budget_bytes}"
            )
        if self.distributed is not None:
            self.distributed.validate()
            if self.checkpoint.interval_s is not None:
                raise ValueError(
                    "distributed jobs checkpoint with count-based triggers "
                    "(checkpoint.every_n_records), not interval_s — barrier "
                    "positions must be deterministic across the cohort"
                )
        self.metrics.validate()
        self.checkpoint.validate()
        if self.health is not None:
            self.health.validate()
        if self.roofline is not None:
            self.roofline.validate()
        return self
