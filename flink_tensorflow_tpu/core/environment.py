"""StreamExecutionEnvironment — job construction and execution entry point.

Equivalent of Flink's ``StreamExecutionEnvironment`` (SURVEY.md §3.1: the
user job builds a graph, ``execute()`` ships it to the runtime).  The local
executor replaces the JobManager/TaskManager cluster for one host; the same
graph runs per host in the multi-host deployment with jax.distributed
providing the global device mesh (flink_tensorflow_tpu.parallel.multihost).
"""

from __future__ import annotations

import dataclasses
import time
import typing
import warnings

from flink_tensorflow_tpu.core import functions as fn
from flink_tensorflow_tpu.core.config import JobConfig
from flink_tensorflow_tpu.core.graph import DataflowGraph
from flink_tensorflow_tpu.core.operators import SourceOperator
from flink_tensorflow_tpu.core.runtime import LocalExecutor
from flink_tensorflow_tpu.core.stream import DataStream
from flink_tensorflow_tpu.io.sources import CollectionSource
from flink_tensorflow_tpu.metrics.registry import MetricRegistry


class JobResult:
    def __init__(self, metrics: typing.Dict[str, typing.Any], restarts: int = 0):
        self.metrics = metrics
        self.restarts = restarts


@dataclasses.dataclass(frozen=True)
class RestartStrategy:
    """Flink-style restart strategy (SURVEY.md §5 "Failure detection /
    elastic recovery"): on job failure, rebuild the executor, restore the
    latest snapshot from the checkpoint dir, and replay from the source
    offsets.  Operator/keyed state is exactly-once; sink emissions for
    replayed records are at-least-once (standard non-transactional sinks)
    or exactly-once through a 2PC sink (io.files.ExactlyOnceRecordFileSink).

    The default is Flink's fixed-delay shape (``delay_s`` between
    attempts).  ``backoff_multiplier > 1`` turns it into an exponential
    restart budget — attempt k waits ``delay_s * multiplier**(k-1)``,
    capped at ``max_delay_s`` — so a persistently failing job backs off
    instead of hammering its checkpoint store, and ``jitter`` (a ±
    fraction, deterministic per metrics seed + attempt) decorrelates
    fleets restarting off the same outage.
    """

    max_restarts: int = 3
    delay_s: float = 0.0
    backoff_multiplier: float = 1.0
    max_delay_s: float = 30.0
    jitter: float = 0.0

    def delay_for(self, attempt: int, *, seed: int = 0) -> float:
        """Seconds to wait before restart ``attempt`` (1-based)."""
        delay = self.delay_s * (self.backoff_multiplier ** max(0, attempt - 1))
        delay = min(delay, self.max_delay_s)
        if self.jitter and delay > 0:
            import random

            rng = random.Random((seed or 0) * 1000003 + attempt)
            delay *= 1.0 + self.jitter * (2.0 * rng.random() - 1.0)
        return max(0.0, delay)


class JobHandle:
    """Handle to an asynchronously running job."""

    def __init__(self, executor: LocalExecutor, reporter=None, health=None):
        self.executor = executor
        #: metrics.reporters.ReporterThread when the job runs with a
        #: report interval; None otherwise (no thread ever started).
        self.reporter = reporter
        #: metrics.health.HealthEvaluator when JobConfig.health is set
        #: (process 0 only); None otherwise (no thread ever started).
        self.health = health
        #: tracing.flight.ShutdownFlusher installed by execute_async so
        #: SIGTERM/SIGINT flush the reporter + flight recorder + trace
        #: before the process dies; uninstalled at wait()/cancel().
        self._flusher = None

    def trigger_checkpoint(self, timeout: typing.Optional[float] = None):
        """Run one aligned checkpoint; returns the snapshot mapping.
        ``timeout`` defaults to the job's ``checkpoint.timeout_s``."""
        if timeout is None:
            timeout = self.executor.checkpoint_timeout_s
        return self.executor.coordinator.trigger(timeout=timeout)

    def wait(self, timeout: typing.Optional[float] = None) -> JobResult:
        try:
            self.executor.join(timeout)
        finally:
            # Stop on failure too: the final report + sink close land
            # before the exception surfaces (last observations are often
            # exactly what the failure post-mortem needs).
            if self._flusher is not None:
                self._flusher.uninstall()
            if self.health is not None:
                self.health.stop()
            if self.reporter is not None:
                self.reporter.stop()
            self._export_trace()
        return JobResult(self.executor.metrics.report())

    def _export_trace(self) -> None:
        """Write the span tracer's Chrome trace (success AND failure
        paths — the crash trace is the one that matters).  Best-effort:
        a full disk must not mask the job's own outcome."""
        tracer = getattr(self.executor, "tracer", None)
        path = getattr(self.executor, "trace_path", None)
        if tracer is None or not path:
            return
        try:
            tracer.export(path)
        except OSError:
            import logging

            logging.getLogger(__name__).warning(
                "trace export to %s failed", path, exc_info=True)

    def cancel(self) -> None:
        self.executor.cancel()
        # COMPLETED checkpoints may still be persisting on the async
        # writer; they are valid restore points, so cancel must not
        # abandon them (a caller typically restores right after).
        self.executor.coordinator.wait_for_persistence(60.0)
        if self._flusher is not None:
            self._flusher.uninstall()
        if self.health is not None:
            self.health.stop()
        if self.reporter is not None:
            self.reporter.stop()
        # A cancelled worker keeps its black box, same as a killed one.
        self.executor.flight_dump("cancel")
        self._export_trace()

    @property
    def autoscale_decision(self):
        """The AutoscaleDecision this process made (None without one) —
        a cohort worker checks this after ``wait()`` and exits with the
        rescale code so its supervisor respawns the cohort resized."""
        actuator = getattr(self.executor, "autoscale_actuator", None)
        return actuator.decision if actuator is not None else None

    @property
    def metrics(self) -> MetricRegistry:
        return self.executor.metrics


class StreamExecutionEnvironment:
    def __init__(self, parallelism: int = 1, *, config: typing.Optional[JobConfig] = None):
        self.graph = DataflowGraph()
        if config is not None and parallelism != 1:
            config = dataclasses.replace(config, parallelism=parallelism)
        self.config: JobConfig = config or JobConfig(parallelism=parallelism)
        self.metric_registry = MetricRegistry(seed=self.config.metrics.seed)

    # -- configuration ----------------------------------------------------
    # The typed JobConfig (core.config) is the single source of truth;
    # the fluent setters and legacy attributes below rebuild it via
    # dataclasses.replace so existing jobs keep working unchanged.

    def configure(self, **changes) -> "StreamExecutionEnvironment":
        """Replace JobConfig fields in one call: ``env.configure(channel_capacity=64)``."""
        self.config = dataclasses.replace(self.config, **changes)
        return self

    def set_parallelism(self, parallelism: int) -> "StreamExecutionEnvironment":
        return self.configure(parallelism=parallelism)

    def enable_checkpointing(
        self, checkpoint_dir: str, interval_s: typing.Optional[float] = None,
        *, every_n_records: typing.Optional[int] = None,
        retain_last: typing.Optional[int] = None,
    ) -> "StreamExecutionEnvironment":
        """Persist aligned snapshots under ``checkpoint_dir``; with
        ``interval_s`` they trigger periodically (Flink's checkpoint
        interval), with ``every_n_records`` at deterministic source
        positions (the multi-host mode — see CheckpointCoordinator),
        otherwise only on explicit ``trigger_checkpoint``.
        ``retain_last`` keeps only the newest N checkpoints on disk
        (pruned after a newer one is durable and notified)."""
        return self.configure(
            checkpoint=dataclasses.replace(
                self.config.checkpoint, dir=checkpoint_dir, interval_s=interval_s,
                every_n_records=every_n_records, retain_last=retain_last,
            )
        )

    def set_device_provider(
        self, provider: typing.Callable[[str, int], typing.Any]
    ) -> "StreamExecutionEnvironment":
        """Assign a jax device per (task_name, subtask_index) — operator DP."""
        return self.configure(device_provider=provider)

    def set_mesh(self, mesh) -> "StreamExecutionEnvironment":
        """Share a jax.sharding.Mesh with gang operators (DP/TP training).

        Also accepts a ``jax.sharding.AbstractMesh``
        (``parallel.mesh.abstract_mesh``): a shape-only mesh declaration
        the plan-time sharding analyzer (analysis/shardcheck.py) checks
        layouts and memory budgets against on boxes with no devices.
        """
        return self.configure(mesh=mesh)

    def set_hbm_budget(self, hbm_budget_bytes: typing.Optional[int]) -> "StreamExecutionEnvironment":
        """Declare the per-device HBM ceiling the plan must fit
        (JobConfig.hbm_budget_bytes): shardcheck's static memory budget
        — params + optimizer state + KV pool + peak activation liveness
        per device under the mesh — gates validation against it."""
        return self.configure(hbm_budget_bytes=hbm_budget_bytes)

    # -- legacy attribute surface (delegates to the typed config) ---------
    @property
    def default_parallelism(self) -> int:
        return self.config.parallelism

    @default_parallelism.setter
    def default_parallelism(self, v: int) -> None:
        self.configure(parallelism=v)

    @property
    def channel_capacity(self) -> int:
        return self.config.channel_capacity

    @channel_capacity.setter
    def channel_capacity(self, v: int) -> None:
        self.configure(channel_capacity=v)

    @property
    def source_throttle_s(self) -> float:
        return self.config.source_throttle_s

    @source_throttle_s.setter
    def source_throttle_s(self, v: float) -> None:
        self.configure(source_throttle_s=v)

    @property
    def checkpoint_dir(self) -> typing.Optional[str]:
        return self.config.checkpoint.dir

    @checkpoint_dir.setter
    def checkpoint_dir(self, v: typing.Optional[str]) -> None:
        self.configure(checkpoint=dataclasses.replace(self.config.checkpoint, dir=v))

    @property
    def checkpoint_interval_s(self) -> typing.Optional[float]:
        return self.config.checkpoint.interval_s

    @checkpoint_interval_s.setter
    def checkpoint_interval_s(self, v: typing.Optional[float]) -> None:
        self.configure(
            checkpoint=dataclasses.replace(self.config.checkpoint, interval_s=v)
        )

    @property
    def device_provider(self):
        return self.config.device_provider

    @device_provider.setter
    def device_provider(self, v) -> None:
        self.configure(device_provider=v)

    @property
    def mesh(self):
        return self.config.mesh

    @mesh.setter
    def mesh(self, v) -> None:
        self.configure(mesh=v)

    @property
    def job_config(self) -> typing.Dict[str, typing.Any]:
        """DEPRECATED — untyped user-parameter dict; use
        ``configure(user_params={...})`` (typed JobConfig) instead."""
        warnings.warn(
            "env.job_config is deprecated; use env.configure(user_params=...) "
            "— framework knobs belong in the typed JobConfig",
            DeprecationWarning,
            stacklevel=2,
        )
        params = self.config.user_params
        if not isinstance(params, dict):
            params = dict(params)
            self.configure(user_params=params)
        return params

    @job_config.setter
    def job_config(self, v: typing.Mapping[str, typing.Any]) -> None:
        warnings.warn(
            "env.job_config is deprecated; use env.configure(user_params=...)",
            DeprecationWarning,
            stacklevel=2,
        )
        self.configure(user_params=dict(v))

    # -- sources ----------------------------------------------------------
    def from_collection(
        self, data: typing.Sequence[typing.Any], *, name="collection",
        parallelism: int = 1, schema=None,
    ) -> DataStream:
        return self.from_source(CollectionSource(data), name=name,
                                parallelism=parallelism, schema=schema)

    def from_source(
        self, source, *, name="source", parallelism: int = 1,
        schema=None,
    ) -> DataStream:
        """``source`` is either a legacy :class:`SourceFunction` (fixed
        per-subtask stride) or a :class:`~flink_tensorflow_tpu.sources.
        SplitSource` (FLIP-27-style dynamic split assignment — hosted by
        the mailbox-driven split-source loop).  ``schema`` (a
        RecordSchema) declares the records this source emits — plan-time
        only: the analyzer propagates it downstream and validates
        operator contracts against it before execution; a SplitSource
        may also declare its own ``schema`` attribute (the argument
        wins)."""
        from flink_tensorflow_tpu.sources.api import SplitSource

        if isinstance(source, SplitSource):
            from flink_tensorflow_tpu.sources.operator import SplitSourceOperator

            factory = lambda: SplitSourceOperator(name, source)  # noqa: E731
            schema = schema if schema is not None else source.schema
        elif isinstance(source, fn.SourceFunction):
            factory = lambda: SourceOperator(name, source)  # noqa: E731
        else:
            raise TypeError(
                f"from_source expects a SourceFunction or SplitSource, "
                f"got {type(source).__name__}"
            )
        t = self.graph.add(
            name,
            factory,
            parallelism,
            is_source=True,
            declared_schema=schema,
        )
        return DataStream(self, t)

    def set_distributed(self, distributed) -> "StreamExecutionEnvironment":
        """Join a process cohort: subtasks spread over the cohort and
        keyed/rebalance edges span processes through the record plane
        (core.distributed.DistributedConfig)."""
        return self.configure(distributed=distributed)

    # -- plan validation ---------------------------------------------------
    def validate_plan(self, *, raise_on_error: bool = True):
        """Run the plan-time analyzer over this environment's graph.

        Returns the diagnostics (most severe first).  With
        ``raise_on_error`` (the default), ERROR diagnostics raise
        :class:`~flink_tensorflow_tpu.analysis.PlanValidationError`
        before any executor is built — the ``execute(validate=True)``
        gate.
        """
        from flink_tensorflow_tpu.analysis import (
            PlanValidationError,
            analyze,
            has_errors,
        )

        diagnostics = analyze(self.graph, config=self.config)
        if raise_on_error and has_errors(diagnostics):
            raise PlanValidationError(diagnostics)
        return diagnostics

    # -- execution ---------------------------------------------------------
    def _resolve_checkpoint_location(self, d: typing.Optional[str]) -> typing.Optional[str]:
        """Distributed jobs shard one (possibly shared) checkpoint dir
        per process — see DistributedConfig.process_checkpoint_dir."""
        if d is not None and self.config.distributed is not None:
            return self.config.distributed.process_checkpoint_dir(d)
        return d

    def _make_executor(self, restart_epoch: int = 0) -> LocalExecutor:
        cfg = self.config.validate()
        # configure(metrics=...) may have changed the seed after the
        # registry was created; histograms pick it up at first use.
        self.metric_registry.seed = cfg.metrics.seed
        roofline = cfg.roofline
        if roofline is not None and roofline.cost_table is None:
            # Price the captured plan once here so every worker (local
            # subtask or spawned process) joins against the same table.
            # Fail-soft: an unpriceable plan still runs, the plane just
            # publishes busy/compile gauges without MFU attribution.
            import dataclasses as _dc

            try:
                from flink_tensorflow_tpu.analysis.costmodel import (
                    cost_table_for_env,
                )

                roofline = _dc.replace(
                    roofline, cost_table=cost_table_for_env(self))
            except Exception:  # noqa: BLE001 — analysis never blocks execution
                pass
        common = dict(
            channel_capacity=cfg.channel_capacity,
            metric_registry=self.metric_registry,
            device_provider=cfg.device_provider,
            mesh=cfg.mesh,
            job_config=dict(cfg.user_params),
            source_throttle_s=cfg.source_throttle_s,
            checkpoint_dir=self._resolve_checkpoint_location(cfg.checkpoint.dir),
            checkpoint_every_n=cfg.checkpoint.every_n_records,
            checkpoint_timeout_s=cfg.checkpoint.timeout_s,
            checkpoint_retain_last=cfg.checkpoint.retain_last,
            max_parallelism=cfg.max_parallelism,
            chaining=cfg.chaining,
            sanitize=cfg.sanitize,
            sanitize_log_path=cfg.sanitize_log_path,
            device_resident=cfg.device_resident,
            wire_dtype=cfg.wire_dtype,
            wire_flush_bytes=cfg.wire_flush_bytes,
            wire_flush_ms=cfg.wire_flush_ms,
            shm_channels=cfg.shm_channels,
            flow_control=cfg.flow_control,
            trace=cfg.trace,
            trace_path=cfg.trace_path,
            trace_sample_rate=cfg.trace_sample_rate,
            flight_recorder=cfg.flight_recorder,
            flight_path=cfg.flight_path,
            faults=cfg.faults,
            restart_epoch=restart_epoch,
            roofline=roofline,
        )
        if cfg.distributed is not None:
            from flink_tensorflow_tpu.core.distributed import DistributedExecutor

            return DistributedExecutor(
                self.graph, distributed=cfg.distributed, **common
            )
        return LocalExecutor(self.graph, **common)

    def execute(
        self,
        job_name: str = "job",
        *,
        timeout: typing.Optional[float] = None,
        restore_from: typing.Optional[str] = None,
        restore_checkpoint_id: typing.Optional[int] = None,
        restart_strategy: typing.Optional[RestartStrategy] = None,
        validate: bool = False,
        report_interval_s: typing.Optional[float] = None,
    ) -> JobResult:
        """Run the job to completion on the local executor.

        ``validate=True`` runs the plan-time analyzer first and raises
        ``PlanValidationError`` on ERROR diagnostics — bad plans fail
        before touching a device (see flink_tensorflow_tpu.analysis).

        ``report_interval_s`` publishes metrics while the job runs (a
        daemon reporter thread feeding the sinks configured in
        ``JobConfig.metrics`` — console by default; see
        flink_tensorflow_tpu.metrics.reporters).  ``None`` (the default,
        unless ``config.metrics.report_interval_s`` is set) starts no
        thread at all.

        With a ``restart_strategy`` (requires ``enable_checkpointing``),
        failures restart the job from the latest persisted snapshot — the
        supervisor role Flink's JobManager plays (SURVEY.md §5).
        """
        from flink_tensorflow_tpu.core.runtime import JobFailure, JobTimeout

        if validate:
            self.validate_plan()
        if restart_strategy is None:
            handle = self.execute_async(
                job_name, restore_from=restore_from,
                restore_checkpoint_id=restore_checkpoint_id,
                report_interval_s=report_interval_s,
            )
            return handle.wait(timeout)

        if self.checkpoint_dir is None:
            raise ValueError("restart_strategy requires enable_checkpointing(dir)")
        if self.config.distributed is not None:
            # Each process would restore its OWN shard's latest id with
            # no cohort agreement: one process ahead of another diverges
            # the stream positions permanently (sources replay from the
            # ahead process's offsets; the behind process's keyed state
            # misses those records forever).
            raise ValueError(
                "restart_strategy is per-process and cannot agree on a "
                "cohort-wide restore point — supervise distributed jobs "
                "with parallel.CohortSupervisor and restore from "
                "parallel.latest_common_checkpoint(...) (see "
                "examples/multihost_dp_train.py)"
            )
        deadline = None if timeout is None else time.monotonic() + timeout
        attempt = 0
        restore = restore_from
        restore_id = restore_checkpoint_id
        # Recovery observability (carried by cohort metric pushes like
        # every other scope): restart count + the wall time each
        # recovery took (failure detected -> restored job running).
        recovery = self.metric_registry.group("recovery")
        restarts_total = recovery.counter("restarts_total")
        recovery_timer = recovery.timer("recovery_duration_s")
        t_fail: typing.Optional[float] = None
        while True:
            remaining = None if deadline is None else max(0.1, deadline - time.monotonic())
            try:
                handle = self.execute_async(job_name, restore_from=restore,
                                            restore_checkpoint_id=restore_id,
                                            report_interval_s=report_interval_s,
                                            restart_epoch=attempt)
                if t_fail is not None:
                    # The restored job's subtasks are running again:
                    # failure -> recovered, the headline recovery metric.
                    recovery_timer.update(time.monotonic() - t_fail)
                    t_fail = None
                result = handle.wait(remaining)
                result.restarts = attempt
                return result
            except JobTimeout:
                raise  # the job is slow, not broken — replaying won't help
            except JobFailure:
                t_fail = time.monotonic()
                attempt += 1
                if attempt > restart_strategy.max_restarts:
                    raise
                restarts_total.inc()
                delay = restart_strategy.delay_for(
                    attempt, seed=self.config.metrics.seed)
                if delay:
                    time.sleep(delay)
                # Resume from the newest completed checkpoint; before the
                # first one lands, fall back to the CALLER'S restore point
                # (or a clean replay when none was given).
                from flink_tensorflow_tpu.checkpoint.store import latest_checkpoint_id

                new_id = latest_checkpoint_id(
                    self._resolve_checkpoint_location(self.checkpoint_dir))
                if new_id is not None:
                    restore, restore_id = self.checkpoint_dir, new_id
                else:
                    restore, restore_id = restore_from, restore_checkpoint_id

    def execute_async(
        self,
        job_name: str = "job",
        *,
        restore_from: typing.Optional[str] = None,
        restore_checkpoint_id: typing.Optional[int] = None,
        validate: bool = False,
        report_interval_s: typing.Optional[float] = None,
        restart_epoch: int = 0,
    ) -> JobHandle:
        """``restart_epoch`` stamps which restart attempt this run is
        (restart strategies pass their attempt counter): the fault plan
        keys its schedule on it and remote-plane handshakes carry it as
        the zombie-fencing epoch."""
        if validate:
            self.validate_plan()
        executor = self._make_executor(restart_epoch)
        # Post-mortem accessor: this job's flight ring stays reachable by
        # name (tracing.flight.recorder_of) after the handle is released.
        from flink_tensorflow_tpu.tracing import flight as flight_mod

        flight_mod.keep(job_name, executor.flight)
        reporter = self._make_reporter(report_interval_s,
                                       flight=executor.flight)
        executor.checkpoint_interval_s = self.checkpoint_interval_s
        if restore_from is not None:
            from flink_tensorflow_tpu.checkpoint.store import read_checkpoint

            local_shard = False
            if self.config.distributed is not None:
                from flink_tensorflow_tpu.checkpoint.store import (
                    read_cohort_checkpoint,
                    read_shard_meta,
                    select_cohort_checkpoint,
                )

                dist = self.config.distributed
                # Metadata-only selection: highest id with a COMPLETE
                # cohort shard set (a lost shard makes an id ineligible
                # instead of silently dropping its state).
                cid, shard_set = select_cohort_checkpoint(
                    restore_from, restore_checkpoint_id
                )
                own_dir = dist.process_checkpoint_dir(restore_from)
                job = (read_shard_meta(own_dir, cid) or {}).get("job", {})
                current = {t.name: t.parallelism
                           for t in self.graph.transformations}
                local_shard = (
                    job.get("num_processes") == dist.num_processes
                    and job.get("process_index") == dist.process_index
                    and job.get("task_parallelism") == current
                )
                # An idle non-participant of an UNCHANGED shape (the
                # over-provisioned cohort, ADVICE r3 medium) owns no
                # subtasks and wrote no shard: restoring it needs only
                # the job metadata for max-parallelism pinning — never
                # the full cohort merge (unpickling every peer's state
                # to restore zero subtasks).
                shard_job = (
                    read_shard_meta(shard_set[0], cid) or {}).get("job", {})
                idle_same_shape = (
                    not local_shard
                    and shard_job.get("participants") is not None
                    and dist.process_index not in shard_job["participants"]
                    and shard_job.get("num_processes") == dist.num_processes
                    and shard_job.get("task_parallelism") == current
                )
                if local_shard:
                    # Same cohort shape and operator parallelisms: this
                    # process's own shard holds exactly its subtasks —
                    # no need to unpickle every peer's state.
                    cid, snapshots = read_checkpoint(own_dir, cid)
                elif idle_same_shape:
                    snapshots = {"__job__": {0: dict(shard_job)}}
                    local_shard = True
                else:
                    # Shape changed (cohort grew/shrank or an operator's
                    # parallelism moved): merge ALL shards so keyed
                    # state can redistribute by key group.
                    cid, snapshots = read_cohort_checkpoint(restore_from, cid)
            else:
                cid, snapshots = read_checkpoint(restore_from, restore_checkpoint_id)
            executor.restore(snapshots, from_checkpoint_id=cid,
                             local_shard=local_shard)
        if reporter is not None:
            # Crash-time flush (see LocalExecutor.fail): the snapshot
            # that explains a failure is published the moment the first
            # subtask dies, not only at the clean-join final report.
            executor.failure_listeners.append(reporter.flush_now)
        health = self._make_health(executor)
        executor.start()
        if reporter is not None:
            reporter.start()
        if health is not None:
            health.start()
        handle = JobHandle(executor, reporter, health=health)
        # Graceful-shutdown flush: SIGTERM/SIGINT publish the final
        # reporter snapshot, dump the flight ring, and export the trace
        # BEFORE the previous handler (usually: death) runs — a killed
        # worker no longer loses its last reporting interval.  Chained
        # and uninstalled at wait()/cancel(); no-op off the main thread.
        callbacks = []
        if reporter is not None:
            callbacks.append(reporter.flush_now)
        if executor.flight is not None and executor.flight_path:
            callbacks.append(lambda: executor.flight_dump("signal"))
        if executor.tracer is not None and executor.trace_path:
            callbacks.append(handle._export_trace)
        if callbacks:
            flusher = flight_mod.ShutdownFlusher(callbacks)
            if flusher.install():
                handle._flusher = flusher
        return handle

    def _make_health(self, executor):
        """Build (without starting) the health plane, or None.

        The evaluator runs on process 0 only (the cohort's JobManager
        seat): its feed is the ``CohortCollector.merged_snapshot`` on a
        distributed executor, the local registry snapshot otherwise —
        same shape either way.  With ``health.autoscale`` the actuator
        subscribes level-triggered; its default ``on_decision`` cancels
        the job so a cohort worker can exit with the rescale code
        (``JobHandle.autoscale_decision`` tells it to).
        """
        cfg = self.config
        if cfg.health is None:
            return None
        dist = cfg.distributed
        if dist is not None and dist.process_index != 0:
            return None  # peers push metrics; process 0 evaluates
        from flink_tensorflow_tpu.metrics.health import HealthEvaluator

        collector = getattr(executor, "cohort_collector", None)
        if collector is not None:
            snapshot_fn = collector.merged_snapshot
        else:
            registry = self.metric_registry
            snapshot_fn = lambda: (time.time(), registry.snapshot())  # noqa: E731
        interval = cfg.health.interval_s
        if interval is None:
            telemetry = getattr(dist, "telemetry_interval_s", 0) if dist else 0
            interval = telemetry if telemetry and telemetry > 0 else 1.0
        health = HealthEvaluator(
            cfg.health.resolved_rules(cfg.channel_capacity),
            interval_s=interval,
            snapshot_fn=snapshot_fn,
            registry=self.metric_registry,
            flight=executor.flight,
            tracer=executor.tracer,
        )
        executor.health_evaluator = health
        if cfg.health.autoscale is not None:
            from flink_tensorflow_tpu.core.autoscale import (
                AutoscaleActuator,
                checkpoint_gate,
            )

            actuator = AutoscaleActuator(
                cfg.health.autoscale,
                dist.num_processes if dist is not None else 1,
                checkpoint_ready=checkpoint_gate(
                    executor.coordinator.checkpoint_dir),
                on_decision=lambda _d: executor.cancel(),
                flight=executor.flight,
            )
            health.subscribe_ticks(actuator.on_tick)
            executor.autoscale_actuator = actuator
        return health

    def _make_reporter(self, report_interval_s: typing.Optional[float],
                       flight=None):
        """Build (without starting) the job's ReporterThread, or None.

        The interval resolves call-site argument first, then
        ``config.metrics.report_interval_s``.  No interval -> no thread,
        no sink construction — the documented zero-overhead default.
        ``flight`` (the executor's FlightRecorder) receives compact
        metric-delta events each report.
        """
        cfg = self.config.metrics
        interval = (report_interval_s if report_interval_s is not None
                    else cfg.report_interval_s)
        if interval is None:
            return None
        from flink_tensorflow_tpu.metrics.reporters import (
            ConsoleReporter,
            ReporterThread,
        )

        sinks = cfg.build_reporters()
        if not sinks:
            sinks = [ConsoleReporter()]
        return ReporterThread(self.metric_registry, sinks, interval,
                              flight=flight)
