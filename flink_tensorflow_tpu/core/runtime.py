"""Local multi-subtask executor — the TaskManager equivalent.

The reference runs on Flink's JobManager/TaskManager cluster (SURVEY.md §1
L1); jobs are threads-in-one-process here, one thread per operator CHAIN
(the reference's "task slot" after Flink's operator chaining).  Threads,
not asyncio, because the hot path blocks in XLA device execution which
releases the GIL — a subtask spending its time inside ``jax.jit``-compiled
calls runs truly parallel to the others.

Operator chaining (analysis/chaining.py): forward-partitioned,
same-parallelism neighbors fuse into one subtask and records pass between
them by direct method call through :class:`ChainedOutput` — no queue, no
serialization, no thread wakeup.  Barriers snapshot each chained operator
in stream order before moving on, watermarks traverse the operators' own
``process_watermark`` hooks, and every logical operator keeps its own
metric scope, so exactly-once semantics and per-operator observability
are untouched by fusion.

The record plane between chains is event-driven end to end: the worker
loop blocks on its input gate until a put / wake / close or the chain's
earliest operator deadline — there is no timed idle poll (the 50 ms
``_IDLE_POLL_S`` of round 5's latency floor is gone).

The mapping to TPU topology (SURVEY.md §7 step 4): subtask index -> local
chip for operator-DP inference; gang operators instead share one
``jax.sharding.Mesh`` spanning all chips (DP training).  Multi-host
execution re-uses this executor per host with jax.distributed providing the
global mesh (see flink_tensorflow_tpu.parallel.multihost).
"""

from __future__ import annotations

import logging
import os
import threading
import time
import typing

from flink_tensorflow_tpu.core import elements as el
from flink_tensorflow_tpu.core.channels import ChannelWriter, InputGate
from flink_tensorflow_tpu.core.graph import CycleError, DataflowGraph, Transformation
from flink_tensorflow_tpu.core.operators import (
    Operator,
    Output,
    SourceOperator,
    SubtaskStats,
)
from flink_tensorflow_tpu.core.partitioning import ForwardPartitioner
from flink_tensorflow_tpu.core.runtime_context import RuntimeContext
from flink_tensorflow_tpu.core.state import KeyedStateStore
from flink_tensorflow_tpu.metrics.registry import MetricRegistry

logger = logging.getLogger(__name__)


class JobFailure(RuntimeError):
    pass


class JobTimeout(JobFailure):
    """join() deadline expired — NOT an operator failure; restart
    strategies must propagate it instead of replaying a healthy job."""


class _ChainedUnit:
    """One logical operator inside a chain's subtask.

    Each unit keeps its own metric scope (records in/out, latency) and
    its own checkpoint identity ``(t.name, index)`` — the inspector and
    the snapshot store see per-operator numbers whether or not the
    operator shares a thread with its neighbors."""

    __slots__ = ("t", "index", "operator", "output", "records_in", "latency")

    def __init__(self, t: Transformation, index: int, operator: Operator):
        self.t = t
        self.index = index
        self.operator = operator
        self.output: typing.Optional[typing.Any] = None
        self.records_in = None   # Meter
        self.latency = None      # Timer

    @property
    def scope(self) -> str:
        return f"{self.t.name}.{self.index}"


class ChainedOutput:
    """Output of a non-tail chained operator: invokes the next operator
    in the chain directly on the same thread — the queue-free hop.

    - records: ``emit`` wraps the value and calls the downstream
      operator's ``process`` inline; per-operator meters/timers still
      tick (latency is INCLUSIVE of the downstream's own chained
      emissions — the chain runs synchronously, like Flink's).
    - barriers: the downstream operator snapshots and acks BEFORE the
      barrier moves further down the chain — everything it processed
      precedes the barrier by construction (synchronous direct calls),
      so aligned exactly-once semantics are byte-identical to the
      channel path.
    - watermarks traverse ``process_watermark`` (operators flush
      event-time state, then forward on their own output).
    - end-of-partition runs the downstream ``finish()`` flush, then
      forwards — the tail's real Output broadcasts to the next chains.
    """

    __slots__ = ("_subtask", "_unit", "_records_out", "_tracer",
                 "_accepts_device")

    def __init__(self, subtask: "_Subtask", unit: _ChainedUnit, records_out,
                 tracer=None, accepts_device: bool = False):
        self._subtask = subtask
        self._unit = unit
        self._records_out = records_out  # upstream operator's out-meter
        self._tracer = tracer
        #: Whether the downstream chained operator consumes DeviceBatch
        #: records directly (device-resident handoff).  False = this hop
        #: is a host boundary: a device batch materializes here (the
        #: deferred d2h forces exactly once) and fans out per record.
        self._accepts_device = accepts_device

    def emit(self, value: typing.Any, timestamp: typing.Optional[float] = None) -> None:
        unit = self._unit
        n = 1
        if getattr(value, "is_device_batch", False):
            if not self._accepts_device:
                ts = timestamp if timestamp is not None else value.timestamp
                for tv in value.materialize():
                    self.emit(tv, ts)
                return
            n = value.num_records  # meters stay per-RECORD under fusion
        t0 = time.monotonic()
        unit.operator.process_record_from(0, el.StreamRecord(value, timestamp))
        t1 = time.monotonic()
        unit.latency.update(t1 - t0)
        unit.records_in.mark(n)
        if self._records_out is not None:
            self._records_out.mark(n)
        tracer = self._tracer
        if tracer is not None:
            tctx = tracer.current()
            if tctx is not None:
                # The chained hop's processing span: inclusive of the
                # member's own downstream emissions, like its latency
                # timer (the chain runs synchronously).
                tracer.span(unit.scope, "process", t0, t1,
                            args={"trace": tctx.trace_id})

    def broadcast_element(self, element: el.StreamElement) -> None:
        unit = self._unit
        if isinstance(element, el.Watermark):
            unit.operator.process_watermark(element)
        elif isinstance(element, el.CheckpointBarrier):
            self._subtask.snapshot_unit(unit, element.checkpoint_id)
            unit.output.broadcast_element(element)
        elif isinstance(element, el.EndOfPartition):
            unit.operator.finish()
            unit.output.broadcast_element(element)
        else:  # pragma: no cover - no other control elements exist
            unit.output.broadcast_element(element)

    @property
    def has_downstream(self) -> bool:
        return True


class _Subtask:
    """One executor thread: a chain of operators sharing one input gate.

    ``chain``/``operators`` hold the fused members head-first; a
    degenerate single-member chain is exactly the pre-chaining subtask.
    Head-centric attributes (``t``, ``operator``, ``output``) refer to
    the chain head — the thread body reads the gate for the head and the
    chain propagates everything else by direct call.
    """

    def __init__(
        self,
        executor: "LocalExecutor",
        chain: typing.Sequence[Transformation],
        index: int,
        operators: typing.Sequence[Operator],
        gate: typing.Optional[InputGate],
        num_input_channels: int,
        edge_of_channel: typing.Optional[typing.List[int]] = None,
    ):
        self.executor = executor
        self.units = [
            _ChainedUnit(t, index, op) for t, op in zip(chain, operators)
        ]
        self.t = chain[0]
        self.index = index
        self.operator = operators[0]
        self.gate = gate
        self.num_input_channels = num_input_channels
        #: channel index -> logical input (edge) index, for two-input
        #: operators (connect/join).
        self.edge_of_channel = edge_of_channel or [0] * num_input_channels
        self.control: "typing.List[int]" = []  # pending checkpoint ids (sources)
        self._control_lock = threading.Lock()
        #: Aborted-checkpoint ids awaiting delivery to this subtask's
        #: thread (coordinator deadline sweeps — see notify_checkpoint_
        #: aborted) and the set already processed: late barriers for an
        #: aborted id are swallowed instead of starting a new alignment
        #: that could never complete.
        self._aborts: "typing.List[int]" = []
        self._aborted_cids: typing.Set[int] = set()
        #: Checkpoint ids this SPLIT-source subtask already cut its
        #: stream at.  A barrier can now reach the reader on three
        #: paths — control drain (trigger), count-based position, and
        #: the freeze-deadlock guard below — and racing paths must not
        #: cut (= snapshot + ack) the same id twice.
        self._barriers_cut: typing.Set[int] = set()
        #: sources.mailbox.SourceMailbox for split-source subtasks (set
        #: by _build) — the ONE wait point of run_split_source; barrier
        #: requests and notifications posted here wake the loop.
        self.mailbox = None
        #: Completed-and-durable checkpoint ids awaiting delivery to the
        #: operators on THEIR thread (single-writer contract; Flink mailbox).
        self._notifications: "typing.List[int]" = []
        self.thread: typing.Optional[threading.Thread] = None
        self.finished = threading.Event()
        # -- instrumentation (wired by the executor in _build) -----------
        #: Single-writer accumulators behind this subtask's pull gauges.
        self.stats = SubtaskStats()
        #: Window-level span hook (tracing.flight.SpanHook) shared by the
        #: chain's operators; the two event loops report their parks to
        #: it.  None when the flight ring and the tracer are both off.
        self.spans = None
        #: The subtask thread's account (tracing.flight.ThreadAccount),
        #: made on that thread where there is a hook, and its reading
        #: once the chain was open: what the chain head's gauges
        #: ``cpu_s`` / ``runq_s`` read.
        self.account = None
        self._charge_at_open = None
        self.records_in = None      # Meter (workers only; head operator)
        self.latency = None         # Timer: per-record processing/emit time
        self.alignment = None       # Timer: barrier-alignment spans

    @property
    def scope(self) -> str:
        return f"{self.t.name}.{self.index}"

    def charged(self, which: int) -> typing.Optional[float]:
        """Seconds the subtask thread has been on a core (0) or runnable
        and not run (1) since its chain was open, which is where
        ``busy_s`` and ``idle_s`` start too; None before that."""
        if self.account is None:
            return None
        return self.account.read()[which] - self._charge_at_open[which]

    @property
    def output(self):
        """The chain HEAD's output (a ChainedOutput when fused)."""
        return self.units[0].output

    # --- source control -------------------------------------------------
    def request_checkpoint(self, checkpoint_id: int) -> None:
        with self._control_lock:
            self.control.append(checkpoint_id)
        if self.mailbox is not None:
            self.mailbox.notify()

    def _drain_control(self) -> typing.List[int]:
        with self._control_lock:
            pending, self.control = self.control, []
        return pending

    def add_notification(self, checkpoint_id: int) -> None:
        with self._control_lock:
            self._notifications.append(checkpoint_id)
        if self.mailbox is not None:
            self.mailbox.notify()

    def add_abort(self, checkpoint_id: int) -> None:
        """A checkpoint missed its deadline: deliver the abort to this
        subtask's thread (it drops the id's alignment state and swallows
        its late barriers)."""
        with self._control_lock:
            self._aborts.append(checkpoint_id)
        if self.mailbox is not None:
            self.mailbox.notify()
        elif self.gate is not None:
            self.gate.wake()

    def _drain_aborts(self) -> typing.List[int]:
        with self._control_lock:
            if not self._aborts:
                return []
            pending, self._aborts = self._aborts, []
        self._aborted_cids.update(pending)
        return pending

    def _deliver_notifications(self) -> None:
        with self._control_lock:
            pending, self._notifications = self._notifications, []
        for cid in pending:
            for unit in self.units:
                unit.operator.notify_checkpoint_complete(cid)

    # --- chain helpers ----------------------------------------------------
    def _open_chain(self) -> None:
        """Open tail-to-head so every operator's downstream is live
        before its first record (Flink's chain open order)."""
        for unit in reversed(self.units):
            unit.operator.open()
        if self.spans is not None:
            account = self.spans.account()
            self._charge_at_open = account.read()
            self.account = account  # last: a report may be taken any time

    def _close_chain(self) -> None:
        for unit in self.units:
            unit.operator.close()

    def _chain_next_deadline(self) -> typing.Optional[float]:
        deadlines = [
            d for d in (u.operator.next_deadline() for u in self.units)
            if d is not None
        ]
        return min(deadlines) if deadlines else None

    def _chain_fire_due(self, now: float) -> None:
        for unit in self.units:
            d = unit.operator.next_deadline()
            if d is not None and now >= d:
                unit.operator.fire_due(now)

    def snapshot_unit(self, unit: _ChainedUnit, checkpoint_id: typing.Optional[int]) -> None:
        """Snapshot + ack ONE chained logical operator (called by
        ChainedOutput as the barrier traverses the chain in order)."""
        san = self.executor.sanitizer
        if san is not None and checkpoint_id is not None:
            # Independent snapshot-order state machine: within this
            # subtask, checkpoint k must snapshot the chain head-to-tail
            # with no gaps (snapshot order == stream order).
            san.chain_snapshot(self.scope, checkpoint_id,
                               self.units.index(unit), len(self.units))
        tracer = self.executor.tracer
        t0 = time.monotonic() if tracer is not None else 0.0
        snapshot = unit.operator.snapshot(checkpoint_id)
        self.executor.coordinator.ack(
            checkpoint_id, unit.t.name, unit.index, snapshot)
        if tracer is not None:
            tracer.span(unit.scope, "snapshot", t0, time.monotonic(),
                        args={"checkpoint": checkpoint_id})
        flight = self.executor.flight
        if flight is not None:
            flight.record(unit.scope, "snapshot",
                          {"checkpoint": checkpoint_id})

    # --- thread bodies ---------------------------------------------------
    def _source_barrier(self, checkpoint_id: int) -> None:
        """Cut a legacy source's stream at a barrier: snapshot + broadcast
        (with a trace instant marking the injection point when traced)."""
        if checkpoint_id in self._aborted_cids:
            return  # deadline-swept checkpoint: do not cut, do not ack
        tracer = self.executor.tracer
        if tracer is not None:
            tracer.instant(self.scope, "barrier.inject",
                           args={"checkpoint": checkpoint_id})
        flight = self.executor.flight
        if flight is not None:
            flight.record(self.scope, "barrier.inject",
                          {"checkpoint": checkpoint_id})
        san = self.executor.sanitizer
        if san is not None:
            san.hb("barrier.inject", self.scope, cid=checkpoint_id)
        self._snapshot_and_ack(checkpoint_id)
        self.output.broadcast_element(el.CheckpointBarrier(checkpoint_id))

    def run_source(self) -> None:
        op = typing.cast(SourceOperator, self.operator)
        try:
            self._open_chain()
            throttle = self.executor.source_throttle_s
            every_n = self.executor.checkpoint_every_n
            tracer = self.executor.tracer
            faults = self.executor.faults
            for value in op.iterate():
                if self.executor.cancelled.is_set():
                    break
                self._deliver_notifications()
                self._drain_aborts()
                for cid in self._drain_control():
                    self._source_barrier(cid)
                if isinstance(value, el.SourceIdle):
                    continue  # idle heartbeat: barriers served, no record
                if tracer is not None:
                    # Head-based admission: the ONE sampling decision for
                    # this record's whole trace is made here.
                    tracer.set_current(tracer.admit(self.scope, value))
                t_emit = time.monotonic()
                self.output.emit(value)
                op.record_emitted()
                if faults is not None:
                    faults.record_point(self.scope, op.offset)
                t_done = time.monotonic()
                # Per-record emit latency: dominated by blocked-put time
                # when downstream backpressures (the source-side signal);
                # for a chained source it covers the fused operators'
                # inline processing.
                self.latency.update(t_done - t_emit)
                if tracer is not None:
                    tctx = tracer.current()
                    if tctx is not None:
                        tracer.span(self.scope, "emit", t_emit, t_done,
                                    args={"trace": tctx.trace_id})
                        tracer.set_current(None)
                # Count-based barriers: checkpoint k cuts the stream after
                # this subtask's k*N-th record — a deterministic position,
                # identical on every host running the same job (the
                # multi-host consistency contract; see CheckpointCoordinator).
                if every_n and op.offset % every_n == 0:
                    cid = op.offset // every_n
                    if self.executor.coordinator.begin_source_checkpoint(cid):
                        self._source_barrier(cid)
                if throttle:
                    time.sleep(throttle)
            # Serve any barrier requests that raced with the last records.
            for cid in self._drain_control():
                self._source_barrier(cid)
            op.finish()
            self.output.broadcast_element(el.EndOfPartition())
            self._close_chain()
        except BaseException as exc:  # noqa: BLE001
            self.executor.fail(self, exc)
        finally:
            self.finished.set()
            self.executor.subtask_finished(self)

    def _split_barrier(self, checkpoint_id: int) -> None:
        """Cut this reader's stream at a barrier: register with the
        split coordinator FIRST (freezing assignment and, for reader 0,
        staging the consistent enumerator-pool snapshot), then snapshot
        this subtask and push the barrier down the chain.  Idempotent
        per id: the same checkpoint may be requested via trigger
        control, reached count-based, AND served by the freeze-deadlock
        guard — only the first cut snapshots and acks."""
        if checkpoint_id in self._barriers_cut or checkpoint_id in self._aborted_cids:
            return
        self._barriers_cut.add(checkpoint_id)
        tracer = self.executor.tracer
        if tracer is not None:
            tracer.instant(self.scope, "barrier.inject",
                           args={"checkpoint": checkpoint_id})
        flight = self.executor.flight
        if flight is not None:
            flight.record(self.scope, "barrier.inject",
                          {"checkpoint": checkpoint_id})
        san = self.executor.sanitizer
        if san is not None:
            san.hb("barrier.inject", self.scope, cid=checkpoint_id)
        op = typing.cast("typing.Any", self.operator)
        op.on_barrier(checkpoint_id)
        self._snapshot_and_ack(checkpoint_id)
        self.output.broadcast_element(el.CheckpointBarrier(checkpoint_id))

    def run_split_source(self) -> None:
        """Mailbox event loop for a split-based source (FLIP-27 model).

        Unlike ``run_source`` — which blocks wherever the user generator
        blocks — this loop owns ALL waiting: every iteration serves
        durable-checkpoint notifications, pending barriers, and chained
        operators' due timers, then asks the operator for one
        non-blocking step (emit a record / park until ``due`` / done).
        Parking happens exclusively on the subtask MAILBOX, bounded by
        the earliest of the next record's due time and the chain's
        earliest operator deadline, and is woken early by barrier
        requests, split availability, notifications, ``ctx.wakeup``, and
        cancellation.  This wakeable wait is why the chaining pass lets
        timer-driven operators fuse into split-source chains.
        """
        from flink_tensorflow_tpu.sources.operator import DONE, RECORD

        op = typing.cast("typing.Any", self.operator)
        executor = self.executor
        stats = self.stats
        try:
            self._open_chain()
            throttle = executor.source_throttle_s
            every_n = executor.checkpoint_every_n
            tracer = executor.tracer
            faults = executor.faults
            spans = self.spans
            while not executor.cancelled.is_set():
                self._deliver_notifications()
                self._drain_aborts()
                for cid in self._drain_control():
                    self._split_barrier(cid)
                now = time.monotonic()
                deadline = self._chain_next_deadline()
                if deadline is not None and now >= deadline:
                    self._chain_fire_due(now)
                    deadline = self._chain_next_deadline()
                kind, payload = op.poll_next()
                if kind == RECORD:
                    if tracer is not None:
                        tracer.set_current(tracer.admit(self.scope, payload))
                    t_emit = time.monotonic()
                    self.output.emit(payload)
                    op.record_emitted()
                    if faults is not None:
                        faults.record_point(self.scope, op.offset)
                    t_done = time.monotonic()
                    self.latency.update(t_done - t_emit)
                    if tracer is not None:
                        tctx = tracer.current()
                        if tctx is not None:
                            tracer.span(self.scope, "emit", t_emit, t_done,
                                        args={"trace": tctx.trace_id})
                            tracer.set_current(None)
                    # Count-based barriers at deterministic PER-SUBTASK
                    # positions (CheckpointCoordinator's every_n mode).
                    if every_n and op.offset % every_n == 0:
                        cid = op.offset // every_n
                        if executor.coordinator.begin_source_checkpoint(cid):
                            self._split_barrier(cid)
                    if throttle:
                        time.sleep(throttle)
                    continue
                if kind == DONE:
                    break
                # Freeze-deadlock guard: a reader parked split-less on a
                # frozen assignment emits no records, so with count-based
                # triggers it would NEVER reach the position that makes
                # it cut the pending barrier — the alignment waits on
                # this reader and this reader on the alignment's freeze.
                # Cut the stream for every pending alignment here, at
                # the wait point (positions are per-run for split
                # sources anyway; sources/operator.py docstring), then
                # re-poll: completing the alignment may unfreeze splits.
                served = False
                for cid in op.pending_alignments():
                    self._split_barrier(cid)
                    served = True
                if served:
                    continue
                # WAIT: nothing to do until `payload` (a record's due
                # time, or None = until an event) / the chain's earliest
                # timer — park on the mailbox, charging idle time.
                due = payload
                now = time.monotonic()
                timeout = None
                for target in (due, deadline):
                    if target is not None:
                        t = max(0.0, target - now)
                        timeout = t if timeout is None else min(timeout, t)
                woken = self.mailbox.wait(timeout)
                t1 = time.monotonic()
                stats.idle_s += t1 - now
                if spans is not None:
                    spans.park(self.scope, timeout, t1 - now, woken, t1)
            # Serve barrier requests that raced with the last records.
            for cid in self._drain_control():
                self._split_barrier(cid)
            if not executor.cancelled.is_set():
                op.finish()
                self.output.broadcast_element(el.EndOfPartition())
            self._close_chain()
        except BaseException as exc:  # noqa: BLE001
            executor.fail(self, exc)
        finally:
            self.finished.set()
            self.executor.subtask_finished(self)

    def run_worker(self) -> None:
        op = self.operator
        gate = self.gate
        n = self.num_input_channels
        eop = [False] * n
        barrier_seen: typing.Dict[int, typing.Set[int]] = {}
        #: checkpoint id -> monotonic time its FIRST barrier arrived here
        #: (alignment span = first barrier -> snapshot).
        barrier_t0: typing.Dict[int, float] = {}
        watermarks = [float("-inf")] * n
        current_wm = float("-inf")
        stats = self.stats
        records_in = self.records_in
        latency = self.latency
        tracer = self.executor.tracer
        faults = self.executor.faults
        spans = self.spans
        processed = 0
        try:
            self._open_chain()
            active = n
            while active > 0 and not self.executor.cancelled.is_set():
                deadline = self._chain_next_deadline()
                now = time.monotonic()
                # Event-driven wait: block until a put/wake/close or the
                # chain's earliest operator deadline — no idle poll
                # quantum (the gate's condition variable replaces the
                # former 50 ms _IDLE_POLL_S re-poll).
                timeout = None if deadline is None else max(0.0, deadline - now)
                poll_start = now
                item = gate.poll(timeout=timeout)
                self._deliver_notifications()
                for cid in self._drain_aborts():
                    # Deadline-swept checkpoint: drop its alignment (a
                    # barrier that never arrives must not wedge the gate
                    # behind blocked channels forever); its stashed
                    # records replay in order.
                    if cid in barrier_seen:
                        del barrier_seen[cid]
                        barrier_t0.pop(cid, None)
                        gate.unblock_all()
                now = time.monotonic()
                if item is None:
                    # Nothing to process: the poll wait was idle time
                    # (with data the dequeue returns ~immediately, so
                    # only empty polls are charged — no extra clock read
                    # either way).
                    stats.idle_s += now - poll_start
                    if spans is not None:
                        slept = now - poll_start
                        spans.park(self.scope, timeout, slept,
                                   timeout is None or slept < timeout, now)
                if deadline is not None and now >= deadline:
                    self._chain_fire_due(now)
                if item is None:
                    continue
                idx, element = item
                if isinstance(element, el.StreamRecord):
                    processed += 1
                    if faults is not None:
                        faults.record_point(self.scope, processed)
                    if tracer is None:
                        op.process_record_from(self.edge_of_channel[idx], element)
                        latency.update(time.monotonic() - now)
                    else:
                        tctx = element.trace
                        if tctx is not None:
                            # Queue-wait span (enqueue -> this delivery)
                            # + thread-local continuity for the chain's
                            # downstream emissions.
                            tracer.queue_span(self.scope, tctx, now)
                            tracer.set_current(tctx)
                        op.process_record_from(self.edge_of_channel[idx], element)
                        t1 = time.monotonic()
                        latency.update(t1 - now)
                        if tctx is not None:
                            tracer.span(self.scope, "process", now, t1,
                                        args={"trace": tctx.trace_id})
                            tracer.set_current(None)
                    records_in.mark()
                elif isinstance(element, el.CheckpointBarrier):
                    cid = element.checkpoint_id
                    if cid in self._aborted_cids:
                        # Late barrier of a deadline-swept checkpoint:
                        # swallow it — neither blocking (the alignment
                        # could never complete) nor forwarding (every
                        # downstream received the same abort).
                        continue
                    seen = barrier_seen.setdefault(cid, set())
                    if not seen:
                        barrier_t0[cid] = now
                    seen.add(idx)
                    gate.block_channel(idx)
                    live = {i for i in range(n) if not eop[i]}
                    if live <= seen:
                        t_align = barrier_t0.pop(cid, now)
                        self.alignment.update(now - t_align)
                        if tracer is not None:
                            tracer.span(self.scope, "align", t_align, now,
                                        args={"checkpoint": cid})
                        self._snapshot_and_ack(cid)
                        self.output.broadcast_element(element)
                        del barrier_seen[cid]
                        gate.unblock_all()
                elif isinstance(element, el.Watermark):
                    watermarks[idx] = element.timestamp
                    new_wm = min(
                        watermarks[i] for i in range(n) if not eop[i]
                    )
                    if new_wm > current_wm:
                        current_wm = new_wm
                        if tracer is not None:
                            tracer.instant(self.scope, "watermark", ts=now,
                                           args={"timestamp": current_wm})
                        op.process_watermark(el.Watermark(current_wm))
                elif isinstance(element, el.EndOfPartition):
                    eop[idx] = True
                    active -= 1
                    # A finished channel counts as barriered for all pending
                    # alignments (it can never deliver its barrier).
                    for cid, seen in list(barrier_seen.items()):
                        live = {i for i in range(n) if not eop[i]}
                        if live and live <= seen:
                            t_align = barrier_t0.pop(cid, now)
                            self.alignment.update(now - t_align)
                            if tracer is not None:
                                tracer.span(self.scope, "align", t_align, now,
                                            args={"checkpoint": cid})
                            self._snapshot_and_ack(cid)
                            self.output.broadcast_element(el.CheckpointBarrier(cid))
                            del barrier_seen[cid]
                            gate.unblock_all()
                    # A finished channel no longer holds the combined
                    # watermark back (Flink: finished inputs count as
                    # MAX_WATERMARK) — recompute over the live channels.
                    if active > 0:
                        new_wm = min(
                            watermarks[i] for i in range(n) if not eop[i]
                        )
                        if new_wm > current_wm:
                            current_wm = new_wm
                            op.process_watermark(el.Watermark(current_wm))
            if not self.executor.cancelled.is_set():
                op.finish()
                self.output.broadcast_element(el.EndOfPartition())
            self._close_chain()
        except BaseException as exc:  # noqa: BLE001
            self.executor.fail(self, exc)
        finally:
            self.finished.set()
            self.executor.subtask_finished(self)

    def _snapshot_and_ack(self, checkpoint_id: int) -> None:
        self.snapshot_unit(self.units[0], checkpoint_id)


class LocalExecutor:
    """Builds the physical plan from a DataflowGraph and runs it."""

    def __init__(
        self,
        graph: DataflowGraph,
        *,
        channel_capacity: int = 1024,
        metric_registry: typing.Optional[MetricRegistry] = None,
        device_provider: typing.Optional[typing.Callable[[str, int], typing.Any]] = None,
        mesh: typing.Optional[typing.Any] = None,
        job_config: typing.Optional[dict] = None,
        source_throttle_s: float = 0.0,
        checkpoint_dir: typing.Optional[str] = None,
        checkpoint_every_n: typing.Optional[int] = None,
        checkpoint_timeout_s: float = 60.0,
        checkpoint_retain_last: typing.Optional[int] = None,
        max_parallelism: int = 128,
        chaining: bool = True,
        sanitize: bool = False,
        sanitize_log_path: typing.Optional[str] = None,
        trace: bool = False,
        trace_path: typing.Optional[str] = None,
        trace_sample_rate: float = 1.0,
        flight_recorder: bool = True,
        flight_path: typing.Optional[str] = None,
        device_resident: bool = False,
        wire_dtype: typing.Optional[str] = None,
        wire_flush_bytes: typing.Optional[int] = None,
        wire_flush_ms: typing.Optional[float] = None,
        shm_channels: bool = True,
        flow_control: bool = True,
        faults: typing.Optional[typing.Any] = None,
        restart_epoch: int = 0,
        roofline: typing.Optional[typing.Any] = None,
    ):
        from flink_tensorflow_tpu import tracing
        from flink_tensorflow_tpu.core import sanitizer_rt
        from flink_tensorflow_tpu.core.checkpoint import CheckpointCoordinator
        from flink_tensorflow_tpu.tensors.transfer import (
            env_device_resident,
            env_wire_dtype,
        )

        self.graph = graph
        #: Device-resident dataflow (tensors/transfer.DeviceBatch):
        #: chains of device-capable operators hand HBM-resident batches
        #: between fused members, eliding the d2h/h2d pair per hop; the
        #: first host-only consumer forces the fetch exactly once.
        #: JobConfig.device_resident or FLINK_TPU_DEVICE_RESIDENT=1.
        self.device_resident = device_resident or env_device_resident()
        #: Job-wide compact wire dtype (h2d + remote frames); model
        #: functions/remote sinks default to it at open().
        #: JobConfig.wire_dtype or FLINK_TPU_WIRE_DTYPE.
        self.wire_dtype = wire_dtype if wire_dtype is not None else env_wire_dtype()
        if self.wire_dtype == "f32":
            self.wire_dtype = None
        #: Remote-plane coalescing knobs (JobConfig.wire_flush_bytes /
        #: wire_flush_ms; FLINK_TPU_WIRE_FLUSH_* take precedence inside
        #: the writers) and the same-host shm upgrade.  A LocalExecutor
        #: has no remote edges — these only feed RemoteSink defaults via
        #: the RuntimeContext and the DistributedExecutor's writers.
        self.wire_flush_bytes = wire_flush_bytes
        self.wire_flush_ms = wire_flush_ms
        from flink_tensorflow_tpu.core.shuffle import (
            env_flow_control_enabled,
            env_shm_enabled,
        )

        env_shm = env_shm_enabled()
        self.shm_channels = shm_channels if env_shm is None else env_shm
        #: Credit-based flow control on the cross-process record plane
        #: (JobConfig.flow_control; FLINK_TPU_FLOW_CONTROL overrides).
        #: A LocalExecutor has no remote edges — this only feeds the
        #: DistributedExecutor's writers and RemoteSink defaults.
        env_fc = env_flow_control_enabled()
        self.flow_control = flow_control if env_fc is None else env_fc
        #: Debug-mode concurrency sanitizer (core/sanitizer_rt):
        #: JobConfig.sanitize=True or FLINK_TPU_SANITIZE=1 instruments
        #: every gate/mailbox/coordinator lock and asserts the barrier
        #: protocol invariants; None (the default) leaves the runtime's
        #: production no-op path — plain threading primitives, one
        #: is-None test per hook site.
        self.sanitizer = (
            sanitizer_rt.ConcurrencySanitizer(name="executor")
            if (sanitize or sanitizer_rt.env_enabled()) else None
        )
        #: Happens-before event-log destination (core/sanitizer_stitch
        #: input): JobConfig.sanitize_log_path or FLINK_TPU_SANITIZE_LOG.
        #: Kept even when the sanitizer is off so the distributed layer
        #: can test it unconditionally; no sanitizer → no dump.
        self.sanitize_log_path = (
            sanitize_log_path or sanitizer_rt.env_hb_log_path())
        self.channel_capacity = channel_capacity
        self.metrics = metric_registry or MetricRegistry()
        #: Span tracer (flink_tensorflow_tpu.tracing): JobConfig.trace
        #: or FLINK_TPU_TRACE=1 turns on per-record/per-batch span
        #: recording across sources, chains, channels, the model
        #: runner's h2d/compute/d2h stages, checkpoints, splits and
        #: remote edges; None (the default) keeps the production no-op
        #: path — one is-None test per hook site, zero allocation.
        if trace or tracing.env_enabled():
            self.tracer = tracing.Tracer(
                sample_rate=tracing.env_sample_rate() or trace_sample_rate,
                seed=self.metrics.seed,
            )
        else:
            self.tracer = None
        #: Chrome-trace export destination: written by JobHandle.wait
        #: when the job finishes OR fails (the crash trace is the one
        #: that matters).  None keeps spans in memory (CLI path).
        self.trace_path = trace_path or tracing.env_trace_path()
        #: Flight recorder (tracing/flight.py): the always-on black box —
        #: a bounded ring of control-rate lifecycle/checkpoint/metric-
        #: delta events, dumped to ``flight_path`` on crash, sanitizer
        #: violation, signal, or cancel.  ``flight_recorder=False`` /
        #: FLINK_TPU_FLIGHT=0 is the zero-alloc off path (tier-1
        #: guarded); the ring runs regardless of whether a dump path is
        #: configured.
        from flink_tensorflow_tpu.tracing import flight as flight_mod

        env_flight = flight_mod.env_enabled()
        flight_on = flight_recorder if env_flight is None else env_flight
        self.flight = flight_mod.FlightRecorder() if flight_on else None
        self.flight_path = flight_path or flight_mod.env_flight_path()
        #: The process's heartbeat in the ring (flight.Pulse): started
        #: with the subtask threads, stopped where they are joined.
        self.pulse = None
        if self.flight is not None:
            self.pulse = flight_mod.Pulse(
                self.flight,
                timer=self.metrics.group("process").timer("pulse_late_s"),
                on_stall=lambda: self.flight_dump("stall"))
        if self.sanitizer is not None and self.tracer is not None:
            # Satellite wiring: sanitizer findings (stall dumps with
            # thread stacks + lock ownership, protocol violations) land
            # as instants on the trace timeline, next to the spans the
            # hang interrupted.
            self.sanitizer.tracer = self.tracer
        #: Zero-arg hooks fired once, at the FIRST subtask failure —
        #: the reporter thread flushes a crash-time snapshot here so the
        #: metrics that explain the failure are published even if the
        #: caller never joins.
        self.failure_listeners: typing.List[typing.Callable[[], None]] = []
        #: Which restart attempt of the job this executor runs (0 = the
        #: first): the fault plan keys its schedule on it, remote-plane
        #: handshakes carry it as the fencing epoch, and the flight
        #: recorder stamps it on lifecycle events.
        self.restart_epoch = restart_epoch
        #: Chaos plane (core/faults.py): a deterministic fault schedule
        #: armed for THIS restart epoch — JobConfig.faults or
        #: FLINK_TPU_FAULTS.  None (the default) keeps the production
        #: path at one is-None test per hook site.
        from flink_tensorflow_tpu.core.faults import FaultInjector, FaultPlan

        injector = None
        plan = FaultPlan.resolve(faults)
        if plan is not None and plan.specs:
            injector = FaultInjector(plan, epoch=restart_epoch,
                                     metrics=self.metrics, flight=self.flight)
            if not injector.active:
                # Nothing armed for THIS epoch (e.g. the restarted run
                # of an epoch-0 schedule): drop back to the zero-cost
                # no-op path.
                injector = None
        self.faults = injector
        #: Roofline attribution plane (metrics/roofline.py):
        #: JobConfig.roofline declares the DeviceSpec peak and carries
        #: the plan's CostTable; model runners mint per-operator probes
        #: off ``ctx.roofline`` and publish ``roofline.*`` gauges +
        #: compile events.  None (the default) keeps the production path
        #: at one is-None test per runner.
        self.roofline = None
        if roofline is not None:
            from flink_tensorflow_tpu.metrics.roofline import RooflinePlane

            self.roofline = RooflinePlane(
                roofline, flight=self.flight, tracer=self.tracer)
        self.device_provider = device_provider
        self.mesh = mesh
        self.job_config = job_config or {}
        self.source_throttle_s = source_throttle_s
        self.checkpoint_every_n = checkpoint_every_n
        self.checkpoint_timeout_s = checkpoint_timeout_s
        self.checkpoint_retain_last = checkpoint_retain_last
        self.max_parallelism = max_parallelism
        self.chaining = chaining
        self.cancelled = threading.Event()
        self._error: typing.Optional[BaseException] = None
        self._error_lock = threading.Lock()
        self.subtasks: typing.List[_Subtask] = []
        self._gates: typing.List[InputGate] = []
        #: One split coordinator per split-source transformation (the
        #: FLIP-27 enumerator host) — shared by that source's readers.
        self._split_coordinators: typing.Dict[str, typing.Any] = {}
        self._split_lock = threading.Lock()
        #: The chaining decision (analysis.chaining.ChainPlan) — the
        #: inspector/analysis CLIs print its topology.
        self.chain_plan = None
        self.coordinator = CheckpointCoordinator(self, checkpoint_dir)
        self.checkpoint_interval_s: typing.Optional[float] = None
        self._finished_count = 0
        self._all_done = threading.Event()
        self._periodic_thread: typing.Optional[threading.Thread] = None
        self._build()
        if self.sanitizer is not None:
            # Observability: the sanitizer reports through the same
            # metric plane as everything else (inspector/reporters show
            # violation counts next to the runtime gauges).
            grp = self.metrics.group("sanitizer")
            grp.gauge("violations", lambda: len(self.sanitizer.violations))
            grp.gauge("tracked_ops", lambda: self.sanitizer.progress_ops)
            # Cross-process happens-before log (PR 15): ring occupancy
            # and drop counts ride the cohort telemetry pushes so the
            # stitcher's truncation caveats are visible live.
            cohort = self.metrics.group("sanitizer.cohort")
            cohort.gauge("hb_events", lambda: self.sanitizer.hb_events)
            cohort.gauge("hb_recorded", lambda: self.sanitizer.hb_recorded)
            cohort.gauge("hb_dropped", lambda: self.sanitizer.hb_dropped)
            cohort.gauge("violations",
                         lambda: len(self.sanitizer.violations))

    # --- plan construction ----------------------------------------------
    def _build(self) -> None:
        by_head: typing.Dict[int, typing.List[_Subtask]] = {}
        gates: typing.Dict[typing.Tuple[int, int], InputGate] = {}

        try:
            order = self.graph.topological_order()
        except CycleError:
            logger.error(
                "cannot build the physical plan: the dataflow graph is "
                "cyclic — run the plan analyzer (env.validate_plan() or "
                "`python -m flink_tensorflow_tpu.analysis <pipeline>`) "
                "for full diagnostics"
            )
            raise

        from flink_tensorflow_tpu.analysis.chaining import compute_chains
        from flink_tensorflow_tpu.core.partitioning import HashPartitioner

        for t in order:
            keyed = any(isinstance(e.partitioner, HashPartitioner) for e in t.inputs)
            if keyed and t.parallelism > self.max_parallelism:
                # Non-keyed operators hold no key-partitioned state and
                # may exceed the bound freely (Flink's rule).
                raise ValueError(
                    f"keyed operator {t.name!r} parallelism {t.parallelism} "
                    f"exceeds max_parallelism {self.max_parallelism} — key "
                    "groups would starve the subtasks above the bound; raise "
                    "JobConfig.max_parallelism"
                )

        # The chaining decision is a pure function of the graph, so every
        # process of a distributed cohort computes the identical plan and
        # channel layouts agree cluster-wide.
        plan = compute_chains(self.graph, enabled=self.chaining)
        self.chain_plan = plan
        chain_by_head = {chain[0].id: chain for chain in plan.chains}
        heads = [t for t in order if t.id in chain_by_head]

        # Pass 1: channel layout per chain HEAD (chained edges pass
        # records by direct call and get no channels at all).  Forward
        # edges contribute 1 channel per gate; others contribute the
        # upstream parallelism.
        channel_base: typing.Dict[typing.Tuple[int, int], int] = {}  # (head_id, edge_idx) -> base
        gate_size: typing.Dict[int, int] = {}
        edge_of_channel: typing.Dict[int, typing.List[int]] = {}  # head id -> per-channel edge idx
        for t in heads:
            base = 0
            channel_edges: typing.List[int] = []
            for edge_idx, edge in enumerate(t.inputs):
                channel_base[(t.id, edge_idx)] = base
                if isinstance(edge.partitioner, ForwardPartitioner):
                    if edge.upstream.parallelism != t.parallelism:
                        raise ValueError(
                            f"forward edge {edge.upstream.name}->{t.name} requires equal "
                            f"parallelism ({edge.upstream.parallelism} vs {t.parallelism})"
                        )
                    span = 1
                else:
                    span = edge.upstream.parallelism
                channel_edges.extend([edge_idx] * span)
                base += span
            gate_size[t.id] = base
            edge_of_channel[t.id] = channel_edges

        # Pass 2: instantiate one subtask per chain per parallel index.
        # A distributed executor owns only the subtasks placed on this
        # process (_owns_subtask); the identical graph AND chain plan are
        # built on every process, so channel layout and subtask indices
        # agree cluster-wide.  Chain members share their head's index —
        # chaining requires equal parallelism, so placement is identical.
        for t in heads:
            chain = chain_by_head[t.id]
            subtasks = []
            for i in range(t.parallelism):
                if not self._owns_subtask(t, i):
                    continue
                operators = [member.operator_factory() for member in chain]
                gate = None
                if not t.is_source:
                    gate = InputGate(gate_size[t.id], capacity=self.channel_capacity,
                                     sanitizer=self.sanitizer,
                                     name=f"{t.name}.{i}.gate")
                    gates[(t.id, i)] = gate
                    self._gates.append(gate)
                st = _Subtask(self, chain, i, operators, gate, gate_size[t.id],
                              edge_of_channel[t.id])
                if t.is_source and getattr(operators[0], "is_split_source", False):
                    from flink_tensorflow_tpu.sources.mailbox import SourceMailbox

                    st.mailbox = SourceMailbox(sanitizer=self.sanitizer,
                                               name=f"{t.name}.{i}.mailbox")
                subtasks.append(st)
            by_head[t.id] = subtasks

        # Pass 3: wire outputs.  Only the chain TAIL talks to channels —
        # every cross-chain edge targets another chain's head gate (a
        # non-head member's sole input is its fused edge).  Within the
        # chain, each operator's output is a ChainedOutput invoking the
        # next member directly.
        for t in heads:
            chain = chain_by_head[t.id]
            tail = chain[-1]
            downstream = [
                (d, edge_idx, edge)
                for d in self.graph.transformations
                for edge_idx, edge in enumerate(d.inputs)
                if edge.upstream.id == tail.id
            ]
            for st in by_head[t.id]:
                edges_for_output = []
                for d, edge_idx, edge in downstream:
                    head_d = plan.head_of[d.id]
                    base = channel_base[(head_d.id, edge_idx)]
                    if isinstance(edge.partitioner, ForwardPartitioner):
                        targets = [(st.index, base)]
                    else:
                        targets = [(j, base + st.index) for j in range(d.parallelism)]
                    # A downstream subtask without a local gate lives on a
                    # peer process: the writer becomes a remote channel of
                    # the record plane (records AND barriers flow through
                    # it — alignment spans processes).
                    writers = [
                        ChannelWriter(gates[(head_d.id, j)], ch)
                        if (head_d.id, j) in gates
                        else self._remote_writer(d, j, ch)
                        for j, ch in targets
                    ]
                    # Stateful partitioners (e.g. rebalance round-robin) must
                    # not be shared across upstream subtask threads.
                    import copy

                    edges_for_output.append((copy.deepcopy(edge.partitioner), writers))

                # Tail gets the real channel Output; every earlier member
                # gets a ChainedOutput onto its successor.
                tail_unit = st.units[-1]
                tail_grp = self.metrics.group(tail_unit.scope)
                tail_unit.output = Output(edges_for_output,
                                          meter=tail_grp.meter("records_out"),
                                          stats=st.stats,
                                          tracer=self.tracer)
                for k in range(len(st.units) - 2, -1, -1):
                    unit = st.units[k]
                    nxt = st.units[k + 1]
                    grp_k = self.metrics.group(unit.scope)
                    accepts = getattr(
                        getattr(nxt.operator, "function", None),
                        "accepts_device_batches", False)
                    unit.output = ChainedOutput(
                        st, nxt, grp_k.meter("records_out"),
                        tracer=self.tracer, accepts_device=accepts)
                    if accepts and self.device_resident:
                        # Emission hint: this member's function may keep
                        # its results HBM-resident — the next chained
                        # operator consumes DeviceBatches directly.
                        up_fn = getattr(unit.operator, "function", None)
                        if getattr(up_fn, "device_capable", False):
                            up_fn._device_chain_hint = True

                self._wire_units(st, gates)
        # Register per-edge record-plane gauges after wiring (the gate
        # and channel layout are both final here).
        for t in heads:
            for st in by_head[t.id]:
                self._register_edge_gauges(st, t, channel_base)

    def _wire_units(self, st: _Subtask, gates) -> None:
        """Per-unit instrumentation + RuntimeContext + operator setup."""
        proc_idx, num_procs = self._process_identity()
        head_gate = st.gate
        chain_len = len(st.units)
        if self.flight is not None or self.tracer is not None:
            from flink_tensorflow_tpu.tracing.flight import SpanHook

            st.spans = SpanHook(self.flight, self.tracer)
        for pos, unit in enumerate(st.units):
            grp = self.metrics.group(unit.scope)
            unit.records_in = grp.meter("records_in")
            unit.latency = grp.timer("process_latency_s")
            # Chain-shape gauges: what got fused where (the inspector's
            # chain column and the CI no-queue-traffic guard read these).
            grp.gauge("chain_length", lambda n=chain_len: n)
            grp.gauge("chained_edges", lambda n=chain_len - 1: n)
            grp.gauge("chain_position", lambda p=pos: p)
            if pos == 0:
                st.records_in = unit.records_in
                st.latency = unit.latency
                st.alignment = grp.timer("checkpoint_alignment_s")
                # Pull-based gauges: the hot path only bumps the plain
                # accumulators; evaluation happens at report time.
                stats = st.stats
                latency = unit.latency
                grp.gauge("idle_s", lambda s=stats: s.idle_s)
                grp.gauge("busy_s", lambda tm=latency: tm.total_s)
                grp.gauge("backpressure_s", lambda s=stats: s.blocked_s)
                if st.spans is not None:
                    # What the OS charged the subtask thread since it
                    # started; no run-queue gauge without the figure.
                    from flink_tensorflow_tpu.tracing.flight import SCHEDSTAT

                    grp.gauge("cpu_s", lambda st=st: st.charged(0))
                    if os.access(SCHEDSTAT, os.R_OK):
                        grp.gauge("runq_s", lambda st=st: st.charged(1))
                if head_gate is not None:
                    grp.gauge("queue_depth",
                              lambda g=head_gate: g.depth)
                    grp.gauge("queue_high_watermark",
                              lambda g=head_gate: g.high_watermark)
                    # Time UPSTREAM writers spent blocked putting into
                    # this subtask's gate — "this operator causes the
                    # backpressure above it".
                    grp.gauge("in_backpressure_s",
                              lambda g=head_gate: g.blocked_put_s)
            state = KeyedStateStore()
            device = (
                self.device_provider(unit.t.name, unit.index)
                if self.device_provider else None
            )
            if device is not None:
                from flink_tensorflow_tpu.utils.profiling import (
                    device_memory_stats,
                )

                grp.gauge(
                    "hbm_bytes_in_use",
                    lambda d=device: device_memory_stats(d).get("bytes_in_use"),
                )
            ctx = RuntimeContext(
                task_name=unit.t.name,
                subtask_index=unit.index,
                parallelism=unit.t.parallelism,
                keyed_state=state,
                metric_group=grp,
                device=device,
                mesh=self.mesh,
                job_config=self.job_config,
                process_index=proc_idx,
                num_processes=num_procs,
            )
            # Span tracer hand-off: remote sinks / decode runners read
            # ctx.tracer at open() and record their stage spans on this
            # unit's track; the model and train operators' window-level
            # spans go through the subtask's hook.
            ctx.tracer = self.tracer
            ctx.spans = st.spans
            # Sanitizer hand-off: remote sinks/sources log cross-process
            # happens-before events (frame send/recv, credit grant/spend)
            # through this at open().
            ctx.sanitizer = self.sanitizer
            # Device-residency hand-off: model functions resolve their
            # emission mode / h2d wire dtype from these at open().
            ctx.device_resident = self.device_resident
            ctx.wire_dtype = self.wire_dtype
            # Remote-plane coalescing defaults (RemoteSink reads these
            # at open() when its own knobs are unset).
            ctx.wire_flush_bytes = self.wire_flush_bytes
            ctx.wire_flush_ms = self.wire_flush_ms
            ctx.flow_control = self.flow_control
            # Chaos-plane hand-off: RemoteSink resolves its per-edge
            # fault hook (sever/blackhole/delay) from this at open().
            ctx.fault_injector = self.faults
            ctx.restart_epoch = self.restart_epoch
            # Roofline hand-off: model runners mint a per-operator probe
            # (static-cost join, roofline.* gauges, compile-event log)
            # from this at open().
            ctx.roofline = self.roofline
            if head_gate is not None:
                # Operator-owned background threads (the model runner's
                # fetch thread) use this to break the CHAIN's event wait
                # when results complete — every fused member wakes the
                # one thread that runs it.
                ctx.wakeup = head_gate.wake
            elif st.mailbox is not None:
                # Split-source chains wait on the mailbox instead of a
                # gate; the same completion wakeup applies to every
                # fused member.
                ctx.wakeup = st.mailbox.notify
            unit.operator.setup(ctx, unit.output, state)
            if pos == 0 and st.mailbox is not None:
                # Wire the reader to its source's coordinator before
                # restore() runs (restored enumerator state flows
                # through the operator into the coordinator).
                coord = self.split_coordinator(unit.t, unit.operator.source)
                unit.operator.attach(coord, unit.index, st.mailbox)
        self.subtasks.append(st)

    def _register_edge_gauges(self, st: _Subtask, head: Transformation,
                              channel_base) -> None:
        """Per-EDGE queue gauges on the record plane: cumulative puts and
        current buffered depth for each input edge of the chain head,
        summed over the edge's channel range.  A chained edge has no
        gate, so its absence from the report IS the zero-queue-traffic
        evidence the latency-floor CI guard asserts."""
        gate = st.gate
        if gate is None:
            return
        grp = self.metrics.group(st.scope)
        for edge_idx, edge in enumerate(head.inputs):
            lo = channel_base[(head.id, edge_idx)]
            span = (1 if isinstance(edge.partitioner, ForwardPartitioner)
                    else edge.upstream.parallelism)
            hi = lo + span
            name = f"edge{edge_idx}_{edge.upstream.name}"
            grp.gauge(f"{name}_queue_puts",
                      lambda g=gate, a=lo, b=hi: sum(g.puts_per_channel[a:b]))
            grp.gauge(f"{name}_queue_depth",
                      lambda g=gate, a=lo, b=hi: sum(
                          max(0, c) for c in g.buffered_per_channel[a:b]))

    def split_coordinator(self, t: Transformation, source):
        """The (lazily created) SplitCoordinator for split source ``t``.
        ``source`` is the shared SplitSource instance (every subtask's
        factory closes over the same one).

        Per-process by construction: a distributed cohort spreading one
        split source's subtasks over several processes would run one
        enumerator per process and double-assign every split — refuse
        rather than duplicate records.
        """
        with self._split_lock:
            coord = self._split_coordinators.get(t.name)
            if coord is None:
                if not all(self._owns_subtask(t, i) for i in range(t.parallelism)):
                    raise ValueError(
                        f"split source {t.name!r}: subtasks are spread over a "
                        "process cohort but the split enumerator is "
                        "per-process — run split sources on a single process "
                        "(or use a legacy SourceFunction for cohort jobs)"
                    )
                from flink_tensorflow_tpu.sources.coordinator import (
                    SplitCoordinator,
                )

                coord = SplitCoordinator(source, t.parallelism,
                                         sanitizer=self.sanitizer, name=t.name)
                self._split_coordinators[t.name] = coord
            return coord

    # --- placement hooks (overridden by DistributedExecutor) -------------
    def _owns_subtask(self, t: Transformation, index: int) -> bool:
        """Whether subtask ``index`` of ``t`` runs in this process."""
        return True

    def _process_identity(self) -> typing.Tuple[int, int]:
        """(process_index, num_processes) of this executor's cohort."""
        return 0, 1

    def _remote_writer(self, t: Transformation, subtask_index: int, channel_idx: int):
        raise RuntimeError(
            f"no gate for {t.name}.{subtask_index} — local executor owns "
            "every subtask, so this is a plan-construction bug"
        )

    # --- restore ---------------------------------------------------------
    def restore(
        self,
        snapshots: typing.Dict[str, typing.Dict[int, typing.Any]],
        from_checkpoint_id: typing.Optional[int] = None,
        *,
        local_shard: bool = False,
    ) -> None:
        """``local_shard=True``: ``snapshots`` holds exactly THIS
        process's subtasks (a distributed same-shape restore from the
        process's own shard — the caller validated the shape against the
        shard's recorded metadata), so each local subtask restores by
        index and the rescale inference must not run (per-task counts
        are local, not the old global parallelism)."""
        if from_checkpoint_id is not None:
            # New checkpoints must never overwrite the restore point.
            self.coordinator.resume_from(from_checkpoint_id)
        job_meta = snapshots.pop("__job__", None)
        if job_meta:
            pinned = job_meta.get(0, {}).get("max_parallelism")
            if pinned is not None and pinned != self.max_parallelism:
                raise ValueError(
                    f"checkpoint was taken with max_parallelism={pinned}; "
                    f"this job uses {self.max_parallelism} — the key-group "
                    "routing would change and orphan keyed state. Restore "
                    "with the original max_parallelism."
                )
        # Restore addresses LOGICAL operators — checkpoints key state by
        # (task name, subtask index), so a job re-planned with a
        # different chaining layout (chaining toggled, escape hatches
        # added) still restores every operator's state correctly.
        by_task: typing.Dict[str, typing.List[_ChainedUnit]] = {}
        for st in self.subtasks:
            for unit in st.units:
                by_task.setdefault(unit.t.name, []).append(unit)
        for task, units in by_task.items():
            task_snaps = snapshots.get(task)
            if task_snaps is None:
                continue
            old_parallelism = len(task_snaps)
            # The NEW parallelism is the transformation's declared one —
            # on a distributed executor the local unit list is only
            # this process's share of it.
            new_parallelism = units[0].t.parallelism
            if local_shard or old_parallelism == new_parallelism:
                for unit in units:
                    snap = task_snaps.get(unit.index)
                    if snap is not None:
                        unit.operator.restore(snap)
            else:
                # Parallelism changed across the restart: redistribute by
                # key group (Flink's rescaling semantics; keyed state only
                # — per-subtask state raises StateNotRescalable).
                for unit in units:
                    unit.operator.restore(
                        unit.operator.rescale(
                            task_snaps, unit.index, new_parallelism,
                            self.max_parallelism,
                        )
                    )
        # Split sources: push restored split/pool state into the
        # per-source coordinators NOW — before any reader thread runs —
        # so the lazily built enumerator always sees it (in-flight
        # splits resume at their offsets; pooled splits redistribute).
        for st in self.subtasks:
            for unit in st.units:
                apply = getattr(unit.operator, "apply_restore", None)
                if apply is not None:
                    apply()

    # --- execution --------------------------------------------------------
    def start(self) -> None:
        if self.flight is not None:
            self.flight.record("job", "start", {
                "subtasks": len(self.subtasks),
                "logical_subtasks": self.total_subtasks,
                "restart_epoch": self.restart_epoch,
            })
            if self.restart_epoch:
                self.flight.record("job", "restart.attempt", {
                    "restart_epoch": self.restart_epoch})
        for st in self.subtasks:
            if not st.t.is_source:
                body = st.run_worker
            elif st.mailbox is not None:
                body = st.run_split_source
            else:
                body = st.run_source
            st.thread = threading.Thread(target=body, name=st.scope, daemon=True)
        for st in self.subtasks:
            st.thread.start()
        if self.pulse is not None:
            self.pulse.start()
        if self.checkpoint_interval_s is not None:
            self._periodic_thread = threading.Thread(
                target=self._periodic_checkpoints, name="checkpoint-timer", daemon=True
            )
            self._periodic_thread.start()

    def _periodic_checkpoints(self) -> None:
        """Flink-style periodic snapshots (SURVEY.md §5 "Checkpoint /
        resume"): trigger an aligned checkpoint every interval until the
        job finishes.  Races with completion/cancellation are benign —
        a trigger landing there just fails and is not retried."""
        interval = self.checkpoint_interval_s
        while not self._all_done.wait(interval) and not self.cancelled.is_set():
            try:
                self.coordinator.trigger(timeout=self.checkpoint_timeout_s)
            except Exception:
                # Catch EVERYTHING: an escaping error (serialization bug,
                # disk full, ...) would otherwise kill this daemon thread
                # silently and the job would run on unpersisted, believing
                # it is being checkpointed.
                if self._all_done.is_set() or self.cancelled.is_set():
                    return
                logger.warning("periodic checkpoint failed", exc_info=True)

    def join(self, timeout: typing.Optional[float] = None) -> None:
        try:
            self._join(timeout)
        finally:
            if self.pulse is not None:
                self.pulse.stop()

    def _join(self, timeout: typing.Optional[float]) -> None:
        deadline = None if timeout is None else time.monotonic() + timeout
        for st in self.subtasks:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            st.thread.join(remaining)
            if st.thread.is_alive():
                self.cancel()
                raise JobTimeout(f"timeout waiting for subtask {st.scope}")
        # Completed count-based checkpoints must be durable before the job
        # reports done (a cohort worker exits right after this returns).
        in_flight = self.coordinator.wait_for_persistence(
            None if deadline is None else max(0.1, deadline - time.monotonic())
        )
        if in_flight:
            raise JobTimeout(
                f"{in_flight} checkpoint persist write(s) did not drain — "
                "completed checkpoints are not yet durable"
            )
        # The persist queue fans notifications out via add_notification,
        # but a notification enqueued after a subtask's loop exited would
        # sit undelivered forever (delivery runs on the subtask thread).
        # All threads are joined and all persist jobs drained here, so
        # the join thread can flush the leftovers without violating the
        # single-writer contract — this is what makes "durable before the
        # job reports done" include the final checkpoint's 2PC commit.
        # Best-effort, Flink-style: this late delivery runs AFTER the
        # operator's close(), so a hook that needs close()-released
        # resources may fail — log and keep flushing the remaining
        # subtasks rather than failing a job that already completed.
        if self._error is None:
            for st in self.subtasks:
                try:
                    st._deliver_notifications()
                except Exception:
                    logger.warning(
                        "post-close checkpoint notification failed for %s",
                        st.scope, exc_info=True,
                    )
        if self._error is not None:
            raise JobFailure(f"job failed: {self._error!r}") from self._error
        if self.sanitizer is not None:
            # The job is drained: any recorded violation is a real
            # protocol/lock-discipline bug — surface it as loudly as a
            # failed job (SanitizerError is NOT a JobFailure: restart
            # strategies must not replay over a concurrency bug).
            self.sanitizer.shutdown()
            try:
                self.sanitizer.check()
            except BaseException:
                if self.flight is not None:
                    self.flight.record("job", "sanitizer.violation", {
                        "violations": len(self.sanitizer.violations)})
                    self.flight_dump("sanitizer")
                self.sanitizer_log_dump("violation")
                raise
            # Clean drain: the happens-before log is the stitcher's
            # input — dump it on SUCCESS too, so `flink-tpu-sanitize
            # --cohort` can prove the run conformant (zero violations is
            # an assertion, not an absence of evidence).
            self.sanitizer_log_dump("shutdown")

    def run(self, timeout: typing.Optional[float] = None) -> None:
        self.start()
        self.join(timeout)

    # --- failure / teardown ----------------------------------------------
    def flight_dump(self, reason: str) -> typing.Optional[str]:
        """Dump the flight ring to the configured path (no-op without a
        recorder or a path); returns the written path.  Each artifact
        references the other: the flight dump carries the sanitizer
        event-log path (and vice versa), so whichever one a responder
        finds first points at the rest of the evidence."""
        if self.flight is None or not self.flight_path:
            return None
        extra = ({"sanitizer_log": self.sanitize_log_path}
                 if self.sanitize_log_path else None)
        path = self.flight.dump(self.flight_path, reason,
                                tracer=self.tracer, extra=extra)
        self.sanitizer_log_dump(reason)
        return path

    def sanitizer_log_dump(self, reason: str) -> typing.Optional[str]:
        """Dump the sanitizer's happens-before event log to the
        configured path (no-op without a sanitizer or a path); returns
        the written path.  Idempotent per reason, like flight_dump."""
        if self.sanitizer is None or not self.sanitize_log_path:
            return None
        extra = ({"flight_dump": self.flight_path}
                 if self.flight is not None and self.flight_path else None)
        return self.sanitizer.dump_hb_log(
            self.sanitize_log_path, reason, extra=extra)

    def fail(self, subtask: _Subtask, exc: BaseException) -> None:
        with self._error_lock:
            first = self._error is None
            if first:
                self._error = exc
        logger.error("subtask %s failed", subtask.scope, exc_info=exc)
        self.cancel()
        if first:
            if self.tracer is not None:
                self.tracer.instant(
                    "job", "failure",
                    args={"subtask": subtask.scope, "error": repr(exc)})
            if self.flight is not None:
                # The black box lands BEFORE any teardown runs further:
                # the ring holds the lifecycle that led here.
                self.flight.record("job", "failure", {
                    "subtask": subtask.scope, "error": repr(exc)})
                self.flight_dump("crash")
            # Crash-time observability: flush the reporter (and any other
            # registered listener) NOW, while the gauges still show the
            # state that produced the failure — the final stop() flush
            # runs after teardown and may be too late or never (a caller
            # that crashes before join()).
            for hook in self.failure_listeners:
                try:
                    hook()
                except Exception:  # noqa: BLE001 - observability only
                    logger.warning("failure listener failed", exc_info=True)

    def cancel(self) -> None:
        self.cancelled.set()
        for gate in self._gates:
            gate.close()
        for st in self.subtasks:
            if st.mailbox is not None:
                # close(), not notify(): the sticky shutdown signal is
                # immune to the notify/park race (a one-shot signal
                # consumed by an unrelated wakeup would strand the loop
                # parked between its cancelled-check and its wait).
                st.mailbox.close()
        self.coordinator.cancel_pending()

    def notify_checkpoint_complete(self, checkpoint_id: int) -> None:
        """Fan a durable-checkpoint notification out to every subtask
        (delivered to each chained operator on the subtask's own thread)."""
        for st in self.subtasks:
            st.add_notification(checkpoint_id)

    def notify_checkpoint_aborted(self, checkpoint_id: int) -> None:
        """Fan a checkpoint ABORT out: subtasks drop the id's alignment
        state (unblocking gates a missing barrier wedged) and split
        coordinators cancel its assignment freeze — the job keeps
        flowing and sources keep triggering later checkpoints."""
        for st in self.subtasks:
            st.add_abort(checkpoint_id)
        with self._split_lock:
            coords = list(self._split_coordinators.values())
        for coord in coords:
            coord.cancel_alignment(checkpoint_id)

    def subtask_finished(self, subtask: _Subtask) -> None:
        if self.flight is not None:
            self.flight.record(subtask.scope, "subtask.finished")
        if subtask.account is not None:
            subtask.account.read()  # the thread's last lines: its sums in all
        self.coordinator.subtask_finished(subtask)
        with self._error_lock:
            self._finished_count += 1
            if self._finished_count >= len(self.subtasks):
                self._all_done.set()
                if self.pulse is not None:
                    # A job nobody joins must not leave its pulse behind.
                    self.pulse.stop(join=False)

    @property
    def total_subtasks(self) -> int:
        """LOGICAL subtask count (one per operator per parallel index) —
        the checkpoint coordinator expects one ack per logical operator
        regardless of how chains pack them onto threads."""
        return sum(len(st.units) for st in self.subtasks)
