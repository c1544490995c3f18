"""Runtime operators — the physical counterparts of logical transformations.

Equivalent of Flink's ``StreamOperator`` layer that hosts the reference's
``ModelFunction`` (SURVEY.md §1 L4/L5).  Each operator instance runs on
exactly one subtask thread (single-writer contract, SURVEY.md §5), processes
stream elements, and participates in the snapshot protocol.

Design note (TPU-first): operators are *host-side* control code.  Anything
numeric happens inside user functions via jitted callables on device; the
operator layer never inspects tensor contents, so Python overhead stays off
the per-FLOP path — one operator invocation per *batch*, not per scalar.
"""

from __future__ import annotations

import collections
import time
import typing

from flink_tensorflow_tpu.core import elements as el
from flink_tensorflow_tpu.core import functions as fn
from flink_tensorflow_tpu.core.state import KeyedStateStore
from flink_tensorflow_tpu.core.windows import Trigger, WindowBuffer

if typing.TYPE_CHECKING:
    from flink_tensorflow_tpu.core.runtime_context import RuntimeContext


class SubtaskStats:
    """Per-subtask accumulators behind the runtime's pull-based gauges.

    Written ONLY by the owning subtask thread (single-writer contract),
    read by the reporter thread — plain float adds, no locks, so the
    per-record cost stays O(1) with zero allocation."""

    __slots__ = ("blocked_s", "idle_s", "busy_s")

    def __init__(self) -> None:
        #: Seconds this subtask's emits spent blocked on full downstream
        #: queues (its backpressure time, Flink's backPressuredTime).
        self.blocked_s = 0.0
        #: Seconds spent waiting on the input gate with nothing to do.
        self.idle_s = 0.0
        #: Seconds spent inside record processing.
        self.busy_s = 0.0


class Output:
    """Downstream emitter for one subtask; routes via edge partitioners.

    ``meter``/``stats`` are optional instrumentation hooks (wired by the
    executor): the meter marks one event per emitted record, and blocked
    write time (returned by the channel layer) accumulates into
    ``stats.blocked_s`` — both O(1) per record.  ``tracer`` (span
    tracing, off by default) stamps the thread's current trace context
    onto the outgoing record with a fresh enqueue timestamp, so the
    downstream subtask can attribute the queue wait."""

    def __init__(self, edges, meter=None, stats: typing.Optional[SubtaskStats] = None,
                 tracer=None):
        # edges: list of (partitioner, [ChannelWriter per downstream subtask])
        self._edges = edges
        self._meter = meter
        self._stats = stats
        self._tracer = tracer

    def emit(self, value: typing.Any, timestamp: typing.Optional[float] = None) -> None:
        record = el.StreamRecord(value, timestamp)
        tracer = self._tracer
        if tracer is not None:
            tctx = tracer.current()
            if tctx is not None:
                record.trace = tracer.fork(tctx, time.monotonic())
        blocked = 0.0
        for partitioner, writers in self._edges:
            for idx in partitioner.select(value, len(writers)):
                # Remote writers return None (their send path has its own
                # accounting); local gates return blocked-put seconds.
                dt = writers[idx].write(record)
                if dt:
                    blocked += dt
        if self._meter is not None:
            self._meter.mark()
        if blocked and self._stats is not None:
            self._stats.blocked_s += blocked

    def broadcast_element(self, element: el.StreamElement) -> None:
        """Barriers / watermarks / EOP go to every downstream channel."""
        for _, writers in self._edges:
            for w in writers:
                dt = w.write(element)
                if dt and self._stats is not None:
                    self._stats.blocked_s += dt

    @property
    def has_downstream(self) -> bool:
        return bool(self._edges)


class StateNotRescalable(RuntimeError):
    """Raised when a restore changes an operator's parallelism but its
    snapshot holds per-subtask state that cannot be redistributed by
    key (source offsets, subtask-scoped train state, non-keyed window
    buffers).  Keep that operator's parallelism fixed across restarts."""


class Operator:
    """Base runtime operator."""

    def __init__(self, name: str):
        self.name = name
        self.ctx: typing.Optional["RuntimeContext"] = None
        self.output: typing.Optional[Output] = None
        self.keyed_state: typing.Optional[KeyedStateStore] = None

    # -- lifecycle -----------------------------------------------------
    def setup(self, ctx: "RuntimeContext", output: Output, keyed_state: KeyedStateStore) -> None:
        self.ctx = ctx
        self.output = output
        self.keyed_state = keyed_state

    def open(self) -> None:  # noqa: B027
        pass

    def close(self) -> None:  # noqa: B027
        pass

    # -- element processing -------------------------------------------
    def process_record(self, record: el.StreamRecord) -> None:
        raise NotImplementedError

    def process_record_from(self, input_index: int, record: el.StreamRecord) -> None:
        """Record dispatch carrying the logical input (edge) index —
        two-input operators (connect/join) override this; single-input
        operators ignore the index."""
        self.process_record(record)

    def process_watermark(self, watermark: el.Watermark) -> None:
        self.output.broadcast_element(watermark)

    def finish(self) -> None:  # noqa: B027
        """End of input: flush any buffered elements (e.g. open windows)."""

    # -- timers (adaptive batching) -------------------------------------
    def next_deadline(self) -> typing.Optional[float]:
        """Earliest monotonic time this operator must be poked, or None."""
        return None

    def fire_due(self, now: float) -> None:  # noqa: B027
        """Called by the subtask loop when ``next_deadline`` has passed."""

    @property
    def uses_timers(self) -> bool:
        """Whether this operator may ever declare a wall-clock deadline
        (``next_deadline``/``fire_due``).  The chaining pass
        (analysis/chaining.py) refuses to fuse timer-driven operators
        into SOURCE chains — a source loop blocks inside the user
        function's sleeps and cannot serve deadlines promptly, while a
        worker chain's loop waits event-driven until the chain's
        earliest deadline."""
        return False

    # -- snapshot protocol ----------------------------------------------
    def snapshot(self, checkpoint_id: typing.Optional[int] = None) -> typing.Dict[str, typing.Any]:
        """``checkpoint_id`` is the id this snapshot belongs to (None for
        the job-end final snapshot) — two-phase-commit sinks bind their
        staged output to it.

        The FUNCTION hook runs FIRST: functions flush in-flight work
        there (pipelined model batches, staged fused training steps),
        and those flushes may update keyed state — capturing keyed
        tables earlier would checkpoint a state missing steps whose
        source records precede the barrier (silent loss on restore).
        """
        function = self._function_snapshot(checkpoint_id)
        return {
            "keyed": self.keyed_state.snapshot(),
            "function": function,
            "operator": self._operator_snapshot(),
        }

    def notify_checkpoint_complete(self, checkpoint_id: int) -> None:  # noqa: B027
        """Checkpoint ``checkpoint_id`` is complete AND durable — the
        commit signal for two-phase sinks (Flink's CheckpointListener).

        Normally delivered on the subtask thread (single-writer
        contract); a checkpoint that completes as the job ends is flushed
        best-effort from the join thread AFTER close() — the operator is
        quiescent then, but hooks must not require close()-released
        resources (a failure there is logged, not raised)."""

    def restore(self, snap: typing.Dict[str, typing.Any]) -> None:
        self.keyed_state.restore(snap["keyed"])
        self._function_restore(snap["function"])
        self._operator_restore(snap["operator"])

    def _function_snapshot(self, checkpoint_id: typing.Optional[int] = None) -> typing.Any:
        return None

    def _function_restore(self, state: typing.Any) -> None:
        pass

    def _operator_snapshot(self) -> typing.Any:
        return None

    def _operator_restore(self, state: typing.Any) -> None:
        pass

    # -- rescaling (restore with a different parallelism) -----------------
    def rescale(
        self,
        old: typing.Dict[int, typing.Any],
        index: int,
        parallelism: int,
        max_parallelism: int,
    ) -> typing.Dict[str, typing.Any]:
        """Build THIS subtask's snapshot from all old subtasks' snapshots.

        Keyed state redistributes by key group (the routing the
        HashPartitioner uses, so state lands where records will);
        function/operator state delegates to the per-operator hooks,
        which raise :class:`StateNotRescalable` for state that is
        inherently per-subtask.
        """
        from flink_tensorflow_tpu.core.partitioning import subtask_for_key

        def mine(key) -> bool:
            return subtask_for_key(key, parallelism, max_parallelism) == index

        snaps = [s for s in old.values() if s is not None]
        keyed: typing.Dict[str, typing.Dict[typing.Any, typing.Any]] = {}
        for snap in snaps:
            for name, table in snap["keyed"].items():
                for key, value in table.items():
                    if mine(key):
                        keyed.setdefault(name, {})[key] = value
        return {
            "keyed": keyed,
            "function": self._rescale_function_state(
                [s["function"] for s in snaps], mine
            ),
            "operator": self._rescale_operator_state(
                [s["operator"] for s in snaps], mine
            ),
        }

    def _rescale_function_state(self, states: typing.List[typing.Any], mine) -> typing.Any:
        if any(s is not None for s in states):
            raise StateNotRescalable(
                f"operator {self.name!r}: function state is per-subtask and "
                "cannot be redistributed — restore with the original parallelism"
            )
        return None

    def _rescale_operator_state(self, states: typing.List[typing.Any], mine) -> typing.Any:
        if any(s is not None for s in states):
            raise StateNotRescalable(
                f"operator {self.name!r}: operator state is per-subtask and "
                "cannot be redistributed — restore with the original parallelism"
            )
        return None


class _FunctionOperator(Operator):
    """Operator wrapping one rich user function."""

    def __init__(self, name: str, function: fn.Function):
        super().__init__(name)
        self.function = function.clone()

    def open(self) -> None:
        if isinstance(self.function, fn.RichFunction):
            self.function.open(self.ctx)

    def close(self) -> None:
        if isinstance(self.function, fn.RichFunction):
            self.function.close()

    def _function_snapshot(self, checkpoint_id=None):
        if isinstance(self.function, fn.RichFunction):
            hook = getattr(self.function, "snapshot_state_for_checkpoint", None)
            if hook is not None:
                return hook(checkpoint_id)
            return self.function.snapshot_state()
        return None

    def _function_restore(self, state):
        if state is not None and isinstance(self.function, fn.RichFunction):
            self.function.restore_state(state)

    def notify_checkpoint_complete(self, checkpoint_id: int) -> None:
        hook = getattr(self.function, "notify_checkpoint_complete", None)
        if hook is not None:
            hook(checkpoint_id)

    def _rescale_function_state(self, states, mine):
        if all(s is None for s in states):
            return None
        hook = getattr(self.function, "rescale_state", None)
        if hook is None:
            raise StateNotRescalable(
                f"operator {self.name!r}: {type(self.function).__name__} "
                "snapshots per-subtask state and defines no rescale_state "
                "hook — restore with the original parallelism"
            )
        return hook(states, mine)


class MapOperator(_FunctionOperator):
    """Hosts a MapFunction, or an AsyncMapFunction with deferred emission.

    For async functions the operator keeps a FIFO of input timestamps and
    re-attaches them positionally as results surface (the function's
    FIFO-order contract), flushes in-flight work at end of input and —
    via ``_function_snapshot`` -> ``snapshot_state`` -> ``flush`` — before
    every barrier, and forwards the idle-flush timer hooks."""

    def __init__(self, name, function):
        super().__init__(name, function)
        self._async = isinstance(self.function, fn.AsyncMapFunction)
        self._collector: typing.Optional[fn.Collector] = None
        self._ts_fifo: typing.Deque[typing.Optional[float]] = collections.deque()

    def open(self) -> None:
        if self._async:
            def emit(value, _ts):
                fifo = self._ts_fifo
                ts = fifo.popleft() if fifo else None
                self.output.emit(value, ts)

            self._collector = fn.Collector(emit)
        super().open()

    def process_record(self, record):
        if self._async:
            self._ts_fifo.append(record.timestamp)
            self.function.map_async(record.value, self._collector)
        else:
            self.output.emit(self.function.map(record.value), record.timestamp)

    def process_watermark(self, watermark):
        # A watermark must not overtake in-flight results: flush the
        # function's buffered/in-flight records first, or downstream
        # event-time operators would see them arrive "late" (< watermark)
        # and drop them.  Consequence (documented on ModelMapFunction):
        # watermark_every=1 upstream degrades the transparent
        # micro-batch to batch-of-1 — choose watermark_every >= the
        # micro_batch when an event-time pipeline feeds an async map.
        if self._async:
            self.function.flush(self._collector)
        super().process_watermark(watermark)

    def finish(self):
        if self._async:
            self.function.flush(self._collector)

    def _function_snapshot(self, checkpoint_id=None):
        # Enforce the AsyncMapFunction barrier contract AT the operator:
        # everything in flight is emitted before the snapshot regardless
        # of whether the function's own snapshot_state also flushes.
        # After the flush the timestamp FIFO is empty, so there is no
        # operator-side state left to snapshot.
        if self._async:
            self.function.flush(self._collector)
        return super()._function_snapshot(checkpoint_id)

    def next_deadline(self):
        return self.function.next_deadline() if self._async else None

    def fire_due(self, now):
        if self._async:
            self.function.fire_due(now)

    @property
    def uses_timers(self):
        return self._async


class FlatMapOperator(_FunctionOperator):
    def process_record(self, record):
        for out in self.function.flat_map(record.value):
            self.output.emit(out, record.timestamp)


class FilterOperator(_FunctionOperator):
    def process_record(self, record):
        if self.function.filter(record.value):
            self.output.emit(record.value, record.timestamp)


class ProcessOperator(_FunctionOperator):
    """Hosts a ProcessFunction; keyed if ``key_selector`` is set."""

    def __init__(self, name, function, key_selector=None):
        super().__init__(name, function)
        self.key_selector = key_selector
        self._collector: typing.Optional[fn.Collector] = None
        self._pctx: typing.Optional[fn.ProcessContext] = None
        self._timers: typing.Dict[typing.Tuple[typing.Any, float], None] = {}

    def open(self) -> None:
        self._collector = fn.Collector(self.output.emit)
        self._pctx = fn.ProcessContext(self)
        super().open()

    # ProcessContext runtime hooks -------------------------------------
    def get_value_state(self, descriptor):
        return self.keyed_state.value_state(descriptor)

    def register_timer(self, key, timestamp: float) -> None:
        self._timers[(key, timestamp)] = None

    @property
    def uses_timers(self):
        return True  # the ProcessContext may register timers at any record

    def process_record(self, record):
        if self.key_selector is not None:
            key = self.key_selector(record.value)
            self.keyed_state.current_key = key
            self._pctx.current_key = key
        self._pctx.timestamp = record.timestamp
        self.function.process_element(record.value, self._pctx, self._collector)

    def finish(self):
        self.function.on_finish(self._collector)

    def next_deadline(self):
        if not self._timers:
            return None
        return min(ts for (_, ts) in self._timers)

    def fire_due(self, now):
        due = [(k, ts) for (k, ts) in self._timers if ts <= now]
        for key, ts in sorted(due, key=lambda x: x[1]):
            del self._timers[(key, ts)]
            self.keyed_state.current_key = key
            self._pctx.current_key = key
            self._pctx.timestamp = ts
            self.function.on_timer(ts, self._pctx, self._collector)

    def _operator_snapshot(self):
        return {"timers": list(self._timers.keys())}

    def _operator_restore(self, state):
        self._timers = {tuple(t): None for t in state["timers"]}

    def _rescale_operator_state(self, states, mine):
        timers = []
        for s in states:
            if s:
                timers.extend(tuple(t) for t in s["timers"])
        if timers and self.key_selector is None:
            raise StateNotRescalable(
                f"operator {self.name!r}: non-keyed timers are per-subtask"
            )
        return {"timers": [t for t in timers if mine(t[0])]}


class CoMapOperator(_FunctionOperator):
    """Two-input map: input 0 -> map1, input 1 -> map2."""

    def process_record(self, record):  # pragma: no cover - indexed dispatch only
        raise RuntimeError("two-input operator requires process_record_from")

    def process_record_from(self, input_index, record):
        f = self.function.map1 if input_index == 0 else self.function.map2
        self.output.emit(f(record.value), record.timestamp)


class CoFlatMapOperator(_FunctionOperator):
    def process_record(self, record):  # pragma: no cover - indexed dispatch only
        raise RuntimeError("two-input operator requires process_record_from")

    def process_record_from(self, input_index, record):
        f = self.function.flat_map1 if input_index == 0 else self.function.flat_map2
        for out in f(record.value):
            self.output.emit(out, record.timestamp)


class CoProcessOperator(_FunctionOperator):
    """Two-input process function; keyed when both key selectors are set
    (both inputs must be partitioned by the SAME key space)."""

    def __init__(self, name, function, key_selector1=None, key_selector2=None):
        super().__init__(name, function)
        if (key_selector1 is None) != (key_selector2 is None):
            raise ValueError("connect: key both inputs or neither")
        self.key_selector1 = key_selector1
        self.key_selector2 = key_selector2
        self._collector: typing.Optional[fn.Collector] = None
        self._pctx: typing.Optional[fn.ProcessContext] = None
        self._timers: typing.Dict[typing.Tuple[typing.Any, float], None] = {}

    def open(self) -> None:
        self._collector = fn.Collector(self.output.emit)
        self._pctx = fn.ProcessContext(self)
        super().open()

    def get_value_state(self, descriptor):
        return self.keyed_state.value_state(descriptor)

    def register_timer(self, key, timestamp: float) -> None:
        self._timers[(key, timestamp)] = None

    @property
    def uses_timers(self):
        return True  # the ProcessContext may register timers at any record

    def process_record(self, record):  # pragma: no cover - indexed dispatch only
        raise RuntimeError("two-input operator requires process_record_from")

    def process_record_from(self, input_index, record):
        selector = self.key_selector1 if input_index == 0 else self.key_selector2
        if selector is not None:
            key = selector(record.value)
            self.keyed_state.current_key = key
            self._pctx.current_key = key
        self._pctx.timestamp = record.timestamp
        handler = (
            self.function.process_element1 if input_index == 0
            else self.function.process_element2
        )
        handler(record.value, self._pctx, self._collector)

    def finish(self):
        self.function.on_finish(self._collector)

    def next_deadline(self):
        if not self._timers:
            return None
        return min(ts for (_, ts) in self._timers)

    def fire_due(self, now):
        due = [(k, ts) for (k, ts) in self._timers if ts <= now]
        for key, ts in sorted(due, key=lambda x: x[1]):
            del self._timers[(key, ts)]
            self.keyed_state.current_key = key
            self._pctx.current_key = key
            self._pctx.timestamp = ts
            self.function.on_timer(ts, self._pctx, self._collector)

    def _operator_snapshot(self):
        return {"timers": list(self._timers.keys())}

    def _operator_restore(self, state):
        self._timers = {tuple(t): None for t in state["timers"]}

    def _rescale_operator_state(self, states, mine):
        timers = []
        for s in states:
            if s:
                timers.extend(tuple(t) for t in s["timers"])
        if timers and self.key_selector1 is None:
            raise StateNotRescalable(
                f"operator {self.name!r}: non-keyed timers are per-subtask"
            )
        return {"timers": [t for t in timers if mine(t[0])]}


class WindowOperator(_FunctionOperator):
    """Count/timeout windows per key (or per subtask when non-keyed).

    This operator IS the micro-batcher: a fired window hands its elements
    to a WindowFunction in one call — the TPU path's single jitted
    ``[B, ...]`` invocation (SURVEY.md §3.2).
    """

    GLOBAL_KEY = "__subtask__"

    def __init__(self, name, function: fn.WindowFunction, trigger: Trigger, key_selector=None):
        super().__init__(name, function)
        # Parallel subtasks each construct their own operator from the
        # shared factory closure — clone the trigger so ones carrying
        # mutable estimator state (AdaptiveLatencyTrigger) don't race.
        self.trigger = trigger.clone()
        self.key_selector = key_selector
        self._buffers: typing.Dict[typing.Any, WindowBuffer] = {}
        self._window_seq: typing.Dict[typing.Any, int] = {}
        self._collector: typing.Optional[fn.Collector] = None
        self._svc_feed = None       # resolved in open()

    def open(self) -> None:
        self._collector = fn.Collector(self.output.emit)
        super().open()
        # Budget-targeting triggers reserve the observed service time out
        # of their latency budget; wire the function's runner EWMA to the
        # trigger when both sides speak the protocol (resolved once —
        # this touches the per-record hot path).
        observe = getattr(self.trigger, "observe_service_time", None)
        estimate = getattr(self.function, "service_time_estimate", None)
        self._svc_feed = (
            (estimate, observe) if observe is not None and estimate is not None
            else None
        )

    def _feed_service_time(self) -> None:
        if self._svc_feed is not None:
            est = self._svc_feed[0]()
            if est is not None:
                self._svc_feed[1](est)

    def _key_of(self, value):
        return self.key_selector(value) if self.key_selector is not None else self.GLOBAL_KEY

    def process_record(self, record):
        key = self._key_of(record.value)
        buf = self._buffers.get(key)
        if buf is None:
            from flink_tensorflow_tpu.core.windows import CountWindow

            seq = self._window_seq.get(key, 0)
            buf = WindowBuffer(window=CountWindow(seq))
            self._buffers[key] = buf
        value = record.value
        # Zero-copy ingestion: tensor window functions may take the record
        # payload NOW (into their ring arena) and buffer only a token —
        # non-keyed only, and never for retaining (sliding) triggers:
        # fired slots recycle their payload, but a retained element must
        # survive into the next window.
        ingest = getattr(self.function, "ingest_element", None)
        if ingest is not None and self.key_selector is None and not self.trigger.retains():
            token = ingest(value, self._collector)
            if token is not None:
                value = token
        buf.add(value, record.timestamp)
        self._feed_service_time()
        if self.trigger.on_element(buf):
            self._fire(key, buf)

    def _fire(self, key, buf: WindowBuffer) -> None:
        del self._buffers[key]
        seq = self._window_seq.get(key, 0) + 1
        self._window_seq[key] = seq
        if self.key_selector is not None:
            self.keyed_state.current_key = key
        self.function.process_window(
            key if self.key_selector is not None else None,
            buf.window,
            self.trigger.fire_elements(buf),
            self._collector,
        )
        # Sliding windows: seed the next buffer with the trailing overlap.
        keep = self.trigger.retain_count(buf)
        if keep:
            from flink_tensorflow_tpu.core.windows import CountWindow

            nxt = WindowBuffer(window=CountWindow(seq), retained=keep)
            nxt.elements = list(buf.elements[-keep:])
            nxt.timestamps = list(buf.timestamps[-keep:])
            nxt.first_element_time = time.monotonic()
            self._buffers[key] = nxt

    @property
    def uses_timers(self):
        return (self.trigger.has_deadlines()
                or getattr(self.function, "next_deadline", None) is not None)

    def next_deadline(self):
        deadlines = [
            d for d in (self.trigger.deadline(buf) for buf in self._buffers.values()) if d is not None
        ]
        # Functions with async in-flight work (pipelined model batches)
        # declare their own wake-up so results never strand in a lull.
        fn_deadline = getattr(self.function, "next_deadline", None)
        if fn_deadline is not None and (d := fn_deadline()) is not None:
            deadlines.append(d)
        return min(deadlines) if deadlines else None

    def fire_due(self, now):
        self._feed_service_time()
        due = [
            key
            for key, buf in self._buffers.items()
            if (d := self.trigger.deadline(buf)) is not None and d <= now
        ]
        for key in due:
            self._fire(key, self._buffers[key])
        fn_fire = getattr(self.function, "fire_due", None)
        if fn_fire is not None:
            fn_fire(now)

    def finish(self):
        for key in list(self._buffers.keys()):
            buf = self._buffers[key]
            # A buffer holding ONLY carried-over elements (sliding
            # retention) has emitted everything already — re-firing it
            # would duplicate; flush only windows with new arrivals.
            if len(buf.elements) > buf.retained:
                self._fire(key, buf)
        self._buffers.clear()
        self.function.on_finish(self._collector)

    def _operator_snapshot(self):
        from flink_tensorflow_tpu.core.windows import snapshot_buffers

        # Ring tokens hold no payload: copy buffered records out of the
        # arena so the snapshot is self-contained (the post-snapshot run
        # continues on the materialized values; fresh elements re-enter
        # the ring).
        materialize = getattr(self.function, "materialize_tokens", None)
        if materialize is not None:
            for buf in self._buffers.values():
                buf.elements = materialize(buf.elements)
        return {"buffers": snapshot_buffers(self._buffers), "seq": dict(self._window_seq)}

    def _operator_restore(self, state):
        from flink_tensorflow_tpu.core.windows import restore_buffers

        self._buffers = restore_buffers(state["buffers"])
        self._window_seq = dict(state["seq"])

    def _rescale_operator_state(self, states, mine):
        buffers, seq = {}, {}
        for s in states:
            if not s:
                continue
            for key, payload in s["buffers"].items():
                if key == self.GLOBAL_KEY:
                    raise StateNotRescalable(
                        f"operator {self.name!r}: non-keyed window buffers are "
                        "per-subtask — restore with the original parallelism"
                    )
                if mine(key):
                    buffers[key] = payload
            for key, n in s["seq"].items():
                if key != self.GLOBAL_KEY and mine(key):
                    seq[key] = max(seq.get(key, 0), n)
        return {"buffers": buffers, "seq": seq}


class SinkOperator(_FunctionOperator):
    def process_record(self, record):
        self.function.invoke(record.value)

    def process_watermark(self, watermark):
        pass  # terminal

    def finish(self):
        # Transactional sinks commit their tail on clean end-of-input
        # (close() alone must stay cancel-safe and commit nothing).
        hook = getattr(self.function, "finish", None)
        if hook is not None:
            hook()


class SourceOperator(_FunctionOperator):
    """Replayable source: tracks an offset, skips on restore.

    Mirrors Flink's source-with-offset contract that makes the aligned
    snapshots exactly-once end to end (SURVEY.md §5 "Checkpoint / resume").
    """

    def __init__(self, name, function: fn.SourceFunction):
        super().__init__(name, function)
        self.offset = 0
        self._restored_offset = 0

    def iterate(self) -> typing.Iterator[typing.Any]:
        """Yields values; the caller must call :meth:`record_emitted` after
        each downstream emit so a barrier between yield and emit never
        counts the in-flight record as already emitted."""
        # Replay: skip records already emitted before the restored snapshot.
        # Sources that know how to reposition (e.g. PacedSource, which must
        # not re-run its sleep schedule for skipped records) expose seek();
        # everything else replays by consuming the iterator.
        if self._restored_offset and hasattr(self.function, "seek"):
            self.function.seek(self._restored_offset)
            it = self.function.run()
        else:
            it = self.function.run()
            skipped = 0
            while skipped < self._restored_offset:
                v = next(it, None)
                if v is None:
                    break
                if isinstance(v, el.SourceIdle):
                    continue  # heartbeat, not a record — must not count
                skipped += 1
        self.offset = self._restored_offset
        yield from it

    def record_emitted(self) -> None:
        self.offset += 1

    def process_record(self, record):  # pragma: no cover - sources have no input
        raise RuntimeError("SourceOperator has no input")

    def _operator_snapshot(self):
        return {"offset": self.offset}

    def _operator_restore(self, state):
        self._restored_offset = state["offset"]

    def rescale(self, old, index, parallelism, max_parallelism):
        raise StateNotRescalable(
            f"source {self.name!r}: offsets are bound to the source's record "
            "partitioning (subtask i emits every P-th record) — changing "
            "source parallelism invalidates them; keep source parallelism "
            "fixed and rescale the keyed operators downstream"
        )
