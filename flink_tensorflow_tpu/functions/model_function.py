"""ModelFunction / GraphFunction — models as stream operators.

The reference's core bridge (BASELINE.json:5; SURVEY.md §2 row 7):
``ModelFunction`` wraps a loaded model in a Flink rich function —
``open()`` loads the model and opens a Session, ``map``/``process``
invokes it, ``close()`` releases it.  Same lifecycle here, with the TF
session replaced by a :class:`CompiledMethodRunner` (params in HBM + XLA
executables per bucket):

- :class:`ModelMapFunction` — per-record inference for ``stream.map``
  (SURVEY.md §3.1).  Each record rides a batch-of-1 executable; for
  throughput prefer the windowed form.
- :class:`ModelWindowFunction` — micro-batch inference for
  ``stream.count_window(B).apply(...)`` (SURVEY.md §3.2): the fired
  window becomes ONE jitted call on a ``[B, ...]`` bucket.
- :class:`GraphMapFunction` / :class:`GraphWindowFunction` — same two
  modes over a **frozen function** (GraphLoader artifact, weights baked
  in), for deployments that ship compiled artifacts instead of bundles.

Model sources are lazy: pass a bundle path or a loader, and each subtask
materializes its own replica at ``open()`` — operator parallelism N gives
N independent model replicas, the reference's inference-DP story
(SURVEY.md §2 "Parallelism strategies").
"""

from __future__ import annotations

import time
import typing

import numpy as np

from flink_tensorflow_tpu.core import functions as fn
from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner
from flink_tensorflow_tpu.models.base import Model
from flink_tensorflow_tpu.models.loaders import GraphLoader, SavedModelLoader
from flink_tensorflow_tpu.tensors.batching import BucketLadder, BucketPolicy
from flink_tensorflow_tpu.tensors.coercion import coerce
from flink_tensorflow_tpu.tensors.value import TensorValue
from flink_tensorflow_tpu.tracing.flight import charged

ModelSource = typing.Union[Model, str, SavedModelLoader, typing.Callable[[], Model]]

#: Sentinel: output-schema derivation not attempted yet (None is a
#: legitimate cached answer — "tried, unknowable").
_UNKNOWN = object()


def _resolve(source: ModelSource) -> Model:
    if isinstance(source, Model):
        return source
    if isinstance(source, str):
        return SavedModelLoader(source).load()
    if isinstance(source, SavedModelLoader):
        return source.load()
    if callable(source):
        return source()
    raise TypeError(f"cannot resolve model source {type(source).__name__}")


class _ModelFunctionBase(fn.RichFunction):
    #: Plan-analyzer marker: records entering this function cross into
    #: jitted, static-shape code (see flink_tensorflow_tpu.analysis).
    is_jit_boundary = True

    #: Device-residency capability markers (analysis/chaining.py +
    #: executor wiring): this function both PRODUCES device batches (its
    #: runner can elide the fetch) and CONSUMES them (subclasses feed
    #: upstream DeviceArrays straight into their jitted call).
    device_capable = True
    accepts_device_batches = True

    def __init__(
        self,
        model: ModelSource,
        method: str = "serve",
        *,
        policy: typing.Optional[BucketPolicy] = None,
        warmup_batches: typing.Sequence[int] = (),
        warmup_length_bucket: int = 128,
        donate_inputs: bool = False,
        outputs: typing.Optional[typing.Sequence[str]] = None,
        transfer_lanes: int = 1,
        device_resident: typing.Optional[bool] = None,
        wire_dtype: typing.Optional[str] = None,
        sharding_axes: typing.Optional[typing.Sequence[str]] = None,
        output_sharding_axes: typing.Optional[typing.Sequence[str]] = None,
    ):
        self._source = model
        self._method_name = method
        #: Declared SPMD layouts for the plan analyzers (chaining's
        #: sharding-conflict rule reads ``sharding_axes``; shardcheck's
        #: reshard audit compares upstream ``output_sharding_axes``
        #: against the consumer's input axes).  ``output_sharding_axes``
        #: defaults to the input axes — a jit unit that changes its batch
        #: layout (e.g. gathers model-parallel shards) declares it here.
        if sharding_axes is not None:
            self.sharding_axes = tuple(sharding_axes)
        self.output_sharding_axes = (
            tuple(output_sharding_axes) if output_sharding_axes is not None
            else (tuple(sharding_axes) if sharding_axes is not None else None))
        self._policy = policy
        self._warmup = tuple(warmup_batches)
        self._warmup_length_bucket = warmup_length_bucket
        self._donate = donate_inputs
        self._outputs = outputs
        self._transfer_lanes = transfer_lanes
        #: Device-resident emission: True forces DeviceBatch output,
        #: False forces host records, None (default) follows
        #: JobConfig.device_resident AND the executor's chained-consumer
        #: hint (emission only pays off when the next chained operator
        #: actually consumes device batches).
        self._device_resident = device_resident
        #: Compact h2d wire dtype ("bf16"/"f16"); None follows
        #: JobConfig.wire_dtype.
        self._wire_dtype = wire_dtype
        #: Set by the executor (core/runtime._wire_units) when the next
        #: CHAINED operator declares accepts_device_batches.
        self._device_chain_hint = False
        self.runner: typing.Optional[CompiledMethodRunner] = None
        self._out: typing.Optional[fn.Collector] = None
        self._derived_schema: typing.Any = _UNKNOWN
        #: Window-level span hook + track and the operator's metric group
        #: (from ctx at open; tracing/flight.py lists the spans).
        self._spans = None
        self._track: typing.Optional[str] = None
        self._metrics = None
        #: Seconds spent in :meth:`_emit` so far (plain sum: the ``fill``
        #: span takes its children's share from it, with no clock read
        #: per record).
        self._emit_total_s = 0.0

    # -- plan-time hooks (no model load, no device work) ------------------
    def plan_input_schema(self):
        """The model method's input RecordSchema when it is knowable
        without loading anything: only for an already-resolved Model.
        Lazy sources (bundle paths, loaders, factories) return None —
        the analyzer treats the contract as unknown rather than paying
        a load at plan time."""
        if isinstance(self._source, Model):
            try:
                return self._source.method(self._method_name).input_schema
            except KeyError:
                return None
        return None

    def output_schema(self, input_schema):
        """Plan-analyzer hook: validate the incoming record schema
        against the model method's declared inputs, then DERIVE the
        output schema abstractly via ``jax.eval_shape`` over the input
        schema's batched struct — shape propagation without compiling or
        touching a device (the same AOT posture as the rest of the
        analyzer).  Lazy model sources (bundle paths, loaders) and
        methods whose tracing fails stay unknown (None) rather than
        failing the plan."""
        from flink_tensorflow_tpu.tensors.schema import check_compatible

        expected = self.plan_input_schema()
        if expected is not None and input_schema is not None:
            check_compatible(expected, input_schema,
                             where=f"model method {self._method_name!r}")
        return self._derive_output_schema()

    def _derive_output_schema(self):
        """Output RecordSchema via ``jax.eval_shape`` (cached), or None.

        Only for resolved Models (lazy sources would pay a load at plan
        time) whose method takes no per-record lengths — the lengths
        side input has no schema slot to trace from.  Dynamic input dims
        trace at the warmup length bucket: bucketing pins them before
        anything reaches XLA, so the bucketed trace IS the runtime
        shape contract (dims the method carries through un-reduced stay
        that bucket size in the derived schema).
        """
        if self._derived_schema is not _UNKNOWN:
            return self._derived_schema
        self._derived_schema = None
        expected = self.plan_input_schema()
        if expected is None or not isinstance(self._source, Model):
            return None
        try:
            method = self._source.method(self._method_name)
            if method.needs_lengths:
                return None
            import jax
            import numpy as np

            from flink_tensorflow_tpu.tensors.schema import RecordSchema, TensorSpec

            shapes = expected.resolve_dynamic(self._warmup_length_bucket)
            struct = {
                name: jax.ShapeDtypeStruct((1, *shapes[name]), spec.dtype)
                for name, spec in expected.fields.items()
            }
            # The params go in as an argument: closed over, their concrete
            # leaves would be computed on wherever the trace touches them
            # alone (a cast of a device-resident table, eagerly).
            outputs = jax.eval_shape(method.fn, self._source.params, struct)
            names = self._outputs or method.output_names or sorted(outputs)
            fields = {}
            for name in names:
                out = outputs[name]
                if not out.shape or out.shape[0] != 1:
                    return None  # not batch-major: no per-record schema
                fields[name] = TensorSpec(tuple(out.shape[1:]),
                                          np.dtype(out.dtype))
            self._derived_schema = RecordSchema(fields)
        except Exception:  # noqa: BLE001 - plan-time best effort, never fatal
            self._derived_schema = None
        return self._derived_schema

    def plan_policy(self):
        """The bucket policy the runner will resolve at open()."""
        return self._policy or BucketPolicy()

    def service_time_estimate(self) -> typing.Optional[float]:
        """EWMA of the per-batch service time (dispatch -> results on
        host).  Budget-targeting triggers reserve this out of their
        latency budget (WindowOperator feeds it to the trigger)."""
        return self.runner.service_ewma_s if self.runner is not None else None

    def _poll_collect(self, now: float) -> None:
        """Shared timer-poll body: emit every batch the runner's fetch
        thread has completed.  Never blocks — the blocking d2h round
        trip runs on the fetch thread (r5), which also retired the r4
        stall fallback here: that fallback existed for backends whose
        ``is_ready`` never reports (and its one-batch-per-poll drain was
        ADVICE r4's third finding), but the fetch thread does not
        consult readiness at all — a blocking fetch IS the completion
        signal, so results cannot strand behind a readiness lie."""
        if self.runner is None or self._out is None:
            return
        self._emit(self.runner.collect_batches(), self._out)

    def _emit(self, batches, out: fn.Collector) -> None:
        """Hand collected batches (``runner.collect_batches``) downstream:
        one ``emit`` span and one ``emit_s`` update a fetched batch (the
        chained consumers' own work included, as the chain runs it)."""
        account = self._spans.account() if self._spans is not None else None
        for seq, records in batches:
            if account is not None:
                charge = account.read()
            t0 = time.monotonic()
            for record in records:
                out.collect(record)
            t1 = time.monotonic()
            self._emit_total_s += t1 - t0
            if self._metrics is not None:
                self._metrics.timer("emit_s").update(t1 - t0)
            if account is not None:
                self._spans.span(self._track, "emit", t0, t1, charged(
                    {"seq": seq, "records": len(records)}, charge, account.read()))

    def clone(self) -> "fn.Function":
        # Subtasks share the host-side source (read-only); each builds its
        # own runner/device placement at open().  Deepcopying params per
        # subtask would multiply host RAM by parallelism for nothing.
        import copy

        dup = copy.copy(self)
        dup.runner = None
        dup._out = None
        return dup

    def open(self, ctx) -> None:
        t_open = time.monotonic()
        self._metrics = getattr(ctx, "metrics", None)
        self._spans = getattr(ctx, "spans", None)
        if self._spans is not None:
            self._track = f"{ctx.task_name}.{ctx.subtask_index}"
            account = self._spans.account()
            charge = account.read()
        model = _resolve(self._source)
        wire = (self._wire_dtype if self._wire_dtype is not None
                else getattr(ctx, "wire_dtype", None))
        self.runner = CompiledMethodRunner(
            model,
            self._method_name,
            policy=self._policy,
            donate_inputs=self._donate,
            output_names=self._outputs,
            dispatch_lanes=self._transfer_lanes,
            wire_dtype=wire,
        )
        self.runner.open(ctx)
        # Device-resident emission: explicit kwarg wins; otherwise the
        # job-wide mode applies only where the executor marked the next
        # chained operator as a device-batch consumer (emitting into a
        # host-only consumer would just move the same fetch onto the
        # subtask thread and lose the background-fetch overlap).
        if self._device_resident is not None:
            self.runner.emit_device_batches = self._device_resident
        else:
            self.runner.emit_device_batches = bool(
                getattr(ctx, "device_resident", False)
                and self._device_chain_hint)
        # Completed results wake the subtask loop immediately (instead of
        # waiting out the poll interval) when the runtime provides a
        # gate wakeup hook.
        self.runner.on_results_ready = getattr(ctx, "wakeup", None)
        # Before the warm-up: where the subclass has the runner take its
        # batches in chunks, the warm-up compiles that signature.
        self._open_buffers()
        if self._warmup:
            self.runner.warmup(self._warmup, self._warmup_length_bucket)
        # The ``open`` span (children: the runner's ``params_to_device``
        # and ``jit_warmup_compile``) and ``open_s``.  Whether the compile
        # cache was hit is not recorded: jax tells only a process-wide
        # listener.
        now = time.monotonic()
        if self._metrics is not None:
            self._metrics.timer("open_s").update(now - t_open)
        if self._spans is not None:
            self._spans.span(self._track, "open", t_open, now,
                             charged({}, charge, account.read()))

    def _open_buffers(self) -> None:
        """Subclass hook: the rest of ``open()``, inside its span."""

    def close(self) -> None:
        if self.runner is not None:
            self.runner.close()
            self.runner = None


class ModelMapFunction(_ModelFunctionBase, fn.AsyncMapFunction):
    """Per-record inference: ``stream.map(ModelMapFunction(bundle))``.

    The reference's flagship idiom (SURVEY.md §3.1) — but NOT one
    synchronous device round trip per record: arriving records accumulate
    into a transparent micro-batch (at most ``micro_batch``, dispatched
    the moment it fills) and up to ``pipeline_depth`` batches ride the
    runner's dispatch/collect pipeline concurrently, so the wire transfer
    of batch k+1 overlaps the device compute of batch k exactly like the
    windowed path.  Results surface in arrival order.  A lull flushes the
    partial batch after ``idle_flush_s`` (the map stays a per-record
    operator: latency is bounded by the flush timer, not by batch fill),
    and end-of-input / snapshot barriers flush everything in flight.

    ``micro_batch=1`` recovers strict per-record dispatch — still
    pipelined, so throughput is bounded by ``pipeline_depth / RTT``
    rather than ``1 / RTT``.

    Buckets: partial flushes assemble to the smallest policy bucket
    >= the buffered count (powers of two up to ``micro_batch`` by
    default), padding the remainder, so a flush never recompiles.

    **Watermark interaction (ADVICE r3):** the enclosing operator
    flushes the in-flight micro-batch before forwarding every
    watermark — required for event-time safety (results must not
    arrive "late" behind the watermark that covers them).  With
    fine-grained watermarks (``assign_timestamps(watermark_every=1)``)
    this degrades transparent micro-batching to batch-of-1 dispatch.
    If the downstream has no event-time operators, drop the timestamp
    assigner; otherwise use ``watermark_every >= micro_batch`` so
    flushes land on batch boundaries and the pipelined path keeps its
    throughput.
    """

    def __init__(self, model: ModelSource, method: str = "serve", *,
                 micro_batch: int = 8,
                 pipeline_depth: typing.Optional[int] = None,
                 idle_flush_s: float = 0.01, **kw):
        if micro_batch < 1:
            raise ValueError(f"micro_batch must be >= 1, got {micro_batch}")
        if "policy" not in kw:
            kw["policy"] = BucketPolicy(batch=BucketLadder.up_to(micro_batch))
        super().__init__(model, method, **kw)
        if pipeline_depth is None:
            pipeline_depth = max(2, 2 * self._transfer_lanes)
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self._micro_batch = micro_batch
        self._max_in_flight = pipeline_depth - 1
        self._idle_flush_s = idle_flush_s
        self._buf: typing.List[typing.Any] = []
        self._last_activity: typing.Optional[float] = None
        self._last_poll: typing.Optional[float] = None

    def clone(self) -> "fn.Function":
        dup = super().clone()
        dup._buf = []
        dup._last_activity = None
        dup._last_poll = None
        return dup

    def map_async(self, value, out: fn.Collector):
        self._out = out
        if getattr(value, "is_device_batch", False):
            # HBM-resident handoff from the upstream chained model: the
            # batch bypasses the host micro-batch buffer entirely and
            # feeds the jitted call as-is (no d2h upstream, no h2d
            # here).  Flush the host buffer FIRST so emission order
            # stays arrival order (the runner collects FIFO).
            self._dispatch_buf()
            if not self.runner.dispatch_device(value):
                # Schema-incompatible batch: pay the fetch at this
                # boundary and take the host path in bucket-sized chunks.
                records = value.materialize()
                for i in range(0, len(records), self._micro_batch):
                    self.runner.dispatch(records[i:i + self._micro_batch])
        else:
            self._buf.append(value)
            if len(self._buf) >= self._micro_batch:
                self._dispatch_buf()
        self._last_activity = time.monotonic()
        self._emit(self.runner.collect_batches(self._max_in_flight), out)

    def _dispatch_buf(self):
        if self._buf:
            self.runner.dispatch(self._buf)
            self._buf = []

    def flush(self, out: typing.Optional[fn.Collector] = None):
        out = out if out is not None else self._out
        self._dispatch_buf()
        if self.runner is not None and out is not None:
            self._emit(self.runner.collect_batches(0), out)

    # -- latency bound in a lull (MapOperator timer hooks) ---------------
    # Same poll-don't-block discipline as the windowed path: the idle
    # deadline DISPATCHES the partial micro-batch (the latency bound on
    # buffered records), then emits whatever is ready without parking
    # the subtask thread for the device round trip.
    def _idle_deadline(self) -> typing.Optional[float]:
        """The idle-flush deadline proper: when the buffered partial
        micro-batch must dispatch (the latency bound on buffered
        records)."""
        if self._last_activity is None:
            return None
        if not self._buf and not (self.runner and self.runner._pending):
            return None
        base = self._last_activity
        if self._last_poll is not None and self._last_poll > base:
            base = self._last_poll
        return base + self._idle_flush_s

    def next_deadline(self) -> typing.Optional[float]:
        # Fetched results waiting: due IMMEDIATELY — 0.0 is in the past
        # on the monotonic clock, so the caller's earlier `now` still
        # satisfies `now >= deadline` (a fresh monotonic() here could
        # exceed it and skip the fire).  The fetch thread also pokes the
        # gate via on_results_ready, so the loop re-checks within one
        # poll rather than one idle_flush interval.
        if self.runner is not None and self.runner.has_completed():
            return 0.0
        return self._idle_deadline()

    def fire_due(self, now: float) -> None:
        d = self.next_deadline()
        if d is None or now < d:
            return
        # Dispatch the partial buffer only when the IDLE deadline proper
        # expired — a completion-driven wake (deadline 0.0) must drain
        # results, not force half-full micro-batches out at every batch
        # completion (that would defeat micro-batching under steady
        # load: each completion would flush a partial, padded batch).
        idle = self._idle_deadline()
        if idle is not None and now >= idle:
            self._dispatch_buf()
        self._poll_collect(now)
        self._last_poll = now

    def on_finish(self, out: fn.Collector):
        self.flush(out)

    def snapshot_state(self):
        # Barrier alignment: everything buffered or in flight is emitted
        # BEFORE the snapshot, so no result is in limbo across restore.
        self.flush()
        return None


class _RingToken:
    """Placeholder in the window buffer for a record whose payload lives in
    the ring arena (zero-copy path); carries only the record's metadata."""

    __slots__ = ("meta",)

    def __init__(self, meta):
        self.meta = meta


class _EarlyWindow:
    """The window now filling on the ring path, as far as it has been
    shipped ahead of its fire: ``fill`` ring tokens ingested, ``rows`` of
    them claimed (``claims``: ``({field: [n, ...] view}, n)`` a claim,
    oldest first), the whole chunks among them on their way to the device
    (``device``).  ``off``: nothing more is shipped early — a claim came
    back short at the arena's end, or a record of the window was
    list-buffered — and the window is copied out at its fire."""

    __slots__ = ("fill", "rows", "claims", "device", "off")

    def __init__(self):
        self.fill = 0
        self.rows = 0
        self.claims: typing.List[typing.Tuple[typing.Dict[str, np.ndarray], int]] = []
        self.device: typing.List[typing.Dict[str, typing.Any]] = []
        self.off = False


class ModelWindowFunction(_ModelFunctionBase, fn.WindowFunction):
    """Micro-batch inference: one jitted call per fired window.

    Windows larger than the policy's biggest bucket are chunked into
    multiple calls rather than failing batch assembly.

    Dispatch is pipelined (``pipeline_depth`` windows in flight).  A
    window passes three stages that can overlap: it crosses the link
    (host->device transfer), it runs on the device, and its results are
    fetched and emitted while the next window fills.  After dispatching
    window k a fire waits only until at most ``pipeline_depth - 1``
    windows are still unfetched, so with the default of three, k-1 runs
    on the device while k crosses the link and k+1 fills: the transfer
    hides under compute and the device, not the host's chain, sets the
    period.  ``pipeline_depth=2`` keeps one window fewer in flight (a
    fire then waits for window k-1, and the transfer is exposed wherever
    it is not short against the step).  ``transfer_lanes > 1``
    additionally overlaps the transfers of in-flight batches on a thread
    pool (the lever when single-stream transfer bandwidth is the
    ceiling); ``pipeline_depth`` defaults to ``max(3, 2 *
    transfer_lanes)`` so the lanes stay fed.  The cost of each window in
    flight is one window of inputs on the chip and one in the host's
    arena (275 MB each for 1024 Inception-v3 images).  In-flight batches
    are flushed at end of input and before every state snapshot, so
    barriers never have results in limbo (exactly-once, SURVEY.md §7
    hard part 5).

    **Zero-copy ring buffering** (``use_ring``): with a static input
    schema and a ``fixed_batch`` policy, arriving records are written
    once into a :class:`~flink_tensorflow_tpu.native.ring.TensorRing`
    (the window buffer holds only metadata tokens) and a window fire
    claims ``[B, ...]`` numpy views onto the arena that feed
    ``jax.device_put`` directly — no stacking copy on the steady-state
    path (BASELINE.json "zero-copy Row<->DeviceArray marshalling").
    Slots recycle when the batch's results are collected, so the arena
    is sized ``(pipeline_depth + 2) * fixed_batch`` slots (five windows
    at the default depth): the windows in flight, the one filling, and
    room for results fetched and not yet collected.  The ring rounds
    that up to a power of two (8 windows of 1024 for 5), so where the
    batch is a power of two no batch splits at the arena's end.  Default: auto
    (on when eligible); pass ``use_ring=False`` to force the list path.

    **Early shipping**: a record lies in the arena from its arrival, so
    on the ring path a large window need not wait for its fire to start
    crossing the link.  Where the runner finds it worth while
    (``CompiledMethodRunner.chunk_input``: a fixed batch of 64 MB or more
    of inputs, no narrowed wire), every ``chunk_rows`` records that have
    arrived are claimed and put at once, the fire puts what is left (the
    last chunk of a full window; the rest and the padding rows of one
    fired by its timeout) and the jitted call joins the chunks.  Same
    records, same order, same padding and ``valid`` mask, same trigger;
    slots are still released when the batch's results are collected.
    The filling window's chunks are one more window of inputs on the
    chip.  Anything else - the list path, a small window - crosses in one
    put at the fire.
    """

    #: A window operator counts ELEMENTS into its buffer — one
    #: DeviceBatch would count as one element and skew the window
    #: semantics, so device batches materialize at the boundary before
    #: entering a window (this function still PRODUCES device batches
    #: when chained into a device-capable consumer).
    accepts_device_batches = False

    def __init__(self, model: ModelSource, method: str = "serve", *,
                 pipeline_depth: typing.Optional[int] = None,
                 idle_flush_s: float = 0.05,
                 use_ring: typing.Optional[bool] = None,
                 ring_capacity: typing.Optional[int] = None, **kw):
        super().__init__(model, method, **kw)
        if pipeline_depth is None:
            pipeline_depth = max(3, 2 * self._transfer_lanes)
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self._max_in_flight = pipeline_depth - 1
        #: Most windows in flight right after a dispatch of the fire under
        #: way, before it collected (the ``fire`` span's ``in_flight``).
        self._fire_in_flight = 0
        self._idle_flush_s = idle_flush_s
        self._last_dispatch: typing.Optional[float] = None
        self._last_poll: typing.Optional[float] = None
        self._use_ring = use_ring
        self._ring_capacity = ring_capacity
        self._ring = None
        self._last_ingested: typing.Optional[TensorValue] = None
        #: The window now filling, where windows are shipped as they fill
        #: (the runner's ``chunk_rows``, decided at open); None: not shipped
        #: early, or given up at a snapshot.
        self._early: typing.Optional[_EarlyWindow] = None
        #: When the window now filling got its first record (None: no
        #: window is filling), and the running sums as they stood then:
        #: (emit + collect_wait, ring wait, park seconds since the window
        #: before).
        self._fill_t0: typing.Optional[float] = None
        self._fill_marks = (0.0, 0.0, 0.0)
        #: The subtask thread's account as read where the fill began.
        self._fill_charge = None
        #: Seconds in the ring-full drain loop so far (emissions and
        #: blocked collections: ``emit`` and ``collect_wait`` count them).
        self._ring_wait_total_s = 0.0

    # -- ring lifecycle ----------------------------------------------------
    def _open_buffers(self) -> None:
        if self._use_ring is False:
            return
        method = self.runner.method
        schema = method.input_schema
        static = all(
            all(d is not None for d in schema[n].shape) for n in schema.names
        )
        # Donated inputs may be overwritten by XLA for outputs; on a CPU
        # backend device_put aliases the arena views zero-copy, so
        # donation would let the executable scribble over live ring
        # slots — the two features are mutually exclusive.
        eligible = static and not method.needs_lengths and not self._donate
        if self._use_ring and self._donate:
            raise ValueError("use_ring=True is incompatible with "
                             "donate_inputs=True (donated buffers may alias "
                             "the ring arena)")
        fixed = self.runner.policy.fixed_batch
        if self._ring_capacity is None and fixed is not None:
            # One slot set per in-flight batch + the accumulating window.
            self._ring_capacity = (self._max_in_flight + 3) * fixed
        if self._use_ring and not eligible:
            raise ValueError(
                "use_ring=True requires a fully-static input schema "
                "(dynamic-length fields batch through the list path)"
            )
        if self._use_ring and self._ring_capacity is None:
            raise ValueError("use_ring=True without fixed_batch needs ring_capacity")
        if eligible and self._ring_capacity is not None:
            from flink_tensorflow_tpu.native.ring import TensorRing

            self._ring = TensorRing(schema, self._ring_capacity)
            # Page the whole arena in here, not as the first windows fill:
            # the ring rounds its capacity up to a power of two, so it can
            # hold more windows than a job's warm-up passes through it.
            self._ring.prefault()
            self.runner.chunk_input(self._ring.capacity)

    def clone(self) -> "fn.Function":
        dup = super().clone()
        dup._ring = None
        dup._last_ingested = None
        dup._early = None
        dup._fill_t0 = None
        return dup

    def open(self, ctx) -> None:
        super().open(ctx)
        if self._metrics is not None:
            self._metrics.gauge("windows_in_flight", self._windows_in_flight)

    def _windows_in_flight(self) -> int:
        """Windows dispatched and not yet fetched (what ``pipeline_depth``
        bounds); 0 once closed."""
        return len(self.runner._pending) if self.runner is not None else 0

    def close(self) -> None:
        # The runner's close collects what is in flight, oldest first, and
        # with it those batches' ring releases; a half-shipped window's
        # claims are then the oldest left.  Its device chunks go with it,
        # once their transfers have read the arena that is freed below.
        super().close()
        if self._ring is not None:
            early, self._early = self._early, None
            if early is not None and early.rows:
                try:
                    self._await_puts(early.device)
                except Exception:  # noqa: BLE001 - cancellation teardown
                    pass
                self._ring.release(early.rows)
            self._ring.close()
            self._ring = None

    # -- per-element ingestion (WindowOperator hook) -----------------------
    def ingest_element(self, value, out: fn.Collector):
        """Write one record into the ring at arrival; returns the buffer
        token, or None to buffer the value itself (ring off/full)."""
        if self._fill_t0 is None:
            self._begin_fill()
        if self._ring is None:
            return None
        tv = value if isinstance(value, TensorValue) else coerce(
            value, self.runner.method.input_schema)
        early = self._early
        if not self._ring.try_push(tv.fields) and not self._wait_for_slot(tv, out):
            if early is not None:
                early.off = True  # a mixed window is copied out at its fire
            return None
        self._last_ingested = tv
        if early is not None:
            early.fill += 1
            # The last chunk of a full batch is the fire's own.
            if (early.fill % self.runner.chunk_rows == 0 and not early.off
                    and early.fill < self.runner.policy.fixed_batch):
                self._ship_chunk(early)
        return _RingToken(tv.meta)

    def _ship_chunk(self, early: _EarlyWindow) -> None:
        """``chunk_rows`` more records of the filling window lie in the
        arena: claim them and start their transfer (subtask thread, like
        every claim and release)."""
        rows = self.runner.chunk_rows
        t0 = time.monotonic()
        views, got = self._ring.claim_batch(rows)
        if got == 0:
            raise RuntimeError("ring out of sync with window buffer")
        early.claims.append((views, got))
        early.rows += got
        if got < rows:
            early.off = True  # split at the arena's end
            return
        early.device.append(self.runner.put_chunk(views))
        if self._spans is not None:
            self._spans.span(self._track, "early_put", t0, time.monotonic(), {
                "seq": self.runner._batch_seq + 1, "chunk": len(early.device) - 1,
                "bytes": sum(v.nbytes for v in views.values())})

    @staticmethod
    def _await_puts(device_chunks) -> None:
        """Wait until the transfers of chunks shipped early have read their
        rows.  A ``device_put`` returns at once and the host goes on reading
        the rows for tens of ms (it re-lays them for the device), so rows
        that were put and whose batch is never dispatched — nothing then
        collects it and runs an ``on_done`` — are released, and the arena
        freed, only after this."""
        import jax

        jax.block_until_ready(device_chunks)

    def _wait_for_slot(self, tv, out: fn.Collector) -> bool:
        """Ring full: completed-but-uncollected batches hold slots
        (releases are deferred to collection) — drain them first, then
        block for the oldest in-flight batch and retry.  False when no
        work is in flight at all: the buffered window alone exceeds
        capacity, and the caller list-buffers the record."""
        runner = self.runner
        t0 = time.monotonic()
        try:
            while True:
                drained = runner.collect_batches()
                self._emit(drained, out)
                if not drained:
                    if not runner._pending:
                        return False
                    self._emit(runner.collect_batches(len(runner._pending) - 1), out)
                if self._ring.try_push(tv.fields):
                    return True
        finally:
            self._ring_wait_total_s += time.monotonic() - t0

    # -- the window's spans (tracing/flight.py) ----------------------------
    def _begin_fill(self) -> None:
        """The first record of a window has arrived: one clock read a
        window, none a record."""
        parked = self._spans.park_s if self._spans is not None else 0.0
        self._fill_marks = (self._emit_total_s + self.runner.collect_wait_total_s,
                            self._ring_wait_total_s, parked)
        self._early = _EarlyWindow() if self.runner.chunk_rows is not None else None
        if self._spans is not None:
            self._fill_charge = self._spans.account().read()
        self._fill_t0 = time.monotonic()

    def _close_fill(self, now: float, records: int, charge=None) -> None:
        """``process_window`` is entered: close the ``fill`` span (``charge``:
        the thread's account as read at ``now``).  Its
        self time — the fill less the emissions, blocked collections and
        parks inside it — is the ingest of the window's records (source poll, chain, ring write), got with no
        clock read per record."""
        t0, self._fill_t0 = self._fill_t0, None
        if t0 is None:
            return
        children0, ring0, park_before = self._fill_marks
        children = self._emit_total_s + self.runner.collect_wait_total_s - children0
        spans = self._spans
        # Without a hook (flight ring and tracer both off) nobody counts
        # the parks, and the ingest then includes them.
        park_s, park_n, park_over = (
            spans.take_parks() if spans is not None else (0.0, 0, 0.0))
        park_s -= park_before  # the hook's sums run from the fill before
        self_s = max(now - t0 - children - park_s, 0.0)
        if self._metrics is not None:
            self._metrics.timer("ingest_s").update(self_s)
        if spans is not None:
            spans.span(self._track, "fill", t0, now, charged({
                "seq": self.runner._batch_seq + 1, "records": records,
                "self_s": self_s, "park_s": park_s,
                "ring_wait_s": self._ring_wait_total_s - ring0,
                "park_n": park_n, "park_over_max_s": park_over,
                "park_before_s": park_before}, self._fill_charge, charge))

    def materialize_tokens(self, elements):
        """Replace ring tokens with concrete TensorValues (copy-out) —
        used before operator snapshots and on mixed buffers.  In-flight
        batches must be flushed first so the ring head is the buffer."""
        tokens = [e for e in elements if isinstance(e, _RingToken)]
        if not tokens:
            return list(elements)
        if self.runner is not None and (
                self.runner._pending or self.runner.has_completed()):
            # flush() also runs the deferred ring releases of completed
            # batches, so the ring head is the buffer afterwards.
            drained = self.runner.collect_batches(0)
            if self._out is not None:
                self._emit(drained, self._out)
        # What the fill has claimed already comes first (the flush above
        # made those claims the oldest); the chunks on the device are
        # dropped once they have crossed (their rows are released below),
        # and the rest of this window crosses whole.
        early, self._early = self._early, None
        claims = []
        if early is not None:
            self._await_puts(early.device)
            claims = early.claims
        values = {}
        remaining = len(tokens)
        idx = 0
        while remaining > 0:
            views, n = claims.pop(0) if claims else self._ring.claim_batch(remaining)
            if n == 0:
                raise RuntimeError("ring out of sync with window buffer")
            for i in range(n):
                values[idx] = {f: np.array(v[i]) for f, v in views.items()}
                idx += 1
            self._ring.release(n)
            remaining -= n
        out = []
        ti = 0
        for e in elements:
            if isinstance(e, _RingToken):
                out.append(TensorValue(values[ti], e.meta))
                ti += 1
            else:
                out.append(e)
        return out

    # -- firing ------------------------------------------------------------
    def process_window(self, key, window, elements, out: fn.Collector):
        account = self._spans.account() if self._spans is not None else None
        charge = account.read() if account is not None else None
        t_fire = time.monotonic()
        elements = list(elements)
        self._close_fill(t_fire, len(elements), charge)
        self._out = out
        self._fire_in_flight = 0
        blocked0 = self.runner.collect_wait_total_s
        tokens = all(isinstance(e, _RingToken) for e in elements) and bool(elements)
        early_chunks = 0
        if tokens and self._ring is not None:
            early_chunks = self._fire_ring(elements, out)
        else:
            if any(isinstance(e, _RingToken) for e in elements):
                # Mixed (restored values + fresh tokens): copy tokens out
                # and take the list path for this window only.
                elements = self.materialize_tokens(elements)
            policy = self.runner.policy
            cap = policy.fixed_batch or policy.batch.sizes[-1]
            for i in range(0, len(elements), cap):
                self.runner.dispatch(elements[i:i + cap])
                self._hold_depth(out)
        self._last_dispatch = now = time.monotonic()
        if self._spans is not None:
            policy = self.runner.policy
            cap = policy.fixed_batch or policy.batch.sizes[-1]
            tail = len(elements) % cap
            self._spans.span(self._track, "fire", t_fire, now, charged({
                "seq": self.runner._batch_seq, "records": len(elements),
                "padded": policy.batch_bucket(tail) - tail if tail else 0,
                "in_flight": self._fire_in_flight,
                "blocked_s": self.runner.collect_wait_total_s - blocked0,
                "early_chunks": early_chunks}, charge, account.read()))

    def _hold_depth(self, out: fn.Collector) -> None:
        """A batch has just been dispatched: note how many are in flight,
        then emit what is ready and block until at most ``pipeline_depth
        - 1`` are still unfetched."""
        self._fire_in_flight = max(self._fire_in_flight, len(self.runner._pending))
        self._emit(self.runner.collect_batches(self._max_in_flight), out)

    def _fire_ring(self, tokens, out: fn.Collector) -> int:
        """Claim contiguous arena views per batch and dispatch them — the
        zero-copy fire path.  Returns how many chunks of the window had been
        shipped before the fire (the ``fire`` span's ``early_chunks``)."""
        from flink_tensorflow_tpu.tensors.batching import Batch

        policy = self.runner.policy
        cap = policy.fixed_batch or policy.batch.sizes[-1]
        n_total = len(tokens)
        # What the fill claimed and shipped is the head of the first batch.
        early, self._early = self._early, None
        early_chunks = 0
        for start in range(0, n_total, cap):
            chunk = tokens[start:start + cap]
            n = len(chunk)
            b = policy.batch_bucket(n)
            # Pad slots: replay the last ingested record so the padded
            # rows are benign; they sit contiguously after the chunk.
            for _ in range(b - n):
                if not self._ring.try_push(self._last_ingested.fields):
                    raise RuntimeError("ring cannot hold batch padding; "
                                       "raise ring_capacity")
            # A batch is claimed ``rows`` at a time: whole, or a chunk where
            # it crosses the link in chunks (K claims, the fill's first).
            rows = self.runner.chunk_rows or b
            head = early if early is not None and start == 0 else _EarlyWindow()
            claims, claimed, shipped = head.claims, head.rows, ()
            whole = claimed % rows == 0
            while whole and claimed < b:
                views, got = self._ring.claim_batch(rows)
                if got == 0:
                    raise RuntimeError("ring out of sync with window buffer")
                claims.append((views, got))
                claimed += got
                whole = got == rows
            if not whole:
                arrays, chunks, release = self._copy_out(head, b, out), None, None
            else:
                ring = self._ring
                release = (lambda nn=b, r=ring: r.release(nn))
                if rows == b:
                    arrays, chunks = claims[0][0], None
                else:
                    shipped = head.device
                    arrays = {}
                    chunks = [v for v, _ in claims[len(shipped):]]
                    early_chunks += len(shipped)
            valid = np.zeros((b,), dtype=bool)
            valid[:n] = True
            batch = Batch(arrays=arrays, valid=valid, lengths={},
                          metas=[t.meta for t in chunk])
            self.runner.dispatch_batch(batch, on_done=release, chunks=chunks,
                                       shipped=shipped)
            self._hold_depth(out)
        return early_chunks

    def _copy_out(self, head: _EarlyWindow, b: int, out: fn.Collector):
        """A claim came back short: the arena's end splits this batch.  Copy
        it out (rare; at most once per trip around the ring), the rows
        already claimed (``head.claims``) first, and return it as plain
        ``[b, ...]`` arrays.

        Ring releases are strictly oldest-claim-first, so the immediate
        releases below would free a still-dispatched batch's slots if any
        were in flight OR completed-but-uncollected — drain both (their
        deferred on_done releases run FIFO at collection), making our
        claims the oldest.  Chunks of it already on the device are dropped,
        once they have crossed: the copy crosses again, in the runner's own
        puts."""
        if self.runner._pending or self.runner.has_completed():
            self._emit(self.runner.collect_batches(0), out)
        self._await_puts(head.device)
        claims = head.claims
        arrays = {f: np.empty((b, *v.shape[1:]), v.dtype)
                  for f, v in claims[0][0].items()}
        filled = 0
        while filled < b:
            views, got = claims.pop(0) if claims else self._ring.claim_batch(b - filled)
            if got == 0:
                raise RuntimeError("ring out of sync with window buffer")
            for f, v in views.items():
                arrays[f][filled:filled + got] = v[:got]
            self._ring.release(got)
            filled += got
        return arrays

    # Timer hooks (WindowOperator.next_deadline/fire_due): while batches
    # are in flight, poll every idle_flush_s and emit whatever is READY —
    # without blocking the subtask thread.  The pre-r4 behavior (a full
    # blocking flush idle_flush_s after the last dispatch) turned the
    # operator into an M/D/1 server at open-loop rates: every window's
    # results waited out the whole device round trip on the subtask
    # thread while later windows queued behind it (round 3's 536ms p50
    # at 0.5x capacity).  Polling emits each batch within one poll
    # interval of its results landing, and the thread stays free to
    # accept arrivals and fire the next window meanwhile.
    def next_deadline(self) -> typing.Optional[float]:
        if self.runner is None:
            return None
        # Fetched results waiting: due IMMEDIATELY — 0.0 is in the past
        # on the monotonic clock, so the caller's earlier `now` still
        # satisfies `now >= deadline` (a fresh monotonic() here could
        # exceed it and skip the fire).  The fetch thread also pokes the
        # gate via on_results_ready, so the loop re-checks within one
        # poll rather than one idle_flush interval.
        if self.runner.has_completed():
            return 0.0
        if not self.runner._pending or self._last_dispatch is None:
            return None
        base = self._last_dispatch
        if self._last_poll is not None and self._last_poll > base:
            base = self._last_poll
        return base + self._idle_flush_s

    def fire_due(self, now: float) -> None:
        d = self.next_deadline()
        if d is None or now < d:
            return
        self._poll_collect(now)
        self._last_poll = now

    def on_finish(self, out: fn.Collector):
        self._emit(self.runner.collect_batches(0), out)

    def snapshot_state(self):
        # Barrier alignment: emit everything in flight BEFORE the snapshot
        # is taken — the emissions precede the forwarded barrier, keeping
        # the snapshot consistent with the downstream stream position.
        if self.runner is not None and getattr(self, "_out", None) is not None:
            self._emit(self.runner.collect_batches(0), self._out)
        return None


class _GraphFunctionBase(fn.RichFunction):
    """Runs a frozen function (jax.export artifact) instead of a Model.

    Frozen artifacts are shape-specialized at export time, so the batch
    policy is forced to the artifact's batch size.
    """

    #: Plan-analyzer marker (see _ModelFunctionBase).
    is_jit_boundary = True

    def __init__(self, graph: typing.Union[str, bytes], *, batch: int,
                 input_schema, needs_lengths: bool = False,
                 length_bucket: int = 128):
        self._graph_source = graph
        self._batch = batch
        self._schema = input_schema
        self._needs_lengths = needs_lengths
        self._call = None
        # Frozen artifacts are shape-specialized at export time on BOTH
        # the batch and the length bucket — pin both so assembly always
        # produces exactly the shapes the serialized StableHLO requires
        # (must match freeze_method's batch/length_bucket arguments).
        self._policy = BucketPolicy(
            fixed_batch=batch, lengths=BucketLadder([length_bucket])
        )

    def clone(self):
        import copy

        dup = copy.copy(self)
        dup._call = None
        return dup

    # -- plan-time hooks ---------------------------------------------------
    def output_schema(self, input_schema):
        """Validate against the artifact's declared input schema; output
        shapes live inside the serialized StableHLO — unknown here."""
        from flink_tensorflow_tpu.tensors.schema import check_compatible

        if input_schema is not None:
            check_compatible(self._schema, input_schema,
                             where="frozen graph inputs")
        return None

    def plan_policy(self):
        return self._policy

    def open(self, ctx) -> None:
        self._call = GraphLoader(self._graph_source).load()

    def close(self) -> None:
        self._call = None

    def _run(self, records) -> typing.List[TensorValue]:
        from flink_tensorflow_tpu.tensors.batching import assemble
        from flink_tensorflow_tpu.tensors.transfer import DeviceTransfer

        tvs = [r if isinstance(r, TensorValue) else coerce(r, self._schema) for r in records]
        batch = assemble(tvs, self._schema, self._policy)
        if self._needs_lengths:
            outputs = self._call(batch.arrays, batch.lengths)
        else:
            outputs = self._call(batch.arrays)
        return batch.unbatch(DeviceTransfer.fetch(outputs))


class GraphMapFunction(_GraphFunctionBase, fn.AsyncMapFunction):
    """Per-record inference over a frozen artifact, pipelined.

    Frozen graphs are shape-specialized at export (batch=1 here), so
    there is no transparent micro-batching — but dispatches ride a small
    thread pool with up to ``pipeline_depth`` in flight, so throughput
    is bounded by ``pipeline_depth / RTT`` instead of one synchronous
    round trip per record (the ModelMapFunction rework's guarantee,
    applied to the GraphFunction idiom).  Results surface in arrival
    order; lulls drain after ``idle_flush_s``; end-of-input and barriers
    flush everything in flight.
    """

    def __init__(self, graph, *, input_schema, needs_lengths: bool = False,
                 length_bucket: int = 128, pipeline_depth: int = 4,
                 idle_flush_s: float = 0.01):
        super().__init__(graph, batch=1, input_schema=input_schema,
                         needs_lengths=needs_lengths, length_bucket=length_bucket)
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self._depth = pipeline_depth
        self._idle_flush_s = idle_flush_s
        self._pool = None
        self._pending: typing.Optional[typing.Deque] = None
        self._out: typing.Optional[fn.Collector] = None
        self._last_activity: typing.Optional[float] = None

    def clone(self):
        dup = super().clone()
        dup._pool = None
        dup._pending = None
        dup._out = None
        dup._last_activity = None
        return dup

    def open(self, ctx) -> None:
        import collections
        import concurrent.futures

        super().open(ctx)
        self._pending = collections.deque()
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self._depth, thread_name_prefix="graph-map")

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        self._pending = None
        super().close()

    def map_async(self, value, out: fn.Collector):
        self._out = out
        self._pending.append(self._pool.submit(lambda: self._run([value])[0]))
        self._last_activity = time.monotonic()
        # FIFO emission: drain completed heads, then block only to keep
        # the in-flight count at the pipeline depth.
        while self._pending and (
                self._pending[0].done() or len(self._pending) > self._depth):
            out.collect(self._pending.popleft().result())

    def flush(self, out: typing.Optional[fn.Collector] = None):
        out = out if out is not None else self._out
        while self._pending:
            result = self._pending.popleft().result()
            if out is not None:
                out.collect(result)

    def next_deadline(self) -> typing.Optional[float]:
        if not self._pending or self._last_activity is None:
            return None
        return self._last_activity + self._idle_flush_s

    def fire_due(self, now: float) -> None:
        if self._pending and self._out is not None:
            while self._pending and self._pending[0].done():
                self._out.collect(self._pending.popleft().result())
            self._last_activity = now  # re-arm until the queue drains

    def on_finish(self, out: fn.Collector):
        self.flush(out)

    def snapshot_state(self):
        self.flush()
        return None


class GraphWindowFunction(_GraphFunctionBase, fn.WindowFunction):
    def process_window(self, key, window, elements, out: fn.Collector):
        # Frozen batch is fixed: chunk oversized windows.
        elements = list(elements)
        for i in range(0, len(elements), self._batch):
            for record in self._run(elements[i:i + self._batch]):
                out.collect(record)


class DeviceMapFunction(fn.MapFunction):
    """Elementwise device-side map — a HBM-resident link in a chain.

    Wraps a pure ``arrays -> arrays`` callable (dict of ``[B, ...]``
    batch-major arrays in, dict out) and applies it jitted.  Fed a
    :class:`~flink_tensorflow_tpu.tensors.transfer.DeviceBatch` (chained
    behind a device-resident model), the whole batch transforms ON
    DEVICE and is re-emitted as a DeviceBatch — the hop costs zero wire
    bytes, so a model -> elementwise -> model chain stays HBM-resident
    end to end.  Fed plain host records (unchained placement, or device
    residency off), each record lifts to a batch of one, transforms, and
    returns to a host ``TensorValue`` — semantics identical, only the
    residency differs.

    The callable must be replay-pure (jit traces it once); state, I/O
    and clocks are as illegal here as inside any model method.
    """

    device_capable = True
    accepts_device_batches = True

    def __init__(self, array_fn: typing.Callable[[typing.Mapping[str, typing.Any]],
                                                 typing.Mapping[str, typing.Any]]):
        self._array_fn = array_fn
        self._jit = None

    def clone(self) -> "fn.Function":
        import copy

        dup = copy.copy(self)
        dup._jit = None
        return dup

    def open(self, ctx) -> None:
        import jax

        self._jit = jax.jit(self._array_fn)

    def close(self) -> None:
        self._jit = None

    def map(self, value):
        from flink_tensorflow_tpu.tensors.transfer import DeviceBatch

        if isinstance(value, DeviceBatch):
            return DeviceBatch(self._jit(value.arrays), value.valid,
                               value.metas, timestamp=value.timestamp,
                               tracer=value._tracer, track=value._track)
        if not isinstance(value, TensorValue):
            raise TypeError(
                f"DeviceMapFunction maps tensor records, got {type(value).__name__}")
        lifted = {n: np.asarray(a)[None] for n, a in value.fields.items()}
        out = self._jit(lifted)
        return TensorValue({n: np.asarray(a)[0] for n, a in out.items()},
                           value.meta)
