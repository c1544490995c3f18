"""Training as stream operators — online SGD and data-parallel gangs.

Two training shapes from the reference (BASELINE.json:10-11):

- **Online training on a keyed stream** (Wide&Deep): per-record/mini-batch
  SGD inside a keyed ProcessFunction.  Reference mechanism: ``Session.run
  (train_op)`` with variables hidden in the session (SURVEY.md §3.4).
  Here :class:`OnlineTrainFunction` keeps the TrainState as EXPLICIT
  function state, so checkpoint barriers snapshot params+optimizer
  natively — the state-outside-snapshots caveat of the reference
  (SURVEY.md §5 "Checkpoint / resume") disappears by construction.

- **Data-parallel training** (ResNet-50): reference runs N replica
  sessions + ClusterSpec/NCCL allreduce (SURVEY.md §3.5).  Here
  :class:`DPTrainWindowFunction` is a *gang operator* (SURVEY.md §7 hard
  part 4): parallelism 1 on the stream plane, owning the WHOLE device
  mesh; each fired window becomes one pjit-ed step whose gradient
  allreduce XLA emits over ICI.

Snapshot protocol note: barriers never cut a jitted step in half — the
operator processes elements one at a time and snapshots only between
calls (SURVEY.md §7 hard part 5).  Snapshots are host-side numpy pytrees
(device_get on snapshot, device_put on restore).
"""

from __future__ import annotations

import time
import typing

from flink_tensorflow_tpu.core import functions as fn
from flink_tensorflow_tpu.models.zoo.registry import ModelDef
from flink_tensorflow_tpu.tensors.batching import BucketPolicy, assemble
from flink_tensorflow_tpu.tensors.coercion import coerce
from flink_tensorflow_tpu.tensors.schema import RecordSchema, check_compatible
from flink_tensorflow_tpu.tensors.value import TensorValue
from flink_tensorflow_tpu.tracing.flight import charged


def _to_host(pytree):
    import jax
    import numpy as np

    def conv(a):
        a = jax.device_get(a)
        try:
            return np.asarray(a)
        except TypeError:
            return a  # extended dtypes (PRNG keys) stay as jax arrays

    return jax.tree.map(conv, pytree)


def _validate_train_schema(schema: RecordSchema) -> RecordSchema:
    """The batch dict synthesizes ``<field>_len`` (dynamic fields) and
    ``valid`` keys; schema fields with those names would be silently
    clobbered — reject them at construction."""
    for name in schema.names:
        if name == "valid":
            raise ValueError(
                "train_schema field 'valid' collides with the synthesized "
                "batch-validity mask — rename the feature"
            )
        if any(d is None for d in schema[name].shape) and f"{name}_len" in schema.names:
            raise ValueError(
                f"train_schema field {name + '_len'!r} collides with the "
                f"synthesized length array for dynamic field {name!r} — "
                "rename the feature"
            )
    return schema


def _train_batch_arrays(records, schema: RecordSchema, policy: BucketPolicy):
    """Assemble training records -> batch dict incl. labels and lengths.

    True lengths for dynamic fields are merged as ``<field>_len`` (the
    loss_fn convention, e.g. bilstm's ``tokens_len``).  Training batches
    are NOT padded with replay rows blindly: the batch is bucketed, and
    pad rows replicate record 0 — with loss averaged over the bucket this
    would bias gradients, so we weight via the valid mask when padding
    occurred (callers see ``valid`` in the batch dict).
    """
    import numpy as np

    tvs = [r if isinstance(r, TensorValue) else coerce(r, schema) for r in records]
    batch = assemble(tvs, schema, policy)
    arrays = dict(batch.arrays)
    for name, lengths in batch.lengths.items():
        arrays[f"{name}_len"] = lengths
    arrays["valid"] = batch.valid.astype(np.float32)
    return batch, arrays


class OnlineTrainFunction(fn.ProcessFunction):
    """Per-key (or per-subtask) online SGD on a keyed stream.

    ``scope="subtask"`` (default): one TrainState per operator subtask —
    keys partition the *data*, the model is shared within the subtask.
    ``scope="key"``: one TrainState per key in keyed state — fully
    personalized models (use small model configs).

    Emits one metrics record per mini-batch:
    ``TensorValue({"loss": ..., "step": ...}, meta={"key": key})``.
    """

    #: Plan-analyzer marker: records feed a jitted train step.
    is_jit_boundary = True
    #: The jitted step does NOT donate the TrainState (the pipelined
    #: dispatch keeps the previous state live until its metrics are
    #: fetched) — statecheck's train-state audit turns this into the
    #: 2x-HBM WARN once the abstract TrainState crosses the donation
    #: threshold.
    donates_train_state = False

    def __init__(
        self,
        model_def: ModelDef,
        optimizer=None,
        *,
        train_schema: RecordSchema,
        scope: str = "subtask",
        mini_batch: int = 1,
        seed: int = 0,
        pipeline_depth: int = 4,
        steps_per_dispatch: int = 1,
    ):
        if scope not in ("subtask", "key"):
            raise ValueError(f"scope must be 'subtask' or 'key', got {scope!r}")
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        if steps_per_dispatch < 1:
            raise ValueError("steps_per_dispatch must be >= 1")
        self.model_def = model_def
        self.optimizer = optimizer
        self.train_schema = _validate_train_schema(train_schema)
        self.scope = scope
        self.mini_batch = mini_batch
        self.seed = seed
        #: Steps kept in flight before their METRICS are fetched.  The
        #: train step itself is always dispatched asynchronously (jax
        #: chains the state futures); fetching each step's loss
        #: synchronously would serialize one device round trip per
        #: mini-batch.  Metrics emission lags dispatch by up to this
        #: depth; barriers/finish flush.
        self.pipeline_depth = pipeline_depth
        #: Mini-batch steps fused into ONE lax.scan dispatch (the same
        #: step sequence; last-ulp float rounding may differ from the
        #: unfused executable).  >1 amortizes the per-dispatch
        #: host round trip over K steps — on a remote-attached chip each
        #: dispatch costs ~an RTT, which bounds un-fused online training
        #: to ~1/RTT steps/s regardless of model size.
        self.steps_per_dispatch = steps_per_dispatch
        self._step_fn = None
        self._multi_fn = None
        #: Per-key staged mini-batch arrays awaiting a fused dispatch.
        self._staged: typing.Dict[typing.Any, list] = {}
        self._state = None  # subtask scope
        self._key_state = None  # key scope (ValueState)
        self._buffers: typing.Dict[typing.Any, list] = {}
        #: In-flight (key, device metrics, step number, record count).
        self._pending: typing.Optional[typing.Deque] = None
        #: Host-side step counters per key (device state["step"] is an
        #: async future once steps pipeline; int() on it would sync).
        self._steps: typing.Dict[typing.Any, int] = {}
        self._out: typing.Optional[fn.Collector] = None
        self._policy = BucketPolicy(fixed_batch=mini_batch)

    def clone(self):
        import copy

        dup = copy.copy(self)
        dup._step_fn = None
        dup._multi_fn = None
        dup._state = None
        dup._key_state = None
        dup._buffers = {}
        dup._staged = {}
        dup._pending = None
        dup._steps = {}
        dup._out = None
        return dup

    # -- plan-time hooks ---------------------------------------------------
    def output_schema(self, input_schema):
        """Plan-analyzer hook: incoming records must satisfy the train
        schema; the emitted per-step metrics records have a different,
        model-dependent shape — propagation stops here."""
        if input_schema is not None:
            check_compatible(self.train_schema, input_schema,
                             where="train_schema")
        return None

    def plan_policy(self):
        return self._policy

    # -- lifecycle ---------------------------------------------------------
    def open(self, ctx) -> None:
        import jax
        import optax

        from flink_tensorflow_tpu.parallel.dp import init_train_state, make_train_step

        self.ctx = ctx
        optimizer = self.optimizer or optax.sgd(0.01)
        self.optimizer = optimizer
        self._step_fn = jax.jit(make_train_step(self.model_def, optimizer))
        if self.steps_per_dispatch > 1:
            from flink_tensorflow_tpu.parallel.dp import make_multi_train_step

            self._multi_fn = jax.jit(make_multi_train_step(self.model_def, optimizer))
        self._init = lambda: init_train_state(
            self.model_def, optimizer,
            jax.random.fold_in(jax.random.key(self.seed), ctx.subtask_index),
        )
        if self.scope == "subtask":
            if self._state is None:  # not restored
                self._state = self._init()
        else:
            from flink_tensorflow_tpu.core.state import StateDescriptor

            self._key_state = ctx.state(StateDescriptor("train_state"))

    # -- processing --------------------------------------------------------
    def process_element(self, value, ctx, out: fn.Collector) -> None:
        self._out = out
        key = ctx.current_key
        buf = self._buffers.setdefault(key, [])
        buf.append(value)
        if len(buf) >= self.mini_batch:
            self._buffers[key] = []
            self._train(key, buf, out)

    def on_finish(self, out: fn.Collector) -> None:
        """Flush partial mini-batches: the valid-mask-weighted loss keeps
        pad rows out of the gradient, so short batches train correctly."""
        for key, buf in list(self._buffers.items()):
            if buf:
                self._buffers[key] = []
                self._train(key, buf, out)
        self._flush_staged()
        self._drain_pending(out, 0)

    def _train(self, key, records, out: fn.Collector) -> None:
        _, arrays = _train_batch_arrays(records, self.train_schema, self._policy)
        if self.steps_per_dispatch > 1:
            staged = self._staged.setdefault(key, [])
            staged.append((arrays, len(records)))
            if len(staged) >= self.steps_per_dispatch:
                self._staged[key] = []
                self._run_steps(key, staged, out)
            return
        self._run_steps(key, [(arrays, len(records))], out)

    def _flush_staged(self) -> None:
        """Run staged-but-unfused mini-batches (end of input / barrier):
        a partial chunk takes the single-step path — no extra executable
        per partial length."""
        for key, staged in list(self._staged.items()):
            if staged:
                self._staged[key] = []
                for arrays, n in staged:
                    self._run_steps_fused(key, [(arrays, n)], fused=False)
        # Results ride self._pending; caller decides when to drain.

    def _run_steps(self, key, chunk, out: fn.Collector) -> None:
        self._run_steps_fused(key, chunk, fused=len(chunk) > 1)
        # Dispatch-and-go: fetch metrics only when older dispatches pile
        # past the pipeline depth, so device round trips overlap.
        self._drain_pending(out, self.pipeline_depth - 1)

    def _run_steps_fused(self, key, chunk, *, fused: bool) -> None:
        """Dispatch ``chunk`` (a list of (arrays, n)) as ONE device call:
        lax.scan over the stacked batches when fused, the plain step
        otherwise.  Results are queued on the pending deque."""
        import collections
        import contextlib

        import numpy as np

        # Scope keyed state to THIS key (on_finish flushes several keys
        # outside the per-element current-key window).
        scope = self.ctx.with_key(key) if self.scope == "key" else contextlib.nullcontext()
        with scope:
            if self.scope == "key":
                state = self._key_state.value()
                if state is None:
                    state = self._init()
            else:
                state = self._state
            counter_key = key if self.scope == "key" else None
            if counter_key not in self._steps:
                # First touch: the state is concrete (fresh init or a
                # restored host snapshot), so this int() is free; later
                # states are pipelined device futures we must not sync.
                self._steps[counter_key] = int(state["step"])
            if fused:
                stacked = {
                    name: np.stack([arrays[name] for arrays, _ in chunk])
                    for name in chunk[0][0]
                }
                state, metrics = self._multi_fn(state, stacked)
            else:
                state, metrics = self._step_fn(state, chunk[0][0])
            if self.scope == "key":
                self._key_state.update(state)
            else:
                self._state = state
        first = self._steps[counter_key] + 1
        self._steps[counter_key] += len(chunk)
        if self._pending is None:
            self._pending = collections.deque()
        self._pending.append(
            (key, metrics, first, [n for _, n in chunk], fused)
        )

    def _drain_pending(self, out: fn.Collector, keep: int) -> None:
        import numpy as np

        while self._pending and len(self._pending) > keep:
            key, metrics, first, counts, fused = self._pending.popleft()
            host = {k: np.asarray(v) for k, v in metrics.items()}
            for i, n in enumerate(counts):
                row = {k: (v[i] if fused else v) for k, v in host.items()}
                row["step"] = np.asarray(first + i, np.int64)
                out.collect(TensorValue(row, meta={"key": key}))
                if self.ctx is not None:
                    self.ctx.metrics.meter("train_records").mark(n)
                    self.ctx.metrics.counter("train_steps").inc()

    # -- snapshot (params ARE operator state) ------------------------------
    def snapshot_state(self):
        # Run staged (not-yet-fused) mini-batches and emit all in-flight
        # metrics BEFORE the snapshot: their source records precede the
        # barrier, so post-restore replay will never regenerate them, and
        # the snapshot state must include their steps.
        self._flush_staged()
        if self._pending and self._out is not None:
            self._drain_pending(self._out, 0)
        # Keyed scope rides the KeyedStateStore snapshot automatically;
        # subtask scope snapshots its TrainState + open mini-batches here.
        # Deep-copy buffer lists: the snapshot is acked by reference, and
        # post-barrier appends must not leak into it (exactly-once).
        return {
            "state": _to_host(self._state) if self._state is not None else None,
            "buffers": {k: list(v) for k, v in self._buffers.items()},
        }

    def restore_state(self, snap) -> None:
        self._state = snap["state"]
        self._buffers = {k: list(v) for k, v in snap["buffers"].items()}
        self._steps = {}  # re-read from the (host) restored state at first touch
        self._pending = None

    def rescale_state(self, states, mine):
        """Restore with changed parallelism: per-key mini-batch buffers
        redistribute by key group; a subtask-scoped TrainState cannot
        (every subtask owns an independent model replica)."""
        from flink_tensorflow_tpu.core.operators import StateNotRescalable

        if any(s and s.get("state") is not None for s in states):
            raise StateNotRescalable(
                "OnlineTrainFunction(scope='subtask') keeps one model per "
                "subtask — rescaling would drop or duplicate replicas; use "
                "scope='key' or keep the operator's parallelism fixed"
            )
        buffers: typing.Dict[typing.Any, list] = {}
        for s in states:
            if not s:
                continue
            for key, buf in s["buffers"].items():
                if mine(key):
                    buffers.setdefault(key, []).extend(buf)
        return {"state": None, "buffers": buffers}

    def current_params(self, key=None):
        """Latest variables (for export via models.save_bundle)."""
        if self.scope == "key":
            raise ValueError("pass through keyed state for per-key params")
        return _to_host(self._state["variables"])


class DPTrainWindowFunction(fn.WindowFunction):
    """Gang operator: each fired window = one DP train step on the mesh.

    Use with parallelism=1 — the gang owns every chip via ``env.set_mesh``
    (SURVEY.md §7 hard part 4: "DP training wants one jitted step spanning
    all chips").  The window size is the global batch; it is padded to the
    fixed ``global_batch`` (must divide by the mesh's data axis).

    **Multi-host**: when the mesh spans processes (SURVEY.md §7 step 8),
    every process runs this same gang operator SPMD-style; each ingests
    its own stream partition of ``global_batch // process_count`` records
    per window (size your count_window accordingly) and the global batch
    array is formed from the process-local rows without cross-host
    copies.  All processes must fire the same number of windows — feed
    them equal-length partitions — and checkpoint triggers must land at
    identical step counts on every process (deterministic, count-based
    triggers; see examples/multihost_dp_train.py).
    """

    #: Plan-analyzer markers: a jitted step, and a GANG — stream
    #: parallelism 1 owning the whole mesh (the mesh-divisibility lint
    #: checks global_batch against the mesh's data axis at plan time).
    is_jit_boundary = True
    is_gang = True
    #: make_dp_train_step donates the TrainState through the jitted
    #: step (donate_argnums=(0,)): params + moments update in place,
    #: no double-buffering — statecheck's train-state audit reads this.
    donates_train_state = True

    def __init__(
        self,
        model_def: ModelDef,
        optimizer=None,
        *,
        train_schema: RecordSchema,
        global_batch: int,
        seed: int = 0,
        pipeline_depth: int = 2,
    ):
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self.model_def = model_def
        self.optimizer = optimizer
        self.train_schema = _validate_train_schema(train_schema)
        self.global_batch = global_batch
        self.seed = seed
        #: Steps whose METRICS are still in flight (the step dispatch is
        #: always async; fetching each loss synchronously pays a device
        #: round trip per window — the next window's h2d transfer should
        #: overlap this step's compute instead).
        self.pipeline_depth = pipeline_depth
        self._step_fn = None
        self._state = None
        self._restored = None
        self._pending: typing.Optional[typing.Deque] = None
        self._step_no = 0
        self._policy = BucketPolicy(fixed_batch=global_batch)
        self.mesh = None
        #: Window-level span hook + track (from ctx at open): a step's
        #: ``assemble`` / ``h2d_enqueue`` / ``dispatch`` / ``drain_wait``.
        self._spans = None
        self._track: typing.Optional[str] = None

    def clone(self):
        import copy

        dup = copy.copy(self)
        dup._step_fn = None
        dup._state = None
        dup._pending = None
        return dup

    # -- plan-time hooks ---------------------------------------------------
    def output_schema(self, input_schema):
        if input_schema is not None:
            check_compatible(self.train_schema, input_schema,
                             where="train_schema")
        return None

    def plan_policy(self):
        return self._policy

    def open(self, ctx) -> None:
        import jax
        import optax

        from flink_tensorflow_tpu.parallel.dp import init_train_state, make_dp_train_step
        from flink_tensorflow_tpu.parallel.mesh import replicate

        t_open = time.monotonic()
        self._spans = getattr(ctx, "spans", None)
        if self._spans is not None:
            self._track = f"{ctx.task_name}.{ctx.subtask_index}"
            account = self._spans.account()
            charge = account.read()
        if ctx.mesh is None:
            raise RuntimeError(
                "DPTrainWindowFunction needs env.set_mesh(...) — the gang owns the mesh"
            )
        # Valid gang placements: parallelism 1 on a single-process
        # executor (the gang owns the whole mesh; the manual multi-host
        # pattern runs one such executor PER process), or — on a
        # distributed-record-plane cohort — exactly one subtask per
        # process (round-robin placement puts subtask p on process p, so
        # every process participates in the collective step).  Anything
        # else would leave some process outside the pjit call and the
        # first collective would hang, not error.
        required = ctx.num_processes if ctx.num_processes > 1 else 1
        if ctx.parallelism != required:
            raise RuntimeError(
                f"gang operator parallelism must be {required} "
                f"(num_processes={ctx.num_processes}) so every process "
                f"joins the collective step; got {ctx.parallelism}"
            )
        from flink_tensorflow_tpu.parallel.mesh import spans_processes

        self.ctx = ctx
        self.mesh = ctx.mesh
        data_size = self.mesh.shape.get("data", 1)
        if self.global_batch % data_size:
            raise ValueError(
                f"global_batch {self.global_batch} must be divisible by the "
                f"data-axis size {data_size}"
            )
        n_proc = jax.process_count() if spans_processes(self.mesh) else 1
        if self.global_batch % n_proc:
            raise ValueError(
                f"global_batch {self.global_batch} must be divisible by the "
                f"process count {n_proc}"
            )
        # Each process assembles only its shard of the global batch.
        self._policy = BucketPolicy(fixed_batch=self.global_batch // n_proc)
        optimizer = self.optimizer or optax.sgd(0.01)
        self.optimizer = optimizer
        self._step_fn = make_dp_train_step(self.model_def, optimizer, self.mesh)
        t_init = time.monotonic()
        state = self._restored or init_train_state(
            self.model_def, optimizer, jax.random.key(self.seed)
        )
        self._restored = None
        # Concrete at open (fresh init or restored host snapshot);
        # later states are pipelined futures we must not sync on.
        self._step_no = int(state["step"])
        t_replicate = time.monotonic()
        # Blocked on, so that the span is the transfer and not its enqueue.
        self._state = jax.block_until_ready(replicate(self.mesh, state))
        now = time.monotonic()
        ctx.metrics.timer("open_s").update(now - t_open)
        if self._spans is not None:
            spans, track = self._spans, self._track
            spans.span(track, "init_state", t_init, t_replicate)
            spans.span(track, "replicate", t_replicate, now)
            spans.span(track, "open", t_open, now,
                       charged({}, charge, account.read()))

    def process_window(self, key, window, elements, out: fn.Collector) -> None:
        import collections

        from flink_tensorflow_tpu.parallel.mesh import shard_batch

        self._out = out
        # The thread's account (tracing/flight.py), read at every stamp
        # where there is a hook.
        read = self._spans.account().read if self._spans is not None else lambda: None
        c0, t0 = read(), time.monotonic()
        _, arrays = _train_batch_arrays(list(elements), self.train_schema, self._policy)
        c1, t1 = read(), time.monotonic()
        batch = shard_batch(self.mesh, arrays)
        c2, t2 = read(), time.monotonic()
        # Dispatch-and-go: the state chains asynchronously; metrics fetch
        # lags by pipeline_depth so the NEXT window's h2d transfer
        # overlaps this step's device compute.
        self._state, metrics = self._step_fn(self._state, batch)
        c3, t3 = read(), time.monotonic()
        self._step_no += 1
        # The host's work a step, against the step's device time.
        self.ctx.metrics.timer("feed_s").update(t3 - t0)
        if self._spans is not None:
            spans, track = self._spans, self._track
            args = {"step": self._step_no, "examples": len(elements)}
            spans.span(track, "assemble", t0, t1, charged(dict(args), c0, c1))
            spans.span(track, "h2d_enqueue", t1, t2, charged(dict(args), c1, c2))
            spans.span(track, "dispatch", t2, t3, charged(dict(args), c2, c3))
        if self._pending is None:
            self._pending = collections.deque()
        self._pending.append((metrics, self._step_no, len(elements)))
        self._drain(out, self.pipeline_depth - 1)

    def _drain(self, out: fn.Collector, keep: int) -> None:
        import numpy as np

        while self._pending and len(self._pending) > keep:
            metrics, step_no, n = self._pending.popleft()
            account = self._spans.account() if self._spans is not None else None
            if account is not None:
                charge = account.read()
            t0 = time.monotonic()
            host = {k: np.asarray(v) for k, v in metrics.items()}
            t1 = time.monotonic()
            self.ctx.metrics.timer("drain_wait_s").update(t1 - t0)
            if account is not None:
                self._spans.span(self._track, "drain_wait", t0, t1, charged(
                    {"step": step_no, "examples": n}, charge, account.read()))
            host["step"] = np.asarray(step_no, np.int64)
            out.collect(TensorValue(host))
            self.ctx.metrics.meter("train_records").mark(n)
            self.ctx.metrics.counter("train_steps").inc()

    def on_finish(self, out: fn.Collector) -> None:
        if self._pending:
            self._drain(out, 0)

    def snapshot_state(self):
        # Emit in-flight metrics before the barrier (their records
        # precede it and never replay); _to_host then blocks on the
        # chained state, capturing every dispatched step.
        if self._pending and getattr(self, "_out", None) is not None:
            self._drain(self._out, 0)
        return {"state": _to_host(self._state) if self._state is not None else None}

    def restore_state(self, snap) -> None:
        # open() runs after restore in the operator lifecycle? No: restore
        # happens before start, open() on the subtask thread — stash and
        # let open() place it on the mesh.
        self._restored = snap["state"]

    def current_params(self):
        return _to_host(self._state["variables"])
