"""Per-subtask runtime context handed to rich functions at ``open()``.

Equivalent of Flink's ``RuntimeContext`` (subtask index, parallelism, metric
group, keyed state access).  The TPU-native addition is device placement:
each subtask may own a local device (operator-DP inference, one chip per
subtask — SURVEY.md §7 step 4) or participate in a gang mesh (DP training,
SURVEY.md §7 hard part 4).
"""

from __future__ import annotations

import contextlib
import typing

from flink_tensorflow_tpu.core.state import KeyedStateStore, StateDescriptor
from flink_tensorflow_tpu.metrics.registry import MetricGroup

if typing.TYPE_CHECKING:
    import jax


class RuntimeContext:
    def __init__(
        self,
        task_name: str,
        subtask_index: int,
        parallelism: int,
        keyed_state: KeyedStateStore,
        metric_group: MetricGroup,
        device: typing.Optional["jax.Device"] = None,
        mesh: typing.Optional[typing.Any] = None,
        job_config: typing.Optional[dict] = None,
        process_index: int = 0,
        num_processes: int = 1,
    ):
        self.task_name = task_name
        self.subtask_index = subtask_index
        self.parallelism = parallelism
        self._keyed_state = keyed_state
        self.metrics = metric_group
        #: Local device for per-subtask execution (operator-DP inference).
        self.device = device
        #: Shared jax.sharding.Mesh for gang operators (DP/TP training).
        self.mesh = mesh
        self.job_config = dict(job_config or {})
        #: Cohort identity (DistributedExecutor): which process hosts
        #: this subtask, out of how many.  Gang operators use it to
        #: validate one-subtask-per-process placement.
        self.process_index = process_index
        self.num_processes = num_processes
        #: Zero-arg callable breaking the subtask loop's poll sleep —
        #: operator-owned background threads (the model runner's fetch
        #: thread) call it when async results complete, so emission
        #: doesn't wait out the poll interval.  None for source subtasks
        #: (no input gate) and bare-function tests.
        self.wakeup: typing.Optional[typing.Callable[[], None]] = None
        #: Span tracer (flink_tensorflow_tpu.tracing.Tracer) when the
        #: job runs traced; None (the default) is the zero-cost off
        #: path.  Per-record spans (remote sinks' serde/wire, the decode
        #: runners' steps) are recorded through this on the
        #: ``task_name.subtask_index`` track; the window-level spans of
        #: the model and train operators go through ``spans`` below.
        self.tracer: typing.Optional[typing.Any] = None
        #: Window-level span hook (tracing.flight.SpanHook), one per
        #: subtask thread and shared by the operators chained onto it:
        #: ``spans.span(track, name, t0, t1, args)`` once a window,
        #: batch or step — never per record — lands in the always-on
        #: flight ring and, when tracing is on, in the tracer.  None when
        #: both are off.
        self.spans: typing.Optional[typing.Any] = None
        #: Job-wide compact wire dtype ("bf16"/"f16"/"int8"; None = f32):
        #: remote sinks narrow their TCP frames with it.
        self.wire_dtype: typing.Optional[str] = None
        #: Credit-based flow control on the record plane
        #: (JobConfig.flow_control): RemoteSink consults it at open() to
        #: decide whether to request a credit window from its peer
        #: RemoteSource; the shuffle writers get it from the executor
        #: directly.
        self.flow_control: bool = True
        #: Roofline attribution plane (metrics.roofline.RooflinePlane)
        #: when JobConfig.roofline is declared: model runners mint a
        #: per-operator probe from it at open() — static-cost join,
        #: ``roofline.*`` gauges, compile-event log.  None (the default)
        #: is the zero-cost off path.
        self.roofline: typing.Optional[typing.Any] = None

    def state(self, descriptor: StateDescriptor):
        return self._keyed_state.value_state(descriptor)

    @contextlib.contextmanager
    def with_key(self, key):
        """Scope keyed-state access to ``key`` outside the per-element
        window (end-of-input flushes, timer callbacks across keys)."""
        prev = self._keyed_state.current_key
        self._keyed_state.current_key = key
        try:
            yield
        finally:
            self._keyed_state.current_key = prev
