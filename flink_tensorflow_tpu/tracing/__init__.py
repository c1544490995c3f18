"""End-to-end span tracing + latency attribution (Perfetto-exportable).

Enable with ``JobConfig(trace=True)`` (optionally ``trace_path=...``,
``trace_sample_rate=...``) or ``FLINK_TPU_TRACE=1`` /
``FLINK_TPU_TRACE_PATH`` / ``FLINK_TPU_TRACE_SAMPLE``.  The CLI twin is
``flink-tpu-trace`` (``python -m flink_tensorflow_tpu.tracing``): run a
captured pipeline under tracing and print the per-operator stage
attribution table.  See ``tracer.py`` for the span model and
``attribution.py`` for the profiler.

Two rates.  The ``Tracer`` is per record (sampled, opt-in).  The
WINDOW-LEVEL spans of the model and train operators' hot paths — ``fill``,
``fire``, ``enqueue``, ``in_flight``, ``unbatch``, ``handoff_wait``,
``collect_wait``, ``emit``, ``park.overslept``, ``open``; a train step's
``assemble``, ``h2d_enqueue``, ``dispatch``, ``drain_wait`` — are always
on: one :class:`SpanHook` per subtask (``ctx.spans``) writes them, once
a window, into the flight ring and, when tracing is on, into the tracer
(``flight.py`` lists them with their cuts).  A span whose two stamps are
read on one thread carries what the OS charged that thread between them:
``cpu_s`` (seconds on a core) and, where the machine has
``/proc/thread-self/schedstat``, ``runq_s`` (seconds runnable and not
run).  Beside the operators' tracks the ring holds the track ``process``,
written by one pulse thread an executor (:class:`~flink_tensorflow_tpu.
tracing.flight.Pulse`, none when the ring is off): the instant
``pulse.late`` for a wake that came more than 50 ms late, with what the
gap was charged and the ``cause`` it is booked to (``gc``, ``off_core``,
``lock_held``, ``nothing_ran``), and the span ``gc`` for a full
collection.  :func:`recorder_of` hands back the ring of the most recent
job of a given name after the job has been released:
``recorder_of("job").events()``.
"""

from flink_tensorflow_tpu.tracing.attribution import (
    STAGES,
    attribution,
    events_from_chrome,
    format_attribution_table,
)
from flink_tensorflow_tpu.tracing.clocksync import OffsetEstimator
from flink_tensorflow_tpu.tracing.flight import (
    FlightRecorder,
    SpanHook,
    load_flight_dump,
    recorder_of,
)
from flink_tensorflow_tpu.tracing.stitch import (
    cross_process_traces,
    merge_cohort_trace_files,
    merge_cohort_traces,
)
from flink_tensorflow_tpu.tracing.tracer import (
    TraceContext,
    Tracer,
    env_enabled,
    env_sample_rate,
    env_trace_path,
    events_to_chrome,
)

__all__ = [
    "STAGES",
    "FlightRecorder",
    "OffsetEstimator",
    "SpanHook",
    "TraceContext",
    "Tracer",
    "attribution",
    "cross_process_traces",
    "env_enabled",
    "env_sample_rate",
    "env_trace_path",
    "events_from_chrome",
    "events_to_chrome",
    "format_attribution_table",
    "load_flight_dump",
    "merge_cohort_trace_files",
    "merge_cohort_traces",
    "recorder_of",
]
