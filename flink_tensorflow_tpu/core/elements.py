"""Stream elements — the wire protocol between operator subtasks.

The reference (flink-tensorflow on Apache Flink) inherits Flink's
``StreamElement`` hierarchy: records, watermarks, checkpoint barriers and
end-of-partition events flow through the same channels (SURVEY.md §1 L1).
This module is the TPU-native framework's equivalent: plain Python objects
on the host-side record plane.  Device data never flows through CHANNELS —
records crossing a queue, shuffle or checkpoint carry host buffers (numpy);
only the model operators move them to HBM.
"""

from __future__ import annotations

import dataclasses
import typing

MAX_WATERMARK = float("inf")


@dataclasses.dataclass(slots=True)
class StreamRecord:
    """A data record with an optional event-time timestamp.

    ``trace`` carries the span tracer's per-record context
    (tracing.TraceContext) when the job runs traced AND this record was
    sampled at its source; None always otherwise.  It rides through
    channel queues and pickled shuffle frames with the record, so one
    logical record is one trace across threads and processes.
    """

    value: typing.Any
    timestamp: typing.Optional[float] = None
    trace: typing.Optional[typing.Any] = None


@dataclasses.dataclass(slots=True, frozen=True)
class Watermark:
    """Event-time watermark: no records with ts <= ``timestamp`` will follow."""

    timestamp: float


@dataclasses.dataclass(slots=True, frozen=True)
class CheckpointBarrier:
    """Chandy-Lamport snapshot barrier (Flink-style aligned checkpointing).

    Injected at sources by the checkpoint coordinator; operators align
    barriers across their input channels, snapshot state, then forward the
    barrier downstream (SURVEY.md §5 "Checkpoint / resume").
    """

    checkpoint_id: int


@dataclasses.dataclass(slots=True, frozen=True)
class EndOfPartition:
    """Sent once per output channel when an upstream subtask finishes."""


StreamElement = typing.Union[StreamRecord, Watermark, CheckpointBarrier, EndOfPartition]


@dataclasses.dataclass(slots=True, frozen=True)
class SideOutput:
    """Value wrapper routing a record to a named side output.

    Operators that divert records (late data from event-time windows,
    Flink's ``sideOutputLateData``) emit ``SideOutput(tag, value)`` on
    their regular output; ``DataStream.side_output(tag)`` taps and
    unwraps them, while the main stream filters them out.
    """

    tag: str
    value: typing.Any


class SourceIdle:
    """Sentinel a SourceFunction may yield while WAITING (socket quiet,
    pacing sleep): no record is emitted, but the source loop gets a turn
    to serve checkpoint barriers and notifications.  Without it, a
    source blocked in I/O holds up coordinator-triggered checkpoints
    indefinitely (the barrier can only be injected between yields)."""

    __slots__ = ()


SOURCE_IDLE = SourceIdle()
