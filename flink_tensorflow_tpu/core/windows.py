"""Window assigners and triggers — micro-batching building blocks.

The reference's central performance mechanism is "Flink's windowed
micro-batching feeds" the model (BASELINE.json:4, :7): a count window turns
N single records into one batched ``Session.run``.  On TPU the same window
feeds one ``jax.jit`` call on a ``[B, ...]`` array (SURVEY.md §3.2), so the
window/trigger design directly controls MXU utilization and p50 latency:

- count trigger  -> fixed batch B (full MXU tiles, best throughput)
- timeout hybrid -> flush on count OR deadline (bounds p50 latency)
- adaptive latency trigger -> EWMA arrival-rate projection flushes
  partial windows that provably can't fill inside the latency budget
  (SURVEY.md §7 hard part 3 "adaptive batching" — the latency-TARGETING
  policy)
"""

from __future__ import annotations

import dataclasses
import time
import typing


@dataclasses.dataclass(frozen=True)
class CountWindow:
    """Identifies the n-th tumbling count window for a key/subtask."""

    index: int


@dataclasses.dataclass(frozen=True)
class TimeWindow:
    start: float
    end: float


class WindowAssigner:
    def assign(self, value: typing.Any, timestamp: typing.Optional[float]) -> typing.Any:
        raise NotImplementedError


class Trigger:
    """Decides when a window fires. Returns True to fire-and-purge."""

    def on_element(self, window_state: "WindowBuffer") -> bool:
        raise NotImplementedError

    def deadline(self, window_state: "WindowBuffer") -> typing.Optional[float]:
        """Processing-time deadline at which the window must flush, or None."""
        return None

    def has_deadlines(self) -> bool:
        """Whether this trigger can EVER declare a wall-clock deadline —
        purely-arrival-driven triggers (count, sliding count) inherit the
        base ``deadline`` and return False, which lets the chaining pass
        fuse their windows into source chains (analysis/chaining.py)."""
        return type(self).deadline is not Trigger.deadline

    def clone(self) -> "Trigger":
        """Per-subtask copy.  Stateless triggers (the default) are shared;
        triggers carrying mutable estimator state override this so
        parallel subtasks don't race on it."""
        return self

    # -- retention (sliding windows) -----------------------------------
    def retains(self) -> bool:
        """True when fires carry elements over into the next window
        (sliding semantics).  Retaining triggers are incompatible with
        zero-copy ring ingestion (fired slots recycle their payload)."""
        return False

    def fire_elements(self, window_state: "WindowBuffer") -> typing.List[typing.Any]:
        """The elements a fire emits (sliding triggers trim to the window
        size; tumbling fires emit everything)."""
        return window_state.elements

    def retain_count(self, window_state: "WindowBuffer") -> int:
        """How many TRAILING elements to seed the next window with."""
        return 0


class CountTrigger(Trigger):
    def __init__(self, count: int):
        if count <= 0:
            raise ValueError(f"count must be positive, got {count}")
        self.count = count

    def on_element(self, window_state):
        return len(window_state.elements) >= self.count


class CountOrTimeoutTrigger(Trigger):
    """Fire at B elements or ``timeout_s`` after the first element.

    This is the adaptive-batching policy that reconciles the reference's
    throughput-oriented count windows with the north-star p50 latency
    target (BASELINE.json:2): a sparse stream never waits more than
    ``timeout_s`` for a full batch.
    """

    def __init__(self, count: int, timeout_s: float):
        if count <= 0:
            raise ValueError(f"count must be positive, got {count}")
        if timeout_s <= 0:
            raise ValueError(f"timeout_s must be positive, got {timeout_s}")
        self.count = count
        self.timeout_s = timeout_s

    def on_element(self, window_state):
        return len(window_state.elements) >= self.count

    def deadline(self, window_state):
        if not window_state.elements:
            return None
        return window_state.first_element_time + self.timeout_s


class AdaptiveLatencyTrigger(Trigger):
    """Latency-TARGETED adaptive batcher (SURVEY.md §7 hard part 3): fires
    at B elements like a count trigger, but instead of holding partial
    windows for a static timeout it maintains an EWMA of the observed
    inter-arrival gap and fires a partial window as soon as the
    projection says the window cannot fill within the latency budget.

    Policy, per open window:

    - full (``n >= count``): fire (pure count behavior — at high offered
      rates the projection is short and batches stay full for the MXU);
    - projected fill time ``last_arrival + (count - n) * ewma_gap``
      within ``first_arrival + latency_budget_s``: keep waiting (the
      batch will fill in time);
    - otherwise the window provably won't fill inside the budget, so
      holding the buffered records buys nothing: flush one expected gap
      after the last arrival (a Nagle-style grace so micro-bursts still
      coalesce), never later than the hard budget.

    **Service-time reserve (r4):** the budget is END-TO-END — arrival to
    emitted result — but the trigger only controls the hold.  When the
    operator feeds back an observed per-batch service time
    (``observe_service_time``, wired by WindowOperator from the model
    function's runner EWMA), the fire deadline is pulled forward so that
    ``hold + service <= budget``: a window stops waiting out its Nagle
    grace the moment the remaining budget is needed for the device round
    trip.  Without feedback the behavior is unchanged.

    At 0.5x capacity this puts p50 near one inter-arrival gap plus the
    small-batch service time instead of near the budget — the static
    ``CountOrTimeoutTrigger`` parks every record at the timeout
    (round 2: 1149ms p50 against a 1000ms timeout).

    The EWMA is per-subtask (``clone``) and pools across keys of a keyed
    window — it estimates the subtask's aggregate arrival process.
    """

    def __init__(self, count: int, latency_budget_s: float, *,
                 ewma_alpha: float = 0.25):
        if count <= 0:
            raise ValueError(f"count must be positive, got {count}")
        if latency_budget_s <= 0:
            raise ValueError(
                f"latency_budget_s must be positive, got {latency_budget_s}")
        if not 0.0 < ewma_alpha <= 1.0:
            raise ValueError(f"ewma_alpha must be in (0, 1], got {ewma_alpha}")
        self.count = count
        self.latency_budget_s = latency_budget_s
        self.ewma_alpha = ewma_alpha
        self._gap_ewma: typing.Optional[float] = None
        self._last_arrival: typing.Optional[float] = None
        self._service_ewma: typing.Optional[float] = None

    def clone(self) -> "AdaptiveLatencyTrigger":
        return AdaptiveLatencyTrigger(
            self.count, self.latency_budget_s, ewma_alpha=self.ewma_alpha)

    def observe_service_time(self, service_s: float) -> None:
        """Feed the observed per-batch service time (dispatch -> result).
        The deadline reserves it out of the budget so holds never spend
        budget the round trip needs."""
        self._service_ewma = service_s

    def on_element(self, window_state):
        now = time.monotonic()
        if self._last_arrival is not None:
            gap = now - self._last_arrival
            self._gap_ewma = (
                gap if self._gap_ewma is None
                else (1.0 - self.ewma_alpha) * self._gap_ewma
                + self.ewma_alpha * gap
            )
        self._last_arrival = now
        if len(window_state.elements) >= self.count:
            return True
        d = self.deadline(window_state)
        return d is not None and now >= d

    def deadline(self, window_state):
        if not window_state.elements:
            return None
        hard = window_state.first_element_time + self.latency_budget_s
        if self._gap_ewma is None or self._last_arrival is None:
            return hard  # no rate estimate yet: behave like the timeout
        remaining = self.count - len(window_state.elements)
        projected_fill = self._last_arrival + remaining * self._gap_ewma
        if projected_fill <= hard:
            return hard  # on track to fill: let the count fire
        # Won't fill in budget: flush after one expected gap of quiet.
        d = min(hard, self._last_arrival + self._gap_ewma)
        if self._service_ewma is not None:
            # Reserve the device round trip out of the END-TO-END budget:
            # the latest on-time fire is ``hard - service``.  Clamped to
            # one expected gap after the FIRST arrival — firing earlier
            # collapses the window to a single record, and the per-call
            # overhead of 1-record dispatches can sink below the offered
            # rate (measured: service-reserve without this clamp drove
            # batch-1 fires whose ~RTT-per-call capacity was HALF the
            # offered rate — a queueing collapse with p50 in seconds,
            # strictly worse than the latency the reserve was saving).
            reserved = hard - self._service_ewma
            d = min(d, max(reserved,
                           window_state.first_element_time + self._gap_ewma))
        return d


class SlidingCountTrigger(Trigger):
    """Fire every ``slide`` new elements, emitting the last ``size``.

    Flink's ``countWindow(size, slide)``: early windows are partial
    (first fire after ``slide`` elements), steady-state windows overlap —
    each fire carries the trailing ``size - slide`` elements forward.
    """

    def __init__(self, size: int, slide: int):
        if size <= 0 or slide <= 0:
            raise ValueError(f"size and slide must be positive, got {size}, {slide}")
        self.size = size
        self.slide = slide

    def on_element(self, window_state):
        return len(window_state.elements) - window_state.retained >= self.slide

    def retains(self):
        return True

    def fire_elements(self, window_state):
        return window_state.elements[-self.size:]

    def retain_count(self, window_state):
        return min(len(window_state.elements), max(0, self.size - self.slide))


@dataclasses.dataclass
class WindowBuffer:
    """Accumulating contents of one in-flight window."""

    window: typing.Any
    elements: typing.List[typing.Any] = dataclasses.field(default_factory=list)
    timestamps: typing.List[typing.Optional[float]] = dataclasses.field(default_factory=list)
    first_element_time: float = 0.0
    #: Number of leading elements carried over from the previous fire
    #: (sliding windows) — triggers count "new" arrivals past this.
    retained: int = 0
    #: The window already fired at least once (event-time windows kept
    #: alive by allowed lateness: late arrivals RE-fire; end of input
    #: must not fire it again).
    fired: bool = False

    def add(self, value: typing.Any, timestamp: typing.Optional[float]) -> None:
        if not self.elements:
            self.first_element_time = time.monotonic()
        self.elements.append(value)
        self.timestamps.append(timestamp)


def snapshot_buffers(buffers: typing.Mapping[typing.Any, WindowBuffer]) -> dict:
    """Picklable snapshot of open windows (shared by the count/timeout and
    event-time window operators — one format, one restore path)."""
    return {
        key: (buf.window, list(buf.elements), list(buf.timestamps),
              buf.retained, buf.fired)
        for key, buf in buffers.items()
    }


def restore_buffers(snap: dict) -> typing.Dict[typing.Any, WindowBuffer]:
    out: typing.Dict[typing.Any, WindowBuffer] = {}
    for key, (window, elements, timestamps, *rest) in snap.items():
        # Older checkpoints carry no retained count / fired flag.
        buf = WindowBuffer(window=window, retained=rest[0] if rest else 0,
                           fired=rest[1] if len(rest) > 1 else False)
        buf.elements = list(elements)
        buf.timestamps = list(timestamps)
        # Restart resets the processing-time clock: timeout triggers count
        # from the restore, not the (meaningless) pre-crash wall time.
        buf.first_element_time = time.monotonic()
        out[key] = buf
    return out
