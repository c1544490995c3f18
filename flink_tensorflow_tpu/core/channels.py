"""Host-side record channels between operator subtasks.

Equivalent of Flink's Netty credit-based shuffle (SURVEY.md §2 "Distributed
communication backend") scoped to one host: bounded queues give backpressure;
each downstream subtask owns one :class:`InputGate` merging the channels from
all upstream subtasks, which is where checkpoint-barrier alignment happens.

The gate is **event-driven**: readers block on a condition variable and are
woken by the first put (or :meth:`wake`, or :meth:`close`) — there is no
timed poll interval anywhere on the record plane, so an idle hop costs a
wakeup latency of one ``notify``, not a 50 ms sleep quantum (the
``collection_poll`` / idle-poll floor components of round 5).  Writers
blocked on a full queue are likewise woken by the consuming ``poll``.

Only host objects (numpy buffers, metadata) cross channels.  Device arrays
stay in HBM inside the model operators — moving ``jax.Array``s through the
record plane would serialize HBM traffic through the host and throw away the
zero-copy design (BASELINE.json:4).

A native C++ ring-buffer backend can replace the deque without touching the
gate protocol (see native/ — SURVEY.md §2 notes the reference's only native
component is the external TF core; ours is the channel layer).

Operator chaining (analysis/chaining.py + core/runtime.py) removes this
layer entirely from forward same-parallelism hops: chained operators pass
records by direct method call and no gate exists between them.
"""

from __future__ import annotations

import collections
import threading
import time
import typing

from flink_tensorflow_tpu.core import elements as el


class InputGate:
    """Merged input for one subtask: N channels + barrier alignment.

    Writers push ``(channel_idx, element)`` into a shared bounded deque.
    Per-channel FIFO order is preserved because each writer is a single
    thread.  During barrier alignment, elements from already-barriered
    channels are stashed and replayed after the checkpoint completes —
    Flink's aligned exactly-once protocol (SURVEY.md §5).
    """

    def __init__(self, num_channels: int, capacity: int = 1024, *,
                 sanitizer: typing.Optional[typing.Any] = None,
                 name: typing.Optional[str] = None):
        self.num_channels = num_channels
        self.capacity = capacity
        self._queue: typing.Deque[typing.Tuple[int, el.StreamElement]] = (
            collections.deque()
        )
        self._stashed: typing.List[typing.Deque[typing.Tuple[int, el.StreamElement]]] = [
            collections.deque() for _ in range(num_channels)
        ]
        self._replay: typing.Deque[typing.Tuple[int, el.StreamElement]] = collections.deque()
        self._blocked: typing.List[bool] = [False] * num_channels
        self._closed = False
        #: Debug-mode sanitizer (core/sanitizer_rt): when set, the gate's
        #: lock/condvars are instrumented (happens-before + deadlock
        #: detection) and every delivery is checked against the barrier-
        #: alignment state machine.  None (production) keeps plain
        #: threading primitives and one is-None test per delivery.
        self._san = sanitizer
        self._san_name = name or f"gate@{id(self):x}"
        #: One lock, two wait-sets: readers park on ``_not_empty`` (woken
        #: by put/wake/close), writers on ``_not_full`` (woken by poll's
        #: dequeue and by close) — fully event-driven, no poll quantum.
        if sanitizer is not None:
            self._lock = sanitizer.lock(f"{self._san_name}.lock")
            self._not_empty = sanitizer.condition(
                f"{self._san_name}.not_empty", self._lock)
            self._not_full = sanitizer.condition(
                f"{self._san_name}.not_full", self._lock)
        else:
            self._lock = threading.Lock()
            self._not_empty = threading.Condition(self._lock)
            self._not_full = threading.Condition(self._lock)
        # -- observability (metrics/: pull-based gauges read these) ------
        #: Deepest queue occupancy ever observed at a put (monotone max).
        self.high_watermark = 0
        #: Total seconds writers spent blocked on a full queue — the
        #: backpressure signal.
        self.blocked_put_s = 0.0
        #: Per-channel cumulative put counts — the record plane's
        #: PER-EDGE traffic counters (the executor maps channel ranges
        #: back to logical edges for the ``edge*_queue_puts`` gauges;
        #: a chained edge has no gate, hence provably zero queue puts).
        self.puts_per_channel: typing.List[int] = [0] * num_channels
        #: Per-channel elements currently buffered anywhere in the gate
        #: (queue + alignment stash + replay) — decremented only when
        #: poll hands the element to the operator.
        self.buffered_per_channel: typing.List[int] = [0] * num_channels
        #: Wake sentinels currently sitting in the queue — subtracted
        #: from the depth gauge so they never read as buffered records.
        self._wake_sentinels = 0
        #: Space listeners (core/reactor): invoked under the gate lock on
        #: the full -> not-full transition (and on close) so a PAUSED
        #: reactor connection re-arms event-driven instead of polling.
        #: Listeners must be non-blocking (a reactor wakeup pipe write).
        self._space_listeners: typing.List[typing.Callable[[], None]] = []
        #: Drain listeners (record-plane flow control): invoked under the
        #: gate lock when the consuming ``poll`` pulls the queue DOWN
        #: across the low-water mark (and on close).  The shuffle
        #: server's routes use this as the gate-drain -> credit-replenish
        #: hook: grants withheld while the gate sat near-full are issued
        #: once the consumer demonstrably drains.  Edge-triggered at
        #: ``capacity // 2`` so a hot consumer costs one callback per
        #: refill cycle, not one per element.
        self._drain_listeners: typing.List[typing.Callable[[], None]] = []
        self._low_water = max(1, capacity // 2)

    # -- writer side ---------------------------------------------------
    def put(self, channel_idx: int, element: el.StreamElement) -> float:
        """Enqueue; returns seconds spent blocked on a full queue (0.0 on
        the uncontended fast path — callers attribute it to the WRITING
        subtask's backpressure time)."""
        with self._not_full:
            if len(self._queue) < self.capacity or self._closed:
                blocked = 0.0
            else:
                t0 = time.monotonic()
                while len(self._queue) >= self.capacity and not self._closed:
                    self._not_full.wait()
                blocked = time.monotonic() - t0
                self.blocked_put_s += blocked
            if self._closed:
                # Gate torn down (job cancelled/finished): drop silently.
                return blocked
            self._queue.append((channel_idx, element))
            self.puts_per_channel[channel_idx] += 1
            self.buffered_per_channel[channel_idx] += 1
            depth = len(self._queue)
            if depth > self.high_watermark:
                self.high_watermark = depth
            self._not_empty.notify()
            return blocked

    def try_put(self, channel_idx: int, element: el.StreamElement) -> bool:
        """Non-blocking :meth:`put` for the reactor's receive path:
        False when the queue is full (the caller pauses its connection
        and retries after a space listener fires).  A closed gate drops
        silently and reports True — same teardown semantics as put."""
        with self._not_full:
            if self._closed:
                return True
            if len(self._queue) >= self.capacity:
                return False
            self._queue.append((channel_idx, element))
            self.puts_per_channel[channel_idx] += 1
            self.buffered_per_channel[channel_idx] += 1
            depth = len(self._queue)
            if depth > self.high_watermark:
                self.high_watermark = depth
            self._not_empty.notify()
            return True

    def try_put_batch(self, channel_idx: int,
                      elements: typing.Sequence[el.StreamElement]) -> int:
        """Batch :meth:`try_put` for the reactor's coalesced frames:
        append as many of ``elements`` as capacity allows under ONE lock
        acquisition and ONE reader wakeup (per-element notifies are the
        dominant cost of frame expansion at 100k+ records/s).  Returns
        the count accepted — the caller re-offers the rest after a space
        listener fires.  A closed gate swallows everything (drop)."""
        with self._not_full:
            if self._closed:
                return len(elements)
            room = self.capacity - len(self._queue)
            if room <= 0:
                return 0
            taken = 0
            append = self._queue.append
            for element in elements:
                if taken >= room:
                    break
                append((channel_idx, element))
                taken += 1
            self.puts_per_channel[channel_idx] += taken
            self.buffered_per_channel[channel_idx] += taken
            depth = len(self._queue)
            if depth > self.high_watermark:
                self.high_watermark = depth
            self._not_empty.notify()
            return taken

    def add_space_listener(self, fn: typing.Callable[[], None]) -> None:
        """Register a callback fired (under the gate lock — it must not
        block) whenever the queue leaves the full state or the gate
        closes.  The reactor uses this to resume paused connections
        event-driven — no timed re-poll on the backpressure path."""
        with self._lock:
            self._space_listeners.append(fn)

    def _notify_space(self) -> None:
        for fn in self._space_listeners:
            try:
                fn()
            except Exception:  # noqa: BLE001 — observer only, never the plane
                pass

    def add_drain_listener(self, fn: typing.Callable[[], None]) -> None:
        """Register a callback fired (under the gate lock — it must not
        block) when the consumer drains the queue below the low-water
        mark, and on close.  This is the credit-replenish hook: a
        receiver route that withheld grants against a backed-up gate
        re-evaluates once the downstream demonstrably consumes."""
        with self._lock:
            self._drain_listeners.append(fn)

    def _notify_drain(self) -> None:
        for fn in self._drain_listeners:
            try:
                fn()
            except Exception:  # noqa: BLE001 — observer only, never the plane
                pass

    def wake(self) -> None:
        """Break a blocked :meth:`poll` immediately.

        For operator-owned background threads (e.g. the model runner's
        fetch thread) whose completions should be handled NOW rather
        than after the subtask loop's deadline wait expires.  The
        sentinel makes ``poll`` return None early; the loop then
        re-evaluates the operator's ``next_deadline`` and fires.
        Lossless: no stream element is consumed or reordered."""
        with self._not_empty:
            self._queue.append((-1, None))
            self._wake_sentinels += 1
            self._not_empty.notify()

    # -- reader side (single consumer thread) --------------------------
    def poll(self, timeout: typing.Optional[float] = None) -> typing.Optional[typing.Tuple[int, el.StreamElement]]:
        """Next (channel, element) honoring blocked channels.

        Blocks event-driven: ``timeout=None`` waits until a put /
        :meth:`wake` / :meth:`close` arrives (no timed re-poll).  Returns
        None on timeout, wake sentinel, or a closed-and-empty gate.
        """
        while self._replay:
            idx, element = self._replay.popleft()
            if self._blocked[idx]:
                self._stashed[idx].append((idx, element))
                continue
            self.buffered_per_channel[idx] -= 1
            if self._san is not None:
                self._san.gate_delivered(self._san_name, idx)
            return idx, element
        deadline = None if timeout is None else (time.monotonic() + timeout)
        while True:
            with self._not_empty:
                while not self._queue:
                    if self._closed:
                        return None
                    if deadline is None:
                        self._not_empty.wait()
                    else:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0 or not self._not_empty.wait(remaining):
                            if not self._queue:
                                return None
                idx, element = self._queue.popleft()
                self._not_full.notify()
                if self._space_listeners and len(self._queue) == self.capacity - 1:
                    # full -> not-full transition: wake paused reactors.
                    self._notify_space()
                if self._drain_listeners and len(self._queue) == self._low_water - 1:
                    # crossed the low-water mark going DOWN: the consumer
                    # is keeping up — replenish withheld credits.
                    self._notify_drain()
                if idx < 0:
                    self._wake_sentinels -= 1
                    return None  # wake() sentinel: hand control back NOW
            if self._blocked[idx]:
                self._stashed[idx].append((idx, element))
                continue
            self.buffered_per_channel[idx] -= 1
            if self._san is not None:
                self._san.gate_delivered(self._san_name, idx)
            return idx, element

    def block_channel(self, idx: int) -> None:
        self._blocked[idx] = True
        if self._san is not None:
            self._san.gate_channel_blocked(self._san_name, idx)

    def unblock_all(self) -> None:
        self._blocked = [False] * self.num_channels
        if self._san is not None:
            self._san.gate_unblocked(self._san_name)
        stashed = self._stashed
        self._stashed = [collections.deque() for _ in range(self.num_channels)]
        for dq in stashed:
            self._replay.extend(dq)

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()
            # Paused reactor connections must not stay parked on a gate
            # nobody will ever drain again (try_put drops from here on).
            self._notify_space()
            self._notify_drain()

    @property
    def any_blocked(self) -> bool:
        return any(self._blocked)

    @property
    def depth(self) -> int:
        """Elements currently buffered (queue + alignment stashes +
        replay, minus un-consumed wake sentinels) — the queue-depth
        gauge.  Approximate under concurrent mutation; reporters
        tolerate off-by-a-few."""
        return max(0, len(self._queue) + len(self._replay)
                   + sum(len(d) for d in self._stashed)
                   - self._wake_sentinels)

    def channel_depth(self, idx: int) -> int:
        """Buffered elements attributable to channel ``idx`` — the
        per-edge depth gauges sum these over an edge's channel range."""
        return max(0, self.buffered_per_channel[idx])

    def channel_puts(self, idx: int) -> int:
        return self.puts_per_channel[idx]


class ChannelWriter:
    """Upstream handle to one channel of a downstream gate."""

    __slots__ = ("_gate", "_idx")

    def __init__(self, gate: InputGate, idx: int):
        self._gate = gate
        self._idx = idx

    def write(self, element: el.StreamElement) -> float:
        """Forward to the gate; returns seconds the write spent blocked
        (backpressure, attributed by Output to the writing subtask)."""
        return self._gate.put(self._idx, element)
