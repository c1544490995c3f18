"""Flight recorder — a cheap always-on ring of recent runtime events.

Tracing (``trace=True``) prices per-record spans and is therefore
opt-in; the flight recorder is the black box that is on by DEFAULT
(``JobConfig.flight_recorder``): a bounded per-process ring of recent
CONTROL-RATE events — job/subtask lifecycle, barrier injections and
snapshots, failures, and per-report metric deltas — recorded at a cost
bounded by one tuple append.  When something goes wrong the ring is
dumped to disk:

- **crash** — the first subtask failure (extends PR 6's crash-time
  reporter flush);
- **sanitizer violation** — ``join()`` dumps before re-raising;
- **signal** — SIGTERM/SIGINT land a dump (and a reporter flush)
  before the previous handler runs, so a killed worker keeps its last
  interval;
- **cancel** — ``JobHandle.cancel`` dumps explicitly.

Dumps are JSON (``{"kind": "flink-tpu-flight", ...}``) holding the
flight events in the tracer's ``(track, name, ph, t0, dur, args)``
tuple shape — plus, when tracing was on, the tracer's own recent ring —
so ``flink-tpu-trace --from-flight-dump`` replays one through the
standard attribution table and Chrome-trace export.

Disk writes only happen when a dump PATH is configured
(``JobConfig.flight_path`` / ``FLINK_TPU_FLIGHT_PATH``); the in-memory
ring itself always runs unless disabled (``flight_recorder=False`` /
``FLINK_TPU_FLIGHT=0`` — the zero-alloc off path, tier-1 guarded).

**Window-level spans.**  The ring also holds the hot path's spans at
WINDOW rate — never per record: one :class:`SpanHook` per subtask
(``ctx.spans``) takes ``(track, name, t0, t1, args)`` on
``time.monotonic()`` and appends the event here and, when tracing is
on, to the :class:`~flink_tensorflow_tpu.tracing.tracer.Tracer`.  Every
span of one batch carries the runner's batch number as ``args["seq"]``.
On the model operator's track (``<task>.<subtask>``), by thread:

- subtask thread: ``fill`` (first record of a window ingested ..
  ``process_window`` entered; ``args``: ``records``, ``ring_wait_s`` in
  the ring-full drain loop (emissions and blocked collections, counted
  as those), ``park_s`` inside it and ``park_before_s`` between the fill
  before and it, ``park_n``/``park_over_max_s`` over both, ``self_s`` =
  the fill less its ``emit``/``collect_wait`` children and its parks: the
  ingest), ``fire`` (``process_window`` entered .. returned; ``args``:
  ``records``, ``padded``, ``in_flight`` = windows dispatched and not
  yet fetched right after this fire's dispatch, before it collects: it
  reaches ``pipeline_depth`` on a backlog and never passes it;
  ``blocked_s`` = seconds of this fire inside ``collect_wait``; and
  ``early_chunks`` = chunks of the window that were on their way to the
  device before it fired: K - 1 of a full window's K, 0 where windows
  cross whole.  The operator's metric group has the same level as the
  gauge ``windows_in_flight``, read when a report is taken),
  ``early_put`` (one chunk of the window now filling claimed and its
  ``device_put`` issued, inside that window's ``fill``; ``args``: ``seq``
  of the batch it will be part of, ``chunk`` = its place in the batch,
  ``bytes``),
  ``collect_wait`` (each blocking stretch of ``collect_ready``),
  ``emit`` (one fetched batch handed downstream), ``open`` with children
  ``params_to_device`` and ``jit_warmup_compile``, and on the chain
  head's track the instant ``park.overslept`` (a park that returned more
  than 50 ms after the timeout it asked for);
- lane thread: ``lane_wait`` (dispatch call .. lane picked it up, only
  where a lane pool exists), ``enqueue`` (``device_put`` of what had not
  been put before the fire + jit launch; ``bytes`` = all the batch's
  input bytes, ``early_bytes`` = those put before the fire.  The
  operator's counters ``h2d_bytes`` and ``h2d_early_bytes`` sum the two
  over fetched batches);
- fetch thread: ``in_flight`` (launched .. results on the host: where a
  window was shipped early, only its last chunks' transfer is still in
  front of the step; it and
  ``enqueue`` carry ``tokens``, the batch's real positions of the input
  field ``tokens``, where the method takes one: never padding, and what
  the operator's counter ``tokens`` sums beside ``batches``),
  ``unbatch`` (results built), ``handoff_wait`` (results queued ..
  popped by the subtask thread).

The gang train operator records ``assemble``, ``h2d_enqueue``,
``dispatch`` and ``drain_wait`` a step (``args["step"]``), and ``open``
with children ``init_state`` and ``replicate``.

**Post-mortem accessor.**  :func:`recorder_of` returns the ring of the
most recent job of a given name in this process, after the job has been
released — for a notebook, a test, a crash handler, or a benchmark
reader that only ever gets ``job.metrics``.
"""

from __future__ import annotations

import collections
import json
import os
import signal
import threading
import time
import typing

_TRUTHY = ("1", "true", "on", "yes")


def env_enabled() -> typing.Optional[bool]:
    """FLINK_TPU_FLIGHT: force the recorder on/off; None = unset."""
    v = os.environ.get("FLINK_TPU_FLIGHT")
    if v is None or v == "":
        return None
    return v.lower() in _TRUTHY


def env_flight_path() -> typing.Optional[str]:
    return os.environ.get("FLINK_TPU_FLIGHT_PATH") or None


#: Events the ring holds.  The window-level spans run near 50 events/s
#: on the stream path (ten a window, five windows a second) and 80 on
#: the train path (eight a step, ten steps a second); 16384 keeps the
#: last 200-330 s, so a reader at the end of a minute's run still finds
#: its first seconds (4096 held 50-80 s: too close).  At most ~6 MB.
DEFAULT_CAPACITY = 16384


class FlightRecorder:
    """Bounded ring of recent events + metric deltas.

    ``record`` is the hot(ish) entry point — one clock read and one
    deque append, safe from any thread (CPython deque appends are
    atomic) — but its callers are all CONTROL-RATE sites: checkpoints,
    lifecycle transitions, reporter ticks.  The ring never grows past
    ``capacity``; a long job keeps the most recent window, exactly the
    part a post-mortem needs.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._ring: typing.Deque[tuple] = collections.deque(maxlen=capacity)
        self.capacity = capacity
        self._last_counts: typing.Dict[str, typing.Any] = {}
        self._dump_lock = threading.Lock()
        #: Reasons already dumped (a crash dump and a signal dump may
        #: both fire; each reason lands once).
        self.dumped: typing.List[str] = []

    # -- recording -------------------------------------------------------
    def record(self, track: str, name: str,
               args: typing.Optional[dict] = None, *,
               t0: typing.Optional[float] = None, dur: float = 0.0) -> None:
        self._ring.append((track, name, "X" if dur else "i",
                           time.monotonic() if t0 is None else t0,
                           dur, args))

    def metric_delta(self, snapshot: typing.Mapping[str, typing.Mapping[str, typing.Any]]) -> None:
        """Fold one reporter snapshot into compact per-scope delta
        events: records in/out movement since the previous report.  One
        instant per ACTIVE scope per report — bounded by scope count,
        not record rate."""
        now = time.monotonic()
        for scope in snapshot:
            m = snapshot[scope]
            rec_in = (m.get("records_in") or {})
            rec_out = (m.get("records_out") or {})
            counts = (rec_in.get("count", 0), rec_out.get("count", 0))
            prev = self._last_counts.get(scope, (0, 0))
            if counts == prev:
                continue
            self._last_counts[scope] = counts
            self._ring.append((scope, "metrics.delta", "i", now, 0.0, {
                "records_in": counts[0] - prev[0],
                "records_out": counts[1] - prev[1],
                "queue_depth": m.get("queue_depth"),
            }))

    def events(self) -> typing.List[tuple]:
        return list(self._ring)

    # -- dumping ---------------------------------------------------------
    def dump(self, path: str, reason: str, *,
             tracer: typing.Optional[typing.Any] = None,
             extra: typing.Optional[dict] = None) -> typing.Optional[str]:
        """Write the ring (and, when tracing was on, the tracer's recent
        events + cohort metadata) to ``path`` atomically.  Idempotent
        per reason; best-effort — a full disk must never mask the
        failure being recorded.  Returns the path written, or None."""
        with self._dump_lock:
            if reason in self.dumped:
                return None
            self.dumped.append(reason)
        doc: typing.Dict[str, typing.Any] = {
            "kind": "flink-tpu-flight",
            "reason": reason,
            "pid": os.getpid(),
            "monotonic_s": time.monotonic(),
            "wall_time_s": time.time(),
            "events": [list(ev) for ev in self._ring],
        }
        if tracer is not None:
            doc["tracer_events"] = [list(ev) for ev in tracer.events()]
            doc["tracer_epoch_s"] = tracer.epoch
            if tracer.cohort_meta is not None:
                doc["cohort"] = dict(tracer.cohort_meta)
        if extra:
            doc["extra"] = extra
        try:
            parent = os.path.dirname(os.path.abspath(path))
            os.makedirs(parent, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        except OSError:
            import logging

            logging.getLogger(__name__).warning(
                "flight-recorder dump to %s failed", path, exc_info=True)
            return None
        return path


#: A park counts as overslept when it returns this long after the
#: timeout it asked for.
OVERSLEPT_S = 0.05


class SpanHook:
    """The one hook the hot path's window-level spans go through: one
    per subtask, handed to every chained operator as ``ctx.spans``
    (None when the flight ring and the tracer are both off — callers
    guard with one ``is None`` test and build no ``args``).

    Callers fire once a window, batch or step — never per record — with
    two clock reads they already have.  The runtime's loops report each
    park of the subtask thread through :meth:`park`; the sums wait here
    for the ``fill`` span that closes next (:meth:`take_parks`)."""

    __slots__ = ("_flight", "_tracer", "park_s", "park_n", "park_over_max_s")

    def __init__(self, flight: typing.Optional[FlightRecorder],
                 tracer: typing.Optional[typing.Any] = None):
        self._flight = flight._ring if flight is not None else None
        self._tracer = tracer
        self.park_s = 0.0
        self.park_n = 0
        self.park_over_max_s = 0.0

    def _write(self, ev: tuple) -> None:
        if self._flight is not None:
            self._flight.append(ev)
        if self._tracer is not None:
            self._tracer.record(ev)

    def span(self, track: str, name: str, t0: float, t1: float,
             args: typing.Optional[dict] = None) -> None:
        self._write((track, name, "X", t0, t1 - t0, args))

    def instant(self, track: str, name: str, ts: float,
                args: typing.Optional[dict] = None) -> None:
        self._write((track, name, "i", ts, 0.0, args))

    def park(self, track: str, asked: typing.Optional[float], slept: float,
             woken: bool, now: float) -> None:
        """One park of the subtask thread: it asked to wait ``asked``
        seconds (None = until signalled) and came back after ``slept``."""
        self.park_s += slept
        self.park_n += 1
        if asked is not None and slept > asked + OVERSLEPT_S:
            over = slept - asked
            if over > self.park_over_max_s:
                self.park_over_max_s = over
            self.instant(track, "park.overslept", now, {
                "asked_s": asked, "slept_s": slept, "woken": woken})

    def take_parks(self) -> typing.Tuple[float, int, float]:
        """(seconds, count, longest oversleep) of the parks since the
        last call; clears them."""
        out = (self.park_s, self.park_n, self.park_over_max_s)
        self.park_s, self.park_n, self.park_over_max_s = 0.0, 0, 0.0
        return out


#: (job name, recorder) of the most recent job started in this process.
_kept: typing.Optional[typing.Tuple[str, FlightRecorder]] = None


def keep(job_name: str, recorder: typing.Optional[FlightRecorder]) -> None:
    """Called by ``execute_async``: the newest job's ring replaces the
    one kept (one job, bounded by the ring's capacity)."""
    global _kept
    _kept = (job_name, recorder) if recorder is not None else None


def recorder_of(job_name: str) -> typing.Optional[FlightRecorder]:
    """The flight ring of the most recent job in this process if it ran
    under ``job_name`` (``env.execute_async(job_name)``), else None.  It
    stays reachable after the job's handle, environment and executor
    are released; ``.events()`` gives the ``(track, name, ph, t0, dur,
    args)`` tuples on ``time.monotonic()``."""
    if _kept is not None and _kept[0] == job_name:
        return _kept[1]
    return None


def load_flight_dump(path: str) -> dict:
    """Parse a dump back into event-tuple form: ``events`` /
    ``tracer_events`` become the tracer's ``(track, name, ph, t0, dur,
    args)`` tuples, time-ordered — ready for attribution or
    ``events_to_chrome``."""
    with open(path) as f:
        doc = json.load(f)
    if doc.get("kind") != "flink-tpu-flight":
        raise ValueError(f"{path} is not a flight-recorder dump")
    for key in ("events", "tracer_events"):
        if key in doc:
            doc[key] = sorted(
                (tuple(ev) for ev in doc[key]), key=lambda ev: ev[3])
    return doc


def flight_dump_to_chrome(doc: dict) -> dict:
    """A dump as a Perfetto-loadable Chrome trace (flight events and,
    when present, the tracer's spans on their own tracks)."""
    from flink_tensorflow_tpu.tracing.tracer import events_to_chrome

    events = list(doc.get("events", ())) + list(doc.get("tracer_events", ()))
    events.sort(key=lambda ev: ev[3])
    epoch = doc.get("tracer_epoch_s")
    if epoch is None:
        epoch = min((ev[3] for ev in events), default=0.0)
    trace = events_to_chrome(
        events, epoch=epoch,
        process_name=f"flight dump ({doc.get('reason', '?')})")
    if "cohort" in doc:
        trace["cohort"] = doc["cohort"]
    return trace


class ShutdownFlusher:
    """SIGTERM/SIGINT hook: run the registered flush callbacks (reporter
    flush, flight dump, trace export), then hand control back to the
    PREVIOUS handler so process semantics are unchanged — a killed
    worker still dies, it just stops losing its final reporting
    interval.  Installable only from the main thread (signal module
    contract); elsewhere ``install`` is a no-op returning False."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self, callbacks: typing.Sequence[typing.Callable[[], None]]):
        self.callbacks = list(callbacks)
        self._prev: typing.Dict[int, typing.Any] = {}
        self._installed = False

    def _handler(self, signum, frame) -> None:
        self.flush()
        prev = self._prev.get(signum)
        self.uninstall()
        if callable(prev):
            prev(signum, frame)
        elif prev != signal.SIG_IGN:
            # Re-deliver with default disposition (terminate / KeyboardInterrupt).
            signal.raise_signal(signum)

    def flush(self) -> None:
        for cb in self.callbacks:
            try:
                cb()
            except Exception:  # noqa: BLE001 — observability only
                import logging

                logging.getLogger(__name__).warning(
                    "shutdown flush callback failed", exc_info=True)

    def install(self) -> bool:
        if self._installed or threading.current_thread() is not threading.main_thread():
            return False
        try:
            for sig in self.SIGNALS:
                self._prev[sig] = signal.getsignal(sig)
                signal.signal(sig, self._handler)
        except (ValueError, OSError):  # non-main thread / exotic platform
            self.uninstall()
            return False
        self._installed = True
        return True

    def uninstall(self) -> None:
        if not self._installed:
            return
        self._installed = False
        for sig, prev in self._prev.items():
            try:
                if signal.getsignal(sig) == self._handler:
                    signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
        self._prev.clear()
