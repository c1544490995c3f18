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

**What the OS charged the thread.**  A span whose two stamps are read on
one thread carries two more args, the deltas of that thread's
:class:`ThreadAccount` between them: ``cpu_s`` (seconds on a core) and
``runq_s`` (seconds runnable and not run; left out where
``/proc/thread-self/schedstat`` cannot be read, never 0 in its place).
A wall-clock span of 2.3 s then says whether its thread ran, stood in a
run queue, or waited.  On the subtask thread ``fill``, ``fire``,
``collect_wait``, ``emit`` and ``open``; on the lane thread ``enqueue``;
on the fetch thread ``unbatch``, and on ``in_flight`` (which starts on
the lane thread) ``fetch_cpu_s`` / ``fetch_runq_s``: the stretch the
fetch thread itself spent getting the results, from ``fetch_reached_s``
in to the span's end; in the gang train operator ``assemble``,
``h2d_enqueue``, ``dispatch``, ``drain_wait`` and ``open``.  The kernel
books a running thread's seconds at its scheduler's tick, so a span's
``cpu_s`` may run a few ms past what it ran.  The chain head's metric
group reports the subtask thread's two sums since it started as the
gauges ``cpu_s`` and ``runq_s``, beside ``busy_s`` / ``idle_s`` /
``backpressure_s``, read when a report is taken.

**Track ``process``: the pulse.**  One daemon thread an executor
(:class:`Pulse`, ``flight-pulse``; none when the ring is off) sleeps
``PULSE_S`` and writes nothing while it wakes on time.  A wake more than
``OVERSLEPT_S`` late writes the instant ``pulse.late`` at the time it
woke, with ``late_s`` and what the gap was charged: ``cpu_s`` (the
process, since the wake before), ``runq_s`` (the pulse thread's own:
near ``late_s`` when it stood in a run queue, near 0 when it waited for
the interpreter's lock or the process was frozen), ``gc_s``, and the
deltas of the OS's slower accounts since their last sample, at most a
second back, each only where its source can be read: ``majflt``,
``steal_s``, ``throttled_s``, ``psi_cpu_s``, ``psi_mem_s``,
``psi_io_s``; and ``cause`` (:func:`book_stall`): ``gc``, ``off_core``,
``lock_held`` or ``nothing_ran``.  A generation-2 collection writes the
span ``gc`` (``args``: ``collected``).  The lateness feeds the timer
``process.pulse_late_s``; the first ``pulse.late`` of a second or more
dumps the ring with reason ``stall`` where a dump path is configured,
two seconds later (or when the job's threads are joined), so that the
spans that cover the gap, which are written when they end, are in it.
A stall of the device side is no ``pulse.late``: it is an ``in_flight``
far over the run's median whose ``fetch_cpu_s`` and ``fetch_runq_s``
read near 0.

**Post-mortem accessor.**  :func:`recorder_of` returns the ring of the
most recent job of a given name in this process, after the job has been
released — for a notebook, a test, a crash handler, or a benchmark
reader that only ever gets ``job.metrics``.
"""

from __future__ import annotations

import collections
import gc
import json
import os
import resource
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


#: A thread's seconds on a core and seconds runnable and not run, in ns.
SCHEDSTAT = "/proc/thread-self/schedstat"


class ThreadAccount:
    """What the OS has charged one hot-path thread: seconds on a core and
    seconds runnable and not run.  Made ON its thread (it opens that
    thread's ``schedstat`` once); :meth:`read` is one ``pread`` and may be
    called from any thread, which is how a gauge reads the subtask
    thread's.  Where the file cannot be read the seconds come from the
    thread's CPU clock and there is no run-queue figure: None, never 0."""

    __slots__ = ("tid", "_fd", "_clock", "_last")

    def __init__(self):
        #: Native id: the same file is ``/proc/self/task/<tid>/schedstat``.
        self.tid = threading.get_native_id()
        self._clock = time.pthread_getcpuclockid(threading.get_ident())
        try:
            self._fd: typing.Optional[int] = os.open(SCHEDSTAT, os.O_RDONLY)
        except OSError:
            self._fd = None
        self._last: typing.Tuple[float, typing.Optional[float]] = (0.0, None)
        self.read()

    def read(self) -> typing.Tuple[float, typing.Optional[float]]:
        """``(cpu_s, runq_s)`` since the thread started; once it has
        ended, what it was charged in all."""
        try:
            if self._fd is not None:
                cpu, runq = os.pread(self._fd, 64, 0).split()[:2]
                self._last = (int(cpu) * 1e-9, int(runq) * 1e-9)
            elif self._clock is not None:
                self._last = (time.clock_gettime_ns(self._clock) * 1e-9, None)
        except (OSError, ValueError):
            pass  # the thread has ended
        return self._last

    def close(self) -> None:
        fd, self._fd, self._clock = self._fd, None, None
        if fd is not None:
            os.close(fd)

    __del__ = close


def charged(args: dict, then, now, prefix: str = "") -> dict:
    """``args`` with what a thread was charged between two readings of
    its account: ``cpu_s`` and, where there is a run-queue figure,
    ``runq_s``."""
    args[prefix + "cpu_s"] = now[0] - then[0]
    if now[1] is not None and then[1] is not None:
        args[prefix + "runq_s"] = now[1] - then[1]
    return args


class SpanHook:
    """The one hook the hot path's window-level spans go through: one
    per subtask, handed to every chained operator as ``ctx.spans``
    (None when the flight ring and the tracer are both off — callers
    guard with one ``is None`` test and build no ``args``).

    Callers fire once a window, batch or step — never per record — with
    two clock reads they already have.  The runtime's loops report each
    park of the subtask thread through :meth:`park`; the sums wait here
    for the ``fill`` span that closes next (:meth:`take_parks`)."""

    __slots__ = ("_flight", "_tracer", "_threads", "park_s", "park_n",
                 "park_over_max_s")

    def __init__(self, flight: typing.Optional[FlightRecorder],
                 tracer: typing.Optional[typing.Any] = None):
        self._flight = flight._ring if flight is not None else None
        self._tracer = tracer
        self._threads = threading.local()
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

    def account(self) -> ThreadAccount:
        """The calling thread's account, made at its first span and closed
        when the thread ends: a span site reads it at its two stamps and
        hands the readings to :func:`charged`."""
        try:
            return self._threads.account
        except AttributeError:
            account = self._threads.account = ThreadAccount()
            return account

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


#: Seconds the pulse sleeps between two wakes.
PULSE_S = 0.1


def book_stall(late_s: float, *, gc_s: float = 0.0, cpu_s: float = 0.0,
               runq_s: typing.Optional[float] = None,
               **slow: float) -> str:
    """What a late wake of the pulse is booked to, from what its gap was
    charged, in this order: ``gc`` where full collections cover over half
    of it; ``off_core`` where the pulse thread stood that long in a run
    queue (without a run-queue figure: where the cgroup was throttled,
    cores were stolen or tasks waited for a core that long, by whichever
    of ``throttled_s`` / ``steal_s`` / ``psi_cpu_s`` there is);
    ``lock_held`` where neither does and the process was on its cores for
    over half of it (a thread ran on with the interpreter's lock: the
    spans that cover the gap say which, by their own ``cpu_s``); else
    ``nothing_ran`` (a frozen process, or the lock held by a call that
    itself blocked)."""
    half = late_s / 2
    if gc_s > half:
        return "gc"
    if runq_s is None:
        runq_s = max((slow[k] for k in ("throttled_s", "steal_s", "psi_cpu_s")
                      if k in slow), default=0.0)
    if runq_s > half:
        return "off_core"
    return "lock_held" if cpu_s > half else "nothing_ran"


def _cgroup_cpu_stat() -> typing.Optional[str]:
    """Path of the ``cpu.stat`` of this process's cgroup (v2, or v1's
    ``cpu`` controller), or None."""
    try:
        with open("/proc/self/cgroup") as f:
            rows = [line.rstrip("\n").split(":", 2) for line in f]
    except OSError:
        return None
    for _, controllers, path in (r for r in rows if len(r) == 3):
        if controllers == "" or "cpu" in controllers.split(","):
            stat = os.path.join("/sys/fs/cgroup", controllers, path.lstrip("/"), "cpu.stat")
            if os.access(stat, os.R_OK):
                return stat
    return None


class Pulse:
    """The process's heartbeat in the flight ring (track ``process``): one
    daemon thread an executor, none when the ring is off.  It sleeps
    ``period`` and records nothing while it wakes on time; a wake more
    than ``OVERSLEPT_S`` late leaves ``pulse.late`` with what the gap was
    charged and the ``cause`` :func:`book_stall` books it to.  It owns
    the ``gc.callbacks`` hook that writes a ``gc`` span a full collection.

    ``timer`` (the registry's ``process.pulse_late_s``) takes every
    lateness; ``on_stall`` is called once, for the first ``pulse.late`` of
    ``STALL_S`` or more (the executor dumps the ring with reason
    ``stall``): ``DUMP_AFTER`` wakes later or when the pulse is stopped,
    whichever comes first, because a span is written when it ends and the
    spans that cover the gap are still open when the pulse wakes."""

    #: The first lateness of this many seconds dumps the ring.
    STALL_S = 1.0
    #: ... this many wakes after it: two seconds, a dozen windows.
    DUMP_AFTER = 20
    #: The OS's slower accounts are sampled every this many wakes.
    SAMPLE_EVERY = 10

    def __init__(self, flight: FlightRecorder, *, timer=None,
                 on_stall: typing.Optional[typing.Callable[[], typing.Any]] = None,
                 period: float = PULSE_S):
        self._ring = flight._ring
        self._timer = timer
        self._on_stall = on_stall
        self.period = period
        self._stop = threading.Event()
        self._thread: typing.Optional[threading.Thread] = None
        #: Wakes left until the stall met is dumped; None while none was.
        self._dump_in: typing.Optional[int] = None
        #: (start, end) of the newest full collections, for ``gc_s``.
        self._collections: typing.Deque[tuple] = collections.deque(maxlen=32)
        self._collecting_since: typing.Optional[float] = None
        #: name -> (fd, reads the file's bytes to seconds or a count).
        self._sources: typing.Dict[str, typing.Tuple[int, typing.Callable]] = {}

    # -- lifecycle -------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        gc.callbacks.append(self._on_collection)
        self._thread = threading.Thread(target=self._run, name="flight-pulse",
                                        daemon=True)
        self._thread.start()

    def stop(self, join: bool = True) -> None:
        """Ends the thread and takes the collector's hook away; idempotent.
        ``join=False`` only signals (from a subtask's own last lines)."""
        self._stop.set()
        thread = self._thread
        if thread is None:
            return
        if join and thread is not threading.current_thread():
            thread.join(timeout=5.0)
            self._thread = None
        try:
            gc.callbacks.remove(self._on_collection)
        except ValueError:
            pass

    def _on_collection(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._collecting_since = time.monotonic()
            return
        t0, now = self._collecting_since, time.monotonic()
        if t0 is None:
            return
        # Kept first and cleared last: the pulse may take the interpreter's
        # lock between any two of these lines (it has waited for it), and
        # then still finds the collection, under way or done.
        self._collections.append((t0, now))
        self._collecting_since = None
        self._ring.append(("process", "gc", "X", t0, now - t0,
                           {"collected": info["collected"]}))

    # -- the OS's slower accounts ----------------------------------------
    def _open_sources(self) -> None:
        tick = 1.0 / os.sysconf("SC_CLK_TCK")

        def steal(text: bytes) -> float:  # first line: cpu user nice ... steal
            return int(text.split(b"\n", 1)[0].split()[8]) * tick

        def psi(text: bytes) -> float:  # some avg10=.. total=<us>
            return int(text.split(b"total=", 1)[1].split()[0]) * 1e-6

        def throttled(text: bytes) -> float:
            fields = dict(line.split()[:2] for line in text.splitlines() if line)
            if b"throttled_usec" in fields:
                return int(fields[b"throttled_usec"]) * 1e-6
            return int(fields[b"throttled_time"]) * 1e-9  # cgroup v1, ns

        wanted = [("steal_s", "/proc/stat", steal),
                  ("psi_cpu_s", "/proc/pressure/cpu", psi),
                  ("psi_mem_s", "/proc/pressure/memory", psi),
                  ("psi_io_s", "/proc/pressure/io", psi),
                  ("throttled_s", _cgroup_cpu_stat(), throttled)]
        for name, path, parse in wanted:
            if path is None:
                continue
            try:
                fd = os.open(path, os.O_RDONLY)
                parse(os.pread(fd, 4096, 0))
            except (OSError, ValueError, IndexError, KeyError):
                continue  # a source that is missing is left out
            self._sources[name] = (fd, parse)

    def _sample(self) -> typing.Dict[str, float]:
        sample = {"majflt": resource.getrusage(resource.RUSAGE_SELF).ru_majflt}
        for name, (fd, parse) in self._sources.items():
            try:
                sample[name] = parse(os.pread(fd, 4096, 0))
            except (OSError, ValueError, IndexError, KeyError):
                pass
        return sample

    # -- the thread ------------------------------------------------------
    def _run(self) -> None:
        account = ThreadAccount()
        self._open_sources()
        try:
            self._beat(account)
        finally:
            account.close()
            for fd, _ in self._sources.values():
                os.close(fd)
            self._sources.clear()

    def _beat(self, account: ThreadAccount) -> None:
        period, wait = self.period, self._stop.wait
        slow = self._sample()
        wakes = 0
        cpu, charge = time.process_time(), account.read()
        due = time.monotonic() + period
        while not wait(max(due - time.monotonic(), 0.0)):
            now = time.monotonic()
            wakes += 1
            if now - due > OVERSLEPT_S:
                slow = self._late(due, now, cpu, charge, account, slow)
            elif wakes % self.SAMPLE_EVERY == 0:
                slow = self._sample()
            if self._dump_in is not None:
                self._dump_in -= 1
                if self._dump_in <= 0:
                    self._dump()
            # What the next gap is measured from: read last, so that a late
            # wake's deltas are those of the gap and of nothing before it.
            cpu, charge = time.process_time(), account.read()
            due += period
            if due <= (now := time.monotonic()):
                due = now + period
        if self._dump_in is not None:
            self._dump()

    def _late(self, due: float, now: float, cpu: float, charge, account,
              slow: dict) -> dict:
        """The wake due at ``due`` came at ``now``: ``pulse.late``, with what
        the gap was charged since the wake before.  Returns the sample of
        the slower accounts it took."""
        late_s = now - due
        args = {"late_s": late_s, "cpu_s": time.process_time() - cpu}
        runq0, runq1 = charge[1], account.read()[1]
        if runq0 is not None and runq1 is not None:
            args["runq_s"] = runq1 - runq0
        collected = dict(self._collections)  # start -> end
        if (since := self._collecting_since) is not None:
            collected.setdefault(since, now)  # its hook has not closed it yet
        args["gc_s"] = sum(max(min(end, now) - max(start, due), 0.0)
                           for start, end in collected.items())
        sample = self._sample()
        for name, value in sample.items():
            if name in slow:
                args[name] = value - slow[name]
        args["cause"] = book_stall(**args)
        self._ring.append(("process", "pulse.late", "i", now, 0.0, args))
        if self._timer is not None:
            self._timer.update(late_s)
        if (late_s >= self.STALL_S and self._on_stall is not None
                and self._dump_in is None):
            self._dump_in = self.DUMP_AFTER
        return sample

    def _dump(self) -> None:
        on_stall, self._on_stall, self._dump_in = self._on_stall, None, None
        try:
            on_stall()
        except Exception:  # noqa: BLE001 - observability only
            import logging

            logging.getLogger(__name__).warning(
                "stall dump failed", exc_info=True)


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
