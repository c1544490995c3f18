"""The program's own record of its stalls (tracing/flight.py): a thread's
account of what the OS charged it, the pulse on track ``process`` with
``pulse.late`` and ``gc``, the booking of a late wake to a cause, the gauges
and the timer in the registry, the ``stall`` dump, and that none of it exists
with the ring off or after the job is released.

All tier-1 fast: no TPU, LeNet on tiny windows (helpers of test_window_spans).
"""

import ctypes
import gc
import json
import os
import threading
import time

import pytest

from flink_tensorflow_tpu.tracing import flight
from flink_tensorflow_tpu.tracing.flight import (
    FlightRecorder,
    Pulse,
    ThreadAccount,
    book_stall,
    charged,
    load_flight_dump,
)
from test_window_spans import WINDOW, WINDOWS, _job, _paced_source, lenet  # noqa: F401

HAS_SCHEDSTAT = os.access(flight.SCHEDSTAT, os.R_OK)


def _spin(seconds):
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        pass


def _hold_the_lock(seconds):
    """Sleeps inside a C call that keeps the interpreter's lock."""
    ctypes.PyDLL(None).usleep(int(seconds * 1e6))


def _schedstat_fds():
    out = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if target.endswith("/schedstat"):
            out.append(target)
    return out


def _pulse_threads():
    return [t for t in threading.enumerate() if t.name == "flight-pulse"]


# -- a thread's account ------------------------------------------------------

def _two_threads(work):
    """One thread does ``work``, the other sleeps as long; each reads its own
    account before and after.  Returns {name: (account, before, after)}."""
    out, gate = {}, threading.Barrier(2)

    def body(name, fn):
        account = ThreadAccount()
        gate.wait()
        before = account.read()
        fn()
        out[name] = (account, before, account.read())

    threads = [threading.Thread(target=body, args=("worker", work)),
               threading.Thread(target=body, args=("sleeper", lambda: time.sleep(0.15)))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def test_account_rises_with_its_threads_work_and_not_with_anothers():
    got = _two_threads(lambda: _spin(0.15))
    worker, sleeper = (charged({}, before, after) for _, before, after in
                       (got["worker"], got["sleeper"]))
    assert worker["cpu_s"] > 0.05, worker
    assert sleeper["cpu_s"] < 0.03, sleeper
    assert got["worker"][0].tid != got["sleeper"][0].tid
    assert ("runq_s" in worker) == HAS_SCHEDSTAT
    # Its threads have ended: another thread still reads what each was charged in all.
    assert got["worker"][0].read()[0] >= got["worker"][2][0] - 1e-9


def test_account_is_read_from_another_thread_while_its_own_runs():
    box, stop = {}, threading.Event()

    def body():
        box["account"] = ThreadAccount()
        _spin(0.1)
        box["spun"] = True
        stop.wait(10)

    t = threading.Thread(target=body)
    t.start()
    while "spun" not in box:
        time.sleep(0.01)
    try:
        assert box["account"].read()[0] > 0.03  # read here, charged there
        assert ThreadAccount().tid != box["account"].tid
    finally:
        stop.set()
        t.join()


def test_account_falls_back_to_cpu_seconds_without_schedstat(monkeypatch):
    monkeypatch.setattr(flight, "SCHEDSTAT", "/proc/thread-self/no-such-file")
    got = _two_threads(lambda: _spin(0.15))
    account, before, after = got["worker"]
    assert before[1] is None and after[1] is None  # absent, never 0
    args = charged({"seq": 7}, before, after)
    assert set(args) == {"seq", "cpu_s"} and args["cpu_s"] > 0.05
    assert charged({}, *got["sleeper"][1:])["cpu_s"] < 0.03
    assert charged({}, before, after, "fetch_").keys() == {"fetch_cpu_s"}
    # The thread has ended: what it was charged in all, its last lines included.
    assert account.read()[0] == pytest.approx(after[0], abs=5e-3) and account.read()[1] is None


def test_account_closes_its_descriptor():
    before = len(_schedstat_fds())
    account = ThreadAccount()
    assert len(_schedstat_fds()) == before + HAS_SCHEDSTAT
    last = account.read()
    account.close()
    assert len(_schedstat_fds()) == before
    assert account.read() == last
    account.close()  # idempotent


# -- the booking ---------------------------------------------------------------

@pytest.mark.parametrize("sample,cause", [
    (dict(late_s=0.08, gc_s=0.075, cpu_s=0.08, runq_s=0.0), "gc"),
    (dict(late_s=2.0, gc_s=0.07, cpu_s=0.1, runq_s=1.9), "off_core"),
    (dict(late_s=2.0, gc_s=0.0, cpu_s=1.9, runq_s=0.01), "lock_held"),
    (dict(late_s=2.0, gc_s=0.0, cpu_s=0.02, runq_s=0.001), "nothing_ran"),
    # Collections come first, then the run queue, then the process's CPU.
    (dict(late_s=1.0, gc_s=0.6, cpu_s=0.9, runq_s=0.9), "gc"),
    (dict(late_s=1.0, gc_s=0.4, cpu_s=0.9, runq_s=0.6), "off_core"),
    # No run-queue figure: off its cores by whichever slower account there is.
    (dict(late_s=2.0, gc_s=0.0, cpu_s=0.1, throttled_s=1.5), "off_core"),
    (dict(late_s=2.0, gc_s=0.0, cpu_s=0.1, steal_s=0.0, psi_cpu_s=1.2), "off_core"),
    (dict(late_s=2.0, gc_s=0.0, cpu_s=0.1, steal_s=0.0, majflt=3), "nothing_ran"),
    (dict(late_s=2.0, gc_s=0.0, cpu_s=1.5), "lock_held"),
    # With the figure the slower accounts do not decide.
    (dict(late_s=2.0, gc_s=0.0, cpu_s=0.1, runq_s=0.0, psi_cpu_s=1.9), "nothing_ran"),
], ids=lambda v: v if isinstance(v, str) else "-".join(sorted(set(v) - {"late_s", "gc_s", "cpu_s"})) or "bare")
def test_a_late_wake_is_booked_to_its_cause(sample, cause):
    assert book_stall(**sample) == cause


# -- the pulse -----------------------------------------------------------------

@pytest.fixture
def pulse():
    """A pulse on a ring of its own, with what its timer and its stall hook got."""
    ring = FlightRecorder()
    seen = {"late": [], "stalls": 0}

    class Timer:
        def update(self, seconds):
            seen["late"].append(seconds)

    def on_stall():
        seen["stalls"] += 1

    p = Pulse(ring, timer=Timer(), on_stall=on_stall)
    p.start()
    time.sleep(0.25)  # a few wakes on time first
    yield p, ring, seen
    p.stop()


def _on_process(ring, name):
    return [e for e in ring.events() if e[0] == "process" and e[1] == name]


def test_a_lock_kept_inside_a_c_call_leaves_one_late_pulse(pulse):
    p, ring, seen = pulse
    assert ring.events() == []  # no event a pulse
    for attempt in range(3):  # a loaded worker may make the pulse late by itself
        _hold_the_lock(0.3)
        time.sleep(0.15)
        late = _on_process(ring, "pulse.late")
        if len(late) == 1:
            break
        ring._ring.clear()
    (event,) = late
    _, _, ph, t, dur, args = event
    assert (ph, dur) == ("i", 0.0)
    assert 0.15 < args["late_s"] < 0.45
    if HAS_SCHEDSTAT:
        assert args["runq_s"] < args["late_s"] / 3  # it waited for the lock, in no run queue
    else:
        assert "runq_s" not in args
    assert args["gc_s"] == 0.0 and args["majflt"] >= 0
    # The holder slept: nobody ran, nobody was runnable.
    assert args["cause"] == book_stall(**{k: v for k, v in args.items() if k != "cause"})
    assert args["cause"] in ("nothing_ran", "lock_held")
    assert seen["late"][-1] == args["late_s"] and seen["stalls"] == 0
    # Every slower account that is there is a number; one that is not is left out.
    for name in ("steal_s", "throttled_s", "psi_cpu_s", "psi_mem_s", "psi_io_s"):
        assert isinstance(args.get(name, 0.0), float)


def test_a_full_collection_leaves_a_gc_span_and_the_young_ones_nothing(pulse):
    p, ring, _ = pulse
    gc.collect(0)
    gc.collect(1)
    assert _on_process(ring, "gc") == []
    gc.collect()
    (span,) = _on_process(ring, "gc")
    assert span[2] == "X" and span[4] > 0 and set(span[5]) == {"collected"}
    p.stop()
    gc.collect()
    assert len(_on_process(ring, "gc")) == 1  # the hook went with the pulse


def test_a_collection_its_hook_has_not_closed_yet_counts_for_the_gap_and_once():
    """The pulse has waited for the interpreter's lock all through the
    collection and may take it before the collector's hook has run to its end
    (seen on the chip: a wake 0.2 ms before the ``gc`` span's end, booked
    ``lock_held``)."""
    ring, account = FlightRecorder(), ThreadAccount()
    p = Pulse(ring)
    now = time.monotonic()
    p._collecting_since = now - 0.10
    p._late(now - 0.09, now, time.process_time(), account.read(), account, p._sample())
    p._collections.append((now - 0.10, now + 0.0002))  # kept, not yet cleared
    p._late(now - 0.09, now, time.process_time(), account.read(), account, p._sample())
    first, second = (e[5] for e in _on_process(ring, "pulse.late"))
    assert first["gc_s"] == pytest.approx(0.09) == second["gc_s"]
    assert first["cause"] == second["cause"] == "gc"


def test_the_first_late_pulse_of_a_stall_dumps_once_and_some_wakes_later(pulse, monkeypatch):
    p, ring, seen = pulse
    monkeypatch.setattr(Pulse, "STALL_S", 0.2)
    monkeypatch.setattr(Pulse, "DUMP_AFTER", 4)
    _hold_the_lock(0.35)
    time.sleep(0.15)
    # Not yet: the spans that cover the gap are written when they end.
    assert len(_on_process(ring, "pulse.late")) >= 1 and seen["stalls"] == 0
    time.sleep(0.45)
    assert seen["stalls"] == 1
    _hold_the_lock(0.35)
    time.sleep(0.6)
    assert len(_on_process(ring, "pulse.late")) >= 2 and seen["stalls"] == 1


def test_a_stall_met_just_before_the_stop_is_dumped_at_the_stop(pulse, monkeypatch):
    p, ring, seen = pulse
    monkeypatch.setattr(Pulse, "STALL_S", 0.2)
    _hold_the_lock(0.35)
    time.sleep(0.15)
    assert seen["stalls"] == 0
    p.stop()
    assert seen["stalls"] == 1


def test_stop_is_idempotent_and_leaves_no_thread_no_hook_no_descriptor():
    fds, hooks = len(_schedstat_fds()), len(gc.callbacks)
    p = Pulse(FlightRecorder())
    p.start()
    p.start()
    time.sleep(0.05)
    assert len(_pulse_threads()) == 1 and len(gc.callbacks) == hooks + 1
    p.stop(join=False)
    p.stop()
    p.stop()
    assert _pulse_threads() == [] and len(gc.callbacks) == hooks
    assert len(_schedstat_fds()) == fds


# -- in a job --------------------------------------------------------------------

def test_the_job_has_one_pulse_and_it_is_gone_once_the_job_is_joined(lenet):  # noqa: F811
    gc.collect()
    seen, fds, hooks = [], len(_schedstat_fds()), len(gc.callbacks)
    handle, out = _job(lenet, "pulse-job", sink=lambda r: seen.append(
        (len(_pulse_threads()), len(gc.callbacks))))
    assert len(out) == 0 and len(seen) == WINDOW * WINDOWS
    assert set(seen) == {(1, hooks + 1)}  # one while it ran, with its one hook
    assert _pulse_threads() == [] and len(gc.callbacks) == hooks
    assert handle.executor.pulse is not None
    del handle, out
    gc.collect()
    assert len(_schedstat_fds()) == fds  # the threads' accounts went with them


def test_a_job_nobody_joins_does_not_keep_its_pulse(lenet):  # noqa: F811
    from flink_tensorflow_tpu import StreamExecutionEnvironment

    env = StreamExecutionEnvironment(parallelism=1)
    env.from_collection(list(range(10))).sink_to_callable(lambda v: None)
    handle = env.execute_async("pulse-unjoined")
    deadline = time.monotonic() + 30
    while _pulse_threads() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert _pulse_threads() == []
    handle.wait(30)


def test_ring_off_means_no_pulse_no_hook_no_descriptor(lenet):  # noqa: F811
    gc.collect()
    hooks, fds, seen = len(gc.callbacks), len(_schedstat_fds()), []

    def sink(record):
        seen.append((len(_pulse_threads()), len(gc.callbacks), len(_schedstat_fds())))

    handle, _ = _job(lenet, "pulse-off", sink=sink, flight_recorder=False)
    assert set(seen) == {(0, hooks, fds)}
    assert handle.executor.pulse is None and handle.executor.flight is None
    metrics = handle.executor.metrics.report()
    assert not [k for k in metrics if k.endswith((".cpu_s", ".runq_s", "pulse_late_s"))]


def test_gauges_and_timer_are_in_the_registry_under_the_scope_that_has_busy_s(lenet):  # noqa: F811
    started = time.monotonic()
    handle, _ = _job(lenet, "pulse-gauges", source=_paced_source(), source_name="offered",
                     sink=lambda r: _spin(0.0002))
    metrics = handle.wait(60).metrics
    wall = time.monotonic() - started  # of the whole job: the thread's own is shorter
    heads = [k[:-len(".busy_s")] for k in metrics if k.endswith(".busy_s")]
    assert heads == ["offered.0"]
    cpu = metrics["offered.0.cpu_s"]
    assert 0.03 < cpu < wall  # 192 records x 0.2 ms of spinning at the least
    if HAS_SCHEDSTAT:
        assert 0.0 <= metrics["offered.0.runq_s"] < wall
    else:
        assert "offered.0.runq_s" not in metrics
    assert metrics["process.pulse_late_s"]["count"] >= 0
    # Pulled when a report is taken: the thread has ended, the sums stand.
    assert handle.executor.metrics.report()["offered.0.cpu_s"] == cpu


def test_a_stall_in_a_job_dumps_the_ring_with_reason_stall(lenet, tmp_path, monkeypatch):  # noqa: F811
    monkeypatch.setattr(Pulse, "STALL_S", 0.2)
    path, held = str(tmp_path / "flight.json"), []

    def sink(record):
        if len(held) < 2 and record.meta["id"] % WINDOW == 5:
            held.append(record.meta["id"])
            _hold_the_lock(0.4)

    handle, _ = _job(lenet, "pulse-stall", sink=sink, flight_path=path)
    assert handle.executor.flight.dumped == ["stall"]
    doc = load_flight_dump(path)
    assert doc["reason"] == "stall"
    late = [e for e in doc["events"] if e[0] == "process" and e[1] == "pulse.late"]
    assert late and late[0][5]["late_s"] > 0.2 and late[0][5]["cause"] in (
        "nothing_ran", "lock_held", "off_core", "gc")
    # The span that covers the gap says who held the thread: the emission, here.
    t = late[0][3]
    emits = [e for e in doc["events"] if e[0] == "model.0" and e[1] == "emit"]
    assert emits and "cpu_s" in emits[0][5]
    # The operator's track stands beside it in the export.
    from flink_tensorflow_tpu.tracing.flight import flight_dump_to_chrome

    names = {e["args"]["name"] for e in flight_dump_to_chrome(doc)["traceEvents"]
             if e.get("name") == "thread_name"}
    assert {"process", "model.0"} <= names, names
    assert t > 0 and json.dumps(doc["events"][0]) is not None
    assert handle.executor.metrics.report()["process.pulse_late_s"]["count"] >= 1
