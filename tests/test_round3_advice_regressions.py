"""Pins for the round-2 advisor findings.

1. (high) IntervalJoinOperator evicted matches prematurely when the
   interval excludes zero — retention/acceptance now use the
   min(lower,0)/max(upper,0) slack bounds.
2. (low) WindowJoinOperator mixed ns-integer window ends with float
   ``start + size`` arithmetic; boundary disagreement could drop-as-late
   while open or double-fire.  The ns-derived end is now stored in the
   buffer and used for fire/late/stamp alike.
3. (low) spans_processes cached by id(mesh) — stale after GC + id reuse.
   Now a WeakKeyDictionary keyed on the mesh object.
4. (low) Source-initiated checkpoint persists were submitted after
   releasing the coordinator lock, so notify(k+1) could overtake
   persist(k).  Submission now happens in the completion critical
   section; the single-worker pool preserves checkpoint-id order.
"""

import threading
import time

import numpy as np

from flink_tensorflow_tpu.core import elements as el
from flink_tensorflow_tpu.core.joins import (
    IntervalJoinOperator,
    WindowJoinOperator,
    as_join_function,
)
from flink_tensorflow_tpu.core.operators import Output
from flink_tensorflow_tpu.core.state import KeyedStateStore


def _drive(op):
    """Wire an operator for standalone driving; returns (pairs, stamps, wms)."""
    pairs, stamps, wms = [], [], []
    op.setup(None, Output([(None, [])]), KeyedStateStore())
    op.output.emit = lambda v, ts=None: (pairs.append(v), stamps.append(ts))
    op.output.broadcast_element = lambda e: wms.append(e.timestamp)
    return pairs, stamps, wms


class TestIntervalJoinExcludesZero:
    def test_positive_interval_on_time_match_survives(self):
        """ADVICE repro: lower=1, upper=2, L@9, wm 10.5, R@10.8 —
        10.8-9=1.8 is in [1,2]; the pre-fix retention (lts+upper >=
        wm+lower → 11 >= 11.5) evicted L before R arrived."""
        op = IntervalJoinOperator(
            "ij", as_join_function(lambda l, r: (l, r)), 1.0, 2.0,
            lambda v: "k", lambda v: "k",
        )
        pairs, stamps, _ = _drive(op)
        op.process_record_from(0, el.StreamRecord("L9", 9.0))
        op.process_watermark(el.Watermark(10.5))
        op.process_record_from(1, el.StreamRecord("R10.8", 10.8))
        assert pairs == [("L9", "R10.8")]
        assert stamps == [10.8]

    def test_negative_interval_on_time_match_survives(self):
        """Mirror case: upper<0 — a buffered right must outlive the
        pre-fix rts-lower >= wm-upper bound to meet a future left."""
        op = IntervalJoinOperator(
            "ij", as_join_function(lambda l, r: (l, r)), -2.0, -1.0,
            lambda v: "k", lambda v: "k",
        )
        pairs, _, _ = _drive(op)
        op.process_record_from(1, el.StreamRecord("R9", 9.0))
        op.process_watermark(el.Watermark(10.5))
        # lts=10.8: rts in [8.8, 9.8] ∋ 9.0 — valid, on-time (10.8 > wm).
        op.process_record_from(0, el.StreamRecord("L10.8", 10.8))
        assert pairs == [("L10.8", "R9")]

    def test_genuinely_dead_left_still_dropped(self):
        """The slack bound must not disable eviction: with [1,2] and
        wm=20, no admissible right (rts >= wm+lower-upper = 19) can pair
        L@9 (needs rts <= 11), so the arrival is dead."""
        op = IntervalJoinOperator(
            "ij", as_join_function(lambda l, r: (l, r)), 1.0, 2.0,
            lambda v: "k", lambda v: "k",
        )
        pairs, _, _ = _drive(op)
        op.process_watermark(el.Watermark(20.0))
        op.process_record_from(0, el.StreamRecord("L9", 9.0))
        assert op._state == {}  # not buffered
        op.process_record_from(1, el.StreamRecord("R10.8", 10.8))
        assert pairs == []

    def test_holdback_covers_positive_interval_emissions(self):
        """Emissions after a watermark are stamped >= the broadcast
        watermark (downstream must not see them as late)."""
        op = IntervalJoinOperator(
            "ij", as_join_function(lambda l, r: (l, r)), 1.0, 2.0,
            lambda v: "k", lambda v: "k",
        )
        pairs, stamps, wms = _drive(op)
        op.process_record_from(0, el.StreamRecord("L9", 9.0))
        op.process_watermark(el.Watermark(10.5))
        op.process_record_from(1, el.StreamRecord("R10.8", 10.8))
        assert wms == [10.5 - (2.0 - 1.0)]
        assert stamps and min(stamps) >= wms[-1]


class TestWindowJoinBoundary:
    def test_no_double_fire_when_float_end_undershoots(self):
        """size=0.3, window [0.6, 0.9): float start+size is
        0.8999999999999999 < the ns end 0.9.  Pre-fix, a watermark at
        the float value fired the window early; a subsequent in-window
        record re-created it (late check used the ns end) and it fired
        again.  Now nothing fires until wm >= 0.9 and the single fire
        sees all elements."""
        assert 0.6 + 0.3 < 0.9  # the float hazard this test rides on
        op = WindowJoinOperator(
            "wj", as_join_function(lambda l, r: (l, r)), 0.3,
            lambda v: "k", lambda v: "k",
        )
        pairs, stamps, _ = _drive(op)
        op.process_record_from(0, el.StreamRecord("L0.7", 0.7))
        op.process_record_from(1, el.StreamRecord("R0.8", 0.8))
        op.process_watermark(el.Watermark(0.6 + 0.3))  # 0.8999999999999999
        assert pairs == []  # ns end 0.9 not reached yet
        op.process_record_from(0, el.StreamRecord("L0.65", 0.65))
        op.process_watermark(el.Watermark(0.9))
        assert sorted(pairs) == [("L0.65", "R0.8"), ("L0.7", "R0.8")]
        assert stamps == [0.9, 0.9]

    def test_fires_at_ns_end_when_float_end_overshoots(self):
        """size=0.1, window [0.2, 0.3): float start+size is
        0.30000000000000004 > the ns end 0.3.  Pre-fix, wm=0.3 dropped
        new arrivals as late (ns end <= wm) but never fired the open
        buffer (float end > wm).  Now the window fires exactly at 0.3."""
        assert 0.2 + 0.1 > 0.3  # the float hazard this test rides on
        op = WindowJoinOperator(
            "wj", as_join_function(lambda l, r: (l, r)), 0.1,
            lambda v: "k", lambda v: "k",
        )
        pairs, stamps, _ = _drive(op)
        op.process_record_from(0, el.StreamRecord("L0.25", 0.25))
        op.process_record_from(1, el.StreamRecord("R0.28", 0.28))
        op.process_watermark(el.Watermark(0.3))
        assert pairs == [("L0.25", "R0.28")]
        assert stamps == [0.3]
        assert op._buffers == {}

    def test_restores_pre_r3_two_tuple_snapshot(self):
        """Checkpoints written before the stored-end change carried
        (left, right) buffer values; restore must backfill the end with
        the same ns derivation instead of crashing."""
        op = WindowJoinOperator(
            "wj", as_join_function(lambda l, r: (l, r)), 0.3,
            lambda v: "k", lambda v: "k",
        )
        pairs, stamps, _ = _drive(op)
        old_snap = {"watermark": -float("inf"),
                    "buffers": {("k", 0.6): (["L0.7"], [])}}
        op._operator_restore(old_snap)
        op.process_record_from(1, el.StreamRecord("R0.8", 0.8))
        op.process_watermark(el.Watermark(0.9))
        assert pairs == [("L0.7", "R0.8")]
        assert stamps == [0.9]

    def test_snapshot_roundtrip_preserves_stored_end(self):
        op = WindowJoinOperator(
            "wj", as_join_function(lambda l, r: (l, r)), 0.3,
            lambda v: "k", lambda v: "k",
        )
        _drive(op)
        op.process_record_from(0, el.StreamRecord("L0.7", 0.7))
        snap = op._operator_snapshot()

        op2 = WindowJoinOperator(
            "wj", as_join_function(lambda l, r: (l, r)), 0.3,
            lambda v: "k", lambda v: "k",
        )
        pairs, stamps, _ = _drive(op2)
        op2._operator_restore(snap)
        op2.process_record_from(1, el.StreamRecord("R0.8", 0.8))
        op2.process_watermark(el.Watermark(0.9))
        assert pairs == [("L0.7", "R0.8")]
        assert stamps == [0.9]


class _Dev:
    def __init__(self, process_index):
        self.process_index = process_index


class _StubMesh:
    def __init__(self, process_indices):
        self.devices = np.array([_Dev(p) for p in process_indices], dtype=object)


class TestSpansProcessesCache:
    def test_fresh_mesh_not_served_stale_answer(self):
        from flink_tensorflow_tpu.parallel.mesh import spans_processes

        m = _StubMesh([0, 0, 1, 1])
        assert spans_processes(m) is True
        reused = id(m)
        del m
        # Try to land a new mesh on the recycled id — CPython usually
        # reuses the slot immediately; if it doesn't, the assertion is
        # vacuous but the test still passes for the right reason.
        hold = []
        for _ in range(64):
            m2 = _StubMesh([0])
            if id(m2) == reused:
                break
            hold.append(m2)
        assert spans_processes(m2) is False

    def test_cache_entries_die_with_the_mesh(self):
        from flink_tensorflow_tpu.parallel import mesh as mesh_mod

        before = len(mesh_mod._SPANS_CACHE)
        m = _StubMesh([0, 1])
        assert mesh_mod.spans_processes(m) is True
        assert len(mesh_mod._SPANS_CACHE) == before + 1
        del m
        assert len(mesh_mod._SPANS_CACHE) == before


class _StubExecutor:
    max_parallelism = 8
    subtasks = ()

    def __init__(self, total_subtasks=1):
        self.total_subtasks = total_subtasks
        self.events = []
        self._ev_lock = threading.Lock()

    def log(self, kind, cid):
        with self._ev_lock:
            self.events.append((kind, cid))

    def notify_checkpoint_complete(self, cid):
        self.log("notify", cid)


class TestPersistOrdering:
    def test_notify_never_overtakes_earlier_persist(self, tmp_path, monkeypatch):
        """Complete checkpoint 1 (slow write) then 2 (fast) from two
        threads: notify(2) must come after write_end(1) — the 2PC sink
        may only promote on a durable predecessor."""
        from flink_tensorflow_tpu.core.checkpoint import CheckpointCoordinator

        ex = _StubExecutor(total_subtasks=1)
        coord = CheckpointCoordinator(ex, checkpoint_dir=str(tmp_path))

        def fake_write(directory, cid, snapshots):
            ex.log("write_start", cid)
            if cid == 1:
                time.sleep(0.15)
            ex.log("write_end", cid)

        monkeypatch.setattr(
            "flink_tensorflow_tpu.checkpoint.store.write_checkpoint", fake_write
        )

        assert coord.begin_source_checkpoint(1)
        assert coord.begin_source_checkpoint(2)

        def ack(cid, delay):
            time.sleep(delay)
            coord.ack(cid, "src", 0, {"s": cid})

        t1 = threading.Thread(target=ack, args=(1, 0.0))
        t2 = threading.Thread(target=ack, args=(2, 0.03))
        t1.start(); t2.start(); t1.join(); t2.join()
        assert coord.wait_for_persistence(10.0) == 0

        ev = ex.events
        notifies = [cid for kind, cid in ev if kind == "notify"]
        assert notifies == [1, 2]
        assert ev.index(("notify", 2)) > ev.index(("write_end", 1))

    def test_final_notification_delivered_before_job_reports_done(self, tmp_path):
        """A count-based checkpoint completing as the stream ends must
        still deliver notify_checkpoint_complete to operators: join()
        flushes notifications queued after subtask loops exited (the
        persist queue runs them off the subtask threads)."""
        from flink_tensorflow_tpu import StreamExecutionEnvironment
        from flink_tensorflow_tpu.core import functions as fn

        notified = []

        class NotifySink(fn.SinkFunction):
            def invoke(self, value):
                pass

            def notify_checkpoint_complete(self, checkpoint_id):
                notified.append(checkpoint_id)

        env = StreamExecutionEnvironment(parallelism=1)
        env.enable_checkpointing(str(tmp_path), every_n_records=5)
        env.from_collection(list(range(10)), parallelism=1).add_sink(
            NotifySink(), parallelism=1
        )
        env.execute("final-notify", timeout=60)
        assert 2 in notified  # the checkpoint cut at record 10 (2*5)

    def test_inmemory_notify_is_ordered_and_drained(self, tmp_path):
        """Without a checkpoint_dir, notifications route through the same
        ordered queue and wait_for_persistence drains them."""
        from flink_tensorflow_tpu.core.checkpoint import CheckpointCoordinator

        ex = _StubExecutor(total_subtasks=1)
        coord = CheckpointCoordinator(ex, checkpoint_dir=None)
        assert coord.begin_source_checkpoint(1)
        assert coord.begin_source_checkpoint(2)
        coord.ack(1, "src", 0, {"s": 1})
        coord.ack(2, "src", 0, {"s": 2})
        assert coord.wait_for_persistence(10.0) == 0
        assert [cid for kind, cid in ex.events if kind == "notify"] == [1, 2]
