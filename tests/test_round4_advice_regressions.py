"""Pins for the round-3 advisor findings.

1. (medium) Cohort restore was impossible when num_processes exceeded the
   job's max operator parallelism: idle processes own no subtasks and
   never write proc-* shards, yet completeness required process indices
   {0..P-1}.  Shards now record the PARTICIPANT set (processes owning
   >= 1 subtask) and completeness is validated against it.
2. (low) MapOperator flushes the async micro-batch before every
   watermark; with watermark_every=1 that silently degrades to
   batch-of-1 — now documented on ModelMapFunction (behavioral pin
   below: the flush itself must still happen, it is load-bearing for
   event-time safety).
3. (low) The global commit gate could stall teardown: no cancellation
   check before/between peer announcements, and a control writer's
   connect-retry loop ignored close().  Both paths now abort promptly.
"""

import json
import os
import signal
import socket
import threading
import time
import types

import pytest

from flink_tensorflow_tpu.checkpoint.store import (
    read_cohort_checkpoint,
    select_cohort_checkpoint,
    write_checkpoint,
)


def _write_shard(base, proc, cid, *, num_processes, participants, tasks):
    import os

    job = {0: {"max_parallelism": 128, "num_processes": num_processes,
               "process_index": proc, "task_parallelism": {}}}
    if participants is not None:
        job[0]["participants"] = list(participants)
    snaps = {"__job__": job}
    for task, idx in tasks:
        snaps.setdefault(task, {})[idx] = {"x": idx}
    write_checkpoint(os.path.join(base, f"proc-{proc:05d}"), cid, snaps)


class TestOverprovisionedCohortRestore:
    """ADVICE r3 medium: num_processes=3 but max parallelism 2 — only
    processes 0 and 1 own subtasks and write shards; the checkpoint must
    still be restorable."""

    def test_participant_shards_form_complete_set(self, tmp_path):
        base = str(tmp_path)
        for p in range(2):  # process 2 is idle: writes nothing
            _write_shard(base, p, 1, num_processes=3, participants=[0, 1],
                         tasks=[("op", p)])
        cid, shards = select_cohort_checkpoint(base)
        assert cid == 1 and len(shards) == 2
        cid, snaps = read_cohort_checkpoint(base)
        assert sorted(snaps["op"]) == [0, 1]

    def test_lost_participant_shard_still_loud(self, tmp_path):
        """The participant set must not weaken the loss check: with
        participants {0,1} and only proc-0's shard present, restore
        refuses rather than silently dropping proc-1's state."""
        base = str(tmp_path)
        _write_shard(base, 0, 1, num_processes=3, participants=[0, 1],
                     tasks=[("op", 0)])
        with pytest.raises(ValueError, match="INCOMPLETE"):
            select_cohort_checkpoint(base, 1)
        with pytest.raises(FileNotFoundError):
            select_cohort_checkpoint(base)

    def test_r3_shards_without_participant_set_still_work(self, tmp_path):
        """Shards written before the participant set existed imply
        participants = {0..P-1} (the r3 rule), both ways."""
        base = str(tmp_path)
        for p in range(2):
            _write_shard(base, p, 1, num_processes=2, participants=None,
                         tasks=[("op", p)])
        cid, shards = select_cohort_checkpoint(base)
        assert cid == 1 and len(shards) == 2
        _write_shard(base, 0, 2, num_processes=2, participants=None,
                     tasks=[("op", 0)])
        with pytest.raises(ValueError, match="INCOMPLETE"):
            select_cohort_checkpoint(base, 2)

    def test_executor_records_participants(self, tmp_path):
        """The distributed executor's shard metadata carries the
        participant set it computes for the commit gate — the two must
        never diverge (restore validates what commit awaited)."""
        from flink_tensorflow_tpu import (
            DistributedConfig,
            StreamExecutionEnvironment,
        )

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        env = StreamExecutionEnvironment(parallelism=1)
        env.set_distributed(
            DistributedConfig(0, 1, (f"127.0.0.1:{port}",)))
        env.enable_checkpointing(str(tmp_path / "chk"), every_n_records=4)
        env.from_collection(list(range(8)), parallelism=1).sink_to_list()
        env.execute("participants-meta", timeout=60)
        cid, shards = select_cohort_checkpoint(str(tmp_path / "chk"))
        meta_path = f"{shards[0]}/chk-{cid:06d}/METADATA.json"
        with open(meta_path) as f:
            job = json.load(f)["job"]
        assert job["participants"] == [0]
        assert job["num_processes"] == 1


class TestOverprovisionedCohortEndToEnd:
    """The full ADVICE r3 medium scenario with real processes: a
    2-process cohort whose job has max parallelism 1, so process 1 is
    idle and writes no shard.  Kill the working process mid-stream, then
    restore the SAME over-provisioned cohort — pre-fix, restore raised
    'no complete cohort shard set' forever."""

    def test_kill_and_restore_with_idle_process(self, tmp_path):
        from flink_tensorflow_tpu.parallel import latest_common_checkpoint
        from test_distributed_plane import (
            _free_ports,
            _read_sorted,
            _spawn,
            _wait,
            expected_emissions,
        )

        out, chk = str(tmp_path / "out"), str(tmp_path / "chk")
        ports = _free_ports(2)

        def spawn(index, restore_id=-1):
            return _spawn(index, ports, out, chk=chk, n=240, every=40,
                          par=1, throttle=0.005, restore_id=restore_id)

        procs = [spawn(i) for i in range(2)]
        # Only proc-00000 writes shards (participants == {0}).
        shard0 = [os.path.join(chk, "proc-00000")]
        deadline = time.monotonic() + 60.0
        common = None
        while time.monotonic() < deadline:
            common = latest_common_checkpoint(shard0)
            if common is not None or procs[0].poll() is not None:
                break
            time.sleep(0.02)
        assert common is not None, "no checkpoint before worker 0 exited"
        procs[0].send_signal(signal.SIGKILL)
        for p in procs:
            _wait(p)

        common = latest_common_checkpoint(shard0)
        procs = [spawn(i, restore_id=common) for i in range(2)]
        for p in procs:
            rc, log = _wait(p)
            assert rc == 0, f"restored worker failed:\n{log}"
        assert _read_sorted(out) == expected_emissions(240)


class TestWatermarkFlushStillLoadBearing:
    def test_async_map_flushes_before_watermark(self):
        """The documented degradation (ADVICE r3 low #3) must not be
        'fixed' by dropping the flush: in-flight async results may never
        arrive behind the watermark that covers them."""
        from flink_tensorflow_tpu.core import elements as el
        from flink_tensorflow_tpu.core import functions as fn
        from flink_tensorflow_tpu.core.operators import MapOperator, Output
        from flink_tensorflow_tpu.core.state import KeyedStateStore

        class Buffering(fn.AsyncMapFunction):
            def __init__(self):
                self.buf = []

            def map_async(self, value, collector):
                self.buf.append(value)

            def flush(self, collector):
                for v in self.buf:
                    collector.collect(v * 10)
                self.buf.clear()

        op = MapOperator("m", Buffering())
        emitted, wms = [], []
        op.setup(None, Output([(None, [])]), KeyedStateStore())
        op.output.emit = lambda v, ts=None: emitted.append(v)
        op.output.broadcast_element = lambda e: wms.append(e.timestamp)
        op.open()
        op.process_record(el.StreamRecord(1, 0.5))
        op.process_record(el.StreamRecord(2, 0.6))
        assert emitted == []  # buffered, pipelined
        op.process_watermark(el.Watermark(1.0))
        # Results surfaced BEFORE the watermark was forwarded.
        assert emitted == [10, 20]
        assert wms == [1.0]


class TestCommitGateTeardown:
    def test_writer_connect_aborts_on_close(self):
        """A writer spinning in its connect-retry loop (peer dead) must
        abort within ~1 poll interval of close(), not wait out the full
        connect timeout."""
        from flink_tensorflow_tpu.core import elements as el
        from flink_tensorflow_tpu.core.shuffle import RemoteChannelWriter

        # A port with no listener: connect refuses instantly, so the
        # writer sits in its retry/sleep loop.
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        w = RemoteChannelWriter("127.0.0.1", dead_port, "op", 0, 0,
                                connect_timeout_s=30.0)
        done = threading.Event()

        def attempt():
            try:
                w.write(el.StreamRecord(1))
            except (OSError, TimeoutError):
                pass
            done.set()

        t = threading.Thread(target=attempt, daemon=True)
        t.start()
        time.sleep(0.3)  # let it enter the retry loop
        start = time.monotonic()
        w.close()
        assert done.wait(5.0), "close() did not abort the connect loop"
        assert time.monotonic() - start < 5.0

    def test_gate_checks_cancellation_before_announcing(self):
        """A cancelled executor's gate returns False without touching the
        network (pre-fix it could first block a full connect timeout in
        a lazily-created control writer)."""
        from flink_tensorflow_tpu.core.distributed import (
            DistributedConfig,
            DistributedExecutor,
        )

        stub = types.SimpleNamespace(
            dist=DistributedConfig(
                0, 2, ("127.0.0.1:1", "127.0.0.1:2")).validate(),
            _participants=frozenset({0, 1}),
            _control_writers={},
            _durable_acks={},
            _durable_cv=threading.Condition(),
            cancelled=threading.Event(),
            checkpoint_timeout_s=60.0,
        )
        stub.cancelled.set()
        start = time.monotonic()
        ok = DistributedExecutor._global_commit_gate(stub, 1)
        assert ok is False
        assert time.monotonic() - start < 1.0
        # No control writer was created for the unreachable peer.
        assert stub._control_writers == {}
