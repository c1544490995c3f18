"""Operator chaining: plan-time fusion rules, chained execution
semantics (barriers, watermarks, checkpoint/restore, failover), and the
event-driven record plane that replaces the timed idle polls.

The acceptance contract (ISSUE 3): a forward pipeline of N chainable
operators runs as ONE subtask thread per chain with zero inter-operator
queue traffic (verified via the per-edge gauges), while every logical
operator keeps its own metric scope and checkpoint identity.
"""

import threading
import time

import pytest

from flink_tensorflow_tpu import StreamExecutionEnvironment
from flink_tensorflow_tpu.analysis.chaining import (
    compute_chains,
    sharding_axes_of,
    sharding_fusion_conflict,
)
from flink_tensorflow_tpu.core import functions as fn
from flink_tensorflow_tpu.core.channels import InputGate


def _chain_names(graph, **kw):
    return compute_chains(graph, **kw).names()


class _GangMap(fn.MapFunction):
    is_gang = True

    def map(self, value):
        return value


class _ShardedMap(fn.MapFunction):
    def __init__(self, axes):
        self.sharding_axes = axes

    def map(self, value):
        return value


class _CountingRichMap(fn.MapFunction):
    """Stateful chained operator for the exactly-once tests: counts every
    record through it, snapshot/restore carries the count."""

    def __init__(self, box=None):
        self.count = 0
        #: shared across clones so the test can read the final count.
        self.box = box if box is not None else [0]

    def clone(self):
        return _CountingRichMap(self.box)

    def map(self, value):
        self.count += 1
        self.box[0] = self.count
        return value

    def snapshot_state(self):
        return {"count": self.count}

    def restore_state(self, state):
        self.count = state["count"]
        self.box[0] = self.count


class TestChainPlan:
    def test_linear_forward_pipeline_fuses_completely(self, env):
        s = env.from_collection(range(8), parallelism=2)
        s.map(lambda x: x, name="a", parallelism=2) \
            .filter(lambda x: True, name="b", parallelism=2) \
            .sink_to_list(name="c", parallelism=2)
        assert _chain_names(env.graph) == [["collection", "a", "b", "c"]]

    def test_keyed_broadcast_rebalance_edges_never_fuse(self, env):
        s = env.from_collection(range(8), parallelism=2)
        keyed = s.key_by(lambda x: x).process(
            _KeyedNoop(), name="keyed", parallelism=2)
        keyed.broadcast().map(lambda x: x, name="bcast", parallelism=2) \
            .rebalance().map(lambda x: x, name="rebal", parallelism=2)
        names = _chain_names(env.graph)
        # Every operator is its own chain: hash, broadcast and rebalance
        # edges all re-route records between subtasks.
        assert names == [["collection"], ["keyed"], ["bcast"], ["rebal"]]

    def test_parallelism_change_and_fanout_break_chains(self, env):
        s = env.from_collection(range(8), parallelism=1)
        m = s.map(lambda x: x, name="wide", parallelism=2)  # 1 -> 2
        m.map(lambda x: x, name="t1", parallelism=2)
        m.map(lambda x: x, name="t2", parallelism=2)  # fan-out from wide
        names = _chain_names(env.graph)
        assert ["collection"] in names and ["wide"] in names
        assert ["t1"] in names and ["t2"] in names

    def test_two_input_operators_head_their_own_chain(self, env):
        a = env.from_collection(range(4), parallelism=1)
        b = env.from_collection(range(4), parallelism=1)
        joined = a.union(b)
        joined.map(lambda x: x, name="after", parallelism=1)
        plan = compute_chains(env.graph)
        union_chain = plan.chain_of(
            next(t for t in env.graph.transformations if t.name == "union"))
        # The union merge has two input edges -> never fused INTO; its
        # forward downstream still chains onto it.
        assert [t.name for t in union_chain] == ["union", "after"]

    def test_escape_hatches_respected(self, env):
        s = env.from_collection(range(8), parallelism=1)
        s.map(lambda x: x, name="a", parallelism=1) \
            .map(lambda x: x, name="b", parallelism=1).start_new_chain() \
            .map(lambda x: x, name="c", parallelism=1).disable_chaining() \
            .map(lambda x: x, name="d", parallelism=1)
        names = _chain_names(env.graph)
        assert names == [["collection", "a"], ["b"], ["c"], ["d"]]
        reasons = compute_chains(env.graph).unchained_reasons
        assert any("starts a new chain" in r for r in reasons.values())
        assert any("chaining disabled" in r for r in reasons.values())

    def test_gang_operators_never_fuse(self, env):
        s = env.from_collection(range(8), parallelism=1)
        s.map(lambda x: x, name="pre", parallelism=1) \
            .map(_GangMap(), name="gang", parallelism=1) \
            .map(lambda x: x, name="post", parallelism=1)
        names = _chain_names(env.graph)
        assert ["gang"] in names
        assert ["post"] in names

    def test_mismatched_sharding_never_fuses_matching_does(self, env):
        s = env.from_collection(range(8), parallelism=1)
        s.map(_ShardedMap(("data",)), name="d1", parallelism=1) \
            .map(_ShardedMap(("model",)), name="m1", parallelism=1) \
            .map(_ShardedMap(("model",)), name="m2", parallelism=1)
        plan = compute_chains(env.graph)
        names = plan.names()
        # data|model mismatch splits; model|model fuses.
        assert ["m1", "m2"] in names
        assert all("m1" not in c for c in names if "d1" in c)
        assert any("mismatched sharding" in r
                   for r in plan.unchained_reasons.values())

    def test_sharding_helpers_shared_vocabulary(self):
        gang = _GangMap()
        assert sharding_axes_of(gang) == ("data",)
        assert sharding_axes_of(_ShardedMap(("model",))) == ("model",)
        assert sharding_axes_of(None) is None

        class Op:
            def __init__(self, f):
                self.function = f

        assert sharding_fusion_conflict(Op(gang), Op(None)) is not None
        assert sharding_fusion_conflict(Op(None), Op(None)) is None

    def test_timer_operator_never_chains_into_source_loop(self, env):
        s = env.from_collection(range(32), parallelism=1)
        # count-or-timeout window declares wall-clock deadlines; a pure
        # count window is arrival-driven and may ride the source thread.
        s.map(lambda x: x, name="pre", parallelism=1) \
            .count_window(4, timeout_s=1.0) \
            .apply(_SumWindow(), name="timed", parallelism=1)
        plan = compute_chains(env.graph)
        assert ["collection", "pre"] in plan.names()
        assert any("timer-driven" in r for r in plan.unchained_reasons.values())

        env2 = StreamExecutionEnvironment(parallelism=1)
        env2.from_collection(range(32), parallelism=1) \
            .count_window(4).apply(_SumWindow(), name="counted", parallelism=1)
        assert ["collection", "counted"] in _chain_names(env2.graph)

    def test_disabled_chaining_mode_degenerates(self, env):
        s = env.from_collection(range(4), parallelism=1)
        s.map(lambda x: x, name="a", parallelism=1)
        plan = compute_chains(env.graph, enabled=False)
        assert plan.names() == [["collection"], ["a"]]
        assert plan.chained_edge_count == 0


class _KeyedNoop(fn.ProcessFunction):
    def process_element(self, value, ctx, out):
        out.collect(value)


class _SumWindow(fn.WindowFunction):
    def process_window(self, key, window, elements, out):
        out.collect(sum(elements))


class _SumFirstWindow(fn.WindowFunction):
    """Sums the integer component of (i, ts) event tuples per window."""

    def process_window(self, key, window, elements, out):
        out.collect(sum(e[0] for e in elements))


class TestChainedExecution:
    def test_one_thread_per_chain_zero_queue_traffic(self):
        env = StreamExecutionEnvironment(parallelism=1)
        out = (
            env.from_collection(list(range(50)), parallelism=1)
            .map(lambda x: x * 2, name="dbl")
            .filter(lambda x: x % 4 == 0, name="quad")
            .sink_to_list()
        )
        ex = env._make_executor()
        assert len(ex.subtasks) == 1          # one THREAD for the chain
        assert ex.total_subtasks == 4         # four LOGICAL operators
        assert ex._gates == []                # no queue anywhere
        ex.run(timeout=60)
        assert sorted(out) == [x * 2 for x in range(50) if (x * 2) % 4 == 0]
        # Per-edge gauges are the no-traffic witness: none exist because
        # no edge has a queue.
        report = ex.metrics.report()
        assert not [k for k in report if "_queue_puts" in k]

    def test_unchained_comparison_has_queue_traffic(self):
        env = StreamExecutionEnvironment(parallelism=1)
        env.configure(chaining=False)
        out = (
            env.from_collection(list(range(50)), parallelism=1)
            .map(lambda x: x * 2, name="dbl")
            .sink_to_list()
        )
        ex = env._make_executor()
        assert len(ex.subtasks) == 3
        ex.run(timeout=60)
        assert len(out) == 50
        report = ex.metrics.report()
        puts = {k: v for k, v in report.items() if k.endswith("_queue_puts")}
        assert puts, "per-edge gauges must exist for real channels"
        # 50 records + 1 end-of-partition down each of the two edges.
        assert report["dbl.0.edge0_collection_queue_puts"] >= 50
        assert report["collect.0.edge0_dbl_queue_puts"] >= 50

    def test_chaining_on_off_parity(self):
        def run(chaining):
            env = StreamExecutionEnvironment(parallelism=1)
            env.configure(chaining=chaining)
            out = (
                env.from_collection(list(range(40)), parallelism=1)
                .map(lambda x: x + 1, name="inc")
                .flat_map(lambda x: [x, -x], name="fan")
                .sink_to_list()
            )
            env.execute(timeout=60)
            return sorted(out)

        assert run(True) == run(False)

    def test_per_logical_operator_metrics_preserved(self):
        env = StreamExecutionEnvironment(parallelism=1)
        (
            env.from_collection(list(range(30)), parallelism=1)
            .map(lambda x: x, name="ident")
            .filter(lambda x: x % 3 == 0, name="third")
            .sink_to_list(name="sink")
        )
        env.execute(timeout=60)
        rep = env.metric_registry.report()
        assert rep["collection.0.records_out"]["count"] == 30
        assert rep["ident.0.records_in"]["count"] == 30
        assert rep["ident.0.records_out"]["count"] == 30
        assert rep["third.0.records_in"]["count"] == 30
        assert rep["third.0.records_out"]["count"] == 10
        assert rep["sink.0.records_in"]["count"] == 10
        # Per-operator latency timers ticked for every fused member.
        for scope in ("ident.0", "third.0", "sink.0"):
            assert rep[f"{scope}.process_latency_s"]["count"] > 0
        # Chain-shape gauges: 4 members, 3 fused edges, on every scope.
        assert rep["ident.0.chain_length"] == 4
        assert rep["sink.0.chained_edges"] == 3

    def test_watermarks_traverse_chain_in_order(self):
        """Event-time windows fused into the source chain still fire on
        watermark passage with every preceding record processed first."""
        env = StreamExecutionEnvironment(parallelism=1)
        events = [(i, float(i)) for i in range(20)]
        out = (
            env.from_collection(events, parallelism=1)
            .assign_timestamps(lambda e: e[1], watermark_every=2)
            .time_window_all(5.0)
            .apply(_SumFirstWindow(), name="win", parallelism=1)
            .sink_to_list()
        )
        ex = env._make_executor()
        assert len(ex.subtasks) == 1  # fully fused incl. the window
        ex.run(timeout=60)
        # Tumbling [0,5) [5,10) [10,15) [15,20): sums of i per window.
        assert sorted(out) == [sum(range(0, 5)), sum(range(5, 10)),
                               sum(range(10, 15)), sum(range(15, 20))]

    def test_barrier_snapshots_every_chained_operator_in_order(self, tmp_path):
        env = StreamExecutionEnvironment(parallelism=1)
        env.enable_checkpointing(str(tmp_path / "c"))
        env.source_throttle_s = 0.005
        box = [0]
        (
            env.from_collection(list(range(200)), parallelism=1)
            .map(_CountingRichMap(box), name="counted")
            .sink_to_list()
        )
        handle = env.execute_async()
        time.sleep(0.25)
        snapshots = handle.trigger_checkpoint(timeout=30)
        # One snapshot per LOGICAL operator, all cut at the same barrier.
        assert set(snapshots) >= {"collection", "counted", "collect"}
        offset = snapshots["collection"][0]["operator"]["offset"]
        counted = snapshots["counted"][0]["function"]["count"]
        assert 0 < offset < 200, "checkpoint should be mid-stream"
        # The chain is synchronous: everything the source emitted before
        # the barrier was fully processed by the chained map — the two
        # counts agree EXACTLY, no in-flight records.
        assert counted == offset
        handle.cancel()
        handle.wait(timeout=30)

    def test_chained_restore_is_exactly_once(self, tmp_path):
        ckpt = str(tmp_path / "c")
        env1 = StreamExecutionEnvironment(parallelism=1)
        env1.enable_checkpointing(ckpt)
        env1.source_throttle_s = 0.005
        (
            env1.from_collection(list(range(200)), parallelism=1)
            .map(_CountingRichMap(), name="counted")
            .sink_to_list()
        )
        handle = env1.execute_async()
        time.sleep(0.25)
        snaps = handle.trigger_checkpoint(timeout=30)
        assert 0 < snaps["collection"][0]["operator"]["offset"] < 200
        handle.cancel()
        handle.wait(timeout=30)

        env2 = StreamExecutionEnvironment(parallelism=1)
        box = [0]
        out = (
            env2.from_collection(list(range(200)), parallelism=1)
            .map(_CountingRichMap(box), name="counted")
            .sink_to_list()
        )
        env2.execute(restore_from=ckpt, timeout=60)
        # Replay resumes at the restored offset; the map's restored count
        # continues seamlessly: every record counted exactly once.
        assert box[0] == 200
        assert len(out) + snaps["collection"][0]["operator"]["offset"] == 200

    def test_failover_restart_of_chained_job(self, tmp_path):
        from flink_tensorflow_tpu.core.environment import RestartStrategy

        crashed = [False]

        class FailingMap(fn.MapFunction):
            def __init__(self, count=0):
                self.count = count

            def clone(self):
                return FailingMap(self.count)

            def map(self, value):
                self.count += 1
                if not crashed[0] and self.count >= 60:
                    crashed[0] = True
                    raise RuntimeError("injected chain failure")
                return value

            def snapshot_state(self):
                return {"count": self.count}

            def restore_state(self, state):
                self.count = state["count"]

        env = StreamExecutionEnvironment(parallelism=1)
        env.enable_checkpointing(str(tmp_path / "c"), interval_s=0.05)
        env.source_throttle_s = 0.002
        out = (
            env.from_collection(list(range(150)), parallelism=1)
            .map(FailingMap(), name="fragile")
            .sink_to_list()
        )
        result = env.execute(
            timeout=120, restart_strategy=RestartStrategy(max_restarts=2))
        assert crashed[0]
        assert result.restarts >= 1
        # At-least-once sink emission, exactly-once state replay: every
        # value present, duplicates only from records between the last
        # checkpoint and the crash.
        assert set(out) == set(range(150))


class TestEventDrivenRecordPlane:
    def test_no_timed_poll_constants_remain(self):
        """The 50 ms quanta of round 5's fixed floor components are
        gone from both layers — waits are condition-variable driven."""
        from flink_tensorflow_tpu.core import channels, runtime

        assert not hasattr(channels, "_POLL_INTERVAL_S")
        assert not hasattr(runtime, "_IDLE_POLL_S")

    def test_blocked_poll_wakes_on_put_immediately(self):
        """A reader parked with NO timeout is woken by the first put —
        the latency of an idle hop is a notify, not a poll quantum."""
        gate = InputGate(1, capacity=8)
        got = []

        def consume():
            got.append(gate.poll(timeout=None))

        t = threading.Thread(target=consume)
        t.start()
        time.sleep(0.2)  # reader is parked, provably idle
        t0 = time.monotonic()
        gate.put(0, "x")
        t.join(timeout=5.0)
        wake_s = time.monotonic() - t0
        assert got == [(0, "x")]
        assert wake_s < 0.045, (
            f"wakeup took {wake_s * 1e3:.1f}ms — an event-driven gate "
            "must beat the old 50ms poll quantum by an order of magnitude")

    def test_blocked_put_wakes_on_drain(self):
        gate = InputGate(1, capacity=1)
        gate.put(0, "a")
        blocked_s = []

        def writer():
            blocked_s.append(gate.put(0, "b"))

        t = threading.Thread(target=writer)
        t.start()
        time.sleep(0.15)
        assert gate.poll(timeout=1.0) == (0, "a")
        t.join(timeout=5.0)
        assert not t.is_alive()
        assert blocked_s and blocked_s[0] >= 0.1  # backpressure attributed
        assert gate.blocked_put_s >= 0.1

    def test_close_releases_blocked_reader_and_writer(self):
        full = InputGate(1, capacity=1)
        full.put(0, "a")
        w = threading.Thread(target=lambda: full.put(0, "b"))
        w.start()
        time.sleep(0.05)
        full.close()
        w.join(timeout=5.0)
        assert not w.is_alive()

        empty = InputGate(1)
        got = []
        r = threading.Thread(target=lambda: got.append(empty.poll(timeout=None)))
        r.start()
        time.sleep(0.05)
        empty.close()
        r.join(timeout=5.0)
        assert not r.is_alive()
        assert got == [None]


@pytest.mark.slow
class TestLatencyFloorGuard:
    """CI latency-floor regression guard (slow tier): the chained
    forward pipeline must show ZERO inter-operator queue puts, and the
    idle path must be event-driven (no timed 50 ms poll)."""

    def test_two_op_forward_pipeline_floor(self):
        env = StreamExecutionEnvironment(parallelism=1)
        out = (
            env.from_collection(list(range(64)), parallelism=1)
            .map(lambda x: x + 1, name="stage1")
            .map(lambda x: x * 2, name="stage2")
            .sink_to_list()
        )
        ex = env._make_executor()
        assert len(ex.subtasks) == 1
        ex.run(timeout=60)
        assert sorted(out) == [(x + 1) * 2 for x in range(64)]
        report = ex.metrics.report()
        # Zero inter-operator queue traffic, asserted via the per-edge
        # gauges: none exist (no gate was even built), and the gate list
        # is empty.
        edge_puts = {k: v for k, v in report.items()
                     if k.endswith("_queue_puts")}
        assert edge_puts == {}
        assert ex._gates == []
        assert report["stage2.0.chained_edges"] == 3

        # No timed poll in the idle path: a worker chain parked on an
        # empty gate reacts to a put within single-digit milliseconds.
        gate = InputGate(1)
        woke = []

        def park():
            woke.append(gate.poll(timeout=None))

        t = threading.Thread(target=park)
        t.start()
        time.sleep(0.3)
        t0 = time.monotonic()
        gate.put(0, "ping")
        t.join(timeout=5.0)
        assert woke and (time.monotonic() - t0) < 0.045
