"""End-to-end span tracing + latency attribution (flink_tensorflow_tpu.tracing).

Covers: tracer unit semantics (sampling determinism, ring bounds, Chrome
export validity), trace-context propagation through chains / channels /
remote edges, checkpoint span lifecycle ordering, split-lifecycle spans,
the attribution profiler + CLI, the live inspector, the crash-time
reporter flush, sanitizer-finding instants on the timeline, and the
tier-1 guard that the OFF path performs zero tracing allocations.

All tier-1 fast — no TPU, tiny streams.
"""

import json
import pathlib
import sys
import threading
import time
import tracemalloc

import pytest

sys.path.insert(0, ".")

from flink_tensorflow_tpu import StreamExecutionEnvironment
from flink_tensorflow_tpu.tracing import (
    Tracer,
    attribution,
    events_from_chrome,
    format_attribution_table,
)

REPO = pathlib.Path(__file__).resolve().parents[1]


def _traced_env(tmp_path, **cfg):
    env = StreamExecutionEnvironment()
    env.configure(trace=True,
                  trace_path=str(tmp_path / "trace.json"), **cfg)
    return env


def _span_ids(events, name, track_prefix=None):
    """Trace ids of all "name" spans (optionally restricted to a track)."""
    return sorted({
        args["trace"] for track, ev_name, ph, _t0, _dur, args in events
        if ph == "X" and ev_name == name and args and "trace" in args
        and (track_prefix is None or track.startswith(track_prefix))
    })


# ---------------------------------------------------------------------------
# tracer unit semantics
# ---------------------------------------------------------------------------


class TestTracerUnit:
    def test_sampling_is_deterministic_given_seed(self):
        def decisions(seed):
            tr = Tracer(sample_rate=0.25, seed=seed)
            return [tr.admit("src.0", object()) is not None for _ in range(64)]

        a, b = decisions(7), decisions(7)
        assert a == b
        assert sum(a) == 16  # every 4th record, head-based stride
        # A different seed phases the stride differently but stays
        # deterministic.
        c = decisions(8)
        assert sum(c) == 16 and decisions(8) == c

    def test_rate_one_samples_everything_and_ids_are_unique(self):
        tr = Tracer(sample_rate=1.0)
        ctxs = [tr.admit("src.0", object()) for _ in range(32)]
        assert all(c is not None for c in ctxs)
        assert len({c.trace_id for c in ctxs}) == 32

    def test_invalid_sample_rate_rejected(self):
        with pytest.raises(ValueError):
            Tracer(sample_rate=0.0)
        with pytest.raises(ValueError):
            Tracer(sample_rate=1.5)

    def test_ring_buffer_bounds_memory_and_counts_drops(self):
        tr = Tracer(ring_capacity=16)
        for i in range(100):
            tr.span("op.0", "x", float(i), float(i) + 1.0)
        assert len(tr.events()) == 16
        assert tr.dropped() == 84

    def test_chrome_trace_round_trips_as_valid_json(self, tmp_path):
        tr = Tracer()
        tr.span("op.0", "enqueue", 1.0, 1.5, args={"bytes": 128})
        tr.instant("op.0", "barrier.inject", ts=1.2, args={"checkpoint": 1})
        path = tr.export(str(tmp_path / "t.json"))
        trace = json.loads(pathlib.Path(path).read_text())
        evs = trace["traceEvents"]
        # Perfetto essentials: named process + thread, complete + instant
        # events with microsecond timestamps.
        assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
        threads = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"]
        assert [t["args"]["name"] for t in threads] == ["op.0"]
        (x,) = [e for e in evs if e["ph"] == "X"]
        assert x["name"] == "enqueue" and abs(x["dur"] - 0.5e6) < 1.0
        (i,) = [e for e in evs if e["ph"] == "i"]
        assert i["name"] == "barrier.inject" and i["s"] == "t"

    def test_attribution_and_table_from_synthetic_events(self):
        events = [
            ("lenet.0", "queue", "X", 0.0, 0.001, None),
            ("lenet.0", "queue", "X", 0.1, 0.003, None),
            ("lenet.0", "enqueue", "X", 0.2, 0.010, None),
            ("lenet.0", "in_flight", "X", 0.3, 0.020, None),
            ("checkpoint", "checkpoint", "X", 0.0, 1.0, None),  # job track: excluded
        ]
        attr = attribution(events)
        assert set(attr) == {"lenet"}
        assert attr["lenet"]["queue"]["count"] == 2
        assert attr["lenet"]["enqueue"]["p50_ms"] == 10.0
        table = format_attribution_table(attr)
        # Canonical stage order: queue before enqueue before in_flight.
        lines = [ln.split()[1] for ln in table.splitlines()[2:]]
        assert lines == ["queue", "enqueue", "in_flight"]

    def test_events_from_chrome_preserves_attribution(self, tmp_path):
        tr = Tracer()
        tr.span("op.0", "in_flight", 5.0, 5.25)
        tr.span("op.0", "queue", 4.0, 4.5)
        path = tr.export(str(tmp_path / "t.json"))
        loaded = events_from_chrome(json.loads(pathlib.Path(path).read_text()))
        attr = attribution(loaded)
        assert attr["op"]["in_flight"]["count"] == 1
        assert abs(attr["op"]["in_flight"]["p50_ms"] - 250.0) < 1.0
        assert abs(attr["op"]["queue"]["p50_ms"] - 500.0) < 1.0


# ---------------------------------------------------------------------------
# pipeline tracing: propagation, export, checkpoint/split lifecycles
# ---------------------------------------------------------------------------


class TestPipelineTracing:
    def _execute(self, env, n=20):
        out = []
        (env.from_collection(list(range(n)))
            .map(lambda x: x + 1, name="inc")
            .sink_to_callable(out.append))
        handle = env.execute_async("t")
        handle.wait(60)
        return out, handle.executor.tracer

    def test_context_propagates_through_chained_direct_calls(self, tmp_path):
        env = _traced_env(tmp_path)  # chaining on: source->inc->sink fused
        out, tracer = self._execute(env)
        assert len(out) == 20
        events = tracer.events()
        # Every record's trace id seen at the source is seen at every
        # downstream chained member — direct calls preserve the context.
        src_ids = _span_ids(events, "emit", "collection.0")
        assert len(src_ids) == 20
        assert _span_ids(events, "process", "inc.0") == src_ids
        assert _span_ids(events, "process", "sink.0") == src_ids
        # Chained edges have no queues: no queue spans anywhere.
        assert not [e for e in events if e[1] == "queue"]

    def test_context_propagates_through_channel_queues(self, tmp_path):
        env = _traced_env(tmp_path, chaining=False)
        out, tracer = self._execute(env)
        events = tracer.events()
        src_ids = _span_ids(events, "emit", "collection.0")
        assert len(src_ids) == 20
        # One queue span per record per channel hop, same trace ids.
        assert _span_ids(events, "queue") == src_ids
        assert _span_ids(events, "process", "inc.0") == src_ids
        # Queue spans carry a real wait (enqueue precedes delivery).
        qspans = [e for e in events if e[1] == "queue"]
        assert all(dur >= 0.0 for _tr, _n, _p, _t0, dur, _a in qspans)

    def test_trace_file_written_on_job_completion(self, tmp_path):
        env = _traced_env(tmp_path)
        self._execute(env)
        trace = json.loads((tmp_path / "trace.json").read_text())
        tracks = {e["args"]["name"] for e in trace["traceEvents"]
                  if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert {"collection.0", "inc.0", "sink.0"} <= tracks

    def test_sample_rate_traces_a_deterministic_subset(self, tmp_path):
        env = _traced_env(tmp_path, trace_sample_rate=0.25)
        out, tracer = self._execute(env, n=40)
        assert len(out) == 40  # sampling affects spans, never records
        assert len(_span_ids(tracer.events(), "emit", "collection.0")) == 10

    def test_checkpoint_lifecycle_span_ordering(self, tmp_path):
        env = _traced_env(tmp_path, chaining=False)
        env.enable_checkpointing(str(tmp_path / "chk"), every_n_records=5)
        out, tracer = self._execute(env, n=20)
        events = tracer.events()

        def for_cid(name, cid, ph="X"):
            return [e for e in events
                    if e[1] == name and e[2] == ph
                    and (e[5] or {}).get("checkpoint") == cid]

        injects = [e for e in events if e[1] == "barrier.inject"]
        assert len(injects) == 4
        for cid in (1, 2, 3, 4):
            (inject,) = for_cid("barrier.inject", cid, ph="i")
            snaps = {e[0]: e for e in for_cid("snapshot", cid)}
            assert set(snaps) == {"collection.0", "inc.0", "sink.0"}
            aligns = {e[0]: e for e in for_cid("align", cid)}
            assert set(aligns) == {"inc.0", "sink.0"}
            # Lifecycle order: inject at the source -> source snapshot ->
            # downstream alignment completes -> downstream snapshot, and
            # the job-level checkpoint span covers it all.
            assert inject[3] <= snaps["collection.0"][3]
            assert snaps["collection.0"][3] <= snaps["inc.0"][3] <= snaps["sink.0"][3]
            for scope, align in aligns.items():
                end = align[3] + align[4]
                assert end <= snaps[scope][3] + snaps[scope][4] + 1e-6
            (chk,) = for_cid("checkpoint", cid)
            assert chk[0] == "checkpoint"
            assert chk[3] <= inject[3] and chk[3] + chk[4] >= snaps["sink.0"][3]

    def test_split_source_lifecycle_spans(self, tmp_path):
        from flink_tensorflow_tpu.sources import ReplaySplitSource

        env = _traced_env(tmp_path)
        out = []
        (env.from_source(ReplaySplitSource(list(range(24)), num_splits=4),
                         name="replay", parallelism=2)
            .sink_to_callable(out.append))
        handle = env.execute_async("t")
        handle.wait(60)
        assert sorted(out) == list(range(24))
        events = handle.executor.tracer.events()
        reads = [e for e in events if e[1] == "split.read"]
        assert len(reads) == 4  # one span per consumed split
        assert {(e[5] or {}).get("split") for e in reads} == {
            "range[0:6]", "range[6:12]", "range[12:18]", "range[18:24]"}
        assigns = [e for e in events if e[1] == "split.assign"]
        assert len(assigns) == 4
        assert any(e[1] == "split.request" for e in events)
        # Records admitted at the split source carry contexts too.
        assert len(_span_ids(events, "emit", "replay.")) == 24

    def test_off_path_has_no_tracer_and_zero_tracing_allocations(self):
        # Import everything tracing-related BEFORE tracemalloc starts so
        # only RUNTIME allocations are attributed to the package.
        import flink_tensorflow_tpu.tracing.attribution  # noqa: F401
        import flink_tensorflow_tpu.tracing.tracer  # noqa: F401

        # flight_recorder=False: the PR 9 flight recorder also lives in
        # tracing/ and is ON by default (its own off-path zero-alloc
        # guard is in test_cohort_telemetry.py); this test isolates the
        # TRACER's off path.
        env = StreamExecutionEnvironment().configure(
            flight_recorder=False)
        out = []
        (env.from_collection(list(range(200)))
            .map(lambda x: x + 1, name="inc")
            .sink_to_callable(out.append))
        tracemalloc.start()
        try:
            handle = env.execute_async("t")
            handle.wait(60)
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        assert len(out) == 200
        assert handle.executor.tracer is None
        pkg = str(REPO / "flink_tensorflow_tpu" / "tracing")
        stats = snap.filter_traces(
            [tracemalloc.Filter(True, pkg + "/*")]).statistics("filename")
        assert sum(s.size for s in stats) == 0, stats

    def test_trace_exported_on_job_failure(self, tmp_path):
        from flink_tensorflow_tpu.core.runtime import JobFailure

        env = _traced_env(tmp_path)

        def boom(x):
            if x >= 5:
                raise RuntimeError("synthetic failure")
            return x

        (env.from_collection(list(range(20)))
            .map(boom, name="boom")
            .sink_to_callable(lambda v: None))
        with pytest.raises(JobFailure):
            env.execute("t", timeout=60)
        trace = json.loads((tmp_path / "trace.json").read_text())
        names = {e["name"] for e in trace["traceEvents"]}
        assert "failure" in names  # the crash marker instant


# ---------------------------------------------------------------------------
# remote edge: context over frame headers + serde/wire spans
# ---------------------------------------------------------------------------


class TestRemoteTracing:
    def test_trace_ids_cross_the_remote_edge(self, tmp_path):
        import numpy as np

        from flink_tensorflow_tpu.io.remote import RemoteSink, RemoteSource
        from flink_tensorflow_tpu.tensors import TensorValue

        source = RemoteSource(bind="127.0.0.1")
        up_tracer = []

        def upstream():
            env = StreamExecutionEnvironment(parallelism=1)
            env.configure(trace=True)
            records = [TensorValue({"x": np.full(4, i, np.float32)}, {"i": i})
                       for i in range(30)]
            (env.from_collection(records)
                .add_sink(RemoteSink("127.0.0.1", source.port)))
            handle = env.execute_async("up")
            handle.wait(60)
            up_tracer.append(handle.executor.tracer)

        t = threading.Thread(target=upstream)
        t.start()
        env2 = StreamExecutionEnvironment(parallelism=1)
        env2.configure(trace=True)
        out = env2.from_source(source).sink_to_list()
        handle2 = env2.execute_async("down")
        handle2.wait(60)
        t.join()

        assert len(out) == 30
        up_events = up_tracer[0].events()
        down_events = handle2.executor.tracer.events()
        up_ids = _span_ids(up_events, "emit", "collection.0")
        down_ids = _span_ids(down_events, "emit", "source.0")
        # The __trace__ frame-header entry carried every id across: the
        # downstream re-admits under the SAME identities.
        assert down_ids == up_ids and len(up_ids) == 30
        # Sender-side serde/wire stage spans exist on the sink's track —
        # per coalesced FLUSH since the PR-8 record plane, with the
        # record count attributed on the span (plus the wire.flush span
        # pricing the coalescing delay separately).
        up_serde = [e for e in up_events if e[1] == "serde"]
        up_wire = [e for e in up_events if e[1] == "wire"]
        assert up_serde and len(up_wire) == len(up_serde)
        assert sum(e[5]["records"] for e in up_serde) == 30
        assert [e for e in up_events if e[1] == "wire.flush"]
        # Receiver-side decode cost is measured too (per frame).
        down_serde = [e for e in down_events if e[1] == "serde"]
        assert down_serde
        assert sum(e[5]["records"] for e in down_serde) == 30
        # The header never leaks into user-visible metadata.
        assert all("__trace__" not in r.meta for r in out)


# ---------------------------------------------------------------------------
# satellites: crash-time reporter flush, sanitizer timeline, live view
# ---------------------------------------------------------------------------


class TestFailureReporterFlush:
    def test_reporter_publishes_crash_snapshot_before_join(self):
        from flink_tensorflow_tpu.core.runtime import JobFailure
        from flink_tensorflow_tpu.metrics import LatestSnapshotReporter, MetricConfig

        latest = LatestSnapshotReporter()
        env = StreamExecutionEnvironment()
        # Interval far beyond the test: without the crash-time flush the
        # reporter would publish nothing until stop().
        env.configure(metrics=MetricConfig(report_interval_s=600.0,
                                           reporters=(latest,)))

        def boom(x):
            raise RuntimeError("synthetic failure")

        (env.from_collection(list(range(5)))
            .map(boom, name="boom")
            .sink_to_callable(lambda v: None))
        handle = env.execute_async("t")
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and latest.latest() is None:
            time.sleep(0.02)
        # The snapshot that explains the crash landed at failure time —
        # BEFORE anyone joined the job.
        assert latest.latest() is not None
        ts, snapshot = latest.latest()
        assert any(scope.startswith("boom.") for scope in snapshot)
        with pytest.raises(JobFailure):
            handle.wait(60)

    def test_clean_jobs_still_get_exactly_the_final_report(self):
        from flink_tensorflow_tpu.metrics import LatestSnapshotReporter, MetricConfig

        latest = LatestSnapshotReporter()
        env = StreamExecutionEnvironment()
        env.configure(metrics=MetricConfig(report_interval_s=600.0,
                                           reporters=(latest,)))
        out = []
        env.from_collection([1, 2, 3]).sink_to_callable(out.append)
        env.execute("t", timeout=60)
        assert out == [1, 2, 3]
        # No failure -> no crash flush; the stop() flush alone reports.
        assert latest.reports == 1


class TestSanitizerTimeline:
    def test_stall_dump_lands_as_trace_instant(self):
        from flink_tensorflow_tpu.core.sanitizer_rt import ConcurrencySanitizer

        tracer = Tracer()
        san = ConcurrencySanitizer("t", stall_timeout_s=0.3)
        san.tracer = tracer
        cond = san.condition("mbox.cond")
        parked = threading.Event()

        def buggy_wait():
            with cond:
                parked.set()
                cond.wait()  # untimed: nothing will ever wake it

        th = threading.Thread(target=buggy_wait, daemon=True,
                              name="lost-wakeup-victim")
        th.start()
        assert parked.wait(5.0)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not any(
                e[1] == "stall" for e in tracer.events()):
            time.sleep(0.05)
        san.shutdown()
        (stall,) = [e for e in tracer.events() if e[1] == "stall"]
        # The instant sits on the sanitizer track and carries the full
        # post-mortem: thread stacks + lock ownership, visible in
        # Perfetto next to the spans the hang interrupted.
        assert stall[0] == "sanitizer" and stall[2] == "i"
        assert "mbox.cond" in stall[5]["message"]
        assert "state dump" in stall[5]["dump"]
        assert "buggy_wait" in stall[5]["dump"]
        with cond:
            cond.notify_all()  # unpark the victim for clean teardown


class TestLiveInspector:
    def _write_pipeline(self, tmp_path):
        path = tmp_path / "pipe.py"
        path.write_text(
            "def main(argv=None):\n"
            "    from flink_tensorflow_tpu import StreamExecutionEnvironment\n"
            "    env = StreamExecutionEnvironment()\n"
            "    env.configure(source_throttle_s=0.005)\n"
            "    out = []\n"
            "    (env.from_collection(list(range(200)))\n"
            "        .map(lambda x: x + 1, name='inc')\n"
            "        .sink_to_callable(out.append))\n"
            "    env.execute('live', timeout=120)\n"
            "    return 0\n"
        )
        return str(path)

    def test_live_view_renders_operator_frames(self, tmp_path):
        import io

        from flink_tensorflow_tpu.metrics.inspector import live_inspect

        stream = io.StringIO()
        snap = live_inspect(self._write_pipeline(tmp_path), (),
                            interval_s=0.1, stream=stream, max_frames=3,
                            timeout_s=120.0)
        assert snap["frames"] >= 1
        rendered = stream.getvalue()
        assert "inc.0" in rendered and "in/s" in rendered
        assert any(r["operator"] == "inc" for r in snap["subtasks"])

    def test_build_live_rows_reads_window_rates(self):
        rows_in = {
            "inc.0": {"records_in": {"count": 10, "rate": 1.0, "window_rate": 5.0},
                      "records_out": {"count": 10, "rate": 1.0, "window_rate": 4.0},
                      "queue_depth": 3, "queue_high_watermark": 7,
                      "backpressure_s": 0.25, "idle_s": 1.5,
                      "watermark_lag_s": 0.1},
            "checkpoint": {"completed": 2},
        }
        from flink_tensorflow_tpu.metrics.inspector import (
            build_live_rows,
            format_live_table,
        )

        (row,) = build_live_rows(rows_in)
        assert row["operator"] == "inc" and row["in_per_s"] == 5.0
        assert row["queue_depth"] == 3 and row["backpressure_s"] == 0.25
        assert "inc.0" in format_live_table([row])


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


class TestTraceCli:
    def test_cli_runs_pipeline_and_prints_attribution(self, tmp_path, capsys):
        from flink_tensorflow_tpu.tracing.cli import main

        pipe = tmp_path / "pipe.py"
        pipe.write_text(
            "def main(argv=None):\n"
            "    from flink_tensorflow_tpu import StreamExecutionEnvironment\n"
            "    env = StreamExecutionEnvironment()\n"
            "    out = []\n"
            "    (env.from_collection(list(range(30)))\n"
            "        .map(lambda x: x * 2, name='double')\n"
            "        .sink_to_callable(out.append))\n"
            "    env.execute('t', timeout=60)\n"
            "    return 0\n"
        )
        out_path = tmp_path / "trace.json"
        rc = main([str(pipe), "--job-args=", "--out", str(out_path)])
        assert rc == 0
        printed = capsys.readouterr().out
        assert "double" in printed and "stage" in printed
        summary = json.loads(printed.strip().splitlines()[-1])
        assert summary["events"] > 0
        assert summary["attribution"]["double"]["process"]["count"] == 30
        # The exported file attributes identically (--from-file path).
        rc = main(["--from-file", str(out_path), "--table-only"])
        assert rc == 0
