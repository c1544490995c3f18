"""ModelFunction-in-stream integration tests — the reference's MiniCluster
end-to-end shape (SURVEY.md §4): a bounded stream through a model operator
with a tiny model, asserting exact outputs."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_tensorflow_tpu import StreamExecutionEnvironment
from flink_tensorflow_tpu.functions import (
    GraphWindowFunction,
    ModelMapFunction,
    ModelWindowFunction,
)
from flink_tensorflow_tpu.models import freeze_method, get_model_def, save_bundle
from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue


@pytest.fixture(scope="module")
def lenet_model():
    mdef = get_model_def("lenet")
    params = jax.jit(mdef.init_fn)(jax.random.key(0))
    return mdef.to_model(params)


@pytest.fixture(scope="module")
def images():
    rng = np.random.RandomState(7)
    return [
        TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)}, {"i": i})
        for i in range(10)
    ]


@pytest.fixture(scope="module")
def expected_labels(lenet_model, images):
    serve = jax.jit(lenet_model.method("serve").fn)
    batch = jnp.stack([jnp.asarray(r["image"]) for r in images])
    out = serve(lenet_model.params, {"image": batch})
    return [int(x) for x in np.asarray(out["label"])]


class TestModelWindowFunction:
    def test_windowed_microbatch_inference(self, lenet_model, images, expected_labels):
        env = StreamExecutionEnvironment(parallelism=1)
        results = (
            env.from_collection(images)
            .count_window(4)
            .apply(ModelWindowFunction(lenet_model))
            .sink_to_list()
        )
        env.execute(timeout=120)
        assert len(results) == 10
        got = {r.meta["i"]: int(r["label"]) for r in results}
        assert got == {i: l for i, l in enumerate(expected_labels)}

    def test_parallel_subtasks_share_host_model(self, lenet_model, images, expected_labels):
        env = StreamExecutionEnvironment(parallelism=2)
        results = (
            env.from_collection(images)
            .rebalance()
            .count_window(4, timeout_s=0.2)
            .apply(ModelWindowFunction(lenet_model), parallelism=2)
            .sink_to_list()
        )
        env.execute(timeout=120)
        got = {r.meta["i"]: int(r["label"]) for r in results}
        assert got == {i: l for i, l in enumerate(expected_labels)}

    @pytest.mark.parametrize("pipeline_depth", [None, 2, 3])
    def test_pipelined_dispatch_completeness(self, lenet_model, images, expected_labels,
                                             pipeline_depth):
        """In-flight batches must all flush at end of input — every record
        exactly once and in dispatch order, labels correct; at the default
        depth (None: three windows in flight) as at an explicit one."""
        env = StreamExecutionEnvironment(parallelism=1)
        results = (
            env.from_collection(images)
            .count_window(2)
            .apply(ModelWindowFunction(lenet_model, pipeline_depth=pipeline_depth))
            .sink_to_list()
        )
        env.execute(timeout=120)
        assert [r.meta["i"] for r in results] == list(range(len(images)))
        assert [int(r["label"]) for r in results] == expected_labels

    def test_oversized_window_chunks(self, lenet_model, images, expected_labels):
        env = StreamExecutionEnvironment(parallelism=1)
        results = (
            env.from_collection(images)
            .count_window(10)
            .apply(ModelWindowFunction(lenet_model, policy=BucketPolicy(fixed_batch=4)))
            .sink_to_list()
        )
        env.execute(timeout=120)
        got = {r.meta["i"]: int(r["label"]) for r in results}
        assert got == {i: l for i, l in enumerate(expected_labels)}

    def test_bundle_path_source(self, lenet_model, images, expected_labels, tmp_path):
        mdef = get_model_def("lenet")
        path = str(tmp_path / "bundle")
        save_bundle(mdef, lenet_model.params, path)
        env = StreamExecutionEnvironment(parallelism=1)
        results = (
            env.from_collection(images[:4])
            .count_window(4)
            .apply(ModelWindowFunction(path))
            .sink_to_list()
        )
        env.execute(timeout=120)
        assert [int(r["label"]) for r in results] == expected_labels[:4]


def _delay_fetch(monkeypatch, wait):
    """A slow device: the fetch thread calls ``wait()`` before it fetches a
    batch's results."""
    from flink_tensorflow_tpu.tensors.transfer import DeviceTransfer

    fetch = DeviceTransfer.fetch

    def delayed(outputs):
        wait()
        return fetch(outputs)

    monkeypatch.setattr(DeviceTransfer, "fetch", staticmethod(delayed))


@pytest.fixture
def held_fetch(monkeypatch):
    """The device stands still: no batch's results reach the host until
    the returned event is set."""
    import threading

    gate = threading.Event()

    def wait():
        assert gate.wait(60), "the test never released the fetch"

    _delay_fetch(monkeypatch, wait)
    yield gate
    gate.set()


def _opened(function, name="model"):
    from flink_tensorflow_tpu.core.runtime_context import RuntimeContext
    from flink_tensorflow_tpu.core.state import KeyedStateStore
    from flink_tensorflow_tpu.metrics.registry import MetricRegistry

    reg = MetricRegistry()
    function.open(RuntimeContext(name, 0, 1, KeyedStateStore(), reg.group(f"{name}.0")))
    return reg


class TestWindowsInFlight:
    """The third window in flight (the default ``pipeline_depth`` of 3)."""

    B = 2

    def _fire(self, f, records, out):
        tokens = [f.ingest_element(r, out) for r in records]
        f.process_window(None, None, tokens, out)

    def test_snapshot_with_two_windows_in_flight_emits_both_before_the_barrier(
            self, lenet_model, images, expected_labels, held_fetch):
        import threading

        from flink_tensorflow_tpu.core.functions import Collector

        got = []
        out = Collector(lambda value, ts: got.append(value))
        f = ModelWindowFunction(lenet_model, policy=BucketPolicy(fixed_batch=self.B))
        reg = _opened(f)
        try:
            # Neither fire blocks: a fire waits only once a third window
            # is dispatched.
            self._fire(f, images[0:2], out)
            self._fire(f, images[2:4], out)
            assert got == []
            assert len(f.runner._pending) == 2
            assert reg.report()["model.0.windows_in_flight"] == 2
            # The barrier's flush waits for both, however long they take.
            threading.Timer(0.2, held_fetch.set).start()
            assert f.snapshot_state() is None
            assert [r.meta["i"] for r in got] == [0, 1, 2, 3]
            assert [int(r["label"]) for r in got] == expected_labels[:4]
            assert not f.runner._pending and not f.runner.has_completed()
            assert reg.report()["model.0.windows_in_flight"] == 0
        finally:
            held_fetch.set()
            f.close()
        assert reg.report()["model.0.windows_in_flight"] == 0

    def test_fire_span_counts_the_windows_in_flight_and_its_blocked_time(
            self, lenet_model, images, expected_labels, monkeypatch):
        """On a backlog (the host fills a window faster than its results
        come back) ``in_flight`` climbs 1, 2, 3 and stays at the default
        depth; a fire that found three blocks until the oldest is fetched."""
        import time

        _delay_fetch(monkeypatch, lambda: time.sleep(0.15))
        env = StreamExecutionEnvironment(parallelism=1)
        results = (
            env.from_collection(images)
            .count_window(self.B)
            .apply(ModelWindowFunction(lenet_model, policy=BucketPolicy(fixed_batch=self.B),
                                       warmup_batches=(self.B,)), name="model")
            .sink_to_list()
        )
        handle = env.execute_async("in-flight")
        handle.wait(120)
        assert [r.meta["i"] for r in results] == list(range(len(images)))
        fires = [e[5] for e in handle.executor.flight.events()
                 if e[0] == "model.0" and e[1] == "fire"]
        assert len(fires) == len(images) // self.B
        in_flight = [a["in_flight"] for a in fires]
        assert in_flight[:3] == [1, 2, 3]
        assert max(in_flight) == 3  # reaches the depth and never passes it
        assert all(n == 3 for n in in_flight[2:]), in_flight
        for args in fires:
            # Below the depth a fire returns at once; at it, it waits for
            # the oldest window's fetch.
            if args["in_flight"] < 3:
                assert args["blocked_s"] == 0.0
            else:
                assert 0.0 < args["blocked_s"] < 5.0
        waits = [e for e in handle.executor.flight.events()
                 if e[0] == "model.0" and e[1] == "collect_wait"]
        assert sum(a["blocked_s"] for a in fires) <= sum(e[4] for e in waits) + 1e-9


class TestModelMapFunction:
    def test_per_record_inference(self, lenet_model, images, expected_labels):
        env = StreamExecutionEnvironment(parallelism=1)
        results = (
            env.from_collection(images[:3])
            .map(ModelMapFunction(lenet_model))
            .sink_to_list()
        )
        env.execute(timeout=120)
        assert [int(r["label"]) for r in results] == expected_labels[:3]


class TestGraphFunction:
    def test_frozen_window_inference(self, lenet_model, images, expected_labels):
        frozen = freeze_method(lenet_model, "serve", batch=4)
        env = StreamExecutionEnvironment(parallelism=1)
        results = (
            env.from_collection(images)
            .count_window(4)
            .apply(GraphWindowFunction(
                frozen, batch=4,
                input_schema=lenet_model.method("serve").input_schema,
            ))
            .sink_to_list()
        )
        env.execute(timeout=120)
        got = {r.meta["i"]: int(r["label"]) for r in results}
        assert got == {i: l for i, l in enumerate(expected_labels)}


class TestMetrics:
    def test_inference_metrics_populated(self, lenet_model, images):
        env = StreamExecutionEnvironment(parallelism=1)
        (
            env.from_collection(images)
            .count_window(5)
            .apply(ModelWindowFunction(lenet_model), name="infer")
            .sink_to_list()
        )
        result = env.execute(timeout=120)
        assert result.metrics["infer.0.records"]["count"] == 10
        assert result.metrics["infer.0.batches"] == 2
        assert result.metrics["infer.0.record_latency_s"]["p50"] > 0


def _shape_model(shape, dtype, name="shapes"):
    """A model whose ``serve`` sums each record: what matters is its schema."""
    from flink_tensorflow_tpu.models.base import Model, ModelMethod
    from flink_tensorflow_tpu.tensors.schema import RecordSchema, spec

    schema = RecordSchema({"x": spec(shape, dtype)})

    def serve(params, inputs):
        x = inputs["x"]
        return {"sum": x.astype(jnp.float32).reshape(x.shape[0], -1).sum(axis=1)
                + params["bias"]}

    return Model(name, {"bias": jnp.float32(1.0)},
                 {"serve": ModelMethod("serve", schema, ("sum",), serve)})


class TestEarlyShippingRule:
    """Whether a window crosses the link in chunks is decided at ``open()``
    from the batch, the schema, the wire and the arena: no argument names it."""

    @pytest.mark.parametrize("shape, dtype, fixed, arena, kw, rows", [
        # The flagship: 1024 x 268 KB, an arena of 8192 slots: 8 chunks of 34 MB.
        pytest.param((299, 299, 3), np.uint8, 1024, 8192, {}, 128, id="inception_1024"),
        # 17 MB a chunk at 16 would do, but 8 chunks are the most.
        pytest.param((299, 299, 3), np.uint8, 2048, 16384, {}, 256, id="inception_2048"),
        # 64 MB and more in 4: the fewest chunks that still engage.
        pytest.param((299, 299, 3), np.uint8, 256, 2048, {}, 64, id="inception_256_in_4"),
        pytest.param((299, 299, 3), np.uint8, 128, 1024, {}, None, id="inception_128_too_small"),
        # 6 is the largest count that divides 768 and leaves a chunk the arena's
        # 4096 slots are a multiple of (8 x 96 and 7 are not).
        pytest.param((299, 299, 3), np.uint8, 768, 4096, {}, 128, id="batch_of_768_in_6"),
        pytest.param((299, 299, 3), np.uint8, 1000, 8192, {}, None, id="batch_no_chunk_divides"),
        # Two records of int32[4096] (the language-model cell): 32 KB a window.
        pytest.param((4096,), np.int32, 2, 16, {}, None, id="two_int32_4096"),
        pytest.param((299, 299, 3), np.float32, 1024, 8192, {"wire_dtype": "bf16"}, None,
                     id="narrowed_wire"),
        pytest.param((299, 299, 3), np.float32, 1024, 8192, {"wire_dtype": "int8"}, None,
                     id="int8_wire_scale_is_per_batch"),
        pytest.param((299, 299, 3), np.uint8, None, 8192, {}, None, id="no_fixed_batch"),
    ])
    def test_chunk_rows(self, shape, dtype, fixed, arena, kw, rows):
        from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner

        runner = CompiledMethodRunner(_shape_model(shape, dtype),
                                      policy=BucketPolicy(fixed_batch=fixed), **kw)
        assert runner.chunk_rows is None
        assert runner.chunk_input(arena) == rows
        assert runner.chunk_rows == rows

    def test_chunks_cross_as_flat_runs_and_the_early_ones_are_taken_as_they_are(self):
        """``ship_chunks``: the leading chunks already on the device pass
        through untouched, the rest are put, every record of a chunk a flat
        run, in row order; all bytes counted, the early ones apart."""
        from flink_tensorflow_tpu.tensors.transfer import DeviceTransfer

        rows = np.arange(8 * 3 * 5, dtype=np.int16).reshape(8, 3, 5)
        transfer = DeviceTransfer(jax.devices()[0])
        early = [transfer.put_chunk({"x": rows[i:i + 2]}) for i in (0, 2)]
        device, nbytes, early_bytes = transfer.ship_chunks(
            early, [{"x": rows[4:6]}, {"x": rows[6:8]}])
        assert device[0] is early[0] and device[1] is early[1]
        assert nbytes == rows.nbytes and early_bytes == rows.nbytes // 2
        for i, chunk in enumerate(device):
            assert isinstance(chunk["x"], jax.Array) and chunk["x"].shape == (2, 3 * 5)
            assert np.array_equal(np.asarray(chunk["x"]), rows[2 * i:2 * i + 2].reshape(2, -1))

    def _served(self, function, records, window):
        env = StreamExecutionEnvironment(parallelism=1)
        results = (
            env.from_collection(records).count_window(window)
            .apply(function, name="model").sink_to_list()
        )
        return results, env.execute(timeout=120).metrics

    def _puts(self, monkeypatch):
        """Every ``device_put`` of input rows: ``("whole", rows)`` a batch in
        one put, ``("chunks", K, early)`` a batch in chunks."""
        from flink_tensorflow_tpu.tensors.transfer import DeviceTransfer

        seen = []
        ship, ship_chunks = DeviceTransfer.ship, DeviceTransfer.ship_chunks

        def whole(self, batch):
            seen.append(("whole", batch.padded_size))
            return ship(self, batch)

        def chunked(self, shipped, rest):
            out = ship_chunks(self, shipped, rest)
            seen.append(("chunks", len(out[0]), out[2]))
            return out

        monkeypatch.setattr(DeviceTransfer, "ship", whole)
        monkeypatch.setattr(DeviceTransfer, "ship_chunks", chunked)
        return seen

    def test_large_windows_cross_7_of_8_chunks_early_on_one_executable(
            self, lenet_model, monkeypatch):
        """Full windows of a schema large enough (here: the floor under a chunk
        lowered to lenet's size): ``h2d_early_bytes / h2d_bytes`` is (K - 1) /
        K, every ``fire`` says 7 chunks, every ``early_put`` names its window's
        batch, and ``warmup`` and the live windows share one executable."""
        from flink_tensorflow_tpu.functions import runner as runner_module

        monkeypatch.setattr(runner_module, "EARLY_CHUNK_MIN_BYTES", 1 << 10)
        seen = self._puts(monkeypatch)
        compiled = []

        class Function(ModelWindowFunction):
            def close(self):
                compiled.append(self.runner._jit_fn._cache_size())
                super().close()

        rng = np.random.RandomState(3)
        records = [TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)}, {"i": i})
                   for i in range(4 * 32)]
        env = StreamExecutionEnvironment(parallelism=1)
        results = (
            env.from_collection(records).count_window(32)
            .apply(Function(lenet_model, policy=BucketPolicy(fixed_batch=32),
                            warmup_batches=(32,)), name="model").sink_to_list()
        )
        handle = env.execute_async("early")
        handle.wait(120)
        metrics = handle.executor.metrics.report()
        assert [r.meta["i"] for r in results] == list(range(len(records)))
        assert metrics["model.0.h2d_bytes"] == 4 * 32 * 28 * 28 * 4
        assert metrics["model.0.h2d_early_bytes"] * 8 == metrics["model.0.h2d_bytes"] * 7
        # The warm-up's batch is assembled: the same 8 puts, none early.
        assert seen == [("chunks", 8, 0)] + [("chunks", 8, 7 * 4 * 28 * 28 * 4)] * 4
        assert compiled == [1]
        events = [e for e in handle.executor.flight.events() if e[0] == "model.0"]
        fires = [e[5] for e in events if e[1] == "fire"]
        assert [a["early_chunks"] for a in fires] == [7] * 4
        puts = [e[5] for e in events if e[1] == "early_put"]
        assert [(a["seq"], a["chunk"]) for a in puts] == [
            (seq, chunk) for seq in (a["seq"] for a in fires) for chunk in range(7)]
        assert all(a["bytes"] == 4 * 28 * 28 * 4 for a in puts)
        enqueues = [e[5] for e in events if e[1] == "enqueue"]
        assert [a["early_bytes"] * 8 for a in enqueues] == [a["bytes"] * 7 for a in enqueues]

    @pytest.mark.parametrize("case", ["two_int32_4096", "list_path", "wire_dtype"])
    def test_anything_else_crosses_whole_in_one_put(self, lenet_model, monkeypatch, case):
        """A window of two ``int32[4096]`` records, the list path, a narrowed
        wire: one whole put a window, as before, and the counter reads 0."""
        from flink_tensorflow_tpu.functions import runner as runner_module

        seen = self._puts(monkeypatch)
        if case == "two_int32_4096":
            window = 2
            model = _shape_model((4096,), np.int32)
            records = [TensorValue({"x": np.full(4096, i, np.int32)}, {"i": i})
                       for i in range(3 * window)]
            kw = {}
        else:
            # Lenet's windows with the floor lowered to their size: only the
            # list path, or the wire, keeps them whole.
            monkeypatch.setattr(runner_module, "EARLY_CHUNK_MIN_BYTES", 1 << 10)
            window = 32
            model = lenet_model
            rng = np.random.RandomState(5)
            records = [TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)}, {"i": i})
                       for i in range(3 * window)]
            kw = {"use_ring": False} if case == "list_path" else {"wire_dtype": "bf16"}
        results, metrics = self._served(
            ModelWindowFunction(model, policy=BucketPolicy(fixed_batch=window), **kw),
            records, window)
        assert [r.meta["i"] for r in results] == list(range(len(records)))
        assert seen == [("whole", window)] * 3
        assert metrics["model.0.h2d_early_bytes"] == 0
        assert metrics["model.0.h2d_bytes"] > 0
        if case == "two_int32_4096":
            assert [float(r["sum"]) for r in results] == [4096.0 * i + 1 for i in range(6)]


class TestStageTiling:
    def test_stage_boundaries_telescope(self):
        """The runner's stamps must tile t0..t_done with no overlap and
        no gap — and lane_wait must CONTAIN assemble (the review found a
        double-count where h2d_dispatch re-added assemble_s)."""
        import jax

        from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner
        from flink_tensorflow_tpu.models import get_model_def
        from flink_tensorflow_tpu.tensors import (
            BucketLadder,
            BucketPolicy,
            TensorValue,
        )

        mdef = get_model_def("lenet", num_classes=10)
        model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
        from flink_tensorflow_tpu.tracing.flight import FlightRecorder, SpanHook

        r = CompiledMethodRunner(
            model, policy=BucketPolicy(batch=BucketLadder.up_to(4)),
            dispatch_lanes=2)
        r.open(None)
        try:
            r.warmup([1, 2, 4])
            ring = FlightRecorder()
            r._spans, r._trace_track = SpanHook(ring), "lenet.0"
            rng = np.random.RandomState(0)
            r.run_batch([
                TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)})
                for _ in range(3)
            ])
            # The cuts the stamps carried, read from the batch's spans.
            st = {e[1]: (e[3], e[3] + e[4], e[5]) for e in ring.events()}
            t0, t_lane_start, lane = st["lane_wait"]
            t_lane_start_2, t_dispatched, _ = st["enqueue"]
            t_dispatched_2, t_done, fly = st["in_flight"]
            t_fetch_start = t_dispatched_2 + fly["fetch_reached_s"]
            # Boundaries are monotone and the intervals tile exactly.
            assert t0 <= t_lane_start <= t_dispatched
            assert t_dispatched_2 <= t_fetch_start <= t_done + 1e-6
            assert abs(t_lane_start_2 - t_lane_start) < 1e-9
            assert abs(t_dispatched_2 - t_dispatched) < 1e-9
            tiled = ((t_lane_start - t0) + (t_dispatched - t_lane_start_2)
                     + (t_done - t_dispatched_2))
            assert abs(tiled - (t_done - t0)) < 1e-9
            # assemble happens INSIDE the lane interval, not after it.
            assert 0 < lane["assemble_s"] <= t_lane_start - t0 + 1e-9
        finally:
            r.close()


def _lenet_runner(**kw):
    import jax

    from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.tensors import BucketLadder, BucketPolicy

    mdef = get_model_def("lenet", num_classes=10)
    model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
    r = CompiledMethodRunner(
        model, policy=BucketPolicy(batch=BucketLadder.up_to(8)), **kw)
    r.open(None)
    r.warmup([1, 2, 4, 8])
    return r


def _recs(n):
    from flink_tensorflow_tpu.tensors import TensorValue

    rng = np.random.RandomState(0)
    return [
        TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)},
                    {"id": i})
        for i in range(n)
    ]


class TestBackgroundFetch:
    """VERDICT r4 #2 / weak #1: the d2h fetch must overlap the wait, not
    serialize after it — a background fetch thread completes batches
    with NO collect call from the subtask thread."""

    def test_results_complete_without_any_collect_call(self):
        r = _lenet_runner(dispatch_lanes=2)
        try:
            r.dispatch(_recs(2))
            deadline = time.monotonic() + 10.0
            # has_completed flips by background action alone.
            while not r.has_completed() and time.monotonic() < deadline:
                time.sleep(0.002)
            assert r.has_completed()
            out = r.collect_available()
            assert len(out) == 2
        finally:
            r.close()

    def test_on_results_ready_fires_per_completed_batch(self):
        r = _lenet_runner(dispatch_lanes=1)
        hits = []
        r.on_results_ready = lambda: hits.append(time.monotonic())
        try:
            r.dispatch(_recs(2))
            r.dispatch(_recs(1))
            deadline = time.monotonic() + 10.0
            while len(hits) < 2 and time.monotonic() < deadline:
                time.sleep(0.002)
            assert len(hits) == 2
            assert len(r.collect_available()) == 3
        finally:
            r.close()

    def test_deferred_on_done_runs_on_collecting_thread(self):
        """Ring releases must stay on the SPSC consumer thread: on_done
        runs at COLLECTION (subtask thread), not on the fetch thread."""
        from flink_tensorflow_tpu.tensors.batching import assemble, BucketPolicy

        r = _lenet_runner(dispatch_lanes=1)
        done_threads = []
        try:
            recs = _recs(2)
            batch = assemble(recs, r.method.input_schema,
                             BucketPolicy(fixed_batch=2))
            r.dispatch_batch(
                batch, on_done=lambda: done_threads.append(
                    threading.current_thread()))
            deadline = time.monotonic() + 10.0
            while not r.has_completed() and time.monotonic() < deadline:
                time.sleep(0.002)
            assert not done_threads  # fetched, but release deferred
            out = r.collect_available()
            assert len(out) == 2
            assert done_threads == [threading.main_thread()]
        finally:
            r.close()

    def test_stage_cuts_are_per_batch_and_records_share_nothing(self):
        """VERDICT r4 weak #5, after the stamps went: a batch's cuts live
        in its own spans (one ``args`` dict a span, none shared between
        batches), and each record still owns its metadata."""
        from flink_tensorflow_tpu.tracing.flight import FlightRecorder, SpanHook

        r = _lenet_runner(dispatch_lanes=1)
        ring = FlightRecorder()
        r._spans, r._trace_track = SpanHook(ring), "lenet.0"
        try:
            out = r.run_batch(_recs(3))
            out[0].meta["t0"] = -1.0
            assert "t0" not in out[1].meta and "t0" not in out[2].meta
            r.run_batch(_recs(2))
            flights = [e[5] for e in ring.events() if e[1] == "in_flight"]
            assert [a["batch"] for a in flights] == [3, 2]
            assert flights[0]["seq"] + 1 == flights[1]["seq"]
            args = [e[5] for e in ring.events() if e[5] is not None]
            assert len({id(a) for a in args}) == len(args)
        finally:
            r.close()

    def test_next_deadline_immediate_when_results_wait(self):
        """Completed results make the window function due in the past
        (0.0), so the subtask loop's earlier `now` still fires it."""
        import jax

        from flink_tensorflow_tpu.functions import ModelWindowFunction
        from flink_tensorflow_tpu.models import get_model_def
        from flink_tensorflow_tpu.tensors import BucketLadder, BucketPolicy
        from flink_tensorflow_tpu.core import functions as fn

        mdef = get_model_def("lenet", num_classes=10)
        model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
        svc = ModelWindowFunction(
            model, policy=BucketPolicy(batch=BucketLadder.up_to(8)),
            warmup_batches=(2,), transfer_lanes=2, pipeline_depth=8,
            idle_flush_s=30.0)  # poll interval alone would strand results
        emitted = []
        out = fn.Collector(lambda v, ts=None: emitted.append(v))
        svc.open(None)
        try:
            svc._out = out
            svc.process_window(None, None, _recs(2), out)
            deadline = time.monotonic() + 10.0
            while not svc.runner.has_completed() and time.monotonic() < deadline:
                time.sleep(0.002)
            assert svc.next_deadline() == 0.0
            svc.fire_due(time.monotonic())
            assert len(emitted) == 2
        finally:
            svc.close()

    def test_completion_wake_does_not_flush_partial_microbatch(self):
        """A completion-driven fire (deadline 0.0) must drain results
        but NOT dispatch the async map's partial micro-batch — under
        steady load that would flush a padded partial batch at every
        completion, defeating micro-batching.  Only the idle-flush
        deadline proper dispatches the buffer."""
        import jax

        from flink_tensorflow_tpu.functions import ModelMapFunction
        from flink_tensorflow_tpu.models import get_model_def
        from flink_tensorflow_tpu.core import functions as fn

        mdef = get_model_def("lenet", num_classes=10)
        model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
        # An idle deadline that cannot pass by itself: under loaded
        # workers the poll below may outlast any short one, and the
        # completion wake would then find the flush due as well.
        f = ModelMapFunction(model, micro_batch=8, idle_flush_s=600.0,
                             transfer_lanes=1)
        emitted = []
        out = fn.Collector(lambda v, ts=None: emitted.append(v))
        f.open(None)
        try:
            recs = _recs(11)
            for r in recs[:8]:  # fills the micro-batch -> dispatches
                f.map_async(r, out)
            for r in recs[8:]:  # partial: stays buffered
                f.map_async(r, out)
            assert len(f._buf) == 3
            deadline = time.monotonic() + 10.0
            while not f.runner.has_completed() and time.monotonic() < deadline:
                time.sleep(0.002)
            # Completion wake: results drain, the partial buffer stays.
            f.fire_due(time.monotonic())
            assert len(emitted) == 8
            assert len(f._buf) == 3
            # Idle deadline passed: NOW the partial dispatches.
            f.fire_due(time.monotonic() + f._idle_flush_s + 0.01)
            assert not f._buf
            f.flush(out)
            assert len(emitted) == 11
        finally:
            f.close()

    def test_fetch_thread_stress_fifo_and_completeness(self):
        """Concurrency shakeout for the fetch-thread path: many small
        batches through both lane modes with a mixed, randomly-timed
        collect pattern (available/ready/progress/defer) must deliver
        every record exactly once, in dispatch order, with nothing left
        pending — and close() must not deadlock regardless of where the
        pattern stopped."""
        import random

        rng = random.Random(7)
        for lanes in (1, 3):
            r = _lenet_runner(dispatch_lanes=lanes)
            try:
                total = 120
                recs = _recs(total)
                out = []
                i = 0
                while i < total:
                    n = rng.choice((1, 2, 3))
                    r.dispatch(recs[i:i + n])
                    i += n
                    mode = rng.random()
                    if mode < 0.35:
                        out.extend(r.collect_available())
                    elif mode < 0.6:
                        out.extend(r.collect_ready(rng.choice((1, 2, 4))))
                    elif mode < 0.8:
                        out.extend(r.collect_progress(rng.choice((1, 2, 4))))
                    # else: defer — let batches pile up for later modes
                    if rng.random() < 0.2:
                        time.sleep(0.002)
                out.extend(r.flush())
                assert [v.meta["id"] for v in out] == list(range(total))
                assert not r._pending and not r.has_completed()
            finally:
                r.close()

    def test_gate_wake_breaks_poll_sleep(self):
        """InputGate.wake() returns a blocked poll immediately, losing
        no stream elements."""
        from flink_tensorflow_tpu.core.channels import InputGate
        from flink_tensorflow_tpu.core import elements as el

        gate = InputGate(num_channels=1)
        t0 = time.monotonic()
        threading.Timer(0.05, gate.wake).start()
        got = gate.poll(timeout=5.0)
        waited = time.monotonic() - t0
        assert got is None and waited < 2.0
        # A real element queued after a wake still arrives intact.
        gate.put(0, el.StreamRecord("x"))
        idx, element = gate.poll(timeout=1.0)
        assert idx == 0 and element.value == "x"
