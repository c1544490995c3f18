"""ModelFunction-in-stream integration tests — the reference's MiniCluster
end-to-end shape (SURVEY.md §4): a bounded stream through a model operator
with a tiny model, asserting exact outputs."""

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
