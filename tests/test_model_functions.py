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
