"""Zero-copy ring buffering in ModelWindowFunction (VERDICT r1 #3):
records write once into the TensorRing arena at arrival, window fires
claim [B, ...] views that feed device_put directly, and the fallback
list path stays bit-identical."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_tensorflow_tpu import StreamExecutionEnvironment
from flink_tensorflow_tpu.functions import ModelWindowFunction
from flink_tensorflow_tpu.functions.model_function import _RingToken
from flink_tensorflow_tpu.models import get_model_def
from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue

N = 20
B = 4


@pytest.fixture(scope="module")
def lenet_model():
    mdef = get_model_def("lenet")
    params = jax.jit(mdef.init_fn)(jax.random.key(0))
    return mdef.to_model(params)


@pytest.fixture(scope="module")
def images():
    rng = np.random.RandomState(11)
    return [
        TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)}, {"i": i})
        for i in range(N)
    ]


@pytest.fixture(scope="module")
def expected_labels(lenet_model, images):
    serve = jax.jit(lenet_model.method("serve").fn)
    batch = jnp.stack([jnp.asarray(r["image"]) for r in images])
    out = serve(lenet_model.params, {"image": batch})
    return {i: int(x) for i, x in enumerate(np.asarray(out["label"]))}


def _ctx():
    from flink_tensorflow_tpu.core.runtime_context import RuntimeContext
    from flink_tensorflow_tpu.core.state import KeyedStateStore
    from flink_tensorflow_tpu.metrics.registry import MetricRegistry

    return RuntimeContext("t", 0, 1, KeyedStateStore(), MetricRegistry().group("t.0"))


def _run(fn_kwargs, images, window=B, timeout_s=None, parallelism=1):
    env = StreamExecutionEnvironment(parallelism=parallelism)
    stream = env.from_collection(images)
    win = (stream.count_window(window, timeout_s=timeout_s)
           if timeout_s else stream.count_window(window))
    results = win.apply(
        ModelWindowFunction(**fn_kwargs)
    ).sink_to_list()
    env.execute(timeout=120)
    return results


class TestRingWindowPath:
    def test_ring_enabled_with_fixed_batch(self, lenet_model, images, expected_labels):
        results = _run(
            dict(model=lenet_model, policy=BucketPolicy(fixed_batch=B)),
            images,
        )
        assert len(results) == N
        got = {r.meta["i"]: int(r["label"]) for r in results}
        assert got == expected_labels

    def test_ring_matches_list_path(self, lenet_model, images):
        """Same stream through ring and list paths -> identical outputs."""
        ring = _run(dict(model=lenet_model, policy=BucketPolicy(fixed_batch=B),
                         use_ring=True), images)
        flat = _run(dict(model=lenet_model, policy=BucketPolicy(fixed_batch=B),
                         use_ring=False), images)
        by_i = lambda rs: {r.meta["i"]: np.asarray(r["logits"]) for r in rs}
        ring_out, flat_out = by_i(ring), by_i(flat)
        assert ring_out.keys() == flat_out.keys()
        for i in ring_out:
            np.testing.assert_allclose(ring_out[i], flat_out[i], atol=1e-6)

    def test_ring_actually_engaged(self, lenet_model, images):
        """White-box: ingest_element returns tokens once opened with a
        fixed-batch policy (guards against the ring silently not wiring)."""
        f = ModelWindowFunction(lenet_model, policy=BucketPolicy(fixed_batch=B))
        f.open(_ctx())
        try:
            assert f._ring is not None
            token = f.ingest_element(images[0], None)
            assert isinstance(token, _RingToken)
            assert token.meta == images[0].meta
            assert f._ring.poppable() == 1
        finally:
            f.close()

    @pytest.mark.parametrize("lanes, depth", [(1, 3), (2, 4), (3, 6)])
    def test_default_depth_and_the_ring_sized_with_it(self, lenet_model, lanes, depth):
        """``pipeline_depth`` defaults to max(3, 2 * transfer_lanes), and
        the auto-sized arena follows it: (depth + 2) batches of slots, which
        the ring rounds up to a power of two."""
        f = ModelWindowFunction(lenet_model, policy=BucketPolicy(fixed_batch=B),
                                transfer_lanes=lanes)
        assert f._max_in_flight == depth - 1
        f.open(_ctx())
        try:
            assert f._ring is not None
            assert f._ring_capacity == (depth + 2) * B
            assert f._ring.capacity == 32  # 20, 24 and 32 asked
        finally:
            f.close()
        # An explicit depth still wins (2 is the behaviour before PR 29).
        two = ModelWindowFunction(lenet_model, policy=BucketPolicy(fixed_batch=B),
                                  transfer_lanes=lanes, pipeline_depth=2)
        assert two._max_in_flight == 1

    def test_open_pages_the_whole_arena_in(self, lenet_model):
        """The arena is allocated lazily; ``open`` touches every page of it, so
        no window's first fill pays for mapping its slots (the arena holds
        more windows than a job's warm-up passes through it)."""
        import os

        if not os.path.exists("/proc/self/statm"):
            pytest.skip("needs /proc/self/statm to read the resident set")

        def resident_bytes():
            with open("/proc/self/statm") as f:
                return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")

        f = ModelWindowFunction(lenet_model, policy=BucketPolicy(fixed_batch=B),
                                ring_capacity=1 << 15)  # 32768 slots of 3136 B: 103 MB
        before = resident_bytes()
        f.open(_ctx())
        try:
            arena_bytes = f._ring._ring.arena_view().nbytes
            assert arena_bytes > 100e6
            assert resident_bytes() - before > 0.9 * arena_bytes
            assert f._ring.poppable() == 0
        finally:
            f.close()

    def test_auto_sized_ring_never_splits_a_batch(self, lenet_model, images,
                                                  expected_labels, monkeypatch):
        """Five batches of slots asked, eight got (a power of two), and every
        claim one whole batch: at the default depth no fire takes the
        wraparound copy-out, which would drain every window in flight."""
        from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner

        zero_copy = []
        dispatch_batch = CompiledMethodRunner.dispatch_batch

        def spy(self, batch, *, assemble_s=None, on_done=None):
            zero_copy.append(on_done is not None)
            return dispatch_batch(self, batch, assemble_s=assemble_s, on_done=on_done)

        monkeypatch.setattr(CompiledMethodRunner, "dispatch_batch", spy)
        # Windows of 1 in batches of 4, padded in the ring: 80 slots claimed
        # of 32, two and a half trips around the arena.
        results = _run(dict(model=lenet_model, policy=BucketPolicy(fixed_batch=B)),
                       images, window=1)
        assert [r.meta["i"] for r in results] == list(range(N))
        assert {r.meta["i"]: int(r["label"]) for r in results} == expected_labels
        assert zero_copy == [True] * N

    def test_partial_window_timeout_pads_in_ring(self, lenet_model, images, expected_labels):
        """Count-or-timeout fires partial windows: ring pads to the fixed
        bucket with replayed rows and drops them on unbatch."""
        results = _run(
            dict(model=lenet_model, policy=BucketPolicy(fixed_batch=B)),
            images[:7],  # 7 % 4 != 0 -> final partial fire via end-of-input
            window=B,
        )
        assert len(results) == 7
        got = {r.meta["i"]: int(r["label"]) for r in results}
        assert got == {i: expected_labels[i] for i in range(7)}

    def test_pipelined_ring_completeness(self, lenet_model, images, expected_labels):
        results = _run(
            dict(model=lenet_model, policy=BucketPolicy(fixed_batch=B),
                 pipeline_depth=3),
            images,
        )
        got = {r.meta["i"]: int(r["label"]) for r in results}
        assert got == expected_labels

    def test_tiny_ring_backpressures_not_deadlocks(self, lenet_model, images, expected_labels):
        """Capacity barely above one batch: ingestion must collect
        in-flight batches to free slots, never deadlock or drop."""
        results = _run(
            dict(model=lenet_model, policy=BucketPolicy(fixed_batch=B),
                 use_ring=True, ring_capacity=2 * B, pipeline_depth=2),
            images,
        )
        got = {r.meta["i"]: int(r["label"]) for r in results}
        assert got == expected_labels

    def test_dynamic_schema_rejected(self, lenet_model):
        """use_ring=True on a dynamic-length schema must fail fast."""
        mdef = get_model_def("bilstm", vocab_size=50, num_classes=3)
        params = jax.jit(mdef.init_fn)(jax.random.key(0))
        model = mdef.to_model(params)
        f = ModelWindowFunction(model, policy=BucketPolicy(fixed_batch=B),
                                use_ring=True)
        with pytest.raises(ValueError, match="static"):
            f.open(_ctx())
        f.close()


class TestRingCheckpoint:
    def test_snapshot_materializes_buffered_tokens(self, lenet_model, images, expected_labels, tmp_path):
        """A checkpoint taken while records sit in the ring must capture
        them; the restored run must produce every record exactly once."""
        import time

        ckpt = str(tmp_path / "ck")
        env = StreamExecutionEnvironment(parallelism=1)
        env.enable_checkpointing(ckpt)
        env.source_throttle_s = 0.02  # ~50 rec/s: snapshot lands mid-window
        out1 = (
            env.from_collection(images)
            .count_window(B)
            .apply(ModelWindowFunction(lenet_model, policy=BucketPolicy(fixed_batch=B)))
            .sink_to_list()
        )
        handle = env.execute_async()
        time.sleep(0.3)
        snaps = handle.trigger_checkpoint(timeout=60)
        offset = sum(s["operator"]["offset"] for s in snaps["collection"].values())
        assert 0 < offset < N, offset
        # Buffered window elements must be concrete values in the snapshot.
        for sub in snaps["window"].values():
            for _, elements, *_ in sub["operator"]["buffers"].values():
                assert all(isinstance(e, TensorValue) for e in elements)
        handle.cancel()
        handle.wait(timeout=60)

        env2 = StreamExecutionEnvironment(parallelism=1)
        out2 = (
            env2.from_collection(images)
            .count_window(B)
            .apply(ModelWindowFunction(lenet_model, policy=BucketPolicy(fixed_batch=B)))
            .sink_to_list()
        )
        env2.execute(restore_from=ckpt, timeout=120)
        # Exactly-once state: run 2 resumes from the snapshot, so records
        # delivered before the barrier appear only in run 1.  Together the
        # two runs must cover every record (none lost from the ring), with
        # correct labels everywhere (sinks are at-least-once on replay, so
        # overlap between the runs is permitted but loss is not).
        seen = {}
        for r in list(out1) + list(out2):
            i = r.meta["i"]
            assert int(r["label"]) == expected_labels[i], i
            seen[i] = True
        assert sorted(seen) == list(range(N))
        # The restored run must re-serve at least the buffered (materialized)
        # window contents — it cannot be empty unless the stream finished.
        assert out2, "restored run emitted nothing"
