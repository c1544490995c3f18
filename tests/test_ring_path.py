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

        def spy(self, batch, *, on_done=None, **kw):
            zero_copy.append(on_done is not None)
            return dispatch_batch(self, batch, on_done=on_done, **kw)

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


# -- early shipping: a filling window crosses the link chunk by chunk ----------
EB = 32                      # the fixed batch of these tests: 8 chunks of 4 rows
ROW_BYTES = 28 * 28 * 4


@pytest.fixture(scope="module")
def many_images():
    rng = np.random.RandomState(23)
    return [
        TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)}, {"i": i})
        for i in range(10 * EB + 9)
    ]


def _engage(monkeypatch):
    """Lenet's windows are a hundred kilobytes: lower the floor under a chunk,
    so that the runner's rule engages (the test steers; the program has no
    option for it)."""
    from flink_tensorflow_tpu.functions import runner

    monkeypatch.setattr(runner, "EARLY_CHUNK_MIN_BYTES", 1 << 10)


def _opened(model, **kw):
    f = ModelWindowFunction(model, policy=BucketPolicy(fixed_batch=EB),
                            warmup_batches=(EB,), **kw)
    f.open(_ctx())
    return f


def _collector(results):
    from flink_tensorflow_tpu.core import functions as fn

    return fn.Collector(lambda value, timestamp=None: results.append(value))


def _fill(f, records, out):
    """What ``WindowOperator.process_record`` does with a window function that
    ingests: the ring token where the ring took the record, else the record."""
    elements = []
    for r in records:
        token = f.ingest_element(r, out)
        elements.append(r if token is None else token)
    return elements


def _drive(f, records, sizes, *, head=()):
    """Windows of ``sizes`` records, filled and fired by hand (a count that
    is reached, or a timeout that fires a window short of it, is the same
    call); ``head``: elements already buffered for the first window.
    Returns the answers in order and the operator's counters."""
    results = []
    out = _collector(results)
    it = iter(records)
    elements = list(head)
    try:
        for n in sizes:
            elements += _fill(f, [next(it) for _ in range(n)], out)
            f.process_window(None, None, elements, out)
            elements = []
        f.on_finish(out)
        counters = {k: f._metrics.counter(k).value
                    for k in ("h2d_bytes", "h2d_early_bytes", "padded_records", "batches")}
    finally:
        f.close()
    return results, counters


def _same_answers(got, want):
    assert [r.meta["i"] for r in got] == [r.meta["i"] for r in want]
    for g, w in zip(got, want):
        for name in ("logits", "label", "prob"):
            assert np.array_equal(np.asarray(g[name]), np.asarray(w[name])), (g.meta, name)


def _early_rows(sizes, rows=EB // 8):
    """Rows of all-token windows that cross before their fire: whole chunks,
    never the last of a full batch."""
    return sum(min(n // rows, EB // rows - 1) * rows for n in sizes)


class TestEarlyShipping:
    @pytest.mark.parametrize("sizes, kw", [
        pytest.param([EB] * 3, {}, id="full_windows"),
        # Fired by the timeout short of the count: the tail chunks hold the
        # padding rows; 13 records have 3 chunks shipped, 3 records none.
        pytest.param([EB, 13, EB, 3, 4], {}, id="timeout_fired_short_windows"),
        # 128 slots hold four windows: ten go two and a half times around.
        pytest.param([EB] * 10, {"ring_capacity": 4 * EB}, id="across_the_arenas_wrap"),
        pytest.param([EB] * 6, {"pipeline_depth": 2, "ring_capacity": 2 * EB},
                     id="tiny_ring_depth_2"),
    ])
    def test_answers_bit_equal_to_one_put_a_window(self, lenet_model, many_images,
                                                   monkeypatch, sizes, kw):
        """Early shipping engaged against K = 1 (the window whole, in one put):
        same records in the same order, every answer bit for bit, the same
        padding; and every byte counted, (K - 1) / K of a full window early."""
        whole_f = _opened(lenet_model, **kw)
        assert whole_f.runner.chunk_rows is None
        want, base = _drive(whole_f, many_images, sizes)
        _engage(monkeypatch)
        early_f = _opened(lenet_model, **kw)
        assert early_f.runner.chunk_rows == EB // 8
        got, counters = _drive(early_f, many_images, sizes)
        assert len(got) == sum(sizes)
        _same_answers(got, want)
        assert base["h2d_early_bytes"] == 0
        assert counters["h2d_bytes"] == base["h2d_bytes"] == len(sizes) * EB * ROW_BYTES
        assert counters["padded_records"] == base["padded_records"] == len(sizes) * EB - sum(sizes)
        assert counters["h2d_early_bytes"] == _early_rows(sizes) * ROW_BYTES

    def test_through_end_of_input(self, lenet_model, many_images, monkeypatch):
        """Through the window operator: full windows by count, the last one
        (9 records, 2 chunks shipped) by end of input."""
        images = many_images[:5 * EB + 9]
        kw = dict(model=lenet_model, policy=BucketPolicy(fixed_batch=EB))
        want = _run(kw, images, window=EB)
        _engage(monkeypatch)
        got = _run(kw, images, window=EB)
        assert len(got) == len(images)
        _same_answers(got, want)

    def test_short_claim_at_the_arenas_end_copies_that_window_out(
            self, lenet_model, many_images, monkeypatch):
        """A snapshot's copy-out and a short window leave every later chunk one
        row off the arena's grid: once a trip around, a chunk's claim comes
        back short, and that window takes the copy path (drain, copy - the
        rows already claimed first -, plain arrays, the runner's own puts)."""
        sizes = [10] + [EB] * 6
        want, _ = _drive(_opened(lenet_model, ring_capacity=2 * EB), many_images,
                         [13] + sizes[1:])
        _engage(monkeypatch)
        f = _opened(lenet_model, ring_capacity=2 * EB)
        copied = []
        copy_out = f._copy_out

        def spy(head, b, out):
            copied.append(sum(n for _, n in head.claims))
            return copy_out(head, b, out)

        monkeypatch.setattr(f, "_copy_out", spy)
        values = f.materialize_tokens(_fill(f, many_images[:3], _collector([])))
        got, counters = _drive(f, many_images[3:], sizes, head=values)
        _same_answers(got, want)
        # The full windows start at slot 13 or 45 of 64: from 45, the fifth
        # chunk is slots 61-63 and one more after the wrap.
        assert copied == [4 * (EB // 8) + 3] * 3
        assert counters["h2d_early_bytes"] == 3 * (EB - EB // 8) * ROW_BYTES

    def test_snapshot_with_3_of_8_chunks_shipped(self, lenet_model, many_images, monkeypatch):
        """An operator snapshot in the middle of a fill: the rows already
        claimed are copied out of the retained views, then the rest; every
        claim is released, the chunks on the device dropped.  The run goes on,
        and a restored run starts, from those values: no record lost or
        doubled, the same answers."""
        n = 3 * (EB // 8) + 2
        sizes = [EB, EB, EB]
        want, _ = _drive(_opened(lenet_model), many_images, sizes)
        _engage(monkeypatch)
        f = _opened(lenet_model)
        results = []
        out = _collector(results)
        f.process_window(None, None, _fill(f, many_images[:EB], out), out)
        buffered = _fill(f, many_images[EB:EB + n], out)
        assert len(f._early.device) == 3 and f._early.rows == 12
        f._out = out
        f.snapshot_state()                      # what is in flight is emitted first
        assert [r.meta["i"] for r in results] == list(range(EB))
        values = f.materialize_tokens(buffered)
        assert f._early is None
        assert f._ring.poppable() == 0 and f._ring._claim_ahead == 0
        assert all(isinstance(v, TensorValue) for v in values)
        for v, r in zip(values, many_images[EB:EB + n]):
            assert v.meta == r.meta and np.array_equal(v["image"], r["image"])
        # The run goes on: fresh tokens behind the values, a mixed window.
        got, counters = _drive(f, many_images[EB + n:], [EB - n, EB], head=values)
        _same_answers(results + got, want)
        assert counters["h2d_early_bytes"] == 2 * (EB - EB // 8) * ROW_BYTES  # windows 1 and 3
        # A restored run: the snapshot's values are its first window's head.
        restored, _ = _drive(_opened(lenet_model), many_images[EB + n:], [EB - n, EB],
                             head=values)
        _same_answers(restored, want[EB:])

    def test_window_turned_mixed_by_a_full_ring(self, lenet_model, many_images, monkeypatch):
        """A window larger than the arena with nothing in flight: the ring
        fills, the rest is list-buffered, and the mixed window is copied out
        (the 28 rows shipped early first) and served from the list path."""
        n = EB + 8
        images = many_images[:n]
        want = _run(dict(model=lenet_model, policy=BucketPolicy(fixed_batch=EB),
                         use_ring=False), images, window=n)
        _engage(monkeypatch)
        f = _opened(lenet_model, ring_capacity=EB)
        assert f._ring.capacity == EB
        out = _collector([])
        elements = _fill(f, images, out)
        assert [isinstance(e, _RingToken) for e in elements] == [True] * EB + [False] * 8
        assert len(f._early.device) == 7 and f._early.off
        got, counters = _drive(f, [], [0], head=elements)
        assert f._early is None
        _same_answers(got, want)
        assert counters["h2d_early_bytes"] == 0 and counters["batches"] == 2

    @pytest.mark.parametrize("corner", ["close", "snapshot", "short_claim"])
    def test_rows_put_early_are_released_only_once_their_puts_are_ready(
            self, lenet_model, many_images, monkeypatch, corner):
        """A ``device_put`` returns before the host has read the rows (on the
        chip it re-lays them for tens of ms; on the CPU it aliases them, which
        hides this).  Where a half-shipped window's chunks are dropped and
        their rows released with no batch to collect - ``close()``, which then
        frees the arena, a snapshot's copy-out, the copy path of a window split
        at the arena's end - every chunk put is waited for first."""
        _engage(monkeypatch)
        log = []

        class Pending:
            nbytes = 0

            def block_until_ready(self):
                log.append("ready")
                return self

        f = _opened(lenet_model, ring_capacity=2 * EB)
        out = _collector([])
        if corner == "short_claim":
            # 45 slots of 64 on, the window's fifth chunk is split by the arena's end.
            values = f.materialize_tokens(_fill(f, many_images[:45], out))
            f.process_window(None, None, values[:EB], out)
        monkeypatch.setattr(f.runner, "put_chunk", lambda views: {"image": Pending()})
        ring = f._ring
        release, destroy = ring.release, ring.close
        monkeypatch.setattr(ring, "release", lambda n: log.append("release") or release(n))
        monkeypatch.setattr(ring, "close", lambda: log.append("close") or destroy())
        try:
            elements = _fill(f, many_images[:14], out)
            assert len(f._early.device) == 3 and not log
            if corner == "snapshot":
                f.materialize_tokens(elements)
            elif corner == "short_claim":
                f.process_window(None, None, elements, out)
        finally:
            f.close()
        assert log[:4] == ["ready"] * 3 + ["release"] and log[-1] == "close"
        assert log.count("ready") == 3

    def test_close_with_a_half_shipped_window(self, lenet_model, many_images, monkeypatch):
        """``close()`` in the middle of a fill: the window's claims are
        released (after the in-flight batches', oldest first) and its device
        chunks dropped; the ring is left empty with no claim outstanding."""
        _engage(monkeypatch)
        f = _opened(lenet_model)
        out = _collector([])
        f.process_window(None, None, _fill(f, many_images[:EB], out), out)  # in flight
        _fill(f, many_images[EB:EB + 12], out)
        ring = f._ring
        assert len(f._early.device) == 3
        assert ring._claim_ahead in (12, EB + 12)  # the window in flight may be collected
        left = []
        destroy = ring.close
        monkeypatch.setattr(ring, "close", lambda: left.append(
            (ring.poppable(), ring._claim_ahead)) or destroy())
        f.close()
        assert left == [(0, 0)]
        assert f._early is None and f._ring is None
