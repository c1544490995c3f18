"""Training-operator tests: per-key online SGD (Wide&Deep shape,
BASELINE.json:10) and the DP gang operator (ResNet shape, BASELINE.json:11)
on the virtual 8-device mesh."""

import numpy as np
import pytest

import jax
import optax

from flink_tensorflow_tpu import StreamExecutionEnvironment
from flink_tensorflow_tpu.functions import DPTrainWindowFunction, OnlineTrainFunction
from flink_tensorflow_tpu.models import get_model_def
from flink_tensorflow_tpu.parallel import make_mesh
from flink_tensorflow_tpu.tensors import RecordSchema, TensorValue, spec


def widedeep_tiny():
    return get_model_def("widedeep", hash_buckets=50, embed_dim=4,
                         num_cat_slots=2, num_dense=3, num_wide=8, hidden=(8,))


def widedeep_train_schema():
    return RecordSchema({
        "wide": spec((8,)),
        "dense": spec((3,)),
        "cat": spec((2,), np.int32),
        "label": spec((), np.int32),
    })


def make_records(n, seed=0, users=("a", "b")):
    rng = np.random.RandomState(seed)
    recs = []
    for i in range(n):
        user = users[i % len(users)]
        label = 1 if user == "a" else 0  # separable by user -> loss must fall
        recs.append(TensorValue({
            "wide": (rng.rand(8) * (1 + label)).astype(np.float32),
            "dense": rng.rand(3).astype(np.float32),
            "cat": rng.randint(0, 50, (2,)).astype(np.int32),
            "label": np.int32(label),
        }, meta={"user": user}))
    return recs


class _StubMetrics:
    @staticmethod
    def meter(name):
        class M:
            @staticmethod
            def mark(n):
                pass
        return M

    @staticmethod
    def counter(name):
        class C:
            @staticmethod
            def inc(n=1):
                pass
        return C


class _StubCtx:
    subtask_index = 0
    metrics = _StubMetrics


class _StubPCtx:
    current_key = "a"


class _ListOut:
    def __init__(self):
        self.items = []

    def collect(self, v, ts=None):
        self.items.append(v)


class TestOnlineTrain:
    def test_keyed_online_sgd_loss_decreases(self):
        env = StreamExecutionEnvironment(parallelism=1)
        f = OnlineTrainFunction(
            widedeep_tiny(), optax.adam(5e-2),
            train_schema=widedeep_train_schema(), mini_batch=4,
        )
        out = (
            env.from_collection(make_records(80))
            .key_by(lambda r: r.meta["user"])
            .process(f, name="train")
            .sink_to_list()
        )
        env.execute(timeout=300)
        losses = [float(r["loss"]) for r in out]
        assert len(losses) == 20  # 80 records / mini_batch 4
        assert np.mean(losses[-4:]) < np.mean(losses[:4]), losses

    def test_per_key_scope_independent_models(self):
        env = StreamExecutionEnvironment(parallelism=1)
        f = OnlineTrainFunction(
            widedeep_tiny(), optax.sgd(1e-2),
            train_schema=widedeep_train_schema(), scope="key", mini_batch=2,
        )
        out = (
            env.from_collection(make_records(12, users=("a", "b", "c")))
            .key_by(lambda r: r.meta["user"])
            .process(f, name="train")
            .sink_to_list()
        )
        env.execute(timeout=300)
        # 3 keys x 4 records each / mini_batch 2 = 6 steps; per-key step
        # counters advance independently (each reaches 2).
        by_key = {}
        for r in out:
            by_key.setdefault(r.meta["key"], []).append(int(r["step"]))
        assert set(by_key) == {"a", "b", "c"}
        for steps in by_key.values():
            assert steps == [1, 2]

    def test_snapshot_restore_roundtrip(self):
        f = OnlineTrainFunction(
            widedeep_tiny(), optax.sgd(1e-2),
            train_schema=widedeep_train_schema(), mini_batch=2,
        )
        f.open(_StubCtx())
        out = _ListOut()
        for r in make_records(4, users=("a",)):
            f.process_element(r, _StubPCtx, out)
        # Metric emission is pipelined (dispatch-and-go); the snapshot
        # flushes everything in flight before capturing state.
        snap = f.snapshot_state()
        assert len(out.items) == 2
        assert [int(r["step"]) for r in out.items] == [1, 2]

        g = OnlineTrainFunction(
            widedeep_tiny(), optax.sgd(1e-2),
            train_schema=widedeep_train_schema(), mini_batch=2,
        )
        g.restore_state(snap)
        g.open(_StubCtx())
        leaves_f = jax.tree.leaves(f.current_params())
        leaves_g = jax.tree.leaves(g.current_params())
        for a, b in zip(leaves_f, leaves_g):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_disk_checkpoint_roundtrip_with_adam(self, tmp_path):
        """Persistence regression (round-1 advisor): a training snapshot must
        survive write_checkpoint → pickle → read_checkpoint with (a) the
        typed PRNG key and (b) optax's namedtuple optimizer state intact,
        and the restored function must complete a post-restore adam step."""
        from flink_tensorflow_tpu.checkpoint.store import read_checkpoint, write_checkpoint

        def make():
            return OnlineTrainFunction(
                widedeep_tiny(), optax.adam(1e-2),
                train_schema=widedeep_train_schema(), mini_batch=2,
            )

        f = make()
        f.open(_StubCtx())
        out = _ListOut()
        for r in make_records(4, users=("a",)):
            f.process_element(r, _StubPCtx, out)
        snap = f.snapshot_state()

        write_checkpoint(str(tmp_path), 1, {"train": {0: snap}})
        cid, snapshots = read_checkpoint(str(tmp_path))
        assert cid == 1

        g = make()
        g.restore_state(snapshots["train"][0])
        g.open(_StubCtx())
        # Params identical after the disk round trip...
        for a, b in zip(jax.tree.leaves(f.current_params()),
                        jax.tree.leaves(g.current_params())):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # ...and a further adam step works (namedtuple opt state preserved).
        out2 = _ListOut()
        for r in make_records(2, seed=1, users=("a",)):
            g.process_element(r, _StubPCtx, out2)
        g.on_finish(out2)
        assert len(out2.items) == 1
        # Step numbering continues from the restored state (2 steps done).
        assert int(out2.items[0]["step"]) == 3
        assert np.isfinite(float(out2.items[0]["loss"]))


class TestDPTrainGang:
    def test_gang_dp_training_loss_decreases(self):
        mesh = make_mesh({"data": 8})
        mdef = get_model_def("lenet")
        schema = RecordSchema({
            "image": spec((28, 28, 1)),
            "label": spec((), np.int32),
        })
        rng = np.random.RandomState(0)
        # Learnable mapping: label = brightness bucket
        recs = []
        for i in range(128):
            label = i % 4
            img = rng.rand(28, 28, 1).astype(np.float32) * 0.2 + label * 0.25
            recs.append(TensorValue({"image": img, "label": np.int32(label)}))

        env = StreamExecutionEnvironment(parallelism=1)
        env.set_mesh(mesh)
        f = DPTrainWindowFunction(
            mdef, optax.adam(1e-2), train_schema=schema, global_batch=32,
        )
        out = (
            env.from_collection(recs * 2)  # 256 records -> 8 steps
            .count_window(32)
            .apply(f, name="dp_train")
            .sink_to_list()
        )
        result = env.execute(timeout=600)
        losses = [float(r["loss"]) for r in out]
        assert len(losses) == 8
        assert losses[-1] < losses[0], losses
        assert result.metrics["dp_train.0.train_steps"] == 8

    def test_gang_requires_mesh(self):
        env = StreamExecutionEnvironment(parallelism=1)
        f = DPTrainWindowFunction(
            get_model_def("lenet"), train_schema=RecordSchema({
                "image": spec((28, 28, 1)), "label": spec((), np.int32)}),
            global_batch=8,
        )
        env.from_collection([TensorValue({
            "image": np.zeros((28, 28, 1), np.float32), "label": np.int32(0)
        })]).count_window(8).apply(f).sink_to_list()
        from flink_tensorflow_tpu.core.runtime import JobFailure

        with pytest.raises(JobFailure):
            env.execute(timeout=60)


class TestFusedOnlineSteps:
    """steps_per_dispatch fuses K SGD steps into one lax.scan dispatch;
    the step sequence must match the unfused path (float rounding may
    differ across executables) and partial chunks must flush."""

    def _run(self, k, n=24):
        env = StreamExecutionEnvironment(parallelism=1)
        out = (
            env.from_collection(make_records(n, users=("a",)))
            .key_by(lambda r: r.meta["user"])
            .process(
                OnlineTrainFunction(
                    widedeep_tiny(), optax.sgd(5e-2),
                    train_schema=widedeep_train_schema(), mini_batch=2,
                    steps_per_dispatch=k,
                ),
                name="train", parallelism=1,
            )
            .sink_to_list()
        )
        env.execute(timeout=300)
        return out

    def test_fused_matches_sequential(self):
        a, b = self._run(1), self._run(4)
        assert [int(r["step"]) for r in a] == [int(r["step"]) for r in b] \
            == list(range(1, 13))
        np.testing.assert_allclose([float(r["loss"]) for r in a],
                                   [float(r["loss"]) for r in b], rtol=1e-5)

    def test_partial_chunk_flushes_at_finish(self):
        # 24 records / mini_batch 2 = 12 steps; with k=5 the last fused
        # chunk holds only 2 staged steps — on_finish must run them.
        out = self._run(5)
        assert [int(r["step"]) for r in out] == list(range(1, 13))
