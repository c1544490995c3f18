"""Plan-time analyzer: lint-rule matrix, schema propagation, and the
ISSUE-1 acceptance criteria (five clean examples via the CLI; a
mis-schemaed pipeline yielding exactly one ERROR naming its edge)."""

import pathlib
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, ".")

from flink_tensorflow_tpu import StreamExecutionEnvironment
from flink_tensorflow_tpu.analysis import (
    PlanValidationError,
    Severity,
    analyze,
    capture_plan,
    edge_name,
    format_diagnostics,
)
from flink_tensorflow_tpu.core import functions as fn
from flink_tensorflow_tpu.core.graph import CycleError, DataflowGraph, Edge
from flink_tensorflow_tpu.core.operators import MapOperator, ProcessOperator
from flink_tensorflow_tpu.core.partitioning import (
    ForwardPartitioner,
    RebalancePartitioner,
)
from flink_tensorflow_tpu.tensors import RecordSchema, spec
from flink_tensorflow_tpu.tensors.batching import BucketLadder, BucketPolicy

REPO = pathlib.Path(__file__).resolve().parents[1]
EXAMPLES = [
    "examples/mnist_lenet.py",
    "examples/widedeep_online.py",
    "examples/bilstm_stream.py",
    "examples/resnet_dp_train.py",
    "examples/inception_inference.py",
]


def by_rule(diags, rule_id):
    return [d for d in diags if d.rule == rule_id]


def errors(diags):
    return [d for d in diags if d.severity == Severity.ERROR]


class _IdMap(fn.MapFunction):
    def map(self, value):
        return value


class _Proc(fn.ProcessFunction):
    def process_element(self, value, ctx, out):
        out.collect(value)


class _StubJitWindowFn(fn.WindowFunction):
    """Minimal jit-boundary window function for lint-rule tests."""

    is_jit_boundary = True

    def __init__(self, policy=None):
        self._policy = policy

    def process_window(self, key, window, elements, out):
        for e in elements:
            out.collect(e)


class _StubGangFn(_StubJitWindowFn):
    is_gang = True

    def __init__(self, global_batch, policy=None):
        super().__init__(policy)
        self.global_batch = global_batch


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


SCHEMA_F32 = RecordSchema({"x": spec((4,), np.float32)})
SCHEMA_I32 = RecordSchema({"x": spec((4,), np.int32)})


def clean_env(parallelism=1):
    env = StreamExecutionEnvironment(parallelism=parallelism)
    (env.from_collection([1, 2, 3], schema=SCHEMA_F32)
        .map(_IdMap(), output_schema=lambda s: s)
        .filter(lambda v: True)
        .sink_to_list())
    return env


class TestCycleDetection:
    def test_topological_order_raises_with_names(self):
        g = DataflowGraph()
        g.add("src", lambda: None, 1, is_source=True)
        b = g.add("b", lambda: None, 1)
        c = g.add("c", lambda: None, 1)
        b.inputs.append(Edge(c, RebalancePartitioner()))
        c.inputs.append(Edge(b, RebalancePartitioner()))
        with pytest.raises(CycleError) as exc:
            g.topological_order()
        assert "b" in exc.value.cycle_names and "c" in exc.value.cycle_names

    def test_acyclic_order_unchanged(self):
        env = clean_env()
        order = env.graph.topological_order()
        assert [t.name for t in order] == ["collection", "map", "filter", "collect"]

    def test_cycle_is_sole_error_diagnostic(self):
        g = DataflowGraph()
        b = g.add("b", lambda: None, 1)
        c = g.add("c", lambda: None, 1)
        b.inputs.append(Edge(c, RebalancePartitioner()))
        c.inputs.append(Edge(b, RebalancePartitioner()))
        diags = analyze(g)
        assert len(diags) == 1 and diags[0].rule == "cycle"
        assert diags[0].severity == Severity.ERROR

    def test_runtime_build_raises_cycle_error(self):
        env = StreamExecutionEnvironment()
        s = env.from_collection([1])
        m = s.map(_IdMap()).transformation
        # Hand-wire a back edge (the fluent API cannot build one).
        m.inputs.append(Edge(m, RebalancePartitioner()))
        with pytest.raises(CycleError):
            env.execute("cyclic")


class TestSchemaHashability:
    def test_hash_consistent_with_eq(self):
        a = RecordSchema({"x": spec((4,)), "y": spec((), np.int32)})
        b = RecordSchema({"y": spec((), np.int32), "x": spec((4,))})
        assert a == b and hash(a) == hash(b)
        assert len({a, b}) == 1

    def test_distinct_schemas_distinct_in_sets(self):
        assert len({SCHEMA_F32, SCHEMA_I32}) == 2


class TestLintRules:
    def test_clean_pipeline_no_diagnostics(self):
        env = clean_env()
        assert analyze(env.graph, config=env.config) == []

    def test_dangling_root(self):
        env = clean_env()
        env.graph.add("orphan", lambda: MapOperator("orphan", _IdMap()), 1)
        diags = by_rule(analyze(env.graph), "dangling-root")
        assert len(diags) == 1 and diags[0].node == "orphan"
        assert diags[0].severity == Severity.ERROR

    def test_keyed_partitioning(self):
        env = StreamExecutionEnvironment()
        src = env.from_collection([1, 2, 3], schema=SCHEMA_F32)
        env.graph.add(
            "keyed",
            lambda: ProcessOperator("keyed", _Proc(), key_selector=lambda v: v),
            1,
            inputs=[Edge(src.transformation, RebalancePartitioner())],
        )
        diags = by_rule(analyze(env.graph), "keyed-partitioning")
        assert len(diags) == 1
        assert diags[0].edge == edge_name("collection", "keyed")

    def test_keyed_partitioning_clean_via_key_by(self):
        env = StreamExecutionEnvironment()
        env.from_collection([1, 2, 3]).key_by(lambda v: v).process(_Proc())
        assert by_rule(analyze(env.graph), "keyed-partitioning") == []

    def test_forward_parallelism(self):
        env = StreamExecutionEnvironment()
        src = env.from_collection([1, 2, 3])
        env.graph.add(
            "fwd", lambda: MapOperator("fwd", _IdMap()), 4,
            inputs=[Edge(src.transformation, ForwardPartitioner())],
        )
        diags = by_rule(analyze(env.graph), "forward-parallelism")
        assert len(diags) == 1
        assert diags[0].edge == edge_name("collection", "fwd")

    def test_keyed_parallelism_bound(self):
        env = StreamExecutionEnvironment()
        env.configure(max_parallelism=2)
        env.from_collection([1, 2, 3]).key_by(lambda v: v).process(
            _Proc(), parallelism=3)
        diags = by_rule(analyze(env.graph, config=env.config),
                        "keyed-parallelism-bound")
        assert len(diags) == 1 and "max_parallelism 2" in diags[0].message
        # Without a config the rule cannot know the bound and stays quiet.
        assert by_rule(analyze(env.graph), "keyed-parallelism-bound") == []

    def test_gang_parallelism_and_missing_mesh(self):
        env = StreamExecutionEnvironment(parallelism=2)
        (env.from_collection([1, 2, 3], schema=SCHEMA_F32)
            .count_window(4)
            .apply(_StubGangFn(global_batch=4), name="gang", parallelism=2))
        msgs = by_rule(analyze(env.graph, config=env.config), "mesh-divisibility")
        assert any("parallelism 2" in d.message for d in msgs)
        assert any("set_mesh" in d.message for d in msgs)

    def test_mesh_divisibility(self):
        env = StreamExecutionEnvironment()
        env.set_mesh(_FakeMesh({"data": 3}))
        (env.from_collection([1, 2, 3], schema=SCHEMA_F32)
            .count_window(4)
            .apply(_StubGangFn(global_batch=4), name="gang"))
        diags = by_rule(analyze(env.graph, config=env.config), "mesh-divisibility")
        assert len(diags) == 1 and "does not divide" in diags[0].message

    def test_mesh_divisibility_clean(self):
        env = StreamExecutionEnvironment()
        env.set_mesh(_FakeMesh({"data": 4}))
        (env.from_collection([1, 2, 3], schema=SCHEMA_F32)
            .count_window(4)
            .apply(_StubGangFn(global_batch=4), name="gang"))
        assert by_rule(analyze(env.graph, config=env.config),
                       "mesh-divisibility") == []

    def test_dynamic_jit_boundary_unbucketed_is_error(self):
        env = StreamExecutionEnvironment()
        dyn = RecordSchema({"tokens": spec((None,), np.int32)})
        (env.from_collection([1], schema=dyn)
            .count_window(4)
            .apply(_StubJitWindowFn(policy=None), name="jit"))
        diags = by_rule(analyze(env.graph), "dynamic-jit-boundary")
        assert [d.severity for d in diags].count(Severity.ERROR) == 1
        assert "tokens" in diags[0].message

    def test_dynamic_jit_boundary_bucketed_is_info(self):
        env = StreamExecutionEnvironment()
        dyn = RecordSchema({"tokens": spec((None,), np.int32)})
        policy = BucketPolicy(lengths=BucketLadder([64, 128]))
        (env.from_collection([1], schema=dyn)
            .count_window(4)
            .apply(_StubJitWindowFn(policy=policy), name="jit"))
        diags = by_rule(analyze(env.graph), "dynamic-jit-boundary")
        assert len(diags) == 1 and diags[0].severity == Severity.INFO

    def test_recompile_churn_on_mixed_signatures(self):
        env = StreamExecutionEnvironment()
        a = env.from_collection([1], schema=SCHEMA_F32, name="a")
        b = env.from_collection([2], schema=SCHEMA_I32, name="b")
        policy = BucketPolicy(fixed_batch=4)
        (a.union(b)
            .count_window(4)
            .apply(_StubJitWindowFn(policy=policy), name="jit"))
        diags = by_rule(analyze(env.graph), "recompile-churn")
        assert len(diags) == 1 and "2 distinct schema signatures" in diags[0].message
        assert diags[0].severity == Severity.WARN

    def test_recompile_churn_window_without_policy(self):
        env = StreamExecutionEnvironment()
        (env.from_collection([1], schema=SCHEMA_F32)
            .count_window(4, timeout_s=0.1)
            .apply(_StubJitWindowFn(policy=None), name="jit"))
        diags = by_rule(analyze(env.graph), "recompile-churn")
        assert len(diags) == 1 and "no batch-bucket policy" in diags[0].message

    def test_source_schema_unknown_is_info(self):
        env = StreamExecutionEnvironment()
        env.from_collection([1, 2, 3]).map(_IdMap()).sink_to_list()
        diags = by_rule(analyze(env.graph), "source-schema-unknown")
        assert len(diags) == 1 and diags[0].severity == Severity.INFO


class TestSchemaPropagation:
    """Propagation through map -> window -> model-function chains."""

    @pytest.fixture(scope="class")
    def lenet_model(self):
        import jax

        from flink_tensorflow_tpu.models import get_model_def

        mdef = get_model_def("lenet")
        return mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))

    def _pipeline(self, model, source_dtype=np.float32, map_hook=None):
        from flink_tensorflow_tpu.functions import ModelWindowFunction

        env = StreamExecutionEnvironment()
        schema = RecordSchema({"image": spec((28, 28, 1), source_dtype)})
        (env.from_collection([], schema=schema)
            .map(_IdMap(), name="preprocess",
                 output_schema=map_hook or (lambda s: s))
            .count_window(8, timeout_s=0.02)
            .apply(ModelWindowFunction(model), name="lenet")
            .sink_to_list())
        return env

    def test_clean_chain_propagates_and_validates(self, lenet_model):
        env = self._pipeline(lenet_model)
        diags = analyze(env.graph, config=env.config)
        assert errors(diags) == [], format_diagnostics(diags)

    def test_dtype_mismatch_exactly_one_error_naming_edge(self, lenet_model):
        """ISSUE-1 acceptance: a dtype mismatch injected at one edge
        yields exactly ONE error, naming that edge."""
        env = self._pipeline(lenet_model, source_dtype=np.uint8)
        diags = analyze(env.graph, config=env.config)
        errs = errors(diags)
        assert len(errs) == 1
        assert errs[0].rule == "schema-mismatch"
        assert errs[0].edge == edge_name("preprocess", "lenet")
        assert "dtype" in errs[0].message and "image" in errs[0].message

    def test_map_hook_transform_is_applied(self, lenet_model):
        # The map declares it converts uint8 -> float32: the chain is
        # clean even though the source emits uint8.
        to_f32 = lambda s: RecordSchema(  # noqa: E731
            {n: spec(s[n].shape, np.float32) for n in s.names})
        env = self._pipeline(lenet_model, source_dtype=np.uint8,
                             map_hook=to_f32)
        assert errors(analyze(env.graph)) == []

    def test_rank_mismatch_detected(self, lenet_model):
        from flink_tensorflow_tpu.functions import ModelWindowFunction

        env = StreamExecutionEnvironment()
        schema = RecordSchema({"image": spec((28, 28), np.float32)})
        (env.from_collection([], schema=schema)
            .count_window(8)
            .apply(ModelWindowFunction(lenet_model), name="lenet"))
        errs = errors(analyze(env.graph))
        assert len(errs) == 1 and "rank" in errs[0].message

    def test_missing_field_detected(self, lenet_model):
        from flink_tensorflow_tpu.functions import ModelWindowFunction

        env = StreamExecutionEnvironment()
        schema = RecordSchema({"pixels": spec((28, 28, 1), np.float32)})
        (env.from_collection([], schema=schema)
            .count_window(8)
            .apply(ModelWindowFunction(lenet_model), name="lenet"))
        errs = errors(analyze(env.graph))
        assert len(errs) == 1 and "missing field" in errs[0].message

    def test_training_function_validates_train_schema(self):
        from flink_tensorflow_tpu.functions import OnlineTrainFunction
        from flink_tensorflow_tpu.models import get_model_def

        cfg = dict(hash_buckets=16, embed_dim=2, num_cat_slots=2,
                   num_dense=2, num_wide=4, hidden=(4,))
        mdef = get_model_def("widedeep", **cfg)
        train_schema = RecordSchema({
            "wide": spec((4,)), "dense": spec((2,)),
            "cat": spec((2,), np.int32), "label": spec((), np.int32),
        })
        bad_source = RecordSchema({
            "wide": spec((4,)), "dense": spec((2,)),
            "cat": spec((2,), np.float32),  # wrong dtype
            "label": spec((), np.int32),
        })
        env = StreamExecutionEnvironment()
        (env.from_collection([], schema=bad_source)
            .key_by(lambda r: 0)
            .process(OnlineTrainFunction(mdef, train_schema=train_schema),
                     name="train"))
        errs = errors(analyze(env.graph))
        assert len(errs) == 1
        assert errs[0].edge == edge_name("collection", "train")
        assert "cat" in errs[0].message


class TestValidateGate:
    def test_execute_validate_true_raises_before_running(self):
        env = StreamExecutionEnvironment()
        env.graph.add("orphan", lambda: MapOperator("orphan", _IdMap()), 1)
        with pytest.raises(PlanValidationError) as exc:
            env.execute("bad", validate=True)
        assert any(d.rule == "dangling-root" for d in exc.value.diagnostics)

    def test_execute_validate_true_clean_job_runs(self):
        env = StreamExecutionEnvironment()
        out = (env.from_collection([1, 2, 3], schema=SCHEMA_F32)
               .map(_IdMap(), output_schema=lambda s: s)
               .sink_to_list())
        env.execute("good", validate=True, timeout=60)
        assert sorted(out) == [1, 2, 3]

    def test_validate_plan_returns_diagnostics_without_raising(self):
        env = StreamExecutionEnvironment()
        env.from_collection([1]).sink_to_list()
        diags = env.validate_plan(raise_on_error=False)
        assert all(d.severity != Severity.ERROR for d in diags)


class TestCapture:
    def test_capture_plan_returns_env_without_executing(self):
        ran = []

        def job():
            env = StreamExecutionEnvironment()
            env.from_collection([1, 2, 3], schema=SCHEMA_F32).sink_to_list()
            env.execute("captured")
            ran.append(True)  # must never run

        env = capture_plan(job)
        assert not ran
        assert [t.name for t in env.graph.transformations] == [
            "collection", "collect"]

    def test_capture_plan_requires_execute(self):
        with pytest.raises(RuntimeError, match="no plan to analyze"):
            capture_plan(lambda: None)


class TestCLIAcceptance:
    def test_cli_clean_on_all_five_examples(self):
        """ISSUE-1 acceptance: the CLI exits 0 (no ERROR diagnostics) on
        each of the five example pipelines."""
        proc = subprocess.run(
            [sys.executable, "-m", "flink_tensorflow_tpu.analysis", *EXAMPLES],
            cwd=REPO, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        for path in EXAMPLES:
            assert path in proc.stdout
        assert "ERROR" not in proc.stdout

    def test_cli_nonzero_on_missing_file(self):
        proc = subprocess.run(
            [sys.executable, "-m", "flink_tensorflow_tpu.analysis",
             "examples/does_not_exist.py"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 2


class _StubAsyncMap(fn.AsyncMapFunction):
    """Async map with a declared transparent micro-batch (the attribute
    ModelMapFunction carries) for the watermark-flush lint."""

    _micro_batch = 8

    def map_async(self, value, out):
        out.collect(value)

    def flush(self, out=None):
        pass


class _CountWindowFn(fn.WindowFunction):
    def process_window(self, key, window, elements, out):
        out.collect(len(elements))


class TestServingLints:
    """serving-unkeyed-input / serving-recompile-churn matrix (ISSUE 10)."""

    @staticmethod
    def _model():
        import jax

        from flink_tensorflow_tpu.models import get_model_def

        mdef = get_model_def("char_transformer", vocab_size=32, embed_dim=32,
                             num_heads=2, num_layers=1, capacity=32)
        return mdef.to_model(mdef.init_params(jax.random.PRNGKey(0)))

    @staticmethod
    def _requests():
        from flink_tensorflow_tpu.serving import GenerateRequest

        return [GenerateRequest(session_id="a",
                                prompt=np.ones((4,), np.int32))]

    def test_clean_keyed_serving_plan(self):
        from flink_tensorflow_tpu.serving import ServingConfig, continuous_batching

        env = StreamExecutionEnvironment(parallelism=1)
        continuous_batching(
            env.from_collection(self._requests())
            .key_by(lambda r: r.session_id),
            self._model(), config=ServingConfig(capacity=32),
        ).sink_to_list()
        diags = analyze(env.graph, config=env.config)
        assert not by_rule(diags, "serving-unkeyed-input")
        assert not by_rule(diags, "serving-recompile-churn")

    def test_unkeyed_edge_is_error(self):
        from flink_tensorflow_tpu.serving import (
            ContinuousBatchingOperator,
            ServingConfig,
        )

        env = StreamExecutionEnvironment(parallelism=1)
        src = env.from_collection(self._requests())
        model = self._model()
        # Hand-built plan bypassing continuous_batching: rebalance edge,
        # no key selector — both findings fire.
        env.graph.add(
            "serve",
            lambda: ContinuousBatchingOperator(
                "serve", model, ServingConfig(capacity=32)),
            1,
            inputs=[Edge(src.transformation, RebalancePartitioner())],
        )
        diags = by_rule(analyze(env.graph, config=env.config),
                        "serving-unkeyed-input")
        assert len(diags) == 2
        assert all(d.severity == Severity.ERROR for d in diags)
        assert any("Rebalance" in d.message for d in diags)
        assert any("key selector" in d.message for d in diags)

    def test_disabled_padding_buckets_warn(self):
        from flink_tensorflow_tpu.serving import ServingConfig, continuous_batching

        env = StreamExecutionEnvironment(parallelism=1)
        continuous_batching(
            env.from_collection(self._requests())
            .key_by(lambda r: r.session_id),
            self._model(),
            config=ServingConfig(capacity=32, padding_buckets=False),
        ).sink_to_list()
        diags = by_rule(analyze(env.graph, config=env.config),
                        "serving-recompile-churn")
        assert len(diags) == 1 and diags[0].severity == Severity.WARN
        assert "padding_buckets" in diags[0].message

    def test_fixed_window_baseline_also_covered(self):
        from flink_tensorflow_tpu.serving import (
            FixedWindowGenerateFunction,
            ServingConfig,
        )

        env = StreamExecutionEnvironment(parallelism=1)
        (
            env.from_collection(self._requests())
            .count_window(4)
            .apply(FixedWindowGenerateFunction(
                self._model(),
                ServingConfig(capacity=32, padding_buckets=False)),
                name="fixed")
            .sink_to_list()
        )
        diags = by_rule(analyze(env.graph, config=env.config),
                        "serving-recompile-churn")
        assert len(diags) == 1 and diags[0].node == "fixed"


class TestKvPoolUndersizedLint:
    """kv-pool-undersized: an open-loop paced source offering sessions
    faster than the ``max_active_seqs``-bounded admission plane can
    possibly turn over, against a serving config with no KV tier valve
    (dense plane, or paged with tiering off).  ISSUE 19 matrix."""

    def _env(self, config, *, rate_hz=100.0, paced=True):
        from flink_tensorflow_tpu.serving import continuous_batching
        from flink_tensorflow_tpu.sources import PacedSplitSource

        env = StreamExecutionEnvironment(parallelism=1)
        reqs = TestServingLints._requests()
        if paced:
            stream = env.from_source(
                PacedSplitSource(reqs, rate_hz), name="paced")
        else:
            stream = env.from_collection(reqs)
        continuous_batching(
            stream.key_by(lambda r: r.session_id),
            TestServingLints._model(), config=config,
        ).sink_to_list()
        return env

    def test_open_loop_overrate_dense_warns(self):
        from flink_tensorflow_tpu.serving import ServingConfig

        env = self._env(ServingConfig(capacity=32, max_active_seqs=4))
        diags = by_rule(analyze(env.graph, config=env.config),
                        "kv-pool-undersized")
        assert len(diags) == 1 and diags[0].severity == Severity.WARN
        assert "paged_kv" in diags[0].message
        assert "4 admission slots" in diags[0].message

    def test_paged_with_tiering_off_still_warns(self):
        from flink_tensorflow_tpu.serving import ServingConfig

        env = self._env(ServingConfig(
            capacity=32, max_active_seqs=4, paged_kv=True, page_tokens=8,
            tiering=False))
        diags = by_rule(analyze(env.graph, config=env.config),
                        "kv-pool-undersized")
        assert len(diags) == 1
        assert "tiering" in diags[0].message

    def test_paged_tiered_plan_is_silent(self):
        from flink_tensorflow_tpu.serving import ServingConfig

        env = self._env(ServingConfig(
            capacity=32, max_active_seqs=4, paged_kv=True, page_tokens=8))
        assert by_rule(analyze(env.graph, config=env.config),
                       "kv-pool-undersized") == []

    def test_rate_within_admission_bound_is_silent(self):
        from flink_tensorflow_tpu.serving import ServingConfig

        env = self._env(ServingConfig(capacity=32, max_active_seqs=4),
                        rate_hz=2.0)
        assert by_rule(analyze(env.graph, config=env.config),
                       "kv-pool-undersized") == []

    def test_closed_loop_source_is_silent(self):
        from flink_tensorflow_tpu.serving import ServingConfig

        env = self._env(ServingConfig(capacity=32, max_active_seqs=4),
                        paced=False)
        assert by_rule(analyze(env.graph, config=env.config),
                       "kv-pool-undersized") == []


class TestWatermarkLints:
    """ISSUE-2 satellite: the deferred watermark lints from ROADMAP."""

    def test_event_time_window_without_assigner_is_error(self):
        env = StreamExecutionEnvironment()
        (env.from_collection([("k", 1.0)])
            .key_by(lambda e: e[0])
            .time_window(1.0)
            .apply(_CountWindowFn())
            .sink_to_list())
        diags = by_rule(analyze(env.graph), "watermark-missing-assigner")
        assert len(diags) == 1
        assert diags[0].severity == Severity.ERROR
        assert diags[0].node == "time_window"

    def test_session_window_without_assigner_is_error(self):
        env = StreamExecutionEnvironment()
        (env.from_collection([("k", 1.0)])
            .key_by(lambda e: e[0])
            .session_window(1.0)
            .apply(_CountWindowFn())
            .sink_to_list())
        assert len(by_rule(analyze(env.graph),
                           "watermark-missing-assigner")) == 1

    def test_assigner_anywhere_upstream_is_clean(self):
        env = StreamExecutionEnvironment()
        (env.from_collection([("k", 1.0)])
            .assign_timestamps(lambda e: e[1])
            .map(_IdMap(), name="hop")           # assigner not adjacent
            .key_by(lambda e: e[0])
            .time_window(1.0)
            .apply(_CountWindowFn())
            .sink_to_list())
        assert by_rule(analyze(env.graph), "watermark-missing-assigner") == []

    def test_fine_watermarks_feeding_async_map_warn(self):
        env = StreamExecutionEnvironment()
        (env.from_collection([1.0, 2.0])
            .assign_timestamps(lambda e: e, watermark_every=1)
            .map(_StubAsyncMap(), name="asyncmap")
            .sink_to_list())
        diags = by_rule(analyze(env.graph), "watermark-async-flush")
        assert len(diags) == 1
        assert diags[0].severity == Severity.WARN
        assert diags[0].node == "asyncmap"
        assert "watermark_every >= 8" in diags[0].message

    def test_coarse_watermarks_are_clean(self):
        env = StreamExecutionEnvironment()
        (env.from_collection([1.0, 2.0])
            .assign_timestamps(lambda e: e, watermark_every=8)
            .map(_StubAsyncMap(), name="asyncmap")
            .sink_to_list())
        assert by_rule(analyze(env.graph), "watermark-async-flush") == []

    def test_second_assigner_retimes_the_stream(self):
        env = StreamExecutionEnvironment()
        (env.from_collection([1.0, 2.0])
            .assign_timestamps(lambda e: e, watermark_every=1, name="fine")
            .assign_timestamps(lambda e: e, watermark_every=8, name="coarse")
            .map(_StubAsyncMap(), name="asyncmap")
            .sink_to_list())
        diags = by_rule(analyze(env.graph), "watermark-async-flush")
        # Only the assigner actually feeding the map counts; the fine one
        # is shadowed by the coarse re-timing below it.
        assert diags == []


class _AnnotatedMap(fn.MapFunction):
    """Non-gang map declaring batch-dim sharding axes + a fixed batch."""

    def __init__(self, axes, batch=None):
        self.sharding_axes = axes
        self._policy = BucketPolicy(fixed_batch=batch) if batch else None

    def map(self, value):
        return value


class TestShardingAxisLint:
    """ROADMAP's deferred sharding-axis lint: NamedSharding / batch-dim
    annotations validated against the mesh axes at plan time, sharing
    its annotation vocabulary with the operator-chaining pass."""

    def test_unknown_axis_is_error(self):
        env = StreamExecutionEnvironment()
        env.set_mesh(_FakeMesh({"data": 4}))
        (env.from_collection([1, 2, 3])
            .map(_AnnotatedMap(("model",)), name="tp")
            .sink_to_list())
        diags = by_rule(analyze(env.graph, config=env.config), "sharding-axis")
        errors = [d for d in diags if d.severity == Severity.ERROR]
        assert len(errors) == 1
        assert "model" in errors[0].message and errors[0].node == "tp"

    def test_annotation_without_mesh_is_error(self):
        env = StreamExecutionEnvironment()
        (env.from_collection([1, 2, 3])
            .map(_AnnotatedMap(("data",)), name="dp")
            .sink_to_list())
        diags = by_rule(analyze(env.graph, config=env.config), "sharding-axis")
        assert any("no mesh" in d.message for d in diags)
        # Without a config the rule cannot know the mesh and stays quiet.
        assert by_rule(analyze(env.graph), "sharding-axis") == []

    def test_ragged_batch_over_declared_axes_is_error(self):
        env = StreamExecutionEnvironment()
        env.set_mesh(_FakeMesh({"data": 4}))
        (env.from_collection([1, 2, 3])
            .map(_AnnotatedMap(("data",), batch=6), name="ragged")
            .sink_to_list())
        diags = by_rule(analyze(env.graph, config=env.config), "sharding-axis")
        errors = [d for d in diags if d.severity == Severity.ERROR]
        assert len(errors) == 1 and "does not divide" in errors[0].message

    def test_valid_annotation_is_clean(self):
        env = StreamExecutionEnvironment()
        env.set_mesh(_FakeMesh({"data": 4}))
        (env.from_collection([1, 2, 3])
            .map(_AnnotatedMap(("data",), batch=8), name="ok")
            .sink_to_list())
        diags = by_rule(analyze(env.graph, config=env.config), "sharding-axis")
        assert [d for d in diags if d.severity == Severity.ERROR] == []

    def test_gang_mesh_errors_not_duplicated(self):
        """Gang missing-mesh / data-divisibility stay mesh-divisibility's
        findings; sharding-axis adds only the axis-existence check."""
        env = StreamExecutionEnvironment()
        (env.from_collection([1, 2, 3], schema=SCHEMA_F32)
            .count_window(4)
            .apply(_StubGangFn(global_batch=4), name="gang"))
        diags = analyze(env.graph, config=env.config)
        assert by_rule(diags, "sharding-axis") == []
        assert any(d.rule == "mesh-divisibility" for d in diags)

    def test_mismatched_forward_edge_is_warned(self):
        env = StreamExecutionEnvironment()
        env.set_mesh(_FakeMesh({"data": 2, "model": 2}))
        (env.from_collection([1, 2, 3])
            .map(_AnnotatedMap(("data",)), name="up")
            .map(_AnnotatedMap(("model",)), name="down")
            .sink_to_list())
        diags = by_rule(analyze(env.graph, config=env.config), "sharding-axis")
        warns = [d for d in diags if d.severity == Severity.WARN]
        assert any("will not chain" in d.message
                   and d.edge == edge_name("up", "down") for d in warns)


class TestExactlyOnceBoundaryLint:
    """exactly-once-boundary: restartable plan behind a non-replayable
    (TCP) source is at-least-once — the documented io/remote.py hole."""

    @staticmethod
    def _tcp_source():
        from flink_tensorflow_tpu.io.remote import RemoteSource

        return RemoteSource(bind="127.0.0.1")

    def test_checkpointed_remote_source_warns(self):
        src = self._tcp_source()
        try:
            env = StreamExecutionEnvironment(parallelism=1)
            env.enable_checkpointing("/tmp/eob-lint")
            env.from_source(src, name="tcp").sink_to_list()
            diags = by_rule(analyze(env.graph, config=env.config),
                            "exactly-once-boundary")
            assert len(diags) == 1
            assert diags[0].severity == Severity.WARN
            assert diags[0].node == "tcp"
            assert "FileSplitSource" in diags[0].message
        finally:
            src.close()

    def test_no_checkpointing_no_warning(self):
        src = self._tcp_source()
        try:
            env = StreamExecutionEnvironment(parallelism=1)
            env.from_source(src, name="tcp").sink_to_list()
            assert by_rule(analyze(env.graph, config=env.config),
                           "exactly-once-boundary") == []
        finally:
            src.close()

    def test_replayable_sources_stay_clean(self):
        env = StreamExecutionEnvironment(parallelism=1)
        env.enable_checkpointing("/tmp/eob-lint")
        env.from_collection([1, 2, 3]).sink_to_list()
        assert by_rule(analyze(env.graph, config=env.config),
                       "exactly-once-boundary") == []

    def test_bare_graph_without_config_skips(self):
        src = self._tcp_source()
        try:
            env = StreamExecutionEnvironment(parallelism=1)
            env.enable_checkpointing("/tmp/eob-lint")
            env.from_source(src, name="tcp").sink_to_list()
            assert by_rule(analyze(env.graph), "exactly-once-boundary") == []
        finally:
            src.close()


class TestFlowControlLint:
    """flow-control: a checkpointed multi-process plan running with
    ``JobConfig.flow_control=False`` behind an open-loop paced source is
    the exact configuration whose sender queues (and checkpoint
    alignment times) grow without bound under a consumer stall.  The
    rule fires ONLY when every leg is present — disable any one and the
    plan is defensible."""

    @staticmethod
    def _dist():
        from flink_tensorflow_tpu.core.distributed import DistributedConfig

        return DistributedConfig(
            0, 2, ("127.0.0.1:9001", "127.0.0.1:9002"))

    def _env(self, *, fc=False, dist=True, checkpoint=True, paced=True):
        from flink_tensorflow_tpu.sources import PacedSplitSource

        env = StreamExecutionEnvironment(parallelism=1)
        env.configure(flow_control=fc)
        if dist:
            env.set_distributed(self._dist())
        if checkpoint:
            env.enable_checkpointing("/tmp/fc-lint")
        if paced:
            stream = env.from_source(
                PacedSplitSource([1, 2, 3], 100.0), name="paced")
        else:
            stream = env.from_collection([1, 2, 3])
        stream.map(lambda x: x, name="m").sink_to_callable(lambda v: None)
        return env

    def test_open_loop_uncredited_checkpointed_cohort_warns(self):
        env = self._env()
        diags = by_rule(analyze(env.graph, config=env.config),
                        "flow-control")
        assert len(diags) == 1
        assert diags[0].severity == Severity.WARN
        assert diags[0].node == "paced"
        assert "flow_control" in diags[0].message
        assert "credit window" in diags[0].message

    def test_flow_control_on_is_silent(self):
        env = self._env(fc=True)
        assert by_rule(analyze(env.graph, config=env.config),
                       "flow-control") == []

    def test_single_process_is_silent(self):
        # In-memory channels are bounded by construction.
        env = self._env(dist=False)
        assert by_rule(analyze(env.graph, config=env.config),
                       "flow-control") == []

    def test_uncheckpointed_is_silent(self):
        # No alignment to wedge — overload just slows the job down.
        env = self._env(checkpoint=False)
        assert by_rule(analyze(env.graph, config=env.config),
                       "flow-control") == []

    def test_closed_loop_source_is_silent(self):
        # A pull-paced collection source already closes the loop.
        env = self._env(paced=False)
        assert by_rule(analyze(env.graph, config=env.config),
                       "flow-control") == []

    def test_bare_graph_without_config_skips(self):
        env = self._env()
        assert by_rule(analyze(env.graph), "flow-control") == []


class TestSloUnmonitoredLint:
    """slo-unmonitored: JobConfig.health over a cohort whose telemetry
    service is off — the evaluator/actuator would watch process 0 only."""

    @staticmethod
    def _dist(telemetry_interval_s):
        from flink_tensorflow_tpu.core.distributed import DistributedConfig

        return DistributedConfig(
            0, 2, ("127.0.0.1:9001", "127.0.0.1:9002"),
            telemetry_interval_s=telemetry_interval_s)

    @staticmethod
    def _health(autoscale=False):
        from flink_tensorflow_tpu.core.autoscale import AutoscaleConfig
        from flink_tensorflow_tpu.metrics.health import HealthConfig

        return HealthConfig(
            autoscale=AutoscaleConfig() if autoscale else None)

    def _env(self, *, health=None, dist=None):
        env = clean_env()
        if health is not None:
            env.configure(health=health)
        if dist is not None:
            env.set_distributed(dist)
        return env

    def test_warns_health_on_dead_cohort_feed(self):
        env = self._env(health=self._health(), dist=self._dist(0.0))
        diags = by_rule(analyze(env.graph, config=env.config),
                        "slo-unmonitored")
        assert len(diags) == 1
        assert diags[0].severity == Severity.WARN
        assert "health evaluation" in diags[0].message
        assert "telemetry_interval_s" in diags[0].message

    def test_warn_names_the_actuator_when_autoscale_set(self):
        env = self._env(health=self._health(autoscale=True),
                        dist=self._dist(0.0))
        diags = by_rule(analyze(env.graph, config=env.config),
                        "slo-unmonitored")
        assert len(diags) == 1
        assert "autoscale actuator" in diags[0].message

    def test_clean_when_telemetry_enabled(self):
        env = self._env(health=self._health(autoscale=True),
                        dist=self._dist(2.0))
        assert by_rule(analyze(env.graph, config=env.config),
                       "slo-unmonitored") == []

    def test_clean_single_process(self):
        env = self._env(health=self._health())
        assert by_rule(analyze(env.graph, config=env.config),
                       "slo-unmonitored") == []

    def test_clean_without_health(self):
        env = self._env(dist=self._dist(0.0))
        assert by_rule(analyze(env.graph, config=env.config),
                       "slo-unmonitored") == []

    def test_bare_graph_without_config_skips(self):
        env = self._env(health=self._health(), dist=self._dist(0.0))
        assert by_rule(analyze(env.graph), "slo-unmonitored") == []
