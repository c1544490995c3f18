"""Shardcheck tests (PR 16) — the SPMD layout / donation / HBM-budget
static analyzer.

The contract under test: on a CPU-only box, against a declared ABSTRACT
mesh (no devices anywhere), each of the five seeded defect classes is
caught and NAMED with operator/edge provenance —

1. a non-donated KV-pool-sized buffer through a jit boundary (2x HBM),
2. an fsdp-indivisible batch under the declared mesh,
3. an implicit reshard across a device-resident chained edge,
4. a plan whose static HBM footprint exceeds the declared budget,
5. an unbounded compile-signature ladder (padding_buckets off),

while healthy plans produce zero shardcheck ERROR/WARN findings.
Donation and reshard findings must name the offending buffer/axis.
"""

import sys

import numpy as np
import pytest

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from flink_tensorflow_tpu import StreamExecutionEnvironment
from flink_tensorflow_tpu.analysis import Severity, analyze, capture_plan
from flink_tensorflow_tpu.functions.model_function import ModelMapFunction
from flink_tensorflow_tpu.models.base import Model, ModelMethod
from flink_tensorflow_tpu.parallel import abstract_mesh
from flink_tensorflow_tpu.tensors.batching import BucketPolicy
from flink_tensorflow_tpu.tensors.schema import RecordSchema, spec


def _shard_diags(env):
    return [d for d in analyze(env.graph, config=env.config)
            if d.rule.startswith("shardcheck")]


def _by_rule(diags, rule):
    return [d for d in diags if d.rule == rule]


# ---------------------------------------------------------------------------
# Fixture models (all host-side; nothing ever compiles or executes).
# ---------------------------------------------------------------------------

def _cache_model(*, out_dtype=np.float32, emit_cache=True):
    """A decode-like step: a 1.5 MiB per-record KV-pool field rides
    through the method next to a small token field."""
    schema = RecordSchema({
        "k_cache": spec((768, 512), np.float32),  # 1.5 MiB per record
        "token": spec((8,), np.int32),
    })

    def fn(params, batch):
        out = {"next": jnp.sum(batch["token"], axis=-1) + params["bias"]}
        if emit_cache:
            out["k_cache"] = (batch["k_cache"] + 1.0).astype(out_dtype)
        return out

    outputs = ("k_cache", "next") if emit_cache else ("next",)
    method = ModelMethod(name="decode", input_schema=schema,
                         output_names=outputs, fn=fn)
    return Model("cache_model", {"bias": jnp.zeros((), np.float32)},
                 {"decode": method})


def _tiny_model():
    """A small pure map model: {"x": [8]} -> {"x": [8]} (chainable)."""
    schema = RecordSchema({"x": spec((8,), np.float32)})
    method = ModelMethod(
        name="serve", input_schema=schema, output_names=("x",),
        fn=lambda params, batch: {"x": batch["x"] * params["scale"]})
    return Model("tiny", {"scale": jnp.ones((), np.float32)},
                 {"serve": method})


def _zoo_decoder():
    from flink_tensorflow_tpu.models import get_model_def

    mdef = get_model_def("char_transformer", vocab_size=32, embed_dim=16,
                         num_heads=2, num_layers=1, capacity=16)
    return mdef.to_model(mdef.init_params(jax.random.PRNGKey(0)))


def _plan(build):
    """Capture the plan a job builder wires (execution never starts)."""
    def job():
        env = StreamExecutionEnvironment(parallelism=1)
        build(env)
        env.execute("shardcheck-fixture")
    return capture_plan(job)


# ---------------------------------------------------------------------------
# Seeded defect 1: the non-donated KV pool (2x HBM trap).
# ---------------------------------------------------------------------------
class TestDonation:
    def test_non_donated_kv_pool_is_named(self):
        env = _plan(lambda env: env.from_collection([{}]).map(
            ModelMapFunction(_cache_model(), "decode",
                             policy=BucketPolicy(fixed_batch=1)),
            name="decode"))
        hits = _by_rule(_shard_diags(env), "shardcheck-donation")
        assert hits, "non-donated cache buffer not flagged"
        assert hits[0].severity == Severity.WARN
        assert hits[0].node == "decode"
        assert "'k_cache'" in hits[0].message
        assert "NOT donated" in hits[0].message
        assert "2x HBM" in hits[0].message

    def test_donated_matching_cache_is_clean(self):
        env = _plan(lambda env: env.from_collection([{}]).map(
            ModelMapFunction(_cache_model(), "decode", donate_inputs=True,
                             policy=BucketPolicy(fixed_batch=1)),
            name="decode"))
        assert _by_rule(_shard_diags(env), "shardcheck-donation") == []

    def test_dtype_defeated_donation_is_named(self):
        env = _plan(lambda env: env.from_collection([{}]).map(
            ModelMapFunction(_cache_model(out_dtype=jnp.bfloat16), "decode",
                             donate_inputs=True,
                             policy=BucketPolicy(fixed_batch=1)),
            name="decode"))
        hits = _by_rule(_shard_diags(env), "shardcheck-donation")
        assert hits and "DEFEATED" in hits[0].message
        assert "'k_cache'" in hits[0].message

    def test_dead_donation_is_named(self):
        env = _plan(lambda env: env.from_collection([{}]).map(
            ModelMapFunction(_cache_model(emit_cache=False), "decode",
                             donate_inputs=True,
                             policy=BucketPolicy(fixed_batch=1)),
            name="decode"))
        hits = _by_rule(_shard_diags(env), "shardcheck-donation")
        assert hits and "dead" in hits[0].message
        assert "'k_cache'" in hits[0].message


# ---------------------------------------------------------------------------
# Seeded defect 2: fsdp-indivisible batch under the declared mesh.
# ---------------------------------------------------------------------------
class TestPartition:
    def test_indivisible_batch_errors_and_names_axes(self):
        def build(env):
            env.set_mesh(abstract_mesh({"data": 2, "fsdp": 2}))
            env.from_collection([{}]).map(
                ModelMapFunction(_tiny_model(), "serve",
                                 sharding_axes=("data", "fsdp"),
                                 policy=BucketPolicy(fixed_batch=6)),
                name="serve")
        hits = _by_rule(_shard_diags(_plan(build)), "shardcheck-partition")
        assert hits, "6 % (data x fsdp = 4) not flagged"
        assert hits[0].severity == Severity.ERROR
        assert hits[0].node == "serve"
        assert "batch 6" in hits[0].message
        assert "dataxfsdp" in hits[0].message

    def test_indivisible_param_dim_errors_and_names_buffer(self):
        from flink_tensorflow_tpu.analysis import SpecLayout

        schema = RecordSchema({"x": spec((6,), np.float32)})
        method = ModelMethod(
            name="serve", input_schema=schema, output_names=("y",),
            fn=lambda p, b: {"y": b["x"] @ p["w_in"]})
        model = Model("m", {"w_in": jnp.zeros((6, 10), np.float32)},
                      {"serve": method})

        def build(env):
            env.set_mesh(abstract_mesh({"fsdp": 4}))
            f = ModelMapFunction(model, "serve",
                                 policy=BucketPolicy(fixed_batch=4))
            f.spec_layout = SpecLayout(fsdp_axis="fsdp")
            env.from_collection([{}]).map(f, name="serve")

        hits = _by_rule(_shard_diags(_plan(build)), "shardcheck-partition")
        assert hits and hits[0].severity == Severity.ERROR
        assert "'w_in'" in hits[0].message
        assert "'fsdp'" in hits[0].message

    def test_divisible_batch_is_clean(self):
        def build(env):
            env.set_mesh(abstract_mesh({"data": 2, "fsdp": 2}))
            env.from_collection([{}]).map(
                ModelMapFunction(_tiny_model(), "serve",
                                 sharding_axes=("data", "fsdp"),
                                 policy=BucketPolicy(fixed_batch=8)),
                name="serve")
        assert _by_rule(_shard_diags(_plan(build)),
                        "shardcheck-partition") == []


# ---------------------------------------------------------------------------
# Seeded defect 3: implicit reshard across a device-resident chain.
# ---------------------------------------------------------------------------
class TestReshard:
    def _chained(self, up_out_axes):
        def build(env):
            # Device residency ON: the chained edge keeps batches in HBM,
            # which is exactly what a layout mismatch would defeat.
            env.configure(device_resident=True)
            env.from_collection([{}]).map(
                ModelMapFunction(_tiny_model(), "serve",
                                 sharding_axes=("data",),
                                 output_sharding_axes=up_out_axes),
                name="up", parallelism=1,
            ).map(
                ModelMapFunction(_tiny_model(), "serve",
                                 sharding_axes=("data",)),
                name="down", parallelism=1,
            )
        return _plan(build)

    def test_layout_mismatch_on_device_resident_chain_is_error(self):
        from flink_tensorflow_tpu.analysis import compute_chains

        env = self._chained(("model",))
        # Preconditions: the two model maps really did chain, with a
        # device-resident edge between them — the reshard then defeats
        # the h2d elision and must escalate to ERROR.
        diags = analyze(env.graph, config=env.config)
        ops = {t.id: t.operator_factory() for t in env.graph.transformations}
        plan = compute_chains(env.graph, operators=ops)
        assert plan.device_resident_edges, "fixture did not chain"
        hits = [d for d in diags if d.rule == "shardcheck-reshard"]
        assert hits, "layout mismatch across the chain not flagged"
        assert hits[0].severity == Severity.ERROR
        assert hits[0].edge == "up -> down"
        assert "('model',)" in hits[0].message
        assert "('data',)" in hits[0].message
        assert "h2d elision" in hits[0].message

    def test_matching_layouts_are_clean(self):
        env = self._chained(("data",))
        assert _by_rule(_shard_diags(env), "shardcheck-reshard") == []


# ---------------------------------------------------------------------------
# Seeded defect 4: plan HBM footprint exceeds the declared budget.
# ---------------------------------------------------------------------------
class TestHbmBudget:
    def test_over_budget_plan_errors_with_breakdown(self):
        def build(env):
            env.set_hbm_budget(64 * 1024)  # 64 KiB: nothing real fits
            env.from_collection([{}]).map(
                ModelMapFunction(_cache_model(), "decode",
                                 donate_inputs=True,
                                 policy=BucketPolicy(fixed_batch=1)),
                name="decode")
        hits = _by_rule(_shard_diags(_plan(build)), "shardcheck-hbm-budget")
        errors = [d for d in hits if d.severity == Severity.ERROR]
        assert errors, "over-budget plan not flagged"
        assert errors[0].node == "decode"
        assert "exceeds hbm_budget_bytes" in errors[0].message
        assert "activations=" in errors[0].message

    def test_generous_budget_is_info_only(self):
        def build(env):
            env.set_hbm_budget(16 * 1024**3)
            env.from_collection([{}]).map(
                ModelMapFunction(_cache_model(), "decode",
                                 donate_inputs=True,
                                 policy=BucketPolicy(fixed_batch=1)),
                name="decode")
        hits = _by_rule(_shard_diags(_plan(build)), "shardcheck-hbm-budget")
        assert hits, "budget declared but no HBM summary emitted"
        assert all(d.severity == Severity.INFO for d in hits)

    def test_no_budget_no_mesh_stays_silent(self):
        env = _plan(lambda env: env.from_collection([{}]).map(
            ModelMapFunction(_cache_model(), "decode", donate_inputs=True,
                             policy=BucketPolicy(fixed_batch=1)),
            name="decode"))
        assert _by_rule(_shard_diags(env), "shardcheck-hbm-budget") == []


# ---------------------------------------------------------------------------
# Seeded defect 5: unbounded compile-signature ladder.
# ---------------------------------------------------------------------------
class TestSignatures:
    def test_padding_buckets_off_warns_unbounded(self):
        from flink_tensorflow_tpu import serving

        model = _zoo_decoder()

        def build(env):
            serving.continuous_batching(
                env.from_collection([{}]).key_by(lambda r: 0),
                model,
                config=serving.ServingConfig(
                    max_active_seqs=2, capacity=16, token_budget=32,
                    padding_buckets=False),
                name="serve_llm", parallelism=1)
        hits = _by_rule(_shard_diags(_plan(build)), "shardcheck-signatures")
        warns = [d for d in hits if d.severity == Severity.WARN]
        assert warns, "unbounded signature set not flagged"
        assert warns[0].node == "serve_llm"
        assert "unbounded" in warns[0].message

    def test_bucketed_serving_is_bounded_info(self):
        from flink_tensorflow_tpu import serving

        model = _zoo_decoder()
        cfg = serving.ServingConfig(max_active_seqs=2, capacity=16,
                                    token_budget=32)

        def build(env):
            serving.continuous_batching(
                env.from_collection([{}]).key_by(lambda r: 0),
                model, config=cfg, name="serve_llm", parallelism=1)
        hits = _by_rule(_shard_diags(_plan(build)), "shardcheck-signatures")
        assert hits and all(d.severity == Severity.INFO for d in hits)
        # The count matches the config's own enumeration exactly.
        assert f"{len(cfg.compile_signatures())} signature(s)" \
            in hits[0].message

    def test_compile_signatures_enumeration(self):
        from flink_tensorflow_tpu.serving import ServingConfig

        cfg = ServingConfig(max_active_seqs=4, capacity=16, token_budget=32)
        sigs = cfg.compile_signatures()
        # admit buckets x prompt buckets prefills + one decode step.
        expect = (len(cfg.resolved_admit_buckets())
                  * len(cfg.resolved_prompt_buckets()) + 1)
        assert len(sigs) == expect
        assert ("decode", 4, 1) in sigs
        assert ServingConfig(padding_buckets=False).compile_signatures() \
            is None


# ---------------------------------------------------------------------------
# Healthy plans: clean end to end (and collectives stay INFO).
# ---------------------------------------------------------------------------
class TestHealthy:
    def test_healthy_sharded_plan_has_no_actionable_findings(self):
        def build(env):
            env.set_mesh(abstract_mesh({"data": 4, "tp": 2}))
            env.set_hbm_budget(16 * 1024**3)
            env.from_collection([{}]).map(
                ModelMapFunction(_cache_model(), "decode",
                                 donate_inputs=True,
                                 sharding_axes=("data",),
                                 policy=BucketPolicy(fixed_batch=8)),
                name="decode")
        diags = _shard_diags(_plan(build))
        assert [d for d in diags if d.severity >= Severity.WARN] == [], \
            "\n".join(d.format() for d in diags)

    def test_audit_json_report_shape(self):
        from flink_tensorflow_tpu.analysis import report_for_env

        def build(env):
            env.set_mesh(abstract_mesh({"data": 4, "tp": 2}))
            env.set_hbm_budget(16 * 1024**3)
            env.from_collection([{}]).map(
                ModelMapFunction(_cache_model(), "decode",
                                 donate_inputs=True,
                                 policy=BucketPolicy(fixed_batch=8)),
                name="decode")
        report = report_for_env(_plan(build), pipeline="fixture")
        assert report["mesh_axes"] == {"data": 4, "tp": 2}
        assert report["hbm_budget_bytes"] == 16 * 1024**3
        assert report["errors"] == 0
        (op,) = report["operators"]
        assert op["node"] == "decode" and op["kind"] == "model"
        assert op["hbm_per_device_bytes"]["params"] >= 0
        assert op["hbm_per_device_bytes"]["activations"] > 0
        assert all({"rule", "severity", "message"} <= set(f)
                   for f in report["findings"])

    def test_collective_census_counts_psum(self):
        """A method with an explicit psum under shard_map is counted
        from the jaxpr — the per-step ICI bill, statically."""
        from functools import partial

        from jax import shard_map
        from jax.sharding import AbstractMesh, PartitionSpec as P

        mesh = AbstractMesh((1,), ("data",))
        schema = RecordSchema({"x": spec((8,), np.float32)})

        def fn(params, batch):
            @partial(shard_map, mesh=mesh, in_specs=P("data"),
                     out_specs=P())
            def _mean(x):
                return jax.lax.psum(jnp.sum(x), "data")
            return {"y": jnp.broadcast_to(_mean(batch["x"]), (1,))}

        model = Model("coll", {}, {"serve": ModelMethod(
            name="serve", input_schema=schema, output_names=("y",),
            fn=fn)})

        def build(env):
            env.from_collection([{}]).map(
                ModelMapFunction(model, "serve",
                                 policy=BucketPolicy(fixed_batch=1)),
                name="coll")
        hits = _by_rule(_shard_diags(_plan(build)), "shardcheck-collectives")
        assert hits and hits[0].severity == Severity.INFO
        assert "psum" in hits[0].message


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))


# ---------------------------------------------------------------------------
# Paged KV economy (ISSUE 19): the audit prices the page pool and the
# block-table h2d, not the dense seats x capacity layout.
# ---------------------------------------------------------------------------
class TestPagedKvAudit:
    def _serving_op(self, **kw):
        from flink_tensorflow_tpu import serving
        from flink_tensorflow_tpu.analysis import report_for_env

        model = _zoo_decoder()
        cfg = serving.ServingConfig(max_active_seqs=2, capacity=16,
                                    token_budget=32, **kw)

        def build(env):
            serving.continuous_batching(
                env.from_collection([{}]).key_by(lambda r: 0),
                model, config=cfg, name="serve_llm", parallelism=1)
        report = report_for_env(_plan(build))
        (op,) = [o for o in report["operators"] if o["kind"] == "serving"]
        return op

    def test_paged_pool_budget_is_page_count_not_seats(self):
        dense = self._serving_op()
        paged = self._serving_op(paged_kv=True, page_tokens=8, hbm_pages=3)
        # 2 (K+V) * L * page_tokens * H * Dh * itemsize, zoo decoder
        # geometry: 1 layer, 2 heads, Dh=8, fp32.
        page_bytes = 2 * 1 * 8 * 2 * 8 * 4
        assert paged["hbm_per_device_bytes"]["kv_pool"] == 3 * page_bytes
        # The dense audit prices seats x capacity (= 4 pages worth) —
        # an undersized paged pool audits SMALLER than the dense pool;
        # the overflow is the host/disk tiers' problem, not HBM's.
        assert (dense["hbm_per_device_bytes"]["kv_pool"]
                == 2 * 2 * page_bytes)
        assert not paged["notes"], paged["notes"]

    def test_paged_step_h2d_rides_block_tables(self):
        dense = self._serving_op()
        paged = self._serving_op(paged_kv=True, page_tokens=8, hbm_pages=4)
        # Paged: [S] tokens + [S] lengths + [S, C/pt] int32 block
        # tables (no bool mask — liveness rides the sentinel page id).
        assert paged["predicted_step_h2d_bytes"] == 2 * 4 + 2 * 4 + 2 * 2 * 4
        assert dense["predicted_step_h2d_bytes"] == 2 * 4 + 2 * 4 + 2 * 1
