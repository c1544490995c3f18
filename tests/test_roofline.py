"""Roofline plane (ISSUE 17): CostTable pricing, the runtime probe
join, the seeded-drift matrix (inflated h2d -> drift finding; forced
recompile outside the predicted ladder -> compile-event finding;
healthy serving fixture -> zero drift), the report/CLI, the doctor
fold, cohort gauge policies, the inspector columns, trace-file
auto-discovery, and the per-step join overhead guard."""

import json
import sys
import time

import numpy as np
import pytest

import jax

sys.path.insert(0, ".")

from flink_tensorflow_tpu import StreamExecutionEnvironment, serving
from flink_tensorflow_tpu.analysis.costmodel import (
    CostEntry,
    CostTable,
    OperatorCost,
    cost_table_for_env,
    serving_signature,
)
from flink_tensorflow_tpu.metrics.roofline import (
    BOUND_COMPUTE,
    BOUND_HOST,
    BOUND_NAMES,
    BOUND_WIRE,
    DEVICE_SPECS,
    DeviceSpec,
    RooflineConfig,
    RooflinePlane,
    drift_findings,
    format_report,
    roofline_report,
    rows_from_snapshot,
    rows_from_trace,
)
from flink_tensorflow_tpu.metrics.roofline import main as roofline_main
from flink_tensorflow_tpu.models import get_model_def


# ---------------------------------------------------------------------------
# shared fixtures / helpers
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    mdef = get_model_def("char_transformer", vocab_size=48, embed_dim=32,
                         num_heads=2, num_layers=2, capacity=40)
    return mdef.to_model(mdef.init_params(jax.random.PRNGKey(0)))


def make_requests(n, seed=3):
    rng = np.random.RandomState(seed)
    return [
        serving.GenerateRequest(
            session_id=f"s{i}",
            prompt=rng.randint(1, 48, (int(rng.randint(4, 11)),)),
            max_new_tokens=int(rng.randint(4, 9)),
        )
        for i in range(n)
    ]


def serving_env(model, roofline=None, n=6):
    env = StreamExecutionEnvironment(parallelism=1)
    if roofline is not None:
        env.configure(roofline=roofline)
    serving.continuous_batching(
        env.from_collection(make_requests(n)).key_by(
            lambda r: r.session_id),
        model,
        config=serving.ServingConfig(max_active_seqs=4, token_budget=256,
                                     capacity=40),
        parallelism=1,
    ).sink_to_list()
    return env


class FakeGroup:
    """Minimal MetricGroup stand-in: captures the gauge callables so a
    test can render the probe's snapshot row exactly as published."""

    def __init__(self):
        self.gauges = {}

    def gauge(self, name, fn):
        self.gauges[name] = fn

    def read(self):
        return {name: fn() for name, fn in self.gauges.items()}


def make_table(predicted=("decode:4", "prefill:4x16"), h2d=72):
    return CostTable(ops=[OperatorCost(
        node="continuous_batching", kind="serving",
        entries=[
            CostEntry(unit="decode_step", signature="decode:4",
                      flops=1_000_000, hbm_bytes=400_000,
                      h2d_bytes=h2d, d2h_bytes=16),
            CostEntry(unit="prefill", signature="prefill:4x16",
                      flops=2_000_000, hbm_bytes=800_000,
                      h2d_bytes=288, d2h_bytes=16),
        ],
        predicted_signatures=tuple(predicted))])


def make_probe(metrics=None, table=None, flight=None, tracer=None, **cfg):
    plane = RooflinePlane(
        RooflineConfig(device="cpu-test",
                       cost_table=table if table is not None
                       else make_table(), **cfg),
        flight=flight, tracer=tracer)
    return plane.probe("continuous_batching", metrics=metrics)


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_signature_names_match_runtime(self):
        assert serving_signature("decode", 4, 1) == "decode:4"
        assert serving_signature("prefill", 2, 16) == "prefill:2x16"

    def test_serving_plan_priced(self, model):
        table = cost_table_for_env(serving_env(model))
        ops = [oc for oc in table.ops if oc.kind == "serving"]
        assert len(ops) == 1
        oc = ops[0]
        assert oc.predicted_signatures  # padding buckets on => a ladder
        step = oc.entry("decode_step")
        assert step is not None
        # Mirrors DecodeStepRunner: [S] tokens + [S] lengths int32,
        # [S] bool mask up; [S] next-tokens down (S = max_active_seqs).
        assert step.h2d_bytes == 4 * 4 + 4 * 4 + 4 * 1
        assert step.d2h_bytes == 4 * 4
        assert step.flops > 0 and step.hbm_bytes > 0
        assert any(e.unit == "prefill" for e in oc.entries)

    def test_json_roundtrip(self, model):
        table = cost_table_for_env(serving_env(model))
        back = CostTable.from_json(
            json.loads(json.dumps(table.to_json())))
        assert [oc.node for oc in back.ops] == [oc.node for oc in table.ops]
        assert back.ops[0].entries == table.ops[0].entries
        assert (back.ops[0].predicted_signatures
                == table.ops[0].predicted_signatures)
        with pytest.raises(ValueError):
            CostTable.from_json({"kind": "not-a-cost-table"})


# ---------------------------------------------------------------------------
# the probe join + the seeded-drift matrix
# ---------------------------------------------------------------------------


class TestProbe:
    def test_first_sight_is_compile_event_not_throughput(self):
        probe = make_probe()
        probe.observe("decode_step", 0.5, signature="decode:4")
        # The first call of a signature pays the XLA compile inside its
        # measured time: logged, excluded from attribution.
        assert probe.compile_events == 1
        assert probe.busy_s == 0.0 and probe.flops == 0
        probe.observe("decode_step", 0.5, signature="decode:4")
        assert probe.busy_s == pytest.approx(0.5)
        assert probe.flops == 1_000_000

    def test_warmup_compiles_suppressed_with_provenance(self):
        from flink_tensorflow_tpu.tracing import FlightRecorder, Tracer

        flight, tracer = FlightRecorder(), Tracer()
        probe = make_probe(flight=flight, tracer=tracer)
        probe.begin_warmup()
        probe.observe("prefill", 1.0, signature="prefill:4x16")
        probe.end_warmup()
        assert probe.compile_events == 1
        assert probe.unpredicted_compiles == 0
        assert probe.busy_s == 0.0
        ev = [e for e in flight.events() if e[1] == "jit_compile"]
        assert len(ev) == 1
        args = ev[0][5]
        assert args["trigger"] == "warmup" and args["predicted"] is True
        assert any(e[0] == "compile.events" for e in tracer.events())

    def test_seeded_h2d_drift_names_operator_and_pair(self):
        grp = FakeGroup()
        probe = make_probe(metrics=grp)
        probe.observe("decode_step", 0.01, signature="decode:4",
                      h2d_bytes=144)  # compile sighting, excluded
        for _ in range(4):
            # Measured h2d inflated 2x over the predicted 72 B/call.
            probe.observe("decode_step", 0.01, signature="decode:4",
                          h2d_bytes=144)
        assert probe.h2d_drift_frac() == pytest.approx(1.0)
        snapshot = {"continuous_batching.0": grp.read()}
        report = roofline_report(snapshot, device="cpu-test")
        drift = [f for f in report["findings"]
                 if f["rule"] == "roofline-drift"]
        assert len(drift) == 1
        f = drift[0]
        assert f["operator"] == "continuous_batching.0"
        assert f["measured_h2d_per_call"] == pytest.approx(144.0)
        assert f["predicted_h2d_per_call"] == pytest.approx(72.0)
        assert "144.0 B/call" in f["message"]
        assert "72.0 B/call" in f["message"]

    def test_forced_recompile_outside_ladder_is_a_finding(self):
        from flink_tensorflow_tpu.tracing import FlightRecorder

        grp, flight = FakeGroup(), FlightRecorder()
        probe = make_probe(metrics=grp, flight=flight)
        for _ in range(3):
            probe.observe("decode_step", 0.01, signature="decode:4",
                          h2d_bytes=72)
        # An unplanned shape reaches the device: a jit cache miss whose
        # signature is outside the predicted ladder.
        probe.observe("decode_step", 0.01, signature="decode:9",
                      h2d_bytes=72)
        assert probe.compile_events == 2
        assert probe.unpredicted_compiles == 1
        miss = [e[5] for e in flight.events() if e[1] == "jit_compile"
                and e[5]["signature"] == "decode:9"]
        assert miss and miss[0]["predicted"] is False
        report = roofline_report({"continuous_batching.0": grp.read()},
                                 device="cpu-test")
        recompile = [f for f in report["findings"]
                     if f["rule"] == "roofline-recompile"]
        assert len(recompile) == 1
        assert recompile[0]["operator"] == "continuous_batching.0"
        assert recompile[0]["unpredicted_compiles"] == 1

    def test_healthy_probe_zero_drift(self):
        grp = FakeGroup()
        probe = make_probe(metrics=grp)
        for _ in range(5):
            probe.observe("decode_step", 0.01, signature="decode:4",
                          h2d_bytes=72)
        assert probe.h2d_drift_frac() == 0.0
        report = roofline_report({"continuous_batching.0": grp.read()},
                                 device="cpu-test")
        assert report["findings"] == []
        (row,) = report["rows"]
        assert row["measured_h2d_per_call"] == row["predicted_h2d_per_call"]

    def test_bound_classification(self):
        # Host-bound: device busy a tiny fraction of wall time.
        probe = make_probe()
        probe.observe("decode_step", 1e-4, signature="decode:4")
        probe.observe("decode_step", 1e-4, signature="decode:4")
        time.sleep(0.05)
        assert probe.bound() == BOUND_HOST
        # Compute-bound: back-to-back busy time, flops fraction dominates
        # (cpu-test peaks make the fractions directly comparable).
        probe = make_probe()
        for _ in range(3):
            probe.observe("decode_step", 0.5, signature="decode:4")
        assert probe.bound() == BOUND_COMPUTE
        # Wire-bound: measured h2d rate above both utilization fractions.
        probe = make_probe()
        for _ in range(3):
            probe.observe("decode_step", 0.5, signature="decode:4",
                          h2d_bytes=10 ** 9)
        assert probe.bound() == BOUND_WIRE

    def test_flops_drift_past_physical_ceiling(self):
        rows = rows_from_snapshot({"op.0": {
            "roofline.busy_s": 1.0,
            "roofline.flops_per_s": 2e9,  # 200% of the cpu-test peak
            "roofline.hbm_bytes_per_s": 0.0,
        }}, DEVICE_SPECS["cpu-test"])
        findings = drift_findings(rows)
        assert [f["rule"] for f in findings] == ["roofline-flops-drift"]
        assert findings[0]["mfu_pct"] == pytest.approx(200.0)


# ---------------------------------------------------------------------------
# healthy end-to-end fixture: live gauges -> report -> doctor
# ---------------------------------------------------------------------------


class TestServingEndToEnd:
    @pytest.fixture(scope="class")
    def executed(self, model):
        env = serving_env(model,
                          roofline=RooflineConfig(device="cpu-test"))
        handle = env.execute_async("roofline-e2e")
        handle.wait(120)
        return env, handle.executor

    def test_auto_priced_table_reaches_executor(self, executed):
        env, executor = executed
        assert executor.roofline is not None
        assert executor.roofline.table is not None
        assert any(oc.kind == "serving"
                   for oc in executor.roofline.table.ops)

    def test_healthy_fixture_reports_zero_drift(self, executed):
        env, _ = executed
        snapshot = env.metric_registry.snapshot()
        report = roofline_report(snapshot, device="cpu-test")
        assert report["findings"] == []
        rows = report["rows"]
        assert rows and rows[0]["operator"] == "continuous_batching.0"
        row = rows[0]
        assert row["busy_s"] > 0
        assert row["compile_events"] >= 2  # prefill + decode signatures
        assert row["unpredicted_compiles"] == 0
        # Measured h2d bytes a call join the plan's prediction exactly.
        assert row["predicted_h2d_per_call"] > 0
        assert (row["measured_h2d_per_call"]
                == pytest.approx(row["predicted_h2d_per_call"]))
        assert row["h2d_drift_frac"] == 0.0
        assert row["bound"] in BOUND_NAMES
        text = format_report(report)
        assert "continuous_batching.0" in text
        assert "drift: none" in text

    def test_doctor_folds_roofline_report(self, executed):
        from flink_tensorflow_tpu.tracing.doctor import diagnose

        env, _ = executed
        report = roofline_report(env.metric_registry.snapshot(),
                                 device="cpu-test")
        diag = diagnose(roofline_report=report)
        assert any(f.startswith("roofline headroom:")
                   for f in diag["findings"])
        assert diag["roofline"] == diag["findings"][:len(diag["roofline"])]


# ---------------------------------------------------------------------------
# offline joins: trace evidence + the CLI
# ---------------------------------------------------------------------------


class TestReportAndCli:
    def test_rows_from_trace_joins_cost_table(self):
        spec = DEVICE_SPECS["cpu-test"]
        events = [
            ("continuous_batching.0", "decode.step", "X", 0.0, 0.5, {}),
            ("continuous_batching.0", "decode.prefill", "X", 0.5, 0.5,
             {"bucket": [4, 16]}),
            ("continuous_batching.0", "queue", "X", 0.0, 0.2, {}),
        ]
        rows = rows_from_trace(events, make_table(), spec)
        (row,) = rows
        assert row["busy_s"] == pytest.approx(1.0)
        # decode_step flops + prefill flops over the 1s trace window.
        assert row["flops_per_s"] == pytest.approx(3_000_000.0)
        assert row["measured_h2d_per_call"] == pytest.approx((72 + 288) / 2)

    def test_headroom_ranking_orders_rows(self):
        spec = DEVICE_SPECS["cpu-test"]
        report = roofline_report({
            "hot.0": {"roofline.busy_s": 10.0,
                      "roofline.flops_per_s": 1e7,
                      "roofline.hbm_bytes_per_s": 0.0},
            "cold.0": {"roofline.busy_s": 0.1,
                       "roofline.flops_per_s": 1e7,
                       "roofline.hbm_bytes_per_s": 0.0},
        }, device=spec)
        assert [r["operator"] for r in report["rows"]] == ["hot.0", "cold.0"]
        assert report["rows"][0]["headroom_s"] > report["rows"][1]["headroom_s"]

    def test_cli_exit_codes(self, tmp_path, capsys):
        drifted = {"continuous_batching.0": {
            "roofline.busy_s": 1.0, "roofline.flops_per_s": 1e6,
            "roofline.hbm_bytes_per_s": 1e6, "roofline.bound": 1,
            "roofline.measured_h2d_per_call": 144.0,
            "roofline.predicted_h2d_per_call": 72.0,
            "roofline.h2d_drift_frac": 1.0,
            "roofline.compile_events": 2,
            "roofline.unpredicted_compiles": 0,
        }}
        drift_path = tmp_path / "drift.json"
        drift_path.write_text(json.dumps(drifted))
        out_path = tmp_path / "report.json"
        assert roofline_main(["--snapshot", str(drift_path),
                              "--device", "cpu-test",
                              "--out", str(out_path)]) == 1
        report = json.loads(out_path.read_text())
        assert report["kind"] == "flink-tpu-roofline-report"
        assert [f["rule"] for f in report["findings"]] == ["roofline-drift"]
        clean = dict(drifted["continuous_batching.0"],
                     **{"roofline.measured_h2d_per_call": 72.0,
                        "roofline.h2d_drift_frac": 0.0})
        clean_path = tmp_path / "clean.json"
        clean_path.write_text(json.dumps({"op.0": clean}))
        assert roofline_main(["--snapshot", str(clean_path),
                              "--device", "cpu-test"]) == 0
        assert roofline_main(["--snapshot", str(tmp_path / "missing.json")
                              ]) == 2
        with pytest.raises(SystemExit):
            roofline_main([])  # no evidence at all -> parser.error
        capsys.readouterr()

    def test_doctor_cli_accepts_roofline_report(self, tmp_path, capsys):
        from flink_tensorflow_tpu.tracing.doctor import main as doctor_main

        report = roofline_report({"op.0": {
            "roofline.busy_s": 1.0, "roofline.flops_per_s": 1e6,
            "roofline.hbm_bytes_per_s": 0.0,
        }}, device="cpu-test")
        path = tmp_path / "roofline.json"
        path.write_text(json.dumps(report))
        assert doctor_main(["--roofline", str(path)]) == 0
        assert "roofline headroom" in capsys.readouterr().out

    def test_unknown_device_preset_raises_with_choices(self):
        with pytest.raises(ValueError, match="cpu-test"):
            DeviceSpec.resolve("v99")
        with pytest.raises(ValueError):
            RooflineConfig(device="v99").validate()
        with pytest.raises(ValueError):
            RooflineConfig(h2d_tolerance=0.0).validate()


# ---------------------------------------------------------------------------
# cohort gauge policies + inspector columns
# ---------------------------------------------------------------------------


class TestCohortPolicy:
    def test_roofline_gauge_policies(self):
        from flink_tensorflow_tpu.metrics.cohort import gauge_policy

        # Rates and accumulated seconds sum to the cohort's aggregate
        # device bill; utilization/drift keep the hottest process; the
        # bound code is an identity, never a numeric reduction.
        assert gauge_policy("roofline.busy_s") == "sum"
        assert gauge_policy("roofline.flops_per_s") == "sum"
        assert gauge_policy("roofline.hbm_bytes_per_s") == "sum"
        assert gauge_policy("roofline.compile_events") == "sum"
        assert gauge_policy("roofline.unpredicted_compiles") == "sum"
        assert gauge_policy("roofline.mfu_pct") == "max"
        assert gauge_policy("roofline.membw_pct") == "max"
        assert gauge_policy("roofline.h2d_drift_frac") == "max"
        assert gauge_policy("roofline.measured_h2d_per_call") == "max"
        assert gauge_policy("roofline.predicted_h2d_per_call") == "max"
        assert gauge_policy("roofline.bound") == "last"

    def test_merge_applies_roofline_policies(self):
        from flink_tensorflow_tpu.metrics.cohort import merge_states

        def state(busy, mfu, bound, compiles):
            return {"op.0": {
                "roofline.busy_s": ("gauge", busy),
                "roofline.mfu_pct": ("gauge", mfu),
                "roofline.bound": ("gauge", bound),
                "roofline.unpredicted_compiles": ("gauge", compiles),
            }}

        merged = merge_states([state(1.0, 10.0, 1, 0),
                               state(2.0, 30.0, 2, 1)])["op.0"]
        assert merged["roofline.busy_s"] == ("gauge", 3.0)
        assert merged["roofline.mfu_pct"] == ("gauge", 30.0)
        assert merged["roofline.bound"] == ("gauge", 2)
        assert merged["roofline.unpredicted_compiles"] == ("gauge", 1)

    def test_health_rules_cover_roofline(self):
        from flink_tensorflow_tpu.metrics.health import default_rules

        names = {r.id for r in default_rules()}
        assert {"mfu-collapse", "roofline-drift",
                "roofline-recompile"} <= names


class TestInspectorColumns:
    SNAP = {"model.0": {
        "records_in": {"count": 10, "window_rate": 5.0},
        "records_out": {"count": 10, "window_rate": 5.0},
        "roofline.mfu_pct": 12.5,
        "roofline.bound": 2,
    }}

    def test_live_rows_carry_mfu_and_bound(self):
        from flink_tensorflow_tpu.metrics.inspector import (
            build_live_rows,
            format_live_table,
        )

        rows = build_live_rows(self.SNAP)
        (row,) = rows
        assert row["mfu_pct"] == pytest.approx(12.5)
        assert row["bound"] == "memory"
        table = format_live_table(rows)
        assert "mfu%" in table and "memory" in table

    def test_columns_absent_without_roofline(self):
        from flink_tensorflow_tpu.metrics.inspector import (
            build_live_rows,
            format_live_table,
        )

        snap = {"model.0": {"records_in": {"count": 1},
                            "records_out": {}}}
        table = format_live_table(build_live_rows(snap))
        assert "mfu%" not in table


# ---------------------------------------------------------------------------
# trace-file auto-discovery (flink-tpu-trace --cohort / --from-file)
# ---------------------------------------------------------------------------


class TestExpandProcFiles:
    def test_bare_prefix_discovers_in_process_order(self, tmp_path):
        from flink_tensorflow_tpu.tracing.cli import expand_proc_files

        for k in (0, 2, 10):
            (tmp_path / f"t.proc{k}.json").write_text("{}")
        base = str(tmp_path / "t")
        files = expand_proc_files([base])
        # Numeric process order — proc10 after proc2, not before.
        assert [f.rsplit("/", 1)[-1] for f in files] == [
            "t.proc0.json", "t.proc2.json", "t.proc10.json"]

    def test_glob_and_passthrough_and_miss(self, tmp_path):
        from flink_tensorflow_tpu.tracing.cli import expand_proc_files

        real = tmp_path / "solo.json"
        real.write_text("{}")
        (tmp_path / "c.proc0.json").write_text("{}")
        (tmp_path / "c.proc1.json").write_text("{}")
        assert expand_proc_files([str(real)]) == [str(real)]
        assert len(expand_proc_files([str(tmp_path / "c.proc*.json")])) == 2
        # No match: the argument passes through for the caller's error.
        assert expand_proc_files(["nope"]) == ["nope"]


# ---------------------------------------------------------------------------
# overhead guard: the per-step join priced next to span/flight records
# ---------------------------------------------------------------------------


class TestOverheadGuard:
    def test_observe_priced_next_to_span_record(self):
        from flink_tensorflow_tpu.tracing import Tracer

        probe = make_probe()
        probe.observe("decode_step", 1e-6, signature="decode:4",
                      h2d_bytes=72)  # compile sighting
        samples = 20000
        t0 = time.perf_counter()
        for _ in range(samples):
            probe.observe("decode_step", 1e-6, signature="decode:4",
                          h2d_bytes=72)
        observe_ns = (time.perf_counter() - t0) / samples * 1e9

        tracer = Tracer()
        t0 = time.perf_counter()
        for _ in range(samples):
            tracer.span("bench.0", "overhead_probe", 0.0, 1.0)
        span_ns = (time.perf_counter() - t0) / samples * 1e9

        # The join is a set lookup + entry lookup + integer adds: it
        # must stay within the same order as one span-ring append
        # (generous x25 bound absorbs CI scheduler noise), and in any
        # case far below per-step work (decode steps are >= ~100us).
        assert observe_ns < max(20_000.0, 25.0 * span_ns), (
            f"observe {observe_ns:.0f}ns vs span {span_ns:.0f}ns")

    def test_plane_off_is_none(self, model):
        env = serving_env(model)  # no JobConfig.roofline
        handle = env.execute_async("roofline-off")
        handle.wait(120)
        assert handle.executor.roofline is None
        assert not any("roofline" in k
                       for k in env.metric_registry.report())


# ---------------------------------------------------------------------------
# cache tier moves (ISSUE 19 satellite): priced, attributed, no compiles
# ---------------------------------------------------------------------------


def make_cache_table():
    """A table pricing tier moves only — one paged and one dense row
    (the byte values are 2 * L * tokens * H * Dh * 4 for the shared
    char_transformer geometry: page_tokens=8 -> 4096 B/page)."""
    return CostTable(ops=[OperatorCost(
        node="continuous_batching", kind="serving",
        entries=[
            CostEntry(unit="cache_move", signature="cache:pages:2",
                      h2d_bytes=8192, d2h_bytes=8192),
            CostEntry(unit="cache_move", signature="cache:block",
                      h2d_bytes=20480, d2h_bytes=20480),
        ])])


class TestCacheMoveAttribution:
    """observe_transfer closes the PR-17 "non-runner h2d attribution"
    deferral: tier moves accrue busy time and drift pairs, but they are
    data motion, not executables — no compile event, no first-sight
    suppression."""

    def test_no_compile_event_and_first_call_counts(self):
        probe = make_probe(table=make_cache_table())
        probe.observe_transfer("cache_move", 0.01,
                               signature="cache:pages:2", d2h_bytes=8192)
        # The FIRST spill pays the same wire time as the hundredth:
        # counted immediately, and never logged as a jit cache miss.
        assert probe.compile_events == 0
        assert probe.busy_s == pytest.approx(0.01)
        assert probe.h2d_paired_calls == 1
        assert probe.h2d_drift_frac() == 0.0

    def test_warmup_suppresses_transfers(self):
        probe = make_probe(table=make_cache_table())
        probe.begin_warmup()
        probe.observe_transfer("cache_move", 0.5,
                               signature="cache:block", h2d_bytes=20480)
        probe.end_warmup()
        assert probe.busy_s == 0.0 and probe.h2d_bytes == 0

    def test_inflated_transfer_raises_drift_finding(self):
        grp = FakeGroup()
        probe = make_probe(metrics=grp, table=make_cache_table())
        for _ in range(3):
            # A revival moving 2x the priced bytes (e.g. an fp32 spill
            # of a cache the plan priced at bf16).
            probe.observe_transfer("cache_move", 0.01,
                                   signature="cache:pages:2",
                                   h2d_bytes=16384)
        assert probe.h2d_drift_frac() == pytest.approx(1.0)
        report = roofline_report({"continuous_batching.0": grp.read()},
                                 device="cpu-test")
        drift = [f for f in report["findings"]
                 if f["rule"] == "roofline-drift"]
        assert len(drift) == 1
        assert drift[0]["measured_h2d_per_call"] == pytest.approx(16384.0)
        assert drift[0]["predicted_h2d_per_call"] == pytest.approx(8192.0)

    def test_transfer_only_probe_ranks_wire_bound(self):
        probe = make_probe(table=make_cache_table())
        for _ in range(3):
            probe.observe_transfer("cache_move", 0.5,
                                   signature="cache:pages:2",
                                   d2h_bytes=8192)
        # No compute entry ever joined (flops == hbm == 0) — pure cache
        # churn still classifies instead of dropping to "none".
        assert probe.flops == 0 and probe.hbm_bytes == 0
        assert probe.bound() == BOUND_WIRE

    def test_rows_from_trace_joins_cache_spans(self):
        spec = DEVICE_SPECS["cpu-test"]
        events = [
            # A paged demotion (d2h) and a dense warm-tier insert (h2d),
            # exactly as the runners emit them.
            ("continuous_batching.0", "cache.d2h", "X", 0.0, 0.1,
             {"pages": 2, "bytes": 8192}),
            ("continuous_batching.0", "cache.h2d", "X", 0.2, 0.1,
             {"slot": 0, "bytes": 20480}),
            ("continuous_batching.0", "queue", "X", 0.0, 0.2, {}),
        ]
        rows = rows_from_trace(events, make_cache_table(), spec)
        (row,) = rows
        assert row["busy_s"] == pytest.approx(0.2)
        assert row["measured_h2d_per_call"] == pytest.approx(
            (8192 + 20480) / 2)
        assert row["predicted_h2d_per_call"] == pytest.approx(
            (8192 + 20480) / 2)
        assert row["h2d_drift_frac"] == 0.0

    def test_paged_plan_prices_pages_tables_and_moves(self, model):
        from flink_tensorflow_tpu.analysis.costmodel import (
            cost_table_for_env,
        )

        env = StreamExecutionEnvironment(parallelism=1)
        serving.continuous_batching(
            env.from_collection(make_requests(6)).key_by(
                lambda r: r.session_id),
            model,
            config=serving.ServingConfig(
                max_active_seqs=4, token_budget=256, capacity=40,
                paged_kv=True, page_tokens=8),
            parallelism=1,
        ).sink_to_list()
        table = cost_table_for_env(env)
        (oc,) = [o for o in table.ops if o.kind == "serving"]
        assert not oc.notes
        # Paged decode h2d: tokens + lengths + the [S, C/pt] block
        # tables (no dense bool mask — liveness rides the sentinel).
        step = oc.entry("decode_step")
        assert step.h2d_bytes == 4 * 4 + 4 * 4 + 4 * 5 * 4
        assert step.flops > 0
        # Prefill rides the [b, C/pt] scatter table instead of the [b]
        # slot vector.
        pre = oc.entry("prefill", serving_signature("prefill", 4, 8))
        assert pre.h2d_bytes == 4 * 8 * 4 + 4 * 4 + 4 * 5 * 4
        # One cache_move entry per possible page count, priced at
        # 2 (K+V) * L * page_tokens * H * Dh * itemsize each way.
        moves = [e for e in oc.entries if e.unit == "cache_move"]
        assert [e.signature for e in moves] == [
            f"cache:pages:{n}" for n in range(1, 6)]
        page_bytes = 2 * 2 * 8 * 2 * 16 * 4
        assert all(e.h2d_bytes == e.d2h_bytes == (i + 1) * page_bytes
                   for i, e in enumerate(moves))
        # Transfers are not executables: never in the compile ladder.
        assert not any(s.startswith("cache")
                       for s in oc.predicted_signatures)

    def test_tiered_run_attributes_transfers_live(self, model, tmp_path):
        """End-to-end: an oversubscribed paged run with tiering forces
        demote/revive traffic; the probe must absorb it with zero
        unpredicted compiles, non-zero measured transfer bytes, and no
        drift (the cache_move prices match the real page geometry)."""
        rng = np.random.RandomState(7)
        reqs = [serving.GenerateRequest(
            session_id=f"s{i}",
            prompt=rng.randint(1, 48, (int(rng.randint(4, 10)),)),
            max_new_tokens=8) for i in range(24)]
        env = StreamExecutionEnvironment(parallelism=1)
        env.configure(roofline=RooflineConfig(device="cpu-test"))
        serving.continuous_batching(
            env.from_collection(reqs).key_by(lambda r: r.session_id),
            model,
            config=serving.ServingConfig(
                max_active_seqs=4, token_budget=40, capacity=40,
                paged_kv=True, page_tokens=8, hbm_pages=9,
                prefix_sharing=False,
                tier_high_watermark=0.6, tier_low_watermark=0.3,
                host_cache_sessions=0, spill_dir=str(tmp_path)),
            parallelism=1,
        ).sink_to_list()
        handle = env.execute_async("roofline-kveconomy")
        handle.wait(120)
        m = env.metric_registry.report()
        assert m["continuous_batching.0.kv_tier_moves"] >= 2
        report = roofline_report(env.metric_registry.snapshot(),
                                 device="cpu-test")
        row = [r for r in report["rows"]
               if r["operator"] == "continuous_batching.0"][0]
        assert row["unpredicted_compiles"] == 0
        assert row["measured_h2d_per_call"] > 0
        # Demote d2h and revive h2d both priced exactly: no drift.
        assert row["h2d_drift_frac"] == 0.0
