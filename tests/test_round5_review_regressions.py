"""Round-5 regression pins (VERDICT r4 #1/#2/#3/#4/#6 + ADVICE r4).

Each test pins a defect found in the round-5 adversarial sweep over the
round-4 surface, or a contract the final round's auditability depends
on:

1. Round 4's bench record was archived with ``parsed: null`` — the single
   full-detail JSON line outgrew the driver's ~2KB stdout tail capture,
   so the round's headline driver-run numbers were LOST.  bench.py now
   prints a compact scoreboard as the FINAL stdout line (full detail to
   earlier lines + BENCH_full.json); the scoreboard must stay under the
   tail window whatever fields future edits add — and the same contract
   covers ``--mfu-attribution`` and write-failure honesty (a stale
   artifact is never advertised as current).
2. The open-loop fetch serialized a full device round trip per
   window AFTER readiness, and ``is_ready`` is not a completion
   guarantee, so readiness-gated fetches could block arbitrarily.  The
   runner now fetches on a dedicated background thread (no readiness
   consulted — a blocking fetch IS completion), defers ring releases
   to the collecting thread (the TensorRing is SPSC), wakes the
   subtask loop on completion (InputGate.wake), and a completion wake
   must NOT flush the async map's partial micro-batch.
3. The per-batch stage stamp was ONE dict shared by every
   record of the batch (VERDICT r4 weak #5): mutating one record's
   stamps mutated its siblings'.
4. MFU attribution (VERDICT r4 #3): the trace parser aggregates only
   device-side events inside the module window, classifies categories
   by roofline, resolves chip tables by longest prefix, and the
   2x-batch experiment verdict survives zero-valued measurements.
5. Workload physical consistency (VERDICT r4 #4): secondary workload
   lines carry wire brackets/ceilings/efficiency/drift/bottleneck with
   flagship semantics (no silent >1.0 efficiency, no NaN emission).
6. ADVICE r4: the durability gate's fast-fail connect cap arms only
   after the first cohort-wide exchange proves every peer up.
"""

import json
import threading
import time

import numpy as np

import bench


def _flagship_out():
    """A full-detail Inception output dict with every round-4 field
    populated at realistic magnitudes (shapes from the round-3/4
    records), so the size test measures the real serialized widths."""
    sweep = [
        {"probe_batch": b, "per_record_us": 161.61, "records_per_sec": 6187.7,
         "flops_per_record": 24061773527.0, "flops_source": "xla_cost_analysis",
         "achieved_tflops": 79.43, "device_kind": "TPU v5 lite",
         "chip_peak_bf16_tflops": 197.0, "mfu_pct": 40.32}
        for b in (256, 512, 1024)
    ]
    return {
        "metric": "inception_v3_streaming_inference_records_per_sec_per_chip",
        "value": 49.19, "unit": "records/s/chip", "vs_baseline": 0.328,
        "p50_record_latency_ms": 2862.426, "p99_record_latency_ms": 4880.896,
        "records": 2048, "batch": 128, "transfer_lanes": 6,
        "rps_first_half": 48.3, "rps_second_half": 51.08, "chips": 1,
        "platform": "tpu",
        "decomposition_per_batch": {
            "host_assemble_s_p50": 0.05922, "h2d_bytes": 34330030,
            "h2d_plus_dispatch_s_p50": 2.38717, "steady_state_s": 2.6022,
            "device_compute_s": 0.02069, "fixed_call_roundtrip_s": 0.09334,
        },
        "wire": {"sustained_mb_s": 4.71, "burst_mb_s": 443.2,
                 "bucket_mb": 134.0, "record_bytes": 268203,
                 "wire_ceiling_records_per_sec": 17.6},
        "wire_pre": {"sustained_mb_s": 5.39,
                     "wire_ceiling_records_per_sec": 20.1},
        "wire_ceiling_records_per_sec_range": [17.6, 20.1],
        "device_compute": sweep[1],
        "device_compute_sweep": sweep,
        "conv_dtypes": ["bf16"],
        "device_compute_train_resnet50": {
            "workload": "resnet50_train_step", "probe_batch": 128,
            "image_size": 224, "steps_per_sec": 20.876,
            "records_per_sec": 2672.1, "flops_per_step": 3060412973056.0,
            "flops_source": "xla_cost_analysis", "achieved_tflops": 63.89,
            "chip_peak_bf16_tflops": 197.0, "mfu_pct": 32.43,
        },
        "bottleneck": "host->device transfer bandwidth",
        "pipeline_efficiency_vs_wire_ceiling": 0.942,
        "pipeline_efficiency_range": [0.942, 1.04],
        "ceiling_drift": None,
        "ceiling_drift_code": None,
        "projected_records_per_sec_host_attached_chip": 6187.7,
        "projected_vs_baseline": 41.3,
        "baseline_note": "reference published no numbers (BASELINE.json "
                         "published={}); vs_baseline uses a 150 rec/s/GPU estimate",
        "open_loop": {
            "arrival_process": "poisson", "offered_rate_rps": 8.92,
            "rate_fraction_of_capacity": 0.5, "service_capacity_rps": 21.33,
            "capacity_cap_rps": 17.84, "service_batch": 16,
            "trigger": "adaptive_latency_ewma+service_reserve",
            "result_collection": "ready-poll every 15ms",
            "latency_budget_requested_ms": 300.0, "latency_budget_ms": 300.0,
            "budget_auto_raised": False, "latency_floor_ms": 158.1,
            "floor_components_ms": {"fixed_call_roundtrip": 93.3,
                                    "one_record_wire": 49.8,
                                    "collection_poll": 15.0},
            "records": 512, "steady_state_samples": 485,
            "warmup_contaminated": False, "achieved_rate_rps": 8.87,
            "saturated": False,
            "wire_sustained_mb_s_bracket": [5.39, 4.71],
            "offered_mb_s": 2.39, "p50_latency_ms": 814.9,
            "p99_latency_ms": 1891.2, "p50_over_floor": 5.15,
            "median_fired_window": 3,
            "latency_floor_at_operating_point_ms": 403.4,
            "p50_over_operating_floor": 2.02, "budget_met": False,
            "per_sample_decomposition_ms": {
                k: {"p50_ms": 100.0, "p99_ms": 1000.0}
                for k in ("queue_wait", "trigger_hold", "lane_wait",
                          "h2d_dispatch", "ready_wait", "fetch", "emit")
            },
        },
    }


def _secondary_outs():
    return [
        {"metric": "mnist_lenet_windowed_records_per_sec", "value": 1888.3,
         "unit": "records/s", "vs_baseline": None},
        {"metric": "bilstm_dynamic_batching_records_per_sec", "value": 555.4,
         "unit": "records/s", "vs_baseline": None},
        {"metric": "widedeep_online_training_steps_per_sec", "value": 20.1,
         "unit": "steps/s", "vs_baseline": None},
        {"metric": "resnet50_dp_training_records_per_sec_per_chip",
         "value": 72.7, "unit": "records/s/chip", "vs_baseline": None},
    ]


class TestScoreboardLine:
    """VERDICT r4 #1: the final stdout line must fit the driver tail."""

    def test_fits_tail_window_with_all_workloads(self):
        sb = bench._fit_scoreboard(
            bench._scoreboard([_flagship_out(), *_secondary_outs()]))
        line = json.dumps(sb, allow_nan=False)
        assert len(line.encode()) <= bench.SCOREBOARD_MAX_BYTES
        # Strict RFC-8259 round trip.
        back = json.loads(line)
        assert back["scoreboard"] is True

    def test_carries_every_headline_field(self):
        sb = bench._fit_scoreboard(
            bench._scoreboard([_flagship_out(), *_secondary_outs()]))
        # Headline rate + latency.
        assert sb["value"] == 49.19 and sb["unit"] == "records/s/chip"
        assert sb["p50_ms"] == 2862.426 and sb["p99_ms"] == 4880.896
        # Wire bracket, efficiency, drift verdict.
        assert sb["wire_mb_s_bracket"] == [5.39, 4.71]
        assert sb["eff_vs_wire_ceiling"] == 0.942
        assert sb["ceiling_drift"] is None
        # MFU characterization: forward sweep + train step.
        assert [b for b, _ in sb["mfu_sweep_batch_pct"]] == [256, 512, 1024]
        assert sb["resnet_train"]["mfu_pct"] == 32.43
        # Open-loop digest: p50, both floors, floor-multiple, verdicts.
        ol = sb["open_loop"]
        assert ol["p50_ms"] == 814.9 and ol["floor_ms"] == 158.1
        assert ol["op_floor_ms"] == 403.4
        assert ol["p50_over_op_floor"] == 2.02
        assert ol["budget_met"] is False and ol["saturated"] is False
        # One row per secondary workload.
        assert set(sb["workloads"]) == {"mnist", "bilstm", "widedeep",
                                        "resnet50"}
        assert sb["full_detail"] == "BENCH_full.json"

    def test_drift_verdict_copied_from_machine_code(self):
        # The digest copies the machine-readable ceiling_drift_code the
        # source emits next to the prose — rewording the prose can never
        # flip the severity the driver-parsed line reports.
        out = _flagship_out()
        out["ceiling_drift"] = "some future rewording of the severe message"
        out["ceiling_drift_code"] = "unreliable"
        assert bench._scoreboard([out])["ceiling_drift"] == "unreliable"
        out["ceiling_drift_code"] = None
        assert bench._scoreboard([out])["ceiling_drift"] is None

    def test_drift_prose_fallback_for_pre_r5_dicts(self):
        out = _flagship_out()
        del out["ceiling_drift_code"]
        out["ceiling_drift"] = ("measured pipeline rate exceeds BOTH "
                                "bracketing wire probes ... efficiency is "
                                "unreliable for this run")
        assert bench._scoreboard([out])["ceiling_drift"] == "unreliable"
        out["ceiling_drift"] = ("pipeline rate marginally above the upper "
                                "bracket (<=5%) ...")
        assert bench._scoreboard([out])["ceiling_drift"] == "marginal<=5%"

    def test_fit_drops_optional_blocks_never_headline(self):
        sb = bench._scoreboard([_flagship_out(), *_secondary_outs()])
        sb["workloads"]["padded"] = ["x" * 4000, "records/s"]
        fitted = bench._fit_scoreboard(sb)
        line = json.dumps(fitted, allow_nan=False)
        assert len(line.encode()) <= bench.SCOREBOARD_MAX_BYTES
        # The oversized block went; the headline and open-loop stayed.
        assert "workloads" not in fitted
        assert fitted["value"] == 49.19
        assert fitted["open_loop"]["p50_ms"] == 814.9

    def test_main_prints_scoreboard_last_and_writes_full(self, tmp_path,
                                                         monkeypatch, capsys):
        """End-to-end emission contract without real compute: stub the
        workload table, run main(), assert the FINAL stdout line is the
        compact scoreboard and the full detail landed in the file."""
        flag = _flagship_out()
        monkeypatch.setattr(bench, "WORKLOADS",
                            {"inception": lambda args: flag})
        monkeypatch.setattr(bench, "BENCH_FULL_PATH",
                            str(tmp_path / "BENCH_full.json"))
        bench.main(["--workload", "inception"])
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 2  # full-detail line, then the scoreboard
        full_line = json.loads(lines[0])
        assert full_line["metric"] == flag["metric"]
        last = lines[-1]
        assert len(last.encode()) <= bench.SCOREBOARD_MAX_BYTES
        sb = json.loads(last)
        assert sb["scoreboard"] is True and sb["value"] == flag["value"]
        on_disk = json.loads((tmp_path / "BENCH_full.json").read_text())
        assert on_disk["workloads"][0]["metric"] == flag["metric"]

    def test_full_detail_pointer_null_when_write_fails(self, tmp_path,
                                                       monkeypatch, capsys):
        """A stale BENCH_full.json from a previous run must not be
        advertised as this run's detail: on write failure the scoreboard
        pointer is null."""
        monkeypatch.setattr(bench, "WORKLOADS",
                            {"inception": lambda args: _flagship_out()})
        # A path whose parent does not exist fails the open with an
        # OSError even when running as root (chmod-based denial doesn't).
        monkeypatch.setattr(bench, "BENCH_FULL_PATH",
                            str(tmp_path / "missing-dir" / "BENCH_full.json"))
        bench.main(["--workload", "inception"])
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        sb = json.loads(lines[-1])
        assert sb["scoreboard"] is True
        assert sb["full_detail"] is None


def _synthetic_trace():
    """A chrome-trace dict shaped like the jax profiler's device export
    (field shapes verified against a real v5e capture, 2026-07-30)."""
    def op(name, offset, dur, cat, flops=0, nbytes=0):
        return {"ph": "X", "pid": 3, "name": name, "dur": dur / 1e6,
                "args": {"device_offset_ps": str(offset),
                         "device_duration_ps": str(dur),
                         "hlo_category": cat,
                         "model_flops": str(flops),
                         "raw_bytes_accessed": str(nbytes)}}

    module = {"ph": "X", "pid": 3, "name": "jit_tstep(123)",
              "args": {"device_offset_ps": "1000000",
                       "device_duration_ps": "100000000"}}  # 100us window
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 701, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        module,
        # 60us of conv at ~160 TFLOP/s (MXU-bound on a 197-peak chip).
        op("conv_fusion.1", 2_000_000, 60_000_000, "convolution fusion",
           flops=9_600_000_000, nbytes=1_000_000),
        # 30us of loop fusion moving 20MB (≈667 GB/s on 819 -> bw-bound).
        op("loop_fusion.1", 62_000_000, 30_000_000, "loop fusion",
           flops=100_000_000, nbytes=20_000_000),
        # An op OUTSIDE the last module window: must be excluded.
        op("conv_fusion.0", 999_000_000, 50_000_000, "convolution fusion",
           flops=1, nbytes=1),
        # Host-side event (wrong pid): must be ignored entirely.
        {"ph": "X", "pid": 701, "name": "some_host_thing", "args": {}},
    ]
    return {"traceEvents": events}


class TestMfuAttributionParser:
    """VERDICT r4 #3: the per-fusion attribution must come from
    device-side timing, bucketed by HLO category with roofline verdicts."""

    def test_aggregates_categories_inside_module_window(self):
        out = bench._parse_xla_trace(_synthetic_trace(), "tstep",
                                     peak_tflops=197.0, hbm_gbps=819.0)
        assert out["module"] == "jit_tstep(123)"
        assert out["device_time_ms"] == 0.1
        by = {r["category"]: r for r in out["by_category"]}
        conv = by["convolution fusion"]
        # Only the in-window conv op: 9.6 GFLOP / 60us = 160 TFLOP/s.
        assert conv["ops"] == 1
        assert conv["achieved_tflops"] == 160.0
        assert conv["mfu_pct"] == 81.2
        assert conv["time_share_pct"] == 60.0
        assert conv["verdict"] == "MXU-bound"
        lf = by["loop fusion"]
        assert lf["achieved_gb_s"] == 666.7
        assert lf["verdict"] == "HBM-bandwidth-bound"
        # Module roll-up: 9.7 GFLOP over 100us = 97 TFLOP/s = 49.2% MFU.
        assert out["module_mfu_pct"] == 49.2
        assert out["accounted_time_pct"] == 90.0

    def test_under_utilized_verdict_for_low_intensity_flops(self):
        tr = _synthetic_trace()
        # Shrink the conv's FLOPs: low TFLOP/s AND low GB/s -> small-tile.
        tr["traceEvents"][3]["args"]["model_flops"] = "600000000"
        out = bench._parse_xla_trace(tr, "tstep",
                                     peak_tflops=197.0, hbm_gbps=819.0)
        conv = {r["category"]: r for r in out["by_category"]}[
            "convolution fusion"]
        assert conv["verdict"].startswith("under-utilized")

    def test_graceful_without_device_events(self):
        out = bench._parse_xla_trace(
            {"traceEvents": [{"ph": "M", "pid": 1, "name": "process_name",
                              "args": {"name": "/host:CPU"}}]}, "tstep")
        assert "attribution_unavailable" in out

    def test_graceful_without_module_event(self):
        tr = _synthetic_trace()
        out = bench._parse_xla_trace(tr, "no_such_module",
                                     peak_tflops=197.0, hbm_gbps=819.0)
        assert "attribution_unavailable" in out


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

    def test_first_commit_gate_keeps_full_connect_window(self, monkeypatch):
        """ADVICE r4: the durability gate's 5s fast-fail connect cap must
        not apply to the FIRST cohort-wide exchange — a peer's shuffle
        server can legitimately still be in its cold-compile window, and
        a spuriously failed gate withholds the first 2PC commit.  Once an
        announce reached every peer, later (re)connects fail fast."""
        import threading as _threading

        from flink_tensorflow_tpu.core import distributed as dist_mod
        from flink_tensorflow_tpu.core.distributed import (
            DistributedConfig, DistributedExecutor)

        seen_timeouts = []

        class _StubWriter:
            def __init__(self, host, port, task, sender, channel,
                         connect_timeout_s, epoch=0):
                seen_timeouts.append(connect_timeout_s)

            def write(self, payload):
                pass

        monkeypatch.setattr(dist_mod, "RemoteChannelWriter", _StubWriter)
        ex = DistributedExecutor.__new__(DistributedExecutor)
        ex.dist = DistributedConfig(
            process_index=0, num_processes=2,
            peers=("127.0.0.1:1", "127.0.0.1:2"),
            connect_timeout_s=60.0).validate()
        ex.cancelled = _threading.Event()
        ex._control_writers = {}
        ex._control_writers_lock = _threading.Lock()
        ex._participants = {0, 1}
        ex._durable_cv = _threading.Condition()
        ex._durable_acks = {1: {1}, 2: {1}}  # peer already announced
        ex.checkpoint_timeout_s = 5.0
        ex._gate_warmed = False

        assert ex._global_commit_gate(1) is True
        assert seen_timeouts == [60.0]  # first gate: full window
        assert ex._gate_warmed is True
        ex._control_writers.clear()  # simulate a dropped cached writer
        assert ex._global_commit_gate(2) is True
        assert seen_timeouts == [60.0, 5.0]  # warmed: fast-fail cap

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
        f = ModelMapFunction(model, micro_batch=8, idle_flush_s=0.5,
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

    def test_mfu_mode_prints_compact_digest_last(self, tmp_path,
                                                  monkeypatch, capsys):
        """--mfu-attribution obeys the same final-line contract as the
        workload path: full dict first, compact digest as the LAST
        stdout line (the full dict is ~9.6KB — over the tail window)."""
        stub = {
            "metric": "mfu_attribution", "value": 36.9,
            "inception_fwd": {"module_mfu_pct": 36.9,
                              "by_category": [{"pad": "x" * 4000}]},
            "resnet50_train": {"module_mfu_pct": 33.2},
            "resnet50_train_2x": {"module_mfu_pct": 31.4},
            "experiment_verdict": "flat within ~15%",
        }
        monkeypatch.setattr(bench, "bench_mfu_attribution", lambda args: stub)
        monkeypatch.setattr(bench, "MFU_ATTRIBUTION_PATH",
                            str(tmp_path / "MFU_ATTRIBUTION.json"))
        bench.main(["--mfu-attribution"])
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 2
        last = lines[-1]
        assert len(last.encode()) <= bench.SCOREBOARD_MAX_BYTES
        digest = json.loads(last)
        assert digest["inception_fwd_mfu_pct"] == 36.9
        assert digest["resnet50_train_mfu_pct"] == 33.2
        assert digest["full_detail"] == "MFU_ATTRIBUTION.json"

    def test_experiment_verdict_survives_zero_mfu(self):
        """`if m0 and m1` would drop the verdict when a measurement
        rounds to 0.0 — a real value on a tiny smoke model."""
        v = bench._experiment_verdict(0.0, 0.0, 8, 16)
        assert v is not None and "flat within" in v
        assert bench._experiment_verdict(None, 31.4, 128, 256) is None
        assert "moves it" in bench._experiment_verdict(20.0, 25.0, 128, 256)
        # m0 == 0.0 with a nonzero m1 IS a move — a positivity guard on
        # m0 would force every zero-base run to read "flat".
        assert "moves it" in bench._experiment_verdict(0.0, 0.3, 8, 16)

    def test_secondary_workload_consistency_fields(self):
        """VERDICT r4 #4: secondary workload lines carry the same wire
        bracket / ceiling / efficiency / bottleneck evidence as the
        flagship."""
        out = bench._attach_wire_consistency(
            {"value": 1800.0}, {"sustained_mb_s": 6.0},
            {"sustained_mb_s": 5.0}, 3136, 1800.0,
            bytes_source="measured_h2d/records")
        assert out["wire_sustained_mb_s_bracket"] == [6.0, 5.0]
        lo, hi = out["wire_ceiling_records_per_sec_range"]
        assert lo == round(5.0e6 / 3136, 1) and hi == round(6.0e6 / 3136, 1)
        assert out["efficiency_vs_wire_ceiling"] == round(1800.0 / hi, 3)
        assert out["bottleneck"].startswith("host->device transfer")
        # Far below the ceiling: the verdict flips to compute/RTT-bound.
        out2 = bench._attach_wire_consistency(
            {"value": 100.0}, {"sustained_mb_s": 6.0},
            {"sustained_mb_s": 5.0}, 116, 100.0, bytes_source="schema_bytes")
        assert out2["bottleneck"].startswith("device compute")
        # Degenerate probes degrade gracefully (no ceiling fields).
        out3 = bench._attach_wire_consistency(
            {"value": 1.0}, {"sustained_mb_s": None},
            {"sustained_mb_s": None}, 100, 1.0, bytes_source="schema_bytes")
        assert "wire_ceiling_records_per_sec_range" not in out3
        # NaN rates (1-step runs) must not emit NaN efficiency.
        out4 = bench._attach_wire_consistency(
            {"value": None}, {"sustained_mb_s": 6.0},
            {"sustained_mb_s": 5.0}, 100, float("nan"),
            bytes_source="schema_bytes")
        assert "efficiency_vs_wire_ceiling" not in out4
        # A rate above BOTH brackets carries the drift annotation —
        # never a silent >1.0 efficiency.
        out5 = bench._attach_wire_consistency(
            {"value": 2026.0}, {"sustained_mb_s": 6.0},
            {"sustained_mb_s": 5.0}, 3136, 2026.0,
            bytes_source="measured_h2d/records")
        assert out5["efficiency_vs_wire_ceiling"] > 1.0
        assert out5["ceiling_drift_code"] == "unreliable"
        in_band = bench._attach_wire_consistency(
            {"value": 1000.0}, {"sustained_mb_s": 6.0},
            {"sustained_mb_s": 5.0}, 3136, 1000.0,
            bytes_source="measured_h2d/records")
        assert in_band["ceiling_drift_code"] is None

    def test_hbm_table_uses_prefix_match(self):
        """An exact .get on device_kind killed the HBM-bandwidth-bound
        verdict for suffixed kind strings; both chip tables go through
        the same longest-prefix matcher."""
        class _Dev:
            device_kind = "TPU v5 lite (something new)"

        assert bench._chip_table_lookup(_Dev(), bench.CHIP_HBM_GBPS) == 819.0
        assert bench._chip_peak_tflops(_Dev()) == 197.0

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
