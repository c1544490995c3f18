"""The reduction from a trace to numbers, on a table whose answers are known by
hand and on a small trace recorded on the chip; the FLOP counts against XLA's;
the table of peaks has no default."""

import json
import os

import pytest

from benchmark import flops, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RECORDED = os.path.join(ROOT, "tests", "benchmark", "data", "small_trace.json.gz")
MS = 1_000_000


def _table():
    rows = []
    for dev, plane in enumerate(("/device:TPU:0", "/device:TPU:1")):
        for step in range(2):
            t = step * 10 * MS
            rows.append((plane, "XLA Modules", "jit_step(1)", t, 8 * MS))
            rows.append((plane, "XLA Ops", "fusion.1", t, 4 * MS))
            # Device 1 hides half of its all-reduce behind a fusion; device 0 none of it.
            rows.append((plane, "XLA Ops", "all-reduce.7", t + 4 * MS, 2 * MS))
            rows.append((plane, "XLA Ops", "fusion.2", t + (6 - dev) * MS, (2 + dev) * MS))
    rows.append(("/host:CPU", "python", "PjitFunction(step)", 0, 30 * MS))  # not a device plane
    return rows


def test_busy_idle_and_step_time_by_hand():
    trace = trace_reduce.Trace(_table())
    assert trace.window_s == pytest.approx(0.018)
    assert trace.busy_by_device() == {0: pytest.approx(0.016), 1: pytest.approx(0.016)}
    assert trace.busy_s() == pytest.approx(0.016)
    assert trace.idle_share() == pytest.approx(1 - 16 / 18)
    assert trace.module_runs(r"^jit_step\b") == [pytest.approx(0.008)] * 2
    with pytest.raises(LookupError, match="jit_step"):
        trace.module_runs("^jit_serve")  # a step renamed is an error, not another reading


def test_exposed_collective_by_hand():
    # 2 ms a step on device 0, 1 ms a step on device 1: the worst device counts.
    assert trace_reduce.Trace(_table()).exposed_collective_s() == pytest.approx(0.004)


def test_breakdown_names_ops_and_labels_gaps():
    b = trace_reduce.Trace(_table()).breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.008)]
    assert b["idle_gaps"] == [["host: before jit_step", pytest.approx(0.002)]]
    assert len(b["device_ops"]) <= 10


def test_a_gap_inside_a_running_program_is_named_so():
    rows = [("/device:TPU:0", "XLA Modules", "jit_serve(7)", 0, 10 * MS),
            ("/device:TPU:0", "XLA Ops", "fusion.1", 0, 3 * MS),
            ("/device:TPU:0", "XLA Ops", "fusion.2", 5 * MS, 5 * MS)]
    gaps = trace_reduce.Trace(rows).breakdown()["idle_gaps"]
    assert gaps == [["inside jit_serve", pytest.approx(0.002)]]


def test_a_trace_with_no_device_op_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.Trace([("/host:CPU", "python", "PjitFunction(step)", 0, 5)])


def test_union_of_overlapping_intervals():
    assert trace_reduce.union_ns([(0, 5), (3, 8), (10, 12), (11, 11)]) == 10


def test_recorded_chip_trace():
    """Four steps of a small jitted conv step, traced on a TPU v5e (PR 26)."""
    trace = trace_reduce.load(RECORDED)
    assert set(trace.device_events) == {0}
    runs = trace.module_runs("jit_step")
    assert len(runs) == 4 and all(0 < r < 0.1 for r in runs)
    assert 0 < trace.busy_s() < trace.window_s
    assert 0.0 < trace.idle_share() < 1.0
    # The op line and the module line account for the same device time.
    assert trace.busy_s() == pytest.approx(sum(runs), rel=0.1)
    assert trace.exposed_collective_s() == 0.0


@pytest.mark.parametrize("config,training,xla_gflop", [("inception_v3", False, 11.11),
                                                       ("resnet50", True, 24.10)])
def test_flops_from_shapes_match_xlas_count(config, training, xla_gflop):
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        cfg = json.load(f)
    assert bool(cfg.get("training")) == training
    assert flops.step_flops(cfg) / 1e9 == pytest.approx(xla_gflop, rel=0.02)


def test_a_device_kind_without_peaks_raises():
    assert trace_reduce.peaks_for(ROOT, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        trace_reduce.peaks_for(ROOT, "cpu")


def test_the_profiles_start_is_read_from_an_xplane_and_put_on_the_hosts_clock(tmp_path):
    import glob
    import time

    import jax

    from benchmark import harness

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = options.host_tracer_level = 0
    unix_ns, monotonic = unix_at = harness.unix_instant()
    again = harness.unix_instant()
    assert (again[0] - unix_ns) / 1e9 == pytest.approx(again[1] - monotonic, abs=1e-3)
    t_call = time.monotonic()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    t_on = time.monotonic()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    rows, start_ns = trace_reduce.read_xplane(path)
    assert rows == []  # no TPU here: no device plane, but the profile says when it began
    trace = trace_reduce.Trace(_table(), start_ns)
    assert trace.profile_start_host() is None  # no Unix instant kept beside it
    trace.unix_at = unix_at
    assert t_call - 1e-3 <= trace.profile_start_host() <= t_on + 1e-3
    # A recorded table, or an xplane without the stat, has no start to read.
    trace = trace_reduce.load(RECORDED)
    trace.unix_at = unix_at
    assert trace.profile_start_unix_ns is None and trace.profile_start_host() is None
