"""Reader ``pulse`` on rings made by hand, and PR 39's metric files read off the
real job kinds on tiny cells: every one finds its number where the program
has a pulse, and ``None`` where it has not."""

import importlib
import json
import os
import time

import jax
import pytest

from benchmark import harness
from benchmark.readers import pulse
from flink_tensorflow_tpu.tracing import flight
from flink_tensorflow_tpu.tracing.flight import FlightRecorder

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: PR 39's metric files, and the tiny cell that stands for each one's cell.
NEW = {name: ("tiny_resnet.train1" if name.endswith(".train") else "tiny_inception.paced")
       for name in [f"pulse_late_ms_max.{s}" for s in ("stream", "paced", "train", "lm", "lfm2")]
       + [f"subtask_cpu_share.{s}" for s in ("stream", "paced", "train")]
       + ["drain_wait_ms_per_step.train"]}


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _state(ring, job="cell", lo=100.0, hi=151.0):
    flight.keep(job, ring)
    return {"cell": {"name": job}, "run": {"window": {"t_start": lo, "t_close": hi}}}


def _late(ring, t, late_s, cause="nothing_ran", **more):
    ring.record("process", "pulse.late", {"late_s": late_s, "cpu_s": 0.01, "gc_s": 0.0,
                                          "cause": cause, **more}, t0=t)


def test_none_late_reads_zero(capsys):
    ring = FlightRecorder()
    ring.record("model.0", "fill", {"seq": 2}, t0=101.0, dur=0.1)
    state = _state(ring)
    assert pulse.read(state, what="late_ms_max") == 0.0
    assert "pulse: 0 late in the window; 0 full collections" in capsys.readouterr().err
    assert pulse.read(state, what="late_ms_max") == 0.0
    assert capsys.readouterr().err == ""  # one line a run


def test_two_late_one_outside_the_window(capsys):
    ring = FlightRecorder()
    _late(ring, 99.0, 3.5)                       # before the window opened
    _late(ring, 120.0, 0.08, cause="gc", gc_s=0.075)
    _late(ring, 130.0, 2.3, cause="lock_held", cpu_s=2.2)
    _late(ring, 151.0, 9.0)                      # at its close: outside
    _late(ring, 125.0, 7.0)
    ring._ring[-1] = ("model.0",) + ring._ring[-1][1:]  # another track's: not the pulse's
    # What covered the longest: the subtask thread in an emission (inside a fill), the
    # fetch thread waiting for the device, and no lane at work.
    ring.record("model.0", "fill", {"seq": 9, "cpu_s": 2.25, "runq_s": 0.0}, t0=127.0, dur=3.5)
    ring.record("model.0", "emit", {"seq": 8, "cpu_s": 2.2, "runq_s": 0.001}, t0=127.6, dur=2.5)
    ring.record("model.0", "emit", {"seq": 7, "cpu_s": 0.01}, t0=127.2, dur=0.3)
    ring.record("model.0", "in_flight", {"seq": 9, "fetch_cpu_s": 0.0, "fetch_runq_s": 0.0},
                t0=127.5, dur=2.6)
    ring.record("model.0", "enqueue", {"seq": 9, "cpu_s": 0.001}, t0=127.49, dur=0.01)
    ring.record("process", "gc", {"collected": 12}, t0=119.92, dur=0.075)
    ring.record("process", "gc", {"collected": 3}, t0=90.0, dur=0.07)
    ring.record("offered.0", "park.overslept", {"asked_s": 0.001, "slept_s": 0.081, "woken": False},
                t0=120.0)
    ring.record("offered.0", "park.overslept", {"asked_s": 0.001, "slept_s": 0.061, "woken": False},
                t0=140.0)
    state = _state(ring)
    assert pulse.read(state, what="late_ms_max") == pytest.approx(2300.0)
    line = capsys.readouterr().err
    assert "pulse: 2 late in the window, gc 0.080 s, lock_held 2.300 s" in line
    assert "longest at 30.0 s: late_s 2.3000, cpu_s 2.2000, gc_s 0.0000, cause lock_held" in line
    assert "subtask: emit 2.500 s {'cpu_s': 2.2, 'runq_s': 0.001}" in line
    assert "fetch: in_flight 2.600 s {'fetch_cpu_s': 0.0, 'fetch_runq_s': 0.0}" in line
    assert "lane:" not in line
    assert "1 full collections in the window" in line
    assert "longest late park 80.0 ms at 20.0 s, 75.0 ms of it inside a full collection" in line
    assert pulse.read(state, what="late_ms_max", track="model.0") == pytest.approx(7000.0)
    with pytest.raises(ValueError):
        pulse.read(state, what="no_such_reading")


def test_a_late_park_outside_every_collection_says_so(capsys):
    ring = FlightRecorder()
    ring.record("offered.0", "park.overslept", {"asked_s": 0.0, "slept_s": 0.06, "woken": True}, t0=110.0)
    pulse.read(_state(ring), what="late_ms_max")
    assert "longest late park 60.0 ms at 10.0 s, no full collection inside it" in capsys.readouterr().err


def test_a_program_without_a_ring_or_without_a_pulse_reads_none(monkeypatch):
    flight.keep("cell", None)
    state = {"cell": {"name": "cell"}, "run": {"window": {"t_start": 0.0, "t_close": 1.0}}}
    assert pulse.read(state, what="late_ms_max") is None  # no recorder_of(cell)
    # The parent's program: the ring and its accessor are there, the pulse is not.
    ring = FlightRecorder()
    _late(ring, 120.0, 2.0)
    state = _state(ring)
    monkeypatch.delattr(flight, "Pulse")
    assert pulse.read(state, what="late_ms_max") is None


def test_covering_takes_the_innermost_of_each_thread():
    ring = FlightRecorder()
    ring.record("train.0", "drain_wait", {"step": 5, "cpu_s": 0.0}, t0=10.0, dur=2.0)
    ring.record("train.0", "dispatch", {"step": 6}, t0=12.0, dur=0.01)
    ring.record("other.0", "emit", {}, t0=10.0, dur=2.0)  # not an operator's track
    got = pulse.covering(ring.events(), 10.5, 11.5)
    assert set(got) == {"subtask"} and got["subtask"][0] == "drain_wait"
    assert pulse.covering(ring.events(), 20.0, 21.0) == {}


@pytest.fixture(scope="module")
def readings(tiny_root):
    """Every new metric read, as the harness reads it, off one run of the tiny
    cell that stands for its cell; and what the run's stderr line would say."""
    out = {}
    for workload in sorted(set(NEW.values())):
        _, cell, config, mix = harness.load_cell(tiny_root, workload)
        ctx = harness.Context(root=tiny_root, cell=cell, config=config, mix=mix, seed=2**31 + 39,
                              seconds=1.5, trace=False, devices=jax.devices()[:1], t0=time.monotonic())
        run = importlib.import_module("benchmark.jobs." + config["job"]).run(ctx)
        state = {"ctx": ctx, "run": run, "cell": cell, "config": config}
        for name, cell_of in NEW.items():
            if cell_of == workload:
                spec = _spec(name)
                reader = importlib.import_module("benchmark.readers." + spec["reader"])
                out[name] = reader.read(state, **spec.get("args", {}))
    return out


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_finds_its_number_in_its_job_kind(readings, name):
    value = readings[name]
    assert isinstance(value, float), (name, value)
    if name.startswith("pulse_late_ms_max"):
        assert 0.0 <= value < 60e3
    elif name.startswith("subtask_cpu_share"):
        assert 0.0 < value <= 120.0  # the thread's CPU over its wall time, to the tick
    else:
        assert value >= 0.0


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_names_the_cell_and_the_layer_the_manifest_names(name):
    spec = _spec(name)
    (entry,) = [m for m in harness.load_manifest(ROOT)["per_layer"] if m["name"] == name]
    assert {k: spec[k] for k in entry} == entry
    assert len(spec["workloads"]) == 1 and spec["note"]
    if spec["reader"] == "pulse":
        assert spec["args"] == {"what": "late_ms_max", "track": "process"}
        assert (spec["layer"], spec["source"], spec["better"]) == ("dataflow_runtime", "program_span", "lower")
    else:
        head = spec["args"]["of"].rsplit(".", 1)[0]
        assert all(k.startswith(head + ".") for k in spec["args"].get("over", []))
