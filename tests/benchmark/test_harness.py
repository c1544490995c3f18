"""The command's refusals, the result line, and cells found by name: tiny
configurations and mixes that exist only as files in a temporary checkout run
through the harness's internals on CPU devices the test hands over (the command
itself has no option for that)."""

import gc
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from benchmark import harness, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(root, workload, chips=1, seconds=2.0, **kw):
    return harness.run_cell(root=root, workload=workload, seed=2**31 + 7, seconds=seconds,
                            trace=False, devices=jax.devices()[:chips], t0=time.monotonic(), **kw)


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "inception_v3.saturated",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_result_line_has_the_contracts_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 1}
    line = json.loads(harness.result_line(
        correct=True, attempted=3, failed=0, metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device=device, checks=[("logit_err", 0.01, 0.05, True)]))
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["checks"] == {"logit_err": {"value": 0.01, "limit": 0.05}}
    traced = json.loads(harness.result_line(
        correct=False, attempted=3, failed=1, metrics={}, device=device,
        breakdown={"device_ops": [], "idle_gaps": []}, checks=[]))
    assert list(traced)[-2:] == ["breakdown", "checks"] and traced["correct"] is False


def test_unknown_workload_is_refused(tiny_root):
    with pytest.raises(SystemExit):
        harness.load_cell(tiny_root, "no.such.cell")


def test_more_chips_asked_than_found(tiny_root):
    with pytest.raises(SystemExit):
        _run(tiny_root, "tiny_resnet.train4", chips=1)


@pytest.mark.parametrize("workload,metric", [
    ("tiny_inception.backlog", "records_per_s"),
    ("tiny_inception.paced", "latency_p95_ms"),
    ("tiny_resnet.train1", "train_examples_per_s"),
])
def test_a_cell_added_as_files_runs(tiny_root, workload, metric):
    out = _run(tiny_root, workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {metric, "setup_s"}
    assert out["metrics"][metric]["value"] > 0
    assert all(ok for *_, ok in out["checks"])
    assert out["device"]["platform"] == "cpu"  # and so never a result of the command


def test_gang_training_over_four_virtual_devices(tiny_root):
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices (tests/conftest.py forces eight)")
    out = _run(tiny_root, "tiny_resnet.train4", chips=4)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 11])
def test_every_seed_offers_the_same_gaps_in_another_order(seed):
    mix = {"arrivals": "poisson", "rate_per_s": 50.0}
    import numpy as np

    a = np.diff(traffic.due_offsets(mix, seed, 4.0))
    b = np.diff(traffic.due_offsets(mix, seed + 1, 4.0))
    assert len(a) == len(b) == 199
    assert not np.array_equal(a, b)
    # The same set of gaps but for the one that the permutation puts first.
    assert abs(np.sort(a)[10:-10].sum() - np.sort(b)[10:-10].sum()) < 0.2
    assert traffic.due_offsets(mix, seed, 4.0).max() < 4.0


@pytest.mark.parametrize("mix", [{"arrivals": "uniform", "rate_per_s": 50.0},
                                 {"arrivals": "poisson", "rate_per_s": 0.1}])
def test_a_mix_the_generator_cannot_offer_is_refused(mix):
    with pytest.raises(ValueError):
        traffic.due_offsets(mix, 3, 4.0)


def test_a_backlog_has_no_schedule_and_every_seed_its_own_start():
    assert traffic.due_offsets({"arrivals": "backlog"}, 3, 4.0) is None
    starts = {traffic.first_index(2048, seed) for seed in (0, 5, 2**31 + 11)}
    assert len(starts) == 3 and all(0 <= s < 2048 for s in starts)


def test_a_silent_sink_is_told_with_where_the_threads_stand(capsys):
    import time
    import types

    ctx = harness.Context(root=".", cell={}, config={}, mix={}, seed=1, seconds=0.6, trace=False,
                          devices=[], t0=time.monotonic())
    now = time.monotonic()
    clock = types.SimpleNamespace(t_start=now, t_close=now + 0.6)
    ctx._watch(clock, lambda: now, silence=0.2, tick=0.05)  # nothing ever arrives
    told = capsys.readouterr().err
    assert told.count("sink silent for 0.2 s") == 1  # once a window, not once a tick
    assert "MainThread: _watch" in told and 0.0 <= ctx.oversleep_s < 0.5
    gc.callbacks.remove(ctx._on_collection)
