"""`correct` has to be able to fail.  At a size a test run can hold: the
low-precision control (the reference computed in float8 in the program's place)
comes out not correct, and so does a run whose timed path is broken underneath,
once for each fault a one-chip cell can have."""

import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import controls, harness


def _run(root, workload, fault):
    return harness.run_cell(root=root, workload=workload, seed=2**31 + 3, seconds=1.5, trace=False,
                            devices=jax.devices()[:1], t0=time.monotonic(), fault=fault)


def _failed(out):
    return [name for name, *_, ok in out["checks"] if not ok]


def _readings(root, workload, seed=5):
    _, _, cfg, mix = harness.load_cell(root, workload)
    read = controls.train_readings if cfg.get("training") else controls.stream_readings
    return controls.verdicts(read(cfg, mix, seed), cfg["limits"])


@pytest.fixture(scope="module")
def stream_verdicts(tiny_root):
    return _readings(tiny_root, "tiny_inception.backlog")


@pytest.fixture(scope="module")
def train_verdicts(tiny_root):
    return _readings(tiny_root, "tiny_resnet.train1")


@pytest.mark.parametrize("reading,trips", [
    ("control_float8_e4m3fn", "logit_rms_err"), ("control_float8_e5m2", "logit_rms_err"),
    ("label_plus_one", "label_gap"), ("score_halved", "score_log_err")])
def test_a_serving_control_is_refused_at_the_cells_limits(stream_verdicts, reading, trips):
    verdict = stream_verdicts[reading]
    assert not verdict["correct"] and trips in verdict["fails"], verdict
    if reading.startswith("control"):
        assert verdict["numbers"][trips] > 3 * 0.014  # three times what the tiny sound runs read


@pytest.mark.parametrize("reading,trips", [
    ("control_float8_e4m3fn", "grad_diff_med"), ("control_float8_e5m2", "grad_diff_med"),
    ("half_batch", "grad_norm_gap"), ("half_batch", "grad_diff_med"),
    ("state_unchanged", "change_norm_gap")])
def test_a_training_control_is_refused_at_the_cells_limits(train_verdicts, reading, trips):
    verdict = train_verdicts[reading]
    assert not verdict["correct"] and trips in verdict["fails"], verdict


def test_the_float8_control_keeps_its_gradient(train_verdicts):
    # Its cotangents are scaled into the type's range: unscaled they underflow,
    # and the control would fail only for having no gradient at all.
    numbers = train_verdicts["control_float8_e4m3fn"]["numbers"]
    assert numbers["grad_norm_gap"] < 0.9 and numbers["grad_norm_gap_med"] < 0.2
    assert train_verdicts["state_unchanged"]["numbers"]["change_norm_gap"] == pytest.approx(1.0)


def _wrong_label(record):
    return record.replace(label=(record["label"] + 1) % 10)


def _scaled_logits(record):
    return record.replace(logits=record["logits"] * 1.5)


def _halved_score(record):
    return record.replace(score=record["score"] / 2)


@pytest.mark.parametrize("fault,trips", [(_wrong_label, "label_gap"), (_scaled_logits, "logit_rms_err"),
                                         (_halved_score, "score_log_err")])
def test_an_answer_altered_where_it_is_produced(tiny_root, fault, trips):
    out = _run(tiny_root, "tiny_inception.backlog", fault)
    assert not out["correct"] and trips in _failed(out), out["checks"]


def _lost_records(record, _seen=[]):  # noqa: B006 - the list is the fault's memory
    _seen.append(1)
    return record if len(_seen) % 5 else record.with_meta(id=0)


def test_answers_lost_and_duplicated(tiny_root):
    out = _run(tiny_root, "tiny_inception.backlog", _lost_records)
    assert not out["correct"] and out["failed"] > 0


def _state_unchanged(function):
    step = function._step_fn

    def frozen(state, batch):
        keep = jax.tree.map(jnp.copy, state)
        _, metrics = step(state, batch)
        return keep, metrics

    function._step_fn = frozen


def _half_batch(function):
    step = function._step_fn

    def halved(state, batch):
        # The second half never reaches the step: the mean is over the first.
        batch = jax.tree.map(lambda x: jnp.concatenate([x[:len(x) // 2]] * 2), batch)
        return step(state, batch)

    function._step_fn = halved


@pytest.mark.parametrize("fault,trips", [(_state_unchanged, "change_norm_gap"),
                                         (_half_batch, "grad_norm_gap")])
def test_a_training_step_broken_underneath(tiny_root, fault, trips):
    out = _run(tiny_root, "tiny_resnet.train1", fault)
    assert not out["correct"], out["checks"]
    assert trips in _failed(out), out["checks"]
