"""Job kind ``stream_lm`` through the harness on the CPU: a tiny Falcon-H1 cell
that exists only as files in a temporary checkout (which is how a cell is
added); its controls at the same size; the ``lm`` reader's patterns held to
the op names a v5e printed for the real cell."""

import gzip
import json
import os
import re
import shutil
import time
import types

import jax
import pytest

from benchmark import controls, controls_lm, harness, trace_reduce
from benchmark.readers import lm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "falcon_h1_34b.score_4k"
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=10, num_key_value_heads=2, head_dim=16,
            mamba_d_ssm=128, mamba_n_heads=32, mamba_d_head=4, mamba_n_groups=2,
            mamba_d_state=16, mamba_chunk_size=8)


@pytest.fixture(scope="module")
def tiny_lm_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout_lm"))
    bench = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    shutil.copy(os.path.join(ROOT, "benchmark", "peaks.json"), bench)
    with open(os.path.join(ROOT, "benchmark", "configs", "falcon_h1_34b.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["model"].update(TINY)
    cfg.update(name="tiny_falcon", check_records=4,
               limits={"logit_rms_err": 0.03, "label_gap": 0.1, "score_log_err": 0.25})
    with open(os.path.join(bench, "configs", "tiny_falcon.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "workloads", "tiny_falcon.score.json"), "w") as f:
        json.dump({"arrivals": "backlog", "pool_records": 8, "record_tokens": 20,
                   "window_records": 2}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "tiny_falcon", "source": "test", "reduced": [], "why": "test",
                            "file": "benchmark/configs/tiny_falcon.json"}]
    manifest["workloads"] = [{"name": "tiny_falcon.score", "config": "tiny_falcon",
                              "traffic": "score", "chips": 1, "why": "test"}]
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_falcon.score"] if m["name"] == "records_per_s" else []
    manifest["per_layer"] = []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, **kw):
    return harness.run_cell(root=root, workload="tiny_falcon.score", seed=2**31 + 7, seconds=2.0,
                            trace=False, devices=jax.devices()[:1], t0=time.monotonic(), **kw)


def test_a_stream_lm_cell_added_as_files_runs(tiny_lm_root):
    out = _run(tiny_lm_root)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"records_per_s", "setup_s"}
    assert out["metrics"]["records_per_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"  # and so never a result of the command


def test_an_altered_answer_is_refused(tiny_lm_root):
    out = _run(tiny_lm_root, fault=lambda record: record.replace(logits=record["logits"] * 1.5))
    assert not out["correct"]
    assert "logit_rms_err" in [name for name, *_, ok in out["checks"] if not ok]


def test_a_configuration_whose_two_copies_differ_is_refused(tiny_lm_root):
    from benchmark.jobs import stream_lm

    _, _, cfg, _ = harness.load_cell(tiny_lm_root, "tiny_falcon.score")
    assert stream_lm.model_of(cfg) is cfg["model"]
    with pytest.raises(ValueError, match="hidden_size"):
        stream_lm.model_of(dict(cfg, hidden_size=128))


def test_the_real_file_holds_the_published_config_twice():
    with open(os.path.join(ROOT, "benchmark", "configs", "falcon_h1_34b.json")) as f:
        cfg = json.load(f)
    assert all(cfg[key] == value for key, value in cfg["model"].items())
    assert cfg["reduced"] == ["num_hidden_layers"] and 4 <= cfg["num_hidden_layers"] <= 6
    assert (cfg["hidden_size"], cfg["vocab_size"], cfg["mamba_d_state"]) == (5120, 261120, 256)


@pytest.fixture(scope="module")
def verdicts(tiny_lm_root):
    _, _, cfg, mix = harness.load_cell(tiny_lm_root, "tiny_falcon.score")
    return controls.verdicts(controls_lm.readings(cfg, mix, 5), cfg["limits"])


@pytest.mark.parametrize("reading", ["control_float8_e4m3fn", "control_float8_e5m2",
                                     "fault_state_dropped", "fault_no_attention", "fault_no_conv"])
def test_a_control_is_refused_at_the_cells_limits(verdicts, reading):
    verdict = verdicts[reading]
    assert not verdict["correct"] and "logit_rms_err" in verdict["fails"], verdict
    assert verdict["numbers"]["logit_rms_err"] > 3 * 0.0075  # three times what the tiny sound runs read


# -- the lm reader on op names recorded on the chip ----------------------------

@pytest.fixture(scope="module")
def recorded():
    """One run of the real cell's step on a v5e: the op events of the XLA Ops
    line (name, start, end) and the program's own event."""
    with gzip.open(os.path.join(DATA, "lm_step_ops.json.gz"), "rt") as f:
        doc = json.load(f)
    rows = [("/device:TPU:0", trace_reduce.OPS_LINE, n, s, e - s) for n, s, e in doc["ops"]]
    rows += [("/device:TPU:0", trace_reduce.MODULES_LINE, n, s, e - s) for n, s, e in doc["modules"]]
    return trace_reduce.Trace(rows), doc


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", metric + ".json")) as f:
        return json.load(f)


def _state(trace, counters):
    _, cell, cfg, mix = harness.load_cell(ROOT, CELL)
    run = {"counters": counters, "window": {"batch_records": mix["window_records"],
                                            "record_tokens": mix["record_tokens"]}}
    return {"ctx": types.SimpleNamespace(traced=trace), "run": run, "cell": cell, "config": cfg,
            "peaks": trace_reduce.peaks_for(ROOT, "TPU v5 lite")}


def test_the_scans_pattern_and_the_kernels_name_match_what_the_chip_printed(recorded):
    trace, doc = recorded
    state = _state(trace, {"model.0.tokens": 8192, "model.0.batches": 1})
    scan = lm.read(state, **_spec("ssd_scan_share_of_step.lm")["args"])
    kernel = lm.read(state, **_spec("flash_attention_roofline_share.lm")["args"])
    assert scan == pytest.approx(doc["expect"]["ssd_scan_share_of_step.lm"], rel=1e-6)
    assert kernel == pytest.approx(doc["expect"]["flash_attention_roofline_share.lm"], rel=1e-6)
    assert 0 < scan < 100 and 0 < kernel < 100
    # One kernel call a layer; the scan's ops are neither the kernel nor the products of the MLP.
    names = [lm.produced(n) for n, _, _ in doc["ops"]]
    kernel_pattern = _spec("flash_attention_roofline_share.lm")["args"]["pattern"]
    scan_pattern = _spec("ssd_scan_share_of_step.lm")["args"]["pattern"]
    assert sum(bool(re.search(kernel_pattern, n)) for n in names) == 6
    assert not any(re.search(scan_pattern, n) and re.search(kernel_pattern, n) for n in names)
    assert not any(re.search(scan_pattern, n) for n in names if "21504" in n or "261120" in n)
    assert 15 < sum(bool(re.search(scan_pattern, n)) for n in names) < 600


def test_a_pattern_that_matches_nothing_raises(recorded):
    trace, _ = recorded
    state = _state(trace, {})
    with pytest.raises(LookupError):
        lm.read(state, what="op_share", module=r"^jit_call\b", pattern=r"no_such_op")
    with pytest.raises(LookupError):
        lm.read(state, what="kernel_roofline", pattern=r"no_such_kernel")


def test_padding_is_never_counted_as_work(recorded):
    trace, doc = recorded
    args = _spec("lm_step_mfu")["args"]
    mfu = lm.read(_state(trace, {"model.0.tokens": 16384, "model.0.batches": 2}), **args)
    # One whole run: 2 records x 21.78 TFLOP in 325.3 ms of a 197 TFLOP/s chip.
    assert mfu == pytest.approx(doc["expect"]["lm_step_mfu"], rel=1e-6) and 60 < mfu < 75
    with pytest.raises(ValueError, match="padding"):
        lm.read(_state(trace, {"model.0.tokens": 8192 + 4096, "model.0.batches": 1}), **args)
    # A program that counts no tokens has nothing to hold the share to: the metric is left out.
    assert lm.read(_state(trace, {"model.0.batches": 1}), **args) is None
    assert lm.read(_state(None, {}), **args) is None
