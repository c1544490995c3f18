"""Job kind ``stream_lm_routed`` through the harness on the CPU: a tiny
LFM2-MoE cell that exists only as files in a temporary checkout (which is how
a cell is added); the float8 controls, the planted faults and a wrong route
through the job's own held comparison at the same size; the ``moe`` reader
and the new metrics' patterns held to the op names a v5e printed for the real
cell."""

import gzip
import json
import os
import re
import shutil
import time
import types

import jax
import numpy as np
import pytest

from benchmark import controls, harness, trace_reduce
from benchmark.jobs import _zoo, stream_lm_routed
from benchmark.readers import counters, lm, moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "lfm2_8b_a1b.score_4k"
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=6, layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
            num_attention_heads=8, num_key_value_heads=2, num_experts=8, num_experts_per_tok=2)
#: The tiny cell computes in float32, so that program and reference choose
#: alike; three times what its sound runs read (0.0000026).
TINY_LIMITS = {"logit_rms_err": 0.001, "label_gap": 0.01, "score_log_err": 0.01, "routing_wrong_share": 0.0}


@pytest.fixture(scope="module")
def tiny_routed_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout_routed"))
    bench = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    shutil.copy(os.path.join(ROOT, "benchmark", "peaks.json"), bench)
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2_8b_a1b.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["model"].update(TINY)
    cfg["program_kwargs"]["compute_dtype"] = "float32"
    cfg.update(name="tiny_lfm2", check_records=4, limits=TINY_LIMITS, routing_delta=1e-4)
    with open(os.path.join(bench, "configs", "tiny_lfm2.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "workloads", "tiny_lfm2.score.json"), "w") as f:
        json.dump({"arrivals": "backlog", "pool_records": 8, "record_tokens": 24,
                   "window_records": 2}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "tiny_lfm2", "source": "test", "reduced": [], "why": "test",
                            "file": "benchmark/configs/tiny_lfm2.json"}]
    manifest["workloads"] = [{"name": "tiny_lfm2.score", "config": "tiny_lfm2",
                              "traffic": "score", "chips": 1, "why": "test"}]
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_lfm2.score"] if m["name"] == "records_per_s" else []
    manifest["per_layer"] = []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, **kw):
    return harness.run_cell(root=root, workload="tiny_lfm2.score", seed=2**31 + 7, seconds=2.0,
                            trace=False, devices=jax.devices()[:1], t0=time.monotonic(), **kw)


def test_a_routed_cell_added_as_files_runs(tiny_routed_root):
    out = _run(tiny_routed_root)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"records_per_s", "setup_s"}
    assert {name for name, *_ in out["checks"]} == set(TINY_LIMITS)
    assert out["device"]["platform"] == "cpu"  # and so never a result of the command


def test_an_altered_answer_is_refused(tiny_routed_root):
    out = _run(tiny_routed_root, fault=lambda record: record.replace(logits=record["logits"] * 1.5))
    assert not out["correct"]
    assert "logit_rms_err" in [name for name, *_, ok in out["checks"] if not ok]


def test_a_wrong_route_is_refused(tiny_routed_root):
    # Every token's second expert moved on by one in the first expert layer: for
    # most tokens an expert whose score lies far under the reference's own second.
    def rerouted(record):
        routing = np.array(record["routing"])
        routing[:, 0, 1] = (routing[:, 0, 1] + 1) % TINY["num_experts"]
        return record.replace(routing=routing)

    out = _run(tiny_routed_root, fault=rerouted)
    assert not out["correct"]
    assert "routing_wrong_share" in [name for name, *_, ok in out["checks"] if not ok]


@pytest.fixture(scope="module")
def tiny_cell(tiny_routed_root):
    _, _, cfg, mix = harness.load_cell(tiny_routed_root, "tiny_lfm2.score")
    return cfg, mix


@pytest.fixture(scope="module")
def verdicts(tiny_cell):
    cfg, mix = tiny_cell
    return controls.verdicts(stream_lm_routed.controls(cfg, mix, 5), cfg["limits"])


@pytest.mark.parametrize("reading", ["control_float8_e4m3fn", "control_float8_e5m2"]
                         + ["fault_" + f for f in ("no_selection_bias", "weights_not_normalised", "no_conv",
                                                   "no_qk_norm", "expert_zeroed")])
def test_a_control_is_refused_at_the_cells_limits(verdicts, reading):
    verdict = verdicts[reading]
    assert not verdict["correct"] and verdict["fails"], verdict


def test_the_faults_are_the_references(tiny_cell):
    cfg, _ = tiny_cell
    assert set(_zoo.reference_of(cfg).FAULTS) == {"no_selection_bias", "weights_not_normalised", "no_conv",
                                                  "no_qk_norm", "expert_zeroed"}


def test_a_fault_in_the_choice_shows_in_the_routing_and_one_in_the_sum_in_the_logits(verdicts):
    # Selection without the bias hands over routes the reference would not take; its own
    # logits, held to them, agree.  Weights not normalised move the sum (and with it the
    # later layers' inputs, so their routes too).
    assert verdicts["fault_no_selection_bias"]["fails"] == ["routing_wrong_share"]
    assert "logit_rms_err" in verdicts["fault_weights_not_normalised"]["fails"]


def test_free_running_a_float8_control_reads_worse_than_held(verdicts):
    free = verdicts["free_running_float8_e4m3fn"]["numbers"]["logit_rms_err"]
    assert free > verdicts["control_float8_e4m3fn"]["numbers"]["logit_rms_err"] > TINY_LIMITS["logit_rms_err"]


def test_the_real_file_holds_the_published_config_twice_and_only_the_depth_is_cut():
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2_8b_a1b.json")) as f:
        cfg = json.load(f)
    assert all(cfg[key] == value for key, value in cfg["model"].items())
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 14
    assert cfg["layer_types"] == (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3)
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]) == (2048, 7168, 1792)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"], cfg["vocab_size"]) == (32, 4, 65536)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["num_dense_layers"]) == (32, 8, 2)
    assert cfg["limits"]["routing_wrong_share"] == 0.0 and 0 < cfg["routing_delta"] < 0.5
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        assert json.load(f) == {"arrivals": "backlog", "pool_records": 64, "record_tokens": 4096,
                                "window_records": 2, "warmup_windows": 4}


# -- the readers on op names recorded on the chip -------------------------------

@pytest.fixture(scope="module")
def recorded():
    """One run of the real cell's step on a v5e: the op events of the XLA Ops
    line (name, start, end) and the program's own event."""
    with gzip.open(os.path.join(DATA, "lfm2_step_ops.json.gz"), "rt") as f:
        doc = json.load(f)
    rows = [("/device:TPU:0", trace_reduce.OPS_LINE, n, s, e - s) for n, s, e in doc["ops"]]
    rows += [("/device:TPU:0", trace_reduce.MODULES_LINE, n, s, e - s) for n, s, e in doc["modules"]]
    return trace_reduce.Trace(rows), doc


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", metric + ".json")) as f:
        return json.load(f)


def _state(trace, registry):
    _, cell, cfg, mix = harness.load_cell(ROOT, CELL)
    run = {"counters": registry, "window": {"batch_records": mix["window_records"],
                                            "record_tokens": mix["record_tokens"]}}
    return {"ctx": types.SimpleNamespace(traced=trace), "run": run, "cell": cell, "config": cfg,
            "peaks": trace_reduce.peaks_for(ROOT, "TPU v5 lite")}


def _read(metric, state):
    spec = _spec(metric)
    reader = {"lm": lm, "moe": moe, "counters": counters}[spec["reader"]]
    return reader.read(state, **spec["args"])


@pytest.mark.parametrize("metric", ["lm_step_mfu.lfm2", "flash_attention_roofline_share.lfm2",
                                    "expert_matmul_roofline_share.lfm2", "moe_share_of_step.lfm2"])
def test_a_trace_metric_reads_what_the_chip_printed(recorded, metric):
    trace, doc = recorded
    value = _read(metric, _state(trace, {"model.0.tokens": 8192, "model.0.batches": 1}))
    assert value == pytest.approx(doc["expect"][metric], rel=1e-6)
    assert 0 < value < 100


def test_the_patterns_find_the_routed_layers_and_nothing_else(recorded):
    _, doc = recorded
    names = [lm.produced(n) for n, _, _ in doc["ops"]]
    products = _spec("expert_matmul_roofline_share.lfm2")["args"]["pattern"]
    routed = _spec("moe_share_of_step.lfm2")["args"]["pattern"]
    kernel = _spec("flash_attention_roofline_share.lfm2")["args"]["pattern"]
    # Two grouped products in each of 12 expert layers, one kernel call in each of 3 attention layers.
    found = [n for n in names if re.search(products, n)]
    assert len(found) == 24 and all("f32[32768," in n for n in found)
    assert sum(bool(re.search(kernel, n)) for n in names) == 3
    assert all(re.search(routed, n) for n in found)
    assert not any(re.search(routed, n) for n in names if re.search(kernel, n) or "65536" in n or "7168" in n)
    assert 24 < sum(bool(re.search(routed, n)) for n in names) < 1500


def test_a_pattern_that_matches_nothing_raises(recorded):
    trace, _ = recorded
    with pytest.raises(LookupError):
        moe.read(_state(trace, {}), what="kernel_roofline", module=r"^jit_call\b", pattern=r"no_such_kernel")
    with pytest.raises(ValueError):
        moe.read(_state(trace, {}), what="no_such_reading", module=r"^jit_call\b", pattern=r"ragged")
    assert moe.read(_state(None, {}), what="kernel_roofline", module=r"^jit_call\b", pattern=r"ragged") is None


@pytest.mark.parametrize("metric,registry,want", [
    ("expert_rows_per_token.lfm2", {"model.0.expert_rows": 48 * 8192, "model.0.tokens": 8192}, 48.0),
    ("expert_rows_per_token.lfm2", {"model.0.tokens": 8192}, None),  # a program that counts no rows
    ("expert_rows_max_share.lfm2", {"model.0.expert_rows_max": 12 * 1024, "model.0.expert_rows": 48 * 8192}, 3.125),
    ("expert_rows_max_share.lfm2", {}, None),
])
def test_the_counter_metrics(metric, registry, want):
    assert _read(metric, _state(None, registry)) == want
