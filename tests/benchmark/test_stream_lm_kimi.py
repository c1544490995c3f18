"""Job kind ``stream_lm_routed`` over Kimi-K2 through the harness on the CPU: a
tiny cell that exists only as files in a temporary checkout (which is how a
cell is added); the float8 controls, the eight planted faults and a wrong
route through the job's own held comparison at the same size; the real
configuration's file; the new metrics' files, and their patterns held to the
op names a v5e printed for the real cell."""

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
from benchmark.readers import trace as trace_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "kimi_k2_7_code.score_4k"
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=4, router_experts=16, first_expert=0, num_experts_per_tok=2)
#: The tiny cell computes in float32, so that program and reference choose
#: alike; some hundred times what its sound runs read.
TINY_LIMITS = {"logit_rms_err": 0.001, "label_gap": 0.01, "score_log_err": 0.01, "routing_wrong_share": 0.0}
METRICS = ("lm_step_mfu.kimi", "lm_step_device_ms.kimi", "device_idle_share.kimi",
           "flash_attention_roofline_share.kimi", "expert_matmul_roofline_share.kimi", "moe_share_of_step.kimi",
           "latent_attention_share_of_step.kimi", "expert_rows_per_token.kimi", "expert_rows_max_share.kimi",
           "expert_passes_per_batch.kimi", "unbatch_ms_per_batch.kimi", "emit_ms_per_batch.kimi",
           "collect_wait_ms_per_batch.kimi", "operator_open_s.kimi")


@pytest.fixture(scope="module")
def tiny_kimi_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout_kimi"))
    bench = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    shutil.copy(os.path.join(ROOT, "benchmark", "peaks.json"), bench)
    with open(os.path.join(ROOT, "benchmark", "configs", "kimi_k2_7_code.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["model"].update(TINY)
    cfg["program_kwargs"]["compute_dtype"] = "float32"
    cfg.update(name="tiny_kimi", check_records=4, limits=TINY_LIMITS, routing_delta=1e-4)
    with open(os.path.join(bench, "configs", "tiny_kimi.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "workloads", "tiny_kimi.score.json"), "w") as f:
        json.dump({"arrivals": "backlog", "pool_records": 8, "record_tokens": 24,
                   "window_records": 2}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "tiny_kimi", "source": "test", "reduced": [], "why": "test",
                            "file": "benchmark/configs/tiny_kimi.json"}]
    manifest["workloads"] = [{"name": "tiny_kimi.score", "config": "tiny_kimi",
                              "traffic": "score", "chips": 1, "why": "test"}]
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_kimi.score"] if m["name"] == "records_per_s" else []
    manifest["per_layer"] = []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def _run(root, **kw):
    return harness.run_cell(root=root, workload="tiny_kimi.score", seed=2**31 + 7, seconds=2.0,
                            trace=False, devices=jax.devices()[:1], t0=time.monotonic(), **kw)


def test_a_kimi_cell_added_as_files_runs(tiny_kimi_root):
    out = _run(tiny_kimi_root)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"records_per_s", "setup_s"}
    assert {name for name, *_ in out["checks"]} == set(TINY_LIMITS)
    assert out["device"]["platform"] == "cpu"  # and so never a result of the command


def test_an_altered_answer_is_refused(tiny_kimi_root):
    out = _run(tiny_kimi_root, fault=lambda record: record.replace(logits=record["logits"] * 1.5))
    assert not out["correct"]
    assert "logit_rms_err" in [name for name, *_, ok in out["checks"] if not ok]


def test_a_wrong_route_is_refused(tiny_kimi_root):
    # Every token's second expert moved on by one in the first expert layer: for
    # most tokens an expert whose score lies far under the reference's own second.
    def rerouted(record):
        routing = np.array(record["routing"])
        routing[:, 0, 1] = (routing[:, 0, 1] + 1) % TINY["router_experts"]
        return record.replace(routing=routing)

    out = _run(tiny_kimi_root, fault=rerouted)
    assert not out["correct"]
    assert "routing_wrong_share" in [name for name, *_, ok in out["checks"] if not ok]


@pytest.fixture(scope="module")
def tiny_cell(tiny_kimi_root):
    _, _, cfg, mix = harness.load_cell(tiny_kimi_root, "tiny_kimi.score")
    return cfg, mix


@pytest.fixture(scope="module")
def verdicts(tiny_cell):
    cfg, mix = tiny_cell
    return controls.verdicts(stream_lm_routed.controls(cfg, mix, 5), cfg["limits"])


FAULTS = ("no_selection_bias", "no_routed_scaling", "no_shared_expert", "expert_zeroed",
          "over_capacity_dropped", "k_pe_not_rotated", "no_mscale", "no_latent_norms")


@pytest.mark.parametrize("reading", ["control_float8_e4m3fn", "control_float8_e5m2"] + ["fault_" + f for f in FAULTS])
def test_a_control_is_refused_at_the_cells_limits(verdicts, reading):
    verdict = verdicts[reading]
    assert not verdict["correct"] and verdict["fails"], verdict


def test_the_faults_are_the_references(tiny_cell):
    cfg, _ = tiny_cell
    assert _zoo.reference_of(cfg).FAULTS == FAULTS


def test_a_fault_in_the_choice_shows_in_the_routing_and_one_in_the_sum_in_the_logits(verdicts):
    # Selection without the bias hands over routes the reference would not take; its own logits, held to
    # them, agree.  A fault in what is summed moves the logits (and the later layers' routes with them).
    assert verdicts["fault_no_selection_bias"]["fails"] == ["routing_wrong_share"]
    for fault in ("no_routed_scaling", "no_shared_expert", "k_pe_not_rotated", "no_mscale", "no_latent_norms"):
        assert "logit_rms_err" in verdicts["fault_" + fault]["fails"], fault


def test_free_running_a_float8_control_reads_worse_than_held(verdicts):
    free = verdicts["free_running_float8_e4m3fn"]["numbers"]["logit_rms_err"]
    assert free > verdicts["control_float8_e4m3fn"]["numbers"]["logit_rms_err"] > TINY_LIMITS["logit_rms_err"]


def test_the_real_file_holds_the_published_config_twice_and_states_the_three_cuts():
    with open(os.path.join(ROOT, "benchmark", "configs", "kimi_k2_7_code.json")) as f:
        cfg = json.load(f)
    assert all(cfg[key] == value for key, value in cfg["model"].items())
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"] == list(cfg["reduced_from"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (7, 12, 20480)
    assert all(str(n) in cfg["reduced_from"][key].replace(",", "") for key, n in
               (("num_hidden_layers", 61), ("n_routed_experts", 384), ("vocab_size", 163840)))
    # Every width, head size and rank, the router's 384 outputs, top-8, the scaling factor and yarn: as published.
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]) == (7168, 18432, 2048)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"]) == (1536, 512)
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == (128, 64, 128)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (64, 64)
    assert (cfg["router_experts"], cfg["first_expert"], cfg["num_experts_per_tok"]) == (384, 0, 8)
    assert (cfg["n_shared_experts"], cfg["first_k_dense_replace"], cfg["routed_scaling_factor"]) == (1, 1, 2.827)
    assert cfg["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                                   "original_max_position_embeddings": 4096, "type": "yarn"}
    assert (cfg["rope_theta"], cfg["rms_norm_eps"], cfg["n_group"], cfg["topk_group"]) == (50000, 1e-5, 1, 1)
    assert "32 chips share each layer" in cfg["deployment"] and "tower" in cfg["assumed"]
    assert cfg["limits"]["routing_wrong_share"] == 0.0 and 0 < cfg["routing_delta"] < 0.5
    assert set(cfg["limits"]) == set(cfg["limits_reason"]) and cfg["check_records"] == 8
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        assert json.load(f) == {"arrivals": "backlog", "pool_records": 64, "record_tokens": 4096,
                                "window_records": 2, "warmup_windows": 4}


def test_the_manifest_has_the_cell_and_its_fourteen_metrics():
    manifest = harness.load_manifest(ROOT)
    assert [m["name"] for m in harness.metrics_of(manifest, "per_layer", CELL)] == list(METRICS)
    assert {m["name"] for m in harness.metrics_of(manifest, "end_to_end", CELL)} == {"records_per_s", "setup_s"}
    entry = {c["name"]: c for c in manifest["configs"]}["kimi_k2_7_code"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert manifest["workloads"][-1]["name"] == CELL and manifest["workloads"][-1]["chips"] == 1


# -- the readers on op names recorded on the chip -------------------------------

@pytest.fixture(scope="module")
def recorded():
    """One run of the real cell's step on a v5e: the op events of the XLA Ops
    line (name, start, end) and the program's own event."""
    with gzip.open(os.path.join(DATA, "kimi_step_ops.json.gz"), "rt") as f:
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
    reader = {"lm": lm, "moe": moe, "counters": counters, "trace": trace_reader}[spec["reader"]]
    return reader.read(state, **spec["args"])


@pytest.mark.parametrize("metric", ["lm_step_mfu.kimi", "flash_attention_roofline_share.kimi",
                                    "expert_matmul_roofline_share.kimi", "moe_share_of_step.kimi",
                                    "latent_attention_share_of_step.kimi"])
def test_a_trace_metric_reads_what_the_chip_printed(recorded, metric):
    trace, doc = recorded
    value = _read(metric, _state(trace, {"model.0.tokens": 8192, "model.0.batches": 1}))
    assert value == pytest.approx(doc["expect"][metric], rel=1e-6)
    assert 0 < value < 100


def test_the_step_time_is_the_programs_own_event(recorded):
    trace, doc = recorded
    assert _read("lm_step_device_ms.kimi", _state(trace, {})) == pytest.approx(doc["modules"][0][2] / 1e6)


def test_the_patterns_find_their_layers_and_nothing_else(recorded):
    _, doc = recorded
    names = [lm.produced(n) for n, _, _ in doc["ops"]]
    products = _spec("expert_matmul_roofline_share.kimi")["args"]["pattern"]
    routed = _spec("moe_share_of_step.kimi")["args"]["pattern"]
    latent = _spec("latent_attention_share_of_step.kimi")["args"]["pattern"]
    kernel = _spec("flash_attention_roofline_share.kimi")["args"]["pattern"]
    # Two grouped products a pass in each of 6 expert layers, one kernel call in each of 7 layers.
    found = [n for n in names if re.search(products, n)]
    assert len(found) >= 12 and len(found) % 2 == 0 and all("f32[4096," in n for n in found)
    assert sum(bool(re.search(kernel, n)) for n in names) == 7
    assert all(re.search(routed, n) for n in found) and not any(re.search(latent, n) for n in found)
    assert all(re.search(latent, n) for n in names if re.search(kernel, n))
    # One loop of passes a routed layer; no op is counted for both layers.
    assert sum(n.startswith("%while") for n in names) >= 6
    assert not any(re.search(routed, n) and re.search(latent, n) for n in names)
    # What neither claims: the dense MLP, the shared expert, the output projections, the head.
    for shape in ("[2,4096,18432]", "[2,4096,2048]", "[2,20480]"):
        mine = [n for n in names if shape in n.split(" = ")[1]]
        assert mine and not any(re.search(routed, n) or re.search(latent, n) for n in mine), shape
    # No buffer of the routed layer has a row for every one of the 65,536 pairs.
    assert not any(re.search(r"\[65536,\d", n) for n in names)
    assert any("[65536]" in n for n in names)


def test_a_pattern_that_matches_nothing_raises(recorded):
    trace, _ = recorded
    with pytest.raises(LookupError):
        lm.read(_state(trace, {}), what="op_share", module=r"^jit_call\b", pattern=r"no_such_tensor")


@pytest.mark.parametrize("metric,registry,want", [
    ("expert_rows_per_token.kimi", {"model.0.expert_rows": 12288, "model.0.tokens": 8192}, 1.5),
    ("expert_rows_per_token.kimi", {"model.0.tokens": 8192}, None),  # a program that counts no rows
    ("expert_rows_max_share.kimi", {"model.0.expert_rows_max": 1024, "model.0.expert_rows": 12288}, 100 / 12),
    ("expert_rows_max_share.kimi", {}, None),
    ("expert_passes_per_batch.kimi", {"model.0.expert_passes": 60, "model.0.batches": 10}, 6.0),
    ("expert_passes_per_batch.kimi", {"model.0.batches": 10}, None),  # the parent counts no passes
    ("operator_open_s.kimi", {"model.0.open_s": {"total_s": 4.5, "count": 1}}, 4.5),
])
def test_the_counter_metrics(metric, registry, want):
    got = _read(metric, _state(None, registry))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", METRICS)
def test_a_metric_of_the_cell_reads_nothing_and_does_not_raise_without_a_trace_or_a_counter(metric):
    # What the parent's program gives the new files: no counter of this PR's, and in an untraced run no trace.
    assert _read(metric, _state(None, {})) is None
