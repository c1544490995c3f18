"""Mellum 2 (``mellum``) at a small preset on the CPU (8 layers, two periods
of sliding, sliding, sliding, full; hidden 256, 4 query heads on 2, heads of
64, a window of 64 at 256 positions so that the band is crossed, 8 experts
top-2, all held), seeded random weights: the program against the plain
reference, free-running at float32 and held to the program's routing at
bfloat16; yarn's frequencies and scale at the published numbers against
values worked by hand; the window's edge; every planted fault; the counts
through the operator; a tiny cell through the harness, with its controls; the
real configuration's file and the work it counts at the published widths."""

import json
import math
import os
import re
import shutil
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import controls, harness, trace_reduce
from benchmark.jobs import _zoo, stream_lm_routed
from benchmark.jobs.stream_infer import compare
from benchmark.readers import band, counters
from benchmark.reference import afmoe
from benchmark.reference import mellum as ref
from flink_tensorflow_tpu.models import get_model_def
from flink_tensorflow_tpu.models.zoo import mellum
from flink_tensorflow_tpu.ops import mla
from flink_tensorflow_tpu.ops.flash_attention import flash_attention, tile_plan

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "mellum2_12b_a2_5b.score_32k"
with open(os.path.join(ROOT, "benchmark", "configs", "mellum2_12b_a2_5b.json")) as _f:
    CONFIG = json.load(_f)

SMALL_SIZES = dict(vocab_size=512, hidden_size=256, moe_intermediate_size=128, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=64, sliding_window=64, num_experts=8, num_experts_per_tok=2)
SMALL = dict(CONFIG["model"], **SMALL_SIZES)
LAYERS = 8
T = 256


@pytest.fixture(autouse=True)
def _query_blocks_of_64(monkeypatch):
    # The reference's query blocks (reference/afmoe.py) are 1,024 rows at the cell's size; at 256 positions
    # blocks of 64 make the band start past key 0 in the later blocks, as it does on the chip.
    monkeypatch.setattr(afmoe, "QUERY_BLOCK", 64)


def program(model, params, tokens, compute_dtype="float32"):
    mdef = get_model_def("mellum", seq_len=tokens.shape[1], compute_dtype=compute_dtype, **model)
    tree = _zoo.program_tree(params, jax.eval_shape(mdef.init_fn, jax.random.key(0)), CONFIG["param_rules"])
    return mdef, tree, jax.jit(mdef.methods["serve"].fn)(tree, {"tokens": jnp.asarray(tokens)})


def worst(got, want):
    """The largest difference, in units of the reference logits' spread."""
    return float(np.abs(np.asarray(got) - np.asarray(want)).max() / np.asarray(want).std())


@pytest.fixture(scope="module")
def small():
    params = ref.make_params(SMALL, 2**31 + 5)
    tokens = ref.make_tokens(SMALL, 2, T, 11)
    afmoe.QUERY_BLOCK, before = 64, afmoe.QUERY_BLOCK
    try:
        chosen, rms = [], []
        want = ref.forward(params, tokens, SMALL, chosen=chosen, rms=rms)
    finally:
        afmoe.QUERY_BLOCK = before
    return params, tokens, want, np.stack(chosen), rms


def test_program_and_reference_choose_alike_and_agree_at_float32(small):
    params, tokens, want, chosen, _ = small
    assert want.shape == (2, 512) and 1.5 < float(want.std()) < 4.0
    _, _, out = program(SMALL, params, tokens)
    assert out["routing"].shape == (2, T, LAYERS, 2) and out["routing"].dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(out["routing"]), chosen)
    assert worst(out["logits"], want) < 1e-4


def test_at_bfloat16_the_program_agrees_with_the_reference_held_to_its_routing(small):
    params, tokens, want, _, _ = small
    _, _, out = program(SMALL, params, tokens, compute_dtype="bfloat16")
    routed = []
    # In logit units; the preset's residual (hidden 256, rms up to 7) carries more rounding than the cell's.
    held = ref.forward(params, tokens, SMALL, routing=np.asarray(out["routing"]), routed=routed, routing_delta=0.5)
    assert [r["pairs"] for r in routed] == [T * LAYERS * 2] * 2
    assert sum(r["wrong"] for r in routed) == 0 and sum(r["near"] for r in routed) > 0
    assert 0.1 < max(r["gap_max"] for r in routed) < 0.5
    logits, labels, scores = np.asarray(out["logits"]), np.asarray(out["label"]), np.asarray(out["score"])
    numbers = compare(np.asarray(held), logits, labels, scores)
    free = compare(np.asarray(want), logits, labels, scores)
    # Held, the rounding alone (0.082 here); free-running, what a few pairs on other experts add (0.28).
    assert numbers["logit_rms_err"] < 0.12 and free["logit_rms_err"] > 2 * numbers["logit_rms_err"], (numbers, free)
    assert numbers["label_gap"] < CONFIG["limits"]["label_gap"]
    # Reported and not compared (the file's not_compared): under the largest reading of the cell's sound runs.
    assert "score_log_err" not in CONFIG["limits"] and numbers["score_log_err"] < 0.740


def test_the_terms_reach_the_residual_near_rms_one(small):
    *_, rms = small
    assert len(rms) == 2 * LAYERS
    for layer in rms:  # spreads set for the cell's 32,768 positions and top-8; here 256 and top-2
        assert 0.7 < layer["op"] < 2.5 and 0.7 < layer["ff"] < 2.5, layer
    assert 1.5 < rms[0]["residual"] < 3.0 < rms[LAYERS - 1]["residual"] < 9.0


# -- positions: yarn by hand, the window's edge ------------------------------------------

def test_yarns_frequencies_and_scale_at_the_published_numbers_against_numbers_worked_by_hand():
    full = CONFIG["model"]["rope_parameters"]["full_attention"]
    # b(32 turns) = 128 ln(8192 / (64 pi)) / (2 ln 500,000) = 18.08, b(1 turn) = 34.98: the ramp from pair 18 to 35.
    assert math.floor(128 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(500000))) == 18
    assert math.ceil(128 * math.log(8192 / (2 * math.pi)) / (2 * math.log(500000))) == 35
    f = lambda j: 500000.0 ** (-2 * j / 128)  # noqa: E731
    for inv in (mla.yarn_inv_freq(128, 500000.0, full), ref.yarn_frequencies(full, 128)):
        assert inv.shape == (64,)
        np.testing.assert_allclose(inv[:19], [f(j) for j in range(19)], rtol=1e-12)          # as they were
        np.testing.assert_allclose(inv[35:], [f(j) / 16 for j in range(35, 64)], rtol=1e-12)  # 16 times slower
        assert inv[26] == pytest.approx(f(26) * (9 / 17 + (8 / 17) / 16), rel=1e-12)         # (26 - 18) / (35 - 18)
    np.testing.assert_allclose(ref.yarn_frequencies(full, 128), mla.yarn_inv_freq(128, 500000.0, full), rtol=1e-12)
    # attention_factor 0.1 ln 16 + 1 on the full layers' cos and sin, and only there.
    assert full["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    sliding = mellum.rope_of(CONFIG["model"]["rope_parameters"]["sliding_attention"], 128)
    theta, inv, scale = mellum.rope_of(full, 128)
    assert sliding == (500000.0, None, 1.0) and theta == 500000.0 and scale == pytest.approx(1.2772588722239782)
    np.testing.assert_allclose(inv, mla.yarn_inv_freq(128, 500000.0, full), rtol=0)
    cos_full, _ = ref.rope_tables(CONFIG["model"], "full_attention", 4)
    cos_sliding, _ = ref.rope_tables(CONFIG["model"], "sliding_attention", 4)
    assert float(cos_full[0, 0]) == pytest.approx(1.2772588722239782) and float(cos_sliding[0, 0]) == 1.0


def _edge_values(t, edge_rows):
    """Values that are 0 but at two keys: column 0 at ``i - 1024``, column 1 at ``i - 1023`` for the row ``i``."""
    v = np.zeros((1, t, 1, 128), np.float32)
    i = edge_rows
    v[0, i - 1024, 0, 0] = 1.0
    v[0, i - 1023, 0, 1] = 1.0
    return v


@pytest.mark.parametrize("who", ["program_kernel", "reference"])
def test_the_window_edge_key_i_minus_1023_is_seen_and_i_minus_1024_is_not(who, monkeypatch):
    t, i = 2048, 1500
    # q = 0: every key a row sees weighs alike, 1 / 1,024 over a full band.
    q = np.zeros((1, t, 1, 128), np.float32)
    k = np.random.default_rng(0).normal(size=(1, t, 1, 128)).astype(np.float32)
    v = _edge_values(t, i)
    if who == "program_kernel":
        out = np.asarray(flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True, window=1024))[0, :, 0]
    else:
        monkeypatch.setattr(afmoe, "QUERY_BLOCK", 512)
        out = np.asarray(ref.attention(jnp.asarray(q[0]), jnp.asarray(k[0]), jnp.asarray(v[0]),
                                       ref.seen_by(1024)))[:, 0]
    assert out[i, 0] == 0.0
    assert out[i, 1] == pytest.approx(1 / 1024, rel=1e-5)


# -- the planted faults ----------------------------------------------------------------

@pytest.mark.parametrize("fault", ["no_window", "band_one_chunk_lower"])
def test_a_fault_of_the_band_moves_only_what_lies_past_the_window(small, fault):
    params, tokens, *_ = small
    assert worst(ref.forward(params, tokens[:1, :64], SMALL, fault=fault),
                 ref.forward(params, tokens[:1, :64], SMALL)) < 1e-6


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_answer_or_the_routes(small, fault):
    params, tokens, want, chosen, _ = small
    used = []
    got = ref.forward(params, tokens, SMALL, fault=fault, chosen=used)
    assert worst(got, want) > 1e-3 or (np.stack(used) != chosen).mean() > 1e-3


def test_a_wrong_choice_of_experts_is_told_by_the_routings_near_share_alone(small):
    params, tokens, *_ = small
    used, routed = [], []
    got = ref.forward(params, tokens, SMALL, fault="ninth_for_best", chosen=used)
    held = ref.forward(params, tokens, SMALL, routing=np.stack(used), routed=routed,
                       routing_delta=CONFIG["routing_delta"])
    # Held to the wrong choice, the reference weighs the same experts: its logits agree.
    assert worst(held, got) < 1e-5
    pairs, wrong, near = (sum(r[key] for r in routed) for key in ("pairs", "wrong", "near"))
    # Of a token's two slots one is its 3rd best, under its 2nd by the gap between them, and the other is as it
    # should be: one pair in k off the reference's choice, nearly all within routing_delta.
    assert wrong + near == pairs // 2 and near / pairs > CONFIG["limits"]["routing_near_share"]


def test_an_unknown_fault_is_refused(small):
    params, tokens, *_ = small
    with pytest.raises(ValueError):
        ref.forward(params, tokens, SMALL, fault="no_such_fault")


# -- counts made on the device, through the operator ------------------------------------

def test_the_stream_job_counts_the_attention_tiles_and_every_pair_on_the_operators_track(small):
    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue

    params, *_ = small
    n = 3  # the last window holds one record and one of padding
    tokens = ref.make_tokens(SMALL, n, T, 17)
    mdef, tree, want = program(SMALL, params, tokens)
    assert mdef.methods["serve"].count_names == ("expert_rows", "expert_rows_max", "expert_passes", "attention_tiles")
    env = StreamExecutionEnvironment(parallelism=1)
    records = [TensorValue({"tokens": tokens[i]}, {"id": i}) for i in range(n)]
    out = (env.from_collection(records)
           .count_window(2)
           .apply(ModelWindowFunction(mdef.to_model(tree), policy=BucketPolicy(fixed_batch=2),
                                      warmup_batches=(2,), outputs=("logits", "routing")),
                  name="model", parallelism=1)
           .sink_to_list())
    registry = env.execute("mellum_small", timeout=300).metrics
    assert sorted(r.meta["id"] for r in out) == list(range(n))
    for r in out:
        np.testing.assert_allclose(r["logits"], np.asarray(want["logits"])[r.meta["id"]], rtol=1e-4, atol=1e-4)
    # A record's tiles, real records only: 4 heads x the kernel's own count of its eight calls.
    plans = [tile_plan(T, T, 64, jnp.float32, True, window=64 if kind == "sliding_attention" else None)
             for kind in SMALL["layer_types"]]
    assert registry["model.0.attention_tiles"] == n * 4 * sum(p.tiles_visited for p in plans)
    # Every pair is held: 8 layers x top-2 rows a token, one pass a layer.
    assert registry["model.0.tokens"] == n * T and registry["model.0.expert_passes"] == 2 * LAYERS
    assert registry["model.0.expert_rows"] == n * T * LAYERS * 2


@pytest.mark.parametrize("change,match", [
    (dict(norm_topk_prob=False), "as published"), (dict(attention_bias=True), "as published"),
    (dict(tie_word_embeddings=True), "as published"), (dict(mlp_layer_types=["dense"] + ["sparse"] * 7), "every layer sparse"),
    (dict(layer_types=["sliding_attention"] * 4), "layer_types"),
    (dict(rope_parameters={"full_attention": {"rope_type": "linear", "rope_theta": 1e4, "factor": 2},
                           "sliding_attention": {"rope_type": "default", "rope_theta": 1e4}}), "default or yarn")])
def test_a_config_the_model_does_not_build_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        get_model_def("mellum", **dict(SMALL, **change))


# -- a tiny cell through the harness, and its controls ------------------------------------

TINY = dict(SMALL_SIZES, hidden_size=64, moe_intermediate_size=32, head_dim=16, sliding_window=8)
TINY_LIMITS = {"logit_rms_err": 0.001, "label_gap": 0.01, "score_log_err": 0.01, "routing_wrong_share": 0.0}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout_mellum"))
    bench = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    shutil.copy(os.path.join(ROOT, "benchmark", "peaks.json"), bench)
    cfg = json.loads(json.dumps(CONFIG))
    cfg.update(TINY)
    cfg["model"].update(TINY)
    cfg["program_kwargs"]["compute_dtype"] = "float32"
    cfg.update(name="tiny_mellum", check_records=2, limits=TINY_LIMITS, routing_delta=1e-4)
    with open(os.path.join(bench, "configs", "tiny_mellum.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "workloads", "tiny_mellum.score.json"), "w") as f:
        json.dump({"arrivals": "backlog", "pool_records": 4, "record_tokens": 16, "window_records": 1}, f)
    manifest = harness.load_manifest(ROOT)
    manifest["configs"] = [{"name": "tiny_mellum", "source": "test", "reduced": [], "why": "test",
                            "file": "benchmark/configs/tiny_mellum.json"}]
    manifest["workloads"] = [{"name": "tiny_mellum.score", "config": "tiny_mellum", "traffic": "score", "chips": 1,
                              "why": "test"}]
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_mellum.score"] if m["name"] == "records_per_s" else []
    manifest["per_layer"] = []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_a_mellum_cell_added_as_files_runs(tiny_root):
    out = harness.run_cell(root=tiny_root, workload="tiny_mellum.score", seed=2**31 + 7, seconds=1.0, trace=False,
                           devices=jax.devices()[:1], t0=time.monotonic())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0 and set(out["metrics"]) == {"records_per_s", "setup_s"}


@pytest.fixture(scope="module")
def verdicts(tiny_root):
    _, _, cfg, mix = harness.load_cell(tiny_root, "tiny_mellum.score")
    return controls.verdicts(stream_lm_routed.controls(cfg, mix, 5), cfg["limits"])


@pytest.mark.parametrize("reading", ["control_float8_e4m3fn", "control_float8_e5m2"] + ["fault_" + f for f in ref.FAULTS])
def test_a_control_is_refused_at_the_cells_limits(verdicts, reading):
    verdict = verdicts[reading]
    assert not verdict["correct"] and verdict["fails"], verdict


# -- the real configuration, its metrics and the work it counts ------------------------------

def test_the_real_file_holds_the_published_config_twice_and_states_the_cuts():
    cfg = CONFIG
    assert all(cfg[key] == value for key, value in cfg["model"].items())
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types"]
    assert list(cfg["reduced_from"]) == cfg["reduced"]
    assert cfg["num_hidden_layers"] == 8 and cfg["mlp_layer_types"] == ["sparse"] * 8
    assert cfg["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    # Every width, both RoPE sections, the window, 64 experts top-8 and the whole vocabulary: as published.
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]) == (2304, 7168, 896)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (32, 4, 128)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"], cfg["norm_topk_prob"]) == (64, 8, True)
    assert (cfg["sliding_window"], cfg["vocab_size"], cfg["rms_norm_eps"]) == (1024, 98304, 1e-6)
    assert cfg["rope_parameters"] == {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert (cfg["tie_word_embeddings"], cfg["attention_bias"], cfg["max_window_layers"]) == (False, False, 0)
    assert "pipeline stages" in cfg["deployment"]
    assert {"no_qk_norm", "mtp_head_left_out", "layer_types_decide", "spreads", "router", "rope"} <= set(cfg["assumed"])
    assert cfg["limits"]["routing_wrong_share"] == 0.0 and set(cfg["limits"]) == set(cfg["limits_reason"])
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        assert json.load(f) == {"arrivals": "backlog", "pool_records": 32, "record_tokens": 32768,
                                "window_records": 1, "warmup_windows": 4}


def test_the_work_at_the_published_widths_against_a_count_by_hand():
    model = CONFIG["model"]
    # By hand: 21.23 M a layer's attention, 396.4 M its experts, 417.7 M a layer, 453.0 M the ends.
    shapes = ref.leaf_shapes(model)
    count = lambda prefix: sum(int(np.prod(s)) for n, s in shapes.items() if n.startswith(prefix))  # noqa: E731
    assert count("layers.0.attn.") == pytest.approx(21.23e6, rel=1e-3)
    assert count("layers.0.moe.w") == 64 * 3 * 2304 * 896 and count("layers.0.") == pytest.approx(417.7e6, rel=1e-3)
    assert count("") == pytest.approx(3795.0e6, rel=1e-3)  # 7.59 GB in bfloat16
    # 37.19 TFLOP of products, 6 x 0.541 of band, 2 x 8.80 of triangle: 58.03 a record.
    assert ref.forward_flops(model, 32768) == pytest.approx(58.03e12, rel=1e-3)
    assert ref.attention_kernel_cost(model, 32768, 1, window=1024) == (541174267904, 603979776)
    assert ref.attention_kernel_cost(model, 32768, 1)[0] == pytest.approx(8.80e12, rel=1e-3)
    # A layer's grouped products: 262,144 rows through all 64 experts.
    flops, moved = ref.expert_kernel_cost(model, 32768, 1)
    assert flops == 2 * 262144 * 3 * 2304 * 896 and moved == 2 * (64 * 3 * 2304 * 896 + 2 * 262144 * 2304)
    assert flops / 197e12 > moved / 819e9  # compute-bound
    # The kernel's tiles: the band of 1,024 visits 189 a head, the triangle 2,080.
    plans = [tile_plan(32768, 32768, 128, jnp.bfloat16, True, window=w).tiles_visited for w in (1024, None)]
    assert plans == [189, 2080] and (6 * 189 + 2 * 2080) * 32 / 32768 == pytest.approx(5.1699, rel=1e-4)


def test_the_manifest_has_the_cell_and_its_metrics():
    manifest = harness.load_manifest(ROOT)
    names = [m["name"] for m in harness.metrics_of(manifest, "per_layer", CELL)]
    assert len(names) == 16 and all(n.endswith(".mellum") for n in names)
    assert "expert_passes_per_batch.mellum" not in names  # the whole-layer path always reads 1 a layer
    assert {m["name"] for m in harness.metrics_of(manifest, "end_to_end", CELL)} == {"records_per_s", "setup_s"}
    # By name and not by place: a later PR appends its own cells after this one.
    entry = {c["name"]: c for c in manifest["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("mellum2_12b_a2_5b", "score_32k", 1)
    config = {c["name"]: c for c in manifest["configs"]}["mellum2_12b_a2_5b"]
    assert config["reduced"] == CONFIG["reduced"] and config["source"] == CONFIG["source"]


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", metric + ".json")) as f:
        return json.load(f)


def _state(trace, registry):
    _, cell, cfg, mix = harness.load_cell(ROOT, CELL)
    run = {"counters": registry, "window": {"batch_records": mix["window_records"], "record_tokens": mix["record_tokens"]}}
    return {"ctx": types.SimpleNamespace(traced=trace), "run": run, "cell": cell, "config": cfg,
            "peaks": trace_reduce.peaks_for(ROOT, "TPU v5 lite")}


@pytest.mark.parametrize("line,routed", [
    ("%fusion.650 = f32[32768,64]{0,1:T(8,128)S(1)} fusion(%multiply_multiply_fusion.9)", True),  # the router's logits
    ("%sort.17 = (f32[32768,64]{1,0}, s32[32768,64]{1,0}) sort(%fusion.650, %iota.3)", True),  # top-8 of 64
    ("%copy-done.41 = s32[32768,64]{1,0} copy-done(%copy-start.41)", True),  # top-k's indices
    ("%fusion.71 = f32[32768,8]{1,0} fusion(%fusion.650, %copy.12)", True),  # the chosen weights
    ("%fusion.55 = bf16[262144,2304]{1,0} fusion(%p.1, %reshape.8)", True),  # the rows gathered
    ("%pad_add_fusion.3 = s32[65]{0} fusion(%reduce.7)", True),  # group offsets
    # RoPE's cos and sin, [32768, 64] with heads of 128, and its inverse frequencies, [64]:
    ("%fusion.369 = (f32[32768,64]{1,0:T(8,128)S(1)}, f32[32768,64]{1,0:T(8,128)S(1)}) fusion(%copy-done.150)", False),
    ("%get-tuple-element.1713 = f32[32768,64]{1,0:T(8,128)S(1)} get-tuple-element(%fusion.369), index=0", False),
    ("%constant.88 = f32[64]{0} constant({1, 0.81, 0.66})", False),
    ("%fusion.689 = f32[32768,2304]{1,0} fusion(%add.3, %fusion.41)", False),  # every activation's shape
])
def test_the_routed_layers_pattern_takes_the_router_and_not_ropes_tables(line, routed):
    from benchmark.readers import lm

    pattern = _spec("moe_share_of_step.mellum")["args"]["pattern"]
    assert bool(re.search(pattern, lm.produced(line))) == routed


def test_the_band_reader_prices_each_kind_of_call_by_its_own_window():
    # Six band calls of 4 ms and two triangle calls of 60 ms in one run of the step.
    ops = [(f"%flash_attention_window.{i} = bf16[32,32768,128]{{2,1,0}} custom-call(...)", 4e6 * i, 4e6 * (i + 1))
           for i in range(6)]
    ops += [(f"%flash_attention.{i} = bf16[32,32768,128]{{2,1,0}} custom-call(...)", 24e6 + 60e6 * i, 84e6 + 60e6 * i)
            for i in range(2)]
    rows = [("/device:TPU:0", trace_reduce.OPS_LINE, n, s, e - s) for n, s, e in ops]
    rows.append(("/device:TPU:0", trace_reduce.MODULES_LINE, "jit_call(1)", 0, 144e6))
    state = _state(trace_reduce.Trace(rows), {})
    assert band.read(state, **_spec("window_attention_roofline_share.mellum")["args"]) == pytest.approx(
        100 * 0.54117e12 / 197e12 / 4e-3, rel=1e-3)
    assert band.read(state, **_spec("flash_attention_roofline_share.mellum")["args"]) == pytest.approx(
        100 * 8.7964e12 / 197e12 / 60e-3, rel=1e-3)


@pytest.mark.parametrize("metric,registry,want", [
    ("attention_tiles_per_token.mellum", {"model.0.attention_tiles": 169408, "model.0.tokens": 32768}, 5.1699),
    ("attention_tiles_per_token.mellum", {"model.0.tokens": 32768}, None),  # the parent counts no tiles
    ("expert_rows_per_token.mellum", {"model.0.expert_rows": 64 * 32768, "model.0.tokens": 32768}, 64.0),
    ("expert_rows_max_share.mellum", {"model.0.expert_rows_max": 8 * 4096, "model.0.expert_rows": 64 * 32768}, 1.5625),
])
def test_the_counter_metrics(metric, registry, want):
    got = counters.read(_state(None, registry), **_spec(metric)["args"])
    assert got == (None if want is None else pytest.approx(want, rel=1e-4))


# (The pulse reader reads the job's flight ring, which a bare state has none of: tests/benchmark/test_pulse_reader.py.)
@pytest.mark.parametrize("metric", [m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]
                                    if m["name"].endswith(".mellum") and not m["name"].startswith("pulse_")],
                         ids=lambda m: m["name"])
def test_a_metric_of_the_cell_reads_nothing_and_does_not_raise_without_a_trace_or_a_counter(metric):
    import importlib

    spec = _spec(metric["name"])
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert reader.read(_state(None, {}), **spec["args"]) is None
