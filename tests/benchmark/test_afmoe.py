"""Trinity-Large (``afmoe``) at a small preset on the CPU (1 dense + 4 expert
layers, sliding, sliding, sliding, sliding, full; hidden 256, 4 query heads on
2, heads of 64, a window of 64 at 256 positions so that the band is crossed, 16
experts of which 4 are held, a shared expert), seeded random weights: the
program against the plain reference, free-running at float32 and held to the
program's routing at bfloat16; the four shares of a layer against the uncut
reference; every planted fault; the counts through the operator; a tiny cell
through the harness, with its controls; the real configuration's file and the
work it counts at the published widths."""

import json
import os
import shutil
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import controls, harness, trace_reduce
from benchmark.jobs import _zoo, stream_lm_routed
from benchmark.readers import band, counters
from benchmark.reference import afmoe as ref
from flink_tensorflow_tpu.models import get_model_def
from flink_tensorflow_tpu.ops import moe
from flink_tensorflow_tpu.ops.flash_attention import tile_plan

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "trinity_large_preview.score_32k"
with open(os.path.join(ROOT, "benchmark", "configs", "trinity_large_preview.json")) as _f:
    CONFIG = json.load(_f)

SMALL_SIZES = dict(vocab_size=512, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=64, sliding_window=64,
                   num_experts=4, router_experts=16, first_expert=0)
SMALL = dict(CONFIG["model"], **SMALL_SIZES)
EXPERT_LAYERS = 4
T = 256


@pytest.fixture(autouse=True)
def _query_blocks_of_64(monkeypatch):
    # The reference's query blocks are 1,024 rows at the cell's size; at 256 positions blocks of 64 make
    # the band start past key 0 in the later blocks, as it does on the chip.
    monkeypatch.setattr(ref, "QUERY_BLOCK", 64)


def program(model, params, tokens, compute_dtype="float32"):
    mdef = get_model_def("afmoe", seq_len=tokens.shape[1], compute_dtype=compute_dtype, **model)
    tree = _zoo.program_tree(params, jax.eval_shape(mdef.init_fn, jax.random.key(0)), CONFIG["param_rules"])
    return mdef, tree, jax.jit(mdef.methods["serve"].fn)(tree, {"tokens": jnp.asarray(tokens)})


def worst(got, want):
    """The largest difference, in units of the reference logits' spread."""
    return float(np.abs(np.asarray(got) - np.asarray(want)).max() / np.asarray(want).std())


@pytest.fixture(scope="module")
def small():
    params = ref.make_params(SMALL, 2**31 + 5)
    tokens = ref.make_tokens(SMALL, 2, T, 11)
    ref.QUERY_BLOCK, before = 64, ref.QUERY_BLOCK
    try:
        chosen, rms = [], []
        want = ref.forward(params, tokens, SMALL, chosen=chosen, rms=rms)
    finally:
        ref.QUERY_BLOCK = before
    return params, tokens, want, np.stack(chosen), rms


def test_program_and_reference_choose_alike_and_agree_at_float32(small):
    params, tokens, want, chosen, _ = small
    assert want.shape == (2, 512) and 1.5 < float(want.std()) < 4.0
    _, _, out = program(SMALL, params, tokens)
    assert out["routing"].shape == (2, T, EXPERT_LAYERS, 4) and out["routing"].dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(out["routing"]), chosen)
    assert chosen.max() >= SMALL_SIZES["num_experts"]  # experts held elsewhere are chosen too
    assert worst(out["logits"], want) < 1e-4


def test_at_bfloat16_the_program_agrees_with_the_reference_held_to_its_routing(small):
    params, tokens, _, _, _ = small
    _, _, out = program(SMALL, params, tokens, compute_dtype="bfloat16")
    routed = []
    held = ref.forward(params, tokens, SMALL, routing=np.asarray(out["routing"]), routed=routed,
                       routing_delta=0.05)
    assert [r["pairs"] for r in routed] == [T * EXPERT_LAYERS * 4] * 2
    assert sum(r["wrong"] for r in routed) == 0 and max(r["gap_max"] for r in routed) < 0.05
    assert worst(out["logits"], held) < 0.25


def test_the_terms_reach_the_residual_at_rms_one(small):
    *_, rms = small
    assert len(rms) == 2 * 5
    for layer in rms:  # each term is normed by its post-norm, whose weights are 1 +- 0.1
        assert 0.9 < layer["op"] < 1.1 and 0.9 < layer["ff"] < 1.1, layer
    assert 1.3 < rms[0]["residual"] < 2.0 < rms[4]["residual"] < 4.0


@pytest.mark.parametrize("fault", ["no_window", "band_one_chunk_lower"])
def test_a_fault_of_the_band_moves_only_what_lies_past_the_window(small, fault):
    # (That every fault moves the answer or the routes: test_a_control_is_refused_at_the_cells_limits.)
    params, tokens, want, _, _ = small
    assert worst(ref.forward(params, tokens[:1, :64], SMALL, fault=fault),
                 ref.forward(params, tokens[:1, :64], SMALL)) < 1e-6


def test_an_unknown_fault_is_refused(small):
    params, tokens, *_ = small
    with pytest.raises(ValueError):
        ref.forward(params, tokens, SMALL, fault="no_such_fault")


def test_the_reference_attends_the_band_from_positions(monkeypatch):
    monkeypatch.setattr(ref, "KEY_SPAN", 96)  # a full layer's blocks see 96, 192 and 200 keys
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(size=(200, h, 16)), jnp.float32) for h in (4, 2, 2))
    whole = ref.attention(q, k, v, None)
    for window in (1, 37, 199, None):
        got = ref.attention(q, k, v, window)
        s = np.einsum("thd,shd->hts", np.asarray(q, np.float64), np.repeat(np.asarray(k, np.float64), 2, 1)) / 4
        i, j = np.arange(200)[:, None], np.arange(200)[None, :]
        s = np.where((i - j >= 0) & (i - j < (window or 200)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hts,shd->thd", w / w.sum(-1, keepdims=True), np.repeat(np.asarray(v, np.float64), 2, 1))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.attention(q, k, v, 200)), np.asarray(whole), rtol=1e-6, atol=1e-6)


# -- the shares of a layer ---------------------------------------------------------

def _layer_of(params, i):
    prefix = f"layers.{i}."
    return {name[len(prefix):]: w for name, w in params.items() if name.startswith(prefix)}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5)], ids=["ragged_dot"])  # gmm's shares: test_kimi_k2.py
def test_the_four_shares_of_a_layer_add_up_to_the_uncut_references_whole_layer(dtype, tol):
    uncut = dict(SMALL, num_experts=16, router_experts=16)
    params = ref.make_params(uncut, 7)
    p = _layer_of(params, 2)
    fns = ref._compiled(json.dumps(uncut, sort_keys=True), None, None)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 24, 256)).astype(np.float32))
    shared = [fns["shared_ff"]({n: w for n, w in p.items() if n.startswith("shared.")}, x[b]) for b in range(2)]
    whole = [ref.routed_ff(fns, p, x[b], uncut)[0] + shared[b] for b in range(2)]  # the layer's whole term
    shares = [moe.routed_experts(x, p["moe.router"], p["moe.bias"], p["moe.w13"][lo:lo + 4], p["moe.w2"][lo:lo + 4],
                                 k=4, first=lo, scaling=uncut["route_scale"], eps=1e-20,
                                 compute_dtype=jnp.dtype(dtype)) for lo in (0, 4, 8, 12)]
    # What every chip computes alike, the shared expert (and attention, before it), is counted once.
    got = sum(s.out for s in shares) + jnp.stack(shared)
    np.testing.assert_allclose(got, jnp.stack(whole), rtol=tol, atol=tol * float(jnp.abs(jnp.stack(whole)).max()))
    assert sum(s.rows for s in shares).tolist() == [24 * 4] * 2
    # And a share is what the reference gives when told the same share.
    share_model = dict(uncut, num_experts=4, first_expert=8)
    cut = dict(p, **{"moe.w13": p["moe.w13"][8:12], "moe.w2": p["moe.w2"][8:12]})
    share_fns = ref._compiled(json.dumps(share_model, sort_keys=True), None, None)
    want = jnp.stack([ref.routed_ff(share_fns, cut, x[b], share_model)[0] for b in range(2)])
    np.testing.assert_allclose(shares[2].out, want, rtol=tol, atol=tol * float(jnp.abs(want).max()))


# -- counts made on the device, through the operator ------------------------------------

def test_the_stream_job_counts_the_attention_tiles_and_the_rows_on_the_operators_track(small):
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
    registry = env.execute("afmoe_small", timeout=300).metrics
    assert sorted(r.meta["id"] for r in out) == list(range(n))
    for r in out:
        np.testing.assert_allclose(r["logits"], np.asarray(want["logits"])[r.meta["id"]], rtol=1e-4, atol=1e-4)
    # A record's tiles, real records only: 4 heads x the kernel's own count of its five calls.
    plans = [tile_plan(T, T, 64, jnp.float32, True, window=64 if kind == "sliding_attention" else None)
             for kind in SMALL["layer_types"]]
    assert registry["model.0.attention_tiles"] == n * 4 * sum(p.tiles_visited for p in plans)
    assert registry["model.0.tokens"] == n * T and registry["model.0.expert_passes"] == 2 * EXPERT_LAYERS
    assert registry["model.0.expert_rows"] == int(np.asarray(want["expert_rows"]).sum())


@pytest.mark.parametrize("change,match", [
    (dict(n_group=8, topk_group=4), "one group"), (dict(score_func="softmax"), "as published"),
    (dict(route_norm=False), "as published"), (dict(rope_scaling={"type": "yarn", "factor": 8}), "as published"),
    (dict(layer_types=["sliding_attention"] * 4), "layer_types"), (dict(first_expert=14), "of the router's 16"),
    (dict(num_dense_layers=5), "at least one routed layer")])
def test_a_config_the_builder_does_not_build_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        get_model_def("afmoe", **dict(SMALL, **change))


# -- a tiny cell through the harness, and its controls ------------------------------------

TINY = dict(SMALL_SIZES, hidden_size=64, intermediate_size=128, moe_intermediate_size=32, head_dim=16,
            sliding_window=8)
TINY_LIMITS = {"logit_rms_err": 0.001, "label_gap": 0.01, "score_log_err": 0.01, "routing_wrong_share": 0.0}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout_afmoe"))
    bench = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    shutil.copy(os.path.join(ROOT, "benchmark", "peaks.json"), bench)
    cfg = json.loads(json.dumps(CONFIG))
    cfg.update(TINY)
    cfg["model"].update(TINY)
    cfg["program_kwargs"]["compute_dtype"] = "float32"
    cfg.update(name="tiny_afmoe", check_records=2, limits=TINY_LIMITS, routing_delta=1e-4)
    with open(os.path.join(bench, "configs", "tiny_afmoe.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "workloads", "tiny_afmoe.score.json"), "w") as f:
        json.dump({"arrivals": "backlog", "pool_records": 4, "record_tokens": 16, "window_records": 1}, f)
    manifest = harness.load_manifest(ROOT)
    manifest["configs"] = [{"name": "tiny_afmoe", "source": "test", "reduced": [], "why": "test",
                            "file": "benchmark/configs/tiny_afmoe.json"}]
    manifest["workloads"] = [{"name": "tiny_afmoe.score", "config": "tiny_afmoe", "traffic": "score", "chips": 1,
                              "why": "test"}]
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_afmoe.score"] if m["name"] == "records_per_s" else []
    manifest["per_layer"] = []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_a_trinity_cell_added_as_files_runs(tiny_root):
    # (That an altered answer is refused through the same check, test_stream_lm_kimi.py holds for the job kind.)
    out = harness.run_cell(root=tiny_root, workload="tiny_afmoe.score", seed=2**31 + 7, seconds=1.0, trace=False,
                           devices=jax.devices()[:1], t0=time.monotonic())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0 and set(out["metrics"]) == {"records_per_s", "setup_s"}


@pytest.fixture(scope="module")
def verdicts(tiny_root):
    _, _, cfg, mix = harness.load_cell(tiny_root, "tiny_afmoe.score")
    return controls.verdicts(stream_lm_routed.controls(cfg, mix, 5), cfg["limits"])


@pytest.mark.parametrize("reading", ["control_float8_e4m3fn", "control_float8_e5m2"] + ["fault_" + f for f in ref.FAULTS])
def test_a_control_is_refused_at_the_cells_limits(verdicts, reading):
    verdict = verdicts[reading]
    assert not verdict["correct"] and verdict["fails"], verdict


# -- the real configuration, its metrics and the work it counts ------------------------------

def test_the_real_file_holds_the_published_config_twice_and_states_the_cuts():
    cfg = CONFIG
    assert all(cfg[key] == value for key, value in cfg["model"].items())
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "num_dense_layers", "num_experts", "vocab_size"]
    assert list(cfg["reduced_from"]) == cfg["reduced"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 32, 25024)
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    # Every width, the router's 256 outputs, top-4, route_scale and the window: as published.
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]) == (3072, 12288, 3072)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (48, 8, 128)
    assert (cfg["router_experts"], cfg["first_expert"], cfg["num_experts_per_tok"]) == (256, 0, 4)
    assert (cfg["num_shared_experts"], cfg["route_scale"], cfg["sliding_window"]) == (1, 2.448, 4096)
    assert (cfg["rope_theta"], cfg["rope_scaling"], cfg["rms_norm_eps"], cfg["mup_enabled"]) == (10000, None, 1e-5, True)
    assert "8 chips share each layer" in cfg["deployment"]
    assert {"nope_on_full_layers", "mup_scale", "spreads", "records", "precision", "window"} <= set(cfg["assumed"])
    assert cfg["limits"]["routing_wrong_share"] == 0.0 and set(cfg["limits"]) == set(cfg["limits_reason"])
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        assert json.load(f) == {"arrivals": "backlog", "pool_records": 32, "record_tokens": 32768,
                                "window_records": 1, "warmup_windows": 4}


def test_the_work_at_the_published_widths_against_a_count_by_hand():
    model = CONFIG["model"]
    # ISSUE 41: 62.91 M a layer's attention, 176.2 M the dense layer, 998.0 M an expert layer, 153.7 M the ends.
    shapes = ref.leaf_shapes(model)
    count = lambda prefix: sum(int(np.prod(s)) for n, s in shapes.items() if n.startswith(prefix))  # noqa: E731
    assert count("layers.1.attn.") == pytest.approx(62.91e6, rel=1e-3)
    assert count("layers.0.") == pytest.approx(176.2e6, rel=1e-3) and count("layers.4.") == pytest.approx(998.0e6, rel=1e-3)
    assert count("") == pytest.approx(4321.9e6, rel=1e-4)  # 8.64 GB in bfloat16
    # 39.37 TFLOP of products, 4 x 3.09 of band, 13.19 of triangle: 64.93 a record.
    band, triangle = ref.pairs_seen(32768, 4096), ref.pairs_seen(32768)
    assert band == 125_831_168 and triangle == 536_887_296
    assert ref.forward_flops(model, 32768) == pytest.approx(64.93e12, rel=1e-3)
    assert ref.attention_kernel_cost(model, 32768, 1, window=4096)[0] == 2 * 2 * 48 * 128 * band
    assert ref.attention_kernel_cost(model, 32768, 1)[0] == pytest.approx(13.19e12, rel=1e-3)
    # A layer's grouped products at the even share: 131,072 pairs x 32 / 256 = 16,384 rows.
    flops, moved = ref.expert_kernel_cost(model, 32768, 1)
    assert flops == 2 * 16384 * 3 * 3072 * 3072 and moved == 2 * (32 * 3 * 3072 * 3072 + 2 * 16384 * 3072)
    assert flops / 197e12 > moved / 819e9  # compute-bound


def test_the_manifest_has_the_cell_and_its_metrics():
    manifest = harness.load_manifest(ROOT)
    names = [m["name"] for m in harness.metrics_of(manifest, "per_layer", CELL)]
    assert len(names) == 17 and all(n.endswith(".trinity") for n in names)
    assert {m["name"] for m in harness.metrics_of(manifest, "end_to_end", CELL)} == {"records_per_s", "setup_s"}
    # By name and not by place: a later PR appends its own cells after this one.
    entry = {c["name"]: c for c in manifest["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("trinity_large_preview", "score_32k", 1)


def _spec(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", metric + ".json")) as f:
        return json.load(f)


def _state(trace, registry):
    _, cell, cfg, mix = harness.load_cell(ROOT, CELL)
    run = {"counters": registry, "window": {"batch_records": mix["window_records"], "record_tokens": mix["record_tokens"]}}
    return {"ctx": types.SimpleNamespace(traced=trace), "run": run, "cell": cell, "config": cfg,
            "peaks": trace_reduce.peaks_for(ROOT, "TPU v5 lite")}


def test_the_band_reader_prices_each_kind_of_call_by_its_own_window():
    # Four band calls of 20 ms and one triangle call of 100 ms in one run of the step.
    ops = [("%flash_attention_window.3 = bf16[48,32768,128]{2,1,0} custom-call(...)", 0, 20e6),
           ("%flash_attention_window.4 = bf16[48,32768,128]{2,1,0} custom-call(...)", 20e6, 40e6),
           ("%flash_attention_window.5 = bf16[48,32768,128]{2,1,0} custom-call(...)", 40e6, 60e6),
           ("%flash_attention_window = bf16[48,32768,128]{2,1,0} custom-call(...)", 60e6, 80e6),
           ("%flash_attention.2 = bf16[48,32768,128]{2,1,0} custom-call(...)", 80e6, 180e6)]
    rows = [("/device:TPU:0", trace_reduce.OPS_LINE, n, s, e - s) for n, s, e in ops]
    rows.append(("/device:TPU:0", trace_reduce.MODULES_LINE, "jit_call(1)", 0, 180e6))
    state = _state(trace_reduce.Trace(rows), {})
    spec = _spec("window_attention_roofline_share.trinity")
    assert band.read(state, **spec["args"]) == pytest.approx(100 * 3.0924e12 / 197e12 / 20e-3, rel=1e-3)
    spec = _spec("flash_attention_roofline_share.trinity")
    assert band.read(state, **spec["args"]) == pytest.approx(100 * 13.194e12 / 197e12 / 100e-3, rel=1e-3)


@pytest.mark.parametrize("metric,registry,want", [
    ("attention_tiles_per_token.trinity", {"model.0.attention_tiles": 203520, "model.0.tokens": 32768}, 6.2109),
    ("attention_tiles_per_token.trinity", {"model.0.tokens": 32768}, None),  # the parent counts no tiles
    ("expert_rows_per_token.trinity", {"model.0.expert_rows": 65536, "model.0.tokens": 32768}, 2.0),
    ("expert_passes_per_batch.trinity", {"model.0.expert_passes": 40, "model.0.batches": 10}, 4.0),
])
def test_the_counter_metrics(metric, registry, want):
    got = counters.read(_state(None, registry), **_spec(metric)["args"])
    assert got == (None if want is None else pytest.approx(want, rel=1e-4))


@pytest.mark.parametrize("metric", [m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]
                                    if m["name"].endswith(".trinity")], ids=lambda m: m["name"])
def test_a_metric_of_the_cell_reads_nothing_and_does_not_raise_without_a_trace_or_a_counter(metric):
    import importlib

    spec = _spec(metric["name"])
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    if spec["reader"] == "pulse":
        pytest.skip("the pulse reader reads the job's flight ring, which a bare state has none of")
    assert reader.read(_state(None, {}), **spec["args"]) is None
