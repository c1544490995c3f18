"""LFM2-MoE at a tiny preset on the CPU (2 dense + 4 expert layers with one
``full_attention``, 8 experts, widths cut), seeded random weights: the program
against the plain reference, free-running at float32 and held to the program's
routing at bfloat16; the routed layer against a loop over tokens; the experts'
shares of a layer; the counts made on the device; the flash kernel at a head
of 64; the stream job end to end; the reference's count of operations against
XLA's and against a count by hand."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.jobs import _zoo
from benchmark.reference import lfm2_moe as ref
from flink_tensorflow_tpu.models import get_model_def
from flink_tensorflow_tpu.ops import moe
from flink_tensorflow_tpu.ops.flash_attention import flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs", "lfm2_8b_a1b.json")) as _f:
    CONFIG = json.load(_f)

TINY_SIZES = dict(vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
                  num_hidden_layers=6, layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"],
                  num_attention_heads=8, num_key_value_heads=2, num_experts=8)
TINY = {k: dict(CONFIG["model"], **TINY_SIZES, num_experts_per_tok=k) for k in (2, 4)}
EXPERT_LAYERS = TINY_SIZES["num_hidden_layers"] - CONFIG["model"]["num_dense_layers"]


def program(model, params, tokens, compute_dtype="float32"):
    mdef = get_model_def("lfm2_moe", seq_len=tokens.shape[1], compute_dtype=compute_dtype, **model)
    tree = _zoo.program_tree(params, jax.eval_shape(mdef.init_fn, jax.random.key(0)),
                             CONFIG["param_rules"])
    return mdef, tree, jax.jit(mdef.methods["serve"].fn)(tree, {"tokens": jnp.asarray(tokens)})


def worst(got, want):
    """The largest difference, in units of the reference logits' spread."""
    return float(np.abs(np.asarray(got) - np.asarray(want)).max() / np.asarray(want).std())


@pytest.fixture(scope="module", params=[2, 4], ids=["top2", "top4"])
def tiny(request):
    model = TINY[request.param]
    return model, ref.make_params(model, 2**31 + 5)


@pytest.mark.parametrize("length", [8, 24], ids=["8_positions", "24_positions"])
def test_program_and_reference_choose_alike_and_agree_at_float32(tiny, length):
    model, params = tiny
    tokens = ref.make_tokens(model, 3, length, 11)
    chosen = []
    want = ref.forward(params, tokens, model, chosen=chosen)
    assert want.shape == (3, model["vocab_size"]) and 1.5 < float(want.std()) < 4.0
    _, _, out = program(model, params, tokens)
    assert out["routing"].shape == (3, length, EXPERT_LAYERS, model["num_experts_per_tok"])
    assert out["routing"].dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(out["routing"]), np.stack(chosen))  # best first, both
    assert worst(out["logits"], want) < 1e-4


def test_at_bfloat16_the_program_agrees_with_the_reference_held_to_its_routing(tiny):
    model, params = tiny
    tokens = ref.make_tokens(model, 3, 24, 11)
    _, _, out = program(model, params, tokens, compute_dtype="bfloat16")
    routed = []
    held = ref.forward(params, tokens, model, routing=np.asarray(out["routing"]), routed=routed,
                       routing_delta=0.05)
    free = ref.forward(params, tokens, model)
    # At a hidden size of 64 bfloat16 moves the scores by a few hundredths: some
    # pairs swap, none lands far from the reference's own choice.
    pairs = 24 * EXPERT_LAYERS * model["num_experts_per_tok"]
    assert [r["pairs"] for r in routed] == [pairs] * 3
    assert sum(r["wrong"] for r in routed) == 0 and max(r["gap_max"] for r in routed) < 0.05
    assert worst(out["logits"], held) < 0.25
    assert worst(out["logits"], held) <= worst(out["logits"], free)


def test_routing_handed_over_is_used_and_judged(tiny):
    model, params = tiny
    k = model["num_experts_per_tok"]
    tokens = ref.make_tokens(model, 1, 8, 3)
    chosen, routed = [], []
    own = ref.forward(params, tokens, model, chosen=chosen)
    routing = np.stack(chosen)
    again = ref.forward(params, tokens, model, routing=routing, routed=routed, routing_delta=0.01)
    np.testing.assert_allclose(again, own, rtol=1e-6, atol=1e-6)
    assert routed == [{"pairs": 8 * EXPERT_LAYERS * k, "wrong": 0, "near": 0, "gap_max": 0.0}]
    # One token's last expert replaced by one it did not choose: another answer, and a pair off the choice.
    routing[0, 5, 1, k - 1] = min(set(range(model["num_experts"])) - set(routing[0, 5, 1].tolist()))
    routed.clear()
    moved = ref.forward(params, tokens, model, routing=routing, routed=routed, routing_delta=0.0)
    assert routed[0]["wrong"] >= 1 and routed[0]["gap_max"] > 0
    assert worst(moved, own) > 1e-3
    # The same expert named twice is wrong whatever its score.
    routing = np.stack(chosen)
    routing[0, 2, 0, 1] = routing[0, 2, 0, 0]
    routed.clear()
    ref.forward(params, tokens, model, routing=routing, routed=routed, routing_delta=10.0)
    assert routed[0]["wrong"] == k


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_answer_or_the_routes(tiny, fault):
    model, params = tiny
    tokens = ref.make_tokens(model, 2, 24, 3)
    chosen, routed = [], []
    got = ref.forward(params, tokens, model, fault=fault, chosen=chosen)
    held = ref.forward(params, tokens, model, routing=np.stack(chosen), routed=routed, routing_delta=1e-4)
    assert worst(got, held) > 1e-2 or sum(r["wrong"] for r in routed) > 0
    with pytest.raises(ValueError):
        ref.forward(params, tokens, model, fault="no_such_fault")


def test_the_terms_are_of_the_residuals_order(tiny):
    model, params = tiny
    rms = []
    ref.forward(params, ref.make_tokens(model, 2, 24, 3), model, rms=rms)
    assert len(rms) == 2 * model["num_hidden_layers"]
    # The spreads aim at 1 at 4,096 positions; a softmax over 24 keys averages less away, so the
    # attention term reads 2.4 here (on the chip at the cell's size 0.80-0.83 with ATTENTION_RMS 0.40,
    # from which 0.33 was set: PERF.md 6).
    for layer in rms:
        assert 0.3 < layer["op"] < 3.0 and 0.3 < layer["ff"] < 2.5, layer
    assert 0.05 < float(np.std(np.asarray(params["embed"], np.float32))) < 0.15


def test_token_ids_are_zipfian_over_the_whole_vocabulary():
    model = dict(TINY[4], vocab_size=65536)
    tokens = ref.make_tokens(model, 64, 4096, 7)
    assert tokens.dtype == np.int32 and tokens.shape == (64, 4096) and 0 <= tokens.min() and tokens.max() < 65536
    counts = np.sort(np.bincount(tokens.reshape(-1), minlength=65536))[::-1]
    share = counts / counts.sum()
    harmonic = np.sum(1.0 / np.arange(1, 65537))
    assert share[0] == pytest.approx(1 / harmonic, rel=0.05)       # the hottest id: 8.6% of positions
    assert share[9] == pytest.approx(0.1 / harmonic, rel=0.15)     # rank 10: a tenth of that
    assert not np.array_equal(tokens, ref.make_tokens(model, 64, 4096, 8))
    np.testing.assert_array_equal(tokens, ref.make_tokens(model, 64, 4096, 7))


# -- the routed layer ------------------------------------------------------------

def _layer_weights(rng, d, f, experts):
    return dict(w_router=rng.normal(size=(d, experts)).astype(np.float32) * 1.5 / np.sqrt(d),
                bias=rng.normal(size=experts).astype(np.float32) * 0.05,
                w13=rng.normal(size=(experts, d, 2 * f)).astype(np.float32) / np.sqrt(d),
                w2=rng.normal(size=(experts, f, d)).astype(np.float32) / np.sqrt(f))


def _token_loop(x, w_router, bias, w13, w2, k, first=0, held=None):
    """The layer one token and one chosen expert at a time, float64 on the host."""
    x, w_router, bias, w13, w2 = (np.asarray(a, np.float64) for a in (x, w_router, bias, w13, w2))
    held = w_router.shape[1] if held is None else held
    f = w2.shape[1]
    out, rows = np.zeros_like(x), np.zeros(w_router.shape[1], int)
    for b, t in np.ndindex(x.shape[:2]):
        s = 1.0 / (1.0 + np.exp(-(x[b, t] @ w_router)))
        sel = np.argsort(-(s + bias), kind="stable")[:k]
        w = s[sel] / (s[sel].sum() + 1e-6)
        for e, weight in zip(sel, w):
            rows[e] += 1
            if first <= e < first + held:
                both = x[b, t] @ w13[e - first]
                gate, up = both[:f], both[f:]
                out[b, t] += weight * ((gate / (1.0 + np.exp(-gate)) * up) @ w2[e - first])
    return out, rows


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 5e-2)], ids=["ragged_dot", "gmm_interpreted"])
@pytest.mark.parametrize("k", [2, 4])
def test_the_routed_layer_is_the_loop_over_tokens_however_uneven_the_routing(k, dtype, tol):
    rng = np.random.default_rng(k)
    d, f, experts = 32, 16, 8
    w = _layer_weights(rng, d, f, experts)
    # Expert 0 takes every token, expert 3 none: groups of every row and of no row.
    w["bias"][0], w["bias"][3] = 10.0, -10.0
    x = rng.normal(size=(2, 24, d)).astype(np.float32)
    got = moe.routed_experts(jnp.asarray(x), *(jnp.asarray(w[n]) for n in ("w_router", "bias", "w13", "w2")),
                             k=k, compute_dtype=jnp.dtype(dtype))
    want, rows = _token_loop(x, **w, k=k)
    assert rows[0] == 48 and rows[3] == 0
    np.testing.assert_allclose(got.out, want, rtol=tol, atol=tol)
    assert np.asarray(got.experts)[..., 0].tolist() == [[0] * 24] * 2  # best first
    assert got.rows.tolist() == [24 * k, 24 * k] and int(got.rows_max) == 48
    assert got.out.dtype == jnp.float32 and got.experts.dtype == jnp.int32


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 5e-2)], ids=["ragged_dot", "gmm_interpreted"])
def test_the_shares_of_a_32_expert_layer_add_up_to_the_whole_layer(dtype, tol):
    rng = np.random.default_rng(3)
    d, f, experts, k = 32, 16, 32, 4
    w = _layer_weights(rng, d, f, experts)
    x = jnp.asarray(rng.normal(size=(2, 16, d)).astype(np.float32))
    router = (jnp.asarray(w["w_router"]), jnp.asarray(w["bias"]))
    whole = moe.routed_experts(x, *router, jnp.asarray(w["w13"]), jnp.asarray(w["w2"]), k=k,
                               compute_dtype=jnp.dtype(dtype))
    shares = [moe.routed_experts(x, *router, jnp.asarray(w["w13"][lo:lo + 8]), jnp.asarray(w["w2"][lo:lo + 8]),
                                 k=k, first=lo, compute_dtype=jnp.dtype(dtype)) for lo in (0, 8, 16, 24)]
    np.testing.assert_allclose(sum(s.out for s in shares), whole.out, rtol=tol, atol=tol)
    for share in shares:  # every share routes over all 32
        np.testing.assert_array_equal(share.experts, whole.experts)
    assert sum(s.rows for s in shares).tolist() == whole.rows.tolist() == [16 * k] * 2
    assert all(0 < int(s.rows.sum()) < 2 * 16 * k for s in shares)
    lo = 8
    want, _ = _token_loop(np.asarray(x), w["w_router"], w["bias"], w["w13"][lo:lo + 8], w["w2"][lo:lo + 8],
                          k=k, first=lo, held=8)
    np.testing.assert_allclose(shares[1].out, want, rtol=tol, atol=tol)
    with pytest.raises(ValueError):
        moe.routed_experts(x, *router, jnp.asarray(w["w13"][:8]), jnp.asarray(w["w2"][:8]), k=k, first=28)


def test_the_bias_chooses_and_never_weighs():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(40, 16)).astype(np.float32))
    w_router = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    bias = jnp.zeros(8).at[5].set(3.0)
    experts, weights = moe.route(x, w_router, bias, k=2)
    plain, plain_weights = moe.route(x, w_router, jnp.zeros(8), k=2)
    assert (np.asarray(experts)[:, 0] == 5).all() and not (np.asarray(plain)[:, 0] == 5).all()
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-5)
    # Where both chose the same two, the weights are the same: the bias is not in them.
    same = (np.sort(experts, -1) == np.sort(plain, -1)).all(-1)
    assert same.any()
    np.testing.assert_allclose(np.sort(weights[same], -1), np.sort(plain_weights[same], -1), atol=1e-6)
    # Equal scores: the lower index first.
    tied, _ = moe.route(jnp.zeros((3, 16)), w_router, jnp.zeros(8), k=3)
    assert np.asarray(tied).tolist() == [[0, 1, 2]] * 3


# -- counts made on the device, through the operator ------------------------------------

def test_expert_rows_are_tokens_times_k_times_layers(tiny):
    model, params = tiny
    tokens = ref.make_tokens(model, 3, 24, 17)
    _, _, out = program(model, params, tokens)
    assert out["expert_rows"].tolist() == [24 * model["num_experts_per_tok"] * EXPERT_LAYERS] * 3
    fullest = sum(np.bincount(np.asarray(out["routing"])[:, :, layer].reshape(-1)).max()
                  for layer in range(EXPERT_LAYERS))
    assert int(out["expert_rows_max"]) == fullest


def test_the_stream_job_answers_every_record_once_and_counts_on_the_operators_track(tiny):
    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue

    model, params = tiny
    k, length, n = model["num_experts_per_tok"], 20, 9  # the last window holds one record and one of padding
    tokens = ref.make_tokens(model, n, length, 17)
    mdef, tree, want = program(model, params, tokens)
    assert mdef.methods["serve"].count_names == ("expert_rows", "expert_rows_max")
    env = StreamExecutionEnvironment(parallelism=1)
    records = [TensorValue({"tokens": tokens[i]}, {"id": i}) for i in range(n)]
    out = (env.from_collection(records)
           .count_window(2)
           .apply(ModelWindowFunction(mdef.to_model(tree), policy=BucketPolicy(fixed_batch=2),
                                      warmup_batches=(2,), outputs=("logits", "routing")),
                  name="model", parallelism=1)
           .sink_to_list())
    job = env.execute("lfm2_tiny", timeout=300)
    assert sorted(r.meta["id"] for r in out) == list(range(n))
    for r in out:
        i = r.meta["id"]
        assert set(r.names) == {"logits", "routing"}  # what was asked for: a count is never in a record
        np.testing.assert_allclose(r["logits"], np.asarray(want["logits"])[i], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(r["routing"], np.asarray(want["routing"])[i])
        assert r["routing"].dtype == np.int8
    registry = job.metrics
    assert registry["model.0.tokens"] == n * length and registry["model.0.batches"] == 5
    # Real records only: the padding row of the last window is not counted.
    assert registry["model.0.expert_rows"] == n * length * k * EXPERT_LAYERS
    assert registry["model.0.expert_rows"] / registry["model.0.tokens"] == k * EXPERT_LAYERS
    assert registry["model.0.expert_rows_max"] >= registry["model.0.expert_rows"] / model["num_experts"]


def test_a_method_that_declares_no_counts_is_run_as_before():
    from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner

    mdef = get_model_def("lenet")
    assert mdef.methods["serve"].count_names == ()
    runner = CompiledMethodRunner(mdef.to_model(mdef.init_fn(jax.random.key(0))), output_names=("label",))
    assert runner._count_names == ()
    assert runner._take_counts({"label": np.zeros(2)}, np.ones(2, bool)) == {}


def test_open_takes_the_resident_tree_as_it_is(tiny):
    from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner

    model, params = tiny
    mdef = get_model_def("lfm2_moe", seq_len=8, **model)  # bfloat16, as the cell holds them
    tree = _zoo.program_tree(params, jax.eval_shape(mdef.init_fn, jax.random.key(0)), CONFIG["param_rules"])
    tree = jax.block_until_ready(jax.device_put(tree, jax.devices()[0]))
    before = [leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(tree)]
    runner = CompiledMethodRunner(mdef.to_model(tree), device=jax.devices()[0])
    runner.open()
    try:
        runner.warmup((2,))
        held = jax.tree.leaves(runner._params_on_device)
        assert [leaf.unsafe_buffer_pointer() for leaf in held] == before
        assert all(leaf.dtype == jnp.bfloat16 for leaf in held)
    finally:
        runner.close()


def test_a_config_the_builder_does_not_build_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        get_model_def("lfm2_moe", **dict(TINY[4], num_hidden_layers=5))
    with pytest.raises(ValueError, match="as published"):
        get_model_def("lfm2_moe", **dict(TINY[4], norm_topk_prob=False))


# -- the flash kernel at a head of 64 --------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_flash_attention_at_a_head_of_64_with_32_query_heads_on_8(dtype, tol):
    rng = np.random.default_rng(9)
    b, t, heads, kv, d = 1, 48, 32, 8, 64
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), dtype) for h in (heads, kv, kv))
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    # Plain attention, query head i on key/value head i // 4.
    kk, vv = (np.repeat(np.asarray(x, np.float64), heads // kv, axis=2) for x in (k, v))
    s = np.einsum("bthd,bshd->bhts", np.asarray(q, np.float64), kk) / np.sqrt(d)
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhts,bshd->bthd", w / w.sum(-1, keepdims=True), vv)
    assert got.shape == (b, t, heads, d) and got.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=tol, atol=tol)


# -- work from shapes ------------------------------------------------------------------

def test_forward_flops_against_xlas_count():
    # XLA counts what it runs: the whole square of the attention scores, the
    # elementwise work, a widening of a stored weight among it (so the weights go
    # in widened), and of the experts every row that the reference hands them,
    # padding included (so every expert is handed whole buckets).  At a width
    # where the products dominate the two agree.
    model = dict(TINY[4], hidden_size=256, intermediate_size=1024, moe_intermediate_size=256,
                 vocab_size=2048, num_hidden_layers=3, layer_types=["conv", "full_attention", "conv"],
                 num_dense_layers=1, num_attention_heads=8, num_key_value_heads=2, num_experts=8)
    length = ref.ROW_BUCKET
    params = {name: w.astype(jnp.float32) for name, w in ref.make_params(model, 3).items()}
    fns = ref._compiled(json.dumps(model, sort_keys=True), None, None)
    h = jnp.zeros((length, 256), jnp.float32)
    layer = lambda i: {n[len(f"layers.{i}."):]: w for n, w in params.items() if n.startswith(f"layers.{i}.")}  # noqa: E731
    split = lambda p: ({n: w for n, w in p.items() if not n.startswith(("mlp.", "moe."))},  # noqa: E731
                       {n: w for n, w in p.items() if n.startswith(("mlp.", "moe."))})
    (op0, ff0), (op1, ff1), (op2, _) = (split(layer(i)) for i in range(3))
    rows = np.arange(length)
    calls = [(fns["operator"], (op0, h), 1), (fns["dense_ff"], (ff0, h, h), 1),
             (fns["operator"], (op1, h), 1), (fns["operator"], (op2, h), 1),
             (fns["scores"], (ff1["moe.router"], ff1["moe.bias"], h), 2),
             # Four rows a token over the experts: four whole buckets a layer.
             (fns["expert"], (h, h, ff1["moe.w13"][0], ff1["moe.w2"][0], rows, jnp.ones(length)), 2 * 4),
             (fns["head"], (params["norm_f"], params["embed"], h[-1]), 1)]
    counted = 0.0
    for fn, args, times in calls:
        cost = fn.lower(*args).compile().cost_analysis()
        counted += times * float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])
    assert ref.forward_flops(model, length) == pytest.approx(counted, rel=0.03)


def test_the_work_at_the_published_widths_against_a_count_by_hand():
    model = CONFIG["model"]
    # ISSUE 35's arithmetic: 1.72 GFLOP a token, 7.03 TFLOP a record of 4,096 positions.
    assert ref.forward_flops(model, 4096) == pytest.approx(7.03e12, rel=0.005)
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(model).values()) == 4_667_077_376
    # A layer's grouped products, by hand: 32,768 rows through 3 matrices of 2,048 x 1,792.
    flops, moved = ref.expert_kernel_cost(model, 4096, 2)
    assert flops == 2 * 32768 * 3 * 2048 * 1792 == 721_554_505_728
    assert moved == 2 * 32 * 3 * 2048 * 1792 + 2 * 2 * 32768 * 2048 == 973_078_528
    assert flops / 197e12 > moved / 819e9  # compute-bound: 3.66 ms against 1.19 ms
    flops, moved = ref.attention_kernel_cost(model, 4096, 2)
    assert flops == 2 * 2 * 2 * 32 * 64 * (4096 * 4097 // 2) and moved == 2 * 2 * 4096 * 64 * 80
    assert ref.sizes(model) == {"head_dim": 64, "q": 2048, "kv": 512, "conv_layers": 11,
                                "attention_layers": 3, "expert_layers": 12}
