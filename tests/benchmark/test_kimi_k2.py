"""Kimi-K2 at a tiny preset on the CPU (1 dense + 3 expert layers, 16 experts
of which 4 are held, 4 heads of 24 = 16 + 8 on keys and 16 on values, ranks 24
and 16, widths cut), seeded random weights: the program against the plain
reference, free-running at float32 and held to the program's routing at
bfloat16; the shares of a layer against the uncut reference; the routed layer
under routing that sends every pair, and none, to the experts held; yarn's
numbers worked by hand; the flash kernel at unequal head sizes with a given
scale, and the programs that callers without them trace to; the stream job end
to end; the reference's count of operations against XLA's and against a count
by hand."""

import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.jobs import _zoo
from benchmark.reference import kimi_k2 as ref
from flink_tensorflow_tpu.models import get_model_def
from flink_tensorflow_tpu.ops import mla, moe
from flink_tensorflow_tpu.ops.flash_attention import flash_attention, tile_plan

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs", "kimi_k2_7_code.json")) as _f:
    CONFIG = json.load(_f)

TINY_SIZES = dict(vocab_size=512, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
                  num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
                  kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  n_routed_experts=4, router_experts=16, first_expert=0)
TINY = {k: dict(CONFIG["model"], **TINY_SIZES, num_experts_per_tok=k) for k in (2, 4)}
EXPERT_LAYERS = TINY_SIZES["num_hidden_layers"] - CONFIG["model"]["first_k_dense_replace"]


def program(model, params, tokens, compute_dtype="float32"):
    mdef = get_model_def("kimi_k2", seq_len=tokens.shape[1], compute_dtype=compute_dtype, **model)
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
    assert out["routing"].dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(out["routing"]), np.stack(chosen))  # best first, both
    assert np.stack(chosen).max() >= TINY_SIZES["n_routed_experts"]  # experts held elsewhere are chosen too
    assert worst(out["logits"], want) < 1e-4


def test_at_bfloat16_the_program_agrees_with_the_reference_held_to_its_routing(tiny):
    model, params = tiny
    tokens = ref.make_tokens(model, 3, 24, 11)
    _, _, out = program(model, params, tokens, compute_dtype="bfloat16")
    routed = []
    held = ref.forward(params, tokens, model, routing=np.asarray(out["routing"]), routed=routed,
                       routing_delta=0.05)
    free = ref.forward(params, tokens, model)
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
    assert routing.dtype == np.int16
    again = ref.forward(params, tokens, model, routing=routing, routed=routed, routing_delta=0.01)
    np.testing.assert_allclose(again, own, rtol=1e-6, atol=1e-6)
    assert routed == [{"pairs": 8 * EXPERT_LAYERS * k, "wrong": 0, "near": 0, "gap_max": 0.0}]
    # One token's last expert replaced by one it did not choose: a pair off the choice.
    routing[0, 5, 1, k - 1] = min(set(range(16)) - set(routing[0, 5, 1].tolist()))
    routed.clear()
    ref.forward(params, tokens, model, routing=routing, routed=routed, routing_delta=0.0)
    assert routed[0]["wrong"] >= 1 and routed[0]["gap_max"] > 0
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
    # The spreads aim at 1 at 4,096 positions; a softmax over 24 keys averages less away.
    for layer in rms:
        assert 0.3 < layer["op"] < 3.5 and 0.3 < layer["ff"] < 2.5, layer
    assert 0.9 < float(np.std(np.asarray(params["embed"], np.float32))) < 1.1


def test_token_ids_are_zipfian_over_the_slice_of_the_vocabulary():
    model = CONFIG["model"]
    tokens = ref.make_tokens(model, 64, 4096, 7)
    assert tokens.dtype == np.int32 and tokens.shape == (64, 4096) and 0 <= tokens.min() and tokens.max() < 20480
    share = np.sort(np.bincount(tokens.reshape(-1), minlength=20480))[::-1] / tokens.size
    harmonic = np.sum(1.0 / np.arange(1, 20481))
    assert share[0] == pytest.approx(1 / harmonic, rel=0.05)       # the hottest id: 9.5% of positions
    assert share[9] == pytest.approx(0.1 / harmonic, rel=0.15)
    np.testing.assert_array_equal(tokens, ref.make_tokens(model, 64, 4096, 7))
    assert not np.array_equal(tokens, ref.make_tokens(model, 64, 4096, 8))


# -- the shares of a layer ---------------------------------------------------------

def _layer_of(params, i):
    prefix = f"layers.{i}."
    return {name[len(prefix):]: w for name, w in params.items() if name.startswith(prefix)}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 6e-2)], ids=["ragged_dot", "gmm_interpreted"])
@pytest.mark.parametrize("k", [2, 4])
def test_the_four_shares_of_a_layer_add_up_to_the_uncut_references_whole_layer(k, dtype, tol):
    uncut = dict(TINY[k], n_routed_experts=16, router_experts=16)
    params = ref.make_params(uncut, 7)
    p = _layer_of(params, 2)
    fns = ref._compiled(json.dumps(uncut, sort_keys=True), None, None)
    x = jnp.asarray(np.random.default_rng(k).normal(size=(2, 24, 64)).astype(np.float32))
    shared = [fns["shared_ff"]({n: w for n, w in p.items() if n.startswith("shared.")}, x[b]) for b in range(2)]
    whole = [ref.routed_ff(fns, p, x[b], uncut)[0] + shared[b] for b in range(2)]  # the layer's whole term
    scaling = uncut["routed_scaling_factor"]
    shares = [moe.routed_experts(x, p["moe.router"], p["moe.bias"], p["moe.w13"][lo:lo + 4], p["moe.w2"][lo:lo + 4],
                                 k=k, first=lo, scaling=scaling, eps=1e-20, compute_dtype=jnp.dtype(dtype))
              for lo in (0, 4, 8, 12)]
    # What every chip computes alike, the shared expert, is counted once.
    got = sum(s.out for s in shares) + jnp.stack(shared)
    np.testing.assert_allclose(got, jnp.stack(whole), rtol=tol, atol=tol * float(jnp.abs(jnp.stack(whole)).max()))
    for share in shares:  # every share routes over all 16
        np.testing.assert_array_equal(share.experts, shares[0].experts)
    assert sum(s.rows for s in shares).tolist() == [24 * k] * 2
    assert all(0 < int(s.rows.sum()) < 2 * 24 * k for s in shares)
    # And a share is what the reference gives when told the same share.
    lo = 8
    share_model = dict(uncut, n_routed_experts=4, first_expert=lo)
    cut = dict(p, **{"moe.w13": p["moe.w13"][lo:lo + 4], "moe.w2": p["moe.w2"][lo:lo + 4]})
    share_fns = ref._compiled(json.dumps(share_model, sort_keys=True), None, None)
    want = jnp.stack([ref.routed_ff(share_fns, cut, x[b], share_model)[0] for b in range(2)])
    np.testing.assert_allclose(shares[2].out, want, rtol=tol, atol=tol * float(jnp.abs(want).max()))


def _layer_weights(rng, d, f, experts, held):
    return dict(w_router=rng.normal(size=(d, experts)).astype(np.float32) * 1.5 / np.sqrt(d),
                bias=rng.normal(size=experts).astype(np.float32) * 0.05,
                w13=rng.normal(size=(held, d, 2 * f)).astype(np.float32) / np.sqrt(d),
                w2=rng.normal(size=(held, f, d)).astype(np.float32) / np.sqrt(f))


def _token_loop(x, w_router, bias, w13, w2, k, first, scaling):
    """The share one token and one chosen expert at a time, float64 on the host."""
    x, w_router, bias, w13, w2 = (np.asarray(a, np.float64) for a in (x, w_router, bias, w13, w2))
    held, f = w2.shape[0], w2.shape[1]
    out, rows = np.zeros_like(x), 0
    for b, t in np.ndindex(x.shape[:2]):
        s = 1.0 / (1.0 + np.exp(-(x[b, t] @ w_router)))
        sel = np.argsort(-(s + bias), kind="stable")[:k]
        w = s[sel] / (s[sel].sum() + 1e-20) * scaling
        for e, weight in zip(sel, w):
            if first <= e < first + held:
                rows += 1
                both = x[b, t] @ w13[e - first]
                gate, up = both[:f], both[f:]
                out[b, t] += weight * ((gate / (1.0 + np.exp(-gate)) * up) @ w2[e - first])
    return out, rows


@pytest.mark.parametrize("dtype,tol", [("float32", 3e-5), ("bfloat16", 6e-2)], ids=["ragged_dot", "gmm_interpreted"])
@pytest.mark.parametrize("held,k,passes", [(4, 2, 2), (2, 2, 4), (4, 4, 2), (8, 8, 1)])
def test_every_pair_on_the_held_experts_takes_several_passes_and_loses_none(held, k, passes, dtype, tol):
    rng = np.random.default_rng(held + k)
    d, f, experts, first = 32, 16, 16, 8
    w = _layer_weights(rng, d, f, experts, held)
    w["bias"][first:first + held] = 10.0  # every token's k best are held here
    x = rng.normal(size=(2, 24, d)).astype(np.float32)
    got = moe.routed_experts(jnp.asarray(x), *(jnp.asarray(w[n]) for n in ("w_router", "bias", "w13", "w2")),
                             k=k, first=first, scaling=2.827, eps=1e-20, compute_dtype=jnp.dtype(dtype))
    want, rows = _token_loop(x, **w, k=k, first=first, scaling=2.827)
    assert rows == 2 * 24 * k == int(got.rows.sum())
    assert moe.share_capacity(2 * 24 * k, held, experts) * passes >= rows  # the buffers are a share's, not the layer's
    assert int(got.passes) == passes == -(-rows // moe.share_capacity(2 * 24 * k, held, experts))
    np.testing.assert_allclose(got.out, want, rtol=tol, atol=tol * np.abs(want).max())


def test_no_pair_on_the_held_experts_takes_no_pass_and_adds_nothing():
    rng = np.random.default_rng(1)
    w = _layer_weights(rng, 32, 16, 16, 4)
    w["bias"][4:8] = -10.0
    x = rng.normal(size=(2, 24, 32)).astype(np.float32)
    got = moe.routed_experts(jnp.asarray(x), *(jnp.asarray(w[n]) for n in ("w_router", "bias", "w13", "w2")),
                             k=2, first=4, compute_dtype=jnp.float32)
    assert int(got.passes) == 0 and got.rows.tolist() == [0, 0] and int(got.rows_max) == 0
    assert not np.asarray(got.out).any()
    assert not ((np.asarray(got.experts) >= 4) & (np.asarray(got.experts) < 8)).any()


def test_a_shares_buffers_are_sized_by_the_share():
    # The cell's sizes: 65,536 pairs, 12 of 384 held: twice the even share of 2,048, in whole row tiles.
    assert moe.share_capacity(2 * 4096 * 8, 12, 384) == 4096
    assert moe.share_capacity(2 * 24 * 2, 4, 16) == 48 and moe.share_capacity(100, 1, 64) == 8
    x = jax.ShapeDtypeStruct((2, 256, 64), jnp.float32)
    w = (jax.ShapeDtypeStruct((64, 128), jnp.float32), jax.ShapeDtypeStruct((128,), jnp.float32),
         jax.ShapeDtypeStruct((4, 64, 64), jnp.float32), jax.ShapeDtypeStruct((4, 32, 64), jnp.float32))
    text = str(jax.make_jaxpr(lambda x, *w: moe.routed_experts(x, *w, k=8, compute_dtype=jnp.float32).out)(x, *w))
    pairs, capacity = 2 * 256 * 8, moe.share_capacity(2 * 256 * 8, 4, 128)
    assert capacity == 256
    # Of the tensors as long as the pairs none holds a row: the sorted ids, their keys and weights.
    assert f"i32[{pairs}]" in text and f"f32[{pairs}]" in text and not re.search(rf"f32\[{pairs},", text)
    assert f"f32[{capacity},64]" in text and f"f32[{capacity},32]" in text


@pytest.mark.parametrize("dtype,want", [("bfloat16", "439b22ed23568f72"), ("float32", "341f361e95f3a5b2")])
def test_where_every_expert_is_held_the_layer_traces_to_the_program_it_did(dtype, want):
    # Recorded on the parent commit (0c9542a): the jaxpr of the whole-layer path, kernel and all.
    args = (jax.ShapeDtypeStruct((2, 64, 128), jnp.float32), jax.ShapeDtypeStruct((128, 8), jnp.bfloat16),
            jax.ShapeDtypeStruct((8,), jnp.bfloat16), jax.ShapeDtypeStruct((8, 128, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((8, 64, 128), jnp.bfloat16))

    def layer(x, r, b, w13, w2):
        o = moe.routed_experts(x, r, b, w13, w2, k=2, compute_dtype=jnp.dtype(dtype))
        return o.out, o.experts, o.rows, o.rows_max

    assert _traced(layer, *args) == want


# -- yarn, by hand -------------------------------------------------------------------

def test_yarns_frequencies_and_scale_against_numbers_worked_by_hand():
    yarn, theta = CONFIG["model"]["rope_scaling"], CONFIG["model"]["rope_theta"]
    inv = mla.yarn_inv_freq(64, theta, yarn)
    # dim(32 turns) = 64 ln(4096 / (64 pi)) / (2 ln 50000) = 8.91, dim(1 turn) = 19.16: the ramp runs from pair 8 to pair 20.
    assert math.floor(64 * math.log(4096 / (64 * math.pi)) / (2 * math.log(50000))) == 8
    assert math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000))) == 20
    f = lambda j: 50000.0 ** (-2 * j / 64)  # noqa: E731
    assert inv.shape == (32,) and inv[0] == 1.0
    np.testing.assert_allclose(inv[:9], [f(j) for j in range(9)], rtol=1e-12)            # as they were
    np.testing.assert_allclose(inv[20:], [f(j) / 64 for j in range(20, 32)], rtol=1e-12)  # 64 times slower
    assert inv[14] == pytest.approx(f(14) * (0.5 / 64 + 0.5), rel=1e-12)                  # half way up the ramp
    assert inv[14] == pytest.approx(0.004466, rel=1e-3) and inv[31] == pytest.approx(4.382e-7, rel=1e-3)
    np.testing.assert_allclose(ref._yarn_inv_freq(CONFIG["model"]), inv, rtol=1e-12)
    # m = 0.1 ln 64 + 1 = 1.4159; 192^-0.5 x m^2 = 0.1447
    assert mla.yarn_mscale(64, 1) == pytest.approx(1.41589, rel=1e-5)
    assert mla.softmax_scale(192, yarn) == pytest.approx(0.14468, rel=1e-4)
    assert mla.softmax_scale(192, yarn) == pytest.approx(ref.softmax_scale(CONFIG["model"]), rel=1e-12)
    assert ref.softmax_scale(CONFIG["model"], fault="no_mscale") == pytest.approx(192 ** -0.5)
    assert mla.softmax_scale(192, None) == pytest.approx(192 ** -0.5)


def test_rotating_the_pairs_in_place_gives_the_published_dot_products():
    rng = np.random.default_rng(2)
    model = TINY[2]
    q, k = (rng.normal(size=(12, h, 8)).astype(np.float32) for h in (4, 1))
    angle = np.arange(12)[:, None] * mla.yarn_inv_freq(8, model["rope_theta"], model["rope_scaling"])[None, :]
    cos, sin = jnp.asarray(np.cos(angle), jnp.float32), jnp.asarray(np.sin(angle), jnp.float32)
    ours = jnp.einsum("thd,sd->hts", mla.rope_pairs(jnp.asarray(q)[None], cos, sin)[0],
                      mla.rope_pairs(jnp.asarray(k)[None], cos, sin)[0, :, 0])
    theirs = jnp.einsum("thd,sd->hts", ref._rope_as_published(jnp.asarray(q), model),
                        ref._rope_as_published(jnp.asarray(k), model)[:, 0])
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-5)
    # By hand, pair 0 of position 3 (f_0 = 1): (x0 cos 3 - x1 sin 3, x0 sin 3 + x1 cos 3).
    turned = np.asarray(mla.rope_pairs(jnp.asarray(q)[None], cos, sin))[0, 3, 0, :2]
    x0, x1 = q[3, 0, :2]
    np.testing.assert_allclose(turned, [x0 * np.cos(3) - x1 * np.sin(3), x0 * np.sin(3) + x1 * np.cos(3)], rtol=1e-5)


# -- the flash kernel at unequal head sizes -----------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("heads,kv,d,dv,scale", [(2, 2, 192, 128, 0.1447), (4, 2, 24, 16, 0.3), (4, 4, 24, 16, None)],
                         ids=["192_128", "24_16_grouped", "24_16_default_scale"])
def test_flash_attention_with_values_of_another_head_size_and_a_given_scale(heads, kv, d, dv, scale, dtype, tol):
    rng = np.random.default_rng(9)
    b, t = 1, 48
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, w)), dtype) for h, w in ((heads, d), (kv, d), (kv, dv)))
    got = flash_attention(q, k, v, causal=True, scale=scale, block_q=16, block_k=16, interpret=True)
    kk, vv = (np.repeat(np.asarray(x, np.float64), heads // kv, axis=2) for x in (k, v))
    s = np.einsum("bthd,bshd->bhts", np.asarray(q, np.float64), kk) * (scale or d ** -0.5)
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhts,bshd->bthd", w / w.sum(-1, keepdims=True), vv)
    assert got.shape == (b, t, heads, dv) and got.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=tol, atol=tol)


def test_the_tile_reckons_with_both_head_sizes():
    plan = tile_plan(4096, 4096, 192, jnp.bfloat16, True, dv=128)
    # K of 192 fills two lane tiles a row: 4,096 rows are 2 MiB, the whole sequence still fits; V is half of that.
    assert plan[:5] == (512, 4096, 512, 36, 8)
    assert plan.vmem_bytes < tile_plan(4096, 4096, 256, jnp.bfloat16, True).vmem_bytes
    assert plan.vmem_bytes > tile_plan(4096, 4096, 128, jnp.bfloat16, True).vmem_bytes
    assert tile_plan(4096, 4096, 128, jnp.bfloat16, True, dv=128) == tile_plan(4096, 4096, 128, jnp.bfloat16, True)
    with pytest.raises(ValueError, match="cannot meet"):
        flash_attention(jnp.zeros((1, 8, 2, 24)), jnp.zeros((1, 8, 2, 16)), jnp.zeros((1, 8, 2, 16)))


def _traced(fn, *args):
    """A hash of the jaxpr ``fn`` traces to, kernels' bodies, tiles and compiler
    parameters included; the kernel's source position (a line number) left out."""
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


#: q shape, key positions, key/value heads, dtype, causal, return_lse -> the hash on the parent commit (0c9542a).
_AS_BEFORE = {
    "falcon_h1": ((2, 4096, 20, 128), 4096, 4, "bfloat16", True, False, "36401d793c957ca1"),
    "lfm2": ((2, 4096, 32, 64), 4096, 8, "bfloat16", True, False, "003aa4eb3185ac64"),
    "float32_lse": ((1, 1024, 2, 128), 1024, 2, "float32", True, True, "3adb3357b7fc1d97"),
    "keys_in_tiles": ((1, 16384, 8, 128), 16384, 8, "bfloat16", True, False, "a2a0da4b7f0464cd"),
    "ring_off_diagonal": ((1, 1024, 4, 128), 4096, 4, "bfloat16", False, True, "9f378b2c2720d423"),
}


@pytest.mark.parametrize("case", list(_AS_BEFORE))
def test_a_call_with_equal_head_sizes_and_no_scale_traces_to_the_program_it_did(case):
    shape, tk, kv, dtype, causal, lse, want = _AS_BEFORE[case]
    q = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    k = jax.ShapeDtypeStruct((shape[0], tk, kv, shape[3]), jnp.dtype(dtype))
    assert _traced(lambda q, k, v: flash_attention(q, k, v, causal=causal, interpret=False, return_lse=lse),
                   q, k, k) == want


# -- counts made on the device, through the operator ------------------------------------

def test_expert_rows_count_the_pairs_that_fell_here(tiny):
    model, params = tiny
    tokens = ref.make_tokens(model, 3, 24, 17)
    _, _, out = program(model, params, tokens)
    routing = np.asarray(out["routing"])
    here = (routing >= 0) & (routing < 4)
    assert out["expert_rows"].tolist() == here.reshape(3, -1).sum(1).tolist()
    assert 0 < int(out["expert_rows"].sum()) < routing.size
    fullest = sum(np.bincount(routing[:, :, layer][here[:, :, layer]], minlength=4).max()
                  for layer in range(EXPERT_LAYERS))
    assert int(out["expert_rows_max"]) == fullest
    assert int(out["expert_passes"]) == EXPERT_LAYERS  # every layer's pairs fit its capacity


def test_the_stream_job_answers_every_record_once_and_counts_on_the_operators_track(tiny):
    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue

    model, params = tiny
    length, n = 20, 9  # the last window holds one record and one of padding
    tokens = ref.make_tokens(model, n, length, 17)
    mdef, tree, want = program(model, params, tokens)
    assert mdef.methods["serve"].count_names == ("expert_rows", "expert_rows_max", "expert_passes")
    env = StreamExecutionEnvironment(parallelism=1)
    records = [TensorValue({"tokens": tokens[i]}, {"id": i}) for i in range(n)]
    out = (env.from_collection(records)
           .count_window(2)
           .apply(ModelWindowFunction(mdef.to_model(tree), policy=BucketPolicy(fixed_batch=2),
                                      warmup_batches=(2,), outputs=("logits", "routing")),
                  name="model", parallelism=1)
           .sink_to_list())
    job = env.execute("kimi_tiny", timeout=300)
    assert sorted(r.meta["id"] for r in out) == list(range(n))
    for r in out:
        i = r.meta["id"]
        assert set(r.names) == {"logits", "routing"}  # what was asked for: a count is never in a record
        np.testing.assert_allclose(r["logits"], np.asarray(want["logits"])[i], rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(r["routing"], np.asarray(want["routing"])[i])
        assert r["routing"].dtype == np.int16
    registry = job.metrics
    assert registry["model.0.tokens"] == n * length and registry["model.0.batches"] == 5
    # Real records only: the padding row of the last window is not counted.
    assert registry["model.0.expert_rows"] == int(np.asarray(want["expert_rows"]).sum())
    assert registry["model.0.expert_passes"] == 5 * EXPERT_LAYERS
    assert registry["model.0.expert_rows_max"] >= registry["model.0.expert_rows"] / 4


def test_open_takes_the_resident_tree_as_it_is(tiny):
    from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner

    model, params = tiny
    mdef = get_model_def("kimi_k2", seq_len=8, **model)  # bfloat16, as the cell holds them
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


@pytest.mark.parametrize("change,match", [
    (dict(n_group=8, topk_group=4), "one group"), (dict(scoring_func="softmax"), "as published"),
    (dict(norm_topk_prob=False), "as published"), (dict(num_key_value_heads=2), "every query head"),
    (dict(first_expert=14), "of the router's 16"), (dict(first_k_dense_replace=4), "at least one routed layer")])
def test_a_config_the_builder_does_not_build_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        get_model_def("kimi_k2", **dict(TINY[4], **change))


def test_the_published_keys_are_the_builders_keywords_and_the_defaults_are_the_published_model():
    mdef = get_model_def("kimi_k2", **CONFIG["model"])
    assert mdef.config["model_type"] == "kimi_k2" and mdef.config["max_position_embeddings"] == 262144
    shapes = jax.eval_shape(mdef.init_fn, jax.random.key(0))
    assert shapes["layers"][1]["moe"]["w13"].shape == (12, 7168, 4096)
    assert shapes["layers"][1]["moe"]["router"].shape == (7168, 384)
    assert shapes["layers"][0]["mlp"]["w1"].shape == (7168, 18432) and shapes["head"].shape == (7168, 20480)
    assert shapes["layers"][6]["attn"]["q_b"].shape == (1536, 64 * 192)
    assert shapes["layers"][6]["attn"]["kv_a"].shape == (7168, 576)
    assert shapes["layers"][6]["attn"]["kv_b"].shape == (512, 64 * 256)
    whole = get_model_def("kimi_k2").config
    assert (whole["num_hidden_layers"], whole["n_routed_experts"], whole["vocab_size"]) == (61, 384, 163840)


# -- work from shapes ------------------------------------------------------------------

def test_forward_flops_against_xlas_count():
    # XLA counts what it runs: the whole square of the attention scores, the elementwise work, a
    # widening of a stored weight among it (so the weights go in widened), and of the experts every
    # row that the reference hands them, padding included (so an expert is handed whole buckets).
    # At a width where the products dominate the two agree.
    model = dict(TINY[4], hidden_size=256, intermediate_size=1024, moe_intermediate_size=256, vocab_size=2048,
                 num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=96,
                 kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                 n_routed_experts=4, router_experts=16)
    length = ref.ROW_BUCKET
    params = {name: w.astype(jnp.float32) for name, w in ref.make_params(model, 3).items()}
    fns = ref._compiled(json.dumps(model, sort_keys=True), None, None)
    h = jnp.zeros((length, 256), jnp.float32)
    layer = lambda i: _layer_of(params, i)  # noqa: E731
    ff = lambda p: {n: w for n, w in p.items() if n.startswith(("mlp.", "moe.", "shared."))}  # noqa: E731
    op = lambda p: {n: w for n, w in p.items() if n not in ff(p)}  # noqa: E731
    rows = np.arange(length)
    calls = [(fns["operator"], (op(layer(0)), h), 1), (fns["dense_ff"], (ff(layer(0)), h, h), 1),
             (fns["operator"], (op(layer(1)), h), 1),
             (fns["scores"], (layer(1)["moe.router"], layer(1)["moe.bias"], h), 1),
             (fns["shared_ff"], ({n: w for n, w in layer(1).items() if n.startswith("shared.")}, h), 1),
             # 4 pairs a token x 4 of 16 held: one row a token over the held experts, one whole bucket.
             (fns["expert"], (h, h, layer(1)["moe.w13"][0], layer(1)["moe.w2"][0], rows, jnp.ones(length)), 1),
             (fns["head"], (params["norm_f"], params["head"], h[-1]), 1)]
    counted = 0.0
    for fn, args, times in calls:
        cost = fn.lower(*args).compile().cost_analysis()
        counted += times * float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])
    # XLA's square of scores against the reference's lower triangle: taken out of both.
    square = 2 * 2 * 4 * (48 + 32) * length * length
    triangle = 2 * 2 * 4 * (48 + 32) * (length * (length + 1) // 2)
    assert ref.forward_flops(model, length) - triangle == pytest.approx(counted - square, rel=0.03)


def test_the_work_at_the_published_widths_against_a_count_by_hand():
    model = CONFIG["model"]
    # ISSUE 37's arithmetic: a token 202.2 (projections) + 83.9 (attention) MFLOP a layer, the dense MLP
    # 792.7, the shared expert 88.1, the router 5.5, the experts held 22.0; 14.29 TFLOP a record.
    assert 2 * ref._attention_macs(model) == pytest.approx(202.2e6, rel=1e-3)
    assert ref.forward_flops(model, 4096) == pytest.approx(14.29e12, rel=2e-3)
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(model).values()) == pytest.approx(4849.6e6, rel=1e-4)
    assert ref.sizes(model) == {"qk_head_dim": 192, "q": 12288, "kv": 16384, "o": 8192, "attention_layers": 7,
                                "dense_layers": 1, "expert_layers": 6, "held": 12, "router_experts": 384,
                                "first_expert": 0, "shared_width": 2048}
    # A layer's grouped products at the even share: 65,536 pairs x 12 / 384 = 2,048 rows through 3 matrices
    # of 7,168 x 2,048; the 12 experts' weights once.
    flops, moved = ref.expert_kernel_cost(model, 4096, 2)
    assert flops == 2 * 2048 * 3 * 7168 * 2048 == 180_388_626_432
    assert moved == 2 * 12 * 3 * 7168 * 2048 + 2 * 2 * 2048 * 7168 == 1_115_684_864
    assert moved / 819e9 > flops / 197e12  # bandwidth-bound: 1.36 ms against 0.92 ms
    assert 2 * 12 * 3 * 7168 * 2048 / moved > 0.94  # the weights are nearly all of the bytes
    # The kernel: the lower triangle at 2 x (192 + 128) a pair a head; k_pe once, not once a head.
    flops, moved = ref.attention_kernel_cost(model, 4096, 2)
    assert flops == 2 * 2 * 64 * 320 * (4096 * 4097 // 2) == 687_362_539_520
    assert moved == 2 * 2 * 4096 * (64 * 320 + 64 * 256 + 64) == 605_028_352
    assert flops / 197e12 > moved / 819e9  # compute-bound: 3.49 ms against 0.74 ms
    # The uncut model counts all eight experts a token.
    uncut = {k: v for k, v in model.items() if k not in ("router_experts", "first_expert")}
    uncut.update(n_routed_experts=384)
    assert ref.sizes(uncut)["router_experts"] == 384 and ref.sizes(uncut)["held"] == 384
    assert (ref.forward_flops(uncut, 4096) - ref.forward_flops(model, 4096)
            == pytest.approx(2 * 4096 * 6 * (8 - 0.25) * 3 * 7168 * 2048, rel=1e-9))
