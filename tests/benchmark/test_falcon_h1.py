"""Falcon-H1 at a tiny preset on the CPU (2 layers, hidden 64, the published
ratios of heads and groups, chunks of 8), float32 compute, seeded random
weights: the program against the plain reference, the chunked scan against
the recurrence, the grouped-query kernel against plain attention, every
multiplier live, the stream job end to end, ``open()`` leaving resident
parameters where they are, and the reference's count of operations against
XLA's."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.jobs import _zoo
from benchmark.reference import falcon_h1 as ref
from flink_tensorflow_tpu.models import get_model_def
from flink_tensorflow_tpu.ops.flash_attention import flash_attention
from flink_tensorflow_tpu.ops.ssd import ssd_scan

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs", "falcon_h1_34b.json")) as _f:
    CONFIG = json.load(_f)

#: 5 query heads a key/value head and 16 SSM heads a group, as published.
TINY_SIZES = dict(vocab_size=512, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=10, num_key_value_heads=2, head_dim=16,
                  mamba_d_ssm=128, mamba_n_heads=32, mamba_d_head=4, mamba_n_groups=2,
                  mamba_d_state=16, mamba_chunk_size=8)
TINY = dict(CONFIG["model"], **TINY_SIZES)
#: The fourteen multipliers, as (key, index within the key's list or None).
MULTIPLIERS = [(key, None) for key in (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")]
MULTIPLIERS += [("ssm_multipliers", i) for i in range(5)] + [("mlp_multipliers", i) for i in range(2)]


def program_logits(model, params, tokens, **kwargs):
    mdef = get_model_def("falcon_h1", seq_len=tokens.shape[1], compute_dtype="float32",
                         **model, **kwargs)
    tree = _zoo.program_tree(params, jax.eval_shape(mdef.init_fn, jax.random.key(0)),
                             CONFIG["param_rules"])
    return np.asarray(jax.jit(mdef.methods["serve"].fn)(tree, {"tokens": jnp.asarray(tokens)})["logits"])


def worst(got, want):
    """The largest difference, in units of the reference logits' spread."""
    return float(np.abs(np.asarray(got) - np.asarray(want)).max() / np.asarray(want).std())


@pytest.fixture(scope="module")
def params():
    return ref.make_params(TINY, 2**31 + 5)


@pytest.mark.parametrize("length", [8, 20, 24], ids=["one_chunk", "ragged_last_chunk", "three_chunks"])
def test_program_agrees_with_the_reference(params, length):
    tokens = ref.make_tokens(TINY, 3, length, 11)
    want = ref.forward(params, tokens, TINY)
    assert want.shape == (3, TINY["vocab_size"]) and 1.5 < float(want.std()) < 4.0
    assert worst(program_logits(TINY, params, tokens), want) < 1e-4


def test_the_branches_are_of_the_residuals_order(params):
    rms = []
    ref.forward(params, ref.make_tokens(TINY, 2, 24, 3), TINY, rms=rms)
    assert len(rms) == 2 * TINY["num_hidden_layers"]
    for layer in rms:
        for branch in ("ssm", "attention", "mlp"):
            assert 0.25 * layer["residual"] < layer[branch] < 2.0 * layer["residual"], layer


def _recurrence(x, dt, a, b, c, state=None):
    """The scan position by position, float64 on the host."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    bsz, t, heads, p = x.shape
    per = heads // b.shape[2]
    state = np.zeros((bsz, heads, p, b.shape[3])) if state is None else np.asarray(state, np.float64)
    ys = np.zeros_like(x)
    for i in range(t):
        bi, ci = np.repeat(b[:, i], per, axis=1), np.repeat(c[:, i], per, axis=1)
        state = (np.exp(dt[:, i] * a)[..., None, None] * state
                 + (dt[:, i, :, None] * x[:, i])[..., None] * bi[:, :, None, :])
        ys[:, i] = np.einsum("bhpn,bhn->bhp", state, ci)
    return ys, state


@pytest.fixture(scope="module")
def scan_inputs():
    rng = np.random.default_rng(5)
    t, heads, p, groups, n = 29, 8, 4, 2, 6
    return dict(x=rng.normal(size=(2, t, heads, p)).astype(np.float32),
                dt=rng.uniform(0.01, 0.5, (2, t, heads)).astype(np.float32),
                a=-rng.uniform(0.5, 4.0, heads).astype(np.float32),
                b=rng.normal(size=(2, t, groups, n)).astype(np.float32),
                c=rng.normal(size=(2, t, groups, n)).astype(np.float32))


@pytest.mark.parametrize("chunk", [32, 8, 5], ids=["one_chunk", "ragged_chunks", "chunks_of_5"])
def test_chunked_scan_is_the_recurrence(scan_inputs, chunk):
    want, last = _recurrence(**scan_inputs)
    got, state = ssd_scan(**{k: jnp.asarray(v) for k, v in scan_inputs.items()}, chunk=chunk,
                          compute_dtype=jnp.float32, return_state=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, last, rtol=2e-4, atol=2e-4)


def test_a_state_carried_across_a_chunks_edge(scan_inputs):
    # Two calls, the second starting from the first's state, are one call; and
    # the state matters: without it the second half reads otherwise.
    want, _ = _recurrence(**scan_inputs)
    cut = 13  # inside a chunk of 8
    part = lambda lo, hi: {k: jnp.asarray(v if k == "a" else v[:, lo:hi])  # noqa: E731
                           for k, v in scan_inputs.items()}
    first, state = ssd_scan(**part(0, cut), chunk=8, compute_dtype=jnp.float32, return_state=True)
    second = ssd_scan(**part(cut, None), chunk=8, compute_dtype=jnp.float32, initial_state=state)
    np.testing.assert_allclose(np.concatenate([first, second], axis=1), want, rtol=2e-4, atol=2e-4)
    dropped = ssd_scan(**part(cut, None), chunk=8, compute_dtype=jnp.float32)
    assert np.abs(np.asarray(dropped) - want[:, cut:]).max() > 0.05


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_grouped_query_flash_attention(dtype, tol):
    rng = np.random.default_rng(9)
    b, t, heads, kv, d = 2, 48, 10, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), dtype) for h in (heads, kv, kv))
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    # Plain attention, query head i on key/value head i // 5.
    kk, vv = (np.repeat(np.asarray(x, np.float64), heads // kv, axis=2) for x in (k, v))
    s = np.einsum("bthd,bshd->bhts", np.asarray(q, np.float64), kk) / np.sqrt(d)
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhts,bshd->bthd", w / w.sum(-1, keepdims=True), vv)
    assert got.shape == (b, t, heads, d) and got.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=tol, atol=tol)


def test_heads_that_do_not_divide_are_refused():
    q, k = jnp.zeros((1, 8, 6, 16)), jnp.zeros((1, 8, 4, 16))
    with pytest.raises(ValueError):
        flash_attention(q, k, k, interpret=True)


@pytest.mark.parametrize("key,index", MULTIPLIERS,
                         ids=[k if i is None else f"{k}.{i}" for k, i in MULTIPLIERS])
def test_every_multiplier_is_live_and_sits_where_the_reference_has_it(params, key, index):
    tokens = ref.make_tokens(TINY, 2, 20, 13)
    model = dict(TINY)
    if index is None:
        model[key] = TINY[key] * 1.5
    else:
        model[key] = [m * (1.5 if i == index else 1.0) for i, m in enumerate(TINY[key])]
    base = program_logits(TINY, params, tokens)
    got = program_logits(model, params, tokens)
    assert worst(got, base) > 1e-2, "the multiplier changes nothing"
    assert worst(got, ref.forward(params, tokens, model)) < 1e-4


def test_the_stream_job_answers_every_record_once_as_the_method_does(params):
    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue

    length, n = 20, 10
    tokens = ref.make_tokens(TINY, n, length, 17)
    mdef = get_model_def("falcon_h1", seq_len=length, compute_dtype="float32", **TINY)
    tree = _zoo.program_tree(params, jax.eval_shape(mdef.init_fn, jax.random.key(0)),
                             CONFIG["param_rules"])
    want = jax.jit(mdef.methods["serve"].fn)(tree, {"tokens": jnp.asarray(tokens)})
    env = StreamExecutionEnvironment(parallelism=1)
    records = [TensorValue({"tokens": tokens[i]}, {"id": i}) for i in range(n)]
    out = (env.from_collection(records)
           .count_window(2)
           .apply(ModelWindowFunction(mdef.to_model(tree), policy=BucketPolicy(fixed_batch=2),
                                      warmup_batches=(2,), outputs=("logits", "label", "score")),
                  name="model", parallelism=1)
           .sink_to_list())
    job = env.execute("falcon_tiny", timeout=300)
    assert sorted(r.meta["id"] for r in out) == list(range(n))
    for r in out:
        i = r.meta["id"]
        np.testing.assert_allclose(r["logits"], np.asarray(want["logits"])[i], rtol=1e-5, atol=1e-5)
        assert int(r["label"]) == int(want["label"][i])
        assert float(r["score"]) == pytest.approx(float(want["score"][i]), rel=1e-4)
    # Real tokens, window by window, and the tree's bytes as a gauge.
    counters = job.metrics
    assert counters["model.0.tokens"] == n * length
    assert counters["model.0.batches"] == n // 2
    assert counters["model.0.param_bytes"] == sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def test_open_takes_resident_parameters_as_they_are(params):
    from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner

    mdef = get_model_def("falcon_h1", seq_len=8, **TINY)  # bfloat16, as the cell holds them
    tree = _zoo.program_tree(params, jax.eval_shape(mdef.init_fn, jax.random.key(0)),
                             CONFIG["param_rules"])
    device = jax.devices()[0]
    tree = jax.block_until_ready(jax.device_put(tree, device))
    before = [leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(tree)]
    runner = CompiledMethodRunner(mdef.to_model(tree), device=device)
    runner.open()
    try:
        held = jax.tree.leaves(runner._params_on_device)
        assert [leaf.unsafe_buffer_pointer() for leaf in held] == before
        assert all(leaf.dtype == jnp.bfloat16 for leaf in held)
        assert runner.param_bytes == sum(leaf.nbytes for leaf in held)
        runner.warmup((2,))
        assert [leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(runner._params_on_device)] == before
    finally:
        runner.close()
    # Host leaves are still transferred.
    host = jax.tree.map(np.asarray, tree)
    runner = CompiledMethodRunner(mdef.to_model(host), device=device)
    runner.open()
    assert all(isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(runner._params_on_device))
    runner.close()


def test_forward_flops_against_xlas_count():
    # XLA counts what it runs: the reference's recurrence (4 operations a state
    # element a position, which is what forward_flops counts for the chunk states
    # and their read-out), the whole square of the attention scores, and the
    # elementwise work, a widening of a stored weight among it (so the weights go
    # in widened).  At a width where the products dominate the two agree.
    model = dict(TINY, hidden_size=256, intermediate_size=1024, vocab_size=2048, num_hidden_layers=1,
                 mamba_d_ssm=512, mamba_d_head=16, mamba_d_state=8, head_dim=32)
    length = model["mamba_chunk_size"]
    params = {name: w.astype(jnp.float32) for name, w in ref.make_params(model, 3).items()}
    tokens = ref.make_tokens(model, 1, length, 3)
    embed, layer, head = ref._compiled(json.dumps(model, sort_keys=True), None, None)
    layer_params = {name[len("layers.0."):]: w for name, w in params.items() if name.startswith("layers.0.")}
    h = embed(params["embed"], tokens[0])
    counted = 0.0
    for fn, args in ((layer, (layer_params, h)), (head, (params["norm_f"], params["head"], h[-1]))):
        cost = fn.lower(*args).compile().cost_analysis()
        counted += float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])
    assert ref.forward_flops(model, length) == pytest.approx(counted, rel=0.03)


def test_forward_flops_at_the_published_widths():
    # The issue's arithmetic: 21.8 TFLOP a record of 4,096 positions.
    model = CONFIG["model"]
    assert ref.forward_flops(model, 4096) == pytest.approx(21.78e12, rel=0.005)
    per_layer = sum(np.prod(s) for n, s in ref.leaf_shapes(model).items() if n.startswith("layers.0."))
    assert int(per_layer) == 430_120_032
    flops, moved = ref.attention_kernel_cost(model, 4096, 2)
    assert flops == 2 * 2 * 2 * 20 * 128 * (4096 * 4097 // 2) and moved == 100_663_296
