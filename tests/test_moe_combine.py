"""The whole layer's combine (``ops/moe.py: combine_rows``), interpreted on the
CPU: against the loop it replaces (one slot's gathered rows added at a time) and
against a float64 sum, within the rounding of a float32 sum of ``k`` terms; and
which combine each routed configuration's layer takes, traced on shapes alone,
since the choice is made by shape when the layer is traced and no count made
on the device says which ran.

What Mosaic makes of the kernel at Mellum 2's layer is held by
``tests/test_flash_compile.py`` (compiled for a described v5e); its speed and
its bits on the chip by PERF.md section 6."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from flink_tensorflow_tpu.ops import moe

F32 = jnp.float32


def _loop(y, back, weights):
    """The combine above ``COMBINE_UNROLLED_BYTES`` until the kernel: a loop over the slots."""
    return lax.fori_loop(0, back.shape[1], lambda j, out: out + y[back[:, j]] * weights[:, j, None],
                         jnp.zeros((back.shape[0], y.shape[1]), F32))


def _inputs(seed, tokens, k, d):
    rng = np.random.default_rng(seed)
    y = rng.normal(size=(tokens * k, d)).astype(np.float32)
    back = rng.permutation(tokens * k).reshape(tokens, k).astype(np.int32)  # every row is one token's slot
    logits = rng.normal(size=(tokens, k))
    weights = (np.exp(logits) / np.exp(logits).sum(1, keepdims=True)).astype(np.float32)
    return y, back, weights


#: name -> (tokens, k, d, tokens a block; None for the block the kernel picks).  Blocks that do not divide the
#: tokens, so that the last is padded, at both widths the tests name: one lane tile, and Mellum 2's 2,304.
_CASES = {
    "k2-d128-40-tokens-blocks-of-16": (40, 2, 128, 16),
    "k4-d128-38-tokens-blocks-of-16": (38, 4, 128, 16),
    "k8-d128-300-tokens-blocks-of-32": (300, 8, 128, 32),
    "k8-d128-36-tokens-its-own-block": (36, 8, 128, None),
    "k2-d2304-20-tokens-blocks-of-8": (20, 2, 2304, 8),
    "k4-d2304-12-tokens-blocks-of-8": (12, 4, 2304, 8),
    "k8-d2304-20-tokens-blocks-of-8": (20, 8, 2304, 8),
}


@pytest.mark.parametrize("case", list(_CASES), ids=list(_CASES))
def test_the_kernel_is_the_loops_sum_within_a_float32_sums_rounding(case):
    tokens, k, d, tm = _CASES[case]
    y, back, weights = _inputs(tokens + k + d, tokens, k, d)
    if tm is None:
        got = moe.combine_rows(jnp.asarray(y), jnp.asarray(back), jnp.asarray(weights))
    else:
        got = moe._combine_call(jnp.asarray(y), jnp.asarray(back), jnp.asarray(weights), tm, True)
    got, loop = np.asarray(got), np.asarray(_loop(jnp.asarray(y), jnp.asarray(back), jnp.asarray(weights)))
    terms = y.astype(np.float64)[back] * weights.astype(np.float64)[..., None]  # [tokens, k, d]
    exact = terms.sum(axis=1)
    # k roundings of the sum and one of each product, each at most half an ulp of what it rounds
    bound = k * np.finfo(np.float32).eps * np.abs(terms).sum(axis=1)
    assert got.shape == (tokens, d) and got.dtype == np.float32
    assert np.all(np.abs(got - exact) <= bound)
    assert np.all(np.abs(got - loop) <= 2 * bound)
    # a sum of the wrong rows, or of a slot left out, is far outside it
    assert np.abs(exact - terms[:, 1:].sum(axis=1)).max() > 100 * bound.max()


def test_a_width_off_the_lanes_is_refused():
    y, back, weights = _inputs(0, 8, 2, 96)
    with pytest.raises(ValueError, match="lanes"):
        moe.combine_rows(jnp.asarray(y), jnp.asarray(back), jnp.asarray(weights))


def test_the_block_is_the_most_tokens_whose_vmem_fits_mosaics_default():
    assert moe._combine_tokens(32768, 2304) == 256  # Mellum 2's layer; 512 would not fit
    assert moe._combine_tokens(32768, 2048) == 256
    assert moe._combine_tokens(20, 2304) == 24  # fewer tokens: all of them, in whole sublanes
    for d in (128, 2048, 2304, 4096, 7168):
        tm = moe._combine_tokens(1 << 20, d)
        assert 6 * tm * d * 4 <= moe._VMEM_DEFAULT < 6 * 2 * tm * d * 4


# -- which combine a configuration's layer takes -------------------------------------------------------------

#: name -> (tokens a step, d, experts, f, k, router): the two configurations whose every expert is held.  Kimi and
#: Trinity hold a share and never reach the whole-layer path.
_LAYERS = {
    "mellum2_12b_a2_5b": (32768, 2304, 64, 896, 8, "softmax"),
    "lfm2_8b_a1b": (2 * 4096, 2048, 32, 1792, 4, "sigmoid"),
}


def _combine_ops(jaxpr, inside=False):
    """(primitive, or a kernel's name; its first output's shape) of what is traced under the scope ``combine``,
    nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        here = inside or "combine" in str(eqn.source_info.name_stack)
        if here:
            found.append((eqn.params["name"] if eqn.primitive.name == "pallas_call" else eqn.primitive.name,
                          getattr(eqn.outvars[0].aval, "shape", None) if eqn.outvars else None))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _combine_ops(sub, here)
    return found


@pytest.mark.parametrize("name", list(_LAYERS))
def test_which_combine_each_configurations_layer_takes(name):
    tokens, d, experts, f, k, router = _LAYERS[name]
    shapes = (jax.ShapeDtypeStruct((1, tokens, d), F32), jax.ShapeDtypeStruct((d, experts), F32),
              None if router == "softmax" else jax.ShapeDtypeStruct((experts,), F32),
              jax.ShapeDtypeStruct((experts, d, 2 * f), jnp.bfloat16),
              jax.ShapeDtypeStruct((experts, f, d), jnp.bfloat16))
    jaxpr = jax.make_jaxpr(lambda x, w, b, w13, w2: moe.routed_experts(x, w, b, w13, w2, k=k, score_func=router).out)(
        *shapes)
    ops = _combine_ops(jaxpr.jaxpr)
    names = [op for op, _ in ops]
    unrolled = k * tokens * d * 4 <= moe.COMBINE_UNROLLED_BYTES
    assert unrolled == (name == "lfm2_8b_a1b")  # LFM2's 4 x 67 MB; Mellum's 8 x 302 MB
    assert "while" not in names  # neither combine is a loop
    rows_gathered = ops.count(("gather", (tokens, d)))
    if unrolled:  # a gather of [tokens, d] a slot, summed by XLA
        assert "combine_rows" not in names and rows_gathered == k
    else:  # the kernel copies the rows itself
        assert names.count("combine_rows") == 1 and rows_gathered == 0
