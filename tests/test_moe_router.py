"""What the softmax router brought to shared code, held on the CPU: the
sigmoid router's programs (every expert held, and a share) and the rotary
embedding's default programs trace to what they traced to before it; the
softmax router against numbers worked by hand, a tie included; and both
grouped kernels, interpreted, at Mellum 2's widths (d = 2,304, f = 896: gate
and up in column tiles of 128 over one contraction tile of 2,304, the ``W2``
product's 2,304 output columns three tiles of 768 over one contraction tile of
896) against ``ragged_dot`` at ``HIGHEST``."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from flink_tensorflow_tpu.models.zoo import falcon_h1
from flink_tensorflow_tpu.ops import moe

S = jax.ShapeDtypeStruct


def _traced(fn, *args):
    """A hash of the jaxpr ``fn`` traces to, kernels' bodies, tiles and compiler
    parameters included; the kernel's source position (a line number) left out."""
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _whole(dtype):
    def layer(x, r, b, w13, w2):  # every expert held (lfm2_moe's regime): 8 experts, top-4
        o = moe.routed_experts(x, r, b, w13, w2, k=4, scaling=1.0, compute_dtype=jnp.dtype(dtype))
        return o.out, o.experts, o.rows, o.rows_max
    return layer, (S((2, 24, 64), jnp.float32), S((64, 8), jnp.bfloat16), S((8,), jnp.bfloat16),
                   S((8, 64, 64), jnp.bfloat16), S((8, 32, 64), jnp.bfloat16))


def _share(dtype):
    def layer(x, r, b, w13, w2):  # a share (kimi_k2's and afmoe's regime): 4 of 16 held, top-4
        o = moe.routed_experts(x, r, b, w13, w2, k=4, first=4, scaling=2.827, eps=1e-20,
                               compute_dtype=jnp.dtype(dtype))
        return o.out, o.experts, o.rows, o.rows_max, o.passes
    return layer, (S((2, 24, 64), jnp.float32), S((64, 16), jnp.bfloat16), S((16,), jnp.bfloat16),
                   S((4, 64, 64), jnp.bfloat16), S((4, 32, 64), jnp.bfloat16))


#: Recorded on the commit before the softmax router was added (b3df8db).  The two bfloat16 programs were recorded
#: again when the grouped kernels' tiles came to be read off the shapes: the tiles are in the jaxpr (gmm's
#: contraction of 2,048 and 512 columns became 32 and 64 here); with the fixed tiles put back they hash to what they
#: did, c1e72f9400c74a7c and a76909cade38395c (tests/test_gated_grouped_matmul.py: _fixed_tiles).  The float32
#: programs, ``ragged_dot`` with no tile, are as they were.
_AS_BEFORE = {
    ("whole", "bfloat16"): (_whole, "51d3dfee5a4c0d7e"),
    ("whole", "float32"): (_whole, "0bd848dda0a94722"),
    ("share", "bfloat16"): (_share, "71c68e895267bb39"),
    ("share", "float32"): (_share, "2a526cd987036a4b"),
}


@pytest.mark.parametrize("case", list(_AS_BEFORE), ids=["-".join(c) for c in _AS_BEFORE])
def test_the_sigmoid_routed_layer_traces_to_the_program_it_did(case):
    make, want = _AS_BEFORE[case]
    layer, args = make(case[1])
    assert _traced(layer, *args) == want


def test_the_sigmoid_router_traces_to_the_program_it_did():
    got = _traced(lambda x, r, b: moe.route(x, r, b, k=4, scaling=2.448, eps=1e-20),
                  S((48, 64), jnp.float32), S((64, 16), jnp.bfloat16), S((16,), jnp.bfloat16))
    assert got == "804cf8797c5cfe5c"


def _logits_router(rows):
    """``x`` the identity and ``W`` the logits wanted: ``x W`` is ``rows``."""
    rows = np.asarray(rows, np.float32)
    return jnp.eye(len(rows), rows.shape[0], dtype=jnp.float32), jnp.asarray(rows)


def test_the_softmax_router_against_numbers_worked_by_hand():
    x, w = _logits_router([[1.0, 3.0, 2.0, 0.0],
                           [2.0, 0.0, 2.0, 1.0],    # a tie for the best: the lower index first
                           [0.0, 1.0, 1.0, 1.0],    # a tie for the 2nd place: the lower index is chosen
                           [-1.0, -1.0, 5.0, -3.0]])
    experts, weights = moe.route(x, w, None, k=2, score_func="softmax")
    assert experts.tolist() == [[1, 2], [0, 2], [1, 2], [2, 0]]
    e = np.e
    # s[sel] / sum(s[sel]): the softmax over all four, renormalised over the two chosen.
    np.testing.assert_allclose(np.asarray(weights), [[e / (e + 1), 1 / (e + 1)], [0.5, 0.5], [0.5, 0.5],
                                                     [e ** 6 / (e ** 6 + 1), 1 / (e ** 6 + 1)]], rtol=1e-6)
    s = jax.nn.softmax(w, axis=-1)
    picked = jnp.take_along_axis(s, experts, axis=-1)
    np.testing.assert_allclose(np.asarray(weights), np.asarray(picked / picked.sum(-1, keepdims=True)), rtol=1e-6)
    _, scaled = moe.route(x, w, None, k=2, score_func="softmax", scaling=2.0)
    np.testing.assert_allclose(np.asarray(scaled), 2 * np.asarray(weights), rtol=1e-6)


def test_a_softmax_router_takes_no_bias_and_an_unknown_one_is_refused():
    x, w = _logits_router(np.eye(4))
    with pytest.raises(ValueError, match="no selection bias"):
        moe.route(x, w, jnp.zeros(4), k=2, score_func="softmax")
    with pytest.raises(ValueError, match="not 'relu'"):
        moe.route(x, w, jnp.zeros(4), k=2, score_func="relu")


def test_the_softmax_routed_layer_holds_every_expert_at_eight_of_sixty_four():
    rng = np.random.default_rng(0)
    d, f, e, k = 32, 16, 64, 8
    x = jnp.asarray(rng.normal(size=(2, 40, d)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(d, e)) * 1.5 / np.sqrt(d), jnp.float32)
    w13 = jnp.asarray(rng.normal(size=(e, d, 2 * f)) / np.sqrt(d), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(e, f, d)) / np.sqrt(f), jnp.float32)
    got = moe.routed_experts(x, r, None, w13, w2, k=k, score_func="softmax", compute_dtype=jnp.float32)
    # By hand: every token, its eight experts, their softmax weights.
    tokens = np.asarray(x, np.float64).reshape(-1, d)
    logits = tokens @ np.asarray(r, np.float64)
    want = np.zeros_like(tokens)
    for t, row in enumerate(logits):
        sel = np.argsort(-row, kind="stable")[:k]
        p = np.exp(row[sel] - row[sel].max())
        for j, wgt in zip(sel, p / p.sum()):
            both = tokens[t] @ np.asarray(w13[j], np.float64)
            want[t] += wgt * ((both[:f] / (1 + np.exp(-both[:f])) * both[f:]) @ np.asarray(w2[j], np.float64))
        assert sorted(np.asarray(got.experts).reshape(-1, k)[t]) == sorted(sel)
    np.testing.assert_allclose(np.asarray(got.out).reshape(-1, d), want, rtol=1e-4, atol=1e-5)
    assert got.rows.tolist() == [40 * k] * 2 and got.passes == 1


# -- the rotary embedding's defaults -------------------------------------------------------

def _rope_as_it_was(x, theta):
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * cos + half * sin


@pytest.mark.parametrize("theta,shape", [(10000.0, (2, 64, 4, 64)), (10000.0, (1, 300, 2, 128)),
                                         (1e6, (1, 96, 3, 128))], ids=["afmoe", "afmoe-head128", "falcon_h1"])
def test_rope_with_its_defaults_gives_falcons_and_afmoes_values_to_the_bit(theta, shape):
    x = jnp.asarray(np.random.default_rng(1).normal(size=shape), jnp.float32)
    got = jax.jit(lambda x: falcon_h1._rope(x, theta))(x)
    want = jax.jit(lambda x: _rope_as_it_was(x, theta))(x)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))


def test_rope_with_its_defaults_traces_to_the_program_it_did():
    assert _traced(lambda x: falcon_h1._rope(x, 10000.0), S((2, 16, 4, 64), jnp.float32)) == "d31de8eca4513033"


def test_rope_with_given_frequencies_and_a_scale():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 40, 2, 8)), jnp.float32)
    inv = np.array([1.0, 0.1, 0.01, 0.001])
    got = np.asarray(falcon_h1._rope(x, 1.0, inv, 1.5), np.float64)
    angle = np.arange(40)[:, None] * inv[None, :]
    xs = np.asarray(x, np.float64)
    rotated = np.concatenate([-xs[..., 4:], xs[..., :4]], axis=-1)
    want = 1.5 * (xs * np.cos(np.tile(angle, 2))[None, :, None] + rotated * np.sin(np.tile(angle, 2))[None, :, None])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- the grouped kernels at Mellum 2's widths, interpreted ------------------------------------

@pytest.fixture(scope="module")
def at_mellums_widths():
    rng = np.random.default_rng(3)
    d, f, sizes = 2304, 896, [20, 0, 37, 7]
    rows = jnp.asarray(rng.normal(size=(72, d)), jnp.bfloat16)
    w13 = jnp.asarray(rng.normal(size=(len(sizes), d, 2 * f)) / np.sqrt(d), jnp.bfloat16)
    w2 = jnp.asarray(rng.normal(size=(len(sizes), f, d)) / np.sqrt(f), jnp.bfloat16)
    return rows, w13, w2, jnp.asarray(sizes, jnp.int32)


def _ragged(rows, stacked, sizes):
    return lax.ragged_dot(rows.astype(jnp.float32), stacked.astype(jnp.float32), sizes,
                          precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def test_the_gated_product_at_d_2304_f_896_against_ragged_dot(at_mellums_widths):
    rows, w13, _, sizes = at_mellums_widths
    # 256 does not divide 896: gate and up in 128s; the contraction in one tile of 2,304
    assert moe.grouped_tiles(72, 2304, 896)[0] == (72, 2304, 128)
    got = moe.gated_grouped_matmul(rows, w13, sizes, interpret=True)
    both = _ragged(rows, w13, sizes)
    want = jax.nn.silu(both[:, :896]) * both[:, 896:]
    live = int(sizes.sum())
    assert got.shape == (72, 896) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got[:live], np.float32), np.asarray(want[:live]), rtol=1e-2, atol=1e-2)


def test_the_w2_product_writes_all_2304_columns_against_ragged_dot(at_mellums_widths):
    rows, w13, w2, sizes = at_mellums_widths
    hidden = moe.gated_grouped_matmul(rows, w13, sizes, interpret=True)
    got = moe.grouped_matmul(hidden, w2, sizes)  # 768-column tiles: three, none over the edge
    want = _ragged(hidden, w2, sizes)
    live = int(sizes.sum())
    assert got.shape == (72, 2304) and moe.grouped_tiles(72, 2304, 896)[1] == (72, 896, 768)
    np.testing.assert_allclose(np.asarray(got[:live]), np.asarray(want[:live]), rtol=1e-5, atol=1e-4)
    assert np.abs(np.asarray(got[:live, 2048:])).mean() > 0.1  # the 256 columns a fixed 512-column tile hung over
