"""The flash kernel's sliding window, interpreted on the CPU: its output against
a dense softmax masked from positions (``0 <= i - j < W``), nearer the ``W``
reference than the ``W - 1`` and ``W + 1`` ones; ``tile_plan``'s count of the
compute tiles against a brute-force count of the chunks that meet the band; the
calls a window is refused for; and a causal call's plan, which a window leaves
as it was."""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest

from flink_tensorflow_tpu.ops.flash_attention import flash_attention, tile_plan


def _dense(q, k, v, window):
    """float64 softmax over the keys ``0 <= i - j < window`` (all earlier keys if None)."""
    b, t, h, d = q.shape
    kk, vv = (np.repeat(np.asarray(x, np.float64), h // k.shape[2], axis=2) for x in (k, v))
    s = np.einsum("bthd,bshd->bhts", np.asarray(q, np.float64), kk) / np.sqrt(d)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (i - j >= 0) & (i - j < (t if window is None else window))
    s = np.where(seen, s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhts,bshd->bthd", w / w.sum(-1, keepdims=True), vv)


def _operands(t, heads=4, kv=2, d=32, dtype="float32", seed=0):
    rng = np.random.default_rng(seed)
    # Scores of spread 3, so that a key more or less in the band moves a row's output.
    q = rng.normal(size=(1, t, heads, d)) * np.sqrt(3.0)
    k = rng.normal(size=(1, t, kv, d)) * np.sqrt(3.0)
    v = rng.normal(size=(1, t, kv, d))
    return tuple(jnp.asarray(x, dtype) for x in (q, k, v))


#: (T, W, block_q, block_k): W a multiple of the chunk and not, smaller than a q block and larger,
#: tiles of keys that the band skips whole, the tile the chooser picks, a window past the sequence.
_CASES = [
    (256, 64, 32, 32), (256, 50, 32, 32), (256, 100, 64, 16), (256, 7, 32, 32), (256, 1, 32, 32),
    (128, 64, None, None), (256, 300, 32, 32), (512, 130, 64, 128),
]


@pytest.mark.parametrize("t,window,bq,bk", _CASES, ids=[f"T{t}-W{w}-bq{bq}-bk{bk}" for t, w, bq, bk in _CASES])
def test_the_band_against_a_softmax_masked_from_positions(t, window, bq, bk):
    q, k, v = _operands(t)
    got = np.asarray(flash_attention(q, k, v, causal=True, window=window, block_q=bq, block_k=bk,
                                     interpret=True), np.float64)
    want = _dense(q, k, v, window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # Nearer the W reference than W - 1 and W + 1 wherever those differ from it.
    err = np.abs(got - want).max()
    for other in (window - 1, window + 1):
        if 1 <= other < t and window < t:
            assert np.abs(got - _dense(q, k, v, other)).max() > 100 * err


def test_the_band_at_bfloat16_with_its_log_sum_exp():
    q, k, v = _operands(256, dtype="bfloat16", seed=3)
    out, lse = flash_attention(q, k, v, causal=True, window=40, block_q=32, block_k=32, interpret=True,
                               return_lse=True)
    np.testing.assert_allclose(np.asarray(out, np.float64), _dense(q, k, v, 40), rtol=3e-2, atol=3e-2)
    assert lse.shape == (1, 4, 256) and np.isfinite(np.asarray(lse)).all()


def _brute_force(t, bq, bk, chunk, window):
    """(compute tiles any row of a q block sees a key of, those some row sees only in part)."""
    visited = masked = 0
    for qi, c in itertools.product(range(t // bq), range(t // chunk)):
        rows = np.arange(qi * bq, qi * bq + bq)[:, None]
        keys = np.arange(c * chunk, c * chunk + chunk)[None, :]
        seen = (rows - keys >= 0) & (rows - keys < (t if window is None else window))
        visited += bool(seen.any())
        masked += bool(seen.any() and not seen.all())
    return visited, masked


@pytest.mark.parametrize("t,window,bq,bk", [(256, 64, 32, 32), (256, 50, 64, 16), (256, 7, 32, 32),
                                            (512, 130, 64, 128), (1024, 300, 128, 256), (4096, 1000, None, None),
                                            (4096, None, None, None), (2048, 2048, 512, 512)])
def test_tiles_visited_is_the_count_of_the_chunks_that_meet_the_band(t, window, bq, bk):
    plan = tile_plan(t, t, 128, jnp.bfloat16, True, bq, bk, window=window)
    assert (plan.tiles_visited, plan.tiles_masked) == _brute_force(t, plan.block_q, plan.block_k, plan.chunk, window)


def test_the_cells_shapes_visit_the_band_and_the_triangle():
    band = tile_plan(32768, 32768, 128, jnp.bfloat16, True, window=4096)
    triangle = tile_plan(32768, 32768, 128, jnp.bfloat16, True)
    # 512 query rows and 512 keys a compute tile; the band's tiles of 2,048 keys, the triangle's of 8,192.
    assert band[:5] == (512, 2048, 512, 540, 120) and triangle[:5] == (512, 8192, 512, 2080, 64)
    # ISSUE 41: 48 heads x (4 x 540 + 2,080) over 32,768 positions
    assert 48 * (4 * band.tiles_visited + triangle.tiles_visited) / 32768 == pytest.approx(6.2109, abs=1e-4)
    # A window that covers the sequence visits what the causal call visits.
    assert tile_plan(4096, 4096, 128, jnp.bfloat16, True, window=4096).tiles_visited == \
        tile_plan(4096, 4096, 128, jnp.bfloat16, True).tiles_visited


@pytest.mark.parametrize("kw", [dict(causal=False), dict(window=0), dict(keys=128)], ids=["not_causal", "empty", "other_keys"])
def test_a_window_is_a_causal_calls_over_its_own_positions(kw):
    q, k, v = _operands(64)
    if "keys" in kw:
        k, v = k[:, :kw.pop("keys") // 4], v[:, :32]
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, **{"causal": True, "window": 16, **kw}, interpret=True)
