"""The gated grouped product (``ops/moe.py: gated_grouped_matmul``), interpreted
on the CPU: it is the first grouped product, ``silu(gate) * up`` and the one
rounding to bfloat16 that followed them, BIT FOR BIT, whatever the groups and
the tiles; the tiles are read off the shapes and leave no remainder, and where
the old fixed tile had one the ``W2`` product is still its product to the bit;
the routed layer through it is the layer it was; and
the float32 ``[rows, 2f]`` between a layer's two products is no tensor of the
bfloat16 program, while the float32 program is the one it was.

What Mosaic makes of the kernel at the cells' sizes is held by
``tests/test_flash_compile.py`` (compiled for a described v5e); its speed and
its last float32 bit on the chip by PERF.md section 6, PR 40."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_tensorflow_tpu.ops import moe

BF16 = jnp.bfloat16


def _three_lines(rows, w13, group_sizes, *, compute_dtype=BF16, tile_rows=moe.TILE_ROWS):
    """What stood between ``rows`` and the ``W2`` product until PR 40: the first
    grouped product, float32 ``[M, 2f]`` out, and XLA's ``silu * up`` off it
    (float32: the second product rounded it as it took it)."""
    f = w13.shape[2] // 2
    both = moe.grouped_matmul(rows, w13, group_sizes, compute_dtype=compute_dtype, tile_rows=tile_rows)
    return jax.nn.silu(both[:, :f]) * both[:, f:]


def _bits(x):
    return np.asarray(x).view(np.uint16)


#: name -> (M, d, f, group sizes, tile_rows, contraction tile, None for all of d).  The cells' shapes scaled down by
#: 8 to 64: 32,768 rows over 32 experts of 2,048 x 2 x 1,792 at tiles of 512 rows (lfm2), 4,096 rows over 12 of
#: 7,168 x 2 x 2,048 at 128-512 rows (kimi: 7,168 was 3.5 contraction tiles of 2,048, and is 2 of 3,584), d 3,072 in
#: 2 tiles of 1,536 (trinity), d 2,304 and f 896 in one contraction tile (mellum).  The kernel takes its column tile
#: from ``f``: 256 or 128 where one divides it, else all of ``f``.  No contraction tile is over 64: beyond that the
#: CPU's own dot sums in an order that depends on how many columns it is given (a [64, 128] x [128, 16] product
#: differs from the same columns of [128, 224] in half its float32 results), which no kernel can be held to; the MXU
#: has no such order (PERF.md 6 has the chip's count of differing bits).
_CASES = {
    "uneven-groups-one-empty": (96, 32, 16, [40, 0, 50, 6], 512, None),
    "one-group-of-every-row": (96, 32, 16, [0, 96, 0, 0], 512, None),
    "rows-past-the-last-group": (96, 32, 16, [10, 0, 30, 7], 32, None),
    "no-row-in-any-group": (64, 32, 16, [0, 0, 0, 0], 32, None),
    "f-of-seven-column-tiles": (128, 64, 896, [50, 30, 0, 48], 64, None),            # 1,792 = 7 x 256
    "f-of-1792-at-the-tile-chosen": (64, 64, 1792, [20, 44], 32, None),
    "contraction-of-three-and-a-half-tiles": (200, 224, 384, [100, 50, 0, 50], 32, 56),  # 3.5 tiles of 64: 4 of 56
    "row-tile-128-scaled": (200, 224, 128, [3, 90, 17, 0, 60, 30], 16, 56),
    "row-tile-256-scaled": (200, 224, 128, [3, 90, 17, 0, 60, 30], 32, 56),
    "row-tile-512-scaled": (200, 224, 128, [3, 90, 17, 0, 60, 30], 64, 56),
    "rows-no-multiple-of-the-row-tile": (100, 64, 48, [30, 20, 45], 16, None),
    "group-edges-on-tile-edges": (128, 64, 128, [32, 64, 0, 32], 32, None),
    "f-of-three-column-tiles": (96, 64, 768, [40, 0, 50, 6], 32, None),
    "two-column-tiles-over-a-split-contraction": (96, 160, 512, [40, 0, 50, 6], 32, 40),
    "kimi-7168-in-two-tiles-scaled": (200, 112, 128, [3, 90, 17, 0, 60, 30], 16, 56),
    "trinity-3072-in-two-tiles-scaled": (160, 96, 96, [70, 0, 40, 50], 32, 48),
    "mellum-2304-in-one-tile-f-896-scaled": (120, 36, 14, [20, 0, 37, 7, 56], 32, None),
}


def _tiles_at(tile_k):
    """``moe.grouped_tiles`` with the contraction tile ``tile_k`` (all of it where None): the gated product's
    ``d`` and the three lines' product of ``rows`` and ``w13`` are then split alike."""
    rule = moe.grouped_tiles

    def tiles(m, d, f, tile_rows=moe.TILE_ROWS):
        (tm, _, gate_n), (_, _, w2_n) = rule(m, d, f, tile_rows)
        return (tm, tile_k or d, gate_n), (tm, tile_k or f, w2_n)
    return tiles


@pytest.mark.parametrize("case", list(_CASES), ids=list(_CASES))
def test_the_gated_product_is_the_three_lines_bit_for_bit(case, monkeypatch):
    m, d, f, sizes, tile_rows, tile_k = _CASES[case]
    monkeypatch.setattr(moe, "grouped_tiles", _tiles_at(tile_k))
    rng = np.random.default_rng(len(case))
    rows = jnp.asarray(rng.normal(size=(m, d)), BF16)
    w13 = jnp.asarray(rng.normal(size=(len(sizes), d, 2 * f)) / np.sqrt(d), BF16)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = moe.gated_grouped_matmul(rows, w13, sizes, tile_rows=tile_rows)
    want = _three_lines(rows, w13, sizes, tile_rows=tile_rows).astype(BF16)
    assert got.shape == (m, f) and got.dtype == BF16
    live = int(sizes.sum())  # rows past the last group are left uncomputed and hold anything
    np.testing.assert_array_equal(_bits(got[:live]), _bits(want[:live]))
    if live:
        assert np.isfinite(np.asarray(got[:live], np.float32)).all() and np.asarray(got[:live], np.float32).any()


def _lane_divisors(n):
    return [t for t in range(128, n + 1, 128) if n % t == 0]


#: (rows, d, f, tile_rows): the four routed cells' layers (lfm2 and mellum whole, a pass of trinity's and kimi's
#: shares at the row tiles a share takes) and wider layers no cell runs
_SHAPES = ((32768, 2048, 1792, 512), (262144, 2304, 896, 512), (32768, 3072, 3072, 512), (4096, 7168, 2048, 256),
           (4096, 7168, 2048, 128), (4096, 7168, 2048, 512), (65536, 2048, 8192, 512), (8192, 4096, 14336, 512),
           (4096, 1024, 384, 512))


def test_the_tile_is_read_off_the_shapes():
    for m, d, f, tile_rows in _SHAPES:
        gated, w2 = moe.grouped_tiles(m, d, f, tile_rows)
        # one row tile for both kernels: one set of group metadata
        assert gated[0] == w2[0] == tile_rows
        # gate and up in the widest whole lane tiles of at most 256 that divide f: the work of 512 columns a step
        assert gated[2] == max(t for t in _lane_divisors(f) if t <= 256)
        # W2's columns in the narrowest whole lane tiles of at least 512 that divide d
        assert w2[2] == min(t for t in _lane_divisors(d) if t >= 512)
        for (tm, tk, tn), k, weights, out_bytes, split_accs in ((gated, d, 2, 2, 2), (w2, f, 1, 4, 1)):
            # no remainder, whole lanes, under Mosaic's default VMEM; and no wider tile would fit: the fewest tiles
            assert k % tk == 0 and tk % 128 == 0 and tn % 128 == 0
            vmem = lambda t: moe._vmem_bytes(tm, t, tn, weights=weights, out_bytes=out_bytes,  # noqa: E731
                                             accumulators=split_accs if t < k or weights == 1 else 0)
            assert vmem(tk) <= 16 << 20
            assert all(vmem(t) > 16 << 20 for t in _lane_divisors(k) if t > tk)
    assert moe.grouped_tiles(4096, 1024, 384)[0][2] == 128  # 384 = 3 x 128: no wider tile under 256 divides it
    assert moe.grouped_tiles(40, 32, 16) == ((40, 32, 16), (40, 16, 32))  # a toy: one tile each, interpreted only
    with pytest.raises(ValueError, match="whole lane tiles"):
        moe.gated_grouped_matmul(jnp.zeros((8, 32), BF16), jnp.zeros((2, 32, 64), BF16), jnp.zeros(2, jnp.int32),
                                 interpret=False)
    with pytest.raises(ValueError, match="remainder"):  # a tile that does not divide d: the kernel masks nothing
        moe._gated_call(jnp.zeros((8, 96), BF16), jnp.zeros((2, 96, 64), BF16), jnp.zeros(2, jnp.int32),
                        jnp.dtype(BF16), (8, 64, 32), True)


#: cell -> ((rows, d, f, tile_rows), the gated product's tile, the W2 product's tile), compiled for a described v5e
#: by tests/test_flash_compile.py.  At the fixed contraction of 2,048 (and W2's 512 columns) mellum's gated product
#: took 2,048 + 256 and its W2 896 of 2,048 in 4.5 column tiles; lfm2's W2 1,792 of 2,048; trinity's 2,048 + 1,024
#: (3,072 whole is 16.5 MiB by the estimate, 16.1 by Mosaic's count); kimi's 3.5 x 2,048 (7,168 whole: 25.3 MiB).
_CELL_TILES = {
    "mellum-whole-layer": ((262144, 2304, 896, 512), (512, 2304, 128), (512, 896, 768)),
    "lfm2-whole-layer": ((32768, 2048, 1792, 512), (512, 2048, 256), (512, 1792, 512)),
    "trinity-share-512-rows": ((32768, 3072, 3072, 512), (512, 1536, 256), (512, 1536, 512)),
    "kimi-share-256-rows": ((4096, 7168, 2048, 256), (256, 3584, 256), (256, 2048, 512)),
}


@pytest.mark.parametrize("cell", list(_CELL_TILES), ids=list(_CELL_TILES))
def test_the_moe_cells_tiles(cell):
    shape, gated, w2 = _CELL_TILES[cell]
    assert moe.grouped_tiles(*shape) == (gated, w2)


#: The parent's W2 tiles, at its fixed contraction of 2,048 and columns of 512, scaled down by 16 as the shapes are:
#: name -> (M, f, d, group sizes, the parent's tile).  Its one contraction step covered all of f with a remainder
#: masked; the new tile is that step without the mask, so the two sum alike and must agree to the bit.
_REMAINDERS = {
    "mellum-w2-f896-d2304-scaled": (120, 56, 144, [20, 0, 37, 7, 56], (32, 128, 32)),  # 4.5 column tiles then
    "lfm2-w2-f1792-d2048-scaled": (96, 112, 128, [40, 0, 50, 6], (32, 128, 32)),
}


@pytest.mark.parametrize("case", list(_REMAINDERS), ids=list(_REMAINDERS))
def test_a_contraction_that_had_a_remainder_is_the_parents_product_bit_for_bit(case):
    m, f, d, sizes, parents = _REMAINDERS[case]
    rng = np.random.default_rng(len(case))
    hidden = jnp.asarray(rng.normal(size=(m, f)), BF16)
    w2 = jnp.asarray(rng.normal(size=(len(sizes), f, d)) / np.sqrt(f), BF16)
    sizes = jnp.asarray(sizes, jnp.int32)
    assert moe.grouped_tiles(m, d, f, 32)[1] == (32, f, d)  # one step, no mask: all of f, all of d
    got = moe.grouped_matmul(hidden, w2, sizes, tile_rows=32)
    want = moe.gmm(jnp.pad(hidden, ((0, -m % 32), (0, 0))), w2, sizes, preferred_element_type=jnp.float32,
                   tiling=parents, interpret=True)[:m]
    live = int(sizes.sum())
    np.testing.assert_array_equal(np.asarray(got[:live]).view(np.uint32), np.asarray(want[:live]).view(np.uint32))
    assert np.abs(np.asarray(got[:live])).mean() > 0.1


def test_a_layers_two_products_visit_the_same_row_tiles(monkeypatch):
    # PERF.md 6, PR 40: with a row tile of its own each kernel computes group metadata of its own, and the cell's
    # open() took 1.8-2.4 s longer.  What either kernel is handed, at the whole layer's rows and at a share's.
    seen = []  # (rows as padded, row tile), kernel by kernel

    def w2_kernel(lhs, rhs, sizes, tiling, **_):
        seen.append((lhs.shape[0], tiling[0]))
        return jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)

    def gated_kernel(rows, w13, sizes, dtype, tile, interpret):
        seen.append((-(-rows.shape[0] // tile[0]) * tile[0], tile[0]))
        return jnp.zeros((rows.shape[0], w13.shape[2] // 2), dtype)

    monkeypatch.setattr(moe, "gmm", w2_kernel)
    monkeypatch.setattr(moe, "_gated_call", gated_kernel)
    rng = np.random.default_rng(0)
    for experts, held, tokens in ((8, 8, 300), (64, 4, 1200)):  # 1,200 pairs at 512 rows; a share at 128
        w_router, b, w13, w2 = _layer_weights(rng, 32, 16, experts, held)
        x = jnp.asarray(rng.normal(size=(2, tokens, 32)).astype(np.float32))
        moe.routed_experts(x, w_router, b, w13, w2, k=2, compute_dtype=BF16)
    assert len(seen) == 4 and seen[0] == seen[1] == (1536, 512) and seen[2] == seen[3] and seen[2][1] == 128


# -- through the routed layer ------------------------------------------------------------


def _layer_weights(rng, d, f, experts, held):
    return tuple(jnp.asarray(w) for w in (
        rng.normal(size=(d, experts)).astype(np.float32) * 1.5 / np.sqrt(d),
        rng.normal(size=experts).astype(np.float32) * 0.05,
        rng.normal(size=(held, d, 2 * f)).astype(np.float32) / np.sqrt(d),
        rng.normal(size=(held, f, d)).astype(np.float32) / np.sqrt(f)))


def _as_it_was(monkeypatch, fn):
    """``fn()`` on the layer of the parent commit: the three lines in the gated product's place."""
    with monkeypatch.context() as m:
        m.setattr(moe, "gated_grouped_matmul", _three_lines)
        return fn()


#: The layer tests of tests/benchmark/test_lfm2_moe.py (d 32, f 16: 8 experts all held at k 2 and 4, one taking
#: every token and one none; shares of 8 of 32) and test_kimi_k2.py (shares of 4 of 16; every pair on the held
#: experts, in two and four passes).  name -> (experts, held, first, k, tokens a record, bias on the held ones)
_LAYERS = {
    "lfm2-whole-layer-top2": (8, 8, 0, 2, 24, None),
    "lfm2-whole-layer-top4": (8, 8, 0, 4, 24, None),
    "lfm2-share-8-of-32": (32, 8, 8, 4, 16, None),
    "kimi-share-4-of-16": (16, 4, 8, 2, 24, None),
    "kimi-every-pair-here-two-passes": (16, 4, 8, 4, 24, 10.0),
    "kimi-every-pair-here-four-passes": (16, 2, 8, 2, 24, 10.0),
}


@pytest.mark.parametrize("case", list(_LAYERS), ids=list(_LAYERS))
def test_the_routed_layer_is_the_layer_it_was_bit_for_bit(case, monkeypatch):
    experts, held, first, k, tokens, bias = _LAYERS[case]
    rng = np.random.default_rng(experts + held + k)
    w_router, b, w13, w2 = _layer_weights(rng, 32, 16, experts, held)
    if bias is not None:
        b = b.at[first:first + held].set(bias)
    elif held == experts:
        b = b.at[0].set(10.0).at[3].set(-10.0)  # a group of every token and a group of none
    x = jnp.asarray(rng.normal(size=(2, tokens, 32)).astype(np.float32))

    def layer():
        return moe.routed_experts(x, w_router, b, w13, w2, k=k, first=first, scaling=2.827, compute_dtype=BF16)

    got, want = layer(), _as_it_was(monkeypatch, layer)
    assert int(got.rows.sum()) > 0 and np.asarray(got.out).any()
    assert int(got.passes) == int(want.passes) and (bias is None or int(got.passes) > 1)
    np.testing.assert_array_equal(np.asarray(got.out).view(np.uint32), np.asarray(want.out).view(np.uint32))
    np.testing.assert_array_equal(got.experts, want.experts)


# -- what the program holds ----------------------------------------------------------------

#: tests/benchmark/test_kimi_k2.py's shapes for the whole-layer path: 256 rows (2 x 64 tokens, top-2), d 128, f 64.
_TRACED = (jax.ShapeDtypeStruct((2, 64, 128), jnp.float32), jax.ShapeDtypeStruct((128, 8), BF16),
           jax.ShapeDtypeStruct((8,), BF16), jax.ShapeDtypeStruct((8, 128, 128), BF16),
           jax.ShapeDtypeStruct((8, 64, 128), BF16))


def _layer_jaxpr(dtype):
    return jax.make_jaxpr(lambda x, *w: moe.routed_experts(x, *w, k=2, compute_dtype=jnp.dtype(dtype)).out)(*_TRACED)


def _kernels(jaxpr):
    """Every ``pallas_call`` of a jaxpr, nested ones too: (name, output avals)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], [v.aval for v in eqn.outvars]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernels(sub)
    return found


def test_no_float32_rows_by_2f_is_a_tensor_of_the_bfloat16_layer_and_its_kernel_is_named_gmm(monkeypatch):
    # f = 96, so that [rows, 2f] = [256, 192] is no other tensor's shape (d = 128)
    args = _TRACED[:3] + (jax.ShapeDtypeStruct((8, 128, 192), BF16), jax.ShapeDtypeStruct((8, 96, 128), BF16))
    trace = lambda: jax.make_jaxpr(  # noqa: E731
        lambda x, *w: moe.routed_experts(x, *w, k=2, compute_dtype=BF16).out)(*args)
    jaxpr = trace()
    assert not re.search(r"\w+\[256,192\]", str(jaxpr))  # in no type
    (first, (hidden,)), (_, (y,)) = _kernels(jaxpr.jaxpr)
    # the name the benchmark's roofline share finds the grouped products by (benchmark/readers/moe.md)
    assert first == "gmm" and (hidden.shape, hidden.dtype) == ((256, 96), BF16)
    assert (y.shape, y.dtype) == ((256, 128), jnp.float32)
    # the search does find it where it is: the parent's layer
    assert re.search(r"f32\[256,192\]", str(_as_it_was(monkeypatch, trace)))


def _hash(jaxpr):
    return hashlib.sha256(re.sub(r" at \S+:\d+", "", str(jaxpr)).encode()).hexdigest()[:16]


def test_the_float32_layer_traces_to_the_program_it_did(monkeypatch):
    now = _layer_jaxpr("float32")
    assert str(now) == str(_as_it_was(monkeypatch, lambda: _layer_jaxpr("float32")))
    assert not _kernels(now.jaxpr) and "ragged_dot" in str(now) and "f32[256,128]" in str(now)
    # ... and the share's path, a loop of passes
    share = lambda: jax.make_jaxpr(lambda x, *w: moe.routed_experts(  # noqa: E731
        x, *w, k=2, first=2, compute_dtype=jnp.float32).out)(*_TRACED[:3], *(
            jax.ShapeDtypeStruct((4,) + s.shape[1:], BF16) for s in _TRACED[3:]))
    assert str(share()) == str(_as_it_was(monkeypatch, share))


_RULE = moe.grouped_tiles


def _fixed_tiles(m, d, f, tile_rows=moe.TILE_ROWS):
    """The tiles the kernels had before they were read off the shapes: a contraction tile of 2,048 and ``W2``'s
    512 columns, whatever the operands (the gated product's columns were read off ``f`` already)."""
    (tm, _, gate_n), _ = _RULE(m, d, f, tile_rows)
    return (tm, min(2048, d), gate_n), (tm, 2048, 512)


def test_the_bfloat16_layer_no_longer_traces_to_the_parents_program(monkeypatch):
    # tests/benchmark/test_kimi_k2.py pins the hash of this very path before the gated product ("439b22ed23568f72");
    # with the three lines and the fixed tiles back in place the program is that one again: what changed since is
    # the gated product and the tiles, and no more.
    def traced():
        jaxpr = jax.make_jaxpr(lambda x, *w: tuple(moe.routed_experts(x, *w, k=2, compute_dtype=BF16))[:4])(*_TRACED)
        return _hash(jaxpr)

    with monkeypatch.context() as m:
        m.setattr(moe, "grouped_tiles", _fixed_tiles)
        before = _as_it_was(monkeypatch, traced)
    assert before == "439b22ed23568f72" != traced()
