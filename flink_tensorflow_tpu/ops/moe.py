"""Routed experts — a sparse feed-forward layer on one chip's share.

Every token picks ``k`` of ``num_experts`` gated MLPs by a router; the layer's
output is the weighted sum of the picked experts' outputs.  Dropless: a
(token, slot) pair is never discarded, so an expert's batch is whatever the
router gave it.  The pairs are sorted by expert, and each of the layer's two
products (``W1|W3`` side by side, then ``W2``) is ONE grouped product over
contiguous groups of rows against the experts' weights stacked
``[held, d, f]``; no row is padded to a capacity and none is computed twice.

The layer is told which experts it holds, ``[first, first + held)`` of
``num_experts`` (``held`` is the stacked weights' leading dimension).  The
router keeps its full width and chooses among all experts; pairs that fall on
an expert held elsewhere sort past the last group and contribute nothing,
which is a chip's part of an expert-parallel layer without its exchange.

A share costs what falls on it.  Where ``held < num_experts`` no buffer has a
row for every pair: the rows gathered, both products and their outputs are
``share_capacity`` rows long (twice what an even routing sends the share,
in whole row tiles), and the weighted outputs are added into ``[B T, d]`` at
their tokens (``_rows_at_tokens``: no scatter and no gather a slot).  Pairs
beyond the capacity are taken in further passes over the same buffers (a
``lax.while_loop``; ``Routed.passes`` says how many), so none is ever dropped,
however uneven the routing.  Only the sorted pair ids and their weights are
``B T k`` long.  On a v5e, 12 of 384 experts of 7,168 x 2,048 held, 8,192
tokens and top-8 (65,536 pairs, 1,058 to 2,024 of them here, a capacity of
4,096): 10.0 to 10.9 ms a layer alone, where a row for every pair took 26.5 to
27.2 (PERF.md section 6, PR 37, which also says where the 10 ms go: the two
products' floor is 1.36 ms).  Where every expert is held the layer is the
one-pass program it was before shares had a path of their own (at the same
sizes with every pair here it takes 62 ms, and sixteen passes 117).

A layer's two grouped products are two Pallas kernels.  The first
(:func:`gated_grouped_matmul`: rows against ``W1|W3``) is ``megablox.gmm``'s
algorithm with two accumulators: it reads the gate and the up half of ``w13``
where they are stored, and writes ``silu(gate) * up`` in ``compute_dtype``
``[rows, f]``, computed in float32 on its accumulators and rounded once.  The
second (:func:`grouped_matmul`: that against ``W2``) is ``megablox.gmm`` itself,
float32 out.  Both take their tiles from :func:`grouped_tiles`, whose
contraction and column tiles divide the operands: no grid step multiplies the
zeros of a remainder.  So what crosses HBM between
the two is the bfloat16 ``[rows, f]`` the second one reads, and the float32
``[rows, 2f]`` is no tensor of the program (until PR 40 the first product was
``gmm`` too, wrote it, and an XLA pass read it back for ``silu * up``: 470 MB
written and read for 117 MB a layer at 32,768 rows of 3,584).  On a v5e, alone,
at 32,768 rows of 2,048 against 32 experts of 2 x 1,792 and uneven groups (the
fullest 6.6% of the rows), before -> since: the first product 3.84 -> 3.85
ms, ``silu * up`` 0.83 -> none, the ``W2`` product 2.21, a layer's products
6.70 -> 6.09 (PERF.md section 6, PR 40; bit for bit the same ``[rows, f]``,
on the chip as interpreted).  ``gmm`` had taken 6.2 ms a layer's two products
where ``jax.lax.ragged_dot`` (which this XLA compiles to the same kernel at
tiles of its own choosing: the results are equal bit for bit) took 8.6 (PERF.md
section 6, PR 35).  A share's groups are small, and either kernel computes a
whole row tile for every group with a row in it: its row tile is then 256 or
128 (2,040 rows over 12 groups of 7,168 x 4,096: 2.57 ms at 256, 2.83 at 128,
2.97 at 512; PR 37).  In a share's loop of passes the narrower output has a
second effect, which is XLA's and not the kernel's: with 17 MB out where 67
were, the compiler keeps the pass's gathered rows (59 MB) in VMEM beside the
kernel, which reads them once a column tile: 2.24 -> 1.87 ms a pass in
``kimi_k2_7_code``'s step, where the two kernels alone take the same 2.46-2.48
(PERF.md section 6, PR 40).  float32 callers get ``ragged_dot`` at ``HIGHEST`` and
XLA's ``silu * up``: a kernel's MXU pass would round their operands to
bfloat16, as ops/flash_attention.py says of its own.

Where every expert is held, each token's ``k`` weighted rows of the ``W2``
product are summed back at the token.  Up to ``COMBINE_UNROLLED_BYTES`` of
slots XLA gathers a slot at a time and sums them (LFM2's layer); above it one
more kernel, :func:`combine_rows`, copies each row by DMA into VMEM and adds it
there, so every row is read once and the sum written once.  At Mellum 2's layer
(32,768 tokens, top-8, d = 2,304) on a v5e, alone: 5.6 ms, where the loop over
the slots it replaced took 22.9, the same to the bit (PERF.md section 6).

Precision: the router's product is float32 at ``Precision.HIGHEST`` (the
choice is discontinuous: it must not be made on rounded scores), as are the
sigmoid or the softmax, the selection bias, the top-k and the combine weights; the experts'
products take ``compute_dtype`` operands and accumulate in float32; the combine is float32
throughout, each token's rows weighed and added from slot 0 on.
"""

from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

# Mosaic's scoped VMEM default: the grouped kernels' tiles fit under it, so that neither names ``vmem_limit_bytes``
# (a call that names one, even the default, makes XLA set that much aside for the whole program: PERF.md 6).
from flink_tensorflow_tpu.ops.flash_attention import _VMEM_DEFAULT

F32 = jnp.float32
#: The grouped kernels' row tile (shrunk for fewer rows), and the work of a grid step in columns: the ``W2``
#: product's least column tile, twice the gated product's most (gate and up each).  The contraction tile has
#: no constant: :func:`grouped_tiles` reads it off the shapes.
TILE_ROWS, TILE_N = 512, 512
_LANES = 128
#: A share's buffers hold this many times the rows an even routing sends it.
CAPACITY_SLACK = 2
#: Where every expert is held and a layer's ``k`` slots, float32 ``[tokens, d]`` each, would hold more
#: than this at once, the combine is one kernel (:func:`combine_rows`) that copies each row it sums
#: straight into VMEM.  Unrolled, XLA gathers every slot before the sum: at Mellum 2's layer (32,768
#: tokens, top-8, d = 2,304: 8 x 302 MB) 8 layers took 15.4 GiB of scratch compiled for a v5e, over the
#: chip with the weights.  A loop over the slots fitted (8.0 GB) and took 22.9 ms a layer on a v5e,
#: writing each slot's gather out and reading it back beside the sum; the kernel takes 5.6 (PERF.md 6).
#: No timing sets the number itself: any cutoff between LFM2's 268 MB (unrolled, the program its cell
#: has always run) and Mellum's 2.4 GB (unrolled, no room) picks the same path for every model here.
COMBINE_UNROLLED_BYTES = 1 << 30


class Routed(typing.NamedTuple):
    """What :func:`routed_experts` returns."""

    #: ``[B, T, d]`` float32: the weighted sum of the held experts' outputs.
    out: jax.Array
    #: ``[B, T, k]`` int32: the experts each token chose, best first.
    experts: jax.Array
    #: ``[B]`` int32: a record's (token, slot) pairs that fell on a held expert.
    rows: jax.Array
    #: ``[]`` int32: the fullest held expert's rows over the whole batch.
    rows_max: jax.Array
    #: ``[]`` int32: the passes the layer took over its buffers (1 where every
    #: expert is held; see :func:`routed_experts`).
    passes: typing.Any


def route(x, w_router, bias, *, k: int, scaling: float = 1.0, eps: float = 1e-6, score_func: str = "sigmoid"):
    """The router: ``x`` ``[N, d]`` float32 -> (``experts`` int32 ``[N, k]``,
    ``weights`` float32 ``[N, k]``).

    ``score_func="sigmoid"``: a sigmoid router with a selection bias.  ``bias``
    chooses and never weighs: the top-k is over ``sigmoid(x W) + bias`` (ties
    to the lower index), the weights are the chosen experts' own scores over
    their sum plus ``eps``, times ``scaling``.

    ``score_func="softmax"``: ``s = softmax(x W)`` over all experts, the top-k
    of ``s`` (ties to the lower index), the weights ``s`` of the chosen over
    their sum, times ``scaling``.  The softmax is monotone in the logit, so the
    top-k is taken of the logits, where it cannot tie on a rounding of ``exp``,
    and ``s[sel] / sum(s[sel])`` is the softmax of the ``k`` chosen logits; no
    ``eps`` (the sum is at least ``k / num_experts``) and no ``bias``."""
    with jax.named_scope("router"):
        logits = jnp.dot(x.astype(F32), w_router.astype(F32), precision=lax.Precision.HIGHEST)
        if score_func == "softmax":
            if bias is not None:
                raise ValueError("a softmax router takes no selection bias")
            _, experts = lax.top_k(logits, k)
            weights = jax.nn.softmax(jnp.take_along_axis(logits, experts, axis=-1), axis=-1) * scaling
            return experts.astype(jnp.int32), weights
        if score_func != "sigmoid":
            raise ValueError(f"a router of score_func sigmoid or softmax, not {score_func!r}")
        scores = jax.nn.sigmoid(logits)
        _, experts = lax.top_k(scores + bias.astype(F32), k)
        picked = jnp.take_along_axis(scores, experts, axis=-1)
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps) * scaling
        return experts.astype(jnp.int32), weights


def _row_tile(m: int, tile_rows: int = TILE_ROWS) -> int:
    """The grouped kernels' row tile over ``m`` rows: ``tile_rows``, or all of
    fewer rows in whole sublanes.  One rule for a layer's two products: they
    then visit the same row tiles by the same group metadata."""
    return min(tile_rows, -(-m // 8) * 8)


def grouped_matmul(rows, stacked, group_sizes, *, compute_dtype=jnp.bfloat16, tile_rows: int = TILE_ROWS):
    """``rows[group g] @ stacked[g]`` for contiguous groups of ``rows`` ``[M,
    K]``; ``stacked`` ``[G, K, N]``, ``group_sizes`` int32 ``[G]``; float32
    out.  Rows past the last group are not computed and hold anything.  Off
    the TPU the kernel runs interpreted: the same code, as the flash kernel's.

    ``megablox.gmm`` at the ``W2`` tile of :func:`grouped_tiles` (a layer's
    ``W2`` product is ``[M, f] @ [f, d]``, so ``K`` is ``f`` and ``N`` is
    ``d``): contraction and column tiles that divide ``K`` and ``N``.  At a
    fixed 2,048 x 512, Mellum 2's ``f`` of 896 was one contraction step of
    2,048 with 56% of both blocks masked to zero, and its ``d`` of 2,304 was
    4.5 column tiles: 2.75 TFLOP of MXU work a layer for 1.08 (PERF.md 5)."""
    if jnp.dtype(compute_dtype) == F32:
        return lax.ragged_dot(rows.astype(F32), stacked.astype(F32), group_sizes,
                              precision=lax.Precision.HIGHEST, preferred_element_type=F32)
    m, k = rows.shape
    _, tile = grouped_tiles(m, stacked.shape[2], k, tile_rows)
    padded = jnp.pad(rows.astype(compute_dtype), ((0, -m % tile[0]), (0, 0)))
    out = gmm(padded, stacked.astype(compute_dtype), group_sizes, preferred_element_type=F32,
              tiling=tile, interpret=jax.default_backend() != "tpu")
    return out[:m]


def gated_grouped_matmul(rows, w13, group_sizes, *, compute_dtype=jnp.bfloat16, tile_rows: int = TILE_ROWS,
                         interpret: typing.Optional[bool] = None):
    """``silu(rows[group g] @ gate[g]) * (rows[group g] @ up[g])`` for contiguous
    groups of ``rows`` ``[M, d]``, where ``w13`` ``[G, d, 2f]`` holds expert
    ``g``'s gate in its first ``f`` columns and its up projection in the last
    ``f``; ``compute_dtype`` ``[M, f]`` out.  Rows past the last group are not
    computed and hold anything.

    One Pallas kernel, ``megablox.gmm`` with two accumulators: it reads ``w13``
    as it is stored through two block specs (column block ``j`` of either half),
    accumulates both products in float32 in VMEM and, on the last contraction
    step, writes ``silu(gate) * up`` computed in float32 on the accumulators and
    rounded ONCE, so the float32 ``[M, 2f]`` between a layer's two products is
    no tensor of the program.  Group metadata, the grid's order and the rows'
    mask of a tile that two groups share are ``gmm``'s; the tile is read off the
    shapes (:func:`grouped_tiles`; ``tile_rows`` is :func:`grouped_matmul`'s) and
    divides the contraction, so no step masks a remainder.  float32 callers get
    :func:`grouped_matmul`'s ``ragged_dot`` and the XLA ``silu * up``."""
    f = w13.shape[2] // 2
    if jnp.dtype(compute_dtype) == F32:
        both = grouped_matmul(rows, w13, group_sizes, compute_dtype=compute_dtype)
        return jax.nn.silu(both[:, :f]) * both[:, f:]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tile, _ = grouped_tiles(rows.shape[0], rows.shape[1], f, tile_rows)
    return _gated_call(rows, w13, group_sizes, jnp.dtype(compute_dtype), tile, interpret)


# Jitted as ``megablox.gmm`` is: a model's layers of one shape are traced and lowered once.  The tile is
# an argument, so that the trace reads nothing the cache's key does not hold.
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gated_call(rows, w13, group_sizes, compute_dtype, tile, interpret):
    f = w13.shape[2] // 2
    m, d = rows.shape
    tm, tk, tn = tile
    if not interpret and tn % _LANES:
        raise ValueError(f"gate and up of {f} columns cannot be blocked out of [d, 2f] in tiles of {tn}: "
                         f"compiled, a tile is whole lane tiles of {_LANES}")
    if d % tk or f % tn:
        raise ValueError(f"tiles of {tk} x {tn} leave a remainder of [{d}, {f}]: the kernel computes none")
    padded = jnp.pad(rows.astype(compute_dtype), ((0, -m % tm), (0, 0)))
    (offsets, group_ids, m_tile_ids), active_tiles = make_group_metadata(
        group_sizes=group_sizes, m=padded.shape[0], tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=w13.shape[0], visit_empty_groups=False)
    tiles_k = d // tk

    def kernel(offsets, group_ids, m_tile_ids, lhs, gate, up, out, *accs):
        visit, k_i = pl.program_id(1), pl.program_id(2)

        def step(last):
            x = lhs[...]
            parts = [lax.dot_general(x, w[...], (((1,), (0,)), ((), ())), preferred_element_type=F32)
                     for w in (gate, up)]
            if accs:
                for acc, part in zip(accs, parts):
                    acc[...] += part
                parts = [acc[...] for acc in accs]
            if last:  # only the rows of this visit's group: a tile may be shared by two
                group = group_ids[visit]
                row = lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + m_tile_ids[visit] * tm
                mine = (row >= offsets[group]) & (row < offsets[group + 1])
                gate_acc, up_acc = parts
                out[...] = jnp.where(mine, jax.nn.silu(gate_acc) * up_acc, out[...].astype(F32)).astype(out.dtype)

        if tiles_k == 1:  # nothing to accumulate over: no scratch
            step(True)
            return

        @pl.when(k_i == 0)
        def _():
            for acc in accs:
                acc[...] = jnp.zeros_like(acc)

        lax.cond(k_i == tiles_k - 1, functools.partial(step, True), functools.partial(step, False))

    half = f // tn  # the up half starts this many column blocks in
    call = pl.pallas_call(
        kernel,
        name="gmm",  # what the benchmark's roofline share finds the grouped products by
        out_shape=jax.ShapeDtypeStruct((padded.shape[0], f), compute_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lambda n, v, k, offsets, group_ids, m_tile_ids: (m_tile_ids[v], k)),
                      pl.BlockSpec((None, tk, tn), lambda n, v, k, offsets, group_ids, m_tile_ids: (group_ids[v], k, n)),
                      pl.BlockSpec((None, tk, tn),
                                   lambda n, v, k, offsets, group_ids, m_tile_ids: (group_ids[v], k, n + half))],
            out_specs=pl.BlockSpec((tm, tn), lambda n, v, k, offsets, group_ids, m_tile_ids: (m_tile_ids[v], n)),
            grid=(half, active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), F32)] * (2 if tiles_k > 1 else 0)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(flops=4 * m * d * f, transcendentals=m * f,
                                      bytes_accessed=(half * m * d + w13.size + m * f) * padded.dtype.itemsize),
        interpret=interpret)
    w13 = w13.astype(compute_dtype)
    return call(offsets, group_ids, m_tile_ids, padded, w13, w13)[:m]


def grouped_tiles(m: int, d: int, f: int, tile_rows: int = TILE_ROWS):
    """The tiles, (rows, contraction, columns) each, of a layer's two grouped
    kernels, read off its shapes: (the gated product's, ``[m, d]`` against
    ``[d, 2f]``; the ``W2`` product's, ``[m, f]`` against ``[f, d]``).

    No tile leaves a remainder.  A contraction tile that does not divide the
    contraction makes the last grid step a whole tile's MXU product, with both
    blocks masked to zero past the edge; a column tile that does not divide the
    columns computes the last tile's columns past the edge.  At Mellum 2's
    layer (d 2,304, f 896), at the fixed 2,048 x 512 these kernels had, the
    gated product did 4,096 / 2,304 of its work and a mask pass besides, the
    ``W2`` product 2.5 times its work: 387 ms of a 982 ms step at 34% of their
    floor.  Alone on a v5e at that layer (262,144 rows over 64 experts), the
    gated product took 33.1 ms at (512, 2,048, 128) and 15.2 at (512, 2,304,
    128), the ``W2`` product 17.2 at (512, 2,048, 512) and 7.7 at (512, 896,
    768) with its output the same to the bit (PERF.md 6).

    - Rows: :func:`_row_tile`, at a share's own ``tile_rows`` where it passes
      one, the same for both kernels, so that they visit the same row tiles by
      the same group metadata (a row tile for one alone cost ``open()`` 1.8-2.4
      s in LFM2's cell: PERF.md 6, the gated product).
    - Columns: gate and up in the widest whole lane tiles of at most half
      ``TILE_N`` each that divide ``f`` (the work of ``TILE_N`` columns a grid
      step; wider ones at a share's sizes were 1.3 ms of a 252 ms step and 0.7
      s of ``open()``); ``W2``'s in the narrowest whole lane tiles of at
      least ``TILE_N`` that divide ``d``.
    - Contraction: the fewest tiles that divide it, in whole lane tiles, whose
      VMEM (:func:`_vmem_bytes`) fits under Mosaic's default.  One tile where
      it fits: a group's weights' block then keeps its index over the group's
      row tiles and Pallas fetches it once, where a split contraction fetches it
      again every step (two tiles of 1,152 took Mellum's gated product 20.9
      ms where one of 2,304 took 15.2)."""
    tm = _row_tile(m, tile_rows)
    gate_n = max((n for n in range(_LANES, TILE_N // 2 + 1, _LANES) if f % n == 0), default=f)
    w2_n = min((n for n in range(TILE_N, d, _LANES) if d % n == 0), default=d)
    gate_k = _contraction_tile(d, lambda tk: _vmem_bytes(tm, tk, gate_n, weights=2, out_bytes=2,
                                                          accumulators=2 if tk < d else 0))
    w2_k = _contraction_tile(f, lambda tk: _vmem_bytes(tm, tk, w2_n, weights=1, out_bytes=4, accumulators=1))
    return (tm, gate_k, gate_n), (tm, w2_k, w2_n)


def _contraction_tile(k: int, vmem) -> int:
    """The widest tile that divides ``k``, all of it or whole lane tiles, whose
    ``vmem(tile)`` fits under Mosaic's default; the narrowest where none does."""
    tiles = [k] + [t for t in range(k - k % _LANES, 0, -_LANES) if t < k and k % t == 0]
    return next((t for t in tiles if vmem(t) <= _VMEM_DEFAULT), tiles[-1])


def _vmem_bytes(tm: int, tk: int, tn: int, *, weights: int, out_bytes: int, accumulators: int) -> int:
    """What a grouped kernel holds in VMEM at a tile, bfloat16 operands: the
    rows' block and ``weights`` weight blocks, each twice (the pipeline fetches
    the next step's while the MXU reads this one's), the output block twice,
    ``accumulators`` float32 ``[tm, tn]``, and what the body makes: the rows'
    block loaded once more and a float32 product a weight block.  Compiled for a
    described v5e at the cells' tiles, Mosaic's own count lies 3-8% under it for
    the gated kernel and 6-26% under it for ``gmm``."""
    return (2 * (tm * tk + weights * tk * tn) * 2 + tm * tk * 2 + 2 * tm * tn * out_bytes
            + (accumulators + weights) * tm * tn * 4)


def share_capacity(pairs: int, held: int, num_experts: int) -> int:
    """Rows of one pass over a share of the experts: ``CAPACITY_SLACK`` times
    what falls on ``held`` of ``num_experts`` when routing is even, rounded up
    to whole row tiles of the grouped kernel."""
    rows = -(-CAPACITY_SLACK * pairs * held // num_experts)
    tile = _row_tile(rows)
    return -(-rows // tile) * tile


def routed_experts(x, w_router, bias, w13, w2, *, k: int, first: int = 0, scaling: float = 1.0,
                   eps: float = 1e-6, score_func: str = "sigmoid", compute_dtype=jnp.bfloat16) -> Routed:
    """The routed layer on ``x`` ``[B, T, d]`` float32 (already normed).

    ``w_router`` ``[d, num_experts]``, ``bias`` ``[num_experts]`` (None with a softmax router); ``w13``
    ``[held, d, 2f]`` holds each held expert's gate (first ``f`` columns) and
    up projection side by side, ``w2`` ``[held, f, d]`` its down projection:
    an expert is ``w2(silu(gate x) * up x)``.  ``eps`` and ``score_func`` are
    the router's (:func:`route`)."""
    b, t, d = x.shape
    held, f = w2.shape[0], w2.shape[1]
    num_experts = w_router.shape[1]
    if not 0 <= first <= num_experts - held:
        raise ValueError(f"experts [{first}, {first + held}) are not among the router's {num_experts}")
    tokens = x.reshape(b * t, d)
    experts, weights = route(tokens, w_router, bias, k=k, scaling=scaling, eps=eps, score_func=score_func)

    with jax.named_scope("dispatch"):
        # Pair p is slot p % k of token p // k.  Pairs on an expert held
        # elsewhere get the key ``held`` and so sort past the last group.
        local = experts.reshape(-1) - first
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)

    if held == num_experts:
        out, passes = _whole_layer(tokens, order, group_sizes, weights, w13, w2, k, compute_dtype), 1
    else:
        out, passes = _share_of_layer(tokens, order, group_sizes, weights, w13, w2, k,
                                      share_capacity(b * t * k, held, num_experts), compute_dtype)

    return Routed(out.reshape(b, t, d), experts.reshape(b, t, k),
                  jnp.sum(here.reshape(b, t * k), axis=1, dtype=jnp.int32),
                  jnp.max(group_sizes), passes)


def _whole_layer(tokens, order, group_sizes, weights, w13, w2, k, compute_dtype):
    """Every pair falls on a held expert: a row for every pair, in one pass,
    summed back at its token (slot by slot, or by :func:`combine_rows` above
    ``COMBINE_UNROLLED_BYTES``)."""
    with jax.named_scope("dispatch"):
        rows = tokens.astype(compute_dtype)[order // k]

    with jax.named_scope("experts"):
        hidden = gated_grouped_matmul(rows, w13, group_sizes, compute_dtype=compute_dtype)
        y = grouped_matmul(hidden, w2, group_sizes, compute_dtype=compute_dtype)

    with jax.named_scope("combine"):
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
        back = back.reshape(tokens.shape[0], k)
        # Slot by slot: one ``[B T, k, d]`` gather would be re-tiled for its sublane of k.
        if k * tokens.size * 4 <= COMBINE_UNROLLED_BYTES:
            return sum(y[back[:, j]] * weights[:, j, None] for j in range(k))
        return combine_rows(y, back, weights)


def combine_rows(y, back, weights, *, interpret: typing.Optional[bool] = None):
    """A whole layer's combine: float32 ``[tokens, d]`` whose row ``t`` is
    ``y[back[t, 0]] * weights[t, 0] + ... + y[back[t, k-1]] * weights[t, k-1]``,
    summed from slot 0 on, for ``y`` float32 ``[rows, d]``, ``back`` int32 and
    ``weights`` float32 ``[tokens, k]``: the operations, in their order, of a
    loop that adds one slot's gathered rows at a time.

    One Pallas kernel over blocks of tokens (:func:`_combine_tokens`).  ``y``
    stays in HBM.  For slot ``j`` the kernel copies each token's row
    ``y[back[t, j]]`` into VMEM, one DMA a row, and starts slot ``j + 1``'s
    copies before it waits on slot ``j``'s; it adds the slot into the output
    block, which is written once.  So each row of ``y`` is read once and the sum
    written once, where the loop wrote every slot's gather out and read it back
    beside the sum it read and wrote again.  A row copy may not take one sublane
    of a tile, so ``y`` is handed over as ``[rows / 8, d / 128, 8, 1, 128]``,
    the same bytes (a bitcast) in tiles of one row, and the rows land in VMEM
    the same way; the block is summed as ``[tm / 8, d / 128, 8, 128]`` and
    written as ``[tm, d]``.  (Written out as the former, XLA's bitcast back to
    ``[tokens, d]`` changed how it fused the residual adds after the layer:
    Mellum 2's step held 0.6 GB more scratch on a v5e, PERF.md 6.)  Off the
    TPU it runs interpreted."""
    rows, d = y.shape
    if d % _LANES or rows % 8:
        raise ValueError(f"rows of [{rows}, {d}] are copied in tiles of one row of {_LANES} lanes, "
                         f"eight to a tile: {rows} rows of {d} are not")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _combine_call(y, back, weights, _combine_tokens(back.shape[0], d), interpret)


def _combine_tokens(tokens: int, d: int) -> int:
    """Tokens a grid step of :func:`combine_rows`: the most, a power of two,
    whose VMEM fits under Mosaic's default (two slots' rows, the output block
    twice, the sum, a slot's weighted rows as the sum makes them); all of fewer
    tokens in whole sublanes.  256 at Mellum 2's d = 2,304, where 128 took the same time
    on a v5e (PERF.md 6) and 512 does not fit."""
    tm = 8
    while 6 * (2 * tm) * d * 4 <= _VMEM_DEFAULT:
        tm *= 2
    return min(tm, -(-tokens // 8) * 8)


# Jitted as the grouped kernels are; the block is an argument, so that the trace reads nothing the key does not hold.
@functools.partial(jax.jit, static_argnums=(3, 4))
def _combine_call(y, back, weights, tm, interpret):
    n, k = back.shape
    d = y.shape[1]
    lanes = d // _LANES
    blocks = -(-n // tm)
    # A block's indices in one SMEM row, token-major, padded to whole tiles of XLA's s32 vector layout (1,024).
    stride = -(-tm * k // 1024) * 1024
    back = jnp.pad(back, ((0, blocks * tm - n), (0, 0))).reshape(blocks, tm * k)
    back = jnp.pad(back, ((0, 0), (0, stride - tm * k))).reshape(-1)
    # Padded tokens copy row 0 and weigh it 0; they are cut off below.
    weights = jnp.pad(weights, ((0, blocks * tm - n), (0, 0)))

    def kernel(back, w, y, out, buf, acc, sem):
        def copies(j):  # slot j's rows, eight tokens a loop step: no division, no sublane index computed
            def eight(g, carry):
                for u in range(8):
                    r = back[(g * 8 + u) * k + j]
                    pltpu.make_async_copy(y.at[lax.shift_right_logical(r, 3), :, lax.bitwise_and(r, 7)],
                                          buf.at[j % 2, g, :, u], sem.at[j % 2]).start()
                return carry
            lax.fori_loop(0, tm // 8, eight, 0)

        copies(0)
        for j in range(k):
            if j + 1 < k:
                copies(j + 1)
            # One wait for the slot's tm rows: a DMA semaphore counts the bytes that arrived.
            pltpu.make_async_copy(buf.at[j % 2], buf.at[j % 2], sem.at[j % 2]).wait()
            term = buf.at[j % 2].reshape(tm // 8, lanes, 8, _LANES)[...] * w[:, j:j + 1].reshape(tm // 8, 1, 8, 1)
            acc[...] = term if j == 0 else acc[...] + term
        # [tm / 8, d / 128, 8, 128] holds the block's rows as [tm, d] holds them in tiles of (8, 128)
        out[...] = acc[...].transpose(0, 2, 1, 3).reshape(tm, d)

    call = pl.pallas_call(
        kernel,
        name="combine_rows",  # not "gmm": the grouped products' roofline share finds its kernels by that name
        out_shape=jax.ShapeDtypeStruct((blocks * tm, d), F32),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((stride,), lambda i: (i,), memory_space=pltpu.SMEM),
                  pl.BlockSpec((tm, k), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tm, d), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((2, tm // 8, lanes, 8, 1, _LANES), F32),
                        pltpu.VMEM((tm // 8, lanes, 8, _LANES), F32), pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(flops=2 * n * k * d, transcendentals=0,
                                      bytes_accessed=(n * k * d + n * d) * 4 + n * k * 8),
        interpret=interpret)
    rows = y.reshape(-1, 8, lanes, _LANES).transpose(0, 2, 1, 3).reshape(-1, lanes, 8, 1, _LANES)
    return call(back, weights, rows)[:n]


def _share_of_layer(tokens, order, group_sizes, weights, w13, w2, k, capacity, compute_dtype):
    """``held < num_experts``: the pairs that fall here are the first ``sum(
    group_sizes)`` of ``order``, and every buffer is ``capacity`` rows long.
    Pass ``p`` takes sorted pairs ``[p capacity, (p + 1) capacity)``: gathers
    their tokens' rows, runs both grouped products with the pass's own group
    sizes, and adds the weighted outputs into ``[B T, d]`` at their tokens.
    As many passes as the pairs here need, so none is dropped; (out, passes)."""
    n = tokens.shape[0]
    # The kernel computes a whole row tile for every group that has a row in it, and a
    # share's groups are small: a tile near a group's rows under an even routing (a
    # power of two, 128 to 512) wastes less of the MXU on other groups' rows.
    even = capacity // (CAPACITY_SLACK * w2.shape[0])
    tile_rows = max(128, min(TILE_ROWS, 1 << max(even - 1, 0).bit_length()))
    here = jnp.sum(group_sizes)
    ends = jnp.cumsum(group_sizes)
    # Padded by one pass, so that a slice of ``capacity`` never runs off the end.
    order = jnp.pad(order, (0, capacity))
    weights = weights.reshape(-1)
    narrow = tokens.astype(compute_dtype)

    def one_pass(carry):
        p, out = carry
        lo = p * capacity
        with jax.named_scope("dispatch"):
            pairs = lax.dynamic_slice(order, (lo,), (capacity,))
            token = pairs // k
            rows = narrow[token]
            # Of each group, what lies in [lo, lo + capacity).
            sizes = (jnp.clip(ends - lo, 0, capacity) - jnp.clip(ends - group_sizes - lo, 0, capacity))
        with jax.named_scope("experts"):
            hidden = gated_grouped_matmul(rows, w13, sizes, compute_dtype=compute_dtype, tile_rows=tile_rows)
            y = grouped_matmul(hidden, w2, sizes, compute_dtype=compute_dtype, tile_rows=tile_rows)
        with jax.named_scope("combine"):
            # Rows past the last group are another chip's pairs: nothing was computed there.
            live = jnp.arange(capacity) < here - lo
            y = jnp.where(live[:, None], y, 0.0) * jnp.where(live, weights[pairs], 0.0)[:, None]
            return p + 1, out + _rows_at_tokens(y, jnp.where(live, token, n), n, k)

    passes = (here + capacity - 1) // capacity
    _, out = lax.while_loop(lambda carry: carry[0] < passes, one_pass,
                            (jnp.int32(0), jnp.zeros(tokens.shape, F32)))
    return out, passes


def _rows_at_tokens(y, token, n: int, k: int):
    """``[n, d]``: row ``t`` the sum of the rows of ``y`` ``[C, d]`` whose ``token``
    is ``t`` (at most ``k`` of them; a ``token`` of ``n`` is no token's).  Without
    a scatter, which this chip runs a row at a time (1.7 us a row of 7,168:
    PERF.md 6, PR 37): the rows are sorted by token, a run of one token is
    summed into its first row by doubling (a run is at most ``k`` long), and
    every token gathers the first row of its run."""
    by_token = jnp.argsort(token)
    token, y = token[by_token], y[by_token]
    step = 1
    while step < k:
        same = jnp.pad(token[step:] == token[:-step], (0, step))
        y = y + jnp.where(same[:, None], jnp.pad(y[step:], ((0, step), (0, 0))), 0.0)
        step *= 2
    # Where token t's run starts: the rows before it (one fused compare-and-count;
    # a binary search is thirteen gathers of ``n``, 0.8 ms a pass at the cell's sizes).
    first = jnp.minimum(jnp.sum(token[None, :] < jnp.arange(n)[:, None], axis=1), token.shape[0] - 1)
    return jnp.where((token[first] == jnp.arange(n))[:, None], y[first], 0.0)
