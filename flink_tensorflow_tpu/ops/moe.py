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

The grouped product is the Pallas kernel ``pallas.ops.tpu.megablox.gmm`` at
tiles of 512 rows x 2,048 x 512: on a v5e, at 32,768 rows of 2,048 against 32
experts of 3,584 and uneven groups, it took 6.2 ms a layer's two products
where ``jax.lax.ragged_dot`` (which this XLA compiles to the same kernel at
tiles of its own choosing: the results are equal bit for bit) took 8.6, and
5.5 against 6.3 on even groups (PERF.md section 6, PR 35).  A share's groups
are small, and the kernel computes a whole row tile for every group with a row
in it: its row tile is then 256 or 128 (2,040 rows over 12 groups of 7,168 x
4,096: 2.57 ms at 256, 2.83 at 128, 2.97 at 512; PR 37).  float32 callers
get ``ragged_dot`` at ``HIGHEST``: the kernel's MXU pass would round their
operands to bfloat16, as ops/flash_attention.py says of its own.

Precision: the router's product is float32 at ``Precision.HIGHEST`` (the
choice is discontinuous: it must not be made on rounded scores), as are the
sigmoid, the selection bias, the top-k and the combine weights; the experts'
products take ``compute_dtype`` operands and accumulate in float32.
"""

from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import gmm

F32 = jnp.float32
#: The grouped kernel's tiles: rows (shrunk for fewer rows), contraction, columns.
TILE_ROWS, TILE_K, TILE_N = 512, 2048, 512
#: A share's buffers hold this many times the rows an even routing sends it.
CAPACITY_SLACK = 2


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


def route(x, w_router, bias, *, k: int, scaling: float = 1.0, eps: float = 1e-6):
    """Sigmoid router with a selection bias: ``x`` ``[N, d]`` float32 ->
    (``experts`` int32 ``[N, k]``, ``weights`` float32 ``[N, k]``).

    ``bias`` chooses and never weighs: the top-k is over ``sigmoid(x W) +
    bias`` (ties to the lower index), the weights are the chosen experts' own
    scores over their sum plus ``eps``, times ``scaling``."""
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(jnp.dot(x.astype(F32), w_router.astype(F32),
                                        precision=lax.Precision.HIGHEST))
        _, experts = lax.top_k(scores + bias.astype(F32), k)
        picked = jnp.take_along_axis(scores, experts, axis=-1)
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps) * scaling
        return experts.astype(jnp.int32), weights


def grouped_matmul(rows, stacked, group_sizes, *, compute_dtype=jnp.bfloat16, tile_rows: int = TILE_ROWS):
    """``rows[group g] @ stacked[g]`` for contiguous groups of ``rows`` ``[M,
    K]``; ``stacked`` ``[G, K, N]``, ``group_sizes`` int32 ``[G]``; float32
    out.  Rows past the last group are not computed and hold anything.  Off
    the TPU the kernel runs interpreted: the same code, as the flash kernel's."""
    if jnp.dtype(compute_dtype) == F32:
        return lax.ragged_dot(rows.astype(F32), stacked.astype(F32), group_sizes,
                              precision=lax.Precision.HIGHEST, preferred_element_type=F32)
    m = rows.shape[0]
    tile = min(tile_rows, -(-m // 8) * 8)
    padded = jnp.pad(rows.astype(compute_dtype), ((0, -m % tile), (0, 0)))
    out = gmm(padded, stacked.astype(compute_dtype), group_sizes, preferred_element_type=F32,
              tiling=(tile, TILE_K, TILE_N), interpret=jax.default_backend() != "tpu")
    return out[:m]


def share_capacity(pairs: int, held: int, num_experts: int) -> int:
    """Rows of one pass over a share of the experts: ``CAPACITY_SLACK`` times
    what falls on ``held`` of ``num_experts`` when routing is even, rounded up
    to whole row tiles of the grouped kernel."""
    rows = -(-CAPACITY_SLACK * pairs * held // num_experts)
    tile = min(TILE_ROWS, -(-rows // 8) * 8)
    return -(-rows // tile) * tile


def routed_experts(x, w_router, bias, w13, w2, *, k: int, first: int = 0, scaling: float = 1.0,
                   eps: float = 1e-6, compute_dtype=jnp.bfloat16) -> Routed:
    """The routed layer on ``x`` ``[B, T, d]`` float32 (already normed).

    ``w_router`` ``[d, num_experts]``, ``bias`` ``[num_experts]``; ``w13``
    ``[held, d, 2f]`` holds each held expert's gate (first ``f`` columns) and
    up projection side by side, ``w2`` ``[held, f, d]`` its down projection:
    an expert is ``w2(silu(gate x) * up x)``.  ``eps`` is the router's
    (:func:`route`)."""
    b, t, d = x.shape
    held, f = w2.shape[0], w2.shape[1]
    num_experts = w_router.shape[1]
    if not 0 <= first <= num_experts - held:
        raise ValueError(f"experts [{first}, {first + held}) are not among the router's {num_experts}")
    tokens = x.reshape(b * t, d)
    experts, weights = route(tokens, w_router, bias, k=k, scaling=scaling, eps=eps)

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
    gathered back to its token slot by slot."""
    f = w2.shape[1]
    with jax.named_scope("dispatch"):
        rows = tokens.astype(compute_dtype)[order // k]

    with jax.named_scope("experts"):
        both = grouped_matmul(rows, w13, group_sizes, compute_dtype=compute_dtype)
        hidden = jax.nn.silu(both[:, :f]) * both[:, f:]
        y = grouped_matmul(hidden, w2, group_sizes, compute_dtype=compute_dtype)

    with jax.named_scope("combine"):
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
        back = back.reshape(tokens.shape[0], k)
        # Slot by slot: one ``[B T, k, d]`` gather would be re-tiled for its sublane of k.
        return sum(y[back[:, j]] * weights[:, j, None] for j in range(k))


def _share_of_layer(tokens, order, group_sizes, weights, w13, w2, k, capacity, compute_dtype):
    """``held < num_experts``: the pairs that fall here are the first ``sum(
    group_sizes)`` of ``order``, and every buffer is ``capacity`` rows long.
    Pass ``p`` takes sorted pairs ``[p capacity, (p + 1) capacity)``: gathers
    their tokens' rows, runs both grouped products with the pass's own group
    sizes, and adds the weighted outputs into ``[B T, d]`` at their tokens.
    As many passes as the pairs here need, so none is dropped; (out, passes)."""
    n, f = tokens.shape[0], w2.shape[1]
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
            both = grouped_matmul(rows, w13, sizes, compute_dtype=compute_dtype, tile_rows=tile_rows)
            hidden = jax.nn.silu(both[:, :f]) * both[:, f:]
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
