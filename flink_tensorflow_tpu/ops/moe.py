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

The grouped product is the Pallas kernel ``pallas.ops.tpu.megablox.gmm`` at
tiles of 512 rows x 2,048 x 512: on a v5e, at 32,768 rows of 2,048 against 32
experts of 3,584 and uneven groups, it took 6.2 ms a layer's two products
where ``jax.lax.ragged_dot`` (which this XLA compiles to the same kernel at
tiles of its own choosing: the results are equal bit for bit) took 8.6, and
5.5 against 6.3 on even groups (PERF.md section 6, PR 35).  float32 callers
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


def grouped_matmul(rows, stacked, group_sizes, *, compute_dtype=jnp.bfloat16):
    """``rows[group g] @ stacked[g]`` for contiguous groups of ``rows`` ``[M,
    K]``; ``stacked`` ``[G, K, N]``, ``group_sizes`` int32 ``[G]``; float32
    out.  Rows past the last group are not computed and hold anything.  Off
    the TPU the kernel runs interpreted: the same code, as the flash kernel's."""
    if jnp.dtype(compute_dtype) == F32:
        return lax.ragged_dot(rows.astype(F32), stacked.astype(F32), group_sizes,
                              precision=lax.Precision.HIGHEST, preferred_element_type=F32)
    m = rows.shape[0]
    tile = min(TILE_ROWS, -(-m // 8) * 8)
    padded = jnp.pad(rows.astype(compute_dtype), ((0, -m % tile), (0, 0)))
    out = gmm(padded, stacked.astype(compute_dtype), group_sizes, preferred_element_type=F32,
              tiling=(tile, TILE_K, TILE_N), interpret=jax.default_backend() != "tpu")
    return out[:m]


def routed_experts(x, w_router, bias, w13, w2, *, k: int, first: int = 0, scaling: float = 1.0,
                   compute_dtype=jnp.bfloat16) -> Routed:
    """The routed layer on ``x`` ``[B, T, d]`` float32 (already normed).

    ``w_router`` ``[d, num_experts]``, ``bias`` ``[num_experts]``; ``w13``
    ``[held, d, 2f]`` holds each held expert's gate (first ``f`` columns) and
    up projection side by side, ``w2`` ``[held, f, d]`` its down projection:
    an expert is ``w2(silu(gate x) * up x)``."""
    b, t, d = x.shape
    held, f = w2.shape[0], w2.shape[1]
    num_experts = w_router.shape[1]
    if not 0 <= first <= num_experts - held:
        raise ValueError(f"experts [{first}, {first + held}) are not among the router's {num_experts}")
    tokens = x.reshape(b * t, d)
    experts, weights = route(tokens, w_router, bias, k=k, scaling=scaling)

    with jax.named_scope("dispatch"):
        # Pair p is slot p % k of token p // k.  Pairs on an expert held
        # elsewhere get the key ``held`` and so sort past the last group.
        local = experts.reshape(-1) - first
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
        rows = tokens.astype(compute_dtype)[order // k]

    with jax.named_scope("experts"):
        both = grouped_matmul(rows, w13, group_sizes, compute_dtype=compute_dtype)
        hidden = jax.nn.silu(both[:, :f]) * both[:, f:]
        y = grouped_matmul(hidden, w2, group_sizes, compute_dtype=compute_dtype)

    with jax.named_scope("combine"):
        if held < num_experts:
            # Rows past the last group are another chip's pairs: nothing was computed there.
            y = jnp.where(jnp.arange(y.shape[0])[:, None] < jnp.sum(group_sizes), y, 0.0)
            weights = jnp.where(here.reshape(weights.shape), weights, 0.0)
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
        back = back.reshape(b * t, k)
        # Slot by slot: one ``[B T, k, d]`` gather would be re-tiled for its sublane of k.
        out = sum(y[back[:, j]] * weights[:, j, None] for j in range(k))

    return Routed(out.reshape(b, t, d), experts.reshape(b, t, k),
                  jnp.sum(here.reshape(b, t * k), axis=1, dtype=jnp.int32),
                  jnp.max(group_sizes))
