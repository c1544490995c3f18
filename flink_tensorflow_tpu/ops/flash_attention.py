"""Flash attention — pallas TPU kernel for the long-sequence hot path.

The reference has no attention at all (its sequence model is a BiLSTM,
SURVEY.md §5 "Long-context": absent); this framework treats long-context
as first-class, so the O(T^2)-memory-free attention primitive ships as a
native TPU kernel (pallas) rather than a composed jnp graph:

- one grid program per (batch*head, q-block, K/V tile): the q block and
  the f32 accumulators live in VMEM; a copied K/V tile is the whole key
  sequence where that fits, and the program walks it in column chunks
- online softmax (running max/denominator, kept lane-replicated so that a
  row's work is plain vector ops) — no [T, T] score matrix ever
  materializes in HBM
- the tile is read off the call's shape (:func:`tile_plan`), not named by
  the caller; only the chunks the diagonal crosses are masked
- ``jnp.dot(..., preferred_element_type=f32)`` keeps both matmuls on the
  MXU with f32 accumulation over bf16 inputs; float32 inputs contract at
  float32 precision (Mosaic's default would round them to bf16 — a
  0.01 output error the interpreter never shows)
- causal grids skip fully-masked K/V tiles and chunks entirely
  (upper-triangle blocks are never read or computed)
- a sliding window (``window=W``: key ``j`` seen by query ``i`` iff ``0 <=
  i - j < W``) skips the tiles and chunks below the band as well: the grid's
  K extent is the band's, not the sequence's, and only the chunks on the
  band's two edges are masked

Composes with the ``seq``-axis ring (parallel/ring_attention.py): ring
moves K/V shards BETWEEN chips over ICI, this kernel computes each local
block WITHIN a chip.  On non-TPU backends the kernel runs in interpreter
mode (tests) — same code path, no hand-written fallback to drift.
"""

from __future__ import annotations

import functools
import math
import typing


def flash_attention(
    q, k, v,
    *,
    causal: bool = False,
    scale: typing.Optional[float] = None,
    block_q: typing.Optional[int] = None,
    block_k: typing.Optional[int] = None,
    interpret: typing.Optional[bool] = None,
    return_lse: bool = False,
    q_rope=None,
    k_rope=None,
    rotate=None,
    window: typing.Optional[int] = None,
):
    """Attention over ``[B, T, H, D]`` tensors (same layout/semantics as
    parallel.full_attention).  The kernel picks its tile from the shape
    (:func:`tile_plan`); ``block_q`` / ``block_k`` name an edge instead, and
    shrink for short sequences as the chosen ones do.

    ``v`` may have another head size than ``q`` and ``k`` (``[B, Tk, Hkv,
    Dv]``: latent attention's keys carry a rotary part its values lack); the
    output is then ``[B, T, H, Dv]``, and nothing is padded to the larger size
    in HBM.  ``scale`` multiplies the scores (default ``1 / sqrt(D)``); it goes
    onto ``q`` where that rounds nothing (float32, or a power of two) and onto
    the float32 scores otherwise.

    Grouped queries: ``k`` and ``v`` may carry fewer heads than ``q``
    (``[B, T, Hkv, D]``, ``H`` a multiple of ``Hkv``); query head ``i``
    reads key/value head ``i // (H / Hkv)`` through the kernel's block
    index, so no repeated copy of K or V is ever made in HBM.

    A rotary part handed over beside ``q`` and ``k`` (latent attention:
    ``q_rope`` ``[B, T, H, R]``, ``k_rope`` ``[B, Tk, R]``, ONE head that
    every query head reads): scores are ``(q . k + q_rope . k_rope) *
    scale`` (default ``1 / sqrt(D + R)``), summed in float32, and the
    call takes a path of its own (:func:`_flash_split`) on which every
    operand is read where its projection wrote it: ``q``, ``k``, ``v``
    out of ``[B, T, H x D]`` through the block index, ``k_rope``'s tile
    by all heads, the output written ``[B, T, H x Dv]``; nothing is
    transposed, concatenated or repeated over heads in HBM.  Compiled,
    that path needs ``D`` and ``Dv`` in whole lane tiles of 128 (or one
    head).  ``k_rope`` comes turned; ``q_rope`` too, unless ``rotate=(cos,
    sin)`` (``[T, R / 2]`` each, as ``ops.mla.rope_pairs`` takes them) is
    given: then ``q_rope`` is what its projection wrote (float32, say)
    and the kernel turns adjacent pairs of a block in float32 before it
    rounds them to ``q``'s dtype.  A call without ``q_rope`` traces to
    the program it always did.

    A sliding window (``window=W``, causal calls only): query ``i`` sees key
    ``j`` iff ``0 <= i - j < W``.  The grid visits only the K tiles and the
    chunks that meet a q block's band ``[first_row - W + 1, last_row]``, the
    copied tile is at most half the band (three tiles a q block at ``W =
    4096``, so that a q block copies three K/V tiles of 2,048 rows and not two of
    8,192), and the call is named ``flash_attention_window`` so that a trace
    tells its calls from the causal ones.  Without ``window`` nothing changes.

    Head sizes it has run at on the chip (TPU v5e, bfloat16, causal,
    4,096 positions: 512 query rows a program, K and V copied whole,
    scores 512 columns at a time): 128 (20 query heads on 4,
    Falcon-H1), 64 (32 on 8, LFM2: the block's last dimension is then
    the whole head, half a lane tile wide), and latent attention's 128
    with a rotary part of 64 on ``q`` and ``k`` and 128 on ``v`` (64 on
    64, a scale of 0.1447 given, ``q_rope`` turned in the kernel:
    Kimi-K2; 6.34 ms a call, 55% of its roofline: the rotary product
    fills half the MXU's 128 and takes a pass of its own; the same
    heads concatenated to 192 through the plain call took 6.00 ms and
    4.5 ms of copies around it).  At 32,768 positions, heads of 128, 48
    query heads on 8 (Trinity-Large, PERF.md 6, PR 41): causal, K and V in
    four tiles of 8,192 rows; and the band of ``W = 4096``, tiles of 2,048
    rows, 540 compute tiles a head where the causal call visits 2,080.  The
    tests also run 16, 64 and 128, 24 with 16, and 24 + 8 with 16, and
    windows of 1 to 200 positions, interpreted.

    ``return_lse=True`` also returns the per-row log-sum-exp
    ``[B, H, T]`` (f32; the call is built without that output otherwise)
    — the residual that lets callers combine partial
    attention over K/V shards, which is how the seq-axis ring
    (parallel/ring_attention.py) folds this kernel's per-block outputs
    into a global softmax without ever materializing full scores."""
    import jax

    b, t, h, d = q.shape
    tk, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    if h % hkv or v.shape[2] != hkv:
        raise ValueError(f"{h} query heads cannot share {hkv} key / {v.shape[2]} value heads")
    if k.shape[3] != d:
        raise ValueError(f"queries of {d} cannot meet keys of {k.shape[3]}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if window is not None and (not causal or q_rope is not None or tk != t or int(window) < 1):
        raise ValueError(f"a window of {window} is a causal call's over its own {t} positions "
                         f"(keys {tk}, causal {causal}, rotary part {q_rope is not None})")
    if q_rope is not None:
        return _flash_split(q, q_rope, k, k_rope, v, causal=causal, scale=scale, block_q=block_q,
                            block_k=block_k, interpret=interpret, return_lse=return_lse, rotate=rotate)
    window = None if window is None else int(window)
    plan = tile_plan(t, tk, d, q.dtype, causal, block_q, block_k, dv=dv, window=window)

    # [B, T, H, D] -> [B*H, T, D]: one grid row per (batch, head).
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], x.shape[1], x.shape[3])

    out, lse = _flash_bh(
        to_bh(q), to_bh(k), to_bh(v), group=h // hkv,
        scale=1.0 / math.sqrt(d) if scale is None else float(scale),
        causal=causal, plan=plan, interpret=interpret, with_lse=return_lse, window=window,
    )
    out = out.reshape(b, h, t, dv).transpose(0, 2, 1, 3)
    if return_lse:
        return out, lse.reshape(b, h, t)  # drop the tiling-only unit dim
    return out


def _flash_split(q, q_rope, k, k_rope, v, *, causal, scale, block_q, block_k, interpret, return_lse, rotate):
    """The call with a rotary part handed over beside ``q`` and ``k``
    (:func:`flash_attention` has the shapes): every operand is read where its
    projection wrote it.  ``[B, T, H, D]`` is viewed ``[B, T, H x D]``, which
    moves nothing, and a program's block is head ``h``'s ``D`` lanes of a row
    block; the output is written the same way.  ``q_rope``'s block holds the
    rotary parts of as many heads as fill whole lane tiles
    (:func:`_rope_heads`: two at 64 a head), the program turns it if asked to
    and zeroes the other heads' lanes, once a q block, and ``k_rope`` is
    repeated across those lanes (a copy of ``[B, Tk, 128]``, one head), so
    that the rotary product is one plain contraction over the block's lanes
    and no lane is ever shifted between heads."""
    import jax.numpy as jnp

    b, t, h, d = q.shape
    tk, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    rope = q_rope.shape[3]
    if q_rope.shape != (b, t, h, rope) or k_rope is None or k_rope.shape != (b, tk, rope):
        raise ValueError(f"rotary parts of {q_rope.shape} on queries of {q.shape} and "
                         f"{None if k_rope is None else k_rope.shape} on keys of {k.shape}")
    if not interpret and any(n > 1 and w % _LANES for n, w in ((h, d), (hkv, d), (hkv, dv))):
        raise ValueError(f"heads of {d} and {dv} are no whole lane tiles: Mosaic cannot block them "
                         f"out of [B, T, H x D]; concatenate and make the plain call")
    per = _rope_heads(h, rope)
    plan = tile_plan(t, tk, d + rope, q.dtype, causal, block_q, block_k, dv=dv)
    if rotate is not None:  # blocks of cos and sin, and q_rope's in float32, double-buffered
        plan = plan._replace(vmem_bytes=plan.vmem_bytes + 3 * 2 * plan.block_q * per * rope * 4)
    fn = _build_flash_call(
        b * h, t, tk, d, jnp.dtype(q.dtype).name, causal, plan, interpret,
        _vma(q, q_rope, k, k_rope, v), h // hkv, return_lse, dv,
        1.0 / math.sqrt(d + rope) if scale is None else float(scale), h, rope, rotate is not None,
    )
    # cos and sin of a position's pairs, each beside itself (-sin, sin: the sign an even lane's
    # partner takes), across the block's heads
    turn = [jnp.tile((jnp.stack([x, x], axis=-1) * sign).reshape(t, rope), (1, per))
            for x, sign in zip(rotate or (), (jnp.ones(2, jnp.float32), jnp.asarray([-1.0, 1.0], jnp.float32)))]
    out, lse = fn(q.reshape(b, t, h * d), q_rope.reshape(b, t, h * rope), *turn, k.reshape(b, tk, hkv * d),
                  jnp.tile(k_rope, (1, 1, per)), v.reshape(b, tk, hkv * dv))
    out = out.reshape(b, t, h, dv)
    if return_lse:
        return out, lse.reshape(b, h, t)
    return out


def _rope_heads(heads: int, rope: int) -> int:
    """How many heads' rotary parts one block of ``q_rope`` ``[B, T, H x rope]``
    holds: the fewest that fill whole lane tiles (Mosaic blocks the last
    dimension by whole tiles of 128 or not at all), else all of them."""
    return next((n for n in range(1, heads) if heads % n == 0 and n * rope % _LANES == 0), heads)


def flash_attention_decode(
    q, k, v,
    lengths=None,
    *,
    return_lse: bool = False,
):
    """Single-step decode attention: ONE query per row over a cached
    prefix — the serving plane's per-token hot path.

    ``q``: ``[B, 1, H, D]`` (or ``[B, H, D]``), the current position's
    query.  ``k``/``v``: ``[B, C, H, D]`` KV-cache blocks at (padded)
    capacity ``C``.  ``lengths``: ``[B]`` int32 — the number of VALID
    cached positions per row; positions ``>= lengths[b]`` are masked
    out (cache slack never attends).  Returns ``[B, 1, H, D]`` in q's
    dtype (squeezed back to ``[B, H, D]`` for 3-D q), plus the per-row
    log-sum-exp ``[B, H, 1]`` f32 when ``return_lse=True`` — the same
    residual contract as :func:`flash_attention`, so ring-style callers
    (parallel/ring_attention.ring_decode_attention) fold shard outputs
    with ``_combine_blocks`` unchanged.

    Deliberately NOT a pallas grid: a 1-row q block leaves the MXU
    >99% idle, and the score row is ``[B, H, C]`` — O(C), not O(T^2) —
    so the online-softmax streaming that justifies the kernel buys
    nothing here.  A fused jnp einsum pair (f32 accumulation, masked
    softmax) is the fastest shape on TPU and CPU alike, and it jits
    into the decode step's single executable alongside the cache
    update.  A fully-masked row (``lengths == 0``) returns zeros with
    ``lse = -inf`` instead of NaN (inactive pool slots hit this).
    """
    import jax.numpy as jnp

    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, tq, h, d = q.shape
    if tq != 1:
        raise ValueError(
            f"flash_attention_decode takes exactly one query step, got T={tq}; "
            "use flash_attention for prefill"
        )
    c = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale  # [B,H,1,C]
    if lengths is not None:
        valid = jnp.arange(c)[None, None, None, :] < lengths[:, None, None, None]
        s = jnp.where(valid, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                      # [B,H,1]
    safe_m = jnp.where(jnp.isinf(m), 0.0, m)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isinf(s), 0.0, p)
    l = jnp.sum(p, axis=-1)                      # [B,H,1]
    denom = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    out = (out / denom.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    if squeeze:
        out = out[:, 0]
    if return_lse:
        lse = jnp.where(l == 0.0, -jnp.inf, safe_m + jnp.log(denom))
        return out, lse
    return out


def _tileable_block(t: int, pref: int) -> int:
    """Largest TPU-tileable block for a dim of size ``t``: Mosaic needs
    the block's sublane dim divisible by 8 OR equal to the whole array
    dim.  (A gcd here produced sizes like 4 for t=100, which lowers fine
    in interpret mode but crashes Mosaic on the real chip.)"""
    if t <= pref:
        return t  # one block spanning the dim — always legal
    b = pref
    while b >= 8:
        if t % b == 0 and b % 8 == 0:
            return b
        b = 1 << (b - 1).bit_length() - 1  # the next power of two below
    # No multiple-of-8 divisor (e.g. t odd): one whole-dim block.
    # Correct but VMEM-heavy for very long odd lengths — the stream
    # layer's power-of-two buckets keep production shapes off this path.
    return t


#: Lanes of a vector register: the running statistics are kept this wide.
_LANES = 128
#: What the chooser asks of a shape (PERF.md 6, PR 36 has the table behind them):
#: rows of queries a program holds, columns of scores it computes at once, and
#: the bytes of one copied K (or V) tile, which is the whole sequence if it fits.
_PREF_BLOCK_Q = 512
_PREF_CHUNK = 512
_KV_TILE_BYTES = 2 << 20
#: Mosaic's scoped default, and what is left of the chip's 128 MiB to ask for.
_VMEM_DEFAULT = 16 << 20
_VMEM_MOST = 100 << 20


class TilePlan(typing.NamedTuple):
    """What one call does with a ``(batch x head)`` row: the edges of the copied
    tiles, the columns of scores computed at once (a compute tile is ``block_q x
    chunk``), how many compute tiles a row visits, how many of those the diagonal
    crosses (they alone are masked), and an estimate of the VMEM the call needs
    (asked of Mosaic only where it is over Mosaic's own default)."""

    block_q: int
    block_k: int
    chunk: int
    tiles_visited: int
    tiles_masked: int
    vmem_bytes: int


def tile_plan(t: int, tk: int, d: int, dtype, causal: bool,
              block_q: typing.Optional[int] = None,
              block_k: typing.Optional[int] = None, *,
              dv: typing.Optional[int] = None,
              window: typing.Optional[int] = None) -> TilePlan:
    """The tile of a call, read off its shape.  An edge the caller names is kept
    (as far as `_tileable_block` allows) and is then the compute tile's edge too;
    an edge left out is chosen: up to 512 query rows a program, the whole key
    sequence copied once if K and V fit 2 MiB each, scores 512 columns at a time.
    ``d`` is the head size of ``q`` and ``k``, ``dv`` that of ``v`` and the output
    (``d`` if left out): the larger of the two sets the copied tile's rows.

    With a ``window`` the copied tile chosen is at most half the band (and at
    least a chunk), so that a q block copies a few tiles near its band and not
    whole sequences of keys it never reads.  ``tiles_visited`` counts the
    compute tiles (``block_q x chunk``) of one ``(batch x head)`` row that the
    kernel computes: those that meet the causal triangle, or the band, by
    :func:`_chunk_counts`, which is also the kernel's loop bounds; tiles above
    the diagonal or below the band are neither copied nor computed and are not
    counted.  ``tiles_masked`` counts those of them that the diagonal or the
    band's lower edge crosses: they alone are masked."""
    import numpy as np

    itemsize = np.dtype(dtype).itemsize
    lanes = lambda n: -(-n // _LANES) * _LANES  # noqa: E731  (VMEM pads the last dim)
    dv = d if dv is None else dv
    wide = lanes(max(d, dv))
    bq = _tileable_block(t, block_q or _PREF_BLOCK_Q)
    if block_k:
        bk = chunk = _tileable_block(tk, block_k)
    else:
        pref = max(_PREF_CHUNK, _KV_TILE_BYTES // (wide * itemsize))
        if window:
            pref = min(pref, max(_PREF_CHUNK, 1 << max(window // 2, 1).bit_length() - 1))
        bk = _tileable_block(tk, pref)
        # A chunk inside the copied tile starts at a multiple of itself: whole lane
        # tiles of scores and whole sublane tiles of K, or the tile is one chunk.
        chunk = next((c for c in (_PREF_CHUNK, 256, 128) if bk % c == 0), bk)
    visited = masked = 0
    for qi in range(t // bq):
        for j in range(_kv_steps(t, tk, bq, bk, window)):
            tile = _first_tile(qi, bq, bk, window) + j if window else j
            low, whole_lo, whole_hi, high = _chunk_counts(qi, tile, bq, bk, chunk, causal, window)
            visited, masked = visited + high - low, masked + (high - low) - (whole_hi - whole_lo)
    vmem = (
        2 * bq * (lanes(d) + lanes(dv)) * itemsize    # q and out blocks, double-buffered
        + 2 * bk * (lanes(d) + lanes(dv)) * itemsize  # K and V tiles, double-buffered
        + bq * (lanes(dv) * 4 + lanes(d) * itemsize)  # accumulator, scaled q
        + 2 * bq * _LANES * 4                   # running max and denominator
        + 2 * bq * _LANES * 4                   # the lse block, double-buffered
        + 4 * bq * lanes(chunk) * 4             # scores, mask, exp and its cast
    )
    return TilePlan(bq, bk, chunk, visited, masked, vmem)


def _chunk_counts(qi, j, block_q: int, block_k: int, chunk: int, causal: bool,
                  window: typing.Optional[int] = None):
    """Which of K tile ``j``'s chunks the rows of q block ``qi`` see: four chunk
    indices ``low <= whole_lo <= whole_hi <= high``, where rows of the block see
    chunks ``[low, high)`` at all and every row sees ``[whole_lo, whole_hi)``
    whole; the chunks outside the whole range are the masked ones (``low`` and
    ``whole_lo`` are 0 but for a ``window``'s lower edge).  Python ints and
    traced scalars alike: the kernel's loop bounds and `tile_plan`'s counts are
    this one rule."""
    n = block_k // chunk
    if not causal:
        return 0, 0, n, n
    if isinstance(qi, int):
        most, least = max, min
    else:
        import jax.numpy as jnp

        most, least = jnp.maximum, jnp.minimum
    # Chunk c holds keys lo + c*chunk .. lo + (c+1)*chunk - 1; row r sees keys 0 .. r.
    first_row, lo = qi * block_q, j * block_k
    if window is None:
        return (0, 0, least(most(first_row + 1 - lo, 0) // chunk, n),
                least((most(first_row + block_q - lo, 0) + chunk - 1) // chunk, n))
    # Row r sees keys r - window + 1 .. r: a chunk is seen at all where its last key
    # reaches the first row's band and its first key the last row; whole where it
    # lies above the last row's lower edge and below the first row.
    clip = lambda c, a, b: least(most(c, a), b)  # noqa: E731
    high = clip((first_row + block_q - lo + chunk - 1) // chunk, 0, n)
    low = clip((first_row - window + 1 - lo) // chunk, 0, high)
    whole_lo = clip((first_row + block_q - window - lo + chunk - 1) // chunk, low, high)
    whole_hi = clip((first_row + 1 - lo) // chunk, whole_lo, high)
    return low, whole_lo, whole_hi, high


def _first_tile(qi, block_q: int, block_k: int, window: int):
    """The first K tile that q block ``qi``'s band meets (Python ints or traced)."""
    if isinstance(qi, int):
        return max(qi * block_q - window + 1, 0) // block_k
    import jax.numpy as jnp

    return jnp.maximum(qi * block_q - window + 1, 0) // block_k


def _kv_steps(t: int, tk: int, block_q: int, block_k: int, window: typing.Optional[int]) -> int:
    """The grid's K extent: every K tile without a window; with one, the most
    tiles any q block's band meets (a step past a block's diagonal asks again for
    its last tile, which is then not copied, and computes nothing)."""
    if window is None:
        return tk // block_k
    return max((qi * block_q + block_q - 1) // block_k - _first_tile(qi, block_q, block_k, window) + 1
               for qi in range(t // block_q))


def _vma(*xs):
    """Union of the operands' varying-mesh-axes sets — required on pallas
    out_shapes when the kernel runs inside shard_map (check_vma=True)."""
    import jax

    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _flash_bh(q, k, v, *, scale, causal, plan, interpret, group=1, with_lse=True, window=None):
    """``q`` ``[B*H, T, D]``; ``k`` ``[B*H/group, Tk, D]``, ``v`` ``[B*H/group,
    Tk, Dv]``: row ``i`` of ``q`` reads row ``i // group`` of ``k`` and ``v``.
    Returns ``(out, lse)``, ``out`` ``[B*H, T, Dv]``, ``lse`` ``None`` unless
    asked for."""
    import jax

    bh, t, d = q.shape
    # Dtype keyed by NAME: ml_dtypes (bfloat16) have no portable .str.
    fn = _build_flash_call(
        bh, t, k.shape[1], d, jax.numpy.dtype(q.dtype).name, causal,
        plan, interpret, _vma(q, k, v), group, with_lse,
        v.shape[2], scale, window=window,
    )
    return fn(q, k, v)


@functools.lru_cache(maxsize=256)
def _build_flash_call(bh, t, tk, d, dtype_str, causal, plan, interpret, vma,
                      group, with_lse, dv, scale, heads=0, rope=0, rotate=False, window=None):
    """Jitted pallas_call per static configuration.  Building a fresh
    closure per invocation would defeat jax.jit's cache (keyed on the
    function object) and recompile the Mosaic kernel on EVERY eager call.

    With ``rope`` (:func:`_flash_split`) the operands are ``q`` ``[B, T, heads x
    d]``, ``q_rope`` ``[B, T, heads x rope]``, ``k`` ``[B, Tk, heads / group x
    d]``, ``k_rope`` ``[B, Tk, lanes]`` (one head, repeated over a ``q_rope``
    block's lanes) and ``v``; grid row ``b_`` is head ``b_ % heads`` of batch
    ``b_ // heads``, and the body is the same but for a second product into the
    scores."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype_str)
    block_q, block_k, chunk = plan.block_q, plan.block_k, plan.chunk
    nq, nk = t // block_q, tk // block_k
    if window:
        # The band's K extent; a masked score is finite, since a row can meet a tile
        # (the band's first) in which it sees no key before it has seen one.
        nk = _kv_steps(t, tk, block_q, block_k, window)
        hidden = -0.7 * float(jnp.finfo(jnp.float32).max)
    # Mosaic's default contraction feeds the MXU one bf16 pass whatever
    # the operand dtype: a silent downcast of q/k/v/p for float32 callers,
    # who get float32 operands at HIGHEST; narrower callers' tiles go to the
    # MXU as they are (no widened copy in VMEM).
    wide = dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if wide else None
    # The scale goes onto q once a q block where that rounds nothing (float32,
    # or a power of two: 1/8 at a head of 64), else onto the float32 scores.
    scale_q = wide or math.frexp(scale)[0] == 0.5
    # A q_rope block holds `per` heads' rotary parts, `lanes` wide in all.
    per = _rope_heads(heads, rope) if rope else 0
    lanes = per * rope

    def kv_tile(b_, qi, j):
        # Row b_ of q reads row b_ // group of k and v (grouped queries).
        # Causal: a tile wholly above the diagonal is not computed, and
        # asking again for the last visible one keeps it from being copied.
        if window:  # the band's tiles from its first; past the diagonal, the last again
            j = jnp.minimum(_first_tile(qi, block_q, block_k, window) + j, (qi * block_q + block_q - 1) // block_k)
        elif causal:
            j = jnp.minimum(j, (qi * block_q + block_q - 1) // block_k)
        return (b_ // group, j, 0)

    def head_of(index, each=1):
        """``index`` (a `[B*H, ..]` block index) on ``[B, .., H x D]``: the batch
        first, the head last, ``each`` heads a block (all of them: one block for all)."""
        def split(b_, qi, j):
            _, row, _ = index(b_, qi, j)
            return (b_ // heads, row, b_ % heads // each)
        return split

    def across(x, n):
        """Lane-replicated ``(block_q, 128)`` statistics, ``n`` lanes wide."""
        if n % _LANES == 0:
            return jnp.tile(x, (1, n // _LANES))
        return x[:, :n] if n < _LANES else jnp.broadcast_to(x[:, :1], (block_q, n))

    def kernel(q_ref, *refs):
        if rotate:
            qr_ref, cos_ref, sin_ref, k_ref, kr_ref, v_ref, o_ref, *rest = refs
        elif rope:
            qr_ref, k_ref, kr_ref, v_ref, o_ref, *rest = refs
        else:
            k_ref, v_ref, o_ref, *rest = refs
        # Grid (bh, nq, nk): the innermost k dimension iterates sequentially
        # on TPU, so the VMEM scratch carries the online softmax across K/V
        # tiles and across the chunks of one.  The running max and denominator
        # stay two-dimensional and lane-replicated from the reduction to the
        # store: `s - m` and `acc * alpha` are then plain vector ops.
        lse_ref, (m_scr, l_scr, acc_scr, *q_scr) = (rest[0], rest[1:]) if with_lse else (None, rest)
        qr_scr = q_scr.pop() if rope else None  # this head's rotary part, the block's other lanes zero
        q_scr = q_scr[0] if scale_q else None  # q times the scale, once a q block
        qi = pl.program_id(1)
        j = pl.program_id(2)
        # The K tile this step reads: the j-th of the band's, or the j-th.
        tile = _first_tile(qi, block_q, block_k, window) + j if window else j
        head = pl.program_id(0) % heads if rope else None

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full((block_q, _LANES), -jnp.inf, jnp.float32)
            l_scr[...] = jnp.zeros((block_q, _LANES), jnp.float32)
            acc_scr[...] = jnp.zeros((block_q, dv), jnp.float32)
            if scale_q:
                q_scr[...] = q_ref[0] * scale
            if rope:
                lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, lanes), 1)
                x = qr_ref[0]
                if rotate:
                    # (x[2j], x[2j+1]) turns to (x[2j] cos - x[2j+1] sin, x[2j+1] cos + x[2j] sin), in
                    # float32; sin_ref holds -sin on the even lanes.  (The sum in this order: on the CPU
                    # XLA contracts one of the products into the add, and tests/benchmark/test_kimi_k2.py
                    # holds a float32 program to the reference's routing across a tie of three ulps.)
                    x = x.astype(jnp.float32)
                    partner = jnp.where(lane % 2 == 0, pltpu.roll(x, lanes - 1, 1), pltpu.roll(x, 1, 1))
                    x = (partner * sin_ref[...] + x * cos_ref[...]).astype(dtype)
                mine = jnp.where(lane // rope == head % per, x, 0)
                qr_scr[...] = mine * scale if scale_q else mine

        def update(c, masked):
            """Fold chunk ``c`` of this K tile into the running softmax.  No row
            is ever empty here: key 0 is in the first chunk a row visits (causal
            rows see keys 0..r, others see all), so the max is finite from then
            on, ``exp(-inf - m)`` is 0 for a masked score and for the first
            ``alpha``, and nothing needs a guard.  In a band a row can see no
            key of the first chunks it visits: its masked scores are then
            ``hidden``, finite, and what they add is wiped by the first
            ``alpha`` after its first real key (``exp(hidden - m)`` is 0), which
            every row meets on its diagonal."""
            if chunk == block_k:
                k_blk, v_blk = k_ref[0], v_ref[0]
            else:
                cols = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
                k_blk, v_blk = k_ref[0, cols, :], v_ref[0, cols, :]
            q_blk = q_scr[...] if scale_q else q_ref[0]
            s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                                    precision=precision,
                                    preferred_element_type=jnp.float32)
            if rope:
                # The other heads' lanes of q are zero, so k_rope's copies there add nothing.
                kr_blk = kr_ref[0] if chunk == block_k else kr_ref[0, cols, :]
                s = s + jax.lax.dot_general(qr_scr[...], kr_blk, (((1,), (1,)), ((), ())),
                                            precision=precision,
                                            preferred_element_type=jnp.float32)
            if not scale_q:
                s = s * scale
            if masked:
                # key j*block_k + c*chunk + col <= query qi*block_q + row
                rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, chunk), 0)
                cols_ = jax.lax.broadcasted_iota(jnp.int32, (block_q, chunk), 1)
                ahead = qi * block_q - tile * block_k - c * chunk
                if window:  # and key > query - window
                    s = jnp.where((cols_ - rows <= ahead) & (cols_ - rows > ahead - window), s, hidden)
                else:
                    s = jnp.where(cols_ - rows <= ahead, s, -jnp.inf)
            m_prev = m_scr[...]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - across(m_next, chunk))
            alpha = jnp.exp(m_prev - m_next)
            m_scr[...] = m_next
            l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * across(alpha, dv) + jnp.dot(
                p.astype(v_blk.dtype), v_blk, precision=precision,
                preferred_element_type=jnp.float32)

        # Chunks below the diagonal first, with no mask; then those it crosses.
        if window:  # the band's lower edge, the chunks every row sees whole, the diagonal
            low, whole_lo, whole_hi, high = _chunk_counts(qi, tile, block_q, block_k, chunk, causal, window)
            jax.lax.fori_loop(low, whole_lo, lambda c, _: update(c, True), None)
            jax.lax.fori_loop(whole_lo, whole_hi, lambda c, _: update(c, False), None)
            jax.lax.fori_loop(whole_hi, high, lambda c, _: update(c, True), None)
        else:
            _, _, whole, some = _chunk_counts(qi, j, block_q, block_k, chunk, causal)
            jax.lax.fori_loop(0, whole, lambda c, _: update(c, False), None)
            if causal:
                jax.lax.fori_loop(whole, some, lambda c, _: update(c, True), None)

        @pl.when(j == nk - 1)
        def _finalize():
            l = l_scr[...]
            denom = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_scr[...] / across(denom, dv)).astype(o_ref.dtype)
            if with_lse:
                # log-sum-exp residual; rows that saw no key (l=0, m=-inf) -> -inf.
                lse = jnp.where(l == 0.0, -jnp.inf, m_scr[...] + jnp.log(denom))
                lse_ref[0] = lse[:, :1]

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def q_tile(b_, qi, j):
        return (b_, qi, 0)

    if rope:
        in_specs = [
            spec((1, block_q, d), head_of(q_tile)),
            spec((1, block_q, lanes), head_of(q_tile, per)),
            *[spec((block_q, lanes), lambda b_, qi, j: (qi, 0))] * (2 * rotate),  # cos, sin: by position alone
            spec((1, block_k, d), head_of(kv_tile, group)),
            # one head for all: the block index does not change with the head, so the
            # tile is copied once a batch row and every head's programs read it
            spec((1, block_k, lanes), head_of(kv_tile, heads)),
            spec((1, block_k, dv), head_of(kv_tile, group)),
        ]
        out_specs = [spec((1, block_q, dv), head_of(q_tile))]
        out_shape = [jax.ShapeDtypeStruct((bh // heads, t, heads * dv), dtype, vma=vma)]
    else:
        in_specs = [spec((1, block_q, d), q_tile), spec((1, block_k, d), kv_tile), spec((1, block_k, dv), kv_tile)]
        out_specs = [spec((1, block_q, dv), q_tile)]
        out_shape = [jax.ShapeDtypeStruct((bh, t, dv), dtype, vma=vma)]
    if with_lse:
        # Trailing unit dim keeps the block's last-two dims TPU-tileable
        # ((block_q, 1) instead of (1, block_q)).
        out_specs.append(spec((1, block_q, 1), q_tile))
        out_shape.append(jax.ShapeDtypeStruct((bh, t, 1), jnp.float32, vma=vma))
    call = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ] + ([pltpu.VMEM((block_q, d), dtype)] if scale_q else [])
        + ([pltpu.VMEM((block_q, lanes), dtype)] if rope else []),
        # bh and q-blocks are independent programs (scratch re-inits at
        # j==0 per (bh, qi)): declaring them parallel lets Mosaic
        # megacore-partition the grid on v4/v5p; only the K sweep is
        # order-dependent (online-softmax carry).
        # A limit is asked for only by a tile that needs more than Mosaic's own:
        # naming one, even the default, makes XLA set that much VMEM aside for
        # the whole program, and its other fusions then prefetch and tile with
        # less (PERF.md 6, PR 36: 0.8% on every op of the Falcon step).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=(min(plan.vmem_bytes, _VMEM_MOST)
                              if plan.vmem_bytes > _VMEM_DEFAULT else None),
        ),
        interpret=interpret,
        name="flash_attention_window" if window else "flash_attention",
    )

    def fn(*operands):
        out, *lse = call(*operands)
        return out, (lse[0] if with_lse else None)

    return jax.jit(fn)
