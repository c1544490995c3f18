"""Flash attention — pallas TPU kernel for the long-sequence hot path.

The reference has no attention at all (its sequence model is a BiLSTM,
SURVEY.md §5 "Long-context": absent); this framework treats long-context
as first-class, so the O(T^2)-memory-free attention primitive ships as a
native TPU kernel (pallas) rather than a composed jnp graph:

- one grid program per (batch*head, q-block): the q block and the
  f32 accumulators live in VMEM; K/V stream through in ``block_k`` tiles
- online softmax (running max/denominator) — no [T, T] score matrix ever
  materializes in HBM
- ``jnp.dot(..., preferred_element_type=f32)`` keeps both matmuls on the
  MXU with f32 accumulation over bf16 inputs; float32 inputs contract at
  float32 precision (Mosaic's default would round them to bf16 — a
  0.01 output error the interpreter never shows)
- causal grids skip fully-masked K/V tiles entirely (upper-triangle
  blocks are never read)

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
    block_q: int = 128,
    block_k: int = 128,
    interpret: typing.Optional[bool] = None,
    return_lse: bool = False,
):
    """Attention over ``[B, T, H, D]`` tensors (same layout/semantics as
    parallel.full_attention).  Block sizes shrink automatically for short
    sequences; the stream layer's power-of-two buckets keep them aligned.

    Grouped queries: ``k`` and ``v`` may carry fewer heads than ``q``
    (``[B, T, Hkv, D]``, ``H`` a multiple of ``Hkv``); query head ``i``
    reads key/value head ``i // (H / Hkv)`` through the kernel's block
    index, so no repeated copy of K or V is ever made in HBM.

    Head sizes it has run at on the chip (TPU v5e, bfloat16, causal,
    blocks of 512 over 4,096 positions): 128 (20 query heads on 4,
    Falcon-H1) and 64 (32 on 8, LFM2: the block's last dimension is then
    the whole head, half a lane tile wide).  The tests also run 16, 32
    and 64 interpreted.

    ``return_lse=True`` also returns the per-row log-sum-exp
    ``[B, H, T]`` (f32) — the residual that lets callers combine partial
    attention over K/V shards, which is how the seq-axis ring
    (parallel/ring_attention.py) folds this kernel's per-block outputs
    into a global softmax without ever materializing full scores."""
    import jax

    b, t, h, d = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    if h % hkv or v.shape[2] != hkv:
        raise ValueError(f"{h} query heads cannot share {hkv} key / {v.shape[2]} value heads")
    block_q = _tileable_block(t, block_q)
    block_k = _tileable_block(tk, block_k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # [B, T, H, D] -> [B*H, T, D]: one grid row per (batch, head).
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], x.shape[1], d)

    out, lse = _flash_bh(
        to_bh(q), to_bh(k), to_bh(v), group=h // hkv,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    out = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    if return_lse:
        return out, lse.reshape(b, h, t)  # drop the tiling-only unit dim
    return out


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
    for b in (pref, 128, 64, 32, 16, 8):
        if b <= pref and t % b == 0:
            return b
    # No multiple-of-8 divisor (e.g. t odd): one whole-dim block.
    # Correct but VMEM-heavy for very long odd lengths — the stream
    # layer's power-of-two buckets keep production shapes off this path.
    return t


def _vma(*xs):
    """Union of the operands' varying-mesh-axes sets — required on pallas
    out_shapes when the kernel runs inside shard_map (check_vma=True)."""
    import jax

    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _flash_bh(q, k, v, *, causal, block_q, block_k, interpret, group=1):
    """``q`` ``[B*H, T, D]``; ``k``, ``v`` ``[B*H/group, Tk, D]``: row ``i`` of
    ``q`` reads row ``i // group`` of ``k`` and ``v``."""
    import jax

    bh, t, d = q.shape
    # Dtype keyed by NAME: ml_dtypes (bfloat16) have no portable .str.
    fn = _build_flash_call(
        bh, t, k.shape[1], d, jax.numpy.dtype(q.dtype).name, causal,
        block_q, block_k, interpret, _vma(q, k, v), group,
    )
    return fn(q, k, v)


@functools.lru_cache(maxsize=256)
def _build_flash_call(bh, t, tk, d, dtype_str, causal, block_q, block_k,
                      interpret, vma, group=1):
    """Jitted pallas_call per static configuration.  Building a fresh
    closure per invocation would defeat jax.jit's cache (keyed on the
    function object) and recompile the Mosaic kernel on EVERY eager call."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype_str)
    nq, nk = t // block_q, tk // block_k
    scale = 1.0 / math.sqrt(d)
    # Mosaic's default contraction feeds the MXU one bf16 pass whatever
    # the operand dtype: a silent downcast of q/k/v/p for float32 callers,
    # who get float32 operands at HIGHEST; narrower callers' tiles go to the
    # MXU as they are (no widened copy in VMEM), the scores' scale applied
    # to the float32 product.
    wide = dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if wide else None

    def kv_tile(b_, qi, j):
        # Row b_ of q reads row b_ // group of k and v (grouped queries).
        # Causal: a tile wholly above the diagonal is not computed, and
        # asking again for the last visible one keeps it from being copied.
        if causal:
            j = jnp.minimum(j, (qi * block_q + block_q - 1) // block_k)
        return (b_ // group, j, 0)

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
        # Grid (bh, nq, nk): the innermost k dimension iterates
        # sequentially on TPU, so the VMEM scratch accumulators carry the
        # online softmax across K/V tiles — only ONE (block_k, d) K and V
        # tile is resident at a time, so VMEM use is O(block) not O(T).
        qi = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_scr[:] = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
            l_scr[:] = jnp.zeros((block_q, 1), jnp.float32)
            acc_scr[:] = jnp.zeros((block_q, d), jnp.float32)

        # Causal: tiles strictly above the diagonal contribute nothing.
        visible = True if not causal else (j * block_k <= qi * block_q + block_q - 1)

        @pl.when(visible)
        def _update():
            q_blk, k_blk, v_blk = q_ref[0], k_ref[0], v_ref[0]  # [bq, d], [bk, d] x 2
            if wide:
                q_blk = q_blk * scale
            s = jax.lax.dot_general(q_blk, k_blk, (((1,), (1,)), ((), ())),
                                    precision=precision,
                                    preferred_element_type=jnp.float32)
            if not wide:
                s = s * scale
            if causal:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(k_pos <= q_pos, s, -jnp.inf)
            m = m_scr[:, 0]
            l = l_scr[:, 0]
            m_blk = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, m_blk)
            # Fully-masked rows keep m_new = -inf: guard the exps so they
            # contribute 0 instead of NaN.
            safe_m = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - safe_m[:, None])
            p = jnp.where(jnp.isinf(m_new)[:, None] | jnp.isinf(s), 0.0, p)
            alpha = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - safe_m))
            m_scr[:] = m_new[:, None]
            l_scr[:] = (l * alpha + jnp.sum(p, axis=-1))[:, None]
            acc_scr[:] = acc_scr[:] * alpha[:, None] + jnp.dot(
                p.astype(v_blk.dtype), v_blk, precision=precision,
                preferred_element_type=jnp.float32)

        @pl.when(j == nk - 1)
        def _finalize():
            l = l_scr[:, 0]
            m = m_scr[:, 0]
            denom = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)
            # log-sum-exp residual; fully-masked rows (l=0, m=-inf) -> -inf.
            lse_ref[0] = jnp.where(l == 0.0, -jnp.inf, m + jnp.log(denom))[:, None]

    fn = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, qi, j: (b_, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_tile, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_tile, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, qi, j: (b_, qi, 0),
                         memory_space=pltpu.VMEM),
            # Trailing unit dim keeps the block's last-two dims TPU-tileable
            # ((block_q, 1) instead of (1, block_q)).
            pl.BlockSpec((1, block_q, 1), lambda b_, qi, j: (b_, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        # bh and q-blocks are independent programs (scratch re-inits at
        # j==0 per (bh, qi)): declaring them parallel lets Mosaic
        # megacore-partition the grid on v4/v5p; only the K sweep is
        # order-dependent (online-softmax carry).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_attention",
    )
    return jax.jit(fn)
