"""Latent attention (MLA), the prefill side: queries and keys/values come out
of low-rank projections with a norm in the middle, a key's rotary part is ONE
head that every query head reads, and a head is wider on ``q`` and ``k``
(``qk_nope_head_dim + qk_rope_head_dim``) than on ``v`` (``v_head_dim``).  The
names are the keys of a DeepSeek-V3-style ``config.json`` (``kimi_k2`` has the
same layer).

``u`` ``[B, T, d]``, the layer's normed input; ``norm`` an RMS norm with a
weight:

- ``latent_q``: ``c_q = norm(u W_qa)`` (``q_lora_rank``); ``c_q W_qb``: ``heads``
  heads of ``q_nope | q_pe``.  ``W_qb`` is split by columns before the product,
  so that ``q_nope`` ``[B, T, heads x nope]`` and ``q_pe`` ``[B, T, heads x
  rope]`` come out of two products and neither is sliced out of a third.
- ``latent_kv``: ``u W_kva`` = ``c | k_pe``; ``c_kv = norm(c)``
  (``kv_lora_rank``); ``c_kv W_kvb``: ``heads`` heads of ``k_nope | v``, ``W_kvb``
  split likewise.
- ``rope``: on ``q_pe`` and ``k_pe``, adjacent pairs ``(x[2j], x[2j+1])``,
  yarn frequencies (:func:`yarn_inv_freq`).  (The published code first
  regroups the pairs into halves on ``q`` and ``k`` alike; every dot product is
  the same.)  ``k_pe``, ONE head, is turned here (:func:`rope_pairs`); ``q_pe``
  goes to the kernel as its product wrote it, float32 and not yet turned, with
  the positions' ``cos`` and ``sin``, and the kernel turns a block of it in
  VMEM by the same float32 arithmetic: no pass over ``q_pe`` is made in HBM.
- ``attention``: scores ``(q_nope . k_nope + q_pe . k_pe) * scale``,
  :func:`softmax_scale`; causal softmax; ``P v``; ``W_o``.  The flash kernel
  (ops/flash_attention.py, ``q_rope=``, ``k_rope=``, ``rotate=``) reads every
  operand where and as its projection wrote it: ``q_nope``, ``k_nope`` and
  ``v`` out of ``[B, T, heads x 128]``, a program's block head ``h``'s lanes of
  a row block at block index ``(b, row block, h)``; ``q_pe`` two heads a block
  of 128 lanes, the other head's lanes zeroed in VMEM; ``k_pe`` ``[B, T,
  rope]``, whose block index names no head, so that all ``heads`` query heads
  read the one tile; the output written ``[B, T, heads x v]``, which is what
  ``W_o`` consumes.  ``q`` and ``k`` are never concatenated, ``k_pe`` is never
  written beside a head's ``k_nope``, and no transposed copy of ``q``, ``k``,
  ``v`` or the output exists in HBM (``tests/test_mla_layout.py`` searches the
  traced layer for one).

Precision: products take ``compute_dtype`` operands and accumulate in float32;
the two latent norms, RoPE and the softmax statistics are float32.  The scale
is no power of two, so the kernel puts it onto the float32 scores, where it
rounds nothing.
"""

from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

from flink_tensorflow_tpu.ops.flash_attention import flash_attention

F32 = jnp.float32


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, rope_scaling: typing.Mapping) -> np.ndarray:
    """``float64[dim // 2]``: yarn's frequencies.  Pair ``j`` turns by ``f_j =
    base^(-2j/dim)`` a position where it turns more than ``beta_fast`` times
    over the original length, by ``f_j / factor`` where fewer than
    ``beta_slow`` times, and by a linear blend between (the ramp runs over
    whole pair indices: ``floor`` and ``ceil`` of the two boundaries)."""
    factor, original = rope_scaling["factor"], rope_scaling["original_max_position_embeddings"]
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def boundary(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(boundary(rope_scaling.get("beta_fast") or 32)), 0)
    high = min(math.ceil(boundary(rope_scaling.get("beta_slow") or 1)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high - low) or 0.001), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def softmax_scale(qk_head_dim: int, rope_scaling: typing.Optional[typing.Mapping]) -> float:
    """``qk_head_dim^-0.5``, times yarn's ``mscale(factor, mscale_all_dim)``
    squared where the config scales its positions."""
    scale = qk_head_dim ** -0.5
    if rope_scaling and rope_scaling.get("mscale_all_dim"):
        scale *= yarn_mscale(rope_scaling["factor"], rope_scaling["mscale_all_dim"]) ** 2
    return scale


def rope_pairs(x, cos, sin):
    """Rotate adjacent pairs of the last axis: ``x`` ``[B, T, ..., 2n]`` float32,
    ``cos`` and ``sin`` ``[T, n]``.  Pair ``j`` of position ``t`` turns by the
    angle whose cosine is ``cos[t, j]``."""
    lead = (1, x.shape[1]) + (1,) * (x.ndim - 3)
    cos = jnp.repeat(cos, 2, axis=-1).reshape(*lead, -1)
    sin = jnp.repeat(sin, 2, axis=-1).reshape(*lead, -1)
    # (-x[2j+1], x[2j]) without a reshape to pairs, which would put 2 on the lanes.
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
    return x * cos + partner * sin


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w.astype(F32)


def latent_attention(p, u, *, num_heads: int, qk_nope_head_dim: int, qk_rope_head_dim: int,
                     v_head_dim: int, rope_theta: float, rope_scaling: typing.Mapping,
                     eps: float, compute_dtype=jnp.bfloat16):
    """``u`` ``[B, T, d]`` float32 -> ``[B, T, d]`` float32.  ``p``: ``q_a`` ``[d,
    q_lora_rank]``, ``q_a_norm``, ``q_b`` ``[q_lora_rank, heads x (nope + rope)]``,
    ``kv_a`` ``[d, kv_lora_rank + rope]``, ``kv_a_norm``, ``kv_b`` ``[kv_lora_rank,
    heads x (nope + v)]``, ``o`` ``[heads x v, d]``."""
    b, t, _ = u.shape
    nope, rope, dv = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    cdt = jnp.dtype(compute_dtype)
    exact = jax.lax.Precision.HIGHEST if cdt == F32 else None

    def dot(x, w):
        return jnp.dot(x.astype(cdt), w.astype(cdt), precision=exact, preferred_element_type=F32)

    with jax.named_scope("latent_q"):
        c_q = rms_norm(dot(u, p["q_a"]), p["q_a_norm"], eps)
        w_qb = p["q_b"].reshape(c_q.shape[-1], num_heads, nope + rope)
        q_nope = dot(c_q, w_qb[..., :nope].reshape(-1, num_heads * nope)).astype(cdt)
        q_pe = dot(c_q, w_qb[..., nope:].reshape(-1, num_heads * rope)).reshape(b, t, num_heads, rope)
    with jax.named_scope("latent_kv"):
        kv = dot(u, p["kv_a"])
        rank = kv.shape[-1] - rope
        c_kv, k_pe = rms_norm(kv[..., :rank], p["kv_a_norm"], eps), kv[..., rank:]
        w_kvb = p["kv_b"].reshape(rank, num_heads, nope + dv)
        k_nope = dot(c_kv, w_kvb[..., :nope].reshape(rank, num_heads * nope)).astype(cdt)
        v = dot(c_kv, w_kvb[..., nope:].reshape(rank, num_heads * dv)).astype(cdt)
    with jax.named_scope("rope"):
        if rope_scaling.get("type", rope_scaling.get("rope_type")) != "yarn":
            raise ValueError(f"rope_scaling of type yarn, not {rope_scaling!r}")
        angle = (np.arange(t, dtype=np.float64)[:, None]
                 * yarn_inv_freq(rope, float(rope_theta), rope_scaling)[None, :])
        # yarn also scales cos and sin: by 1 where mscale equals mscale_all_dim.
        factor, m, m_all = (rope_scaling.get(key) for key in ("factor", "mscale", "mscale_all_dim"))
        stretch = yarn_mscale(factor, m) / yarn_mscale(factor, m_all) if m and m_all else yarn_mscale(factor, 1.0)
        cos, sin = (jnp.asarray(fn(angle) * stretch, F32) for fn in (np.cos, np.sin))
        k_pe = rope_pairs(k_pe, cos, sin).astype(cdt)
    with jax.named_scope("attention"):
        # [B, T, H x D] viewed [B, T, H, D] and back inside the kernel's entry: nothing moves.
        out = flash_attention(q_nope.reshape(b, t, num_heads, nope), k_nope.reshape(b, t, num_heads, nope),
                              v.reshape(b, t, num_heads, dv), q_rope=q_pe, k_rope=k_pe, rotate=(cos, sin), causal=True,
                              scale=softmax_scale(nope + rope, rope_scaling))
        return dot(out.reshape(b, t, num_heads * dv), p["o"])
