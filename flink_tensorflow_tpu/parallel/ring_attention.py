"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

Long-context support is first-class in this framework even though the
reference has none (SURVEY.md §5 "Long-context": its longest-sequence path
is BiLSTM bucketing).  Design (Liu et al. 2023, blockwise ring attention;
see PAPERS.md — pattern reference only):

Tokens are sharded ``[B, T/n, H, D]`` across n ``seq`` devices.  Each
device computes flash-style online-softmax attention of its local Q block
against K/V blocks that rotate around the ring via ``lax.ppermute`` — after
n-1 hops every Q has attended to every K/V without any device ever holding
the full sequence or the full ``T x T`` score matrix.  Communication is
neighbor-to-neighbor only, so it rides the ICI torus at full bandwidth and
overlaps with the per-block attention compute.

Accumulation is float32 (max ``m``, denominator ``l``, numerator ``o``)
regardless of input dtype; inputs may be bfloat16.
"""

from __future__ import annotations

import functools
import math

from flink_tensorflow_tpu.parallel.mesh import SEQ_AXIS


def _block_attention(q, k, v, m, l, o, mask):
    """One flash step: fold K/V block into the online-softmax accumulators.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; m,l: [B, H, Tq]; o: [B, Tq, H, D];
    mask: [Tq, Tk] bool (True = attend) or None.
    """
    import jax.numpy as jnp

    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, :, :], s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # exp(-inf - -inf) guard: fully-masked rows keep p = 0.
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(jnp.isnan(p), 0.0, p)
    alpha = jnp.exp(m - m_new)
    alpha = jnp.where(jnp.isnan(alpha), 0.0, alpha)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, o_new


def _combine_blocks(o_acc, lse_acc, o_blk, lse_blk):
    """Fold one block's normalized output+lse into the running pair.

    Standard flash/ring recombination: with per-block softmax-normalized
    outputs ``o_i`` and residuals ``lse_i``, the global softmax output is
    ``sum_i o_i * exp(lse_i - lse_total)``.  o: [B, T, H, D] f32;
    lse: [B, H, T] f32 (-inf = block contributed nothing to that row).
    """
    import jax.numpy as jnp

    lse_new = jnp.logaddexp(lse_acc, lse_blk)
    safe = jnp.where(jnp.isinf(lse_new), 0.0, lse_new)
    c_acc = jnp.where(jnp.isinf(lse_acc), 0.0, jnp.exp(lse_acc - safe))
    c_blk = jnp.where(jnp.isinf(lse_blk), 0.0, jnp.exp(lse_blk - safe))
    o_new = (o_acc * c_acc.transpose(0, 2, 1)[..., None]
             + o_blk * c_blk.transpose(0, 2, 1)[..., None])
    return o_new, lse_new


def ring_attention_sharded(q, k, v, *, axis_name: str = SEQ_AXIS,
                           causal: bool = False, impl: str = "flash"):
    """Ring attention body — call INSIDE ``shard_map`` over ``axis_name``.

    q/k/v: the local shard ``[B, T_local, H, D]``.  Returns the local
    attention output shard ``[B, T_local, H, D]`` in q's dtype.

    ``impl="flash"`` (default) computes each local block with the pallas
    flash kernel (ops/flash_attention.py) and folds blocks together via
    their log-sum-exp residuals; ``impl="einsum"`` keeps the composed-jnp
    online-softmax path (golden baseline / debugging).
    """
    if impl == "flash":
        return _ring_flash(q, k, v, axis_name=axis_name, causal=causal)
    if impl != "einsum":
        raise ValueError(f"impl must be 'flash' or 'einsum', got {impl!r}")
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    qf = q.astype(jnp.float32)

    # Derive accumulators from q so they inherit q's varying mesh axes
    # (shard_map vma rules: fori_loop carry types must match exactly).
    zeros_bht = jnp.sum(qf, axis=-1).transpose(0, 2, 1) * 0.0  # [B,H,T]
    m0 = zeros_bht - jnp.inf
    l0 = zeros_bht
    o0 = qf * 0.0
    # Ring: receive from the previous rank, send to the next — K/V block i
    # on this device originated at rank (my - i) mod n.
    perm = [(j, (j + 1) % n) for j in range(n)]

    def mask_for(step):
        if not causal:
            return None
        src = (my - step) % n
        q_pos = my * t + jnp.arange(t)[:, None]
        k_pos = src * t + jnp.arange(t)[None, :]
        return k_pos <= q_pos

    def body(i, carry):
        # Rotate at the TOP so the last block's attention isn't followed by
        # a dead K/V exchange (n-1 ppermutes total, not n).
        k_blk, v_blk, m, l, o = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        m, l, o = _block_attention(qf, k_blk, v_blk, m, l, o, mask_for(i))
        return k_blk, v_blk, m, l, o

    # Peel step 0 (local K/V, no exchange), ring through the remaining n-1.
    m0, l0, o0 = _block_attention(qf, k, v, m0, l0, o0, mask_for(0))
    _, _, m, l, o = lax.fori_loop(1, n, body, (k, v, m0, l0, o0))
    # Fully-masked rows (can happen only with exotic masks) -> 0, not NaN.
    denom = jnp.where(l == 0.0, 1.0, l)
    out = o / denom.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_flash(q, k, v, *, axis_name: str, causal: bool):
    """Flash-kernel ring body: each K/V block runs through the pallas
    kernel (MXU matmuls, O(block) VMEM), blocks merge via lse residuals.

    Causal masking never reaches the kernel as a dynamic mask: a block is
    either fully visible (source rank before mine — plain kernel), the
    diagonal (source == mine — the kernel's own causal grid), or fully
    masked (source after mine — skipped, lse=-inf), selected with
    ``lax.switch`` on the traced source rank.
    """
    import jax.numpy as jnp
    from jax import lax

    from flink_tensorflow_tpu.ops.flash_attention import flash_attention

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    def block(k_blk, v_blk, step):
        if not causal:
            o, lse = flash_attention(q, k_blk, v_blk, return_lse=True)
            return o.astype(jnp.float32), lse
        src = (my - step) % n

        def diag(args):
            q_, k_, v_ = args
            o, lse = flash_attention(q_, k_, v_, causal=True, return_lse=True)
            return o.astype(jnp.float32), lse

        def full(args):
            q_, k_, v_ = args
            o, lse = flash_attention(q_, k_, v_, return_lse=True)
            return o.astype(jnp.float32), lse

        def skip(args):
            # Derive from q so outputs inherit q's varying mesh axes.
            q_, _, _ = args
            o = q_.astype(jnp.float32) * 0.0
            lse = jnp.sum(o, axis=-1).transpose(0, 2, 1) - jnp.inf
            return o, lse

        idx = jnp.where(src == my, 0, jnp.where(src < my, 1, 2))
        return lax.switch(idx, [diag, full, skip], (q, k_blk, v_blk))

    # Accumulators derived from q (shard_map vma rules, as in the einsum path).
    o0 = q.astype(jnp.float32) * 0.0
    lse0 = jnp.sum(o0, axis=-1).transpose(0, 2, 1) - jnp.inf

    def body(i, carry):
        k_blk, v_blk, o_acc, lse_acc = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        o_blk, lse_blk = block(k_blk, v_blk, i)
        o_acc, lse_acc = _combine_blocks(o_acc, lse_acc, o_blk, lse_blk)
        return k_blk, v_blk, o_acc, lse_acc

    o_blk, lse_blk = block(k, v, 0)
    o_acc, lse_acc = _combine_blocks(o0, lse0, o_blk, lse_blk)
    _, _, o, _ = lax.fori_loop(1, n, body, (k, v, o_acc, lse_acc))
    return o.astype(q.dtype)


def ring_attention(mesh, q, k, v, *, causal: bool = False, impl: str = "flash"):
    """User-facing ring attention over a mesh with a ``seq`` axis.

    q/k/v: global ``[B, T, H, D]`` arrays (host or device); T must divide
    by the seq-axis size.  Output: global ``[B, T, H, D]``.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_tensorflow_tpu.parallel.mesh import DATA_AXIS

    # Batch rides the data axis when the mesh has one (dp x sp composes).
    batch_axis = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
    spec = P(batch_axis, SEQ_AXIS, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention_sharded, causal=causal, impl=impl),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # The INTERPRETED kernel's dynamic_slice trips the vma check
        # inside the ring's lax.switch (the Mosaic-compiled one passes
        # it on a v5e), so the flash body runs with the check off on
        # every backend; einsum keeps it on.
        check_vma=impl != "flash",
    )
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return jax.jit(fn)(q, k, v)


def ring_decode_attention(mesh, q, k, v, lengths, *, axis_name: str = SEQ_AXIS):
    """Decode-step attention with the KV cache sharded over ``seq``.

    The serving counterpart of :func:`ring_attention`: at decode time
    there is ONE query per row, so instead of rotating K/V blocks n-1
    times, every device computes :func:`flash_attention_decode` over its
    LOCAL cache shard and the per-shard ``(o, lse)`` pairs fold with the
    same ``_combine_blocks`` recombination the ring uses — one
    ``all_gather`` of a ``[B, 1, H, D]`` output (tiny next to the cache)
    replaces the whole K/V ring.

    ``q``: global ``[B, 1, H, D]``; ``k``/``v``: global ``[B, C, H, D]``
    cache at capacity ``C`` (``C`` divisible by the seq-axis size);
    ``lengths``: global ``[B]`` valid cache lengths.  Output: global
    ``[B, 1, H, D]`` replicated over the axis.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_tensorflow_tpu.ops.flash_attention import flash_attention_decode

    n = dict(mesh.shape)[axis_name]
    c = k.shape[1]
    if c % n:
        raise ValueError(f"cache capacity {c} must divide the {axis_name} "
                         f"axis size {n}")
    c_local = c // n

    def body(q_, k_, v_, lengths_):
        i = lax.axis_index(axis_name)
        local_valid = jnp.clip(lengths_ - i * c_local, 0, c_local)
        o, lse = flash_attention_decode(q_, k_, v_, local_valid,
                                        return_lse=True)
        # Fold every shard's (o, lse): gather the tiny outputs, combine
        # sequentially (n is a static python int — unrolled, no carry).
        os = lax.all_gather(o.astype(jnp.float32), axis_name)   # [n,B,1,H,D]
        lses = lax.all_gather(lse, axis_name)                   # [n,B,H,1]
        o_acc, lse_acc = os[0], lses[0]
        for j in range(1, n):
            o_acc, lse_acc = _combine_blocks(o_acc, lse_acc, os[j], lses[j])
        return o_acc.astype(q_.dtype)

    kv_spec = P(None, axis_name, None, None)
    rep = P(None, None, None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, kv_spec, kv_spec, P(None)),
        out_specs=rep,
        # The fold of all-gathered (o, lse) pairs IS replicated, but the
        # replication checker can't infer that through the combine math.
        check_vma=False,
    )
    q = jax.device_put(q, NamedSharding(mesh, rep))
    k = jax.device_put(k, NamedSharding(mesh, kv_spec))
    v = jax.device_put(v, NamedSharding(mesh, kv_spec))
    lengths = jax.device_put(lengths, NamedSharding(mesh, P(None)))
    return jax.jit(fn)(q, k, v, lengths)


def full_attention(q, k, v, *, causal: bool = False):
    """Unsharded reference implementation (tests/golden baseline)."""
    import jax.numpy as jnp

    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t_k)[None, :] <= jnp.arange(t_q)[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    out = out / jnp.sum(p, axis=-1).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)
