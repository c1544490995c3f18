"""Ulysses-style sequence parallelism — all-to-all over the ``seq`` axis.

The second of the two long-context strategies (the other is ring
attention, parallel/ring_attention.py; neither exists in the reference —
SURVEY.md §5 "Long-context").  Pattern after DeepSpeed-Ulysses (see
PAPERS.md — pattern reference only), reshaped for TPU collectives:

Tokens arrive sharded ``[B, T/n, H, D]`` over n ``seq`` devices.  One
``lax.all_to_all`` re-shards from sequence- to HEAD-parallel: each device
then holds the FULL sequence for ``H/n`` heads, computes ordinary (or
pallas-flash) attention locally — no online-softmax recombination, no
per-block masking logic — and a second all-to-all restores sequence
sharding.

Trade-off vs the ring: Ulysses moves each token exactly twice over the
interconnect (4 all-to-alls: q/k/v in, output back) regardless of n,
while the ring moves K/V n-1 times but overlaps transfers under compute
and keeps communication strictly neighbor-to-neighbor on the ICI torus.
Ulysses needs ``H % n == 0``; the ring has no head constraint.  Both
compose with a ``data`` axis for dp x sp meshes.
"""

from __future__ import annotations

import functools

from flink_tensorflow_tpu.parallel.mesh import SEQ_AXIS


def ulysses_attention_sharded(q, k, v, *, axis_name: str = SEQ_AXIS,
                              causal: bool = False, impl: str = "flash"):
    """Ulysses body — call INSIDE ``shard_map`` over ``axis_name``.

    q/k/v: the local shard ``[B, T_local, H, D]`` with ``H`` divisible by
    the axis size.  Returns the local output shard, q's dtype.
    """
    from jax import lax

    n = lax.axis_size(axis_name)
    b, t, h, d = q.shape
    if h % n:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by the seq-axis size ({n}); "
            "use ring attention for head counts that don't split"
        )

    def seq_to_heads(x):
        # [B, T/n, H, D] -> [B, T, H/n, D]: split the head axis n ways,
        # exchange, concatenate the sequence chunks.
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    q_h, k_h, v_h = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if impl == "flash":
        from flink_tensorflow_tpu.ops.flash_attention import flash_attention

        out_h = flash_attention(q_h, k_h, v_h, causal=causal)
    elif impl == "einsum":
        from flink_tensorflow_tpu.parallel.ring_attention import full_attention

        out_h = full_attention(q_h, k_h, v_h, causal=causal)
    else:
        raise ValueError(f"impl must be 'flash' or 'einsum', got {impl!r}")
    return heads_to_seq(out_h.astype(q.dtype))


def ulysses_decode_attention(mesh, q, k, v, lengths, *,
                             axis_name: str = SEQ_AXIS):
    """Decode-step attention with the KV cache sharded over HEADS.

    The Ulysses inference layout: at decode time the query is one
    position, so re-sharding sequence<->heads with all-to-alls
    degenerates (there is no sequence to split).  Instead the cache is
    stored head-sharded ``[B, C, H/n, D]`` across the ``seq`` axis and
    every device computes :func:`flash_attention_decode` over its own
    heads — embarrassingly parallel, zero collectives per step.  Same
    ``H % n == 0`` constraint as prefill Ulysses.

    ``q``: global ``[B, 1, H, D]``; ``k``/``v``: global ``[B, C, H, D]``;
    ``lengths``: global ``[B]``.  Output: global ``[B, 1, H, D]``
    head-sharded (one ``device_get`` materializes it).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_tensorflow_tpu.ops.flash_attention import flash_attention_decode

    n = dict(mesh.shape)[axis_name]
    h = q.shape[2]
    if h % n:
        raise ValueError(
            f"ulysses decode needs heads ({h}) divisible by the "
            f"{axis_name}-axis size ({n}); use ring_decode_attention for "
            "head counts that don't split"
        )

    def body(q_, k_, v_, lengths_):
        return flash_attention_decode(q_, k_, v_, lengths_)

    head_spec = P(None, None, axis_name, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(head_spec, head_spec, head_spec, P(None)),
        out_specs=head_spec,
    )
    q = jax.device_put(q, NamedSharding(mesh, head_spec))
    k = jax.device_put(k, NamedSharding(mesh, head_spec))
    v = jax.device_put(v, NamedSharding(mesh, head_spec))
    lengths = jax.device_put(lengths, NamedSharding(mesh, P(None)))
    return jax.jit(fn)(q, k, v, lengths)


def ulysses_attention(mesh, q, k, v, *, causal: bool = False, impl: str = "flash"):
    """User-facing Ulysses attention over a mesh with a ``seq`` axis.

    q/k/v: global ``[B, T, H, D]`` arrays; T must divide by the seq-axis
    size and H must divide by it too.  Output: global ``[B, T, H, D]``.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_tensorflow_tpu.parallel.mesh import DATA_AXIS

    # Batch rides the data axis when the mesh has one (dp x sp composes).
    batch_axis = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
    spec = P(batch_axis, SEQ_AXIS, None, None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention_sharded, causal=causal, impl=impl),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # Same caveat as the ring's flash body: the interpreted kernel
        # trips the vma check, the Mosaic-compiled one passes it.
        check_vma=impl != "flash",
    )
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return jax.jit(fn)(q, k, v)
