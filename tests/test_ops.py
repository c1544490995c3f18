"""Pallas kernel tests (interpreter mode on CPU — same code path that
compiles on TPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from flink_tensorflow_tpu.ops import flash_attention, flash_attention_decode
from flink_tensorflow_tpu.parallel import full_attention


def _plain_scores(q, k, causal):
    """float64 scaled scores ``[B, H, T, Tk]`` of ``[B, T, H, D]`` q against k with
    as many heads, masked to ``-inf`` above the diagonal when causal."""
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) / np.sqrt(q.shape[-1])
    if causal:
        s = np.where(np.arange(s.shape[-1])[None, :] <= np.arange(s.shape[-2])[:, None],
                     s, -np.inf)
    return s


#: (b, t, tk, heads, kv heads, head size, dtype, causal, block_q, block_k, atol).
#: ``None`` for a block leaves the edge to the kernel's chooser.
_PARITY = {
    # the two cases this test had before it was a table
    "square16-full": (2, 64, 64, 2, 2, 16, "float32", False, 16, 16, 1e-5),
    "square16-causal": (2, 64, 64, 2, 2, 16, "float32", True, 16, 16, 1e-5),
    # a row of tiles with an unmasked tile, a diagonal one and a skipped one
    "tall-causal": (1, 96, 96, 2, 2, 16, "float32", True, 32, 16, 1e-5),
    "wide-causal": (1, 96, 96, 2, 2, 16, "float32", True, 16, 48, 1e-5),
    "wide-full": (1, 96, 96, 2, 2, 16, "float32", False, 16, 48, 1e-5),
    # keys and queries of different lengths
    "short-keys-causal": (1, 64, 32, 2, 2, 16, "float32", True, 16, 16, 1e-5),
    "long-keys-causal": (1, 32, 64, 2, 2, 16, "float32", True, 16, 16, 1e-5),
    "long-keys-full": (2, 32, 80, 2, 2, 16, "float32", False, 16, 16, 1e-5),
    # grouped queries, through the block index
    "4on1-head64-causal": (1, 64, 64, 4, 1, 64, "float32", True, 32, 32, 1e-5),
    "5on1-head128-causal": (2, 64, 64, 5, 1, 128, "float32", True, 32, 16, 1e-5),
    "8on2-head64-full": (1, 48, 48, 8, 2, 64, "float32", False, 16, 16, 1e-5),
    # bfloat16 tiles to the MXU, the scale folded into q at a head of 64 and not at 128
    "bf16-4on1-head64-causal": (1, 64, 64, 4, 1, 64, "bfloat16", True, 32, 32, 3e-2),
    "bf16-5on1-head128-causal": (1, 64, 64, 5, 1, 128, "bfloat16", True, 32, 32, 3e-2),
    "bf16-head16-causal": (1, 64, 64, 2, 2, 16, "bfloat16", True, 16, 32, 3e-2),
    # the chooser's own tile: one block; three chunks of 128 in one copied tile;
    # chunks of 512 below, on and above the diagonal in a copied tile of 1,024
    "chosen-one-block-causal": (2, 128, 128, 2, 2, 16, "float32", True, None, None, 1e-5),
    "chosen-chunks-of-128-causal": (1, 384, 384, 2, 1, 16, "float32", True, None, None, 1e-5),
    "chosen-chunks-of-128-full": (1, 128, 384, 2, 1, 16, "float32", False, None, None, 1e-5),
    "chosen-chunks-of-512-causal": (1, 1024, 1024, 1, 1, 16, "float32", True, None, None, 1e-5),
    "chosen-rows-given-causal": (1, 256, 256, 2, 2, 16, "float32", True, 64, None, 1e-5),
    "chosen-bf16-head64-causal": (1, 256, 256, 4, 1, 64, "bfloat16", True, None, None, 3e-2),
}


class TestFlashAttention:
    @pytest.mark.parametrize("return_lse", [False, True], ids=["out", "out+lse"])
    @pytest.mark.parametrize("case", list(_PARITY), ids=list(_PARITY))
    def test_matches_full_attention(self, case, return_lse):
        """The kernel against plain attention, and its ``lse`` against the
        log-sum-exp of the plain scores."""
        b, t, tk, h, hkv, d, dtype, causal, block_q, block_k, atol = _PARITY[case]
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, t, h, d), dtype)
        k, v = (jnp.asarray(rng.randn(b, tk, hkv, d), dtype) for _ in range(2))
        k_all, v_all = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
        want = full_attention(q, k_all, v_all, causal=causal)
        got = flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                              return_lse=return_lse)
        if return_lse:
            got, lse = got
            assert lse.shape == (b, h, t) and lse.dtype == jnp.float32
            s = _plain_scores(q, k_all, causal)
            top = s.max(-1, keepdims=True)
            want_lse = (top + np.log(np.exp(s - top).sum(-1, keepdims=True)))[..., 0]
            np.testing.assert_allclose(np.asarray(lse), want_lse,
                                       atol=1e-4 if dtype == "float32" else 2e-2)
        assert got.shape == q.shape and got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=atol)

    def test_tile_plan(self):
        """The tile the kernel reads off a shape, and what a row of it costs."""
        from flink_tensorflow_tpu.ops.flash_attention import (
            _VMEM_MOST,
            tile_plan,
        )

        for d in (128, 64):  # the two language-model cells: 4,096 causal positions
            plan = tile_plan(4096, 4096, d, jnp.bfloat16, True)
            assert plan[:5] == (512, 4096, 512, 36, 8), plan
            assert plan.vmem_bytes <= _VMEM_MOST
        # edges given: they are the copied tile's, and it is one chunk
        assert tile_plan(4096, 4096, 128, jnp.bfloat16, True, 1024, 1024)[:5] == (1024, 1024, 1024, 10, 4)
        assert tile_plan(4096, 4096, 128, jnp.bfloat16, False, 512, 256)[:5] == (512, 256, 256, 128, 0)
        # K and V past 2 MiB each are copied in tiles
        assert tile_plan(16384, 16384, 128, jnp.bfloat16, True)[:3] == (512, 8192, 512)
        # shorter keys than queries: the rows past them see every key, unmasked
        assert tile_plan(64, 32, 16, jnp.float32, True, 16, 16)[3:5] == (7, 2)
        for t in [8, 12, 64, 100, 128, 136, 200, 264, 1000, 1001, 4096, 12288]:
            for dtype in (jnp.float32, jnp.bfloat16):
                plan = tile_plan(t, t, 64, dtype, True)
                for edge in (plan.block_q, plan.block_k):  # Mosaic-legal
                    assert t % edge == 0 and (edge % 8 == 0 or edge == t), (t, plan)
                # a chunk is the copied tile or whole lane tiles of it
                assert plan.block_k % plan.chunk == 0, (t, plan)
                assert plan.chunk == plan.block_k or plan.chunk % 128 == 0, (t, plan)
                assert plan.tiles_masked <= plan.tiles_visited
                assert plan.vmem_bytes <= _VMEM_MOST, (t, plan)

    def test_odd_block_sizes_shrink(self):
        rng = np.random.RandomState(1)
        b, t, h, d = 1, 24, 1, 8  # 24 not divisible by 128 -> gcd blocks
        q, k, v = (rng.randn(b, t, h, d).astype(np.float32) for _ in range(3))
        want = full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_bfloat16_inputs(self):
        rng = np.random.RandomState(2)
        b, t, h, d = 1, 32, 2, 16
        q, k, v = (jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16) for _ in range(3))
        want = full_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=3e-2)

    def test_lse_residual_recombines_split_kv(self):
        """The returned log-sum-exp must be exactly the residual needed to
        fold two half-K/V flash calls into full attention — the contract
        the seq-axis ring relies on."""
        from flink_tensorflow_tpu.parallel.ring_attention import _combine_blocks

        rng = np.random.RandomState(3)
        b, t, h, d = 2, 32, 2, 8
        q, k, v = (rng.randn(b, t, h, d).astype(np.float32) for _ in range(3))
        want = full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

        o1, lse1 = flash_attention(jnp.asarray(q), jnp.asarray(k[:, :16]),
                                   jnp.asarray(v[:, :16]), return_lse=True)
        o2, lse2 = flash_attention(jnp.asarray(q), jnp.asarray(k[:, 16:]),
                                   jnp.asarray(v[:, 16:]), return_lse=True)
        assert lse1.shape == (b, h, t)
        got, _ = _combine_blocks(o1, lse1, o2, lse2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_tpu_matches_interpret(self):
        """Compiled-TPU vs interpret-mode equivalence (VERDICT r1 #7).
        Skips unless a real TPU is attached (the conftest pins tests to
        the virtual CPU mesh; the driver's bench path exercises this)."""
        import jax

        if jax.default_backend() != "tpu":
            pytest.skip("needs a real TPU; interpret-only backend here")
        rng = np.random.RandomState(5)
        b, t, h, d = 2, 256, 4, 64
        q, k, v = (jnp.asarray(rng.randn(b, t, h, d), jnp.bfloat16) for _ in range(3))
        for causal in (False, True):
            o_t, lse_t = flash_attention(q, k, v, causal=causal,
                                         interpret=False, return_lse=True)
            o_i, lse_i = flash_attention(q, k, v, causal=causal,
                                         interpret=True, return_lse=True)
            np.testing.assert_allclose(np.asarray(o_t, np.float32),
                                       np.asarray(o_i, np.float32), atol=3e-3)
            np.testing.assert_allclose(np.asarray(lse_t), np.asarray(lse_i), atol=1e-4)

    def test_lse_fully_masked_rows_are_neg_inf(self):
        """Causal first row attends only to itself; a fully-masked block
        (k entirely after q in a later ring step) must yield lse=-inf —
        exercised here via the ring's skip branch shape contract."""
        rng = np.random.RandomState(4)
        b, t, h, d = 1, 16, 1, 8
        q, k, v = (rng.randn(b, t, h, d).astype(np.float32) for _ in range(3))
        _, lse = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                 causal=True, return_lse=True)
        assert np.all(np.isfinite(np.asarray(lse)))


class TestFlashAttentionDecode:
    """Single-query decode step (the serving plane's per-token path):
    must equal the full-prefix kernel at the last valid position."""

    def test_single_step_equals_full_prefix(self):
        rng = np.random.RandomState(0)
        b, c, h, d = 3, 32, 2, 16
        lengths = np.array([32, 20, 7], np.int32)
        k = rng.randn(b, c, h, d).astype(np.float32)
        v = rng.randn(b, c, h, d).astype(np.float32)
        q1 = rng.randn(b, 1, h, d).astype(np.float32)
        got = flash_attention_decode(jnp.asarray(q1), jnp.asarray(k),
                                     jnp.asarray(v), jnp.asarray(lengths))
        # Reference: per row, full (non-causal) attention of the single
        # query over exactly the valid prefix.
        for i in range(b):
            n = lengths[i]
            want = full_attention(jnp.asarray(q1[i:i + 1]),
                                  jnp.asarray(k[i:i + 1, :n]),
                                  jnp.asarray(v[i:i + 1, :n]))
            np.testing.assert_allclose(np.asarray(got[i]),
                                       np.asarray(want[0]), atol=1e-5)

    def test_matches_causal_prefill_last_position(self):
        """Decode over a cache built by causal prefill == the causal
        kernel's output at the final position — the incremental/full
        consistency the KV cache relies on."""
        rng = np.random.RandomState(1)
        b, t, h, d = 2, 24, 2, 8
        q = rng.randn(b, t, h, d).astype(np.float32)
        k = rng.randn(b, t, h, d).astype(np.float32)
        v = rng.randn(b, t, h, d).astype(np.float32)
        full = flash_attention(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=True,
                               block_q=8, block_k=8)
        step = flash_attention_decode(
            jnp.asarray(q[:, -1:]), jnp.asarray(k), jnp.asarray(v),
            jnp.full((b,), t, np.int32))
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(full[:, -1]), atol=1e-5)

    def test_squeezed_3d_query_and_zero_length_rows(self):
        rng = np.random.RandomState(2)
        b, c, h, d = 2, 16, 2, 8
        q = rng.randn(b, h, d).astype(np.float32)
        k = rng.randn(b, c, h, d).astype(np.float32)
        v = rng.randn(b, c, h, d).astype(np.float32)
        lengths = np.array([10, 0], np.int32)  # row 1: inactive pool slot
        out, lse = flash_attention_decode(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(lengths), return_lse=True)
        assert out.shape == (b, h, d)
        assert np.all(np.isfinite(np.asarray(out)))
        assert np.all(np.asarray(out)[1] == 0.0)       # masked row -> zeros
        assert np.all(np.isneginf(np.asarray(lse)[1]))  # lse residual -inf

    def test_lse_recombines_split_cache_ring_style(self):
        """Two half-cache decode calls fold into the full answer via the
        ring's _combine_blocks — the sharded-decode contract."""
        from flink_tensorflow_tpu.parallel.ring_attention import _combine_blocks

        rng = np.random.RandomState(3)
        b, c, h, d = 2, 32, 2, 8
        q = rng.randn(b, 1, h, d).astype(np.float32)
        k = rng.randn(b, c, h, d).astype(np.float32)
        v = rng.randn(b, c, h, d).astype(np.float32)
        lengths = np.array([28, 11], np.int32)
        want = flash_attention_decode(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), jnp.asarray(lengths))
        half = c // 2
        lo = np.clip(lengths, 0, half).astype(np.int32)
        hi = np.clip(lengths - half, 0, half).astype(np.int32)
        o1, l1 = flash_attention_decode(jnp.asarray(q), jnp.asarray(k[:, :half]),
                                        jnp.asarray(v[:, :half]),
                                        jnp.asarray(lo), return_lse=True)
        o2, l2 = flash_attention_decode(jnp.asarray(q), jnp.asarray(k[:, half:]),
                                        jnp.asarray(v[:, half:]),
                                        jnp.asarray(hi), return_lse=True)
        # _combine_blocks wants lse as [B, H, T]; decode returns [B, H, 1].
        got, _ = _combine_blocks(o1.astype(jnp.float32), l1,
                                 o2.astype(jnp.float32), l2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


class TestShardedDecode:
    """Ring/Ulysses decode paths, smoke-tested on the virtual CPU mesh."""

    def _case(self, seed=5, b=2, c=32, h=4, d=8):
        rng = np.random.RandomState(seed)
        q = rng.randn(b, 1, h, d).astype(np.float32)
        k = rng.randn(b, c, h, d).astype(np.float32)
        v = rng.randn(b, c, h, d).astype(np.float32)
        lengths = np.array([c, 13], np.int32)[:b]
        want = flash_attention_decode(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), jnp.asarray(lengths))
        return q, k, v, lengths, want

    def test_ring_decode_matches_unsharded(self):
        from flink_tensorflow_tpu.parallel import make_mesh, ring_decode_attention

        import jax

        q, k, v, lengths, want = self._case()
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        got = ring_decode_attention(mesh, jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), jnp.asarray(lengths))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_ulysses_decode_matches_unsharded(self):
        from flink_tensorflow_tpu.parallel import (
            make_mesh,
            ulysses_decode_attention,
        )

        import jax

        q, k, v, lengths, want = self._case()
        # Shards the 4 heads over a 4-device slice of the virtual mesh.
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        got = ulysses_decode_attention(mesh, jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), jnp.asarray(lengths))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_ulysses_decode_indivisible_heads_rejected(self):
        from flink_tensorflow_tpu.parallel import (
            make_mesh,
            ulysses_decode_attention,
        )

        mesh = make_mesh({"seq": 8})
        q = jnp.zeros((1, 1, 6, 8))
        kv = jnp.zeros((1, 16, 6, 8))
        with pytest.raises(ValueError, match="divisible"):
            ulysses_decode_attention(mesh, q, kv, kv,
                                     jnp.full((1,), 16, jnp.int32))

    def test_ring_decode_indivisible_capacity_rejected(self):
        from flink_tensorflow_tpu.parallel import make_mesh, ring_decode_attention

        mesh = make_mesh({"seq": 8})
        q = jnp.zeros((1, 1, 4, 8))
        kv = jnp.zeros((1, 30, 4, 8))  # 30 % 8 != 0
        with pytest.raises(ValueError, match="divide"):
            ring_decode_attention(mesh, q, kv, kv,
                                  jnp.full((1,), 30, jnp.int32))


class TestTileableBlocks:
    def test_block_selection_is_mosaic_legal(self):
        """Mosaic requires a block's sublane dim divisible by 8 OR equal
        to the whole array dim; the old gcd picked sizes like 4 for
        t=100, which crashed only on the real chip (interpret mode can't
        catch it)."""
        from flink_tensorflow_tpu.ops.flash_attention import _tileable_block

        for t in [8, 12, 64, 100, 128, 136, 200, 264, 1000, 1001, 4096]:
            b = _tileable_block(t, 128)
            assert t % b == 0, (t, b)
            assert b % 8 == 0 or b == t, (t, b)
            assert b <= 128 or b == t, (t, b)

    def test_non_divisible_lengths_match_reference(self):
        """Shapes that used to crash Mosaic (t=100, 264, mixed) run the
        same kernel path in interpret mode and match full attention."""
        import jax.numpy as jnp

        from flink_tensorflow_tpu.ops.flash_attention import flash_attention
        from flink_tensorflow_tpu.parallel import full_attention

        rng = np.random.RandomState(3)
        for t, tk in [(100, 100), (264, 136), (12, 200)]:
            q = rng.randn(1, t, 2, 16).astype(np.float32)
            k = rng.randn(1, tk, 2, 16).astype(np.float32)
            v = rng.randn(1, tk, 2, 16).astype(np.float32)
            got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
            want = full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5)
