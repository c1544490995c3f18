"""Latent attention's operands go to the flash kernel where and as their
projections wrote them (``ops/flash_attention.py``: ``q_rope=``, ``k_rope=``,
``rotate=``; ``ops/mla.py``): the split entry against the plain call on
concatenated operands, the layer against a concatenating reference kept here,
and the traced program searched for what the layout was meant to remove.
Interpreted: ``tests/test_flash_compile.py`` compiles the cell's shape for a
described v5e, ``chip_smoke.py`` phase 2 runs it on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_tensorflow_tpu.ops import mla
from flink_tensorflow_tpu.ops.flash_attention import _rope_heads, flash_attention

YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}

#: heads, key/value heads, nope, rope, the values' head size
_HEADS = {"4x128+64_values128": (4, 4, 128, 64, 128), "6on3x24+8_values16": (6, 3, 24, 8, 16)}


def _operands(case, dtype, b=2, t=96, seed=0):
    h, hkv, nope, rope, dv = _HEADS[case]
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    angle = np.arange(t)[:, None] * mla.yarn_inv_freq(rope, 50000.0, YARN)[None, :]
    cos, sin = (jnp.asarray(f(angle), jnp.float32) for f in (np.cos, np.sin))
    cast = lambda x: x.astype(dtype)  # noqa: E731
    return (cast(draw(b, t, h, nope)), draw(b, t, h, rope), cast(draw(b, t, hkv, nope)),
            cast(mla.rope_pairs(draw(b, t, rope), cos, sin)), cast(draw(b, t, hkv, dv)), cos, sin)


def _concatenated(q_nope, q_pe, k_nope, k_pe, v, **kwargs):
    """The plain call on ``q_nope | q_pe`` and ``k_nope | k_pe``, the one rotary
    key head written beside every head's ``k_nope``: what the split entry replaces."""
    b, t, hkv, _ = k_nope.shape
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (b, t, hkv, k_pe.shape[-1]))], axis=-1)
    return flash_attention(jnp.concatenate([q_nope, q_pe], axis=-1), k, v, **kwargs)


@pytest.mark.parametrize("return_lse", [False, True], ids=["out", "out_and_lse"])
@pytest.mark.parametrize("in_kernel", [False, True], ids=["rotated_before", "rotated_in_the_kernel"])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", list(_HEADS))
def test_the_split_entry_gives_what_the_plain_call_gives_on_concatenated_operands(case, dtype, tol, in_kernel,
                                                                                  return_lse):
    q_nope, q_pe, k_nope, k_pe, v, cos, sin = _operands(case, dtype)
    turned = mla.rope_pairs(q_pe, cos, sin).astype(dtype)
    kwargs = dict(causal=True, scale=0.1447, block_q=32, block_k=32, interpret=True, return_lse=return_lse)
    want = _concatenated(q_nope, turned, k_nope, k_pe, v, **kwargs)
    if in_kernel:  # q_pe as its projection wrote it: float32, not yet turned
        got = flash_attention(q_nope, k_nope, v, q_rope=q_pe, k_rope=k_pe, rotate=(cos, sin), **kwargs)
    else:
        got = flash_attention(q_nope, k_nope, v, q_rope=turned, k_rope=k_pe, **kwargs)
    if return_lse:
        assert got[1].shape == want[1].shape == (2, _HEADS[case][0], 96) and got[1].dtype == jnp.float32
        np.testing.assert_allclose(got[1], want[1], rtol=tol, atol=tol)
        got, want = got[0], want[0]
    assert got.shape == want.shape and got.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_the_chosen_tile_and_the_default_scale_are_the_plain_calls(causal):
    # No block named: the tile is read off the shape, as the plain call's is; no scale: 1 / sqrt(nope + rope).
    q_nope, q_pe, k_nope, k_pe, v, cos, sin = _operands("4x128+64_values128", "float32", b=1, t=1024, seed=3)
    want = _concatenated(q_nope, mla.rope_pairs(q_pe, cos, sin), k_nope, k_pe, v, causal=causal, interpret=True)
    got = flash_attention(q_nope, k_nope, v, q_rope=q_pe, k_rope=k_pe, rotate=(cos, sin), causal=causal,
                          interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("heads,rope,want", [(64, 64, 2), (4, 8, 4), (8, 128, 1), (6, 32, 6), (16, 32, 4), (8, 192, 2)])
def test_a_block_of_rotary_parts_fills_whole_lane_tiles_or_holds_every_head(heads, rope, want):
    assert _rope_heads(heads, rope) == want
    assert heads % want == 0 and (want * rope % 128 == 0 or want == heads)


def test_operands_that_do_not_fit_are_refused_before_anything_is_built():
    q_nope, q_pe, k_nope, k_pe, v, cos, sin = _operands("6on3x24+8_values16", "float32")
    with pytest.raises(ValueError, match="rotary parts"):
        flash_attention(q_nope, k_nope, v, q_rope=q_pe, interpret=True)  # no k_rope
    with pytest.raises(ValueError, match="rotary parts"):
        flash_attention(q_nope, k_nope, v, q_rope=q_pe[:, :, :3], k_rope=k_pe, interpret=True)
    with pytest.raises(ValueError, match="rotary parts"):
        flash_attention(q_nope, k_nope, v, q_rope=q_pe, k_rope=k_pe[:, :, None], interpret=True)
    # Mosaic blocks the last dimension by whole lane tiles: a head of 24 cannot be cut out of [B, T, H x 24].
    with pytest.raises(ValueError, match="whole lane tiles"):
        flash_attention(q_nope, k_nope, v, q_rope=q_pe, k_rope=k_pe, interpret=False)


# -- the layer -------------------------------------------------------------------------------

SIZES = dict(num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)


def _layer(seed=5, d=64, q_rank=48, kv_rank=32, b=2, t=40):
    rng = np.random.default_rng(seed)
    h, nope, rope, dv = SIZES.values()
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[0]), jnp.float32)  # noqa: E731
    p = {"q_a": draw(d, q_rank), "q_a_norm": jnp.full((q_rank,), 1.4), "q_b": draw(q_rank, h * (nope + rope)),
         "kv_a": draw(d, kv_rank + rope), "kv_a_norm": jnp.ones((kv_rank,)), "kv_b": draw(kv_rank, h * (nope + dv)),
         "o": draw(h * dv, d)}
    return p, jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)


def _concatenating_latent_attention(p, u, compute_dtype):
    """The route ``ops/mla.py`` took before: one product for ``q``, its parts
    sliced out and concatenated again, ``k_pe`` broadcast beside every head."""
    h, nope, rope, dv = SIZES.values()
    b, t, _ = u.shape
    cdt = jnp.dtype(compute_dtype)
    dot = lambda x, w: jnp.dot(x.astype(cdt), w.astype(cdt), precision=jax.lax.Precision.HIGHEST,  # noqa: E731
                               preferred_element_type=jnp.float32)
    q = dot(mla.rms_norm(dot(u, p["q_a"]), p["q_a_norm"], 1e-6), p["q_b"]).reshape(b, t, h, nope + rope)
    kv = dot(u, p["kv_a"])
    kvb = dot(mla.rms_norm(kv[..., :-rope], p["kv_a_norm"], 1e-6), p["kv_b"]).reshape(b, t, h, nope + dv)
    angle = np.arange(t)[:, None] * mla.yarn_inv_freq(rope, 50000.0, YARN)[None, :]
    cos, sin = (jnp.asarray(f(angle), jnp.float32) for f in (np.cos, np.sin))
    out = _concatenated(q[..., :nope].astype(cdt), mla.rope_pairs(q[..., nope:], cos, sin).astype(cdt),
                        kvb[..., :nope].astype(cdt), mla.rope_pairs(kv[..., -rope:], cos, sin).astype(cdt),
                        kvb[..., nope:].astype(cdt), causal=True, scale=mla.softmax_scale(nope + rope, YARN))
    return dot(out.reshape(b, t, h * dv), p["o"])


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_latent_attention_gives_what_the_concatenating_route_gave(dtype, tol):
    p, u = _layer()
    got = mla.latent_attention(p, u, **SIZES, rope_theta=50000.0, rope_scaling=YARN, eps=1e-6, compute_dtype=dtype)
    want = _concatenating_latent_attention(p, u, dtype)
    assert got.shape == u.shape and got.dtype == jnp.float32 and 0.1 < float(want.std()) < 10
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * float(jnp.abs(want).max()))


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the programs nested in it, a kernel's body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


def test_the_traced_layer_moves_no_activation_with_a_heads_axis():
    p, u = _layer()
    b, t, _ = u.shape
    h, nope, rope, dv = SIZES.values()
    traced = jax.make_jaxpr(lambda p, u: mla.latent_attention(
        p, u, **SIZES, rope_theta=50000.0, rope_scaling=YARN, eps=1e-6))(p, u)
    eqns = list(_equations(traced.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1 and kernels[0].params["name"] == "flash_attention"
    # what the kernel reads and writes: [B, T, H x D] as the products wrote them, one rotary key head
    assert [v.aval.shape for v in kernels[0].invars][:2] == [(b, t, h * nope), (b, t, h * rope)]
    assert (b, t, h * dv) in [v.aval.shape for v in kernels[0].outvars]
    per_head = b * t * h * min(nope, rope, dv)  # the smallest tensor that has a heads axis
    moved = [(e.primitive.name, v.aval.shape) for e in eqns for v in e.outvars
             if e.primitive.name in ("transpose", "concatenate", "broadcast_in_dim", "gather")
             and v.aval.shape[:2] == (b, t) and np.prod(v.aval.shape) >= per_head]
    assert moved == []
    # and nothing is as wide as q or k whole: nope + rope a head
    assert not [v.aval.shape for e in eqns for v in e.outvars
                if v.aval.shape[:2] == (b, t) and v.aval.shape[-1] in (nope + rope, h * (nope + rope))]
