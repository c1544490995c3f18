"""The flash kernel, the routed experts' gated grouped product, the whole layer's
combine and Inception-v3's step, compiled for a described TPU v5e (no chip attached): what Mosaic refuses (a block it cannot tile, a slice off the tiling, more VMEM than
the call asked for) fails here and not on the chip, and so does a fusion XLA would
pick that the model is written to avoid.  Nothing runs, so this says nothing of
results or speed; ``chip_smoke.py`` phase 2 holds the results.

The topology is described inside a fixture, never at import: one process at a
time may load the TPU's library, and every xdist worker imports this file."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from flink_tensorflow_tpu.models import get_model_def
from flink_tensorflow_tpu.ops import moe
from flink_tensorflow_tpu.ops.flash_attention import flash_attention, tile_plan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to hold
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


#: (q shape [B, T, H, D], key positions, key/value heads, dtype, causal, return_lse[, the values' head size
#: [, the rotary part handed over beside q and k, whether the kernel turns q's[, a sliding window]]])
_SHAPES = {
    # the three language-model cells
    "falcon_h1-20on4-head128": ((2, 4096, 20, 128), 4096, 4, "bfloat16", True, False),
    "lfm2-32on8-head64": ((2, 4096, 32, 64), 4096, 8, "bfloat16", True, False),
    "kimi_k2-64on64-head192-values128": ((2, 4096, 64, 192), 4096, 64, "bfloat16", True, False, 128),
    "values-narrower-float32": ((1, 1024, 2, 192), 1024, 2, "float32", True, True, 128),
    # latent attention's own call: q_nope, k_nope, v out of [B, T, H x 128], q_pe as its product wrote it
    # (float32, turned in the kernel), one rotary key head for all 64
    "kimi_k2-split-64x128-rope64-turned-in-kernel": ((2, 4096, 64, 128), 4096, 64, "bfloat16", True, False, 128, 64, True),
    "split-rope-turned-before-float32-lse": ((1, 1024, 4, 128), 1024, 2, "float32", True, True, 128, 64, False),
    # chartransformer's prefill and chip_smoke's long-sequence shape
    "prefill-128": ((8, 128, 4, 16), 128, 4, "float32", True, True),
    "bf16-256-full": ((2, 256, 4, 64), 256, 4, "bfloat16", False, True),
    # the ring's blocks: lse out, causal on the diagonal, keys of another length off it
    "ring-diagonal": ((1, 1024, 4, 128), 1024, 4, "bfloat16", True, True),
    "ring-off-diagonal": ((1, 1024, 4, 128), 4096, 4, "bfloat16", False, True),
    # K and V too long to copy whole: a grid over key tiles, chunks inside each
    "keys-in-tiles": ((1, 16384, 8, 128), 16384, 8, "bfloat16", True, False),
    # Trinity-Large's two calls at 32,768 positions, 48 query heads on 8: the band of 4,096 (three
    # tiles of 2,048 keys a q block) and the full layer's triangle (four tiles of 8,192)
    "trinity-window-4096-48on8-32768": ((1, 32768, 48, 128), 32768, 8, "bfloat16", True, False, None, 0, False, 4096),
    "trinity-full-48on8-32768": ((1, 32768, 48, 128), 32768, 8, "bfloat16", True, False),
    # Mellum 2's two calls at 32,768 positions, 32 query heads on 4: the band of 1,024 (three tiles of 512
    # keys a q block) and the full layer's triangle
    "mellum-window-1024-32on4-32768": ((1, 32768, 32, 128), 32768, 4, "bfloat16", True, False, None, 0, False, 1024),
    "mellum-full-32on4-32768": ((1, 32768, 32, 128), 32768, 4, "bfloat16", True, False),
    "window-not-a-multiple-of-the-chunk-float32-lse": ((1, 4096, 4, 128), 4096, 2, "float32", True, True, None, 0, False, 1000),
    "float32-4096": ((1, 4096, 2, 128), 4096, 2, "float32", True, True),
    # TestTileableBlocks' lengths: no multiple of 128, no multiple of 8, mixed
    "length-100": ((1, 100, 2, 16), 100, 2, "float32", True, True),
    "length-264-136": ((1, 264, 2, 16), 136, 2, "float32", False, False),
    "length-12-200": ((1, 12, 2, 16), 200, 2, "float32", False, False),
    "length-1000-bf16": ((1, 1000, 2, 64), 1000, 2, "bfloat16", True, False),
    "length-1001-odd": ((1, 1001, 2, 64), 1001, 2, "bfloat16", True, False),
}


@pytest.mark.parametrize("case", list(_SHAPES), ids=list(_SHAPES))
def test_chosen_tile_compiles_for_v5e(one_chip, case):
    case = _SHAPES[case]  # the last four are optional: as q's, none, not turned, no window
    shape, tk, kv_heads, dtype, causal, return_lse, dv, rope, turn, window = case + (None, 0, False, None)[len(case) - 6:]
    b, t, h, d = shape
    dv = dv or d
    described = lambda shape, dtype=dtype: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)  # noqa: E731
    operands = {"q": described(shape), "k": described((b, tk, kv_heads, d)), "v": described((b, tk, kv_heads, dv))}
    if rope:
        operands.update(q_rope=described((b, t, h, rope), "float32" if turn else dtype), k_rope=described((b, tk, rope)))
    if turn:
        operands["rotate"] = (described((t, rope // 2), "float32"),) * 2

    def call(operands):
        # A scale is given where the head sizes differ, as latent attention gives one.
        return flash_attention(**operands, causal=causal, interpret=False, return_lse=return_lse,
                               scale=0.1447 if rope or dv != d else None, window=window)

    compiled = jax.jit(call).lower(operands).compile()
    # one kernel a call, under the name the benchmark's roofline share reads
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert "flash_attention" in compiled.as_text()
    assert ("flash_attention_window" in compiled.as_text()) == bool(window)
    plan = tile_plan(t, tk, d + rope, jnp.dtype(dtype), causal, dv=dv, window=window)
    assert t % plan.block_q == 0 and tk % plan.block_k == 0 and plan.block_k % plan.chunk == 0


#: (rows, d, f, experts held, tile_rows): the two routed cells' first products at the row tiles their layers take,
#: and at the others a share can take or that were tried on the chip (PERF.md 6, PR 40)
_GATED = {
    "lfm2-32768-rows-32-experts": (32768, 2048, 1792, 32, 512),
    "kimi-a-pass-of-4096-rows-12-experts": (4096, 7168, 2048, 12, 256),  # 2 contraction tiles of 3,584
    "kimi-row-tile-128": (4096, 7168, 2048, 12, 128),
    "kimi-row-tile-512": (4096, 7168, 2048, 12, 512),
    "lfm2-half-the-rows": (32768, 2048, 1792, 32, 256),
    "rows-no-multiple-of-the-tile": (1000, 512, 384, 3, 256),
    "mellum-262144-rows-64-experts-f896": (262144, 2304, 896, 64, 512),  # gate and up in tiles of 128
}


@pytest.mark.parametrize("case", list(_GATED), ids=list(_GATED))
def test_the_gated_grouped_product_compiles_for_v5e_under_the_name_gmm(one_chip, case):
    m, d, f, held, tile_rows = _GATED[case]
    described = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731

    def call(rows, w13, group_sizes):
        return moe.gated_grouped_matmul(rows, w13, group_sizes, tile_rows=tile_rows, interpret=False)

    text = jax.jit(call).lower(described((m, d)), described((held, d, 2 * f)),
                               described((held,), jnp.int32)).compile().as_text()
    # one kernel, found by the benchmark's pattern for the grouped products, which writes bfloat16 [rows, f]
    padded = -(-m // tile_rows) * tile_rows
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(rf"^\s*(ROOT )?%gmm(\.\d+)? = bf16\[{padded},{f}\]", text, re.M)
    assert f"[{m},{2 * f}]" not in text and f"[{padded},{2 * f}]" not in text


def test_the_w2_product_at_2304_output_columns_compiles_for_v5e_under_the_name_gmm(one_chip, monkeypatch):
    """Mellum 2's second product: 2,304 columns out in three tiles of 768, f = 896 in one contraction tile:
    no tile hangs over an edge."""
    described = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernel chooses to be interpreted off the TPU
    text = jax.jit(lambda rows, w2, sizes: moe.grouped_matmul(rows, w2, sizes)).lower(
        described((262144, 896)), described((64, 896, 2304)), described((64,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"^\s*(ROOT )?%gmm(\.\d+)? = f32\[262144,2304\]", text, re.M)


def test_the_combine_at_mellums_layer_compiles_for_v5e_and_reads_the_w2_product_as_it_lies(one_chip):
    """Mellum 2's whole-layer combine: one kernel, named so that the grouped products' pattern (``^%gmm``) does
    not count it, in Mosaic's default VMEM; the ``W2`` product's ``[262144, 2304]`` reaches it in tiles of one
    row by a bitcast, with no copy, and it writes ``[32768, 2304]`` itself."""
    described = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    args = (described((262144, 2304)), described((32768, 8), jnp.int32), described((32768, 8)))
    combine = lambda y, back, weights: moe.combine_rows(y, back, weights, interpret=False)  # noqa: E731
    params = _mosaic_params(jax.make_jaxpr(combine)(*args).jaxpr)
    assert len(params) == 1 and params[0].vmem_limit_bytes is None
    compiled = jax.jit(combine).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r"^\s*ROOT %combine_rows(\.\d+)? = f32\[32768,2304\]\{1,0:T\(8,128\)\}", text, re.M)
    assert re.search(r"%bitcast(\.\d+)? = f32\[32768,18,8,1,128\]\{[^}]*T\(1,128\)\} bitcast\(%y", text)
    assert not re.search(r"= f32\[(262144|32768),2304\]\S* (copy|transpose|fusion)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 32768 * 2304 * 4  # not one slot's rows


#: (rows, d, f, experts held, tile_rows): the four routed cells' layers, at the row tiles they take
_MOE_CELLS = {
    "mellum-whole-layer": (262144, 2304, 896, 64, 512),
    "lfm2-whole-layer": (32768, 2048, 1792, 32, 512),
    "trinity-a-pass-of-32768-rows-32-experts": (32768, 3072, 3072, 32, 512),
    "kimi-a-pass-of-4096-rows-12-experts": (4096, 7168, 2048, 12, 256),
}


def _mosaic_params(jaxpr):
    """The Mosaic compiler parameters of every ``pallas_call`` of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["compiler_params"]["mosaic_tpu"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _mosaic_params(sub)
    return found


@pytest.mark.parametrize("cell", list(_MOE_CELLS), ids=list(_MOE_CELLS))
def test_both_grouped_kernels_compile_for_v5e_at_the_cells_tiles_in_mosaics_default_vmem(one_chip, cell, monkeypatch):
    """The tiles ``moe.grouped_tiles`` reads off each cell's shapes: Mosaic takes both kernels, and neither names
    ``vmem_limit_bytes`` (a call that names one makes XLA set that much VMEM aside for the whole program)."""
    m, d, f, held, tile_rows = _MOE_CELLS[cell]
    described = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels choose to be interpreted off the TPU

    def layer(rows, w13, w2, sizes):
        hidden = moe.gated_grouped_matmul(rows, w13, sizes, tile_rows=tile_rows)
        return moe.grouped_matmul(hidden, w2, sizes, tile_rows=tile_rows)

    args = (described((m, d)), described((held, d, 2 * f)), described((held, f, d)), described((held,), jnp.int32))
    params = _mosaic_params(jax.make_jaxpr(layer)(*args).jaxpr)
    assert len(params) == 2 and all(p.vmem_limit_bytes is None for p in params)
    text = jax.jit(layer).lower(*args).compile().as_text()
    padded = -(-m // tile_rows) * tile_rows
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert re.search(rf"^\s*(ROOT )?%gmm(\.\d+)? = bf16\[{padded},{f}\]", text, re.M)
    assert re.search(rf"^\s*(ROOT )?%gmm(\.\d+)? = f32\[{padded},{d}\]", text, re.M)


def _nested_convolutions(text):
    """The op_name of every fusion of the entry computation that holds, in
    itself or in a fusion nested in it, more than one convolution: a
    producer computed inside its consumer's operand."""
    bodies, entry, body = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            body = bodies.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
        elif line.startswith("}"):
            body = None
        elif body is not None:
            body.append(line)

    def convolutions(name):
        return sum((" convolution(" in line) + sum(map(convolutions, re.findall(r"calls=%([\w.\-]+)", line)))
                   for line in bodies[name])
    return [re.search(r'op_name="([^"]+)"', line).group(1) for line in bodies[entry]
            if re.search(r" fusion\(", line) and convolutions(re.search(r"calls=%([\w.\-]+)", line).group(1)) > 1]


def test_inception_blocks_compute_no_convolution_inside_another_for_v5e(one_chip):
    """Left alone XLA fuses a branch's producer convolution into the operand
    of the windowed one that reads it and computes it once per tap (17 such
    fusions in the blocks at a batch of 1,024: PERF.md section 7, row 6).
    The blocks store those values, so only the stem's (left there on
    purpose) remain.  A batch of 128 lays the blocks' tensors out as the
    cell's 1,024 does, batch in the lanes, in a fifth of the compile."""
    mdef = get_model_def("inception_v3", uint8_input=True)
    params = jax.tree_util.tree_map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one_chip),
                                    jax.eval_shape(mdef.init_fn, jax.random.key(0)))
    image = jax.ShapeDtypeStruct((128, 299, 299, 3), jnp.uint8, sharding=one_chip)
    text = jax.jit(mdef.methods["serve"].fn).lower(params, {"image": image}).compile().as_text()

    assert re.search(r"= bf16\[128,17,17,192\]\{0,3,2,1:", text)
    nested = [name.split("InceptionV3/")[1] for name in _nested_convolutions(text)]
    assert nested, "the census finds not even the stem's nested convolutions"
    assert not [name for name in nested if name.startswith(("Inception", "Reduction"))], nested
