"""Model layer tests: zoo forward passes, loader round-trips, frozen
functions — the reference's loader-behavior unit tests (SURVEY.md §4)
recast for bundles and jax-export artifacts.

Everything is jitted: eager per-op dispatch is pathologically slow in this
environment, and the framework's production path is always-compiled anyway
(the model runner jits per batch bucket)."""

import json
import pathlib

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from flink_tensorflow_tpu.analysis.shardcheck import _iter_levels
from flink_tensorflow_tpu.models import (
    GraphLoader,
    SavedModelLoader,
    freeze_method,
    get_model_def,
    save_bundle,
)
from flink_tensorflow_tpu.models.zoo import inception


@pytest.fixture(scope="module")
def rng():
    return jax.random.key(0)


def init_jit(mdef, rng):
    return jax.jit(mdef.init_fn)(rng)


def _perturbed(variables):
    """A fresh norm is the identity: give every leaf a value of its own
    (variances stay positive)."""
    leaves, treedef = jax.tree_util.tree_flatten(variables)
    keys = jax.random.split(jax.random.key(3), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.5 * jax.random.uniform(k, leaf.shape) for leaf, k in zip(leaves, keys)])


#: Each block's branches as chains of its ``ConvBN_<i>`` units from the
#: block's input, in the order they are concatenated; "pool" is the 3x3/2
#: max-pool of the input.
_BRANCHES = {
    "A": (inception.InceptionA(32, jnp.float32), [[0], [1, 2], [3, 4, 5], [6]]),
    "ReductionA": (inception.ReductionA(jnp.float32), [[0], [1, 2, 3], "pool"]),
    "B": (inception.InceptionB(128, jnp.float32), [[0], [1, 2, 3], [4, 5, 6, 7, 8], [9]]),
    "ReductionB": (inception.ReductionB(jnp.float32), [[0, 1], [2, 3, 4, 5], "pool"]),
    "C": (inception.InceptionC(jnp.float32), [[0], [1, 2], [1, 3], [4, 5, 6], [4, 5, 7], [8]]),
}


class TestZoo:
    def test_lenet_serve(self, rng):
        mdef = get_model_def("lenet")
        params = init_jit(mdef, rng)
        out = jax.jit(mdef.methods["serve"].fn)(params, {"image": jnp.zeros((4, 28, 28, 1))})
        assert out["logits"].shape == (4, 10)
        assert out["label"].shape == (4,) and out["label"].dtype == jnp.int32
        np.testing.assert_allclose(np.sum(np.asarray(out["prob"]), -1), 1.0, rtol=1e-3)

    def test_resnet_tiny_serve_and_loss(self, rng):
        mdef = get_model_def("resnet50", num_classes=7, image_size=32, width=8,
                             stage_sizes=(1, 1))
        params = init_jit(mdef, rng)
        out = jax.jit(mdef.methods["serve"].fn)(params, {"image": jnp.zeros((2, 32, 32, 3))})
        assert out["logits"].shape == (2, 7)
        batch = {"image": jnp.zeros((2, 32, 32, 3)),
                 "label": jnp.array([1, 2], jnp.int32)}
        loss, (new_state, metrics) = jax.jit(mdef.loss_fn)(params, batch, rng)
        assert np.isfinite(float(loss)) and "batch_stats" in new_state
        assert 0.0 <= float(metrics["accuracy"]) <= 1.0

    def test_inception_v3_serve(self, rng):
        mdef = get_model_def("inception_v3", num_classes=10)
        params = init_jit(mdef, rng)
        out = jax.jit(mdef.methods["serve"].fn)(
            params, {"image": jnp.zeros((1, 299, 299, 3))}
        )
        assert out["logits"].shape == (1, 10)
        assert float(out["score"][0]) <= 1.0

    def test_inception_uint8_matches_prescaled_float(self, rng):
        """uint8 ingestion + on-device normalize == float ingestion of the
        same normalized pixels (the 4x-transfer-saving path is lossless
        up to bf16 rounding)."""
        mdef8 = get_model_def("inception_v3", num_classes=5, uint8_input=True)
        mdeff = get_model_def("inception_v3", num_classes=5)
        params = init_jit(mdef8, rng)
        img8 = np.random.RandomState(0).randint(0, 256, (1, 299, 299, 3)).astype(np.uint8)
        imgf = img8.astype(np.float32) / 127.5 - 1.0
        out8 = jax.jit(mdef8.methods["serve"].fn)(params, {"image": jnp.asarray(img8)})
        outf = jax.jit(mdeff.methods["serve"].fn)(params, {"image": jnp.asarray(imgf)})
        np.testing.assert_allclose(np.asarray(out8["logits"]),
                                   np.asarray(outf["logits"]), atol=0.25)

    @pytest.mark.parametrize("train", [False, True], ids=["serve", "train"])
    @pytest.mark.parametrize("block,unit,features", [
        (inception.InceptionA(32, jnp.float32), "ConvBN_6", 32),
        (inception.InceptionB(128, jnp.float32), "ConvBN_9", 192),
        (inception.InceptionC(jnp.float32), "ConvBN_8", 192),
    ], ids=["A", "B", "C"])
    def test_inception_pool_branch_is_average_then_project(self, block, unit,
                                                           features, train):
        """The pool branch projects first and averages second; the average
        and the bias-free 1x1 commute, so it answers as the textbook order
        (average the block's input, then conv, norm, relu) does from the
        same variables, and in training leaves the same batch_stats."""
        x = jax.random.normal(jax.random.key(1), (2, 9, 9, 24))
        variables = _perturbed(jax.jit(lambda k: block.init(k, x))(jax.random.key(2)))
        plain = inception.ConvBN(features, (1, 1), compute_dtype=jnp.float32)
        of_unit = {col: tree[unit] for col, tree in variables.items()}

        @jax.jit
        def both(variables, x):
            out, new = block.apply(variables, x, train, mutable=["batch_stats"])
            ref, ref_new = plain.apply(of_unit, inception._avg_pool_same(x), train,
                                       mutable=["batch_stats"])
            return out[..., -features:], new["batch_stats"][unit], ref, ref_new["batch_stats"]

        got, got_stats, ref, ref_stats = both(variables, x)
        assert float(jnp.max(ref)) > 0.1  # the relu has not zeroed the case
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(got_stats),
                        jax.tree_util.tree_leaves(ref_stats)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        if train:  # and the norm did see a batch
            was = variables["batch_stats"][unit]["BatchNorm_0"]["mean"]
            assert float(jnp.max(jnp.abs(got_stats["BatchNorm_0"]["mean"] - was))) > 0

    def test_inception_v3_variable_tree_is_the_recorded_one(self):
        """Saved bundles, the TF import path and the benchmark's rename
        rules address leaves by path: the 472 paths and shapes recorded from
        the tree before the pool branches were reordered still hold."""
        mdef = get_model_def("inception_v3")
        tree = jax.eval_shape(mdef.init_fn, jax.random.key(0))
        got = sorted(["/".join(k.key for k in path), list(leaf.shape), str(leaf.dtype)]
                     for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0])
        want = json.loads(
            pathlib.Path(__file__).with_name("inception_v3_tree.json").read_text())
        assert len(want) == 472
        assert got == want

    def test_inception_v3_averages_only_projected_channels(self):
        """Nine 3x3 averages a step, each over what its block's 1x1 leaves
        (32, 64, 64, 192 x6 channels), none over a block's whole input."""
        mdef = get_model_def("inception_v3", uint8_input=True)
        params = jax.eval_shape(mdef.init_fn, jax.random.key(0))
        image = jax.ShapeDtypeStruct((1, 299, 299, 3), jnp.uint8)
        jaxpr = jax.make_jaxpr(mdef.methods["serve"].fn)(params, {"image": image})

        pooled = [eqn.invars[0].aval.shape[-1]
                  for level in _iter_levels(jaxpr.jaxpr) for eqn in level.eqns
                  if eqn.primitive.name == "reduce_window_sum"]
        assert pooled == [32, 64, 64] + [192] * 6

    def test_inception_v3_stores_what_its_windowed_units_read(self):
        """Every branch value that a unit with a kernel other than 1x1 reads
        goes through one barrier (InceptionC's two shared ones feed both
        readers): 45 a step at the recorded sizes and widths, none in the
        stem (149, 147, 73, 71 wide) and none on a block's input (a
        concatenation or the stem's last max-pool)."""
        mdef = get_model_def("inception_v3", uint8_input=True)
        params = jax.eval_shape(mdef.init_fn, jax.random.key(0))
        image = jax.ShapeDtypeStruct((1, 299, 299, 3), jnp.uint8)
        jaxpr = jax.make_jaxpr(mdef.methods["serve"].fn)(params, {"image": image})

        stored, producers = [], []
        for level in _iter_levels(jaxpr.jaxpr):
            made_by = {v: eqn.primitive.name for eqn in level.eqns for v in eqn.outvars}
            for eqn in level.eqns:
                if eqn.primitive.name == "optimization_barrier":
                    stored += [v.aval.shape[1:] for v in eqn.invars]
                    producers += [made_by.get(v) for v in eqn.invars]
        block_a = [(35, 35, 48), (35, 35, 64), (35, 35, 96)]
        block_b = lambda c7: [(17, 17, c7)] * 6  # noqa: E731
        assert stored == (block_a * 3 + [(35, 35, 64), (35, 35, 96)]
                          + block_b(128) + block_b(160) * 2 + block_b(192)
                          + [(17, 17, 192)] * 4 + [(8, 8, 384), (8, 8, 448), (8, 8, 384)] * 2)
        assert not {"concatenate", "reduce_window_max"} & set(producers)

    @pytest.mark.parametrize("train", [False, True], ids=["serve", "train"])
    @pytest.mark.parametrize("name", list(_BRANCHES))
    def test_inception_block_answers_as_its_units_applied_one_by_one(self, name, train):
        """The barriers are identities: a block gives the output, and in
        training the batch_stats, of its units applied one after another by
        hand from the same variables."""
        block, branches = _BRANCHES[name]
        x = jax.random.normal(jax.random.key(1), (2, 9, 9, 24))
        units = {}

        def record(f, args, kwargs, context):
            m = context.module
            if isinstance(m, inception.ConvBN) and context.method_name == "__call__":
                units[m.name] = inception.ConvBN(m.features, m.kernel, m.strides, m.padding,
                                                 m.compute_dtype, m.avg_pool)
            return f(*args, **kwargs)

        with nn.intercept_methods(record):
            variables = _perturbed(jax.jit(lambda k: block.init(k, x))(jax.random.key(2)))

        @jax.jit
        def both(variables, x):
            out, new = block.apply(variables, x, train, mutable=["batch_stats"])
            parts, stats = [], {}
            for chain in branches:
                if chain == "pool":
                    parts.append(nn.max_pool(x, (3, 3), strides=(2, 2)))
                    continue
                y = x
                for i in chain:
                    unit = f"ConvBN_{i}"
                    y, stats[unit] = units[unit].apply(
                        {col: tree[unit] for col, tree in variables.items()}, y, train,
                        mutable=["batch_stats"])
                parts.append(y)
            return out, new["batch_stats"], jnp.concatenate(parts, axis=-1), stats

        got, got_stats, ref, ref_stats = both(variables, x)
        assert len(units) == len(got_stats) == len(ref_stats)
        assert float(jnp.max(ref)) > 0.1  # the relus have not zeroed the case
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
        for unit, stats in ref_stats.items():
            for a, b in zip(jax.tree_util.tree_leaves(got_stats[unit]),
                            jax.tree_util.tree_leaves(stats["batch_stats"])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_inception_v3_gradient_crosses_the_stored_values(self, rng):
        """The train path runs the same blocks: on two 75x75 images (the
        least the stem and reductions take) every gradient is finite, and a
        unit whose output is stored (InceptionB_0's first 1x1, read by a
        1x7) gets one."""
        mdef = get_model_def("inception_v3", num_classes=10, image_size=75)
        params = init_jit(mdef, rng)
        batch = {"image": jax.random.normal(jax.random.key(4), (2, 75, 75, 3)),
                 "label": jnp.array([1, 7], jnp.int32)}
        grads = jax.jit(jax.grad(lambda p: mdef.loss_fn(p, batch, rng)[0]))(params)
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))
        kernel = grads["params"]["InceptionB_0"]["ConvBN_1"]["Conv_0"]["kernel"]
        assert float(jnp.max(jnp.abs(kernel))) > 0

    def test_bilstm_padding_invariance(self, rng):
        """Same sequence padded to different buckets -> same logits: the
        masking contract dynamic batching relies on (BASELINE.json:9)."""
        mdef = get_model_def("bilstm", vocab_size=50, hidden_dim=16, embed_dim=8)
        params = init_jit(mdef, rng)
        tokens = np.array([3, 7, 11, 2], np.int32)
        fn = jax.jit(mdef.methods["serve"].fn)
        out8 = fn(params,
                  {"tokens": jnp.asarray(np.pad(tokens, (0, 4))[None])},
                  {"tokens": jnp.array([4], jnp.int32)})
        out16 = fn(params,
                   {"tokens": jnp.asarray(np.pad(tokens, (0, 12))[None])},
                   {"tokens": jnp.array([4], jnp.int32)})
        np.testing.assert_allclose(np.asarray(out8["logits"]),
                                   np.asarray(out16["logits"]), atol=2e-2)

    def test_widedeep_serve_and_loss(self, rng):
        mdef = get_model_def("widedeep", hash_buckets=100, embed_dim=4,
                             hidden=(16, 8))
        params = init_jit(mdef, rng)
        inputs = {
            "wide": jnp.ones((3, 64)),
            "dense": jnp.ones((3, 13)),
            "cat": jnp.zeros((3, 8), jnp.int32),
        }
        out = jax.jit(mdef.methods["serve"].fn)(params, inputs)
        assert out["prob"].shape == (3,)
        batch = dict(inputs, label=jnp.array([0, 1, 1], jnp.int32))
        loss, (_, metrics) = jax.jit(mdef.loss_fn)(params, batch, rng)
        assert np.isfinite(float(loss))

    def test_unknown_architecture(self):
        with pytest.raises(KeyError):
            get_model_def("alexnet")


class TestLoaders:
    def test_bundle_roundtrip(self, rng, tmp_path):
        mdef = get_model_def("lenet")
        params = init_jit(mdef, rng)
        path = str(tmp_path / "lenet_bundle")
        save_bundle(mdef, params, path)

        model = SavedModelLoader(path).load()
        assert model.metadata["architecture"] == "lenet"
        x = {"image": jnp.ones((2, 28, 28, 1))}
        serve = jax.jit(mdef.methods["serve"].fn)
        want = serve(params, x)
        got = serve(model.params, x)
        np.testing.assert_allclose(np.asarray(want["logits"]),
                                   np.asarray(got["logits"]), atol=1e-6)

    def test_bundle_bad_format(self, tmp_path):
        import json

        (tmp_path / "model.json").write_text(json.dumps({"format": "other"}))
        with pytest.raises(ValueError):
            SavedModelLoader(str(tmp_path)).manifest()

    def test_frozen_graph_roundtrip(self, rng, tmp_path):
        mdef = get_model_def("lenet")
        model = mdef.to_model(init_jit(mdef, rng))
        frozen_bytes = freeze_method(model, "serve", batch=2)
        path = tmp_path / "lenet.stablehlo"
        path.write_bytes(frozen_bytes)

        fn = GraphLoader(str(path)).load()
        x = {"image": jnp.ones((2, 28, 28, 1))}
        got = fn(x)
        want = jax.jit(model.method("serve").fn)(model.params, x)
        np.testing.assert_allclose(np.asarray(want["logits"]),
                                   np.asarray(got["logits"]), atol=1e-6)

    def test_missing_method(self, rng):
        mdef = get_model_def("lenet")
        model = mdef.to_model(init_jit(mdef, rng))
        with pytest.raises(KeyError):
            model.method("nope")
