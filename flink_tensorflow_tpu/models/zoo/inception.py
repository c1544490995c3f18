"""Inception-v3 — the north-star workload's model (BASELINE.json:2,7).

The reference's flagship example labels an image stream with a frozen
Inception-v3 GraphDef pulled into an embedded TF session (SURVEY.md §1 L6,
§3.1).  This is the native flax definition of the same architecture
(Szegedy et al. 2015, "Rethinking the Inception Architecture"): stem ->
3x InceptionA -> ReductionA -> 4x InceptionB -> ReductionB -> 2x InceptionC
-> global pool -> logits.  299x299x3 inputs, 1000 classes, NHWC, bfloat16
compute so every conv tiles onto the MXU.

All the asymmetric 1xN/Nx1 factorized convs are expressed directly; XLA
fuses the BN+relu chains into the conv epilogues, which is the fusion the
reference relies on cuDNN for.
"""

from __future__ import annotations

import functools
import typing

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from flink_tensorflow_tpu.models.base import ModelMethod
from flink_tensorflow_tpu.models.zoo.registry import ModelDef, register_model_def
from flink_tensorflow_tpu.tensors.schema import RecordSchema, spec


class ConvBN(nn.Module):
    """conv -> batchnorm -> relu, the Inception "BasicConv2d" unit.

    ``avg_pool`` is the pool branch of the Inception blocks, a 3x3 average
    in front of a 1x1 conv.  The average and a bias-free 1x1 conv commute
    (one is linear over space, the other over channels), so the unit
    projects first and averages the 32-192 channels the conv leaves, not
    the block's 192-2,048: conv -> avg_pool -> batchnorm -> relu.
    """

    features: int
    kernel: typing.Tuple[int, int]
    strides: typing.Tuple[int, int] = (1, 1)
    padding: typing.Any = "VALID"
    compute_dtype: jnp.dtype = jnp.bfloat16
    avg_pool: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(self.features, self.kernel, strides=self.strides,
                    padding=self.padding, use_bias=False, dtype=self.compute_dtype)(x)
        if self.avg_pool:
            x = _avg_pool_same(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9997,
                         epsilon=1e-3, dtype=self.compute_dtype)(x)
        return nn.relu(x)


def _avg_pool_same(x):
    return nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")


def _stored(x):
    """A branch's value that a windowed unit (a kernel other than 1x1) reads,
    written out once.

    Left alone, XLA fuses the convolution that makes the value (or its
    norm and relu) into the operand of the windowed one and computes it
    again for every tap of the window: a 1x7 would run the 7x1 before it
    seven times.  The barrier is an identity that keeps the two apart, so
    the reader takes the bfloat16 tensor its producer stored.  Never on a
    block's input, whose concatenation would then be written out, and not
    in the stem, whose producers are cheap to recompute and their outputs
    (0.9-1.5 GB at a batch of 1024) dear to store and read back.
    """
    return jax.lax.optimization_barrier(x)


class InceptionA(nn.Module):
    pool_features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = functools.partial(ConvBN, compute_dtype=self.dtype)
        b1 = c(64, (1, 1))(x, train)
        b5 = _stored(c(48, (1, 1))(x, train))
        b5 = c(64, (5, 5), padding="SAME")(b5, train)
        b3 = _stored(c(64, (1, 1))(x, train))
        b3 = _stored(c(96, (3, 3), padding="SAME")(b3, train))
        b3 = c(96, (3, 3), padding="SAME")(b3, train)
        bp = c(self.pool_features, (1, 1), avg_pool=True)(x, train)
        return jnp.concatenate([b1, b5, b3, bp], axis=-1)


class ReductionA(nn.Module):
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = functools.partial(ConvBN, compute_dtype=self.dtype)
        b3 = c(384, (3, 3), strides=(2, 2))(x, train)
        bd = _stored(c(64, (1, 1))(x, train))
        bd = _stored(c(96, (3, 3), padding="SAME")(bd, train))
        bd = c(96, (3, 3), strides=(2, 2))(bd, train)
        bp = nn.max_pool(x, (3, 3), strides=(2, 2))
        return jnp.concatenate([b3, bd, bp], axis=-1)


class InceptionB(nn.Module):
    """The 17x17 blocks with factorized 7x7 (1x7 then 7x1) convs."""

    channels_7x7: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = functools.partial(ConvBN, compute_dtype=self.dtype)
        c7 = self.channels_7x7
        b1 = c(192, (1, 1))(x, train)
        b7 = _stored(c(c7, (1, 1))(x, train))
        b7 = _stored(c(c7, (1, 7), padding="SAME")(b7, train))
        b7 = c(192, (7, 1), padding="SAME")(b7, train)
        bd = _stored(c(c7, (1, 1))(x, train))
        bd = _stored(c(c7, (7, 1), padding="SAME")(bd, train))
        bd = _stored(c(c7, (1, 7), padding="SAME")(bd, train))
        bd = _stored(c(c7, (7, 1), padding="SAME")(bd, train))
        bd = c(192, (1, 7), padding="SAME")(bd, train)
        bp = c(192, (1, 1), avg_pool=True)(x, train)
        return jnp.concatenate([b1, b7, bd, bp], axis=-1)


class ReductionB(nn.Module):
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = functools.partial(ConvBN, compute_dtype=self.dtype)
        b3 = _stored(c(192, (1, 1))(x, train))
        b3 = c(320, (3, 3), strides=(2, 2))(b3, train)
        b7 = _stored(c(192, (1, 1))(x, train))
        b7 = _stored(c(192, (1, 7), padding="SAME")(b7, train))
        b7 = _stored(c(192, (7, 1), padding="SAME")(b7, train))
        b7 = c(192, (3, 3), strides=(2, 2))(b7, train)
        bp = nn.max_pool(x, (3, 3), strides=(2, 2))
        return jnp.concatenate([b3, b7, bp], axis=-1)


class InceptionC(nn.Module):
    """The 8x8 blocks with split 1x3/3x1 branches."""

    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = functools.partial(ConvBN, compute_dtype=self.dtype)
        b1 = c(320, (1, 1))(x, train)
        b3 = _stored(c(384, (1, 1))(x, train))
        b3a = c(384, (1, 3), padding="SAME")(b3, train)
        b3b = c(384, (3, 1), padding="SAME")(b3, train)
        bd = _stored(c(448, (1, 1))(x, train))
        bd = _stored(c(384, (3, 3), padding="SAME")(bd, train))
        bda = c(384, (1, 3), padding="SAME")(bd, train)
        bdb = c(384, (3, 1), padding="SAME")(bd, train)
        bp = c(192, (1, 1), avg_pool=True)(x, train)
        return jnp.concatenate([b1, b3a, b3b, bda, bdb, bp], axis=-1)


class InceptionV3(nn.Module):
    num_classes: int = 1000
    compute_dtype: jnp.dtype = jnp.bfloat16
    dropout_rate: float = 0.2

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = functools.partial(ConvBN, compute_dtype=self.compute_dtype)
        x = x.astype(self.compute_dtype)
        # Stem: 299x299x3 -> 35x35x192
        x = c(32, (3, 3), strides=(2, 2))(x, train)
        x = c(32, (3, 3))(x, train)
        x = c(64, (3, 3), padding="SAME")(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        x = c(80, (1, 1))(x, train)
        x = c(192, (3, 3))(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        # 35x35
        x = InceptionA(32, self.compute_dtype)(x, train)
        x = InceptionA(64, self.compute_dtype)(x, train)
        x = InceptionA(64, self.compute_dtype)(x, train)
        x = ReductionA(self.compute_dtype)(x, train)
        # 17x17
        x = InceptionB(128, self.compute_dtype)(x, train)
        x = InceptionB(160, self.compute_dtype)(x, train)
        x = InceptionB(160, self.compute_dtype)(x, train)
        x = InceptionB(192, self.compute_dtype)(x, train)
        x = ReductionB(self.compute_dtype)(x, train)
        # 8x8
        x = InceptionC(self.compute_dtype)(x, train)
        x = InceptionC(self.compute_dtype)(x, train)
        x = jnp.mean(x, axis=(1, 2))
        if train and self.dropout_rate > 0:
            x = nn.Dropout(self.dropout_rate, deterministic=False)(x)
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


@register_model_def("inception_v3")
def build(num_classes: int = 1000, image_size: int = 299,
          uint8_input: bool = False) -> ModelDef:
    """``uint8_input=True``: records carry raw uint8 pixels and the model
    normalizes on device (x/127.5 - 1, Inception's canonical transform) —
    4x less host->HBM traffic per batch, and the normalize fuses into the
    first conv.  The reference does the same thing for the same reason:
    its Inception example builds the normalization INTO the TF graph
    (SURVEY.md §2 "Examples": "image normalization graph built
    programmatically")."""
    module = InceptionV3(num_classes=num_classes)
    in_dtype = np.uint8 if uint8_input else np.float32
    schema = RecordSchema({"image": spec((image_size, image_size, 3), in_dtype)})

    def _prep(x):
        if uint8_input:
            from flink_tensorflow_tpu.ops.preprocessing import inception_normalize

            return inception_normalize(x)
        return x

    def serve(variables, inputs):
        logits = module.apply(variables, _prep(inputs["image"]), train=False)
        prob = jax.nn.softmax(logits, axis=-1)
        return {
            "logits": logits,
            "label": jnp.argmax(logits, axis=-1).astype(jnp.int32),
            "score": jnp.max(prob, axis=-1),
        }

    def init_fn(rng):
        return module.init(rng, jnp.zeros((1, image_size, image_size, 3)), train=False)

    def loss_fn(variables, batch, rng):
        import optax

        from flink_tensorflow_tpu.models.zoo._common import weighted_metrics

        logits, new_state = module.apply(
            variables, _prep(batch["image"]), train=True, mutable=["batch_stats"],
            rngs={"dropout": rng},
        )
        labels = batch["label"]
        per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        hits = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        loss, acc = weighted_metrics(per_ex, hits, batch.get("valid"))
        return loss, (new_state, {"loss": loss, "accuracy": acc})

    methods = {
        "serve": ModelMethod(
            name="serve",
            input_schema=schema,
            output_names=("logits", "label", "score"),
            fn=serve,
            compute_dtype=jnp.bfloat16,
        )
    }
    return ModelDef(
        architecture="inception_v3",
        config={"num_classes": num_classes, "image_size": image_size,
                "uint8_input": uint8_input},
        module=module,
        input_schema=schema,
        methods=methods,
        init_fn=init_fn,
        loss_fn=loss_fn,
    )
