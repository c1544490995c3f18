"""Zoo registry — names architectures so loaders can reconstruct them.

A saved bundle stores ``{"architecture": "lenet", "config": {...}}``; the
loader looks the name up here and rebuilds the flax module, then attaches
restored params (models/loaders.py).  This is the TPU-native stand-in for
the reference's GraphDef self-description: our "graph" is code, so bundles
carry a pointer to it instead of protobuf ops.
"""

from __future__ import annotations

import dataclasses
import typing

from flink_tensorflow_tpu.models.base import Model, ModelMethod
from flink_tensorflow_tpu.tensors.schema import RecordSchema


@dataclasses.dataclass(frozen=True)
class ModelDef:
    """An instantiable architecture: flax module + typed methods + loss."""

    architecture: str
    config: typing.Dict[str, typing.Any]
    module: typing.Any  # flax nn.Module
    input_schema: RecordSchema
    methods: typing.Mapping[str, ModelMethod]
    #: rng -> variables pytree (flax ``{"params": ..., "batch_stats": ...}``)
    init_fn: typing.Callable[[typing.Any], typing.Any]
    #: ``loss_fn(variables, batch, rng) -> (loss, (new_model_state, metrics))``
    #: for trainable defs; None for inference-only use.
    loss_fn: typing.Optional[typing.Callable] = None

    def init_params(self, rng) -> typing.Any:
        return self.init_fn(rng)

    def to_model(self, params, name: typing.Optional[str] = None) -> Model:
        return Model(
            name or self.architecture,
            params,
            self.methods,
            metadata={"architecture": self.architecture, "config": dict(self.config)},
        )


_BUILDERS: typing.Dict[str, typing.Callable[..., ModelDef]] = {}


def register_model_def(name: str):
    def deco(builder):
        _BUILDERS[name] = builder
        return builder

    return deco


_ZOO_MODULES = ("lenet", "inception", "resnet", "bilstm", "widedeep",
                "chartransformer", "falcon_h1", "lfm2_moe", "kimi_k2", "afmoe", "mellum")


def get_model_def(architecture: str, **config) -> ModelDef:
    # Import zoo modules lazily so registry import stays cheap.
    import importlib

    if architecture not in _BUILDERS:
        for mod in _ZOO_MODULES:
            importlib.import_module(f"flink_tensorflow_tpu.models.zoo.{mod}")
    try:
        builder = _BUILDERS[architecture]
    except KeyError:
        raise KeyError(
            f"unknown architecture {architecture!r}; registered: {sorted(_BUILDERS)}"
        ) from None
    return builder(**config)
