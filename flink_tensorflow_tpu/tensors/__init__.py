"""Tensor record layer — schemas, records, coercion, batching, transfer.

TPU-native replacement for the reference's ``TensorValue`` wrapper,
``TensorTypeInfo`` serializers, and implicit coercion layer (SURVEY.md §2
rows 1-3; BASELINE.json:5 "tensor-coercion layer").
"""

from flink_tensorflow_tpu.tensors.batching import (
    Batch,
    BucketLadder,
    BucketPolicy,
    assemble,
)
from flink_tensorflow_tpu.tensors.coercion import coerce, coerce_field, image_to_float, register_converter
from flink_tensorflow_tpu.tensors.schema import (
    RecordSchema,
    SchemaMismatch,
    TensorSpec,
    check_compatible,
    spec,
)
from flink_tensorflow_tpu.tensors.transfer import DeviceTransfer
from flink_tensorflow_tpu.tensors.value import TensorValue

__all__ = [
    "Batch",
    "BucketLadder",
    "BucketPolicy",
    "DeviceTransfer",
    "RecordSchema",
    "SchemaMismatch",
    "TensorSpec",
    "TensorValue",
    "assemble",
    "check_compatible",
    "coerce",
    "coerce_field",
    "image_to_float",
    "register_converter",
    "spec",
]
