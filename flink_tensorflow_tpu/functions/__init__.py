"""Stream-operator model bridge — ModelFunction/GraphFunction equivalents
(BASELINE.json:5; SURVEY.md §2 row 7)."""

from flink_tensorflow_tpu.functions.model_function import (
    GraphMapFunction,
    GraphWindowFunction,
    ModelMapFunction,
    ModelWindowFunction,
)
from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner
from flink_tensorflow_tpu.functions.training_function import (
    DPTrainWindowFunction,
    OnlineTrainFunction,
)

__all__ = [
    "CompiledMethodRunner",
    "DPTrainWindowFunction",
    "OnlineTrainFunction",
    "GraphMapFunction",
    "GraphWindowFunction",
    "ModelMapFunction",
    "ModelWindowFunction",
]
