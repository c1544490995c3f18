"""Operations a configuration's step needs, from its shapes alone.

The count walks the plain reference's architecture (convolutions and dense
layers, two operations a multiply-add) and so reads the same work whatever
implements the step.  Training counts the forward pass three times: once
forward, twice backward (gradients of inputs and of weights); recomputed work
does not count.
"""

from __future__ import annotations

import importlib

from benchmark.reference import nn


def forward_flops(config: dict) -> int:
    """One record's forward pass."""
    ref = importlib.import_module("benchmark.reference." + config["reference"])
    return nn.describe(ref.forward, config["model"])[1]


def step_flops(config: dict) -> int:
    """One record (serving) or one example (training) of the configuration's step."""
    forward = forward_flops(config)
    return 3 * forward if config.get("training") else forward
