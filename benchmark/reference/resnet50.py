"""ResNet-50 (He et al., arXiv:1512.03385), v1.5: the stride sits on the 3x3.

7x7/2 conv, norm, relu, 3x3/2 max pool, stages of (3, 4, 6, 3) bottleneck
blocks at widths 64..512 (x4 out), global mean, logits.  Batch norm eps 1e-5;
training uses the batch's own statistics.  Strided 3x3 convs pad as
TensorFlow's ``SAME`` does (the paper does not say).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.nn import max_pool, normalize_uint8

EPS = 1e-5


def _block(net, name, x, filters, stride):
    y = net.conv(f"{name}.0", x, filters, (1, 1))
    y = jnp.maximum(net.bn(f"{name}.0", y, EPS), 0.0)
    y = net.conv(f"{name}.1", y, filters, (3, 3), stride, "SAME")
    y = jnp.maximum(net.bn(f"{name}.1", y, EPS), 0.0)
    y = net.conv(f"{name}.2", y, filters * 4, (1, 1))
    y = net.bn(f"{name}.2", y, EPS, kind="bn_scale_last")
    if x.shape != y.shape:
        x = net.conv(f"{name}.proj", x, filters * 4, (1, 1), stride)
        x = net.bn(f"{name}.proj", x, EPS)
    return jnp.maximum(x + y, 0.0)


def forward(net, images, config):
    """uint8 [N, S, S, 3] -> float32 logits [N, num_classes]."""
    width = config["width"]
    x = normalize_uint8(images)
    x = net.conv("stem", x, width, (7, 7), 2, ((3, 3), (3, 3)))
    x = jnp.maximum(net.bn("stem", x, EPS), 0.0)
    x = max_pool(x, 3, 2, ((1, 1), (1, 1)))
    n = 0
    for i, blocks in enumerate(config["stage_sizes"]):
        for j in range(blocks):
            block = functools.partial(_block, net, f"block{n}", filters=width * 2**i,
                                      stride=2 if i > 0 and j == 0 else 1)
            # Training a full batch in float32: keep only each block's input.
            x = jax.checkpoint(block)(x) if net.train and net.params is not None else block(x)
            n += 1
    x = jnp.mean(x, axis=(1, 2))
    return net.dense("logits", x, config["num_classes"])
