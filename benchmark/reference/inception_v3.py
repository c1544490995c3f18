"""Inception-v3 (Szegedy et al., arXiv:1512.00567), plain forward pass.

Stem, 3x block A (35x35), reduction A, 4x block B (17x17, factorised 7x7),
reduction B, 2x block C (8x8), global mean, logits.  Each conv unit is
conv (no bias) -> batch norm (eps 1e-3, running statistics) -> relu.
Names: ``<block>.<n>`` with ``n`` counting the block's conv units in the order
written here.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.reference.nn import avg_pool_same, max_pool, normalize_uint8

EPS = 1e-3


def _unit(net, block):
    count = [0]

    def c(x, cout, kernel, stride=1, padding="VALID"):
        name = f"{block}.{count[0]}"
        count[0] += 1
        y = net.conv(name, x, cout, kernel, stride, padding)
        return jnp.maximum(net.bn(name, y, EPS), 0.0)

    return c


def _block_a(net, name, x, pool_features):
    c = _unit(net, name)
    b1 = c(x, 64, (1, 1))
    b5 = c(c(x, 48, (1, 1)), 64, (5, 5), padding="SAME")
    b3 = c(x, 64, (1, 1))
    b3 = c(b3, 96, (3, 3), padding="SAME")
    b3 = c(b3, 96, (3, 3), padding="SAME")
    bp = c(avg_pool_same(x, 3), pool_features, (1, 1))
    return jnp.concatenate([b1, b5, b3, bp], axis=-1)


def _reduction_a(net, name, x):
    c = _unit(net, name)
    b3 = c(x, 384, (3, 3), 2)
    bd = c(x, 64, (1, 1))
    bd = c(bd, 96, (3, 3), padding="SAME")
    bd = c(bd, 96, (3, 3), 2)
    return jnp.concatenate([b3, bd, max_pool(x, 3, 2)], axis=-1)


def _block_b(net, name, x, c7):
    c = _unit(net, name)
    b1 = c(x, 192, (1, 1))
    b7 = c(x, c7, (1, 1))
    b7 = c(b7, c7, (1, 7), padding="SAME")
    b7 = c(b7, 192, (7, 1), padding="SAME")
    bd = c(x, c7, (1, 1))
    bd = c(bd, c7, (7, 1), padding="SAME")
    bd = c(bd, c7, (1, 7), padding="SAME")
    bd = c(bd, c7, (7, 1), padding="SAME")
    bd = c(bd, 192, (1, 7), padding="SAME")
    bp = c(avg_pool_same(x, 3), 192, (1, 1))
    return jnp.concatenate([b1, b7, bd, bp], axis=-1)


def _reduction_b(net, name, x):
    c = _unit(net, name)
    b3 = c(c(x, 192, (1, 1)), 320, (3, 3), 2)
    b7 = c(x, 192, (1, 1))
    b7 = c(b7, 192, (1, 7), padding="SAME")
    b7 = c(b7, 192, (7, 1), padding="SAME")
    b7 = c(b7, 192, (3, 3), 2)
    return jnp.concatenate([b3, b7, max_pool(x, 3, 2)], axis=-1)


def _block_c(net, name, x):
    c = _unit(net, name)
    b1 = c(x, 320, (1, 1))
    b3 = c(x, 384, (1, 1))
    b3a = c(b3, 384, (1, 3), padding="SAME")
    b3b = c(b3, 384, (3, 1), padding="SAME")
    bd = c(c(x, 448, (1, 1)), 384, (3, 3), padding="SAME")
    bda = c(bd, 384, (1, 3), padding="SAME")
    bdb = c(bd, 384, (3, 1), padding="SAME")
    bp = c(avg_pool_same(x, 3), 192, (1, 1))
    return jnp.concatenate([b1, b3a, b3b, bda, bdb, bp], axis=-1)


def forward(net, images, config):
    """uint8 [N, S, S, 3] -> float32 logits [N, num_classes]."""
    x = normalize_uint8(images)
    c = _unit(net, "stem")
    x = c(x, 32, (3, 3), 2)
    x = c(x, 32, (3, 3))
    x = c(x, 64, (3, 3), padding="SAME")
    x = max_pool(x, 3, 2)
    x = c(x, 80, (1, 1))
    x = c(x, 192, (3, 3))
    x = max_pool(x, 3, 2)
    for i, pool_features in enumerate((32, 64, 64)):
        x = _block_a(net, f"a{i}", x, pool_features)
    x = _reduction_a(net, "ra0", x)
    for i, c7 in enumerate((128, 160, 160, 192)):
        x = _block_b(net, f"b{i}", x, c7)
    x = _reduction_b(net, "rb0", x)
    for i in range(2):
        x = _block_c(net, f"c{i}", x)
    x = jnp.mean(x, axis=(1, 2))
    return net.dense("logits", x, config["num_classes"])
