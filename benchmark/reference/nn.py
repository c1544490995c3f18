"""Plain building blocks for the reference networks: jax.numpy / lax only.

Imports nothing of the program.  One ``Net`` walks an architecture function
either to list what it needs (``describe``: parameter shapes, conv/dense
FLOPs) or to compute it from a flat ``{name: array}`` dict.  Everything is
float32 at ``Precision.HIGHEST`` unless ``quant`` rounds the operands of every
contraction to a narrower type first (the low-precision control).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def rounded(a, dtype):
    """``a`` rounded to ``dtype``; the cotangent passes straight through."""
    return a.astype(jnp.dtype(dtype)).astype(a.dtype)


rounded.defvjp(lambda a, dtype: (rounded(a, dtype), None), lambda dtype, _, ct: (ct,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def rounded_cotangent(y, dtype):
    """``y`` itself; its cotangent rounded to ``dtype``, scaled for each tensor
    so that its largest magnitude is the type's largest.  Unscaled, a float8
    cotangent underflows and the gradient all but vanishes, which no
    low-precision step a later PR would write does."""
    return y


def _round_scaled(dtype, _, ct):
    dtype = jnp.dtype(dtype)
    scale = jnp.max(jnp.abs(ct)) / float(jnp.finfo(dtype).max)
    scale = jnp.where(scale > 0, scale, 1.0)
    return ((ct / scale).astype(dtype).astype(ct.dtype) * scale,)


rounded_cotangent.defvjp(lambda y, dtype: (y, None), _round_scaled)


class Net:
    def __init__(self, params=None, *, train=False, quant=None):
        self.params = params  # None: describe mode
        self.train = train
        self.quant = quant  # dtype name the contraction operands are rounded to
        self.specs = {}  # name -> (shape, kind, fan_in)
        self.flops = 0  # forward multiply-adds * 2, convs and dense only

    def _param(self, name, shape, kind, fan_in=1):
        if self.params is None:
            self.specs[name] = (tuple(int(s) for s in shape), kind, int(fan_in))
            return jnp.zeros(shape, jnp.float32)
        value = self.params[name]
        if tuple(value.shape) != tuple(shape):
            raise ValueError(f"{name}: have {value.shape}, the architecture wants {shape}")
        return value.astype(jnp.float32)

    def _q(self, a):
        return a if self.quant is None else rounded(a, self.quant)

    def _q_back(self, y):
        """On a contraction's output: both backward contractions take its
        cotangent rounded."""
        return y if self.quant is None else rounded_cotangent(y, self.quant)

    def conv(self, name, x, cout, kernel, stride=1, padding="VALID"):
        kh, kw = kernel
        cin = x.shape[-1]
        w = self._param(name + ".kernel", (kh, kw, cin, cout), "conv", kh * kw * cin)
        y = lax.conv_general_dilated(
            self._q(x), self._q(w), (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
        y = self._q_back(y)
        # Multiply-adds on real pixels only: a tap that falls on padding is no work.
        pads = lax.padtype_to_pads(x.shape[1:3], (kh, kw), (stride, stride), padding) \
            if isinstance(padding, str) else padding
        taps = [sum(0 <= o * stride + k - lo < size for o in range(out) for k in range(kk))
                for size, out, kk, (lo, _) in zip(x.shape[1:3], y.shape[1:3], (kh, kw), pads)]
        self.flops += 2 * taps[0] * taps[1] * cin * cout
        return y

    def bn(self, name, x, eps, kind="bn_scale"):
        c = x.shape[-1]
        scale = self._param(name + ".scale", (c,), kind)
        bias = self._param(name + ".bias", (c,), "bn_bias")
        if self.train:
            mean = jnp.mean(x, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
        else:
            mean = self._param(name + ".mean", (c,), "bn_mean")
            var = self._param(name + ".var", (c,), "bn_var")
        return (x - mean) * (scale * lax.rsqrt(var + eps)) + bias

    def dense(self, name, x, cout):
        cin = x.shape[-1]
        w = self._param(name + ".kernel", (cin, cout), "dense", cin)
        b = self._param(name + ".bias", (cout,), "dense_bias")
        self.flops += 2 * cin * cout
        return self._q_back(jnp.dot(self._q(x), self._q(w), precision=lax.Precision.HIGHEST)) + b


def max_pool(x, window, stride, padding="VALID"):
    if not isinstance(padding, str):
        padding = ((0, 0), *padding, (0, 0))
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, window, window, 1),
                             (1, stride, stride, 1), padding)


def avg_pool_same(x, window):
    """Stride 1, zero padded, every window divided by window*window."""
    s = lax.reduce_window(x, 0.0, lax.add, (1, window, window, 1), (1, 1, 1, 1), "SAME")
    return s / float(window * window)


def normalize_uint8(x):
    """Inception's x/127.5 - 1 on raw uint8 pixels."""
    return x.astype(jnp.float32) * (1.0 / 127.5) - 1.0


def describe(forward, config, train=False):
    """(specs, forward FLOPs of one record) of ``forward(net, x, config)``."""
    net = Net(None, train=train)
    size = config["image_size"]
    jax.eval_shape(lambda x: forward(net, x, config),
                   jax.ShapeDtypeStruct((1, size, size, 3), jnp.uint8))
    return net.specs, net.flops


_INIT = {
    # kind -> (distribution, a, b): normal(mean a, std b*fan_in**-0.5) or uniform[a, b)
    "conv": ("normal", 0.0, math.sqrt(2.0)),
    "dense": ("normal", 0.0, 1.0),
    "dense_bias": ("normal", 0.0, 0.01),
    "bn_scale": ("uniform", 0.5, 1.5),
    "bn_scale_last": ("uniform", 0.1, 0.3),  # the block's last norm: keeps the residual sum tame
    "bn_bias": ("normal", 0.0, 0.1),
    "bn_mean": ("normal", 0.0, 0.1),
    "bn_var": ("uniform", 0.5, 1.5),
}


def key_of(seed):
    """A key from any whole number up to a little over 2**32."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def build_params(specs, key):
    """Every leaf from ``key``, float32: two flat draws, cut into the leaves in
    name order.  Traceable, so that a program's own init can be this."""
    order = sorted(specs.items())
    total = sum(math.prod(shape) for _, (shape, _, _) in order)
    normal = jax.random.normal(jax.random.fold_in(key, 0), (total,), jnp.float32)
    uniform = jax.random.uniform(jax.random.fold_in(key, 1), (total,), jnp.float32)
    out, at = {}, 0
    for name, (shape, kind, fan_in) in order:
        dist, a, b = _INIT[kind]
        n = math.prod(shape)
        if dist == "normal":
            leaf = a + normal[at:at + n] * (b / math.sqrt(fan_in))
        else:
            leaf = a + uniform[at:at + n] * (b - a)
        out[name] = leaf.reshape(shape)
        at += n
    return out


def make_params(specs, seed):
    """``build_params`` in one jitted call, on the default device."""
    return jax.jit(lambda key: build_params(specs, key))(key_of(seed))
