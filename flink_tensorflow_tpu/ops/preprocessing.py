"""Device-side fused preprocessing ops for the inference hot path.

The reference builds its image-normalization graph programmatically and
runs it inside the TF session (SURVEY.md §2 "Examples": "image
normalization graph built programmatically"), so normalization executes
on the accelerator next to the model.  The TPU-native equivalent is a
plain jax function traced into the same jit as the model forward: XLA
fuses the cast/scale/offset into the first convolution's input, so the
"op" costs nothing extra and the host ships uint8 (4x fewer bytes per
host->device transfer than float32).

Host-side fallbacks for records that truly arrive as floats live in
tensors.coercion (``image_to_float``); everything here runs under jit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def normalize_image(x: jax.Array, *, scale: float, offset: float,
                    dtype=jnp.bfloat16) -> jax.Array:
    """Cast + affine-normalize an image batch on device.

    ``x`` is typically uint8 ``[B, H, W, C]``; the cast-to-bf16 and the
    multiply/add fuse into the consuming conv under jit, so this is the
    zero-cost place to do normalization (vs. paying 4x host->HBM bytes
    to ship pre-normalized float32).
    """
    return x.astype(dtype) * scale + offset


def inception_normalize(x: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Inception's canonical ``x/127.5 - 1`` transform (uint8 -> [-1, 1])."""
    return normalize_image(x, scale=1.0 / 127.5, offset=-1.0, dtype=dtype)


def mnist_normalize(x: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """MNIST's ``x/255`` transform (uint8 -> [0, 1])."""
    return normalize_image(x, scale=1.0 / 255.0, offset=0.0, dtype=dtype)


def central_crop(x: jax.Array, fraction: float) -> jax.Array:
    """Static central crop of an NHWC batch (shape is jit-static).

    Mirrors the crop step of the reference Inception example's input
    graph; implemented with static slicing so XLA sees fixed shapes.
    """
    if not 0.0 < fraction <= 1.0:
        raise ValueError(f"fraction must be in (0, 1], got {fraction}")
    h, w = x.shape[-3], x.shape[-2]
    # round(), not int(): binary floats put e.g. 100*0.29 an epsilon
    # below 29, and truncation would silently crop one row short.
    ch, cw = max(1, round(h * fraction)), max(1, round(w * fraction))
    top, left = (h - ch) // 2, (w - cw) // 2
    return x[..., top:top + ch, left:left + cw, :]


def resize_bilinear(x: jax.Array, size: tuple) -> jax.Array:
    """Bilinear resize of an NHWC batch to ``size=(H, W)`` (static)."""
    return jax.image.resize(
        x, x.shape[:-3] + (size[0], size[1], x.shape[-1]), method="bilinear"
    )
