"""Custom TPU kernels (pallas) for hot ops the XLA graph path can't fuse
optimally — see /opt/skills/guides/pallas_guide.md conventions."""

from flink_tensorflow_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_decode,
)
from flink_tensorflow_tpu.ops.paged_attention import (
    dense_to_pages,
    gather_pages,
    paged_attention_decode,
    pages_per_session,
    pages_to_dense,
    scatter_pages,
)
from flink_tensorflow_tpu.ops.ssd import causal_conv1d, ssd_scan
from flink_tensorflow_tpu.ops.preprocessing import (
    central_crop,
    inception_normalize,
    mnist_normalize,
    normalize_image,
    resize_bilinear,
)

__all__ = [
    "flash_attention",
    "flash_attention_decode",
    "dense_to_pages",
    "gather_pages",
    "paged_attention_decode",
    "pages_per_session",
    "pages_to_dense",
    "scatter_pages",
    "causal_conv1d",
    "ssd_scan",
    "central_crop",
    "inception_normalize",
    "mnist_normalize",
    "normalize_image",
    "resize_bilinear",
]
