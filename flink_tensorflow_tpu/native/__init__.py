"""Native runtime bindings (ctypes) with pure-Python fallbacks.

The reference's runtime rides on native code (TF C++ core via JNI,
Netty's native transports — SURVEY.md §2); this package is the TPU
framework's native layer: a C++ SPSC ring arena for zero-copy record
marshalling (native/src/spsc_ring.cpp), loaded via ctypes.  A missing
build is never an error — every consumer falls back to the Python
implementation with identical semantics (`TensorRing` chooses at
construction; force with ``native=False``) — but it is never silent:
the loader logs which ring it took and ``ring_impl()`` names it.

Build:  make -C native
"""

from flink_tensorflow_tpu.native.ring import (
    TensorRing,
    native_available,
    ring_impl,
)

__all__ = ["TensorRing", "native_available", "ring_impl"]
