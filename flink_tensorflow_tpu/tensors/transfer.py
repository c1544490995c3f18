"""Host <-> HBM transfer for assembled batches, and the device-resident
record kind that lets chained operators skip the wire entirely.

The reference crosses the JVM->native boundary with a heap copy per tensor
per record (SURVEY.md §3.1).  Here the entire batch pytree moves in one
``jax.device_put`` call per direction, arrays are donated into the jitted
call wherever the caller permits (input buffers are dead after the call, so
XLA reuses their HBM pages for outputs — BASELINE.json:5 "donated,
HBM-resident device arrays").

Fetch semantics (honest version — the old docstring promised an async
fetch this function never had): :meth:`DeviceTransfer.fetch` calls
``jax.device_get`` and BLOCKS until the d2h transfer completes.  The
asynchrony lives one layer up, in two places:

- the model runner's dedicated **fetch thread** (functions/runner.py)
  pays that block off the subtask thread, so fetch overlaps the next
  batch's assemble/h2d — the runner's ``in_flight`` span ends exactly
  where the block returns;
- :class:`DeviceBatch` makes the fetch **lazy**: a device-resident
  result defers the d2h until the first host-only consumer forces
  :meth:`DeviceBatch.materialize`, which fetches exactly once (and, when
  recorded, leaves a ``materialize`` span at the point of the block).

Wire narrowing: ``DeviceTransfer(wire_dtype=...)`` casts float fields to
a compact dtype (bf16/f16) host-side before ``device_put``, halving the
bytes of the host->device transfer; the model runner restores the declared
dtype INSIDE its jitted call, so the upcast runs fused on device and the
numerics past the input cast are full precision.
"""

from __future__ import annotations

import os
import typing

import numpy as np

from flink_tensorflow_tpu.tensors.batching import Batch
from flink_tensorflow_tpu.tensors.serde import normalize_wire_dtype
from flink_tensorflow_tpu.tensors.value import TensorValue

_TRUTHY = ("1", "true", "on", "yes")


def env_device_resident() -> bool:
    """Whether ``FLINK_TPU_DEVICE_RESIDENT`` force-enables HBM-resident
    chained handoff without config changes."""
    return os.environ.get("FLINK_TPU_DEVICE_RESIDENT", "").lower() in _TRUTHY


def env_wire_dtype() -> typing.Optional[str]:
    """Job-wide wire dtype from ``FLINK_TPU_WIRE_DTYPE`` (f32 = off)."""
    return normalize_wire_dtype(
        os.environ.get("FLINK_TPU_WIRE_DTYPE") or None)


_SCALE_PREFIX = "__scale__"


def scale_key(name: str) -> str:
    """Companion-input key carrying a narrowed field's absmax scale
    through ``device_put`` into the jitted call (int8 h2d narrowing)."""
    return _SCALE_PREFIX + name


def is_scale_key(name: str) -> bool:
    return name.startswith(_SCALE_PREFIX)


def _narrow_np_dtype(wire: str) -> np.dtype:
    if wire == "bf16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    if wire == "f16":
        return np.dtype(np.float16)
    if wire == "int8":
        return np.dtype(np.int8)
    raise ValueError(f"unknown h2d wire dtype {wire!r}")


class DeviceTransfer:
    """Per-operator-subtask transfer helper bound to one device (or sharding).

    ``device`` may be a ``jax.Device``, a ``Sharding``, or None (jit default
    placement).  One instance per model operator subtask — created at
    ``open()`` alongside the compiled executable.  ``wire_dtype``
    ("bf16"/"f16") narrows float fields host-side before the transfer;
    the caller is responsible for restoring the declared dtype
    device-side (the model runner does it inside its jitted call).
    """

    def __init__(self, device=None, wire_dtype: typing.Optional[str] = None):
        self.device = device
        self.wire_dtype = normalize_wire_dtype(wire_dtype)
        self._narrow = (
            _narrow_np_dtype(self.wire_dtype)
            if self.wire_dtype is not None else None
        )

    def _narrow_arrays(
        self, arrays: typing.Mapping[str, np.ndarray]
    ) -> typing.Tuple[typing.Dict[str, np.ndarray], int]:
        """Cast float fields to the wire dtype; returns (arrays, saved).

        ``int8`` is an absmax quantization (PR-7 deferral, now on the
        h2d hop too): each narrowed field ships as int8 plus a scalar
        f32 scale under :func:`scale_key` — the model runner's jitted
        call multiplies the scale back in as its first (fused) op, so
        the wire pays 1/4 the bytes and the numerics past the input
        dequant are full precision of a absmax/127-quantized input.
        Use it only for activations/pixels that tolerate ~0.4% absmax
        error — never ids (same caveat as the serde codec).
        """
        narrow = self._narrow
        if narrow is None:
            return dict(arrays), 0
        quantize = self.wire_dtype == "int8"
        out: typing.Dict[str, np.ndarray] = {}
        saved = 0
        for n, a in arrays.items():
            if a.dtype.kind == "f" and a.dtype.itemsize > narrow.itemsize:
                saved += a.size * (a.dtype.itemsize - narrow.itemsize)
                if quantize:
                    absmax = float(np.max(np.abs(a))) if a.size else 0.0
                    scale = absmax / 127.0 if absmax > 0.0 else 1.0
                    q = np.clip(np.rint(a.astype(np.float32) / scale),
                                -127, 127)
                    out[n] = q.astype(np.int8)
                    out[scale_key(n)] = np.float32(scale)
                else:
                    out[n] = a.astype(narrow)
            else:
                out[n] = a
        return out, saved

    def ship(self, batch: Batch) -> typing.Tuple[typing.Dict[str, typing.Any], int, int]:
        """Transfer a batch's fields to HBM in one ``device_put``.

        Returns ``(device_arrays, h2d_bytes, wire_bytes_saved)`` —
        ``h2d_bytes`` is what actually crossed the wire (narrowed when
        ``wire_dtype`` is set), ``wire_bytes_saved`` the narrowing gain.
        """
        import jax

        arrays, saved = self._narrow_arrays(batch.arrays)
        nbytes = sum(a.nbytes for a in arrays.values())
        return jax.device_put(arrays, self.device), nbytes, saved

    def put_chunk(self, arrays: typing.Mapping[str, np.ndarray]) -> typing.Dict[str, typing.Any]:
        """Start the transfer of one chunk ``{field: [C, ...]}`` of a batch
        (never narrowed: an absmax scale is per batch).  Each field crosses
        as ``[C, bytes of a record's field]``, every record one flat run,
        which is how its rows lie in memory anyway; the jitted call gives
        the chunks their shape back as it joins them.  On a TPU the host
        re-lays every array into the device's tiles on one thread an array,
        and an innermost dimension of 3 channels makes that slow: 34 MB as
        ``u8[128, 299, 299, 3]`` take 45 ms, as ``u8[128, 268203]`` 19.5 ms,
        for a step 0.4% longer (as ONE run of bytes, 6 ms, but the device
        then re-lays it inside the step: +55%; PERF.md 6, PR 32)."""
        import jax

        return jax.device_put({n: a.reshape(a.shape[0], -1) for n, a in arrays.items()},
                              self.device)

    def ship_chunks(
        self, shipped: typing.Sequence[typing.Mapping[str, typing.Any]],
        rest: typing.Sequence[typing.Mapping[str, np.ndarray]],
    ) -> typing.Tuple[typing.Tuple[typing.Dict[str, typing.Any], ...], int, int]:
        """Transfer a batch that comes as row-ordered chunks ``[C, ...]``:
        ``shipped``, its leading chunks as :meth:`put_chunk` returned them
        while their window was still filling, are taken as they are; the
        ``rest`` cross now, side by side.

        Returns ``(device_chunks, h2d_bytes, early_bytes)``: every byte of
        the batch, and those of them that had crossed before this call.
        """
        early = sum(a.nbytes for c in shipped for a in c.values())
        late = sum(a.nbytes for c in rest for a in c.values())
        return (*shipped, *(self.put_chunk(c) for c in rest)), early + late, early

    def to_device(self, batch: Batch) -> typing.Dict[str, typing.Any]:
        """Ship all batch fields to HBM in one transfer.

        ``device_put`` on the whole pytree dispatches one transfer; None
        means jit-default placement.
        """
        return self.ship(batch)[0]

    def lengths_to_device(self, batch: Batch) -> typing.Dict[str, typing.Any]:
        import jax

        if not batch.lengths:
            return {}
        return jax.device_put(batch.lengths, self.device)

    @staticmethod
    def fetch(outputs) -> typing.Dict[str, np.ndarray]:
        """Device -> host for a pytree of outputs.  BLOCKS until the d2h
        transfer completes (``jax.device_get`` is eager) — callers that
        need overlap run this on the runner's fetch thread, and callers
        that can defer it hand out a :class:`DeviceBatch` instead.

        Fetched arrays are frozen so per-record row views taken by
        ``Batch.unbatch`` are born read-only — TensorValue then aliases
        them instead of copying (keeps the output path at 1x traffic).
        """
        import jax

        host = jax.device_get(outputs)
        out = {}
        for n, a in host.items():
            a = np.asarray(a)
            if a.flags.writeable and a.flags.owndata:
                a.setflags(write=False)
            elif a.flags.writeable:
                a = a.copy()
                a.setflags(write=False)
            out[n] = a
        return out


class DeviceBatch:
    """An HBM-resident micro-batch riding the record plane as ONE record.

    Produced by a device-resident model runner in place of per-record
    host ``TensorValue``s: ``arrays`` are live ``jax.Array``s (the
    jitted call's outputs, still on device), ``valid``/``metas`` carry
    the batch bookkeeping a later unbatch needs.  A downstream chained
    operator that declares ``accepts_device_batches`` consumes the
    arrays directly — no d2h, no h2d, the hop never touches the wire.

    The first host-only consumer (sink, keyed shuffle, remote edge, any
    plain user function) hits the **lazy materialization boundary**:
    :meth:`materialize` forces the deferred d2h exactly once, caches the
    per-record ``TensorValue``s, and (when recorded) leaves a
    ``materialize`` span at the point of the block — beside the
    ``h2d.elided``/``d2h.elided`` instants of the hops that paid none.  The runtime's ``Output``/``ChainedOutput`` call
    it automatically, so user code never sees a ``DeviceBatch`` unless
    it asked to.

    NOT serializable by design: a checkpoint or channel crossing is a
    host boundary, so the runtime materializes first (pickling raises to
    keep that invariant loud).
    """

    #: Duck-type marker the runtime layers test (cheap getattr — no
    #: import of this module on the hot path of host-only jobs).
    is_device_batch = True

    __slots__ = ("arrays", "valid", "lengths", "metas", "timestamp",
                 "_host", "_tracer", "_track")

    def __init__(self, arrays: typing.Mapping[str, typing.Any],
                 valid: np.ndarray,
                 metas: typing.Sequence[typing.Mapping[str, typing.Any]],
                 lengths: typing.Optional[typing.Mapping[str, typing.Any]] = None,
                 timestamp: typing.Optional[float] = None,
                 tracer=None, track: typing.Optional[str] = None):
        self.arrays = dict(arrays)
        self.valid = valid
        self.lengths = dict(lengths or {})
        self.metas = list(metas)
        #: Event-time timestamp shared by the batch's records (None when
        #: the producing stream was untimed).
        self.timestamp = timestamp
        self._host: typing.Optional[typing.List[TensorValue]] = None
        self._tracer = tracer
        self._track = track

    @property
    def num_records(self) -> int:
        return int(self.valid.sum())

    @property
    def padded_size(self) -> int:
        return int(self.valid.shape[0])

    @property
    def materialized(self) -> bool:
        return self._host is not None

    def device_nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in self.arrays.values())

    def materialize(self) -> typing.List[TensorValue]:
        """Force the deferred d2h (once) and return per-record values.

        This IS the host-only boundary: the fetch blocks HERE, on the
        consumer's thread — the ``materialize`` span (args
        ``deferred=true``) asserts exactly where that block lands.
        """
        if self._host is None:
            import time

            t0 = time.monotonic()
            host = DeviceTransfer.fetch(self.arrays)
            t1 = time.monotonic()
            if self._tracer is not None:
                self._tracer.span(
                    self._track, "materialize", t0, t1,
                    {"batch": self.num_records, "deferred": True})
            records: typing.List[TensorValue] = []
            for i in range(self.padded_size):
                if not self.valid[i]:
                    continue
                records.append(TensorValue(
                    {n: a[i] for n, a in host.items()},
                    self.metas[len(records)],
                ))
            self._host = records
        return self._host

    def __iter__(self):
        return iter(self.materialize())

    def __len__(self) -> int:
        return self.num_records

    def __repr__(self) -> str:
        inner = ", ".join(
            f"{k}: {tuple(a.shape)}/{np.dtype(a.dtype)}"
            for k, a in self.arrays.items()
        )
        state = "materialized" if self._host is not None else "device"
        return f"DeviceBatch({inner}; n={self.num_records}, {state})"

    def __reduce__(self):
        raise TypeError(
            "DeviceBatch is device-resident and never crosses a pickle "
            "boundary — the runtime materializes at channels/checkpoints; "
            "call materialize() if you really need host records"
        )
