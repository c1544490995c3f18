"""Host <-> HBM transfer for assembled batches.

The reference crosses the JVM->native boundary with a heap copy per tensor
per record (SURVEY.md §3.1).  Here the entire batch pytree moves in one
``jax.device_put`` call per direction, every field in its declared dtype,
and arrays are donated into the jitted call wherever the caller permits
(input buffers are dead after the call, so XLA reuses their HBM pages for
outputs — BASELINE.json:5 "donated, HBM-resident device arrays").

:meth:`DeviceTransfer.fetch` calls ``jax.device_get`` and BLOCKS until the
d2h transfer completes.  The asynchrony lives one layer up: the model
runner's dedicated **fetch thread** (functions/runner.py) pays that block
off the subtask thread, so fetch overlaps the next batch's assemble/h2d —
the runner's ``in_flight`` span ends exactly where the block returns.
"""

from __future__ import annotations

import os
import typing

import numpy as np

from flink_tensorflow_tpu.tensors.batching import Batch
from flink_tensorflow_tpu.tensors.serde import normalize_wire_dtype


def env_wire_dtype() -> typing.Optional[str]:
    """Job-wide wire dtype from ``FLINK_TPU_WIRE_DTYPE`` (f32 = off)."""
    return normalize_wire_dtype(
        os.environ.get("FLINK_TPU_WIRE_DTYPE") or None)


class DeviceTransfer:
    """Per-operator-subtask transfer helper bound to one device (or sharding).

    ``device`` may be a ``jax.Device``, a ``Sharding``, or None (jit default
    placement).  One instance per model operator subtask — created at
    ``open()`` alongside the compiled executable.
    """

    def __init__(self, device=None):
        self.device = device

    def ship(self, batch: Batch) -> typing.Tuple[typing.Dict[str, typing.Any], int]:
        """Transfer a batch's fields to HBM in one ``device_put``; returns
        ``(device_arrays, h2d_bytes)``."""
        import jax

        return (jax.device_put(batch.arrays, self.device),
                sum(a.nbytes for a in batch.arrays.values()))

    def put_chunk(self, arrays: typing.Mapping[str, np.ndarray]) -> typing.Dict[str, typing.Any]:
        """Start the transfer of one chunk ``{field: [C, ...]}`` of a batch.
        Each field crosses as ``[C, bytes of a record's field]``, every
        record one flat run, which is how its rows lie in memory anyway;
        the jitted call gives
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

    def lengths_to_device(self, batch: Batch) -> typing.Dict[str, typing.Any]:
        import jax

        if not batch.lengths:
            return {}
        return jax.device_put(batch.lengths, self.device)

    @staticmethod
    def fetch(outputs) -> typing.Dict[str, np.ndarray]:
        """Device -> host for a pytree of outputs.  BLOCKS until the d2h
        transfer completes (``jax.device_get`` is eager) — callers that
        need overlap run this on the runner's fetch thread.

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
