"""KV-cache blocks and the keyed-state facade that owns them.

One session's cache is a ``[L, C, H, Dh]`` K/V pair plus its valid
length.  Two residency forms exist:

- :class:`KVBlock` — host numpy, picklable: the form that lives in
  checkpoints.  A barrier snapshot converts every resident cache to
  this form (the d2h there IS the documented "cache snapshots on
  barriers" cost).
- :class:`DeviceKVBlock` — live jax arrays: the form a PREEMPTED
  session's cache keeps between eviction and re-admission when the
  serving config runs device-resident.  Moving a block out of the pool
  and back in then never touches the host.  It refuses to pickle: a
  checkpoint crossing is a host boundary, and the
  operator's snapshot hook converts first (loudly keeping the
  invariant if some future path forgets).

:class:`KVCacheState` wraps the runtime's KeyedStateStore: per-session
:class:`SessionState` values keyed by session id, so the base
``Operator.snapshot``/``rescale`` machinery checkpoints and
redistributes them by key group with zero serving-specific code.
Values are treated as IMMUTABLE — every mutation writes a fresh
``SessionState`` (``dataclasses.replace``), because the store's
snapshot is a shallow table copy pickled asynchronously.
"""

from __future__ import annotations

import dataclasses
import typing

import numpy as np

from flink_tensorflow_tpu.core.state import KeyedStateStore, StateDescriptor


class KVBlock:
    """Host-resident cache of one session: k/v ``[L, C, H, Dh]`` f32."""

    __slots__ = ("k", "v", "length")
    kind = "host"

    def __init__(self, k: np.ndarray, v: np.ndarray, length: int):
        self.k = np.asarray(k)
        self.v = np.asarray(v)
        self.length = int(length)

    def __reduce__(self):
        return (KVBlock, (self.k, self.v, self.length))

    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes

    def __repr__(self) -> str:
        return f"KVBlock(shape={tuple(self.k.shape)}, length={self.length})"


class DeviceKVBlock:
    """HBM-resident cache of one session (live jax arrays).

    Produced by preemption under ``ServingConfig.device_resident_blocks``
    — the slice copies device-to-device out of the pool, no d2h — and
    consumed by re-admission (device-to-device scatter back, no h2d).
    ``to_host()`` is the explicit materialization boundary (barrier
    snapshots call it); pickling raises.
    """

    __slots__ = ("k", "v", "length")
    kind = "device"

    def __init__(self, k, v, length: int):
        self.k = k
        self.v = v
        self.length = int(length)

    def to_host(self) -> KVBlock:
        import jax

        k, v = jax.device_get((self.k, self.v))
        return KVBlock(np.asarray(k), np.asarray(v), self.length)

    def __reduce__(self):
        raise TypeError(
            "DeviceKVBlock is device-resident and never crosses a pickle "
            "boundary — the serving operator's snapshot hook converts it "
            "to a host KVBlock first; call to_host() if you really need "
            "the bytes"
        )

    def __repr__(self) -> str:
        return f"DeviceKVBlock(shape={tuple(self.k.shape)}, length={self.length})"


#: Session lifecycle states.  ``WAITING`` covers both never-admitted and
#: preempted/restored sessions (the latter carry a KV block to resume
#: from); ``ACTIVE`` sessions own a pool slot; ``DONE`` sessions keep
#: only their generated tokens (replay dedup).
WAITING = "waiting"
ACTIVE = "active"
DONE = "done"


@dataclasses.dataclass(frozen=True)
class SessionState:
    """Everything one session needs to resume anywhere: the keyed-state
    value.  Immutable — mutations go through ``dataclasses.replace``."""

    seq: int                          # arrival order (admission fairness)
    prompt: np.ndarray                # [P] int32
    max_new: int
    eos: typing.Optional[int]
    status: str = WAITING
    generated: typing.Tuple[int, ...] = ()
    #: #tokens already emitted downstream (restore resumes emission here
    #: without double-counting inside one attempt; cross-restart sink
    #: delivery stays at-least-once like every non-transactional sink).
    emitted: int = 0
    kv: typing.Optional[typing.Union[KVBlock, DeviceKVBlock]] = None
    meta: typing.Dict[str, typing.Any] = dataclasses.field(default_factory=dict)

    def cache_length(self) -> int:
        """Valid cache positions a resume starts from (0 = fresh prefill)."""
        return self.kv.length if self.kv is not None else 0


class KVCacheState:
    """Keyed-state facade: one :class:`SessionState` per session id.

    A thin veneer over the runtime's KeyedStateStore that scopes
    ``current_key`` per call — the serving step loop touches MANY keys
    per invocation (one per active session), unlike the one-key-per-
    record shape ProcessFunction state assumes."""

    DESCRIPTOR = StateDescriptor("serving_sessions")

    def __init__(self, store: KeyedStateStore):
        self._store = store

    def get(self, key) -> typing.Optional[SessionState]:
        prev = self._store.current_key
        self._store.current_key = key
        try:
            return self._store.get(self.DESCRIPTOR)
        finally:
            self._store.current_key = prev

    def put(self, key, state: SessionState) -> None:
        prev = self._store.current_key
        self._store.current_key = key
        try:
            self._store.put(self.DESCRIPTOR, state)
        finally:
            self._store.current_key = prev

    def remove(self, key) -> None:
        prev = self._store.current_key
        self._store.current_key = key
        try:
            self._store.remove(self.DESCRIPTOR)
        finally:
            self._store.current_key = prev

    def keys(self) -> typing.List[typing.Any]:
        return list(self._store.keys(self.DESCRIPTOR.name))
