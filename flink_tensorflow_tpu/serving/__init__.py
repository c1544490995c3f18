"""Streaming LLM serving plane — continuous batching over keyed sessions.

The "millions of users, heavy traffic" workload the north star asks for
(ROADMAP): generation requests arrive as a KEYED stream (key = session
id), responses stream back token by token, and the KV cache lives in
keyed operator state — so it snapshots on barriers, restores after
failover mid-generation, and rescales by key group exactly like any
other keyed state.  The pieces:

- :mod:`records` — ``GenerateRequest`` in, ``TokenEvent`` out.
- :mod:`kv_cache` — ``KVBlock``/``DeviceKVBlock`` (one session's cache,
  host- or HBM-resident) and ``KVCacheState`` (the keyed-state facade).
- :mod:`scheduler` — ``ServingConfig`` + ``TokenBudgetScheduler``
  (vLLM-style admit/evict/preempt per decode step under a token budget).
- :mod:`operator` — ``ContinuousBatchingOperator`` (the stateful
  decode-step loop) and :func:`continuous_batching` (the DataStream
  entry point).
- :mod:`baseline` — ``FixedWindowGenerateFunction``, the fixed
  count-window comparison arm.
- :mod:`paged` — ``PagedKVPool`` (page-granular HBM cache economy with
  per-session block tables) and ``RadixPrefixIndex`` (sessions sharing
  a prompt prefix share pages, copy-on-write at divergence).
- :mod:`tiering` — ``SessionTierManager``, the HBM -> host -> disk
  residency ladder (hot parked pages, warm host blocks, cold spill
  files revived byte-identically).

The decode hot path runs through
:class:`~flink_tensorflow_tpu.functions.runner.DecodeStepRunner`: the
cache pool stays HBM-resident across steps (h2d per step = the new
token ids only), ``flash_attention_decode`` computes the single-query
step, and ``flash_attention``'s causal pallas grid computes prefill.
"""

from flink_tensorflow_tpu.serving.baseline import FixedWindowGenerateFunction
from flink_tensorflow_tpu.serving.kv_cache import (
    DeviceKVBlock,
    KVBlock,
    KVCacheState,
    SessionState,
)
from flink_tensorflow_tpu.serving.operator import (
    ContinuousBatchingOperator,
    continuous_batching,
)
from flink_tensorflow_tpu.serving.paged import (
    PagedKVHandle,
    PagedKVPool,
    RadixPrefixIndex,
)
from flink_tensorflow_tpu.serving.records import GenerateRequest, TokenEvent
from flink_tensorflow_tpu.serving.scheduler import (
    ServingConfig,
    TokenBudgetScheduler,
)
from flink_tensorflow_tpu.serving.tiering import (
    SessionTierManager,
    SpilledKVBlock,
)

__all__ = [
    "ContinuousBatchingOperator",
    "DeviceKVBlock",
    "FixedWindowGenerateFunction",
    "GenerateRequest",
    "KVBlock",
    "KVCacheState",
    "PagedKVHandle",
    "PagedKVPool",
    "RadixPrefixIndex",
    "ServingConfig",
    "SessionState",
    "SessionTierManager",
    "SpilledKVBlock",
    "TokenBudgetScheduler",
    "TokenEvent",
    "continuous_batching",
]
