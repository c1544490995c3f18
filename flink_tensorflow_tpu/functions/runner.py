"""CompiledMethodRunner — the Session.run replacement.

The reference's ``ModelFunction`` binds a model method to per-record (or
per-window) ``Session.run`` calls across the JNI boundary (SURVEY.md §3.1
hot loop).  The TPU-native engine room:

- ``open()``: place params in HBM once (reference: Session owns variables
  on device).  Optionally pre-warm executables for expected buckets so the
  stream never stalls on a first-fire XLA compile.
- per batch: coerce -> assemble (pad to bucket) -> ONE host->HBM transfer
  -> ONE jitted call -> fetch -> unbatch.  ``jax.jit`` caches one
  executable per bucket shape (the compile cache of SURVEY.md §7 step 3);
  input buffers are donated so XLA reuses their HBM pages for outputs.
- dispatch is async: the jitted call returns futures, and ``run_batch``
  only blocks when fetching results — back-to-back windows overlap host
  batching with device compute.
- result fetches run on a dedicated **fetch thread** (r5): the d2h
  round trip happens in the background the moment a batch's lane work
  resolves, so the subtask thread only ever drains already-fetched
  results.  A poll-then-fetch path serializes one full device round
  trip per window AFTER readiness, and ``is_ready`` is not a completion
  guarantee on every transport, so a readiness-gated fetch may block
  anyway.  The fetch thread removes the need for readiness polling
  entirely: a blocking fetch IS the completion signal.
- assemble+h2d+launch runs on a lane pool of two workers, never on the
  subtask thread: the h2d of batch N+1 overlaps the device compute of
  batch N, and the subtask thread stays free to accept arrivals
  (``lane_wait`` is the pool's queue).  Every field crosses in its
  declared dtype.
- **chunked input** (``chunk_input``): a caller that holds a window's
  records in place while it fills — the ring path of
  ``ModelWindowFunction`` — may ship it ``chunk_rows`` records at a time
  (:meth:`CompiledMethodRunner.put_chunk`), so the link works during the
  fill and only the last chunk is outstanding when the window fires.  The
  jitted call then takes the batch as ``K`` chunks and joins them as its
  first operation.  Chosen from what is known at ``open()``; a window too
  small to be worth ``EARLY_MIN_CHUNKS`` puts of ``EARLY_CHUNK_MIN_BYTES``
  crosses whole, in one put, as it always did.
"""

from __future__ import annotations

import collections
import concurrent.futures
import functools
import math
import threading
import time
import typing

from flink_tensorflow_tpu.models.base import Model
from flink_tensorflow_tpu.tensors.batching import Batch, BucketPolicy, assemble
from flink_tensorflow_tpu.tensors.coercion import coerce
from flink_tensorflow_tpu.tensors.transfer import DeviceTransfer
from flink_tensorflow_tpu.tensors.value import TensorValue
from flink_tensorflow_tpu.tracing.flight import charged
from flink_tensorflow_tpu.utils.profiling import annotate_batch

if typing.TYPE_CHECKING:
    from flink_tensorflow_tpu.core.runtime_context import RuntimeContext

#: Chunked input: a chunk carries at least this many bytes (a put costs the
#: subtask thread 0.4 ms), a batch is cut into at most EARLY_MAX_CHUNKS of
#: them (the step's join is cheapest with few: on a TPU the batch is the lane
#: dimension, and 1024 rows in 8 chunks of 128 are whole lane tiles, where 16
#: of 64 made the step 4% slower: PERF.md 6, PR 32), and a batch that would
#: not give EARLY_MIN_CHUNKS crosses whole: with fewer, most of the transfer
#: would still come after the fire.
EARLY_CHUNK_MIN_BYTES = 16 << 20
EARLY_MIN_CHUNKS = 4
EARLY_MAX_CHUNKS = 8

#: Lane workers of a CompiledMethodRunner: two keep the h2d of one batch in
#: flight while the one before it computes.
LANES = 2


@functools.lru_cache(maxsize=64)
def _build_decode_calls(prefill_fn, decode_fn, capacity: int):
    """Jitted (prefill_into, step_full, step_exact) per (model methods,
    capacity) — cached at MODULE level so every DecodeStepRunner built
    over the same model (a restarted job, comparison arms,
    parallel subtasks) reuses the same callables and therefore jax's
    compiled executables: the 1-3s decode/prefill compiles are paid
    once per process, not once per operator open()."""
    import jax

    def prefill_into(params, tokens, lengths, slots, kc, vc):
        import jax.numpy as jnp

        out = prefill_fn(params, {"tokens": tokens, "lengths": lengths})
        t = tokens.shape[1]
        pad = capacity - t
        k_new, v_new = out["k_cache"], out["v_cache"]
        if pad:
            widths = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
            k_new = jnp.pad(k_new, widths)
            v_new = jnp.pad(v_new, widths)
        # Bucket-padding rows carry slot == S: out of range, dropped.
        kc = kc.at[slots].set(k_new, mode="drop")
        vc = vc.at[slots].set(v_new, mode="drop")
        return out["next_token"], kc, vc

    def step_full(params, tokens, lengths, mask, kc, vc):
        import jax.numpy as jnp

        out = decode_fn(params, {
            "token": tokens, "lengths": lengths,
            "k_cache": kc, "v_cache": vc,
        })
        keep = mask[:, None, None, None, None]
        return (out["next_token"],
                jnp.where(keep, out["k_cache"], kc),
                jnp.where(keep, out["v_cache"], vc))

    def step_exact(params, tokens, lengths, slots, kc, vc):
        out = decode_fn(params, {
            "token": tokens, "lengths": lengths,
            "k_cache": kc[slots], "v_cache": vc[slots],
        })
        return (out["next_token"],
                kc.at[slots].set(out["k_cache"]),
                vc.at[slots].set(out["v_cache"]))

    return (jax.jit(prefill_into, donate_argnums=(4, 5)),
            jax.jit(step_full, donate_argnums=(4, 5)),
            jax.jit(step_exact, donate_argnums=(4, 5)))


class DecodeStepRunner:
    """Autoregressive decode dispatch — CompiledMethodRunner's sibling
    for the serving plane (flink_tensorflow_tpu/serving/).

    Where CompiledMethodRunner pays one h2d + one compute + one d2h per
    micro-batch, generation threads a KV cache through EVERY step, so
    the residency rules invert:

    - the cache POOL (``[S, L, C, H, Dh]`` K/V arrays, one row per
      active-session slot) lives in HBM for the runner's whole life and
      is DONATED into each jitted step — XLA updates it in place, and
      the only h2d per decode step is the ``[S]`` int32 token/length
      vectors (bytes counted in ``step_h2d_bytes``; the serving tests'
      one-h2d-per-admitted-token guard reads exactly this);
    - greedy argmax runs INSIDE the jitted methods, so the only d2h per
      step is ``[S]`` int32 next-tokens;
    - per-session cache blocks cross the pool boundary only at
      admission (``insert_block`` — h2d iff the block is host-resident)
      and extraction (``extract_block`` — d2h iff the caller asks for
      host form; barriers do, device-resident preemption doesn't).

    Shape discipline: with ``padding_buckets`` the decode step always
    runs the FULL pool shape ``[S]`` (inactive rows masked — one
    executable, ever) and prefill shapes quantize to the admit x
    prompt-length bucket grid; without it, every distinct active count
    and prompt length compiles fresh — the churn the
    ``serving-recompile-churn`` lint flags.

    The model contributes two typed methods (models/zoo/chartransformer
    is the reference instance):

    - ``prefill``:     ``{tokens [B, T], lengths [B]}`` ->
      ``{next_token [B], k_cache [B, L, T, H, Dh], v_cache ...}``
    - ``decode_step``: ``{token [B], lengths [B], k_cache, v_cache}`` ->
      same outputs with the caches grown by one position.
    """

    def __init__(
        self,
        model: Model,
        *,
        pool_slots: int,
        capacity: int,
        padding_buckets: bool = True,
        prompt_buckets: typing.Optional[typing.Sequence[int]] = None,
        device=None,
    ):
        self.model = model
        self.pool_slots = pool_slots
        self.capacity = capacity
        self.padding_buckets = padding_buckets
        self.prompt_buckets = tuple(prompt_buckets or ())
        self.device = device
        self._prefill = model.method("prefill")
        self._decode = model.method("decode_step")
        self._params_on_device = None
        self._kc = None       # [S, L, C, H, Dh] jax arrays (lazy, first prefill)
        self._vc = None
        self._prefill_fn = None
        self._step_full_fn = None
        self._step_exact_fn = None
        self._metrics = None
        self._tracer = None
        self._roofline = None
        self._trace_track: typing.Optional[str] = None
        #: Plain counters (mirrored to the metric plane when open(ctx)
        #: wired one): the serving tests' residency guards read these.
        self.step_h2d_bytes = 0
        self.block_h2d_events = 0     # host block -> pool (admission/restore)
        self.block_d2h_events = 0     # pool -> host block (barrier/preempt)
        self.device_block_moves = 0   # pool <-> DeviceKVBlock (no host touch)

    # -- lifecycle ---------------------------------------------------------
    def open(self, ctx: typing.Optional["RuntimeContext"] = None) -> None:
        import jax

        if ctx is not None:
            if self.device is None and ctx.device is not None:
                self.device = ctx.device
            self._metrics = ctx.metrics
            self._tracer = getattr(ctx, "tracer", None)
            if self._tracer is not None:
                self._trace_track = f"{ctx.task_name}.{ctx.subtask_index}"
            plane = getattr(ctx, "roofline", None)
            if plane is not None:
                # Per-operator roofline probe: joins each measured
                # prefill/decode step against the plan's CostTable and
                # publishes roofline.* gauges on this subtask's scope.
                self._roofline = plane.probe(ctx.task_name,
                                             metrics=ctx.metrics)
        self._params_on_device = jax.device_put(self.model.params, self.device)
        self._build_calls()

    def _build_calls(self) -> None:
        (self._prefill_fn, self._step_full_fn,
         self._step_exact_fn) = _build_decode_calls(
            self._prefill.fn, self._decode.fn, self.capacity)

    def close(self) -> None:
        self._params_on_device = None
        self._kc = self._vc = None
        self._prefill_fn = self._step_full_fn = self._step_exact_fn = None

    def warmup(self, admit_buckets: typing.Sequence[int],
               prompt_buckets: typing.Sequence[int]) -> None:
        """Pre-compile every (admit x prompt-length) prefill bucket plus
        the decode step, so the first live session never pays an XLA
        compile inside its measured latency.  Warmup rows scatter to the
        out-of-range slot (dropped) and the warm decode runs fully
        masked — the pool stays clean.  Counters, metrics, and stage
        spans are suppressed (compile time must not masquerade as
        steady-state transfer cost), mirroring CompiledMethodRunner.
        Only meaningful under padding buckets — exact-shape mode churns
        by design and has nothing finite to warm."""
        import numpy as np

        if not self.padding_buckets:
            return
        metrics, self._metrics = self._metrics, None
        tracer, self._tracer = self._tracer, None
        saved = (self.step_h2d_bytes, self.block_h2d_events,
                 self.block_d2h_events, self.device_block_moves)
        t_warm = time.monotonic()
        if self._roofline is not None:
            # Warmup compiles still log compile events (trigger =
            # "warmup"), but none of the throughput accounting.
            self._roofline.begin_warmup()
        try:
            for b in admit_buckets:
                for t in prompt_buckets:
                    t = min(t, self.capacity)
                    self.prefill([np.ones((t,), np.int32)], [t],
                                 [self.pool_slots], batch_bucket=b)
            self.decode_step([0] * self.pool_slots, [0] * self.pool_slots, [])
        finally:
            if self._roofline is not None:
                self._roofline.end_warmup()
            self._metrics = metrics
            self._tracer = tracer
            (self.step_h2d_bytes, self.block_h2d_events,
             self.block_d2h_events, self.device_block_moves) = saved
            if tracer is not None:
                tracer.span(self._trace_track, "jit_warmup_compile",
                            t_warm, time.monotonic(),
                            args={"admit_buckets": list(admit_buckets),
                                  "prompt_buckets": list(prompt_buckets)})

    @property
    def pool_built(self) -> bool:
        return self._kc is not None

    def _ensure_pool(self, k_like) -> None:
        """Allocate the pool on first use, shaped after one session's
        cache ``[L, C, H, Dh]`` (shape knowledge lives in the model)."""
        import jax
        import jax.numpy as jnp

        if self._kc is not None:
            return
        # k_like: [B, L, T, H, Dh] for any T <= capacity — the pool is
        # always allocated at FULL capacity (one decode shape, ever).
        _, layers, _, heads, hd = k_like.shape
        shape = (self.pool_slots, layers, self.capacity, heads, hd)
        # Two DISTINCT buffers: the jitted step donates both pools, and
        # aliased zeros would be one buffer donated twice.
        self._kc = jax.device_put(jnp.zeros(shape, k_like.dtype), self.device)
        self._vc = jax.device_put(jnp.zeros(shape, k_like.dtype), self.device)

    # -- dispatch ----------------------------------------------------------
    def _bucket_len(self, n: int) -> int:
        if not self.padding_buckets:
            return max(1, n)
        for b in self.prompt_buckets:
            if n <= b:
                return b
        return self.capacity

    def prefill(self, prompts: typing.Sequence, lengths: typing.Sequence[int],
                slots: typing.Sequence[int],
                *, batch_bucket: typing.Optional[int] = None):
        """Prefill newly admitted sessions into their pool slots.

        ``prompts``: per-session int32 token arrays; ``slots``: their
        pool rows.  Returns the per-session first generated token (host
        int32, in order).  Shapes quantize to (batch_bucket x
        prompt-length bucket) under ``padding_buckets``."""
        import jax
        import numpy as np

        n = len(prompts)
        b = batch_bucket or n
        t = self._bucket_len(max(int(x) for x in lengths))
        tokens = np.zeros((b, t), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
        lens = np.zeros((b,), np.int32)
        lens[:n] = np.asarray(lengths, np.int32)
        slot_arr = np.full((b,), self.pool_slots, np.int32)  # pad rows drop
        slot_arr[:n] = np.asarray(slots, np.int32)
        t0 = time.monotonic()
        if self._kc is None:
            # Bootstrap: run the raw prefill once to learn the cache
            # shape, then scatter through the jitted path like any
            # other call (one extra compile, first admission only).
            out = jax.jit(self._prefill.fn)(
                self._params_on_device,
                {"tokens": jax.device_put(tokens, self.device),
                 "lengths": jax.device_put(lens, self.device)})
            self._ensure_pool(out["k_cache"])
        next_tok, self._kc, self._vc = self._prefill_fn(
            self._params_on_device,
            jax.device_put(tokens, self.device),
            jax.device_put(lens, self.device),
            jax.device_put(slot_arr, self.device),
            self._kc, self._vc,
        )
        host = np.asarray(jax.device_get(next_tok))[:n]
        t1 = time.monotonic()
        self.step_h2d_bytes += tokens.nbytes + lens.nbytes + slot_arr.nbytes
        if self._tracer is not None:
            self._tracer.span(self._trace_track, "decode.prefill", t0, t1,
                              args={"batch": n, "bucket": [b, t]})
        if self._metrics is not None:
            self._metrics.histogram("prefill_s").record(t1 - t0)
            self._metrics.counter("prefill_batches").inc()
        if self._roofline is not None:
            self._roofline.observe(
                "prefill", t1 - t0, signature=f"prefill:{b}x{t}",
                h2d_bytes=tokens.nbytes + lens.nbytes + slot_arr.nbytes,
                d2h_bytes=b * 4)
        return host

    def decode_step(self, tokens_by_slot, lengths_by_slot, active_slots):
        """One decode step over the pool.

        ``tokens_by_slot``/``lengths_by_slot``: ``[S]`` int32 host
        arrays (inactive rows 0); ``active_slots``: the slots whose
        results matter.  Returns ``[S]`` next tokens (host int32).
        """
        import jax
        import numpy as np

        if self._kc is None:
            raise RuntimeError("decode_step before any prefill")
        t0 = time.monotonic()
        h2d_before = self.step_h2d_bytes
        if self.padding_buckets:
            mask = np.zeros((self.pool_slots,), bool)
            mask[list(active_slots)] = True
            args = (jax.device_put(np.asarray(tokens_by_slot, np.int32), self.device),
                    jax.device_put(np.asarray(lengths_by_slot, np.int32), self.device),
                    jax.device_put(mask, self.device))
            self.step_h2d_bytes += (len(tokens_by_slot) * 4
                                    + len(lengths_by_slot) * 4
                                    + mask.nbytes)
            next_tok, self._kc, self._vc = self._step_full_fn(
                self._params_on_device, *args, self._kc, self._vc)
            out = np.asarray(jax.device_get(next_tok))
        else:
            slots = np.asarray(sorted(active_slots), np.int32)
            toks = np.asarray([tokens_by_slot[s] for s in slots], np.int32)
            lens = np.asarray([lengths_by_slot[s] for s in slots], np.int32)
            self.step_h2d_bytes += toks.nbytes + lens.nbytes + slots.nbytes
            next_tok, self._kc, self._vc = self._step_exact_fn(
                self._params_on_device,
                jax.device_put(toks, self.device),
                jax.device_put(lens, self.device),
                jax.device_put(slots, self.device),
                self._kc, self._vc)
            got = np.asarray(jax.device_get(next_tok))
            out = np.zeros((self.pool_slots,), np.int32)
            out[slots] = got
        t1 = time.monotonic()
        if self._tracer is not None:
            self._tracer.span(self._trace_track, "decode.step", t0, t1,
                              args={"active": len(active_slots)})
        if self._metrics is not None:
            self._metrics.histogram("decode_step_s").record(t1 - t0)
            self._metrics.counter("decode_steps").inc()
        if self._roofline is not None:
            # Padded mode always presents the one [S] signature; exact
            # mode churns by design — each active-set size is its own
            # (unpriced, unpredicted) signature.
            sig = (f"decode:{self.pool_slots}" if self.padding_buckets
                   else f"decode:{len(active_slots)}")
            self._roofline.observe(
                "decode_step", t1 - t0, signature=sig,
                h2d_bytes=self.step_h2d_bytes - h2d_before,
                d2h_bytes=int(out.nbytes))
        return out

    # -- block movement (keyed-state residency boundary) -------------------
    def extract_block(self, slot: int, length: int, *, host: bool):
        """One session's cache out of the pool.

        ``host=True`` forces the d2h (barrier snapshots — the cache
        must pickle); ``host=False`` returns live device slices (a
        device-resident preemption: the block parks in keyed state
        without touching the wire).  Returns ``(k, v)``."""
        import jax

        k, v = self._kc[slot], self._vc[slot]
        if not host:
            self.device_block_moves += 1
            if self._tracer is not None:
                self._tracer.instant(self._trace_track, "cache.resident",
                                     args={"slot": slot, "length": length})
            return k, v
        t0 = time.monotonic()
        k, v = jax.device_get((k, v))
        t1 = time.monotonic()
        self.block_d2h_events += 1
        if self._tracer is not None:
            self._tracer.span(self._trace_track, "cache.d2h", t0, t1,
                              args={"slot": slot, "length": length,
                                    "bytes": int(k.nbytes + v.nbytes)})
        if self._roofline is not None:
            # Tier-move transfer: priced against the plan's cache_move
            # entries WITHOUT minting a compile event — block moves are
            # data motion, not executables (the PR-17 "non-runner h2d
            # attribution" deferral).
            self._roofline.observe_transfer(
                "cache_move", t1 - t0, signature="cache:block",
                d2h_bytes=int(k.nbytes + v.nbytes))
        return k, v

    def insert_block(self, slot: int, k, v) -> None:
        """One session's cache back into the pool.  Host arrays pay the
        h2d here (admission after restore / host-mode preemption);
        device arrays scatter device-side — zero host traffic."""
        import numpy as np

        if self._kc is None:
            import jax.numpy as jnp

            self._ensure_pool(jnp.asarray(k)[None])
        is_host = isinstance(k, np.ndarray)
        t0 = time.monotonic()
        self._kc = self._kc.at[slot].set(k)
        self._vc = self._vc.at[slot].set(v)
        t1 = time.monotonic()
        if is_host:
            self.block_h2d_events += 1
            if self._tracer is not None:
                self._tracer.span(self._trace_track, "cache.h2d", t0, t1,
                                  args={"slot": slot,
                                        "bytes": int(k.nbytes + v.nbytes)})
            if self._roofline is not None:
                self._roofline.observe_transfer(
                    "cache_move", t1 - t0, signature="cache:block",
                    h2d_bytes=int(k.nbytes + v.nbytes))
        else:
            self.device_block_moves += 1
            if self._tracer is not None:
                self._tracer.instant(self._trace_track, "cache.resident",
                                     args={"slot": slot})


@functools.lru_cache(maxsize=64)
def _build_paged_calls(prefill_fn, decode_fn, capacity: int,
                       page_tokens: int, num_pages: int):
    """Jitted (paged_prefill_into, paged_step, copy_page) per (model
    methods, capacity, page geometry) — module-level cache for the same
    reason as :func:`_build_decode_calls`: restarted jobs, comparison
    arms, and parallel subtasks all reuse the compiled
    executables.

    The paged step is gather -> dense kernel -> scatter
    (ops/paged_attention.py): the decode/prefill MATH is byte-for-byte
    the model's existing methods over a materialized dense view, which
    is what makes paged output bit-identical to the dense pool on the
    same schedule.  Sentinel table entries (``num_pages``) clamp on
    gather (garbage masked by lengths) and drop on scatter, so inactive
    rows, bucket-padding rows, and prefix-SHARED pages (sentinel in the
    prefill scatter table — the first writer's bytes stay authoritative)
    all ride the one padded signature with no mask argument."""
    import jax

    from flink_tensorflow_tpu.ops.paged_attention import (
        gather_pages,
        scatter_pages,
    )

    def prefill_into(params, tokens, lengths, tables, kp, vp):
        import jax.numpy as jnp

        out = prefill_fn(params, {"tokens": tokens, "lengths": lengths})
        t = tokens.shape[1]
        pad = capacity - t
        k_new, v_new = out["k_cache"], out["v_cache"]
        if pad:
            widths = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
            k_new = jnp.pad(k_new, widths)
            v_new = jnp.pad(v_new, widths)
        kp = scatter_pages(kp, tables, k_new, page_tokens)
        vp = scatter_pages(vp, tables, v_new, page_tokens)
        return out["next_token"], kp, vp

    def step(params, tokens, lengths, tables, kp, vp):
        kc = gather_pages(kp, tables)
        vc = gather_pages(vp, tables)
        out = decode_fn(params, {
            "token": tokens, "lengths": lengths,
            "k_cache": kc, "v_cache": vc,
        })
        kp = scatter_pages(kp, tables, out["k_cache"], page_tokens)
        vp = scatter_pages(vp, tables, out["v_cache"], page_tokens)
        return out["next_token"], kp, vp

    def copy_page(src, dst, kp, vp):
        # The copy-on-write split: duplicate one page device-side
        # before a write into shared bytes.  Scalar int32 src/dst trace
        # once — one executable for every split.
        return kp.at[dst].set(kp[src]), vp.at[dst].set(vp[src])

    return (jax.jit(prefill_into, donate_argnums=(4, 5)),
            jax.jit(step, donate_argnums=(4, 5)),
            jax.jit(copy_page, donate_argnums=(2, 3)))


class PagedDecodeStepRunner(DecodeStepRunner):
    """Paged variant of :class:`DecodeStepRunner`: the HBM pool is
    ``num_pages`` fixed-size pages ``[P, L, page_tokens, H, Dh]`` and
    every active slot carries a block table instead of owning a
    contiguous ``[L, C, H, Dh]`` row.

    What changes at the dispatch boundary: the per-step int32 h2d grows
    the ``[S, C/page_tokens]`` block tables alongside the token/length
    vectors (the tables ARE host state — they re-serialize every step,
    which is what keeps them out of the donation cycle), the pool is
    still donated through the jitted step, and admission needs FREE
    PAGES, not a slot-shaped hole.  The host-side policy objects
    (:class:`~flink_tensorflow_tpu.serving.paged.PagedKVPool` free
    list/refcounts, the radix prefix index) live on this runner; the
    serving operator drives them through the block-movement methods
    below (park/attach for hot preemption, insert/extract for the
    warm/cold tiers, ``ensure_writable`` for the copy-on-write check
    before each step's write position).

    Paged mode requires ``padding_buckets`` — the whole point is ONE
    decode signature over the padded pool; exact-shape churn would
    recompile per active-set size with the table width riding along."""

    def __init__(
        self,
        model: Model,
        *,
        pool_slots: int,
        capacity: int,
        page_tokens: int = 16,
        num_pages: typing.Optional[int] = None,
        prefix_sharing: bool = True,
        padding_buckets: bool = True,
        prompt_buckets: typing.Optional[typing.Sequence[int]] = None,
        device=None,
    ):
        from flink_tensorflow_tpu.ops.paged_attention import (
            pages_per_session,
        )
        from flink_tensorflow_tpu.serving.paged import (
            PagedKVPool,
            RadixPrefixIndex,
        )

        if not padding_buckets:
            raise ValueError(
                "paged KV requires padding_buckets — the paged step has "
                "exactly one [S, C/page_tokens] signature by design")
        super().__init__(model, pool_slots=pool_slots, capacity=capacity,
                         padding_buckets=padding_buckets,
                         prompt_buckets=prompt_buckets, device=device)
        self.page_tokens = page_tokens
        self.table_width = pages_per_session(capacity, page_tokens)
        self.num_pages = (num_pages if num_pages is not None
                          else pool_slots * self.table_width)
        if self.num_pages < self.table_width:
            raise ValueError(
                f"hbm_pages {self.num_pages} cannot seat even one "
                f"full-capacity session ({self.table_width} pages) — "
                "grow the pool or shrink capacity")
        self.pool = PagedKVPool(self.num_pages, page_tokens)
        self.index = RadixPrefixIndex(self.pool) if prefix_sharing else None
        #: Active slot -> block table (logical page i at position i).
        self._tables: typing.Dict[int, typing.List[int]] = {}
        self._paged_prefill_fn = None
        self._paged_step_fn = None
        self._copy_page_fn = None

    def _build_calls(self) -> None:
        (self._paged_prefill_fn, self._paged_step_fn,
         self._copy_page_fn) = _build_paged_calls(
            self._prefill.fn, self._decode.fn, self.capacity,
            self.page_tokens, self.num_pages)

    def close(self) -> None:
        super().close()
        self._paged_prefill_fn = self._paged_step_fn = None
        self._copy_page_fn = None
        self._tables.clear()

    # -- pool geometry -----------------------------------------------------
    def _ensure_pool(self, k_like) -> None:
        import jax
        import jax.numpy as jnp

        if self._kc is not None:
            return
        _, layers, _, heads, hd = k_like.shape
        shape = (self.num_pages, layers, self.page_tokens, heads, hd)
        # Two DISTINCT buffers, same donation reasoning as the dense pool.
        self._kc = jax.device_put(jnp.zeros(shape, k_like.dtype), self.device)
        self._vc = jax.device_put(jnp.zeros(shape, k_like.dtype), self.device)

    def page_nbytes(self) -> typing.Optional[int]:
        """K+V bytes of ONE page (None before the pool is built)."""
        if self._kc is None:
            return None
        per = 1
        for d in self._kc.shape[1:]:
            per *= d
        return 2 * per * self._kc.dtype.itemsize

    def _alloc(self, n: int) -> typing.Optional[typing.List[int]]:
        """Allocate ``n`` pages, evicting index-only pages LRU under
        pressure; None when the pool is genuinely out (the caller's
        tier machinery demotes parked sessions and retries)."""
        if n <= 0:
            return []
        got = self.pool.alloc(n)
        if got is None and self.index is not None:
            self.index.evict_until(n)
            got = self.pool.alloc(n)
        return got

    def free_pages_evictable(self) -> int:
        """Free pages plus what index eviction could free — the
        admission gate's optimistic bound."""
        free = self.pool.free_pages
        if self.index is not None:
            free += sum(1 for _, _, node in self.index._leaves()
                        if self.pool.refs[node.page] == 1)
        return free

    # -- dispatch ----------------------------------------------------------
    def prefill(self, prompts: typing.Sequence, lengths: typing.Sequence[int],
                slots: typing.Sequence[int],
                *, batch_bucket: typing.Optional[int] = None):
        """Paged prefill: per session, adopt prefix pages from the
        radix index (refcount bump, zero compute), allocate the rest,
        and scatter the freshly computed K/V ONLY into owned pages (the
        scatter table carries the sentinel where pages are shared —
        the first writer's bytes stay authoritative, which is the
        byte-identity argument for prefix sharing)."""
        import jax
        import numpy as np

        n = len(prompts)
        b = batch_bucket or n
        t = self._bucket_len(max(int(x) for x in lengths))
        tokens = np.zeros((b, t), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
        lens = np.zeros((b,), np.int32)
        lens[:n] = np.asarray(lengths, np.int32)
        # Scatter tables: sentinel everywhere a page is NOT owned by the
        # prefilling session (pad rows, beyond-allocation, adopted).
        scatter = np.full((b, self.table_width), self.num_pages, np.int32)
        adopted_pages = 0
        for i, (p, ln, slot) in enumerate(zip(prompts, lengths, slots)):
            slot = int(slot)
            if slot >= self.pool_slots:
                continue  # warmup pad row: all-sentinel, pure compile
            adopted: typing.List[int] = []
            if self.index is not None:
                full, partial = self.index.match(p)
                adopted = full + ([partial] if partial is not None else [])
            own_n = self.pool.pages_for(int(ln)) - len(adopted)
            own = self._alloc(own_n)
            if own is None:
                self.pool.release(adopted)
                raise RuntimeError(
                    f"paged KV pool exhausted at prefill: need {own_n} "
                    f"pages, {self.pool.free_pages} free — the admission "
                    "gate should have held this session back")
            table = adopted + own
            self._tables[slot] = table
            adopted_pages += len(adopted)
            for j in range(len(adopted), len(table)):
                scatter[i, j] = table[j]
        t0 = time.monotonic()
        if self._kc is None:
            # Bootstrap: one raw prefill to learn the cache shape (same
            # one-extra-compile cost as the dense runner's first call).
            out = jax.jit(self._prefill.fn)(
                self._params_on_device,
                {"tokens": jax.device_put(tokens, self.device),
                 "lengths": jax.device_put(lens, self.device)})
            self._ensure_pool(out["k_cache"])
        next_tok, self._kc, self._vc = self._paged_prefill_fn(
            self._params_on_device,
            jax.device_put(tokens, self.device),
            jax.device_put(lens, self.device),
            jax.device_put(scatter, self.device),
            self._kc, self._vc,
        )
        host = np.asarray(jax.device_get(next_tok))[:n]
        t1 = time.monotonic()
        h2d = tokens.nbytes + lens.nbytes + scatter.nbytes
        self.step_h2d_bytes += h2d
        if self._tracer is not None:
            self._tracer.span(self._trace_track, "decode.prefill", t0, t1,
                              args={"batch": n, "bucket": [b, t],
                                    "pages_shared": adopted_pages})
        if self._metrics is not None:
            self._metrics.histogram("prefill_s").record(t1 - t0)
            self._metrics.counter("prefill_batches").inc()
        if self._roofline is not None:
            self._roofline.observe(
                "prefill", t1 - t0, signature=f"prefill:{b}x{t}",
                h2d_bytes=h2d, d2h_bytes=b * 4)
        return host

    def decode_step(self, tokens_by_slot, lengths_by_slot, active_slots):
        """One paged decode step: the block tables ride the per-step
        int32 h2d alongside the token/length vectors; rows without a
        table (inactive, warmup) go all-sentinel and no-op through the
        gather/scatter."""
        import jax
        import numpy as np

        if self._kc is None:
            raise RuntimeError("decode_step before any prefill")
        t0 = time.monotonic()
        s = self.pool_slots
        tables = np.full((s, self.table_width), self.num_pages, np.int32)
        for slot, table in self._tables.items():
            tables[slot, :len(table)] = table
        toks = np.asarray(tokens_by_slot, np.int32)
        lens = np.asarray(lengths_by_slot, np.int32)
        h2d = toks.nbytes + lens.nbytes + tables.nbytes
        self.step_h2d_bytes += h2d
        next_tok, self._kc, self._vc = self._paged_step_fn(
            self._params_on_device,
            jax.device_put(toks, self.device),
            jax.device_put(lens, self.device),
            jax.device_put(tables, self.device),
            self._kc, self._vc)
        out = np.asarray(jax.device_get(next_tok))
        t1 = time.monotonic()
        if self._tracer is not None:
            self._tracer.span(self._trace_track, "decode.step", t0, t1,
                              args={"active": len(active_slots)})
        if self._metrics is not None:
            self._metrics.histogram("decode_step_s").record(t1 - t0)
            self._metrics.counter("decode_steps").inc()
        if self._roofline is not None:
            self._roofline.observe(
                "decode_step", t1 - t0, signature=f"decode:{s}",
                h2d_bytes=h2d, d2h_bytes=int(out.nbytes))
        return out

    # -- copy-on-write / growth -------------------------------------------
    def ensure_writable(self, slot: int, length: int) -> bool:
        """Guarantee the page holding write position ``length`` exists
        and is exclusively owned before the step runs.  Allocates the
        next page at a page boundary; splits a shared page
        (copy-on-write) when the write would land in bytes the prefix
        index or another session still references.  False = the pool is
        out of pages even after index eviction — the operator's tier
        machinery must free pressure and retry."""
        table = self._tables[slot]
        li = length // self.page_tokens
        while len(table) <= li:
            got = self._alloc(1)
            if got is None:
                return False
            table.extend(got)
        pid = table[li]
        if self.pool.is_shared(pid):
            got = self._alloc(1)
            if got is None:
                return False
            self._copy_page(pid, got[0])
            self.pool.decref(pid)
            self.pool.cow_splits += 1
            table[li] = got[0]
        return True

    def _copy_page(self, src: int, dst: int) -> None:
        import numpy as np

        self._kc, self._vc = self._copy_page_fn(
            np.int32(src), np.int32(dst), self._kc, self._vc)
        if self._tracer is not None:
            self._tracer.instant(self._trace_track, "cache.cow",
                                 args={"src": src, "dst": dst})

    # -- block movement (tier-ladder boundary) -----------------------------
    def park(self, slot: int, length: int):
        """Hot preemption: the session's pages STAY in HBM behind a
        :class:`~flink_tensorflow_tpu.serving.paged.PagedKVHandle`;
        only the block table leaves the step batch.  Zero traffic —
        the paged analogue of the dense device-resident preemption."""
        from flink_tensorflow_tpu.serving.paged import PagedKVHandle

        table = self._tables.pop(slot)
        self.device_block_moves += 1
        if self._tracer is not None:
            self._tracer.instant(self._trace_track, "cache.resident",
                                 args={"slot": slot, "length": length,
                                       "pages": len(table)})
        return PagedKVHandle(table, length)

    def attach(self, slot: int, handle) -> None:
        """Re-admission of a hot-parked session: re-attach the table."""
        self._tables[slot] = list(handle.pages)
        self.device_block_moves += 1
        if self._tracer is not None:
            self._tracer.instant(self._trace_track, "cache.resident",
                                 args={"slot": slot, "pages":
                                       len(handle.pages)})

    def _gather_host(self, pages: typing.Sequence[int], length: int):
        """Pages -> dense host ``[L, C, H, Dh]`` K/V (zero-fill beyond
        the allocated pages; positions past ``length`` are masked by
        every consumer).  Returns ``(k, v, wire_bytes)`` — only the
        gathered pages cross the wire; the capacity pad is minted
        host-side and must not count as transfer traffic."""
        import jax
        import numpy as np

        from flink_tensorflow_tpu.ops.paged_attention import pages_to_dense

        ids = np.asarray(pages, np.int32)
        k_pages, v_pages = jax.device_get(
            (self._kc[ids], self._vc[ids]))
        wire_bytes = int(k_pages.nbytes + v_pages.nbytes)
        k = pages_to_dense(np.asarray(k_pages)[None])[0]
        v = pages_to_dense(np.asarray(v_pages)[None])[0]
        layers, got, heads, hd = k.shape
        if got < self.capacity:
            pad = np.zeros((layers, self.capacity - got, heads, hd), k.dtype)
            k = np.concatenate([k, pad], axis=1)
            v = np.concatenate([v, pad], axis=1)
        return k, v, wire_bytes

    def snapshot_block(self, slot: int, length: int):
        """Barrier copy of an ACTIVE session: dense host K/V, pages
        untouched (the pool stays authoritative — same contract as the
        dense ``extract_block(host=True)`` at a barrier)."""
        t0 = time.monotonic()
        k, v, wire = self._gather_host(self._tables[slot], length)
        t1 = time.monotonic()
        self.block_d2h_events += 1
        n = len(self._tables[slot])
        if self._tracer is not None:
            self._tracer.span(self._trace_track, "cache.d2h", t0, t1,
                              args={"slot": slot, "length": length,
                                    "pages": n, "bytes": wire})
        if self._roofline is not None:
            self._roofline.observe_transfer(
                "cache_move", t1 - t0, signature=f"cache:pages:{n}",
                d2h_bytes=wire)
        return k, v

    def extract_host(self, slot: int, length: int):
        """Demotion of an ACTIVE session (pressure preemption to the
        warm tier): dense host K/V out, pages released."""
        table = self._tables.pop(slot)
        t0 = time.monotonic()
        k, v, wire = self._gather_host(table, length)
        t1 = time.monotonic()
        self.pool.release(table)
        self.block_d2h_events += 1
        if self._tracer is not None:
            self._tracer.span(self._trace_track, "cache.d2h", t0, t1,
                              args={"slot": slot, "length": length,
                                    "pages": len(table), "bytes": wire})
        if self._roofline is not None:
            self._roofline.observe_transfer(
                "cache_move", t1 - t0,
                signature=f"cache:pages:{len(table)}",
                d2h_bytes=wire)
        return k, v

    def demote_handle(self, handle):
        """Hot -> warm: a PARKED session's pages gather d2h into a host
        :class:`~flink_tensorflow_tpu.serving.kv_cache.KVBlock` and
        free."""
        from flink_tensorflow_tpu.serving.kv_cache import KVBlock

        t0 = time.monotonic()
        k, v, wire = self._gather_host(handle.pages, handle.length)
        t1 = time.monotonic()
        self.pool.release(handle.pages)
        self.block_d2h_events += 1
        if self._tracer is not None:
            self._tracer.span(self._trace_track, "cache.d2h", t0, t1,
                              args={"length": handle.length,
                                    "pages": len(handle.pages),
                                    "bytes": wire})
        if self._roofline is not None:
            self._roofline.observe_transfer(
                "cache_move", t1 - t0,
                signature=f"cache:pages:{len(handle.pages)}",
                d2h_bytes=wire)
        return KVBlock(k, v, handle.length)

    def insert_block(self, slot: int, k, v,
                     length: typing.Optional[int] = None) -> None:
        """Warm/cold revival: a host block's exact bytes back into
        freshly allocated pages (the admission gate reserved them).
        ``length`` bounds the pages allocated — a full-capacity scatter
        would waste pages on masked positions."""
        import jax
        import numpy as np

        from flink_tensorflow_tpu.ops.paged_attention import dense_to_pages

        if length is None:
            length = k.shape[1]
        if self._kc is None:
            import jax.numpy as jnp

            self._ensure_pool(jnp.asarray(k)[None])
        n = self.pool.pages_for(int(length))
        got = self._alloc(n)
        if got is None:
            raise RuntimeError(
                f"paged KV pool exhausted at re-admission: need {n} "
                f"pages, {self.pool.free_pages} free — the admission "
                "gate should have held this session back")
        self._tables[slot] = got
        ids = np.asarray(got, np.int32)
        k_pages = dense_to_pages(np.asarray(k)[None], self.page_tokens)[0][:n]
        v_pages = dense_to_pages(np.asarray(v)[None], self.page_tokens)[0][:n]
        t0 = time.monotonic()
        self._kc = self._kc.at[ids].set(jax.device_put(k_pages, self.device))
        self._vc = self._vc.at[ids].set(jax.device_put(v_pages, self.device))
        t1 = time.monotonic()
        self.block_h2d_events += 1
        if self._tracer is not None:
            self._tracer.span(self._trace_track, "cache.h2d", t0, t1,
                              args={"slot": slot, "pages": n,
                                    "bytes": int(k_pages.nbytes
                                                 + v_pages.nbytes)})
        if self._roofline is not None:
            self._roofline.observe_transfer(
                "cache_move", t1 - t0, signature=f"cache:pages:{n}",
                h2d_bytes=int(k_pages.nbytes + v_pages.nbytes))

    def release_finished(self, slot: int, cached_tokens,
                         length: int) -> None:
        """A finished session leaves the pool: its FULL pages publish to
        the prefix index (keyed by the token sequence that produced
        them — future sessions sharing the prefix adopt instead of
        recompute), everything else frees."""
        table = self._tables.pop(slot)
        if self.index is not None:
            self.index.publish(cached_tokens, table)
        self.pool.release(table)

    # -- legacy interface guards ------------------------------------------
    def extract_block(self, slot: int, length: int, *, host: bool):
        """The dense runner's extraction split maps onto the paged
        world as snapshot (host copy, pages keep) — the only dense call
        site that reaches a paged runner is the barrier hook."""
        if not host:
            raise RuntimeError(
                "paged preemption parks pages (park()/attach()); "
                "device-resident extract_block is a dense-pool concept")
        return self.snapshot_block(slot, length)


def _place_params(params, device):
    """``(params on device, {"bytes", "resident_bytes"})``.  A leaf that
    already lives on ``device`` alone is taken as it is — the same buffer:
    no copy, no cast, no round trip through the host — and counted under
    ``resident_bytes``; the rest are transferred, and waited for so that the
    caller's span is the transfer and not its enqueue."""
    import jax

    seen = {"bytes": 0, "resident_bytes": 0}

    def place(leaf):
        nbytes = int(getattr(leaf, "nbytes", 0))
        seen["bytes"] += nbytes
        if isinstance(leaf, jax.Array) and (device is None or leaf.devices() == {device}):
            seen["resident_bytes"] += nbytes
            return leaf
        return jax.device_put(leaf, device)

    return jax.block_until_ready(jax.tree.map(place, params)), seen


def _real_tokens(batch: Batch, record_shape) -> int:
    """Positions of the field ``tokens`` that belong to real records: the
    true lengths where the field is dynamic, never batch or length padding
    (``record_shape``: the schema's, read where it is static)."""
    lengths = batch.lengths.get("tokens")
    if lengths is not None:
        return int(lengths[batch.valid].sum())
    # A dynamic field is always batched with its lengths.
    assert None not in record_shape, record_shape
    return batch.num_records * math.prod(record_shape)


def _flat(batches) -> typing.List[TensorValue]:
    """``[(seq, results)]`` as one list of results."""
    return [r for _, results in batches for r in results]


class _FetchError:
    """Completed-queue marker for a batch whose lane work or fetch
    failed; the exception re-raises on the collecting thread."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class CompiledMethodRunner:
    """Executes one model method on one device, bucketed and compiled."""

    def __init__(
        self,
        model: Model,
        method_name: str = "serve",
        *,
        policy: typing.Optional[BucketPolicy] = None,
        device=None,
        donate_inputs: bool = False,
        output_names: typing.Optional[typing.Sequence[str]] = None,
    ):
        self.model = model
        self.method = model.method(method_name)
        self.policy = policy or BucketPolicy()
        self.device = device
        self.donate_inputs = donate_inputs
        #: The lane pool (LANES workers, built at open): assemble+h2d+launch
        #: of every batch, so the h2d of batch N+1 overlaps the compute of
        #: batch N and the subtask thread never blocks in the transfer.
        self._pool: typing.Optional[concurrent.futures.ThreadPoolExecutor] = None
        #: Subset of method outputs to return; selection happens INSIDE the
        #: jitted fn so XLA dead-code-eliminates unused heads and the
        #: device->host fetch only moves what the job consumes.
        self.output_names = tuple(output_names) if output_names is not None else None
        self._params_on_device = None
        #: Bytes of the parameter tree this runner holds on its device
        #: (the gauge ``param_bytes``); set at :meth:`open`.
        self.param_bytes = 0
        #: Real positions of the input field ``tokens`` (where the method
        #: has one) are counted a batch: never padding.
        self._counts_tokens = "tokens" in self.method.input_schema.names
        #: Outputs of the method that are counts made on the device
        #: (ModelMethod.count_names): fetched with every batch whatever
        #: ``output_names`` selects, added into counters, never emitted.
        self._count_names = tuple(self.method.count_names)
        self._jit_fn = None
        self._transfer: typing.Optional[DeviceTransfer] = None
        #: Rows of one input chunk where batches cross the link in chunks
        #: (:meth:`chunk_input`); None: a batch is one put.
        self.chunk_rows: typing.Optional[int] = None
        self._metrics = None
        #: In-flight dispatched batches: the lane futures of
        #: :meth:`_launch_batch`.  Appended by the dispatching thread,
        #: consumed (FIFO) by the fetch thread; guarded by ``_lock``.
        self._pending: collections.deque = collections.deque()
        #: Dispatch timestamps of in-flight batches (same order as
        #: ``_pending``) — lets callers age the oldest batch without
        #: touching lane futures.
        self._pending_t0: collections.deque = collections.deque()
        #: Batches the fetch thread has fully fetched+unbatched, waiting
        #: for the subtask thread to collect: ``(results, on_done, seq,
        #: t_ready)`` or a :class:`_FetchError`.  ``on_done`` (ring-slot
        #: release) runs at COLLECTION, on the subtask thread — the
        #: TensorRing is SPSC and claims happen there, so releases must too.
        self._completed: collections.deque = collections.deque()
        self._lock = threading.Lock()
        #: Signals the fetch thread that ``_pending`` gained work.
        self._work_cv = threading.Condition(self._lock)
        #: Signals collectors that ``_completed`` gained results.
        self._done_cv = threading.Condition(self._lock)
        self._fetcher: typing.Optional[threading.Thread] = None
        self._fetch_stop = False
        #: Optional zero-arg callback fired (from the fetch thread) each
        #: time a batch's results land in ``_completed`` — wired to the
        #: subtask gate's ``wake()`` so emission doesn't wait out the
        #: poll interval.
        self.on_results_ready: typing.Optional[typing.Callable[[], None]] = None
        #: Number of the newest dispatched batch: ``args["seq"]`` of every
        #: span of that batch, across the subtask, lane and fetch threads.
        self._batch_seq = 0
        #: Newest batch collected on the subtask thread (the ``seq`` of
        #: the ``collect_wait`` span that ends with it).
        self.collected_seq = 0
        #: Seconds the collecting thread has spent blocked in
        #: :meth:`collect_ready` (the ``collect_wait`` spans' sum).
        self.collect_wait_total_s = 0.0
        #: EWMA of dispatch-call -> results-fetched seconds per batch.
        #: Fed to latency-budget triggers (AdaptiveLatencyTrigger
        #: reserves this much of the budget for service).
        self.service_ewma_s: typing.Optional[float] = None
        #: Window-level span hook + track (from ctx at open): per-batch
        #: spans lane_wait / enqueue / in_flight / unbatch /
        #: handoff_wait / collect_wait (tracing/flight.py lists them).
        #: None = flight ring and tracer both off (no-op path).
        self._spans = None
        self._trace_track: typing.Optional[str] = None
        #: Roofline probe (metrics/roofline.py) when the executor wired
        #: a plane through ctx.roofline: each fetched batch's compute
        #: time joins against the plan's static cost entries.
        self._roofline = None

    # -- lifecycle ---------------------------------------------------------
    def open(self, ctx: typing.Optional["RuntimeContext"] = None) -> None:
        import jax

        device = self.device
        if device is None and ctx is not None and ctx.device is not None:
            device = ctx.device
        self.device = device
        self._transfer = DeviceTransfer(device)
        # Params to HBM once — the Session-owns-variables analogue.
        t_params = time.monotonic()
        self._params_on_device, placed = _place_params(self.model.params, device)
        # What this runner holds on the device, and how much of it was
        # there already (taken by reference: a tree of ten gigabytes
        # cannot be copied beside itself).
        self.param_bytes = placed["bytes"]
        spans = getattr(ctx, "spans", None)
        if spans is not None:
            # Track name computed only on the recorded path — bare test
            # contexts carry metrics but no task identity.
            self._spans = spans
            self._trace_track = f"{ctx.task_name}.{ctx.subtask_index}"
            spans.span(self._trace_track, "params_to_device", t_params,
                       time.monotonic(), placed)

        method = self.method
        select = self.output_names
        if select is not None:
            select = (*select, *(n for n in self._count_names if n not in select))
        schema = method.input_schema
        # Fields that arrive in another dtype than the schema declares are
        # cast to it as the FIRST op of the jitted call (XLA fuses it); an
        # input already in its declared dtype traces to no op at all.
        restore = {n: schema[n].dtype for n in schema.names}

        def widen(inputs):
            if isinstance(inputs, tuple):
                # A batch that crossed in chunks (chunk_input), every
                # record a flat run (DeviceTransfer.put_chunk): shaped and
                # joined here, beside the convert, so the model sees one
                # ``[B, ...]`` operand.
                import jax.numpy as jnp

                inputs = {k: jnp.concatenate(
                    [c[k].reshape(-1, *schema[k].shape) for c in inputs], axis=0)
                    for k in inputs[0]}
            return {k: v.astype(restore[k]) if k in restore and v.dtype != restore[k] else v
                    for k, v in inputs.items()}

        def prune(outputs):
            if select is None:
                return outputs
            missing = set(select) - set(outputs)
            if missing:
                raise KeyError(f"method {method.name!r} has no outputs {missing}")
            return {k: outputs[k] for k in select}

        if method.needs_lengths:
            def call(params, inputs, lengths):
                return prune(method.fn(params, widen(inputs), lengths))
        else:
            def call(params, inputs):
                return prune(method.fn(params, widen(inputs)))
        # Inference outputs (logits/labels) never alias input image/token
        # buffers, so donation buys nothing here and XLA warns per bucket;
        # opt in only for methods whose outputs can reuse input pages.
        donate = (1,) if self.donate_inputs else ()
        # Pin execution to the subtask's device; params already live there.
        self._jit_fn = jax.jit(call, donate_argnums=donate)
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=LANES,
                thread_name_prefix=f"{self.model.name}-dispatch",
            )
        if self._fetcher is None:
            self._fetch_stop = False
            self._fetcher = threading.Thread(
                target=self._fetch_loop,
                name=f"{self.model.name}-fetch",
                daemon=True,
            )
            self._fetcher.start()
        if ctx is not None:
            self._metrics = ctx.metrics
            if self._metrics is not None:
                self._metrics.gauge("param_bytes", lambda: self.param_bytes)
            plane = getattr(ctx, "roofline", None)
            if plane is not None:
                self._roofline = plane.probe(ctx.task_name,
                                             metrics=ctx.metrics)

    def chunk_input(self, arena_slots: int) -> typing.Optional[int]:
        """Decide, once, whether this runner's batches cross the link in
        chunks, and return the rows of a chunk (``chunk_rows``) or None.

        For a caller that keeps a filling window's records in an arena of
        ``arena_slots`` rows and can ship it as it fills.  A chunk is a
        divisor of the fixed batch that also divides the arena (so that no
        aligned chunk straddles the arena's end), of EARLY_CHUNK_MIN_BYTES
        or more, and there are EARLY_MIN_CHUNKS to EARLY_MAX_CHUNKS of them
        (the most that fit): 1024 records of 268 KB go in 8 chunks of 128,
        34 MB a put.  Anything smaller keeps the batch whole.  An arena
        holds records of one static shape, so the schema is static here.
        Call before :meth:`warmup`, which then compiles the chunked
        signature."""
        fixed = self.policy.fixed_batch
        if fixed is None:
            return None
        row_bytes = sum(math.prod(spec.shape) * spec.dtype.itemsize
                        for _, spec in self.method.input_schema)
        for k in range(EARLY_MAX_CHUNKS, EARLY_MIN_CHUNKS - 1, -1):
            rows = fixed // k
            if (fixed % k == 0 and arena_slots % rows == 0
                    and rows * row_bytes >= EARLY_CHUNK_MIN_BYTES):
                self.chunk_rows = rows
                break
        return self.chunk_rows

    def put_chunk(self, arrays: typing.Mapping[str, typing.Any]):
        """Start the transfer of one chunk (``{field: [chunk_rows, ...]}``)
        of a batch that has not been dispatched yet; what it returns goes
        back to :meth:`dispatch_batch` in ``chunks``.  Asynchronous, like
        every ``device_put``: the caller keeps the rows alive and unchanged
        until the batch's ``on_done``."""
        return self._transfer.put_chunk(arrays)

    def warmup(self, batch_sizes: typing.Iterable[int], length_bucket: int = 128) -> None:
        """Pre-compile executables for the given batch buckets (open-time,
        so the first live window doesn't pay the 20-40s XLA compile)."""
        import numpy as np

        batch_sizes = tuple(batch_sizes)
        schema = self.method.input_schema
        shapes = schema.resolve_dynamic(length_bucket)
        # Warmup batches pay the XLA compile inside the dispatch interval;
        # keep them out of the steady-state metrics (dispatch_s would
        # otherwise report compile time as wire-transfer time) AND out of
        # the service-time EWMA (a compile-contaminated estimate would
        # make the latency-budget trigger reserve seconds it never needs).
        metrics, self._metrics = self._metrics, None
        spans, self._spans = self._spans, None
        t_warm = time.monotonic()
        if self._roofline is not None:
            # Compile events still log (trigger = "warmup"); throughput
            # accounting is suppressed like the metrics above.
            self._roofline.begin_warmup()
        try:
            for b in batch_sizes:
                fields = {n: np.zeros(shapes[n], schema[n].dtype) for n in schema.names}
                self.run_batch([TensorValue(fields)] * b)
        finally:
            if self._roofline is not None:
                self._roofline.end_warmup()
            self._metrics = metrics
            self._spans = spans
            self.service_ewma_s = None
            if spans is not None:
                # One span for the whole warmup (per-batch spans are
                # suppressed above for the same reason as the metrics:
                # compile time must not masquerade as steady-state
                # enqueue/in_flight cost).
                spans.span(self._trace_track, "jit_warmup_compile",
                           t_warm, time.monotonic(),
                           {"batches": list(batch_sizes)})

    def close(self) -> None:
        # Drain dispatched work through the fetch thread before dropping
        # it: fetch completion is a stronger barrier than
        # block_until_ready (the executable can no longer be reading
        # input buffers that alias the ring arena — CPU-backend
        # device_put is zero-copy and the caller frees the arena right
        # after close()), and the deferred ring releases must run here,
        # on the consumer thread.  Errors are irrelevant during teardown.
        deadline = time.monotonic() + 60.0
        while True:
            entries: typing.List[typing.Any] = []
            with self._lock:
                while self._completed:
                    entries.append(self._completed.popleft())
                if not entries:
                    fetching = (self._pending
                                and self._fetcher is not None
                                and self._fetcher.is_alive())
                    if fetching and time.monotonic() < deadline:
                        self._done_cv.wait(timeout=0.5)
                        continue
            for e in entries:
                try:
                    self._consume(e)
                except Exception:  # noqa: BLE001 - cancellation teardown
                    pass
            if not entries:
                break
        with self._lock:
            self._fetch_stop = True
            self._pending.clear()
            self._pending_t0.clear()
            self._completed.clear()
            self._work_cv.notify_all()
        if self._fetcher is not None:
            self._fetcher.join(timeout=10.0)
            self._fetcher = None
        pool, self._pool = self._pool, None
        if pool is not None:  # closed before it was opened
            pool.shutdown(wait=True, cancel_futures=True)
        self._params_on_device = None
        self._jit_fn = None

    # -- execution ---------------------------------------------------------
    def dispatch(self, records: typing.Sequence[typing.Any]) -> None:
        """Assemble + transfer + launch one micro-batch WITHOUT blocking.

        jax dispatch is async: the jitted call returns future-backed
        arrays immediately, so the device crunches this batch while the
        host assembles the next one.  The whole assemble+transfer+launch
        runs on a lane thread.  Results are collected in dispatch order by
        :meth:`collect_ready` / :meth:`flush`.
        """
        if self._jit_fn is None:
            raise RuntimeError("runner not opened")
        t0, seq = self._next_seq()
        self._enqueue(self._pool.submit(self._dispatch_work, list(records), t0, seq), t0)

    def dispatch_batch(self, batch: Batch, *,
                       assemble_s: typing.Optional[float] = None,
                       on_done: typing.Optional[typing.Callable[[], None]] = None,
                       chunks: typing.Optional[typing.Sequence[
                           typing.Mapping[str, typing.Any]]] = None,
                       shipped: typing.Sequence[typing.Any] = ()) -> None:
        """Transfer + launch a pre-assembled :class:`Batch` (zero-copy ring
        path: ``batch.arrays`` are views onto the ring arena).

        With ``chunk_rows`` set the caller may hand the batch over in row
        order as ``shipped``, what :meth:`put_chunk` returned for its leading
        chunks, and ``chunks``, views of the rest (``batch.arrays`` is then
        unused).

        ``on_done`` fires when the batch's results are COLLECTED on the
        subtask thread — by then the fetch completed, so the arena slots
        are provably no longer read by the executable (completion order
        == dispatch order, so ring releases stay FIFO, and claims and
        releases stay on the single SPSC consumer thread).  Releasing
        earlier would let the producer overwrite slots that a
        CPU-backend ``device_put`` aliases zero-copy.
        """
        if self._jit_fn is None:
            raise RuntimeError("runner not opened")
        t0, seq = self._next_seq()
        self._enqueue(self._pool.submit(
            self._launch_batch, batch, t0, seq, assemble_s, on_done, chunks, shipped), t0)

    def _next_seq(self) -> typing.Tuple[float, int]:
        with self._lock:
            self._batch_seq += 1
            return time.monotonic(), self._batch_seq

    def _enqueue(self, item, t0: float) -> None:
        with self._lock:
            self._pending.append(item)
            self._pending_t0.append(t0)
            self._work_cv.notify()

    def _dispatch_work(self, records: typing.Sequence[typing.Any], t0: float, seq: int):
        """Assemble + transfer + launch; returns (batch, output futures, timings)."""
        tvs = [
            r if isinstance(r, TensorValue) else coerce(r, self.method.input_schema)
            for r in records
        ]
        t_a = time.monotonic()
        batch = assemble(tvs, self.method.input_schema, self.policy)
        return self._launch_batch(batch, t0, seq, time.monotonic() - t_a, None)

    def _launch_batch(self, batch: Batch, t0: float, seq: int,
                      assemble_s: typing.Optional[float], on_done, chunks=None,
                      shipped=()):
        """Transfer + launch; returns (batch, output futures, timings, on_done)."""
        import jax

        rows = self.chunk_rows
        if chunks is None and rows is not None and batch.padded_size == self.policy.fixed_batch:
            # An assembled batch where batches cross in chunks: the same
            # puts and the same executable, none of them early.
            chunks = [{n: a[i:i + rows] for n, a in batch.arrays.items()}
                      for i in range(0, batch.padded_size, rows)]
        spans = self._spans
        account = spans.account() if spans is not None else None
        with annotate_batch(f"{self.model.name}.{self.method.name}", seq):
            if account is not None:
                charge = account.read()
            t_b = time.monotonic()
            if chunks is not None:
                inputs, h2d_bytes, early_bytes = self._transfer.ship_chunks(shipped, chunks)
            else:
                inputs, h2d_bytes = self._transfer.ship(batch)
                early_bytes = 0
            if self.method.needs_lengths:
                lengths = self._transfer.lengths_to_device(batch)
                outputs = self._jit_fn(self._params_on_device, inputs, lengths)
            else:
                outputs = self._jit_fn(self._params_on_device, inputs)
            # Start the d2h result copy the moment compute finishes,
            # overlapping it with the queueing/fetch of earlier batches —
            # the r4 decomposition showed the copy serialized as a full
            # transport round trip AFTER readiness.  Best-effort: a
            # backend without the hook just pays the copy inside fetch.
            for leaf in jax.tree.leaves(outputs):
                if hasattr(leaf, "copy_to_host_async"):
                    try:
                        leaf.copy_to_host_async()
                    except Exception:  # noqa: BLE001 - optional fast path
                        break
            t_c = time.monotonic()
        timings = {
            "t0": t0,
            "seq": seq,
            "assemble_s": assemble_s,
            # ``device_put`` is asynchronous: this is the enqueue of the
            # transfer and the launch, not the transfer.
            "dispatch_s": t_c - t_b,
            "h2d_bytes": h2d_bytes,
            # Those of them that crossed before the dispatch (put_chunk).
            "h2d_early_bytes": early_bytes,
            "tokens": (_real_tokens(batch, self.method.input_schema["tokens"].shape)
                       if self._counts_tokens else None),
            # Span boundaries: t0 -> t_lane_start is lane-pool queueing
            # (and assembly on the list path), t_lane_start ->
            # t_dispatched the enqueue of transfer and launch.
            "t_lane_start": t_b,
            "t_dispatched": t_c,
        }
        if account is not None:
            # What this lane thread was charged between the two stamps:
            # the ``enqueue`` span's cpu_s / runq_s.
            timings["enqueue_charge"] = charged({}, charge, account.read())
        return batch, outputs, timings, on_done

    # -- background fetch ---------------------------------------------------
    def _fetch_loop(self) -> None:
        """Fetch-thread body: resolve the oldest in-flight batch, fetch
        its results (the blocking d2h round trip), run the bookkeeping,
        and hand ``(results, on_done)`` to the completed queue.  FIFO by
        construction — one thread, oldest first — so result order and
        ring-release order both match dispatch order."""
        while True:
            with self._lock:
                while not self._pending and not self._fetch_stop:
                    self._work_cv.wait()
                if not self._pending:
                    return  # stop requested and queue drained
                item = self._pending[0]
            try:
                entry = self._process_item(item)
            except BaseException as exc:  # noqa: BLE001 - re-raised on collect
                entry = _FetchError(exc)
            with self._lock:
                # Teardown may have cleared the queues mid-fetch; the
                # guards keep this thread alive to observe the stop flag
                # (an unguarded popleft would die on the empty deque).
                if self._pending:
                    self._pending.popleft()
                if self._pending_t0:
                    self._pending_t0.popleft()
                if not self._fetch_stop:
                    self._completed.append(entry)
                self._done_cv.notify_all()
            cb = self.on_results_ready
            if cb is not None:
                try:
                    cb()
                except Exception:  # noqa: BLE001 - wakeup is best-effort
                    pass

    def _process_item(self, future: concurrent.futures.Future):
        item = future.result()  # re-raises lane-thread failures here
        # Stamped AFTER the lane future resolves: the fetch thread can
        # reach this batch while its lane is still enqueueing, so the
        # stamp never precedes t_dispatched.
        spans = self._spans
        account = spans.account() if spans is not None else None
        reached = account.read() if account is not None else None
        t_fetch_start = time.monotonic()
        batch, outputs, timings, on_done = item
        host = DeviceTransfer.fetch(outputs)  # blocks on this batch only
        t_done = time.monotonic()
        fetched = account.read() if account is not None else None
        timings["counts"] = self._take_counts(host, batch.valid)
        results = batch.unbatch(host)
        t_unbatched = time.monotonic()
        dt = t_done - timings["t0"]
        # Per-batch service time (dispatch call -> results on host): the
        # latency-budget trigger reserves this out of its budget.
        self.service_ewma_s = (
            dt if self.service_ewma_s is None
            else 0.75 * self.service_ewma_s + 0.25 * dt
        )
        if spans is not None:
            self._batch_spans(timings, t_fetch_start, t_done, len(results),
                              charged({}, reached, fetched, "fetch_"))
            spans.span(self._trace_track, "unbatch", t_done, t_unbatched, charged(
                {"seq": timings["seq"], "records": len(results)},
                fetched, account.read()))
        if self._metrics is not None:
            self._metrics.meter("records").mark(len(results))
            self._metrics.histogram("batch_latency_s").record(dt)
            self._metrics.histogram("record_latency_s").record(dt / max(1, len(results)))
            self._metrics.timer("unbatch_s").update(t_unbatched - t_done)
            if timings["assemble_s"] is not None:
                # The list path's stacking copy.  Ring views were never
                # assembled (None) and record nothing:
                # their per-record cost is the ``fill`` span's.
                self._metrics.histogram("assemble_s").record(timings["assemble_s"])
            self._metrics.histogram("dispatch_s").record(timings["dispatch_s"])
            self._metrics.counter("h2d_bytes").inc(timings["h2d_bytes"])
            self._metrics.counter("h2d_early_bytes").inc(timings["h2d_early_bytes"])
            self._metrics.counter("batches").inc()
            self._count(timings)
            self._metrics.counter("padded_records").inc(batch.padded_size - batch.num_records)
        if self._roofline is not None:
            # Busy time = the compute span (launch -> fetch reached);
            # the padded batch size is the jit signature the cost table
            # keyed its entries on.
            self._roofline.observe(
                self.method.name, t_fetch_start - timings["t_dispatched"],
                signature=f"b{batch.padded_size}",
                h2d_bytes=timings["h2d_bytes"])
        return results, on_done, timings["seq"], time.monotonic()

    def _batch_spans(self, timings, t_fetch_start: float, t_done: float,
                     n: int, fetch_charge: dict) -> None:
        """One batch's spans off the subtask thread, written by the fetch
        thread from the stamps the lane left: the boundaries t0 ->
        t_lane_start -> t_dispatched -> t_done tile the batch's service
        time.  ``fetch_charge``: what the fetch thread was charged
        from ``t_fetch_start`` to ``t_done`` (``fetch_cpu_s`` /
        ``fetch_runq_s``: ``in_flight`` starts on another thread, so they
        are not the whole span's)."""
        spans, track, seq = self._spans, self._trace_track, timings["seq"]
        # ``tokens``: the batch's real positions, where the method takes tokens.
        tokens = {} if timings.get("tokens") is None else {"tokens": timings["tokens"]}
        # On the list path the lane assembles before it launches:
        # ``assemble_s`` is a part of this span (None on the ring path).
        spans.span(track, "lane_wait", timings["t0"],
                   timings["t_lane_start"],
                   {"seq": seq, "batch": n,
                    "assemble_s": timings["assemble_s"]})
        spans.span(track, "enqueue", timings["t_lane_start"],
                   timings["t_dispatched"],
                   {"seq": seq, "bytes": timings["h2d_bytes"],
                    "early_bytes": timings["h2d_early_bytes"], "batch": n, **tokens,
                    **timings.get("enqueue_charge", {})})
        # Launched .. results on the host.  Where the fetch thread stood
        # when it reached the batch is an accident of its schedule, so it
        # is a number here and no longer a cut between two spans.
        spans.span(track, "in_flight", timings["t_dispatched"], t_done,
                   {"seq": seq, "batch": n, "fetch_reached_s":
                    round(t_fetch_start - timings["t_dispatched"], 6), **tokens,
                    **timings["counts"], **fetch_charge})

    def _take_counts(self, outputs: dict, valid) -> typing.Dict[str, int]:
        """Takes the method's count outputs out of ``outputs`` (which then
        holds answers only) and returns them as numbers: a ``[B]`` count
        summed over the real records, a scalar as it is."""
        import numpy as np

        counts = {}
        for name in self._count_names:
            value = np.asarray(outputs.pop(name))
            counts[name] = int(value[valid].sum() if value.ndim else value)
        return counts

    def _count(self, timings) -> None:
        """One batch's real tokens and device-made counts into the registry."""
        if timings.get("tokens") is not None:
            self._metrics.counter("tokens").inc(timings["tokens"])
        for name, value in timings["counts"].items():
            self._metrics.counter(name).inc(value)

    def _consume(self, entry) -> typing.Tuple[int, typing.List[TensorValue]]:
        """Collect one completed entry on the calling (subtask) thread:
        re-raise fetch-thread failures, run the deferred ring release.
        Returns the batch's ``(seq, results)``."""
        if isinstance(entry, _FetchError):
            raise entry.exc
        results, on_done, seq, t_ready = entry
        now = time.monotonic()
        self.collected_seq = seq
        if self._metrics is not None:
            self._metrics.timer("handoff_wait_s").update(now - t_ready)
        if self._spans is not None:
            self._spans.span(self._trace_track, "handoff_wait", t_ready, now,
                             {"seq": seq})
        if on_done is not None:
            on_done()
        return seq, results

    def has_completed(self) -> bool:
        """True when fetched results are waiting to be collected."""
        return bool(self._completed)

    def in_flight(self) -> int:
        """Batches dispatched and not yet fetched (what a caller's pipeline
        depth bounds)."""
        with self._lock:
            return len(self._pending)

    @property
    def dispatched_seq(self) -> int:
        """``seq`` of the newest dispatched batch (0 before the first): the
        next dispatch gets ``dispatched_seq + 1``."""
        with self._lock:
            return self._batch_seq

    def collect_ready(self, max_in_flight: int = 1) -> typing.List[TensorValue]:
        """Drain completed batches until <= ``max_in_flight`` remain in
        flight (dispatched but not yet fetched), blocking as needed."""
        return _flat(self._collect_ready(max_in_flight))

    def _collect_ready(self, max_in_flight: int) -> typing.List[tuple]:
        """:meth:`collect_ready`, batch by batch: ``[(seq, results)]``."""
        max_in_flight = max(0, max_in_flight)
        out: typing.List[tuple] = []
        t_blocked = None  # start of the blocking stretch under way
        account = self._spans.account() if self._spans is not None else None
        charge = None  # the thread's account as read at t_blocked
        while True:
            entries: typing.List[typing.Any] = []
            with self._lock:
                while self._completed:
                    entries.append(self._completed.popleft())
                done = len(self._pending) <= max_in_flight
                if not entries and not done:
                    if t_blocked is None:
                        if account is not None:
                            charge = account.read()
                        t_blocked = time.monotonic()
                    self._done_cv.wait(timeout=0.2)
                    if (self._fetcher is None or not self._fetcher.is_alive()) \
                            and self._pending and not self._completed:
                        raise RuntimeError(
                            "fetch thread died with batches in flight")
                    continue
                in_flight = len(self._pending)
            now = time.monotonic() if t_blocked is not None else 0.0
            spent = charged({}, charge, account.read()) if charge is not None else {}
            out.extend(self._consume(e) for e in entries)
            if t_blocked is not None:
                self._note_collect_wait(t_blocked, now, in_flight, spent)
                t_blocked = charge = None
            if done:
                return out

    def _note_collect_wait(self, t_blocked: float, now: float,
                           in_flight: int, spent: dict) -> None:
        """One blocking stretch of :meth:`collect_ready` has ended with
        the results of batch ``collected_seq``; ``spent`` is what the
        thread was charged in it."""
        self.collect_wait_total_s += now - t_blocked
        if self._metrics is not None:
            self._metrics.timer("collect_wait_s").update(now - t_blocked)
        if self._spans is not None:
            self._spans.span(self._trace_track, "collect_wait", t_blocked, now,
                             {"seq": self.collected_seq, "in_flight": in_flight,
                              **spent})

    def collect_batches(self, max_in_flight: typing.Optional[int] = None
                        ) -> typing.List[tuple]:
        """Collected batches as ``[(seq, results)]`` in dispatch order:
        every batch the fetch thread has already completed, never blocking
        on in-flight work (the subtask thread emits results the moment they
        land, instead of parking in a full flush for the whole device round
        trip), then, with ``max_in_flight``, blocking until at most that
        many are in flight (0 is a flush).  For a caller that emits a batch
        at a time under the batch's ``seq``."""
        out: typing.List[tuple] = []
        while True:
            with self._lock:
                if not self._completed:
                    break
                entry = self._completed.popleft()
            out.append(self._consume(entry))
        if max_in_flight is not None:
            out.extend(self._collect_ready(max_in_flight))
        return out

    def oldest_pending_age_s(self, now: typing.Optional[float] = None) -> typing.Optional[float]:
        """Seconds since the oldest in-flight batch was dispatched, or
        None when nothing is pending (stall-detection hook)."""
        with self._lock:
            if not self._pending_t0:
                return None
            t0 = self._pending_t0[0]
        return (now if now is not None else time.monotonic()) - t0

    def flush(self) -> typing.List[TensorValue]:
        """Block for every in-flight batch (end of input / pre-snapshot)."""
        return self.collect_ready(0)

    def run_batch(self, records: typing.Sequence[typing.Any]) -> typing.List[TensorValue]:
        """Synchronous micro-batch: dispatch + wait (single-record map and
        tests; the windowed path pipelines via dispatch/collect_ready)."""
        self.dispatch(records)
        return self.flush()
