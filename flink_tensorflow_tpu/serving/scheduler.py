"""Token-budget continuous-batching scheduler (vLLM-style).

Per decode step the scheduler decides WHO computes: finished sessions
freed their slots last step, waiting sessions admit in arrival order
while slots, ``max_active_seqs``, and the token budget allow, and when
the active set's cache growth overruns the budget the NEWEST active
session preempts back to the head of the waiting queue (its cache
follows it through keyed state, so nothing recomputes on re-admission).
Oldest-first admission + newest-first preemption means the scheduler
never livelocks: the oldest session always keeps its slot and finishes.

Pure bookkeeping — no jax, no arrays — so the policy unit-tests in
microseconds and the operator stays a thin driver around it.
"""

from __future__ import annotations

import collections
import dataclasses
import typing


def _pow2_buckets(cap: int) -> typing.Tuple[int, ...]:
    out = []
    b = 8
    while b < cap:
        out.append(b)
        b *= 2
    out.append(cap)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Knobs of the serving plane (the README documents each).

    ``capacity`` bounds prompt + generated tokens per session (the KV
    pool's padded length — one jit shape, ever).  ``padding_buckets``
    off is the recompile-churn footgun the ``serving-recompile-churn``
    lint warns about: every distinct active-set size and prompt length
    then compiles a fresh decode/prefill executable.
    """

    max_active_seqs: int = 8
    token_budget: int = 512
    capacity: int = 64
    #: Prefill shape ladders (batch x prompt-length), used only when
    #: ``padding_buckets`` is on.  ``None`` = powers of two up to the
    #: bound.
    prompt_buckets: typing.Optional[typing.Tuple[int, ...]] = None
    admit_buckets: typing.Optional[typing.Tuple[int, ...]] = None
    padding_buckets: bool = True
    #: Preempted sessions keep their cache HBM-resident (DeviceKVBlock:
    #: slice out / scatter back, zero host traffic).  Off = preemption
    #: pays a d2h and re-admission an h2d per block.
    device_resident_blocks: bool = True
    #: Pre-compile every prefill bucket + the decode step at open(), so
    #: no live session pays an XLA compile inside its latency (tests
    #: keep it off for speed).
    warmup_compile: bool = False
    #: Admission hysteresis: with a deep backlog, hold admissions until
    #: this many slots are free so waiting prefills batch into ONE
    #: dispatch instead of one per freed slot (dispatch overhead is the
    #: per-step floor at small model sizes).  Never delays when the
    #: active set is empty or the backlog is shallower than the
    #: threshold, so light-load time-to-first-token is untouched.
    admit_hysteresis: int = 1
    #: Paged KV economy (serving/paged.py + serving/tiering.py): the
    #: cache pool becomes ``hbm_pages`` fixed-size pages of
    #: ``page_tokens`` positions with a per-session block table —
    #: admission needs free PAGES, not a contiguous slot — plus a
    #: radix-tree prefix index (sessions sharing a prompt prefix share
    #: pages, copy-on-write at divergence) and the HBM->host->disk
    #: residency ladder.  Off (the default) keeps the dense
    #: ``[S, L, C, H, Dh]`` pool exactly as before.
    paged_kv: bool = False
    page_tokens: int = 16
    #: HBM page budget.  ``None`` sizes the pool to the dense
    #: equivalent (``max_active_seqs * capacity / page_tokens``); the
    #: oversubscription benches size it far SMALLER than the live
    #: session population and let tiering absorb the difference.
    hbm_pages: typing.Optional[int] = None
    prefix_sharing: bool = True
    #: The residency ladder's watermark sweep: parked (preempted-hot)
    #: sessions demote to host blocks when pool occupancy crosses the
    #: high watermark, draining to the low one; the warm rung spills to
    #: ``spill_dir`` past ``host_cache_sessions``.  ``tiering=False``
    #: keeps only pressure-forced demotion (an allocation that cannot
    #: be satisfied any other way) — the ``kv-pool-pressure`` SLO rule
    #: is how that misconfiguration surfaces.
    tiering: bool = True
    tier_high_watermark: float = 0.90
    tier_low_watermark: float = 0.70
    host_cache_sessions: int = 64
    #: Cold rung directory; ``None`` disables disk spill (warm blocks
    #: then accumulate on the host without bound).
    spill_dir: typing.Optional[str] = None

    def resolved_hbm_pages(self) -> int:
        if self.hbm_pages is not None:
            return self.hbm_pages
        return self.max_active_seqs * (self.capacity // self.page_tokens)

    def page_partition(self, key_groups: int) -> typing.Tuple[int, int]:
        """``(pages_per_group, remainder)`` when the HBM page pool is
        dealt out along ``key_groups`` key groups.  A zero remainder
        means a p→p′ rescale hands whole key-group page sets between
        subtasks (pages move, sessions don't re-prefill); a nonzero one
        is the ``statecheck-page-keygroup`` WARN."""
        pages = self.resolved_hbm_pages()
        return pages // key_groups, pages % key_groups

    def resolved_prompt_buckets(self) -> typing.Tuple[int, ...]:
        return self.prompt_buckets or _pow2_buckets(self.capacity)

    def resolved_admit_buckets(self) -> typing.Tuple[int, ...]:
        return self.admit_buckets or _pow2_buckets(self.max_active_seqs)

    def bucket_prompt_len(self, n: int) -> int:
        if not self.padding_buckets:
            return max(1, n)
        for b in self.resolved_prompt_buckets():
            if n <= b:
                return b
        return self.capacity

    def bucket_admit(self, n: int) -> int:
        if not self.padding_buckets:
            return max(1, n)
        for b in self.resolved_admit_buckets():
            if n <= b:
                return b
        return self.max_active_seqs

    def compile_signatures(
        self,
    ) -> typing.Optional[typing.Tuple[typing.Tuple[str, int, int], ...]]:
        """Every distinct jit signature this config can present, as
        ``(kind, batch, length)`` tuples — the prefill admit x prompt
        bucket grid plus the single padded decode step — or ``None``
        when ``padding_buckets`` is off and the set is unbounded (the
        recompile-churn footgun, statically visible to shardcheck)."""
        if not self.padding_buckets:
            return None
        sigs = [("prefill", b, t)
                for b in self.resolved_admit_buckets()
                for t in self.resolved_prompt_buckets()]
        sigs.append(("decode", self.max_active_seqs, 1))
        return tuple(sigs)


@dataclasses.dataclass
class SchedulerCounters:
    """Mirrored into the metric plane by the operator each step."""

    admitted: int = 0
    evicted: int = 0      # finished sessions releasing their slot
    preempted: int = 0    # budget overruns pushing a session back
    rejected: int = 0     # prompt + max_new > capacity (cannot ever fit)
    steps: int = 0


class TokenBudgetScheduler:
    """Active-set bookkeeping for one subtask's continuous batcher."""

    def __init__(self, config: ServingConfig):
        self.config = config
        #: session key -> pool slot (the active set).
        self.active: "collections.OrderedDict[typing.Any, int]" = (
            collections.OrderedDict())
        #: session key -> current cache length (budget accounting).
        self.lengths: typing.Dict[typing.Any, int] = {}
        self.waiting: "collections.deque[typing.Any]" = collections.deque()
        self.free_slots: typing.List[int] = list(
            range(config.max_active_seqs - 1, -1, -1))
        self.tokens_in_use = 0
        self.counters = SchedulerCounters()

    # -- queries ---------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return bool(self.active) or bool(self.waiting)

    def slot_of(self, key) -> int:
        return self.active[key]

    # -- transitions -----------------------------------------------------
    def enqueue(self, key, *, front: bool = False) -> None:
        if front:
            self.waiting.appendleft(key)
        else:
            self.waiting.append(key)

    def plan_admissions(
        self, length_of: typing.Callable[[typing.Any], int],
        admit_gate: typing.Optional[
            typing.Callable[[typing.Any, int], bool]] = None,
    ) -> typing.List[typing.Tuple[typing.Any, int]]:
        """Pop admissible sessions off the waiting queue: returns
        ``[(key, slot)]`` in arrival order.  ``length_of(key)`` is the
        cache length the session will occupy at admission (prompt length
        for fresh sessions, the preserved block length for resumed
        ones).  Budget charges length + 1 — the step it's admitted into
        grows it immediately.  ``admit_gate(key, length)`` is the paged
        pool's page-availability check (free pages instead of a
        contiguous slot); a False stops admission FIFO-fairly — nobody
        jumps the queue past a session the pool can't seat yet."""
        out: typing.List[typing.Tuple[typing.Any, int]] = []
        hyst = self.config.admit_hysteresis
        if (hyst > 1 and self.active
                and len(self.free_slots) < min(hyst, len(self.waiting))):
            return out  # batch the backlog's prefills into one dispatch
        while (self.waiting and self.free_slots
               and len(self.active) < self.config.max_active_seqs):
            key = self.waiting[0]
            need = length_of(key) + 1
            if self.tokens_in_use + need > self.config.token_budget and self.active:
                break  # budget-full (never starves: an empty active set admits)
            if admit_gate is not None and not admit_gate(key, need - 1):
                break  # no pages free — tier pressure clears first
            self.waiting.popleft()
            slot = self.free_slots.pop()
            self.active[key] = slot
            self.lengths[key] = need - 1
            self.tokens_in_use += need - 1
            self.counters.admitted += 1
            out.append((key, slot))
        return out

    def grow(self, key) -> None:
        """One decode step appended one cache position for ``key``."""
        self.lengths[key] += 1
        self.tokens_in_use += 1

    def release(self, key, *, reason: str) -> int:
        """Drop ``key`` from the active set; returns its freed slot."""
        slot = self.active.pop(key)
        self.tokens_in_use -= self.lengths.pop(key)
        self.free_slots.append(slot)
        if reason == "finished":
            self.counters.evicted += 1
        return slot

    def over_budget(self) -> typing.List[typing.Any]:
        """Keys to preempt (newest admitted first) until the active set
        fits the budget again.  At least one session always survives."""
        victims: typing.List[typing.Any] = []
        keys = list(self.active.keys())
        projected = self.tokens_in_use
        i = len(keys) - 1
        while projected > self.config.token_budget and i > 0:
            victims.append(keys[i])
            projected -= self.lengths[keys[i]]
            i -= 1
        # Accounting happens in preempt()/release(); only pick here.
        return victims

    def preempt(self, key) -> int:
        slot = self.release(key, reason="preempted")
        self.counters.preempted += 1
        self.enqueue(key, front=True)
        return slot
