"""The one traffic generator.  A mix is a data file of parameters under
``benchmark/workloads/``; this module turns it and ``--seed`` into the instants
at which records are due, and into the source that offers them to a job.

Keys of a mix:

- ``arrivals``: ``"backlog"`` (the next record is always due now: the system
  sets the pace) or ``"poisson"`` (open loop at ``rate_per_s``).  Every
  ``--seed`` gets the SAME set of inter-arrival gaps, in another order, so that
  seeds do not change the work.
- ``rate_per_s``: mean offered rate of an open loop.
- ``pool_records``: distinct records made from the seed and cycled.

Further keys belong to the job kind that reads the cell (window sizes, ...).
"""

from __future__ import annotations

import array
import json
import threading
import time

import numpy as np


def load(path: str) -> dict:
    if not path.endswith(".json"):
        raise ValueError(f"traffic mix {path}: only .json mixes are read today")
    with open(path) as f:
        return json.load(f)


#: The seed of the one set of gaps that every ``--seed`` reorders.
GAPS_SEED = 0


def due_offsets(mix: dict, seed: int, seconds: float):
    """Seconds after the window's start at which each record is due, ascending;
    None for a backlog."""
    kind = mix["arrivals"]
    if kind == "backlog":
        return None
    if kind != "poisson":
        raise ValueError(f"unknown arrivals {kind!r}")
    rate = float(mix["rate_per_s"])
    n = int(rate * seconds)
    if n < 1:
        raise ValueError(f"rate {rate}/s offers nothing in {seconds} s")
    gaps = np.random.default_rng(GAPS_SEED).exponential(1.0, n)
    gaps = np.random.default_rng(int(seed)).permutation(gaps)
    # Scaled so that the n records fill the window: the first is due at its start.
    return (np.cumsum(gaps) - gaps[0]) / gaps.sum() * seconds


class RunClock:
    """The measured window: opens when the job says its operators are warm."""

    def __init__(self, seconds: float, start_delay_s: float = 0.05):
        self.seconds = float(seconds)
        self.start_delay_s = start_delay_s
        self.started = threading.Event()
        self.t_start = None
        self.t_close = None

    def open_window(self) -> None:
        self.t_start = time.monotonic() + self.start_delay_s
        self.t_close = self.t_start + self.seconds
        self.started.set()


def first_index(pool_n: int, seed: int) -> int:
    """Where in the pool a run with ``seed`` starts its cycle."""
    return int(np.random.default_rng(int(seed)).integers(pool_n))


class Offered:
    """What the generator did: one row per record offered, in order.  Typed
    arrays, not lists of objects: a run appends some hundred thousand rows, and
    nothing the benchmark keeps may feed the garbage collector (PERF.md 6)."""

    def __init__(self):
        self.pool_index = array.array("q")
        self.due = array.array("d")
        self.emitted = array.array("d")


def make_source(pool_records, mix: dict, seed: int, clock: RunClock, offered: Offered,
                lead_records: int = 0):
    """A source on the program's split-source API that offers ``pool_records``
    cyclically, starting at a seeded offset, each stamped with its id and the
    instant it was due.  The schedule and the stamps are the benchmark's own.
    ``lead_records`` are offered at once, before the window opens (set-up steps
    that the job checks): rows that all differ while the pool is that large."""
    from flink_tensorflow_tpu.sources.api import (
        ListSplitEnumerator, NotReady, SourceReader, SourceSplit, SplitSource)

    pool_n = len(pool_records)
    first = first_index(pool_n, seed)

    def emit(k, due):
        idx = (first + k) % pool_n
        offered.pool_index.append(idx)
        offered.due.append(due)
        offered.emitted.append(time.monotonic())
        return pool_records[idx].with_meta(id=k, due=due)

    class Reader(SourceReader):
        def read(self, split):
            offsets = due_offsets(mix, seed, clock.seconds)
            k = 0
            while k < lead_records:
                yield emit(k, time.monotonic())
                k += 1
            while not clock.started.is_set():
                yield NotReady(time.monotonic() + 0.002)
            while True:
                if offsets is None:
                    due = time.monotonic()
                    if due >= clock.t_close:
                        return
                    if due < clock.t_start:
                        yield NotReady(clock.t_start)
                        continue
                else:
                    if k - lead_records >= len(offsets):
                        return
                    due = clock.t_start + float(offsets[k - lead_records])
                    while time.monotonic() < due:
                        yield NotReady(due)
                yield emit(k, due)
                k += 1

    class Source(SplitSource):
        bounded = True
        schema = None

        def create_enumerator(self):
            return ListSplitEnumerator([SourceSplit(split_id="offered")])

        def create_reader(self, ctx):
            return Reader()

        def plan_split_count(self):
            return 1

    return Source()
