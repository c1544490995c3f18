"""Reader ``spans``: the program's window-level spans, alone or joined to the
device trace's clock (``benchmark/span_join.py``).

What it relies on in the program (``benchmark/readers/spans.md``): the
post-mortem accessor ``flink_tensorflow_tpu.tracing.flight.recorder_of(job)``,
with the job named after the cell, and the span names of ``tracing/flight.py``.
A program without the accessor (the parent of the PR that brought it) reads
nothing: every ``what`` then returns None and the line leaves the metric out.

``what``:

- ``span_ms``: ``stat`` (a percentile: ``p50``, ``p95``) of the durations of the spans ``span``
  on ``track`` that ended inside the measured window, in ms;
- ``overslept_ms_max``: the longest by which a park of the chain's head
  (``park.overslept`` on ``track``) outlasted what it asked for inside the
  measured window, in ms; 0.0 with none;
- ``in_flight_ms``: median over the traced span of one ``part`` of the
  ``in_flight`` span split by the join: ``dispatch_to_start`` or
  ``end_to_fetched``;
- ``idle_share``: % of the traced span the device idled between programs
  while the host was at ``of``: ``ingest``, ``emit``, ``in_flight`` (a batch
  dispatched and not started), or ``rest`` (all that is left of the
  between-program idle time: blocked collections, fires, parks, and what no
  span covers).

The joined readings need ``module``, the pattern of the step's program, and
of the harness's trace ``start_call`` (the two stamps around ``start_trace``)
and ``profile_start_host()`` (the xplane's own start, where it has one): the
join chooses its pairing by them.  They raise when the two records cannot be
put on one clock (``span_join.JoinError``): a traced run must not print a
wrong share.
"""

from __future__ import annotations

import statistics
import sys
import time

import numpy as np

from benchmark import span_join


def _events(state):
    try:
        from flink_tensorflow_tpu.tracing.flight import recorder_of
    except ImportError:
        return None
    ring = recorder_of(state["cell"]["name"])
    return ring.events() if ring is not None else None


def _joined(state, events, track, module):
    """The join of this run, made once: the ``in_flight`` parts, the booked
    idle seconds and the traced span's length."""
    key = ("span_join", track, module)
    if key not in state:
        t_read = time.monotonic()
        trace = state["ctx"].traced
        spans = span_join.spans_of(events, track)
        device = min(trace.module_events)
        batches = span_join.batches_inside(spans, *trace.host_span)
        runs = span_join.device_runs(trace.module_events[device], module)
        joined = span_join.join(batches, runs, trace.start_call, trace.profile_start_host())
        lo, hi = (ns / 1e9 for ns in trace.window_ns)
        gaps = span_join.idle_gaps(trace.device_events[device], trace.module_events[device], lo, hi)
        booked = span_join.book_gaps(gaps, spans, joined)
        parts = span_join.split_in_flight(joined)
        read = joined["read_offset"]
        print(f"span join: {len(joined['pairs'])} batches of {len(runs)} runs, shift {joined['shift']}, "
              f"profile began {(trace.host_span[0] + joined['offset']) * 1e3:.1f} ms before start_trace returned "
              f"(the call took {(trace.start_call[1] - trace.start_call[0]) * 1e3:.1f} ms), "
              + ("no start in the xplane, " if read is None else
                 f"the offset read lies {(read - joined['offset']) * 1e3:.3f} ms past the min-filter's, ")
              + f"offsets allowed over {(joined['bracket'][1] - joined['bracket'][0]) * 1e3:.3f} ms, "
              f"tight edge iqr {joined['tight_iqr_s'] * 1e3:.3f} ms; medians ms: "
              + ", ".join(f"{k} {statistics.median(v) * 1e3:.2f}" for k, v in parts.items())
              + f"; between-program idle {sum(b - a for a, b in gaps):.4f} s booked "
              + ", ".join(f"{k} {v:.4f}" for k, v in booked.items())
              + f"; joined in {time.monotonic() - t_read:.3f} s", file=sys.stderr)
        state[key] = (parts, booked, trace.window_s)
    return state[key]


def read(state, *, what, track="model.0", span=None, stat="p50", part=None, of=None, module=None):
    events = _events(state)
    if events is None:
        return None
    if what in ("span_ms", "overslept_ms_max"):
        window = state["run"]["window"]
        lo, hi = window["t_start"], window["t_close"]
        if what == "overslept_ms_max":
            over = [args["slept_s"] - args["asked_s"] for ev_track, name, _, t0, _, args in events
                    if ev_track == track and name == "park.overslept" and lo <= t0 < hi]
            return 1e3 * max(over, default=0.0)
        durations = [t1 - t0 for t0, t1, _ in span_join.spans_of(events, track).get(span, [])
                     if lo <= t1 < hi]
        if not durations:
            return None
        return 1e3 * float(np.percentile(durations, float(stat.lstrip("p"))))
    if state["ctx"].traced is None:
        return None
    parts, booked, window_s = _joined(state, events, track, module)
    if what == "in_flight_ms":
        return 1e3 * statistics.median(parts[part])
    if what == "idle_share":
        named = ("ingest", "emit", "in_flight")
        seconds = booked[of] if of in named else sum(booked.values()) - sum(booked[k] for k in named)
        return 100.0 * seconds / window_s
    raise ValueError(f"reader spans: unknown what={what!r}")
