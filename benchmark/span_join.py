"""The program's window-level spans joined to the device trace's clock.

Two records of one run, as they are: the program's flight ring (events
``(track, name, ph, t0, dur, args)`` on ``time.monotonic()``, see
``flink_tensorflow_tpu/tracing/flight.py``) and the device trace's runs of the
step's program (``trace_reduce.Trace.module_events``, nanoseconds on the
xplane's clock).  The host's tracer stays off (it slows the job tenfold), so
nothing in the trace says which batch a run served: runs are matched to
batches by order, and every pair is held to causality.

Pure functions; seconds throughout.  ``offset`` maps the host's clock onto the
device's: ``device = host + offset``.
"""

from __future__ import annotations

import re
import statistics

#: The subtask thread's spans, innermost first: where two cover an instant the
#: first named wins (``emit`` and ``collect_wait`` run inside ``fire`` or
#: ``fill``).
SUBTASK_SPANS = ("emit", "collect_wait", "fire", "fill")
#: A pair may break causality by this much before the join refuses.
SLACK_S = 1e-3
#: The widest the true pairing's tight edge (``end - done``) may spread
#: between its quartiles.
TIGHT_IQR_S = 3e-3
#: How far a pairing's offset (the least ``done - end`` over its pairs) may lie
#: from the offset read off the clocks and still be the pairing read.  The
#: min-filter is short of the read offset by the quickest pair's copy back:
#: 1.91-3.28 ms in 15 traced stream runs (PERF.md 6, PR 28).  Pairings lie one
#: period apart, so this has to stay well under half the shortest period
#: joined (162.6 ms, the Inception window's own run).
CLOCK_TOL_S = 20e-3


class JoinError(ValueError):
    """The two records cannot be put on one clock: no number is better than a
    wrong share."""


def spans_of(events, track: str) -> dict:
    """``{name: [(t0, t1, args)]}`` of the complete spans on ``track``, each
    list in order of start."""
    out = {}
    for ev_track, name, ph, t0, dur, args in events:
        if ev_track == track and ph == "X":
            out.setdefault(name, []).append((t0, t0 + dur, args or {}))
    for rows in out.values():
        rows.sort(key=lambda r: r[0])
    return out


def batches_inside(spans: dict, lo: float, hi: float) -> list:
    """The batches whose ``in_flight`` span lies wholly inside ``[lo, hi]``,
    in order of dispatch: ``{"seq", "dispatched", "done", "enqueue_s"}``."""
    enqueue = {args.get("seq"): t1 - t0 for t0, t1, args in spans.get("enqueue", [])}
    return [{"seq": args.get("seq"), "dispatched": t0, "done": t1,
             "enqueue_s": enqueue.get(args.get("seq"), 0.0)}
            for t0, t1, args in spans.get("in_flight", []) if t0 >= lo and t1 <= hi]


def device_runs(module_events, pattern: str) -> list:
    """``[(start, end)]`` in seconds, in order, of the runs on one device of
    the programs whose name matches ``pattern``."""
    runs = sorted((s / 1e9, e / 1e9) for name, s, e in module_events if re.search(pattern, name))
    if not runs:
        raise LookupError(f"no program on the device matches {pattern!r}")
    return runs


def edges(batches, runs):
    """What causality allows a pairing, pair by pair: a program ends before
    its results are on the host (``end <= done + offset``, so ``offset >= end
    - done``: tight, a copy back of a few MB and one wake-up) and starts after
    its batch was dispatched (``offset <= start - dispatched``: loose by the
    transfer).  Returns the two lists."""
    tight = [end - b["done"] for b, (_, end) in zip(batches, runs)]
    loose = [start - b["dispatched"] for b, (start, _) in zip(batches, runs)]
    return tight, loose


def join(batches, runs, start_call, profile_start=None, slack_s: float = SLACK_S,
         tol_s: float = CLOCK_TOL_S) -> dict:
    """Pair ``batches`` with ``runs`` by order and put them on one clock.

    The trace may hold a run more at either edge (of a batch cut by the
    span's edge), so every pairing ``batch[i] <-> run[i + shift]`` is a
    candidate.  Each has the offset it would cut with, the least ``done -
    end`` over its pairs (a min-filter: a late wake-up only ever adds to a
    pair's, so the least is the cleanest), and the bracket causality allows,
    ``[max(end - done), min(start - dispatched)]``.  Neither chooses: in a
    steady pipeline a pairing shifted by one moves both edges by one period
    and still holds, and once the device sets the period (programs back to
    back) a shifted pairing's tight edge is as narrow as the true one's.  The
    brackets are as wide as the shortest ``dispatch_to_start``, which behind a
    queue of programs is over a period, so they overlap too.

    What chooses is the clock.  The xplane's zero is the profile's start,
    which lies inside the ``start_trace`` call (40-76 us into it on the chip:
    PERF.md 6, PR 28), and the candidates' offsets lie one period apart:

    - ``profile_start``, the xplane's own ``profile_start_time`` put on the
      host's clock (``trace_reduce.Trace.profile_start_host``), gives the
      offset read, ``-profile_start``.  The one candidate whose offset lies
      within ``tol_s`` of it is taken.
    - Without it (a recorded table, an xplane without the stat) the one
      candidate whose offset puts the profile's start inside ``start_call``,
      the host's two stamps ``(t_call, t_on)`` around ``start_trace``, to
      within ``tol_s``.  That decides only while the period is longer than
      the call (45-184 ms measured).

    None or more than one: :class:`JoinError` with every candidate's numbers.
    The read clock chooses and does not cut: the offset returned is the
    chosen pairing's min-filter, which leaves every ``dispatch_to_start``
    long and every ``end_to_fetched`` short by the copy back of the quickest
    pair, at most one d2h of the results.  The chosen pairing is then held to
    a narrow tight edge (``TIGHT_IQR_S`` between its quartiles: in a true
    pairing ``end - done`` is the same in every pair to within a wake-up) and
    every pair to causality within ``slack_s``.

    Returns ``{"offset", "shift", "pairs": [(batch, (start, end))], "bracket",
    "tight_iqr_s", "read_offset"}`` (``read_offset`` None without
    ``profile_start``).  Raises :class:`JoinError` with the numbers otherwise.
    """
    if len(batches) < 4:
        raise JoinError(f"{len(batches)} whole batches inside the traced span: too few to pair")
    spare = len(runs) - len(batches)
    if spare < 0:
        raise JoinError(f"{len(batches)} whole batches in the traced span but only "
                        f"{len(runs)} runs of the step's program in the trace")
    t_call, t_on = start_call
    if profile_start is None:
        want_lo, want_hi = -t_on, -t_call
        want = (f"a profile started inside start_trace: offset in [{want_lo:.6f}, {want_hi:.6f}] "
                f"(the call took {(t_on - t_call) * 1e3:.1f} ms)")
    elif t_call - tol_s <= profile_start <= t_on + tol_s:
        want_lo = want_hi = -profile_start
        want = f"the offset read off the clocks, {want_lo:.6f}"
    else:
        raise JoinError(f"the profile's start as read, {profile_start:.6f} on the host's clock, lies outside "
                        f"the start_trace call [{t_call:.6f}, {t_on:.6f}]: the clocks were not read together")
    tried = []
    for shift in range(spare + 1):
        tight, loose = edges(batches, runs[shift:])
        q1, _, q3 = statistics.quantiles(tight, n=4)
        tried.append((shift, max(tight), min(loose), q3 - q1))
    told = (f"{len(batches)} batches, {len(runs)} runs; wanted {want} to within {tol_s * 1e3:.1f} ms; "
            + ", ".join(f"shift {s}: offset {lo:.6f}, allowed up to {hi:.6f}, tight edge iqr "
                        f"{iqr * 1e3:.3f} ms" for s, lo, hi, iqr in tried))
    fit = [t for t in tried if want_lo - tol_s <= t[1] <= want_hi + tol_s]
    if len(fit) != 1:
        raise JoinError(f"cannot tell which run served which batch: {len(fit)} pairings fit the clock "
                        f"({told})")
    shift, lo, hi, iqr = fit[0]
    if iqr > TIGHT_IQR_S:
        raise JoinError(f"the pairing the clock chose, shift {shift}, is not a steady one: its tight "
                        f"edge spreads {iqr * 1e3:.3f} ms between its quartiles ({told})")
    if lo > hi + slack_s:
        _, loose = edges(batches, runs[shift:])
        worst = max(range(len(batches)), key=lambda i: lo - loose[i])
        raise JoinError(
            f"batch seq {batches[worst]['seq']} breaks causality by {(lo - loose[worst]) * 1e3:.3f} ms: "
            f"its program started {(loose[worst] - lo) * 1e3:.3f} ms after its dispatch on the joined "
            f"clock ({told})")
    return {"offset": lo, "shift": shift, "bracket": (lo, hi), "tight_iqr_s": iqr,
            "read_offset": None if profile_start is None else -profile_start,
            "pairs": list(zip(batches, runs[shift:shift + len(batches)]))}


def split_in_flight(joined: dict) -> dict:
    """Every paired ``in_flight`` span cut in three, as lists of seconds:
    ``dispatch_to_start`` (the transfer, and queueing behind the program
    before), ``device_run``, ``end_to_fetched`` (copy back and wake-up); and
    ``enqueue`` beside them."""
    d = joined["offset"]
    out = {"enqueue": [], "dispatch_to_start": [], "device_run": [], "end_to_fetched": []}
    for batch, (start, end) in joined["pairs"]:
        out["enqueue"].append(batch["enqueue_s"])
        out["dispatch_to_start"].append(start - d - batch["dispatched"])
        out["device_run"].append(end - start)
        out["end_to_fetched"].append(batch["done"] + d - end)
    return out


def idle_gaps(op_events, module_events, lo: float, hi: float) -> list:
    """The device's idle gaps between programs as ``trace_reduce.Trace.
    breakdown`` finds them, in seconds on the device's clock and clipped to
    ``[lo, hi]``: stretches in which no op runs and which no run of a program
    covers.  (A gap inside a running program is the program's own.)"""
    programs = sorted((s, e) for _, s, e in module_events)
    gaps, reach = [], None
    for _, s, e in sorted(op_events, key=lambda ev: ev[1]):
        if reach is not None and s > reach and not any(
                ps <= reach and s <= pe for ps, pe in programs):
            a, b = max(reach / 1e9, lo), min(s / 1e9, hi)
            if b > a:
                gaps.append((a, b))
        reach = e if reach is None else max(reach, e)
    return gaps


def _union(intervals) -> list:
    """Sorted, disjoint intervals covering what ``intervals`` cover."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        elif b > a:
            out.append((a, b))
    return out


def _both(pieces, cover) -> list:
    """The parts of the disjoint ``pieces`` that the disjoint ``cover`` covers."""
    return [(max(a, lo), min(b, hi)) for a, b in pieces for lo, hi in cover
            if min(b, hi) > max(a, lo)]


def _less(pieces, cover) -> list:
    """The disjoint ``pieces`` less the sorted, disjoint ``cover``."""
    out = []
    for a, b in pieces:
        for lo, hi in cover:
            if hi <= a or lo >= b:
                continue
            if lo > a:
                out.append((a, lo))
            a = max(a, hi)
        if b > a:
            out.append((a, b))
    return out


def book_gaps(gaps, spans: dict, joined: dict) -> dict:
    """Each idle second between programs booked to what the host was doing,
    in this order: a batch dispatched whose program has not started
    (``in_flight``: the exposed transfer), else the subtask thread's span at
    that instant (``emit``, ``collect_wait``, ``fire``; of a ``fill`` its
    self time as ``ingest`` and its parks as ``park``, pro rata, since a
    fill's parks are a sum and not intervals), else
    ``unattributed``.  The booked seconds sum to the gaps' seconds."""
    d = joined["offset"]
    start_of = {b["seq"]: start for b, (start, _) in joined["pairs"]}
    booked = dict.fromkeys(("in_flight", "emit", "collect_wait", "fire", "ingest", "park",
                            "unattributed"), 0.0)
    rest = _union(gaps)
    # Dispatched and not started, on the device's clock.  A batch with no
    # paired run (cut by the span's edge) counts to the end of its span.
    layers = [("in_flight", [(t0 + d, start_of.get(args.get("seq"), t1 + d), args)
                             for t0, t1, args in spans.get("in_flight", [])])]
    layers += [(name, [(t0 + d, t1 + d, args) for t0, t1, args in spans.get(name, [])])
               for name in SUBTASK_SPANS]
    for name, rows in layers:
        if name == "fill":
            for t0, t1, args in rows:
                own, away = args.get("self_s", t1 - t0), args.get("park_s", 0.0)
                share = own / (own + away) if own + away > 0 else 1.0
                seconds = _length(_both(rest, [(t0, t1)]))
                booked["ingest"] += seconds * share
                booked["park"] += seconds * (1.0 - share)
        cover = _union((t0, t1) for t0, t1, _ in rows)
        if name != "fill":
            booked[name] += _length(_both(rest, cover))
        rest = _less(rest, cover)
    booked["unattributed"] = _length(rest)
    return booked


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)

