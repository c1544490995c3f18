"""The join of the program's window-level spans to the device trace's clock:
on pairs recorded on the chip (the flight ring's spans beside the device trace
of the same run: PR 27's, of the cell as it stands, and PR 28's, of the same
job with a third window in flight), on the first of them replayed as a
device-bound pipeline, and on tables whose answers are known by hand."""

import gzip
import json
import os
import statistics
import types

import pytest

from benchmark import span_join, trace_reduce
from benchmark.readers import spans as spans_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "tests", "benchmark", "data")
SERVE = r"^jit_call\b"
MS = 1e-3
#: PR 27's pair kept the stamp after ``start_trace`` alone; the longest such
#: call measured took 125 ms (PERF.md 6, PR 27 step 0).
LONGEST_CALL_S = 0.125
#: The offset the parent's rule (narrowest tight edge) cut PR 27's pair with.
RECORDED_OFFSET = -101.153930037


def _load(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        doc = json.load(f)
    trace = trace_reduce.Trace([tuple(r) for r in doc["rows"]], doc.get("profile_start_unix_ns"))
    trace.host_span = tuple(doc["host_span"])
    trace.start_call = tuple(doc.get("start_call", (trace.host_span[0] - LONGEST_CALL_S, trace.host_span[0])))
    trace.unix_at = doc.get("unix_at")
    spans = span_join.spans_of([tuple(e) for e in doc["events"]], "model.0")
    batches = span_join.batches_inside(spans, *trace.host_span)
    runs = span_join.device_runs(trace.module_events[0], SERVE)
    return types.SimpleNamespace(doc=doc, trace=trace, spans=spans, batches=batches, runs=runs)


@pytest.fixture(scope="module")
def recorded():
    return _load("span_pair_saturated.json.gz")


@pytest.fixture(scope="module")
def recorded_depth3():
    return _load("span_pair_saturated_depth3.json.gz")


def _join(r):
    return span_join.join(r.batches, r.runs, r.trace.start_call, r.trace.profile_start_host())


def _iqr(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def _tight_iqrs(batches, runs):
    """Each pairing's tight-edge spread, which the parent's rule chose by: by shift."""
    return [_iqr(span_join.edges(batches, runs[shift:])[0]) for shift in range(len(runs) - len(batches) + 1)]


def test_recorded_pair_joins_with_the_offset_in_every_bracket(recorded):
    r = recorded
    assert (len(r.batches), len(r.runs)) == (41, 43)  # a run more at either edge of the span
    joined = _join(r)
    # As the parent's rule took it: the pairing with the narrowest tight edge, cut at its least done - end.
    iqrs = _tight_iqrs(r.batches, r.runs)
    assert joined["shift"] == iqrs.index(min(iqrs)) == 1
    assert joined["offset"] == pytest.approx(RECORDED_OFFSET, abs=1e-9)
    assert joined["offset"] == max(span_join.edges(r.batches, r.runs[1:])[0]) and joined["read_offset"] is None
    d = joined["offset"]
    for batch, (start, end) in joined["pairs"]:
        assert batch["dispatched"] + d <= start + 1e-9
        assert end <= batch["done"] + d + 1e-9
    # The profile's clock starts inside start_trace, before the host's stamp.
    assert 0 <= r.trace.host_span[0] + d < 0.2
    assert joined["tight_iqr_s"] < 0.5 * MS
    parts = span_join.split_in_flight(joined)
    assert statistics.median(parts["device_run"]) == pytest.approx(162.6 * MS, abs=0.1 * MS)
    assert 120 * MS < statistics.median(parts["dispatch_to_start"]) < 160 * MS
    assert 0 <= min(parts["end_to_fetched"]) < 1e-9 < statistics.median(parts["end_to_fetched"]) < 3 * MS


@pytest.mark.parametrize("drop", ["first", "last"])
def test_a_pairing_shifted_by_one_is_rejected(recorded, drop):
    # Causality alone holds a shifted pairing of a steady pipeline (both edges
    # move by a period); the clock does not: it puts the profile's start a
    # period (163-223 ms here) away from the start_trace call.
    runs = recorded.runs[:-2] if drop == "last" else recorded.runs[2:]
    tight, loose = span_join.edges(recorded.batches, runs)
    assert max(tight) <= min(loose)
    with pytest.raises(span_join.JoinError, match="0 pairings fit the clock") as raised:
        span_join.join(recorded.batches, runs, recorded.trace.start_call)
    assert f"shift 0: offset {max(tight):.6f}" in str(raised.value)
    with pytest.raises(span_join.JoinError, match="0 pairings fit the clock"):
        span_join.join(recorded.batches, runs, recorded.trace.start_call, -(RECORDED_OFFSET + MS))


def _device_bound(r, gap_s, earlier_s, anchor):
    """PR 27's pair as a pipeline bound by the device would have left it: the
    same runs with their own durations laid ``gap_s`` apart (the first, or the
    last, where it was), every batch with its own ``done - end`` and its own
    ``start - dispatched`` plus ``earlier_s``.  Returns the batches that still
    lie whole inside the traced span, the runs, and the shift that is true."""
    joined = _join(r)
    runs, at = [], r.runs[0][0]
    for start, end in r.runs:
        runs.append((at, at + end - start))
        at += end - start + gap_s
    if anchor == "last":
        late = r.runs[-1][1] - runs[-1][1]
        runs = [(start + late, end + late) for start, end in runs]
    batches = []
    for i, (batch, (start, end)) in enumerate(joined["pairs"]):
        new_start, new_end = runs[i + joined["shift"]]
        batches.append(dict(batch, dispatched=new_start - (start - batch["dispatched"]) - earlier_s,
                            done=new_end + batch["done"] - end))
    t_on, t_off = r.trace.host_span
    whole = [b for b in batches if b["dispatched"] >= t_on and b["done"] <= t_off]
    return whole, runs, joined["shift"] + batches.index(whole[0])


@pytest.mark.parametrize("clock", ["start_read", "start_trace_call_only"])
@pytest.mark.parametrize("anchor", ["first", "last"])
@pytest.mark.parametrize("earlier_ms", [0, 100, 250])
@pytest.mark.parametrize("gap_ms", [0.03, 1, 5])
def test_a_device_bound_replay_of_the_recorded_pair_joins(recorded, gap_ms, earlier_ms, anchor, clock):
    batches, runs, true_shift = _device_bound(recorded, gap_ms * MS, earlier_ms * MS, anchor)
    assert 38 <= len(batches) <= 41 and true_shift == {"last": 1, "first": 43 - 1 - len(batches)}[anchor]
    # What the parent's rule refused on: the period is the program's own, so a
    # shifted pairing's tight edge is as narrow as the true one's ...
    iqrs = sorted(_tight_iqrs(batches, runs))
    assert iqrs[1] < 4 * iqrs[0] < 4 * span_join.TIGHT_IQR_S
    # ... and, with dispatches a period ahead, the brackets of neighbours overlap.
    if earlier_ms:
        tight, loose = span_join.edges(batches, runs[true_shift - 1:])
        assert RECORDED_OFFSET < min(loose) and max(tight) < RECORDED_OFFSET
    # The start read lies past the min-filter's offset by the quickest copy back, about a ms.
    read = -(RECORDED_OFFSET + MS) if clock == "start_read" else None
    joined = span_join.join(batches, runs, recorded.trace.start_call, read)
    assert joined["shift"] == true_shift
    assert joined["offset"] == pytest.approx(RECORDED_OFFSET, abs=0.1 * MS)
    assert [b["seq"] for b, _ in joined["pairs"]] == [b["seq"] for b in batches]


@pytest.mark.parametrize("clock", ["start_read", "start_trace_call_only"])
def test_the_pair_recorded_with_a_third_window_in_flight_joins(recorded_depth3, clock):
    r = recorded_depth3
    assert (len(r.batches), len(r.runs)) == (45, 49)  # five pairings to choose from
    periods = [b[0] - a[0] for a, b in zip(r.runs, r.runs[1:])]
    assert statistics.median(periods) == pytest.approx(162.9 * MS, abs=0.1 * MS) and _iqr(periods) < 0.2 * MS
    # The parent's rule raised on this run on the chip: every pairing's tight edge is narrow.
    iqrs = sorted(_tight_iqrs(r.batches, r.runs))
    assert iqrs[-1] < 4 * iqrs[0] < 4 * span_join.TIGHT_IQR_S
    t_call, t_on = r.trace.start_call
    start = r.trace.profile_start_host()
    assert 0 < start - t_call < 0.1 * MS < 40 * MS < t_on - start  # at the head of the call
    joined = span_join.join(r.batches, r.runs, r.trace.start_call, start if clock == "start_read" else None)
    assert joined["shift"] == 3 and joined["tight_iqr_s"] == iqrs[0]
    assert joined["offset"] == pytest.approx(-47.259855555, abs=1e-9)
    assert joined["offset"] == max(span_join.edges(r.batches, r.runs[3:])[0])  # cut with the min-filter
    # The offset read lies past it by the quickest pair's copy back.
    assert -start - joined["offset"] == pytest.approx(3.281 * MS, abs=1e-6)
    parts = span_join.split_in_flight(joined)
    assert statistics.median(parts["dispatch_to_start"]) == pytest.approx(253.19 * MS, abs=0.01 * MS)
    lo, hi = (ns / 1e9 for ns in r.trace.window_ns)
    gaps = span_join.idle_gaps(r.trace.device_events[0], r.trace.module_events[0], lo, hi)
    booked = span_join.book_gaps(gaps, r.spans, joined)
    between = sum(s for label, s in r.trace.breakdown()["idle_gaps"] if not label.startswith("inside"))
    assert sum(booked.values()) == pytest.approx(between, abs=1e-9) and between == pytest.approx(0.1550, abs=1e-4)
    assert booked["in_flight"] == pytest.approx(between, rel=1e-3)
    assert 100.0 * r.trace.idle_share() == pytest.approx(1.939, abs=1e-3)


def test_a_start_read_that_fits_no_pairing_or_two_raises_with_the_numbers(recorded):
    r = recorded
    t_on = r.trace.start_call[1]
    # Read 30 ms off every candidate's offset, though inside the call: no pairing.
    with pytest.raises(span_join.JoinError, match="0 pairings fit the clock") as raised:
        span_join.join(r.batches, r.runs, r.trace.start_call, -(RECORDED_OFFSET + 30 * MS))
    told = str(raised.value)
    assert f"wanted the offset read off the clocks, -101.123930 to within {span_join.CLOCK_TOL_S / MS:.1f} ms" in told
    assert "shift 1: offset -101.153930, allowed up to -101.024673, tight edge iqr 0.112 ms" in told
    assert "shift 0: offset -101.316752" in told and "shift 2: offset -100.875887" in told
    # A tolerance of a period takes in a neighbour: two pairings, no choice.
    with pytest.raises(span_join.JoinError, match="2 pairings fit the clock"):
        span_join.join(r.batches, r.runs, r.trace.start_call, -(RECORDED_OFFSET + MS), tol_s=0.2)
    # A start_trace call longer than the period, and no start read: two again.
    with pytest.raises(span_join.JoinError, match=r"2 pairings fit the clock.*the call took 350\.0 ms"):
        span_join.join(r.batches, r.runs, (t_on - 0.35, t_on))
    # A start read outside the call it was made in: the clocks were not read together.
    with pytest.raises(span_join.JoinError, match="outside the start_trace call"):
        span_join.join(r.batches, r.runs, (t_on - 0.02, t_on), -(RECORDED_OFFSET + MS))
    # The clock's choice is still held to a narrow tight edge: a pairing shifted by one that the clock
    # is made to point at is refused as unsteady.
    with pytest.raises(span_join.JoinError, match="shift 0, is not a steady one.*34.001 ms"):
        span_join.join(r.batches, r.runs, (t_on + 0.05, t_on + 0.15), 101.316752)


def test_a_planted_skew_of_five_ms_raises_with_the_numbers(recorded):
    joined = _join(recorded)
    batch, (start, _) = joined["pairs"][7]
    skewed = [dict(b) for b in recorded.batches]
    # Its program now starts 5 ms before it was dispatched, on the joined clock.
    skewed[7]["dispatched"] = start - joined["offset"] + 5 * MS
    with pytest.raises(span_join.JoinError, match=rf"seq {batch['seq']} breaks causality by 5\.\d+ ms"):
        span_join.join(skewed, recorded.runs, recorded.trace.start_call)
    # Within the slack it passes.
    skewed[7]["dispatched"] = start - joined["offset"] + 0.5 * MS
    assert span_join.join(skewed, recorded.runs, recorded.trace.start_call)["shift"] == 1


def test_bookings_sum_to_the_between_program_idle_time(recorded):
    r = recorded
    joined = _join(r)
    lo, hi = (ns / 1e9 for ns in r.trace.window_ns)
    gaps = span_join.idle_gaps(r.trace.device_events[0], r.trace.module_events[0], lo, hi)
    booked = span_join.book_gaps(gaps, r.spans, joined)
    between = sum(s for label, s in r.trace.breakdown()["idle_gaps"] if not label.startswith("inside"))
    assert sum(booked.values()) == pytest.approx(between, abs=1e-9)
    assert sum(b - a for a, b in gaps) == pytest.approx(between, abs=1e-9)
    # In this run the device only ever waited with a batch dispatched and not started.
    assert booked["in_flight"] == pytest.approx(between, rel=1e-3)
    inside = 1.0 - r.trace.busy_s() / r.trace.window_s - between / r.trace.window_s
    assert 0 <= inside < 1e-4


def _hand_table():
    """Four batches a second apart on the host; the device's clock is the
    host's + 100 s.  Program k runs [k + 0.30, k + 0.50] on the host's clock;
    results are on the host 2 ms (batch 0: 1 ms) after its end."""
    events, modules, ops = [], [], []
    for k in range(4):
        t = float(k)
        done = t + 0.50 + (0.001 if k == 0 else 0.002)
        events += [
            ("m.0", "fill", "X", t - 0.20, 0.20,
             {"seq": k, "self_s": 0.15, "park_s": 0.05}),
            ("m.0", "fire", "X", t, 0.12, {"seq": k}),
            ("m.0", "enqueue", "X", t, 0.01, {"seq": k}),
            ("m.0", "in_flight", "X", t + 0.01, done - t - 0.01, {"seq": k}),
            ("m.0", "collect_wait", "X", t + 0.02, 0.05, {"seq": k}),
            ("m.0", "emit", "X", t + 0.07, 0.05, {"seq": k}),
        ]
        s, e = int((100 + t + 0.30) * 1e9), int((100 + t + 0.50) * 1e9)
        modules.append(("jit_call(1)", s, e))
        ops.append(("fusion", s, e))
    return events, modules, ops


def test_join_and_booking_by_hand():
    events, modules, ops = _hand_table()
    spans = span_join.spans_of(events, "m.0")
    batches = span_join.batches_inside(spans, -1.0, 10.0)
    assert [b["seq"] for b in batches] == [0, 1, 2, 3]
    runs = span_join.device_runs(modules, SERVE)
    joined = span_join.join(batches, runs, (-100.05, -99.9))
    # The min-filter takes batch 0's 1 ms as no time at all: offset 1 ms short.
    assert joined["offset"] == pytest.approx(100.0 - 0.001, abs=1e-6)
    parts = span_join.split_in_flight(joined)
    assert parts["dispatch_to_start"] == pytest.approx([0.291] * 4, abs=1e-6)
    assert parts["end_to_fetched"] == pytest.approx([0.0, 0.001, 0.001, 0.001], abs=1e-6)
    assert parts["enqueue"] == pytest.approx([0.01] * 4)
    # Gaps between programs: [k + 0.50, k + 1.30] on the host's clock, 0.8 s each.
    gaps = span_join.idle_gaps(ops, modules, 0.0, 1000.0)
    assert len(gaps) == 3 and sum(b - a for a, b in gaps) == pytest.approx(2.4)
    booked = span_join.book_gaps(gaps, spans, joined)
    # Of each gap, on the host's clock (the joined one runs 1 ms behind it): batch k+1 is in flight from
    # k + 1.01 to its start, first in order: 0.291 s; before that emit covers nothing of the gap, the fire
    # [k + 1, k + 1.12] gives its first 0.01 s less what in_flight took, the fill [k + 0.8, k + 1.0] 0.2 s at
    # three quarters ingest, and [k + 0.501, k + 0.8] is covered by nothing.
    assert booked["in_flight"] == pytest.approx(3 * 0.291, abs=1e-6)
    assert booked["fire"] == pytest.approx(3 * 0.010, abs=1e-6)
    assert booked["ingest"] == pytest.approx(3 * 0.150, abs=1e-6)
    assert booked["park"] == pytest.approx(3 * 0.050, abs=1e-6)
    assert booked["emit"] == booked["collect_wait"] == 0.0
    assert booked["unattributed"] == pytest.approx(3 * 0.299, abs=1e-6)
    assert sum(booked.values()) == pytest.approx(2.4)


def test_too_few_runs_or_batches_raise():
    events, modules, _ = _hand_table()
    spans = span_join.spans_of(events, "m.0")
    batches = span_join.batches_inside(spans, -1.0, 10.0)
    runs = span_join.device_runs(modules, SERVE)
    with pytest.raises(span_join.JoinError, match="only 3 runs"):
        span_join.join(batches, runs[:3], (-100.05, -99.9))
    with pytest.raises(span_join.JoinError, match="too few"):
        span_join.join(batches[:3], runs, (-100.05, -99.9))
    # start_trace returned 1 s before the joined clock has the profile start.
    with pytest.raises(span_join.JoinError, match="0 pairings fit the clock"):
        span_join.join(batches, runs, (-101.05, -101.0))
    with pytest.raises(LookupError):
        span_join.device_runs(modules, "^jit_step")


def _state(recorded, cell="inception_v3.saturated"):
    ctx = types.SimpleNamespace(traced=recorded.trace)
    t_on, t_off = recorded.trace.host_span
    return {"ctx": ctx, "cell": {"name": cell},
            "run": {"window": {"t_start": t_on - 0.5, "t_close": t_off + 0.5}}}


@pytest.mark.parametrize("pair,d2s_ms", [("recorded", (120, 160)), ("recorded_depth3", (240, 270))])
def test_reader_reads_the_kept_ring_and_nothing_without_it(pair, d2s_ms, request, monkeypatch):
    from flink_tensorflow_tpu.tracing import flight

    recorded = request.getfixturevalue(pair)

    ring = flight.FlightRecorder()
    for ev in recorded.doc["events"]:
        ring._ring.append(tuple(ev))
    ring.record("offered.0", "park.overslept", {"asked_s": 0.002, "slept_s": 0.302},
                t0=recorded.trace.host_span[0] + 1.0)
    monkeypatch.setattr(flight, "_kept", ("inception_v3.saturated", ring))
    state = _state(recorded)
    d2s = spans_reader.read(state, what="in_flight_ms", part="dispatch_to_start", module=SERVE)
    assert d2s_ms[0] < d2s < d2s_ms[1]
    shares = {of: spans_reader.read(state, what="idle_share", of=of, module=SERVE)
              for of in ("ingest", "emit", "in_flight", "rest")}
    idle = 100.0 * recorded.trace.idle_share()
    assert sum(shares.values()) == pytest.approx(idle, abs=0.01)  # in-program idle is 0.0007 points
    assert shares["in_flight"] == pytest.approx(idle, abs=0.01) and 0 <= shares["rest"] < 1e-3
    assert 5 < spans_reader.read(state, what="span_ms", span="emit", stat="p95") < 20
    assert 0 < spans_reader.read(state, what="span_ms", span="handoff_wait", stat="p95") < 5
    assert spans_reader.read(state, what="overslept_ms_max", track="offered.0") == pytest.approx(300.0)
    assert spans_reader.read(state, what="overslept_ms_max", track="model.0") == 0.0
    with pytest.raises(ValueError):
        spans_reader.read(state, what="no_such_reading", module=SERVE)
    # Another job's ring, or a program that has no accessor (the parent of PR 27): nothing to read.
    assert spans_reader.read(_state(recorded, "inception_v3.paced"), what="span_ms", span="emit") is None
    monkeypatch.delattr(flight, "recorder_of")
    assert spans_reader.read(_state(recorded), what="idle_share", of="ingest", module=SERVE) is None


def test_a_run_that_breaks_causality_fails_the_reader(recorded, monkeypatch):
    from flink_tensorflow_tpu.tracing import flight

    ring = flight.FlightRecorder()
    for ev in recorded.doc["events"]:
        ev = list(ev)
        if ev[1] == "in_flight" and ev[5]["seq"] % 2:
            ev[3] += 0.150  # every other batch dispatched 150 ms late: after its program began
            ev[4] -= 0.150
        ring._ring.append(tuple(ev))
    monkeypatch.setattr(flight, "_kept", ("inception_v3.saturated", ring))
    with pytest.raises(span_join.JoinError):
        spans_reader.read(_state(recorded), what="idle_share", of="ingest", module=SERVE)
