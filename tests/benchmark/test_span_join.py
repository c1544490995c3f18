"""The join of the program's window-level spans to the device trace's clock:
on a pair recorded on the chip for PR 27 (the flight ring's spans beside the
device trace of the same run), and on tables whose answers are known by hand."""

import gzip
import json
import os
import statistics
import types

import pytest

from benchmark import span_join, trace_reduce
from benchmark.readers import spans as spans_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RECORDED = os.path.join(ROOT, "tests", "benchmark", "data", "span_pair_saturated.json.gz")
SERVE = r"^jit_call\b"
MS = 1e-3


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        doc = json.load(f)
    trace = trace_reduce.Trace([tuple(r) for r in doc["rows"]])
    trace.host_span = tuple(doc["host_span"])
    spans = span_join.spans_of([tuple(e) for e in doc["events"]], "model.0")
    batches = span_join.batches_inside(spans, *trace.host_span)
    runs = span_join.device_runs(trace.module_events[0], SERVE)
    return types.SimpleNamespace(doc=doc, trace=trace, spans=spans, batches=batches, runs=runs)


def test_recorded_pair_joins_with_the_offset_in_every_bracket(recorded):
    r = recorded
    assert (len(r.batches), len(r.runs)) == (41, 43)  # a run more at either edge of the span
    joined = span_join.join(r.batches, r.runs, r.trace.host_span[0])
    assert joined["shift"] == 1
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
    # move by a period); its tight edge carries the period's jitter.
    runs = recorded.runs[:-2] if drop == "last" else recorded.runs[2:]
    tight, loose = span_join.edges(recorded.batches, runs)
    assert max(tight) <= min(loose)
    with pytest.raises(span_join.JoinError, match="cannot tell which run served which batch"):
        span_join.join(recorded.batches, runs, recorded.trace.host_span[0])


def test_a_planted_skew_of_five_ms_raises_with_the_numbers(recorded):
    joined = span_join.join(recorded.batches, recorded.runs, recorded.trace.host_span[0])
    batch, (start, _) = joined["pairs"][7]
    skewed = [dict(b) for b in recorded.batches]
    # Its program now starts 5 ms before it was dispatched, on the joined clock.
    skewed[7]["dispatched"] = start - joined["offset"] + 5 * MS
    with pytest.raises(span_join.JoinError, match=rf"seq {batch['seq']} breaks causality by 5\.\d+ ms"):
        span_join.join(skewed, recorded.runs, recorded.trace.host_span[0])
    # Within the slack it passes.
    skewed[7]["dispatched"] = start - joined["offset"] + 0.5 * MS
    assert span_join.join(skewed, recorded.runs, recorded.trace.host_span[0])["shift"] == 1


def test_bookings_sum_to_the_between_program_idle_time(recorded):
    r = recorded
    joined = span_join.join(r.batches, r.runs, r.trace.host_span[0])
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
    joined = span_join.join(batches, runs, trace_on=-99.0)
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
        span_join.join(batches, runs[:3], trace_on=-99.0)
    with pytest.raises(span_join.JoinError, match="too few"):
        span_join.join(batches[:3], runs, trace_on=-99.0)
    with pytest.raises(span_join.JoinError, match="after start_trace had returned"):
        span_join.join(batches, runs, trace_on=-101.0)
    with pytest.raises(LookupError):
        span_join.device_runs(modules, "^jit_step")


def _state(recorded, cell="inception_v3.saturated"):
    ctx = types.SimpleNamespace(traced=recorded.trace)
    t_on, t_off = recorded.trace.host_span
    return {"ctx": ctx, "cell": {"name": cell},
            "run": {"window": {"t_start": t_on - 0.5, "t_close": t_off + 0.5}}}


def test_reader_reads_the_kept_ring_and_nothing_without_it(recorded, monkeypatch):
    from flink_tensorflow_tpu.tracing import flight

    ring = flight.FlightRecorder()
    for ev in recorded.doc["events"]:
        ring._ring.append(tuple(ev))
    ring.record("offered.0", "park.overslept", {"asked_s": 0.002, "slept_s": 0.302},
                t0=recorded.trace.host_span[0] + 1.0)
    monkeypatch.setattr(flight, "_kept", ("inception_v3.saturated", ring))
    state = _state(recorded)
    d2s = spans_reader.read(state, what="in_flight_ms", part="dispatch_to_start", module=SERVE)
    assert 120 < d2s < 160
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
