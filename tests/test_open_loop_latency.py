"""Open-loop latency machinery (VERDICT r3 #1).

The r3 bench's unexplained 536ms open-loop p50 decomposed into three
framework defects, each pinned here:

1. Results were only emitted by a BLOCKING flush (idle-flush timer) or
   by the pipeline-depth drain — the subtask thread parked for whole
   device round trips.  ``CompiledMethodRunner.collect_batches`` now
   collects exactly the batches the fetch thread has completed, never
   blocking, and ``ModelWindowFunction.fire_due`` polls it.
2. The adaptive trigger ignored service time: an end-to-end budget was
   spent entirely on holds.  ``observe_service_time`` (fed by
   WindowOperator from the runner's EWMA) reserves the round trip out
   of the budget — clamped to one expected gap so the reserve can never
   collapse windows to batch-1 (whose per-call overhead sinks below
   offered rates and the queue collapses).
3. Nothing attributed latency to stages.  The runner stamps per-record
   cuts as window-level spans (tracing/flight.py) and the window operator
   stamps arrival (``__arrive_ts__``) when the function opts in.
"""

import time

import numpy as np
import pytest

from flink_tensorflow_tpu.core.windows import AdaptiveLatencyTrigger, WindowBuffer
from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner
from flink_tensorflow_tpu.tensors import BucketLadder, BucketPolicy, TensorValue


def _lenet_runner(**kw):
    import jax

    from flink_tensorflow_tpu.models import get_model_def

    mdef = get_model_def("lenet", num_classes=10)
    model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
    r = CompiledMethodRunner(
        model, policy=BucketPolicy(batch=BucketLadder.up_to(8)), **kw)
    r.open(None)
    r.warmup([1, 2, 4, 8])
    return r


def _with_spans(r, track="lenet.0"):
    """Give a bare runner a span hook; returns the ring it writes to."""
    from flink_tensorflow_tpu.tracing.flight import FlightRecorder, SpanHook

    ring = FlightRecorder()
    r._spans, r._trace_track = SpanHook(ring), track
    return ring


def _span(ring, name, seq=None):
    """The one span ``name`` (of batch ``seq``) as (t0, t1, args)."""
    (ev,) = [e for e in ring.events() if e[1] == name
             and (seq is None or e[5]["seq"] == seq)]
    return ev[3], ev[3] + ev[4], ev[5]


def _collected(runner, max_in_flight=None):
    """``runner.collect_batches(max_in_flight)`` as one list of records."""
    return [r for _, records in runner.collect_batches(max_in_flight) for r in records]


def _recs(n):
    rng = np.random.RandomState(0)
    return [
        TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)},
                    {"id": i})
        for i in range(n)
    ]


class TestCollectAvailable:
    def test_collects_ready_batches_without_blocking(self):
        r = _lenet_runner()
        try:
            r.dispatch(_recs(2))
            deadline = time.monotonic() + 10.0
            out = []
            while not out and time.monotonic() < deadline:
                out = _collected(r)
                time.sleep(0.002)
            assert len(out) == 2
            assert r.in_flight() == 0 and r.oldest_pending_age_s() is None
        finally:
            r.close()

    def test_returns_empty_when_nothing_pending(self):
        r = _lenet_runner()
        try:
            assert _collected(r) == []
            assert r.oldest_pending_age_s() is None
        finally:
            r.close()

    def test_preserves_fifo_order(self):
        r = _lenet_runner()
        try:
            recs = _recs(6)
            for i in range(0, 6, 2):
                r.dispatch(recs[i:i + 2])
            deadline = time.monotonic() + 10.0
            out = []
            while len(out) < 6 and time.monotonic() < deadline:
                out.extend(_collected(r))
                time.sleep(0.002)
            assert [v.meta["id"] for v in out] == list(range(6))
        finally:
            r.close()

    def test_lane_failure_surfaces_through_fetch(self):
        r = _lenet_runner()
        try:
            bad = TensorValue({"image": np.zeros((7, 7, 1), np.float32)})
            r.dispatch([bad])  # wrong shape: lane raises during assemble
            deadline = time.monotonic() + 10.0
            with pytest.raises(Exception):
                while time.monotonic() < deadline:
                    _collected(r)
                    time.sleep(0.002)
                raise AssertionError("lane failure never surfaced")
        finally:
            r._pending.clear()
            r._pending_t0.clear()
            r.close()

    def test_service_ewma_updates_on_fetch(self):
        r = _lenet_runner()
        try:
            assert r.service_ewma_s is None
            r.run_batch(_recs(2))
            assert r.service_ewma_s is not None and r.service_ewma_s > 0
        finally:
            r.close()

    def test_stage_spans_of_a_batch(self):
        """The cuts the per-record stamps carried, read from the batch's
        spans: lane_wait -> enqueue -> in_flight -> unbatch, one of each,
        sharing the batch's seq."""
        r = _lenet_runner()
        ring = _with_spans(r)
        try:
            out = r.run_batch(_recs(3))
            assert len(out) == 3
            seq = r.dispatched_seq
            t0, t_lane_start, lane = _span(ring, "lane_wait", seq)
            _, t_dispatched, enq = _span(ring, "enqueue", seq)
            t_disp, t_done, fly = _span(ring, "in_flight", seq)
            assert lane["batch"] == enq["batch"] == fly["batch"] == 3
            assert t_lane_start - t0 >= 0
            assert t0 <= t_lane_start <= t_dispatched + 1e-9
            assert abs(t_disp - t_dispatched) < 1e-9
            # Where the fetch thread reached the batch is a number now.
            assert 0 <= fly["fetch_reached_s"] <= t_done - t_disp + 1e-6
            _, _, unb = _span(ring, "unbatch", seq)
            assert unb["records"] == 3
            assert _span(ring, "handoff_wait", seq)[0] >= t_done
        finally:
            r.close()

    def test_no_hook_records_nothing_and_meta_is_untouched(self):
        r = _lenet_runner()
        try:
            assert r._spans is None
            out = r.run_batch(_recs(1))
            assert out[0].meta == {"id": 0}
        finally:
            r.close()


class TestServiceReserve:
    @staticmethod
    def _warm_trigger(count=16, budget=0.3, gap=0.1):
        """Trigger with a converged gap EWMA of ``gap`` seconds."""
        trig = AdaptiveLatencyTrigger(count, budget)
        trig._gap_ewma = gap
        return trig

    def test_reserve_pulls_deadline_forward(self):
        trig = self._warm_trigger(budget=0.5, gap=0.05)
        buf = WindowBuffer(window=None)
        buf.add("a", None)
        trig._last_arrival = buf.first_element_time
        base = trig.deadline(buf)  # nagle: last + gap
        trig.observe_service_time(0.4)
        reserved = trig.deadline(buf)
        # hard - service = first + 0.1 > first + gap(0.05): the reserve
        # binds but stays above the one-gap clamp.
        assert reserved <= base + 1e-9
        assert reserved >= buf.first_element_time + 0.05 - 1e-9

    def test_reserve_clamped_to_one_gap(self):
        """Service time >= budget must NOT mean fire-at-once: the clamp
        keeps the Nagle gap so windows never collapse to batch-1."""
        trig = self._warm_trigger(budget=0.3, gap=0.08)
        buf = WindowBuffer(window=None)
        buf.add("a", None)
        trig._last_arrival = buf.first_element_time
        trig.observe_service_time(2.0)  # round trip alone eats the budget
        d = trig.deadline(buf)
        assert d >= buf.first_element_time + 0.08 - 1e-9

    def test_no_feedback_is_r3_behavior(self):
        trig = self._warm_trigger(budget=0.3, gap=0.05)
        buf = WindowBuffer(window=None)
        buf.add("a", None)
        trig._last_arrival = buf.first_element_time
        assert trig.deadline(buf) == pytest.approx(
            min(buf.first_element_time + 0.3,
                trig._last_arrival + 0.05))

    def test_clone_does_not_share_estimators(self):
        trig = self._warm_trigger()
        trig.observe_service_time(1.0)
        dup = trig.clone()
        assert dup._service_ewma is None and dup._gap_ewma is None

    def test_operator_feeds_service_time(self):
        """WindowOperator wires function.service_time_estimate into
        trigger.observe_service_time on the hot path."""
        from flink_tensorflow_tpu.core.operators import Output, WindowOperator
        from flink_tensorflow_tpu.core.state import KeyedStateStore
        from flink_tensorflow_tpu.core import elements as el
        from flink_tensorflow_tpu.core import functions as fn

        class Svc(fn.WindowFunction):
            def service_time_estimate(self):
                return 0.123

            def process_window(self, key, window, elements, out):
                pass

        trig = AdaptiveLatencyTrigger(16, 0.3)
        op = WindowOperator("w", Svc(), trig)
        op.setup(None, Output([(None, [])]), KeyedStateStore())
        op.open()
        op.process_record(el.StreamRecord("x"))
        assert op.trigger._service_ewma == 0.123


class TestArrivalStamp:
    """The arrival of a window's first record is the start of its
    ``fill`` span; no record's metadata is stamped."""

    def _driven_op(self, spans):
        import jax

        from flink_tensorflow_tpu.core import functions as fn
        from flink_tensorflow_tpu.core.operators import Output, WindowOperator
        from flink_tensorflow_tpu.core.runtime_context import RuntimeContext
        from flink_tensorflow_tpu.core.state import KeyedStateStore
        from flink_tensorflow_tpu.core.windows import CountTrigger
        from flink_tensorflow_tpu.functions import ModelWindowFunction
        from flink_tensorflow_tpu.metrics.registry import MetricRegistry
        from flink_tensorflow_tpu.models import get_model_def

        mdef = get_model_def("lenet", num_classes=10)
        model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
        func = ModelWindowFunction(model, policy=BucketPolicy(fixed_batch=4))
        got = []
        op = WindowOperator("w", func, CountTrigger(4))
        state = KeyedStateStore()
        ctx = RuntimeContext("w", 0, 1, state, MetricRegistry().group("w.0"))
        ctx.spans = spans
        op.setup(ctx, Output([(None, [])]), state)
        op.open()
        op._collector = fn.Collector(lambda value, ts: got.append(value))
        return op, got

    def test_fill_span_starts_at_the_first_arrival(self):
        from flink_tensorflow_tpu.core import elements as el
        from flink_tensorflow_tpu.tracing.flight import FlightRecorder, SpanHook

        ring = FlightRecorder()
        op, got = self._driven_op(SpanHook(ring))
        try:
            recs = _recs(4)
            before = time.monotonic()
            op.process_record(el.StreamRecord(recs[0]))
            after = time.monotonic()
            for r in recs[1:]:
                op.process_record(el.StreamRecord(r))
            fired = time.monotonic()
            op.finish()
            t0, t1, args = _span(ring, "fill")
            assert before <= t0 <= after
            assert after <= t1 <= fired
            assert args["records"] == 4 and args["seq"] == _span(ring, "fire")[2]["seq"]
            # The input record objects stay untouched — they may fan out to
            # sibling operators or be retained by a sliding trigger.
            assert [r.meta for r in recs] == [{"id": i} for i in range(4)]
            assert [v.meta for v in got] == [{"id": i} for i in range(4)]
        finally:
            op.close()

    def test_no_stamp_and_no_span_without_a_hook(self):
        from flink_tensorflow_tpu.core import elements as el

        op, got = self._driven_op(None)
        try:
            for r in _recs(4):
                op.process_record(el.StreamRecord(r))
            op.finish()
            assert [v.meta for v in got] == [{"id": i} for i in range(4)]
            assert op.function._fill_t0 is None  # closed at the fire
        finally:
            op.close()

class TestAsyncMapPolling:
    def test_partial_batch_dispatches_and_emits_via_poll(self):
        """The async map's idle deadline must dispatch the partial
        micro-batch and surface its results through the non-blocking
        poll — without end-of-input and without reaching the pipeline
        depth (the map-path twin of the windowed fix)."""
        import jax

        from flink_tensorflow_tpu.functions import ModelMapFunction
        from flink_tensorflow_tpu.models import get_model_def
        from flink_tensorflow_tpu.core import functions as fn

        mdef = get_model_def("lenet", num_classes=10)
        model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
        f = ModelMapFunction(model, micro_batch=8, idle_flush_s=0.005)
        emitted = []
        out = fn.Collector(lambda v, ts=None: emitted.append(v))
        f.open(None)
        try:
            for r in _recs(3):  # partial: under the micro_batch of 8
                f.map_async(r, out)
            assert f._buf, "partial batch should still be buffered"
            deadline = time.monotonic() + 10.0
            while len(emitted) < 3 and time.monotonic() < deadline:
                d = f.next_deadline()
                if d is not None:
                    time.sleep(max(0.0, min(d - time.monotonic(), 0.01)))
                    f.fire_due(time.monotonic())
            assert len(emitted) == 3
            assert not f._buf and not f.runner.in_flight()
        finally:
            f.close()


class TestPollingEmission:
    def test_window_results_emitted_by_poll_not_depth(self):
        """One fired window's results must surface via the fire_due poll
        loop well before pipeline-depth batches accumulate and without
        end-of-input."""
        import jax

        from flink_tensorflow_tpu.functions import ModelWindowFunction
        from flink_tensorflow_tpu.models import get_model_def
        from flink_tensorflow_tpu.core import functions as fn

        mdef = get_model_def("lenet", num_classes=10)
        model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
        svc = ModelWindowFunction(
            model, policy=BucketPolicy(batch=BucketLadder.up_to(8)),
            warmup_batches=(1, 2, 4, 8),
            pipeline_depth=8, idle_flush_s=0.005)
        emitted = []
        out = fn.Collector(lambda v, ts=None: emitted.append(v))
        svc.open(None)
        try:
            svc._out = out
            svc.process_window(None, None, _recs(2), out)
            # Poll as the subtask loop would: deadline-driven fire_due.
            deadline = time.monotonic() + 10.0
            while not emitted and time.monotonic() < deadline:
                d = svc.next_deadline()
                if d is not None:
                    time.sleep(max(0.0, min(d - time.monotonic(), 0.01)))
                    svc.fire_due(time.monotonic())
            assert len(emitted) == 2
            assert not svc.runner.in_flight()  # drained, not stuck at depth
        finally:
            svc.close()
