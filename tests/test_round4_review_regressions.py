"""Pins for defects uncovered by the round-4 in-session reviews
(VERDICT r3 #8: pin anything the round's work uncovers).

1. ``_steady_rps`` trailing exclusion: the end-of-input flush burst
   (last pipeline-depth windows completing together) must leave the
   measured span, and a small run (records < 2*batch) must clamp the
   exclusion instead of indexing past the arrivals list.
2. ``_delta_timing``: the shared probe-timing helper widens the K
   spread once when round-trip variance inverts the delta, and reports
   degenerate (never a negative rate) when even the widened spread
   inverts.
3. Stage stamps tile: the per-record stage boundaries stamped by the
   runner must telescope exactly to t0..t_done — the decomposition's
   "nothing unexplained" invariant.
4. The per-sample decomposition must not double-count assemble time
   (lane_wait INCLUDES it; h2d_dispatch is the launch interval proper).
"""

import numpy as np

import bench


class TestSteadyRps:
    def test_trailing_exclusion_shrinks_span(self):
        arrivals = [i * 0.01 for i in range(100)]
        rps, span = bench._steady_rps(arrivals, 100, 10, 1,
                                      trailing_exclude=30)
        assert abs(span - (arrivals[69] - arrivals[0])) < 1e-9
        assert abs(rps - 60 / span) < 1e-6

    def test_small_run_clamps_instead_of_crashing(self):
        """records_n < 2*batch: the caller's max(0, ...) clamp pattern
        must yield a working zero exclusion."""
        arrivals = [i * 0.01 for i in range(100)]
        records_n, batch, depth = 100, 64, 6
        trailing = max(0, min(depth * batch, records_n - 2 * batch))
        assert trailing == 0
        rps, span = bench._steady_rps(arrivals, records_n, batch, 1,
                                      trailing_exclude=trailing)
        assert rps > 0 and span > 0

    def test_too_few_records_raises(self):
        import pytest

        with pytest.raises(ValueError, match="more windows"):
            bench._steady_rps([0.0, 0.1], 2, 1, 1, trailing_exclude=1)


class TestDeltaTiming:
    def test_clean_delta(self):
        import time as _time

        base = [0.0]

        def fake_monotonic():
            return base[0]

        def run(k):
            base[0] += {2: 0.1, 12: 0.6}[k]

        _time.monotonic, saved = fake_monotonic, _time.monotonic
        try:
            per, degenerate, k2 = bench._delta_timing(run, 2, 12)
            assert not degenerate
            assert abs(per - 0.05) < 1e-9
            assert k2 == 12
        finally:
            _time.monotonic = saved

    def test_inverted_delta_widens_then_degenerates(self):
        import time as _time

        base = [0.0]

        def fake_monotonic():
            return base[0]

        # k=2 takes LONGER than any larger k (inverted medians — the
        # round-trip-variance pathology): widened once, then degenerate.
        def run(k):
            base[0] += 0.5 if k == 2 else 0.1

        _time.monotonic, saved = fake_monotonic, _time.monotonic
        try:
            per, degenerate, k2 = bench._delta_timing(run, 2, 12)
            assert degenerate
            assert k2 == 48  # widened exactly once
        finally:
            _time.monotonic = saved

    def test_widening_can_recover(self):
        import time as _time

        base = [0.0]

        def fake_monotonic():
            return base[0]

        # Inverted at k=12 but recovers at the widened k=48.
        def run(k):
            base[0] += {2: 0.3, 12: 0.25, 48: 2.3}[k]

        _time.monotonic, saved = fake_monotonic, _time.monotonic
        try:
            per, degenerate, k2 = bench._delta_timing(run, 2, 12)
            assert not degenerate and k2 == 48
            assert abs(per - (2.3 - 0.3) / 46) < 1e-9
        finally:
            _time.monotonic = saved


class TestCapToPeak:
    @staticmethod
    def _rewrite(o, rate):
        o["rate"] = round(rate, 1) if rate is not None else None

    def test_valid_probe_untouched(self):
        out = {"achieved_tflops": 80.0, "mfu_pct": 40.6, "rate": 7000.0}
        got = bench._cap_to_peak(dict(out), False, 197.0, 11e9, self._rewrite)
        assert got == out

    def test_above_peak_capped_and_flagged(self):
        out = {"achieved_tflops": 500.0, "mfu_pct": 253.0, "rate": 45000.0}
        got = bench._cap_to_peak(out, False, 197.0, 11e9, self._rewrite)
        assert got["probe_invalid_capped_to_peak"] is True
        assert got["achieved_tflops"] == 197.0 and got["mfu_pct"] == 100.0
        assert abs(got["rate"] - round(197e12 / 11e9, 1)) < 0.2

    def test_degenerate_without_peak_withholds(self):
        out = {"achieved_tflops": 0.0, "mfu_pct": None, "rate": 1.0}
        got = bench._cap_to_peak(out, True, None, 11e9, self._rewrite)
        assert got["probe_invalid_capped_to_peak"] is True
        assert got["rate"] is None and got["achieved_tflops"] is None


class TestStageTiling:
    def test_stage_boundaries_telescope(self):
        """The runner's stamps must tile t0..t_done with no overlap and
        no gap — and lane_wait must CONTAIN assemble (the review found a
        double-count where h2d_dispatch re-added assemble_s)."""
        import jax

        from flink_tensorflow_tpu.functions.runner import CompiledMethodRunner
        from flink_tensorflow_tpu.models import get_model_def
        from flink_tensorflow_tpu.tensors import (
            BucketLadder,
            BucketPolicy,
            TensorValue,
        )

        mdef = get_model_def("lenet", num_classes=10)
        model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
        from flink_tensorflow_tpu.tracing.flight import FlightRecorder, SpanHook

        r = CompiledMethodRunner(
            model, policy=BucketPolicy(batch=BucketLadder.up_to(4)),
            dispatch_lanes=2)
        r.open(None)
        try:
            r.warmup([1, 2, 4])
            ring = FlightRecorder()
            r._spans, r._trace_track = SpanHook(ring), "lenet.0"
            rng = np.random.RandomState(0)
            r.run_batch([
                TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)})
                for _ in range(3)
            ])
            # The cuts the stamps carried, read from the batch's spans.
            st = {e[1]: (e[3], e[3] + e[4], e[5]) for e in ring.events()}
            t0, t_lane_start, lane = st["lane_wait"]
            t_lane_start_2, t_dispatched, _ = st["enqueue"]
            t_dispatched_2, t_done, fly = st["in_flight"]
            t_fetch_start = t_dispatched_2 + fly["fetch_reached_s"]
            # Boundaries are monotone and the intervals tile exactly.
            assert t0 <= t_lane_start <= t_dispatched
            assert t_dispatched_2 <= t_fetch_start <= t_done + 1e-6
            assert abs(t_lane_start_2 - t_lane_start) < 1e-9
            assert abs(t_dispatched_2 - t_dispatched) < 1e-9
            tiled = ((t_lane_start - t0) + (t_dispatched - t_lane_start_2)
                     + (t_done - t_dispatched_2))
            assert abs(tiled - (t_done - t0)) < 1e-9
            # assemble happens INSIDE the lane interval, not after it.
            assert 0 < lane["assemble_s"] <= t_lane_start - t0 + 1e-9
        finally:
            r.close()
