"""Window-level spans of the model and train operators' hot paths
(tracing/flight.py): one hook a subtask, always on in the flight ring, one
event a window and span kind, none a record; the subtask thread's spans tile
its time; a batch's spans share its seq; the ring outlives the job's release;
with the ring and the tracer off the hooks allocate nothing; a park that
returns late leaves ``park.overslept``; a span whose two stamps are read on one
thread says what the OS charged that thread between them; the registry reports
every name the benchmark's ``counters`` metrics read
(``benchmark/layer_metrics/*.json``).

All tier-1 fast — no TPU, LeNet on tiny windows.
"""

import collections
import gc
import json
import pathlib
import time
import tracemalloc

import numpy as np
import pytest

from flink_tensorflow_tpu import StreamExecutionEnvironment
from flink_tensorflow_tpu.functions import ModelWindowFunction
from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue
from flink_tensorflow_tpu.tracing import flight
from flink_tensorflow_tpu.tracing.flight import FlightRecorder, SpanHook, recorder_of

REPO = pathlib.Path(__file__).resolve().parents[1]
WINDOW, WINDOWS = 64, 3
#: The spans every batch leaves on the model's track, by thread.
SUBTASK = ("fill", "fire", "collect_wait", "emit")
OFF_THREAD = ("lane_wait", "enqueue", "in_flight", "unbatch", "handoff_wait")


@pytest.fixture(scope="module")
def lenet():
    import jax

    from flink_tensorflow_tpu.models import get_model_def

    mdef = get_model_def("lenet", num_classes=10)
    return mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))


def _records(n):
    rng = np.random.RandomState(0)
    return [TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32)}, {"id": i})
            for i in range(n)]


def _job(model, name, *, sink=None, source=None, source_name="paced", **cfg):
    """records -> count_window(64) -> LeNet -> sink, three windows."""
    env = StreamExecutionEnvironment(parallelism=1)
    if cfg:
        env.configure(**cfg)
    out = []
    stream = (env.from_source(source, name=source_name, parallelism=1) if source is not None
              else env.from_collection(_records(WINDOW * WINDOWS)))
    (stream.count_window(WINDOW)
     .apply(ModelWindowFunction(model, policy=BucketPolicy(fixed_batch=WINDOW),
                                warmup_batches=(WINDOW,)), name="model", parallelism=1)
     .sink_to_callable(sink or out.append))
    handle = env.execute_async(name)
    handle.wait(120)
    return handle, out


def _paced_source():
    from flink_tensorflow_tpu.sources import PacedSplitSource

    return PacedSplitSource(_records(WINDOW * WINDOWS), 2000.0, jitter="none", num_splits=1)


def _model_events(events):
    return [e for e in events if e[0] == "model.0"]


def test_one_event_a_window_and_span_kind_and_none_a_record(lenet):
    handle, out = _job(lenet, "spans-count")
    assert len(out) == WINDOW * WINDOWS
    events = _model_events(handle.executor.flight.events())
    kinds = collections.Counter(e[1] for e in events if e[2] == "X")
    for name in SUBTASK[:2] + OFF_THREAD + ("emit",):
        assert kinds[name] == WINDOWS, (name, kinds)
    # A blocking collection is at most one stretch a fire and one at the end.
    assert kinds["collect_wait"] <= WINDOWS + 1
    assert kinds["open"] == kinds["params_to_device"] == kinds["jit_warmup_compile"] == 1
    # Nothing scales with the 192 records: a handful of events a window.
    assert len(events) <= 12 * WINDOWS + 6
    fills = [e[5] for e in events if e[1] == "fill"]
    assert [a["records"] for a in fills] == [WINDOW] * WINDOWS
    # The same stretches feed the operator's timers, one update a window.
    metrics = handle.executor.metrics.report()
    for timer in ("ingest_s", "emit_s", "unbatch_s", "handoff_wait_s"):
        assert metrics[f"model.0.{timer}"]["count"] == WINDOWS, timer
    assert metrics["model.0.open_s"]["count"] == 1
    assert "model.0.assemble_s" not in metrics  # the ring path assembles nothing
    assert metrics["model.0.ingest_s"]["total_s"] == pytest.approx(
        sum(a["self_s"] for a in fills))


def test_every_span_of_a_batch_shares_its_seq(lenet):
    handle, _ = _job(lenet, "spans-seq")
    by_seq = collections.defaultdict(collections.Counter)
    for e in _model_events(handle.executor.flight.events()):
        if e[2] == "X" and e[5] and "seq" in e[5] and e[1] != "collect_wait":
            by_seq[e[5]["seq"]][e[1]] += 1
    # The warm-up batch took seq 1 and leaves no span.
    assert sorted(by_seq) == [2, 3, 4]
    for seq, names in by_seq.items():
        assert names == {name: 1 for name in ("fill", "fire", "emit") + OFF_THREAD}, (seq, names)


def _union(intervals):
    total, reach = 0.0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total, reach = total + b - a, b
        elif b > reach:
            total, reach = total + b - reach, b
    return total


def _tiling_gap(events):
    """Share of the subtask thread's wall time, first fill to last fire,
    that its spans and parks leave uncovered; and the widest share by which
    a fill differs from its self time + children + parks."""
    spans = [(e[1], e[3], e[3] + e[4], e[5]) for e in events
             if e[2] == "X" and e[1] in SUBTASK]
    fills = [s for s in spans if s[0] == "fill"]
    start, end = min(s[1] for s in fills), max(s[2] for s in spans if s[0] == "fire")
    covered = _union((a, b) for _, a, b, _ in spans if b > start and a < end)
    covered += sum(f[3]["park_before_s"] for f in fills[1:])
    worst = 0.0
    for _, a, b, args in fills:
        children = _union((max(x, a), min(y, b)) for name, x, y, _ in spans
                          if name in ("emit", "collect_wait") and y > a and x < b)
        parts = args["self_s"] + children + args["park_s"]
        worst = max(worst, abs(parts - (b - a)) / (b - a))
    return 1.0 - covered / (end - start), worst


def test_subtask_spans_tile_its_wall_time_within_two_percent(lenet):
    # A sink that takes a millisecond a record makes a window long enough
    # (70 ms) for 2% to stand clear of the scheduler; a worker kept off its
    # core between two spans is still a gap, so the best of three counts.
    gaps = []
    for attempt in range(3):
        handle, _ = _job(lenet, f"spans-tile-{attempt}", sink=lambda r: time.sleep(0.001))
        gaps.append(_tiling_gap(_model_events(handle.executor.flight.events())))
        if max(gaps[-1]) < 0.02:
            break
    uncovered, fill_error = min(gaps, key=max)
    assert -1e-6 <= uncovered < 0.02, gaps
    assert fill_error < 0.02, gaps


def test_ring_outlives_the_release_of_the_job(lenet):
    handle, out = _job(lenet, "spans-kept")
    n = len(handle.executor.flight.events())
    del handle, out
    gc.collect()
    ring = recorder_of("spans-kept")
    assert ring is not None and len(ring.events()) == n
    assert {e[1] for e in ring.events()} >= set(SUBTASK + OFF_THREAD)
    assert recorder_of("some-other-job") is None
    # One job kept: the next one replaces it.
    _job(lenet, "spans-next")
    assert recorder_of("spans-kept") is None and recorder_of("spans-next") is not None


def test_ring_still_holds_the_first_seconds_of_a_minute(lenet):
    # 80 events/s for 51 s, then as many again: the default ring keeps them.
    ring = FlightRecorder()
    hook = SpanHook(ring)
    for i in range(2 * 80 * 51):
        hook.span("model.0", "fill", float(i), float(i) + 0.5)
    assert ring.capacity == flight.DEFAULT_CAPACITY >= 2 * 80 * 51
    assert ring.events()[0][3] == 0.0


def test_off_path_allocates_nothing_in_the_hooks(lenet):
    import flink_tensorflow_tpu.tracing.attribution  # noqa: F401 - before tracemalloc

    tracemalloc.start()
    try:
        handle, out = _job(lenet, "spans-off", flight_recorder=False)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    assert len(out) == WINDOW * WINDOWS
    assert handle.executor.flight is None and handle.executor.tracer is None
    assert all(st.spans is None for st in handle.executor.subtasks)
    # No pulse and no thread's account either: both hang on the ring.
    assert handle.executor.pulse is None
    assert all(st.account is None for st in handle.executor.subtasks)
    assert recorder_of("spans-off") is None
    pkg = str(REPO / "flink_tensorflow_tpu" / "tracing")
    stats = snap.filter_traces([tracemalloc.Filter(True, pkg + "/*")]).statistics("filename")
    assert sum(s.size for s in stats) == 0, stats
    # The timers are fed all the same.
    assert handle.executor.metrics.report()["model.0.ingest_s"]["count"] == WINDOWS


def test_tracer_gets_the_same_spans_when_it_is_on(lenet):
    handle, _ = _job(lenet, "spans-traced", trace=True)
    ring = [e for e in _model_events(handle.executor.flight.events()) if e[2] == "X"]
    traced = [e for e in _model_events(handle.executor.tracer.events())
              if e[2] == "X" and e[1] in SUBTASK + OFF_THREAD + ("open",)]
    assert sorted(e[:5] for e in traced) == sorted(
        e[:5] for e in ring if e[1] in SUBTASK + OFF_THREAD + ("open",))


class TestParkOverslept:
    def test_hook_marks_only_a_park_that_returns_late(self):
        ring = FlightRecorder()
        hook = SpanHook(ring)
        hook.park("src.0", 0.010, 0.012, False, 1.0)   # on time
        hook.park("src.0", None, 5.0, True, 6.0)        # asked for no timeout
        hook.park("src.0", 0.010, 0.059, True, 7.0)     # 49 ms over: under the mark
        assert ring.events() == []
        hook.park("src.0", 0.010, 0.200, False, 8.0)
        (ev,) = ring.events()
        assert ev[:4] == ("src.0", "park.overslept", "i", 8.0)
        assert ev[5] == {"asked_s": 0.010, "slept_s": 0.200, "woken": False}
        seconds, count, over = hook.take_parks()
        assert (count, over) == (4, pytest.approx(0.190))
        assert seconds == pytest.approx(0.012 + 5.0 + 0.059 + 0.200)
        assert hook.take_parks() == (0.0, 0, 0.0)

    def _paced_job(self, lenet, name):
        handle, out = _job(lenet, name, source=_paced_source())
        assert len(out) == WINDOW * WINDOWS
        events = handle.executor.flight.events()
        fills = [e[5] for e in events if e[1] == "fill"]
        return [e for e in events if e[1] == "park.overslept"], fills

    def test_a_job_whose_parks_return_on_time_leaves_none(self, lenet):
        overslept, fills = self._paced_job(lenet, "parks-on-time")
        assert overslept == []
        # The source parks between records: the fills say for how long.
        assert sum(a["park_n"] for a in fills) > 0
        assert all(a["park_over_max_s"] == 0.0 for a in fills)

    def test_a_park_forced_late_is_marked_and_booked_to_its_fill(self, lenet, monkeypatch):
        from flink_tensorflow_tpu.sources.mailbox import SourceMailbox

        real, late = SourceMailbox.wait, []

        def wait(self, timeout):
            woken = real(self, timeout)
            if timeout is not None and not late:
                late.append(timeout)
                time.sleep(0.12)
            return woken

        monkeypatch.setattr(SourceMailbox, "wait", wait)
        overslept, fills = self._paced_job(lenet, "parks-late")
        assert len(overslept) == 1
        track, _, ph, _, _, args = overslept[0]
        assert (track, ph) == ("paced.0", "i")
        assert args["asked_s"] == late[0]
        assert args["slept_s"] - args["asked_s"] >= 0.1
        assert max(a["park_over_max_s"] for a in fills) >= 0.1


def _train_job(name):
    """labeled records -> count_window(32) -> the gang train operator on the
    8-device mesh -> list, four steps."""
    import optax

    from flink_tensorflow_tpu.functions import DPTrainWindowFunction
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.parallel import make_mesh
    from flink_tensorflow_tpu.tensors import RecordSchema, spec

    schema = RecordSchema({"image": spec((28, 28, 1)), "label": spec((), np.int32)})
    rng = np.random.RandomState(0)
    recs = [TensorValue({"image": rng.rand(28, 28, 1).astype(np.float32),
                         "label": np.int32(i % 4)}) for i in range(32 * 4)]
    env = StreamExecutionEnvironment(parallelism=1)
    env.set_mesh(make_mesh({"data": 8}))
    out = (env.from_collection(recs).count_window(32)
           .apply(DPTrainWindowFunction(get_model_def("lenet"), optax.adam(1e-2),
                                        train_schema=schema, global_batch=32), name="train")
           .sink_to_list())
    handle = env.execute_async(name)
    job = handle.wait(600)
    assert len(out) == 4
    return job


def test_train_step_spans_and_timers():
    """The gang train operator: four spans a step sharing its number,
    ``open`` with its two children, ``feed_s``/``drain_wait_s``/``open_s``."""
    job = _train_job("spans-train")
    events = [e for e in recorder_of("spans-train").events() if e[0] == "train.0" and e[2] == "X"]
    kinds = collections.Counter(e[1] for e in events)
    steps = ("assemble", "h2d_enqueue", "dispatch", "drain_wait")
    assert {k: kinds[k] for k in steps} == dict.fromkeys(steps, 4)
    assert kinds["open"] == kinds["init_state"] == kinds["replicate"] == 1
    for name in steps:
        assert sorted(e[5]["step"] for e in events if e[1] == name) == [1, 2, 3, 4]
        assert all(e[5]["examples"] == 32 for e in events if e[1] == name)
    # assemble -> h2d_enqueue -> dispatch abut, and sum to the step's feed.
    feed = 0.0
    for step in range(1, 5):
        a, h, d = (next(e for e in events if e[1] == n and e[5]["step"] == step) for n in steps[:3])
        assert a[3] + a[4] == pytest.approx(h[3], abs=1e-9)
        assert h[3] + h[4] == pytest.approx(d[3], abs=1e-9)
        feed += a[4] + h[4] + d[4]
    m = job.metrics
    assert m["train.0.feed_s"]["count"] == 4
    assert m["train.0.feed_s"]["total_s"] == pytest.approx(feed, rel=1e-6)
    assert m["train.0.drain_wait_s"]["count"] == 4 and m["train.0.open_s"]["count"] == 1
    (opened,) = [e for e in events if e[1] == "open"]
    assert m["train.0.open_s"]["total_s"] == pytest.approx(opened[4], rel=1e-6)


#: The spans that carry what the OS charged their thread, by job and thread;
#: ``in_flight`` starts on another thread than it ends on, so its args are the
#: fetch thread's stretch and are named for it.
CHARGED = {
    "model": ("fill", "fire", "collect_wait", "emit", "open",   # subtask thread
              "enqueue",                                          # lane thread
              "unbatch", "in_flight"),                            # fetch thread
    "train": ("assemble", "h2d_enqueue", "dispatch", "drain_wait", "open"),
}


@pytest.fixture(scope="module")
def charged_spans(lenet):
    """``{(job, span name): [(seconds, args)]}`` of one job of each kind."""
    handle, _ = _job(lenet, "spans-charged")
    _train_job("spans-charged-train")
    out = collections.defaultdict(list)
    for job, events in (("model", _model_events(handle.executor.flight.events())),
                        ("train", [e for e in recorder_of("spans-charged-train").events()
                                   if e[0] == "train.0"])):
        for _, name, ph, _, dur, args in events:
            if ph == "X":
                out[job, name].append((dur, args or {}))
    return out


@pytest.mark.parametrize("job,name", [(job, name) for job, names in CHARGED.items()
                                      for name in names])
def test_span_says_what_the_os_charged_its_thread(charged_spans, job, name):
    import os

    spans = charged_spans[job, name]
    assert spans, f"no {name} span"
    prefix = "fetch_" if name == "in_flight" else ""
    has_runq = os.access(flight.SCHEDSTAT, os.R_OK)
    for dur, args in spans:
        cpu = args[prefix + "cpu_s"]
        # The kernel books a running thread's seconds at its tick.
        assert -1e-9 <= cpu <= dur + 0.02, (name, dur, args)
        assert ((prefix + "runq_s") in args) == has_runq
        if has_runq:
            assert args[prefix + "runq_s"] >= 0.0
    if name == "in_flight":
        assert all("cpu_s" not in args for _, args in spans)  # not the whole span's


def test_spans_whose_stamps_lie_on_two_threads_carry_no_charge(charged_spans):
    for name in ("lane_wait", "handoff_wait"):
        assert charged_spans["model", name]
        assert all("cpu_s" not in args for _, args in charged_spans["model", name])


#: The benchmark's per-layer metrics that read the job's registry, from its
#: own files: a cell a later PR adds is held without an edit here.
COUNTER_METRICS = [m for m in (json.loads(p.read_text()) for p in sorted(
    (REPO / "benchmark" / "layer_metrics").glob("*.json"))) if m["reader"] == "counters"]


#: Tiny routed language models, one that holds every expert and one that holds
#: a share of them (and so also counts the passes over its share's buffers).
_ROUTED = {
    "lfm2_moe": dict(seq_len=8, vocab_size=64, hidden_size=32, intermediate_size=64,
                     moe_intermediate_size=16, num_hidden_layers=3, num_dense_layers=1,
                     layer_types=("conv", "full_attention", "conv"), num_attention_heads=4,
                     num_key_value_heads=2, num_experts=4, num_experts_per_tok=2),
    "kimi_k2": dict(seq_len=8, vocab_size=64, hidden_size=32, intermediate_size=64,
                    moe_intermediate_size=16, num_hidden_layers=2, num_attention_heads=2,
                    num_key_value_heads=2, q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
                    qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=2, router_experts=8,
                    num_experts_per_tok=2),
    "afmoe": dict(seq_len=8, vocab_size=64, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
                  num_hidden_layers=2, layer_types=("sliding_attention", "full_attention"), num_dense_layers=1,
                  num_attention_heads=2, num_key_value_heads=1, head_dim=16, sliding_window=4, num_experts=2,
                  router_experts=8, num_experts_per_tok=2),
}


def _routed_job(name, architecture):
    """token records -> count_window(2) -> a tiny routed language model ->
    list, two windows: the counters a method makes on the device."""
    import jax

    from flink_tensorflow_tpu.models import get_model_def

    mdef = get_model_def(architecture, **_ROUTED[architecture])
    rng = np.random.RandomState(0)
    recs = [TensorValue({"tokens": rng.randint(0, 64, 8).astype(np.int32)}) for _ in range(4)]
    env = StreamExecutionEnvironment(parallelism=1)
    out = (env.from_collection(recs).count_window(2)
           .apply(ModelWindowFunction(mdef.to_model(mdef.init_fn(jax.random.key(0))),
                                      policy=BucketPolicy(fixed_batch=2), warmup_batches=(2,),
                                      outputs=("label",)), name="model", parallelism=1)
           .sink_to_list())
    job = env.execute(name, timeout=300)
    assert len(out) == 4
    return job


@pytest.fixture(scope="module")
def registry(lenet):
    """What the registry reports after one job of each kind the benchmark
    runs, with the operators named as its jobs name them."""
    handle, _ = _job(lenet, "registry-stream", source=_paced_source(), source_name="offered")
    return {**_routed_job("registry-routed", "lfm2_moe").metrics,
            **_routed_job("registry-share", "kimi_k2").metrics, **_routed_job("registry-band", "afmoe").metrics,
            **handle.executor.metrics.report(),
            **_train_job("registry-train").metrics}


@pytest.mark.parametrize("metric", COUNTER_METRICS, ids=lambda m: m["name"])
def test_registry_carries_what_the_counter_metric_reads(registry, metric):
    """A timer or counter renamed in the program leaves the benchmark's
    metric out of the traced line, which the driver refuses after chip time:
    this fails first, on the CPU.  A job this small may ship nothing early, so
    a share may read 0: what is held is that the reader finds its number."""
    from benchmark.readers import counters

    args = metric["args"]
    over = args.get("over", [])
    keys = [args["of"], *([over] if isinstance(over, str) else over)]
    missing = [k for k in keys if k not in registry]
    assert not missing, f"{metric['name']} reads {missing}, which the registry no longer reports"
    assert counters.read({"run": {"counters": registry}}, **args) is not None
