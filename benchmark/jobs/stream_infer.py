"""Job kind ``stream_infer``: records -> count(-or-timeout) window -> the
program's ``ModelWindowFunction`` -> sink, built through the entry points a
job author calls.  Serves both backlog and open-loop mixes."""

from __future__ import annotations

import array
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import traffic
from benchmark.jobs import _zoo
from benchmark.reference import nn

#: An open loop's count window also fires after this many fill times at the
#: mix's rate, so that the timeout fires only on a stall.
TIMEOUT_FILLS = 2


def make_pool(model_cfg: dict, mix: dict, seed: int) -> np.ndarray:
    size = model_cfg["image_size"]
    pool = np.random.default_rng(int(seed)).integers(
        0, 256, (int(mix["pool_records"]), size, size, 3), dtype=np.uint8)
    pool.setflags(write=False)
    return pool


def sample_of(pool_n: int, config: dict, seed: int) -> np.ndarray:
    """Which records of the pool a run with ``seed`` compares: a mask."""
    sampled = np.zeros(pool_n, bool)
    rng = np.random.default_rng(int(seed) + 1)
    sampled[rng.choice(pool_n, min(int(config["check_records"]), pool_n), replace=False)] = True
    return sampled


def reference_logits(ref, model_cfg, params, images, block, quant=None):
    """The plain forward pass over ``images`` in blocks of ``block`` rows."""
    fwd = jax.jit(lambda p, x: ref.forward(nn.Net(p, quant=quant), x, model_cfg))
    out = [np.asarray(fwd(params, jnp.asarray(images[lo:lo + block])))
           for lo in range(0, len(images), block)]
    return np.concatenate(out)


def compare(want_logits, got_logits, got_label, got_score) -> dict:
    """The numbers of ``correct``: rows of served answers against the
    reference's rows for the same records.  A row's scale is the spread of the
    reference's logits over the classes.

    - ``logit_rms_err``: root mean square, over every logit compared, of served
      minus reference in units of the row's scale;
    - ``label_gap``: the widest gap, in the same units, by which the
      reference's logit of a served label lies below the reference's best;
    - ``score_log_err``: the widest gap between the logarithms of the served
      score and of the reference's largest softmax probability."""
    want = np.asarray(want_logits, np.float64)
    got = np.asarray(got_logits, np.float64)
    scale = want.std(axis=1, keepdims=True)
    rows = np.arange(len(want))
    log_best = -np.log(np.exp(want - want.max(axis=1, keepdims=True)).sum(axis=1))
    with np.errstate(divide="ignore", invalid="ignore"):
        score_log = np.log(np.asarray(got_score, np.float64))
    return {
        "logit_rms_err": float(np.sqrt(np.mean(np.square((got - want) / scale)))),
        "label_gap": float(((want.max(axis=1) - want[rows, np.asarray(got_label)]) / scale[:, 0]).max()),
        "score_log_err": float(np.nan_to_num(np.abs(score_log - log_best), nan=np.inf).max()),
    }


def run(ctx):
    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.native import ring_impl
    from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue

    cfg, mix, seed = ctx.config, ctx.mix, ctx.seed
    model_cfg = cfg["model"]
    window = int(mix["window_records"])
    # Whole windows sent through the whole path before the window opens: the
    # first pass through ring, transfer lanes and fetch thread is set-up.
    lead = int(mix.get("warmup_windows", 2)) * window
    ref = _zoo.reference_of(cfg)

    pool = make_pool(model_cfg, mix, seed)
    ctx.note("pool made")
    specs, _ = nn.describe(ref.forward, model_cfg)
    with jax.default_device(ctx.devices[0]):
        params = nn.make_params(specs, seed)
    mdef = get_model_def(cfg["program_model"], **cfg["program_kwargs"])
    variables = _zoo.program_tree(
        params, jax.eval_shape(mdef.init_fn, jax.random.key(0)), cfg["param_rules"])
    model = mdef.to_model(variables)
    jax.block_until_ready(variables)
    ctx.note("weights made")
    records = [TensorValue({"image": pool[i]}) for i in range(len(pool))]

    clock = traffic.RunClock(ctx.seconds)
    offered = traffic.Offered()
    sampled = sample_of(len(pool), cfg, seed)
    # What the sink received, as typed arrays (see traffic.Offered).
    got_at, got_id, got_label, got_score = (array.array(t) for t in "dqqd")
    kept_row, kept_logits = array.array("q"), []  # sampled answers: row in got_*, logits
    fault = ctx.fault or (lambda record: record)

    def sink(record):
        record = fault(record)
        k = record.meta["id"]
        if sampled[offered.pool_index[k]]:
            kept_row.append(len(got_at))
            kept_logits.append(np.array(record["logits"]))
        got_at.append(time.monotonic())
        got_id.append(k)
        got_label.append(int(record["label"]))
        got_score.append(float(record["score"]))
        if len(got_at) == lead:
            clock.open_window()

    class Function(ModelWindowFunction):
        def open(self, fctx):
            super().open(fctx)
            ctx.note(f"operator open; ring: {ring_impl()}")
            if not lead:
                clock.open_window()

    env = StreamExecutionEnvironment(parallelism=1)
    env.configure(device_provider=lambda task, i: ctx.devices[0])
    timeout_s = None
    if mix["arrivals"] != "backlog":
        timeout_s = TIMEOUT_FILLS * window / float(mix["rate_per_s"])
    (
        env.from_source(traffic.make_source(records, mix, seed, clock, offered, lead_records=lead),
                        name="offered", parallelism=1)
        .count_window(window, timeout_s=timeout_s)
        .apply(Function(model, policy=BucketPolicy(fixed_batch=window),
                        warmup_batches=(window,), outputs=("logits", "label", "score")),
               name="model", parallelism=1)
        .sink_to_callable(sink)
    )
    ctx.note("job built")
    handle = env.execute_async(ctx.cell["name"])
    ctx.await_window(clock, handle, lambda: got_at[-1] if got_at else clock.t_start)
    ctx.trace_window(clock)
    job = ctx.finish(handle, clock)
    counters = job.metrics if job is not None else {}

    # The program is done: read the device, then let go of everything of its.
    ctx.read_device()
    del handle, env, model, variables, records, job
    gc.collect()

    arrival = np.array(got_at, np.float64)
    ids = np.array(got_id, np.int64)
    attempted = len(offered.due)
    due = np.asarray(offered.due, np.float64)
    seen = np.bincount(ids, minlength=attempted) if len(ids) else np.zeros(attempted, int)
    failed = int((seen[:attempted] != 1).sum()) + int(seen[attempted:].sum())
    in_window = int(((arrival >= clock.t_start) & (arrival < clock.t_close)).sum())
    if ctx.traced is not None:
        t_on, t_off = ctx.traced.host_span
        ctx.note(f"records/s while traced: {((arrival >= t_on) & (arrival < t_off)).sum() / (t_off - t_on):.0f}")
    timed = (ids >= lead) & (ids < attempted)  # the window's own records
    latency_ms = (arrival[timed] - due[ids[timed]]) * 1e3
    late_ms = (np.asarray(offered.emitted) - due)[lead:] * 1e3

    ctx.note_stalls(clock, arrival)
    metrics = {"records_per_s": in_window / clock.seconds}
    if len(latency_ms):
        metrics["latency_p50_ms"] = float(np.percentile(latency_ms, 50))
        metrics["latency_p95_ms"] = float(np.percentile(latency_ms, 95))

    if mix["arrivals"] != "backlog" and len(latency_ms) > 30:
        thirds = np.array_split(latency_ms[np.argsort(due[ids[timed]])], 3)
        ctx.note("latency p50/p95 ms by third of the window: " + ", ".join(
            f"{np.percentile(t, 50):.0f}/{np.percentile(t, 95):.0f}" for t in thirds)
            + f"; generator late p95 {np.percentile(late_ms, 95):.2f} ms, worst {late_ms.max():.0f} ms "
              f"at {due[lead + int(late_ms.argmax())] - clock.t_start:.1f} s")

    if kept_logits:
        order = np.flatnonzero(sampled)
        want = reference_logits(ref, model_cfg, params, pool[order], int(cfg["reference_block"]))
        kept = np.array(kept_row, np.int64)
        rows = np.searchsorted(order, np.array(offered.pool_index, np.int64)[ids[kept]])
        numbers = compare(want[rows], np.stack(kept_logits), np.array(got_label, np.int64)[kept],
                          np.array(got_score, np.float64)[kept])
    else:
        numbers = {name: float("inf") for name in cfg["limits"]}
    ctx.note(f"compared {len(kept_logits)} served answers of {len(ids)} on {int(sampled.sum())} records")

    return {
        "attempted": attempted, "failed": failed, "metrics": metrics,
        "numbers": numbers, "counters": counters,
        "window": {"t_start": clock.t_start, "t_close": clock.t_close,
                   "arrival": arrival, "late_ms": late_ms, "batch_records": window},
    }
