"""Job kind ``stream_lm``: records of token ids -> ``count_window`` -> the
program's ``ModelWindowFunction`` over a language model -> sink, built
through the entry points a job author calls, as ``stream_infer`` is.  A record
is one fixed-length sequence (``record_tokens`` of the mix); the answer is the
next-token distribution after it.  Backlog mixes only.

The weights are the reference's (``reference/<name>.py: make_params``): made on
the device in the stored precision, leaf by leaf, and handed to the program as
they are.  Program and reference then read the SAME buffers, which is the only
way a tree of 10 GB is held once.  ``readers/lm.md`` lists what this relies on
in the program."""

from __future__ import annotations

import array
import gc
import time

import jax
import numpy as np

from benchmark import traffic
from benchmark.jobs import _zoo
from benchmark.jobs.stream_infer import compare, sample_of


def model_of(cfg: dict) -> dict:
    """The published keys as the job reads them: ``cfg["model"]``, held equal
    to the copy at the file's top level (which the catalog's check reads)."""
    model = cfg["model"]
    differ = [k for k in model if k in cfg and cfg[k] != model[k]]
    if differ:
        raise ValueError(f"configuration {cfg['name']}: top level and model disagree on {differ}")
    return model


def make_pool(ref, model: dict, mix: dict, seed: int) -> np.ndarray:
    pool = ref.make_tokens(model, int(mix["pool_records"]), int(mix["record_tokens"]), seed)
    pool.setflags(write=False)
    return pool


def answers(logits):
    """(logits, label, score) as ``serve`` gives them, of reference logits."""
    logits = np.asarray(logits)
    return logits, logits.argmax(axis=1), np.asarray(jax.nn.softmax(logits, axis=-1)).max(axis=1)


def in_use(device) -> int:
    return int((device.memory_stats() or {}).get("bytes_in_use", 0))


def run(ctx):
    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.native import ring_impl
    from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue

    cfg, mix, seed = ctx.config, ctx.mix, ctx.seed
    if mix["arrivals"] != "backlog":
        raise ValueError(f"job kind stream_lm offers a backlog, not {mix['arrivals']!r}")
    model_cfg = model_of(cfg)
    window, length = int(mix["window_records"]), int(mix["record_tokens"])
    lead = int(mix.get("warmup_windows", 2)) * window
    # Before a weight is made: a tree without the model ends here, in seconds.
    mdef = get_model_def(cfg["program_model"], seq_len=length, **model_cfg, **cfg["program_kwargs"])
    ref = _zoo.reference_of(cfg)
    device = ctx.devices[0]

    pool = make_pool(ref, model_cfg, mix, seed)
    with jax.default_device(device):
        params = ref.make_params(model_cfg, seed)
    variables = _zoo.program_tree(
        params, jax.eval_shape(mdef.init_fn, jax.random.key(0)), cfg["param_rules"])
    model = mdef.to_model(variables)
    jax.block_until_ready(variables)
    tree_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(variables))
    ctx.note(f"weights made: {tree_bytes} B in {len(params)} leaves; device holds {in_use(device)} B")
    records = [TensorValue({"tokens": pool[i]}) for i in range(len(pool))]

    clock = traffic.RunClock(ctx.seconds)
    offered = traffic.Offered()
    sampled = sample_of(len(pool), cfg, seed)
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
            before = in_use(device)
            super().open(fctx)
            ctx.note(f"operator open; ring: {ring_impl()}; the device held {before} B before open() "
                     f"and holds {in_use(device)} B after it")
            if not lead:
                clock.open_window()

    env = StreamExecutionEnvironment(parallelism=1)
    env.configure(device_provider=lambda task, i: device)
    (
        env.from_source(traffic.make_source(records, mix, seed, clock, offered, lead_records=lead),
                        name="offered", parallelism=1)
        .count_window(window)
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

    # The program is done: read the device, then let go of everything of its
    # but the weights, which the reference reads next.
    ctx.read_device()
    del handle, env, model, variables, records, job
    gc.collect()

    arrival = np.array(got_at, np.float64)
    ids = np.array(got_id, np.int64)
    attempted = len(offered.due)
    seen = np.bincount(ids, minlength=attempted) if len(ids) else np.zeros(attempted, int)
    failed = int((seen[:attempted] != 1).sum()) + int(seen[attempted:].sum())
    in_window = int(((arrival >= clock.t_start) & (arrival < clock.t_close)).sum())
    if ctx.traced is not None:
        t_on, t_off = ctx.traced.host_span
        ctx.note(f"records/s while traced: {((arrival >= t_on) & (arrival < t_off)).sum() / (t_off - t_on):.2f}")
    ctx.note_stalls(clock, arrival)

    if kept_logits:
        order = np.flatnonzero(sampled)
        rms = []
        t_ref = time.monotonic()
        want = np.asarray(ref.forward(params, pool[order], model_cfg, rms=rms))
        last = rms[-1]  # the last layer of the last record
        ctx.note(f"reference: {len(order)} records in {time.monotonic() - t_ref:.1f} s; rms of the residual "
                 f"and of what its last layer adds: " + ", ".join(f"{k} {v:.3f}" for k, v in last.items()))
        kept = np.array(kept_row, np.int64)
        rows = np.searchsorted(order, np.array(offered.pool_index, np.int64)[ids[kept]])
        numbers = compare(want[rows], np.stack(kept_logits), np.array(got_label, np.int64)[kept],
                          np.array(got_score, np.float64)[kept])
    else:
        numbers = {name: float("inf") for name in cfg["limits"]}
    ctx.note(f"compared {len(kept_logits)} served answers of {len(ids)} on {int(sampled.sum())} records")

    return {
        "attempted": attempted, "failed": failed,
        "metrics": {"records_per_s": in_window / clock.seconds},
        "numbers": numbers, "counters": counters,
        "window": {"t_start": clock.t_start, "t_close": clock.t_close, "arrival": arrival,
                   "batch_records": window, "record_tokens": length},
    }
