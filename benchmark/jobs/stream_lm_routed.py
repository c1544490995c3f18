"""Job kind ``stream_lm_routed``: ``stream_lm``'s job for a language model with
routed experts.  Two differences: the operator is asked for ``routing`` too
(the experts every token chose in every expert layer, part of the timed
path), and the reference is run on the compared records HELD to the routing
the program served for them (``reference/<name>.py: forward(routing=)``).

Why held: a top-k choice is discontinuous.  The program's hidden states
differ from a float32 reference's by a rounding, and where a token's k-th and
(k+1)-th scores lie closer than that the two choose differently; one such
pair moves a quarter of a layer's term.  Free-running, the comparison would
need limits under which a float8 program passes.  Held, it compares what the
routing leaves, and the routing itself is held to the reference's own scores:
``routing_wrong_share``, the share of (token, layer, slot) pairs whose served
expert lies more than the configuration's ``routing_delta`` under the
reference's own k-th best (or names one expert twice), has the limit 0.

``controls(cfg, mix, seed)`` puts the float8 controls and the reference's
planted faults through the same held comparison: the control plays the
program, free-running, and its routing goes to the float32 reference.
``python -m benchmark.jobs.stream_lm_routed --workload <cell> --seeds ...``
runs them on the chip at the cell's own size; not part of a run.
``readers/moe.md`` lists what this relies on in the program."""

from __future__ import annotations

import array
import gc
import time

import jax
import numpy as np

from benchmark import traffic
from benchmark.jobs import _zoo
from benchmark.jobs.stream_lm import answers, compare, in_use, make_pool, model_of, sample_of


def held_reference(ref, params, rows, routing, model, cfg, note=lambda text: None):
    """The reference's logits on ``rows`` held to ``routing`` (``[N, T, expert
    layers, k]``), and the routing's own distance from the reference's choice
    over all its (token, layer, slot) pairs."""
    routed, rms = [], []
    t_ref = time.monotonic()
    want = np.asarray(ref.forward(params, rows, model, routing=routing, routed=routed, rms=rms,
                                  routing_delta=float(cfg["routing_delta"])))
    pairs, wrong, near = (sum(r[key] for r in routed) for key in ("pairs", "wrong", "near"))
    gap_max = max(r["gap_max"] for r in routed)
    note(f"reference held to the served routing: {len(rows)} records in {time.monotonic() - t_ref:.1f} s; "
         f"of {pairs} pairs {wrong} wrong, {near} near (within {cfg['routing_delta']} of the reference's "
         f"own choice); largest shortfall {gap_max:.6f}; rms of the residual and of what its last layer adds: "
         + ", ".join(f"{k} {v:.3f}" for k, v in rms[-1].items()))
    return want, {"routing_wrong_share": wrong / pairs, "routing_near_share": near / pairs,
                  "routing_gap_max": gap_max}


def controls(cfg, mix, seed, stated="bfloat16") -> dict:
    """{reading: numbers}: each float8 control and each planted fault in the
    program's place, on the records a run with ``seed`` compares, plus one
    free-running comparison of the nearest control (no routing handed over),
    which is the reason for the held one."""
    from benchmark.controls import LOWER

    ref, model = _zoo.reference_of(cfg), model_of(cfg)
    pool = make_pool(ref, model, mix, seed)
    rows = pool[np.flatnonzero(sample_of(len(pool), cfg, seed))]
    params = ref.make_params(model, seed)

    def played(**kw):
        chosen = []
        served = answers(ref.forward(params, rows, model, chosen=chosen, **kw))
        return served, np.stack(chosen)

    out = {}
    for name, kw in ([("control_" + q, {"quant": q}) for q in LOWER[stated]]
                     + [("fault_" + f, {"fault": f}) for f in ref.FAULTS]):
        served, routing = played(**kw)
        want, held = held_reference(ref, params, rows, routing, model, cfg)
        out[name] = {**compare(want, *served), **held}
    sound, _ = played()
    served, _ = played(quant=LOWER[stated][0])
    out["free_running_" + LOWER[stated][0]] = compare(sound[0], *served)
    return out


def run(ctx):
    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.native import ring_impl
    from flink_tensorflow_tpu.tensors import BucketPolicy, TensorValue

    cfg, mix, seed = ctx.config, ctx.mix, ctx.seed
    if mix["arrivals"] != "backlog":
        raise ValueError(f"job kind stream_lm_routed offers a backlog, not {mix['arrivals']!r}")
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
    kept_row, kept_logits, kept_routing = array.array("q"), [], []  # sampled answers
    fault = ctx.fault or (lambda record: record)

    def sink(record):
        record = fault(record)
        k = record.meta["id"]
        if sampled[offered.pool_index[k]]:
            kept_row.append(len(got_at))
            kept_logits.append(np.array(record["logits"]))
            kept_routing.append(np.array(record["routing"]))
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
        .apply(Function(model, policy=BucketPolicy(fixed_batch=window), warmup_batches=(window,),
                        outputs=("logits", "label", "score", "routing")),
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
        kept = np.array(kept_row, np.int64)
        # A record of the pool is served many times in a run, every time with
        # one routing (the same weights on the same ids): the reference is held
        # once to each DISTINCT (record, routing) served, and every answer is
        # compared with the reference's for its own.
        routing = np.stack(kept_routing)
        record_of = np.array(offered.pool_index, np.int64)[ids[kept]]
        served = np.concatenate([record_of[:, None], routing.reshape(len(kept), -1)], axis=1)
        _, first, row_of = np.unique(served, axis=0, return_index=True, return_inverse=True)
        want, held = held_reference(ref, params, pool[record_of[first]], routing[first], model_cfg, cfg,
                                    note=ctx.note)
        row_of, logits = row_of.reshape(-1), np.stack(kept_logits)
        labels, scores = np.array(got_label, np.int64)[kept], np.array(got_score, np.float64)[kept]
        numbers = {**compare(want[row_of], logits, labels, scores), **held}
        # The reason for the held comparison, on record with its number: the
        # first of these records against the reference left to its own routing.
        one = row_of == 0
        free = np.asarray(ref.forward(params, pool[record_of[first[:1]]], model_cfg))
        numbers["free_running_logit_rms_err"] = compare(
            np.repeat(free, one.sum(), axis=0), logits[one], labels[one], scores[one])["logit_rms_err"]
        numbers["held_logit_rms_err_of_that_record"] = compare(
            want[row_of[one]], logits[one], labels[one], scores[one])["logit_rms_err"]
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


def main(argv=None) -> int:
    """The controls on the chip, each through the run's own check at the
    cell's limits, a line a seed (as ``python -m benchmark.controls_lm``)."""
    import argparse
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap = argparse.ArgumentParser(prog="benchmark.jobs.stream_lm_routed")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, root)
    from flink_tensorflow_tpu.utils.platform import enable_compile_cache

    from benchmark import harness
    from benchmark.controls import verdicts

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _, cell, cfg, mix = harness.load_cell(root, args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "platform": jax.devices()[0].platform, "limits": cfg["limits"],
                          **verdicts(controls(cfg, mix, seed), cfg["limits"])}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
