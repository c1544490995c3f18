"""Job kind ``gang_train``: labeled records -> count window -> the program's
``DPTrainWindowFunction`` on a ``{"data": chips}`` mesh -> sink of losses.

Set-up drives the operator through its first ``check_steps`` steps (the first
compiles), reads from its state what ``correct`` compares, and hands the same
operator to the measured window."""

from __future__ import annotations

import dataclasses
import gc
import statistics
import time

import jax
import numpy as np

from benchmark import traffic
from benchmark.jobs import _zoo
from benchmark.reference import nn, training


def make_pool(model_cfg, mix, seed):
    size, n = model_cfg["image_size"], int(mix["pool_records"])
    rng = np.random.default_rng(int(seed))
    images = rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8)
    images.setflags(write=False)
    labels = rng.integers(0, model_cfg["num_classes"], n).astype(np.int32)
    return images, labels


def leaf_gaps(got: dict, want: dict, skip=()) -> dict:
    """Each leaf's gap between two norms, against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    floor = statistics.median(want.values())
    return {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want if k not in skip}


def compare(got, want) -> dict:
    """``got``/``want``: (losses, first-gradient leaf norms, change leaf norms,
    first gradient).  Leaves whose reference gradient is under a thousandth of
    the median leaf's move under Adam by round-off alone and are left out of the
    change.  Of the leaves' gaps the worst and the median: rounding noise adds
    to every leaf's norm a little, a leaf left out or moved double to one
    leaf's a lot.  A gap of norms sees rounding only at second order, so the
    first gradient is also compared at first order: each leaf's norm of the
    difference, against the same floor."""
    numbers = {f"loss_{i + 1}_gap": abs(g - w) / abs(w)
               for i, (g, w) in enumerate(zip(got[0], want[0]))}
    dead = {k for k, v in want[1].items() if v < 1e-3 * statistics.median(want[1].values())}
    floor = statistics.median(want[1].values())
    diffs = {k: host_norms({k: got[3][k] - want[3][k]})[k] / max(want[1][k], floor) for k in want[3]}
    for name, gaps in (("grad_norm_gap", leaf_gaps(got[1], want[1])),
                       ("change_norm_gap", leaf_gaps(got[2], want[2], skip=dead)),
                       ("grad_diff", diffs)):
        numbers[name] = max(gaps.values())
        numbers[name + "_med"] = statistics.median(gaps.values())
    return numbers


def host_norms(tree: dict) -> dict:
    return {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64))))) for k, v in tree.items()}


def adam_first_moment(opt_state):
    """The ``mu`` of the one ``ScaleByAdamState`` in an optax state, wherever a
    chain or a wrapper has put it."""
    import optax

    found = [node for node in jax.tree.leaves(
        opt_state, is_leaf=lambda node: isinstance(node, optax.ScaleByAdamState))
        if isinstance(node, optax.ScaleByAdamState)]
    if len(found) != 1:
        raise LookupError(f"the optimizer's state holds {len(found)} ScaleByAdamState, not one")
    return found[0].mu


def run(ctx):
    import optax

    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import DPTrainWindowFunction
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.parallel import make_mesh
    from flink_tensorflow_tpu.tensors import RecordSchema, TensorValue, spec

    cfg, mix, seed = ctx.config, ctx.mix, ctx.seed
    model_cfg, train_cfg = cfg["model"], cfg["training"]
    batch = int(mix["window_records"])
    check_steps = int(cfg["check_steps"])
    ref = _zoo.reference_of(cfg)
    specs, _ = nn.describe(ref.forward, model_cfg, train=True)

    images, labels = make_pool(model_cfg, mix, seed)
    ctx.note("pool made")
    records = [TensorValue({"image": images[i], "label": labels[i]}) for i in range(len(images))]
    size = model_cfg["image_size"]
    schema = RecordSchema({"image": spec((size, size, 3), np.uint8), "label": spec((), np.int32)})

    mdef = get_model_def(cfg["program_model"], **cfg["program_kwargs"])
    abstract = jax.eval_shape(mdef.init_fn, jax.random.key(0))
    # The program makes its state through its own init path; that path is given
    # the benchmark's weights, built on the device inside the one jitted call.
    # The key comes in as the operator's argument (``nn.key_of`` split in two), so
    # that one compiled init serves every seed.
    low, high = seed & 0x7FFFFFFF, seed >> 31
    mdef = dataclasses.replace(mdef, init_fn=lambda rng: _zoo.program_tree(
        nn.build_params(specs, jax.random.fold_in(rng, high)), abstract, cfg["param_rules"]))
    to_ref = _zoo.reference_names(abstract["params"], cfg["param_rules"])

    clock = traffic.RunClock(ctx.seconds)
    offered = traffic.Offered()
    got = []  # (arrival, step, loss)
    read = {}
    fault = ctx.fault or (lambda function: None)

    def flat(params):
        return {to_ref[path]: leaf for path, leaf in _zoo.flatten(params).items()}

    class Function(DPTrainWindowFunction):
        """The operator, read through its public calls (``current_params``,
        ``snapshot_state``) while set-up drives its first steps."""

        steps_seen = 0

        def open(self, fctx):
            super().open(fctx)
            ctx.note("operator open")
            fault(self)
            read["start"] = flat(self.current_params()["params"])

        def process_window(self, key, win, elements, out):
            super().process_window(key, win, elements, out)
            self.steps_seen += 1
            if self.steps_seen <= check_steps:
                ctx.note(f"step {self.steps_seen} dispatched")
            if self.steps_seen == 1:
                # Adam's first moment after one step is (1 - b1) times the
                # gradient the optimizer got.
                mu = flat(adam_first_moment(self.snapshot_state()["state"]["opt_state"]))
                read["grad"] = {k: np.asarray(v) / (1.0 - float(train_cfg["b1"]))
                                for k, v in mu.items()}
            if self.steps_seen == check_steps:
                now, start = flat(self.current_params()["params"]), read.pop("start")
                read["change"] = host_norms({k: now[k] - start[k] for k in now})
                clock.open_window()

        def on_finish(self, out):
            super().on_finish(out)
            read["final_step"] = int(self.snapshot_state()["state"]["step"])

    def sink(record):
        got.append((time.monotonic(), int(record["step"]), float(record["loss"])))

    optimizer = optax.adam(float(train_cfg["learning_rate"]), b1=float(train_cfg["b1"]),
                           b2=float(train_cfg["b2"]), eps=float(train_cfg["eps"]))
    env = StreamExecutionEnvironment(parallelism=1)
    env.set_mesh(make_mesh({"data": len(ctx.devices)}, ctx.devices))
    (
        env.from_source(
            traffic.make_source(records, mix, seed, clock, offered, lead_records=check_steps * batch),
            name="offered", parallelism=1, schema=schema)
        .count_window(batch)
        .apply(Function(mdef, optimizer, train_schema=schema, global_batch=batch, seed=low),
               name="train")
        .sink_to_callable(sink)
    )
    ctx.note("job built")
    handle = env.execute_async(ctx.cell["name"])
    ctx.await_window(clock, handle, lambda: got[-1][0] if got else clock.t_start)
    ctx.trace_window(clock)
    job = ctx.finish(handle, clock)
    counters = job.metrics if job is not None else {}

    ctx.read_device()
    del handle, env, records, job
    gc.collect()

    attempted = len(offered.due)
    steps_due = -(-attempted // batch)
    steps = sorted(g[1] for g in got)
    missing = steps_due - len(set(steps) & set(range(1, steps_due + 1)))
    failed = batch * (missing + len(steps) - len(set(steps)))
    if read.get("final_step") != steps_due:
        failed = max(failed, batch)
    arrival = np.array([g[0] for g in got], np.float64)
    in_window = int(((arrival >= clock.t_start) & (arrival < clock.t_close)).sum())
    ctx.note_stalls(clock, arrival)
    metrics = {"train_examples_per_s": in_window * batch / clock.seconds}

    if all(k in read for k in ("grad", "change")) and len(steps) >= check_steps:
        order = np.array(offered.pool_index[:check_steps * batch], np.int64)
        batches = [(images[order[i * batch:(i + 1) * batch]], labels[order[i * batch:(i + 1) * batch]])
                   for i in range(check_steps)]
        with jax.default_device(ctx.devices[0]):
            want = training.first_steps(ref.forward, model_cfg, train_cfg,
                                        nn.make_params(specs, seed), batches)
        loss_of_step = {g[1]: g[2] for g in got}
        grad_norms = host_norms(read["grad"])
        numbers = compare(([loss_of_step[i + 1] for i in range(check_steps)],
                           grad_norms, read["change"], read["grad"]), want)
        worst = {name: max(gaps, key=gaps.get) for name, gaps in
                 (("gradient", leaf_gaps(grad_norms, want[1])),
                  ("change", leaf_gaps(read["change"], want[2])))}
        ctx.note(f"leaf with the widest gap of norms: {worst}")
    else:
        numbers = {}
    ctx.note(f"{len(steps)} steps at the sink, {steps_due} due, final step {read.get('final_step')}")

    return {
        "attempted": attempted, "failed": failed, "metrics": metrics,
        "numbers": numbers, "counters": counters,
        "window": {"t_start": clock.t_start, "t_close": clock.t_close, "arrival": arrival,
                   "batch_records": batch},
    }
