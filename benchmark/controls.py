"""python -m benchmark.controls --workload <cell> --seeds 1 2 3

The readings that a cell's limits are set between, taken on the chip at the
cell's own size and on a run's own rows (PERF.md section 2): the plain reference
put in the program's place and computed in the precision below the one the
configuration states (float8 for bfloat16), and the planted faults; each goes
through the run's own check at the cell's limits and has to come out not
correct.  Not part of a benchmark run.  tests/benchmark/test_controls.py keeps
the same at a tiny size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: The precisions below a stated one, nearest first.
LOWER = {"bfloat16": ("float8_e4m3fn", "float8_e5m2")}


def stream_readings(cfg, mix, seed):
    """On the records that a run with this seed compares."""
    from benchmark.jobs import _zoo, stream_infer
    from benchmark.reference import nn

    ref, model = _zoo.reference_of(cfg), cfg["model"]
    pool = stream_infer.make_pool(model, mix, seed)
    rows = pool[np.flatnonzero(stream_infer.sample_of(len(pool), cfg, seed))]
    params = nn.make_params(nn.describe(ref.forward, model)[0], seed)
    block = int(cfg["reference_block"])
    want = stream_infer.reference_logits(ref, model, params, rows, block)
    answer = lambda z: (z, z.argmax(axis=1), np.asarray(jax.nn.softmax(z, axis=-1)).max(axis=1))  # noqa: E731
    logits, label, score = answer(want)
    low = lambda q: answer(stream_infer.reference_logits(ref, model, params, rows, block, quant=q))  # noqa: E731
    return {
        **{"control_" + q: stream_infer.compare(want, *low(q)) for q in LOWER[model["compute_dtype"]]},
        # An answer altered where it is produced, planted in the reference's own answers.
        "label_plus_one": stream_infer.compare(want, logits, (label + 1) % want.shape[1], score),
        "score_halved": stream_infer.compare(want, logits, label, score / 2),
    }


def train_readings(cfg, mix, seed):
    """On the rows of the first steps of a run with this seed."""
    from benchmark import traffic
    from benchmark.jobs import _zoo, gang_train
    from benchmark.reference import nn, training

    ref, model, train = _zoo.reference_of(cfg), cfg["model"], cfg["training"]
    batch, steps = int(mix["window_records"]), int(cfg["check_steps"])
    images, labels = gang_train.make_pool(model, mix, seed)
    order = (traffic.first_index(len(images), seed) + np.arange(batch * steps)) % len(images)
    batches = [(images[rows], labels[rows]) for rows in np.split(order, steps)]
    params = nn.make_params(nn.describe(ref.forward, model, train=True)[0], seed)
    run = lambda **kw: training.first_steps(ref.forward, model, train, params, batches, **kw)  # noqa: E731
    want = run()
    return {
        **{"control_" + q: gang_train.compare(run(quant=q), want) for q in LOWER[model["compute_dtype"]]},
        "half_batch": gang_train.compare(run(rows=batch // 2), want),
        "state_unchanged": gang_train.compare(run(frozen=True), want),
    }


def verdicts(readings: dict, limits: dict) -> dict:
    """Each reading through the run's own check at the cell's limits."""
    from benchmark import harness

    out = {}
    for name, numbers in readings.items():
        rows = harness.checked(numbers, limits)
        out[name] = {"correct": all(ok for *_, ok in rows),
                     "fails": [n for n, *_, ok in rows if not ok], "numbers": numbers}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.controls")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from flink_tensorflow_tpu.utils.platform import enable_compile_cache

    from benchmark import harness

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _, cell, cfg, mix = harness.load_cell(ROOT, args.workload)
    read = train_readings if cfg.get("training") else stream_readings
    for seed in args.seeds:
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "platform": jax.devices()[0].platform, "limits": cfg["limits"],
                          **verdicts(read(cfg, mix, seed), cfg["limits"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
