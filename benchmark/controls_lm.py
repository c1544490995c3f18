"""python -m benchmark.controls_lm --workload <cell> --seeds 1 2 3

``benchmark.controls`` for a ``stream_lm`` cell: on the chip, at the cell's
own size and on the records that a run with the seed compares, the plain
reference put in the program's place and (a) computed with every
contraction's operands in the precisions below the stated one, (b) with one
of the reference's planted faults (``FAULTS``: the scan's state dropped at
every chunk edge, the attention branch zeroed, the conv left out).  Each goes
through the run's own check at the cell's limits and has to come out not
correct.  Not part of a run.  tests/benchmark/test_stream_lm.py keeps the
same at a tiny size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cfg, mix, seed, stated="bfloat16"):
    from benchmark import controls
    from benchmark.jobs import _zoo, stream_lm

    ref, model = _zoo.reference_of(cfg), stream_lm.model_of(cfg)
    pool = stream_lm.make_pool(ref, model, mix, seed)
    rows = pool[np.flatnonzero(stream_lm.sample_of(len(pool), cfg, seed))]
    params = ref.make_params(model, seed)
    want = np.asarray(ref.forward(params, rows, model))
    served = lambda **kw: stream_lm.compare(  # noqa: E731
        want, *stream_lm.answers(ref.forward(params, rows, model, **kw)))
    return {**{"control_" + q: served(quant=q) for q in controls.LOWER[stated]},
            **{"fault_" + f: served(fault=f) for f in ref.FAULTS}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.controls_lm")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from flink_tensorflow_tpu.utils.platform import enable_compile_cache

    from benchmark import controls, harness

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _, cell, cfg, mix = harness.load_cell(ROOT, args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "platform": jax.devices()[0].platform, "limits": cfg["limits"],
                          **controls.verdicts(readings(cfg, mix, seed), cfg["limits"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
