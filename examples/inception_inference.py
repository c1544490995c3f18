"""Inception-v3 streaming image labeling — the flagship workload.

Reference: the Inception demo job, a bounded DataStream of images mapped
through a ``ModelFunction`` running a frozen Inception-v3 graph in an
embedded TF session (BASELINE.json:7; SURVEY.md §3.1).  This job is the
north-star measurement path (BASELINE.json:2): records/sec/chip and p50
per-record latency.

TPU-native shape: images arrive as records, a count-or-timeout window
micro-batches them, and each fired window is ONE jitted bfloat16 forward
on a ``[B, 299, 299, 3]`` HBM-resident batch.

Run:  python examples/inception_inference.py --records 512 --batch 32
      python examples/inception_inference.py --smoke --cpu   # CI-safe
      python examples/inception_inference.py --bundle-dir /tmp/incep  # artifact path
"""

import os
import sys
import time

sys.path.insert(0, ".")  # repo-root invocation
from examples._common import base_parser, report, select_platform, synthetic_images


def main(argv=None):
    p = base_parser(__doc__)
    p.add_argument("--bundle-dir", default=None,
                   help="serve from a saved model bundle (exported on first "
                        "run) — the reference's load-an-artifact deployment "
                        "shape, instead of in-process init")
    p.add_argument("--output-dir", default=None,
                   help="also write results through the exactly-once "
                        "two-phase-commit file sink (committed on durable "
                        "checkpoints)")
    args = p.parse_args(argv)
    device_provider = select_platform(args.cpu, parallelism=args.parallelism)
    if args.smoke:
        args.records, args.batch = 16, 8

    import jax

    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.models import SavedModelLoader, get_model_def, save_bundle
    from flink_tensorflow_tpu.tensors import BucketPolicy

    num_classes = 10 if args.smoke else 1000
    mdef = get_model_def("inception_v3", num_classes=num_classes)
    if args.bundle_dir:
        # The reference's flagship job LOADS its model (frozen graph /
        # SavedModel) rather than building it in-process (SURVEY.md §3.3).
        # Export once, then every operator replica loads the bundle at
        # open() — the artifact-deployment shape.
        if not os.path.isdir(args.bundle_dir):
            params = jax.jit(mdef.init_fn)(jax.random.key(0))
            save_bundle(mdef, params, args.bundle_dir)
        else:
            print(f"serving EXISTING bundle {args.bundle_dir} as-is "
                  "(its architecture config wins over this run's flags)",
                  file=sys.stderr)
        model = SavedModelLoader(args.bundle_dir)
    else:
        model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
    records = synthetic_images(args.records, 299)

    env = StreamExecutionEnvironment(parallelism=args.parallelism)
    env.configure(device_provider=device_provider)
    if args.output_dir:
        # Deterministic barriers + the 2PC sink: committed output files
        # hold each result exactly once even across failover.
        env.enable_checkpointing(args.output_dir + ".chk",
                                 every_n_records=4 * args.batch)
    labeled = (
        # Source schema declaration — plan-time validation against the
        # model contract (see flink_tensorflow_tpu.analysis).
        env.from_collection(records, parallelism=1, schema=mdef.input_schema)
        .rebalance()
        .count_window(args.batch, timeout_s=0.05)
        .apply(
            ModelWindowFunction(
                model,
                policy=BucketPolicy(fixed_batch=args.batch),
                warmup_batches=(args.batch,),
            ),
            name="inception",
            parallelism=args.parallelism,
        )
    )
    results = labeled.sink_to_list()
    if args.output_dir:
        from flink_tensorflow_tpu.io import ExactlyOnceRecordFileSink

        labeled.add_sink(ExactlyOnceRecordFileSink(args.output_dir),
                         name="committed_results", parallelism=args.parallelism)
    t0 = time.time()
    job = env.execute("inception-v3-labeling", timeout=3600)
    assert len(results) == args.records, (len(results), args.records)
    labels = [int(r["label"]) for r in results[:5]]
    extra = {"sample_labels": labels}
    if args.output_dir:
        from flink_tensorflow_tpu.io import read_committed

        extra["committed_records"] = len(read_committed(args.output_dir))
    return report("inception_v3_streaming_inference", job.metrics, t0,
                  args.records, extra)


if __name__ == "__main__":
    main()
