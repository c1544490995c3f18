"""MNIST LeNet windowed micro-batch inference.

Reference workload 2 (BASELINE.json:8): "windowed ProcessFunction,
count-window micro-batch" — a count window collects B digit images, the
fired window runs one batched forward (SURVEY.md §3.2).

Run:  python examples/mnist_lenet.py --records 512 --batch 64
"""

import sys
import time

sys.path.insert(0, ".")
from examples._common import base_parser, report, select_platform, synthetic_images


def main(argv=None):
    args = base_parser(__doc__).parse_args(argv)
    device_provider = select_platform(args.cpu, parallelism=args.parallelism)
    if args.smoke:
        args.records, args.batch = 32, 8

    import jax

    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.models import get_model_def

    mdef = get_model_def("lenet")
    model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
    records = synthetic_images(args.records, 28, channels=1)

    env = StreamExecutionEnvironment(parallelism=args.parallelism)
    env.configure(device_provider=device_provider)
    results = (
        # Declaring the source schema lets the plan analyzer check the
        # stream against the model's input contract before execution
        # (python -m flink_tensorflow_tpu.analysis examples/mnist_lenet.py).
        env.from_collection(records, parallelism=1, schema=mdef.input_schema)
        .rebalance()
        # count-or-timeout: bounds p50 latency when the stream runs dry
        # (SURVEY.md §7 hard part 3 — adaptive batching).
        .count_window(args.batch, timeout_s=0.02)
        .apply(ModelWindowFunction(model), name="lenet",
               parallelism=args.parallelism)
        .sink_to_list()
    )
    t0 = time.time()
    job = env.execute("mnist-lenet-microbatch", timeout=600)
    assert len(results) == args.records
    hist = {}
    for r in results:
        hist[int(r["label"])] = hist.get(int(r["label"]), 0) + 1
    return report("mnist_lenet_microbatch", job.metrics, t0, args.records,
                  {"label_histogram": hist})


if __name__ == "__main__":
    main()
