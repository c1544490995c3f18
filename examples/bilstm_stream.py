"""BiLSTM text-classification streaming inference with dynamic batching.

Reference workload 3 (BASELINE.json:9): variable-length token sequences,
"dynamic batching".  TPU-native: the window fires on count-or-timeout and
the batcher buckets both batch size and sequence length (powers of two),
so XLA compiles one executable per (batch, length) bucket and reuses it
(SURVEY.md §7 hard part 2).

Run:  python examples/bilstm_stream.py --records 256 --batch 16
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")
from examples._common import base_parser, report, select_platform


def synthetic_texts(n, vocab, max_len, seed=0):
    from flink_tensorflow_tpu.tensors import TensorValue

    rng = np.random.RandomState(seed)
    records = []
    for i in range(n):
        length = int(rng.randint(4, max_len + 1))
        records.append(TensorValue(
            {"tokens": rng.randint(0, vocab, (length,)).astype(np.int32)},
            {"id": i, "length": length},
        ))
    return records


def main(argv=None):
    args = base_parser(__doc__).parse_args(argv)
    device_provider = select_platform(args.cpu, parallelism=args.parallelism)
    if args.smoke:
        args.records, args.batch = 24, 8
    vocab, hidden, max_len = (1000, 64, 48) if args.smoke else (20000, 256, 192)

    import jax

    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import ModelWindowFunction
    from flink_tensorflow_tpu.models import get_model_def

    mdef = get_model_def("bilstm", vocab_size=vocab, hidden_dim=hidden)
    model = mdef.to_model(jax.jit(mdef.init_fn)(jax.random.key(0)))
    records = synthetic_texts(args.records, vocab, max_len)

    env = StreamExecutionEnvironment(parallelism=args.parallelism)
    env.configure(device_provider=device_provider)
    results = (
        # Plan-time schema: tokens has a dynamic (None) length dim — the
        # analyzer confirms the model's length-bucketing policy resolves
        # it before anything reaches XLA.
        env.from_collection(records, parallelism=1, schema=mdef.input_schema)
        .rebalance()
        .count_window(args.batch, timeout_s=0.05)
        .apply(ModelWindowFunction(model), name="bilstm",
               parallelism=args.parallelism)
        .sink_to_list()
    )
    t0 = time.time()
    job = env.execute("bilstm-text-classification", timeout=600)
    assert len(results) == args.records
    pos = sum(int(r["label"]) for r in results)
    return report("bilstm_streaming_inference", job.metrics, t0, args.records,
                  {"positive_fraction": round(pos / len(results), 3)})


if __name__ == "__main__":
    main()
