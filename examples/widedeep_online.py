"""Wide&Deep online training on a keyed stream.

Reference workload 4 (BASELINE.json:10): "keyed stream, per-key SGD step"
— click/impression events keyed by user, the model updates online as
events arrive (SURVEY.md §3.4).  Params + optimizer state are explicit
operator state, so checkpoint barriers snapshot them (unlike the
reference, whose session-held variables sit outside Flink state —
SURVEY.md §5).

Run:  python examples/widedeep_online.py --records 512 --batch 8
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")
from examples._common import base_parser, report, select_platform


def synthetic_events(n, num_wide, num_dense, slots, buckets, users=16, seed=0):
    from flink_tensorflow_tpu.tensors import TensorValue

    rng = np.random.RandomState(seed)
    records = []
    for i in range(n):
        user = int(rng.randint(users))
        # Click probability correlates with one wide feature per user
        # cohort -> the model has signal to learn online.
        x_wide = rng.rand(num_wide).astype(np.float32)
        label = np.int32(x_wide[user % num_wide] > 0.5)
        records.append(TensorValue({
            "wide": x_wide,
            "dense": rng.rand(num_dense).astype(np.float32),
            "cat": rng.randint(0, buckets, (slots,)).astype(np.int32),
            "label": label,
        }, meta={"user": user}))
    return records


def main(argv=None):
    args = base_parser(__doc__).parse_args(argv)
    device_provider = select_platform(args.cpu, parallelism=args.parallelism)
    if args.smoke:
        args.records, args.batch = 64, 4

    import optax

    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import OnlineTrainFunction
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.tensors import RecordSchema, spec

    cfg = dict(hash_buckets=1000, embed_dim=8, num_cat_slots=4,
               num_dense=8, num_wide=16, hidden=(32, 16))
    mdef = get_model_def("widedeep", **cfg)
    schema = RecordSchema({
        "wide": spec((cfg["num_wide"],)),
        "dense": spec((cfg["num_dense"],)),
        "cat": spec((cfg["num_cat_slots"],), np.int32),
        "label": spec((), np.int32),
    })
    records = synthetic_events(args.records, cfg["num_wide"], cfg["num_dense"],
                               cfg["num_cat_slots"], cfg["hash_buckets"])

    env = StreamExecutionEnvironment(parallelism=args.parallelism)
    env.configure(device_provider=device_provider)
    out = (
        # The train schema doubles as the source's record schema, so the
        # plan analyzer validates the keyed pipeline end to end.
        env.from_collection(records, parallelism=1, schema=schema)
        .key_by(lambda r: r.meta["user"])
        .process(
            # State declared explicitly: the TrainState (params +
            # optimizer moments) lives in subtask-scoped OPERATOR state
            # — snapshot_state()/restore_state() round-trip it through
            # checkpoint barriers, and per-step RNG derives via
            # jax.random.fold_in from the seeded key.  flink-tpu-
            # statecheck audits exactly this: nothing model-shaped may
            # hide in closures, globals, or undeclared instance attrs.
            OnlineTrainFunction(mdef, optax.adam(1e-2), train_schema=schema,
                                scope="subtask", seed=0,
                                mini_batch=args.batch,
                                # Fuse 8 SGD steps into one lax.scan
                                # dispatch: on remote-attached chips the
                                # per-dispatch round trip otherwise caps
                                # online training at ~1/RTT steps/s.
                                steps_per_dispatch=8),
            name="online_train", parallelism=args.parallelism,
        )
        .sink_to_list()
    )
    t0 = time.time()
    job = env.execute("widedeep-online-training", timeout=600)
    losses = [float(r["loss"]) for r in out]
    k = max(1, len(losses) // 5)
    return report("widedeep_online_training", job.metrics, t0, args.records, {
        "steps": len(losses),
        "loss_first": round(float(np.mean(losses[:k])), 4),
        "loss_last": round(float(np.mean(losses[-k:])), 4),
    })


if __name__ == "__main__":
    main()
