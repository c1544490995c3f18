"""ResNet-50 data-parallel training across the device mesh.

Reference workload 5 (BASELINE.json:11): DP training across TaskManagers
with TF ClusterSpec + NCCL gradient allreduce (SURVEY.md §3.5).  Here the
gang operator owns a ``{data: N}`` mesh and every fired window is one
pjit-ed train step — the allreduce is an XLA collective over ICI emitted
from sharding annotations; this file contains zero communication code.

Run:  python examples/resnet_dp_train.py --records 512 --batch 64
      python examples/resnet_dp_train.py --smoke --cpu  # tiny resnet, 8 virtual devices
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")
from examples._common import base_parser, report, select_platform


def main(argv=None):
    p = base_parser(__doc__)
    p.add_argument("--image-size", type=int, default=None)
    args = p.parse_args(argv)
    select_platform(args.cpu)
    if args.smoke:
        args.records, args.batch = 64, 16

    import jax
    import optax

    from flink_tensorflow_tpu import StreamExecutionEnvironment
    from flink_tensorflow_tpu.functions import DPTrainWindowFunction
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.parallel import make_mesh
    from flink_tensorflow_tpu.tensors import RecordSchema, TensorValue, spec

    n_dev = len(jax.devices())
    mesh = make_mesh({"data": n_dev})
    size = args.image_size or (32 if args.smoke else 224)
    classes = 10 if args.smoke else 1000
    if args.smoke:
        mdef = get_model_def("resnet50", num_classes=classes, image_size=size,
                             width=8, stage_sizes=(1, 1))
    else:
        mdef = get_model_def("resnet50", num_classes=classes, image_size=size)

    rng = np.random.RandomState(0)
    records = []
    for i in range(args.records):
        label = i % classes
        img = (rng.rand(size, size, 3) * 0.3 + (label / classes) * 0.7)
        records.append(TensorValue({"image": img.astype(np.float32),
                                    "label": np.int32(label)}))
    schema = RecordSchema({"image": spec((size, size, 3)),
                           "label": spec((), np.int32)})

    if args.parallelism != 1:
        print("note: --parallelism is ignored here — the DP gang operator "
              "runs at stream-parallelism 1 and owns ALL devices via the "
              f"mesh (data={n_dev})", file=sys.stderr)
    env = StreamExecutionEnvironment(parallelism=1)
    env.set_mesh(mesh)
    out = (
        # Schema declaration: the analyzer checks it against train_schema
        # and the mesh-divisibility of the gang step at plan time.
        env.from_collection(records, parallelism=1, schema=schema)
        .count_window(args.batch)
        .apply(DPTrainWindowFunction(mdef, optax.adam(1e-3), train_schema=schema,
                                     global_batch=args.batch),
               name="dp_train")
        .sink_to_list()
    )
    t0 = time.time()
    job = env.execute("resnet50-dp-training", timeout=3600)
    losses = [float(r["loss"]) for r in out]
    return report("resnet50_dp_training", job.metrics, t0, args.records, {
        "steps": len(losses),
        "loss_first": round(losses[0], 4) if losses else None,
        "loss_last": round(losses[-1], 4) if losses else None,
    })


if __name__ == "__main__":
    main()
