"""Streaming LLM serving: continuous batching + KV cache as keyed state.

The "millions of users" workload (ROADMAP): generation requests arrive
as a keyed stream (key = session id), a continuous-batching operator
admits/evicts sessions per decode step under a token budget, and each
session's KV cache lives in keyed operator state — checkpointable,
restorable mid-generation, rescalable by key group.  The model is the
zoo's char-level causal transformer (random params — the point is the
serving plane, not the prose), driving the pallas flash kernel for
prefill and the single-query decode path per token.

Run:  python examples/llm_serving_pipeline.py --records 24 --cpu
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")
from examples._common import base_parser, report, select_platform

#: Char vocab: printable ASCII 32..126 at ids 1..95; 0 is padding.
VOCAB = 96


def encode(text: str) -> np.ndarray:
    return np.array([max(1, min(95, ord(c) - 31)) for c in text], np.int32)


def decode(tokens) -> str:
    return "".join(chr(max(32, min(126, t + 31))) for t in tokens if t > 0)


PROMPTS = [
    "the quick brown fox",
    "streaming systems",
    "tensor processing",
    "continuous batching",
    "keyed operator state",
    "flash attention",
    "exactly once",
    "token budget",
]


def main(argv=None):
    args = base_parser(__doc__).parse_args(argv)
    device_provider = select_platform(args.cpu, parallelism=args.parallelism)
    if args.smoke:
        args.records = 8

    import jax

    from flink_tensorflow_tpu import StreamExecutionEnvironment, serving
    from flink_tensorflow_tpu.models import get_model_def
    from flink_tensorflow_tpu.sources import PacedSplitSource

    mdef = get_model_def("char_transformer", vocab_size=VOCAB, embed_dim=64,
                         num_heads=4, num_layers=2, capacity=64)
    model = mdef.to_model(mdef.init_params(jax.random.PRNGKey(0)))

    n = args.records or 24
    max_new = 8 if args.smoke else 16
    requests = [
        serving.GenerateRequest(
            session_id=f"user-{i}",
            prompt=encode(PROMPTS[i % len(PROMPTS)]),
            max_new_tokens=max_new,
        )
        for i in range(n)
    ]

    env = StreamExecutionEnvironment(parallelism=args.parallelism)
    env.configure(device_provider=device_provider)
    # Declared serving layout: an ABSTRACT v5e-8 mesh (data=4 x tp=2) +
    # the per-chip HBM ceiling.  Nothing at execution time touches these
    # on a CPU box — they exist so `flink-tpu-shardcheck` (and the
    # analyzer's shardcheck-* rules) can audit partitioning, donation,
    # and the static HBM budget of this plan without any TPU attached.
    from flink_tensorflow_tpu.parallel import abstract_mesh

    env.set_mesh(abstract_mesh({"data": 4, "tp": 2}))
    env.set_hbm_budget(16 * 1024**3)  # v5e: 16 GiB per chip
    events = (
        serving.continuous_batching(
            # Open-loop arrivals: sessions show up on a Poisson schedule
            # whether or not the pipeline keeps up, and each TokenEvent
            # carries meta["sched_ts"] so latency is measured against
            # the schedule (coordinated-omission-free).
            env.from_source(
                PacedSplitSource(requests, rate_hz=50.0, num_splits=4),
                name="sessions", parallelism=1,
            )
            .key_by(lambda r: r.session_id),
            model,
            config=serving.ServingConfig(
                max_active_seqs=8,       # pool slots (one decode shape)
                token_budget=256,        # sum of active cache lengths
                capacity=64,             # prompt + generated must fit
            ),
            name="continuous_batching",
            parallelism=args.parallelism,
        )
        .sink_to_list()
    )
    t0 = time.time()
    job = env.execute("llm-serving", timeout=600)

    sessions = {}
    for ev in events:
        sessions.setdefault(ev.session_id, {})[ev.index] = ev.token
    completions = {
        sid: decode([toks[i] for i in sorted(toks)])
        for sid, toks in sessions.items()
    }
    for sid in sorted(completions)[:4]:
        print(f"  {sid}: {completions[sid]!r}")
    total_tokens = sum(len(t) for t in sessions.values())
    return report("llm_serving_pipeline", job.metrics, t0, n, {
        "sessions": len(sessions),
        "tokens": total_tokens,
        "all_sessions_completed": all(
            len(t) == max_new for t in sessions.values()),
    })


if __name__ == "__main__":
    main()
