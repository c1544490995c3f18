"""Shared plumbing for the example jobs (platform selection, data gen,
reporting).  Each example mirrors one reference workload (BASELINE.json:6-12)
as a runnable job script — the reference ships its workloads as Flink job
mains (SURVEY.md §1 L6)."""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def base_parser(description: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--records", type=int, default=256, help="stream length")
    p.add_argument("--batch", type=int, default=32, help="micro-batch / window size")
    p.add_argument("--parallelism", type=int, default=1)
    p.add_argument("--cpu", action="store_true",
                   help="force CPU with 8 virtual devices (default: the "
                        "platform jax finds, named in the JSON line)")
    p.add_argument("--smoke", action="store_true", help="tiny sizes for CI")
    return p


def select_platform(force_cpu: bool, virtual_devices: int = 8,
                    parallelism: int = 1):
    """Must run before jax touches a backend.  Returns the job's device
    provider for ``env.configure(device_provider=...)``: None at
    parallelism 1 (the library's default placement), otherwise subtask
    ``i`` runs on ``jax.local_devices()[i % n]`` — one replica per chip
    instead of every replica on the first."""
    from flink_tensorflow_tpu.utils import platform

    if force_cpu:
        platform.force_cpu(virtual_devices)
    else:
        # The cache is for the chip's compiles (Inception-v3 takes tens
        # of seconds); forced-CPU runs are the tests' and skip it.
        platform.enable_compile_cache()
    if parallelism <= 1:
        return None

    def device_provider(task: str, index: int):
        import jax

        devices = jax.local_devices()
        return devices[index % len(devices)]

    return device_provider


def synthetic_images(n: int, size: int, channels: int = 3, seed: int = 0):
    """Deterministic fake image records (the examples are about the
    streaming+model path, not datasets — reference examples fetch
    Inception inputs at run time too, SURVEY.md §4 fixtures note)."""
    from flink_tensorflow_tpu.tensors import TensorValue

    rng = np.random.RandomState(seed)
    return [
        TensorValue(
            {"image": rng.rand(size, size, channels).astype(np.float32)},
            {"id": i},
        )
        for i in range(n)
    ]


def report(job: str, metrics: dict, t0: float, records: int, extra: dict = None):
    """One human-readable summary + one machine-readable JSON line."""
    import jax

    wall = time.time() - t0
    devices = jax.devices()
    out = {
        "job": job,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
        "records": records,
        "wall_s": round(wall, 3),
        "records_per_s": round(records / wall, 2) if wall > 0 else None,
    }
    out.update(extra or {})
    # One latency histogram per SUBTASK: report the worst across them
    # (overwriting per key would report whichever subtask iterates last).
    p50s, p99s = [], []
    for key, value in metrics.items():
        if key.endswith("record_latency_s") and isinstance(value, dict):
            p50s.append(value["p50"])
            p99s.append(value["p99"])
    if p50s:
        out["p50_latency_ms"] = round(max(p50s) * 1e3, 3)
        out["p99_latency_ms"] = round(max(p99s) * 1e3, 3)
        if len(p50s) > 1:
            out["latency_aggregation"] = f"max over {len(p50s)} subtasks"
    print(json.dumps(out))
    return out
