"""python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once, on the TPU this machine holds.  Anything else than
a TPU, or fewer chips than the cell asks for, ends non-zero with no result line.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_native_ring(root: str) -> None:
    """Build the program's native ring once where its lib directory is empty."""
    lib = os.path.join(root, "native", "lib")
    if os.path.isdir(lib) and os.listdir(lib):
        return
    if shutil.which("make") and shutil.which(os.environ.get("CXX", "g++")):
        subprocess.run(["make", "-C", os.path.join(root, "native")], check=True,
                       stdout=subprocess.DEVNULL, timeout=300)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    # Raises ImportError, and so ends non-zero, where the program is not there.
    from flink_tensorflow_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.local_devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark.run: needs a TPU, jax found {devices[0].platform!r} "
                         f"({len(devices)} x {devices[0].device_kind}); nothing was run")
    build_native_ring(ROOT)

    from benchmark import harness

    out = harness.run_cell(root=ROOT, workload=args.workload, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace), devices=devices, t0=T0)
    print(harness.result_line(**out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
