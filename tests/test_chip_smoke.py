"""chip_smoke.py's phases as functions at tiny sizes on the CPU (kernel
interpreted), and its refusal to run as a script without a TPU."""

import os
import subprocess
import sys

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phase1_stream_matches_direct_jit():
    out = chip_smoke.phase1_stream([jax.devices()[0]], records_n=16, batch=8,
                                   num_classes=10)
    assert out["records"] == 16 and out["windows"] == 2
    labels, scores = out["outputs"]
    assert labels.shape == scores.shape == (16,) and labels.dtype == np.int32
    assert out["replica_device_ids"] == [jax.devices()[0].id]


def test_phase2_serving_and_interpreted_kernel():
    dev = jax.devices()[0]
    # The Mosaic-lowering proof only holds where Mosaic exists.
    served = chip_smoke.phase2_serving(dev, max_new_tokens=4,
                                       compiled_kernel=False)
    assert served == {"sessions": 8, "tokens": 32, "capacity": 128}
    flash = chip_smoke.phase2_flash(dev, interpret=True, cell_positions=256)
    assert [c["shape"] for c in flash["checked"]] == [
        [8, 128, 4, 16], [2, 256, 4, 64], [2, 256, 4, 64],
        [2, 256, 20, 128], [2, 256, 32, 64],  # the cells' heads
        [2, 256, 64, 128]]  # latent attention's split entry against the plain call
    assert flash["checked"][-1]["rope"] == 64 and flash["checked"][-1]["max_err"] < 1.2e-2


def test_result_line_has_exactly_the_contract_keys():
    import json

    devices = jax.devices()
    got = json.loads(chip_smoke.result_line(devices))
    assert got == {"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}
    assert isinstance(got["device"]["count"], int)


def test_script_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout  # no result line
