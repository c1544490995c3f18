"""A tiny checkout for the benchmark's tests: the real harness, readers and
metric files, with tiny configurations and mixes ADDED as files, never by an
edit: which is how a later PR adds a cell."""

import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_LIMITS = {
    "tiny_inception": {"logit_rms_err": 0.04, "label_gap": 0.3, "score_log_err": 0.1},
    "tiny_resnet": {"loss_1_gap": 0.02, "loss_2_gap": 0.02, "loss_3_gap": 0.02,
                    "grad_norm_gap": 0.3, "change_norm_gap": 0.3, "grad_diff_med": 0.3},
}


def _config(name, **model):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg["program_kwargs"].update(model)
    cfg["model"].update(model)
    return cfg


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    bench = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bench, "configs"))
    os.makedirs(os.path.join(bench, "workloads"))
    shutil.copytree(os.path.join(ROOT, "benchmark", "layer_metrics"),
                    os.path.join(bench, "layer_metrics"))
    shutil.copy(os.path.join(ROOT, "benchmark", "peaks.json"), bench)

    inception = _config("inception_v3", num_classes=10, image_size=75)
    inception.update(check_records=8, reference_block=4, limits=TINY_LIMITS["tiny_inception"])
    resnet = _config("resnet50", num_classes=10, image_size=32, width=8, stage_sizes=[1, 1, 1, 1])
    resnet.update(limits=TINY_LIMITS["tiny_resnet"])
    files = {
        "configs/tiny_inception.json": inception,
        "configs/tiny_resnet.json": resnet,
        "workloads/tiny_inception.backlog.json":
            {"arrivals": "backlog", "pool_records": 16, "window_records": 8},
        "workloads/tiny_inception.paced.json":
            {"arrivals": "poisson", "rate_per_s": 20, "pool_records": 16,
             "window_records": 8},
        "workloads/tiny_resnet.train1.json":
            {"arrivals": "backlog", "pool_records": 64, "window_records": 16},
        "workloads/tiny_resnet.train4.json":
            {"arrivals": "backlog", "pool_records": 64, "window_records": 16},
    }
    for rel, body in files.items():
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(body, f)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [
        {"name": n, "source": "test", "file": f"benchmark/configs/{n}.json", "reduced": [],
         "why": "test"} for n in ("tiny_inception", "tiny_resnet")]
    cells = {"tiny_inception.backlog": 1, "tiny_inception.paced": 1,
             "tiny_resnet.train1": 1, "tiny_resnet.train4": 4}
    manifest["workloads"] = [
        {"name": n, "config": n.split(".")[0], "traffic": n.split(".")[1], "chips": chips,
         "why": "test"} for n, chips in cells.items()]
    for m in manifest["end_to_end"]:
        if "workloads" in m:
            kind = "tiny_resnet" if m["name"].startswith("train") else "tiny_inception"
            m["workloads"] = [n for n in cells if n.startswith(kind)]
    manifest["per_layer"] = []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
