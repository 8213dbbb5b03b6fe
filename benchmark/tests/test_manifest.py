"""BENCHMARK.json and the files it names; a cell and a per-layer metric
added the way README.md says, by new files and appended entries alone."""
import json
import os
import shutil

import pytest

from benchmark.lib import manifest


def test_every_name_resolves():
    man = manifest.manifest()
    assert manifest.check(man)
    for w in man["workloads"]:
        assert manifest.metrics_of(man, "end_to_end", w["name"])
        assert manifest.metrics_of(man, "per_layer", w["name"])
        names = {m["name"] for m in
                 manifest.metrics_of(man, "end_to_end", w["name"])}
        assert "setup_s" in names and len(names) >= 2


def test_bad_names_and_units_are_refused():
    man = manifest.manifest()
    man["per_layer"][0] = dict(man["per_layer"][0], unit="tokens per second")
    with pytest.raises(ValueError):
        manifest.check(man)
    man = manifest.manifest()
    man["workloads"][0] = dict(man["workloads"][0], name="has space")
    with pytest.raises(ValueError):
        manifest.check(man)


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    root = str(tmp_path)
    shutil.copytree(manifest.BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = manifest.manifest()
    # a new traffic mix, its limits and a metric over an existing reducer
    t = manifest.traffic("train_b256")
    t.update(name="train_b128", batch=128)
    files = {
        "benchmark/traffic/train_b128.json": t,
        "benchmark/limits/resnet50_train_b128.json":
            manifest.limits("resnet50_train_b256"),
        "benchmark/layer_metrics/train_step_ms_median.json":
            {"name": "train_step_ms_median", "reducer": "window_value",
             "args": {"key": "step_ms_median"}},
    }
    for rel, body in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(body, f)
    man["workloads"].append({"name": "resnet50_train_b128",
                             "config": "resnet50_v1", "traffic": "train_b128",
                             "chips": 1, "why": "throw-away"})
    man["per_layer"].append({
        "name": "train_step_ms_median", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "train loop",
        "moves": "train_throughput", "workloads": ["resnet50_train_b128"]})
    for m in man["end_to_end"]:
        if m["name"] == "train_throughput":
            m["workloads"].append("resnet50_train_b128")
    assert manifest.check(man, root)
    got = {m["name"] for m in
           manifest.metrics_of(man, "per_layer", "resnet50_train_b128")}
    assert "train_step_ms_median" in got
    assert manifest.traffic("train_b128", root)["batch"] == 128
