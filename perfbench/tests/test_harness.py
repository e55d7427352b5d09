"""The harness is driven by data: a cell, a configuration, a traffic mix and
a per-layer metric dropped into a temporary copy as NEW files are found by
name with no other edit; the last line's keys; no result off the TPU."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.tests import helpers


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.make_root(tmp_path_factory.mktemp("bench"))


def _add_files(root):
    """A new configuration, mix, cell and per-layer metric, as files."""
    pb = os.path.join(root, "perfbench")
    cfg = json.load(open(os.path.join(pb, "configs", "toy-nmt.json")))
    cfg.update(name="toy-nmt-wide", decoder_size=256)
    json.dump(cfg, open(os.path.join(pb, "configs", "toy-nmt-wide.json"), "w"))
    mix = json.load(open(os.path.join(pb, "traffic", "toy_pairs.json")))
    mix["arrival"]["batch"] = 4
    json.dump(mix, open(os.path.join(pb, "traffic", "toy_pairs_b4.json"), "w"))
    wl = json.load(open(os.path.join(pb, "workloads", "toy.train.json")))
    wl.update(config="toy-nmt-wide", traffic="toy_pairs_b4")
    json.dump(wl, open(os.path.join(pb, "workloads", "toy.wide.json"), "w"))
    with open(os.path.join(pb, "layer_metrics", "steps_in_window.py"), "w") as f:
        f.write("def read(view):\n    return view.run.facts['steps']\n")
    with open(os.path.join(pb, "layer_metrics", "nothing_to_read.py"), "w") as f:
        f.write("def read(view):\n    return None\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "toy-nmt-wide", "source": "toy",
                             "file": "perfbench/configs/toy-nmt-wide.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "toy.wide", "config": "toy-nmt-wide",
                               "traffic": "toy_pairs_b4", "chips": 1, "why": "toy"})
    bench["end_to_end"][0]["workloads"].append("toy.wide")
    for name in ("steps_in_window", "nothing_to_read"):
        bench["per_layer"].append(
            {"name": name, "unit": "steps", "better": "higher",
             "source": "program_counter", "layer": "trainer loop",
             "moves": "train_tokens_per_s", "workloads": ["toy.wide"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))


def test_new_cell_config_mix_and_metric_are_found_as_files(root):
    _add_files(root)
    line = helpers.run_toy(root, "toy.wide", seconds=0.5, trace=1)
    assert line["correct"] is True, line["compared"]
    assert line["metrics"]["steps_in_window"]["value"] >= 1
    assert "nothing_to_read" not in line["metrics"]   # a reader with nothing to read
    line = helpers.run_toy(root, "toy.wide", seconds=0.5)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_last_line_keys(root, cell="toy.train",
                        metrics=frozenset({"train_tokens_per_s", "setup_s"})):
    line = helpers.run_toy(root, cell, seed=2 ** 31 + 5, seconds=0.5)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == metrics
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert all(set(v) == {"value", "limit"} for v in line["compared"].values())


def test_off_the_tpu_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=helpers.REPO)
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "nmt.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=helpers.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tokens_per_s" not in p.stderr


def test_alone_with_only_the_benchmarks_files_it_fails(tmp_path):
    import shutil

    root = tmp_path / "alone"
    shutil.copytree(os.path.join(helpers.REPO, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(helpers.REPO, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", "nmt.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
