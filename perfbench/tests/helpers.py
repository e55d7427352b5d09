"""Shared by the tests and the CPU rehearsal: a temporary copy of the
benchmark with the toy files of `perfbench/tests/data/overlay` dropped in
as NEW files, and a run of the harness against it with the look for a chip
skipped."""

import io
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def make_root(tmp):
    root = os.path.join(str(tmp), "root")
    shutil.copytree(os.path.join(REPO, "perfbench"), os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copytree(os.path.join(HERE, "data", "overlay"), root, dirs_exist_ok=True)
    return root


def run_toy(root, workload, seed=7, seconds=1.0, trace=0):
    from perfbench import harness

    out = io.StringIO()
    harness.run_cell(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)],
                     root=root, require_chip=False, out=out)
    return json.loads(out.getvalue().strip().splitlines()[-1])
