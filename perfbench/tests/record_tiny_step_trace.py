"""Record the pair the program-trace tests read, on the chip:
`python -m perfbench.tests.record_tiny_step_trace <out dir>` runs the toy
train cell of `tests/data/overlay/` traced, four steps in the window, and
leaves `tiny_step.xplane.pb` (the trace) and `tiny_step.hlo.txt` (the train
step's optimized HLO text, as the program keeps it beside its compile
record) in `<out dir>`. The cell's per-layer readers run too, so their
tables are on standard error."""

import glob
import io
import json
import os
import shutil
import sys
import tempfile

from perfbench import harness, trace_reduce
from perfbench.tests import helpers


def main(out_dir):
    os.makedirs(out_dir, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        root = helpers.make_root(tmp)
        path = os.path.join(root, "perfbench", "workloads", "toy.train.json")
        with open(path) as f:
            wl = json.load(f)
        wl.update(trace_seconds=0.0, trace_min_steps=4)
        with open(path, "w") as f:
            json.dump(wl, f)
        # the toy cell reports the train step's per-layer metrics of the
        # real benchmark too
        with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
            real = json.load(f)
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        have = {m["name"] for m in bench["per_layer"]}
        bench["per_layer"] += [dict(m, workloads=["toy.train"])
                               for m in real["per_layer"] if m["name"] not in have]
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        out = io.StringIO()
        harness.run_cell(["--workload", "toy.train", "--seed", "7", "--seconds", "1",
                          "--trace", "1"], root=root, require_chip=True, out=out)
        print(out.getvalue().strip().splitlines()[-1])
        run_dir = os.path.join(root, harness.OUT_DIR, "toy.train")
        trace = trace_reduce.newest_xplane(os.path.join(run_dir, "trace"))
        shutil.copy(trace, os.path.join(out_dir, "tiny_step.xplane.pb"))
        (hlo,) = glob.glob(os.path.join(run_dir, "hlo", "train_step-*.hlo.txt"))
        shutil.copy(hlo, os.path.join(out_dir, "tiny_step.hlo.txt"))
        for name in ("tiny_step.xplane.pb", "tiny_step.hlo.txt"):
            print(name, os.path.getsize(os.path.join(out_dir, name)), "bytes",
                  file=sys.stderr)


if __name__ == "__main__":
    main(sys.argv[1])
