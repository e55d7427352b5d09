"""The control and the planted faults, read on the chip at a cell's own
size: `python3 -m perfbench.tests.control_on_chip <cell> <seed> [<seed> ...]`
(three seeds or more; the benchmark's own runs never run this).

The control is the plain reference put in the program's place and computed
in the nearest precision below the one the configuration states
(`precision.control_mode` in the configuration's file); the faults are
planted in the reference put in the program's place: half of the batch left
out and the mean taken over the rest, and a state left unchanged. Each goes
through the cell's own comparison and limits (`compare.checks`, `Check.ok`),
as a run's readings do, and has to come out as not correct: a reading that
comes out correct makes this exit 1. The limits in the cell's file are set
between the largest reading of sound runs of the program and the smallest
reading here (PERF.md gives both).
"""

import json
import os
import sys

from perfbench import harness


def main(argv):
    cell = harness.load_cell(os.getcwd(), argv[0])
    seeds = [int(s) for s in argv[1:]]
    # the compile cache the benchmark's runs use
    from paddle_tpu.observability.compile_log import enable_compile_cache
    enable_compile_cache("")
    gen = cell.module("traffic", cell.mix["generator"])
    ref = cell.module("reference", cell.config["reference"])
    compare = cell.module("compare", cell.workload["compare"])
    mode = cell.config["precision"]["control_mode"]
    limits = cell.workload["limits"]
    passed = []
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/{cell.name}.control.jsonl", "a") as out:
        for seed in seeds:
            items = gen.generate(cell.mix, cell.config, seed)
            batches = [gen.arrays_of(items, g) for g in range(3)]
            base = compare.reference_steps(ref, cell.config, seed, batches)
            for name, kw in (("control_" + mode, {"mode": mode}),
                             ("fault_half_batch", {"fault": "half_batch"}),
                             ("fault_state_unchanged", {"fault": "state_unchanged"})):
                other = compare.reference_steps(ref, cell.config, seed, batches, **kw)
                checks = compare.checks(other, base, limits)
                correct = all(c.ok for c in checks)
                line = {"cell": cell.name, "seed": seed, "reading": name,
                        "correct": correct,
                        "compared": {c.name: {"value": c.value, "limit": c.limit,
                                              "ok": c.ok} for c in checks}}
                print(json.dumps(line), flush=True)
                print(json.dumps(line), file=out, flush=True)
                if correct:
                    passed.append((seed, name))
    if passed:
        print(f"control_on_chip: came out CORRECT: {passed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
