"""Spreads of a cell's two sets of runs, as the contract measures them:
`python -m perfbench.tests.spread chiprun_out/<cell>.setA.jsonl
chiprun_out/<cell>.setB.jsonl`. A spread is the distance between the first
and the third quartile (`statistics.quantiles(values, n=4)`) as a share of
the median; a bound is about five times the wider of the two sets' spreads
and never under 1 %."""

import json
import statistics
import sys


def read(path):
    return [json.loads(l) for l in open(path) if l.startswith("{")]


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(paths):
    sets = [read(p) for p in paths]
    names = sorted({k for s in sets for r in s for k in r["metrics"]})
    for name in names:
        row = []
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in s if name in r["metrics"]]
            # the first run of a side compiles: setup_s leaves it out
            if name == "setup_s":
                vals = vals[1:]
            row.append((statistics.median(vals), spread(vals), len(vals)))
        widest = max(r[1] for r in row)
        print(f"{name:24s} " + "  ".join(
            f"median {m:.6g} spread {100 * s:.3f}% (n={n})" for m, s, n in row)
            + f"  -> bound {max(0.01, 5 * widest):.4f}")
    for s, p in zip(sets, paths):
        bad = [r for r in s if not r["correct"] or r["failed"]]
        peaks = {r["device"]["memory_peak_bytes"] for r in s}
        print(f"{p}: {len(s)} runs, {len(bad)} not correct or with failures, "
              f"memory peaks {sorted(peaks)}")
        for k in sorted({k for r in s for k in r["compared"]}):
            vals = [r["compared"][k]["value"] for r in s]
            print(f"    compared {k}: max {max(vals):.6g} min {min(vals):.6g}")


if __name__ == "__main__":
    main(sys.argv[1:])
