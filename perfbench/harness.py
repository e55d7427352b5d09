"""The harness: one cell, one run, one JSON line.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix, entry or
per-layer metric is a file of its own that this finds by the name in
`BENCHMARK.json` (see `perfbench/README.md`); there is no list of cells
here and no branch on a cell's or a configuration's name.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

T_PROCESS_START = time.monotonic()

EXIT_NO_CHIP = 3
OUT_DIR = "perfbench_out"          # inside the checkout, git-ignored


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import a file by path (names such as `step_mfu.train.py` are not
    importable by name)."""
    name = "perfbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of `workloads` with every file it names resolved."""
    root: str
    name: str
    chips: int
    benchmark: Dict[str, Any]
    workload: Dict[str, Any]       # perfbench/workloads/<cell>.json
    config: Dict[str, Any]         # perfbench/configs/<config>.json
    config_path: str
    mix: Dict[str, Any]            # perfbench/traffic/<traffic>.json

    def path(self, *parts):
        return os.path.join(self.root, "perfbench", *parts)

    def module(self, kind, name):
        return load_module(self.path(kind, name + ".py"))

    def metrics(self, group):
        """The cell's metrics of `end_to_end` or `per_layer`."""
        return [m for m in self.benchmark[group]
                if self.name in m.get("workloads", [self.name])]


def load_cell(root, name) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    pb = os.path.join(root, "perfbench")
    return Cell(
        root=root, name=name, chips=int(entry["chips"]), benchmark=bench,
        workload=load_json(os.path.join(pb, "workloads", name + ".json")),
        config=load_json(os.path.join(root, cfg_entry["file"])),
        config_path=os.path.join(root, cfg_entry["file"]),
        mix=load_json(os.path.join(pb, "traffic", entry["traffic"] + ".json")))


@dataclasses.dataclass
class Check:
    """One number compared for `correct`, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self):
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What an entry hands back. `values` holds every end-to-end metric it
    measured by name; `facts` whatever the per-layer readers need."""
    attempted: int
    failed: int
    values: Dict[str, float]
    facts: Dict[str, Any]
    checks: List[Check]
    memory_peak_bytes: int
    trace_dir: Optional[str] = None


class Context:
    """What an entry is given."""

    def __init__(self, cell, seed, seconds, trace, require_chip):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.require_chip = bool(trace), require_chip
        self.out_dir = os.path.join(cell.root, OUT_DIR, cell.name)
        self.t_start = T_PROCESS_START

    def fresh_out_dir(self):
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        return self.out_dir

    def records(self):
        """The program's own metrics.jsonl records of this run."""
        out = []
        for path in sorted(glob.glob(os.path.join(self.out_dir, "**", "*.jsonl"),
                                     recursive=True)):
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if line.startswith("{"):
                        try:
                            out.append(json.loads(line))
                        except ValueError:
                            pass
        return out


def describe_device(chips, require_chip):
    """The device as JAX reports it; leaves the process, with no result
    printed, where there is no TPU or fewer chips than the cell asks for."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"perfbench: no accelerator: {e}", file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices)}
    if require_chip and (d["platform"] != "tpu" or d["count"] < chips):
        print(f"perfbench: the cell needs {chips} TPU chip(s), JAX found "
              f"{d['count']} x {d['platform']}", file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    return d, devices[:chips]


def peaks_for(cell, kind):
    table = load_json(cell.path("peaks.json"))
    if kind not in table["devices"]:
        raise SystemExit(f"perfbench/peaks.json has no device {kind!r}: add "
                         "its published peaks with their source")
    return table["devices"][kind]


def memory_peak(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(argv=None, root=None, require_chip=True, out=sys.stdout):
    ap = argparse.ArgumentParser(prog="perfbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.abspath(root or os.getcwd())
    cell = load_cell(root, args.workload)
    ctx = Context(cell, args.seed, args.seconds, args.trace, require_chip)
    ctx.device, ctx.devices = describe_device(cell.chips, require_chip)
    ctx.peaks = peaks_for(cell, ctx.device["kind"]) if require_chip else None

    entry = cell.module("entries", cell.workload["entry"])
    run: Run = entry.run(ctx)

    if ctx.trace:
        metrics, device_extra, breakdown = read_layers(ctx, run)
    else:
        metrics = {}
        for m in cell.metrics("end_to_end"):
            if m["name"] in run.values:
                metrics[m["name"]] = {"value": run.values[m["name"]],
                                      "unit": m["unit"]}
        device_extra, breakdown = {}, None
    correct = bool(run.checks) and all(c.ok for c in run.checks)
    line = {"correct": correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics,
            "device": dict(ctx.device,
                           memory_peak_bytes=run.memory_peak_bytes,
                           **device_extra)}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    sys.stderr.flush()
    for c in run.checks:
        print(f"perfbench compared {c.name}: {c.value:.6g} (limit {c.limit:g})"
              f"{'' if c.ok else '  <-- NOT within its limit'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), file=out, flush=True)
    return line


def read_layers(ctx, run):
    """The traced run's per-layer metrics: each is a reader of its own,
    `perfbench/layer_metrics/<name>.py`, given the reduced trace and the
    run; one that finds nothing to read returns None and is left out."""
    from perfbench import trace_reduce

    cell = ctx.cell
    trace = (trace_reduce.load(run.trace_dir, cell.chips)
             if run.trace_dir else None)
    view = trace_reduce.View(trace=trace, run=run, cell=cell, peaks=ctx.peaks,
                             chips=cell.chips)
    metrics = {}
    for m in cell.metrics("per_layer"):
        reader = cell.module("layer_metrics", m["name"])
        value = reader.read(view)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    extra, breakdown = {}, None
    if trace is not None:
        extra = {"busy_s": trace.busy_s(), "window_s": trace.window_s}
        breakdown = trace.breakdown()
    return metrics, extra, breakdown


def main(argv=None):
    run_cell(argv)
    return 0
