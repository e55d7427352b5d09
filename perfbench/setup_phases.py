"""Set-up by phase, from the program's own records: what `setup_s` (one
host clock difference from process start to the window's first instant) is
made of. The trace starts with the window, so nothing of set-up is in the
xplane; the program's spans and counters are.

Read: the window's `pass_end` record and the one before it (as
`scope_times.window_counters` finds them).

- spans before the window = the window's `spans_total` (the StatSet's
  `{name: [count, total_s]}` since process start, at the window's pass end)
  less its `spans` (that pass's growth): every span that closed before the
  window's pass began, none twice. The window's own `trainer/train` and
  `trainer/pass` are still open at its pass end, so they are in neither;
  `data/provider_start` of the window's call closed before its pass and is
  in (one call more than `trainer/train` counts).
- counters before the window = the previous `pass_end`'s `counters`
  (cumulative; the last pass of set-up).

A program whose records lack `spans_total` or the `jax.*` counters (the
parent of the PR that added them) gives nothing to read: every function
returns None, never an error.

By hand, after any run of a cell, traced or not (the table a traced run
says on standard error):

    python3 -m perfbench.setup_phases perfbench_out/<cell> [<setup_s>]
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, Optional, Tuple

# the program's spans of set-up as they nest, parents before children. A
# span's self time is its total less its children's: `trainer/test` also
# runs under `trainer/housekeeping`, and `compile/*` under it for the test
# forward, so a parent's self time reads low by what its child spent
# elsewhere (nothing, in the benchmark's cells: they have no test data)
TREE = (
    ("config/parse", ()),
    ("trainer/init", ("trainer/init_devices", "trainer/init_graph",
                      "trainer/init_params", "trainer/init_opt_state",
                      "checkpoint/load")),
    ("trainer/train", ("data/provider_start", "trainer/pass", "trainer/test",
                       "checkpoint/save")),
    ("trainer/pass", ("trainer/step",)),
    ("trainer/step", ("trainer/data_wait", "trainer/flops_count",
                      "trainer/launch", "trainer/loss_sync",
                      "trainer/eval_outputs", "trainer/housekeeping")),
    ("trainer/launch", ("compile/trace_lower", "compile/backend",
                        "compile/report")),
)
ROOTS = ("config/parse", "trainer/init", "trainer/train")
COUNTERS = ("jax.trace_s", "jax.lower_s", "jax.backend_compile_s",
            "jax.cache_load_s", "jax.compiles")

_said = set()


def pass_ends(out_dir) -> Optional[Tuple[dict, dict]]:
    """(the window's `pass_end` record, the one before it) from the
    program's records under a run's directory; None where there are not
    two."""
    ends = []
    for path in sorted(glob.glob(os.path.join(out_dir, "**", "*.jsonl"),
                                 recursive=True)):
        with open(path) as f:
            for line in f:
                if line.startswith("{") and '"pass_end"' in line:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("kind") == "pass_end":
                        ends.append(rec)
    if len(ends) < 2:
        return None
    ends.sort(key=lambda r: r.get("pass", 0))
    return ends[-1], ends[-2]


def spans_before_window(window: dict) -> Optional[Dict[str, Tuple[int, float]]]:
    """{span: (count, seconds)} closed before the window's pass began."""
    total = window.get("spans_total")
    if not total:
        return None
    out = {}
    for name, (count, sec) in total.items():
        c0, s0 = window.get("spans", {}).get(name, (0, 0.0))
        if count > c0:
            out[name] = (count - c0, sec - s0)
    return out


def before_window(out_dir) -> Optional[Tuple[Dict[str, Tuple[int, float]], Dict[str, float]]]:
    """(spans before the window, counters before the window) of the run
    whose records lie under `out_dir`; None where the program wrote
    neither."""
    ends = pass_ends(out_dir)
    spans = spans_before_window(ends[0]) if ends else None
    if spans is None:
        return None
    return spans, ends[1].get("counters") or {}


def read(view):
    """`before_window` of the view's run (its records lie beside its
    trace); says the table once a run."""
    trace_dir = getattr(view.run, "trace_dir", None)
    got = before_window(os.path.dirname(trace_dir)) if trace_dir else None
    if got is not None and trace_dir not in _said:
        _said.add(trace_dir)
        print(table(*got, view.run.values.get("setup_s")), file=sys.stderr)
    return got


def seconds(spans, *names) -> float:
    return sum(spans.get(n, (0, 0.0))[1] for n in names)


def compile_counters(counters) -> Optional[Dict[str, float]]:
    """The listener's counters, those that never grew as 0; None on a
    program without the listener (tracing happens in every process, so its
    counter is there wherever the listener is)."""
    if "jax.trace_s" not in counters:
        return None
    return {n: float(counters.get(n, 0.0)) for n in COUNTERS}


def outside_program_s(spans, setup_s) -> float:
    """`setup_s` less the program's roots: what is the harness's and the
    runtime's."""
    return setup_s - seconds(spans, *ROOTS)


def table(spans, counters, setup_s) -> str:
    """Set-up by span: count, total, self time, share of `setup_s`; parents
    before children; the listener's counters under it."""
    children = dict(TREE)
    share = (lambda s: 100.0 * s / setup_s) if setup_s else (lambda s: float("nan"))
    out = [f"perfbench: set-up by span, before the window "
           f"(setup_s {setup_s if setup_s is None else round(setup_s, 3)}; "
           "count, total s, self s, % of setup_s)"]
    shown = set()

    def row(name, depth):
        if name not in spans:
            return
        shown.add(name)
        count, total = spans[name]
        own = total - seconds(spans, *children.get(name, ()))
        out.append(f"  {'  ' * depth + name:<34s} {count:5d} {total:10.3f} "
                   f"{own:10.3f} {share(total):7.2f}")
        for child in children.get(name, ()):
            row(child, depth + 1)

    for root in ROOTS:
        row(root, 0)
    if setup_s:
        rest = outside_program_s(spans, setup_s)
        out.append(f"  {'(outside the program)':<34s} {'':5s} {rest:10.3f} "
                   f"{'':10s} {share(rest):7.2f}")
    others = sorted(n for n in spans if n not in shown)
    if others:
        out.append("  on other threads or under several parents:")
        for n in others:
            out.append(f"    {n:<32s} {spans[n][0]:5d} {spans[n][1]:10.3f}")
    got = compile_counters(counters)
    if got is not None:
        out.append("  counters at the last pass end before the window: "
                   + ", ".join(f"{n} {got[n]:.3f}" if n != "jax.compiles"
                               else f"{n} {int(got[n])}" for n in COUNTERS)
                   + f", compile.count {int(counters.get('compile.count', 0))}"
                   + f", compile.aot_fallbacks {int(counters.get('compile.aot_fallbacks', 0))}")
    return "\n".join(out)


if __name__ == "__main__":
    found = before_window(sys.argv[1])
    if found is None:
        raise SystemExit(f"no set-up spans in the records under {sys.argv[1]}")
    print(table(*found, float(sys.argv[2]) if len(sys.argv) > 2 else None))
