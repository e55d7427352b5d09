"""Device time by the program's INNER scopes: the parts a layer names
inside its own `<type>:<name>` scope (`multi_head_attention:l0_attn/core`,
`moe:l0_moe/experts`). `program_trace.scope_path` keeps the layer scopes
only; the readers of the sparse-expert and attention metrics need the part
too, so this maps each device event of the step program to its
instruction's whole `op_name` (the kept HLO text) and matches that.

A program with no such scope (the parent of the PR that added them) gives
nothing to read: every function returns None, never an error.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Optional

from perfbench import program_trace
from perfbench import trace_reduce as tr


def seconds_under(view, pattern, instr_pattern=None) -> Optional[tuple]:
    """(device self seconds in the window of the step program's events
    whose instruction's `op_name` matches `pattern`, or whose instruction's
    own name matches `instr_pattern`; steps in the window); None where
    nothing matches or nothing was traced."""
    pt = program_trace.of(view)
    if pt is None or not pt.trace.devices:
        return None
    program = view.run.facts["step_program"]
    instrs = pt.step_hlo(program)
    steps = pt.trace.module_count(program)
    if not instrs or not steps:
        return None
    rx = re.compile(pattern)
    by_name = re.compile(instr_pattern) if instr_pattern else None
    dev, (lo, hi) = pt.trace.devices[0], pt.trace.window
    runs = [(s, s + d) for n, s, d in dev.modules
            if program in n and s >= lo and s + d <= hi]
    mine = [e for e in dev.ops if lo <= e[1] and e[1] + e[2] <= hi
            and any(a <= e[1] < b for a, b in runs)]
    sec, hits = 0.0, 0
    for name, s in tr.self_times(mine).items():
        instr = name.split(" = ", 1)[0].lstrip("%")
        if rx.search(instrs.get(instr, ("", ()))[0]) or (by_name and by_name.search(instr)):
            sec, hits = sec + s, hits + 1
    return (sec, steps) if hits else None


def scope_ms(view, pattern, instr_pattern=None):
    """Milliseconds a step of device self time under `pattern`."""
    got = seconds_under(view, pattern, instr_pattern)
    return None if got is None else 1e3 * got[0] / got[1]


def window_counters(view):
    """(the traced window's `pass_end` counters, the pass before's) from
    the program's own records beside the trace; None where there are none."""
    trace_dir = getattr(view.run, "trace_dir", None)
    if not trace_dir:
        return None
    ends = []
    for path in sorted(glob.glob(os.path.join(os.path.dirname(trace_dir), "**", "*.jsonl"),
                                 recursive=True)):
        with open(path) as f:
            for line in f:
                if line.startswith("{") and '"pass_end"' in line:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("kind") == "pass_end" and "counters" in rec:
                        ends.append(rec)
    if not ends:
        return None
    ends.sort(key=lambda r: r.get("pass", 0))
    return ends[-1]["counters"], (ends[-2]["counters"] if len(ends) > 1 else {})


# the parts of the two layers, as their scopes nest in an `op_name`
MOE = r"(?:^|[/(])moe:[^/]*/(?:.*/)?"
EXPERTS = MOE + r"experts(?:/|$)"
# The grouped products' own events cannot be matched by scope: the TPU
# compiler rewrites `jax.lax.ragged_dot` into custom calls of its own and
# writes ITS `op_name` over the program's (`metadata={op_name="ragged-dot-none"}`
# on the 48 `ragged-dot-none.N` instructions of the step, "ragged-dot-metadata"
# on the 12 that build their tables; the step compiled for a described v5e, PR
# 28), so no scope opened in the program reaches them (my chip run, PR 28: 65 ms
# a step of them, against 1.5 ms under the scope). They are taken by
# instruction name BESIDE the scope: another implementation of the products
# under the `experts` scope (a Pallas kernel) is read by the scope, and these
# names are then gone from the step
EXPERT_PRODUCTS = r"^ragged-dot"
ATTENTION = r"(?:^|[/(])multi_head_attention:[^/)]*"
