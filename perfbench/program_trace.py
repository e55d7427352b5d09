"""The program's own spans and scopes, read out of the same profiler trace
`trace_reduce.py` reduces (which keeps the `perfbench.*` spans and the
device lines only). The yardstick's own reduction: nothing of `paddle_tpu`
is imported, and a program that has no such span or scope (the parent of
the PR that added them) gives readers nothing to read, never an error.

- host: every event named `<layer>/<what>` (`trainer/step`,
  `trainer/data_wait`, `eval/readback`, `data/pack`, ...) on every host
  thread line. They are `TraceAnnotation`s, so they lie on the device
  trace's clock. The trainer's thread is the line that holds the
  `trainer/step` events; spans nest by containment on a line.
- device: an `XLA Ops` event carries its HLO instruction's name and no
  scope. The program keeps each compile's optimized HLO text beside its
  records (`<out_dir>/hlo/<group>-<sig>.hlo.txt`), and an instruction's
  `metadata={op_name="jit(step)/jvp(fc:out)/dot_general"}` there is the
  map from an event to the `jax.named_scope`s it ran under. A fusion
  carries its root's `op_name`; copies and parameter moves carry none.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from perfbench import trace_reduce as tr

Span = Tuple[str, float, float, Optional[int]]   # name, start s, duration s, step number

SPAN_RE = re.compile(r"^[a-z][a-z_]*/[A-Za-z0-9_\-]+$")
STEP, LAUNCH = "trainer/step", "trainer/launch"
ROOTS = (STEP, "trainer/pass", "(no span)")   # an idle device under these alone is unexplained
SHORT_GAP_S = 2e-3      # the device's clock runs about 1 ms off the host's
FIXED_SCOPES = ("cost", "optimizer", "numerics")
GROUP = "recurrent_layer_group:"
UNSCOPED, OTHER_PROGRAMS = "(no scope)", "(other programs)"

_INSTR_RE = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_OPCODE_RE = re.compile(r" [a-z][a-z\-]*\(")
_SHAPE_RE = re.compile(r"[a-z]\w*\[([\d,]+)\]")
_WRAPPER_RE = re.compile(r"[a-z_]+\((.*)\)")


# ---------------------------------------------------------------- HLO text


def parse_hlo(text):
    """(module name, {instruction name: (op_name, last dimensions of its
    result's arrays)}) of one optimized HLO text."""
    head = text.split("\n", 1)[0]
    module = head.split()[1].rstrip(",") if head.startswith("HloModule ") else ""
    instrs = {}
    for line in text.split("\n"):
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        rest = m.group(2)
        cut = _OPCODE_RE.search(rest)
        result = rest[:cut.start()] if cut else rest
        lasts = frozenset(int(d.rsplit(",", 1)[-1])
                          for d in _SHAPE_RE.findall(result))
        op = _OP_NAME_RE.search(rest)
        instrs[m.group(1)] = (op.group(1) if op else "", lasts)
    return module, instrs


def read_hlo_dir(hlo_dir):
    """{module name: instructions} of every kept HLO text; of two texts of
    one module (two batch shapes) the newer wins where names collide."""
    out: Dict[str, Dict] = {}
    for path in sorted(glob.glob(os.path.join(hlo_dir, "*.hlo.txt")),
                       key=os.path.getmtime):
        with open(path) as f:
            module, instrs = parse_hlo(f.read())
        out.setdefault(module, {}).update(instrs)
    return out


def scope_path(op_name):
    """`jit(step)/transpose(jvp(recurrent_layer_group:dec))/while/body/fc:out/dot_general`
    -> ((`recurrent_layer_group:dec`, `fc:out`), True): the program's scopes
    from the outermost in, the `jit(..)`, `jvp(..)`, `transpose(..)`
    wrappers stripped, and whether the instruction is of the backward pass."""
    parts, depth, cur = [], 0, ""
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
            continue
        depth += (ch == "(") - (ch == ")")
        cur += ch
    parts.append(cur)
    scopes, backward = [], False
    for part in parts:
        backward = backward or "transpose(" in part
        m = _WRAPPER_RE.fullmatch(part)
        while m is not None:
            part = m.group(1)
            m = _WRAPPER_RE.fullmatch(part)
        if ":" in part or part in FIXED_SCOPES:
            scopes.append(part)
    return tuple(scopes), backward


def vocabulary_scopes(instrs, vocab):
    """The layer scopes (a recurrent group's own left out) under which some
    instruction's result has the dictionary's size as its LAST dimension:
    the vocabulary projection and the cost layer over it, whatever their
    names (an embedding's tables hold it first)."""
    out = set()
    for op_name, lasts in instrs.values():
        if vocab in lasts and op_name:
            layers = [s for s in scope_path(op_name)[0]
                      if ":" in s and not s.startswith(GROUP)]
            if layers:
                out.add(layers[-1])
    return frozenset(out)


# ------------------------------------------------------------------ trace


@dataclasses.dataclass
class Step:
    num: Optional[int]
    start: float
    end: float
    total: Dict[str, float]     # span name -> seconds inside this step
    self_s: Dict[str, float]    # span name -> seconds less its children's


@dataclasses.dataclass
class ScopeRow:
    instr: str
    seconds: float
    scopes: Tuple[str, ...]
    backward: bool


@dataclasses.dataclass
class ProgramTrace:
    lines: Dict[str, List[Span]]             # host thread line -> its program spans
    trace: tr.Trace                          # devices and the window
    hlo: Dict[str, Dict]                     # module -> instructions (parse_hlo)
    said: set = dataclasses.field(default_factory=set)   # tables printed

    def step_hlo(self, step_program):
        """The instructions of the step program that ran in the window (its
        HLO text is found by the module's name); None where there is none."""
        dev = self.trace.devices[0] if self.trace.devices else None
        module = next((n for n, _, _ in (dev.modules if dev else [])
                       if step_program in n), None)
        return self.hlo.get(module.split("(", 1)[0]) if module else None

    def trainer_line(self) -> List[Span]:
        lines = [sp for sp in self.lines.values()
                 if any(s[0] == STEP for s in sp)]
        return max(lines, key=len) if lines else []

    def in_window(self, spans):
        lo, hi = self.trace.window
        return [s for s in spans if s[1] >= lo and s[1] + s[2] <= hi]

    def steps(self) -> List[Step]:
        """The window's steps: each `trainer/step` that lies inside the
        window and holds a launch (the pull that finds a pass's end opens a
        `trainer/step` too, with nothing in it but the wait)."""
        line = self.in_window(self.trainer_line())
        out = []
        for name, start, dur, num in line:
            if name != STEP:
                continue
            inside = [(n, s, d) for n, s, d, _ in line
                      if s >= start and s + d <= start + dur]
            if not any(n == LAUNCH for n, _, _ in inside):
                continue
            total: Dict[str, float] = {}
            for n, _, d in inside:
                total[n] = total.get(n, 0.0) + d
            out.append(Step(num, start, start + dur, total,
                            tr.self_times(inside)))
        return out

    def per_step_ms(self, totals=(), selfs=()):
        """Each step's milliseconds in the spans `totals` (children
        included) plus the self time of the spans `selfs`."""
        return [1e3 * (sum(st.total.get(n, 0.0) for n in totals)
                       + sum(st.self_s.get(n, 0.0) for n in selfs))
                for st in self.steps()]

    def idle_by_span(self):
        """{span: idle seconds}: each idle gap of the first device in the
        window (those under 2 ms left out), cut at the boundaries of the
        trainer thread's spans, each piece given to the deepest span over
        it; `(no span)` where the thread was under none."""
        if not self.trace.devices:
            return {}
        line = [(n, s, s + d) for n, s, d, _ in self.trainer_line()]
        out: Dict[str, float] = {}
        for lo, hi in self.trace.gaps(self.trace.devices[0]):
            if hi - lo < SHORT_GAP_S:
                continue
            cover = [sp for sp in line if sp[1] < hi and sp[2] > lo]
            cuts = sorted({lo, hi, *(t for _, a, b in cover for t in (a, b)
                                     if lo < t < hi)})
            for a, b in zip(cuts, cuts[1:]):
                mid = 0.5 * (a + b)
                over = [(e - s, n) for n, s, e in cover if s <= mid <= e]
                name = min(over)[1] if over else "(no span)"
                out[name] = out.get(name, 0.0) + (b - a)
        return out

    def device_rows(self, step_program) -> Optional[List[ScopeRow]]:
        """Self time of the first device's events in the window, each with
        the scopes of its instruction: the step program's through its HLO
        text, every other program's under `(other programs)`. None where
        the step program ran no event or left no HLO text."""
        instrs = self.step_hlo(step_program)
        if not instrs:
            return None
        dev, (lo, hi) = self.trace.devices[0], self.trace.window
        runs = [(s, s + d) for n, s, d in dev.modules
                if step_program in n and s >= lo and s + d <= hi]
        mine, others = [], []
        for e in dev.ops:
            if e[1] >= lo and e[1] + e[2] <= hi:
                inside = any(a <= e[1] < b for a, b in runs)
                (mine if inside else others).append(e)
        rows = []
        for name, sec in tr.self_times(mine).items():
            instr = name.split(" = ", 1)[0].lstrip("%")
            scopes, backward = scope_path(instrs.get(instr, ("", ()))[0])
            rows.append(ScopeRow(instr, sec, scopes or (UNSCOPED,), backward))
        other = sum(tr.self_times(others).values())
        if other > 0:
            rows.append(ScopeRow("", other, (OTHER_PROGRAMS,), False))
        return rows

    def say(self, key, text):
        """A reader's table, once a run, on standard error."""
        if key not in self.said:
            self.said.add(key)
            print(text, file=sys.stderr)


def host_lines(path):
    """{`<plane>/<line>#<n>`: [program spans]} of every host thread line."""
    from jax.profiler import ProfileData

    lines: Dict[str, List[Span]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = []
            for e in line.events:
                if SPAN_RE.match(e.name):
                    num = next((int(v) for k, v in e.stats if k == "step_num"),
                               None) if e.name == STEP else None
                    spans.append((e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9, num))
            if spans:
                # threads share names (`python3`): the index keeps them apart
                lines[f"{plane.name}/{line.name}#{i}"] = spans
    return lines


@functools.lru_cache(maxsize=2)
def _read(xplane_path, hlo_dir):
    """One trace file's host lines and HLO texts, read once for all the
    readers of a run (and the set of the tables they have printed)."""
    return host_lines(xplane_path), read_hlo_dir(hlo_dir), set()


def of(view) -> Optional[ProgramTrace]:
    """The program's side of the view's trace; None where the run was not
    traced."""
    trace_dir = getattr(view.run, "trace_dir", None)
    path = tr.newest_xplane(trace_dir) if trace_dir and view.trace else None
    if path is None:
        return None
    lines, hlo, said = _read(path, os.path.join(os.path.dirname(trace_dir), "hlo"))
    return ProgramTrace(lines, view.trace, hlo, said)


# ---------------------------------------------------------------- readers


def host_table(pt: ProgramTrace) -> str:
    steps = pt.steps()
    names = sorted({n for st in steps for n in st.total})
    out = [f"perfbench: host phases of {len(steps)} steps, numbers "
           f"{steps[0].num} to {steps[-1].num} (ms a step: median, self median)"]
    for n in names:
        out.append(f"  {n:<32s} "
                   f"{1e3 * statistics.median(st.total.get(n, 0.0) for st in steps):10.3f} "
                   f"{1e3 * statistics.median(st.self_s.get(n, 0.0) for st in steps):10.3f}")
    trainer = pt.trainer_line()
    for key, spans in sorted(pt.lines.items()):
        if spans is trainer:
            continue
        acc: Dict[str, List[float]] = {}
        for n, _, d, _ in pt.in_window(spans):
            acc.setdefault(n, []).append(d)
        for n, ds in sorted(acc.items()):
            out.append(f"  {n:<32s} {len(ds):4d} x {1e3 * statistics.median(ds):9.3f} ms "
                       f"(thread {key.rsplit('/', 1)[-1]})")
    return "\n".join(out)


def step_span_ms(view, totals=(), selfs=()):
    """Median over the window's steps of the milliseconds a step spends in
    the spans `totals` plus the self time of `selfs`; None with no step."""
    pt = of(view)
    if pt is None or not pt.steps():
        return None
    pt.say("host", host_table(pt))
    return statistics.median(pt.per_step_ms(totals, selfs))


def idle_unexplained_pct(view):
    """Share of the first device's idle seconds (gaps of 2 ms and more) that
    lie under no program span deeper than `trainer/step` (between a step's
    phases, between a pass's steps, or under no span at all)."""
    pt = of(view)
    if pt is None or not pt.steps():
        return None
    idle = pt.idle_by_span()
    whole = sum(idle.values())
    if whole <= 0:
        return None
    pt.say("idle", "perfbench: the first device's idle seconds by the deepest "
           "program span over them: " + ", ".join(
               f"{n} {s:.4f}" for n, s in sorted(idle.items(), key=lambda kv: -kv[1])))
    return 100.0 * sum(idle.get(n, 0.0) for n in ROOTS) / whole


def device_table(rows, steps, vocab_scopes):
    def by(key):
        acc: Dict[str, List[float]] = {}
        for r in rows:
            a = acc.setdefault(key(r), [0.0, 0.0])
            a[r.backward] += r.seconds
        return sorted(acc.items(), key=lambda kv: -sum(kv[1]))

    out = [f"perfbench: device self time by program scope, ms a step over "
           f"{steps} steps (forward, backward); vocabulary scopes: "
           f"{sorted(vocab_scopes)}"]
    for title, key in (("outermost", lambda r: r.scopes[0]),
                       ("innermost", lambda r: r.scopes[-1])):
        out.append(f"  by {title} scope:")
        for name, (fwd, bwd) in by(key)[:16]:
            out.append(f"    {name:<52s} {1e3 * fwd / steps:9.3f} {1e3 * bwd / steps:9.3f}")
    return "\n".join(out)


def _device(view):
    """(rows, steps, vocabulary scopes) of the view's step program, the
    table printed; None where there is nothing to read."""
    pt = of(view)
    if pt is None:
        return None
    program = view.run.facts["step_program"]
    rows = pt.device_rows(program)
    steps = pt.trace.module_count(program)
    if not rows or not steps:
        return None
    vocab = vocabulary_scopes(pt.step_hlo(program),
                              int(view.cell.config.get("target_dict_dim", 0)))
    pt.say("device", device_table(rows, steps, vocab))
    return rows, steps, vocab


def device_scope_ms(view, pick):
    """Milliseconds a step of the device's self time in the rows that
    `pick(row, vocabulary scopes)` takes."""
    got = _device(view)
    if got is None:
        return None
    rows, steps, vocab = got
    return 1e3 * sum(r.seconds for r in rows if pick(r, vocab)) / steps


def unscoped_device_pct(view):
    """Share of the device's self time in the window whose event has no
    program scope (other programs' events among them)."""
    got = _device(view)
    if got is None:
        return None
    bare = sum(r.seconds for r in got[0]
               if r.scopes[0] in (UNSCOPED, OTHER_PROGRAMS))
    return 100.0 * bare / sum(r.seconds for r in got[0])


def under_vocabulary(row, vocab):
    return "cost" in row.scopes or any(s in vocab for s in row.scopes)


def under_group_scan(row, vocab):
    return (any(s.startswith(GROUP) for s in row.scopes)
            and not under_vocabulary(row, vocab))
