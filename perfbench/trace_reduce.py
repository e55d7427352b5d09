"""From a profiler trace (`*.xplane.pb`) to the numbers the per-layer
readers use. The yardstick's own reduction: nothing here is read from the
program.

A trace is reduced to plain tuples first (`Trace`), so that the arithmetic
(busy union, idle gaps, self times, kernel matching) is
the same for a recorded file and for a hand-made one in the tests.

- device planes are `/device:TPU:<n>`; their `XLA Ops` line holds one event
  an executed HLO op (a `while` event encloses its body's events), their
  `XLA Modules` line one event an executed program;
- host spans are the `perfbench.*` `TraceAnnotation` events of the host
  planes' thread lines. `perfbench.window` brackets the measured window and
  gives its place on the trace's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, float, float]      # name, start s, duration s

WINDOW_SPAN = "perfbench.window"
_SSA_RE = re.compile(r"(\.\d+)+(\.clone\d*|\.remat\d*)*$")


def op_kind(name):
    """`%fusion.26 = ...` / `fusion.26` -> `fusion`."""
    return _SSA_RE.sub("", name.split(" = ", 1)[0].lstrip("%"))


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged intervals `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """name -> seconds of self time on one line whose events nest (an
    enclosing `while` loses what its body's events cover)."""
    out: Dict[str, float] = {}
    stack = []                       # [end, name, duration, children's sum]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + max(0.0, item[2] - item[3])

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start + 1e-12:
            close(stack.pop())
        if stack:
            stack[-1][3] += dur
        stack.append([start + dur, name, dur, 0.0])
    while stack:
        close(stack.pop())
    return out


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host_spans: List[Event]
    window: Tuple[float, float]       # on the trace's clock, seconds

    @property
    def window_s(self):
        return self.window[1] - self.window[0]

    def busy(self, dev):
        return union(clip([(s, s + d) for _, s, d in dev.ops], *self.window))

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(total(self.busy(d)) for d in self.devices) / len(self.devices)

    def idle_pct(self):
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def gaps(self, dev):
        return subtract([tuple(self.window)], self.busy(dev))

    def module_gaps(self, name_part):
        """Idle seconds between consecutive executions of the programs
        whose name holds `name_part`, on the first device."""
        if not self.devices:
            return []
        runs = sorted((s, s + d) for n, s, d in self.devices[0].modules
                      if name_part in n and s >= self.window[0]
                      and s + d <= self.window[1])
        return [max(0.0, b[0] - a[1]) for a, b in zip(runs, runs[1:])]

    def module_count(self, name_part):
        if not self.devices:
            return 0
        return sum(1 for n, s, d in self.devices[0].modules
                   if name_part in n and s >= self.window[0]
                   and s + d <= self.window[1])

    def op_seconds(self, pattern):
        """(seconds, count) of the first device's op events whose name
        matches `pattern`, inside the window."""
        rx = re.compile(pattern)
        sec, n = 0.0, 0
        for name, s, d in (self.devices[0].ops if self.devices else []):
            if s >= self.window[0] and s + d <= self.window[1] and rx.search(name):
                sec, n = sec + d, n + 1
        return sec, n

    def top_ops(self, n=10):
        if not self.devices:
            return []
        acc: Dict[str, float] = {}
        for dev in self.devices:
            inside = [(nm, s, d) for nm, s, d in dev.ops
                      if s >= self.window[0] and s + d <= self.window[1]]
            for name, sec in self_times(inside).items():
                k = op_kind(name)
                acc[k] = acc.get(k, 0.0) + sec / len(self.devices)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:n]

    def gaps_by_host_span(self, n=10):
        """The first device's idle gaps, each given to the shortest
        perfbench span that covers its middle; seconds summed by span."""
        if not self.devices:
            return []
        acc: Dict[str, float] = {}
        spans = [(nm, s, s + d) for nm, s, d in self.host_spans
                 if nm != WINDOW_SPAN]
        for s, e in self.gaps(self.devices[0]):
            mid = 0.5 * (s + e)
            cover = [(b - a, nm) for nm, a, b in spans if a <= mid <= b]
            name = min(cover)[1] if cover else "no_perfbench_span"
            acc[name] = acc.get(name, 0.0) + (e - s)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:n]

    def breakdown(self):
        return {"device_ops": [[k, v] for k, v in self.top_ops()],
                "idle_gaps": [[k, v] for k, v in self.gaps_by_host_span()]}


@dataclasses.dataclass
class View:
    """What a per-layer reader is given."""
    trace: Optional[Trace]
    run: Any
    cell: Any
    peaks: Optional[Dict[str, float]]
    chips: int

    def idle_pct(self):
        """Share of the traced window in which no operation ran on the
        device; None where the trace shows no device at work."""
        if self.trace is None or self.trace.busy_s() <= 0:
            return None
        return self.trace.idle_pct()

    def mfu_pct(self):
        """The run's closed-form operations (`facts["flops"]`, real tokens
        only) over the window's seconds, over chips x the bf16 peak."""
        f = self.run.facts
        if self.peaks is None or not f.get("flops"):
            return None
        peak = self.chips * self.peaks["bf16_tflops"] * 1e12
        return 100.0 * f["flops"] / f["window_s"] / peak


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def from_xplane(path, chips=None):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                               for e in line.events]
            devices.append(Device(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("perfbench."):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    if chips:
        devices = [d for d in devices if d.ops][:chips] or devices[:chips]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        w = max(win, key=lambda s: s[2])
        window = (w[1], w[1] + w[2])
    else:
        ev = [e for d in devices for e in d.ops]
        window = ((min(e[1] for e in ev), max(e[1] + e[2] for e in ev))
                  if ev else (0.0, 0.0))
    return Trace(devices, spans, window)


def load(trace_dir, chips=None):
    path = newest_xplane(trace_dir)
    if path is None:
        return None
    trace = from_xplane(path, chips)
    return trace if trace.devices else None
