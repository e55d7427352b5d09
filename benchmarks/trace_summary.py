"""Summarize a jax.profiler xplane trace: top ops by device self-time.

    python benchmarks/trace_summary.py /path/to/trace_dir [N]

Walks the newest `*.xplane.pb` under the trace dir (written by
`jax.profiler.trace` / `--profile_dir`), accumulates event durations per
op on the device planes (TPU or CPU), and prints the top-N table plus
totals — the quick look that tells you whether the step is matmul-bound
(good: MXU busy) or drowning in transposes/copies, without opening
tensorboard. Pure protobuf walking via tensorboard_plugin_profile's
schema; no TF session anything.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import sys

# HLO SSA suffixes on per-op event names ("dot.4", "tanh.5.clone",
# "fusion.26.remat") — newer profilers emit the bare HLO instruction
# name on the thread-pool lines, so summing requires folding the
# numbered instances back onto their opcode
_SSA_SUFFIX_RE = re.compile(r"(\.\d+)+(\.clone\d*|\.remat\d*)*$")


def _canonical_op(name: str) -> str:
    """Fold one HLO instruction name to its opcode ("dot.4" -> "dot")."""
    return _SSA_SUFFIX_RE.sub("", name.split(" = ", 1)[0])


def _find_xplanes(trace_dir: str):
    pats = [
        os.path.join(trace_dir, "**", "*.xplane.pb"),
    ]
    files: list = []
    for p in pats:
        files.extend(glob.glob(p, recursive=True))
    return sorted(files, key=os.path.getmtime)


def _xplane_pb2():
    candidates = (
        "tensorflow.tsl.profiler.protobuf.xplane_pb2",  # this image's TF
        "tsl.profiler.protobuf.xplane_pb2",             # standalone tsl
        "xprof.protobuf.xplane_pb2",                    # newer xprof wheels
    )
    import importlib

    errs = []
    for mod in candidates:
        try:
            return importlib.import_module(mod)
        except ImportError as e:
            errs.append(f"{mod}: {e}")
    raise ImportError("no xplane_pb2 found; tried:\n  " + "\n  ".join(errs))


def summarize(xplane_path: str):
    xplane_pb2 = _xplane_pb2()

    space = xplane_pb2.XSpace()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())

    tables = {}
    for plane in space.planes:
        name = plane.name
        # device planes: "/device:TPU:0" (accelerators) or "/host:CPU"
        # (the XLA-CPU op line under a forced-CPU run); skip the python
        # host-thread and metadata planes
        if not (name.startswith("/device:") or "TPU" in name or name == "/host:CPU"):
            continue
        ev_names = {i: m.name for i, m in plane.event_metadata.items()}
        # accelerator planes carry whole-step span lines ("Steps",
        # "XLA Modules") next to the "XLA Ops" per-op line — summing those
        # double-counts and puts the module name on top. Prefer the "XLA
        # Ops" line when present (TPU/GPU). The /host:CPU plane (forced-CPU
        # runs) interleaves op events with python frames and PjRt wrapper
        # spans that ENCLOSE them on the same line, so there the filtering
        # must happen per EVENT: drop source refs ($file.py:..), C++
        # wrapper methods (Foo::Bar), python dispatch frames.
        op_lines = [l for l in plane.lines if l.name == "XLA Ops"]
        event_filter = None
        normalize = None
        if op_lines:
            lines = op_lines
        else:
            # host-CPU plane: jax scatters op events over the runtime's
            # thread-pool lines ("tf_XLAEigen/...",
            # "tf_XLATfrtCpuClient/...") interleaved with python frames
            # and C++ wrapper spans, and names events by HLO instruction
            # ("dot.4") instead of framework op — so filtering happens
            # per EVENT and instances fold onto their opcode.
            lines = [
                l
                for l in plane.lines
                if l.name not in ("Steps", "XLA Modules", "Framework Ops",
                                  "Source Code", "python")
            ]

            def event_filter(n):
                return not (
                    n.startswith("$")
                    or "::" in n
                    or n.startswith(("PjitFunction", "profiler", "Pjit", "jit("))
                )

            normalize = _canonical_op

        durs: collections.Counter = collections.Counter()
        count: collections.Counter = collections.Counter()
        for line in lines:
            for ev in line.events:
                n = ev_names.get(ev.metadata_id, "?")
                if event_filter is not None and not event_filter(n):
                    continue
                if normalize is not None:
                    n = normalize(n)
                durs[n] += ev.duration_ps
                count[n] += 1
        if durs:
            tables[name] = (durs, count)
    return tables


_CATEGORIES = (
    # (label, substrings matched against the lowered op name); first match
    # wins, so scan whiles (whole loop bodies, matmul + elementwise mixed)
    # are split out before the generic buckets
    ("scan/while bodies", ("%while",)),
    ("matmul/conv (MXU)", ("convolution", "dot")),
    ("dynamic-slice/update", ("dynamic-slice", "dynamic-update")),
    ("copy/transpose/reshape", ("copy", "transpose", "reshape", "bitcast")),
    ("reduce", ("reduce",)),
    ("fusion (elementwise etc.)", ("fusion",)),
)


def _category(name: str) -> str:
    # match the DEFINING name only ("%fusion.26" of
    # "%fusion.26 = bf16[...] fusion(f32[...] %reshape.4582, ...)") —
    # the operand list repeats other ops' names and would misclassify
    low = name.split(" = ", 1)[0].lower()
    for label, keys in _CATEGORIES:
        if any(k in low for k in keys):
            return label
    return "other"


def print_summary(trace_dir: str, top: int = 20) -> int:
    files = _find_xplanes(trace_dir)
    if not files:
        print(f"no *.xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    path = files[-1]
    print(f"# {path}")
    for plane, (durs, count) in summarize(path).items():
        total_ps = sum(durs.values())
        print(f"\n== {plane}  (total {total_ps / 1e9:.3f} ms summed-event time)")
        # category roll-up first: the one-glance MXU-vs-overhead split
        cats: collections.Counter = collections.Counter()
        for name, ps in durs.items():
            cats[_category(name)] += ps
        for label, ps in cats.most_common():
            print(f"  {label:<28} {ps / 1e9:9.3f} ms {100.0 * ps / max(total_ps, 1):6.1f}%")
        print(f"\n{'op':<58} {'ms':>9} {'%':>6} {'n':>7}")
        for name, ps in durs.most_common(top):
            pct = 100.0 * ps / max(total_ps, 1)
            print(f"{name[:58]:<58} {ps / 1e9:9.3f} {pct:6.1f} {count[name]:7d}")
    return 0


if __name__ == "__main__":
    d = sys.argv[1] if len(sys.argv) > 1 else "."
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    sys.exit(print_summary(d, n))
