"""The GRU kernel calls' share of their roofline: the least time the chip
could take for the recurrent part of every GRU layer call of the traced
steps (the larger of operations over peak FLOP/s and bytes over peak
bytes/s, `perfbench/flops/__init__.py::recurrence_call`, for the call's
padded shapes, whatever implements the recurrence) over the device time of
the kernel's events in the trace. The bound that applies goes to standard
error."""

import sys

from perfbench.flops import roofline_seconds

# a Mosaic kernel's event is named by its HLO text:
# `%transpose_jvp___.2 = (...) custom-call(...), custom_call_target="tpu_custom_call"`;
# the train step of this cell holds no other kernel than the GRU's
KERNEL_EVENTS = r"^%[^ ]+ = .*? custom-call\((?!.*custom_call_target=\"(?!tpu_custom_call))"


def read(view, kind="gru", pattern=KERNEL_EVENTS):
    if view.trace is None or view.peaks is None:
        return None
    calls = [c for c in view.run.facts.get("kernel_calls", [])
             if c["kind"] == kind]
    steps = view.trace.module_count(view.run.facts["step_program"])
    seconds, n = view.trace.op_seconds(pattern)
    if not calls or not steps or not n or seconds <= 0:
        return None
    ideal = 0.0
    for c in calls:
        t, bound = roofline_seconds(c, view.peaks)
        ideal += t
    print(f"perfbench: {kind} kernel: {n} events in {steps} steps, "
          f"{seconds / steps * 1e3:.3f} ms a step against {ideal * 1e3:.3f} ms "
          f"({bound}-bound)", file=sys.stderr)
    return 100.0 * ideal * steps / seconds
