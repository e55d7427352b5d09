"""The grouped matrix products' share of their roofline: the least time
the chip could take for the experts' three products, forward + backward,
of the (token, expert) pairs the program COUNTED in the traced window (its
counter `moe.pairs_held`, held to the plain reference's count by
tests/test_block_diffusion_moe.py; `perfbench/flops/sdar_moe.py::
grouped_mm_call`), over the device time under the `experts` scopes: matched
by scope, so that it reads the same work whatever implements it.
Recomputation costs time and counts nothing, so a step that recomputes the
forward twice cannot pass 60 %. The bound that applies goes to standard
error."""

import sys

from perfbench import scope_times
from perfbench.flops import roofline_seconds


def read(view):
    got = scope_times.seconds_under(view, scope_times.EXPERTS, scope_times.EXPERT_PRODUCTS)
    counters = scope_times.window_counters(view)
    if got is None or counters is None or view.peaks is None:
        return None
    now, before = counters
    pairs = now.get("moe.pairs_held", 0.0) - before.get("moe.pairs_held", 0.0)
    seconds, steps = got
    if pairs <= 0 or seconds <= 0:
        return None
    fl = view.cell.module("flops", view.cell.config["reference"])
    # the counter covers the whole pass; the events, the steps inside the window
    pass_steps = view.run.facts.get("steps") or steps
    call = fl.grouped_mm_call(view.cell.config, pairs * steps / pass_steps)
    ideal, bound = roofline_seconds(call, view.peaks)
    print(f"perfbench: grouped products: {pairs / pass_steps:.0f} pairs a step, "
          f"{seconds / steps * 1e3:.3f} ms a step against {ideal / steps * 1e3:.3f} ms "
          f"({bound}-bound)", file=sys.stderr)
    return 100.0 * ideal / seconds
