"""Block-diffusion attention's share of its roofline: the least time the
chip could take for the scores and values products of the pairs the mask
ALLOWS, forward + backward, all layers (`perfbench/flops/sdar_moe.py::
attention_call`; the step's `kernel_calls`), over the device time under the
attention layers' `core` scopes. A tile the kernel computes and the mask
then empties, and the forward recomputed for the backward, cost time and
count nothing, so tile waste and recomputation show as a lower share. The
bound that applies goes to standard error."""

import sys

from perfbench import scope_times
from perfbench.flops import roofline_seconds


def read(view):
    got = scope_times.seconds_under(view, scope_times.ATTENTION + r"/(?:.*/)?core(?:/|$)")
    calls = [c for c in view.run.facts.get("kernel_calls", [])
             if c["kind"] == "bd_attention"]
    if got is None or not calls or view.peaks is None:
        return None
    seconds, steps = got
    ideal, bound = 0.0, ""
    for c in calls:
        t, bound = roofline_seconds(c, view.peaks)
        ideal += t
    print(f"perfbench: attention core: {seconds / steps * 1e3:.3f} ms a step against "
          f"{ideal * 1e3:.3f} ms ({bound}-bound)", file=sys.stderr)
    return 100.0 * ideal * steps / seconds
