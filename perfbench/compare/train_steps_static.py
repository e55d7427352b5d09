"""`train_steps_lean`'s comparison for a configuration some of whose
parameters are STATIC: leaves that no gradient, no clip and no optimizer
touches (a router's selection bias), which the reference names
(`static_leaves(sizes)`).

The entry reads the program's first gradient from the optimizer's first
moments, and a static parameter has none, so `program["grad"]` lacks the
leaf where the reference, whose three steps run Adam over every leaf, has
one of exact zeros (a choice has no gradient). What the program's optimizer
never sees IS a zero gradient: the leaf is filled in as zeros, and the
numbers, their limits and the three reference steps are
`train_steps_lean.py`'s own. Whether a static leaf moved goes to standard
error (its reference change is 0, so `change_gap` leaves it out by its own
rule).
"""

from __future__ import annotations

import os
import sys

from perfbench.harness import load_module

_lean = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "train_steps_lean.py"))
routing_gap, gaps, checks, reference_steps = (
    _lean.routing_gap, _lean.gaps, _lean.checks, _lean.reference_steps)


def compare(ref, sizes, seed, batches, program, limits):
    import numpy as np

    shapes = ref.param_shapes(sizes)
    static = ref.static_leaves(sizes)
    for leaf in static:
        program["grad"].setdefault(leaf, np.zeros(shapes[leaf], np.float32))
    moved = {k: program["change_norm"].get(k) for k in static}
    print(f"perfbench: static leaves' change over the three steps (each has to "
          f"read 0): {moved}", file=sys.stderr)
    return _lean.compare(ref, sizes, seed, batches, program, limits)
