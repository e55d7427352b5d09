"""Median idle gap on the device between two consecutive train-step
programs (the trainer loop's cost a step: loss readback, bookkeeping, the
next launch), from the `XLA Modules` line of the trace."""

import statistics


def read(view):
    if view.trace is None:
        return None
    gaps = view.trace.module_gaps(view.run.facts["step_program"])
    if len(gaps) < 2:
        return None
    return 1e3 * statistics.median(gaps)
