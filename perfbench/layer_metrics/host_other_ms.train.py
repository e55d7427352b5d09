"""Median a step of the trainer loop's own host time: `trainer/step`'s self
time plus `trainer/flops_count`, `trainer/launch` and
`trainer/housekeeping` (the step less its wait for data, its wait for the
device and the evaluators)."""

from perfbench import program_trace


def read(view):
    return program_trace.step_span_ms(
        view, totals=("trainer/flops_count", "trainer/launch",
                      "trainer/housekeeping"), selfs=("trainer/step",))
