"""Median over the traced window's steps of `trainer/data_wait`: the time a
step waits for its input (the `next()` on the trainer's launch groups: the
feeder's queue, packing, the host-to-device put)."""

from perfbench import program_trace


def read(view):
    return program_trace.step_span_ms(view, totals=("trainer/data_wait",))
