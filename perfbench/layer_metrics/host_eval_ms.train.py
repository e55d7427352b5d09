"""Median a step of `trainer/eval_outputs`: the evaluator chain on the host,
its read-back of the step's outputs included."""

from perfbench import program_trace


def read(view):
    return program_trace.step_span_ms(view, totals=("trainer/eval_outputs",))
