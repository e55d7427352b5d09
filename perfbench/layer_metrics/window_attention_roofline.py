"""Sliding-window attention's share of its roofline: the least time the
chip could take for the scores and values products of the pairs the window
rule ALLOWS, forward + backward, the sliding layers (`perfbench/flops/
laguna.py::attention_call`; the step's `kernel_calls`), over the device
time under those layers' `core` scopes. A tile the kernel computes and the
rule then empties costs time and counts nothing. The bound that applies
goes to standard error."""

from perfbench import attention_kinds


def read(view):
    return attention_kinds.kind_roofline(view, "sliding_attention", "window_attention")
