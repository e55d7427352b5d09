"""Causal attention's share of its roofline on the layers the
configuration's `layer_types` call full: the least time for the scores and
values products of the pairs j <= i, forward + backward
(`perfbench/flops/laguna.py::attention_call`), over the device time under
those layers' `core` scopes. The bound that applies goes to standard
error."""

from perfbench import attention_kinds


def read(view):
    return attention_kinds.kind_roofline(view, "full_attention", "causal_attention")
