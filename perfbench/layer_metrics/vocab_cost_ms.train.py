"""Device self time a step under the dictionary-wide output layer, the cost
layer over it (layer scopes that hold a result whose last dimension is the
target dictionary's size, whatever their names) and the `cost` scope."""

from perfbench import program_trace


def read(view):
    return program_trace.device_scope_ms(view, program_trace.under_vocabulary)
