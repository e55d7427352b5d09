"""Device self time a step under a `recurrent_layer_group:` scope, forward
and backward, less what lies under the vocabulary projection nested in it
(a layer scope that holds a result whose last dimension is the target
dictionary's size). Events are mapped to scopes through the step's kept
HLO text; the table by scope goes to standard error."""

from perfbench import program_trace


def read(view):
    return program_trace.device_scope_ms(view, program_trace.under_group_scan)
