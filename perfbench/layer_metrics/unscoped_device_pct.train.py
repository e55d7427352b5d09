"""Share of the device's busy (self) time in the traced window whose event
maps to no program scope: instructions with no `op_name` (copies, parameter
moves) and the events of programs other than the train step."""

from perfbench import program_trace


def read(view):
    return program_trace.unscoped_device_pct(view)
