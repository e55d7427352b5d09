"""Share of the first device's idle seconds in the traced window that lie
under no program span deeper than `trainer/step` (idle gaps under 2 ms left
out: the device's clock runs about 1 ms off the host's). The table of idle
seconds by span goes to standard error."""

from perfbench import program_trace


def read(view):
    return program_trace.idle_unexplained_pct(view)
