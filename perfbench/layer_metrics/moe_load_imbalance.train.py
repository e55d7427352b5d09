"""The fullest held expert's pairs over the mean held expert's, the worst
layer, of the traced window's last step: the program's counter
`moe.load_max_over_mean` in the window's `pass_end` record. 1 is a
perfectly even load; the grouped product's time follows the sum, the
deployment's step would follow the fullest."""

from perfbench import scope_times


def read(view):
    counters = scope_times.window_counters(view)
    if counters is None:
        return None
    return counters[0].get("moe.load_max_over_mean")
