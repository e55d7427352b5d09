"""The attention layers of a configuration whose layers are of several
kinds (`layer_types`: "full_attention", "sliding_attention"), for the
per-layer metrics that read one kind: which of the program's layer scopes
belong to it, its device time, and its kernel's share of the roofline.

The program's layer names come from the configuration's `param_map` (the
program's `_<layer>.wq` is the reference's `l<n>_wq`), so nothing here
spells a layer's name. A configuration with no `layer_types`, or a program
with no such scopes, gives nothing to read: every function returns None.
"""

from __future__ import annotations

import re
import sys

from perfbench import scope_times
from perfbench.flops import roofline_seconds


def scope_pattern(config, kind, part=""):
    """A regex for the `op_name`s under the attention layers of `kind`
    (and under their inner scope `part`); None where the configuration
    names no such layer."""
    types = config.get("layer_types")
    if not types:
        return None
    by_ref = {ref: prog for prog, ref in config.get("param_map", {}).items()}
    names = []
    for l in range(int(config["num_hidden_layers"])):
        prog = by_ref.get(f"l{l}_wq")
        if types[l] == kind and prog:
            names.append(re.escape(prog.lstrip("_").rsplit(".", 1)[0]))
    if not names:
        return None
    inner = rf"/(?:.*/)?{part}(?:/|$)" if part else r"(?:[/)]|$)"
    return rf"(?:^|[/(])multi_head_attention:(?:{'|'.join(names)})\)*" + inner


def kind_ms(view, kind):
    """Device self ms a step under the attention scopes of the layers of
    `kind`."""
    pattern = scope_pattern(view.cell.config, kind)
    return None if pattern is None else scope_times.scope_ms(view, pattern)


def kind_roofline(view, kind, call_kind):
    """The least time for the scores and values products of the pairs the
    kind's rule ALLOWS, forward + backward (the step's `kernel_calls` entry
    `call_kind`), over the device time under those layers' `core` scopes,
    in percent. A tile computed and then emptied lowers it."""
    pattern = scope_pattern(view.cell.config, kind, "core")
    calls = [c for c in view.run.facts.get("kernel_calls", []) if c["kind"] == call_kind]
    got = scope_times.seconds_under(view, pattern) if pattern else None
    if got is None or not calls or view.peaks is None:
        return None
    seconds, steps = got
    ideal, bound = 0.0, ""
    for c in calls:
        t, bound = roofline_seconds(c, view.peaks)
        ideal += t
    print(f"perfbench: {call_kind} core: {seconds / steps * 1e3:.3f} ms a step against "
          f"{ideal * 1e3:.3f} ms ({bound}-bound)", file=sys.stderr)
    return 100.0 * ideal * steps / seconds
