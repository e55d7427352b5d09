"""Seconds of set-up the process spent tracing to a jaxpr and lowering to
an MLIR module, every jit of it: the step's launch groups, the flops
count's own trace, the parameter initialisers, the harness's jitted
helpers, a fallback's second compile. The program's counters `jax.trace_s`
+ `jax.lower_s` (jax's own events, self time, so none twice) at the end of
the last pass before the window (`setup_phases.py`)."""

from perfbench import setup_phases


def read(view):
    got = setup_phases.read(view)
    c = setup_phases.compile_counters(got[1]) if got else None
    if c is None:
        return None
    return c["jax.trace_s"] + c["jax.lower_s"]
