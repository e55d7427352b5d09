"""Seconds of set-up the process spent in the backend's compiler or
loading an executable from the persistent cache, every jit of it, plus what
the compile telemetry took after them (`compile/report`: cost and memory
analysis, the HLO text, its census and its write). The program's counters
`jax.backend_compile_s` + `jax.cache_load_s` at the end of the last pass
before the window, and the span's total before it (`setup_phases.py`)."""

from perfbench import setup_phases


def read(view):
    got = setup_phases.read(view)
    c = setup_phases.compile_counters(got[1]) if got else None
    if c is None:
        return None
    return (c["jax.backend_compile_s"] + c["jax.cache_load_s"]
            + setup_phases.seconds(got[0], "compile/report"))
