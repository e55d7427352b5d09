"""Seconds of set-up under `trainer/init`, the whole of `Trainer.__init__`:
reaching the backend, the native library, config -> graph, every parameter
initialised on the device, the optimizer's state. The span's total before
the window, from the program's `pass_end` records (`setup_phases.py`)."""

from perfbench import setup_phases


def read(view):
    got = setup_phases.read(view)
    if got is None or "trainer/init" not in got[0]:
        return None
    return setup_phases.seconds(got[0], "trainer/init")
