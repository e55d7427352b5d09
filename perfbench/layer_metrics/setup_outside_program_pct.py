"""Share of this run's `setup_s` under none of the program's roots
(`config/parse`, `trainer/init`, the `trainer/train` calls before the
window): the harness's and the runtime's (importing jax and reaching the
chip, the traffic, the reference's weights, the read-backs of the state
between the first steps), which no change to the program can shorten."""

from perfbench import setup_phases


def read(view):
    got = setup_phases.read(view)
    setup_s = view.run.values.get("setup_s")
    if got is None or not setup_s or "trainer/init" not in got[0]:
        return None
    return 100.0 * setup_phases.outside_program_s(got[0], setup_s) / setup_s
