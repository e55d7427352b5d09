"""The CPU rehearsal: `JAX_PLATFORMS=cpu python -m perfbench.tests.rehearse
<toy workload> [trace]` runs a toy cell end to end through the harness (no
chip, so nothing it prints is a device number)."""

import sys
import tempfile

from perfbench.tests import helpers

if __name__ == "__main__":
    with tempfile.TemporaryDirectory() as tmp:
        line = helpers.run_toy(helpers.make_root(tmp), sys.argv[1],
                               trace=int(sys.argv[2]) if len(sys.argv) > 2 else 0)
        print(line)
