"""`python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`: one cell, one run, one JSON line (perfbench/harness.py)."""

import sys

from perfbench.harness import main

if __name__ == "__main__":
    sys.exit(main())
