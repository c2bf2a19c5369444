"""The control of a re-hydration cell, ``control.py --control no-resync`` under
the name PR 33 gave it:

    python perf/tests/control_no_resync.py --workload <cell> --seed <n> --seconds <s>
"""

import sys

import control

if __name__ == "__main__":
    sys.exit(control.main(["--control", "no-resync", *sys.argv[1:]]))
