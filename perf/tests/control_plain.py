"""The control of the resync cell, ``control.py --control plain`` under the name
PR 37 gave it (exits 0 when the run ended with exit 3 and ``NotCaughtUp``):

    python perf/tests/control_plain.py --workload <cell> --seed <n> --seconds <s>
"""

import sys

import control

if __name__ == "__main__":
    sys.exit(control.main(["--control", "plain", *sys.argv[1:]]))
