"""The control of a re-hydration cell: one run with the killed replica's
storage directory emptied as the cell's own verb empties it, and the replica
started again WITHOUT ``--resync-on-boot`` (``faults_no_resync/``), which has
to come out as not correct.

    python perf/tests/control_no_resync.py --workload <cell> --seed <n> --seconds <s>

``control.py``'s table of controls is a file that was there, and its
``faults_emptied/`` has no verb of this cell's name, so this control adds its
row from a file of its own and runs through ``control.main``.  Takes the same
arguments as ``perf/run.py`` (``--rehearse`` for the CPU rehearsal).  Exits 0
when the run printed ``"correct": false``.
"""

import os
import sys

import control

control.CONTROLS["no-resync"] = {"faults_dir": os.path.join(control.HERE, "faults_no_resync")}

if __name__ == "__main__":
    sys.exit(control.main(["--control", "no-resync", *sys.argv[1:]]))
