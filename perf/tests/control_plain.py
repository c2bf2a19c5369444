"""The control of the resync cell: one run with the killed replica started
again on its own storage directory WITHOUT ``--resync-on-boot``
(``faults_plain/``), which has to end without a result.

    python perf/tests/control_plain.py --workload <cell> --seed <n> --seconds <s>

A plain restart loses nothing it had acknowledged, so the read-backs that
decide ``correct`` pass it (what it lacks committed while it was down); what it
breaks is the configuration's fourth guarantee, READY only after one complete
resync pass, and the cell's verb holds READY to that by the replica's own
``/status``.  So the run has to end with exit 3 and the verb's ``NotCaughtUp``
on standard error; this script exits 0 when it did.  ``control.py``'s table is
a file that was there, so this control brings its row from a file of its own,
as ``control_no_resync.py`` does.  Takes the same arguments as ``perf/run.py``
(``--rehearse`` for the CPU rehearsal).
"""

import contextlib
import io
import os
import sys

import control

FAULTS = os.path.join(control.HERE, "faults_plain")


class Tee(io.StringIO):
    def write(self, text):
        sys.__stderr__.write(text)
        return super().write(text)


if __name__ == "__main__":
    said = Tee()
    with contextlib.redirect_stderr(said):
        rc = control.run.main(sys.argv[1:], faults_dir=FAULTS)
    refused = rc == 3 and "NotCaughtUp" in said.getvalue()
    print(f"[control plain] exit {rc}, refused by the verb's look at the record: {refused}", file=sys.stderr)
    sys.exit(0 if refused else 1)
