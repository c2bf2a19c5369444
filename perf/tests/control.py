"""The control: one run of a cell with a guarantee broken underneath, which
has to come out as not correct.

    python perf/tests/control.py --control accept-all --workload <cell> --seed <n> --seconds <s>

``accept-all``: the verifier service answers "valid" for every signature (the
step that would tempt a later PR is to skip or weaken the certificate check).
``stale-reads``: the generator's reads come from a stale cache.
``emptied-storage`` (a cell with a fault schedule): the restarted replica comes
back with an emptied storage directory, so it has lost what it acknowledged.
``forged-log`` (likewise): a grant's signature is altered in the last commits of
the killed replica's log, CRCs made right, so only a replay that verifies every
certificate notices.  Takes the
same arguments as ``perf/run.py`` (``--rehearse`` for the CPU rehearsal).
Exits 0 when the run printed ``"correct": false``.
"""

import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

CONTROLS = {
    "accept-all": {"launcher": os.path.join(HERE, "accept_all_launch.py")},
    "stale-reads": {"worker_script": os.path.join(HERE, "stale_read_worker.py")},
    "emptied-storage": {"faults_dir": os.path.join(HERE, "faults_emptied")},
    "forged-log": {"faults_dir": os.path.join(HERE, "faults_forged")},
}


def main(argv) -> int:
    i = argv.index("--control")
    control = argv[i + 1]
    argv = argv[:i] + argv[i + 2:]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(argv, **CONTROLS[control])
    sys.stdout.write(out.getvalue())
    if rc != 0:
        return rc
    result = json.loads(out.getvalue().splitlines()[-1])
    print(f"[control {control}] correct={result['correct']}", file=sys.stderr)
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
