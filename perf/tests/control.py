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
certificate notices.
``no-resync`` (a re-hydration cell): the killed replica's directory is emptied as
the cell's own verb empties it, and the replica started again WITHOUT
``--resync-on-boot``.
``plain`` (the resync cell): the killed replica is started again on its own
directory WITHOUT ``--resync-on-boot``; it loses nothing it had acknowledged, so
the read-backs pass it, and what it breaks is the fourth guarantee, which the
cell's verb holds by the replica's own ``/status``: this run has to END WITHOUT A
RESULT, exit 3 and the verb's ``NotCaughtUp`` on standard error.
``honest-member`` (a cell whose configuration states a Byzantine member): the
cluster boots every member honest, so the cell would measure nothing.
``unstated-member`` (a cell whose configuration states none): ``server-1`` boots
on ``forge-cert`` all the same.
Takes the same arguments as ``perf/run.py`` (``--rehearse`` for the CPU
rehearsal).  Exits 0 when the run printed ``"correct": false`` (``plain``: when
it ended as said above).
"""

import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

# a control's keywords for ``run.main``; ``refused_with``: the run has to end
# with exit 3 and this word on standard error, not with a result
CONTROLS = {
    "accept-all": {"launcher": os.path.join(HERE, "accept_all_launch.py")},
    "stale-reads": {"worker_script": os.path.join(HERE, "stale_read_worker.py")},
    "emptied-storage": {"faults_dir": os.path.join(HERE, "faults_emptied")},
    "forged-log": {"faults_dir": os.path.join(HERE, "faults_forged")},
    "no-resync": {"faults_dir": os.path.join(HERE, "faults_no_resync")},
    "plain": {"faults_dir": os.path.join(HERE, "faults_plain"), "refused_with": "NotCaughtUp"},
    "honest-member": {"boot": {"byzantine": None}},
    "unstated-member": {"boot": {"byzantine": {"server-1": "forge-cert"}}},
}


class Tee(io.StringIO):
    """Keeps what passes through to ``stream``."""

    def __init__(self, stream):
        super().__init__()
        self.stream = stream

    def write(self, text):
        self.stream.write(text)
        return super().write(text)


def main(argv) -> int:
    i = argv.index("--control")
    control = argv[i + 1]
    argv = argv[:i] + argv[i + 2:]
    kwargs = dict(CONTROLS[control])
    refused_with = kwargs.pop("refused_with", None)
    out, err = Tee(sys.stdout), Tee(sys.stderr)
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(argv, **kwargs)
    if refused_with is not None:
        refused = rc == 3 and refused_with in err.getvalue()
        print(f"[control {control}] exit {rc}, refused by the verb's look at the record: {refused}", file=sys.stderr)
        return 0 if refused else 1
    if rc != 0:
        return rc
    result = json.loads(out.getvalue().splitlines()[-1])
    print(f"[control {control}] correct={result['correct']}", file=sys.stderr)
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
