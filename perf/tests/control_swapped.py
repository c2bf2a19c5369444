"""A control of a cell whose configuration states members of MORE THAN ONE
strategy: the cluster boots with two members' strategies swapped (the first
two stated members whose strategies differ), so every stated member lies, and
two of them otherwise than stated.  A replica's ``/status`` names its own
strategy (PR 46), so the deployment check counts both
(``replicas_whose_strategy_differs_from_what_the_configuration_states`` 2);
before it did, the swap showed only where the kinds of mark differ.

    python perf/tests/control_swapped.py --workload <cell> --seed <n> --seconds <s> [--rehearse]

Takes ``perf/run.py``'s arguments; exits 0 when the run printed
``"correct": false``, as ``control.py`` does."""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import control  # noqa: E402
import run  # noqa: E402


def swapped(members: dict) -> dict:
    """``members`` with the strategies of its first two members that differ
    exchanged."""
    ids = sorted(members, key=lambda sid: int(sid.rsplit("-", 1)[1]))
    for i, a in enumerate(ids):
        for b in ids[i + 1:]:
            if members[a] != members[b]:
                return dict(members, **{a: members[b], b: members[a]})
    raise SystemExit(f"the stated members {members} run one strategy: nothing to swap")


def main(argv) -> int:
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--root", default=run.REPO)
    args, _ = parser.parse_known_args(argv)
    config = run.load_cell(args.root, args.workload)["config"]
    shape = dict(config, **config["rehearsal"]) if args.rehearse else config
    control.CONTROLS["swapped"] = {"boot": {"byzantine": swapped(run.stated_members(shape))}}
    return control.main(["--control", "swapped", *argv])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
