"""Entries the restarted replica's replay applied (``storage.replay.entries``
at READY): one per key in its snapshot and one per commit in its log after it,
summed over the schedule's restarts."""

import schedule

NAME = "recovery.replay_entries"
UNIT = "entries"
LAYER = "store and storage"
MOVES = "recover_s"
SOURCE = "program_counter"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    n = [r["after"]["replica"]["storage"].get("replay", {}).get("entries") for r in back]
    return float(sum(n)) if all(v is not None for v in n) else None
