"""``recover_s`` less the replay: the restart command to READY without what
the replica itself timed as replay, so process start, imports, the storage
scan before ``recover`` and the socket."""

import schedule

NAME = "recovery.boot_s"
UNIT = "s"
LAYER = "replica dispatch and auth"
MOVES = "recover_s"
SOURCE = "host_clock"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    ms = [r["after"]["replica"]["storage"].get("replay", {}).get("ms") for r in back]
    if any(m is None for m in ms):
        return None
    return sum(r["seconds"] for r in back) - sum(ms) / 1e3
