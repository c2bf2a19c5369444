"""How far behind its peers the replay left the restarted replica:
``entries_adopted`` of its ``/status`` ``storage.resync`` at READY (pulled
entries that were newer than what it held) over its store's ``keys_live`` at
the harness's look after READY, in percent.  100 on an emptied directory; a
few percent where the disk was kept: what committed while it was down or
replaying.  A replica that keeps no such report gives nothing."""

import schedule

NAME = "resync.delta_share"
UNIT = "%"
LAYER = "store and storage"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    reports = [r["after"]["replica"]["storage"].get("resync") for r in back]
    if not back or not all(rep and "entries_adopted" in rep for rep in reports):
        return None
    live = sum(r["after"]["replica"]["store"].get("keys_live", 0) for r in back)
    return 100.0 * sum(rep["entries_adopted"] for rep in reports) / live if live else None
