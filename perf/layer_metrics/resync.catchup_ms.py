"""What the resync pass adds to the boot: ``ms`` of the restarted replica's
``/status`` ``storage.resync`` at READY (``mochi_tpu/server/stages.py``: one
whole run of ``MochiReplica.resync`` up to its flush, after the verified
replay), summed over the schedule's restarts.  A replica that keeps no such
report (a plain restart) gives nothing."""

import schedule

NAME = "resync.catchup_ms"
UNIT = "ms"
LAYER = "store and storage"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    ms = [(r["after"]["replica"]["storage"].get("resync") or {}).get("ms") for r in back]
    return float(sum(ms)) if all(m is not None for m in ms) else None
