"""What the restarted replica's own boot spent in verified replay: its
``/status`` ``storage.replay.ms`` at READY (``storage/durable.py`` ``recover``:
snapshot and WAL read, every certificate re-verified through the service,
every entry applied), summed over the schedule's restarts."""

import schedule

NAME = "recovery.replay_ms"
UNIT = "ms"
LAYER = "store and storage"
MOVES = "recover_s"
SOURCE = "program_span"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    ms = [r["after"]["replica"]["storage"].get("replay", {}).get("ms") for r in back]
    return float(sum(ms)) if all(m is not None for m in ms) else None
