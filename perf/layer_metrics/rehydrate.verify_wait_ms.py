"""How long the re-hydration stood waiting for certificate verdicts with
nothing it could decode or apply meanwhile: ``verify_wait_ms`` of the
restarted replica's ``/status`` ``storage.resync`` at READY (wall clock: the
time in which every pull still alive was inside an await of its verifier
chain; ``mochi_tpu/server/stages.py`` ``ResyncRun``), summed over the
schedule's restarts.  A replica that keeps no such report gives nothing."""

import schedule

NAME = "rehydrate.verify_wait_ms"
UNIT = "ms"
LAYER = "store and storage"
MOVES = "ops_s"
SOURCE = "program_span"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    ms = [(r["after"]["replica"]["storage"].get("resync") or {}).get("verify_wait_ms") for r in back]
    return float(sum(ms)) if all(m is not None for m in ms) else None
